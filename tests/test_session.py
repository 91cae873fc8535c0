"""Session/artifact API: capture-once semantics, store round-trips, N-way
ranking, pluggable backends, report JSON round-trips, and the CLI.

The acceptance-critical properties:
  * artifact save -> load -> compare reproduces the direct (legacy one-shot)
    comparison bit-identically on zoo cases,
  * a store cache hit skips every instrumented execution (spy-verified),
  * rank() over N candidates runs exactly N captures and agrees with the
    pairwise compares.
"""

import json
import os
import subprocess
import sys

import jax.numpy as jnp
import numpy as np
import pytest

import repro.core.interp as interp
from repro.core.artifact import (ArtifactStore, ArtifactValueError,
                                 CandidateArtifact)
from repro.core.diff import DifferentialEnergyDebugger
from repro.core.energy import (AnalyticalBackend, HloCostBackend,
                               ReplayBackend, backend_from_name)
from repro.core.report import Finding, Report
from repro.core.session import RankResult, Session, _perturb
from repro.zoo import cases

ROUNDTRIP_CASES = ["c6-matpow", "c15-expm", "c12-ln-layout"]


def _count_runs(monkeypatch):
    """Spy on every instrumented execution (stats + value captures)."""
    calls = {"n": 0}
    orig = interp.run_instrumented

    def spy(*a, **k):
        calls["n"] += 1
        return orig(*a, **k)

    monkeypatch.setattr(interp, "run_instrumented", spy)
    return calls


# ---------------------------------------------------------------------------
# store round-trip == direct compare
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("cid", ROUNDTRIP_CASES)
def test_artifact_roundtrip_matches_direct_compare(cid, tmp_path):
    case = cases.get_case(cid)
    direct = DifferentialEnergyDebugger().compare(
        case.inefficient, case.efficient, case.make_args(),
        name_a="ineff", name_b="eff",
        config_a=case.config_a, config_b=case.config_b,
        output_rtol=case.output_rtol)

    session = Session(store=str(tmp_path))
    art_a = session.capture(case.inefficient, case.make_args(),
                            name="ineff", config=case.config_a)
    art_b = session.capture(case.efficient, case.make_args(),
                            name="eff", config=case.config_b)
    live = session.compare(art_a, art_b, output_rtol=case.output_rtol)
    assert live.to_json() == direct.to_json()

    # fresh session, artifacts loaded from disk, NO live program attached:
    # matching replays from persisted invariants + memoized phase-2 values
    session2 = Session(store=str(tmp_path))
    la, lb = session2.load(art_a.key), session2.load(art_b.key)
    assert not la.is_live and not lb.is_live
    offline = session2.compare(la, lb, output_rtol=case.output_rtol)
    assert offline.to_json() == direct.to_json()


def test_loaded_artifact_without_values_raises(tmp_path):
    case = cases.get_case("c6-matpow")
    session = Session(store=str(tmp_path))
    art = session.capture(case.inefficient, case.make_args(), name="x")
    loaded = session.load(art.key)          # saved before any compare
    assert not loaded.is_live
    with pytest.raises(ArtifactValueError, match="re-capture"):
        loaded.fetcher()(0, sorted(loaded.graph.tensors)[:3])


# ---------------------------------------------------------------------------
# cache-hit capture skips re-execution
# ---------------------------------------------------------------------------

def test_cache_hit_skips_reexecution(tmp_path, monkeypatch):
    case = cases.get_case("c6-matpow")
    session = Session(store=str(tmp_path))

    calls = _count_runs(monkeypatch)
    art = session.capture(case.inefficient, case.make_args(), name="x")
    assert calls["n"] == session.num_input_samples      # one run per sample
    assert not art.meta.get("cache_hit")

    calls["n"] = 0
    art2 = session.capture(case.inefficient, case.make_args(), name="x")
    assert art2.meta.get("cache_hit")
    assert calls["n"] == 0                  # no instrumented execution at all
    assert art2.key == art.key
    assert art2.is_live                     # re-attached for lazy fetches

    # different sample seeds -> different content address -> full capture
    calls["n"] = 0
    art3 = session.capture(case.inefficient, case.make_args(), name="x",
                           sample_seeds=(99,))
    assert art3.key != art.key
    assert art3.sample_seeds == (99,)
    assert calls["n"] == session.num_input_samples


def test_cache_never_aliases_across_input_values(tmp_path, monkeypatch):
    """Same program + shapes but different input VALUES must re-capture:
    outputs and per-sample invariants are value-dependent."""
    import jax.numpy as jnp

    def f(x):
        return x @ x

    x1 = jnp.asarray(np.random.default_rng(0).standard_normal((16, 16)),
                     jnp.float32)
    x2 = jnp.asarray(np.random.default_rng(1).standard_normal((16, 16)),
                     jnp.float32)
    session = Session(store=str(tmp_path))
    a1 = session.capture(f, (x1,), name="f")
    calls = _count_runs(monkeypatch)
    a2 = session.capture(f, (x2,), name="f")
    assert a2.key != a1.key
    assert not a2.meta.get("cache_hit")
    assert calls["n"] == session.num_input_samples


def test_cache_never_aliases_across_closure_constants(tmp_path, monkeypatch):
    """Functions differing only in closed-over constant values (e.g. model
    weights captured via a lambda) must not collide in the store —
    str(jaxpr) prints constvars by name only."""
    import jax.numpy as jnp

    w1 = jnp.asarray(np.random.default_rng(0).standard_normal((16, 16)),
                     jnp.float32)
    w2 = jnp.asarray(np.random.default_rng(1).standard_normal((16, 16)),
                     jnp.float32)
    x = jnp.ones((4, 16), jnp.float32)
    session = Session(store=str(tmp_path))
    a1 = session.capture(lambda x: x @ w1, (x,), name="m1")
    calls = _count_runs(monkeypatch)
    a2 = session.capture(lambda x: x @ w2, (x,), name="m2")
    assert a2.key != a1.key
    assert not a2.meta.get("cache_hit")
    assert calls["n"] == session.num_input_samples


def test_capture_gate_against_fails_fast(monkeypatch):
    """gate_against raises on the sample-0 mismatch BEFORE further samples
    are captured or the graph is priced (the legacy fail-fast ordering)."""
    import jax.numpy as jnp

    x = (jnp.ones((4, 4), jnp.float32),)
    session = Session()
    art_a = session.capture(lambda x: x * 2.0, x, name="a")
    calls = _count_runs(monkeypatch)
    with pytest.raises(ValueError, match="not the same task"):
        session.capture(lambda x: x * 3.0, x, name="b", gate_against=art_a)
    assert calls["n"] == 1          # sample 0 only; samples 1.. never ran


def test_backend_id_partitions_cache(tmp_path):
    case = cases.get_case("c6-matpow")
    s_analytic = Session(store=str(tmp_path))
    s_replay = Session(backend=ReplayBackend(max_replay_iters=2),
                       store=str(tmp_path))
    a1 = s_analytic.capture(case.inefficient, case.make_args(), name="x")
    a2 = s_replay.capture(case.inefficient, case.make_args(), name="x")
    assert a1.key != a2.key
    with pytest.raises(ValueError, match="different energy backends"):
        s_analytic.compare(a1, a2)


# ---------------------------------------------------------------------------
# rank: N captures, agreement with pairwise compares
# ---------------------------------------------------------------------------

def _matpow_candidates():
    """Four candidate implementations of the zoo c6 task (a^8)."""
    case = cases.get_case("c6-matpow")

    def pow8_naive(a):
        out = a
        for _ in range(7):
            out = out @ a
        return out

    def pow8_binary(a):
        a2 = a @ a
        a4 = a2 @ a2
        return a4 @ a4

    def pow8_mixed(a):
        a2 = a @ a
        return ((a2 @ a2) @ a2) @ a2

    def pow8_semi(a):
        a2 = a @ a
        a4 = a2 @ a2
        return (a4 @ a2) @ a2

    return case.make_args(), [pow8_naive, pow8_binary, pow8_mixed, pow8_semi]


def test_rank_runs_exactly_n_captures(monkeypatch):
    args, fns = _matpow_candidates()
    session = Session()
    calls = _count_runs(monkeypatch)
    arts = [session.capture(fn, args, name=fn.__name__) for fn in fns]
    capture_runs = calls["n"]
    assert capture_runs == len(fns) * session.num_input_samples

    calls["n"] = 0
    result = session.rank(arts, output_rtol=5e-2)
    # ranking performs no additional *capture* executions: any instrumented
    # run during rank is a selective phase-2 value fetch, which retains only
    # the requested tensors — assert nothing re-captured signatures
    assert len(result.reports) == len(fns) * (len(fns) - 1) // 2
    stats_calls = {"n": 0}
    orig_stats = interp.capture_tensor_stats

    def stats_spy(*a, **k):
        stats_calls["n"] += 1
        return orig_stats(*a, **k)

    monkeypatch.setattr(interp, "capture_tensor_stats", stats_spy)
    session.rank(arts, output_rtol=5e-2)
    assert stats_calls["n"] == 0

    # the cheapest implementation wins
    assert result.best == "pow8_binary"
    assert result.total_energy_j[0] == max(result.total_energy_j)


def test_rank_agrees_with_pairwise_compares():
    args, fns = _matpow_candidates()
    fns = fns[:3]
    session = Session()
    arts = [session.capture(fn, args, name=fn.__name__) for fn in fns]
    result = session.rank(arts, output_rtol=5e-2)
    for (i, j), rep in result.reports.items():
        direct = session.compare(arts[i], arts[j], output_rtol=5e-2)
        assert rep.to_json() == direct.to_json()
    # waste matrix entries reproduce the pairwise waste findings
    for (i, j), rep in result.reports.items():
        w_ij = sum(f.energy_a_j - f.energy_b_j for f in rep.waste_findings
                   if f.wasteful_side == "A")
        w_ji = sum(f.energy_b_j - f.energy_a_j for f in rep.waste_findings
                   if f.wasteful_side == "B")
        assert result.waste_matrix[i][j] == pytest.approx(w_ij)
        assert result.waste_matrix[j][i] == pytest.approx(w_ji)


def test_rank_saves_each_artifact_at_most_once(tmp_path):
    """Store-backed rank persists dirty artifacts ONCE at rank exit, not
    after every pairwise compare (O(N²) full .npz rewrites before the fix).
    N captures -> exactly N saves; rank of N candidates -> <= N more."""
    saves: list[str] = []

    class SpyStore(ArtifactStore):
        def save(self, artifact):
            saves.append(artifact.key)
            return super().save(artifact)

    args, fns = _matpow_candidates()
    fns = fns[:3]                  # pow8_mixed/semi share a jaxpr (cache hit)
    session = Session(store=None)
    session.store = SpyStore(tmp_path)
    arts = [session.capture(fn, args, name=fn.__name__) for fn in fns]
    assert len(saves) == len(fns)             # one save per capture

    saves.clear()
    session.rank(arts, output_rtol=5e-2)
    assert len(saves) <= len(fns), \
        f"rank re-saved artifacts per compare: {saves}"
    assert len(set(saves)) == len(saves)      # no artifact written twice
    # the deferred saves persisted the phase-2 memo: offline replay works
    session2 = Session(store=None)
    session2.store = ArtifactStore(tmp_path)
    loaded = [session2.store.load(a.key) for a in arts]
    session2.rank(loaded, output_rtol=5e-2)   # would raise on missing values


def test_rank_result_json_roundtrip():
    args, fns = _matpow_candidates()
    session = Session()
    arts = [session.capture(fn, args, name=fn.__name__) for fn in fns[:3]]
    result = session.rank(arts, output_rtol=5e-2)
    again = RankResult.from_json(result.to_json())
    assert again.to_json() == result.to_json()
    assert "waste matrix" in result.render()
    summary = result.summary_report()
    assert "rank_matrix" in summary.meta
    assert "waste matrix" in summary.render()


# ---------------------------------------------------------------------------
# report JSON round-trip
# ---------------------------------------------------------------------------

def test_report_from_json_roundtrip():
    case = cases.get_case("c6-matpow")
    rep = DifferentialEnergyDebugger().compare(
        case.inefficient, case.efficient, case.make_args(),
        config_a=case.config_a, config_b=case.config_b,
        output_rtol=case.output_rtol)
    again = Report.from_json(rep.to_json())
    assert again.to_json() == rep.to_json()
    assert again.render() == rep.render()
    f = rep.findings[0]
    assert Finding.from_json(json.dumps(
        json.loads(rep.to_json())["findings"][0])) == f


# ---------------------------------------------------------------------------
# artifact schema: v2 per-op HLO costs + v1 backward compatibility
# ---------------------------------------------------------------------------

def test_artifact_persists_per_op_hlo_costs(tmp_path):
    """An HLO-backend capture round-trips its per-op attribution through the
    store: the loaded profile carries the same per-node cost columns."""
    case = cases.get_case("c6-matpow")
    session = Session(backend=HloCostBackend(), store=str(tmp_path))
    art = session.capture(case.inefficient, case.make_args(), name="x")
    assert art.profile.hlo is not None
    assert art.profile.hlo.num_nodes == len(art.graph.nodes)
    loaded = session.load(art.key)
    assert loaded.profile.hlo is not None
    np.testing.assert_array_equal(loaded.profile.hlo.flops,
                                  art.profile.hlo.flops)
    np.testing.assert_array_equal(loaded.profile.hlo.hbm_bytes,
                                  art.profile.hlo.hbm_bytes)
    # JSON round-trip preserves floats exactly
    assert loaded.profile.hlo.module == art.profile.hlo.module


def test_v1_artifact_loads_with_hlo_costs_marked_absent(tmp_path):
    """Old (format v1) monolithic .npz artifacts still load; their per-op
    HLO costs are marked absent (profile.hlo is None) rather than erroring."""
    import json as _json

    from repro.core import artifact as artifact_mod

    case = cases.get_case("c6-matpow")
    session = Session(store=str(tmp_path))
    art = session.capture(case.inefficient, case.make_args(), name="x")
    path = tmp_path / "legacy.npz"
    art.save(path)                  # the monolithic (legacy v2) container

    # rewrite the saved npz's meta block as a v1 payload (no 'hlo' field)
    with np.load(path, allow_pickle=False) as z:
        arrays = {k: z[k] for k in z.files}
    meta = _json.loads(arrays["meta"].tobytes().decode())
    meta["format_version"] = 1
    meta["profile"].pop("hlo", None)
    arrays["meta"] = np.frombuffer(_json.dumps(meta).encode(), dtype=np.uint8)
    np.savez(path, **arrays)

    loaded = CandidateArtifact.load(path)
    assert loaded.profile.hlo is None
    assert loaded.profile.total_energy_j == pytest.approx(
        art.profile.total_energy_j)

    # an unknown future version still refuses loudly
    meta["format_version"] = 99
    arrays["meta"] = np.frombuffer(_json.dumps(meta).encode(), dtype=np.uint8)
    np.savez(path, **arrays)
    with pytest.raises(ValueError, match="format v99"):
        CandidateArtifact.load(path)
    assert artifact_mod.ARTIFACT_FORMAT_VERSION == 3


def test_cache_hit_from_remote_store_skips_reexecution(tmp_path, monkeypatch):
    """A capture recorded on one machine and mirrored is a cache hit on
    another: the read-through local store pulls the manifest from the
    remote, skips every instrumented execution, and re-attaches for lazy
    phase-2 fetches."""
    case = cases.get_case("c6-matpow")
    recorder = Session(store=str(tmp_path / "recorder"))
    art = recorder.capture(case.inefficient, case.make_args(), name="x")
    mirror = tmp_path / "mirror"
    recorder.store.push(f"file://{mirror}")

    fleet = Session(store=ArtifactStore(tmp_path / "fleet",
                                        remote=f"file://{mirror}"))
    calls = _count_runs(monkeypatch)
    hit = fleet.capture(case.inefficient, case.make_args(), name="x")
    assert hit.meta.get("cache_hit")
    assert calls["n"] == 0              # no instrumented execution at all
    assert hit.key == art.key
    assert hit.is_live                  # re-attached for lazy fetches
    assert fleet.store.counters["upstream_manifest_reads"] == 1
    # second hit is served from the local read-through cache
    fleet2 = Session(store=ArtifactStore(tmp_path / "fleet",
                                         remote=f"file://{mirror}"))
    hit2 = fleet2.capture(case.inefficient, case.make_args(), name="x")
    assert hit2.meta.get("cache_hit") and calls["n"] == 0
    assert fleet2.store.counters["upstream_manifest_reads"] == 0


# ---------------------------------------------------------------------------
# CLI zoo re-attach: rejected provenance must not orphan store entries
# ---------------------------------------------------------------------------

def test_maybe_attach_zoo_rejection_leaves_store_clean(tmp_path):
    """A loaded artifact whose zoo provenance fails the key check must NOT
    persist its probe re-capture: before the fix the rejected capture
    stayed behind as an orphan store entry."""
    from repro.cli import _maybe_attach_zoo

    case = cases.get_case("c6-matpow")
    session = Session(store=str(tmp_path))
    art = session.capture(case.inefficient, case.make_args(), name="x",
                          extra_meta={"zoo_case": case.id,
                                      "zoo_side": "ineff"})
    session.store.save(art)
    keys_before = set(session.store.keys())

    stale = session.store.load(art.key)
    assert not stale.is_live
    # tamper the provenance: claims to be the OTHER twin, so the re-capture
    # key cannot match the recorded one
    stale.meta["zoo_side"] = "eff"
    out = _maybe_attach_zoo(stale, session)
    assert out is stale                      # rejected: artifact unchanged
    assert not out.is_live
    assert set(session.store.keys()) == keys_before, \
        "rejected zoo re-attach orphaned an entry in the store"

    # intact provenance still re-attaches (and stays clean: cache hit)
    good = session.store.load(art.key)
    attached = _maybe_attach_zoo(good, session)
    assert attached.is_live
    assert attached.key == art.key
    assert set(session.store.keys()) == keys_before


# ---------------------------------------------------------------------------
# backends
# ---------------------------------------------------------------------------

def test_hlo_cost_backend_profiles_and_detects():
    case = cases.get_case("c6-matpow")
    session = Session(backend=HloCostBackend())
    a = session.capture(case.inefficient, case.make_args(), name="ineff")
    b = session.capture(case.efficient, case.make_args(), name="eff")
    rep = session.compare(a, b, output_rtol=case.output_rtol)
    assert rep.meta["energy_model"].startswith("hlo+")
    assert rep.waste_findings
    assert a.profile.total_energy_j > b.profile.total_energy_j


def test_backend_from_name():
    assert isinstance(backend_from_name("analytic"), AnalyticalBackend)
    assert isinstance(backend_from_name("replay"), ReplayBackend)
    assert isinstance(backend_from_name("hlo"), HloCostBackend)
    with pytest.raises(ValueError):
        backend_from_name("nope")


# ---------------------------------------------------------------------------
# zoo registry
# ---------------------------------------------------------------------------

def test_zoo_registry_lookup_and_filters():
    assert cases.get_case("c6-matpow").id == "c6-matpow"
    assert cases.get_case("hf-34570").id == "c6-matpow"     # paper id
    assert cases.by_id("c6-matpow") is cases.get_case("c6-matpow")
    with pytest.raises(KeyError):
        cases.get_case("not-a-case")
    assert len(cases.list_cases()) == len(cases.CASES) == 20
    assert all(c.known for c in cases.list_cases(known=True))
    assert all(c.category == "redundant"
               for c in cases.list_cases(category="redundant"))
    # the decorator-registered case is present like any other
    assert cases.get_case("n1-gelu-backend").known is False


def test_register_case_rejects_duplicates_and_junk():
    with pytest.raises(ValueError, match="duplicate"):
        cases.register_case(cases.get_case("c6-matpow"))
    with pytest.raises(TypeError):
        cases.register_case(lambda: "not a case")


# ---------------------------------------------------------------------------
# _perturb hardening (satellite fix)
# ---------------------------------------------------------------------------

def test_perturb_handles_degenerate_integer_leaves():
    empty = np.zeros((0, 4), np.int32)
    constant = np.full((3, 3), 7, np.int64)
    varied = np.arange(12, dtype=np.int32).reshape(3, 4)
    floats = np.ones((2, 2), np.float32)
    out = _perturb((empty, constant, varied, floats), seed=0)
    assert out[0].shape == (0, 4) and out[0].dtype == np.int32
    assert np.array_equal(out[1], constant)       # constant: passthrough
    assert out[2].min() >= 0 and out[2].max() <= 11
    assert out[2].dtype == np.int32
    assert out[3].dtype == np.float32


# ---------------------------------------------------------------------------
# capture input validation (satellite fix): clear errors, not deep tracebacks
# ---------------------------------------------------------------------------

def test_capture_rejects_generator_with_clear_error():
    import jax.numpy as jnp

    def streaming(x):
        return (x * i for i in range(3))      # classic mistake: a genexpr

    with pytest.raises(TypeError, match="generator.*arrays"):
        Session().capture(streaming, (jnp.ones((2, 2)),))


def test_capture_rejects_non_array_leaves_with_clear_error():
    import jax.numpy as jnp

    def labelled(x):
        return {"out": x * 2.0, "label": "fast-path"}

    with pytest.raises(TypeError, match="non-array leaves.*str"):
        Session().capture(labelled, (jnp.ones((2, 2)),))


def test_capture_preserves_genuine_candidate_errors():
    """A candidate that raises keeps its own exception — the validation
    probe must not swallow or rewrap real failures."""
    import jax.numpy as jnp

    def boom(x):
        raise RuntimeError("kaboom inside candidate")

    with pytest.raises(RuntimeError, match="kaboom inside candidate"):
        Session().capture(boom, (jnp.ones((2, 2)),))


# ---------------------------------------------------------------------------
# CLI smoke (subprocess)
# ---------------------------------------------------------------------------

def _cli(tmp_path, *argv):
    env = dict(os.environ)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env["PYTHONPATH"] = os.path.join(root, "src") + (
        os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else "")
    env["MAGNETON_STORE"] = str(tmp_path / "store")
    return subprocess.run([sys.executable, "-m", "repro.cli", *argv],
                          capture_output=True, text=True, env=env,
                          cwd=root, timeout=90)


# six CLI runs of at most 90 s each
@pytest.mark.timeout(600)
def test_cli_smoke(tmp_path):
    r = _cli(tmp_path, "cases")
    assert r.returncode == 0, r.stderr
    assert "c6-matpow" in r.stdout and "20 cases" in r.stdout

    rep_json = tmp_path / "rep.json"
    r = _cli(tmp_path, "compare", "c6-matpow:ineff", "c6-matpow:eff",
             "--json", str(rep_json), "--expect-waste")
    assert r.returncode == 0, r.stderr
    assert "energy-waste findings: 1" in r.stdout
    assert rep_json.exists()

    r = _cli(tmp_path, "report", str(rep_json))
    assert r.returncode == 0, r.stderr
    assert "Magneton differential energy report" in r.stdout

    # second capture of the same case must be a store cache hit
    r = _cli(tmp_path, "capture", "c6-matpow:ineff")
    assert r.returncode == 0, r.stderr
    assert "cache-hit" in r.stdout

    r = _cli(tmp_path, "artifacts")
    assert r.returncode == 0, r.stderr
    assert "c6-matpow" in r.stdout
    keys = [line.split()[0] for line in r.stdout.splitlines()
            if line.startswith(tuple("0123456789abcdef")) and "c6" in line]
    assert len(keys) == 2

    # compare by bare artifact key: zoo-born artifacts re-attach via their
    # recorded provenance, so the lazy phase-2 fetches still work
    r = _cli(tmp_path, "compare", keys[0], keys[1], "--output-rtol", "0.05")
    assert r.returncode == 0, r.stderr
    assert "energy-waste findings: 1" in r.stdout


# ---------------------------------------------------------------------------
# parallel per-sample capture
# ---------------------------------------------------------------------------

def _deep_parallel_model():
    def fn(x, w):
        for _ in range(30):           # 151 nodes: parallel auto-threshold hit
            x = (jnp.tanh(x @ w) + 0.5 * x) * 1.01
        return x.sum()
    return fn


def test_parallel_sample_capture_byte_identical_to_serial(monkeypatch):
    """parallel_samples must change wall-clock only: identical store key,
    identical per-sample signatures in identical order, and exactly
    num_samples instrumented executions (spy-visible through the module
    attribute, which the thread pool resolves at submit time)."""
    fn = _deep_parallel_model()
    w = jnp.eye(8) * 0.9
    x = jnp.arange(32.0, dtype=jnp.float32).reshape(4, 8) / 10.0

    art_ser = Session(parallel_samples=False,
                      num_input_samples=4).capture(fn, (x, w), name="m")
    calls = _count_runs(monkeypatch)
    sess = Session(parallel_samples=True, num_input_samples=4)
    art_par = sess.capture(fn, (x, w), name="m")
    assert calls["n"] == 4            # one instrumented run per sample
    assert art_par.key == art_ser.key
    assert len(art_par.sample_stats) == len(art_ser.sample_stats) == 4
    for ks, kp in zip(art_ser.sample_stats, art_par.sample_stats):
        assert sorted(ks) == sorted(kp)
        for t in ks:
            assert repr(ks[t]) == repr(kp[t])   # bitwise-equal invariants


def test_parallel_capture_gate_against_still_fails_fast(monkeypatch):
    """Sample 0 runs serially first, so the functional-equivalence gate
    rejects a different task BEFORE samples 1..n-1 are captured."""
    fn = _deep_parallel_model()

    def other(x, w):
        return (x @ w).sum() * 3.0

    w = jnp.eye(8) * 0.9
    x = jnp.arange(32.0, dtype=jnp.float32).reshape(4, 8) / 10.0
    sess = Session(parallel_samples=True, num_input_samples=4)
    art = sess.capture(fn, (x, w), name="m")
    calls = _count_runs(monkeypatch)
    with pytest.raises(ValueError, match="not the same task"):
        sess.capture(other, (x, w), name="other", gate_against=art)
    assert calls["n"] == 1            # only sample 0 ever executed


def test_compare_stamps_twins_on_live_artifacts():
    """Live-captured artifacts carry their graphs and samples, so compare()
    attaches a BlockStamper: repeated-block pairs are stamped (declared in
    report meta) and the findings still match a stamper-less session's."""
    fn = _deep_parallel_model()
    w = jnp.eye(8) * 0.9
    x = jnp.arange(32.0, dtype=jnp.float32).reshape(4, 8) / 10.0
    sess = Session(num_input_samples=2)
    art_a = sess.capture(fn, (x, w), name="a")
    art_b = sess.capture(fn, (x, w), name="b")
    rep = sess.compare(art_a, art_b)
    assert rep.meta["stamped_pairs"] > 0
    assert rep.meta["eq_tensor_pairs"] >= rep.meta["stamped_pairs"]
