"""Energy-regression harness: golden baselines, the pytest plugin, and
mutation-based detector validation (repro.testing).

Acceptance properties:
  * every recorded zoo baseline replays OFFLINE (no instrumented execution)
    with zero drift,
  * the committed expectations under tests/baselines/ agree with a fresh
    record of the same cases,
  * >= 4 mutation classes are each detected AND correctly classified on
    >= 2 distinct clean programs (>= 8 generated scenarios), with
    misclassifications reported per class,
  * assert_no_energy_regression records, passes clean re-captures, and
    fails mutated candidates with an actionable message.
"""

import json
import os
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.core.interp as interp
from repro.core.diagnose import DIAGNOSIS_KINDS
from repro.testing.baselines import (Baseline, BaselineStore, diff_baselines)
from repro.testing.mutate import (MUTATIONS, InapplicableMutationError,
                                  clean_programs, generate_scenarios,
                                  make_mutant, validate_detector)
from repro.testing.pytest_plugin import assert_no_energy_regression
from repro.zoo import cases as zoo

COMMITTED_DIR = Path(__file__).parent / "baselines"
CASE_IDS = [c.id for c in zoo.list_cases()]


# ---------------------------------------------------------------------------
# golden baselines: offline replay with zero drift
# ---------------------------------------------------------------------------

@pytest.mark.slow
@pytest.mark.parametrize("cid", CASE_IDS)
def test_all_zoo_baselines_replay_offline_with_zero_drift(cid, golden,
                                                          monkeypatch):
    """Every recorded case re-compares bit-identically from its golden
    artifacts — with the instrumented interpreter provably never invoked."""
    golden["records"][cid]                    # lazy fixture: record the case
    def forbid(*a, **k):
        raise AssertionError("offline replay executed a candidate")

    monkeypatch.setattr(interp, "run_instrumented", forbid)
    store = BaselineStore(golden["root"])     # fresh store: disk only
    drifts = store.check(zoo.get_case(cid), offline=True)
    assert not drifts, "golden replay drifted: " + json.dumps(
        [str(d) for d in drifts], indent=2)


@pytest.mark.slow
@pytest.mark.parametrize("cid", CASE_IDS)
def test_committed_baselines_match_fresh_record(cid, golden):
    """The expectations committed under tests/baselines/ are what recording
    produces today — i.e. the detector has not drifted since they were
    blessed.  A legitimate behavior change re-records via
    `python -m repro.cli baseline record`."""
    path = COMMITTED_DIR / f"{cid}.json"
    assert path.exists(), f"{cid}: no committed baseline at {path}"
    committed = Baseline.from_json(path.read_text())
    fresh = golden["records"][cid]["baseline"]
    problems = [str(d) for d in diff_baselines(committed, fresh)]
    assert not problems, "committed baselines drifted:\n  " + \
        "\n  ".join(problems)


def test_baseline_detects_planted_drift(tmp_path):
    """A baseline records the EXPECTED findings: swapping a case's twins
    (so the efficient side is captured as A) must show up as drift."""
    import dataclasses

    case = zoo.get_case("c6-matpow")
    store = BaselineStore(tmp_path)
    store.record(case)
    assert store.check(case, offline=True) == []
    swapped = dataclasses.replace(case, inefficient=case.efficient,
                                  efficient=case.inefficient)
    drifts = store.check(swapped)             # live re-capture of the swap
    fields = {d.field for d in drifts}
    assert fields & {"detected", "waste_findings", "waste[0].wasteful_side"}, \
        f"swapped twins produced no structural drift: {fields}"


def test_offline_check_reports_unmaterialized_fetch_as_drift(tmp_path):
    """A replay that needs phase-2 evidence the golden store never recorded
    is changed matcher behavior — reported as drift, never as advice to
    re-record (which would bless the change unseen)."""
    import json as _json

    case = zoo.get_case("c6-matpow")
    store = BaselineStore(tmp_path)
    store.record(case)
    idx = _json.loads(store.index_path.read_text())
    key = idx[case.id]["a"]
    art = store.artifacts.load(key)
    # the record-time compare persisted its phase-2 decisions (sketch-only:
    # value digests + unfolding spectra, no raw chunks)
    assert art.value_index and not art.values
    # simulate a widened fetch set: strip every recorded decision, so the
    # replay must fetch raw values that were never persisted
    art.value_index.clear()
    art.spectra_memo.clear()
    store.artifacts.save(art)
    drifts = store.check(case, offline=True)
    assert [d.field for d in drifts] == ["offline_replay"]


def test_missing_baseline_raises_with_instructions(tmp_path):
    from repro.testing.baselines import MissingBaselineError

    store = BaselineStore(tmp_path)
    with pytest.raises(MissingBaselineError, match="baseline record"):
        store.check(zoo.get_case("c6-matpow"))


# ---------------------------------------------------------------------------
# mutation-based detector validation
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def mutation_validation():
    return validate_detector(generate_scenarios())


def test_scenario_space_breadth(mutation_validation):
    """The generated scenario space covers the full taxonomy: all 8
    mutation classes generate scenarios, each on >= 2 distinct clean
    programs, >= 20 scenarios overall — and every expected kind is a real
    taxonomy member."""
    res = mutation_validation
    assert len(MUTATIONS) == 8
    assert len(res.results) >= 20
    per_class = res.by_class()
    assert set(per_class) == set(MUTATIONS), \
        f"classes with no generated scenario: {set(MUTATIONS) - set(per_class)}"
    narrow = {cls for cls, rs in per_class.items()
              if len({r.program for r in rs}) < 2}
    assert not narrow, f"classes with <2 programs: {sorted(narrow)}"
    for cls in MUTATIONS.values():
        assert cls.expected_kinds
        assert set(cls.expected_kinds) <= set(DIAGNOSIS_KINDS)


def test_mutants_detected_and_correctly_classified(mutation_validation):
    """All 8 classes detected AND correctly root-caused on >= 2 programs
    each; misclassified scenarios (if any) are reported per class in the
    failure message."""
    res = mutation_validation
    assert res.validated_classes(min_programs=2) == set(MUTATIONS), \
        res.summary()
    # this repo's detector currently clears the whole matrix — hold the line
    assert not res.misclassified(), res.summary()


def test_new_waste_classes_target_the_planted_constructs():
    """The PR-4 taxonomy additions hit their intended sites: scan_body only
    rewrites scans with body matmuls, layout_thrash round-trips matmul
    operands, storage_upcast only fires on bf16 non-matmul ops."""
    progs = {p.name: p for p in clean_programs()}

    scan_prog = progs["scan_mlp"]
    args = scan_prog.make_args()
    mutant, sites = make_mutant(scan_prog.fn, MUTATIONS["scan_body"](), args)
    assert sites == 1                          # one scan super-node
    want = np.asarray(scan_prog.fn(*args))
    np.testing.assert_array_equal(np.asarray(mutant(*args)), want)

    # no scan -> no site, and the refusal says why
    mlp = progs["mlp_swiglu"]
    with pytest.raises(InapplicableMutationError,
                       match="no applicable site"):
        make_mutant(mlp.fn, MUTATIONS["scan_body"](), mlp.make_args())

    # layout_thrash: bitwise-identical values, one site per dot
    args = mlp.make_args()
    mutant, sites = make_mutant(mlp.fn, MUTATIONS["layout_thrash"](), args)
    assert sites == 3
    np.testing.assert_array_equal(np.asarray(mutant(*args)),
                                  np.asarray(mlp.fn(*args)))

    # storage_upcast: fires on the bf16 program, never on f32 ones
    bf16 = progs["act_chain_bf16"]
    args = bf16.make_args()
    mutant, sites = make_mutant(bf16.fn, MUTATIONS["storage_upcast"](), args)
    assert sites >= 2
    got = np.asarray(mutant(*args), dtype=np.float32)
    want = np.asarray(bf16.fn(*args), dtype=np.float32)
    np.testing.assert_allclose(got, want, rtol=1e-2, atol=1e-2)
    with pytest.raises(InapplicableMutationError,
                       match="not uniformly.*bf16|bf16"):
        make_mutant(mlp.fn, MUTATIONS["storage_upcast"](), mlp.make_args())


def test_mutants_preserve_semantics():
    """One scenario per class: the mutant computes the same function (it
    must pass the equivalence gate, not dodge it) and rewrites >= 1 site."""
    prog = clean_programs()[3]                # gelu_dense: dot + tanh
    args = prog.make_args()
    want = np.asarray(prog.fn(*args))
    seen = set()
    for name, cls in MUTATIONS.items():
        mutant, sites = make_mutant(prog.fn, cls(), args,
                                    allow_zero_sites=True)
        if sites == 0:
            continue
        seen.add(name)
        got = np.asarray(mutant(*args))
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5,
                                   err_msg=name)
    assert len(seen) >= 4


def test_mutation_max_sites_bounds_rewrites():
    prog = clean_programs()[0]                # mlp: 3 dot sites
    args = prog.make_args()
    _, all_sites = make_mutant(prog.fn, MUTATIONS["redundant_recompute"](),
                               args)
    assert all_sites == 3
    _, capped = make_mutant(
        prog.fn, MUTATIONS["redundant_recompute"](max_sites=1), args)
    assert capped == 1


# ---------------------------------------------------------------------------
# pytest plugin: assert_no_energy_regression + energy_regression marker
# ---------------------------------------------------------------------------

def _norm_prog():
    from repro.models import layers

    k1, k2 = jax.random.split(jax.random.key(42))
    x = jax.random.normal(k1, (64, 128), jnp.float32)
    scale = jax.random.normal(k2, (128,), jnp.float32) * 0.1 + 1.0

    def rms_norm_candidate(x, scale):
        return layers.rms_norm(x, scale)

    return rms_norm_candidate, (x, scale)


def test_energy_gate_records_then_passes(tmp_path):
    fn, args = _norm_prog()
    path = tmp_path / "norm.npz"
    assert assert_no_energy_regression(fn, args, path, record=True) is None
    assert path.exists()
    # identical re-capture: bit-identical content address, clean pass
    assert assert_no_energy_regression(fn, args, path) is None


def test_energy_gate_fails_on_injected_regression(tmp_path):
    fn, args = _norm_prog()
    path = tmp_path / "norm.npz"
    assert_no_energy_regression(fn, args, path, record=True)
    # inapplicable mutations refuse loudly instead of minting a clean twin
    for inapplicable in ("oversized_padding",   # no matmul in rms_norm
                         "op_split",            # rsqrt is not split
                         "sync_in_loop"):
        with pytest.raises(InapplicableMutationError):
            make_mutant(fn, MUTATIONS[inapplicable](), args)
    # recompute has no dot either -> plant the waste by hand: double work
    def regressed(x, scale):
        a = fn(x, scale)
        b = fn(x + 0.0, scale)
        return a * 0.5 + b * 0.5

    with pytest.raises(pytest.fail.Exception, match="energy regression"):
        assert_no_energy_regression(regressed, args, path, name="regressed")


def test_energy_gate_passes_on_improvement(tmp_path):
    fn, args = _norm_prog()

    def wasteful(x, scale):
        a = fn(x, scale)
        b = fn(x + 0.0, scale)
        return a * 0.5 + b * 0.5

    path = tmp_path / "wasteful.npz"
    assert_no_energy_regression(wasteful, args, path, record=True)
    report = assert_no_energy_regression(fn, args, path, name="improved")
    assert report is not None                 # compared, and came out cheaper
    assert all(f.wasteful_side != "A" for f in report.waste_findings)


def test_energy_gate_missing_baseline_instructs(tmp_path):
    fn, args = _norm_prog()
    with pytest.raises(pytest.fail.Exception,
                       match="MAGNETON_RECORD_BASELINES"):
        assert_no_energy_regression(fn, args, tmp_path / "nope.npz",
                                    record=False)


@pytest.mark.energy_regression
def test_energy_gate_marker_and_fixture(energy_gate, tmp_path):
    """In-suite usage shape: a marked test gating a src/repro kernel via the
    `energy_gate` fixture (redirected to a tmp baseline dir here)."""
    fn, args = _norm_prog()
    energy_gate(fn, args, baseline="rms_norm_gate", record=True,
                baseline_dir=tmp_path)
    assert (tmp_path / "kernels" / "rms_norm_gate.npz").exists()
    energy_gate(fn, args, baseline="rms_norm_gate", baseline_dir=tmp_path)


def test_energy_regression_marker_registered(request):
    assert any("energy_regression" in m
               for m in request.config.getini("markers"))


# ---------------------------------------------------------------------------
# CLI: baseline record / check --offline
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


# three CLI runs of at most 90 s each stay inside the default 300 s limit
@pytest.mark.slow
def test_cli_baseline_record_and_offline_check(tmp_path):
    bdir = tmp_path / "baselines"
    r = _cli(tmp_path, "baseline", "record", "--dir", str(bdir), "c6-matpow")
    assert r.returncode == 0, r.stderr
    assert "recorded c6-matpow" in r.stdout
    assert (bdir / "c6-matpow.json").exists()

    r = _cli(tmp_path, "baseline", "check", "--dir", str(bdir), "--offline",
             "c6-matpow")
    assert r.returncode == 0, r.stderr
    assert "ok    c6-matpow" in r.stdout
    assert "1/1 cases clean" in r.stdout

    # checking a case that was never recorded exits 2 with instructions
    r = _cli(tmp_path, "baseline", "check", "--dir", str(bdir), "--offline",
             "c15-expm")
    assert r.returncode == 2
    assert "baseline record" in r.stderr
