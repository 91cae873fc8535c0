"""CPU tests of the benchmark's own code: traffic, arithmetic, trace
reduction, the reference, and that ``correct`` fails when the timed path is
broken.  Each run here is at the configurations' rehearsal sizes."""

from __future__ import annotations

import hashlib
import importlib.util
import json
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parent


def _load(name: str):
    spec = importlib.util.spec_from_file_location(
        f"bench_test_{name.replace('/', '_').replace('.', '_')}", BENCH / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod


harness = _load("run")
traffic = _load("traffic")
e2e = _load("e2e")
decoder = _load("configs/decoder")
trace_reduce = _load("trace_reduce")
host_gap = _load("metrics/host_gap_ms.serve")

CHAT = json.loads((BENCH / "traffic" / "chat-b32-unpadded.json").read_text())
MIXED = json.loads((BENCH / "traffic" / "chat-b32-mixed.json").read_text())
PHI3 = json.loads((BENCH / "configs" / "phi3-medium.json").read_text())
CELL = "serve.phi3-medium.unpadded"
SEED = 3_000_000_019          # above 2**31: seeds need not fit 32 bits


def _waves(seed, n, mix=CHAT):
    stream = traffic.wave_stream(mix, seed, 32064)
    return [next(stream) for _ in range(n)]


# -- traffic ------------------------------------------------------------------

@pytest.mark.parametrize("mix", [CHAT, MIXED], ids=["unpadded", "mixed"])
def test_one_seed_gives_identical_traffic(mix):
    a, b = _waves(SEED, 30, mix), _waves(SEED, 30, mix)
    for (wa, pa, la), (wb, pb, lb) in zip(a, b):
        assert wa == wb and la == lb
        for x, y in zip(pa, pb):
            np.testing.assert_array_equal(x, y)


@pytest.mark.parametrize("mix", [CHAT, MIXED], ids=["unpadded", "mixed"])
def test_seeds_reorder_the_same_sizes(mix):
    cycle = len(traffic.wave_cycle(mix))
    a, b = _waves(SEED, cycle, mix), _waves(SEED + 1, cycle, mix)
    assert [w for w, _, _ in a] != [w for w, _, _ in b]
    assert not np.array_equal(a[0][1][0], b[0][1][0])

    def key(ws):
        return sorted(tuple(sorted(zip((len(p) for p in ps), ls)))
                      for _, ps, ls in ws)
    assert key(a) == key(b)


def test_unpadded_waves_share_one_warmed_prompt_length():
    warmed = {wv.padded_len for wv in traffic.wave_cycle(CHAT)}
    assert len(warmed) == CHAT["waves_per_cycle"]
    for wave, prompts, lens in _waves(SEED, 60):
        assert len(prompts) == CHAT["batch"] == len(lens)
        assert {len(p) for p in prompts} == {wave.padded_len}
        assert wave.padded_len in warmed
        assert wave.padded_len + max(lens) <= CHAT["max_len"]
        assert min(lens) >= CHAT["output_len"]["min"]


def test_mixed_waves_give_every_request_its_own_prompt_length():
    cycle = traffic.wave_cycle(MIXED)
    lengths = sorted(n for wv in cycle for n in wv.prompt_lens)
    p = MIXED["prompt_len"]
    assert lengths == sorted(traffic.lognormal_quantiles(
        len(lengths), p["median"], p["sigma"], p["min"], p["max"]))
    assert all(len(set(wv.prompt_lens)) > 1 for wv in cycle)
    assert all(wv.padded_len + max(wv.output_lens) <= MIXED["max_len"]
               for wv in cycle)


def test_lengths_follow_the_stated_distribution():
    o = CHAT["output_len"]
    lens = traffic.lognormal_quantiles(1001, o["median"], o["sigma"],
                                       o["min"], o["max"])
    assert lens[500] == o["median"]
    assert min(lens) == o["min"] and max(lens) == o["max"]


# -- end-to-end arithmetic ----------------------------------------------------

def test_rate_is_all_work_over_all_time():
    # 3 waves of 100 tokens; the last ends 0.4 s after the nominal 1 s close
    assert e2e.per_second(300, 1.4) == pytest.approx(300 / 1.4)
    with pytest.raises(ValueError):
        e2e.per_second(300, 0.0)


def test_p95_is_over_every_request_not_over_chunks():
    lat = [1.0] * 90 + [10.0] * 10
    chunks = [lat[i:i + 10] for i in range(0, 100, 10)]
    per_chunk = float(np.mean([np.percentile(c, 95) for c in chunks]))
    assert e2e.p95(lat) == pytest.approx(np.percentile(lat, 95))
    assert e2e.p95(lat) != pytest.approx(per_chunk)


def test_sizes_come_from_the_published_keys():
    shape = decoder.shape_of(PHI3)
    assert (shape["layers"], shape["d_model"], shape["heads"],
            shape["kv_heads"], shape["head_dim"], shape["d_ff"],
            shape["vocab"], shape["tied"]) == (8, 5120, 40, 10, 128, 17920,
                                               32064, False)
    small = decoder.shape_of(PHI3, rehearse=True)
    assert (small["layers"], small["d_model"], small["head_dim"]) == \
        (2, 64, 16)
    with pytest.raises(ValueError):
        decoder.shape_of(dict(PHI3, hidden_act="gelu_new"))


def test_model_flops_match_the_layout():
    shape = decoder.shape_of(PHI3)
    layout = decoder.layout(shape)
    layer_params = sum(int(np.prod(a.shape)) for a in
                       [layout["blocks"]["layer0"]["attn"][k] for k in
                        ("w_q", "w_k", "w_v", "w_o")]
                       + [layout["blocks"]["layer0"]["mlp"][k] for k in
                          ("w_gate", "w_up", "w_down")])
    head = shape["d_model"] * shape["vocab"]
    # one token, one position: every weight once, no attention pairs but 1
    one = decoder.forward(shape, 1, 1, 1)
    attn = 2 * 2 * shape["layers"] * shape["heads"] * shape["head_dim"]
    assert one == 2 * layer_params + 2 * head + attn
    assert decoder.served_request(shape, 4, 1) == \
        decoder.forward(shape, 1, 4, 1)


# What the harness made of phi3-medium before the dense decoder's sizes,
# layout, FLOPs and reference moved into its family module: the move left
# every number of the cell as it was.
PHI3_SHAPE = {"layers": 8, "d_model": 5120, "heads": 40, "kv_heads": 10,
              "head_dim": 128, "d_ff": 17920, "vocab": 32064, "tied": False,
              "rope_theta": 10000.0, "norm_eps": 1e-05, "dtype": "bfloat16"}
PHI3_LAYOUT = {
    "['blocks']['layer0']['attn']['w_k']": ((8, 5120, 10, 128), "bfloat16"),
    "['blocks']['layer0']['attn']['w_o']": ((8, 40, 128, 5120), "bfloat16"),
    "['blocks']['layer0']['attn']['w_q']": ((8, 5120, 40, 128), "bfloat16"),
    "['blocks']['layer0']['attn']['w_v']": ((8, 5120, 10, 128), "bfloat16"),
    "['blocks']['layer0']['mlp']['w_down']": ((8, 17920, 5120), "bfloat16"),
    "['blocks']['layer0']['mlp']['w_gate']": ((8, 5120, 17920), "bfloat16"),
    "['blocks']['layer0']['mlp']['w_up']": ((8, 5120, 17920), "bfloat16"),
    "['blocks']['layer0']['norm1']": ((8, 5120), "float32"),
    "['blocks']['layer0']['norm2']": ((8, 5120), "float32"),
    "['embed']['lm_head']": ((5120, 32064), "bfloat16"),
    "['embed']['tok_embed']": ((32064, 5120), "bfloat16"),
    "['final_norm']": ((5120,), "float32")}
PHI3_REQUEST_FLOPS = {(59, 16): 409200066560, (200, 64): 1460733870080,
                      (512, 256): 4314449838080}
# sha256 of the rehearsal-size weights for SEED (leaves in tree order), and
# of the reference's float32 logits (f32, fp8) on fixed tokens with std 0.2
REHEARSAL_WEIGHTS_SHA256 = \
    "6a71fc7e341a6ad4ab3d1ca4331e84262bf31b25f9dc1e5ed25a42a051861e27"
REFERENCE_LOGITS_SHA256 = {
    "f32": "d9685c47a33f8306809219cdb3af438b826c37976e9d60822245c71dee84feee",
    "fp8": "8e87828d45ba99302285e1287eabd50e5445707d728433e68f29862d5964e91f"}


def _sha256(arrays) -> str:
    h = hashlib.sha256()
    for a in arrays:
        h.update(np.asarray(a).tobytes())
    return h.hexdigest()


def test_the_decoder_family_keeps_phi3_mediums_numbers():
    import jax
    shape = decoder.shape_of(PHI3)
    assert shape == PHI3_SHAPE
    flat = jax.tree_util.tree_flatten_with_path(decoder.layout(shape))[0]
    assert {jax.tree_util.keystr(p): (tuple(a.shape), str(a.dtype))
            for p, a in flat} == PHI3_LAYOUT
    assert {pair: decoder.served_request(shape, *pair)
            for pair in PHI3_REQUEST_FLOPS} == PHI3_REQUEST_FLOPS
    run = _run()
    assert run.family.__file__ == str(BENCH / "configs" / "decoder.py")
    harness._program_on_path()
    params = run.make_params(run.model_config())
    assert _sha256(jax.tree_util.tree_leaves(params)) == \
        REHEARSAL_WEIGHTS_SHA256
    run = _run(std=0.2)
    params = run.make_params(run.model_config())
    toks = np.random.default_rng(0).integers(0, 256, (2, 24))
    for mode, digest in REFERENCE_LOGITS_SHA256.items():
        logits = run.reference(mode).logits(params, toks)
        assert _sha256([logits]) == digest, mode


# -- trace reduction ----------------------------------------------------------

RECORDED = BENCH / "testdata" / "small.xplane.pb"


def test_trace_reduction_on_a_recorded_trace():
    tr = trace_reduce.load(str(RECORDED))
    s = trace_reduce.summarize(tr)
    assert s["devices"] == 1
    assert 0 < s["busy_s"] < s["window_s"]
    # 4 waves of 3 decode steps and one other program; this trace's device
    # clock runs about 1 ms ahead of the host's, so the first step falls
    # before the window's start
    decode = [ex for n, ex in s["modules"].items() if "decode_step" in n]
    assert len(decode) == 1 and len(decode[0]) == 11
    others = [e for n, ex in s["modules"].items() if "decode_step" not in n
              for e in ex]
    assert len(others) == 4
    gaps = host_gap.step_gaps(decode[0], others)
    assert len(gaps) == 7        # 2 per wave not crossed by another program
    assert all(g > 0 for g in gaps)
    # the recorded trace's other program stands for each wave's prefill
    waves = dict(s, modules={("jit_prefill_step" if "decode_step" not in n
                              else n): ex for n, ex in s["modules"].items()})
    assert host_gap.read({"trace": waves}) == pytest.approx(
        sum(gaps) / len(gaps) / 1e6)
    # small programs between two steps (the argmax read) do not split them
    every = host_gap.step_gaps(decode[0], [])
    assert len(every) == 10
    assert host_gap.read({"trace": s}) == pytest.approx(
        sum(every) / len(every) / 1e6)
    assert s["device_ops"] and len(s["device_ops"]) <= 10
    labels = [n for n, _ in s["idle_gaps"]]
    assert any(n.startswith("bench.serve.wave") for n in labels)
    assert sum(t for _, t in s["idle_gaps"]) == pytest.approx(
        s["window_s"] - s["busy_s"], rel=1e-6)


def test_merge_and_step_gaps():
    assert trace_reduce.merge([(0, 5), (3, 8), (10, 12), (11, 20)], 2, 15) \
        == [(2, 8), (10, 15)]
    assert host_gap.step_gaps([(0, 1), (3, 4), (9, 10)], [(5, 6)]) == [2]


# -- the reference and correctness --------------------------------------------

def _run(workload=CELL, mix=None, seconds=0.2, std=None):
    run = harness.make_run(workload, SEED, seconds, False, True)
    if mix is not None:
        run.mix = mix
    if std is not None:
        run.config = dict(run.config, initializer_range=std)
    return run


TINY_CHAT = {"runner": "serve_waves", "batch": 4, "waves_per_cycle": 2,
             "one_prompt_length_per_wave": True,
             "prompt_len": {"median": 12, "sigma": 0.2, "min": 8, "max": 16},
             "output_len": {"median": 6, "sigma": 0.2, "min": 4, "max": 8},
             "max_len": 32, "auditor": None, "check_requests": 4}


@pytest.mark.parametrize("qk_norm_in_reference", [True, False],
                         ids=["reference", "reference_without_qk_norm"])
def test_a_family_brought_by_files_alone_is_served(qk_norm_in_reference,
                                                   monkeypatch):
    """``testdata/qk_norm_decoder.py``, a family no harness file names,
    served through ``run_cell``: correct with its reference, and not correct
    once its reference leaves out the q/k norm the program computes."""
    run = _run(mix=TINY_CHAT, std=0.2)
    run.config = dict(run.config, family="../testdata/qk_norm_decoder")
    family = run.family
    assert run.model_config().qk_norm is True
    if not qk_norm_in_reference:
        monkeypatch.setattr(family.Reference, "_project",
                            family.decoder.Reference._project)
    result = harness.run_cell(run)
    assert result["correct"] is qk_norm_in_reference, result["checks"]
    assert result["attempted"] > 0 and run.outcome["data"]["model_flops"]


def test_reference_is_the_programs_model():
    import jax
    import jax.numpy as jnp
    harness._program_on_path()
    from repro.models import transformer as tf
    run = _run(std=0.2)
    cfg = run.model_config()
    params = run.make_params(cfg)
    toks = np.random.default_rng(0).integers(0, cfg.vocab_size, (2, 24))
    cfg32 = type(cfg)(**{**cfg.__dict__, "dtype": "float32"})
    p32 = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), params)
    with jax.default_matmul_precision("highest"):
        want, _ = tf.forward(cfg32, p32, jnp.asarray(toks), remat=False)
    got = run.reference("f32").logits(params, toks)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-4)
    low = run.reference("fp8").logits(params, toks)
    assert float(jnp.abs(low - got).max()) > 20 * 2e-4


def _break_decode(monkeypatch, kind):
    """Plant ``kind`` in the engine's jitted decode step."""
    harness._program_on_path()
    import jax.numpy as jnp
    from repro.serve import engine as eng
    make = eng.make_decode_step

    def broken_maker(cfg, mesh, attn_impl="xla"):
        step = make(cfg, mesh, attn_impl=attn_impl)

        def broken(params, caches, tok, pos):
            logits, new = step(params, caches, tok, pos)
            if kind == "token_altered":
                return jnp.roll(logits, 1, axis=-1), new
            if kind == "state_unchanged":
                return logits, caches
            half = logits.shape[0] // 2          # half the batch left out
            return logits.at[half:].set(logits[:half]), new
        return broken
    monkeypatch.setattr(eng, "make_decode_step", broken_maker)


@pytest.mark.parametrize("fault", [None, "token_altered", "state_unchanged",
                                   "half_batch"])
def test_a_broken_serving_step_is_not_correct(fault, monkeypatch):
    if fault is not None:
        _break_decode(monkeypatch, fault)
    result = harness.run_cell(_run(mix=TINY_CHAT, std=0.2))
    assert result["correct"] is (fault is None), result["checks"]
    assert result["attempted"] > 0 and result["metrics"] == {}


def _serve_with_the_control(monkeypatch, run):
    """Put the float8 reference in the engine's place: every request is
    decoded greedily by the control, token by token."""
    harness._program_on_path()
    from repro.serve.engine import ServeEngine
    low = run.reference("fp8")

    def serve_wave(engine, wave):
        for r in wave:
            toks = list(r.prompt)
            while len(r.generated) < r.max_new_tokens:
                lg = low.logits(engine.params, np.asarray([toks]),
                                last_only=True)
                r.generated.append(int(np.argmax(np.asarray(lg)[0, -1])))
                toks.append(r.generated[-1])
    monkeypatch.setattr(ServeEngine, "_serve_wave", serve_wave)


def test_the_control_in_the_programs_place_is_not_correct(monkeypatch):
    """The program and the float8 control read ``served_gap`` on the same
    traffic; a limit set between the two readings by the cell's rule (the
    control at least three times the program) passes the program and fails
    the control, through the comparison that decides ``correct``."""
    read = _load("control")
    program = read.read_seed(_run(mix=TINY_CHAT, std=0.2))
    run = _run(mix=TINY_CHAT, std=0.2)
    _serve_with_the_control(monkeypatch, run)
    control = harness.run_cell(run)
    p = program["program"]["served_gap"]
    c = control["checks"]["served_gap"]["value"]
    assert c >= 3 * p, (p, c)
    limits = dict(run.limits, served_gap=float(np.sqrt(max(p, 1e-3) * c)))
    assert harness.judge(program["program"], limits)[1] is True
    assert harness.judge({k: v["value"] for k, v in
                          control["checks"].items()}, limits)[1] is False
    # the control's own reading, as control.py judges it, fails too
    assert harness.judge(program["control"]["checks"], limits)[1] is False


def test_the_auditor_path_rehearses_clean():
    """The runner's live-auditor option (for an audited mix): sampled drift
    checks in the window, none failing and none alarming."""
    mix = dict(TINY_CHAT, auditor={"sample_every": 2, "recheck_every": 2})
    run = _run(mix=mix, std=0.2, seconds=1.0)
    run.limits = dict(run.limits, audit_failures=0, drift_alarms=0)
    result = harness.run_cell(run)
    assert result["correct"] is True, result["checks"]
    assert {"audit_failures", "drift_alarms"} <= set(result["checks"])
    assert run.outcome["data"]["audit_checks"] > 0


# -- the command --------------------------------------------------------------

def test_the_bench_tests_run_under_the_repositorys_time_limit(request):
    conftest = BENCH.parent / "tests" / "conftest.py"
    for hook in ("pytest_runtest_setup", "pytest_runtest_call",
                 "pytest_runtest_teardown"):
        impls = getattr(request.node.ihook, hook).get_hookimpls()
        assert [i for i in impls if i.function.__code__.co_filename
                == str(conftest)], hook


def test_off_a_tpu_the_command_prints_nothing_and_fails(capsys):
    rc = harness.main(["--workload", CELL, "--seed", "1",
                       "--seconds", "1", "--trace", "0"])
    assert rc == 2
    assert capsys.readouterr().out == ""


def test_without_the_program_the_command_fails(tmp_path):
    import shutil
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("out", "__pycache__"))
    shutil.copy(BENCH.parent / "BENCHMARK.json", tmp_path)
    proc = subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         CELL, "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=tmp_path, capture_output=True, text=True,
        timeout=120)
    assert proc.returncode != 0 and proc.stdout == ""
