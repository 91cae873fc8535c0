"""Serve engine: continuous batching, slot reuse, stats, decode parity."""

import importlib.util
import os
import time

import jax
import numpy as np
import pytest

from repro import configs
from repro.core.faults import SimulatedCrash
from repro.models import transformer as tf
from repro.serve.engine import EngineConfig, Request, ServeEngine

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def engine():
    cfg = configs.get_config("gpt2-small").reduced(num_layers=2)
    params = tf.model_init(cfg, jax.random.key(0))
    return cfg, ServeEngine(cfg, params,
                            ecfg=EngineConfig(batch_size=2, max_len=48))


def test_generate_fills_all_requests(engine):
    _, eng = engine
    rng = np.random.default_rng(0)
    reqs = [Request(rid=i, prompt=rng.integers(0, 256, 8, dtype=np.int32),
                    max_new_tokens=4) for i in range(5)]
    eng.generate(reqs)
    for r in reqs:
        assert len(r.generated) == 4
    # 5 requests at batch 2 -> 3 prefill waves
    assert eng.stats["prefill_calls"] >= 3
    assert eng.stats["tokens_generated"] >= 20


def test_variable_prompt_lengths_left_padded(engine):
    _, eng = engine
    rng = np.random.default_rng(1)
    reqs = [Request(rid=0, prompt=rng.integers(0, 256, 4, dtype=np.int32),
                    max_new_tokens=3),
            Request(rid=1, prompt=rng.integers(0, 256, 9, dtype=np.int32),
                    max_new_tokens=3)]
    eng.generate(reqs)
    assert all(len(r.generated) == 3 for r in reqs)


def test_greedy_determinism(engine):
    cfg, eng = engine
    prompt = np.arange(8, dtype=np.int32)
    a = Request(rid=0, prompt=prompt, max_new_tokens=5)
    b = Request(rid=1, prompt=prompt.copy(), max_new_tokens=5)
    eng.generate([a])
    eng.generate([b])
    assert a.generated == b.generated


# -- audit error boundary -----------------------------------------------------

def _small_ecfg(**kw):
    return EngineConfig(batch_size=2, max_len=48, **kw)


def test_engine_default_configs_are_independent(engine):
    """Regression: a mutable `ecfg=EngineConfig()` dataclass-style default
    aliased one config object across every engine construction."""
    cfg, eng = engine
    e1 = ServeEngine(cfg, eng.params)
    e2 = ServeEngine(cfg, eng.params)
    assert e1.ecfg is not e2.ecfg
    e1.ecfg.batch_size = 99
    assert e2.ecfg.batch_size == EngineConfig().batch_size
    assert EngineConfig().batch_size != 99
    assert eng.ecfg.batch_size == 2            # module engine untouched


def test_audit_crash_counts_and_opens_breaker(engine, monkeypatch):
    cfg, base = engine
    eng = ServeEngine(cfg, base.params,
                      ecfg=_small_ecfg(audit_breaker_threshold=2))

    def boom(**kw):
        raise SimulatedCrash("audit process died")

    monkeypatch.setattr(eng, "energy_report", boom)
    assert eng.audit() is None                 # never raises
    assert eng.stats["audit_failures"] == 1
    assert not eng.stats["audit_breaker_open"]
    assert eng.audit() is None
    assert eng.stats["audit_breaker_open"]     # threshold reached
    assert "SimulatedCrash" in eng.stats["audit_last_error"]

    assert eng.audit() is None                 # breaker open: not attempted
    assert eng.stats["audit_calls"] == 2
    assert eng.stats["audit_skipped"] == 1

    eng.reset_audit_breaker()
    monkeypatch.setattr(eng, "energy_report", lambda **kw: None)
    eng.audit()
    assert eng.stats["audit_ok"] == 1
    assert eng.stats["audit_consecutive_failures"] == 0


def test_audit_watchdog_timeout(engine, monkeypatch):
    cfg, base = engine
    eng = ServeEngine(cfg, base.params, ecfg=_small_ecfg())
    monkeypatch.setattr(eng, "energy_report",
                        lambda **kw: time.sleep(2.0))
    assert eng.audit(timeout_s=0.05) is None
    assert eng.stats["audit_timeouts"] == 1
    assert "watchdog" in eng.stats["audit_last_error"]


def test_serving_survives_force_killed_audit(engine, monkeypatch):
    """Smoke test from the issue: the audit force-killed out from under the
    engine; every request still completes and the breaker is open."""
    cfg, base = engine
    eng = ServeEngine(cfg, base.params,
                      ecfg=_small_ecfg(audit_breaker_threshold=1))

    def killed(**kw):
        raise SimulatedCrash("audit force-killed")

    monkeypatch.setattr(eng, "energy_report", killed)
    assert eng.audit() is None
    assert eng.stats["audit_breaker_open"]

    rng = np.random.default_rng(2)
    reqs = [Request(rid=i, prompt=rng.integers(0, 256, 6, dtype=np.int32),
                    max_new_tokens=4) for i in range(4)]
    eng.generate(reqs)
    assert all(len(r.generated) == 4 for r in reqs)
    assert eng.stats["audit_breaker_open"]     # still open, serving unharmed
    assert eng.audit() is None
    assert eng.stats["audit_skipped"] == 1


def test_energy_report_names_the_all_position_lm_head(engine):
    """The HLO-priced audit of the prompt pass finds its waste in the region
    that holds the all-position LM-head dot, and nowhere else."""
    from repro.core.energy import HloCostBackend
    from repro.core.session import Session
    from repro.hw.specs import CPU_HOST
    cfg, eng = engine
    captured = {}

    class Recording(Session):
        def capture(self, *a, **kw):
            art = super().capture(*a, **kw)
            captured[art.name] = art
            return art

    report = eng.energy_report(session=Recording(
        backend=HloCostBackend(CPU_HOST), allow_degraded=False))
    assert not report.is_degraded
    graph = captured["lmhead-all"].graph
    heads = {nd.idx for nd in graph.nodes if nd.primitive == "dot_general"
             and graph.tensors[nd.outvars[0]].shape[-1] == cfg.vocab_size}
    (waste,) = report.waste_findings
    assert waste.wasteful_side == "A" and heads & set(waste.nodes_a)


# -- no path that hides the device ---------------------------------------------

def test_pallas_attention_is_refused_on_the_cached_path(engine):
    """The Pallas kernel has no cached form: serving with it (every served
    step attends over a KV cache) is refused when the engine is built, and
    a cached call outside the engine raises instead of running XLA."""
    cfg, eng = engine
    with pytest.raises(ValueError, match="pallas"):
        ServeEngine(cfg, eng.params, ecfg=_small_ecfg(attn_impl="pallas"))
    tokens = np.zeros((1, 8), np.int32)
    with pytest.raises(ValueError, match="pallas"):
        tf.prefill(cfg, eng.params, tokens, max_len=16, attn_impl="pallas")


def test_launch_serve_fails_when_the_asked_audit_is_unavailable(monkeypatch,
                                                               capsys):
    from repro.launch import serve

    def boom(self, **kw):
        raise SimulatedCrash("profiler unavailable")

    monkeypatch.setattr(ServeEngine, "energy_report", boom)
    argv = ["--smoke", "--requests", "2", "--prompt-len", "4",
            "--max-new", "2", "--batch-size", "2"]
    assert serve.main(argv) == 0               # no audit asked: serving ok
    assert serve.main(argv + ["--energy-audit"]) == 1
    assert "energy audit unavailable" in capsys.readouterr().err


def test_chip_smoke_refuses_a_host_without_a_tpu(capsys, monkeypatch,
                                                 tmp_path):
    monkeypatch.setenv("TPU_LOG_DIR", str(tmp_path))   # main() sets a default
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(REPO, "chip_smoke.py"))
    smoke = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(smoke)
    assert smoke.main([]) != 0
    out = capsys.readouterr()
    assert "'cpu'" in out.err
    assert out.out == ""                        # no result line, no work


def test_compile_cache_dir_is_fixed(monkeypatch):
    """The entry points' cache: $JAX_COMPILATION_CACHE_DIR when set, else
    one fixed directory in the checkout (never a temporary name)."""
    from repro import compile_cache
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert compile_cache.cache_dir() == os.path.realpath(
        os.path.join(REPO, ".jax_cache"))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
    assert compile_cache.cache_dir() == "/elsewhere/cache"
