"""Serve engine: waves, stats, the device-side token loop, decode parity."""

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


# -- the tokens stay on the device between steps --------------------------------

@pytest.fixture(scope="module")
def varied(engine):
    """The module's engine at three times its weights: the greedy tokens
    then vary from step to step, so an ``eos_id`` can fire mid-wave."""
    cfg, eng = engine
    params = jax.tree_util.tree_map(lambda a: a * 3, eng.params)
    return cfg, ServeEngine(cfg, params, ecfg=EngineConfig(batch_size=2,
                                                           max_len=48))


def _host_synced_wave(eng, wave) -> int:
    """The oracle: the wave loop as it was when each step's argmax was read
    back to the host and copied back for the next step.  Returns the
    decode steps it ran."""
    import jax.numpy as jnp
    ecfg = eng.ecfg
    B = len(wave)
    plen = max(len(r.prompt) for r in wave)
    tokens = np.zeros((B, plen), np.int32)
    for i, r in enumerate(wave):
        tokens[i, plen - len(r.prompt):] = r.prompt
    logits, caches = eng._prefill(eng.params, jnp.asarray(tokens), None)
    next_tok = np.asarray(jnp.argmax(logits[:, -1, :], -1),
                          np.int32)[:, None]
    for i, r in enumerate(wave):
        r.generated.append(int(next_tok[i, 0]))
    pos, steps = plen, 0
    for _ in range(max(r.max_new_tokens for r in wave) - 1):
        if pos >= ecfg.max_len:
            break
        logits, caches = eng._decode(eng.params, caches,
                                     jnp.asarray(next_tok), jnp.int32(pos))
        steps += 1
        next_tok = np.asarray(jnp.argmax(logits[:, -1, :], -1),
                              np.int32)[:, None]
        pos += 1
        for i, r in enumerate(wave):
            if r.done or len(r.generated) >= r.max_new_tokens:
                r.done = True
                continue
            t = int(next_tok[i, 0])
            r.generated.append(t)
            if t == ecfg.eos_id:
                r.done = True
        if all(r.done or len(r.generated) >= r.max_new_tokens
               for r in wave):
            break
    return steps


def _oracle(eng, prompts, max_new):
    reqs = [Request(rid=i, prompt=p, max_new_tokens=n)
            for i, (p, n) in enumerate(zip(prompts, max_new))]
    B = eng.ecfg.batch_size
    steps = sum(_host_synced_wave(eng, reqs[i:i + B])
                for i in range(0, len(reqs), B))
    return [r.generated for r in reqs], steps


def _eos_for(generated, row, first):
    """A token that ``row`` serves at a position of at least ``first`` (and
    past its prefill token), at no earlier position of its own and nowhere
    past the prefill token of another row: so it ends ``row`` alone."""
    others = {t for i, g in enumerate(generated) if i != row for t in g[1:]}
    for k in range(max(first, 1), len(generated[row]) - 1):
        t = generated[row][k]
        if t not in others and t not in generated[row][1:k]:
            return t
    raise AssertionError(f"no eos candidate in {generated}")


# (prompt lengths, max_new_tokens, the row an eos_id ends and from which
# position): rows of one wave asking for different lengths, a row asking
# for one token, no decode step at all, the cache's end, and an eos_id that
# ends a row while another goes on or ends the wave's last live row.
PARITY_CASES = {
    "mixed_lengths": ((8, 8, 8), (6, 3, 4), None),
    "one_token": ((8, 8), (1, 4), None),
    "only_the_prefill_token": ((8, 8), (1, 1), None),
    "cache_end": ((44, 40), (10, 10), None),
    "eos_ends_one_row": ((8, 8), (10, 10), (0, 2)),
    "eos_ends_the_last_row": ((8, 8), (3, 10), (1, 3)),
}


@pytest.mark.parametrize("case", list(PARITY_CASES))
def test_wave_serves_the_host_synced_loops_tokens(varied, monkeypatch, case):
    """Each request gets the tokens the host-synced greedy loop gives it,
    in one more decode step at most a wave, and in none more without an
    ``eos_id``; ``decode_overrun`` counts the extra steps."""
    cfg, eng = varied
    lens, max_new, eos_at = PARITY_CASES[case]
    rng = np.random.default_rng(len(case))
    prompts = [rng.integers(0, cfg.vocab_size, n, dtype=np.int32)
               for n in lens]
    if eos_at is not None:
        got, _ = _oracle(eng, prompts, (max(max_new),) * len(max_new))
        monkeypatch.setattr(eng.ecfg, "eos_id", _eos_for(got, *eos_at))
    want, want_steps = _oracle(eng, prompts, max_new)
    s0 = dict(eng.stats)
    reqs = [Request(rid=i, prompt=p, max_new_tokens=n)
            for i, (p, n) in enumerate(zip(prompts, max_new))]
    eng.generate(reqs)
    assert [r.generated for r in reqs] == want
    overrun = eng.stats["decode_overrun"] - s0["decode_overrun"]
    steps = eng.stats["decode_calls"] - s0["decode_calls"]
    assert steps == want_steps + overrun
    if eos_at is None:
        assert overrun == 0
    else:
        row = eos_at[0]
        assert want[row][-1] == eng.ecfg.eos_id
        assert len(want[row]) < max_new[row]
        # the wave ends a step late only when the eos ends its last row
        assert overrun == (1 if case == "eos_ends_the_last_row" else 0)
    assert eng.stats["tokens_generated"] - s0["tokens_generated"] == sum(
        len(g) for g in want)


def test_decode_steps_are_enqueued_before_the_last_tokens_are_read(engine,
                                                                   monkeypatch):
    """No host round trip between steps: every decode step's token argument
    is the device array the sampler gave, and step t+1 is enqueued before
    step t's tokens are read on the host."""
    cfg, eng = engine
    events, made = [], {}
    decode, sample, take = eng._decode, eng._sample, eng._take_tokens

    def sampler(logits):
        tok = sample(logits)
        made[id(tok)] = len(made)
        return tok

    def decoder(params, caches, tok, pos):
        assert isinstance(tok, jax.Array) and not isinstance(tok, np.ndarray)
        events.append(("dispatch", made[id(tok)] + 1))
        return decode(params, caches, tok, pos)

    def reader(wave, tok, **kw):
        events.append(("read", made[id(tok)]))
        return take(wave, tok, **kw)

    monkeypatch.setattr(eng, "_sample", sampler)
    monkeypatch.setattr(eng, "_decode", decoder)
    monkeypatch.setattr(eng, "_take_tokens", reader)
    rng = np.random.default_rng(4)
    reqs = [Request(rid=i, prompt=rng.integers(0, 256, 8, dtype=np.int32),
                    max_new_tokens=n) for i, n in enumerate((5, 3, 4))]
    eng.generate(reqs)
    assert [len(r.generated) for r in reqs] == [5, 3, 4]
    dispatched = [k for kind, k in events if kind == "dispatch"]
    assert len(dispatched) == 4 + 3       # two waves: 4 and 3 decode steps
    reads = {k: i for i, (kind, k) in enumerate(events) if kind == "read"}
    for i, (kind, k) in enumerate(events):
        if kind == "dispatch":
            # step k enqueued before the tokens of step k - 1 are read
            assert i < reads[k - 1], events


# -- the KV cache is updated in place -------------------------------------------

def test_decode_step_writes_only_the_new_position():
    """The lowered decode step (before XLA's passes, so the same on every
    backend) writes each attention cache one position at a time: no
    dynamic_update_slice takes a whole layer's (B, T, KV, D) cache as its
    update, as a scan that collects each layer's cache would."""
    import re

    import jax.numpy as jnp

    from repro.serve.serve_step import make_decode_step
    cfg = configs.get_config("phi3-medium-14b").reduced()
    B, T = 3, 24
    kv, hd = cfg.num_kv_heads, cfg.resolved_head_dim
    params = jax.eval_shape(lambda: tf.model_init(cfg, jax.random.key(0)))
    text = jax.jit(make_decode_step(cfg, None)).lower(
        params, tf.abstract_cache(cfg, B, T),
        jax.ShapeDtypeStruct((B, 1), jnp.int32),
        jax.ShapeDtypeStruct((), jnp.int32)).as_text()
    updates = re.findall(r"stablehlo\.dynamic_update_slice .*?: \(tensor<"
                         r"([0-9x]+)x\w+>, tensor<([0-9x]+)x\w+>", text)
    stacked = f"{cfg.num_superblocks}x{B}x{T}x{kv}x{hd}"
    into_cache = [upd for buf, upd in updates if buf == stacked]
    assert len(into_cache) == 2 * len(cfg.block_pattern)      # K and V
    assert set(into_cache) == {f"1x{B}x1x{kv}x{hd}"}
    whole_layer = {f"1x{B}x{T}x{kv}x{hd}", f"{B}x{T}x{kv}x{hd}"}
    assert not whole_layer & {upd for _, upd in updates}


def test_decode_step_donates_its_cache(engine):
    """The engine's jitted decode step aliases its cache argument to its
    output cache, and the cache handed to a step is consumed by it."""
    import jax.numpy as jnp
    cfg, eng = engine
    _, caches = eng._prefill(eng.params, jnp.zeros((2, 8), jnp.int32), None)
    cache_bytes = sum(a.nbytes for a in jax.tree_util.tree_leaves(caches))
    tok, pos = jnp.zeros((2, 1), jnp.int32), jnp.int32(8)
    mem = eng._decode.lower(eng.params, caches, tok,
                            pos).compile().memory_analysis()
    assert mem.alias_size_in_bytes == cache_bytes
    _, new = eng._decode(eng.params, caches, tok, pos)
    assert all(a.is_deleted() for a in jax.tree_util.tree_leaves(caches))
    assert not any(a.is_deleted() for a in jax.tree_util.tree_leaves(new))


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
