#!/usr/bin/env python3
"""Chip smoke test: serve gpt2-small on a TPU with its energy audit.

    python3 chip_smoke.py                 # one chip
    python3 chip_smoke.py --four-chips    # the sharded serve step, 4 chips
    JAX_PLATFORMS=cpu python3 chip_smoke.py --rehearse   # CPU, reduced model

One chip: gpt2-small at its published widths (12 x 768, vocab 50257, random
weights from a seed) is served through ``ServeEngine`` exactly as
``repro.launch.serve`` serves it, with sampled live audits writing to a store
inside the checkout.  The served logits are checked against a float32
forward pass of the same weights, and the engine's prompt pass is audited
against its all-position LM-head twin under the HLO cost backend for this
device, with degradation off: the audit must name the LM head as the waste.

Four chips: the same requests are served over ``make_host_mesh()`` with
parameters placed by ``model_param_shardings`` and sampled audits on, and
compared with a one-device engine in the same process.

Any failed check exits non-zero.  Without a TPU the script exits non-zero
before any other work, unless ``--rehearse`` is given; the rehearsal runs the
same phases on ``cfg.reduced()``.  The last line of standard output is one
JSON object, printed only when every check passed.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import shutil
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
STORE_DIR = ROOT / ".smoke_store"

SEED = 0
REQUESTS = 8
PROMPT_LEN = 16
MAX_NEW = 8
BATCH = 4
AUDIT_EVERY = 2             # sample every 2nd step of each request class
AUDIT_BUDGET_S = 900.0      # a cold audit compiles every operator it replays
# The energy report's prompt: 4 x 128 positions.  gpt2-small's LM head
# streams its 77 MB table in about 94 us on a v5e; the all-position twin's
# extra MXU work outlasts that only past about 240 positions, and below it
# costs energy but no time, which the report calls a trade-off.
AUDIT_PROMPT_LEN = 128
# Served bf16 logits against the float32 reference, and sharded against
# one-device bf16 logits: |a - b| <= ATOL + RTOL * |b|.  bf16 keeps 8
# significant bits (relative step 2^-8); twelve layers of bf16 activations
# compound that to a few hundredths on logits of order one.
LOGIT_ATOL = 0.08
LOGIT_RTOL = 0.02


class SmokeFailure(Exception):
    pass


def _check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def _parse(argv):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--rehearse", action="store_true",
                   help="run on whatever device JAX finds (the CPU) at "
                        "cfg.reduced() size")
    p.add_argument("--four-chips", action="store_true",
                   help="run only the sharded serve step over four devices "
                        "and its one-device comparison")
    return p.parse_args(argv)


def main(argv: list[str] | None = None) -> int:
    args = _parse(argv)
    # the TPU runtime's logs stay in the checkout, not in /tmp
    os.environ.setdefault("TPU_LOG_DIR", str(ROOT / ".tpu_logs"))
    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu" and not args.rehearse:
        print(f"chip_smoke: no TPU: JAX found platform {dev.platform!r} "
              f"({dev.device_kind}); --rehearse runs a reduced model on it",
              file=sys.stderr)
        return 2
    need = 4 if args.four_chips else 1
    if len(devices) < need:
        print(f"chip_smoke: need {need} devices, found {len(devices)}",
              file=sys.stderr)
        return 2

    sys.path.insert(0, str(ROOT / "src"))
    from repro.compile_cache import enable_compile_cache
    print(f"device: {dev.platform} {dev.device_kind} x{len(devices)}")
    print(f"compile cache: {enable_compile_cache()}")
    compile_s = _count_compile_seconds()
    try:
        if args.four_chips:
            _four_chips(args.rehearse)
        else:
            _one_chip(args.rehearse, compile_s)
    except SmokeFailure as e:
        print(f"chip_smoke FAILED: {e}", file=sys.stderr)
        return 1
    for d in devices[:need]:
        stats = d.memory_stats() or {}
        print(f"peak_bytes_in_use[{d.id}]: "
              f"{stats.get('peak_bytes_in_use', 'not reported')}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


def _count_compile_seconds() -> list[float]:
    """A one-element list that accumulates XLA backend compile seconds."""
    import jax
    total = [0.0]

    def listen(event: str, secs: float, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            total[0] += secs
    jax.monitoring.register_event_duration_secs_listener(listen)
    return total


# ---------------------------------------------------------------------------
# shared pieces
# ---------------------------------------------------------------------------

def _model(rehearse: bool):
    import jax
    from repro.configs.base import get_config
    from repro.models import transformer as tf
    cfg = get_config("gpt2-small")
    if rehearse:
        cfg = cfg.reduced()
    return cfg, tf.model_init(cfg, jax.random.key(SEED))


def _requests(cfg):
    import numpy as np
    from repro.serve.engine import Request
    rng = np.random.default_rng(SEED)
    return [Request(rid=i, prompt=rng.integers(0, cfg.vocab_size,
                                               size=PROMPT_LEN
                                               ).astype(np.int32),
                    max_new_tokens=MAX_NEW)
            for i in range(REQUESTS)]


def _engine_config(**kw):
    from repro.serve.engine import EngineConfig
    return EngineConfig(batch_size=BATCH, max_len=PROMPT_LEN + MAX_NEW + 8,
                        **kw)


def _serve(engine, reqs, label: str):
    t0 = time.perf_counter()
    engine.generate(reqs)
    wall = time.perf_counter() - t0
    toks = engine.stats["tokens_generated"]
    print(f"[{label}] served {len(reqs)} requests, {toks} tokens in "
          f"{wall:.3f} s (host clock, compile and sampled audits included)")
    _check(toks == REQUESTS * MAX_NEW,
           f"{label}: served {toks} tokens, expected {REQUESTS * MAX_NEW}")
    for r in reqs:
        _check(len(r.generated) == MAX_NEW
               and all(0 <= t < engine.cfg.vocab_size for t in r.generated),
               f"{label}: request {r.rid} got tokens {r.generated}")
    return wall


def _teacher_forced_logits(engine, reqs):
    """Logits of every served step, (R, MAX_NEW, V) float32: the engine's
    own jitted prefill and decode steps, fed each request's served tokens."""
    import jax.numpy as jnp
    import numpy as np
    out = []
    for i in range(0, len(reqs), BATCH):
        wave = reqs[i:i + BATCH]
        prompts = jnp.asarray(np.stack([r.prompt for r in wave]))
        fed = np.asarray([r.generated for r in wave], np.int32)
        logits, caches = engine._prefill(engine.params, prompts, None)
        steps = [logits[:, -1]]
        for j in range(MAX_NEW - 1):
            logits, caches = engine._decode(
                engine.params, caches, jnp.asarray(fed[:, j:j + 1]),
                jnp.int32(PROMPT_LEN + j))
            steps.append(logits[:, -1])
        out.append(np.stack([np.asarray(s, np.float32) for s in steps], 1))
    return np.concatenate(out)


def _compare_logits(got, want, reqs, label: str):
    """``got`` within the bf16 tolerance of ``want``, and every served token
    the argmax of ``want`` wherever its top two are further apart than the
    tolerance allows either to move (a near tie may go either way).
    Returns the (R, MAX_NEW) mask of those near ties."""
    import numpy as np
    _check(got.shape == want.shape, f"{label}: shapes {got.shape} vs "
                                    f"{want.shape}")
    _check(bool(np.isfinite(got).all()), f"{label}: non-finite logits")
    err = np.abs(got - want)
    bound = LOGIT_ATOL + LOGIT_RTOL * np.abs(want)
    print(f"[{label}] max |logit diff| {float(err.max()):.5f}, "
          f"max diff/bound {float((err / bound).max()):.3f}")
    _check(bool((err <= bound).all()),
           f"{label}: logits differ by up to {float(err.max()):.5f} "
           f"(bound {LOGIT_ATOL} + {LOGIT_RTOL}*|ref|)")
    top2 = np.sort(want, axis=-1)[..., -2:]
    margin = top2[..., 1] - top2[..., 0]
    slack = 2 * (LOGIT_ATOL + LOGIT_RTOL * np.abs(top2[..., 1]))
    served = np.asarray([r.generated for r in reqs])
    decided = margin > slack
    wrong = decided & (served != want.argmax(-1))
    print(f"[{label}] tokens decided by the reference: "
          f"{int(decided.sum())}/{decided.size}, near ties "
          f"{int((~decided).sum())}, disagreements {int(wrong.sum())}")
    _check(not wrong.any(), f"{label}: {int(wrong.sum())} served tokens "
                            "disagree with the reference")
    return ~decided


def _check_sampled_audits(engine) -> None:
    s = engine.stats
    aud = engine.auditor
    summary = aud.summary()
    kinds: dict[str, int] = {}
    for ev in aud.log:
        kinds[ev.kind] = kinds.get(ev.kind, 0) + 1
    print(f"audits: calls={s['audit_calls']} ok={s['audit_ok']} "
          f"sampled={s['audit_sampled']} failures={s['audit_failures']} "
          f"timeouts={s['audit_timeouts']} skipped={s['audit_skipped']} "
          f"degraded={s['audit_degraded']} alarms={s['audit_alarms']} "
          f"events={kinds} classes={summary['classes']}")
    _check(s["audit_failures"] == 0 and s["audit_timeouts"] == 0
           and s["audit_skipped"] == 0,
           f"sampled audits failed: {s.get('audit_last_error')}")
    _check(s["audit_sampled"] > 0 and s["audit_ok"] == s["audit_calls"],
           "no sampled audit ran to completion")
    errors = [ev.detail for ev in aud.log if ev.kind == "error"]
    _check(not errors, f"auditor error events: {errors}")
    degraded = [ev.class_key for ev in aud.log if ev.degraded]
    _check(not degraded and s["audit_degraded"] == 0,
           f"degraded audits: {degraded}")
    _check(aud.last_error is None and aud.flush_failures == 0,
           f"auditor: {aud.last_error}")
    _check(s["audit_alarms"] == 0, f"drift alarms on an unchanged engine: "
                                   f"{[a.detail for a in aud.alarms]}")
    checked = {ev.class_key.split("/")[0] for ev in aud.log
               if ev.kind == "check"}
    _check(checked == {"prefill", "decode"},
           f"full audit checks ran for {sorted(checked)}, want prefill and "
           "decode")
    store = aud.session.store
    for key in store.keys():
        meta = store.backend.read_manifest(key).get("meta", {})
        for field in ("degraded", "store_warnings"):
            _check(not meta.get(field), f"stored capture {key}: {field} "
                                        f"{meta.get(field)}")


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------

def _one_chip(rehearse: bool, compile_s: list[float]) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core.energy import HloCostBackend
    from repro.core.session import Session
    from repro.hw.specs import local_device_spec
    from repro.models import transformer as tf
    from repro.serve.engine import ServeEngine

    cfg, params = _model(rehearse)
    print(f"model: {cfg.name} layers={cfg.num_layers} d_model={cfg.d_model} "
          f"vocab={cfg.vocab_size} dtype={cfg.dtype}")
    shutil.rmtree(STORE_DIR, ignore_errors=True)
    engine = ServeEngine(cfg, params, ecfg=_engine_config(
        store=str(STORE_DIR), engine_id="chip-smoke",
        audit_sample_every=AUDIT_EVERY, audit_timeout_s=AUDIT_BUDGET_S))
    reqs = _requests(cfg)
    c0 = compile_s[0]
    wall = _serve(engine, reqs, "serve")
    print(f"[serve] backend compile {compile_s[0] - c0:.3f} s of the "
          f"{wall:.3f} s")
    _check_sampled_audits(engine)

    # served logits against a float32 forward pass over the same tokens
    served = _teacher_forced_logits(engine, reqs)
    cfg32 = dataclasses.replace(cfg, dtype="float32")
    params32 = jax.tree_util.tree_map(
        lambda a: a.astype(jnp.float32)
        if jnp.issubdtype(a.dtype, jnp.floating) else a, params)
    seqs = np.asarray([np.concatenate([r.prompt, r.generated[:-1]])
                       for r in reqs], np.int32)
    with jax.default_matmul_precision("highest"):
        ref, _ = jax.jit(lambda p, t: tf.forward(cfg32, p, t))(
            params32, jnp.asarray(seqs))
    ref = np.asarray(ref, np.float32)[:, PROMPT_LEN - 1:]
    _compare_logits(served, ref, reqs, "served vs float32 forward")

    # the engine's prompt pass against its all-position LM-head twin
    spec = local_device_spec()
    captured = []

    class RecordingSession(Session):
        def capture(self, *a, **kw):
            captured.append(super().capture(*a, **kw))
            return captured[-1]

    session = RecordingSession(backend=HloCostBackend(spec),
                               allow_degraded=False)
    c0, t0 = compile_s[0], time.perf_counter()
    report = engine.energy_report(prompt_len=AUDIT_PROMPT_LEN,
                                  session=session)
    wall = time.perf_counter() - t0
    print(f"[energy report] {wall:.3f} s, backend compile "
          f"{compile_s[0] - c0:.3f} s; priced by "
          f"{report.meta.get('energy_model')} (spec {spec.name})")
    for art in captured:
        att = art.profile.hlo.attribution
        print(f"[energy report] {art.name}: nodes={len(art.graph.nodes)} "
              f"energy={art.profile.total_energy_j:.6e} J; HLO instructions "
              f"{att['instructions']}: direct={att['direct']} "
              f"grouped={att['grouped']} residual={att['residual_instrs']} "
              f"(inherited {att.get('inherited', 0)}); "
              f"{art.profile.hlo.attribution_summary()}")
    print(report.render())
    for field in ("degraded", "store_warnings"):
        _check(not report.meta.get(field),
               f"energy report {field}: {report.meta.get(field)}")
    waste = [f for f in report.waste_findings if f.wasteful_side == "A"]
    _check(bool(waste), "the energy report found no waste region on the "
                        "all-position side (A)")
    heads = _lm_head_dots(captured[0].graph, cfg.vocab_size)
    covering = [f for f in waste if heads & set(f.nodes_a)]
    _check(bool(covering),
           f"no side-A waste region holds the LM-head dot (nodes "
           f"{sorted(heads)}); the waste regions hold "
           f"{[f.nodes_a for f in waste]}")
    others = [f for f in report.waste_findings if f not in covering]
    _check(not others, f"waste regions away from the LM head: "
                       f"{[(f.wasteful_side, f.nodes_a) for f in others]}")


def _lm_head_dots(graph, vocab_size: int) -> set[int]:
    """Nodes of ``graph`` that are dots with a vocab-wide output."""
    return {nd.idx for nd in graph.nodes
            if nd.primitive == "dot_general"
            and graph.tensors[nd.outvars[0]].shape[-1:] == (vocab_size,)}


def _four_chips(rehearse: bool) -> None:
    from repro.launch.mesh import make_host_mesh
    from repro.serve.engine import ServeEngine

    cfg, params = _model(rehearse)
    mesh = make_host_mesh()
    print(f"model: {cfg.name} layers={cfg.num_layers} d_model={cfg.d_model} "
          f"vocab={cfg.vocab_size}; mesh {dict(mesh.shape)}")
    single = ServeEngine(cfg, params, ecfg=_engine_config())
    ref_reqs = _requests(cfg)
    _serve(single, ref_reqs, "one device")

    shutil.rmtree(STORE_DIR, ignore_errors=True)
    sharded = ServeEngine(cfg, params, mesh=mesh, ecfg=_engine_config(
        store=str(STORE_DIR), engine_id="chip-smoke-4",
        audit_sample_every=AUDIT_EVERY, audit_timeout_s=AUDIT_BUDGET_S))
    reqs = _requests(cfg)
    _serve(sharded, reqs, "sharded")
    _check_sampled_audits(sharded)

    # both engines fed the one-device tokens: logits agree within the bf16
    # bound, and the sharded engine's own tokens follow the one-device ones
    # until a near tie (after which the two contexts differ)
    tie = _compare_logits(_teacher_forced_logits(sharded, ref_reqs),
                          _teacher_forced_logits(single, ref_reqs),
                          ref_reqs, "sharded vs one device")
    same = 0
    for i, (a, b) in enumerate(zip(reqs, ref_reqs)):
        diff = [j for j in range(MAX_NEW) if a.generated[j] != b.generated[j]]
        if not diff:
            same += 1
            continue
        _check(bool(tie[i, diff[0]]),
               f"request {i}: sharded tokens {a.generated} leave the "
               f"one-device tokens {b.generated} at step {diff[0]}, which "
               "is not a near tie")
    print(f"[sharded vs one device] identical greedy tokens for {same}/"
          f"{len(reqs)} requests; the rest diverge at a near tie")


if __name__ == "__main__":
    sys.exit(main())
