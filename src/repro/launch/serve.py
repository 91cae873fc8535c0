"""Serving launcher: batched generation with the wave-batched engine.

Example (CPU smoke):
  PYTHONPATH=src python -m repro.launch.serve --arch llama3.2-3b --smoke \
      --requests 8 --max-new 12 --energy-audit

Always-on sampled auditing against a fleet store (docs/serving.md):
  PYTHONPATH=src python -m repro.launch.serve --smoke \
      --audit-sample-every 8 --store file:///tmp/fleet
  PYTHONPATH=src python -m repro.cli fleet status --store file:///tmp/fleet
"""

from __future__ import annotations

import argparse
import json
import sys
import time

import jax
import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.configs.base import get_config
from repro.launch.mesh import make_host_mesh
from repro.models import transformer as tf
from repro.serve.engine import EngineConfig, Request, ServeEngine


def main(argv: list[str] | None = None) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--arch", default="gpt2-small")
    p.add_argument("--smoke", action="store_true")
    p.add_argument("--requests", type=int, default=8)
    p.add_argument("--prompt-len", type=int, default=16)
    p.add_argument("--max-new", type=int, default=8)
    p.add_argument("--batch-size", type=int, default=4)
    p.add_argument("--attn-impl", default="xla")
    p.add_argument("--energy-audit", action="store_true")
    p.add_argument("--audit-timeout", type=float, default=None,
                   help="wall-clock budget (s) for one energy audit before "
                        "the watchdog abandons it (default: engine config)")
    p.add_argument("--audit-breaker-threshold", type=int, default=3,
                   help="consecutive audit failures before the circuit "
                        "breaker disables further audits")
    # always-on sampled auditing (repro.audit)
    p.add_argument("--store", default=None,
                   help="fleet store URI (path, file:// or writable "
                        "http(s)://) for live-audit captures, goldens and "
                        "audit logs")
    p.add_argument("--audit-sample-every", type=int, default=0,
                   help="audit every Nth observation of each request class "
                        "(0 = sampled auditing off)")
    p.add_argument("--audit-slo-ms", type=float, default=None,
                   help="latency SLO (ms): sampled audits only run when the "
                        "observed step latency leaves headroom under it")
    p.add_argument("--engine-id", default=None,
                   help="stable engine identity in the fleet store "
                        "(default: <arch>-<pid>)")
    p.add_argument("--mutate-decode", default=None,
                   help="demo/chaos: audit the decode probe through a named "
                        "waste mutation (repro.testing.mutate) so drift "
                        "alarms fire against the healthy fleet golden")
    p.add_argument("--health-json", action="store_true",
                   help="print engine.health() as JSON after serving")
    args = p.parse_args(argv)

    cfg = get_config(args.arch)
    if args.smoke:
        cfg = cfg.reduced()
    assert cfg.is_causal, f"{args.arch} is encoder-only; nothing to decode"

    params = tf.model_init(cfg, jax.random.key(0))
    mesh = make_host_mesh() if len(jax.devices()) > 1 else None
    ecfg = EngineConfig(
        batch_size=args.batch_size,
        max_len=args.prompt_len + args.max_new + 8,
        attn_impl=args.attn_impl,
        audit_breaker_threshold=args.audit_breaker_threshold,
        store=args.store,
        audit_sample_every=args.audit_sample_every,
        audit_slo_ms=args.audit_slo_ms,
        engine_id=args.engine_id,
        audit_mutate_decode=args.mutate_decode)
    if args.audit_timeout is not None:
        ecfg.audit_timeout_s = args.audit_timeout
    engine = ServeEngine(cfg, params, mesh=mesh, ecfg=ecfg)

    rng = np.random.default_rng(0)
    reqs = [Request(rid=i,
                    prompt=rng.integers(0, cfg.vocab_size,
                                        size=args.prompt_len).astype(np.int32),
                    max_new_tokens=args.max_new)
            for i in range(args.requests)]
    t0 = time.time()
    engine.generate(reqs)
    dt = time.time() - t0
    toks = engine.stats["tokens_generated"]
    print(f"served {len(reqs)} requests, {toks} tokens in {dt:.2f}s "
          f"({toks/dt:.1f} tok/s)")
    print("stats:", {k: (round(v, 3) if isinstance(v, float) else v)
                     for k, v in engine.stats.items()})
    s = engine.stats
    print(f"audit-health: calls={s['audit_calls']} ok={s['audit_ok']} "
          f"failures={s['audit_failures']} timeouts={s['audit_timeouts']} "
          f"skipped={s['audit_skipped']} sampled={s['audit_sampled']} "
          f"alarms={s['audit_alarms']} "
          f"breaker_open={s['audit_breaker_open']}")
    if engine.auditor is not None:
        a = engine.auditor.summary()
        print(f"live audit: {len(a['classes'])} request classes "
              f"({', '.join(a['classes'])}), {a['sampled']}/{a['observed']} "
              f"sampled, {a['alarms']} drift alarms, "
              f"{a['flush_failures']} flush failures")
        for alarm in engine.auditor.alarms:
            print(f"  DRIFT {alarm.class_key}: {alarm.energy_delta:+.1%} "
                  f"kind={alarm.diagnosis_kind} "
                  + ("[degraded] " if alarm.degraded else "")
                  + f"- {alarm.detail}")
    for r in reqs[:4]:
        print(f"  req {r.rid}: {r.generated}")
    if args.health_json:
        print(json.dumps(engine.health(), indent=2, sort_keys=True))

    if args.energy_audit:
        # error-bounded audit: a broken/hung profiler reports its failure
        # and leaves the serving results above intact, but the audit that
        # was asked for did not happen, so the run fails
        report = engine.audit(prompt_len=args.prompt_len,
                              timeout_s=args.audit_timeout)
        if report is None:
            print("energy audit unavailable: "
                  f"{engine.stats.get('audit_last_error', 'breaker open')} "
                  f"(failures={engine.stats['audit_failures']}, "
                  f"timeouts={engine.stats['audit_timeouts']}, "
                  f"breaker_open={engine.stats['audit_breaker_open']})",
                  file=sys.stderr)
            return 1
        print(report.render())
    return 0


if __name__ == "__main__":
    enable_compile_cache()
    sys.exit(main())
