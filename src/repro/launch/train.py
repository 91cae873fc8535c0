"""Training launcher.

Drives train/loop.py with an arch + shape from the registry.  On the CPU
container, ``--smoke`` selects the reduced config and a small batch so the
loop actually steps; on real hardware the full config trains on the
production mesh.  ``--energy-audit`` runs the Magneton differential debugger
over the model's own forward pass before training starts — the paper's
profiler wired in as a launcher feature.

Examples:
  PYTHONPATH=src python -m repro.launch.train --arch llama3.2-3b --smoke \
      --steps 20
  PYTHONPATH=src python -m repro.launch.train --arch qwen3-4b --smoke \
      --energy-audit
"""

from __future__ import annotations

import argparse
import json

import jax

from repro.compile_cache import enable_compile_cache
from repro.configs.base import SHAPES, ShapeConfig, get_config
from repro.launch.mesh import make_host_mesh
from repro.train.loop import LoopConfig, run_training
from repro.train.optimizer import OptimizerConfig
from repro.train.train_step import TrainConfig
from repro.train.checkpoint import PreemptionGuard


def energy_audit(cfg, *, store: str | None = None) -> None:
    """Differential audit: the model's unfused GELU/attention twins.

    Session-based; pass ``store`` (CLI: ``--audit-store DIR``) to make the
    captures content-addressed, so re-running the audit on later launches
    hits the store instead of re-executing the instrumented pipeline.
    """
    from repro.core.session import Session
    from repro.zoo.cases import get_case
    print("=== Magneton energy audit (launcher feature) ===")
    session = Session(store=store)
    for cid in ("n1-gelu-backend", "c13-ce-onehot", "c4-gqa-repeat"):
        c = get_case(cid)
        art_cur = session.capture(c.inefficient, c.make_args(),
                                  name=c.id + "-current", config=c.config_a)
        art_fix = session.capture(c.efficient, c.make_args(),
                                  name=c.id + "-fix", config=c.config_b)
        print(session.compare(art_cur, art_fix,
                              output_rtol=c.output_rtol).render())


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--arch", required=True)
    p.add_argument("--shape", default="train_4k")
    p.add_argument("--steps", type=int, default=50)
    p.add_argument("--smoke", action="store_true",
                   help="reduced config + tiny batch (CPU containers)")
    p.add_argument("--batch", type=int, default=None)
    p.add_argument("--seq-len", type=int, default=None)
    p.add_argument("--microbatches", type=int, default=1)
    p.add_argument("--checkpoint-dir", default="/tmp/repro_ckpt")
    p.add_argument("--checkpoint-every", type=int, default=25)
    p.add_argument("--energy-audit", action="store_true")
    p.add_argument("--audit-store", default=None,
                   help="artifact store dir for the energy audit (cache "
                        "hits across launches)")
    p.add_argument("--compress-grads", action="store_true")
    p.add_argument("--attn-impl", default="xla", choices=("xla", "pallas"))
    p.add_argument("--metrics-out", default=None)
    args = p.parse_args()

    cfg = get_config(args.arch)
    if args.smoke:
        cfg = cfg.reduced()
    shape = SHAPES[args.shape]
    if args.smoke:
        shape = ShapeConfig("smoke", seq_len=args.seq_len or 64,
                            global_batch=args.batch or 8, kind="train")
    elif args.batch or args.seq_len:
        shape = ShapeConfig(shape.name, seq_len=args.seq_len or shape.seq_len,
                            global_batch=args.batch or shape.global_batch,
                            kind="train")

    if args.energy_audit:
        energy_audit(cfg, store=args.audit_store)

    mesh = make_host_mesh() if len(jax.devices()) > 1 else None
    guard = PreemptionGuard()
    result = run_training(
        cfg, shape, mesh=mesh,
        opt_cfg=OptimizerConfig(total_steps=args.steps,
                                warmup_steps=max(2, args.steps // 10),
                                compress_grads=args.compress_grads),
        tcfg=TrainConfig(microbatches=args.microbatches,
                         attn_impl=args.attn_impl),
        loop=LoopConfig(num_steps=args.steps,
                        checkpoint_every=args.checkpoint_every,
                        checkpoint_dir=args.checkpoint_dir),
        guard=guard)
    print(f"finished at step {result['final_step']}  "
          f"final loss {result['history'][-1]['loss']:.4f}  "
          f"early-exit={result['exited_early']}")
    if args.metrics_out:
        with open(args.metrics_out, "w") as f:
            json.dump({"history": result["history"],
                       "straggler_events": result["straggler_events"]}, f)


if __name__ == "__main__":
    enable_compile_cache()
    main()
