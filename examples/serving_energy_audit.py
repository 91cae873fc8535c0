"""Serve a small model with batched requests, then run the Magneton energy
audit on the serving stack — the paper's profiler as a deployment feature.

  PYTHONPATH=src python examples/serving_energy_audit.py
"""

import time

import jax
import numpy as np

from repro import configs
from repro.models import transformer as tf
from repro.serve.engine import EngineConfig, Request, ServeEngine


def main():
    cfg = configs.get_config("llama3.2-3b").reduced()
    params = tf.model_init(cfg, jax.random.key(0))
    engine = ServeEngine(cfg, params,
                         ecfg=EngineConfig(batch_size=4, max_len=64))

    rng = np.random.default_rng(0)
    requests = [Request(rid=i,
                        prompt=rng.integers(0, cfg.vocab_size, size=16,
                                            dtype=np.int32),
                        max_new_tokens=8)
                for i in range(8)]

    t0 = time.time()
    engine.generate(requests)
    dt = time.time() - t0
    toks = engine.stats["tokens_generated"]
    print(f"served {len(requests)} requests / {toks} tokens "
          f"in {dt:.1f}s ({toks/dt:.1f} tok/s)")

    print("\n=== Magneton audit of the prompt pass's LM head ===")
    report = engine.energy_report(prompt_len=16)
    print(report.render())


if __name__ == "__main__":
    main()
