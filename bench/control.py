#!/usr/bin/env python3
"""Read the numbers that set a cell's correctness limits, on the chip.

    python3 bench/control.py --workload <name> --seeds 1,2,3 --seconds 5
    python3 bench/control.py --workload <name> --traffic <mix> --seeds 1,2,3

For each seed, in one process: one run of the cell as ``bench/run.py``
makes it, with a short window at the cell's own load, gives the program's
reading of every compared number; then the control, the cell's reference
computed in float8 and put in the program's place on the same inputs,
gives its reading of the numbers the reference is compared by.  Both are
judged against the cell's limits by the comparison that decides
``correct`` (``run.judge``): the control has to come out not correct.
``--traffic`` serves another mix in the cell's place, as for the readings
of a program fault.  Prints one JSON line per seed and a summary line.
The benchmark's own runs never run the control.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))
import run as bench_run  # noqa: E402


def read_seed(run: bench_run.Run) -> dict:
    """The program's and the control's readings of one run, each judged.
    The run's weights are freed before it returns, so the next seed's fit."""
    result = bench_run.run_cell(run)
    try:
        control, control_correct = bench_run.judge(
            run.outcome["control"](), run.limits)
    finally:
        run.outcome = None            # its closure holds the weights
        gc.collect()
    return {"seed": run.seed, "correct": result["correct"],
            "program": {k: c["value"] for k, c in result["checks"].items()},
            "window_compiles": result["window_compiles"],
            "control": {"correct": control_correct,
                        "checks": {k: c["value"]
                                   for k, c in control.items()}}}


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True,
                   help="comma-separated seeds, one run each")
    p.add_argument("--seconds", type=float, default=5.0)
    p.add_argument("--traffic", help="a mix to serve in the cell's place")
    p.add_argument("--rehearse", action="store_true")
    args = p.parse_args(argv)
    logs = bench_run.OUT / "tpu_logs"
    logs.mkdir(parents=True, exist_ok=True)
    os.environ.setdefault("TPU_LOG_DIR", str(logs))
    import jax
    if jax.devices()[0].platform != "tpu" and not args.rehearse:
        print("control: no TPU", file=sys.stderr)
        return 2
    bench_run._program_on_path()
    if not args.rehearse:
        bench_run.enable_cache()
    rows = []
    for seed in (int(s) for s in args.seeds.split(",")):
        run = bench_run.make_run(args.workload, seed, args.seconds, False,
                                 args.rehearse, args.traffic)
        rows.append(read_seed(run))
        print(json.dumps(rows[-1]), flush=True)
    keys = rows[0]["program"]
    print(json.dumps({
        "workload": args.workload, "traffic": args.traffic,
        "seeds": len(rows),
        "program_max": {k: max(r["program"][k] for r in rows) for k in keys},
        "program_correct": all(r["correct"] for r in rows),
        "control_min": {k: min(r["control"]["checks"][k] for r in rows)
                        for k in rows[0]["control"]["checks"]},
        "control_correct": any(r["control"]["correct"] for r in rows)}),
        flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
