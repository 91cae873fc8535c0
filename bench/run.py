#!/usr/bin/env python3
"""Run one benchmark cell once and print its result as one JSON line.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration and its traffic mix are found by name in
``BENCHMARK.json``: ``bench/configs/<config>.json`` holds the model's
published keys and names its ``family``, the module
``bench/configs/<family>.py`` that reads its sizes, lays out its weights,
sets the program's configuration, counts its FLOPs and holds its plain
reference; ``bench/traffic/<traffic>.json`` holds the mix, whose ``runner``
names the module under ``bench/runners/`` that serves it;
``bench/limits/<workload>.json`` the limit of each number that decides
``correct``, and ``bench/metrics/<metric>.py`` the reader of each per-layer
metric.  Adding a cell, a mix, a configuration, a model family or a metric
adds files; it edits none.

Without a TPU, or with fewer chips than the cell asks for, the command exits
2 and prints no result.  ``--rehearse`` runs on whatever JAX finds at the
configuration's ``rehearse`` size and prints no metric: it checks the paths,
not the speed.

With ``--trace 0`` the result's metrics are the cell's end-to-end metrics;
with ``--trace 1`` the window runs under the profiler and the metrics are
the cell's per-layer metrics, with the device's busy time and a breakdown.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse                    # noqa: E402
import contextlib                  # noqa: E402
import dataclasses                 # noqa: E402
import functools                   # noqa: E402
import gc                          # noqa: E402
import importlib.util              # noqa: E402
import json                        # noqa: E402
import os                          # noqa: E402
import shutil                      # noqa: E402
import sys                         # noqa: E402
from pathlib import Path           # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
OUT = BENCH / "out"
# A traced run's window is shorter: on the chip, the trace of a whole 51 s
# serving window lost its host annotations, and 20 s ones kept them.
TRACE_SECONDS = 15.0


def load_module(path: Path):
    """Import a file of the benchmark by path (names may hold dots)."""
    spec = importlib.util.spec_from_file_location(
        "bench_" + path.stem.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod       # dataclasses look their module up
    spec.loader.exec_module(mod)
    return mod


def _json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass
class Run:
    """One run of one cell: what its runner needs and what it reports."""

    spec: dict
    cell: dict
    config: dict
    mix: dict
    limits: dict
    seed: int
    seconds: float
    trace: bool
    rehearse: bool
    setup_s: float | None = None
    window_s: float | None = None
    window_compiles: int = 0
    memory_peak_bytes: int | None = None
    compiles: int = 0                 # backend compilations so far
    _mods: dict = dataclasses.field(default_factory=dict)
    outcome: dict | None = None       # the runner's own return value

    def mod(self, name: str):
        """The benchmark's own module ``bench/<name>.py``, loaded once."""
        if name not in self._mods:
            self._mods[name] = load_module(BENCH / f"{name}.py")
        return self._mods[name]

    @property
    def name(self) -> str:
        return self.cell["name"]

    @functools.cached_property
    def family(self):
        """The configuration's model family, ``bench/configs/<family>.py``:
        its sizes, weight layout, program settings, FLOPs and reference."""
        return load_module(BENCH / "configs" / f"{self.config['family']}.py")

    @property
    def shape(self) -> dict:
        """The configuration's sizes, cut to the rehearsal size if asked."""
        return self.family.shape_of(self.config, self.rehearse)

    @property
    def run_dir(self) -> Path:
        return OUT / "runs" / self.name

    def model_config(self):
        """The program's ModelConfig of its architecture, at this
        configuration's sizes."""
        from repro.configs.base import get_config
        return self.family.program_config(
            get_config(self.config["program"]["arch"]), self.shape)

    def make_params(self, cfg):
        """Weights from the seed, checked against the program's layout."""
        from repro.models import transformer as tf
        weights, layout = self.mod("weights"), self.family.layout(self.shape)
        weights.check_layout(layout, tf.model_abstract_params(cfg))
        return weights.make(layout, self.seed,
                            self.config["initializer_range"])

    def reference(self, mode: str = "f32"):
        return self.family.Reference(self.shape, mode)

    def count_compiles(self) -> None:
        import jax

        def listen(event: str, secs: float, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                self.compiles += 1
        jax.monitoring.register_event_duration_secs_listener(listen)

    @property
    def window_seconds(self) -> float:
        """How long the runner serves: ``--seconds``, or in a traced run
        at most ``TRACE_SECONDS``, all of it under the profiler."""
        return min(self.seconds, TRACE_SECONDS) if self.trace \
            else self.seconds

    @contextlib.contextmanager
    def window(self):
        """The measured window: set-up ends where it starts; under
        ``--trace 1`` the profiler records all of it."""
        import jax
        self.setup_s = time.perf_counter() - T_START
        c0 = self.compiles
        trace_dir = self.run_dir / "trace"
        if self.trace:
            shutil.rmtree(trace_dir, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1      # annotations and dispatch only
            jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
        t0 = time.perf_counter()
        try:
            with jax.profiler.TraceAnnotation("bench.window"):
                yield
        finally:
            self.window_s = time.perf_counter() - t0
            if self.trace:
                jax.profiler.stop_trace()
            self.window_compiles = self.compiles - c0

    def read_memory(self) -> None:
        """Peak device memory so far, before any reference runs."""
        import jax
        peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
                 for d in jax.devices()[:self.cell["chips"]]]
        peaks = [p for p in peaks if p is not None]
        self.memory_peak_bytes = max(peaks) if peaks else None
        gc.collect()


def find_cell(spec: dict, name: str) -> dict:
    for cell in spec["workloads"]:
        if cell["name"] == name:
            return cell
    raise SystemExit(f"unknown workload {name!r}; have "
                     f"{[c['name'] for c in spec['workloads']]}")


def make_run(workload: str, seed: int, seconds: float, trace: bool,
             rehearse: bool, traffic: str | None = None) -> Run:
    """One run of ``workload``; ``traffic`` puts another mix in place of
    the cell's own (for readings, never in a benchmark run)."""
    spec = _json(ROOT / "BENCHMARK.json")
    cell = find_cell(spec, workload)
    return Run(spec=spec, cell=cell,
               config=_json(BENCH / "configs" / f"{cell['config']}.json"),
               mix=_json(BENCH / "traffic" /
                         f"{traffic or cell['traffic']}.json"),
               limits=_json(BENCH / "limits" / f"{cell['name']}.json"),
               seed=seed, seconds=seconds, trace=trace, rehearse=rehearse)


def _reports(metric: dict, cell: str, spec: dict) -> bool:
    """Whether ``cell`` reports ``metric``."""
    if "workloads" in metric:
        return cell in metric["workloads"]
    moves = metric.get("moves")
    if moves is None:
        return True
    e2e = next(m for m in spec["end_to_end"] if m["name"] == moves)
    return "workloads" not in e2e or cell in e2e["workloads"]


def enable_cache() -> None:
    """The program's persistent compile cache, for every program however
    fast it compiles, so that only a cell's first run in a checkout
    compiles."""
    import jax
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def _program_on_path() -> None:
    src = str(ROOT / "src")
    if src not in sys.path:
        sys.path.insert(0, src)


def judge(readings: dict, limits: dict) -> tuple[dict, bool]:
    """Each compared number beside its limit, and whether every one is
    within it: the comparison that decides ``correct``."""
    checks = {k: {"value": v, "limit": limits[k]}
              for k, v in readings.items()}
    return checks, all(c["value"] <= c["limit"] for c in checks.values())


def run_cell(run: Run) -> dict:
    """Set up, measure, check; return the result object (not printed)."""
    _program_on_path()
    run.count_compiles()
    shutil.rmtree(run.run_dir, ignore_errors=True)
    run.run_dir.mkdir(parents=True, exist_ok=True)
    runner = load_module(BENCH / "runners" / f"{run.mix['runner']}.py")
    out = runner.run(run)
    run.outcome = out

    import jax
    devs = jax.devices()
    spec, name = run.spec, run.name
    peaks = None if run.rehearse else \
        _json(BENCH / "peaks.json")[devs[0].device_kind]
    data = dict(out["data"], window_s=run.window_s, shape=run.shape,
                peaks=peaks)
    metrics: dict = {}
    result: dict = {}
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs),
              "memory_peak_bytes": run.memory_peak_bytes}
    if run.trace:
        tr = run.mod("trace_reduce")
        summary = tr.summarize(tr.load(tr.find_profile(
            str(run.run_dir / "trace"))))
        data["trace"] = summary
        device.update(busy_s=summary["busy_s"], window_s=summary["window_s"])
        result["breakdown"] = {"device_ops": summary["device_ops"],
                               "idle_gaps": summary["idle_gaps"]}
    if not run.rehearse:
        if run.trace:
            for m in spec["per_layer"]:
                if _reports(m, name, spec):
                    reader = load_module(BENCH / "metrics" /
                                         f"{m['name']}.py")
                    value = reader.read(data)
                    if value is not None:
                        metrics[m["name"]] = {"value": value,
                                              "unit": m["unit"]}
        else:
            e2e = dict(out["e2e"], setup_s=run.setup_s)
            for m in spec["end_to_end"]:
                if _reports(m, name, spec):
                    metrics[m["name"]] = {"value": e2e[m["name"]],
                                          "unit": m["unit"]}
    checks, correct = judge(out["checks"], run.limits)
    final = {"correct": correct, "attempted": out["attempted"],
             "failed": out["failed"], "metrics": metrics, "device": device,
             **result, "window_compiles": run.window_compiles,
             "window_s": run.window_s}
    if out.get("detail"):
        final["detail"] = out["detail"]     # for reading a far-off run
    if run.rehearse:
        final["rehearsal"] = True
    final["checks"] = checks
    return final


def main(argv: list[str] | None = None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument("--rehearse", action="store_true",
                   help="run at the rehearsal size on any device; no metric")
    args = p.parse_args(argv)
    if args.seed < 0:
        p.error("--seed must be a non-negative integer")
    if not (ROOT / "src").is_dir():
        print("bench: the program is not beside the benchmark (no src/)",
              file=sys.stderr)
        return 2
    run = make_run(args.workload, args.seed, args.seconds, bool(args.trace),
                   args.rehearse)
    # run outputs stay inside the checkout
    logs = OUT / "tpu_logs"
    logs.mkdir(parents=True, exist_ok=True)
    os.environ.setdefault("TPU_LOG_DIR", str(logs))
    import jax
    devs = jax.devices()
    if not args.rehearse:
        if devs[0].platform != "tpu":
            print(f"bench: no TPU: JAX found {devs[0].platform!r} "
                  f"({devs[0].device_kind}); --rehearse runs without one",
                  file=sys.stderr)
            return 2
        if len(devs) < run.cell["chips"]:
            print(f"bench: {run.name} needs {run.cell['chips']} chips, "
                  f"found {len(devs)}", file=sys.stderr)
            return 2
        peaks = _json(BENCH / "peaks.json")
        if devs[0].device_kind not in peaks:
            print(f"bench: no peaks for device kind {devs[0].device_kind!r} "
                  f"in bench/peaks.json", file=sys.stderr)
            return 2
    _program_on_path()
    if not args.rehearse:
        enable_cache()
    result = run_cell(run)
    for k, c in result["checks"].items():
        print(f"check {k}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
