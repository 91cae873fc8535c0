"""Reduction of a JAX profiler trace to the benchmark's device numbers.

A trace is read once into plain tuples (:func:`load`), and everything else
is a pure function of those, so a recorded trace tests the arithmetic:

* busy time: the union of the intervals in which an operation ran on a
  device, clipped to the window, averaged over the devices;
* device time per named executable (``XLA Modules`` line);
* the device operations that took most time (``XLA Ops`` line);
* idle gaps: the stretches of the window in which a device ran nothing,
  each labelled by what the host was doing at its middle: the innermost
  ``bench.`` annotation and the innermost other host event around it.

The window is the host span of the annotation named ``bench.window``.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re
from collections import defaultdict

WINDOW = "bench.window"
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
_SUFFIX = re.compile(r"\(\d+\)$")


@dataclasses.dataclass
class Trace:
    # plane name -> line name -> [(event name, start_ns, end_ns)]
    planes: dict[str, dict[str, list[tuple[str, int, int]]]]

    def devices(self) -> list[str]:
        return sorted(p for p in self.planes if p.startswith("/device:")
                      and self.planes[p].get(OPS_LINE))

    def host_events(self) -> list[tuple[str, int, int]]:
        out = []
        for name, lines in self.planes.items():
            if name.startswith("/host:CPU"):
                for evs in lines.values():
                    out.extend(evs)
        return out


def find_profile(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def load(path: str) -> Trace:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    planes: dict = {}
    for plane in pd.planes:
        lines = {}
        for line in plane.lines:
            lines[line.name] = [(e.name, int(e.start_ns),
                                 int(e.start_ns + e.duration_ns))
                                for e in line.events]
        planes[plane.name] = lines
    return Trace(planes)


def window(tr: Trace) -> tuple[int, int]:
    spans = [(s, e) for n, s, e in tr.host_events() if n == WINDOW]
    if not spans:
        raise ValueError(f"the trace holds no {WINDOW!r} annotation")
    return min(s for s, _ in spans), max(e for _, e in spans)


def merge(intervals, lo: int, hi: int) -> list[tuple[int, int]]:
    """Union of ``intervals`` clipped to [lo, hi], sorted."""
    out: list[list[int]] = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def busy_s(tr: Trace, win: tuple[int, int]) -> float:
    """Seconds in which an operation ran, averaged over the devices."""
    devs = tr.devices()
    if not devs:
        return 0.0
    total = 0
    for d in devs:
        evs = [(s, e) for _, s, e in tr.planes[d][OPS_LINE]]
        total += sum(e - s for s, e in merge(evs, *win))
    return total / len(devs) / 1e9


def module_name(event_name: str) -> str:
    return _SUFFIX.sub("", event_name)


def modules(tr: Trace, win: tuple[int, int]
            ) -> dict[str, list[tuple[int, int]]]:
    """Executions of each named executable inside the window (device 0)."""
    devs = tr.devices()
    if not devs:
        return {}
    lines = tr.planes[devs[0]]
    out: dict[str, list[tuple[int, int]]] = defaultdict(list)
    for n, s, e in lines.get(MODULES_LINE, []):
        if s >= win[0] and e <= win[1]:
            out[module_name(n)].append((s, e))
    return {k: sorted(v) for k, v in out.items()}


def top_ops(tr: Trace, win: tuple[int, int], k: int = 10
            ) -> list[list]:
    """The ``k`` device operations with the most time, in seconds, averaged
    over the devices."""
    devs = tr.devices()
    acc: dict[str, int] = defaultdict(int)
    for d in devs:
        for n, s, e in tr.planes[d][OPS_LINE]:
            s, e = max(s, win[0]), min(e, win[1])
            if e > s:
                acc[n] += e - s
    ranked = sorted(acc.items(), key=lambda kv: -kv[1])[:k]
    return [[op_label(n), ns / len(devs) / 1e9] for n, ns in ranked]


def op_label(name: str, width: int = 120) -> str:
    """An XLA op's event name (its whole HLO instruction) cut to ``width``
    characters."""
    return name if len(name) <= width else name[:width - 3] + "..."


def _label(around: list[tuple[str, int, int]]) -> str:
    inner = sorted((e - s, n) for n, s, e in around)
    bench = [n for _, n in inner if n.startswith("bench.") and n != WINDOW]
    other = [n for _, n in inner if not n.startswith("bench.")
             and not n.startswith("Threadpool")]
    return " / ".join([bench[0] if bench else "(no bench span)"]
                      + other[:1])


def idle_gaps(tr: Trace, win: tuple[int, int], k: int = 10) -> list[list]:
    """Idle seconds of device 0 in the window, summed by host label, the
    ``k`` largest labels first."""
    devs = tr.devices()
    if not devs:
        return []
    busy = merge([(s, e) for _, s, e in tr.planes[devs[0]][OPS_LINE]], *win)
    edges = [win[0]] + [x for iv in busy for x in iv] + [win[1]]
    gaps = [(s, e) for s, e in zip(edges[::2], edges[1::2]) if e > s]
    # one sweep over the host events in start order: at each gap's middle
    # the events still open are the ones around it
    host = sorted(tr.host_events(), key=lambda ev: ev[1])
    acc: dict[str, int] = defaultdict(int)
    i, active = 0, []
    for s, e in gaps:
        t = (s + e) // 2
        while i < len(host) and host[i][1] <= t:
            active.append(host[i])
            i += 1
        active = [ev for ev in active if ev[2] > t]
        acc[_label(active)] += e - s
    ranked = sorted(acc.items(), key=lambda kv: -kv[1])[:k]
    return [[n, ns / 1e9] for n, ns in ranked]


def summarize(tr: Trace) -> dict:
    """Everything the metric readers and the result line take."""
    win = window(tr)
    return {"window_s": (win[1] - win[0]) / 1e9,
            "busy_s": busy_s(tr, win),
            "devices": len(tr.devices()),
            "modules": modules(tr, win),
            "device_ops": top_ops(tr, win),
            "idle_gaps": idle_gaps(tr, win)}
