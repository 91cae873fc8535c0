"""Energy models: analytic (roofline-timed), replay-measured, HLO-calibrated.

Mirrors the paper's modular profiler (§5.2): a physical power meter when you
have one, replay-based software profiling when you don't.  On this CPU-only
container the 'physical meter' role is played by the analytic TPU-v5e model
(DESIGN.md §2); the ReplayProfiler measures real per-operator wall time on the
host and converts it through the host power model, preserving orderings and
relative differences that can be cross-checked against the analytic numbers
(benchmarks/bench_energy_accuracy.py, Table-4 analogue).

Sessions (core/session.py) select between these through the ``EnergyBackend``
protocol: an object with an ``id`` (feeds the artifact cache key), a ``label``
(the ``Report.meta['energy_model']`` string) and a ``profile(graph, args)``
method returning an :class:`EnergyProfile`.  ``AnalyticalBackend`` wraps
:class:`AnalyticalEnergyModel`, ``ReplayBackend`` wraps
:class:`ReplayProfiler`, and ``HloCostBackend`` prices each operator from
XLA's compiled module via per-instruction cost attribution
(core/hlo_costs.py): eqn ids are threaded through the lowering as name
scopes and each optimized-HLO instruction is credited back to its
originating jaxpr equation.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Protocol, Sequence, runtime_checkable

import numpy as np

from repro.core import costs as costs_mod
from repro.core import hlo_costs as hlo_costs_mod
from repro.core.graph import OpGraph
from repro.hw.specs import TPU_V5E, HardwareSpec, local_device_spec


@dataclasses.dataclass
class OpEnergy:
    node_idx: int
    primitive: str
    energy_j: float
    time_s: float
    flops: float
    hbm_bytes: float
    ici_bytes: float
    bound: str          # 'compute' | 'memory' | 'collective'


@dataclasses.dataclass
class EnergyProfile:
    graph_name: str
    ops: list[OpEnergy]
    # per-op costs attributed from the compiled module (HloCostBackend only);
    # persisted with the artifact so loaded captures keep their attribution.
    # compare=False: PerOpCosts holds ndarrays, whose __eq__ is elementwise
    hlo: "hlo_costs_mod.PerOpCosts | None" = dataclasses.field(
        default=None, compare=False)
    # node-indexed energy/time arrays, built lazily once so per-region
    # queries (subgraph_energy/subgraph_time) are O(|region|) array gathers
    # instead of a Python set rebuild + full scan per query.
    _energy_by_node: np.ndarray | None = dataclasses.field(
        default=None, repr=False, compare=False)
    _time_by_node: np.ndarray | None = dataclasses.field(
        default=None, repr=False, compare=False)

    def _index(self) -> tuple[np.ndarray, np.ndarray]:
        if self._energy_by_node is None:
            n = max((o.node_idx for o in self.ops), default=-1) + 1
            e = np.zeros(n)
            t = np.zeros(n)
            for o in self.ops:
                e[o.node_idx] += o.energy_j
                t[o.node_idx] += o.time_s
            self._energy_by_node = e
            self._time_by_node = t
        return self._energy_by_node, self._time_by_node

    @staticmethod
    def _gather(arr: np.ndarray, node_idxs: Sequence[int]) -> float:
        idxs = np.unique(np.fromiter(node_idxs, dtype=np.int64))
        # unknown idxs are ignored, matching the historical set-filter scan
        idxs = idxs[(idxs >= 0) & (idxs < arr.size)]
        return float(arr[idxs].sum()) if idxs.size else 0.0

    def energy_of(self, node_idxs: Sequence[int]) -> float:
        return self._gather(self._index()[0], node_idxs)

    def time_of(self, node_idxs: Sequence[int]) -> float:
        return self._gather(self._index()[1], node_idxs)

    @property
    def total_energy_j(self) -> float:
        return float(self._index()[0].sum())

    @property
    def total_time_s(self) -> float:
        return float(self._index()[1].sum())

    def top_k(self, k: int = 5) -> list[OpEnergy]:
        return sorted(self.ops, key=lambda o: -o.energy_j)[:k]

    def by_primitive(self) -> dict[str, float]:
        agg: dict[str, float] = {}
        for o in self.ops:
            agg[o.primitive] = agg.get(o.primitive, 0.0) + o.energy_j
        return dict(sorted(agg.items(), key=lambda kv: -kv[1]))


class AnalyticalEnergyModel:
    """Prices every operator from cost rules + hardware energy coefficients.

    E_op = e_flop·FLOPs + e_hbm·HBM_bytes + e_ici·ICI_bytes + P_static·t_op,
    with t_op the roofline max of the three terms.  fp32-accurate matmuls
    (precision=HIGHEST) run at peak_flops_fp32 — the TF32/tensor-core
    misconfiguration cases (c1/c8) fall out of this term.
    """

    def __init__(self, spec: HardwareSpec = TPU_V5E):
        self.spec = spec

    def _price(self, costs: "list[costs_mod.OpCost]"):
        """Roofline + energy math over a batch of OpCosts, as array ops.

        Single implementation shared by op_energy and profile: returns
        (flops, hbm, ici, energy, t_op, bound) arrays of len(costs).
        """
        s = self.spec
        n = len(costs)
        flops = np.fromiter((c.flops for c in costs), dtype=np.float64, count=n)
        frac = np.fromiter((c.fp32_fraction for c in costs), dtype=np.float64,
                           count=n)
        hbm = np.fromiter((c.hbm_bytes for c in costs), dtype=np.float64,
                          count=n)
        ici = np.fromiter((c.ici_bytes for c in costs), dtype=np.float64,
                          count=n)
        fp32 = flops * frac
        bf16 = flops - fp32
        t_compute = (bf16 / s.peak_flops_bf16) + (fp32 / s.peak_flops_fp32)
        t_mem = hbm / s.hbm_bw
        t_coll = ici / (s.ici_bw_per_link * s.ici_links)
        t_op = np.maximum(np.maximum(t_compute, t_mem),
                          np.maximum(t_coll, 0.0))
        bound = np.where((t_op == t_compute) & (t_compute > 0), "compute",
                         np.where((t_op == t_coll) & (t_coll > 0),
                                  "collective", "memory"))
        energy = (bf16 * s.joules_per_flop
                  + fp32 * 3.0 * s.joules_per_flop
                  + hbm * s.joules_per_hbm_byte
                  + ici * s.joules_per_ici_byte
                  + s.idle_watts * t_op)
        return flops, hbm, ici, energy, t_op, bound

    def op_energy(self, graph: OpGraph, node_idx: int) -> OpEnergy:
        node = graph.nodes[node_idx]
        c = costs_mod.node_cost(graph, node)
        flops, hbm, ici, energy, t_op, bound = self._price([c])
        return OpEnergy(node_idx=node_idx, primitive=node.primitive,
                        energy_j=float(energy[0]), time_s=float(t_op[0]),
                        flops=float(flops[0]), hbm_bytes=float(hbm[0]),
                        ici_bytes=float(ici[0]), bound=str(bound[0]))

    def profile(self, graph: OpGraph) -> EnergyProfile:
        """Price every node, with the roofline/energy math batched over the
        whole graph as array ops (one pass instead of per-node scalar math)."""
        costs = [costs_mod.node_cost(graph, node) for node in graph.nodes]
        flops, hbm, ici, energy, t_op, bound = self._price(costs)
        ops = [OpEnergy(node_idx=i, primitive=graph.nodes[i].primitive,
                        energy_j=float(energy[i]), time_s=float(t_op[i]),
                        flops=float(flops[i]), hbm_bytes=float(hbm[i]),
                        ici_bytes=float(ici[i]), bound=str(bound[i]))
               for i in range(len(costs))]
        return EnergyProfile(graph_name=graph.name, ops=ops)


class ReplayProfiler:
    """Measures real per-operator wall time by replaying each operator.

    The paper's fallback when no power meter is attached: replay each operator
    long enough to average out sampling noise, then convert time to energy via
    the power model.  The time is measured on the device this process runs
    on, so by default it is priced through that device's spec
    (``hw.specs.DEVICE_SPECS``); an unknown device raises.
    """

    def __init__(self, spec: HardwareSpec | None = None,
                 min_replay_time_s: float = 5e-3, max_replay_iters: int = 64):
        self.spec = spec if spec is not None else local_device_spec()
        self.min_replay_time_s = min_replay_time_s
        self.max_replay_iters = max_replay_iters

    def profile(self, graph: OpGraph, *args) -> EnergyProfile:
        from repro.core.interp import run_instrumented
        _, records = run_instrumented(
            graph, *args, measure=True,
            min_replay_time_s=self.min_replay_time_s,
            max_replay_iters=self.max_replay_iters)
        ops = []
        for rec in records:
            node = graph.nodes[rec.node_idx]
            c = costs_mod.node_cost(graph, node)
            t = rec.wall_time_s or 0.0
            # dynamic power scales with achieved intensity; static always on
            util = min(1.0, (c.flops / max(t, 1e-12)) / self.spec.peak_flops_bf16)
            p_dyn = self.spec.compute_watts * util + self.spec.hbm_watts * min(
                1.0, (c.hbm_bytes / max(t, 1e-12)) / self.spec.hbm_bw)
            energy = (self.spec.idle_watts + p_dyn) * t
            ops.append(OpEnergy(node_idx=rec.node_idx, primitive=rec.primitive,
                                energy_j=energy, time_s=t, flops=c.flops,
                                hbm_bytes=c.hbm_bytes, ici_bytes=c.ici_bytes,
                                bound="measured"))
        return EnergyProfile(graph_name=graph.name, ops=ops)


def subgraph_energy(profile: EnergyProfile, node_idxs: Sequence[int]) -> float:
    return profile.energy_of(node_idxs)


def subgraph_time(profile: EnergyProfile, node_idxs: Sequence[int]) -> float:
    return profile.time_of(node_idxs)


# ---------------------------------------------------------------------------
# pluggable backends (the session-level replacement for `use_replay: bool`)
# ---------------------------------------------------------------------------

def _spec_digest(spec: HardwareSpec) -> str:
    """Stable digest of a spec's coefficients, folded into backend ids so
    artifact cache keys change when pricing constants change (a renamed-only
    or retuned spec must never serve stale cached energy profiles)."""
    import hashlib
    payload = repr(sorted(dataclasses.asdict(spec).items()))
    return hashlib.sha256(payload.encode()).hexdigest()[:8]


@runtime_checkable
class EnergyBackend(Protocol):
    """Per-session energy pricing strategy.

    * ``id`` — stable identifier mixed into artifact cache keys, so captures
      priced by different backends never alias in the store;
    * ``label`` — human-readable name surfaced as
      ``Report.meta['energy_model']`` (the analytic backend keeps the legacy
      hardware-spec name, the replay backend the legacy ``"replay"``);
    * ``profile(graph, args)`` — price one traced graph.  ``args`` are the
      concrete capture inputs; analytic backends ignore them, measuring
      backends (replay) execute on them.
    """

    @property
    def id(self) -> str: ...

    @property
    def label(self) -> str: ...

    def profile(self, graph: OpGraph,
                args: Sequence[Any] = ()) -> EnergyProfile: ...


@dataclasses.dataclass(frozen=True)
class AnalyticalBackend:
    """Roofline/analytic pricing on a hardware spec (no execution)."""

    spec: HardwareSpec = TPU_V5E

    # a pure function of (graph, input avals): profiles are cacheable as
    # content-addressed ``profile--`` entries (core/block_cache.py)
    deterministic = True

    @property
    def id(self) -> str:
        return f"analytic:{self.spec.name}:{_spec_digest(self.spec)}"

    @property
    def label(self) -> str:
        return self.spec.name

    def profile(self, graph: OpGraph,
                args: Sequence[Any] = ()) -> EnergyProfile:
        return AnalyticalEnergyModel(self.spec).profile(graph)


@dataclasses.dataclass(frozen=True)
class ReplayBackend:
    """Replay-measured wall time on the local device, converted through its
    power model (the paper's software-profiling fallback).  The spec defaults
    to the local device's entry in ``hw.specs.DEVICE_SPECS``."""

    spec: HardwareSpec = dataclasses.field(default_factory=local_device_spec)
    min_replay_time_s: float = 5e-3
    max_replay_iters: int = 64

    # measured wall time is not a pure function of the program: never
    # replayed from a profile cache entry
    deterministic = False

    @property
    def id(self) -> str:
        return (f"replay:{self.spec.name}:{_spec_digest(self.spec)}"
                f":{self.min_replay_time_s}:{self.max_replay_iters}")

    @property
    def label(self) -> str:
        return "replay"

    def profile(self, graph: OpGraph,
                args: Sequence[Any] = ()) -> EnergyProfile:
        profiler = ReplayProfiler(self.spec,
                                  min_replay_time_s=self.min_replay_time_s,
                                  max_replay_iters=self.max_replay_iters)
        return profiler.profile(graph, *args)


@dataclasses.dataclass(frozen=True)
class HloCostBackend:
    """Per-instruction pricing from XLA's compiled module.

    The captured jaxpr is re-lowered with every equation bound under a
    ``magop<idx>`` name scope (hlo_costs.annotated_compile), so each HLO
    instruction in the optimized module — including instructions inside
    fused computations and while bodies — carries its originating OpGraph
    node id in its metadata.  Walking that module per instruction yields a
    true per-operator FLOP/byte/collective breakdown under XLA's fusion,
    CSE, and layout decisions (hlo_costs.attribute_costs); proportional
    splitting only happens inside fusions whose constituents are genuinely
    merged.  The resulting per-node columns are priced through the same
    roofline/energy math as the analytic model, and the attribution is kept
    on ``EnergyProfile.hlo`` so artifacts persist it.
    """

    spec: HardwareSpec = TPU_V5E

    # XLA cost analysis of a fixed module is deterministic: cacheable
    deterministic = True

    @property
    def id(self) -> str:
        # 'perop' marks the per-instruction attribution engine: captures
        # priced by the old module-total rescaling must not alias in stores
        return f"hlo:perop:{self.spec.name}:{_spec_digest(self.spec)}"

    @property
    def label(self) -> str:
        return f"hlo+{self.spec.name}"

    def profile(self, graph: OpGraph,
                args: Sequence[Any] = ()) -> EnergyProfile:
        if graph.closed_jaxpr is None:
            raise ValueError(
                "HloCostBackend needs a live graph (with a ClosedJaxpr); "
                "loaded artifacts carry their capture-time profile instead")
        poc = hlo_costs_mod.per_op_costs(graph, args)
        costs = [costs_mod.OpCost(
            flops=float(poc.flops[i]), hbm_bytes=float(poc.hbm_bytes[i]),
            ici_bytes=float(poc.ici_bytes[i]),
            fp32_fraction=float(poc.fp32_fraction[i]))
            for i in range(len(graph.nodes))]
        model = AnalyticalEnergyModel(self.spec)
        flops, hbm, ici, energy, t_op, bound = model._price(costs)
        ops = [OpEnergy(node_idx=i, primitive=graph.nodes[i].primitive,
                        energy_j=float(energy[i]), time_s=float(t_op[i]),
                        flops=float(flops[i]), hbm_bytes=float(hbm[i]),
                        ici_bytes=float(ici[i]), bound=str(bound[i]))
               for i in range(len(costs))]
        return EnergyProfile(graph_name=graph.name, ops=ops, hlo=poc)


def backend_from_name(name: str, *, spec: HardwareSpec = TPU_V5E
                      ) -> EnergyBackend:
    """Resolve a CLI-style backend name ('analytic' | 'replay' | 'hlo')."""
    if name in ("analytic", "analytical"):
        return AnalyticalBackend(spec)
    if name == "replay":
        return ReplayBackend()
    if name == "hlo":
        return HloCostBackend(spec)
    raise ValueError(f"unknown energy backend {name!r} "
                     "(expected 'analytic', 'replay' or 'hlo')")
