"""Instrumenting jaxpr interpreter.

The JAX analogue of the paper's CUPTI Callback tracing (§5.1): executes a
traced program operator by operator, firing a callback with each operator's
inputs/outputs.  Used for
  * STREAMING tensor-signature capture (capture_tensor_stats): each operator's
    outputs are reduced to their cheap symmetric invariants inside the on_op
    callback and the values are discarded immediately, so multi-sample capture
    holds O(tensors) scalars instead of O(activations x samples) float64
    arrays — the default matching path,
  * selective tensor-VALUE capture (capture_tensor_values with only_tids) for
    the matcher's lazy phase-2 spectral checks — now dead-code-sliced: only
    the backward closure of the requested tensors executes,
  * replay-based per-operator wall-time measurement (energy.py ReplayProfiler,
    the paper's §5.2 software profiling mode),
  * runtime overhead benchmarking (Fig. 10 analogue).

Graphs extracted by ``extract_graph`` carry a flat tid-space program (one
leaf equation per node + const/literal values), which enables the fast
executor here: a single flat loop over int-keyed environments instead of the
nested Var-keyed interpreters, reference-counted per-op value discard (the
true streaming-memory watermark), and — for graphs with repeated-block
families — FUSED BLOCK STATS capture: one ``jax.jit``-compiled function per
block family computes every block tensor's five invariants on device in a
single dispatch per repeat, so streaming capture stops paying one host
round-trip per operator (the PR 1 follow-up: per-op invariant reduction no
longer retraces/re-dispatches per op).
"""

from __future__ import annotations

import dataclasses
import math
import time
from typing import Any, Callable, Sequence

import jax
import numpy as np
from jax._src.core import ClosedJaxpr, Literal

from repro.core.graph import COLLECTIVE_PRIMS, OpGraph


@dataclasses.dataclass
class OpRecord:
    node_idx: int
    primitive: str
    # kept if capture_values; with stream_values they are present only for
    # the duration of the on_op callback and dropped right after.  None for
    # ops covered by a fused block-stats dispatch (their invariants are
    # delivered through the ``fused_stats`` dict instead).
    out_values: list[Any] | None
    wall_time_s: float | None          # only set if measure (replay) enabled
    replay_iters: int = 0


def _bind(eqn, invals):
    subfuns, bind_params = eqn.primitive.get_bind_params(eqn.params)
    out = eqn.primitive.bind(*subfuns, *invals, **bind_params)
    if not eqn.primitive.multiple_results:
        out = [out]
    return out


# Collectives appearing inside an inlined shard_map body.  The interpreter
# executes with *global* values; on this single-host container every mesh
# axis has size 1, so each collective is semantically the identity (and
# axis_index is 0).  Multi-shard interpretation is impossible off-cluster and
# raises.
def _collective_passthrough(eqn, invals, axis_sizes: dict[str, int]):
    name = eqn.primitive.name
    axes = eqn.params.get("axes", eqn.params.get("axis_name", ()))
    if not isinstance(axes, (tuple, list)):
        axes = (axes,)
    sizes = [axis_sizes.get(a, 1) for a in axes]
    if any(s != 1 for s in sizes):
        raise NotImplementedError(
            f"cannot interpret {name} over axes {axes} with sizes {sizes} "
            "on a single-host container")
    if name == "axis_index":
        return [np.int32(0)]
    return list(invals)


# ---------------------------------------------------------------------------
# execution plan (memoized per graph)
# ---------------------------------------------------------------------------

# Fused block-stats capture engages only above this node count: the per-block
# jitted reduction accumulates in float32 for EVERY float tensor (the plain
# path uses float64 numpy below tensor_match._JIT_STATS_MIN_NUMEL), so small
# graphs — including every committed zoo baseline — keep the historical
# bit-exact path.
_FUSED_STATS_MIN_NODES = 128


class _BlockExec:
    """One jit-compiled block family: executes the representative block's
    equations under trace and returns (external outputs, (F, 5) float32
    invariant rows for float tensors, raw values for the rest)."""

    def __init__(self, graph: OpGraph, plan: "_ExecPlan", fam):
        import jax.numpy as jnp
        from repro.core.tensor_match import _JIT_DTYPES

        self.fam = fam
        period, count = fam.period, fam.count
        nodes = graph.nodes
        tensors = graph.tensors

        # external inputs per repeat, in first-occurrence (offset, slot) order
        self.ext_in: list[list[int]] = []
        for r in range(count):
            lo, hi = fam.window(r)
            seen: set[int] = set()
            order: list[int] = []
            for o in range(period):
                for t in nodes[lo + o].invars:
                    e = tensors[t]
                    internal = e.producer is not None and lo <= e.producer < hi
                    if internal or t in seen:
                        continue
                    seen.add(t)
                    order.append(t)
            self.ext_in.append(order)
        self.ok = all(len(x) == len(self.ext_in[0]) for x in self.ext_in)
        if not self.ok:
            return

        # outputs needed OUTSIDE the block in ANY repeat (union keeps the
        # jitted return structure identical across repeats: one compile)
        ext_out: set[tuple[int, int]] = set()
        for r in range(count):
            lo, hi = fam.window(r)
            for o in range(period):
                for slot, t in enumerate(nodes[lo + o].outvars):
                    e = tensors[t]
                    if e.is_output or any(c < lo or c >= hi
                                          for c in e.consumers):
                        ext_out.add((o, slot))
        self.ext_out = sorted(ext_out)

        rep_lo = fam.start
        rep_nodes = [nodes[rep_lo + o] for o in range(period)]
        rep_eqns = [plan.eqns[rep_lo + o] for o in range(period)]
        self.float_offsets: list[tuple[int, int]] = []
        self.raw_offsets: list[tuple[int, int]] = []
        # (offset, slot, numel, dtype, shape) per float output: block repeats
        # share avals (families are keyed on structural digests), so the
        # representative's metadata holds for every repeat — precomputing it
        # keeps np.prod/dtype lookups out of the per-repeat dispatch loop
        self.float_meta: list[tuple[int, int, int, str, tuple]] = []
        for o in range(period):
            for slot, t in enumerate(rep_nodes[o].outvars):
                e = tensors[t]
                numel = int(np.prod(e.shape, dtype=np.int64)) if e.shape else 1
                if numel > 0 and e.dtype in _JIT_DTYPES:
                    self.float_offsets.append((o, slot))
                    self.float_meta.append((o, slot, numel, e.dtype, e.shape))
                else:
                    self.raw_offsets.append((o, slot))

        rep_ext_in = tuple(self.ext_in[0])
        ext_out_tids = [rep_nodes[o].outvars[slot] for o, slot in self.ext_out]
        float_tids = [rep_nodes[o].outvars[slot]
                      for o, slot in self.float_offsets]
        raw_tids = [rep_nodes[o].outvars[slot] for o, slot in self.raw_offsets]

        def block(*ext_vals):
            benv = dict(zip(rep_ext_in, ext_vals))
            for eqn, node in zip(rep_eqns, rep_nodes):
                out = _bind(eqn, [benv[t] for t in node.invars])
                for t, v in zip(node.outvars, out):
                    benv[t] = v
            rows = []
            for t in float_tids:
                x = benv[t].astype(jnp.float32).ravel()
                rows.append(jnp.stack([jnp.sum(jnp.abs(x)), jnp.sum(x * x),
                                       jnp.mean(x), jnp.max(x), jnp.min(x)]))
            stats = (jnp.stack(rows) if rows
                     else jnp.zeros((0, 5), jnp.float32))
            return ([benv[t] for t in ext_out_tids], stats,
                    [benv[t] for t in raw_tids])

        self.fn = jax.jit(block)


class _ExecPlan:
    """Per-graph execution plan: flat equations, const values, per-node mesh
    axes, per-node free lists (refcounted discard), lazy fused blocks."""

    def __init__(self, graph: OpGraph):
        self.has_program = (
            graph._eqns is not None
            and len(graph._eqns) == len(graph.nodes))
        if not self.has_program:
            return
        self.eqns = graph._eqns
        self.consts = graph._const_vals or {}
        axes = graph._node_axis_sizes
        self.axes = (axes if axes is not None and len(axes) == len(graph.nodes)
                     else [{}] * len(graph.nodes))
        keep = set(graph.outputs)
        last_use: dict[int, int] = {}
        for node in graph.nodes:
            for t in node.invars:
                last_use[t] = node.idx
        free_after: list[list[int]] = [[] for _ in graph.nodes]
        for node in graph.nodes:       # dead outputs: free immediately
            for t in node.outvars:
                if not graph.tensors[t].consumers and t not in keep:
                    free_after[node.idx].append(t)
        for t, idx in last_use.items():
            e = graph.tensors[t]
            if e.is_const or e.is_input or t in keep:
                continue
            free_after[idx].append(t)
        self.free_after = free_after
        self.nbytes = {t: e.nbytes for t, e in graph.tensors.items()}
        self._blocks: dict[int, _BlockExec] | None = None

    def fused_blocks(self, graph: OpGraph) -> dict[int, _BlockExec]:
        """Block families eligible for fused stats capture, keyed by their
        start node (built + compiled lazily, memoized on the plan)."""
        if self._blocks is not None:
            return self._blocks
        from repro.core.graph import block_structure
        blocks: dict[int, _BlockExec] = {}
        for fam in block_structure(graph).families:
            lo, hi = fam.start, fam.start + fam.period
            if any(graph.nodes[i].primitive in COLLECTIVE_PRIMS
                   or graph.nodes[i].primitive == "axis_index"
                   or self.axes[i] for i in range(lo, hi)):
                continue
            be = _BlockExec(graph, self, fam)
            if be.ok:
                blocks[fam.start] = be
        self._blocks = blocks
        return blocks


def _exec_plan(graph: OpGraph) -> _ExecPlan:
    plan = getattr(graph, "_interp_plan", None)
    if plan is None:
        plan = _ExecPlan(graph)
        graph._interp_plan = plan
    return plan


def run_instrumented(
    graph: OpGraph,
    *args,
    capture_values: bool = False,
    stream_values: bool = False,
    measure: bool = False,
    min_replay_time_s: float = 5e-3,
    max_replay_iters: int = 64,
    on_op: Callable[[OpRecord], None] | None = None,
    only_nodes: "set[int] | None" = None,
    fused_stats: "dict[int, Any] | None" = None,
    mem: "dict[str, int] | None" = None,
    block_cache=None,
) -> tuple[list[Any], list[OpRecord]]:
    """Execute the graph's jaxpr operator-by-operator with instrumentation.

    When ``measure`` is set, each operator is re-executed until at least
    ``min_replay_time_s`` of wall time accumulates — the replay trick from the
    paper's §5.2 that averages out timer/counter noise for microsecond ops.
    Note the instrumented path executes the *unfused* operator stream, which
    is exactly the operator-level execution model priced by costs.py.

    ``capture_values`` retains every operator's outputs on its OpRecord
    (O(activations) extra memory, per sample).  ``stream_values`` instead
    exposes the raw outputs to the ``on_op`` callback ONLY for the duration
    of the call and drops them afterwards: the callback can reduce each
    tensor to a signature so nothing beyond the interpreter's own live
    values is ever retained, across however many samples are captured.

    ``only_nodes`` restricts execution to the given node set (the caller is
    responsible for closure under data dependencies — see
    ``capture_tensor_values``); unexecuted ops fire no records.

    ``fused_stats`` switches large repeated-block graphs to fused block
    capture: covered operators execute inside one jit-compiled function per
    block family (one dispatch per repeat) and their five symmetric
    invariants land in the dict as ``{tid: TensorSignature}``; their
    OpRecords carry ``out_values=None``.  Uncovered operators stream
    normally.

    ``mem``, when provided, receives ``peak_live_bytes``: the high-water
    mark of operator outputs resident in the interpreter environment, with
    per-op reference-counted discard (tensors are dropped after their last
    consumer).  Only the fast tid-space executor tracks this.

    ``block_cache`` (a block_cache.BlockEvidenceCache) makes the fused
    block path INCREMENTAL: each repeat's dispatch is keyed by its family
    digest + external-input value digests; hits splice the cached stats
    and rematerialize the cached external outputs without executing the
    block, misses execute normally and record their evidence.  Only active
    when the fused path is (``fused_stats`` set, graph large enough).
    """
    closed = graph.closed_jaxpr
    if closed is None:
        raise ValueError("OpGraph was built without a ClosedJaxpr; cannot execute")
    # Same flattening used to build `graph` so node idxs line up (memoized on
    # the graph: repeated multi-sample/replay runs stop re-extracting).
    flat = graph.flat_graph()
    if len(flat.nodes) != len(graph.nodes):
        raise ValueError("graph/node mismatch; rebuild graph with extract_graph")

    # Fast tid-space executor: only when the graph IS its own flattening
    # (every extract_graph/trace product), so tids in records/env/fused_stats
    # are the caller's tids.
    plan = _exec_plan(graph) if flat is graph else None
    if plan is not None and plan.has_program:
        return _run_flat(graph, plan, args,
                         capture_values=capture_values,
                         stream_values=stream_values, measure=measure,
                         min_replay_time_s=min_replay_time_s,
                         max_replay_iters=max_replay_iters, on_op=on_op,
                         only_nodes=only_nodes, fused_stats=fused_stats,
                         mem=mem, block_cache=block_cache)

    jaxpr = closed.jaxpr
    env: dict[Any, Any] = {}

    def read(v):
        return v.val if isinstance(v, Literal) else env[v]

    def write(v, val):
        env[v] = val

    for v, val in zip(jaxpr.constvars, closed.consts):
        write(v, val)
    flat_args = jax.tree_util.tree_leaves(args)
    if len(flat_args) != len(jaxpr.invars):
        raise ValueError(f"expected {len(jaxpr.invars)} args, got {len(flat_args)}")
    for v, val in zip(jaxpr.invars, flat_args):
        write(v, val)

    records: list[OpRecord] = []
    node_idx = 0

    def exec_eqns(eqns, inner_env, read_fn, write_fn,
                  axis_sizes: dict[str, int] | None = None):
        nonlocal node_idx
        from repro.core.graph import _INLINE_PRIMITIVES, _nested_jaxpr
        axis_sizes = axis_sizes or {}
        for eqn in eqns:
            inner = _nested_jaxpr(eqn)
            if inner is not None and eqn.primitive.name in _INLINE_PRIMITIVES:
                sub_env: dict[Any, Any] = {}

                def sread(v, _se=sub_env):
                    return v.val if isinstance(v, Literal) else _se[v]

                def swrite(v, val, _se=sub_env):
                    _se[v] = val

                sub_axes = dict(axis_sizes)
                if eqn.primitive.name == "shard_map":
                    mesh = eqn.params.get("mesh")
                    if mesh is not None:
                        sub_axes.update({str(k): int(v)
                                         for k, v in mesh.shape.items()})
                for cv, cval in zip(inner.jaxpr.constvars, inner.consts):
                    swrite(cv, cval)
                for iv, ov in zip(inner.jaxpr.invars, eqn.invars):
                    swrite(iv, read_fn(ov))
                exec_eqns(inner.jaxpr.eqns, sub_env, sread, swrite, sub_axes)
                for ov, iv in zip(eqn.outvars, inner.jaxpr.outvars):
                    write_fn(ov, sread(iv))
                continue

            invals = [read_fn(v) for v in eqn.invars]
            wall = None
            iters = 0
            if eqn.primitive.name in COLLECTIVE_PRIMS or \
                    eqn.primitive.name == "axis_index":
                out = _collective_passthrough(eqn, invals, axis_sizes)
            elif measure:
                # warmup once (compile path), then replay until stable
                out = _bind(eqn, invals)
                jax.block_until_ready(out)
                t0 = time.perf_counter()
                elapsed = 0.0
                while elapsed < min_replay_time_s and iters < max_replay_iters:
                    out = _bind(eqn, invals)
                    jax.block_until_ready(out)
                    iters += 1
                    elapsed = time.perf_counter() - t0
                wall = elapsed / max(iters, 1)
            else:
                out = _bind(eqn, invals)
            for v, val in zip(eqn.outvars, out):
                write_fn(v, val)
            if capture_values:
                out_values = [np.asarray(o) for o in out]
            elif stream_values:
                out_values = list(out)   # raw, handed to on_op then dropped
            else:
                out_values = None
            rec = OpRecord(
                node_idx=node_idx,
                primitive=eqn.primitive.name,
                out_values=out_values,
                wall_time_s=wall,
                replay_iters=iters,
            )
            records.append(rec)
            if on_op is not None:
                on_op(rec)
            if stream_values and not capture_values:
                rec.out_values = None
            node_idx += 1

    exec_eqns(jaxpr.eqns, env, read, write, {})
    outs = [read(v) for v in jaxpr.outvars]
    return outs, records


def _run_flat(graph: OpGraph, plan: _ExecPlan, args, *,
              capture_values: bool, stream_values: bool, measure: bool,
              min_replay_time_s: float, max_replay_iters: int,
              on_op, only_nodes, fused_stats, mem, block_cache=None
              ) -> tuple[list[Any], list[OpRecord]]:
    """Flat tid-space executor (see run_instrumented for semantics)."""
    nodes = graph.nodes
    tensors = graph.tensors
    consts = plan.consts
    env: dict[int, Any] = {}
    flat_args = jax.tree_util.tree_leaves(args)
    if len(flat_args) != len(graph.inputs):
        raise ValueError(
            f"expected {len(graph.inputs)} args, got {len(flat_args)}")
    for t, val in zip(graph.inputs, flat_args):
        env[t] = val

    live = 0
    peak = 0
    nbytes = plan.nbytes
    track_mem = mem is not None and not capture_values

    def write_out(t, val):
        nonlocal live, peak
        env[t] = val
        if track_mem:
            live += nbytes[t]
            if live > peak:
                peak = live

    def free_after(idx):
        nonlocal live
        for t in plan.free_after[idx]:
            if env.pop(t, None) is not None and track_mem:
                live -= nbytes[t]

    use_fused = (fused_stats is not None and not measure
                 and not capture_values and only_nodes is None
                 and len(nodes) >= _FUSED_STATS_MIN_NODES)
    blocks = plan.fused_blocks(graph) if use_fused else {}
    cache = block_cache if use_fused else None
    # run-local tid -> value digest memo: seeded by cache hits/misses so
    # chained blocks never re-hash intermediate values
    run_digests: dict[int, str] = {} if cache is not None else None

    records: list[OpRecord] = []
    idx = 0
    n = len(nodes)
    while idx < n:
        be = blocks.get(idx) if use_fused else None
        if be is not None:
            _run_block(graph, be, env, write_out, free_after, records,
                       on_op, fused_stats, cache=cache,
                       run_digests=run_digests)
            idx = be.fam.end
            continue
        node = nodes[idx]
        if only_nodes is not None and idx not in only_nodes:
            idx += 1
            continue
        eqn = plan.eqns[idx]
        invals = [env[t] if t in env else consts[t] for t in node.invars]
        wall = None
        iters = 0
        if node.primitive in COLLECTIVE_PRIMS or node.primitive == "axis_index":
            out = _collective_passthrough(eqn, invals, plan.axes[idx])
        elif measure:
            out = _bind(eqn, invals)
            jax.block_until_ready(out)
            t0 = time.perf_counter()
            elapsed = 0.0
            while elapsed < min_replay_time_s and iters < max_replay_iters:
                out = _bind(eqn, invals)
                jax.block_until_ready(out)
                iters += 1
                elapsed = time.perf_counter() - t0
            wall = elapsed / max(iters, 1)
        else:
            out = _bind(eqn, invals)
        for t, val in zip(node.outvars, out):
            write_out(t, val)
        if capture_values:
            out_values = [np.asarray(o) for o in out]
        elif stream_values:
            out_values = list(out)
        else:
            out_values = None
        rec = OpRecord(node_idx=idx, primitive=node.primitive,
                       out_values=out_values, wall_time_s=wall,
                       replay_iters=iters)
        records.append(rec)
        if on_op is not None:
            on_op(rec)
        if stream_values and not capture_values:
            rec.out_values = None
        free_after(idx)
        idx += 1

    if only_nodes is None:
        outs = [env[t] if t in env else consts[t] for t in graph.outputs]
    else:   # sliced run: outputs outside the slice were never produced
        outs = [env.get(t, consts.get(t)) for t in graph.outputs]
    if mem is not None:
        mem["peak_live_bytes"] = peak
    return outs, records


def _run_block(graph: OpGraph, be: _BlockExec, env, write_out, free_after,
               records, on_op, fused_stats, cache=None,
               run_digests=None) -> None:
    """Dispatch one fused block family: one compiled call per repeat — or,
    with ``cache``, zero calls for repeats whose evidence key hits."""
    from repro.core.tensor_match import TensorSignature, stats_signature

    nodes = graph.nodes
    tensors = graph.tensors
    consts = getattr(graph, "_interp_plan").consts
    fam = be.fam
    bs = None
    if cache is not None:
        from repro.core.graph import _value_digest, block_structure
        bs = block_structure(graph)

    def in_digest(t: int) -> str:
        d = run_digests.get(t)
        if d is None:
            d = (bs.const_digest(t) if tensors[t].is_const
                 else _value_digest(env[t]))
            run_digests[t] = d
        return d

    def emit_records(lo: int) -> None:
        for o in range(fam.period):
            i = lo + o
            rec = OpRecord(node_idx=i, primitive=nodes[i].primitive,
                           out_values=None, wall_time_s=None)
            records.append(rec)
            if on_op is not None:
                on_op(rec)
            free_after(i)

    for r in range(fam.count):
        lo, _ = fam.window(r)

        entry_key = None
        if cache is not None:
            from repro.core.block_cache import (block_entry_key,
                                                format_value_digest)
            digs = [in_digest(t) for t in be.ext_in[r]]
            entry_key = block_entry_key(fam.digest, fam.period,
                                        be.ext_out, digs)
            hit = cache.get_block(entry_key, fam_digest=fam.digest, lo=lo)
            if hit is not None:
                payload, arrays = hit
                for rec_d, v in zip(payload["ext_out"], arrays):
                    t = nodes[lo + rec_d["o"]].outvars[rec_d["slot"]]
                    write_out(t, v)
                    run_digests[t] = format_value_digest(
                        rec_d["dtype"], rec_d["shape"], rec_d["digest"])
                for row in payload["stats"]:
                    t = nodes[lo + row[0]].outvars[row[1]]
                    fused_stats[t] = TensorSignature(
                        numel=row[2], dtype=row[3],
                        l1=row[4], l2=row[5], mean=row[6],
                        amax=row[7], amin=row[8],
                        spectra=None, shape=tuple(row[9]))
                emit_records(lo)
                continue

        args = [env[t] if t in env else consts[t] for t in be.ext_in[r]]
        ext_vals, stats_arr, raws = be.fn(*args)
        ext_np = ([np.asarray(v) for v in ext_vals]
                  if cache is not None else None)
        for (o, slot), v in zip(be.ext_out, ext_vals):
            write_out(nodes[lo + o].outvars[slot], v)
        # ONE host transfer per repeat, ONE C pass to python floats
        rows = np.asarray(stats_arr).tolist()
        for row, (o, slot, numel, dtype, shape) in zip(rows, be.float_meta):
            t = nodes[lo + o].outvars[slot]
            fused_stats[t] = TensorSignature(
                numel=numel, dtype=dtype,
                l1=row[0], l2=math.sqrt(max(row[1], 0.0)),
                mean=row[2], amax=row[3], amin=row[4],
                spectra=None, shape=shape)
        for v, (o, slot) in zip(raws, be.raw_offsets):
            t = nodes[lo + o].outvars[slot]
            fused_stats[t] = stats_signature(np.asarray(v))

        if cache is not None:
            ext_recs = []
            for (o, slot), a in zip(be.ext_out, ext_np):
                rec_d = cache.value_record(a)
                rec_d["o"], rec_d["slot"] = o, slot
                ext_recs.append(rec_d)
                t = nodes[lo + o].outvars[slot]
                run_digests[t] = format_value_digest(
                    rec_d["dtype"], rec_d["shape"], rec_d["digest"])
            stat_rows = []
            for o, slot, numel, dtype, shape in be.float_meta:
                s = fused_stats[nodes[lo + o].outvars[slot]]
                stat_rows.append([o, slot, numel, dtype,
                                  float(s.l1), float(s.l2), float(s.mean),
                                  float(s.amax), float(s.amin), list(shape)])
            for o, slot in be.raw_offsets:
                s = fused_stats[nodes[lo + o].outvars[slot]]
                stat_rows.append([o, slot, int(s.numel), s.dtype,
                                  float(s.l1), float(s.l2), float(s.mean),
                                  float(s.amax), float(s.amin),
                                  list(s.shape or ())])
            from repro.core.block_cache import BLOCK_SCHEMA_VERSION
            cache.put_block(entry_key, {
                "schema": BLOCK_SCHEMA_VERSION, "kind": "block-evidence",
                "family_digest": fam.digest, "period": fam.period,
                "stats": stat_rows, "ext_out": ext_recs}, ext_np)
        emit_records(lo)


def _needed_nodes(graph: OpGraph, want: set[int]) -> set[int]:
    """Backward closure of the producers of the requested tensors."""
    needed: set[int] = set()
    frontier = [graph.tensors[t].producer for t in want
                if t in graph.tensors and graph.tensors[t].producer is not None]
    while frontier:
        nidx = frontier.pop()
        if nidx is None or nidx in needed:
            continue
        needed.add(nidx)
        for t in graph.nodes[nidx].invars:
            p = graph.tensors[t].producer
            if p is not None and p not in needed:
                frontier.append(p)
    return needed


def capture_tensor_values(
    graph: OpGraph, *args,
    only_tids: "set[int] | Sequence[int] | None" = None,
) -> dict[int, np.ndarray]:
    """Map tensor-id -> concrete value for edges in the graph.

    With ``only_tids`` the run retains ONLY the requested tensors (the
    matcher's phase-2 selective fetch) and — on graphs carrying a flat
    program — executes ONLY the backward closure of their producers
    (dead-code slicing): fetching one early-layer tensor from a 5k-node
    graph costs a few operators, not a full forward pass.
    """
    want = None if only_tids is None else set(only_tids)
    values: dict[int, np.ndarray] = {}
    flat_args = jax.tree_util.tree_leaves(args)
    for tid, val in zip(graph.inputs, flat_args):
        if want is None or tid in want:
            values[tid] = np.asarray(val)

    def on_op(rec: OpRecord) -> None:
        node = graph.nodes[rec.node_idx]
        for tid, val in zip(node.outvars, rec.out_values or []):
            if want is None or tid in want:
                values[tid] = np.asarray(val)

    only_nodes = None if want is None else _needed_nodes(graph, want)
    run_instrumented(graph, *args, stream_values=True, on_op=on_op,
                     only_nodes=only_nodes)
    return values


def capture_tensor_stats(graph: OpGraph, *args,
                         mem: "dict[str, int] | None" = None,
                         block_cache=None):
    """Streaming capture: outputs + tensor-id -> cheap symmetric invariants.

    One instrumented execution computes each intermediate tensor's
    entry-symmetric invariants (l1/l2/mean/amax/amin) in the on_op callback
    — or, for large repeated-block graphs, inside one fused jitted reduction
    per block repeat — and discards the values immediately.  Returns
    ``(graph_outputs, {tid: TensorSignature})`` so callers (diff.py's
    functional-equivalence gate) can reuse the same execution's outputs
    instead of running the program again.  ``mem`` (optional dict) receives
    the executor's ``peak_live_bytes`` watermark.  ``block_cache`` (a
    block_cache.BlockEvidenceCache) makes fused-block capture incremental:
    repeats whose evidence key hits splice cached invariants and outputs
    instead of executing (byte-identical to a cold capture).
    """
    from repro.core.tensor_match import stats_signature

    stats: dict[int, Any] = {}
    flat_args = jax.tree_util.tree_leaves(args)
    for tid, val in zip(graph.inputs, flat_args):
        stats[tid] = stats_signature(val)

    def on_op(rec: OpRecord) -> None:
        node = graph.nodes[rec.node_idx]
        for tid, val in zip(node.outvars, rec.out_values or []):
            stats[tid] = stats_signature(val)

    outs, _ = run_instrumented(graph, *args, stream_values=True, on_op=on_op,
                               fused_stats=stats, mem=mem,
                               block_cache=block_cache)
    return outs, stats
