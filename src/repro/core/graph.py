"""Computational-graph extraction from jaxprs.

This is Magneton's trace substrate, adapted to JAX (DESIGN.md §2): instead of
reconstructing an operator DAG from CUPTI kernel traces + correlation IDs, we
take the dataflow DAG JAX already has — the jaxpr.  Nodes are equations
(operators), edges are tensors (jaxpr variables), and every node carries the
user call path recorded by the tracer (the analogue of the libunwind /
PyEval_SetProfile stacks in the paper's §5.1).
"""

from __future__ import annotations

import dataclasses
import hashlib
from typing import Any, Callable, Iterable, Sequence

import jax
import numpy as np
from jax._src.core import ClosedJaxpr, Jaxpr, Literal, Var

try:  # C-speed BFS for large-graph region queries; pure-python fallback kept
    from scipy import sparse as _sparse
    from scipy.sparse.csgraph import breadth_first_order as _bfs_order
except Exception:  # pragma: no cover - scipy ships with the jax toolchain
    _sparse = None
    _bfs_order = None

# Higher-order primitives whose inner jaxpr we inline during flattening.
# scan / while / cond are kept as super-nodes (their bodies execute a
# data-dependent or repeated number of times; costs.py prices them).
_INLINE_PRIMITIVES = ("pjit", "jit", "closed_call", "custom_jvp_call",
                      "custom_vjp_call", "remat", "checkpoint",
                      "custom_vjp_call_jaxpr", "shard_map")


# Collective primitives as traced jaxprs bind them, inside shard_map bodies
# too: jax 0.4 binds a psum there as psum2 + pbroadcast, jax 0.9 as
# psum_invariant + pvary.
COLLECTIVE_PRIMS = frozenset({
    "psum", "psum2", "psum_invariant", "pbroadcast", "pvary", "pmean",
    "pmin", "pmax", "all_gather", "all_gather_invariant", "all_to_all",
    "ppermute", "psum_scatter", "reduce_scatter"})


def _nested_jaxpr(eqn) -> ClosedJaxpr | None:
    """Return the callee jaxpr of a call-like equation, if any."""
    p = eqn.params
    for key in ("jaxpr", "call_jaxpr", "fun_jaxpr"):
        if key in p:
            val = p[key]
            if isinstance(val, ClosedJaxpr):
                return val
            if isinstance(val, Jaxpr):
                return ClosedJaxpr(val, ())
    return None


@dataclasses.dataclass
class OpNode:
    """One operator (jaxpr equation) in the graph."""

    idx: int
    primitive: str
    params: dict[str, Any]
    invars: list[int]          # tensor ids
    outvars: list[int]         # tensor ids
    call_path: tuple[str, ...]  # user stack frames, outermost first
    scope: tuple[str, ...] = ()  # names of inlined call frames (e.g. remat)

    def __repr__(self) -> str:  # pragma: no cover - debugging aid
        return f"OpNode({self.idx}:{self.primitive})"


@dataclasses.dataclass
class TensorEdge:
    """One tensor (jaxpr variable) in the graph."""

    tid: int
    shape: tuple[int, ...]
    dtype: str
    producer: int | None = None        # OpNode idx, None for graph inputs
    consumers: list[int] = dataclasses.field(default_factory=list)
    is_input: bool = False
    is_output: bool = False
    is_const: bool = False

    @property
    def nbytes(self) -> int:
        return int(np.prod(self.shape, dtype=np.int64)) * np.dtype(self.dtype).itemsize


@dataclasses.dataclass
class OpGraph:
    """Operator-level computational graph of one traced program."""

    name: str
    nodes: list[OpNode]
    tensors: dict[int, TensorEdge]
    inputs: list[int]                   # tensor ids in call order
    outputs: list[int]
    closed_jaxpr: ClosedJaxpr | None = None
    # Memoized flattened extraction of closed_jaxpr.  Graphs built by
    # extract_graph ARE their own flattening, so repeated instrumented runs
    # (multi-sample capture, ReplayProfiler) never re-extract.
    _flat_cache: "OpGraph | None" = dataclasses.field(
        default=None, repr=False, compare=False)
    # Flat tid-space program recorded during extraction: one leaf equation
    # per node (aligned with ``nodes``), the concrete values of every
    # const/literal tensor, and per-node mesh axis sizes (for collectives
    # inlined out of shard_map bodies).  None for graphs rebuilt from
    # persisted artifacts — those cannot execute anyway.
    _eqns: "list | None" = dataclasses.field(
        default=None, repr=False, compare=False)
    _const_vals: "dict[int, Any] | None" = dataclasses.field(
        default=None, repr=False, compare=False)
    _node_axis_sizes: "list[dict[str, int]] | None" = dataclasses.field(
        default=None, repr=False, compare=False)
    # Memoized BlockStructure (per-node digests + repeated-block families).
    _block_cache: "BlockStructure | None" = dataclasses.field(
        default=None, repr=False, compare=False)

    def flat_graph(self) -> "OpGraph":
        """The flattened (inline_calls=True) extraction of this graph's jaxpr.

        Memoized on the instance: extract_graph() seeds the cache with the
        graph itself, and manually constructed OpGraphs pay the extraction
        cost exactly once instead of on every instrumented run.
        """
        if self._flat_cache is None:
            if self.closed_jaxpr is None:
                raise ValueError("OpGraph was built without a ClosedJaxpr")
            self._flat_cache = extract_graph(self.closed_jaxpr, name=self.name,
                                             inline_calls=True)
        return self._flat_cache

    # ---- structural helpers -------------------------------------------------
    def successors(self, node_idx: int) -> list[int]:
        out: list[int] = []
        for tid in self.nodes[node_idx].outvars:
            out.extend(self.tensors[tid].consumers)
        return sorted(set(out))

    def predecessors(self, node_idx: int) -> list[int]:
        out: list[int] = []
        for tid in self.nodes[node_idx].invars:
            p = self.tensors[tid].producer
            if p is not None:
                out.append(p)
        return sorted(set(out))

    def topo_order(self) -> list[int]:
        # jaxpr equations are already topologically sorted.
        return list(range(len(self.nodes)))

    def subgraph_nodes_between(self, src_tids: set[int], dst_tids: set[int]) -> list[int]:
        """Node idxs on any path from the src tensors to the dst tensors.

        Traversal does NOT stop at frontier tensors: a sink tensor may have
        further consumers that feed *another* sink (multi-output graphs), and
        those nodes belong to the region too.  Because the graph is a DAG,
        the fwd∩bwd intersection still yields exactly the between-set.

        The backward sweep runs first (it is bounded by the src frontier) and
        the forward sweep only expands inside the backward set: every node on
        a src→dst path is backward-reachable from dst, so restricting the
        forward frontier this way keeps each region query O(|region|) instead
        of walking the whole downstream graph.

        Large graphs run the same two sweeps as C-speed sparse BFS over the
        memoized edge arrays (scipy); the python implementation below is the
        semantic reference and the fallback, and a dedicated test asserts the
        two agree.
        """
        if _bfs_order is not None and len(self.nodes) >= 512:
            try:
                return self._between_sparse(src_tids, dst_tids)
            except Exception:  # pragma: no cover - defensive fallback
                pass
        # backward reachable from dst (stops at src tensors)
        bwd: set[int] = set()
        frontier = [self.tensors[t].producer for t in dst_tids
                    if self.tensors[t].producer is not None]
        while frontier:
            n = frontier.pop()
            if n is None or n in bwd:
                continue
            bwd.add(n)
            for tid in self.nodes[n].invars:
                if tid in src_tids:
                    continue
                p = self.tensors[tid].producer
                if p is not None:
                    frontier.append(p)
        # forward reachable from src, restricted to the backward set
        fwd: set[int] = set()
        frontier = [c for t in src_tids for c in self.tensors[t].consumers
                    if c in bwd]
        while frontier:
            n = frontier.pop()
            if n in fwd:
                continue
            fwd.add(n)
            for tid in self.nodes[n].outvars:
                frontier.extend(c for c in self.tensors[tid].consumers
                                if c in bwd)
        return sorted(fwd)

    def _between_sparse(self, src_tids: set[int], dst_tids: set[int]) -> list[int]:
        """C-speed ``subgraph_nodes_between`` (identical semantics).

        bwd = nodes reaching a dst producer over reversed edges that avoid
        src tensors; fwd = nodes reachable from the src tensors' consumers
        through edges whose BOTH endpoints lie in bwd.  Multi-source BFS is
        expressed with a virtual root (row ``n``) fanning out to the seeds.
        """
        e_p, e_c, e_t, _, _ = edge_arrays(self)
        n = len(self.nodes)
        seeds_b = {self.tensors[t].producer for t in dst_tids}
        seeds_b.discard(None)
        if not seeds_b:
            return []
        seeds_b_arr = np.fromiter(seeds_b, dtype=np.int32)
        if src_tids:
            keep = ~np.isin(e_t, np.fromiter(src_tids, dtype=np.int32))
            rp, rc = e_p[keep], e_c[keep]
        else:
            rp, rc = e_p, e_c
        rows = np.concatenate([rc, np.full(len(seeds_b_arr), n, np.int32)])
        cols = np.concatenate([rp, seeds_b_arr])
        m = _sparse.csr_matrix(
            (np.ones(len(rows), np.int8), (rows, cols)), shape=(n + 1, n + 1))
        order = _bfs_order(m, n, directed=True, return_predecessors=False)
        bwd = np.zeros(n + 1, dtype=bool)
        bwd[order] = True
        bwd[n] = False

        seeds_f = {c for t in src_tids for c in self.tensors[t].consumers
                   if bwd[c]}
        if not seeds_f:
            return []
        seeds_f_arr = np.fromiter(seeds_f, dtype=np.int32)
        keep = bwd[e_p] & bwd[e_c]
        rows = np.concatenate([e_p[keep], np.full(len(seeds_f_arr), n, np.int32)])
        cols = np.concatenate([e_c[keep], seeds_f_arr])
        m = _sparse.csr_matrix(
            (np.ones(len(rows), np.int8), (rows, cols)), shape=(n + 1, n + 1))
        order = _bfs_order(m, n, directed=True, return_predecessors=False)
        return sorted(int(v) for v in order if v != n)


def edge_arrays(graph: OpGraph) -> tuple[np.ndarray, ...]:
    """Flat int32 edge/outvar arrays for ``graph``, memoized on the instance.

    ``(e_p, e_c, e_t)`` hold one row per producer->consumer tensor edge
    (producer node, consumer node, tensor id); ``(o_n, o_t)`` hold one row
    per node outvar.  These back the C-speed region BFS and the piecewise
    dominator sweep — graphs are immutable, so the cache never invalidates.
    """
    cached = getattr(graph, "_edge_arrays_cache", None)
    if cached is not None:
        return cached
    e_p: list[int] = []
    e_c: list[int] = []
    e_t: list[int] = []
    o_n: list[int] = []
    o_t: list[int] = []
    tensors = graph.tensors
    for node in graph.nodes:
        for t in node.outvars:
            o_n.append(node.idx)
            o_t.append(t)
            for c in tensors[t].consumers:
                e_p.append(node.idx)
                e_c.append(c)
                e_t.append(t)
    out = (np.asarray(e_p, dtype=np.int32), np.asarray(e_c, dtype=np.int32),
           np.asarray(e_t, dtype=np.int32), np.asarray(o_n, dtype=np.int32),
           np.asarray(o_t, dtype=np.int32))
    graph._edge_arrays_cache = out
    return out


def _call_path(eqn, max_frames: int = 12) -> tuple[str, ...]:
    """User-code call path of an equation, outermost first."""
    si = eqn.source_info
    tb = getattr(si, "traceback", None)
    if tb is None:
        return ()
    try:
        import jax._src.source_info_util as siu
        frames = list(siu.user_frames(tb))
    except Exception:
        frames = [f for f in tb.frames
                  if "site-packages/jax" not in f.file_name]
    out = []
    for f in frames[:max_frames]:
        fname = f.file_name.rsplit("/", 1)[-1]
        line = getattr(f, "start_line", None) or getattr(f, "line_num", 0)
        out.append(f"{fname}:{f.function_name}:{line}")
    # user_frames yields innermost first; we want outermost first so common
    # prefixes correspond to shared high-level call sites (Algorithm 2).
    return tuple(reversed(out))


def extract_graph(closed_jaxpr: ClosedJaxpr, *, name: str = "graph",
                  inline_calls: bool = True) -> OpGraph:
    """Build an OpGraph from a ClosedJaxpr, optionally inlining call prims."""

    nodes: list[OpNode] = []
    tensors: dict[int, TensorEdge] = {}
    var_ids: dict[Any, int] = {}
    next_tid = [0]
    eqn_list: list[Any] = []                 # leaf eqn per node, node order
    const_vals: dict[int, Any] = {}          # const/literal tid -> value
    node_axes: list[dict[str, int]] = []     # per node mesh axis sizes

    def tid_for(v, *, scope_suffix: str = "") -> int:
        key = (id(v), scope_suffix)
        if key not in var_ids:
            t = next_tid[0]
            next_tid[0] += 1
            var_ids[key] = t
            aval = v.aval
            tensors[t] = TensorEdge(
                tid=t, shape=tuple(getattr(aval, "shape", ())),
                dtype=str(getattr(aval, "dtype", "float32")))
        return var_ids[key]

    def lit_tid(v) -> int:
        t = next_tid[0]
        next_tid[0] += 1
        arr = np.asarray(v.val)
        tensors[t] = TensorEdge(tid=t, shape=tuple(arr.shape), dtype=str(arr.dtype),
                                is_const=True)
        const_vals[t] = v.val
        return t

    def walk(jaxpr: Jaxpr, env: dict[Var, int], scope: tuple[str, ...],
             axes: dict[str, int]):
        for eqn in jaxpr.eqns:
            prim = eqn.primitive.name
            inner = _nested_jaxpr(eqn) if inline_calls else None
            if inner is not None and prim in _INLINE_PRIMITIVES:
                # Inline: map callee invars to caller tensor ids.
                inner_env: dict[Var, int] = {}
                for cv, cval in zip(inner.jaxpr.constvars, inner.consts):
                    t = next_tid[0]
                    next_tid[0] += 1
                    arr = np.asarray(cval) if not hasattr(cval, "aval") else cval
                    tensors[t] = TensorEdge(
                        tid=t, shape=tuple(np.shape(arr)), dtype=str(np.asarray(arr).dtype)
                        if not hasattr(arr, "dtype") else str(arr.dtype), is_const=True)
                    const_vals[t] = cval
                    inner_env[cv] = t
                for iv, outer_v in zip(inner.jaxpr.invars, eqn.invars):
                    inner_env[iv] = (lit_tid(outer_v) if isinstance(outer_v, Literal)
                                     else env[outer_v])
                sub_scope = scope + (prim,)
                sub_axes = axes
                if prim == "shard_map":
                    mesh = eqn.params.get("mesh")
                    if mesh is not None:
                        sub_axes = dict(axes)
                        sub_axes.update({str(k): int(v)
                                         for k, v in mesh.shape.items()})
                walk(inner.jaxpr, inner_env, sub_scope, sub_axes)
                for ov, inner_ov in zip(eqn.outvars, inner.jaxpr.outvars):
                    if isinstance(inner_ov, Literal):
                        env[ov] = lit_tid(inner_ov)
                    else:
                        env[ov] = inner_env[inner_ov]
                continue

            in_tids = [lit_tid(v) if isinstance(v, Literal) else env[v]
                       for v in eqn.invars]
            out_tids = []
            for v in eqn.outvars:
                t = next_tid[0]
                next_tid[0] += 1
                aval = v.aval
                tensors[t] = TensorEdge(tid=t, shape=tuple(getattr(aval, "shape", ())),
                                        dtype=str(getattr(aval, "dtype", "float32")))
                env[v] = t
                out_tids.append(t)

            idx = len(nodes)
            node = OpNode(idx=idx, primitive=prim, params=dict(eqn.params),
                          invars=in_tids, outvars=out_tids,
                          call_path=_call_path(eqn), scope=scope)
            nodes.append(node)
            eqn_list.append(eqn)
            node_axes.append(axes)
            for t in in_tids:
                tensors[t].consumers.append(idx)
            for t in out_tids:
                tensors[t].producer = idx

    env: dict[Var, int] = {}
    jaxpr = closed_jaxpr.jaxpr
    for cv, cval in zip(jaxpr.constvars, closed_jaxpr.consts):
        t = next_tid[0]
        next_tid[0] += 1
        shape = tuple(np.shape(cval))
        dtype = str(cval.dtype) if hasattr(cval, "dtype") else str(np.asarray(cval).dtype)
        tensors[t] = TensorEdge(tid=t, shape=shape, dtype=dtype, is_const=True)
        const_vals[t] = cval
        env[cv] = t
    inputs = []
    for iv in jaxpr.invars:
        t = next_tid[0]
        next_tid[0] += 1
        aval = iv.aval
        tensors[t] = TensorEdge(tid=t, shape=tuple(getattr(aval, "shape", ())),
                                dtype=str(getattr(aval, "dtype", "float32")),
                                is_input=True)
        env[iv] = t
        inputs.append(t)

    walk(jaxpr, env, (), {})

    outputs = []
    for ov in jaxpr.outvars:
        t = lit_tid(ov) if isinstance(ov, Literal) else env[ov]
        tensors[t].is_output = True
        outputs.append(t)

    g = OpGraph(name=name, nodes=nodes, tensors=tensors, inputs=inputs,
                outputs=outputs, closed_jaxpr=closed_jaxpr,
                _eqns=eqn_list, _const_vals=const_vals,
                _node_axis_sizes=node_axes)
    if inline_calls:
        g._flat_cache = g   # the extraction is its own flattening
    return g


def trace(fn: Callable, *example_args, name: str | None = None,
          inline_calls: bool = True, **example_kwargs) -> OpGraph:
    """Trace ``fn`` on example args and return its operator graph."""
    closed = jax.make_jaxpr(fn)(*example_args, **example_kwargs)
    return extract_graph(closed, name=name or getattr(fn, "__name__", "graph"),
                         inline_calls=inline_calls)


# ---------------------------------------------------------------------------
# block-isomorphism detection (hierarchical matching substrate)
# ---------------------------------------------------------------------------
#
# Production graphs repeat one transformer layer 10-160x.  Each node gets two
# canonical digests:
#
#   * op_digest     — the node's *semantics*: primitive name, canonically
#     tokenized params (nested jaxprs fingerprinted structurally, arrays by
#     value hash, unknown objects by identity so collisions are impossible),
#     and input/output shapes/dtypes.  Two nodes with equal op_digests and
#     bitwise-identical inputs produce bitwise-identical outputs (the twin-
#     propagation invariant core/block_match.py relies on).
#   * struct_digest — op_digest plus local wiring: relative producer offsets
#     for internal edges, value digests for const/literal inputs, tensor ids
#     for shared graph inputs.  Periodic runs of equal struct_digests are
#     repeated layer blocks.
#
# ``block_structure`` rolls the struct_digest sequence into BlockFamily spans
# (start, period, count) used by the fused block capture (interp.py), twin
# stamping (block_match.py) and region memoization (subgraph_match.py).

_MIN_REPEATS = 3        # a family needs >= 3 repeats to be worth stamping
_MIN_SPAN = 6           # ... and >= 6 nodes total
_MAX_PERIOD = 2048


def _value_digest(v) -> str:
    a = np.asarray(v)
    h = hashlib.sha256(np.ascontiguousarray(a).tobytes()).hexdigest()
    return f"{a.dtype}:{a.shape}:{h}"


def _jaxpr_fingerprint(jaxpr: Jaxpr, consts: tuple, memo: dict) -> str:
    """Structural fingerprint of a nested jaxpr: canonical var numbering,
    exact literal/const value hashes — no reliance on pretty-printed floats."""
    key = id(jaxpr)
    hit = memo.get(key)
    if hit is not None:
        return hit
    varid: dict[Any, int] = {}

    def vid(v) -> str:
        if isinstance(v, Literal):
            return "lit:" + _value_digest(v.val)
        if v not in varid:
            varid[v] = len(varid)
        return f"v{varid[v]}:{v.aval}"

    parts = ["in:" + ",".join(vid(v) for v in
                              list(jaxpr.constvars) + list(jaxpr.invars))]
    for eqn in jaxpr.eqns:
        ptok = ",".join(f"{k}={_param_token(p, memo)}"
                        for k, p in sorted(eqn.params.items()))
        parts.append(f"{eqn.primitive.name}[{ptok}]"
                     f"({','.join(vid(v) for v in eqn.invars)})->"
                     f"({','.join(vid(v) for v in eqn.outvars)})")
    parts.append("out:" + ",".join(vid(v) for v in jaxpr.outvars))
    for c in consts:
        parts.append("const:" + _param_token(c, memo))
    fp = "jaxpr:" + hashlib.sha256("|".join(parts).encode()).hexdigest()
    memo[key] = fp
    return fp


def _param_token(v, memo: dict) -> str:
    """Canonical token for one equation param.

    Conservative by construction: objects we cannot canonicalize get an
    identity-unique token, so unequal params can never alias — a digest
    collision would let the matcher stamp a false equivalence.
    """
    import enum
    if v is None or isinstance(v, (bool, int, str, bytes)):
        return repr(v)
    if isinstance(v, float):
        return repr(v)
    if isinstance(v, enum.Enum):
        return f"{type(v).__qualname__}.{v.name}"
    if isinstance(v, ClosedJaxpr):
        return _jaxpr_fingerprint(v.jaxpr, tuple(v.consts), memo)
    if isinstance(v, Jaxpr):
        return _jaxpr_fingerprint(v, (), memo)
    if isinstance(v, (tuple, list)):
        return "(" + ",".join(_param_token(x, memo) for x in v) + ")"
    if isinstance(v, dict):
        items = sorted(v.items(), key=lambda kv: str(kv[0]))
        return "{" + ",".join(f"{k}:{_param_token(x, memo)}"
                              for k, x in items) + "}"
    if isinstance(v, np.dtype):
        return f"dtype[{v}]"
    if isinstance(v, type):
        try:
            return f"dtype[{np.dtype(v)}]"
        except TypeError:
            return f"type[{v.__module__}.{v.__qualname__}]"
    if isinstance(v, (np.ndarray, np.generic)):
        return "arr:" + _value_digest(v)
    if hasattr(v, "dtype") and hasattr(v, "shape") and hasattr(v, "__array__"):
        return "arr:" + _value_digest(v)        # jax arrays in params
    r = repr(v)
    if " at 0x" in r or " object at" in r:
        return f"!opaque:{type(v).__module__}.{type(v).__qualname__}:{id(v)}"
    return f"{type(v).__name__}:{r}"


@dataclasses.dataclass
class BlockFamily:
    """One repeated-block span: nodes [start, start + period*count)."""

    start: int
    period: int
    count: int
    digest: str                 # combined struct_digest of one block

    @property
    def end(self) -> int:
        return self.start + self.period * self.count

    def window(self, repeat: int) -> tuple[int, int]:
        lo = self.start + repeat * self.period
        return lo, lo + self.period


@dataclasses.dataclass
class BlockStructure:
    """Per-node digests + repeated-block families of one graph."""

    graph: OpGraph
    op_digests: list[str]
    struct_digests: list[str]
    families: list[BlockFamily]
    # node idx -> (family idx, repeat, offset within block)
    node_family: dict[int, tuple[int, int, int]]
    _const_digests: dict[int, str] = dataclasses.field(default_factory=dict)

    def const_digest(self, tid: int) -> str:
        """Value digest of a const/literal tensor (identity token when the
        value is unavailable, e.g. graphs rebuilt from persisted artifacts)."""
        d = self._const_digests.get(tid)
        if d is None:
            vals = self.graph._const_vals or {}
            v = vals.get(tid)
            d = f"c?:{tid}" if v is None else _value_digest(v)
            self._const_digests[tid] = d
        return d

    def locate_node(self, idx: int) -> tuple[int, int, int] | None:
        return self.node_family.get(idx)

    def locate_tid(self, tid: int) -> tuple[int, int, int, int] | None:
        """(family, repeat, block offset, outvar slot) of a produced tensor,
        or None when its producer is outside every family."""
        p = self.graph.tensors[tid].producer
        if p is None:
            return None
        loc = self.node_family.get(p)
        if loc is None:
            return None
        return loc + (self.graph.nodes[p].outvars.index(tid),)

    def coverage(self) -> float:
        covered = sum(f.period * f.count for f in self.families)
        return covered / max(len(self.graph.nodes), 1)


def _find_families(struct: list[str]) -> list[BlockFamily]:
    """Greedy periodic-run detection over the struct_digest sequence.

    Candidate periods come from the distance between consecutive occurrences
    of equal digests; smaller periods are claimed first (a period-p layer
    stack also matches period 2p — we want the maximal repeat count)."""
    n = len(struct)
    last: dict[str, int] = {}
    gaps: dict[int, int] = {}
    for i, d in enumerate(struct):
        j = last.get(d)
        if j is not None and i - j <= _MAX_PERIOD:
            g = i - j
            gaps[g] = gaps.get(g, 0) + 1
        last[d] = i
    periods = sorted(sorted(gaps, key=lambda p: -gaps[p])[:8])

    claimed = np.zeros(n, dtype=bool)
    families: list[BlockFamily] = []
    for p in periods:
        if p < 1:
            continue
        i = p
        while i < n:
            if (claimed[i] or claimed[i - p] or struct[i] != struct[i - p]):
                i += 1
                continue
            s = i - p
            e = i
            while (e < n and not claimed[e] and not claimed[e - p]
                   and struct[e] == struct[e - p]):
                e += 1
            # canonical phase anchoring: a run's start is wherever
            # periodicity happened to begin, so two graphs sharing the
            # same repeated content can carve rotated (incompatible)
            # windows — e.g. a program and its single-block rewrite,
            # whose post-rewrite run starts mid-layer.  Re-anchor on the
            # lexicographically least rotation of the period content:
            # the window phase becomes a pure function of the CONTENT,
            # so the block-evidence cache (core/block_cache.py) keys
            # align across such graphs.  Costs at most one repeat.
            s += min(range(p), key=lambda o: struct[s + o:s + o + p])
            count = (e - s) // p
            # trim any partial overlap with an earlier family
            while count >= _MIN_REPEATS and claimed[s:s + count * p].any():
                count -= 1
            if count >= _MIN_REPEATS and count * p >= _MIN_SPAN:
                digest = hashlib.sha256(
                    "".join(struct[s:s + p]).encode()).hexdigest()
                families.append(BlockFamily(start=s, period=p, count=count,
                                            digest=digest))
                claimed[s:s + count * p] = True
            i = e + 1
    families.sort(key=lambda f: f.start)
    return families


def block_structure(graph: OpGraph) -> BlockStructure:
    """Digests + block families of ``graph`` (memoized on the instance)."""
    if graph._block_cache is not None:
        return graph._block_cache
    tensors = graph.tensors
    jmemo: dict = {}
    cdig: dict[int, str] = {}
    const_vals = graph._const_vals or {}

    def const_digest(t: int) -> str:
        d = cdig.get(t)
        if d is None:
            v = const_vals.get(t)
            d = f"c?:{t}" if v is None else _value_digest(v)
            cdig[t] = d
        return d

    axes_list = graph._node_axis_sizes
    op_digests: list[str] = []
    struct_digests: list[str] = []
    for node in graph.nodes:
        ptoks = ",".join(f"{k}={_param_token(v, jmemo)}"
                         for k, v in sorted(node.params.items()))
        ind = ",".join(f"{tensors[t].shape}:{tensors[t].dtype}"
                       for t in node.invars)
        outd = ",".join(f"{tensors[t].shape}:{tensors[t].dtype}"
                        for t in node.outvars)
        ax = ""
        if axes_list is not None and node.idx < len(axes_list) \
                and axes_list[node.idx]:
            ax = repr(sorted(axes_list[node.idx].items()))
        op = hashlib.sha256(
            f"{node.primitive}[{ptoks}]({ind})->({outd})@{ax}"
            .encode()).hexdigest()
        op_digests.append(op)
        ctx: list[str] = []
        for t in node.invars:
            e = tensors[t]
            if e.producer is not None:
                ctx.append(f"r{node.idx - e.producer}")
            elif e.is_const:
                ctx.append("c" + const_digest(t))
            else:
                ctx.append(f"i{t}")
        struct_digests.append(hashlib.sha256(
            (op + ";" + ",".join(ctx)).encode()).hexdigest())

    families = _find_families(struct_digests)
    node_family: dict[int, tuple[int, int, int]] = {}
    for fi, fam in enumerate(families):
        for r in range(fam.count):
            base = fam.start + r * fam.period
            for o in range(fam.period):
                node_family[base + o] = (fi, r, o)

    bs = BlockStructure(graph=graph, op_digests=op_digests,
                        struct_digests=struct_digests, families=families,
                        node_family=node_family, _const_digests=cdig)
    graph._block_cache = bs
    return bs
