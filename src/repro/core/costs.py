"""Per-operator cost rules: FLOPs, HBM bytes, ICI bytes for each primitive.

These rules price the *operator-level* execution model: each operator reads
its inputs from and writes its outputs to HBM.  That is exactly the execution
model of the eager frameworks the paper profiles, and it is what makes
differential energy debugging work — e.g. the unfused 5-op GELU pays five HBM
round-trips while the fused Pallas kernel pays one (paper case hf-39073).

``pallas_call`` nodes are priced as a single fused pass (inputs + outputs
once); higher-order nodes (scan/while/cond) are priced by recursing into
their body and multiplying by the trip count.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Callable

import numpy as np
from jax._src.core import ClosedJaxpr, Jaxpr

from repro.core.graph import OpGraph, OpNode


@dataclasses.dataclass
class OpCost:
    flops: float = 0.0
    hbm_bytes: float = 0.0
    ici_bytes: float = 0.0
    fp32_fraction: float = 0.0   # fraction of flops running in fp32-accurate mode
    notes: str = ""

    def __add__(self, other: "OpCost") -> "OpCost":
        tot = self.flops + other.flops
        frac = 0.0
        if tot > 0:
            frac = (self.flops * self.fp32_fraction + other.flops * other.fp32_fraction) / tot
        return OpCost(tot, self.hbm_bytes + other.hbm_bytes,
                      self.ici_bytes + other.ici_bytes, frac)

    def scaled(self, k: float) -> "OpCost":
        return OpCost(self.flops * k, self.hbm_bytes * k, self.ici_bytes * k,
                      self.fp32_fraction, self.notes)


def _numel(shape) -> int:
    return int(np.prod(shape, dtype=np.int64)) if shape else 1


def _itemsize(dtype: str) -> int:
    try:
        return np.dtype(dtype).itemsize
    except TypeError:
        return 2 if "bfloat16" in str(dtype) else 4


def _tensor_bytes(shape, dtype) -> int:
    return _numel(shape) * _itemsize(dtype)


def _io_bytes(graph: OpGraph, node: OpNode) -> float:
    b = 0.0
    for t in node.invars:
        e = graph.tensors[t]
        b += _tensor_bytes(e.shape, e.dtype)
    for t in node.outvars:
        e = graph.tensors[t]
        b += _tensor_bytes(e.shape, e.dtype)
    return b


def _out_numel(graph: OpGraph, node: OpNode) -> int:
    return sum(_numel(graph.tensors[t].shape) for t in node.outvars)


def _in_numel(graph: OpGraph, node: OpNode) -> int:
    return sum(_numel(graph.tensors[t].shape) for t in node.invars)


# ---------------------------------------------------------------------------
# rule registry
# ---------------------------------------------------------------------------

CostRule = Callable[[OpGraph, OpNode], OpCost]
_RULES: dict[str, CostRule] = {}


def rule(*names: str):
    def deco(fn: CostRule):
        for n in names:
            _RULES[n] = fn
        return fn
    return deco


def _is_highest_precision(params: dict[str, Any]) -> bool:
    prec = params.get("precision")
    if prec is None:
        return False
    return "HIGHEST" in str(prec).upper()


@rule("dot_general")
def _dot_general(graph: OpGraph, node: OpNode) -> OpCost:
    lhs = graph.tensors[node.invars[0]]
    dnums = node.params["dimension_numbers"]
    (lc, _rc), (lb, _rb) = dnums
    m_dims = [d for d in range(len(lhs.shape)) if d not in set(lc) | set(lb)]
    k = _numel([lhs.shape[d] for d in lc])
    b = _numel([lhs.shape[d] for d in lb])
    m = _numel([lhs.shape[d] for d in m_dims])
    out = graph.tensors[node.outvars[0]]
    n = max(1, _numel(out.shape) // max(1, b * m))
    flops = 2.0 * b * m * n * k
    fp32 = 1.0 if (_is_highest_precision(node.params)
                   and "bfloat16" in (lhs.dtype,)) or (
        _is_highest_precision(node.params)) else 0.0
    return OpCost(flops=flops, hbm_bytes=_io_bytes(graph, node), fp32_fraction=fp32)


@rule("conv_general_dilated")
def _conv(graph: OpGraph, node: OpNode) -> OpCost:
    lhs = graph.tensors[node.invars[0]]
    rhs = graph.tensors[node.invars[1]]
    out = graph.tensors[node.outvars[0]]
    groups = node.params.get("feature_group_count", 1)
    # flops = 2 * out_numel * (k_spatial * C_in / groups)
    kernel_numel = _numel(rhs.shape)
    # kernel shape includes C_out; per-output-element MACs = kernel_numel / C_out
    dn = node.params.get("dimension_numbers")
    c_out = max(1, rhs.shape[dn.rhs_spec[0]] if dn is not None else rhs.shape[-1])
    flops = 2.0 * _numel(out.shape) * (kernel_numel / c_out)
    del lhs, groups
    return OpCost(flops=flops, hbm_bytes=_io_bytes(graph, node),
                  fp32_fraction=1.0 if _is_highest_precision(node.params) else 0.0)


_UNARY_CHEAP = ("neg", "abs", "sign", "floor", "ceil", "round", "is_finite",
                "not", "real", "imag", "copy", "population_count", "clz",
                "stop_gradient")
_UNARY_TRANS = ("exp", "log", "log1p", "expm1", "tanh", "sin", "cos", "tan",
                "asin", "acos", "atan", "sinh", "cosh", "erf", "erfc",
                "erf_inv", "rsqrt", "sqrt", "cbrt", "logistic", "exp2")
_BINARY = ("add", "sub", "mul", "div", "max", "min", "pow", "atan2", "rem",
           "and", "or", "xor", "shift_left", "shift_right_logical",
           "shift_right_arithmetic", "nextafter", "complex")
_COMPARE = ("eq", "ne", "lt", "le", "gt", "ge")


def _elementwise(factor: float) -> CostRule:
    def fn(graph: OpGraph, node: OpNode) -> OpCost:
        return OpCost(flops=factor * _out_numel(graph, node),
                      hbm_bytes=_io_bytes(graph, node))
    return fn


for _n in _UNARY_CHEAP:
    _RULES[_n] = _elementwise(1.0)
for _n in _UNARY_TRANS:
    _RULES[_n] = _elementwise(4.0)   # transcendental ≈ 4 VPU flops/elem
for _n in _BINARY:
    _RULES[_n] = _elementwise(1.0)
for _n in _COMPARE:
    _RULES[_n] = _elementwise(1.0)
_RULES["select_n"] = _elementwise(1.0)
_RULES["clamp"] = _elementwise(2.0)
_RULES["square"] = _elementwise(1.0)


@rule("integer_pow")
def _integer_pow(graph: OpGraph, node: OpNode) -> OpCost:
    y = abs(int(node.params.get("y", 2)))
    mults = max(1, math.ceil(math.log2(max(y, 2))))
    return OpCost(flops=mults * _out_numel(graph, node),
                  hbm_bytes=_io_bytes(graph, node))


# --- data movement (zero/low flops, bytes dominate) -------------------------
_MOVEMENT = ("reshape", "transpose", "broadcast_in_dim", "concatenate", "pad",
             "slice", "dynamic_slice", "dynamic_update_slice", "rev",
             "convert_element_type", "bitcast_convert_type", "squeeze",
             "expand_dims", "gather", "scatter", "scatter-add", "scatter_add",
             "iota", "reduce_precision", "copy_p", "device_put", "split",
             "optimization_barrier")


def _movement_rule(graph: OpGraph, node: OpNode) -> OpCost:
    return OpCost(flops=0.0, hbm_bytes=_io_bytes(graph, node))


for _n in _MOVEMENT:
    _RULES[_n] = _movement_rule

# reshape on contiguous data is free in XLA; but at operator granularity a
# standalone reshape of already-materialized data is metadata-only.  We price
# reshape/squeeze/expand_dims at zero bytes to avoid penalizing views.
def _view_rule(graph: OpGraph, node: OpNode) -> OpCost:
    return OpCost(0.0, 0.0)


for _n in ("reshape", "squeeze", "expand_dims"):
    _RULES[_n] = _view_rule


# In-place / windowed ops touch only the window, not the whole operand —
# XLA updates donated buffers in place.  This is the distinction that case
# c2 (vllm-10811: decode cache updated via full-copy concatenate instead of
# an in-place slice update) relies on.
@rule("dynamic_update_slice")
def _dus(graph: OpGraph, node: OpNode) -> OpCost:
    upd = graph.tensors[node.invars[1]]
    b = _tensor_bytes(upd.shape, upd.dtype)
    return OpCost(flops=0.0, hbm_bytes=2.0 * b, notes="in-place window update")


@rule("dynamic_slice")
def _ds(graph: OpGraph, node: OpNode) -> OpCost:
    out = graph.tensors[node.outvars[0]]
    b = _tensor_bytes(out.shape, out.dtype)
    return OpCost(flops=0.0, hbm_bytes=2.0 * b, notes="windowed read")


@rule("gather")
def _gather(graph: OpGraph, node: OpNode) -> OpCost:
    out_b = sum(_tensor_bytes(graph.tensors[t].shape, graph.tensors[t].dtype)
                for t in node.outvars)
    idx = graph.tensors[node.invars[1]]
    idx_b = _tensor_bytes(idx.shape, idx.dtype)
    return OpCost(flops=0.0, hbm_bytes=2.0 * out_b + idx_b,
                  notes="gathered elements only")


@rule("scatter", "scatter-add", "scatter_add")
def _scatter(graph: OpGraph, node: OpNode) -> OpCost:
    upd = graph.tensors[node.invars[2]] if len(node.invars) > 2 else \
        graph.tensors[node.invars[-1]]
    b = _tensor_bytes(upd.shape, upd.dtype)
    return OpCost(flops=float(_numel(upd.shape)), hbm_bytes=3.0 * b,
                  notes="scattered window only")


_REDUCE = ("reduce_sum", "reduce_max", "reduce_min", "reduce_prod",
           "reduce_and", "reduce_or", "argmax", "argmin", "reduce")


def _reduce_rule(graph: OpGraph, node: OpNode) -> OpCost:
    return OpCost(flops=float(_in_numel(graph, node)),
                  hbm_bytes=_io_bytes(graph, node))


for _n in _REDUCE:
    _RULES[_n] = _reduce_rule


@rule("cumsum", "cumprod", "cummax", "cummin", "cumlogsumexp")
def _cumulative(graph: OpGraph, node: OpNode) -> OpCost:
    return OpCost(flops=float(_in_numel(graph, node)),
                  hbm_bytes=_io_bytes(graph, node))


@rule("sort")
def _sort(graph: OpGraph, node: OpNode) -> OpCost:
    e = graph.tensors[node.invars[0]]
    dim = node.params.get("dimension", len(e.shape) - 1)
    n = e.shape[dim] if e.shape else 1
    passes = max(1.0, math.log2(max(n, 2)))
    # bitonic-style sort: ~log^2 passes of compare/exchange through memory
    return OpCost(flops=_in_numel(graph, node) * passes,
                  hbm_bytes=_io_bytes(graph, node) * passes,
                  notes="multi-pass sort")


@rule("top_k")
def _top_k(graph: OpGraph, node: OpNode) -> OpCost:
    k = node.params.get("k", 1)
    n_in = _in_numel(graph, node)
    return OpCost(flops=n_in * max(1.0, math.log2(max(k, 2))),
                  hbm_bytes=_io_bytes(graph, node))


@rule("random_bits", "random_seed", "random_wrap", "random_fold_in", "random_unwrap",
      "threefry2x32")
def _rng(graph: OpGraph, node: OpNode) -> OpCost:
    return OpCost(flops=8.0 * _out_numel(graph, node),
                  hbm_bytes=_io_bytes(graph, node))


@rule("fft")
def _fft(graph: OpGraph, node: OpNode) -> OpCost:
    e = graph.tensors[node.invars[0]]
    lens = node.params.get("fft_lengths", (e.shape[-1],))
    n = _numel(lens)
    batch = max(1, _numel(e.shape) // max(1, n))
    return OpCost(flops=5.0 * batch * n * max(1.0, math.log2(max(n, 2))),
                  hbm_bytes=_io_bytes(graph, node))


# --- collectives (shard_map-level) ------------------------------------------
def _collective_rule(scale: float) -> CostRule:
    def fn(graph: OpGraph, node: OpNode) -> OpCost:
        b = sum(_tensor_bytes(graph.tensors[t].shape, graph.tensors[t].dtype)
                for t in node.outvars)
        return OpCost(flops=0.0, hbm_bytes=b, ici_bytes=scale * b)
    return fn


_RULES["psum"] = _collective_rule(2.0)          # ring all-reduce ≈ 2× data
_RULES["psum2"] = _collective_rule(2.0)          # JAX 0.4.x shard_map name
_RULES["psum_invariant"] = _collective_rule(2.0)  # JAX>=0.7 shard_map name
_RULES["pmean"] = _collective_rule(2.0)
_RULES["pmax"] = _collective_rule(2.0)
_RULES["pmin"] = _collective_rule(2.0)
_RULES["all_gather"] = _collective_rule(1.0)
_RULES["all_gather_invariant"] = _collective_rule(1.0)
_RULES["reduce_scatter"] = _collective_rule(1.0)
_RULES["all_to_all"] = _collective_rule(1.0)
_RULES["ppermute"] = _collective_rule(1.0)
_RULES["psum_scatter"] = _collective_rule(1.0)
_RULES["pvary"] = _view_rule                     # replication annotation only
_RULES["pbroadcast"] = _view_rule                # replication annotation only
_RULES["layout_constraint"] = _view_rule         # layout annotation only


# --- higher-order ------------------------------------------------------------

def _body_cost(closed: ClosedJaxpr | Jaxpr, trip: float) -> OpCost:
    from repro.core.graph import extract_graph
    if isinstance(closed, Jaxpr):
        closed = ClosedJaxpr(closed, ())
    sub = extract_graph(closed, name="body", inline_calls=True)
    total = OpCost()
    for n in sub.nodes:
        total = total + node_cost(sub, n)
    return total.scaled(trip)


@rule("scan")
def _scan(graph: OpGraph, node: OpNode) -> OpCost:
    length = node.params.get("length", 1)
    return _body_cost(node.params["jaxpr"], float(length))


@rule("while")
def _while(graph: OpGraph, node: OpNode) -> OpCost:
    c = _body_cost(node.params["body_jaxpr"], 1.0)
    c.notes = "while: trip count unknown, priced as 1 iteration"
    return c


@rule("cond")
def _cond(graph: OpGraph, node: OpNode) -> OpCost:
    branches = node.params.get("branches", ())
    costs = [_body_cost(b, 1.0) for b in branches]
    if not costs:
        return OpCost()
    return max(costs, key=lambda c: c.flops + c.hbm_bytes)


@rule("pjit", "jit", "closed_call", "custom_jvp_call", "custom_vjp_call",
      "remat", "checkpoint", "shard_map")
def _call(graph: OpGraph, node: OpNode) -> OpCost:
    from repro.core.graph import _nested_jaxpr  # noqa: PLC0415

    class _E:  # minimal shim so _nested_jaxpr can read params
        params = node.params
    inner = _nested_jaxpr(_E)
    if inner is None:
        return OpCost(hbm_bytes=_io_bytes(graph, node))
    return _body_cost(inner, 1.0)


@rule("pallas_call")
def _pallas_call(graph: OpGraph, node: OpNode) -> OpCost:
    """Fused kernel: single HBM pass over inputs+outputs, flops from body."""
    inner = node.params.get("jaxpr")
    flops = 0.0
    if inner is not None:
        try:
            flops = _body_cost(inner, 1.0).flops
        except Exception:
            flops = float(_out_numel(graph, node))
    grid = node.params.get("grid", ())
    trip = _numel(grid) if grid else 1
    return OpCost(flops=flops * max(1, trip),
                  hbm_bytes=_io_bytes(graph, node),
                  notes="fused pallas kernel: one HBM pass")


_UNKNOWN_SEEN: set[str] = set()


def node_cost(graph: OpGraph, node: OpNode) -> OpCost:
    """Cost of one operator; falls back to a bytes-dominant estimate."""
    rule_fn = _RULES.get(node.primitive)
    if rule_fn is None:
        _UNKNOWN_SEEN.add(node.primitive)
        return OpCost(flops=float(_out_numel(graph, node)),
                      hbm_bytes=_io_bytes(graph, node),
                      notes=f"fallback rule for {node.primitive}")
    return rule_fn(graph, node)


def graph_cost(graph: OpGraph) -> OpCost:
    total = OpCost()
    for n in graph.nodes:
        total = total + node_cost(graph, n)
    return total


def unknown_primitives_seen() -> set[str]:
    return set(_UNKNOWN_SEEN)
