"""Per-op cost attribution from compiled XLA artifacts.

Two layers:

* **Module totals** (``extract_costs``): ``compiled.cost_analysis()`` reports
  FLOPs/bytes of the per-device module but does NOT multiply while-loop
  (lax.scan) bodies by their trip count — verified empirically (a scanned
  72-layer stack reports ~72x fewer FLOPs than the same stack unrolled).
  The dry-run therefore uses *segmented* analysis (compile one superblock +
  the ends separately and scale by depth, launch/dryrun.py) with the
  full-program numbers kept as a cross-check.  Collective bytes are not in
  cost_analysis at all: we parse the post-optimization HLO text and sum the
  result-shape bytes of every all-gather / all-reduce / reduce-scatter /
  all-to-all / collective-permute, pricing rings as: ag/rs/a2a ~ 1x result
  bytes, ar ~ 2x.

* **Per-op attribution** (``per_op_costs``): the jaxpr is replayed with every
  equation bound under a ``magop<idx>`` name scope (idx = the equation's
  OpGraph node index), jitted, and compiled.  XLA threads the name stack into
  every HLO instruction's ``metadata={op_name=...}`` — *including*
  instructions inside fused computations and while bodies — so walking the
  optimized module instruction-by-instruction recovers a true per-operator
  cost breakdown:

  - each instruction's FLOPs / transcendentals / bytes are computed from its
    opcode and its operand/result shapes (operands printed by name only are
    resolved from their producers) and credited to the jaxpr equation named
    in its metadata; bytes count HBM only — a value whose layout names
    another memory space (``S(1)``, the TPU's on-chip memory) moves none;
  - a fusion's HBM traffic is what it reads (each parameter whole, or only
    the slices it takes of one, as a scan body does of stacked weights)
    plus its results; interior values, nested fusions included, never touch
    HBM.  When the fusion merges instructions from several equations the
    traffic is split proportionally over those equations' interior
    footprints — the *only* place a proportional split happens;
  - while bodies are multiplied by XLA's ``known_trip_count``, or where the
    compiler prints none (the TPU's) by the count its condition and initial
    tuple spell out (fixing the cost_analysis scan undercount), and
    collectives inside them are credited to the owning scan equation per
    iteration;
  - a convolution (the TPU compiler's form of every dot) is priced from its
    ``dim_labels`` and window: the multiply-adds that land on real input,
    not on padding or dilation holes;
  - opcodes whose cost the HLO text does not expose (custom-call — Pallas
    kernels and interpret callbacks, TopK, FFT —, sort, conditional) fall
    back to the *analytic* rule for the equation they map to;
  - XLA-introduced instructions with no provenance (weight prefetch, layout
    copies) are charged to the first equation their result flows into; what
    reaches none lands in a residual bucket that is distributed
    proportionally over the attributed columns.

  shard_map bodies cannot be replayed equation-by-equation outside their mesh
  context, so the whole region is bound under a ``maggrp<i>_<j>`` scope and
  its costs are split over nodes ``i..j`` by analytic weight (the same
  merged-fusion fallback).
"""

from __future__ import annotations

import dataclasses
import re
from typing import Any, Sequence

import numpy as np

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "s32": 4, "u32": 4,
    "s64": 8, "u64": 8, "f16": 2, "bf16": 2, "f32": 4, "f64": 8,
    "c64": 8, "c128": 16, "f8e4m3fn": 1, "f8e5m2": 1,
}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")

_SHAPE_RE = re.compile(r"\b([a-z0-9]+)\[([0-9,]*)\]")


def _shape_numel(dims: str) -> int:
    if not dims:
        return 1
    return int(np.prod([int(d) for d in dims.split(",") if d],
                       dtype=np.int64))


def _shape_bytes(segment: str) -> float:
    total = 0.0
    for dt, dims in _SHAPE_RE.findall(segment):
        if dt not in _DTYPE_BYTES:
            continue
        total += _shape_numel(dims) * _DTYPE_BYTES[dt]
    return total


@dataclasses.dataclass
class CollectiveStats:
    counts: dict[str, int]
    result_bytes: dict[str, float]

    @property
    def total_traffic_bytes(self) -> float:
        """ICI traffic estimate: all-reduce rings move ~2x the data."""
        t = 0.0
        for kind, b in self.result_bytes.items():
            t += b * (2.0 if kind == "all-reduce" else 1.0)
        return t


def parse_collectives(hlo_text: str) -> CollectiveStats:
    counts: dict[str, int] = {}
    bytes_: dict[str, float] = {}
    for line in hlo_text.splitlines():
        for kind in _COLLECTIVES:
            # match the op invocation, not metadata mentions
            marker = f" {kind}("
            marker2 = f" {kind}-start("
            if marker not in line and marker2 not in line:
                continue
            if "=" not in line:
                continue
            result_part = line.split("=", 1)[1]
            result_part = result_part.split(kind, 1)[0]
            b = _shape_bytes(result_part)
            counts[kind] = counts.get(kind, 0) + 1
            bytes_[kind] = bytes_.get(kind, 0.0) + b
            break
    return CollectiveStats(counts=counts, result_bytes=bytes_)


@dataclasses.dataclass
class CompiledCosts:
    flops: float                 # per-device, loop bodies counted once
    bytes_accessed: float
    argument_bytes: float
    output_bytes: float
    temp_bytes: float
    peak_bytes: float
    collectives: CollectiveStats

    def as_dict(self) -> dict[str, Any]:
        return {
            "flops": self.flops,
            "bytes_accessed": self.bytes_accessed,
            "argument_bytes": self.argument_bytes,
            "output_bytes": self.output_bytes,
            "temp_bytes": self.temp_bytes,
            "peak_bytes": self.peak_bytes,
            "collective_counts": self.collectives.counts,
            "collective_result_bytes": self.collectives.result_bytes,
            "collective_traffic_bytes": self.collectives.total_traffic_bytes,
        }


def extract_costs(compiled) -> CompiledCosts:
    ca = compiled.cost_analysis() or {}
    if isinstance(ca, (list, tuple)):   # jaxlib < 0.5: one dict per program
        ca = ca[0] if ca else {}
    ma = compiled.memory_analysis()
    text = compiled.as_text()
    colls = parse_collectives(text)
    return CompiledCosts(
        flops=float(ca.get("flops", 0.0)),
        bytes_accessed=float(ca.get("bytes accessed", 0.0)),
        argument_bytes=float(getattr(ma, "argument_size_in_bytes", 0)),
        output_bytes=float(getattr(ma, "output_size_in_bytes", 0)),
        temp_bytes=float(getattr(ma, "temp_size_in_bytes", 0)),
        peak_bytes=float(getattr(ma, "argument_size_in_bytes", 0)
                         + getattr(ma, "output_size_in_bytes", 0)
                         + getattr(ma, "temp_size_in_bytes", 0)),
        collectives=colls,
    )


# ---------------------------------------------------------------------------
# annotated lowering: thread jaxpr eqn ids through to HLO metadata
# ---------------------------------------------------------------------------

_TAG_RE = re.compile(r"magop(\d+)")
_GRP_RE = re.compile(r"maggrp(\d+)_(\d+)")


def _bind(eqn, invals):
    subfuns, bind_params = eqn.primitive.get_bind_params(eqn.params)
    out = eqn.primitive.bind(*subfuns, *invals, **bind_params)
    return out if eqn.primitive.multiple_results else [out]


def _count_nodes(closed) -> int:
    """Node count of a (closed) jaxpr under graph.py's flattening rules."""
    from repro.core.graph import _INLINE_PRIMITIVES, _nested_jaxpr
    n = 0
    for eqn in closed.jaxpr.eqns:
        inner = _nested_jaxpr(eqn)
        if inner is not None and eqn.primitive.name in _INLINE_PRIMITIVES:
            n += _count_nodes(inner)
        else:
            n += 1
    return n


def annotated_fn(graph):
    """Replay ``graph.closed_jaxpr`` with each equation bound under a
    ``magop<idx>`` name scope, idx matching ``graph.nodes`` order.

    The walk mirrors :func:`repro.core.graph.extract_graph` exactly (same
    inline set, same DFS order), so the scope index IS the OpGraph node
    index.  shard_map regions are bound whole under a ``maggrp<i>_<j>``
    span scope (their bodies need the mesh context to re-bind)."""
    import jax
    from jax._src.core import Literal

    from repro.core.graph import _INLINE_PRIMITIVES, _nested_jaxpr

    closed = graph.closed_jaxpr
    if closed is None:
        raise ValueError("annotated lowering needs a live graph "
                         "(with a ClosedJaxpr)")

    def run(jaxpr, consts, invals, counter):
        env: dict[Any, Any] = {}

        def read(v):
            return v.val if isinstance(v, Literal) else env[v]

        def write(v, val):
            if type(v).__name__ != "DropVar":
                env[v] = val

        for cv, cval in zip(jaxpr.constvars, consts):
            env[cv] = cval
        for iv, val in zip(jaxpr.invars, invals):
            env[iv] = val
        for eqn in jaxpr.eqns:
            inner = _nested_jaxpr(eqn)
            if inner is not None and eqn.primitive.name in _INLINE_PRIMITIVES:
                if eqn.primitive.name == "shard_map":
                    start = counter[0]
                    end = start + _count_nodes(inner) - 1
                    with jax.named_scope(f"maggrp{start}_{end}"):
                        out = _bind(eqn, [read(v) for v in eqn.invars])
                    counter[0] = end + 1
                    for v, val in zip(eqn.outvars, out):
                        write(v, val)
                    continue
                sub_out = run(inner.jaxpr, inner.consts,
                              [read(v) for v in eqn.invars], counter)
                for ov, val in zip(eqn.outvars, sub_out):
                    write(ov, val)
                continue
            idx = counter[0]
            counter[0] += 1
            with jax.named_scope(f"magop{idx}"):
                out = _bind(eqn, [read(v) for v in eqn.invars])
            for v, val in zip(eqn.outvars, out):
                write(v, val)
        return [read(v) for v in jaxpr.outvars]

    # invariant vs the ACTUAL extraction, not our own count: a walk that
    # diverges from extract_graph must fail loudly, never smear attribution
    expected = len(graph.nodes)

    def fn(*flat_args):
        counter = [0]
        out = run(closed.jaxpr, closed.consts, list(flat_args), counter)
        if counter[0] != expected:
            raise AssertionError(
                f"annotated replay emitted {counter[0]} node scopes but the "
                f"graph flattening has {expected} nodes — annotated_fn's "
                "walk diverged from extract_graph; fix the inline rules "
                "before trusting any attribution")
        return out

    return fn


def annotated_compile(graph, args: Sequence[Any] = ()):
    """Lower + compile the graph's jaxpr with eqn-id metadata preserved."""
    import jax
    flat = jax.tree_util.tree_leaves(tuple(args))
    return jax.jit(annotated_fn(graph)).lower(*flat).compile()


# ---------------------------------------------------------------------------
# optimized-HLO text parsing
# ---------------------------------------------------------------------------

_COMP_HEADER_RE = re.compile(r"^(ENTRY\s+)?%([\w.\-]+)\s*\(.*\{\s*$")
_INSTR_RE = re.compile(r"^\s+(?:ROOT\s+)?%([\w.\-]+)\s+=\s+(.*)$")


# a typed shape with its layout; ``S(n)`` in the layout names a memory
# space other than HBM (the TPU's on-chip memories)
_TYPED_SHAPE_RE = re.compile(r"\b([a-z0-9]+)\[([0-9,]*)\](\{[^}]*\})?")


@dataclasses.dataclass
class HloInstruction:
    name: str
    opcode: str
    line: str                                   # full raw text (attrs)
    shapes_out: list[tuple[str, int, bool]]     # (dtype, numel, in HBM)
    shapes_in: list[tuple[str, int, bool]]
    op_name: str
    trip: int | None
    operands: list[str] = dataclasses.field(default_factory=list)
    dims_out: list[tuple[int, ...]] = dataclasses.field(default_factory=list)
    dims_in: list[tuple[int, ...]] = dataclasses.field(default_factory=list)

    @property
    def result_numel(self) -> float:
        return float(sum(n for _, n, _ in self.shapes_out))

    @property
    def result_bytes(self) -> float:
        """HBM bytes of the result (values in on-chip memory excluded)."""
        return _nbytes(self.shapes_out)

    @property
    def operand_bytes(self) -> float:
        return _nbytes(self.shapes_in)

    @property
    def footprint(self) -> float:
        """Bytes of the result wherever it lives."""
        return _nbytes(self.shapes_out, hbm_only=False)


def _nbytes(shapes, hbm_only: bool = True) -> float:
    return float(sum(n * _DTYPE_BYTES.get(dt, 4) for dt, n, hbm in shapes
                     if hbm or not hbm_only))


def _typed_shapes(segment: str) -> list[tuple[str, tuple[int, ...], bool]]:
    return [(dt, tuple(int(d) for d in dims.split(",") if d),
             not re.search(r"S\([1-9]\d*\)", layout or ""))
            for dt, dims, layout in _TYPED_SHAPE_RE.findall(segment)
            if dt in _DTYPE_BYTES]


def _shapes(segment: str) -> list[tuple[str, int, bool]]:
    return [(dt, int(np.prod(dims, dtype=np.int64)), hbm)
            for dt, dims, hbm in _typed_shapes(segment)]


def _parse_instruction(line: str) -> HloInstruction | None:
    m = _INSTR_RE.match(line)
    if m is None:
        return None
    name, rhs = m.groups()
    if rhs.startswith("("):                      # tuple-typed result
        depth = 0
        i = 0
        for i, ch in enumerate(rhs):
            if ch == "(":
                depth += 1
            elif ch == ")":
                depth -= 1
                if depth == 0:
                    break
        type_str, rest = rhs[:i + 1], rhs[i + 1:].lstrip()
    else:
        parts = rhs.split(" ", 1)
        if len(parts) != 2:
            return None
        type_str, rest = parts
    p = rest.find("(")
    if p < 0:
        return None
    opcode = rest[:p].strip()
    depth = 0
    end = p
    for j in range(p, len(rest)):
        if rest[j] == "(":
            depth += 1
        elif rest[j] == ")":
            depth -= 1
            if depth == 0:
                end = j
                break
    operands = rest[p + 1:end]
    mm = re.search(r"op_name=\"([^\"]*)\"", rest)
    mt = re.search(r"\"known_trip_count\":\{\"n\":\"(\d+)\"\}", rest)
    # older XLA prints each operand's shape before its name; newer prints
    # the name only, and parse_hlo_module resolves it from the producer
    return HloInstruction(
        name=name, opcode=opcode, line=rest,
        shapes_out=_shapes(type_str), shapes_in=_shapes(operands),
        op_name=mm.group(1) if mm else "",
        trip=int(mt.group(1)) if mt else None,
        operands=re.findall(r"%([\w.\-]+)", operands),
        dims_out=[d for _, d, _ in _typed_shapes(type_str)],
        dims_in=[d for _, d, _ in _typed_shapes(operands)])


def _resolve_operands(instrs: list[HloInstruction]) -> None:
    """Fill operand shapes from the producers' result shapes, for modules
    that print operands by name only (an operand always names a value of
    the same computation)."""
    table = {ins.name: ins for ins in instrs}
    for ins in instrs:
        if ins.shapes_in:
            continue
        for name in ins.operands:
            src = table.get(name)
            if src is not None:
                ins.shapes_in.extend(src.shapes_out)
                # one entry per operand; a tuple-typed operand has no dims
                ins.dims_in.append(src.dims_out[0]
                                   if len(src.dims_out) == 1 else ())


def parse_hlo_module(text: str
                     ) -> tuple[str | None, dict[str, list[HloInstruction]]]:
    """Split optimized HLO text into computations; returns (entry, comps)."""
    comps: dict[str, list[HloInstruction]] = {}
    entry: str | None = None
    cur: list[HloInstruction] | None = None
    for raw in text.splitlines():
        line = raw.rstrip()
        if not line:
            continue
        if not line.startswith((" ", "\t")):
            m = _COMP_HEADER_RE.match(line)
            if m is not None:
                cur = comps.setdefault(m.group(2), [])
                if m.group(1):
                    entry = m.group(2)
            elif line.startswith("}"):
                cur = None
            continue
        if line.lstrip().startswith("}"):
            cur = None
            continue
        if cur is None:
            continue
        ins = _parse_instruction(line)
        if ins is not None:
            cur.append(ins)
    for instrs in comps.values():
        _resolve_operands(instrs)
    return entry, comps


def _while_trips(comps: dict[str, list[HloInstruction]], comp: str,
                 ins: HloInstruction) -> int | None:
    """Trip count of a counted loop whose ``known_trip_count`` XLA did not
    print (the TPU compiler prints none): the condition compares one
    element of the loop tuple with a constant, and the tuple the loop
    starts from sets that element to a constant (or a copy of one).  None
    otherwise."""
    m = re.search(r"condition=%([\w.\-]+)", ins.line)
    cond = comps.get(m.group(1), []) if m else []
    here = {i.name: i for i in comps.get(comp, ())}
    inside = {i.name: i for i in cond}
    cmps = [i for i in cond if i.opcode == "compare"]
    if len(cmps) != 1 or len(cmps[0].operands) != 2 or not ins.operands:
        return None
    direction = re.search(r"direction=([A-Z]+)", cmps[0].line)
    counter, bound = (inside.get(n) for n in cmps[0].operands)
    init = here.get(ins.operands[0])
    if (direction is None or counter is None or bound is None
            or init is None or counter.opcode != "get-tuple-element"
            or bound.opcode != "constant" or init.opcode != "tuple"):
        return None
    k = re.search(r"index=(\d+)", counter.line)
    start = (here.get(init.operands[int(k.group(1))])
             if k and int(k.group(1)) < len(init.operands) else None)
    while start is not None and start.opcode in ("copy", "bitcast"):
        start = here.get(start.operands[0]) if start.operands else None
    if start is None or start.opcode != "constant":
        return None
    try:
        lo = int(start.line[start.line.find("(") + 1:start.line.find(")")])
        hi = int(bound.line[bound.line.find("(") + 1:bound.line.find(")")])
    except ValueError:
        return None
    trips = {"LT": hi - lo, "LE": hi - lo + 1,
             "GT": lo - hi, "GE": lo - hi + 1}.get(direction.group(1))
    return trips if trips is not None and trips > 0 else None


def _fusion_read_bytes(comps: dict[str, list[HloInstruction]],
                       comp: str) -> float | None:
    """HBM bytes a fused computation reads: each parameter whole, except a
    parameter the fusion only slices, directly or in a fusion nested in it
    (a scan body's slice of its stacked weights), which reads its slices.
    None when the computation has no parameters to go by."""
    users: dict[str, dict[str, list[tuple[HloInstruction, int]]]] = {}

    def uses(c: str, name: str):
        if c not in users:
            table: dict[str, list[tuple[HloInstruction, int]]] = {}
            for ins in comps.get(c, ()):
                for j, operand in enumerate(ins.operands):
                    table.setdefault(operand, []).append((ins, j))
            users[c] = table
        return users[c].get(name, [])

    def params(c: str) -> dict[int, HloInstruction]:
        out = {}
        for ins in comps.get(c, ()):
            m = re.match(r"parameter\((\d+)\)", ins.line)
            if ins.opcode == "parameter" and m:
                out[int(m.group(1))] = ins
        return out

    def read(c: str, prm: HloInstruction) -> float:
        full = prm.result_bytes
        used = uses(c, prm.name)
        total = 0.0
        for user, j in used:
            inner = re.search(r"calls=%([\w.\-]+)", user.line)
            if user.opcode in ("dynamic-slice", "slice", "gather") and j == 0:
                total += user.footprint
            elif user.opcode == "fusion" and inner and j in params(
                    inner.group(1)):
                total += read(inner.group(1), params(inner.group(1))[j])
            else:
                return full
        return min(total, full) if used else full

    top = params(comp)
    if not top:
        return None
    return sum(read(comp, prm) for prm in top.values())


# ---------------------------------------------------------------------------
# per-instruction cost rules
# ---------------------------------------------------------------------------

_FREE_OPS = {
    "parameter", "constant", "tuple", "get-tuple-element", "bitcast",
    "after-all", "add-dependency", "partition-id", "replica-id", "domain",
    "get-dimension-size", "opt-barrier", "optimization-barrier",
    # async completion halves: the paired -start op carries the full cost
    "all-reduce-done", "all-gather-done", "reduce-scatter-done",
    "all-to-all-done", "collective-permute-done", "copy-done", "send-done",
    "recv-done", "slice-done", "async-done", "async-update",
}
_TRANS_OPS = {
    "exponential", "exponential-minus-one", "log", "log-plus-one",
    "logistic", "tanh", "sine", "cosine", "tan", "power", "sqrt", "rsqrt",
    "cbrt", "erf", "erf-inv", "erfc", "atan2", "expm1",
}
# HLO opcodes whose true cost the text does not expose; the equation they
# attribute to falls back to its analytic operator rule (costs.py).
_OPAQUE_OPS = {
    "custom-call", "conditional", "sort", "rng",
    "rng-bit-generator", "rng-get-and-update-state", "fft", "map",
    "triangular-solve", "cholesky", "infeed", "outfeed", "select-and-scatter",
}
_COLLECTIVE_OPS = {k: (2.0 if k == "all-reduce" else 1.0)
                   for k in _COLLECTIVES}
_COLLECTIVE_OPS.update({f"{k}-start": v for k, v in
                        list(_COLLECTIVE_OPS.items())})


def _dot_flops(ins: HloInstruction) -> float:
    mm = re.search(r"lhs_contracting_dims=\{([0-9,]*)\}", ins.line)
    cdims = [int(d) for d in mm.group(1).split(",") if d] if mm else []
    lhs_shape = ins.dims_in[0] if ins.dims_in else ()
    k = 1
    for d in cdims:
        if d < len(lhs_shape):
            k *= lhs_shape[d]
    return 2.0 * ins.result_numel * float(max(k, 1))


def _conv_flops(ins: HloInstruction) -> float | None:
    """2 x the multiply-adds of a convolution that land on real input:
    per spatial dimension, the (output, kernel tap) pairs that read an
    input element rather than padding or the holes ``lhs_dilate`` makes
    (the TPU compiler lowers dots, batched dots among them, to padded and
    dilated convolutions).  None when the instruction does not say
    enough."""
    m = re.search(r"dim_labels=([^_\s]+)_([^-\s]+)->([\w]+)", ins.line)
    if m is None or len(ins.dims_in) < 2 or not ins.dims_out:
        return None
    lab_in, lab_k, lab_out = m.groups()
    lhs, rhs, out = ins.dims_in[0], ins.dims_in[1], ins.dims_out[0]
    if (len(lab_in) != len(lhs) or len(lab_k) != len(rhs)
            or len(lab_out) != len(out) or "i" not in lab_k):
        return None
    window: dict[str, list[str]] = {}
    mw = re.search(r"window=\{([^}]*)\}", ins.line)
    for item in (mw.group(1).split() if mw else ()):
        key, _, val = item.partition("=")
        window[key] = val.split("x")
    ndim = sum(c.isdigit() for c in lab_k)

    def attr(key: str, j: int, default: str) -> str:
        vals = window.get(key, ())
        return vals[j] if j < len(vals) else default

    macs = float(rhs[lab_k.index("i")])
    out_spatial = 1
    for j in range(ndim):
        d = str(j)
        if d not in lab_in or d not in lab_k or d not in lab_out:
            return None
        size_in, taps = lhs[lab_in.index(d)], rhs[lab_k.index(d)]
        size_out = out[lab_out.index(d)]
        lo = int(attr("pad", j, "0_0").split("_")[0])
        stride = int(attr("stride", j, "1"))
        ldil = int(attr("lhs_dilate", j, "1"))
        rdil = int(attr("rhs_dilate", j, "1"))
        o = np.arange(size_out)[:, None]
        k = np.arange(taps)[None, :]
        pos = o * stride + k * rdil - lo
        real = (pos >= 0) & (pos <= (size_in - 1) * ldil) & (pos % ldil == 0)
        macs *= float(real.sum())
        out_spatial *= size_out
    return 2.0 * macs * ins.result_numel / max(out_spatial, 1)


def _window_read(ins: HloInstruction, window) -> float:
    """Bytes a slicing instruction reads from HBM: its window, when the
    source (the first operand) lives there."""
    src_in_hbm = ins.shapes_in[0][2] if ins.shapes_in else True
    return _nbytes(window, hbm_only=False) if src_in_hbm else 0.0


def _instr_cost(ins: HloInstruction) -> tuple[float, float, float, float]:
    """(flops, transcendentals, hbm_bytes, ici_bytes) of one instruction."""
    op = ins.opcode
    out_n = ins.result_numel
    io = ins.operand_bytes + ins.result_bytes
    if op == "dot":
        return _dot_flops(ins), 0.0, io, 0.0
    if op == "convolution":
        return _conv_flops(ins) or 0.0, 0.0, io, 0.0
    if op in _TRANS_OPS:
        # transcendental ≈ 4 VPU flops/elem (matches costs.py's weighting)
        return 4.0 * out_n, out_n, io, 0.0
    if op in _COLLECTIVE_OPS:
        return 0.0, 0.0, io, ins.result_bytes * _COLLECTIVE_OPS[op]
    if op == "copy":
        return 0.0, 0.0, io, 0.0
    if op == "copy-start":          # result (destination, source, context)
        return 0.0, 0.0, ins.operand_bytes + _nbytes(ins.shapes_out[:1]), 0.0
    if op == "slice-start":         # result (source, window, context)
        k = len(ins.shapes_in)
        window = ins.shapes_out[k:k + 1]
        return 0.0, 0.0, _window_read(ins, window) + _nbytes(window), 0.0
    if op == "dynamic-update-slice":
        # in-place window update: read+write the update window only; any
        # buffer duplication XLA inserts shows up as explicit copy instrs
        upd = (ins.shapes_in[1:2] if len(ins.shapes_in) > 1
               else ins.shapes_out[:1])
        return 0.0, 0.0, 2.0 * _nbytes(upd, hbm_only=False), 0.0
    if op == "dynamic-slice":
        return 0.0, 0.0, (_window_read(ins, ins.shapes_out)
                          + ins.result_bytes), 0.0
    if op == "gather":
        idx_b = _nbytes(ins.shapes_in[1:2], hbm_only=False)
        return 0.0, 0.0, (_window_read(ins, ins.shapes_out)
                          + ins.result_bytes + idx_b), 0.0
    if op == "scatter":
        upd = ins.shapes_in[-1:]
        return (float(sum(n for _, n, _ in upd)), 0.0,
                3.0 * _nbytes(upd, hbm_only=False), 0.0)
    if op in ("reduce", "reduce-window"):
        return float(sum(n for _, n, _ in ins.shapes_in)), 0.0, io, 0.0
    if op in ("broadcast", "iota"):
        return 0.0, 0.0, io, 0.0
    if op in ("reshape", "transpose", "slice", "concatenate", "pad",
              "reverse", "reduce-precision"):
        return 0.0, 0.0, io, 0.0
    if op == "while":                            # handled by the walker
        return 0.0, 0.0, 0.0, 0.0
    # default: cheap elementwise (add/multiply/compare/select/convert/...)
    return out_n, 0.0, io, 0.0


# ---------------------------------------------------------------------------
# attribution
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class PerOpCosts:
    """Per-OpGraph-node costs attributed from the compiled module."""

    flops: np.ndarray
    hbm_bytes: np.ndarray
    ici_bytes: np.ndarray
    transcendentals: np.ndarray
    fp32_fraction: np.ndarray
    module: dict[str, Any]          # compiled module totals (cross-check)
    attribution: dict[str, Any]     # direct/group/residual diagnostics

    @property
    def num_nodes(self) -> int:
        return int(self.flops.shape[0])

    def as_dict(self) -> dict[str, Any]:
        return {
            "flops": [float(x) for x in self.flops],
            "hbm_bytes": [float(x) for x in self.hbm_bytes],
            "ici_bytes": [float(x) for x in self.ici_bytes],
            "transcendentals": [float(x) for x in self.transcendentals],
            "fp32_fraction": [float(x) for x in self.fp32_fraction],
            "module": self.module,
            "attribution": self.attribution,
        }

    @classmethod
    def from_dict(cls, d: dict[str, Any]) -> "PerOpCosts":
        return cls(
            flops=np.asarray(d["flops"], dtype=np.float64),
            hbm_bytes=np.asarray(d["hbm_bytes"], dtype=np.float64),
            ici_bytes=np.asarray(d["ici_bytes"], dtype=np.float64),
            transcendentals=np.asarray(d["transcendentals"],
                                       dtype=np.float64),
            fp32_fraction=np.asarray(d["fp32_fraction"], dtype=np.float64),
            module=dict(d.get("module", {})),
            attribution=dict(d.get("attribution", {})))

    def attribution_summary(self) -> dict[str, Any]:
        """Attribution-quality diagnostics for monitoring (CLI ``capture``).

        * ``residual_flop_fraction`` / ``residual_byte_fraction`` — share of
          the attributed column that came from provenance-free (XLA-
          introduced) instructions and was redistributed proportionally; a
          rising residual means the metadata op_name chain is degrading.
        * ``direct_fraction`` — instructions credited straight to their
          originating equation (neither grouped nor residual).
        * ``opaque_nodes`` — nodes priced by their analytic rule because the
          HLO text hides or distorts their cost (custom-call / pallas
          emulation / a convolution without ``dim_labels``).
        * ``fusion_splits`` — fusions whose HBM traffic was split over
          several genuinely-merged equations (proportional attribution at
          work; artifacts recorded before this counter report 0).
        """
        att = self.attribution or {}
        mod = self.module or {}
        a_flops = float(mod.get("attributed_flops", 0.0))
        a_bytes = float(mod.get("attributed_bytes", 0.0))
        instrs = int(att.get("instructions", 0))
        return {
            "residual_flop_fraction":
                float(att.get("residual_flops", 0.0)) / a_flops
                if a_flops > 0 else 0.0,
            "residual_byte_fraction":
                float(att.get("residual_bytes", 0.0)) / a_bytes
                if a_bytes > 0 else 0.0,
            "direct_fraction":
                int(att.get("direct", 0)) / instrs if instrs else 0.0,
            "opaque_nodes": int(att.get("opaque_nodes", 0)),
            "fusion_splits": int(att.get("fusion_splits", 0)),
            "instructions": instrs,
        }


_COLUMNS = ("flops", "hbm", "ici", "trans")


def _target(op_name: str):
    m = _TAG_RE.search(op_name or "")
    if m is not None:
        return ("node", int(m.group(1)))
    g = _GRP_RE.search(op_name or "")
    if g is not None:
        return ("group", (int(g.group(1)), int(g.group(2))))
    return ("residual", None)


def attribute_costs(graph, compiled) -> PerOpCosts:
    """Walk the compiled module and credit per-instruction costs to the
    OpGraph nodes named in the instruction metadata."""
    from repro.core import costs as costs_mod

    n = len(graph.nodes)
    analytic = [costs_mod.node_cost(graph, nd) for nd in graph.nodes]
    a_cols = {
        "flops": np.array([c.flops for c in analytic], dtype=np.float64),
        "hbm": np.array([c.hbm_bytes for c in analytic], dtype=np.float64),
        "ici": np.array([c.ici_bytes for c in analytic], dtype=np.float64),
        "trans": np.zeros(n),
    }
    text = compiled.as_text()
    entry, comps = parse_hlo_module(text)

    cols = {k: np.zeros(n) for k in _COLUMNS}
    groups: dict[tuple[int, int], dict[str, float]] = {}
    residual = dict.fromkeys(_COLUMNS, 0.0)
    # pallas_call is opaque by construction: on this container it lowers in
    # interpret mode (loops + dynamic slices emulating the kernel), whose
    # instruction stream is an artifact of emulation, not the fused kernel's
    # real traffic — its analytic single-HBM-pass rule is the honest price
    opaque: set[int] = {i for i, nd in enumerate(graph.nodes)
                        if nd.primitive == "pallas_call"}
    stats = {"instructions": 0, "direct": 0, "grouped": 0,
             "residual_instrs": 0, "inherited": 0, "opaque_nodes": 0,
             "fusion_splits": 0}

    def add(tgt, kind: str, amount: float) -> None:
        if amount <= 0.0:
            return
        if tgt[0] == "node":
            if not 0 <= tgt[1] < n:
                raise AssertionError(
                    f"instruction attributed to node {tgt[1]} but the graph "
                    f"has {n} nodes — annotated_fn's walk diverged from "
                    "extract_graph")
            cols[kind][tgt[1]] += amount
        elif tgt[0] == "group":
            groups.setdefault(tgt[1], dict.fromkeys(_COLUMNS, 0.0))
            groups[tgt[1]][kind] += amount
        else:
            residual[kind] += amount

    users: dict[str, dict[str, list[HloInstruction]]] = {}

    def inherited(comp: str, ins: HloInstruction):
        """Target of a provenance-free instruction: the first equation its
        result flows into, so XLA's plumbing (weight prefetch, layout
        copies) is charged to the operator that needs it; residual when
        the value reaches no attributed instruction."""
        if comp not in users:
            table: dict[str, list[HloInstruction]] = {}
            for other in comps.get(comp, ()):
                for name in other.operands:
                    table.setdefault(name, []).append(other)
            users[comp] = table
        seen, frontier = {ins.name}, [ins]
        while frontier:
            nxt = []
            for cur in frontier:
                for user in users[comp].get(cur.name, ()):
                    tgt = _target(user.op_name)
                    if tgt[0] != "residual":
                        return tgt
                    if user.name not in seen:
                        seen.add(user.name)
                        nxt.append(user)
            frontier = nxt
        return ("residual", None)

    def _called(ins: HloInstruction) -> list[str]:
        if ins.opcode == "fusion":
            m = re.search(r"calls=%([\w.\-]+)", ins.line)
            return [m.group(1)] if m else []
        if ins.opcode == "while":
            out = []
            for key in ("body", "condition"):
                m = re.search(key + r"=%([\w.\-]+)", ins.line)
                if m:
                    out.append(m.group(1))
            return out
        if ins.opcode == "call":
            m = re.search(r"to_apply=%([\w.\-]+)", ins.line)
            return [m.group(1)] if m else []
        return []

    def walk(comp: str, mult: float,
             fusion_weights: dict | None = None) -> None:
        for ins in comps.get(comp, ()):
            op = ins.opcode
            if op in _FREE_OPS:
                continue
            stats["instructions"] += 1
            tgt = own = _target(ins.op_name)
            if tgt[0] == "node":
                stats["direct"] += 1
            elif tgt[0] == "group":
                stats["grouped"] += 1
            else:
                stats["residual_instrs"] += 1
                tgt = inherited(comp, ins)
                if tgt[0] != "residual":
                    stats["inherited"] += 1
            if op == "fusion" and fusion_weights is not None:
                # nested in a fusion: its interior is the outer fusion's
                for c in _called(ins):
                    walk(c, mult, fusion_weights)
                continue
            if op == "fusion":
                called = _called(ins)
                weights: dict = {}
                if called:
                    walk(called[0], mult, weights)
                read = (_fusion_read_bytes(comps, called[0])
                        if called else None)
                fus_bytes = ((ins.operand_bytes if read is None else read)
                             + ins.result_bytes) * mult
                total_w = sum(weights.values())
                if total_w > 0:
                    # genuinely merged constituents: proportional split
                    # over each equation's interior footprint
                    if len(weights) > 1:
                        stats["fusion_splits"] += 1
                    for t2, w in weights.items():
                        add(t2, "hbm", fus_bytes * w / total_w)
                else:
                    add(tgt, "hbm", fus_bytes)
                continue
            if op == "while":
                trips = float(ins.trip or _while_trips(comps, comp, ins)
                              or 1)
                for c in _called(ins):
                    walk(c, mult * trips, fusion_weights)
                continue
            if op == "call":
                for c in _called(ins):
                    walk(c, mult, fusion_weights)
                continue
            if op in _OPAQUE_OPS or (op == "convolution"
                                     and _conv_flops(ins) is None):
                # only an equation's own instruction makes it opaque, not
                # plumbing it inherited
                if own[0] == "node" and 0 <= own[1] < n:
                    opaque.add(own[1])
                elif own[0] == "group":
                    opaque.update(i for i in range(own[1][0], own[1][1] + 1)
                                  if i < n)
                continue
            flops, trans, hbm, ici = _instr_cost(ins)
            add(tgt, "flops", flops * mult)
            add(tgt, "trans", trans * mult)
            add(tgt, "ici", ici * mult)
            if fusion_weights is not None:
                # interior of a fusion: no HBM traffic, but remember each
                # equation's footprint as its share of the fusion's traffic
                key = tgt if tgt[0] != "residual" else ("residual", None)
                fusion_weights[key] = (fusion_weights.get(key, 0.0)
                                       + max(hbm, ins.footprint, 1.0))
            else:
                add(tgt, "hbm", hbm * mult)

    if entry is not None:
        walk(entry, 1.0)

    # shard_map group spans: split by analytic weight over the members
    for (g0, g1), kinds in groups.items():
        idxs = [i for i in range(g0, g1 + 1) if i < n]
        if not idxs:
            continue
        for kind, amount in kinds.items():
            if amount <= 0:
                continue
            w = a_cols[kind][idxs] if kind != "trans" else a_cols["flops"][idxs]
            w = np.asarray(w, dtype=np.float64)
            if w.sum() <= 0:
                w = np.ones(len(idxs))
            cols[kind][idxs] += amount * w / w.sum()

    # opaque nodes (custom-call / pallas emulation / ...):
    # the HLO text hides or distorts their cost; use the analytic rule.
    # Applied BEFORE the residual distribution so emulation-inflated
    # accumulations cannot skew the residual weights.
    for i in opaque:
        cols["flops"][i] = a_cols["flops"][i]
        cols["hbm"][i] = a_cols["hbm"][i]
        cols["ici"][i] = a_cols["ici"][i]
        cols["trans"][i] = 0.0
    stats["opaque_nodes"] = len(opaque)

    # residual (XLA-introduced, provenance-free instructions): distribute
    # proportionally over the attributed column, falling back to the
    # analytic column when nothing was attributed at all.  Opaque nodes are
    # excluded: their analytically-priced cost must not be re-inflated by
    # the plumbing of their own emulation (pallas interpret mode), so when
    # every node is opaque the residual is dropped (recorded in stats).
    opaque_idx = sorted(opaque)
    for kind, amount in residual.items():
        if amount <= 0:
            continue
        w = cols[kind].copy()
        w[opaque_idx] = 0.0
        if w.sum() <= 0:
            w = (a_cols[kind] if kind != "trans" else a_cols["flops"]).copy()
            w[opaque_idx] = 0.0
        if w.sum() <= 0:
            stats[f"dropped_residual_{kind}"] = float(amount)
            continue
        cols[kind] += amount * w / w.sum()

    cc = extract_costs(compiled)
    module = cc.as_dict()
    module["attributed_flops"] = float(cols["flops"].sum())
    module["attributed_bytes"] = float(cols["hbm"].sum())
    module["attributed_ici_bytes"] = float(cols["ici"].sum())
    stats["residual_flops"] = float(residual["flops"])
    stats["residual_bytes"] = float(residual["hbm"])

    return PerOpCosts(
        flops=cols["flops"], hbm_bytes=cols["hbm"], ici_bytes=cols["ici"],
        transcendentals=cols["trans"],
        fp32_fraction=np.array([c.fp32_fraction for c in analytic],
                               dtype=np.float64),
        module=module, attribution=stats)


# annotated_compile + attribute_costs memo, keyed by (jaxpr fingerprint,
# const value digests, input avals) — a rewrite candidate sharing its
# target's program text reuses the compile + walk outright, and repeated
# pricing of the same graph (optimize's verify loop, recurring serving
# audits) is free.  Bounded FIFO: compiled-module attributions are a few
# hundred KB each and an unbounded process-wide dict would leak across
# long sweeps.  Results are treated as immutable by every consumer
# (EnergyProfile.hlo holds the same instance).
_PER_OP_MEMO: "dict[str, PerOpCosts]" = {}
_PER_OP_MEMO_MAX = 16
PER_OP_MEMO_COUNTERS = {"hits": 0, "misses": 0}


def _per_op_memo_key(graph, args) -> str:
    import hashlib

    import jax

    from repro.core.graph import _jaxpr_fingerprint, _value_digest
    closed = graph.closed_jaxpr
    h = hashlib.sha256()
    h.update(_jaxpr_fingerprint(closed.jaxpr, tuple(closed.consts),
                                {}).encode())
    for t in sorted((graph._const_vals or {})):
        h.update(_value_digest(graph._const_vals[t]).encode())
    for a in jax.tree_util.tree_leaves(args):
        arr = np.asarray(a)
        h.update(f"{arr.dtype}:{arr.shape}\x00".encode())
    return h.hexdigest()


def per_op_costs(graph, args: Sequence[Any] = (), *,
                 memo: bool = True) -> PerOpCosts:
    """Compile the graph with eqn-id metadata and attribute per-op costs
    (memoized per content digest — see ``_PER_OP_MEMO``)."""
    key = _per_op_memo_key(graph, args) if memo else None
    if key is not None:
        hit = _PER_OP_MEMO.get(key)
        if hit is not None:
            PER_OP_MEMO_COUNTERS["hits"] += 1
            return hit
        PER_OP_MEMO_COUNTERS["misses"] += 1
    compiled = annotated_compile(graph, args)
    poc = attribute_costs(graph, compiled)
    if key is not None:
        while len(_PER_OP_MEMO) >= _PER_OP_MEMO_MAX:
            _PER_OP_MEMO.pop(next(iter(_PER_OP_MEMO)))
        _PER_OP_MEMO[key] = poc
    return poc
