"""Root-cause diagnosis (the paper's Algorithm 2, adapted to JAX/XLA).

FINDDEVIATIONPOINT: compare the call paths (user stack frames recorded by the
JAX tracer per equation) of the operators in a matched-but-unequal region and
report the last common frame before they diverge.

FINDKEYVAR: the paper re-runs with basic-block instrumentation to find the
branch variable that selects a different GPU kernel.  In JAX the kernel
selection is driven by *declarative* operator attributes and global config,
so the key variable is recovered by diffing (1) the jaxpr equation params of
corresponding operators — ``precision``, ``preferred_element_type``, dtypes,
``dimension_numbers`` — and (2) a registered configuration snapshot
(jax.config flags / model-config dataclasses).  See DESIGN.md §2 for why
basic-block tracing has no TPU analogue.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Mapping, Sequence

from repro.core.graph import COLLECTIVE_PRIMS, OpGraph, OpNode

# The closed root-cause taxonomy.  Everything downstream — report
# serialization, golden baselines (repro.testing.baselines), and the
# mutation engine's expected-classification table (repro.testing.mutate) —
# validates against this tuple instead of re-spelling the strings.
DIAGNOSIS_KINDS = ("api_difference", "param_difference", "config_difference")

# Finer-grained waste classes, one per mutation in the engine's taxonomy
# (repro.testing.mutate.MUTATIONS).  The 3 coarse DIAGNOSIS_KINDS say *how*
# the sides differ; the subkind says *which inverse rewrite* would remove
# the waste (repro.optimize keys its rewrite registry on these names).
# ``Diagnosis.subkind`` is None when no class fits — reports and golden
# baselines serialized before the field existed load unchanged.
DIAGNOSIS_SUBKINDS = (
    "dtype_upcast",         # param: dot precision forced to HIGHEST
    "redundant_recompute",  # api: an identical contraction appears twice
    "sync_in_loop",         # api: collective inside the hot region
    "oversized_padding",    # api: pad + slice round-trip around an op
    "op_split",             # api: fused transcendental decomposed by hand
    "scan_body",            # param: scan body jaxpr diverges
    "layout_thrash",        # api: transpose round-trips around an op
    "storage_upcast",       # api: bf16 values bounced through f32
)

# Primitive families used to refine a coarse kind into a subkind.  Closed
# world by design: these mirror what the mutation taxonomy can plant (and
# what the inverse rewrites can remove), not everything XLA can emit.
_COLLECTIVE_PRIMS = COLLECTIVE_PRIMS | {"shard_map"}
_CONTRACTION_PRIMS = ("dot_general", "conv_general_dilated")
_ELEMENTWISE_PRIMS = frozenset({
    "add", "sub", "mul", "div", "neg", "exp", "log", "log1p", "expm1",
    "tanh", "logistic", "rsqrt", "sqrt", "clamp", "max", "min", "pow",
    "integer_pow", "abs", "sign", "erf", "floor", "ceil", "select_n",
    "broadcast_in_dim", "convert_element_type"})


@dataclasses.dataclass
class Diagnosis:
    kind: str                       # one of DIAGNOSIS_KINDS
    deviation_point: str            # last common call frame
    detail: str
    key_variables: list[str]        # differing eqn params / config keys
    ops_a: list[str]
    ops_b: list[str]
    # which energy backend's numbers this diagnosis rests on (the session
    # backend label, e.g. 'tpu_v5e' / 'hlo+tpu_v5e' / 'replay'); None on
    # reports serialized before the field existed.  A ' [degraded]' suffix
    # means some rung of the session's degradation ladder fired — the
    # report's meta['degraded'] lists exactly what was downgraded.
    priced_by: str | None = None
    # one of DIAGNOSIS_SUBKINDS, or None when the region does not match any
    # known waste class (or the report predates the field)
    subkind: str | None = None

    @property
    def degraded(self) -> bool:
        from repro.core.session import DEGRADED_MARK
        return bool(self.priced_by) and DEGRADED_MARK in self.priced_by

    @classmethod
    def from_dict(cls, d: Mapping[str, Any]) -> "Diagnosis":
        return cls(kind=d["kind"], deviation_point=d["deviation_point"],
                   detail=d["detail"],
                   key_variables=list(d["key_variables"]),
                   ops_a=list(d["ops_a"]), ops_b=list(d["ops_b"]),
                   priced_by=d.get("priced_by"),
                   subkind=d.get("subkind"))


def _common_prefix(p1: Sequence[str], p2: Sequence[str]) -> int:
    n = 0
    for a, b in zip(p1, p2):
        if a != b:
            break
        n += 1
    return n


def find_deviation_point(paths_a: Sequence[tuple[str, ...]],
                         paths_b: Sequence[tuple[str, ...]]) -> str:
    """Last common frame across the two sides' operator call paths."""
    best_frame = "<program entry>"
    best_len = -1
    for pa in paths_a:
        for pb in paths_b:
            n = _common_prefix(pa, pb)
            if n > best_len and n > 0:
                best_len = n
                best_frame = pa[n - 1]
    return best_frame


_KEY_PARAMS = ("precision", "preferred_element_type", "dimension_numbers",
               "new_dtype", "dtype", "dimensions", "permutation", "axes",
               "feature_group_count", "window_strides", "k", "is_stable",
               "exhaustively", "accum_dtype")


def _param_repr(v: Any) -> str:
    s = repr(v)
    return s if len(s) <= 80 else s[:77] + "..."


def diff_eqn_params(node_a: OpNode, node_b: OpNode) -> list[str]:
    out = []
    keys = set(node_a.params) | set(node_b.params)
    for k in sorted(keys):
        if k not in _KEY_PARAMS and not (k in node_a.params and k in node_b.params):
            continue
        va, vb = node_a.params.get(k), node_b.params.get(k)
        if _param_repr(va) != _param_repr(vb):
            out.append(f"{k}: A={_param_repr(va)} vs B={_param_repr(vb)}")
    return out


def diff_config(config_a: Mapping[str, Any] | None,
                config_b: Mapping[str, Any] | None) -> list[str]:
    if not config_a or not config_b:
        return []
    out = []
    for k in sorted(set(config_a) | set(config_b)):
        va, vb = config_a.get(k), config_b.get(k)
        if va != vb:
            out.append(f"config[{k!r}]: A={va!r} vs B={vb!r}")
    return out


def _op_multiset(graph: OpGraph, idxs: Sequence[int]) -> list[str]:
    return sorted(graph.nodes[i].primitive for i in idxs)


def infer_subkind(kind: str, ops_wasteful: Sequence[str],
                  ops_efficient: Sequence[str],
                  key_variables: Sequence[str]) -> str | None:
    """Refine a coarse diagnosis kind into a DIAGNOSIS_SUBKINDS entry.

    ``ops_wasteful``/``ops_efficient`` are the region op multisets oriented
    by which side the energy backend flagged.  Returns None when the region
    does not look like any known waste class — callers must treat that as
    "no automated rewrite available", not as an error.
    """
    if kind == "api_difference":
        from collections import Counter
        extra = Counter(ops_wasteful) - Counter(ops_efficient)
        if not extra:
            return None
        # ordered from most to least specific: a collective beats any
        # structural tell, movement ops beat the elementwise catch-all
        if any(p in _COLLECTIVE_PRIMS for p in extra):
            return "sync_in_loop"
        if extra.get("transpose", 0) >= 2:
            return "layout_thrash"
        if extra.get("pad", 0):
            return "oversized_padding"
        # storage bounces add *only* converts (the bounced ops keep their
        # primitive); a mixed bag of extras that merely includes converts
        # (e.g. an inlined clip's literal casts) is not a storage upcast
        if extra.get("convert_element_type", 0) and \
                set(extra) <= {"convert_element_type", "broadcast_in_dim"}:
            return "storage_upcast"
        if any(extra.get(p, 0) for p in _CONTRACTION_PRIMS):
            return "redundant_recompute"
        if all(p in _ELEMENTWISE_PRIMS for p in extra):
            return "op_split"
        return None
    # param/config difference: op multisets agree, so the tell is *which*
    # attribute diverged
    if any(".precision" in kv or "precision" in kv.split(":", 1)[0]
           for kv in key_variables):
        return "dtype_upcast"
    if any(kv.startswith("scan.") for kv in key_variables):
        return "scan_body"
    if any(".preferred_element_type" in kv or ".accum_dtype" in kv
           for kv in key_variables):
        return "dtype_upcast"
    # diverging scan bodies can evade the param diff when the truncated
    # jaxpr reprs share a prefix; the scan super-node itself is the tell
    if "scan" in ops_wasteful and "scan" in ops_efficient:
        return "scan_body"
    return None


def diagnose_region(graph_a: OpGraph, nodes_a: Sequence[int],
                    graph_b: OpGraph, nodes_b: Sequence[int],
                    *,
                    config_a: Mapping[str, Any] | None = None,
                    config_b: Mapping[str, Any] | None = None,
                    priced_by: str | None = None,
                    wasteful_side: str = "A") -> Diagnosis:
    """Explain why two equivalent regions consume different energy.

    ``priced_by`` names the energy backend whose numbers flagged the region
    (recorded on the diagnosis so reports can cite their pricing source).
    ``wasteful_side`` ('A' or 'B') orients the subkind inference toward the
    side the backend says burns more energy.
    """
    ops_a = _op_multiset(graph_a, nodes_a)
    ops_b = _op_multiset(graph_b, nodes_b)
    paths_a = [graph_a.nodes[i].call_path for i in nodes_a if graph_a.nodes[i].call_path]
    paths_b = [graph_b.nodes[i].call_path for i in nodes_b if graph_b.nodes[i].call_path]
    deviation = find_deviation_point(paths_a, paths_b)

    cfg_diffs = diff_config(config_a, config_b)

    ops_w, ops_e = ((ops_a, ops_b) if wasteful_side == "A"
                    else (ops_b, ops_a))

    if ops_a != ops_b:
        only_a = sorted(set(ops_a) - set(ops_b))
        only_b = sorted(set(ops_b) - set(ops_a))
        extra_a = len(ops_a) - len(ops_b)
        detail = (f"different operator combinations: A uses {only_a or '(same set)'} "
                  f"({len(ops_a)} ops), B uses {only_b or '(same set)'} "
                  f"({len(ops_b)} ops, Δ{extra_a:+d})")
        return Diagnosis(kind="api_difference", deviation_point=deviation,
                         detail=detail,
                         key_variables=cfg_diffs, ops_a=ops_a, ops_b=ops_b,
                         priced_by=priced_by,
                         subkind=infer_subkind("api_difference", ops_w,
                                               ops_e, cfg_diffs))

    # same operator multiset -> same API, look for param/config differences
    # pair same-primitive ops in topological order and diff params
    key_vars: list[str] = list(cfg_diffs)
    by_prim_a: dict[str, list[int]] = {}
    by_prim_b: dict[str, list[int]] = {}
    for i in nodes_a:
        by_prim_a.setdefault(graph_a.nodes[i].primitive, []).append(i)
    for i in nodes_b:
        by_prim_b.setdefault(graph_b.nodes[i].primitive, []).append(i)
    for prim, ia_list in by_prim_a.items():
        for ia, ib in zip(ia_list, by_prim_b.get(prim, [])):
            key_vars.extend(f"{prim}.{d}" for d in
                            diff_eqn_params(graph_a.nodes[ia], graph_b.nodes[ib]))
    kind = "config_difference" if cfg_diffs else "param_difference"
    detail = ("same operators, diverging attributes/configuration"
              if key_vars else
              "same operators and attributes; energy difference stems from "
              "tensor shapes/layouts feeding this region")
    key_vars = sorted(set(key_vars))
    return Diagnosis(kind=kind, deviation_point=deviation, detail=detail,
                     key_variables=key_vars, ops_a=ops_a,
                     ops_b=ops_b, priced_by=priced_by,
                     subkind=infer_subkind(kind, ops_w, ops_e, key_vars))
