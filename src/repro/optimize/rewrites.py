"""Inverse rewrites: one waste-removing transform per mutation class.

Each :class:`Rewrite` is the inverse of one entry in the mutation taxonomy
(``repro.testing.mutate.MUTATIONS``) and is keyed by the same name, which is
also the ``Diagnosis.subkind`` the classifier emits for that waste pattern —
so a diagnosis selects its inverse directly:

=====================  =====================================================
rewrite (= subkind)    inverse transform
=====================  =====================================================
``dtype_upcast``       rebind ``precision=HIGHEST`` dots with the default
                       fast path
``redundant_recompute``  CSE duplicated contractions; fold the
                       ``0.5*a + 0.5*a`` average of identical values
``sync_in_loop``       drop collectives that are the identity on their
                       mesh (size-1 all-reduces)
``oversized_padding``  elide zero-pads on free dims and the identity
                       slices they leave behind
``op_split``           re-fuse hand-split transcendentals (tanh /
                       logistic / exp) from their multi-op formulas
``scan_body``          re-bind ``lax.scan`` with the body replayed under
                       the CSE rewrite (per-iteration recompute)
``layout_thrash``      cancel transpose round-trips that compose to the
                       identity permutation
``storage_upcast``     recompute bf16→f32→bf16 storage bounces directly
                       in bf16
=====================  =====================================================

Rewrites are *candidate generators*, not proofs: each proposed candidate is
re-captured and must pass the functional-equivalence gate and price strictly
cheaper before the optimizer reports it (see ``repro.optimize.optimizer``).
A rewrite that cannot tell whether a transform is safe simply proposes it
and lets verification reject it.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.graph import COLLECTIVE_PRIMS
from repro.optimize.engine import (RewriteContext, RewriteRule, bind_eqn,
                                   bind_eqn_with_params, replay_jaxpr)


def _scalar(x, ctx: "RewriteContext | None" = None) -> float | None:
    """Concrete scalar value of a replay input, or None (tracer/array).

    With a ``ctx``, scalars staged behind ``convert_element_type`` chains
    (omnistaging traces even constant casts, e.g. clip bounds) are resolved
    through provenance.
    """
    if isinstance(x, jax.core.Tracer):
        if ctx is None:
            return None
        seen = 0
        while seen < 4:
            prov = ctx.producer(x)
            if not (prov and prov[0].primitive.name == "convert_element_type"
                    and len(prov[1]) == 1):
                return None
            x = prov[1][0]
            if not isinstance(x, jax.core.Tracer):
                break
            seen += 1
        if isinstance(x, jax.core.Tracer):
            return None
    try:
        arr = np.asarray(x)
    except Exception:
        return None
    if arr.ndim != 0:
        return None
    return float(arr)


class Rewrite(RewriteRule):
    """One inverse rewrite.  ``name`` doubles as the registry key and the
    ``Diagnosis.subkind`` it answers; ``roundtrip_rtol`` is the declared
    bound on the residual energy gap of ``inverse(mutation(clean))`` vs
    ``clean`` (small helper ops the inverse cannot remove)."""

    name: str = "?"
    roundtrip_rtol: float = 0.05
    # functional-equivalence tolerance the verifier should use for this
    # rewrite's candidates (bf16 recomputation rounds differently)
    verify_rtol: float = 1e-2

    def rewrite(self, eqn, invals, ctx: RewriteContext) -> list[Any] | None:
        raise NotImplementedError

    def on_eqn(self, eqn, invals, ctx: RewriteContext | None = None):
        if ctx is None:
            raise ValueError(f"rewrite {self.name!r} needs a RewriteContext")
        out = self.rewrite(eqn, invals, ctx)
        if out is not None and not isinstance(out, (list, tuple)):
            out = [out]
        return list(out) if out is not None else None


class DropPrecisionUpcast(Rewrite):
    """Inverse of ``DtypeUpcast``: rebind HIGHEST-precision dots with the
    default (fast-path) precision.  On accelerators this drops the 3-pass
    fp32 MXU emulation; the analytic backend prices it as the
    ``fp32_fraction`` falling back to the native matmul rate."""

    name = "dtype_upcast"
    roundtrip_rtol = 0.02

    def rewrite(self, eqn, invals, ctx):
        if eqn.primitive.name != "dot_general":
            return None
        prec = eqn.params.get("precision")
        if prec is None or "HIGHEST" not in str(prec).upper():
            self.decline("dot_general already on default precision")
            return None
        if not self._take():
            return None
        params = dict(eqn.params)
        params["precision"] = None
        return bind_eqn_with_params(eqn, invals, params)


class CseDuplicates(Rewrite):
    """Inverse of ``RedundantRecompute``: share the first binding of any
    contraction that reappears with identical inputs and params, and fold
    the ``0.5*a + 0.5*a`` average the mutation used to consume both copies
    back to ``a`` (the orphaned muls die in DCE)."""

    name = "redundant_recompute"
    roundtrip_rtol = 0.02

    _CSE_PRIMS = ("dot_general", "conv_general_dilated")

    def __init__(self, max_sites=None):
        super().__init__(max_sites)
        self._memo: dict[tuple, list[Any]] = {}

    def reset(self):
        super().reset()
        self._memo = {}

    def rewrite(self, eqn, invals, ctx):
        prim = eqn.primitive.name
        if prim in self._CSE_PRIMS:
            key = (prim, repr(sorted(eqn.params.items(), key=lambda kv: kv[0])),
                   tuple(id(v) for v in invals))
            hit = self._memo.get(key)
            if hit is not None:
                if not self._take():
                    return None
                return list(hit)
            out = bind_eqn(eqn, invals)
            self._memo[key] = list(out)
            return out
        if prim == "add":
            a, b = invals
            pa, pb = ctx.producer(a), ctx.producer(b)
            if pa and pb and pa[0].primitive.name == "mul" \
                    and pb[0].primitive.name == "mul":
                xa = self._half_of(pa[1])
                xb = self._half_of(pb[1])
                if xa is not None and xa is xb and self._take():
                    return [xa]
        return None

    @staticmethod
    def _half_of(mul_invals):
        a, b = mul_invals
        if _scalar(a) == 0.5:
            return b
        if _scalar(b) == 0.5:
            return a
        return None


class DropIdentityCollective(Rewrite):
    """Inverse of ``SyncInLoop``: remove collectives that are the identity
    on their mesh — a ``shard_map`` whose body is nothing but psum-style
    reductions over a size-1 mesh moves no data and changes no values.
    Collectives on real multi-device meshes are left alone (hoisting those
    needs mesh-aware replay; see ROADMAP)."""

    name = "sync_in_loop"
    roundtrip_rtol = 0.02

    def rewrite(self, eqn, invals, ctx):
        if eqn.primitive.name != "shard_map":
            return None
        mesh = eqn.params.get("mesh")
        body = eqn.params.get("jaxpr")
        if mesh is None or body is None:
            return None
        if getattr(mesh, "size", None) != 1:
            self.decline("collective runs on a >1-device mesh; identity "
                         "elimination does not apply")
            return None
        body_jaxpr = body.jaxpr if hasattr(body, "jaxpr") else body
        prims = {e.primitive.name for e in body_jaxpr.eqns}
        if not prims <= COLLECTIVE_PRIMS:
            self.decline(f"shard_map body is not purely collective: "
                         f"{sorted(prims - COLLECTIVE_PRIMS)}")
            return None
        if len(eqn.outvars) != len(invals) or not self._take():
            return None
        return list(invals)


class TrimPadding(Rewrite):
    """Inverse of ``OversizedPadding``: elide zero-interior pads that only
    grow trailing rows of a free dimension, and the identity slices left
    once the padded rows are gone.  Downstream consumers re-bind on the
    unpadded shapes; if any consumer genuinely needed the padding the
    retrace fails and the candidate is reported as failed."""

    name = "oversized_padding"
    roundtrip_rtol = 0.02

    def rewrite(self, eqn, invals, ctx):
        prim = eqn.primitive.name
        if prim == "pad":
            operand = invals[0]
            cfg = eqn.params.get("padding_config", ())
            if not all(lo == 0 and inner == 0 for lo, _, inner in cfg):
                self.decline("pad has leading/interior padding (not a "
                             "trailing overallocation)")
                return None
            if not any(hi > 0 for _, hi, _ in cfg):
                return None
            if not self._take():
                return None
            return [operand]
        if prim == "slice":
            (operand,) = invals
            starts = eqn.params.get("start_indices", ())
            limits = eqn.params.get("limit_indices", ())
            strides = eqn.params.get("strides") or (1,) * len(starts)
            shape = getattr(operand, "shape", None)
            if shape is None:
                return None
            if all(s == 0 for s in starts) and tuple(limits) == tuple(shape) \
                    and all(s == 1 for s in strides):
                # identity slice (the counterpart of an elided pad) — drop
                # it without consuming a site
                return [operand]
        return None


class FuseSplitOps(Rewrite):
    """Inverse of ``OpSplit``: recognize the eager multi-op formulas for
    tanh / logistic / exp from their final equation and substitute the
    fused primitive; the formula's intermediate chain dies in DCE.

    Patterns (matched on replay provenance):

    * ``(t-1)/(t+1)`` with ``t = exp(2*clip(x,-c,c))``  →  ``tanh(x)``
    * ``1/(1+exp(-x))``                                 →  ``logistic(x)``
    * ``h*h`` with ``h = exp(0.5*x)``                   →  ``exp(x)``
    """

    name = "op_split"
    roundtrip_rtol = 0.05

    def rewrite(self, eqn, invals, ctx):
        prim = eqn.primitive.name
        if prim == "div":
            return self._fuse_div(invals, ctx)
        if prim == "mul":
            return self._fuse_square_exp(invals, ctx)
        return None

    def _fuse_div(self, invals, ctx):
        num, den = invals
        # logistic: 1 / (1 + exp(-x))
        if _scalar(num) == 1.0:
            pd = ctx.producer(den)
            if pd and pd[0].primitive.name == "add":
                e = self._other_of(pd[1], 1.0)
                pe_ = ctx.producer(e) if e is not None else None
                if pe_ and pe_[0].primitive.name == "exp":
                    pn = ctx.producer(pe_[1][0])
                    if pn and pn[0].primitive.name == "neg" and self._take():
                        return [jax.lax.logistic(pn[1][0])]
            return None
        # tanh: (t - 1) / (t + 1) with t = exp(2 * x)
        ps, pa = ctx.producer(num), ctx.producer(den)
        if not (ps and pa and ps[0].primitive.name == "sub"
                and pa[0].primitive.name == "add"):
            return None
        t1, one1 = ps[1]
        if _scalar(one1) != 1.0:
            return None
        t2 = self._other_of(pa[1], 1.0)
        if t2 is None or t1 is not t2:
            return None
        pt = ctx.producer(t1)
        if not (pt and pt[0].primitive.name == "exp"):
            return None
        pm = ctx.producer(pt[1][0])
        if not (pm and pm[0].primitive.name == "mul"):
            return None
        x = self._other_of(pm[1], 2.0)
        if x is None:
            return None
        if not self._take():
            return None
        return [jax.lax.tanh(self._unwrap_clip(x, ctx))]

    def _fuse_square_exp(self, invals, ctx):
        a, b = invals
        if a is not b:
            return None
        ph = ctx.producer(a)
        if not (ph and ph[0].primitive.name == "exp"):
            return None
        pm = ctx.producer(ph[1][0])
        if not (pm and pm[0].primitive.name == "mul"):
            return None
        x = self._other_of(pm[1], 0.5)
        if x is None or not self._take():
            return None
        return [jax.lax.exp(x)]

    @staticmethod
    def _other_of(pair, lit):
        a, b = pair
        if _scalar(a) == lit:
            return b
        if _scalar(b) == lit:
            return a
        return None

    @staticmethod
    def _unwrap_clip(x, ctx):
        """tanh saturates far inside the mutation's ±20 overflow clip, so
        ``tanh(clip(x, -c, c)) == tanh(x)`` for c >= 10 — unwrap the clip
        (traced as min(max(x, -c), c)) so it dies in DCE."""
        pmin = ctx.producer(x)
        if not (pmin and pmin[0].primitive.name == "min"):
            return x
        hi_candidates = [(v, _scalar(w, ctx)) for v, w in
                         ((pmin[1][0], pmin[1][1]), (pmin[1][1], pmin[1][0]))]
        for inner, hi in hi_candidates:
            if hi is not None and hi >= 10.0:
                pmax = ctx.producer(inner)
                if pmax and pmax[0].primitive.name == "max":
                    for orig, lo in ((pmax[1][0], _scalar(pmax[1][1], ctx)),
                                     (pmax[1][1], _scalar(pmax[1][0], ctx))):
                        if lo is not None and lo <= -10.0:
                            return orig
        return x


def _static_duplicate_contraction(jaxpr) -> bool:
    """Whether a jaxpr binds the same contraction twice on the same invars
    (the static signature of planted recompute inside a scan body)."""
    seen = set()
    for eqn in jaxpr.eqns:
        if eqn.primitive.name not in CseDuplicates._CSE_PRIMS:
            continue
        key = (eqn.primitive.name,
               tuple(str(v) for v in eqn.invars),
               repr(sorted(eqn.params.items(), key=lambda kv: kv[0])))
        if key in seen:
            return True
        seen.add(key)
    return False


class CseScanBody(Rewrite):
    """Inverse of ``ScanBodyWaste``: re-bind ``lax.scan`` with the body
    replayed under :class:`CseDuplicates`, removing per-iteration recompute
    (trip-count-scaled, so the win multiplies by ``length``)."""

    name = "scan_body"
    roundtrip_rtol = 0.05

    def rewrite(self, eqn, invals, ctx):
        if eqn.primitive.name != "scan":
            return None
        body = eqn.params["jaxpr"]
        body_jaxpr = body.jaxpr if hasattr(body, "jaxpr") else body
        if not _static_duplicate_contraction(body_jaxpr):
            self.decline("scan body has no duplicated contraction")
            return None
        if not self._take():
            return None
        num_consts = eqn.params["num_consts"]
        num_carry = eqn.params["num_carry"]
        consts = list(invals[:num_consts])
        init = list(invals[num_consts:num_consts + num_carry])
        xs = tuple(invals[num_consts + num_carry:])
        inner = CseDuplicates()

        def body_fn(carry, x):
            inner.reset()
            ictx = RewriteContext()
            x_leaves = [] if x is None else list(x)
            outs = replay_jaxpr(body, [*consts, *list(carry), *x_leaves],
                                inner, ctx=ictx)
            return tuple(outs[:num_carry]), tuple(outs[num_carry:])

        carry_out, ys = jax.lax.scan(
            body_fn, tuple(init), xs if xs else None,
            length=eqn.params.get("length"),
            reverse=eqn.params.get("reverse", False),
            unroll=eqn.params.get("unroll", 1))
        return [*carry_out, *ys]


class CancelTransposeRoundTrip(Rewrite):
    """Inverse of ``LayoutThrash``: a transpose whose input was itself
    produced by a transpose composing to the identity permutation is
    replaced by the original value; the inner transpose dies in DCE."""

    name = "layout_thrash"
    roundtrip_rtol = 0.02

    def rewrite(self, eqn, invals, ctx):
        if eqn.primitive.name != "transpose":
            return None
        (v,) = invals
        p = eqn.params["permutation"]
        prov = ctx.producer(v)
        if not (prov and prov[0].primitive.name == "transpose"):
            self.decline("transpose is not part of a round-trip")
            return None
        q = prov[0].params["permutation"]
        if len(p) != len(q) or any(q[p[i]] != i for i in range(len(p))):
            self.decline("adjacent transposes do not compose to identity")
            return None
        if not self._take():
            return None
        return [prov[1][0]]


class DropStorageUpcast(Rewrite):
    """Inverse of ``StorageUpcast``: a down-convert to bf16 whose producer
    is an elementwise op fed (partly) by up-converts from bf16 is replaced
    by the op recomputed directly on the original bf16 values — halving the
    storage traffic; the f32 op and its up-converts die in DCE.

    bf16 recomputation rounds once instead of rounding an f32 result, so
    candidates from this rewrite verify within bf16 epsilon (~0.4%/op);
    ``verify_rtol`` is widened accordingly."""

    name = "storage_upcast"
    roundtrip_rtol = 0.05
    verify_rtol = 0.05

    _TARGET_FNS = {
        "tanh": jnp.tanh,
        "logistic": jax.nn.sigmoid,
        "exp": jnp.exp,
        "add": jnp.add,
        "mul": jnp.multiply,
    }

    def rewrite(self, eqn, invals, ctx):
        if eqn.primitive.name != "convert_element_type":
            return None
        if eqn.params.get("new_dtype") != jnp.bfloat16:
            return None
        (v,) = invals
        prov = ctx.producer(v)
        if prov is None or prov[0].primitive.name not in self._TARGET_FNS:
            self.decline("down-convert does not follow a supported "
                         "elementwise op")
            return None
        op_eqn, op_invals = prov
        orig, unwrapped = [], 0
        for w in op_invals:
            p = ctx.producer(w)
            if p is not None and p[0].primitive.name == "convert_element_type" \
                    and getattr(w, "dtype", None) == jnp.float32 \
                    and getattr(p[1][0], "dtype", None) == jnp.bfloat16:
                orig.append(p[1][0])
                unwrapped += 1
            else:
                orig.append(w)
        if unwrapped == 0:
            self.decline("elementwise op has no bf16-sourced operands")
            return None
        # jaxpr literals read back as *strong* f32 scalars, which would
        # re-promote the bf16 recomputation; demote them to weak floats
        orig = [s if (s := _scalar(o)) is not None else o for o in orig]
        # any operand that stays f32 (beyond weak scalars) would re-promote
        if not all(_scalar(o) is not None
                   or getattr(o, "dtype", None) == jnp.bfloat16
                   for o in orig):
            self.decline("mixed-precision operands; bf16 recomputation "
                         "would change the op's input dtypes")
            return None
        if not self._take():
            return None
        out = self._TARGET_FNS[op_eqn.primitive.name](*orig)
        if getattr(out, "dtype", None) != jnp.bfloat16:
            out = out.astype(jnp.bfloat16)
        return [out]


REWRITES: dict[str, type[Rewrite]] = {
    r.name: r for r in (DropPrecisionUpcast, CseDuplicates,
                        DropIdentityCollective, TrimPadding, FuseSplitOps,
                        CseScanBody, CancelTransposeRoundTrip,
                        DropStorageUpcast)
}


def rewrites_for(subkind: str | None) -> list[str]:
    """Rewrite names to try for a diagnosis, most specific first.

    A known subkind proposes its inverse first, then every other rewrite
    (the verifier ranks all survivors, so extra candidates only add rank
    columns); ``None`` proposes everything in registry order."""
    names = list(REWRITES)
    if subkind in REWRITES:
        names.remove(subkind)
        names.insert(0, subkind)
    return names
