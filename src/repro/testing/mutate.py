"""Mutation-based detector validation: inject waste patterns into clean jaxprs.

The zoo (zoo/cases.py) validates detection on 20 hand-written twins; a
matcher or diagnosis regression that only shows up elsewhere would slip
through.  This module generates the twins instead: it traces a *clean*
program from ``models/`` / ``kernels/`` to its jaxpr, then replays that
jaxpr through a mutating interpreter that rewrites selected equations into a
semantically-equivalent-but-wasteful form — the paper's waste taxonomy as
executable mutations:

=====================  =====================================================
mutation class         injected pattern (expected diagnosis)
=====================  =====================================================
``dtype_upcast``       matmuls rebound with ``precision=HIGHEST`` — the
                       c1/c8 MXU-fast-path misconfiguration
                       (``param_difference``)
``redundant_recompute``  matmuls executed twice and averaged — c15-style
                       recomputation (``api_difference``)
``sync_in_loop``       an all-reduce inserted after every matmul — the c9
                       per-microbatch collective (``api_difference``)
``oversized_padding``  matmul operands zero-padded to 2x and the result
                       sliced back — dead rows through the MXU
                       (``api_difference``)
``op_split``           fused transcendentals (tanh/logistic/rsqrt/exp)
                       re-expressed as multi-op eager formulas — the n1
                       unfused-GELU pattern (``api_difference``)
``scan_body``          redundant recompute injected INSIDE ``lax.scan``
                       bodies — per-iteration waste hidden in a loop
                       super-node (``param_difference`` on the scan jaxpr)
``layout_thrash``      spurious transpose round-trips inserted on matmul
                       operands — layout churn through HBM
                       (``api_difference``)
``storage_upcast``     bf16 non-matmul ops rebound through f32 storage
                       (convert up, compute, convert back) — doubled
                       element bytes on the VPU path (``api_difference``)
=====================  =====================================================

Because the mutant is an ordinary Python callable replaying the clean jaxpr
with rewritten binds, ``Session.capture`` traces it like any other candidate
— the mutation materializes as real operators in the captured graph, and the
differential pipeline must (1) gate it as the same task, (2) localize the
injected region, and (3) diagnose the planted root cause.
:func:`validate_detector` runs the full scenario matrix and reports
detections and misclassifications per mutation class.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.diagnose import DIAGNOSIS_KINDS, DIAGNOSIS_SUBKINDS
# The replay interpreter and bind helpers moved to the shared bidirectional
# rewrite engine (repro.optimize.engine) when the inverse rewrites landed;
# the historical names stay importable from here.
from repro.optimize.engine import _INLINE_PRIMITIVES  # noqa: F401
from repro.optimize.engine import RewriteRule
from repro.optimize.engine import bind_eqn as _bind
from repro.optimize.engine import bind_eqn_with_params as _bind_with_params
from repro.optimize.engine import nested_jaxpr as _nested_jaxpr  # noqa: F401
from repro.optimize.engine import replay_jaxpr


def _is_float(x) -> bool:
    return jnp.issubdtype(jnp.result_type(x), jnp.floating)


class InapplicableMutationError(ValueError):
    """A mutation found no applicable site in the program's jaxpr.

    Raised by :func:`make_mutant` instead of silently returning an
    unchanged twin (a mutant identical to its clean program makes any
    downstream "the detector must alarm" check vacuously green — the PR 7
    serving demo hit exactly that with ``dtype_upcast`` on a bf16 model).
    ``reasons`` carries the per-site near-miss notes the mutation recorded.
    """

    def __init__(self, mutation: "Mutation", fn_name: str):
        self.mutation_name = mutation.name
        self.reasons = list(mutation.skipped)
        detail = ("; ".join(self.reasons) if self.reasons
                  else "no applicable equation in the jaxpr")
        super().__init__(
            f"mutation {mutation.name!r} found no applicable site in "
            f"{fn_name!r}: {detail}")


# ---------------------------------------------------------------------------
# mutations
# ---------------------------------------------------------------------------

class Mutation(RewriteRule):
    """One waste pattern, applied at replay time.

    Subclasses override :meth:`rewrite` to return replacement output values
    for an equation (or ``None`` to leave it untouched).  ``max_sites``
    bounds how many applicable sites are mutated (default: all);
    ``applied`` counts the sites actually rewritten in the last trace;
    near-miss sites record why via :meth:`RewriteRule.decline` so a
    zero-site mutation can explain itself.
    """

    name: str = "?"
    expected_kinds: tuple[str, ...] = ()

    def rewrite(self, eqn, invals) -> list[Any] | None:
        raise NotImplementedError

    def on_eqn(self, eqn, invals, ctx=None) -> list[Any] | None:
        out = self.rewrite(eqn, invals)
        if out is not None and not isinstance(out, (list, tuple)):
            out = [out]
        return list(out) if out is not None else None


class DtypeUpcast(Mutation):
    """Rebind matmuls with ``precision=HIGHEST`` (3-pass fp32 emulation on
    the MXU) — the c1/c8 misconfiguration.  Same operator multiset, one
    diverging equation param, so the correct diagnosis is a
    ``param_difference`` on ``dot_general.precision``."""

    name = "dtype_upcast"
    expected_kinds = ("param_difference", "config_difference")

    def rewrite(self, eqn, invals):
        if eqn.primitive.name != "dot_general":
            return None
        if "HIGHEST" in str(eqn.params.get("precision")).upper():
            self.decline("dot_general already bound at precision=HIGHEST")
            return None
        # f32 dots only: HIGHEST on bf16 storage changes the accumulation
        # numerics, so the mutant would no longer be bitwise-equivalent and
        # the matcher could not localize the region
        if any(getattr(x, "dtype", None) == jnp.bfloat16 for x in invals):
            self.decline("dot_general runs on bf16 storage (HIGHEST would "
                         "change accumulation numerics); upcast an f32 dot "
                         "or use a program with a master-precision dot")
            return None
        if not self._take():
            return None
        params = dict(eqn.params)
        params["precision"] = (jax.lax.Precision.HIGHEST,
                               jax.lax.Precision.HIGHEST)
        return _bind_with_params(eqn, invals, params)


class RedundantRecompute(Mutation):
    """Execute every matmul twice and average the (identical) results — the
    c15 recompute-instead-of-share pattern.  ``0.5*a + 0.5*a`` is bitwise
    ``a`` for finite floats, so outputs still match exactly."""

    name = "redundant_recompute"
    expected_kinds = ("api_difference",)

    def rewrite(self, eqn, invals):
        if eqn.primitive.name != "dot_general" or not _is_float(invals[0]):
            return None                      # 0.5-averaging an int dot would
        if not self._take():                 # promote its dtype
            return None
        (o1,) = _bind(eqn, invals)
        (o2,) = _bind(eqn, invals)
        return [o1 * 0.5 + o2 * 0.5]


class SyncInLoop(Mutation):
    """Insert an all-reduce after every matmul — the c9 per-microbatch
    collective.  On the single-device mesh the psum is semantically the
    identity, but the jaxpr carries a genuine collective that costs.py
    prices as interconnect traffic."""

    name = "sync_in_loop"
    expected_kinds = ("api_difference",)

    @staticmethod
    def _all_reduce(x):
        from jax.sharding import Mesh
        from jax.sharding import PartitionSpec as P
        mesh = Mesh(np.array(jax.devices()[:1]), ("dp",))
        return jax.shard_map(lambda y: jax.lax.psum(y, "dp"), mesh=mesh,
                         in_specs=P(), out_specs=P())(x)

    def rewrite(self, eqn, invals):
        if eqn.primitive.name != "dot_general" or not self._take():
            return None
        (out,) = _bind(eqn, invals)
        return [self._all_reduce(out)] if _is_float(out) else [out]


class OversizedPadding(Mutation):
    """Zero-pad the lhs of every matmul to twice its leading free dimension
    and slice the dead rows back off the result — over-allocated sequence /
    batch padding pushed through the MXU."""

    name = "oversized_padding"
    expected_kinds = ("api_difference",)

    def rewrite(self, eqn, invals):
        if eqn.primitive.name != "dot_general":
            return None
        lhs, rhs = invals
        (lc, _rc), (lb, _rb) = eqn.params["dimension_numbers"]
        free = [d for d in range(lhs.ndim) if d not in set(lc) | set(lb)]
        if not free or not self._take():
            return None
        d0, n = free[0], lhs.shape[free[0]]
        cfg = [(0, n, 0) if d == d0 else (0, 0, 0) for d in range(lhs.ndim)]
        padded = jax.lax.pad(lhs, jnp.zeros((), lhs.dtype), cfg)
        (out,) = _bind(eqn, [padded, rhs])
        out_axis = len(lb)                   # out dims: batch, lhs free, rhs free
        return [jax.lax.slice_in_dim(out, 0, n, axis=out_axis)]


class OpSplit(Mutation):
    """Re-express fused transcendentals as eager multi-op formulas (one HBM
    round-trip per op) — the n1 unfused-GELU backend pattern."""

    name = "op_split"
    expected_kinds = ("api_difference",)

    def rewrite(self, eqn, invals):
        # rsqrt is deliberately not split: it only ever runs on the (rows, 1)
        # reduced statistics, a region too small for a 10% energy delta
        prim = eqn.primitive.name
        if prim not in ("tanh", "logistic", "exp"):
            return None
        (x,) = invals
        # f32 only: the split formulas round through exp, and in bf16 the
        # accumulated rounding (~0.8%/step) can breach the equivalence gate
        if not _is_float(x) or jnp.result_type(x) != jnp.float32:
            self.decline(f"{prim} runs on {jnp.result_type(x)} (split "
                         "formulas only stay within the gate in f32)")
            return None
        if not self._take():
            return None
        if prim == "tanh":
            xc = jnp.clip(x, -20.0, 20.0)    # exp(2x) stays finite
            t = jnp.exp(2.0 * xc)
            return [(t - 1.0) / (t + 1.0)]
        if prim == "logistic":
            return [1.0 / (1.0 + jnp.exp(-x))]
        h = jnp.exp(x * 0.5)                 # exp: split into two half-exps
        return [h * h]


def _contains_dot(closed) -> bool:
    """Whether a (closed) jaxpr binds a dot_general anywhere, recursively."""
    from jax._src.core import ClosedJaxpr, Jaxpr
    jaxpr = closed.jaxpr if hasattr(closed, "jaxpr") else closed
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general":
            return True
        for v in eqn.params.values():
            vs = v if isinstance(v, (tuple, list)) else (v,)
            for sub in vs:
                if isinstance(sub, (ClosedJaxpr, Jaxpr)) \
                        and _contains_dot(sub):
                    return True
    return False


class ScanBodyWaste(Mutation):
    """Inject redundant recompute INSIDE ``lax.scan`` bodies: the scan is
    re-bound with a body that replays the original body jaxpr under a
    :class:`RedundantRecompute` hook, so every body matmul runs twice per
    iteration.  The outer graphs keep identical operator multisets — both
    sides carry one ``scan`` super-node — so the correct diagnosis is a
    ``param_difference`` (the scan's body jaxpr is the diverging param),
    exercising costs.py's trip-count-scaled loop pricing."""

    name = "scan_body"
    expected_kinds = ("param_difference",)

    def rewrite(self, eqn, invals):
        if eqn.primitive.name != "scan":
            return None
        body = eqn.params["jaxpr"]
        if not _contains_dot(body):
            self.decline("scan body binds no dot_general to recompute")
            return None
        if not self._take():
            return None
        num_consts = eqn.params["num_consts"]
        num_carry = eqn.params["num_carry"]
        consts = list(invals[:num_consts])
        init = list(invals[num_consts:num_consts + num_carry])
        xs = tuple(invals[num_consts + num_carry:])
        inner = RedundantRecompute()

        def body_fn(carry, x):
            x_leaves = [] if x is None else list(x)
            outs = _replay(body, [*consts, *list(carry), *x_leaves], inner)
            return tuple(outs[:num_carry]), tuple(outs[num_carry:])

        carry_out, ys = jax.lax.scan(
            body_fn, tuple(init), xs if xs else None,
            length=eqn.params.get("length"),
            reverse=eqn.params.get("reverse", False),
            unroll=eqn.params.get("unroll", 1))
        return [*carry_out, *ys]


class LayoutThrash(Mutation):
    """Insert transpose round-trips on every matmul's operands — spurious
    layout churn through HBM around the MXU.  The values are bitwise
    unchanged (the full-reverse permutation is an involution) but each
    matmul gains four data-movement operators, so the correct diagnosis is
    an ``api_difference`` with extra ``transpose`` ops on the wasteful
    side."""

    name = "layout_thrash"
    expected_kinds = ("api_difference",)

    @staticmethod
    def _round_trip(x):
        if getattr(x, "ndim", 0) < 2:
            return x
        perm = tuple(reversed(range(x.ndim)))
        return jax.lax.transpose(jax.lax.transpose(x, perm), perm)

    def rewrite(self, eqn, invals):
        if eqn.primitive.name != "dot_general":
            return None
        if getattr(invals[0], "ndim", 0) < 2 or not self._take():
            return None
        return _bind(eqn, [self._round_trip(x) for x in invals])


class StorageUpcast(Mutation):
    """Rebind bf16 non-matmul ops through f32 storage: convert the operands
    up, compute, convert the result back.  Every mutated element pays double
    the HBM bytes plus two conversion passes — the storage-dtype analogue of
    the c1/c8 compute misconfiguration, on ops where no MXU is involved."""

    name = "storage_upcast"
    expected_kinds = ("api_difference",)

    _TARGETS = ("tanh", "logistic", "exp", "add", "mul")

    def rewrite(self, eqn, invals):
        if eqn.primitive.name not in self._TARGETS:
            return None
        if not all(hasattr(x, "dtype") and x.dtype == jnp.bfloat16
                   for x in invals):
            self.decline(f"{eqn.primitive.name} operands are not uniformly "
                         "bf16 (nothing to bounce through f32 storage)")
            return None
        if not self._take():
            return None
        out = _bind(eqn, [x.astype(jnp.float32) for x in invals])
        return [o.astype(jnp.bfloat16) for o in out]


MUTATIONS: dict[str, type[Mutation]] = {
    m.name: m for m in (DtypeUpcast, RedundantRecompute, SyncInLoop,
                        OversizedPadding, OpSplit, ScanBodyWaste,
                        LayoutThrash, StorageUpcast)
}

assert all(k in DIAGNOSIS_KINDS for m in MUTATIONS.values()
           for k in m.expected_kinds)
# the finer subkind taxonomy (and the inverse-rewrite registry keyed on it)
# must stay in lockstep with the mutation classes
assert set(MUTATIONS) == set(DIAGNOSIS_SUBKINDS), \
    (set(MUTATIONS), set(DIAGNOSIS_SUBKINDS))


def default_mutations() -> list[Mutation]:
    return [cls() for cls in MUTATIONS.values()]


# ---------------------------------------------------------------------------
# jaxpr replay with mutation hooks
# ---------------------------------------------------------------------------

def _replay(closed, flat_args: Sequence[Any], mutation: Mutation) -> list[Any]:
    return replay_jaxpr(closed, flat_args, mutation)


def make_mutant(fn: Callable, mutation: Mutation, example_args: Sequence[Any],
                *, name: str | None = None,
                allow_zero_sites: bool = False) -> tuple[Callable, int]:
    """Build the mutated twin of ``fn`` and count its mutated sites.

    Returns ``(mutant, sites)``.  A mutation that finds no applicable
    equation raises :class:`InapplicableMutationError` carrying the
    mutation's recorded skip reasons — a zero-site mutant is bitwise the
    clean program, which silently turns "the detector must alarm on this"
    checks vacuous (the PR 7 serving demo shipped exactly that).  Pass
    ``allow_zero_sites=True`` to get the old ``(mutant, 0)`` behavior for
    callers that probe applicability themselves.  The mutant is an ordinary
    callable over the same argument pytree, so it can be captured, jitted,
    or compared like any hand-written candidate.
    """
    example_args = tuple(example_args)
    closed, out_shape = jax.make_jaxpr(fn, return_shape=True)(*example_args)
    out_tree = jax.tree_util.tree_structure(out_shape)

    def mutant(*args):
        mutation.reset()
        outs = _replay(closed, jax.tree_util.tree_leaves(args), mutation)
        return jax.tree_util.tree_unflatten(out_tree, outs)

    mutant.__name__ = name or (f"{getattr(fn, '__name__', 'fn')}"
                               f"__{mutation.name}")
    mutation.reset()
    jax.eval_shape(mutant, *example_args)
    if mutation.applied == 0 and not allow_zero_sites:
        raise InapplicableMutationError(
            mutation, getattr(fn, "__name__", "fn"))
    return mutant, mutation.applied


# ---------------------------------------------------------------------------
# clean programs (models/ + kernels/)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class CleanProgram:
    """A small, fast, waste-free program drawn from the real model zoo."""

    name: str
    fn: Callable
    make_args: Callable[[], tuple]


def clean_programs() -> list[CleanProgram]:
    """Clean programs spanning matmul, attention, norm, activation, loop,
    and bf16 ops.

    Sizes are small (fast through the instrumenting interpreter) but the
    matmul contraction dims stay >= 64 so the dots have enough arithmetic
    intensity for a flop-side mutation (dtype_upcast's 3x fp32 emulation)
    to clear the 10% region-energy detection threshold over the
    memory-access energy floor.  The ``scan_*`` programs keep their dots
    INSIDE ``lax.scan`` bodies (scan_body mutation targets); the ``*_bf16``
    programs run in bfloat16 storage (storage_upcast targets).
    """
    from repro.kernels import ref
    from repro.models import layers

    k = jax.random.key(20260801)
    ks = list(jax.random.split(k, 12))

    mlp_params = layers.init_params(layers.mlp_schema(128, 256, "float32"),
                                    ks[0])

    def mlp_block(x):
        return layers.mlp_apply(mlp_params, x)

    scale = jax.random.normal(ks[1], (128,), jnp.float32) * 0.1 + 1.0
    w_norm = jax.random.normal(ks[2], (128, 128), jnp.float32) * 0.1
    w_gelu = jax.random.normal(ks[3], (128, 128), jnp.float32) * 0.1

    def rmsnorm_linear(x):
        return layers.rms_norm(x, scale) @ w_norm

    def gelu_dense(x):
        return ref.gelu_tanh(x @ w_gelu)

    def attention_block(q, k_, v):
        return ref.attention(q, k_, v, causal=False)

    w_scan = jax.random.normal(ks[8], (128, 128), jnp.float32) * 0.05
    w_scan2 = jax.random.normal(ks[9], (128, 128), jnp.float32) * 0.05

    def scan_mlp(x):
        def step(c, _):
            return jnp.tanh(c @ w_scan), None
        out, _ = jax.lax.scan(step, x, None, length=4)
        return out

    def scan_residual(x):
        def step(c, _):
            return c + 0.5 * jnp.tanh(c @ w_scan2), None
        out, _ = jax.lax.scan(step, x, None, length=4)
        return out

    w_b16 = (jax.random.normal(ks[10], (128, 128), jnp.float32) * 0.1
             ).astype(jnp.bfloat16)

    def gelu_dense_bf16(x):
        # bf16-native tanh-GELU (ref.gelu_tanh upcasts to f32 internally,
        # which would leave no bf16 elementwise sites to mutate)
        y = x @ w_b16
        inner = 0.7978845608 * (y + 0.044715 * (y * y * y))
        return 0.5 * y * (1.0 + jnp.tanh(inner))

    def act_chain_bf16(x):
        return jnp.tanh(x) * jax.nn.sigmoid(x + jnp.bfloat16(1.0))

    w_master = jax.random.normal(ks[4], (128, 128), jnp.float32) * 0.1

    def mlp_bf16_master(x):
        # mixed precision with f32 master weights: bf16 storage upcast to
        # f32 around the dot.  This is the one program where dtype_upcast
        # has a site on a bf16 model (the dot itself runs f32), closing the
        # gap PR 7 hit: serving models default to bf16, where dtype_upcast
        # declines every dot and used to yield a silent zero-site mutant.
        h = x.astype(jnp.float32) @ w_master
        return jnp.tanh(h).astype(jnp.bfloat16)

    def _qkv():
        kq, kk, kv = jax.random.split(ks[4], 3)
        shape = (1, 2, 64, 128)   # head_dim 128: the score matmul's 3x fp32
        # flop term must outweigh its memory-access + idle energy floor
        return (jax.random.normal(kq, shape, jnp.float32),
                jax.random.normal(kk, shape, jnp.float32),
                jax.random.normal(kv, shape, jnp.float32))

    return [
        CleanProgram("mlp_swiglu", mlp_block,
                     lambda: (jax.random.normal(ks[5], (2, 32, 128),
                                                jnp.float32),)),
        CleanProgram("attention_ref", attention_block, _qkv),
        CleanProgram("rmsnorm_linear", rmsnorm_linear,
                     lambda: (jax.random.normal(ks[6], (64, 128),
                                                jnp.float32),)),
        CleanProgram("gelu_dense", gelu_dense,
                     lambda: (jax.random.normal(ks[7], (64, 128),
                                                jnp.float32),)),
        CleanProgram("scan_mlp", scan_mlp,
                     lambda: (jax.random.normal(ks[8], (64, 128),
                                                jnp.float32),)),
        CleanProgram("scan_residual", scan_residual,
                     lambda: (jax.random.normal(ks[9], (64, 128),
                                                jnp.float32),)),
        CleanProgram("gelu_dense_bf16", gelu_dense_bf16,
                     lambda: (jax.random.normal(ks[10], (64, 128),
                                                jnp.float32
                                                ).astype(jnp.bfloat16),)),
        CleanProgram("act_chain_bf16", act_chain_bf16,
                     lambda: (jax.random.normal(ks[11], (128, 128),
                                                jnp.float32
                                                ).astype(jnp.bfloat16),)),
        CleanProgram("mlp_bf16_master", mlp_bf16_master,
                     lambda: (jax.random.normal(ks[6], (64, 128),
                                                jnp.float32
                                                ).astype(jnp.bfloat16),)),
    ]


# ---------------------------------------------------------------------------
# scenario generation + detector validation
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Scenario:
    program: CleanProgram
    mutation: Mutation
    mutant: Callable
    sites: int

    @property
    def id(self) -> str:
        return f"{self.mutation.name}:{self.program.name}"


def generate_scenarios(programs: Sequence[CleanProgram] | None = None,
                       mutation_names: Sequence[str] | None = None
                       ) -> list[Scenario]:
    """The cross product of clean programs x mutations, minus inapplicable
    pairs (mutations that found no site in a program's jaxpr)."""
    programs = list(programs) if programs is not None else clean_programs()
    names = list(mutation_names) if mutation_names is not None \
        else list(MUTATIONS)
    out: list[Scenario] = []
    for prog in programs:
        args = prog.make_args()
        for mname in names:
            mutation = MUTATIONS[mname]()
            mutant, sites = make_mutant(prog.fn, mutation, args,
                                        allow_zero_sites=True)
            if sites == 0:
                continue                     # inapplicable pair, by design
            out.append(Scenario(program=prog, mutation=mutation,
                                mutant=mutant, sites=sites))
    return out


@dataclasses.dataclass
class ScenarioResult:
    scenario_id: str
    program: str
    mutation: str
    sites: int
    detected: bool
    kinds: list[str]             # diagnosis kinds of the waste findings
    kind_ok: bool                # some kind matches the mutation's expectation
    expected_kinds: tuple[str, ...]
    energy_clean_j: float
    energy_mutant_j: float
    error: str | None = None

    @property
    def ok(self) -> bool:
        return self.detected and self.kind_ok and self.error is None


@dataclasses.dataclass
class ValidationResult:
    """Detector validation over the generated scenario space."""

    results: list[ScenarioResult]

    def by_class(self) -> dict[str, list[ScenarioResult]]:
        out: dict[str, list[ScenarioResult]] = {}
        for r in self.results:
            out.setdefault(r.mutation, []).append(r)
        return out

    def misclassified(self) -> dict[str, list[ScenarioResult]]:
        """Per mutation class: scenarios detected but with a wrong root
        cause, or not detected at all."""
        return {cls: bad for cls, rs in self.by_class().items()
                if (bad := [r for r in rs if not r.ok])}

    def validated_classes(self, min_programs: int = 2) -> set[str]:
        """Mutation classes detected AND correctly classified on at least
        ``min_programs`` distinct clean programs."""
        return {cls for cls, rs in self.by_class().items()
                if len({r.program for r in rs if r.ok}) >= min_programs}

    def summary(self) -> str:
        lines = ["=== mutation-based detector validation ==="]
        for cls, rs in sorted(self.by_class().items()):
            ok = [r for r in rs if r.ok]
            lines.append(
                f"{cls:22} detected+classified on "
                f"{len({r.program for r in ok})}/{len({r.program for r in rs})}"
                f" programs ({len(ok)}/{len(rs)} scenarios)")
            for r in rs:
                if not r.ok:
                    why = (r.error or
                           ("not detected" if not r.detected else
                            f"misclassified: got {r.kinds or ['<none>']}, "
                            f"expected one of {list(r.expected_kinds)}"))
                    lines.append(f"    MISS {r.scenario_id}: {why}")
        return "\n".join(lines)


def validate_detector(scenarios: Sequence[Scenario] | None = None,
                      session=None, *, output_rtol: float = 1e-2
                      ) -> ValidationResult:
    """Capture each mutant against its clean twin and score the debugger.

    For every scenario the mutant is candidate A and the clean program
    candidate B; success means (1) at least one confirmed energy-waste
    region with the mutant on the wasteful side and (2) a diagnosis whose
    kind matches the mutation class's expectation.  Clean programs are
    captured once and reused across their scenarios.
    """
    from repro.core.session import Session

    session = session or Session()
    scenarios = list(scenarios) if scenarios is not None \
        else generate_scenarios()
    clean_arts: dict[str, Any] = {}
    clean_args: dict[str, tuple] = {}
    results: list[ScenarioResult] = []
    for sc in scenarios:
        pname = sc.program.name
        if pname not in clean_arts:
            clean_args[pname] = sc.program.make_args()
            clean_arts[pname] = session.capture(
                sc.program.fn, clean_args[pname], name=pname)
        clean = clean_arts[pname]
        try:
            mut_art = session.capture(sc.mutant, clean_args[pname],
                                      name=sc.mutant.__name__)
            rep = session.compare(mut_art, clean, output_rtol=output_rtol)
            waste = [f for f in rep.waste_findings if f.wasteful_side == "A"]
            kinds = [f.diagnosis.kind for f in waste if f.diagnosis]
            results.append(ScenarioResult(
                scenario_id=sc.id, program=pname, mutation=sc.mutation.name,
                sites=sc.sites, detected=bool(waste), kinds=kinds,
                kind_ok=any(k in sc.mutation.expected_kinds for k in kinds),
                expected_kinds=sc.mutation.expected_kinds,
                energy_clean_j=clean.total_energy_j,
                energy_mutant_j=mut_art.total_energy_j))
        except Exception as e:               # scenario-level isolation
            results.append(ScenarioResult(
                scenario_id=sc.id, program=pname, mutation=sc.mutation.name,
                sites=sc.sites, detected=False, kinds=[], kind_ok=False,
                expected_kinds=sc.mutation.expected_kinds,
                energy_clean_j=float("nan"), energy_mutant_j=float("nan"),
                error=f"{type(e).__name__}: {e}"))
    return ValidationResult(results)
