"""Semantically-equivalent tensor matching via multi-mode SVD invariants.

Implements the paper's §4.2 tensor matcher: layout transformations (permute,
reshape) reorder entries but preserve (a) every entry-symmetric statistic and
(b) the singular-value spectra of the *corresponding* tensor unfoldings.  Two
tensors are declared equivalent when their cheap symmetric invariants agree
within tolerance AND at least one pair of equal-length unfolding spectra
matches (Hypothesis 1 requires this to hold for every probed model input).

Two matching engines live here:

* ``TensorMatcher.match`` / ``match_streamed`` — the production two-phase
  path.  Phase 1 buckets candidates by ``(numel, quantized-l2 key)`` with
  neighbour-bucket probing (exhaustive-equivalent: any pair within ``rtol``
  lands in the same or an adjacent bucket) and applies the cheap symmetric
  gate, collapsing the per-numel cross product.  Phase 2 computes unfolding
  SVD spectra *lazily*, memoized per ``(tid, unfolding-key)``, only for pairs
  that survive the cheap gate — fetching tensor values through a selective
  capture callback so nothing is materialized up front.  Tensors above
  ``max_svd_numel`` get a randomized-sketch spectral test (top-k singular
  values via a randomized range finder) instead of the historical
  invariants-only fallback.

* ``TensorMatcher.match_exhaustive`` — the original eager matcher, kept as
  the reference oracle: it materializes every signature (all unfolding SVDs)
  up front and compares all numel-bucketed pairs.  ``tests/test_matcher_fast``
  asserts the two return identical pair sets on the pipeline workloads.
"""

from __future__ import annotations

import dataclasses
import functools
import itertools
import math
import time
from typing import Callable, Iterable, Protocol, Sequence

import numpy as np


@dataclasses.dataclass
class TensorSignature:
    numel: int
    dtype: str
    # entry-symmetric invariants (exact under any permute/reshape)
    l1: float
    l2: float
    mean: float
    amax: float
    amin: float
    # invariant SET S(T): spectra of ALL unfoldings, keyed by sorted matrix
    # dims (rows, cols) with rows <= cols so transposed unfoldings compare
    # equal.  Each key holds the list of spectra for that unfolding shape —
    # a permutation of axes permutes WHICH unfolding produces WHICH spectrum,
    # so matching is set-wise per key.  None for streamed (cheap-only)
    # signatures: the lazy matcher computes spectra on demand instead.
    spectra: dict[tuple[int, int], list[np.ndarray]] | None
    shape: tuple[int, ...] | None = None

    def is_degenerate(self) -> bool:
        return self.numel < 2 or not np.isfinite(self.l2)


def _unfoldings(shape: tuple[int, ...]) -> list[tuple[tuple[int, ...], tuple[int, ...]]]:
    r = len(shape)
    axes = list(range(r))
    outs = []
    seen = set()
    for k in range(1, r):
        for G in itertools.combinations(axes, k):
            Gc = tuple(a for a in axes if a not in G)
            key = frozenset((G, Gc))
            if key in seen:
                continue
            seen.add(key)
            outs.append((G, Gc))
    return outs


@functools.lru_cache(maxsize=4096)
def _unfolding_key_map(
    shape: tuple[int, ...], limit: int,
) -> dict[tuple[int, int], tuple[tuple[tuple[int, ...], tuple[int, ...]], ...]]:
    """Unfolding (rows, cols) keys -> axis splits, truncated like signature().

    Pure function of the shape, so it is memoized globally: the lazy matcher
    consults it to know which spectra a pair COULD share before computing any.
    """
    numel = int(np.prod(shape, dtype=np.int64)) if shape else 1
    if len(shape) <= 1:
        return {(1, numel): (((0,), ()),)}
    out: dict[tuple[int, int], list] = {}
    for G, Gc in _unfoldings(shape)[:limit]:
        rows = int(np.prod([shape[i] for i in G], dtype=np.int64))
        cols = int(np.prod([shape[i] for i in Gc], dtype=np.int64))
        key = (rows, cols) if rows <= cols else (cols, rows)
        out.setdefault(key, []).append((G, Gc))
    return {k: tuple(v) for k, v in out.items()}


# ---------------------------------------------------------------------------
# cheap symmetric invariants
# ---------------------------------------------------------------------------

def _to_float64(a: np.ndarray) -> np.ndarray:
    if a.dtype.kind == "c":
        return np.abs(a).astype(np.float64)   # complex: layout-invariant modulus
    if a.dtype.kind in "biu?":
        return a.astype(np.float64)
    return a.astype(np.float64, copy=False)


def _cheap_stats_np(a: np.ndarray) -> tuple[float, float, float, float, float]:
    """(l1, l2, mean, amax, amin) in float64 — the oracle's exact formulas."""
    flat = _to_float64(a).ravel()
    numel = flat.size
    l1 = float(np.sum(np.abs(flat))) if numel else 0.0
    l2 = float(np.sqrt(np.sum(flat * flat))) if numel else 0.0
    mean = float(np.mean(flat)) if numel else 0.0
    amax = float(np.max(flat)) if numel else 0.0
    amin = float(np.min(flat)) if numel else 0.0
    return l1, l2, mean, amax, amin


_JITTED_STATS = None
_JIT_STATS_MIN_NUMEL = 4096
_JIT_DTYPES = ("float32", "bfloat16", "float16")


def _jitted_stats_fn():
    """Fused one-pass reduction (l1, sum(x^2), mean, max, min), jit-cached."""
    global _JITTED_STATS
    if _JITTED_STATS is None:
        import jax
        import jax.numpy as jnp

        @jax.jit
        def _stats(x):
            flat = x.astype(jnp.float32).ravel()
            return (jnp.sum(jnp.abs(flat)), jnp.sum(flat * flat),
                    jnp.mean(flat), jnp.max(flat), jnp.min(flat))

        _JITTED_STATS = _stats
    return _JITTED_STATS


def stats_signature(arr, *, use_jit: bool = True) -> TensorSignature:
    """Cheap symmetric invariants of one tensor; no spectra computed.

    This is the streaming-capture reduction: for float tensors of at least
    ``_JIT_STATS_MIN_NUMEL`` elements the five invariants come from one fused
    jitted pass (float32 accumulation); everything else uses the same float64
    numpy formulas as the exhaustive ``signature()`` so the cheap gate is
    bit-compatible with the oracle.
    """
    shape = tuple(int(s) for s in np.shape(arr))
    numel = int(np.prod(shape, dtype=np.int64)) if shape else 1
    dtype = str(getattr(arr, "dtype", np.asarray(arr).dtype))
    if numel == 0:
        return TensorSignature(numel=0, dtype=dtype, l1=0.0, l2=0.0, mean=0.0,
                               amax=0.0, amin=0.0, spectra=None, shape=shape)
    if use_jit and numel >= _JIT_STATS_MIN_NUMEL and dtype in _JIT_DTYPES:
        l1, l2sq, mean, amax, amin = (float(np.asarray(v))
                                      for v in _jitted_stats_fn()(arr))
        l2 = math.sqrt(max(l2sq, 0.0))
    else:
        l1, l2, mean, amax, amin = _cheap_stats_np(np.asarray(arr))
    return TensorSignature(numel=numel, dtype=dtype, l1=l1, l2=l2, mean=mean,
                           amax=amax, amin=amin, spectra=None, shape=shape)


def _dense_spectrum(m: np.ndarray) -> np.ndarray:
    """Every singular value of ``m``, descending: the square roots of the
    eigenvalues of the Gram matrix of its smaller side.

    On the wide unfoldings of a 1M-element tensor that takes a tenth of the
    time of a full SVD.  Each value is off by at most about sqrt(eps) times
    the largest (1.5e-8 in float64), far under the spectral gate's
    tolerance.
    """
    if m.shape[0] > m.shape[1]:
        m = m.T
    ev = np.linalg.eigvalsh(m @ m.T)
    return np.sqrt(np.clip(ev, 0.0, None))[::-1]


def signature(arr: np.ndarray, *, max_svd_numel: int = 1 << 20,
              max_order: int = 5, max_unfoldings: int = 16) -> TensorSignature:
    """Full (eager) signature: cheap invariants + all unfolding SVD spectra."""
    a = _to_float64(np.asarray(arr))
    numel = a.size
    l1, l2, mean, amax, amin = _cheap_stats_np(np.asarray(arr))

    spectra: dict[tuple[int, int], list[np.ndarray]] | None = None
    shape = tuple(int(s) for s in np.shape(arr))
    r = len(shape)
    if 2 <= numel <= max_svd_numel and 1 <= r <= max_order:
        spectra = {}
        unfs = _unfoldings(shape) if r >= 2 else [((0,), ())]
        if r == 1:
            spectra[(1, numel)] = [_dense_spectrum(a.reshape(1, -1))]
        else:
            for G, Gc in unfs[:max_unfoldings]:
                rows = int(np.prod([shape[i] for i in G], dtype=np.int64))
                cols = int(np.prod([shape[i] for i in Gc], dtype=np.int64))
                m = np.transpose(a, G + Gc).reshape(rows, cols)
                if rows > cols:
                    rows, cols = cols, rows
                try:
                    s = _dense_spectrum(m)
                except np.linalg.LinAlgError:
                    continue
                spectra.setdefault((rows, cols), []).append(s)
    return TensorSignature(numel=numel, dtype=str(np.asarray(arr).dtype),
                           l1=l1, l2=l2, mean=mean, amax=amax, amin=amin,
                           spectra=spectra, shape=shape)


# ---------------------------------------------------------------------------
# matching predicates (shared by the oracle and the lazy path)
# ---------------------------------------------------------------------------

def _close(x: float, y: float, rtol: float) -> bool:
    scale = max(abs(x), abs(y), 1e-30)
    return abs(x - y) <= rtol * scale


def _invariants_match(a: TensorSignature, b: TensorSignature,
                      rtol: float) -> bool:
    """The cheap symmetric-invariant gate (phase 1)."""
    if a.is_degenerate() or b.is_degenerate():
        return False
    if a.numel != b.numel:
        return False
    for xa, xb in ((a.l1, b.l1), (a.l2, b.l2), (a.mean, b.mean),
                   (a.amax, b.amax), (a.amin, b.amin)):
        if not _close(xa, xb, rtol):
            return False
    return True


def _spec_close(sa: np.ndarray, sb: np.ndarray, tol: float) -> bool:
    n = min(len(sa), len(sb))
    denom = float(np.linalg.norm(sa[:n])) + 1e-30
    return float(np.linalg.norm(sa[:n] - sb[:n])) / denom <= tol


def _setwise_match(la: Sequence[np.ndarray], lb: Sequence[np.ndarray],
                   tol: float) -> bool:
    """Set-wise spectra match for one unfolding key (the paper's S(T)):
    every spectrum on the smaller side must find a distinct partner."""
    small, big = (la, lb) if len(la) <= len(lb) else (lb, la)
    used: set[int] = set()
    for sa in small:
        hit = None
        for j, sb in enumerate(big):
            if j not in used and _spec_close(sa, sb, tol):
                hit = j
                break
        if hit is None:
            return False
        used.add(hit)
    return True


def signatures_match(a: TensorSignature, b: TensorSignature, *,
                     rtol: float = 1e-3) -> bool:
    """Hypothesis-1 equivalence test for one input sample (eager spectra)."""
    if not _invariants_match(a, b, rtol):
        return False
    if a.spectra is None or b.spectra is None:
        return True  # symmetric invariants only (large tensors)
    shared = set(a.spectra) & set(b.spectra)
    if not shared:
        # No unfolding with common matrix dims (exotic reshape): fall back to
        # the symmetric invariants, which already passed.
        return True
    for key in shared:
        if not _setwise_match(a.spectra[key], b.spectra[key], rtol * 10):
            return False
    return True


# ---------------------------------------------------------------------------
# lazy spectra (phase 2)
# ---------------------------------------------------------------------------

def _sketch_spectrum(m: np.ndarray, rank: int, oversample: int,
                     n_iter: int = 2, seed: int = 0) -> np.ndarray:
    """Randomized top-``rank`` singular values of ``m`` (Halko et al.).

    A Gaussian range finder with ``n_iter`` power iterations: O(numel * k)
    instead of a dense SVD, giving tensors above ``max_svd_numel`` a real
    spectral test.  Deterministic (fixed seed) so repeated queries agree.
    """
    rows, cols = m.shape
    if rows > cols:
        m = m.T
        rows, cols = cols, rows
    k = min(rank + oversample, rows)
    rng = np.random.default_rng(seed)
    omega = rng.standard_normal((cols, k)).astype(m.dtype)
    y = m @ omega
    for _ in range(n_iter):
        y = m @ (m.T @ y)
        y, _ = np.linalg.qr(y)
    q, _ = np.linalg.qr(y)
    b = q.T @ m
    s = np.linalg.svd(b, compute_uv=False)
    return np.sort(s)[::-1][:rank].astype(np.float64)


def _svd_mode(sig: TensorSignature, m: "TensorMatcher") -> str:
    """'dense' | 'sketch' | 'none' spectral test for this tensor (by shape)."""
    shape = sig.shape or ()
    r = len(shape)
    if 2 <= sig.numel <= m.max_svd_numel and 1 <= r <= m.max_order:
        return "dense"
    if m.sketch_large and sig.numel > m.max_svd_numel and r >= 2:
        return "sketch"
    return "none"


def _sig_keys(sig: TensorSignature, m: "TensorMatcher") -> set[tuple[int, int]]:
    mode = _svd_mode(sig, m)
    if mode == "none":
        return set()
    limit = m.max_unfoldings if mode == "dense" else m.sketch_unfoldings
    return set(_unfolding_key_map(sig.shape or (), limit))


class SpectraProvider(Protocol):
    """Persisted phase-2 replay evidence for one artifact side.

    ``digest`` returns the recorded sha256 of a value's raw bytes (or None
    when unknown); ``record_digest`` derives + persists it from an
    in-memory value.  ``spectra``/``record_spectra`` round-trip memoized
    unfolding spectra keyed ``(sample, tid, (rows, cols))``.  Artifacts
    implement this (``CandidateArtifact.spectra_provider``) so matching
    decisions survive into the manifest and replay without raw values.
    """

    def digest(self, k: int, tid: int) -> str | None: ...
    def record_digest(self, k: int, tid: int, value: np.ndarray) -> str: ...
    def spectra(self, k: int, tid: int,
                key: tuple[int, int]) -> list[np.ndarray] | None: ...
    def record_spectra(self, k: int, tid: int, key: tuple[int, int],
                       spectra: list[np.ndarray]) -> None: ...


class _MemoProvider:
    """Run-local SpectraProvider for provider-less matches (in-memory
    ``match()``, oracle comparisons): same interface, nothing persisted."""

    def __init__(self):
        self._digests: dict[tuple[int, int], str] = {}
        self._spectra: dict[tuple[int, int, tuple[int, int]],
                            list[np.ndarray]] = {}

    def digest(self, k, tid):
        return self._digests.get((k, tid))

    def record_digest(self, k, tid, value):
        import hashlib
        d = self._digests.get((k, tid))
        if d is None:
            d = hashlib.sha256(
                np.ascontiguousarray(value).tobytes()).hexdigest()
            self._digests[(k, tid)] = d
        return d

    def spectra(self, k, tid, key):
        return self._spectra.get((k, tid, key))

    def record_spectra(self, k, tid, key, spectra):
        self._spectra[(k, tid, key)] = spectra


class _LazySpectra:
    """Per-sample memoized unfolding spectra with selective value fetch.

    Holds the streamed cheap signatures of one graph side on one input
    sample, plus a ``fetch(tids) -> {tid: value}`` callback (a selective
    re-capture).  Spectra are computed on first use and memoized per
    ``(tid, unfolding-key)``; values are fetched in one batch via
    :meth:`prefetch` so the capture runs at most once per sample.

    A :class:`SpectraProvider` (persisted digests + spectra from a prior
    run of the same comparison) is consulted first: when it can answer,
    no value is fetched at all — the sketch-only offline replay path.
    """

    def __init__(self, sigs: dict[int, TensorSignature],
                 fetch: Callable[[Sequence[int]], dict[int, np.ndarray]],
                 matcher: "TensorMatcher",
                 provider: "SpectraProvider | None" = None,
                 sample: int = 0):
        self._sigs = sigs
        self._fetch = fetch
        self._m = matcher
        self._provider = provider if provider is not None else _MemoProvider()
        self._k = sample
        self._values: dict[int, np.ndarray] = {}
        self._spectra: dict[tuple[int, tuple[int, int]], list[np.ndarray]] = {}
        self.fetched_bytes = 0
        self.dense_svds = 0
        self.sketch_svds = 0

    def mode(self, tid: int) -> str:
        return _svd_mode(self._sigs[tid], self._m)

    def keys(self, tid: int) -> set[tuple[int, int]]:
        return _sig_keys(self._sigs[tid], self._m)

    def prefetch(self, tids: Iterable[int]) -> None:
        missing = sorted(t for t in tids if t not in self._values)
        if not missing:
            return
        got = self._fetch(missing)
        for t in missing:
            v = np.asarray(got[t])
            self._values[t] = v
            self.fetched_bytes += v.nbytes

    def _value(self, tid: int) -> np.ndarray:
        if tid not in self._values:
            self.prefetch([tid])
        return self._values[tid]

    def digest(self, tid: int, *, compute: bool = True) -> str | None:
        """Recorded value digest; with ``compute``, derive (and persist via
        the provider) from the value — fetching it if necessary."""
        d = self._provider.digest(self._k, tid)
        if d is not None or not compute:
            return d
        return self._provider.record_digest(self._k, tid, self._value(tid))

    def spectra(self, tid: int, key: tuple[int, int], *,
                compute: bool = True) -> list[np.ndarray] | None:
        memo = self._spectra.get((tid, key))
        if memo is not None:
            return memo
        memo = self._provider.spectra(self._k, tid, key)
        if memo is not None:
            self._spectra[(tid, key)] = memo
            return memo
        if not compute:
            return None
        sig = self._sigs[tid]
        shape = sig.shape or ()
        mode = self.mode(tid)
        limit = (self._m.max_unfoldings if mode == "dense"
                 else self._m.sketch_unfoldings)
        splits = _unfolding_key_map(shape, limit).get(key, ())
        a = _to_float64(np.asarray(self._value(tid)))
        out: list[np.ndarray] = []
        if len(shape) <= 1:
            out.append(_dense_spectrum(a.reshape(1, -1)))
            self.dense_svds += 1
        else:
            for G, Gc in splits:
                rows = int(np.prod([shape[i] for i in G], dtype=np.int64))
                cols = int(np.prod([shape[i] for i in Gc], dtype=np.int64))
                mat = np.transpose(a, G + Gc).reshape(rows, cols)
                if mode == "dense":
                    try:
                        out.append(_dense_spectrum(mat))
                    except np.linalg.LinAlgError:
                        continue
                    self.dense_svds += 1
                else:
                    m = self._m
                    out.append(_sketch_spectrum(
                        mat.astype(np.float32), m.sketch_rank,
                        m.sketch_oversample))
                    self.sketch_svds += 1
        self._spectra[(tid, key)] = out
        self._provider.record_spectra(self._k, tid, key, out)
        return out


# Above this candidate-product size, the phase-1 bucket gate switches from
# the dense cross-product to the l1-sorted window prefilter (same survivors,
# near-linear cost for buckets holding thousands of layer activations).
_PHASE1_DENSE_MAX = 1 << 16


@dataclasses.dataclass
class MatchStats:
    """Instrumentation of one fast-matcher run (read by fig9_scalability)."""

    n_tids_a: int = 0
    n_tids_b: int = 0
    phase1_pairs: int = 0          # candidates surviving the cheap gate
    pairs: int = 0                 # final equivalent pairs
    dense_svds: int = 0
    sketch_svds: int = 0
    fetched_bytes: int = 0         # total values materialized in phase 2
    peak_value_bytes: int = 0      # peak resident values (one sample's worth)
    decided_dry: int = 0           # pair-verdicts served by persisted evidence
    undecided_dropped: int = 0     # dry_only: pairs undecidable without values
    stamped_pairs: int = 0         # pairs accepted via block-stamped twins
    twin_reseeded: int = 0         # boundary pairs re-proven by resolve_pending
    demoted_pairs: int = 0         # boundary pairs refuted -> full matcher
    phase1_s: float = 0.0
    phase2_s: float = 0.0


# ---------------------------------------------------------------------------
# the matcher
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class TensorMatcher:
    """Matches tensors across two graphs from one or more value captures."""

    rtol: float = 1e-3
    max_svd_numel: int = 1 << 20
    min_numel: int = 2
    max_order: int = 5
    max_unfoldings: int = 16
    # randomized-sketch spectral test for tensors above max_svd_numel
    sketch_large: bool = True
    sketch_rank: int = 16
    sketch_oversample: int = 8
    sketch_unfoldings: int = 4
    sketch_rtol: float = 0.05
    last_stats: MatchStats | None = dataclasses.field(
        default=None, repr=False, compare=False)

    # -- public API ---------------------------------------------------------
    def match(self, values_a: Sequence[dict[int, np.ndarray]],
              values_b: Sequence[dict[int, np.ndarray]]) -> list[tuple[int, int]]:
        """Return (tid_a, tid_b) pairs equivalent under EVERY input sample.

        ``values_a[k]`` / ``values_b[k]`` are tensor-id -> value maps captured
        from the two graphs on the k-th identical model input.  This is the
        fast two-phase path running over in-memory values; the historical
        eager implementation survives as :meth:`match_exhaustive`.
        """
        self._check_samples(values_a, values_b)
        stats_a = [self._stats_table(v) for v in values_a]
        stats_b = [self._stats_table(v) for v in values_b]

        def fetch(vals):
            return lambda k, tids: {t: np.asarray(vals[k][t]) for t in tids}

        return self.match_streamed(stats_a, stats_b,
                                   fetch(values_a), fetch(values_b))

    def match_streamed(
        self,
        stats_a: Sequence[dict[int, TensorSignature]],
        stats_b: Sequence[dict[int, TensorSignature]],
        fetch_a: Callable[[int, Sequence[int]], dict[int, np.ndarray]],
        fetch_b: Callable[[int, Sequence[int]], dict[int, np.ndarray]],
        *,
        provider_a: "SpectraProvider | None" = None,
        provider_b: "SpectraProvider | None" = None,
        dry_only: bool = False,
        stamper: "Any | None" = None,
    ) -> list[tuple[int, int]]:
        """Two-phase match from streamed cheap signatures.

        ``stats_*[k]`` come from ``interp.capture_tensor_stats`` on the k-th
        sample; ``fetch_*(k, tids)`` selectively re-captures the named tensor
        values for phase 2 (``interp.capture_tensor_values(..., only_tids=)``).
        ``provider_*`` supply persisted phase-2 evidence (value digests +
        memoized spectra): pairs whose verdict they decide never fetch a
        value — a replay of a recorded comparison is sketch-only.

        ``dry_only=True`` is the degraded mode for unreachable value stores:
        no fetch is ever issued, pairs the persisted evidence cannot decide
        are conservatively *dropped* (counted in
        ``last_stats.undecided_dropped``) instead of being fetched — the
        result under-matches rather than guesses.

        ``stamper`` (a ``block_match.BlockStamper``) supplies twin pairs
        proven bitwise-identical by block-digest induction: twin survivors
        of phase 1 are accepted without fetches or SVDs (they are equivalent
        by construction, so the pair set stays exhaustive-equivalent), and
        unproven boundary pairs are digest-resolved once up front so a
        bitwise-preserving rewrite demotes only its own pairs.  Twins still
        pass through phase 1 so coincidental cross-layer matches are kept.
        """
        self._check_samples(stats_a, stats_b)
        n = len(stats_a)
        t0 = time.perf_counter()
        tids_a = sorted(self._usable_tids(stats_a))
        tids_b = sorted(self._usable_tids(stats_b))

        # ---- phase 1: bucketed + vectorized cheap gate --------------------
        # Quantize log2(l2) so any pair within rtol lands in the same or an
        # adjacent bucket (probe +-1): |log2 va - log2 vb| <= log2(1/(1-rtol))
        # < W for every rtol < 0.5.  Larger tolerances degrade to numel-only
        # buckets rather than risk splitting a matching pair.
        W = max(0.5, 8.0 * math.log2(1.0 + self.rtol))
        quantize = self.rtol < 0.5

        def bkey(sig: TensorSignature) -> int:
            if not quantize:
                return 0
            return math.floor(math.log2(max(sig.l2, 1e-30)) / W)

        # (n_samples, n_tids, 5) invariant tensors per side; the gate below
        # broadcasts |x - y| <= rtol * max(|x|, |y|, 1e-30) over whole bucket
        # groups at once — float64 arithmetic identical to _close().
        def inv_matrix(stats_list, tids):
            arr = np.empty((n, len(tids), 5))
            for k, table in enumerate(stats_list):
                for i, t in enumerate(tids):
                    s = table[t]
                    arr[k, i, 0] = s.l1
                    arr[k, i, 1] = s.l2
                    arr[k, i, 2] = s.mean
                    arr[k, i, 3] = s.amax
                    arr[k, i, 4] = s.amin
            return arr

        inv_a = inv_matrix(stats_a, tids_a)
        inv_b = inv_matrix(stats_b, tids_b)

        groups_a: dict[tuple[int, int], list[int]] = {}
        for i, ta in enumerate(tids_a):
            s0 = stats_a[0][ta]
            groups_a.setdefault((s0.numel, bkey(s0)), []).append(i)
        groups_b: dict[tuple[int, int], list[int]] = {}
        for j, tb in enumerate(tids_b):
            s0 = stats_b[0][tb]
            groups_b.setdefault((s0.numel, bkey(s0)), []).append(j)

        cand: list[tuple[int, int]] = []
        probes = (-1, 0, 1) if quantize else (0,)
        for (numel, q), ia in groups_a.items():
            jb: list[int] = []
            for dq in probes:
                jb.extend(groups_b.get((numel, q + dq), ()))
            if not jb:
                continue
            if len(ia) * len(jb) > _PHASE1_DENSE_MAX:
                # Giant bucket (thousands of same-shape layer activations in
                # one narrow l2 band): the dense |ia| x |jb| gate would cost
                # O(n^2) memory/time.  Sort side B by sample-0 l1 and gate
                # only the rtol window around each A tensor — a sound
                # overapproximation of the full gate (any matching pair has
                # l1 within rtol on sample 0), so the surviving set is
                # identical to the dense path's.
                ia_arr = np.asarray(ia)
                jb_arr = np.asarray(jb)
                l1b = inv_b[0, jb_arr, 0]
                order = np.argsort(l1b, kind="stable")
                sb = l1b[order]
                l1a = inv_a[0, ia_arr, 0]
                lo = np.searchsorted(sb, l1a * (1.0 - self.rtol) - 1e-30,
                                     side="left")
                hi = np.searchsorted(sb, l1a * (1.0 + 2.0 * self.rtol)
                                     + 1e-30, side="right")
                counts = hi - lo
                ii = np.repeat(np.arange(len(ia)), counts)
                if not ii.size:
                    continue
                jj = np.concatenate(
                    [order[l:h] for l, h in zip(lo, hi) if h > l])
                # staged gate: the (decorrelated) mean column alone rejects
                # almost every window candidate before the full 5-column pass
                ma = inv_a[:, ia_arr[ii], 2]          # (n, m)
                mb = inv_b[:, jb_arr[jj], 2]
                md = np.abs(ma - mb)
                ms = np.maximum(np.maximum(np.abs(ma), np.abs(mb)), 1e-30)
                keep = (md <= self.rtol * ms).all(axis=0)
                ii, jj = ii[keep], jj[keep]
                if not ii.size:
                    continue
                da = inv_a[:, ia_arr[ii], :]          # (n, m, 5)
                db = inv_b[:, jb_arr[jj], :]
                diff = np.abs(da - db)
                scale = np.maximum(np.maximum(np.abs(da), np.abs(db)), 1e-30)
                ok = (diff <= self.rtol * scale).all(axis=(0, 2))    # (m,)
                for t in np.nonzero(ok)[0]:
                    cand.append((tids_a[ia[ii[t]]], tids_b[jb[jj[t]]]))
                continue
            xa = inv_a[:, ia, :]                      # (n, |ia|, 5)
            xb = inv_b[:, jb, :]                      # (n, |jb|, 5)
            diff = np.abs(xa[:, :, None, :] - xb[:, None, :, :])
            scale = np.maximum(np.maximum(np.abs(xa[:, :, None, :]),
                                          np.abs(xb[:, None, :, :])), 1e-30)
            ok = (diff <= self.rtol * scale).all(axis=(0, 3))   # (|ia|, |jb|)
            for ii, jj in zip(*np.nonzero(ok)):
                cand.append((tids_a[ia[ii]], tids_b[jb[jj]]))
        cand.sort()
        t1 = time.perf_counter()

        # ---- phase 2: lazy memoized spectra on survivors ------------------
        # One sample at a time: pairs rejected on sample k never cost a fetch
        # or SVD on sample k+1, and at most one sample's survivor values per
        # side are resident at any moment (the peak-memory bound).  Each
        # sample runs the gate twice: a *dry* pass decides every pair the
        # persisted evidence (digests + spectra) already covers without
        # touching values, then one batched prefetch materializes the
        # remaining pairs' tensors for the *wet* pass.
        st = MatchStats(n_tids_a=len(tids_a), n_tids_b=len(tids_b),
                        phase1_pairs=len(cand), phase1_s=t1 - t0)
        if stamper is not None and not dry_only and stamper.pending and \
                any(not stamper.is_twin(ta, tb) for ta, tb in cand):
            # boundary re-seed: digest-verify unproven pairs once so a
            # bitwise-preserving rewrite demotes only its own pairs
            stamper.resolve_pending(fetch_a, fetch_b, n)
        surviving = cand
        for k in range(n):
            if not surviving:
                break
            la = _LazySpectra(stats_a[k], functools.partial(fetch_a, k), self,
                              provider=provider_a, sample=k)
            lb = _LazySpectra(stats_b[k], functools.partial(fetch_b, k), self,
                              provider=provider_b, sample=k)
            decided: dict[tuple[int, int], bool] = {}
            need_a: set[int] = set()
            need_b: set[int] = set()
            twins = stamper.twins if stamper is not None else frozenset()
            for p in surviving:
                if p in twins:
                    # proven bitwise-identical: accepted with no fetch/SVD
                    if k == 0:
                        st.stamped_pairs += 1
                    continue
                ta, tb = p
                verdict = self._spectra_gate(la, ta, lb, tb, dry=True)
                if verdict is None:
                    if dry_only:
                        decided[p] = False
                        st.undecided_dropped += 1
                        continue
                    need_a.add(ta)
                    need_b.add(tb)
                else:
                    decided[p] = verdict
                    st.decided_dry += 1
            la.prefetch(need_a)
            lb.prefetch(need_b)
            surviving = [
                p for p in surviving
                if p in twins or (decided[p] if p in decided
                                  else self._spectra_gate(la, p[0], lb, p[1]))]
            st.dense_svds += la.dense_svds + lb.dense_svds
            st.sketch_svds += la.sketch_svds + lb.sketch_svds
            st.fetched_bytes += la.fetched_bytes + lb.fetched_bytes
            st.peak_value_bytes = max(st.peak_value_bytes,
                                      la.fetched_bytes + lb.fetched_bytes)
        st.pairs = len(surviving)
        if stamper is not None:
            st.twin_reseeded = stamper.reseeded
            st.demoted_pairs = stamper.demoted
        st.phase2_s = time.perf_counter() - t1
        self.last_stats = st
        return surviving

    def match_exhaustive(self, values_a: Sequence[dict[int, np.ndarray]],
                         values_b: Sequence[dict[int, np.ndarray]]
                         ) -> list[tuple[int, int]]:
        """Reference oracle: eager signatures, numel-bucketed cross product.

        This is the seed implementation, kept verbatim so equivalence tests
        can assert the fast path returns the identical pair set.
        """
        self._check_samples(values_a, values_b)
        sig_a = [self._sig_table(v) for v in values_a]
        sig_b = [self._sig_table(v) for v in values_b]
        tids_a = set(sig_a[0])
        tids_b = set(sig_b[0])
        for t in sig_a[1:]:
            tids_a &= set(t)
        for t in sig_b[1:]:
            tids_b &= set(t)

        # bucket by numel to avoid the full cross product in practice
        by_numel: dict[int, list[int]] = {}
        for tb in tids_b:
            by_numel.setdefault(sig_b[0][tb].numel, []).append(tb)

        pairs: list[tuple[int, int]] = []
        for ta in sorted(tids_a):
            for tb in by_numel.get(sig_a[0][ta].numel, ()):  # candidates
                ok = all(signatures_match(sa[ta], sb[tb], rtol=self.rtol)
                         for sa, sb in zip(sig_a, sig_b))
                if ok:
                    pairs.append((ta, tb))
        return pairs

    # -- internals ----------------------------------------------------------
    @staticmethod
    def _check_samples(a, b) -> None:
        if len(a) != len(b) or not a:
            raise ValueError("need the same nonzero number of captures per side")

    def _sig_table(self, values: dict[int, np.ndarray]) -> dict[int, TensorSignature]:
        out = {}
        for tid, val in values.items():
            if np.size(val) < self.min_numel:
                continue
            out[tid] = signature(val, max_svd_numel=self.max_svd_numel,
                                 max_order=self.max_order,
                                 max_unfoldings=self.max_unfoldings)
        return out

    def _stats_table(self, values: dict[int, np.ndarray]) -> dict[int, TensorSignature]:
        # float64 numpy stats (use_jit=False) so the in-memory fast path is
        # bit-identical to the oracle's cheap gate.
        out = {}
        for tid, val in values.items():
            if np.size(val) < self.min_numel:
                continue
            out[tid] = stats_signature(val, use_jit=False)
        return out

    def _usable_tids(self, stats: Sequence[dict[int, TensorSignature]]) -> set[int]:
        tids = set(stats[0])
        for t in stats[1:]:
            tids &= set(t)
        # A tensor degenerate on ANY sample can never match (the oracle's
        # signatures_match fails on that sample) — drop it up front.
        return {t for t in tids
                if stats[0][t].numel >= self.min_numel
                and all(not s[t].is_degenerate() for s in stats)}

    def _spectra_gate(self, la: _LazySpectra, ta: int,
                      lb: _LazySpectra, tb: int, *,
                      dry: bool = False) -> bool | None:
        """Phase-2 verdict for one pair; ``dry`` answers only from already-
        available evidence (persisted digests/spectra, in-run memos) and
        returns ``None`` when deciding would require fetching a value."""
        ma, mb = la.mode(ta), lb.mode(tb)
        if ma == "dense" and mb == "dense":
            tol = self.rtol * 10
        elif ma == "sketch" and mb == "sketch":
            tol = self.sketch_rtol
        else:
            # Mixed/no spectral test available: symmetric invariants already
            # passed (the oracle's large-tensor / high-order fallback).
            return True
        shared = la.keys(ta) & lb.keys(tb)
        if not shared:
            return True
        # Identical-value fast path: equal-shape, bitwise-equal tensors
        # (equal sha256 digests) pass the full spectral test by construction
        # — both sides would compute the exact same spectra — so skip the
        # SVDs.  Real A/B workloads rarely hit this; self-comparisons,
        # copied values, and matched activations across twin captures do.
        if la._sigs[ta].shape == lb._sigs[tb].shape:
            da = la.digest(ta, compute=not dry)
            db = lb.digest(tb, compute=not dry)
            if da is not None and db is not None and da == db:
                return True
        for key in sorted(shared):
            sa = la.spectra(ta, key, compute=not dry)
            sb = lb.spectra(tb, key, compute=not dry)
            if sa is None or sb is None:
                return None        # dry pass: this pair needs real values
            if not _setwise_match(sa, sb, tol):
                return False
        return True


def bijective_pairs(pairs: Iterable[tuple[int, int]]) -> list[tuple[int, int]]:
    """Keep only pairs whose endpoints match exactly one partner each.

    Ambiguous matches (a tensor numerically equal to several peers, e.g. a
    value and its copy) cannot serve as cut points; Algorithm 1 needs
    unambiguous correspondences.
    """
    count_a: dict[int, int] = {}
    count_b: dict[int, int] = {}
    plist = list(pairs)
    for a, b in plist:
        count_a[a] = count_a.get(a, 0) + 1
        count_b[b] = count_b.get(b, 0) + 1
    return [(a, b) for a, b in plist if count_a[a] == 1 and count_b[b] == 1]
