"""Tensor semantic-equivalence matching: SVD-invariant properties.

Property-based (hypothesis): the paper's §4.2 invariant — layout
transformations (permute / reshape / transposed unfoldings) must never break
equivalence, and genuinely different tensors must not match.
"""

import numpy as np
import pytest

from _hyp import given, settings, st  # skips property tests w/o hypothesis

from repro.core.tensor_match import (TensorMatcher, _dense_spectrum,
                                     bijective_pairs, signature,
                                     signatures_match)


def _rand(shape, seed):
    return np.random.default_rng(seed).standard_normal(shape).astype(np.float32)


# ---------------------------------------------------------------------------
# property tests
# ---------------------------------------------------------------------------

@st.composite
def shaped_array(draw):
    rank = draw(st.integers(2, 4))
    dims = [draw(st.integers(2, 6)) for _ in range(rank)]
    seed = draw(st.integers(0, 2**31 - 1))
    return _rand(tuple(dims), seed)


@settings(max_examples=40, deadline=None)
@given(shaped_array(), st.permutations(list(range(4))))
def test_permute_invariance(a, perm4):
    """Axis permutation preserves the signature match (paper Hypothesis 1)."""
    perm = [p for p in perm4 if p < a.ndim]
    b = np.transpose(a, perm)
    assert signatures_match(signature(a), signature(b))


@settings(max_examples=40, deadline=None)
@given(shaped_array())
def test_reshape_invariance(a):
    """Flattening/reshaping preserves the symmetric invariants and at least
    one common unfolding spectrum."""
    b = a.reshape(-1)
    c = a.reshape(a.shape[0], -1)
    assert signatures_match(signature(a), signature(c))
    # rank-1 has only the trivial spectrum; symmetric invariants carry it
    assert signature(a).numel == signature(b).numel


@settings(max_examples=40, deadline=None)
@given(shaped_array(), st.floats(0.2, 3.0))
def test_scaled_tensor_does_not_match(a, scale):
    """A genuinely different tensor (scaled by != 1) must not match."""
    b = a * (1.0 + scale)
    assert not signatures_match(signature(a), signature(b))


@settings(max_examples=25, deadline=None)
@given(shaped_array())
def test_noise_does_not_match(a):
    b = a + np.random.default_rng(1).standard_normal(a.shape).astype(np.float32)
    assert not signatures_match(signature(a), signature(b))


# ---------------------------------------------------------------------------
# deterministic cases from the paper
# ---------------------------------------------------------------------------

def test_hnd_vs_nhd_layout():
    """Paper's example: HuggingFace HND vs SGLang NHD attention layouts
    differ only by a permute and must be declared equivalent."""
    hnd = _rand((8, 128, 64), 0)             # (H, N, D)
    nhd = np.transpose(hnd, (1, 0, 2))       # (N, H, D)
    assert signatures_match(signature(hnd), signature(nhd))


def test_qkv_split_halves_differ():
    """Q and K projections are mathematically similar ops but different
    values; they must NOT match (the paper's context-awareness argument)."""
    q = _rand((4, 64), 1)
    k = _rand((4, 64), 2)
    assert not signatures_match(signature(q), signature(k))


def test_matcher_multi_sample_consistency():
    """Hypothesis 1: equivalence must hold across ALL input samples.
    A pair equal on sample 1 but different on sample 2 is rejected."""
    a1, b1 = _rand((4, 8), 3), None
    b1 = np.transpose(a1.reshape(4, 8))
    a2 = _rand((4, 8), 4)
    b2 = _rand((8, 4), 5)                    # different on sample 2
    m = TensorMatcher()
    pairs = m.match([{0: a1}, {0: a2}], [{0: b1}, {0: b2}])
    assert pairs == []
    pairs = m.match([{0: a1}, {0: a2}],
                    [{0: b1}, {0: np.transpose(a2)}])
    assert pairs == [(0, 0)]


def test_bijective_filter():
    assert bijective_pairs([(0, 0), (0, 1), (2, 2)]) == [(2, 2)]
    assert bijective_pairs([(0, 0), (1, 0)]) == []


@pytest.mark.parametrize("shape,rank", [((256, 4096), 256), ((8192, 128), 128),
                                        ((1, 5000), 1), ((128, 512), 24)])
def test_dense_spectrum_matches_full_svd(shape, rank):
    """The Gram-matrix spectrum equals numpy's SVD to sqrt(eps) of the
    largest singular value, wide or tall, full rank or rank-deficient."""
    rng = np.random.default_rng(3)
    m = (rng.standard_normal((shape[0], rank))
         @ rng.standard_normal((rank, shape[1])))
    want = np.linalg.svd(m, compute_uv=False)
    got = _dense_spectrum(m)
    assert got.shape == want.shape
    assert np.all(np.diff(got) <= 0)
    np.testing.assert_allclose(got, want, rtol=0,
                               atol=4 * np.sqrt(np.finfo(np.float64).eps)
                               * want[0])


def test_large_tensor_fallback():
    """Tensors above the SVD budget fall back to symmetric invariants."""
    a = _rand((1024, 1100), 6)
    sig = signature(a, max_svd_numel=1000)
    assert sig.spectra is None
    b = np.transpose(a)
    assert signatures_match(sig, signature(b, max_svd_numel=1000))


def test_integer_tensors():
    a = np.arange(24, dtype=np.int32).reshape(4, 6)
    b = np.transpose(a)
    assert signatures_match(signature(a), signature(b))
