"""MoE: the held-experts layer and the capacity (EP) dispatch vs the GShard
one-hot twin vs the dropless oracle; group-limited routing; expert shares."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro import configs
from repro.models import moe
from repro.models.layers import init_params


@pytest.fixture(scope="module")
def setup():
    cfg = dataclasses.replace(
        configs.get_config("llama4-scout-17b-a16e").reduced(),
        moe_num_experts=4, moe_top_k=2, moe_d_ff=16, moe_num_shared=1,
        capacity_factor=8.0)          # high capacity => no token drops
    params = init_params(moe.moe_schema(cfg), jax.random.key(0))
    params = jax.tree_util.tree_map(
        lambda x: x.astype(jnp.float32), params)
    cfg = dataclasses.replace(cfg, dtype="float32")
    x = jax.random.normal(jax.random.key(1), (2, 16, cfg.d_model), jnp.float32)
    return cfg, params, x


def _capacity_path(cfg, params, x):
    """The mesh EP layer's body (sort dispatch into (E, capacity, d)
    buffers, the exchange, the combine) on a one-device "model" axis, where
    the exchange moves nothing; the shared experts added as ``moe_apply``
    adds them."""
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:1]), ("model",))
    routed = dataclasses.replace(cfg, moe_num_shared=0)
    body = jax.shard_map(
        lambda p, xl: moe._moe_local(routed, p, xl, ep_axis="model"),
        mesh=mesh, in_specs=(P(), P()), out_specs=(P(), P()), check_vma=False)
    y, aux = body({k: v for k, v in params.items()
                   if not k.startswith("shared")}, x)
    return y + moe._shared(params, x), aux


@pytest.mark.parametrize("path", ["held", "capacity"])
def test_sort_dispatch_matches_dropless_oracle(setup, path):
    cfg, params, x = setup
    apply = moe.moe_apply if path == "held" else _capacity_path
    y1, aux1 = apply(cfg, params, x)
    y2, aux2 = moe.moe_reference(cfg, params, x)
    np.testing.assert_allclose(y1, y2, atol=1e-4, rtol=1e-3)
    np.testing.assert_allclose(aux1, aux2, atol=1e-5)


def test_einsum_twin_matches_oracle(setup):
    cfg, params, x = setup
    y1, _ = moe.moe_apply_einsum(cfg, params, x)
    y2, _ = moe.moe_reference(cfg, params, x)
    np.testing.assert_allclose(y1, y2, atol=1e-4, rtol=1e-3)


def test_capacity_drops_tokens_when_tight(setup):
    cfg, params, x = setup
    tight = dataclasses.replace(cfg, capacity_factor=0.25)
    y_tight, _ = _capacity_path(tight, params, x)
    y_full, _ = _capacity_path(cfg, params, x)
    # some tokens must differ (dropped -> only shared-expert output)
    assert not np.allclose(y_tight, y_full, atol=1e-5)
    # the held-experts layer has no capacity: it drops nothing
    np.testing.assert_allclose(moe.moe_apply(tight, params, x)[0], y_full,
                               atol=1e-4, rtol=1e-3)


def test_aux_loss_balanced_is_near_one(setup):
    cfg, params, x = setup
    # uniform router -> aux loss ~ 1 (E * sum(1/E * 1/E) * E = 1)
    p2 = dict(params)
    p2["router"] = jnp.zeros_like(params["router"])
    _, aux = moe.moe_apply(cfg, p2, x)
    assert 0.5 < float(aux) < 2.0


def test_grad_flows_through_dispatch(setup):
    cfg, params, x = setup

    def loss(p):
        y, aux = moe.moe_apply(cfg, p, x)
        return (y ** 2).mean() + 0.01 * aux

    g = jax.grad(loss)(params)
    gnorm = sum(float(jnp.abs(l).sum()) for l in jax.tree_util.tree_leaves(g))
    assert np.isfinite(gnorm) and gnorm > 0


# -- group-limited greedy routing and the chip's share of the experts --------

def _np_route(probs, n_group, topk_group, k, norm, scale):
    """Group-limited greedy top-k written out per token in NumPy: groups
    ranked by their best expert, experts of the kept groups by score; the
    lower index first on a tie."""
    n, e = probs.shape
    per = e // n_group
    ids, ws = np.zeros((n, k), np.int64), np.zeros((n, k), np.float64)
    for t in range(n):
        best = probs[t].reshape(n_group, per).max(1)
        groups = sorted(range(n_group), key=lambda g: (-best[g], g))
        cand = [i for g in groups[:topk_group]
                for i in range(g * per, (g + 1) * per)]
        chosen = sorted(cand, key=lambda i: (-probs[t, i], i))[:k]
        w = probs[t, chosen].astype(np.float64)
        ids[t], ws[t] = chosen, (w / w.sum() if norm else w) * scale
    return ids, ws


@pytest.mark.parametrize("norm", [False, True], ids=["scaled", "renormed"])
@pytest.mark.parametrize("ties", [False, True], ids=["distinct", "ties"])
def test_group_limited_routing_matches_numpy(norm, ties):
    e, g, tg, k, d, n = 16, 4, 2, 3, 8, 64
    cfg = dataclasses.replace(
        configs.get_config("deepseek-v2-236b").reduced(),
        moe_num_experts=e, moe_n_group=g, moe_topk_group=tg, moe_top_k=k,
        moe_norm_topk=norm, moe_routed_scale=16.0 if not norm else 1.0)
    rng = np.random.default_rng(7)
    router = rng.standard_normal((d, e)).astype(np.float32)
    if ties:
        # experts 5 and 6 (one group), 9 and 13 (two groups) and all of
        # group 3 read the same logits: ties inside and across groups
        router[:, 6] = router[:, 5]
        router[:, 13] = router[:, 9]
        router[:, 12:] = router[:, 12:13]
    x = rng.standard_normal((n, d)).astype(np.float32)
    ids, w, probs = moe._router(cfg, {"router": jnp.asarray(router)},
                                jnp.asarray(x))
    want_ids, want_w = _np_route(np.asarray(probs), g, tg, k, norm,
                                 cfg.moe_routed_scale)
    np.testing.assert_array_equal(np.asarray(ids), want_ids)
    # float32 softmax against its own float64 renormalisation
    np.testing.assert_allclose(np.asarray(w), want_w, rtol=1e-6)
    if ties:
        tied = np.isin(want_ids, [5, 6, 9, 13, 12, 14, 15]).sum()
        assert tied > 0


def test_expert_shares_sum_to_the_uncut_layer():
    """Eight ranks of expert parallelism, each holding one routing group of
    the layer's experts: their routed parts, with the shared experts added
    once, sum to the uncut layer's dropless reference."""
    e, g = 16, 8
    cfg = dataclasses.replace(
        configs.get_config("deepseek-v2-236b").reduced(),
        moe_num_experts=e, moe_n_group=g, moe_topk_group=3, moe_top_k=4,
        dtype="float32")
    params = init_params(moe.moe_schema(cfg), jax.random.key(2))
    params = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), params)
    x = jax.random.normal(jax.random.key(3), (2, 12, cfg.d_model))
    want, _ = moe.moe_reference(cfg, params, x)
    routed = {k: v for k, v in params.items() if not k.startswith("shared")}
    per = e // g
    total = moe._shared(params, x)
    for r in range(g):
        share = dataclasses.replace(cfg, moe_num_shared=0,
                                    moe_experts_held=(r * per, per))
        part = dict(routed, **{w: routed[w][r * per:(r + 1) * per]
                               for w in ("w_gate", "w_up", "w_down")})
        y, _ = moe.moe_apply(share, part, x)
        total = total + y
    # float32 sums in another order
    np.testing.assert_allclose(np.asarray(total), np.asarray(want),
                               atol=1e-5, rtol=1e-4)
    # one share alone is not the layer
    assert not np.allclose(np.asarray(y + moe._shared(params, x)),
                           np.asarray(want), atol=1e-3)


def test_rows_in_no_group_give_nothing(setup, monkeypatch):
    """The held-experts layer selects away the ragged matmul's rows past the
    held groups (assignments to experts held elsewhere): whatever the matmul
    leaves there, even NaN, gives nothing."""
    cfg, params, x = setup
    share = dataclasses.replace(cfg, moe_num_shared=0, moe_experts_held=(1, 2))
    part = dict(params, **{w: params[w][1:3] for w in moe.EXPERT_WEIGHTS})
    want, _ = moe.moe_apply(share, part, x)
    ragged_dot = jax.lax.ragged_dot

    def poisoned(lhs, rhs, sizes, **kw):
        out = ragged_dot(lhs, rhs, sizes, **kw)
        past = jnp.arange(out.shape[0]) >= jnp.sum(sizes)
        return jnp.where(past[:, None], jnp.nan, out)

    monkeypatch.setattr(jax.lax, "ragged_dot", poisoned)
    got, _ = moe.moe_apply(share, part, x)
    assert np.isfinite(np.asarray(got)).all()
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
