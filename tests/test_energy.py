"""Cost rules and the energy model: per-op pricing, ordering, replay."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.costs import graph_cost, node_cost
from repro.core.energy import AnalyticalEnergyModel, ReplayProfiler
from repro.core.graph import trace
from repro.hw.specs import TPU_V5E


def _graph(fn, *args):
    return trace(fn, *args)


def test_matmul_flops():
    g = _graph(lambda a, b: a @ b, jnp.ones((64, 128)), jnp.ones((128, 32)))
    c = graph_cost(g)
    assert c.flops == pytest.approx(2 * 64 * 128 * 32, rel=0.01)


def test_precision_highest_prices_fp32():
    def hi(a, b):
        return jax.lax.dot(a, b, precision=jax.lax.Precision.HIGHEST)
    g = _graph(hi, jnp.ones((32, 32), jnp.bfloat16), jnp.ones((32, 32), jnp.bfloat16))
    dot = next(n for n in g.nodes if n.primitive == "dot_general")
    assert node_cost(g, dot).fp32_fraction == 1.0


def test_scan_multiplies_body():
    def f(x):
        def body(c, _):
            return jnp.tanh(c @ c), None
        out, _ = jax.lax.scan(body, x, None, length=7)
        return out
    g = _graph(f, jnp.ones((16, 16)))
    c = graph_cost(g)
    single = 2 * 16**3
    assert c.flops >= 7 * single


def test_dynamic_update_slice_cheaper_than_concat():
    cache = jnp.zeros((4, 1024, 64))
    new = jnp.ones((4, 1, 64))

    def by_concat(cache, new):
        return jnp.concatenate([cache[:, :512], new, cache[:, 513:]], axis=1)

    def by_dus(cache, new):
        return jax.lax.dynamic_update_slice_in_dim(cache, new, 512, axis=1)

    c1 = graph_cost(_graph(by_concat, cache, new))
    c2 = graph_cost(_graph(by_dus, cache, new))
    assert c2.hbm_bytes < 0.01 * c1.hbm_bytes


def test_collective_priced_in_ici_bytes():
    from jax.sharding import Mesh, PartitionSpec as P
    mesh = Mesh(np.array(jax.devices()[:1]), ("dp",))

    def f(x):
        return jax.shard_map(lambda y: jax.lax.psum(y, "dp"), mesh=mesh,
                         in_specs=P(), out_specs=P())(x)
    g = _graph(f, jnp.ones((128, 128)))
    c = graph_cost(g)
    assert c.ici_bytes >= 2 * 128 * 128 * 4


def test_pallas_call_priced_as_single_pass():
    from repro.kernels import ops as kops
    x = jnp.ones((256, 256))

    def unfused(x):
        c = 0.7978845608
        inner = c * (x + 0.044715 * x * x * x)
        return 0.5 * x * (1.0 + jnp.tanh(inner))

    g_fused = _graph(lambda x: kops.fused_gelu(x), x)
    g_unfused = _graph(unfused, x)
    b_fused = graph_cost(g_fused).hbm_bytes
    b_unfused = graph_cost(g_unfused).hbm_bytes
    assert b_fused < 0.5 * b_unfused


def test_energy_model_total_positive_and_ordered():
    model = AnalyticalEnergyModel(TPU_V5E)
    g_small = _graph(lambda a, b: a @ b, jnp.ones((32, 32)), jnp.ones((32, 32)))
    g_big = _graph(lambda a, b: a @ b, jnp.ones((256, 256)), jnp.ones((256, 256)))
    e_small = model.profile(g_small).total_energy_j
    e_big = model.profile(g_big).total_energy_j
    assert 0 < e_small < e_big


def test_replay_profiler_measures_wall_time():
    prof = ReplayProfiler(max_replay_iters=4)
    g = _graph(lambda a, b: jnp.tanh(a @ b), jnp.ones((128, 128)),
               jnp.ones((128, 128)))
    p = prof.profile(g, jnp.ones((128, 128)), jnp.ones((128, 128)))
    assert p.total_energy_j > 0
    assert all(op.time_s >= 0 for op in p.ops)
    assert {op.primitive for op in p.ops} >= {"dot_general", "tanh"}


def test_replay_spec_follows_the_device_kind(monkeypatch):
    """Measured time is priced through the spec of the device it ran on;
    on the CPU that stays the host spec, so replay backend ids (and the
    goldens keyed by them) do not rotate, and an unknown device raises."""
    from repro.core.energy import ReplayBackend, backend_from_name
    from repro.hw.specs import CPU_HOST, spec_for_device_kind
    assert spec_for_device_kind("cpu") is CPU_HOST
    assert spec_for_device_kind("TPU v5 lite") is TPU_V5E
    assert ReplayBackend().spec is CPU_HOST
    assert backend_from_name("replay").spec is CPU_HOST
    assert ReplayProfiler().spec is CPU_HOST

    class _Unknown:
        device_kind = "TPU v9 imaginary"
    monkeypatch.setattr(jax, "devices", lambda *a, **k: [_Unknown()])
    with pytest.raises(KeyError, match="TPU v9 imaginary"):
        ReplayBackend()
    with pytest.raises(KeyError, match="TPU v9 imaginary"):
        ReplayProfiler()


def test_profile_top_k_and_breakdown():
    model = AnalyticalEnergyModel(TPU_V5E)
    g = _graph(lambda a, b: jnp.tanh(a @ b) + 1.0, jnp.ones((256, 256)),
               jnp.ones((256, 256)))
    p = model.profile(g)
    top = p.top_k(1)
    assert top[0].primitive == "dot_general"
    agg = p.by_primitive()
    assert set(agg) >= {"dot_general", "tanh", "add"}


# -- optimized-HLO attribution (core/hlo_costs.py) ---------------------------

def _module(text):
    from repro.core import hlo_costs
    entry, comps = hlo_costs.parse_hlo_module(text)
    return entry, comps, {i.name: i for c in comps.values() for i in c}


def test_hlo_operands_printed_by_name_resolve_to_their_shapes():
    """Newer XLA prints operands by name only; their shapes come from the
    producers, so a dot's contracted size and its traffic are known."""
    from repro.core import hlo_costs
    _, _, ins = _module("""
ENTRY %main (a: f32[4,16], b: f32[16,8]) -> f32[4,8] {
  %a = f32[4,16]{1,0} parameter(0)
  %b = f32[16,8]{1,0} parameter(1)
  ROOT %dot = f32[4,8]{1,0} dot(%a, %b), lhs_contracting_dims={1}, rhs_contracting_dims={0}
}
""")
    dot = ins["dot"]
    assert dot.dims_in == [(4, 16), (16, 8)]
    flops, _, hbm, _ = hlo_costs._instr_cost(dot)
    assert flops == 2 * 4 * 8 * 16
    assert hbm == (4 * 16 + 16 * 8 + 4 * 8) * 4


@pytest.mark.parametrize("lhs,rhs,out,attrs,flops", [
    # LM head: (B*S, d) x (V, d)^T
    ((4, 128, 768), (50257, 768, 1), (4, 128, 50257),
     "window={size=1}, dim_labels=0bf_oi0->0bf", 2 * 512 * 768 * 50257),
    # q projection, heads as a padded spatial dimension
    ((4, 128, 768, 1), (768, 12, 64, 1), (4, 128, 12, 64),
     "window={size=1x12 pad=0_0x11_11 rhs_reversal=0x1}, "
     "dim_labels=0bf1_i1o0->0b1f", 2 * 512 * 768 * 768),
    # attention scores, batch and heads as dilated spatial dimensions
    ((4, 128, 12, 64), (4, 128, 12, 64), (4, 12, 128, 128),
     "window={size=4x12 stride=3x11 lhs_dilate=4x12}, "
     "dim_labels=0b1f_0o1i->01bf", 2 * 4 * 12 * 128 * 128 * 64),
])
def test_hlo_convolution_counts_the_taps_on_real_input(lhs, rhs, out, attrs,
                                                       flops):
    """The TPU compiler lowers every dot to a convolution; its flops are
    those of the dot, not of the padded or dilated window."""
    from repro.core import hlo_costs

    def shape(dims):
        return f"bf16[{','.join(map(str, dims))}]"
    _, _, ins = _module(f"""
ENTRY %main (x: {shape(lhs)}, k: {shape(rhs)}) -> {shape(out)} {{
  %x = {shape(lhs)} parameter(0)
  %k = {shape(rhs)} parameter(1)
  ROOT %conv = {shape(out)} convolution(%x, %k), {attrs}
}}
""")
    assert hlo_costs._conv_flops(ins["conv"]) == flops


def test_hlo_while_trip_count_read_from_its_condition():
    from repro.core import hlo_costs
    entry, comps, ins = _module("""
%cond (p: (s32[], f32[8])) -> pred[] {
  %p = (s32[], f32[8]{0}) parameter(0)
  %i = s32[] get-tuple-element(%p), index=0
  %n = s32[] constant(12)
  ROOT %lt = pred[] compare(%i, %n), direction=LT
}

ENTRY %main (x: f32[8]) -> (s32[], f32[8]) {
  %x = f32[8]{0} parameter(0)
  %zero = s32[] constant(0)
  %start = s32[] copy(%zero)
  %init = (s32[], f32[8]{0}) tuple(%start, %x)
  ROOT %loop = (s32[], f32[8]{0}) while(%init), condition=%cond, body=%body
}
""")
    assert hlo_costs._while_trips(comps, entry, ins["loop"]) == 12


def test_hlo_on_chip_memory_is_not_hbm_traffic():
    """A slice-start reads its window from HBM into on-chip memory (S(1));
    the value it lands in moves no HBM bytes."""
    from repro.core import hlo_costs
    _, _, ins = _module("""
ENTRY %main (w: bf16[2,768,3072]) -> bf16[1,768,3072] {
  %w = bf16[2,768,3072]{2,1,0:T(8,128)(2,1)} parameter(0)
  %ss = ((bf16[2,768,3072]{2,1,0:T(8,128)(2,1)}), bf16[1,768,3072]{2,1,0:T(8,128)(2,1)S(1)}, s32[]{:S(2)}) slice-start(%w), slice={[0:1], [0:768], [0:3072]}
  ROOT %sd = bf16[1,768,3072]{2,1,0:T(8,128)(2,1)S(1)} slice-done(%ss)
}
""")
    window = 768 * 3072 * 2
    assert ins["sd"].result_bytes == 0 and ins["sd"].footprint == window
    assert hlo_costs._instr_cost(ins["ss"])[2] == window
    assert "slice-done" in hlo_costs._FREE_OPS


def test_hlo_fusion_reads_only_the_slice_of_a_stacked_weight():
    """A scan body's fusion that slices one layer out of the stacked
    weights (here through a nested fusion) reads that layer only."""
    from repro.core import hlo_costs
    _, comps, _ = _module("""
%inner (p0: bf16[2,64,128], p1: s32[]) -> bf16[1,64,128] {
  %p0 = bf16[2,64,128]{2,1,0} parameter(0)
  %p1 = s32[] parameter(1)
  %z = s32[] constant(0)
  ROOT %ds = bf16[1,64,128]{2,1,0} dynamic-slice(%p0, %p1, %z, %z), dynamic_slice_sizes={1,64,128}
}

%outer (q0: bf16[2,64,128], q1: s32[], q2: f32[64]) -> bf16[1,64,128] {
  %q0 = bf16[2,64,128]{2,1,0} parameter(0)
  %q1 = s32[] parameter(1)
  %q2 = f32[64]{0} parameter(2)
  ROOT %f = bf16[1,64,128]{2,1,0} fusion(%q0, %q1), kind=kLoop, calls=%inner
}
""")
    assert hlo_costs._fusion_read_bytes(comps, "outer") == (
        64 * 128 * 2 + 4 + 64 * 4)
