"""Model configuration system and architecture registry.

Every assigned architecture is a ``ModelConfig`` built from its published
numbers (see the per-arch files in this package).  Layer stacks are described
by a repeating ``block_pattern`` (the *superblock*) so heterogeneous models
(jamba's 1:7 mamba:attn interleave, llama-3.2-vision's every-5th cross-attn)
scan over a fixed-period block — keeping HLO size O(1) in depth.
"""

from __future__ import annotations

import dataclasses
from typing import Callable


@dataclasses.dataclass(frozen=True)
class LayerSpec:
    """One layer inside the repeating superblock."""

    mixer: str        # 'attn' | 'cross_attn' | 'mamba' | 'mlstm' | 'slstm'
    ffn: str          # 'dense' | 'moe' | 'none'


@dataclasses.dataclass(frozen=True)
class YarnScaling:
    """YaRN rotary scaling (arXiv:2309.00071) with DeepSeek's keys: the
    rotary frequencies are blended between the original and ``factor``-times
    interpolated ones over the dimensions whose wavelengths fall between
    ``beta_fast`` and ``beta_slow`` rotations of the original context, and
    the softmax scale is multiplied by ``mscale(factor, mscale_all_dim)**2``
    (see :func:`repro.models.layers.yarn_inv_freq`)."""

    factor: float
    original_max_position: int
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 0.0


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                    # dense | moe | hybrid | ssm | audio | vlm
    num_layers: int
    d_model: int
    num_heads: int
    num_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: int = 0              # 0 -> d_model // num_heads

    # --- attention variants ---
    rope_theta: float = 500000.0
    rope_scaling: YarnScaling | None = None
    qk_norm: bool = False
    qkv_bias: bool = False
    use_mla: bool = False
    kv_lora_rank: int = 0
    q_lora_rank: int = 0
    rope_head_dim: int = 0         # MLA decoupled-RoPE dims
    v_head_dim: int = 0            # MLA value head dim (0 -> head_dim)
    is_causal: bool = True         # False for encoder-only (hubert)

    # --- MoE ---
    moe_num_experts: int = 0
    moe_top_k: int = 0
    moe_num_shared: int = 0
    moe_d_ff: int = 0              # expert hidden dim (0 -> d_ff)
    # routing: softmax over every expert, the ``moe_topk_group`` best of
    # ``moe_n_group`` equal groups (by their best expert) kept, the top
    # ``moe_top_k`` of those taken; weights renormalised or not, then scaled
    # (DeepSeek-V2's group_limited_greedy; one group is plain top-k)
    moe_n_group: int = 1
    moe_topk_group: int = 1
    moe_norm_topk: bool = True
    moe_routed_scale: float = 1.0
    # (first, count): the routed experts this device holds, as one rank of
    # expert parallelism does; None holds all of them
    moe_experts_held: tuple[int, int] | None = None
    capacity_factor: float = 1.25          # the mesh EP layer's buffers
    router_aux_loss: float = 0.01

    # --- SSM (mamba) ---
    ssm_state_dim: int = 16
    ssm_conv_dim: int = 4
    ssm_expand: int = 2
    ssm_dt_rank: int = 0           # 0 -> ceil(d_model/16)
    ssm_chunk: int = 256

    # --- xLSTM ---
    mlstm_proj_factor: float = 2.0
    slstm_ffn_factor: float = 1.3333333

    # --- multimodal stubs ---
    cross_attn_period: int = 0     # vlm: every n-th layer is cross-attn
    num_image_tokens: int = 0      # patch-embedding count from the stub tower
    frontend: str = "none"         # 'none' | 'audio_frames' | 'vision_patches'

    # --- layer stack ---
    block_pattern: tuple[LayerSpec, ...] = (LayerSpec("attn", "dense"),)
    # leading attention + dense-MLP layers (width d_ff) before the
    # superblocks, as DeepSeek's first_k_dense_replace
    first_k_dense: int = 0

    # --- numerics / misc ---
    norm_eps: float = 1e-5
    tie_embeddings: bool = False
    dtype: str = "bfloat16"
    sub_quadratic: bool = False    # supports long_500k decode

    # ------------------------------------------------------------------
    def __post_init__(self):
        e = self.moe_num_experts
        if not e:
            return
        g, tg = self.moe_n_group, self.moe_topk_group
        if e % g or not 1 <= tg <= g or self.moe_top_k > tg * (e // g):
            raise ValueError(
                f"{self.name}: {e} experts in {g} groups, top {tg} groups, "
                f"top {self.moe_top_k} experts do not fit together")
        first, count = self.held_experts
        if first < 0 or count < 1 or first + count > e:
            raise ValueError(f"{self.name}: held experts {first}..."
                             f"{first + count - 1} not among {e}")

    @property
    def held_experts(self) -> tuple[int, int]:
        """(first, count) of the routed experts this device holds."""
        return self.moe_experts_held or (0, self.moe_num_experts)

    @property
    def resolved_head_dim(self) -> int:
        return self.head_dim or self.d_model // self.num_heads

    @property
    def resolved_v_head_dim(self) -> int:
        return self.v_head_dim or self.resolved_head_dim

    @property
    def resolved_moe_d_ff(self) -> int:
        return self.moe_d_ff or self.d_ff

    @property
    def period(self) -> int:
        return len(self.block_pattern)

    @property
    def num_superblocks(self) -> int:
        n = self.num_layers - self.first_k_dense
        assert n % self.period == 0, \
            f"{self.name}: {n} layers not divisible by period {self.period}"
        return n // self.period

    @property
    def ssm_d_inner(self) -> int:
        return self.ssm_expand * self.d_model

    @property
    def resolved_dt_rank(self) -> int:
        return self.ssm_dt_rank or -(-self.d_model // 16)

    def param_count(self) -> int:
        """Total parameters (analytic; embeddings + blocks + head)."""
        from repro.models.transformer import count_params
        return count_params(self)

    def active_param_count(self) -> int:
        from repro.models.transformer import count_params
        return count_params(self, active_only=True)

    def reduced(self, **overrides) -> "ModelConfig":
        """A small same-family config for CPU smoke tests."""
        experts = min(self.moe_num_experts, 4)
        groups = min(self.moe_n_group, 2)
        lead = min(self.first_k_dense, 1)
        small = dict(
            num_layers=lead + self.period * 2,
            first_k_dense=lead,
            d_model=64,
            num_heads=max(4, min(self.num_heads, 4)),
            num_kv_heads=max(2, min(self.num_kv_heads, 2)),
            head_dim=16,
            d_ff=128 if self.d_ff else 0,
            vocab_size=256,
            kv_lora_rank=32 if self.use_mla else 0,
            q_lora_rank=0,
            rope_head_dim=8 if self.use_mla else 0,
            v_head_dim=16 if self.use_mla else 0,
            moe_num_experts=experts,
            moe_top_k=min(self.moe_top_k, 2),
            # as many groups as 4 experts allow, still keeping fewer than all
            moe_n_group=groups,
            moe_topk_group=(groups if self.moe_topk_group == self.moe_n_group
                            else min(self.moe_topk_group, groups - 1)),
            moe_experts_held=None,
            moe_num_shared=min(self.moe_num_shared, 1),
            moe_d_ff=32 if self.moe_num_experts else 0,
            ssm_state_dim=8,
            ssm_chunk=16,
            num_image_tokens=8 if self.family == "vlm" else 0,
            name=self.name + "-smoke",
        )
        small.update(overrides)
        return dataclasses.replace(self, **small)


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------
_REGISTRY: dict[str, Callable[[], ModelConfig]] = {}


def register(name: str):
    def deco(fn: Callable[[], ModelConfig]):
        _REGISTRY[name] = fn
        return fn
    return deco


def get_config(name: str) -> ModelConfig:
    _ensure_imports()
    if name not in _REGISTRY:
        raise KeyError(f"unknown arch {name!r}; have {sorted(_REGISTRY)}")
    return _REGISTRY[name]()


def list_archs() -> list[str]:
    _ensure_imports()
    return sorted(_REGISTRY)


def _ensure_imports() -> None:
    # import the per-arch modules for registration side effects
    import importlib
    for mod in ("xlstm_1_3b", "jamba_1_5_large_398b", "llama4_scout_17b_a16e",
                "deepseek_v2_236b", "qwen1_5_110b", "phi3_medium_14b",
                "qwen3_4b", "llama3_2_3b", "hubert_xlarge",
                "llama_3_2_vision_90b", "gpt2_small"):
        importlib.import_module(f"repro.configs.{mod}")


# shape sets assigned to LM-family archs -------------------------------------
@dataclasses.dataclass(frozen=True)
class ShapeConfig:
    name: str
    seq_len: int
    global_batch: int
    kind: str          # 'train' | 'prefill' | 'decode'


SHAPES: dict[str, ShapeConfig] = {
    "train_4k": ShapeConfig("train_4k", 4096, 256, "train"),
    "prefill_32k": ShapeConfig("prefill_32k", 32768, 32, "prefill"),
    "decode_32k": ShapeConfig("decode_32k", 32768, 128, "decode"),
    "long_500k": ShapeConfig("long_500k", 524288, 1, "decode"),
}


def supported_shapes(cfg: ModelConfig) -> list[str]:
    """Which assigned shapes are semantically valid for this arch.

    Skips (recorded in DESIGN.md §4): decode shapes for encoder-only archs;
    long_500k for pure full-attention archs (needs sub-quadratic mixing).
    """
    out = ["train_4k", "prefill_32k"]
    if cfg.is_causal:
        out.append("decode_32k")
        if cfg.sub_quadratic:
            out.append("long_500k")
    return out
