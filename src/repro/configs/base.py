"""Config system: model / parallelism / train / shape configs + registry.

Every assigned architecture is a ``ModelConfig`` in its own module under
``repro.configs``; ``repro.configs.registry`` maps ``--arch`` ids to them.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Sequence

import jax.numpy as jnp

__all__ = [
    "MLAConfig",
    "MoEConfig",
    "SSMConfig",
    "ModelConfig",
    "ShapeConfig",
    "ParallelConfig",
    "TrainConfig",
    "SHAPES",
]


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    num_experts: int
    top_k: int
    d_ff_expert: int
    capacity_factor: float = 1.25
    router_z_coef: float = 1e-3
    aux_loss_coef: float = 1e-2
    # Routed experts this chip holds of each layer, ids [0, experts_held):
    # the router still scores all ``num_experts`` and picks ``top_k``, and
    # assignments to experts held elsewhere contribute nothing here (one
    # chip of an expert-parallel deployment). None holds every expert.
    experts_held: Optional[int] = None
    # Shared experts every token passes through, each ``d_ff_expert`` wide
    # (run as one SwiGLU of n_shared_experts * d_ff_expert).
    n_shared_experts: int = 0
    # True: the top-k weights are a softmax over the k selected logits.
    # False: they are the selected experts' probabilities under a softmax
    # over all ``num_experts`` (DeepSeek-V2, OLMoE as published).
    norm_topk_prob: bool = True

    @property
    def held(self) -> int:
        return self.num_experts if self.experts_held is None else self.experts_held


@dataclasses.dataclass(frozen=True)
class MLAConfig:
    """Multi-head latent attention (DeepSeek-V2): keys and values are made
    per head from one cached latent per token, ``kv_lora_rank`` wide, beside
    one shared rotary key ``qk_rope_head_dim`` wide. RoPE is YaRN-scaled by
    ``rope_factor`` over ``rope_original_max`` positions (factor 1: plain)."""

    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    q_lora_rank: Optional[int] = None     # a low-rank query path is not built
    rope_factor: float = 1.0
    rope_original_max: int = 4096
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 1.0

    def __post_init__(self):
        if self.q_lora_rank is not None:
            raise ValueError("latent attention takes queries from one plain projection "
                             "(q_lora_rank None)")

    @property
    def latent_dim(self) -> int:
        """Width of the cached latent: the compressed KV and the rotary key."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim


@dataclasses.dataclass(frozen=True)
class SSMConfig:
    state_dim: int = 128          # N
    head_dim: int = 64            # P
    expand: int = 2               # d_inner = expand * d_model
    conv_width: int = 4
    chunk: int = 256              # SSD chunk length
    # hybrid (zamba2-style): apply a shared attention block every k layers
    shared_attn_every: int = 0    # 0 = pure SSM


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                  # dense | moe | ssm | hybrid | encdec | vlm
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab: int
    head_dim: Optional[int] = None          # defaults to d_model // n_heads
    qkv_bias: bool = False
    window: Optional[int] = None            # sliding-window attention
    rope_theta: float = 10000.0
    tie_embeddings: bool = False
    norm_eps: float = 1e-5
    moe: Optional[MoEConfig] = None
    ssm: Optional[SSMConfig] = None
    mla: Optional[MLAConfig] = None         # latent attention in every layer
    first_dense_layers: int = 0             # leading layers with a dense
                                            # d_ff FFN ahead of the MoE stack
    # encdec
    n_encoder_layers: int = 0
    # vlm / audio stubs: number of prefix embedding positions fed by the
    # (stubbed) modality frontend for train/prefill shapes
    n_prefix_embeds: int = 0
    # serving
    eos_id: int = 1                          # end-of-sequence token id
    # execution
    dtype: str = "bfloat16"                  # activation/compute dtype
    param_dtype: str = "float32"
    attn_impl: str = "auto"                  # auto | pallas | pallas_interpret
                                             # | xla (fused blockwise bwd)
                                             # | jnp (recompute-VJP fallback)
                                             # | reference
    attn_order: str = "sawtooth"             # KV traversal order: cyclic |
                                             # sawtooth (the paper's technique,
                                             # on by default) | block_snake
                                             # (capacity-bounded reversal —
                                             # core/schedule.py Traversal IR)
    snake_group: Optional[int] = None        # block_snake reversal window in
                                             # KV tiles; None = schedule
                                             # default. Size to the modeled
                                             # LLC (benchmarks/hillclimb.py
                                             # --sweep-orders).
    q_block: int = 512
    kv_block: int = 512
    bwd_q_block: Optional[int] = None        # fused-backward kernel tiles;
    bwd_kv_block: Optional[int] = None       # None = inherit q_block/kv_block
                                             # (autotuned separately — the bwd
                                             # working set is larger; see
                                             # benchmarks/hillclimb.py)
    remat: str = "full"                      # none | full | dots
    score_dtype: str = "float32"             # attention score/probs dtype in
                                             # the blockwise XLA path (bf16
                                             # halves the dominant HBM term)
    moe_serve_dropless: bool = True          # serve MoE via ragged_dot
    ssd_impl: str = "auto"                   # pallas | pallas_interpret | xla
    kv_cache_dtype: str = "bfloat16"         # bfloat16 | int8 (per-vector
                                             # symmetric scales; halves the
                                             # decode-cache HBM footprint)
    kv_layout: str = "contiguous"            # contiguous | paged (shared page
                                             # pool + per-sequence block
                                             # tables; full attention only —
                                             # DESIGN.md §8)
    page_size: Optional[int] = None          # KV page rows; defaults to
                                             # kv_block so pages coincide with
                                             # the schedule's KV tiles
    scan_layers: bool = True                 # False: python-unrolled layer loop
                                             # (dry-run roofline extrapolation —
                                             # XLA counts while bodies once)
    logit_softcap: Optional[float] = None

    @property
    def hd(self) -> int:
        return self.head_dim or (self.d_model // self.n_heads)

    def activation_dtype(self):
        return jnp.dtype(self.dtype)

    def parameter_dtype(self):
        return jnp.dtype(self.param_dtype)

    def with_(self, **kw) -> "ModelConfig":
        return dataclasses.replace(self, **kw)

    def reduced(self) -> "ModelConfig":
        """Tiny same-family config for CPU smoke tests."""
        kw: dict = dict(
            n_layers=2,
            d_model=64,
            n_heads=4,
            n_kv_heads=max(1, min(self.n_kv_heads, 2)),
            d_ff=128,
            vocab=256,
            head_dim=16,
            q_block=64,
            kv_block=64,
            param_dtype="float32",
            dtype="float32",
            remat="none",
        )
        if self.moe is not None:
            top_k = min(self.moe.top_k, 2)
            held = self.moe.experts_held
            kw["moe"] = dataclasses.replace(
                self.moe, num_experts=4, top_k=top_k, d_ff_expert=32,
                experts_held=None if held is None
                else max(top_k, held * 4 // self.moe.num_experts),
            )
        if self.mla is not None:
            kw["mla"] = dataclasses.replace(
                self.mla, kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
                v_head_dim=16,
            )
            # Two query heads on the one latent head: the CPU's paged
            # attention scores every head of a 512-row chunk against every
            # page, which long-document traffic makes the tests' main cost.
            kw["n_heads"] = 2
        if self.first_dense_layers:
            kw["first_dense_layers"] = 1
        if self.ssm is not None:
            kw["ssm"] = dataclasses.replace(
                self.ssm,
                state_dim=16,
                head_dim=16,
                chunk=32,
                shared_attn_every=2 if self.ssm.shared_attn_every else 0,
            )
        if self.n_encoder_layers:
            kw["n_encoder_layers"] = 2
        if self.n_prefix_embeds:
            kw["n_prefix_embeds"] = 8
        if self.window is not None:
            kw["window"] = 32
        return self.with_(**kw)


@dataclasses.dataclass(frozen=True)
class ShapeConfig:
    name: str
    seq_len: int
    global_batch: int
    kind: str                     # train | prefill | decode

    def reduced(self) -> "ShapeConfig":
        return dataclasses.replace(
            self, seq_len=min(self.seq_len, 128), global_batch=min(self.global_batch, 2)
        )


SHAPES: dict[str, ShapeConfig] = {
    "train_4k": ShapeConfig("train_4k", 4096, 256, "train"),
    "prefill_32k": ShapeConfig("prefill_32k", 32768, 32, "prefill"),
    "decode_32k": ShapeConfig("decode_32k", 32768, 128, "decode"),
    "long_500k": ShapeConfig("long_500k", 524288, 1, "decode"),
}


@dataclasses.dataclass(frozen=True)
class ParallelConfig:
    """How logical dims map onto the mesh + runtime knobs."""

    fsdp_axes: Sequence[str] = ("pod", "data")   # parameter/optimizer sharding
    tensor_axis: str = "model"                    # TP / EP axis
    data_axes: Sequence[str] = ("pod", "data")   # batch sharding
    seq_shard_activations: bool = False           # sequence-shard residuals
    microbatches: int = 1                         # gradient accumulation
    grad_compression: str = "none"                # none | int8_pod
    zero_grads: bool = True                       # reduce-scattered grads


@dataclasses.dataclass(frozen=True)
class TrainConfig:
    lr: float = 3e-4
    warmup_steps: int = 100
    total_steps: int = 1000
    weight_decay: float = 0.1
    b1: float = 0.9
    b2: float = 0.95
    grad_clip: float = 1.0
    z_loss: float = 1e-4
    optimizer: str = "adamw"          # adamw | adamw_factored
    seed: int = 0
    checkpoint_every: int = 100
    checkpoint_dir: str = "checkpoints"
    keep_checkpoints: int = 3
