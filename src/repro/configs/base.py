"""ModelConfig — one dataclass covering every assigned architecture family."""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                  # dense | moe | ssm | hybrid | encoder | vlm
    layers: int
    d_model: int
    n_heads: int                 # 0 for attention-free (ssm)
    kv_heads: int
    d_ff: int
    vocab: int
    head_dim: int = 0            # 0 => d_model // n_heads
    qk_norm: bool = False
    rope_theta: float = 10000.0
    norm_eps: float = 1e-6       # RMSNorm epsilon
    # --- MoE ---
    n_experts: int = 0
    top_k: int = 0
    capacity_factor: float = 1.25
    # pad the expert dim to a mesh-divisible count (dummy experts hold zero
    # weights and receive no tokens); 0 = no padding
    n_experts_padded: int = 0
    # shared experts: one always-on SwiGLU of width n_shared_experts * d_ff
    n_shared_experts: int = 0
    # router scores: softmax over every expert, or sigmoid per expert with
    # a correction bias that steers the top-k choice only (DeepSeek-V3)
    router_scoring: str = "softmax"   # softmax | sigmoid
    norm_topk_prob: bool = True       # renormalize the chosen gates
    routed_scaling: float = 1.0       # times the routed experts' sum
    # the expert-parallel share this chip holds: experts
    # [expert_offset, expert_offset + experts_held) of every expert layer,
    # routed over all n_experts, dispatched dropless; 0 = every expert,
    # capacity-dispatched
    experts_held: int = 0
    expert_offset: int = 0
    # leading dense layers (SwiGLU of width dense_d_ff) before the expert
    # layers (DeepSeek's first_k_dense_replace)
    first_k_dense: int = 0
    dense_d_ff: int = 0
    # --- latent attention (MLA); kv_lora_rank 0 = multi-head attention ---
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    # --- SSM (Mamba-2 / SSD) ---
    ssm_state: int = 0
    ssm_head_dim: int = 64
    ssm_expand: int = 2
    conv_kernel: int = 4
    ssd_chunk: int = 128
    # --- hybrid (Zamba2-style shared attention) ---
    attn_every: int = 0          # shared attn applied before every k-th block
    # --- modality frontend stubs ---
    frontend: str = "none"       # none | audio_frames | vision_patches
    num_patches: int = 0         # VLM: patches prepended to the sequence
    # --- quantization (BitNet b1.58 QAT on projections) ---
    quantization: str = "bitnet"   # bitnet | none
    weight_bits: int = 2
    # --- runtime ---
    causal: bool = True
    tie_embeddings: bool = True
    dtype: str = "bfloat16"
    remat: str = "block"          # none | block (checkpoint each layer block)
    kernel_backend: str = "reference"   # reference | pallas (TPU)
    max_seq: int = 4096

    # ------------------------------------------------------------------ #
    @property
    def head_dim_(self) -> int:
        if self.head_dim:
            return self.head_dim
        return self.d_model // max(self.n_heads, 1)

    @property
    def attn_inner(self) -> int:
        return self.n_heads * self.head_dim_

    @property
    def d_inner(self) -> int:
        """SSM inner width."""
        return self.ssm_expand * self.d_model

    @property
    def ssm_heads(self) -> int:
        return self.d_inner // self.ssm_head_dim

    @property
    def group_size(self) -> int:
        return self.n_heads // max(self.kv_heads, 1)

    @property
    def n_experts_total(self) -> int:
        """Expert-dim size incl. sharding padding (>= n_experts)."""
        return max(self.n_experts_padded, self.n_experts)

    @property
    def has_attention(self) -> bool:
        return self.family != "ssm"

    @property
    def is_decoder(self) -> bool:
        return self.family != "encoder"

    def replace(self, **kw) -> "ModelConfig":
        return dataclasses.replace(self, **kw)

    # ------------------------------------------------------------------ #
    @property
    def n_experts_held(self) -> int:
        """Experts of each expert layer whose weights this chip holds."""
        return self.experts_held or self.n_experts

    @property
    def moe_layers(self) -> int:
        return self.layers - self.first_k_dense if self.family == "moe" else 0

    def attn_params(self) -> int:
        """One attention sub-layer: its projections (and MLA's latent norm)."""
        d, h = self.d_model, self.n_heads
        if self.kv_lora_rank:
            r, rope = self.kv_lora_rank, self.qk_rope_head_dim
            nope, v = self.qk_nope_head_dim, self.v_head_dim
            return (d * h * (nope + rope) + d * (r + rope) + r
                    + r * h * (nope + v) + h * v * d)
        hd = self.head_dim_
        return d * h * hd + 2 * d * self.kv_heads * hd + h * hd * d

    def expert_params(self) -> int:
        """One routed expert (a SwiGLU of width d_ff)."""
        return 3 * self.d_model * self.d_ff

    def param_count(self) -> int:
        """Analytic parameter count of what this chip holds (used for
        6*N*D model-FLOPs): every layer, and of each expert layer its held
        experts."""
        d, l = self.d_model, self.layers
        n = self.vocab * d + d                   # embeddings, final norm
        if not self.tie_embeddings:
            n += self.vocab * d
        per_layer = 0
        if self.family in ("dense", "moe", "encoder", "vlm"):
            per_layer += self.attn_params() + 2 * d   # + norms
            if self.family == "moe":
                n += self.first_k_dense * 3 * d * self.dense_d_ff
                router = d * self.n_experts
                if self.router_scoring == "sigmoid":
                    router += self.n_experts     # selection bias
                experts = self.n_experts_held + self.n_shared_experts
                n += self.moe_layers * (router
                                        + experts * self.expert_params())
            else:
                per_layer += 3 * d * self.d_ff   # swiglu
        elif self.family in ("ssm", "hybrid"):
            di, ns, nh = self.d_inner, self.ssm_state, self.ssm_heads
            in_proj = d * (2 * di + 2 * ns + nh)
            out_proj = di * d
            per_layer += in_proj + out_proj + 3 * nh + d  # +dt/A/D + norm
        n += per_layer * l
        if self.family == "hybrid" and self.attn_every:
            hd = self.head_dim_
            shared = d * self.n_heads * hd + 2 * d * self.kv_heads * hd
            shared += self.n_heads * hd * d + 3 * d * self.d_ff + 2 * d
            n += shared                          # one shared block (Zamba2)
        return n

    def active_param_count(self) -> int:
        """Active params per token (MoE: at most top_k of the held
        experts)."""
        if self.family != "moe":
            return self.param_count()
        idle = self.n_experts_held - min(self.top_k, self.n_experts_held)
        return (self.param_count()
                - idle * self.expert_params() * self.moe_layers)


@dataclasses.dataclass(frozen=True)
class ShapeConfig:
    """An assigned input-shape cell."""

    name: str
    seq_len: int
    global_batch: int
    kind: str      # train | prefill | decode

    @property
    def is_train(self) -> bool:
        return self.kind == "train"


TRAIN_4K = ShapeConfig("train_4k", 4096, 256, "train")
PREFILL_32K = ShapeConfig("prefill_32k", 32768, 32, "prefill")
DECODE_32K = ShapeConfig("decode_32k", 32768, 128, "decode")
LONG_500K = ShapeConfig("long_500k", 524288, 1, "decode")
ALL_SHAPES: Tuple[ShapeConfig, ...] = (
    TRAIN_4K, PREFILL_32K, DECODE_32K, LONG_500K,
)


def shape_by_name(name: str) -> ShapeConfig:
    for s in ALL_SHAPES:
        if s.name == name:
            return s
    raise KeyError(name)
