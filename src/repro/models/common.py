"""Model configuration and parameter-init utilities for the workload zoo.

One :class:`ModelConfig` describes every architecture in the assigned pool
(dense / MoE / MLA / hybrid-recurrent / xLSTM / audio / VLM backbones) plus
the paper's own models. Blocks are stacked by ``block_pattern`` (repeated to
``n_layers``); homogeneous repeats are ``lax.scan``-stacked for compile-time
and HLO-size control.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab_size: int

    # block layout; entries: "attn" | "local" | "rec" | "mlstm" | "slstm"
    block_pattern: Tuple[str, ...] = ("attn",)
    head_dim: Optional[int] = None          # default d_model // n_heads
    window_size: int = 1024                 # for "local" blocks

    # attention details
    qkv_bias: bool = False
    qk_norm: bool = False
    attn_logit_softcap: Optional[float] = None
    causal: bool = True                     # False => encoder (BERT/ViT)

    # positions
    pos_emb: str = "rope"                   # rope|sinusoidal|learned|none
    rope_base: float = 10000.0
    rope_fraction: float = 1.0
    max_position: int = 1 << 19

    # norms
    norm: str = "rmsnorm"                   # rmsnorm|layernorm
    post_norm: bool = False                 # gemma-style post-block norms
    zero_centered_norm: bool = False        # gemma-style (1 + scale)

    # FFN
    ffn: str = "swiglu"                     # swiglu|geglu|gelu|relu|silu
    ffn_bias: bool = False

    # MoE
    n_experts: int = 0
    n_shared_experts: int = 0
    top_k: int = 0
    moe_d_ff: int = 0
    capacity_factor: float = 1.25
    first_dense_layers: int = 0
    router_aux_weight: float = 0.01

    # MLA (deepseek)
    mla: bool = False
    kv_lora_rank: int = 512
    qk_nope_dim: int = 128
    qk_rope_dim: int = 64
    v_head_dim: int = 128

    # recurrent (RG-LRU / griffin)
    lru_width: Optional[int] = None
    conv_width: int = 4

    # xLSTM
    mlstm_proj_factor: float = 2.0
    slstm_ff_factor: float = 4.0 / 3.0
    mlstm_chunk: int = 256

    # vision (ViT classifier / single-stage detector — models/vision.py);
    # image_size > 0 marks a vision workload (encoder over conv patches)
    image_size: int = 0
    patch_size: int = 16
    n_channels: int = 3
    n_classes: int = 0                      # classifier/detection head width
    pool: str = "avg"                       # classifier head pool: avg|max

    # detection head (det_top_k > 0 => detector): feature upsample factor,
    # candidates kept after the score sort, and the NMS thresholds
    det_top_k: int = 0
    det_upsample: int = 2
    det_iou_threshold: float = 0.5
    det_score_threshold: float = 0.05

    # embeddings / head
    tie_embeddings: bool = True
    scale_embeddings: bool = False          # gemma: x *= sqrt(d_model)
    final_logit_softcap: Optional[float] = None
    input_mode: str = "tokens"              # tokens | embeddings (stub frontend)

    # scalar multipliers (granite). None is the neutral value, and a neutral
    # multiplier emits no op at all
    embedding_multiplier: Optional[float] = None  # x *= m after the lookup
    attention_multiplier: Optional[float] = None  # q.k scale (1/sqrt(hd))
    residual_multiplier: Optional[float] = None   # branch *= m before its add
    logits_scaling: Optional[float] = None        # logits /= s
    norm_eps: Optional[float] = None              # repro.nn's default per norm

    # numerics / execution
    dtype: str = "bfloat16"                 # activation/compute dtype
    param_dtype: str = "float32"
    remat: bool = True
    remat_policy: str = "full"              # full | dots | none
    scan_layers: bool = True
    loss_chunk: int = 0                     # 0 = unchunked; else seq chunk
    attn_chunk_q: int = 512
    attn_chunk_kv: int = 1024
    attn_triangular_schedule: bool = False  # skip fully-masked KV chunks
    fused_loss: bool = False                # chunk over vocab too (hillclimb)

    # sharding hints
    fsdp: bool = False                      # shard params over data axis too
    seq_shard: bool = False                 # Megatron-SP residual stream
    family: str = "dense"                   # dense|moe|hybrid|ssm|audio|vlm

    # --- derived -----------------------------------------------------
    @property
    def resolved_head_dim(self) -> int:
        return self.head_dim or self.d_model // self.n_heads

    @property
    def pattern_layers(self) -> Tuple[Tuple[str, ...], Tuple[str, ...]]:
        """(scanned pattern, remainder kinds). pattern repeats n_rep times."""
        p = self.block_pattern
        n_rep = self.n_layers // len(p)
        full = (p * (n_rep + 1))[: self.n_layers]
        return full[: n_rep * len(p)], full[n_rep * len(p):]

    @property
    def n_rep(self) -> int:
        return self.n_layers // len(self.block_pattern)

    def layer_kinds(self) -> Tuple[str, ...]:
        return (self.block_pattern * ((self.n_layers // len(self.block_pattern)) + 1)
                )[: self.n_layers]

    @property
    def is_moe(self) -> bool:
        return self.n_experts > 0

    @property
    def is_vision(self) -> bool:
        return self.image_size > 0

    @property
    def is_detector(self) -> bool:
        return self.is_vision and self.det_top_k > 0

    @property
    def patch_grid(self) -> int:
        """Patches per side (the encoder sees ``patch_grid ** 2`` tokens)."""
        return self.image_size // self.patch_size

    @property
    def activation_dtype(self):
        return jnp.dtype(self.dtype)

    def replace(self, **kw) -> "ModelConfig":
        return dataclasses.replace(self, **kw)

    # --- analytic parameter counts (for MODEL_FLOPS) ------------------
    # These mirror the init functions in models/*.py exactly; a unit test
    # asserts analytic == actual on reduced configs.
    def _ffn_params(self, d_ff: int) -> int:
        d = self.d_model
        mult = 3 if self.ffn in ("swiglu", "geglu") else 2
        c = mult * d * d_ff
        if self.ffn_bias:
            c += d_ff + d
        return c

    def _mixer_params(self, kind: str) -> int:
        d, hd = self.d_model, self.resolved_head_dim
        hq, hkv = self.n_heads, self.n_kv_heads
        if kind in ("attn", "local"):
            if self.mla:
                r = self.kv_lora_rank
                return (d * r + d * self.qk_rope_dim + r
                        + d * hq * (self.qk_nope_dim + self.qk_rope_dim)
                        + r * hq * self.qk_nope_dim
                        + r * hq * self.v_head_dim
                        + hq * self.v_head_dim * d)
            c = d * hq * hd + 2 * d * hkv * hd + hq * hd * d
            if self.qkv_bias:
                c += hq * hd + 2 * hkv * hd
            if self.qk_norm:
                c += 2 * hd
            return c
        if kind == "rec":
            w = self.lru_width or d
            return (2 * d * w + w * d + self.conv_width * w + w
                    + 2 * (w * w + w) + w)
        if kind == "mlstm":
            di = int(d * self.mlstm_proj_factor)
            return (2 * d * di + self.conv_width * di + di + 3 * di * di
                    + 2 * (di * self.n_heads + self.n_heads) + di + di * d)
        if kind == "slstm":
            nh = self.n_heads
            dh = d // nh
            d_ff_s = int(d * self.slstm_ff_factor)
            return (d * 4 * d + 4 * d + nh * dh * 4 * dh + d
                    + d * 2 * d_ff_s + d_ff_s * d)
        raise ValueError(kind)

    def _block_params(self, kind: str, layer_idx: int) -> int:
        d = self.d_model
        norm_p = 2 * d if self.norm == "layernorm" else d
        c = self._mixer_params(kind) + norm_p
        if kind in ("mlstm", "slstm"):
            return c  # single pre-norm, mixer-internal FFN (sLSTM)
        c += norm_p  # norm2
        if self.post_norm:
            c += 2 * norm_p
        if self.is_moe and layer_idx >= self.first_dense_layers:
            c += d * self.n_experts
            c += self.n_experts * self._ffn_params(self.moe_d_ff)
            if self.n_shared_experts:
                c += self._ffn_params(self.moe_d_ff * self.n_shared_experts)
        else:
            c += self._ffn_params(self.d_ff)
        return c

    def param_counts(self) -> dict:
        d = self.d_model
        counts = {}
        if self.input_mode == "tokens":
            counts["embed"] = self.vocab_size * d
        if not self.tie_embeddings or self.input_mode != "tokens":
            counts["head"] = self.vocab_size * d
        if self.pos_emb == "learned":
            counts["pos"] = self.max_position * d
        counts["final_norm"] = 2 * d if self.norm == "layernorm" else d
        counts["blocks"] = sum(
            self._block_params(kind, i)
            for i, kind in enumerate(self.layer_kinds()))
        counts["total"] = sum(v for k, v in counts.items() if k != "total")
        return counts

    def n_params(self) -> int:
        return int(self.param_counts()["total"])

    def n_params_active(self) -> int:
        """Per-token active params (MoE: only top_k routed experts count)."""
        if not self.is_moe:
            return self.n_params()
        n_moe_layers = sum(1 for i in range(self.n_layers)
                           if i >= self.first_dense_layers)
        inactive = ((self.n_experts - self.top_k)
                    * self._ffn_params(self.moe_d_ff))
        return int(self.n_params() - n_moe_layers * inactive)


# ---------------------------------------------------------------------------
# Shape presets (the assigned input-shape set)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class ShapeSpec:
    name: str
    seq_len: int
    global_batch: int
    kind: str  # "train" | "prefill" | "decode"


SHAPES = {
    "train_4k": ShapeSpec("train_4k", 4096, 256, "train"),
    "prefill_32k": ShapeSpec("prefill_32k", 32768, 32, "prefill"),
    "decode_32k": ShapeSpec("decode_32k", 32768, 128, "decode"),
    "long_500k": ShapeSpec("long_500k", 524288, 1, "decode"),
}

#: archs allowed to run long_500k (sub-quadratic / hybrid-local only)
LONG_CONTEXT_ARCHS = {"recurrentgemma-2b", "xlstm-350m", "gemma3-27b"}


def shape_applicable(config: "ModelConfig", shape: ShapeSpec) -> bool:
    if shape.name == "long_500k":
        return config.name in LONG_CONTEXT_ARCHS
    if shape.kind == "decode" and not config.causal:
        return False  # encoder-only has no decode step
    return True


# ---------------------------------------------------------------------------
# init helpers
# ---------------------------------------------------------------------------

def dense_init(key, shape, in_axis: int = 0, dtype=jnp.float32):
    fan_in = shape[in_axis] if shape else 1
    std = 1.0 / math.sqrt(max(fan_in, 1))
    return (jax.random.truncated_normal(key, -2.0, 2.0, shape, jnp.float32)
            * std).astype(dtype)


def stack_trees(trees: Sequence[Any]):
    """Stack a list of identical pytrees along a new leading axis."""
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs, axis=0), *trees)


def tree_slice(tree, i):
    return jax.tree_util.tree_map(lambda x: x[i], tree)


def count_params(tree) -> int:
    return int(sum(np.prod(x.shape) for x in jax.tree_util.tree_leaves(tree)))
