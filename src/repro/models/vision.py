"""Vision workload family — the paper's Torchvision half (NonGEMM Bench §4).

The paper profiles Torchvision classifiers and detectors alongside the HF
transformers, and its most dramatic NonGEMM bottlenecks are vision-side:
RoI selection (NMS), interpolation and pooling dominate detection latency
once the GEMMs are accelerated. This module provides both shapes as pure
functions over a params pytree, built on the same encoder blocks as the LM
zoo (``models/transformer.py``) so the profiling views see one block
implementation everywhere:

* **ViT classifier** (``vit_classify``) — conv patch embedding (GEMM),
  interpolatable learned 2D position embeddings (Interpolation whenever the
  runtime grid differs from the stored one), encoder blocks, a pooled head
  (``avg_pool2d``/``max_pool2d`` + ``global_avg_pool`` — Reduction), linear
  classifier.
* **Single-stage detector** (``detect_forward``) — ViT backbone -> feature
  upsample via ``nn.interpolate_bilinear`` (Interpolation) -> learned
  location prior added to the upsampled map (the interpolate->add fusion
  chain) -> box/class heads -> sigmoid scores + CenterNet-style peak
  pooling (``max_pool2d`` stride 1 — windowed Reduction used *as* RoI
  pre-selection) -> score sort (``top_k`` — Reduction) -> greedy ``nn.nms``
  (RoI Selection).

Every semantic site is scope-tagged, so both profiling views attribute the
RoI / Interpolation / Reduction(pooling) work exactly — the groups the
LM-only zoo never exercised.

Public API:

    init_vision(key, cfg)            -> params  (classifier or detector)
    vit_classify(params, imgs, cfg)  -> logits (B, n_classes)
    detect_forward(params, imgs, cfg)-> (boxes (B,K,4), scores (B,K),
                                         keep (B,K) bool)
    vision_forward(params, imgs, cfg)-> dispatches on ``cfg.is_detector``
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from repro import nn
from repro.core.taxonomy import OpGroup, scope_tag
from repro.models.common import ModelConfig, dense_init
from repro.models.transformer import (_apply_norm, _init_norm, block_forward,
                                      init_block)


def _check_vision(cfg: ModelConfig) -> None:
    if not cfg.is_vision:
        raise ValueError(f"{cfg.name!r} is not a vision config "
                         f"(image_size={cfg.image_size})")
    if cfg.image_size % cfg.patch_size:
        raise ValueError(f"image_size {cfg.image_size} not divisible by "
                         f"patch_size {cfg.patch_size}")
    if cfg.n_classes <= 0:
        raise ValueError("vision configs need n_classes > 0")


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def init_vision(key, cfg: ModelConfig) -> dict:
    """Params for the classifier (default) or detector (``det_top_k > 0``)."""
    _check_vision(cfg)
    d, p, g = cfg.d_model, cfg.patch_size, cfg.patch_grid
    pd = jnp.dtype(cfg.param_dtype)
    ks = jax.random.split(key, cfg.n_layers + 6)
    params: dict = {
        # OIHW conv kernel; fan-in = C * P * P (axis 1 spans C only, so
        # scale by hand like a flattened linear patch embed)
        "patch": {
            "w": dense_init(ks[-1], (d, cfg.n_channels, p, p), in_axis=1,
                            dtype=pd) / float(p),
            "b": jnp.zeros((d,), pd),
        },
        "pos2d": 0.02 * jax.random.normal(ks[-2], (g, g, d),
                                          jnp.float32).astype(pd),
        "blocks": [init_block(ks[i], cfg, kind, i)
                   for i, kind in enumerate(cfg.layer_kinds())],
        "final_norm": _init_norm(cfg),
    }
    if cfg.is_detector:
        gu = g * cfg.det_upsample
        params["neck_prior"] = 0.02 * jax.random.normal(
            ks[-3], (d, gu, gu), jnp.float32).astype(pd)
        params["box_head"] = {"w": dense_init(ks[-4], (d, 4), dtype=pd),
                              "b": jnp.zeros((4,), pd)}
        params["cls_head"] = {"w": dense_init(ks[-5], (d, cfg.n_classes),
                                              dtype=pd),
                              "b": jnp.zeros((cfg.n_classes,), pd)}
        # DETR-style query refinement: the top-K peak cells cross-attend
        # the full feature map (the attn_template ``full`` fragment) and
        # regress a box correction
        xk = jax.random.split(ks[-6], 5)
        params["xattn"] = {
            "wq": dense_init(xk[0], (d, d), dtype=pd),
            "wk": dense_init(xk[1], (d, d), dtype=pd),
            "wv": dense_init(xk[2], (d, d), dtype=pd),
            "wo": dense_init(xk[3], (d, d), dtype=pd),
            "delta": {"w": dense_init(xk[4], (d, 4), dtype=pd),
                      "b": jnp.zeros((4,), pd)},
        }
    else:
        params["head"] = {"w": dense_init(ks[-3], (d, cfg.n_classes),
                                          dtype=pd),
                          "b": jnp.zeros((cfg.n_classes,), pd)}
    return params


# ---------------------------------------------------------------------------
# backbone: patchify -> 2D positions -> encoder blocks
# ---------------------------------------------------------------------------

def resize_pos_embed(pos2d, grid_hw: Tuple[int, int]):
    """(gh0, gw0, D) learned grid -> (gh, gw, D) via bilinear resize.

    The ViT trick for off-train-resolution inputs: position embeddings are
    a 2D field, interpolated to the runtime patch grid (the paper's
    Interpolation group inside a *classifier*). No-op at the stored grid.
    """
    gh0, gw0, d = pos2d.shape
    if (gh0, gw0) == tuple(grid_hw):
        return pos2d
    as_nchw = pos2d.transpose(2, 0, 1)[None]          # (1, D, gh0, gw0)
    resized = nn.interpolate_bilinear(as_nchw, grid_hw)
    return resized[0].transpose(1, 2, 0)              # (gh, gw, D)


def vision_backbone(params, images, cfg: ModelConfig):
    """images (B, C, H, W) -> (normed tokens (B, gh*gw, D), (gh, gw))."""
    p = cfg.patch_size
    b, _, hh, ww = images.shape
    gh, gw = hh // p, ww // p
    x = nn.conv2d(images.astype(cfg.activation_dtype),
                  params["patch"]["w"], params["patch"]["b"],
                  stride=p)                            # (B, gh, gw, D)
    pos = resize_pos_embed(params["pos2d"], (gh, gw))
    with jax.named_scope(scope_tag(OpGroup.MEMORY, "pos_2d")):
        x = x + pos.astype(x.dtype)
    with jax.named_scope(scope_tag(OpGroup.MEMORY, "patches_to_tokens")):
        tokens = x.reshape(b, gh * gw, cfg.d_model)
    positions = jnp.broadcast_to(
        jnp.arange(gh * gw, dtype=jnp.int32)[None], (b, gh * gw))
    for blk, kind in zip(params["blocks"], cfg.layer_kinds()):
        tokens, _ = block_forward(blk, tokens, cfg, kind, positions,
                                  moe_layer=False)
    return _apply_norm(params["final_norm"], tokens, cfg), (gh, gw)


# ---------------------------------------------------------------------------
# classifier head
# ---------------------------------------------------------------------------

def vit_classify(params, images, cfg: ModelConfig):
    """Patchify-ViT image classification: (B, C, H, W) -> (B, n_classes)."""
    h, (gh, gw) = vision_backbone(params, images, cfg)
    b = h.shape[0]
    with jax.named_scope(scope_tag(OpGroup.MEMORY, "tokens_to_grid")):
        feat = h.reshape(b, gh, gw, cfg.d_model)
    if min(gh, gw) >= 2:
        pool = nn.max_pool2d if cfg.pool == "max" else nn.avg_pool2d
        feat = pool(feat, window=2)
    pooled = nn.global_avg_pool(feat)                 # (B, D)
    return nn.linear(pooled, params["head"]["w"].astype(pooled.dtype),
                     params["head"]["b"])


# ---------------------------------------------------------------------------
# detection head
# ---------------------------------------------------------------------------

def _anchor_grid(gh: int, gw: int, stride: float, dtype):
    """(gh*gw, 4) anchors as (cx, cy, w, h) in pixels, one per cell."""
    with jax.named_scope(scope_tag(OpGroup.MEMORY, "anchor_grid")):
        ys = (jnp.arange(gh, dtype=jnp.float32) + 0.5) * stride
        xs = (jnp.arange(gw, dtype=jnp.float32) + 0.5) * stride
        cy, cx = jnp.meshgrid(ys, xs, indexing="ij")
        wh = jnp.full_like(cx, stride)
        anchors = jnp.stack([cx, cy, wh, wh], axis=-1).reshape(-1, 4)
        return anchors.astype(dtype)


def _refine_boxes(xp, tokens, idx, top_b, stride: float, cfg: ModelConfig):
    """DETR-style second stage: top-K peak queries cross-attend the full
    feature map and regress a per-box correction (in units of the feature
    stride). Non-causal cross attention — the template family's ``full``
    fragment on the kernel backends, the flash jnp twin otherwise.
    """
    from repro.models.attention import flash_attention_jnp

    hq = cfg.n_heads
    with jax.named_scope(scope_tag(OpGroup.MEMORY, "gather_queries")):
        qf = jnp.take_along_axis(tokens, idx[..., None], axis=1)  # (B,K,D)
    q = nn.split_heads(nn.linear(qf, xp["wq"].astype(tokens.dtype)), hq)
    kk = nn.split_heads(nn.linear(tokens, xp["wk"].astype(tokens.dtype)), hq)
    vv = nn.split_heads(nn.linear(tokens, xp["wv"].astype(tokens.dtype)), hq)
    backend = nn.get_backend()
    if backend != "jnp":
        from repro.kernels import ops as kops
        att = kops.attn_full_template(
            q, kk, vv, interpret=nn.kernel_interpret())
    else:
        att = flash_attention_jnp(q, kk, vv, causal=False)
    att = nn.linear(nn.merge_heads(att), xp["wo"].astype(tokens.dtype))
    delta = nn.linear(att, xp["delta"]["w"].astype(tokens.dtype),
                      xp["delta"]["b"])                           # (B,K,4)
    with jax.named_scope(scope_tag(OpGroup.ELEMENTWISE, "box_refine")):
        return top_b + delta.astype(top_b.dtype) * stride


def detect_forward(params, images, cfg: ModelConfig):
    """Single-stage detection: (B, C, H, W) ->
    (boxes (B, K, 4) xyxy, scores (B, K), keep (B, K) bool), K=det_top_k.

    The NonGEMM spine the paper measures on Torchvision detectors:
    interpolation (feature upsample), pooling (peak selection), reduction
    (score sort) and RoI selection (greedy NMS) — all downstream of a
    GEMM-heavy backbone, all scope-tagged.
    """
    h, (gh, gw) = vision_backbone(params, images, cfg)
    b, d = h.shape[0], cfg.d_model
    with jax.named_scope(scope_tag(OpGroup.MEMORY, "tokens_to_grid")):
        feat = h.reshape(b, gh, gw, d).transpose(0, 3, 1, 2)   # NCHW
    gh_u, gw_u = gh * cfg.det_upsample, gw * cfg.det_upsample
    up = nn.interpolate_bilinear(feat, (gh_u, gw_u))
    pmap = nn.residual_add(up, params["neck_prior"].astype(up.dtype))
    with jax.named_scope(scope_tag(OpGroup.MEMORY, "grid_to_tokens")):
        t = pmap.reshape(b, d, gh_u * gw_u).transpose(0, 2, 1)  # (B, N, D)

    cls_logits = nn.linear(t, params["cls_head"]["w"].astype(t.dtype),
                           params["cls_head"]["b"])             # (B, N, K)
    box_raw = nn.linear(t, params["box_head"]["w"].astype(t.dtype),
                        params["box_head"]["b"])                # (B, N, 4)

    probs = nn.sigmoid(cls_logits)
    with jax.named_scope(scope_tag(OpGroup.REDUCTION, "score_max")):
        scores = jnp.max(probs.astype(jnp.float32), axis=-1)    # (B, N)

    # CenterNet-style peak NMS: a score survives only where it equals its
    # 3x3 local max — windowed Reduction doing RoI pre-selection
    smap = scores.reshape(b, gh_u, gw_u, 1)
    peak = nn.max_pool2d(smap, window=3, stride=1, padding="SAME")
    with jax.named_scope(scope_tag(OpGroup.ELEMENTWISE, "peak_mask")):
        scores = jnp.where(smap >= peak, smap, 0.0).reshape(b, gh_u * gw_u)

    stride = float(cfg.patch_size) / cfg.det_upsample
    anchors = _anchor_grid(gh_u, gw_u, stride, box_raw.dtype)
    boxes = nn.box_decode(box_raw, anchors)                     # (B, N, 4)

    k = min(cfg.det_top_k, gh_u * gw_u)
    with jax.named_scope(scope_tag(OpGroup.REDUCTION, "topk_scores")):
        top_s, idx = jax.lax.top_k(scores, k)
    with jax.named_scope(scope_tag(OpGroup.MEMORY, "gather_boxes")):
        top_b = jnp.take_along_axis(boxes, idx[..., None], axis=1)

    if "xattn" in params:
        top_b = _refine_boxes(params["xattn"], t, idx, top_b, stride, cfg)

    keep = jnp.stack([
        nn.nms(top_b[i].astype(jnp.float32), top_s[i],
               iou_threshold=cfg.det_iou_threshold,
               score_threshold=cfg.det_score_threshold)
        for i in range(b)])
    return top_b, top_s, keep


def vision_forward(params, images, cfg: ModelConfig):
    """One entry point for both vision shapes (the Workload builder's fn)."""
    if cfg.is_detector:
        return detect_forward(params, images, cfg)
    return vit_classify(params, images, cfg)
