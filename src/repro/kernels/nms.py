"""Pallas TPU non-maximum suppression (the paper's RoI Selection group).

The CUDA NMS the paper profiles is a data-dependent loop over a shrinking
candidate set — shapes a TPU cannot express. The TPU-idiomatic adaptation
(DESIGN.md §3): boxes are score-sorted on the host side of the kernel
(sorting is Reduction-group work XLA already does well), then a
``fori_loop`` walks the N candidates carrying an (N,)-lane suppression mask
in VMEM; each step computes one vectorized IoU row (128-lane VPU work) and
clears the suppressed lanes. O(N^2) IoU math — identical to the greedy
algorithm — but O(N) memory, static shapes, no host round-trips.

Single grid step: all operands resident in VMEM (N <= ~16k boxes:
N x 4 coords + a handful of (N,) vectors ~ 0.5 MiB at N=16384).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _nms_kernel(x1_ref, y1_ref, x2_ref, y2_ref, valid_ref, keep_ref, *,
                n: int, iou_threshold: float):
    x1 = x1_ref[...].astype(jnp.float32)     # (1, N) lane rows
    y1 = y1_ref[...].astype(jnp.float32)
    x2 = x2_ref[...].astype(jnp.float32)
    y2 = y2_ref[...].astype(jnp.float32)
    valid = (valid_ref[...] != 0).astype(jnp.int32)
    area = jnp.maximum(x2 - x1, 0) * jnp.maximum(y2 - y1, 0)
    idx = jax.lax.broadcasted_iota(jnp.int32, (1, n), 1)

    def lane(v, i):
        # lane i of a (1, N) row as a (1, 1) value: Mosaic cannot slice an
        # in-register vector at a dynamic offset, so select and reduce
        return jnp.sum(jnp.where(idx == i, v, 0), axis=1, keepdims=True)

    def body(i, keep):
        bx1, by1 = lane(x1, i), lane(y1, i)
        bx2, by2 = lane(x2, i), lane(y2, i)
        barea = jnp.maximum(bx2 - bx1, 0) * jnp.maximum(by2 - by1, 0)
        iw = jnp.maximum(jnp.minimum(x2, bx2) - jnp.maximum(x1, bx1), 0)
        ih = jnp.maximum(jnp.minimum(y2, by2) - jnp.maximum(y1, by1), 0)
        inter = iw * ih
        union = area + barea - inter
        iou = jnp.where(union > 0, inter / union, 0.0)
        alive = lane(keep * valid, i) > 0
        suppress = (iou > iou_threshold) & (idx > i) & alive
        return jnp.where(suppress, 0, keep)

    keep = jax.lax.fori_loop(0, n, body, valid)
    keep_ref[...] = keep.astype(keep_ref.dtype)


def nms_sorted(boxes_sorted, valid, iou_threshold: float = 0.5,
               interpret: bool = False):
    """Greedy NMS over score-DESC-sorted boxes (N, 4) -> keep mask (N,)."""
    n = boxes_sorted.shape[0]
    pad = -n % 128
    b = jnp.pad(boxes_sorted.astype(jnp.float32), ((0, pad), (0, 0)))
    val = jnp.pad(valid.astype(jnp.int32), (0, pad))
    np_ = n + pad
    cols = [b[:, i][None] for i in range(4)]
    keep = pl.pallas_call(
        functools.partial(_nms_kernel, n=np_, iou_threshold=iou_threshold),
        grid=(1,),
        in_specs=[pl.BlockSpec((1, np_), lambda i: (0, 0))] * 5,
        out_specs=pl.BlockSpec((1, np_), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((1, np_), jnp.int32),
        interpret=interpret,
    )(*cols, val[None])
    return keep[0, :n] != 0


def nms(boxes, scores, iou_threshold: float = 0.5,
        score_threshold: float = 0.0, interpret: bool = False):
    """torchvision-semantics NMS: (N, 4) xyxy + (N,) scores -> keep (N,)."""
    n = boxes.shape[0]
    order = jnp.argsort(-scores)
    keep_sorted = nms_sorted(boxes[order], scores[order] > score_threshold,
                             iou_threshold=iou_threshold, interpret=interpret)
    return jnp.zeros((n,), bool).at[order].set(keep_sorted)
