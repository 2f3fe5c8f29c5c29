"""Pallas TPU kernel for fused rotary-embedding application.

Unfused, ``apply_rope`` is a train of Memory-group micro-ops — slice the
rotating half, build the frequency table, sin/cos, four multiplies, two
concatenates — each its own kernel launch in eager mode, each a full pass
over the (B, S, H, D) activation. Fused, the angle table is recomputed in
registers from the per-row position scalar (sin/cos are VPU-cheap; the
paper's point is that these ops are *bandwidth*-bound) and the tensor is
read and written exactly once.

Tiling: rows are the flattened (B, S) product; each grid step owns a
``(block_rows, H, rot)`` tile plus the matching ``(block_rows, 1)`` slice
of positions. The non-rotated tail (partial-rotary models such as
StableLM's 25% fraction) is sliced off outside the kernel and concatenated
back — it is pass-through data the kernel never needs to touch.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _rope_kernel(x_ref, p_ref, o_ref, *, base: float):
    x = x_ref[...].astype(jnp.float32)          # (rows, H, rot)
    half = x.shape[-1] // 2
    # Mosaic builds iotas in integer registers only
    idx = jax.lax.broadcasted_iota(jnp.int32, (1, 1, half), 2).astype(
        jnp.float32)
    freq = base ** (-idx / half)
    theta = p_ref[...][:, :, None] * freq       # (rows, 1, half)
    cos = jnp.cos(theta)
    sin = jnp.sin(theta)
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                          axis=-1)
    o_ref[...] = out.astype(o_ref.dtype)


def rope(x, positions, base: float = 10000.0, fraction: float = 1.0,
         block_rows: int = 8, interpret: bool = False):
    """Rotary embedding on ``x: (B, S, H, D)`` with ``positions: (B, S)``.

    Matches ``repro.nn.apply_rope`` semantics exactly (rotate-halves
    layout, optional leading ``fraction`` of head dims).
    """
    b, s, h, d = x.shape
    rot = int(d * fraction) // 2 * 2
    x_rot, x_pass = x[..., :rot], x[..., rot:]

    rows = b * s
    x2 = x_rot.reshape(rows, h, rot)
    p2 = jnp.broadcast_to(jnp.asarray(positions, jnp.int32),
                          (b, s)).reshape(rows, 1).astype(jnp.float32)
    pr = -rows % block_rows
    if pr:
        x2 = jnp.pad(x2, ((0, pr), (0, 0), (0, 0)))
        p2 = jnp.pad(p2, ((0, pr), (0, 0)))
    out = pl.pallas_call(
        functools.partial(_rope_kernel, base=base),
        grid=(x2.shape[0] // block_rows,),
        in_specs=[
            pl.BlockSpec((block_rows, h, rot), lambda i: (i, 0, 0)),
            pl.BlockSpec((block_rows, 1), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((block_rows, h, rot), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct(x2.shape, x.dtype),
        interpret=interpret,
    )(x2, p2)
    out = out[:rows].reshape(b, s, h, rot)
    if rot < d:
        return jnp.concatenate([out, x_pass], axis=-1)
    return out
