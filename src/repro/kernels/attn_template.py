"""Template-driven Pallas attention kernel family (one block-level spec).

Every attention variant in the zoo — causal prefill, sliding-window
(local-ring), full/cross (vision encoder, detector queries), and the
one-query decode step over a gathered KV cache — shares ONE online-softmax
schedule. This module owns that schedule as a block-level template and
generates each variant from an :class:`AttnSpec`:

* the **body** is the flash schedule from ``kernels/flash_attention.py``:
  grid ``(B*Hq, nq, nk)`` with KV innermost, (m, l, acc) carried in VMEM
  scratch across the ``nk`` steps of one (head, q-block), output written
  once on the last KV step;
* the **mask**, **softcap**, **RoPE** and **epilogue** are composed in as
  spec-driven fragments — ``mask`` kinds ``causal`` / ``window`` /
  ``full`` / ``decode`` (per-row valid-length via scalar prefetch);
* ``v_head_dim`` may differ from ``head_dim`` (MLA: latent values), and
  GQA is an index-map fragment (KV block row ``(h % hq) // g`` — no HBM
  replication);
* ``paged=True`` on the decode mask reads K/V in place from the paged
  block pool: its index maps read the block tables by scalar prefetch,
  several pool blocks per grid step, and a KV head's query heads share
  one tile (:func:`paged_decode_core`).

The epilogue guards fully-masked query rows: a row whose every key is
masked carries ``m == NEG_INF`` out of the loop (NEG_INF is finite, so the
unguarded ``acc / l`` silently emits ``mean(v)`` garbage, not NaN — e.g. a
window past the cached depth). Guarded rows emit exact zeros, matching the
``kernels/ref.py`` oracle.

Instantiating a spec (:func:`make_attention`) auto-registers the generated
kernel in ``repro.kernels.ops.KERNEL_SPECS`` under ``attn_template:<name>``
so nglint NG005 statically vets every variant — and flags any instantiated
spec that skipped registration. ``flash_attention`` itself is a thin
pre-built spec over :func:`attention_core`.

VMEM budget per step at (bq, bk, dk, dv) = (128, 128, 128, 128): q/k/v
tiles 3 x 64 KiB (bf16) + acc 64 KiB f32 + s/p 64 KiB f32 — well under
the ~16 MiB VMEM with double buffering (see docs/kernels.md).
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG_INF = -1e30

#: the four mask fragments a spec may pick
MASK_KINDS = ("causal", "window", "full", "decode")


@dataclasses.dataclass(frozen=True)
class AttnSpec:
    """Static description of one attention variant.

    ``None`` on a shape field (``head_dim`` / ``v_head_dim`` /
    ``gqa_group``) means "any" — the generated kernel specializes on the
    call shapes; a pinned value is validated at call time. ``window``,
    ``scale`` and ``softcap`` are defaults the call may override (they
    stay static under jit).
    """

    name: str
    mask: str = "causal"                 # one of MASK_KINDS
    window: Optional[int] = None         # mask == "window": lookback span
    head_dim: Optional[int] = None       # pin dk
    v_head_dim: Optional[int] = None     # pin dv (may differ from dk: MLA)
    gqa_group: Optional[int] = None      # pin hq // hkv
    rope: bool = False                   # rotary fragment on q/k pre-GEMM
    rope_base: float = 10000.0
    softcap: Optional[float] = None      # tanh logit cap (pre-mask)
    scale: Optional[float] = None        # None -> 1/sqrt(dk)
    block_q: int = 128
    block_k: int = 128
    paged: bool = False                  # decode: K/V from the block pool

    def __post_init__(self):
        if self.mask not in MASK_KINDS:
            raise ValueError(f"spec {self.name!r}: unknown mask kind "
                             f"{self.mask!r}; known: {MASK_KINDS}")
        if self.paged and self.mask != "decode":
            raise ValueError(f"spec {self.name!r}: only the decode mask "
                             "reads a paged pool")
        if self.mask == "window" and self.window is not None \
                and self.window <= 0:
            raise ValueError(f"spec {self.name!r}: window must be positive")


def kernel_key(spec: AttnSpec) -> str:
    """The ``KERNEL_SPECS`` / micro-bench key of a spec's kernel."""
    return f"attn_template:{spec.name}"


#: every spec instantiated in this process, by name — nglint NG005
#: cross-checks this against ``repro.kernels.ops.KERNEL_SPECS``
_SPECS: Dict[str, AttnSpec] = {}
#: registered public (autojit) callables, by spec name
_PUBLIC: Dict[str, Callable] = {}


def instantiated_specs() -> Tuple[AttnSpec, ...]:
    return tuple(_SPECS.values())


def forget(name: str) -> None:
    """Drop a spec from the instantiation registry (test hygiene)."""
    _SPECS.pop(name, None)
    _PUBLIC.pop(name, None)


# ---------------------------------------------------------------------------
# the one shared body
# ---------------------------------------------------------------------------

def _online_softmax_step(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref,
                         mask, *, scale: float, softcap: Optional[float],
                         nk: int):
    """One (head, q-block, kv-block) step of the flash schedule.

    ``mask`` is the composed (bq, bk) fragment for this step; everything
    else — init, softcapped scores, online rescale, guarded epilogue — is
    identical across variants.
    """
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.float32)            # (bq, dk)
    k = k_ref[0].astype(jnp.float32)            # (bk, dk)
    v = v_ref[0].astype(jnp.float32)            # (bk, dv)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    if softcap is not None:
        s = jnp.tanh(s / softcap) * softcap
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[...]                          # (bq, 1)
    l_prev = l_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_prev * corr + jnp.sum(p, axis=-1, keepdims=True)
    m_ref[...] = m_new
    acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(j == nk - 1)
    def _finish():
        # fully-masked rows never observed a real score: m stays at the
        # (finite) NEG_INF init and l is a count of exp(0) terms — emit
        # exact zeros instead of mean(v) garbage
        l = jnp.maximum(l_ref[...], 1e-30)
        seen = m_ref[...] > NEG_INF * 0.5
        o_ref[0] = jnp.where(seen, acc_ref[...] / l,
                             jnp.zeros_like(acc_ref[...])).astype(o_ref.dtype)


def _positions(bq: int, bk: int, q_offset: int):
    i = pl.program_id(1)
    j = pl.program_id(2)
    qpos = q_offset + i * bq \
        + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    kpos = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    return qpos, kpos


def _template_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                     scale: float, causal: bool, window: Optional[int],
                     softcap: Optional[float], bq: int, bk: int, nk: int,
                     skv: int, q_offset: int):
    """causal / window / full fragments over the shared body."""
    qpos, kpos = _positions(bq, bk, q_offset)
    mask = kpos < skv                            # KV padding
    if causal:
        mask &= qpos >= kpos
    if window is not None:
        mask &= (qpos - kpos) < window
    _online_softmax_step(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref,
                         mask, scale=scale, softcap=softcap, nk=nk)


def _decode_kernel(lengths_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref,
                   acc_ref, *, scale: float, softcap: Optional[float],
                   hq: int, bq: int, bk: int, nk: int, skv: int):
    """decode-1q fragment: per-row valid prefix via scalar prefetch.

    ``lengths[b]`` is the number of attendable leading KV positions for
    batch row ``b`` (``pos + 1`` on a positional cache, ``min(pos + 1, w)``
    on a ring buffer) — the kernel-side twin of the jnp decode paths'
    ``arange(t) <= pos`` masking.
    """
    h = pl.program_id(0)
    _, kpos = _positions(bq, bk, 0)
    length = lengths_ref[h // hq]
    mask = (kpos < skv) & (kpos < length)
    _online_softmax_step(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref,
                         mask, scale=scale, softcap=softcap, nk=nk)


# ---------------------------------------------------------------------------
# wrapper: head-flattening, GQA index maps, padding, grid
# ---------------------------------------------------------------------------

def _vmem(shape, dtype=jnp.float32):
    from jax.experimental.pallas import tpu as pltpu
    return pltpu.VMEM(shape, dtype)


def _rope_jnp(x, positions, base: float):
    """Full-fraction rotary fragment (pre-GEMM, jnp; mirrors ref.rope)."""
    d = x.shape[-1]
    half = d // 2 * 2 // 2
    freq = base ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    theta = positions[None, :, None].astype(jnp.float32) * freq
    cos = jnp.cos(theta)[:, :, None, :]
    sin = jnp.sin(theta)[:, :, None, :]
    x1 = x[..., :half].astype(jnp.float32)
    x2 = x[..., half:2 * half].astype(jnp.float32)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                          axis=-1)
    if 2 * half < d:
        out = jnp.concatenate([out.astype(x.dtype), x[..., 2 * half:]],
                              axis=-1)
    return out.astype(x.dtype)


def _flatten(q, k, v, block_q: int, block_k: int):
    """(B, S, H, D) triple -> head-flat padded operands + grid geometry."""
    b, sq, hq, dk = q.shape
    _, skv, hkv, _ = k.shape
    dv = v.shape[-1]
    g = hq // hkv
    bq = min(block_q, max(sq, 8))
    bk = min(block_k, max(skv, 8))
    pq = -sq % bq
    pk = -skv % bk
    qf = q.transpose(0, 2, 1, 3).reshape(b * hq, sq, dk)
    kf = k.transpose(0, 2, 1, 3).reshape(b * hkv, skv, dk)
    vf = v.transpose(0, 2, 1, 3).reshape(b * hkv, skv, dv)
    if pq:
        qf = jnp.pad(qf, ((0, 0), (0, pq), (0, 0)))
    if pk:
        kf = jnp.pad(kf, ((0, 0), (0, pk), (0, 0)))
        vf = jnp.pad(vf, ((0, 0), (0, pk), (0, 0)))
    nq = qf.shape[1] // bq
    nk = kf.shape[1] // bk
    return qf, kf, vf, (b, sq, hq, hkv, g, dk, dv, skv, bq, bk, nq, nk)


def attention_core(q, k, v, *, causal: bool = True,
                   window: Optional[int] = None, q_offset: int = 0,
                   scale: Optional[float] = None,
                   softcap: Optional[float] = None,
                   rope: bool = False, rope_base: float = 10000.0,
                   block_q: int = 128, block_k: int = 128,
                   interpret: bool = False):
    """The causal / window / full template entry point.

    q: (B, Sq, Hq, Dk); k: (B, Skv, Hkv, Dk); v: (B, Skv, Hkv, Dv)
    -> (B, Sq, Hq, Dv). ``Dv`` may differ from ``Dk`` (MLA prefill).
    """
    if rope:
        q = _rope_jnp(q, q_offset + jnp.arange(q.shape[1]), rope_base)
        k = _rope_jnp(k, jnp.arange(k.shape[1]), rope_base)
    scale = scale if scale is not None else 1.0 / math.sqrt(q.shape[-1])
    qf, kf, vf, geom = _flatten(q, k, v, block_q, block_k)
    b, sq, hq, hkv, g, dk, dv, skv, bq, bk, nq, nk = geom

    def kv_row(h, i, j):
        return ((h // hq) * hkv + (h % hq) // g, j, 0)

    out = pl.pallas_call(
        functools.partial(_template_kernel, scale=scale, causal=causal,
                          window=window, softcap=softcap, bq=bq, bk=bk,
                          nk=nk, skv=skv, q_offset=q_offset),
        grid=(b * hq, nq, nk),
        in_specs=[
            pl.BlockSpec((1, bq, dk), lambda h, i, j: (h, i, 0)),
            pl.BlockSpec((1, bk, dk), kv_row),
            pl.BlockSpec((1, bk, dv), kv_row),
        ],
        out_specs=pl.BlockSpec((1, bq, dv), lambda h, i, j: (h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((b * hq, qf.shape[1], dv), v.dtype),
        scratch_shapes=[
            _vmem((bq, 1)),
            _vmem((bq, 1)),
            _vmem((bq, dv)),
        ],
        interpret=interpret,
    )(qf, kf, vf)
    out = out[:, :sq].reshape(b, hq, sq, dv).transpose(0, 2, 1, 3)
    return out


def decode_core(q, k, v, lengths, *, scale: Optional[float] = None,
                softcap: Optional[float] = None,
                block_q: int = 8, block_k: int = 128,
                interpret: bool = False):
    """The decode-1q template entry point (gathered / paged KV).

    q: (B, 1, Hq, Dk); k: (B, T, Hkv, Dk); v: (B, T, Hkv, Dv);
    lengths: (B,) int32 valid KV prefix per row -> (B, 1, Hq, Dv).
    """
    from jax.experimental.pallas import tpu as pltpu

    scale = scale if scale is not None else 1.0 / math.sqrt(q.shape[-1])
    lengths = jnp.asarray(lengths, jnp.int32).reshape(q.shape[0])
    qf, kf, vf, geom = _flatten(q, k, v, block_q, block_k)
    b, sq, hq, hkv, g, dk, dv, skv, bq, bk, nq, nk = geom

    def q_row(h, i, j, lens):
        return (h, i, 0)

    def kv_row(h, i, j, lens):
        return ((h // hq) * hkv + (h % hq) // g, j, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b * hq, nq, nk),
        in_specs=[
            pl.BlockSpec((1, bq, dk), q_row),
            pl.BlockSpec((1, bk, dk), kv_row),
            pl.BlockSpec((1, bk, dv), kv_row),
        ],
        out_specs=pl.BlockSpec((1, bq, dv), q_row),
        scratch_shapes=[
            _vmem((bq, 1)),
            _vmem((bq, 1)),
            _vmem((bq, dv)),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_decode_kernel, scale=scale, softcap=softcap,
                          hq=hq, bq=bq, bk=bk, nk=nk, skv=skv),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b * hq, qf.shape[1], dv), v.dtype),
        interpret=interpret,
    )(lengths, qf, kf, vf)
    return out[:, :sq].reshape(b, hq, sq, dv).transpose(0, 2, 1, 3)


# ---------------------------------------------------------------------------
# paged decode-1q: K/V read in place from the block pool
# ---------------------------------------------------------------------------

#: widest score row one grid step computes: pages_per_step * bs * Hkv
#: lanes (the (Hq, W) f32 scores stay at 128 vregs for Hq = 32)
PAGED_ROW_LANES = 4096


def _paged_block(b, w, p, lengths_ref, tables_ref, *, nb: int, bs: int,
                 pages: int):
    """Pool block that page input ``p`` holds at grid step ``(b, w)``.

    Input ``p`` reads table entry ``w * pages + p`` while that block is
    live (below ``ceil(length / bs)``). Past it, the input keeps the entry
    of the last window in which it was live, so the pipeline sees the same
    block index again and issues no copy; an input with no live block in
    this sequence points at the scratch block 0. Either way the rows it
    holds are finite and lie past the length, where the mask zeroes them.
    """
    live = (lengths_ref[b] + bs - 1) // bs
    last_w = jnp.maximum((live - 1 - p) // pages, 0)
    j = jnp.minimum(jnp.minimum(w, last_w) * pages + p, nb - 1)
    return jnp.where(p < live, tables_ref[b * nb + j], 0)


def _paged_decode_kernel(lengths_ref, tables_ref, layer_ref, q_ref,
                         col_head_ref, col_tok_ref, row_head_ref, *refs,
                         scale: float, softcap: Optional[float], pages: int,
                         span: int):
    """decode-1q over one window of ``pages`` pool blocks of sequence b.

    Grid ``(B, windows)``; each page input is one pool block
    ``(bs * Hkv, D)``, whose row ``t * Hkv + h`` is token ``t``'s KV head
    ``h`` (the pool's own order, so the block arrives by one copy). The
    scores of ALL query heads against every row of the window are one MXU
    product per block, ``q @ K^T``, laid side by side as ``(Hq, W)``; the
    mask keeps, per query head, the rows of its own KV head below the
    sequence's length. A GQA group therefore reads each K/V row once.
    Windows past the length compute nothing.
    """
    k_refs, v_refs = refs[:pages], refs[pages:2 * pages]
    o_ref, m_ref, l_ref, acc_ref = refs[2 * pages:]
    b, w = pl.program_id(0), pl.program_id(1)
    length = lengths_ref[b]

    @pl.when(w == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(w * span < length)
    def _window():
        q = q_ref[0]                                    # (Hq, Dk)
        s = jnp.concatenate([
            jax.lax.dot_general(q, k[0, 0], (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
            for k in k_refs], axis=1) * scale           # (Hq, W)
        if softcap is not None:
            s = jnp.tanh(s / softcap) * softcap
        mask = (col_head_ref[...] == row_head_ref[...]) \
            & (w * span + col_tok_ref[...] < length)
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=-1, keepdims=True)
        m_ref[...] = m_new
        rows = s.shape[1] // pages
        pv = acc_ref[...] * corr
        for i, v in enumerate(v_refs):
            pv += jax.lax.dot_general(
                p[:, i * rows:(i + 1) * rows].astype(v.dtype), v[0, 0],
                (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        acc_ref[...] = pv

    @pl.when(w == pl.num_programs(1) - 1)
    def _finish():
        # a dead sequence (length 0) saw no key: exact zeros, as in the
        # oracle
        l = l_ref[...]
        o_ref[0] = jnp.where(l > 0, acc_ref[...] / jnp.maximum(l, 1e-30),
                             0.0).astype(o_ref.dtype)


def paged_decode_core(q, k_pool, v_pool, tables, lengths, layer=None, *,
                      scale: Optional[float] = None,
                      softcap: Optional[float] = None,
                      pages_per_step: Optional[int] = None,
                      interpret: bool = False):
    """The paged decode-1q entry point: K/V read in place from the pool.

    q: (B, 1, Hq, D); k_pool / v_pool: one layer's pool leaves
    (N, bs, Hkv, D), or the layer-stacked (L, N, bs, Hkv, D) with
    ``layer`` (int32 scalar) naming the layer; tables: (B, nb) int32 block
    ids; lengths: (B,) int32 valid prefix per row -> (B, 1, Hq, D).

    ``lengths``, ``tables`` and ``layer`` arrive by scalar prefetch, and
    the K/V index maps read the tables, so the pipeline copies pool blocks
    in place. A grid step takes ``pages_per_step`` blocks, one input each,
    so that each step moves enough bytes to pay for itself (default: as
    many as keep the score row at :data:`PAGED_ROW_LANES` lanes, at most a
    whole table row). Blocks at or past ``ceil(lengths[b] / bs)`` are
    neither copied nor computed.
    """
    from jax.experimental.pallas import tpu as pltpu

    if k_pool.ndim == 4:
        k_pool, v_pool, layer = k_pool[None], v_pool[None], 0
    nl, n, bs, hkv, dk = k_pool.shape
    dv = v_pool.shape[-1]
    b, _, hq, _ = q.shape
    nb = tables.shape[1]
    g = hq // hkv
    rows = bs * hkv
    pages = pages_per_step or max(1, min(nb, PAGED_ROW_LANES // rows))
    windows = -(-nb // pages)
    scale = scale if scale is not None else 1.0 / math.sqrt(dk)
    lengths = jnp.asarray(lengths, jnp.int32).reshape(b)
    col = jnp.arange(pages * rows, dtype=jnp.int32)[None, :]
    row_head = (jnp.arange(hq, dtype=jnp.int32) // g)[:, None]

    def const(shape):
        return pl.BlockSpec(shape, lambda i, w, *_: (0,) * len(shape))

    def page_spec(p, d):
        def index(i, w, lens, tabs, lay):
            return (lay[0], _paged_block(i, w, p, lens, tabs, nb=nb, bs=bs,
                                         pages=pages), 0, 0)
        return pl.BlockSpec((1, 1, rows, d), index)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b, windows),
        in_specs=[pl.BlockSpec((1, hq, dk), lambda i, w, *_: (i, 0, 0)),
                  const((1, pages * rows)), const((1, pages * rows)),
                  const((hq, 1))]
        + [page_spec(p, dk) for p in range(pages)]
        + [page_spec(p, dv) for p in range(pages)],
        out_specs=pl.BlockSpec((1, hq, dv), lambda i, w, *_: (i, 0, 0)),
        scratch_shapes=[_vmem((hq, 1)), _vmem((hq, 1)), _vmem((hq, dv))],
    )
    kf = k_pool.reshape(nl, n, rows, dk)
    vf = v_pool.reshape(nl, n, rows, dv)
    out = pl.pallas_call(
        functools.partial(_paged_decode_kernel, scale=scale,
                          softcap=softcap, pages=pages, span=pages * bs),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hq, dv), v_pool.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
    )(lengths, tables.reshape(-1).astype(jnp.int32),
      jnp.asarray(layer, jnp.int32).reshape(1),
      q.reshape(b, hq, dk).astype(k_pool.dtype), col % hkv, col // hkv,
      row_head, *([kf] * pages), *([vf] * pages))
    return out.reshape(b, 1, hq, dv)


# ---------------------------------------------------------------------------
# the generator: spec -> raw kernel entry point (+ auto-registration)
# ---------------------------------------------------------------------------

def build_raw(spec: AttnSpec) -> Tuple[Callable, Tuple[str, ...]]:
    """Emit the raw (unjitted) entry point for a spec.

    Returns ``(fn, static_argnames)`` — the signature matches what
    ``repro.kernels.ops._autojit`` expects (keyword-only ``interpret``).
    """
    def _check(q, k, v):
        if spec.head_dim is not None and q.shape[-1] != spec.head_dim:
            raise ValueError(f"{spec.name}: head_dim {q.shape[-1]} != "
                             f"pinned {spec.head_dim}")
        if spec.v_head_dim is not None and v.shape[-1] != spec.v_head_dim:
            raise ValueError(f"{spec.name}: v_head_dim {v.shape[-1]} != "
                             f"pinned {spec.v_head_dim}")
        if spec.gqa_group is not None \
                and q.shape[2] != k.shape[-2] * spec.gqa_group:
            raise ValueError(f"{spec.name}: GQA group "
                             f"{q.shape[2]}/{k.shape[-2]} != pinned "
                             f"{spec.gqa_group}")

    if spec.paged:
        def fn(q, k_pool, v_pool, tables, lengths, layer=None, *,
               scale: Optional[float] = None,
               softcap: Optional[float] = None,
               pages_per_step: Optional[int] = None,
               interpret: bool = False):
            _check(q, k_pool, v_pool)
            return paged_decode_core(
                q, k_pool, v_pool, tables, lengths, layer,
                scale=spec.scale if scale is None else scale,
                softcap=spec.softcap if softcap is None else softcap,
                pages_per_step=pages_per_step, interpret=interpret)
        static = ("scale", "softcap", "pages_per_step", "interpret")
    elif spec.mask == "decode":
        def fn(q, k, v, lengths, *, scale: Optional[float] = None,
               softcap: Optional[float] = None,
               block_q: int = spec.block_q, block_k: int = spec.block_k,
               interpret: bool = False):
            _check(q, k, v)
            return decode_core(
                q, k, v, lengths,
                scale=spec.scale if scale is None else scale,
                softcap=spec.softcap if softcap is None else softcap,
                block_q=block_q, block_k=block_k, interpret=interpret)
        static = ("scale", "softcap", "block_q", "block_k", "interpret")
    else:
        causal = spec.mask in ("causal", "window")

        def fn(q, k, v, *, window: Optional[int] = spec.window,
               q_offset: int = 0, scale: Optional[float] = None,
               softcap: Optional[float] = None,
               block_q: int = spec.block_q, block_k: int = spec.block_k,
               interpret: bool = False):
            _check(q, k, v)
            if spec.mask == "window" and window is None:
                raise ValueError(f"{spec.name}: window size required")
            if spec.mask != "window":
                window = None
            return attention_core(
                q, k, v, causal=causal, window=window, q_offset=q_offset,
                scale=spec.scale if scale is None else scale,
                softcap=spec.softcap if softcap is None else softcap,
                rope=spec.rope, rope_base=spec.rope_base,
                block_q=block_q, block_k=block_k, interpret=interpret)
        static = ("window", "q_offset", "scale", "softcap", "block_q",
                  "block_k", "interpret")
    fn.__name__ = kernel_key(spec).replace(":", "_")
    fn.__doc__ = (f"attn_template variant {spec.name!r} "
                  f"(mask={spec.mask}, generated by build_raw)")
    return fn, static


def make_attention(spec: AttnSpec, register: bool = True) -> Callable:
    """Instantiate a spec: generate the kernel and (by default) register
    it in ``repro.kernels.ops.KERNEL_SPECS`` under ``attn_template:<name>``.

    Registration at instantiation time is what keeps nglint NG005 honest:
    every generated variant is statically vetted (``interpret`` fallback,
    positive blocks, partial-block handling), and an instantiated spec
    that skipped registration is itself an NG005 finding.
    """
    raw, static = build_raw(spec)
    _SPECS[spec.name] = spec
    if not register:
        return raw
    from repro.kernels import ops as kops

    public = kops.register_template_kernel(spec, raw, static)
    _PUBLIC[spec.name] = public
    return public


#: the variants the model zoo needs, instantiated (and registered) when
#: ``repro.kernels.ops`` finishes importing
BUILTIN_SPECS: Tuple[AttnSpec, ...] = (
    AttnSpec(name="causal", mask="causal"),
    AttnSpec(name="window", mask="window"),
    AttnSpec(name="full", mask="full"),
    AttnSpec(name="decode", mask="decode", block_q=8),
    AttnSpec(name="paged_decode", mask="decode", paged=True),
)


def get(name: str) -> Callable:
    """The registered public callable for a built-in (or registered) spec."""
    if name not in _PUBLIC:
        from repro.kernels import ops  # noqa: F401 — triggers registration
    return _PUBLIC[name]
