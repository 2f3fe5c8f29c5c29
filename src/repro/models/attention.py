"""Attention: GQA/MHA, sliding-window, MLA — with chunked online softmax.

The full-sequence path never materializes the (S, S) score matrix: it scans
query chunks and, inside, KV chunks, carrying online-softmax statistics
(m, l, acc). This is mandatory for the 32k prefill dry-run to fit HBM and is
itself a NonGEMM optimization in the paper's sense (the Logit-Computation +
Memory traffic of naive attention is the cost being removed). The Pallas
flash kernel (kernels/flash_attention.py) is the TPU-native version of the
same schedule; this is the lowering-friendly jnp twin.

Decode paths:
  * full attention  — (B, S_max) KV cache, positional masking
  * window          — fixed ring buffer of size W with a position side-car
  * MLA             — compressed (c_kv, k_rope) cache with absorbed projections
"""

from __future__ import annotations

import math
from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro import nn
from repro.core.taxonomy import OpGroup
from repro.models.common import ModelConfig, dense_init

NEG_INF = -1e30


def pos_vector(pos, batch: int):
    """Normalize a decode position to a per-row ``(B,)`` int32 vector.

    Scalar ``pos`` (all rows in lockstep) broadcasts; a ``(B,)`` vector
    (continuous batching: each slot at its own depth) passes through.
    """
    pos = jnp.asarray(pos, jnp.int32)
    if pos.ndim == 0:
        return jnp.broadcast_to(pos, (batch,))
    if pos.shape != (batch,):
        raise ValueError(f"pos must be scalar or ({batch},), got {pos.shape}")
    return pos


def _softcap(s, cap: Optional[float]):
    if cap is None:
        return s
    return jnp.tanh(s / cap) * cap


# ---------------------------------------------------------------------------
# chunked online-softmax attention (full-sequence / prefill / train)
# ---------------------------------------------------------------------------

def chunked_attention(q, k, v, *, causal: bool = True,
                      window: Optional[int] = None,
                      q_offset: int = 0,
                      chunk_q: int = 512, chunk_kv: int = 1024,
                      softcap: Optional[float] = None,
                      triangular: bool = False):
    """q: (B, Sq, Hq, Dh); k, v: (B, Skv, Hkv, Dv). Returns (B, Sq, Hq, Dv).

    ``triangular=True`` skips KV chunks that are fully masked for the current
    query chunk (dynamic ``fori_loop`` bound) — a compute-roofline
    optimization for causal/windowed shapes, at the cost of an unknown trip
    count in the compiled HLO.
    """
    b, sq, hq, dh = q.shape
    _, skv, hkv, dv = v.shape
    g = hq // hkv
    scale = 1.0 / math.sqrt(dh)

    cq = min(chunk_q, sq)
    ck = min(chunk_kv, skv)
    nq = -(-sq // cq)
    nk = -(-skv // ck)
    pad_q = nq * cq - sq
    pad_k = nk * ck - skv

    qf = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0))) if pad_q else q
    kf = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0), (0, 0))) if pad_k else k
    vf = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0), (0, 0))) if pad_k else v

    # (nq, B, cq, Hkv, G, Dh) / (nk, B, ck, Hkv, Dh)
    qs = qf.reshape(b, nq, cq, hkv, g, dh).transpose(1, 0, 2, 3, 4, 5)
    ks = kf.reshape(b, nk, ck, hkv, dh).transpose(1, 0, 2, 3, 4)
    vs = vf.reshape(b, nk, ck, hkv, dv).transpose(1, 0, 2, 3, 4)

    def kv_step(qi, q_chunk, carry, kj):
        m, l, acc = carry
        k_chunk = ks[kj]
        v_chunk = vs[kj]
        with jax.named_scope(nn.scope_tag(OpGroup.GEMM, "attn_qk")):
            # bf16 operands + f32 accumulation: full MXU rate, and no
            # f32 upcast of KV tiles in HBM (2x the attention traffic).
            s = jnp.einsum("bqkgd,btkd->bkgqt", q_chunk, k_chunk,
                           preferred_element_type=jnp.float32) * scale
        s = _softcap(s, softcap)
        with jax.named_scope(nn.scope_tag(OpGroup.ELEMENTWISE, "attn_mask")):
            qpos = q_offset + qi * cq + jnp.arange(cq)
            kpos = kj * ck + jnp.arange(ck)
            mask = jnp.ones((cq, ck), dtype=bool)
            if causal:
                mask &= qpos[:, None] >= kpos[None, :]
            if window is not None:
                mask &= (qpos[:, None] - kpos[None, :]) < window
            mask &= (kpos < skv)[None, :]
            s = jnp.where(mask[None, None, None], s, NEG_INF)
        with jax.named_scope(nn.scope_tag(OpGroup.LOGIT, "online_softmax")):
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + jnp.sum(p, axis=-1)
        with jax.named_scope(nn.scope_tag(OpGroup.GEMM, "attn_pv")):
            pv = jnp.einsum("bkgqt,btkd->bkgqd", p.astype(v_chunk.dtype),
                            v_chunk, preferred_element_type=jnp.float32)
        acc_new = acc * corr[..., None] + pv
        return m_new, l_new, acc_new

    def q_step(_, qi):
        q_chunk = qs[qi]
        m0 = jnp.full((b, hkv, g, cq), NEG_INF, jnp.float32)
        l0 = jnp.zeros((b, hkv, g, cq), jnp.float32)
        a0 = jnp.zeros((b, hkv, g, cq, dv), jnp.float32)
        if triangular and (causal or window is not None):
            hi = jnp.minimum(
                ((q_offset + (qi + 1) * cq + ck - 1) // ck).astype(jnp.int32),
                nk)
            lo = 0
            if window is not None:
                lo = jnp.maximum(
                    (q_offset + qi * cq - window) // ck, 0).astype(jnp.int32)

            def body(kj, carry):
                return kv_step(qi, q_chunk, carry, kj)
            m, l, acc = jax.lax.fori_loop(lo, hi, body, (m0, l0, a0))
        else:
            def body(carry, kj):
                return kv_step(qi, q_chunk, carry, kj), None
            (m, l, acc), _ = jax.lax.scan(body, (m0, l0, a0),
                                          jnp.arange(nk))
        with jax.named_scope(nn.scope_tag(OpGroup.LOGIT, "softmax_norm")):
            # a fully-masked query row (window past the KV depth, pad rows)
            # keeps m at the finite NEG_INF init with l counting exp(0)
            # terms — emit zeros, not the mean(v) garbage of acc / l
            out = jnp.where(m[..., None] > NEG_INF * 0.5,
                            acc / jnp.maximum(l, 1e-30)[..., None], 0.0)
        return None, out  # (B, Hkv, G, cq, Dv)

    _, outs = jax.lax.scan(q_step, None, jnp.arange(nq))
    # (nq, B, Hkv, G, cq, Dv) -> (B, Sq, Hq, Dv)
    out = outs.transpose(1, 0, 4, 2, 3, 5).reshape(b, nq * cq, hq, dv)
    if pad_q:
        out = out[:, :sq]
    return out.astype(v.dtype)


# ---------------------------------------------------------------------------
# flash attention, jnp twin with a flash-style custom VJP
# ---------------------------------------------------------------------------
# Without the custom VJP, differentiating the chunked online-softmax scan
# makes jax.checkpoint stash EVERY (cq, ck) score tile of every layer for
# the backward pass — an O(S^2) f32 stash that dominated the train-cell
# roofline (measured: a (nq, nk, B, H, cq, ck) stack per layer,
# EXPERIMENTS.md §Perf). The flash backward recomputes tiles from (q, k, v,
# out, lse) instead, exactly like the Pallas kernel does on TPU. The whole
# region runs under the ``ng:gemm:flash_attention`` scope, which the
# roofline analyzer recognizes as a single-kernel region.

def _flash_fwd_impl(q, k, v, causal, window, q_offset, chunk_q, chunk_kv,
                    softcap):
    """Head-flat flash forward: q, k, v all (B, S, H, *) — GQA expansion
    happens in the wrapper so H shards cleanly over the model axis even
    when kv_heads < TP degree. Returns (out, lse (B, H, Sq) f32)."""
    b, sq, h, dh = q.shape
    _, skv, _, dv = v.shape
    scale = 1.0 / math.sqrt(dh)
    cq = min(chunk_q, sq)
    ck = min(chunk_kv, skv)
    nq = -(-sq // cq)
    nk = -(-skv // ck)
    pad_q = nq * cq - sq
    pad_k = nk * ck - skv
    qf = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0))) if pad_q else q
    kf = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0), (0, 0))) if pad_k else k
    vf = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0), (0, 0))) if pad_k else v
    qs = qf.reshape(b, nq, cq, h, dh).transpose(1, 0, 2, 3, 4)
    ks = kf.reshape(b, nk, ck, h, dh).transpose(1, 0, 2, 3, 4)
    vs = vf.reshape(b, nk, ck, h, dv).transpose(1, 0, 2, 3, 4)

    def mask_for(qi, kj):
        qpos = q_offset + qi * cq + jnp.arange(cq)
        kpos = kj * ck + jnp.arange(ck)
        m = jnp.ones((cq, ck), bool)
        if causal:
            m &= qpos[:, None] >= kpos[None, :]
        if window is not None:
            m &= (qpos[:, None] - kpos[None, :]) < window
        m &= (kpos < skv)[None, :]
        return m

    def q_step(_, qi):
        q_chunk = qs[qi]
        m0 = jnp.full((b, h, cq), NEG_INF, jnp.float32)
        l0 = jnp.zeros((b, h, cq), jnp.float32)
        a0 = jnp.zeros((b, h, cq, dv), jnp.float32)

        def kv_step(carry, kj):
            m, l, acc = carry
            s = jnp.einsum("bqhd,bthd->bhqt", q_chunk, ks[kj],
                           preferred_element_type=jnp.float32) * scale
            s = _softcap(s, softcap)
            s = jnp.where(mask_for(qi, kj)[None, None], s, NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + jnp.sum(p, axis=-1)
            pv = jnp.einsum("bhqt,bthd->bhqd", p.astype(vs.dtype), vs[kj],
                            preferred_element_type=jnp.float32)
            return (m_new, l_new, acc * corr[..., None] + pv), None

        (m, l, acc), _ = jax.lax.scan(kv_step, (m0, l0, a0), jnp.arange(nk))
        lsafe = jnp.maximum(l, 1e-30)
        # same fully-masked-row guard as chunked_attention / the Pallas
        # template epilogue: rows that saw no real score emit zeros
        out = jnp.where(m[..., None] > NEG_INF * 0.5,
                        acc / lsafe[..., None], 0.0)
        lse = m + jnp.log(lsafe)
        return None, (out, lse)

    _, (outs, lses) = jax.lax.scan(q_step, None, jnp.arange(nq))
    # outs: (nq, b, h, cq, dv) -> (b, sq, h, dv)
    out = outs.transpose(1, 0, 3, 2, 4).reshape(b, nq * cq, h, dv)
    lse = lses.transpose(1, 2, 0, 3).reshape(b, h, nq * cq)
    if pad_q:
        out = out[:, :sq]
        lse = lse[..., :sq]
    return out.astype(v.dtype), lse


def _flash_bwd_impl(q, k, v, out, lse, dout, causal, window, q_offset,
                    chunk_q, chunk_kv):
    """Head-flat flash backward: recompute tiles; never stores (S, S)."""
    b, sq, h, dh = q.shape
    _, skv, _, dv = v.shape
    scale = 1.0 / math.sqrt(dh)
    cq = min(chunk_q, sq)
    ck = min(chunk_kv, skv)
    nq = -(-sq // cq)
    nk = -(-skv // ck)
    pad_q = nq * cq - sq
    pad_k = nk * ck - skv
    padq = lambda a: jnp.pad(a, ((0, 0), (0, pad_q)) + ((0, 0),) * (a.ndim - 2)) if pad_q else a
    padk = lambda a: jnp.pad(a, ((0, 0), (0, pad_k)) + ((0, 0),) * (a.ndim - 2)) if pad_k else a
    qf, of, do = padq(q), padq(out), padq(dout)
    kf, vf = padk(k), padk(v)
    lsef = (jnp.pad(lse, ((0, 0), (0, 0), (0, pad_q))) if pad_q else lse)

    qs = qf.reshape(b, nq, cq, h, dh).transpose(1, 0, 2, 3, 4)
    os_ = of.reshape(b, nq, cq, h, dv).transpose(1, 0, 2, 3, 4)
    dos = do.reshape(b, nq, cq, h, dv).transpose(1, 0, 2, 3, 4)
    ks = kf.reshape(b, nk, ck, h, dh).transpose(1, 0, 2, 3, 4)
    vs = vf.reshape(b, nk, ck, h, dv).transpose(1, 0, 2, 3, 4)
    lss = lsef.reshape(b, h, nq, cq).transpose(2, 0, 1, 3)

    # delta_i = rowsum(dO * O)  (B, H, cq) per q chunk
    deltas = jnp.einsum("nbqhd,nbqhd->nbhq", dos.astype(jnp.float32),
                        os_.astype(jnp.float32))

    def mask_for(qi, kj):
        qpos = q_offset + qi * cq + jnp.arange(cq)
        kpos = kj * ck + jnp.arange(ck)
        m = jnp.ones((cq, ck), bool)
        if causal:
            m &= qpos[:, None] >= kpos[None, :]
        if window is not None:
            m &= (qpos[:, None] - kpos[None, :]) < window
        m &= (kpos < skv)[None, :]
        return m

    def q_step(carry, qi):
        dk_acc, dv_acc = carry                    # (nk, b, ck, h, d*) f32
        q_chunk = qs[qi]
        do_chunk = dos[qi]
        lse_i = lss[qi]
        delta_i = deltas[qi]

        def kv_step(dq_acc, kj):
            s = jnp.einsum("bqhd,bthd->bhqt", q_chunk, ks[kj],
                           preferred_element_type=jnp.float32) * scale
            s = jnp.where(mask_for(qi, kj)[None, None], s, NEG_INF)
            p = jnp.exp(s - lse_i[..., None])                    # (b,h,q,t)
            dp = jnp.einsum("bqhd,bthd->bhqt", do_chunk, vs[kj],
                            preferred_element_type=jnp.float32)
            ds = p * (dp - delta_i[..., None]) * scale
            dsb = ds.astype(q_chunk.dtype)
            dq_c = jnp.einsum("bhqt,bthd->bqhd", dsb, ks[kj],
                              preferred_element_type=jnp.float32)
            dk_c = jnp.einsum("bhqt,bqhd->bthd", dsb, q_chunk,
                              preferred_element_type=jnp.float32)
            dv_c = jnp.einsum("bhqt,bqhd->bthd", p.astype(do_chunk.dtype),
                              do_chunk, preferred_element_type=jnp.float32)
            return dq_acc + dq_c, (dk_c, dv_c)

        dq_i, (dk_cs, dv_cs) = jax.lax.scan(
            kv_step, jnp.zeros((b, cq, h, dh), jnp.float32),
            jnp.arange(nk))
        return (dk_acc + dk_cs, dv_acc + dv_cs), dq_i

    zk = jnp.zeros((nk, b, ck, h, dh), jnp.float32)
    zv = jnp.zeros((nk, b, ck, h, dv), jnp.float32)
    (dk_all, dv_all), dq_chunks = jax.lax.scan(q_step, (zk, zv),
                                               jnp.arange(nq))
    dq = dq_chunks.transpose(1, 0, 2, 3, 4).reshape(b, nq * cq, h, dh)
    dk = dk_all.transpose(1, 0, 2, 3, 4).reshape(b, nk * ck, h, dh)
    dv = dv_all.transpose(1, 0, 2, 3, 4).reshape(b, nk * ck, h, dv)
    if pad_q:
        dq = dq[:, :sq]
    if pad_k:
        dk = dk[:, :skv]
        dv = dv[:, :skv]
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


@partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash_core(q, k, v, causal, window, q_offset, chunk_q, chunk_kv):
    out, _ = _flash_fwd_impl(q, k, v, causal, window, q_offset, chunk_q,
                             chunk_kv, None)
    return out


def _flash_core_fwd(q, k, v, causal, window, q_offset, chunk_q, chunk_kv):
    out, lse = _flash_fwd_impl(q, k, v, causal, window, q_offset, chunk_q,
                               chunk_kv, None)
    return out, (q, k, v, out, lse)


def _flash_core_bwd(causal, window, q_offset, chunk_q, chunk_kv, res, dout):
    q, k, v, out, lse = res
    with jax.named_scope(nn.scope_tag(OpGroup.GEMM, "flash_attention")):
        dq, dk, dv = _flash_bwd_impl(q, k, v, out, lse, dout, causal, window,
                                     q_offset, chunk_q, chunk_kv)
    return dq, dk, dv


_flash_core.defvjp(_flash_core_fwd, _flash_core_bwd)


def flash_attention_jnp(q, k, v, *, causal: bool = True,
                        window: Optional[int] = None, q_offset: int = 0,
                        chunk_q: int = 512, chunk_kv: int = 1024,
                        softcap: Optional[float] = None):
    """Flash attention (jnp twin of kernels/flash_attention.py).

    GQA is expanded to head-flat form *outside* the custom-VJP core: the
    per-q-head KV gather shards cleanly over the model axis even when
    kv_heads < TP degree (kv_heads=8 on a 16-way axis would otherwise
    replicate the whole attention computation on every model shard —
    EXPERIMENTS.md §Perf iteration 2), and autodiff through the gather
    gives the group-summed dk/dv for free. Softcap falls back to the plain
    chunked path (no assigned arch softcaps attention).
    """
    if softcap is not None:
        return chunked_attention(q, k, v, causal=causal, window=window,
                                 q_offset=q_offset, chunk_q=chunk_q,
                                 chunk_kv=chunk_kv, softcap=softcap)
    from repro.sharding import shard

    hq, hkv = q.shape[2], k.shape[2]
    if hq != hkv:
        g = hq // hkv
        idx = jnp.arange(hq) // g
        k = jnp.take(k, idx, axis=2)
        v = jnp.take(v, idx, axis=2)
    q = shard(q, "batch", None, "heads", None)
    k = shard(k, "batch", None, "heads", None)
    v = shard(v, "batch", None, "heads", None)
    with jax.named_scope(nn.scope_tag(OpGroup.GEMM, "flash_attention")):
        out = _flash_core(q, k, v, causal, window, q_offset,
                          min(chunk_q, q.shape[1]),
                          min(chunk_kv, k.shape[1]))
    return shard(out, "batch", None, "heads", None)


# ---------------------------------------------------------------------------
# standard (GQA) attention layer
# ---------------------------------------------------------------------------

def init_attention(key, cfg: ModelConfig) -> dict:
    d, hq, hkv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    ks = jax.random.split(key, 8)
    pd = jnp.dtype(cfg.param_dtype)
    p = {
        "wq": dense_init(ks[0], (d, hq * hd), dtype=pd),
        "wk": dense_init(ks[1], (d, hkv * hd), dtype=pd),
        "wv": dense_init(ks[2], (d, hkv * hd), dtype=pd),
        "wo": dense_init(ks[3], (hq * hd, d), dtype=pd),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((hq * hd,), pd)
        p["bk"] = jnp.zeros((hkv * hd,), pd)
        p["bv"] = jnp.zeros((hkv * hd,), pd)
    if cfg.qk_norm:
        p["q_norm"] = jnp.ones((hd,), pd)
        p["k_norm"] = jnp.ones((hd,), pd)
    return p


def _qkv(params, x, cfg: ModelConfig, positions):
    hq, hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    q = nn.linear(x, params["wq"].astype(x.dtype),
                  params.get("bq", None) if cfg.qkv_bias else None)
    k = nn.linear(x, params["wk"].astype(x.dtype),
                  params.get("bk", None) if cfg.qkv_bias else None)
    v = nn.linear(x, params["wv"].astype(x.dtype),
                  params.get("bv", None) if cfg.qkv_bias else None)
    q = nn.split_heads(q, hq)
    k = nn.split_heads(k, hkv)
    v = nn.split_heads(v, hkv)
    if cfg.qk_norm:
        q = nn.rms_norm(q, params["q_norm"].astype(x.dtype))
        k = nn.rms_norm(k, params["k_norm"].astype(x.dtype))
    if cfg.pos_emb == "rope":
        q = nn.apply_rope(q, positions, base=cfg.rope_base,
                          fraction=cfg.rope_fraction)
        k = nn.apply_rope(k, positions, base=cfg.rope_base,
                          fraction=cfg.rope_fraction)
    return q, k, v


def _attention_impl(q, k, v, cfg: ModelConfig, window, q_offset: int = 0):
    """Backend dispatch: Pallas flash kernel vs the flash-VJP jnp twin."""
    backend = nn.get_backend()
    if backend != "jnp" and cfg.attn_logit_softcap is None:
        from repro.kernels import ops as kops
        return kops.flash_attention(
            q, k, v, causal=cfg.causal, window=window, q_offset=q_offset,
            interpret=nn.kernel_interpret())
    return flash_attention_jnp(
        q, k, v, causal=cfg.causal, window=window, q_offset=q_offset,
        chunk_q=cfg.attn_chunk_q, chunk_kv=cfg.attn_chunk_kv,
        softcap=cfg.attn_logit_softcap)


def attn_forward(params, x, cfg: ModelConfig, kind: str, positions):
    """Full-sequence attention (train / prefill). x: (B, S, D)."""
    q, k, v = _qkv(params, x, cfg, positions)
    window = cfg.window_size if kind == "local" else None
    out = _attention_impl(q, k, v, cfg, window)
    return nn.linear(nn.merge_heads(out), params["wo"].astype(x.dtype))


def init_attn_cache(cfg: ModelConfig, kind: str, batch: int, max_len: int):
    hkv, hd = cfg.n_kv_heads, cfg.resolved_head_dim
    dt = cfg.activation_dtype
    if kind == "local":
        w = min(cfg.window_size, max_len)
        return {
            "k": jnp.zeros((batch, w, hkv, hd), dt),
            "v": jnp.zeros((batch, w, hkv, hd), dt),
            # per-row position side-car: under continuous batching each
            # slot's ring buffer is at its own depth
            "pos": jnp.full((batch, w), -1, jnp.int32),
        }
    return {
        "k": jnp.zeros((batch, max_len, hkv, hd), dt),
        "v": jnp.zeros((batch, max_len, hkv, hd), dt),
    }


def attn_prefill(params, x, cfg: ModelConfig, kind: str, positions,
                 max_len: int, lengths=None) -> Tuple[jax.Array, dict]:
    """Full-sequence forward that also materializes the decode cache.

    x: (B, S, D) with S <= max_len. The returned cache matches
    :func:`init_attn_cache` layout exactly so ``attn_decode`` continues from
    position S (or from each row's true ``lengths`` under right-padding).

    ``lengths`` (B,) optional true prompt lengths of a right-padded batch.
    The full-cache branch ignores it (pad KV beyond a row's length is never
    attended: decode masks ``arange <= pos`` per row and overwrites pads in
    place), but the ring buffer MUST fill from the true prompt tail — the
    padded tail would otherwise evict in-window real KV with masked pads.
    """
    b, s, _ = x.shape
    q, k, v = _qkv(params, x, cfg, positions)
    window = cfg.window_size if kind == "local" else None
    out = _attention_impl(q, k, v, cfg, window)
    y = nn.linear(nn.merge_heads(out), params["wo"].astype(x.dtype))

    cache = init_attn_cache(cfg, kind, b, max_len)
    if kind == "local":
        w = cache["k"].shape[1]
        if lengths is None:
            lengths = jnp.full((b,), s, jnp.int32)
        lengths = jnp.asarray(lengths, jnp.int32).reshape(-1)
        # ring slot j holds the last real position p ≡ j (mod w) — the
        # true prompt tail per row, independent of right-padding
        j = jnp.arange(w)
        p = (lengths[:, None] - 1) - jnp.mod(lengths[:, None] - 1 - j, w)
        idx = jnp.maximum(p, 0)[:, :, None, None]           # (B, w, 1, 1)
        cache = {
            "k": jnp.take_along_axis(k, idx, axis=1).astype(cache["k"].dtype),
            "v": jnp.take_along_axis(v, idx, axis=1).astype(cache["v"].dtype),
            "pos": jnp.where(p >= 0, p, -1).astype(jnp.int32),
        }
    else:
        cache = {
            "k": jax.lax.dynamic_update_slice_in_dim(
                cache["k"], k.astype(cache["k"].dtype), 0, axis=1),
            "v": jax.lax.dynamic_update_slice_in_dim(
                cache["v"], v.astype(cache["v"].dtype), 0, axis=1),
        }
    return y, cache


def attn_decode(params, x, cfg: ModelConfig, kind: str, cache: dict,
                pos) -> Tuple[jax.Array, dict]:
    """One-token decode. x: (B, 1, D); pos: scalar int32 or per-row (B,)."""
    b = x.shape[0]
    hq, hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    g = hq // hkv
    pos = pos_vector(pos, b)
    positions = pos[:, None]
    q, k_new, v_new = _qkv(params, x, cfg, positions)

    if kind == "local":
        w = cache["k"].shape[1]
        slot = jnp.mod(pos, w)
        kv_write = jax.vmap(
            lambda c, n, i: jax.lax.dynamic_update_slice_in_dim(
                c, n, i, axis=0))
        k = kv_write(cache["k"], k_new.astype(cache["k"].dtype), slot)
        v = kv_write(cache["v"], v_new.astype(cache["v"].dtype), slot)
        cpos = kv_write(cache["pos"], pos[:, None], slot)
        valid = (cpos >= 0) & (cpos <= pos[:, None]) \
            & (pos[:, None] - cpos < w)
        # ring invariant: slot j holds the last position ≡ j (mod w), so
        # the set of valid slots is exactly the first min(pos+1, w) —
        # which is what the decode-1q template masks by prefix length
        lengths = jnp.minimum(pos + 1, w)
        new_cache = {"k": k, "v": v, "pos": cpos}
    else:
        k = nn.kv_cache_update(cache["k"], k_new, pos)
        v = nn.kv_cache_update(cache["v"], v_new, pos)
        t = k.shape[1]
        valid = jnp.arange(t)[None, :] <= pos[:, None]
        lengths = pos + 1
        new_cache = {"k": k, "v": v}

    backend = nn.get_backend()
    if nn.fusion_enabled():
        # one fused operator (attn_template:decode on kernel backends)
        o = nn.fused_attn_decode(q, k, v, lengths,
                                 softcap=cfg.attn_logit_softcap)
        o = o.reshape(b, 1, hq * hd).astype(x.dtype)
        return nn.linear(o, params["wo"].astype(x.dtype)), new_cache
    if backend != "jnp":
        from repro.kernels import ops as kops
        o = kops.attn_decode_template(
            q, k, v, lengths, softcap=cfg.attn_logit_softcap,
            interpret=nn.kernel_interpret())
        o = o.reshape(b, 1, hq * hd).astype(x.dtype)
        return nn.linear(o, params["wo"].astype(x.dtype)), new_cache

    scale = 1.0 / math.sqrt(hd)
    qh = q.reshape(b, hkv, g, hd)
    with jax.named_scope(nn.scope_tag(OpGroup.GEMM, "attn_qk")):
        # KV stays bf16 in HBM; f32 accumulate on the MXU. An explicit
        # .astype(f32) here makes XLA convert (and copy) the whole
        # 32k-deep cache every decode step — see EXPERIMENTS.md §Perf.
        s = jnp.einsum("bkgd,btkd->bkgt", qh, k,
                       preferred_element_type=jnp.float32) * scale
    s = _softcap(s, cfg.attn_logit_softcap)
    with jax.named_scope(nn.scope_tag(OpGroup.ELEMENTWISE, "attn_mask")):
        s = jnp.where(valid[:, None, None, :], s, NEG_INF)
    p = nn.softmax(s, axis=-1)
    with jax.named_scope(nn.scope_tag(OpGroup.GEMM, "attn_pv")):
        o = jnp.einsum("bkgt,btkd->bkgd", p.astype(v.dtype), v,
                       preferred_element_type=jnp.float32)
    o = o.reshape(b, 1, hq * hd).astype(x.dtype)
    return nn.linear(o, params["wo"].astype(x.dtype)), new_cache


def attn_extend(params, x, cfg: ModelConfig, kind: str, cache: dict,
                start) -> Tuple[jax.Array, dict]:
    """Chunked-prefill step: extend the cache with a (B, C) token chunk.

    x: (B, C, D); ``start`` is a traced scalar int32 — the absolute
    position of the chunk's first token. The chunk attends the full cache
    depth (earlier chunks / reused prefix blocks are already resident) via
    ``q_offset=start``; positions past the chunk are causally masked, so
    stale rows there cannot contribute. K/V for the chunk land at
    ``[start, start + C)``. Only full-cache attention supports extension —
    a ring buffer cannot re-enter at an arbitrary depth.
    """
    if kind == "local":
        raise ValueError("chunked prefill requires a full-depth cache; "
                         "sliding-window layers cannot extend")
    b, c_len, _ = x.shape
    start = jnp.asarray(start, jnp.int32)
    positions = start + jnp.arange(c_len, dtype=jnp.int32)[None, :]
    positions = jnp.broadcast_to(positions, (b, c_len))
    q, k_new, v_new = _qkv(params, x, cfg, positions)
    k = jax.lax.dynamic_update_slice_in_dim(
        cache["k"], k_new.astype(cache["k"].dtype), start, axis=1)
    v = jax.lax.dynamic_update_slice_in_dim(
        cache["v"], v_new.astype(cache["v"].dtype), start, axis=1)
    # q_offset is only used inside mask computation, so a traced scalar
    # works — but only through chunked_attention: the flash custom-VJP core
    # takes q_offset as a nondiff argnum, which rejects tracers
    out = chunked_attention(
        q, k, v, causal=cfg.causal, q_offset=start,
        chunk_q=cfg.attn_chunk_q, chunk_kv=cfg.attn_chunk_kv,
        softcap=cfg.attn_logit_softcap)
    y = nn.linear(nn.merge_heads(out), params["wo"].astype(x.dtype))
    return y, {"k": k, "v": v}


# ---------------------------------------------------------------------------
# MLA (DeepSeek-V2) — compressed KV latent attention
# ---------------------------------------------------------------------------

def init_mla(key, cfg: ModelConfig) -> dict:
    d, h = cfg.d_model, cfg.n_heads
    r, nope, rope, vd = (cfg.kv_lora_rank, cfg.qk_nope_dim, cfg.qk_rope_dim,
                         cfg.v_head_dim)
    ks = jax.random.split(key, 6)
    pd = jnp.dtype(cfg.param_dtype)
    return {
        "w_dkv": dense_init(ks[0], (d, r), dtype=pd),
        "w_kr": dense_init(ks[1], (d, rope), dtype=pd),
        "kv_norm": jnp.ones((r,), pd),
        "w_q": dense_init(ks[2], (d, h * (nope + rope)), dtype=pd),
        "w_uk": dense_init(ks[3], (r, h, nope), dtype=pd),
        "w_uv": dense_init(ks[4], (r, h, vd), dtype=pd),
        "wo": dense_init(ks[5], (h * vd, d), dtype=pd),
    }


def _mla_q(params, x, cfg: ModelConfig, positions):
    h = cfg.n_heads
    nope, rope = cfg.qk_nope_dim, cfg.qk_rope_dim
    q = nn.linear(x, params["w_q"].astype(x.dtype))
    q = nn.split_heads(q, h)                        # (B,S,H,nope+rope)
    q_nope, q_rope = q[..., :nope], q[..., nope:]
    q_rope = nn.apply_rope(q_rope, positions, base=cfg.rope_base)
    return q_nope, q_rope


def _mla_ckv(params, x, cfg: ModelConfig, positions):
    c = nn.linear(x, params["w_dkv"].astype(x.dtype))
    c = nn.rms_norm(c, params["kv_norm"].astype(x.dtype))
    kr = nn.linear(x, params["w_kr"].astype(x.dtype))[:, :, None, :]
    kr = nn.apply_rope(kr, positions, base=cfg.rope_base)[:, :, 0, :]
    return c, kr


def mla_forward(params, x, cfg: ModelConfig, positions):
    """Training/prefill MLA: expand K/V from the latent, chunked attention."""
    h, nope, vd = cfg.n_heads, cfg.qk_nope_dim, cfg.v_head_dim
    q_nope, q_rope = _mla_q(params, x, cfg, positions)
    c, kr = _mla_ckv(params, x, cfg, positions)
    k_nope = nn.einsum("bsr,rhn->bshn", c, params["w_uk"].astype(x.dtype))
    v = nn.einsum("bsr,rhv->bshv", c, params["w_uv"].astype(x.dtype))
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(kr[:, :, None, :],
                                  (*kr.shape[:2], h, cfg.qk_rope_dim))],
        axis=-1)
    backend = nn.get_backend()
    if backend != "jnp":
        # the causal template handles Dv != Dk (nope+rope keys, v_head_dim
        # values), so MLA prefill routes through the same Pallas body
        from repro.kernels import ops as kops
        out = kops.flash_attention(
            q, k, v, causal=cfg.causal,
            interpret=nn.kernel_interpret())
    else:
        out = flash_attention_jnp(q, k, v, causal=cfg.causal,
                                  chunk_q=cfg.attn_chunk_q,
                                  chunk_kv=cfg.attn_chunk_kv)
    return nn.linear(out.reshape(*x.shape[:2], h * vd),
                     params["wo"].astype(x.dtype))


def init_mla_cache(cfg: ModelConfig, batch: int, max_len: int):
    dt = cfg.activation_dtype
    return {
        "c": jnp.zeros((batch, max_len, cfg.kv_lora_rank), dt),
        "kr": jnp.zeros((batch, max_len, cfg.qk_rope_dim), dt),
    }


def mla_prefill(params, x, cfg: ModelConfig, positions,
                max_len: int) -> Tuple[jax.Array, dict]:
    """MLA forward that also fills the compressed (c, kr) decode cache."""
    b = x.shape[0]
    y = mla_forward(params, x, cfg, positions)
    c, kr = _mla_ckv(params, x, cfg, positions)
    cache = init_mla_cache(cfg, b, max_len)
    cache = {
        "c": jax.lax.dynamic_update_slice_in_dim(
            cache["c"], c.astype(cache["c"].dtype), 0, axis=1),
        "kr": jax.lax.dynamic_update_slice_in_dim(
            cache["kr"], kr.astype(cache["kr"].dtype), 0, axis=1),
    }
    return y, cache


def mla_decode(params, x, cfg: ModelConfig, cache: dict, pos):
    """Absorbed-projection MLA decode: attends in the 512-d latent space.

    ``pos`` is a scalar or a per-row ``(B,)`` vector (continuous batching).
    """
    b = x.shape[0]
    h, vd = cfg.n_heads, cfg.v_head_dim
    pos = pos_vector(pos, b)
    positions = pos[:, None]
    q_nope, q_rope = _mla_q(params, x, cfg, positions)   # (B,1,H,*)
    c_new, kr_new = _mla_ckv(params, x, cfg, positions)
    c = nn.kv_cache_update(cache["c"], c_new, pos)
    kr = nn.kv_cache_update(cache["kr"], kr_new, pos)
    t = c.shape[1]

    # absorb W_uk into the query: score in latent space
    q_lat = nn.einsum("bqhn,rhn->bqhr", q_nope, params["w_uk"].astype(x.dtype))
    scale = 1.0 / math.sqrt(cfg.qk_nope_dim + cfg.qk_rope_dim)
    backend = nn.get_backend()
    if nn.fusion_enabled() or backend != "jnp":
        # decode-1q spec over the latent cache: q/k live in the
        # concatenated (r + rope) latent space (Hkv=1, GQA group = H),
        # values are the r-dim latent itself (Dv != Dk), and the W_uv
        # up-projection stays OUTSIDE the kernel as the epilogue. The
        # concatenated score sums in one dot where the unfused path sums
        # two einsums — ulp-level, not bit-identical (docs/kernels.md).
        q_eff = jnp.concatenate([q_lat, q_rope.astype(q_lat.dtype)],
                                axis=-1)
        k_eff = jnp.concatenate([c, kr], axis=-1)[:, :, None, :]
        v_eff = c[:, :, None, :]
        lengths = pos + 1
        if nn.fusion_enabled():
            ctx = nn.fused_attn_decode(q_eff, k_eff, v_eff, lengths,
                                       scale=scale)
        else:
            from repro.kernels import ops as kops
            ctx = kops.attn_decode_template(
                q_eff, k_eff, v_eff, lengths, scale=scale,
                interpret=nn.kernel_interpret())
    else:
        with jax.named_scope(nn.scope_tag(OpGroup.GEMM, "attn_qk")):
            s = (jnp.einsum("bqhr,btr->bhqt", q_lat, c,
                            preferred_element_type=jnp.float32) +
                 jnp.einsum("bqhp,btp->bhqt", q_rope, kr,
                            preferred_element_type=jnp.float32)) * scale
        valid = jnp.arange(t)[None, :] <= pos[:, None]
        with jax.named_scope(nn.scope_tag(OpGroup.ELEMENTWISE,
                                          "attn_mask")):
            s = jnp.where(valid[:, None, None, :], s, NEG_INF)
        p = nn.softmax(s, axis=-1)
        with jax.named_scope(nn.scope_tag(OpGroup.GEMM, "attn_pv")):
            ctx = jnp.einsum("bhqt,btr->bqhr", p.astype(c.dtype), c,
                             preferred_element_type=jnp.float32)
    out = nn.einsum("bqhr,rhv->bqhv", ctx.astype(x.dtype),
                    params["w_uv"].astype(x.dtype))
    out = out.reshape(b, 1, h * vd)
    return (nn.linear(out, params["wo"].astype(x.dtype)),
            {"c": c, "kr": kr})


def mla_extend(params, x, cfg: ModelConfig, cache: dict,
               start) -> Tuple[jax.Array, dict]:
    """Chunked-prefill MLA step: extend the latent cache with a (B, C) chunk.

    Mirrors :func:`attn_extend`: writes (c, kr) at ``[start, start + C)``,
    expands K/V from the FULL cached latent depth (like ``mla_forward``),
    and attends with ``q_offset=start`` so positions past the chunk stay
    causally masked.
    """
    b, c_len, _ = x.shape
    h = cfg.n_heads
    start = jnp.asarray(start, jnp.int32)
    positions = start + jnp.arange(c_len, dtype=jnp.int32)[None, :]
    positions = jnp.broadcast_to(positions, (b, c_len))
    q_nope, q_rope = _mla_q(params, x, cfg, positions)
    c_new, kr_new = _mla_ckv(params, x, cfg, positions)
    c = jax.lax.dynamic_update_slice_in_dim(
        cache["c"], c_new.astype(cache["c"].dtype), start, axis=1)
    kr = jax.lax.dynamic_update_slice_in_dim(
        cache["kr"], kr_new.astype(cache["kr"].dtype), start, axis=1)
    k_nope = nn.einsum("bsr,rhn->bshn", c.astype(x.dtype),
                       params["w_uk"].astype(x.dtype))
    v = nn.einsum("bsr,rhv->bshv", c.astype(x.dtype),
                  params["w_uv"].astype(x.dtype))
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(kr[:, :, None, :].astype(x.dtype),
                                  (*kr.shape[:2], h, cfg.qk_rope_dim))],
        axis=-1)
    out = chunked_attention(q, k, v, causal=cfg.causal, q_offset=start,
                            chunk_q=cfg.attn_chunk_q,
                            chunk_kv=cfg.attn_chunk_kv)
    y = nn.linear(out.reshape(b, c_len, h * cfg.v_head_dim),
                  params["wo"].astype(x.dtype))
    return y, {"c": c, "kr": kr}
