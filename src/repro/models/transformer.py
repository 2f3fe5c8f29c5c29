"""LM assembly: pattern-stacked blocks, scan over layers, train/prefill/decode.

Every assigned architecture is an instance of this one decoder stack; the
``block_pattern`` in the config selects the temporal mixer per layer
(attention / local attention / MLA / RG-LRU / mLSTM / sLSTM) and the FFN is
dense or MoE per layer index. Homogeneous pattern repeats are
``lax.scan``-stacked (one compiled block body regardless of depth — the
compile-time lever that makes 80-layer dry-runs tractable) with
``jax.checkpoint`` on the scan body for training memory.

Public API (all pure functions over a params pytree):

    init_lm(key, cfg)                       -> params
    lm_forward(params, tokens, cfg)         -> logits (B, S, V)
    lm_loss(params, batch, cfg)             -> (loss, metrics)
    init_lm_cache(cfg, batch, max_len)      -> caches
    lm_prefill(params, tokens, cfg, max_len, lengths=None)
                                            -> (last_logits, caches)
    lm_decode(params, token, pos, caches, cfg) -> (logits, caches)

``pos`` may be a scalar (a freshly prefilled batch decoding in lockstep) or
a per-row ``(B,)`` vector — the continuous-batching engine keeps every slot
at its own absolute position.  ``lengths`` lets a right-padded prefill read
its last-token logits at each row's true prompt end instead of the pad tail.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from repro import nn
from repro.core.taxonomy import OpGroup
from repro.models import attention as A
from repro.models import moe as M
from repro.models import recurrent as R
from repro.models import xlstm as X
from repro.models.common import ModelConfig, dense_init, stack_trees
from repro.sharding import shard

ATTN_KINDS = ("attn", "local")


# ---------------------------------------------------------------------------
# positions
# ---------------------------------------------------------------------------

def sinusoidal_embedding(positions, d_model: int, base: float = 10000.0):
    """(B, S) int positions -> (B, S, D) sinusoidal table (MusicGen-style)."""
    half = d_model // 2
    freq = base ** (-jnp.arange(half, dtype=jnp.float32) / half)
    theta = positions[..., None].astype(jnp.float32) * freq
    return jnp.concatenate([jnp.sin(theta), jnp.cos(theta)], axis=-1)


def _add_positional(x, positions, params, cfg: ModelConfig):
    if cfg.pos_emb == "sinusoidal":
        with jax.named_scope(nn.scope_tag(OpGroup.MEMORY, "pos_sinusoidal")):
            return x + sinusoidal_embedding(
                positions, cfg.d_model).astype(x.dtype)
    if cfg.pos_emb == "learned":
        with jax.named_scope(nn.scope_tag(OpGroup.MEMORY, "pos_learned")):
            return x + jnp.take(params["pos"], positions, axis=0).astype(x.dtype)
    return x  # rope is applied inside attention; "none" for xLSTM


# ---------------------------------------------------------------------------
# one block: init
# ---------------------------------------------------------------------------

def _init_norm(cfg: ModelConfig):
    d = cfg.d_model
    pd = jnp.dtype(cfg.param_dtype)
    if cfg.norm == "layernorm":
        return {"scale": jnp.ones((d,), pd), "bias": jnp.zeros((d,), pd)}
    return {"scale": jnp.ones((d,), pd)}


def _eps(cfg: ModelConfig) -> dict:
    """The norms' ``eps`` keyword: none where the config keeps
    ``repro.nn``'s default for its norm."""
    return {} if cfg.norm_eps is None else {"eps": cfg.norm_eps}


def _apply_norm(p, x, cfg: ModelConfig):
    if cfg.norm == "layernorm":
        return nn.layer_norm(x, p["scale"].astype(x.dtype),
                             p["bias"].astype(x.dtype), **_eps(cfg))
    return nn.rms_norm(x, p["scale"].astype(x.dtype),
                       zero_centered=cfg.zero_centered_norm, **_eps(cfg))


def _add_norm(p, a, x, cfg: ModelConfig):
    """``h = norm(a + x)``; returns ``(h, a + x)``.

    The pre-norm block boundary every transformer pays twice per layer.
    Routed through ``nn.add_rms_norm`` / ``nn.add_layer_norm`` so that
    under ``nn.fuse()`` (the FusionTransform / ``Engine(fused=True)`` fast
    path) the pair executes as ONE fused kernel-backed operator.
    """
    if cfg.norm == "layernorm":
        return nn.add_layer_norm(a, x, p["scale"].astype(x.dtype),
                                 p["bias"].astype(x.dtype), **_eps(cfg))
    return nn.add_rms_norm(a, x, p["scale"].astype(x.dtype),
                           zero_centered=cfg.zero_centered_norm, **_eps(cfg))


def _multiply(x, factor, name: str):
    """``x * factor`` as one elementwise op under ``ng:elementwise:<name>``;
    no op at all where ``factor`` is None (the neutral value)."""
    if factor is None:
        return x
    with jax.named_scope(nn.scope_tag(OpGroup.ELEMENTWISE, name)):
        return x * jnp.asarray(factor, x.dtype)


def _branch(y, cfg: ModelConfig):
    """A block's attention or FFN branch, scaled by ``residual_multiplier``
    on its way into the residual add."""
    return _multiply(y, cfg.residual_multiplier, "residual_multiplier")


def _is_moe_layer(cfg: ModelConfig, layer_idx: int) -> bool:
    return cfg.is_moe and layer_idx >= cfg.first_dense_layers


def init_block(key, cfg: ModelConfig, kind: str, layer_idx: int) -> dict:
    ks = jax.random.split(key, 4)
    if kind in ("mlstm", "slstm"):
        mixer = X.init_mlstm(ks[0], cfg) if kind == "mlstm" \
            else X.init_slstm(ks[0], cfg)
        return {"norm1": _init_norm(cfg), "mixer": mixer}
    if kind == "rec":
        mixer = R.init_recurrent(ks[0], cfg)
    elif cfg.mla:
        mixer = A.init_mla(ks[0], cfg)
    else:
        mixer = A.init_attention(ks[0], cfg)
    p = {"norm1": _init_norm(cfg), "mixer": mixer, "norm2": _init_norm(cfg)}
    if _is_moe_layer(cfg, layer_idx):
        p["moe"] = M.init_moe(ks[1], cfg)
    else:
        p["ffn"] = M.init_ffn(ks[1], cfg)
    if cfg.post_norm:
        p["post_norm1"] = _init_norm(cfg)
        p["post_norm2"] = _init_norm(cfg)
    return p


# ---------------------------------------------------------------------------
# one block: forward / prefill / decode
# ---------------------------------------------------------------------------

def _mixer_forward(p, h, cfg: ModelConfig, kind: str, positions):
    if kind == "rec":
        return R.recurrent_forward(p, h, cfg)
    if kind == "mlstm":
        return X.mlstm_forward(p, h, cfg)
    if kind == "slstm":
        return X.slstm_forward(p, h, cfg)
    if cfg.mla:
        return A.mla_forward(p, h, cfg, positions)
    return A.attn_forward(p, h, cfg, kind, positions)


def block_forward(params, x, cfg: ModelConfig, kind: str, positions,
                  moe_layer: bool) -> Tuple[jax.Array, jax.Array]:
    """Returns (x_out, aux_loss).

    Sharding choreography (active only under a mesh): the residual stream
    is constrained at block boundaries; "seq" shards over the model axis
    under Megatron-SP (``cfg.seq_shard`` — used by the inference-prefill
    path; for training, GSPMD turns the SP weight-gradient contraction
    into full f32 dW all-reduces and no manual gather placement we tried
    beats plain TP — EXPERIMENTS.md §Perf iterations 3-5).
    """
    aux = jnp.zeros((), jnp.float32)
    h = _apply_norm(params["norm1"], x, cfg)
    a = _mixer_forward(params["mixer"], h, cfg, kind, positions)
    if kind in ("mlstm", "slstm"):
        return nn.residual_add(x, _branch(a, cfg)), aux
    # manual TP (shard_map bodies): the row-sharded out-projection leaves a
    # partial sum — reduce before anything reads it. No-op otherwise.
    a = nn.tp_psum(a)
    a = checkpoint_name(a, "proj_out")
    if cfg.post_norm:
        a = _apply_norm(params["post_norm1"], a, cfg)
    h, x = _add_norm(params["norm2"], _branch(a, cfg), x, cfg)
    # both streams keep the block-boundary constraint the pre-fusion code
    # placed on the sum (h fed the MLP GEMMs from a constrained tensor)
    x = shard(x, "batch", "seq", "embed")
    h = shard(h, "batch", "seq", "embed")
    if moe_layer:
        f, aux = M.moe_forward(params["moe"], h, cfg)
    else:
        f = M.ffn_forward(params["ffn"], h, cfg)
    f = nn.tp_psum(f)
    f = checkpoint_name(f, "proj_out")
    if cfg.post_norm:
        f = _apply_norm(params["post_norm2"], f, cfg)
    x = nn.residual_add(x, _branch(f, cfg))
    return shard(x, "batch", "seq", "embed"), aux


def block_prefill(params, x, cfg: ModelConfig, kind: str, positions,
                  max_len: int, moe_layer: bool, lengths=None):
    """Like block_forward but also emits the decode cache for this block.

    ``lengths`` only matters to mixers whose cache layout depends on the
    true prompt end under right-padding (the local-attention ring buffer).
    Recurrent/xLSTM prefill carries a running state that consumes every
    input token, so those mixers are NOT pad-safe — callers must feed them
    exact-length prompts (the serving engine does).
    """
    aux = jnp.zeros((), jnp.float32)
    h = _apply_norm(params["norm1"], x, cfg)
    if kind == "rec":
        a, cache = R.recurrent_prefill(params["mixer"], h, cfg)
    elif kind == "mlstm":
        a, cache = X.mlstm_prefill(params["mixer"], h, cfg)
    elif kind == "slstm":
        a, cache = X.slstm_prefill(params["mixer"], h, cfg)
    elif cfg.mla:
        a, cache = A.mla_prefill(params["mixer"], h, cfg, positions, max_len)
    else:
        a, cache = A.attn_prefill(params["mixer"], h, cfg, kind, positions,
                                  max_len, lengths=lengths)
    if kind in ("mlstm", "slstm"):
        return nn.residual_add(x, _branch(a, cfg)), cache, aux
    a = nn.tp_psum(a)
    if cfg.post_norm:
        a = _apply_norm(params["post_norm1"], a, cfg)
    h, x = _add_norm(params["norm2"], _branch(a, cfg), x, cfg)
    x = shard(x, "batch", "seq", "embed")
    h = shard(h, "batch", "seq", "embed")
    if moe_layer:
        f, aux = M.moe_forward(params["moe"], h, cfg)
    else:
        f = M.ffn_forward(params["ffn"], h, cfg)
    f = nn.tp_psum(f)
    if cfg.post_norm:
        f = _apply_norm(params["post_norm2"], f, cfg)
    x = nn.residual_add(x, _branch(f, cfg))
    return shard(x, "batch", "seq", "embed"), cache, aux


def init_block_cache(cfg: ModelConfig, kind: str, batch: int, max_len: int):
    if kind == "rec":
        return R.init_recurrent_cache(cfg, batch)
    if kind == "mlstm":
        return X.init_mlstm_cache(cfg, batch)
    if kind == "slstm":
        return X.init_slstm_cache(cfg, batch)
    if cfg.mla:
        return A.init_mla_cache(cfg, batch, max_len)
    return A.init_attn_cache(cfg, kind, batch, max_len)


def block_decode(params, x, cfg: ModelConfig, kind: str, cache, pos,
                 moe_layer: bool):
    h = _apply_norm(params["norm1"], x, cfg)
    if kind == "rec":
        a, cache = R.recurrent_decode(params["mixer"], h, cfg, cache, pos)
    elif kind == "mlstm":
        a, cache = X.mlstm_decode(params["mixer"], h, cfg, cache, pos)
    elif kind == "slstm":
        a, cache = X.slstm_decode(params["mixer"], h, cfg, cache, pos)
    elif isinstance(cache, A.PagedKV):
        a, cache = A.attn_decode_paged(params["mixer"], h, cfg, cache, pos)
    elif cfg.mla:
        a, cache = A.mla_decode(params["mixer"], h, cfg, cache, pos)
    else:
        a, cache = A.attn_decode(params["mixer"], h, cfg, kind, cache, pos)
    if kind in ("mlstm", "slstm"):
        return nn.residual_add(x, _branch(a, cfg)), cache
    a = nn.tp_psum(a)
    if cfg.post_norm:
        a = _apply_norm(params["post_norm1"], a, cfg)
    h, x = _add_norm(params["norm2"], _branch(a, cfg), x, cfg)
    if moe_layer:
        f, _ = M.moe_forward(params["moe"], h, cfg)
    else:
        f = M.ffn_forward(params["ffn"], h, cfg)
    f = nn.tp_psum(f)
    if cfg.post_norm:
        f = _apply_norm(params["post_norm2"], f, cfg)
    return nn.residual_add(x, _branch(f, cfg)), cache


# ---------------------------------------------------------------------------
# layer stacking: leading (unstacked) layers + scan-stacked pattern repeats
# ---------------------------------------------------------------------------

def _layer_layout(cfg: ModelConfig):
    """-> (leading_kinds, pattern, n_rep, trailing_kinds).

    ``first_dense_layers`` MoE leaders are pulled out of the scan (their
    params have a different structure). The remainder is n_rep repeats of
    ``block_pattern`` plus a trailing partial pattern.
    """
    kinds = cfg.layer_kinds()
    lead = cfg.first_dense_layers if cfg.is_moe else 0
    rest = len(kinds) - lead
    p = len(cfg.block_pattern)
    n_rep = rest // p
    trail = rest - n_rep * p
    return (kinds[:lead], kinds[lead:lead + n_rep * p][:p], n_rep,
            kinds[len(kinds) - trail:] if trail else ())


def init_lm(key, cfg: ModelConfig) -> dict:
    lead, pattern, n_rep, trail = _layer_layout(cfg)
    keys = jax.random.split(key, cfg.n_layers + 3)
    pd = jnp.dtype(cfg.param_dtype)
    params: dict = {}
    li = 0
    if cfg.input_mode == "tokens":
        params["embed"] = dense_init(keys[-1], (cfg.vocab_size, cfg.d_model),
                                     in_axis=1, dtype=pd)
    if cfg.pos_emb == "learned":
        params["pos"] = dense_init(keys[-3], (cfg.max_position, cfg.d_model),
                                   in_axis=1, dtype=pd)
    params["lead"] = []
    for kind in lead:
        params["lead"].append(init_block(keys[li], cfg, kind, li))
        li += 1
    # one stacked tree per pattern position (scan_layers=False keeps the
    # per-layer trees separate — the eager-profiling layout: slicing a
    # stacked tree per layer is a Memory op no real eager framework pays)
    params["scan"] = []
    for j, kind in enumerate(pattern):
        per_rep = []
        for r in range(n_rep):
            per_rep.append(init_block(keys[li + r * len(pattern)], cfg, kind,
                                      li + r * len(pattern)))
        params["scan"].append(stack_trees(per_rep) if cfg.scan_layers
                              else per_rep)
    li += n_rep * len(pattern)
    params["trail"] = []
    for kind in trail:
        params["trail"].append(init_block(keys[li], cfg, kind, li))
        li += 1
    params["final_norm"] = _init_norm(cfg)
    if not cfg.tie_embeddings or cfg.input_mode != "tokens":
        params["head"] = dense_init(keys[-2], (cfg.d_model, cfg.vocab_size),
                                    dtype=pd)
    return params


def _moe_flags(cfg: ModelConfig):
    """Whether each (lead, pattern-position, trail) block is a MoE layer."""
    lead, pattern, n_rep, trail = _layer_layout(cfg)
    lead_f = [_is_moe_layer(cfg, i) for i in range(len(lead))]
    base = len(lead)
    pat_f = [_is_moe_layer(cfg, base + j) for j in range(len(pattern))]
    trail_base = base + n_rep * len(pattern)
    trail_f = [_is_moe_layer(cfg, trail_base + j) for j in range(len(trail))]
    return lead_f, pat_f, trail_f


def _remat(fn, cfg: ModelConfig):
    if not cfg.remat or cfg.remat_policy == "none":
        return fn
    if cfg.remat_policy == "dots":
        policy = jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims
        return jax.checkpoint(fn, policy=policy)
    if cfg.remat_policy == "proj":
        # save exactly the post-all-reduce projection outputs (attention
        # out-proj, FFN down-proj): the backward then never re-runs the
        # forward's TP all-reduces — 1/3 of the train-cell collective
        # bytes for +2 d_model-sized saves per layer (§Perf iteration 9)
        policy = jax.checkpoint_policies.save_only_these_names("proj_out")
        return jax.checkpoint(fn, policy=policy)
    return jax.checkpoint(fn)


def forward_hidden(params, x, cfg: ModelConfig, positions) -> Tuple[jax.Array, jax.Array]:
    """Run all blocks. x: (B, S, D) -> (hidden, aux_loss)."""
    lead, pattern, n_rep, trail = _layer_layout(cfg)
    lead_f, pat_f, trail_f = _moe_flags(cfg)
    aux = jnp.zeros((), jnp.float32)

    for p, kind, mf in zip(params["lead"], lead, lead_f):
        x, a = _remat(partial(block_forward, cfg=cfg, kind=kind,
                              positions=positions, moe_layer=mf), cfg)(p, x)
        aux += a

    if n_rep and cfg.scan_layers:
        def body(carry, sliced):
            x, aux = carry
            for j, kind in enumerate(pattern):
                x, a = block_forward(sliced[j], x, cfg, kind, positions,
                                     pat_f[j])
                aux += a
            return (x, aux), None

        (x, aux), _ = jax.lax.scan(_remat(body, cfg), (x, aux),
                                   tuple(params["scan"]))
    elif n_rep:
        # unrolled path: per-op visibility for the profiling views
        from repro.models.common import tree_slice
        for r in range(n_rep):
            for j, kind in enumerate(pattern):
                p = params["scan"][j]
                p = p[r] if isinstance(p, list) else tree_slice(p, r)
                x, a = block_forward(p, x, cfg, kind, positions, pat_f[j])
                aux += a

    for p, kind, mf in zip(params["trail"], trail, trail_f):
        x, a = _remat(partial(block_forward, cfg=cfg, kind=kind,
                              positions=positions, moe_layer=mf), cfg)(p, x)
        aux += a

    return _apply_norm(params["final_norm"], x, cfg), aux


# ---------------------------------------------------------------------------
# embeddings in / logits out
# ---------------------------------------------------------------------------

def embed_inputs(params, inputs, cfg: ModelConfig, positions):
    """Tokens (B, S) int32 -> (B, S, D); or pass-through frame embeddings."""
    if cfg.input_mode == "tokens":
        x = nn.embedding_lookup(params["embed"], inputs)
        x = x.astype(cfg.activation_dtype)
    else:  # precomputed modality-frontend embeddings (musicgen stub)
        x = inputs.astype(cfg.activation_dtype)
    x = _multiply(x, cfg.embedding_multiplier, "embedding_multiplier")
    if cfg.scale_embeddings:
        x = nn.scale(x, jnp.asarray(math.sqrt(cfg.d_model), x.dtype))
    return _add_positional(x, positions, params, cfg)


def logits_from_hidden(params, h, cfg: ModelConfig):
    if "head" in params:
        logits = nn.linear(h, params["head"].astype(h.dtype))
        # manual TP with a vocab-sharded head: gather the logit slices
        # (bit-exact — column-sharded GEMM). No-op everywhere else.
        logits = nn.tp_vocab_gather(logits)
    else:
        # tied head: contract against the embedding table directly — an
        # explicit .T materializes a vocab x d copy every forward
        logits = nn.einsum("...d,vd->...v", h,
                           params["embed"].astype(h.dtype))
    if cfg.logits_scaling is not None:
        logits = _multiply(logits, 1.0 / cfg.logits_scaling, "logits_scaling")
    if cfg.final_logit_softcap:
        c = cfg.final_logit_softcap
        logits = jnp.tanh(logits / c) * c
    return logits


def _default_positions(inputs, cfg: ModelConfig):
    b = inputs.shape[0]
    s = inputs.shape[1]
    return jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None], (b, s))


def lm_forward(params, inputs, cfg: ModelConfig, positions=None):
    """Full-sequence logits (small-model / smoke-test path)."""
    positions = _default_positions(inputs, cfg) if positions is None else positions
    x = embed_inputs(params, inputs, cfg, positions)
    h, _ = forward_hidden(params, x, cfg, positions)
    return logits_from_hidden(params, h, cfg)


# ---------------------------------------------------------------------------
# loss (sequence-chunked: never materializes (B, S, V) beyond a chunk)
# ---------------------------------------------------------------------------

def lm_loss(params, batch: dict, cfg: ModelConfig) -> Tuple[jax.Array, dict]:
    """batch: {"inputs": (B,S) or (B,S,D), "labels": (B,S) int32}.

    Positions with label < 0 are masked out of the loss.
    """
    inputs, labels = batch["inputs"], batch["labels"]
    positions = batch.get("positions")
    if positions is None:
        positions = _default_positions(inputs, cfg)
    x = embed_inputs(params, inputs, cfg, positions)
    h, aux = forward_hidden(params, x, cfg, positions)

    mask = (labels >= 0).astype(jnp.float32)
    safe_labels = jnp.maximum(labels, 0)
    b, s = labels.shape
    chunk = cfg.loss_chunk if cfg.loss_chunk else s

    if chunk >= s:
        logits = logits_from_hidden(params, h, cfg)
        ce = nn.softmax_cross_entropy(logits, safe_labels)
        tot = jnp.sum(ce * mask)
    else:
        nchunk = -(-s // chunk)
        pad = nchunk * chunk - s
        if pad:
            h = jnp.pad(h, ((0, 0), (0, pad), (0, 0)))
            safe_labels = jnp.pad(safe_labels, ((0, 0), (0, pad)))
            mask = jnp.pad(mask, ((0, 0), (0, pad)))
        hc = h.reshape(b, nchunk, chunk, -1).swapaxes(0, 1)
        lc = safe_labels.reshape(b, nchunk, chunk).swapaxes(0, 1)
        mc = mask.reshape(b, nchunk, chunk).swapaxes(0, 1)

        def chunk_ce(carry, xs):
            hj, lj, mj = xs
            logits = logits_from_hidden(params, hj, cfg)
            ce = nn.softmax_cross_entropy(logits, lj)
            return carry + jnp.sum(ce * mj), None

        tot, _ = jax.lax.scan(_remat(chunk_ce, cfg), jnp.zeros((), jnp.float32),
                              (hc, lc, mc))

    n = jnp.maximum(jnp.sum(mask), 1.0)
    loss = tot / n + aux
    return loss, {"ce": tot / n, "aux": aux, "tokens": n}


# ---------------------------------------------------------------------------
# decode path
# ---------------------------------------------------------------------------

def init_lm_cache(cfg: ModelConfig, batch: int, max_len: int) -> dict:
    lead, pattern, n_rep, trail = _layer_layout(cfg)
    caches = {
        "lead": [init_block_cache(cfg, k, batch, max_len) for k in lead],
        "scan": [stack_trees([init_block_cache(cfg, k, batch, max_len)
                              for _ in range(n_rep)]) for k in pattern],
        "trail": [init_block_cache(cfg, k, batch, max_len) for k in trail],
    }
    return caches


def lm_prefill(params, inputs, cfg: ModelConfig, max_len: int,
               positions=None, lengths=None):
    """Process the prompt; return (logits_last (B, V), caches).

    ``lengths`` (B,) int32, optional: true prompt length per row of a
    right-padded batch. The returned logits are read at position
    ``lengths - 1`` (the last real token) instead of the pad tail; with a
    causal mask, right-padding guarantees no real token ever attends a pad
    (pads only occupy *later* positions). Full/MLA attention caches need
    no further masking (decode's per-row ``arange <= pos`` hides stale pad
    KV until each slot is overwritten in place); sliding-window layers
    fill their ring buffer from the true prompt tail (see
    ``attn_prefill``). Recurrent/xLSTM mixers are NOT pad-safe — their
    prefill state consumes every token, pads included — so callers must
    give them exact-length prompts (the serving engine detects this and
    disables prompt bucketing).
    """
    lead, pattern, n_rep, trail = _layer_layout(cfg)
    lead_f, pat_f, trail_f = _moe_flags(cfg)
    positions = _default_positions(inputs, cfg) if positions is None else positions
    x = embed_inputs(params, inputs, cfg, positions)

    caches = {"lead": [], "scan": [], "trail": []}
    for p, kind, mf in zip(params["lead"], lead, lead_f):
        x, c, _ = block_prefill(p, x, cfg, kind, positions, max_len, mf,
                                lengths=lengths)
        caches["lead"].append(c)

    if n_rep:
        def body(x, sliced):
            cs = []
            for j, kind in enumerate(pattern):
                x, c, _ = block_prefill(sliced[j], x, cfg, kind, positions,
                                        max_len, pat_f[j], lengths=lengths)
                cs.append(c)
            return x, tuple(cs)

        x, scan_caches = jax.lax.scan(_remat(body, cfg), x,
                                      tuple(params["scan"]))
        caches["scan"] = list(scan_caches)

    for p, kind, mf in zip(params["trail"], trail, trail_f):
        x, c, _ = block_prefill(p, x, cfg, kind, positions, max_len, mf,
                                lengths=lengths)
        caches["trail"].append(c)

    h = _apply_norm(params["final_norm"], x, cfg)
    if lengths is None:
        h_last = h[:, -1:]
    else:
        idx = jnp.asarray(lengths, jnp.int32).reshape(-1) - 1
        h_last = jnp.take_along_axis(h, idx[:, None, None], axis=1)
    logits = logits_from_hidden(params, h_last, cfg)[:, 0]
    return logits, caches


def lm_decode(params, token, pos, caches, cfg: ModelConfig, tables=None):
    """One decode step.

    token: (B,) int32 (or (B, D) frame embedding for input_mode=embeddings);
    pos: scalar int32 (lockstep batch) or (B,) int32 per-slot absolute
    positions (continuous batching). Returns (logits (B, V), new_caches).

    With ``tables`` (B, nb) int32, ``caches`` are paged block pools of
    plain attention layers (``serving.paged.init_pools``): the layer scan
    carries the stacked pools instead of slicing them, and each layer
    writes its new rows and reads its live blocks in place
    (``attention.attn_decode_paged``).
    """
    lead, pattern, n_rep, trail = _layer_layout(cfg)
    lead_f, pat_f, trail_f = _moe_flags(cfg)
    b = token.shape[0]
    pos = A.pos_vector(pos, b)
    positions = pos[:, None]
    inputs = token[:, None] if cfg.input_mode == "tokens" else token[:, None, :]
    x = embed_inputs(params, inputs, cfg, positions)

    def view(c, layer=None):
        if tables is None:
            return c
        return A.PagedKV(c["k"], c["v"], tables, layer)

    new_caches = {"lead": [], "scan": [], "trail": []}
    for p, kind, mf, c in zip(params["lead"], lead, lead_f, caches["lead"]):
        x, c = block_decode(p, x, cfg, kind, view(c), pos, mf)
        new_caches["lead"].append(c)

    if n_rep and tables is not None:
        def paged_body(carry, ps):
            x, cs, layer = carry
            new_cs = []
            for j, kind in enumerate(pattern):
                x, c = block_decode(ps[j], x, cfg, kind, view(cs[j], layer),
                                    pos, pat_f[j])
                new_cs.append(c)
            return (x, tuple(new_cs), layer + 1), None

        (x, scan_caches, _), _ = jax.lax.scan(
            paged_body, (x, tuple(caches["scan"]), jnp.int32(0)),
            tuple(params["scan"]))
        new_caches["scan"] = list(scan_caches)
    elif n_rep:
        def body(x, sliced):
            ps, cs = sliced
            new_cs = []
            for j, kind in enumerate(pattern):
                x, c = block_decode(ps[j], x, cfg, kind, cs[j], pos, pat_f[j])
                new_cs.append(c)
            return x, tuple(new_cs)

        x, scan_caches = jax.lax.scan(
            body, x, (tuple(params["scan"]), tuple(caches["scan"])))
        new_caches["scan"] = list(scan_caches)

    for p, kind, mf, c in zip(params["trail"], trail, trail_f,
                              caches["trail"]):
        x, c = block_decode(p, x, cfg, kind, view(c), pos, mf)
        new_caches["trail"].append(c)

    h = _apply_norm(params["final_norm"], x, cfg)
    return logits_from_hidden(params, h, cfg)[:, 0], new_caches


def block_extend(params, x, cfg: ModelConfig, kind: str, cache, start,
                 moe_layer: bool):
    """Chunked-prefill step for one block (see ``A.attn_extend``).

    Only full-depth caches can re-enter at an arbitrary position —
    recurrent/xLSTM state and ring buffers cannot, so those kinds refuse.
    """
    if kind in ("rec", "mlstm", "slstm", "local"):
        raise ValueError(f"block kind {kind!r} does not support chunked "
                         "prefill (needs a full-depth positional cache)")
    h = _apply_norm(params["norm1"], x, cfg)
    if cfg.mla:
        a, cache = A.mla_extend(params["mixer"], h, cfg, cache, start)
    else:
        a, cache = A.attn_extend(params["mixer"], h, cfg, kind, cache, start)
    a = nn.tp_psum(a)
    if cfg.post_norm:
        a = _apply_norm(params["post_norm1"], a, cfg)
    h, x = _add_norm(params["norm2"], _branch(a, cfg), x, cfg)
    if moe_layer:
        f, _ = M.moe_forward(params["moe"], h, cfg)
    else:
        f = M.ffn_forward(params["ffn"], h, cfg)
    f = nn.tp_psum(f)
    if cfg.post_norm:
        f = _apply_norm(params["post_norm2"], f, cfg)
    return nn.residual_add(x, _branch(f, cfg)), cache


def lm_extend(params, tokens, start, caches, cfg: ModelConfig):
    """Chunked-prefill step: run a (B, C) token chunk at absolute position
    ``start`` (traced scalar) against caches already holding [0, start).

    The decode-path twin of ``lm_prefill`` for a mid-sequence chunk:
    returns (logits (B, C, V), new_caches) — the caller picks the row of
    the prompt's last real token (chunks may be right-padded to a bucket).
    """
    lead, pattern, n_rep, trail = _layer_layout(cfg)
    lead_f, pat_f, trail_f = _moe_flags(cfg)
    b, c_len = tokens.shape[:2]
    start = jnp.asarray(start, jnp.int32)
    positions = jnp.broadcast_to(
        start + jnp.arange(c_len, dtype=jnp.int32)[None, :], (b, c_len))
    x = embed_inputs(params, tokens, cfg, positions)

    new_caches = {"lead": [], "scan": [], "trail": []}
    for p, kind, mf, c in zip(params["lead"], lead, lead_f, caches["lead"]):
        x, c = block_extend(p, x, cfg, kind, c, start, mf)
        new_caches["lead"].append(c)

    if n_rep:
        def body(x, sliced):
            ps, cs = sliced
            new_cs = []
            for j, kind in enumerate(pattern):
                x, c = block_extend(ps[j], x, cfg, kind, cs[j], start,
                                    pat_f[j])
                new_cs.append(c)
            return x, tuple(new_cs)

        x, scan_caches = jax.lax.scan(
            body, x, (tuple(params["scan"]), tuple(caches["scan"])))
        new_caches["scan"] = list(scan_caches)

    for p, kind, mf, c in zip(params["trail"], trail, trail_f,
                              caches["trail"]):
        x, c = block_extend(p, x, cfg, kind, c, start, mf)
        new_caches["trail"].append(c)

    h = _apply_norm(params["final_norm"], x, cfg)
    return logits_from_hidden(params, h, cfg), new_caches
