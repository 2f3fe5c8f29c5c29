"""Plain reference of a dense decoder-only transformer, and its costs.

Follows the public description of the configurations that name it
(``"reference": "dense_decoder"``): pre-norm blocks of LayerNorm or RMSNorm,
multi-head or grouped-query attention with rotary embedding on a leading
fraction of each head (rotate-half), a SwiGLU feed-forward, untied or tied
output head, and the scalar multipliers of the public config. Everything is
float32 at ``Precision.HIGHEST``; the served bf16 weights are read as they
are and widened. It imports nothing of the program: it reads the
configuration file and the benchmark's own weights.

The cost functions count what the algorithm needs from the shapes alone:
model FLOPs (2 per multiply-add, causal attention over the rows attended)
and the least bytes a step must move in the served dtype.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
DTYPE_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


def _mm(x, w):
    return jnp.einsum("...d,df->...f", x, w.astype(jnp.float32),
                      precision=HIGHEST)


def _norm(conf, p, x):
    if conf["norm"] == "layernorm":
        mu = x.mean(-1, keepdims=True)
        var = jnp.square(x - mu).mean(-1, keepdims=True)
        y = (x - mu) / jnp.sqrt(var + conf["layer_norm_eps"])
        return y * p["scale"].astype(jnp.float32) \
            + p["bias"].astype(jnp.float32)
    ms = jnp.square(x).mean(-1, keepdims=True)
    return x / jnp.sqrt(ms + conf["rms_norm_eps"]) \
        * p["scale"].astype(jnp.float32)


def _rope(conf, x, positions):
    """Rotate-half rotary embedding on the leading fraction of each head."""
    hd = x.shape[-1]
    rot = int(hd * conf["partial_rotary_factor"]) // 2 * 2
    inv = 1.0 / conf["rope_theta"] ** (np.arange(0, rot, 2) / rot)
    ang = positions[:, None].astype(jnp.float32) * jnp.asarray(inv, jnp.float32)
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[:, None, :]
    xr, xp = x[..., :rot], x[..., rot:]
    half = jnp.concatenate([-xr[..., rot // 2:], xr[..., :rot // 2]], -1)
    return jnp.concatenate([xr * cos + half * sin, xp], -1)


def _block(conf, x, lp):
    """One decoder layer over a (S, D) sequence."""
    s = x.shape[0]
    hq, hkv, hd = (conf["num_attention_heads"], conf["num_key_value_heads"],
                   conf["head_dim"])
    pos = jnp.arange(s)
    h = _norm(conf, lp["norm1"], x)
    at = lp["mixer"]
    q = _rope(conf, _mm(h, at["wq"]).reshape(s, hq, hd), pos)
    k = _rope(conf, _mm(h, at["wk"]).reshape(s, hkv, hd), pos)
    v = _mm(h, at["wv"]).reshape(s, hkv, hd)
    k = jnp.repeat(k, hq // hkv, axis=1)
    v = jnp.repeat(v, hq // hkv, axis=1)
    sc = jnp.einsum("qhd,khd->hqk", q, k, precision=HIGHEST) \
        * conf["attention_multiplier"]
    sc = jnp.where(pos[None, :, None] >= pos[None, None, :], sc, -jnp.inf)
    o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(sc, -1), v,
                   precision=HIGHEST).reshape(s, hq * hd)
    x = x + conf["residual_multiplier"] * _mm(o, at["wo"])
    h = _norm(conf, lp["norm2"], x)
    f = lp["ffn"]
    g = _mm(h, f["w_gate"])
    x = x + conf["residual_multiplier"] * _mm(jax.nn.silu(g) * _mm(h, f["w_up"]),
                                              f["w_down"])
    return x


def _layers(params):
    """The weight tree's one group of layers, stacked on a leading axis."""
    if params["lead"] or params["trail"] or len(params["scan"]) != 1:
        raise ValueError("dense_decoder reads one stacked layer group")
    return params["scan"][0]


@partial(jax.jit, static_argnums=0)
def _logits(conf_items, params, tokens):
    """(S,) tokens -> (S, vocab) logits, each position's next-token scores."""
    conf = dict(conf_items)
    x = params["embed"][tokens].astype(jnp.float32) \
        * conf["embedding_multiplier"]

    def body(x, lp):
        return _block(conf, x, lp), None

    x, _ = jax.lax.scan(body, x, _layers(params))
    h = _norm(conf, params["final_norm"], x)
    if conf["tie_word_embeddings"]:
        logits = jnp.einsum("sd,vd->sv", h,
                            params["embed"].astype(jnp.float32),
                            precision=HIGHEST)
    else:
        logits = _mm(h, params["head"])
    return logits / conf["logits_scaling"]


@partial(jax.jit, static_argnums=0)
def _gaps(conf_items, params, tokens, targets):
    """(S,) tokens -> per position, best logit - logit of the target."""
    logits = _logits(conf_items, params, tokens)
    best = logits.max(-1)
    got = jnp.take_along_axis(logits, targets[:, None], -1)[:, 0]
    return best - got


_READS = ("norm", "layer_norm_eps", "rms_norm_eps", "partial_rotary_factor",
          "rope_theta", "num_attention_heads", "num_key_value_heads",
          "head_dim", "attention_multiplier", "residual_multiplier",
          "embedding_multiplier", "logits_scaling", "tie_word_embeddings")


def conf_items(conf: dict) -> tuple:
    """The keys of ``conf`` the forward pass reads, hashable for ``jit``."""
    return tuple((k, conf.get(k)) for k in _READS)


def served_gaps(conf: dict, params, prompt, served, pad_to: int) -> np.ndarray:
    """How far below the reference's best each served token's logit lies.

    One forward pass over ``prompt + served`` (padded to ``pad_to`` so that
    every request shares one program; causal attention leaves the real
    positions untouched by the padding). Entry ``k`` is the gap of
    ``served[k]``, read at the position that predicts it.
    """
    seq = list(prompt) + list(served)
    if len(seq) > pad_to + 1:
        raise ValueError(f"sequence of {len(seq)} past {pad_to + 1}")
    toks = np.zeros(pad_to, np.int32)
    toks[:len(seq) - 1] = seq[:-1]
    tgt = np.zeros(pad_to, np.int32)
    tgt[:len(seq) - 1] = seq[1:]
    gaps = _gaps(conf_items(conf), params, jnp.asarray(toks),
                 jnp.asarray(tgt))
    lo = len(prompt) - 1
    return np.asarray(gaps)[lo:lo + len(served)]


# ---------------------------------------------------------------------------
# costs from the shapes
# ---------------------------------------------------------------------------

def _layer_matmul_params(conf) -> int:
    d, ff = conf["hidden_size"], conf["intermediate_size"]
    hq, hkv, hd = (conf["num_attention_heads"], conf["num_key_value_heads"],
                   conf["head_dim"])
    return d * hq * hd + 2 * d * hkv * hd + hq * hd * d + 3 * d * ff


def matmul_params(conf) -> int:
    """Weights every token multiplies: the layers and the output head."""
    return conf["num_hidden_layers"] * _layer_matmul_params(conf) \
        + conf["hidden_size"] * conf["vocab_size"]


def weight_bytes(conf) -> int:
    """Bytes of every weight a decode step reads once (norms included)."""
    d, nl = conf["hidden_size"], conf["num_hidden_layers"]
    norm = (2 if conf["norm"] == "layernorm" else 1) * d
    b = DTYPE_BYTES[conf["torch_dtype"]]
    return b * (matmul_params(conf) + nl * 2 * norm + norm)


def _attn_flops(conf, rows: int) -> int:
    """qk and pv of one query over ``rows`` keys, all layers."""
    return conf["num_hidden_layers"] * 4 * conf["num_attention_heads"] \
        * conf["head_dim"] * rows


def _kv_row_bytes(conf) -> int:
    """K and V of one position, all layers."""
    return conf["num_hidden_layers"] * 2 * conf["num_key_value_heads"] \
        * conf["head_dim"] * DTYPE_BYTES[conf["torch_dtype"]]


def decode_token_flops(conf, rows: int) -> int:
    """One decoded token attending ``rows`` cached positions (itself too)."""
    return 2 * matmul_params(conf) + _attn_flops(conf, rows)


def prompt_flops(conf, plen: int) -> int:
    """A whole prompt: every position through the layers, causal attention,
    and the output head at the last position only."""
    d, v = conf["hidden_size"], conf["vocab_size"]
    layers = 2 * conf["num_hidden_layers"] * _layer_matmul_params(conf)
    return plen * layers + 2 * d * v \
        + _attn_flops(conf, plen * (plen + 1) // 2)


def decode_step_cost(conf, rows) -> tuple:
    """(FLOPs, least bytes) of one batched decode step over live slots
    attending ``rows[i]`` positions each: weights once, each slot's K/V up
    to its position, its new K/V row written, its embedding row."""
    b = DTYPE_BYTES[conf["torch_dtype"]]
    flops = sum(decode_token_flops(conf, n) for n in rows)
    nbytes = weight_bytes(conf) + sum(
        _kv_row_bytes(conf) * (n + 1) + b * conf["hidden_size"] for n in rows)
    return flops, nbytes


def decode_attention_cost(conf, rows) -> tuple:
    """(FLOPs, least bytes) of the decode attention of one step: each live
    K/V row read once per KV head (not once per query head), the query read
    and the output written once per query head, every layer."""
    b = DTYPE_BYTES[conf["torch_dtype"]]
    qo = conf["num_hidden_layers"] * 2 * conf["num_attention_heads"] \
        * conf["head_dim"] * b
    flops = sum(_attn_flops(conf, n) for n in rows)
    nbytes = sum(_kv_row_bytes(conf) * n + qo for n in rows)
    return flops, nbytes


def check_config(conf: dict) -> None:
    """Refuse a configuration this reference does not describe."""
    need = {"hidden_act": "silu"}
    bad = {k: conf.get(k) for k, v in need.items() if conf.get(k) != v}
    if conf["num_attention_heads"] % conf["num_key_value_heads"]:
        bad["num_key_value_heads"] = conf["num_key_value_heads"]
    if conf["norm"] not in ("layernorm", "rmsnorm"):
        bad["norm"] = conf["norm"]
    if bad:
        raise SystemExit(f"dense_decoder reference does not cover {bad}")
