"""Attention layer tests: flash jnp twin (fwd+VJP), decode vs prefill
consistency, MLA absorbed decode, window ring buffer."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduced
from repro.kernels import ref
from repro.models import attention as A
from repro.models.common import ModelConfig


def mkcfg(**kw):
    base = dict(name="t", n_layers=2, d_model=64, n_heads=4, n_kv_heads=2,
                d_ff=128, vocab_size=64, dtype="float32",
                param_dtype="float32", attn_chunk_q=16, attn_chunk_kv=16,
                remat=False)
    base.update(kw)
    return ModelConfig(**base)


@pytest.mark.parametrize("causal,window", [(True, None), (True, 12),
                                           (False, None)])
def test_flash_jnp_forward(causal, window, rng):
    ks = jax.random.split(rng, 3)
    q = jax.random.normal(ks[0], (2, 50, 8, 16))
    k = jax.random.normal(ks[1], (2, 50, 4, 16))
    v = jax.random.normal(ks[2], (2, 50, 4, 16))
    got = A.flash_attention_jnp(q, k, v, causal=causal, window=window,
                                chunk_q=16, chunk_kv=16)
    want = ref.attention(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


@pytest.mark.parametrize("scale", [None, 1 / 128])
def test_flash_jnp_vjp_matches_naive(rng, scale):
    """Forward and custom backward both apply the scale (default
    1/sqrt(Dh); granite's attention_multiplier 1/128)."""
    ks = jax.random.split(rng, 3)
    q = jax.random.normal(ks[0], (1, 40, 4, 16))
    k = jax.random.normal(ks[1], (1, 40, 2, 16))
    v = jax.random.normal(ks[2], (1, 40, 2, 16))

    def lf(q, k, v):
        return jnp.sum(jnp.cos(A.flash_attention_jnp(
            q, k, v, causal=True, window=8, chunk_q=16, chunk_kv=8,
            scale=scale)))

    def lr(q, k, v):
        return jnp.sum(jnp.cos(ref.attention(q, k, v, causal=True, window=8,
                                             scale=scale)))

    g1 = jax.grad(lf, (0, 1, 2))(q, k, v)
    g2 = jax.grad(lr, (0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-4)


def test_chunked_matches_flash(rng):
    ks = jax.random.split(rng, 3)
    q = jax.random.normal(ks[0], (2, 30, 4, 16))
    k = jax.random.normal(ks[1], (2, 30, 4, 16))
    v = jax.random.normal(ks[2], (2, 30, 4, 16))
    a = A.chunked_attention(q, k, v, causal=True, chunk_q=8, chunk_kv=8)
    b = A.flash_attention_jnp(q, k, v, causal=True, chunk_q=16, chunk_kv=16)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5)


@pytest.mark.parametrize("kind", ["attn", "local"])
def test_decode_matches_full_forward(kind, rng):
    """Prefill S tokens then decode token S: logits must equal running the
    full (S+1)-token forward — the KV-cache correctness invariant."""
    cfg = mkcfg(window_size=8 if kind == "local" else 1024)
    params = A.init_attention(jax.random.PRNGKey(1), cfg)
    s = 12
    x = jax.random.normal(rng, (2, s + 1, cfg.d_model))
    pos = jnp.arange(s + 1)[None].repeat(2, 0)

    full = A.attn_forward(params, x, cfg, kind, pos)
    y_pre, cache = A.attn_prefill(params, x[:, :s], cfg, kind, pos[:, :s],
                                  max_len=s + 4)
    np.testing.assert_allclose(np.asarray(y_pre), np.asarray(full[:, :s]),
                               atol=1e-4)
    y_dec, _ = A.attn_decode(params, x[:, s:s + 1], cfg, kind, cache,
                             jnp.int32(s))
    np.testing.assert_allclose(np.asarray(y_dec), np.asarray(full[:, s:s + 1]),
                               atol=1e-4)


def test_decode_sequence_matches_forward(rng):
    """Decode 5 tokens one by one == full forward on the suffix."""
    cfg = mkcfg()
    params = A.init_attention(jax.random.PRNGKey(1), cfg)
    total = 16
    x = jax.random.normal(rng, (1, total, cfg.d_model))
    pos = jnp.arange(total)[None]
    full = A.attn_forward(params, x, cfg, "attn", pos)
    prefill_len = 11
    _, cache = A.attn_prefill(params, x[:, :prefill_len], cfg, "attn",
                              pos[:, :prefill_len], max_len=total)
    for t in range(prefill_len, total):
        y, cache = A.attn_decode(params, x[:, t:t + 1], cfg, "attn", cache,
                                 jnp.int32(t))
        np.testing.assert_allclose(np.asarray(y),
                                   np.asarray(full[:, t:t + 1]), atol=1e-4)


def test_mla_decode_matches_forward(rng):
    cfg = reduced(get_config("deepseek-v2-lite-16b")).replace(
        dtype="float32", param_dtype="float32")
    params = A.init_mla(jax.random.PRNGKey(1), cfg)
    s = 10
    x = jax.random.normal(rng, (2, s + 1, cfg.d_model))
    pos = jnp.arange(s + 1)[None].repeat(2, 0)
    full = A.mla_forward(params, x, cfg, pos)
    _, cache = A.mla_prefill(params, x[:, :s], cfg, pos[:, :s], max_len=s + 2)
    y, _ = A.mla_decode(params, x[:, s:s + 1], cfg, cache, jnp.int32(s))
    np.testing.assert_allclose(np.asarray(y), np.asarray(full[:, s:s + 1]),
                               atol=2e-4)


def test_partial_rope_fraction(rng):
    """stablelm-style 25% rotary: pass-through dims must be unrotated."""
    from repro import nn
    x = jax.random.normal(rng, (1, 6, 2, 32))
    pos = jnp.arange(6)[None]
    y = nn.apply_rope(x, pos, fraction=0.25)
    rot = int(32 * 0.25) // 2 * 2
    np.testing.assert_allclose(np.asarray(y[..., rot:]),
                               np.asarray(x[..., rot:]), atol=1e-6)
    assert not np.allclose(np.asarray(y[..., :rot]), np.asarray(x[..., :rot]))
