"""attn_template parity sweep: every instantiated spec vs the ref oracle.

Covers the four mask fragments (causal / window / full-cross / decode-1q),
odd sequence lengths, GQA groups, dv != dk, softcap, the RoPE fragment,
the fully-masked-row epilogue guard, the explicit-interpret contract,
the NG005 registration cross-check, and model-level routing
(attn_decode / mla_decode / detector query refinement) across backends.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import nn
from repro.configs import get_config, reduced
from repro.kernels import attn_template as T
from repro.kernels import ops, ref
from repro.models import attention as A
from repro.models.common import ModelConfig


def _rand(key, shape, dt=jnp.float32):
    return jax.random.normal(key, shape, jnp.float32).astype(dt)


def _qkv(rng, b, sq, skv, hq, hkv, dk, dv=None, dt=jnp.float32):
    ks = jax.random.split(rng, 3)
    return (_rand(ks[0], (b, sq, hq, dk), dt),
            _rand(ks[1], (b, skv, hkv, dk), dt),
            _rand(ks[2], (b, skv, hkv, dv or dk), dt))


def mkcfg(**kw):
    base = dict(name="t", n_layers=2, d_model=64, n_heads=4, n_kv_heads=2,
                d_ff=128, vocab_size=64, dtype="float32",
                param_dtype="float32", attn_chunk_q=16, attn_chunk_kv=16,
                remat=False)
    base.update(kw)
    return ModelConfig(**base)


# ---------------------------------------------------------------------------
# registration (satellite: auto-registration at spec-instantiation time)
# ---------------------------------------------------------------------------

def test_builtin_specs_registered():
    names = {s.name for s in T.instantiated_specs()}
    for spec in T.BUILTIN_SPECS:
        assert spec.name in names
        assert T.kernel_key(spec) in ops.KERNEL_SPECS
        ks = ops.KERNEL_SPECS[T.kernel_key(spec)]
        assert ks.handles_remainder in ("pad", "clamp")
        assert all(v > 0 for v in ks.block_defaults.values())


def test_unregistered_spec_flagged_by_nglint():
    from repro.analysis import get_rule, run_static_rules

    spec = T.AttnSpec(name="ghost_variant", mask="full")
    T.make_attention(spec, register=False)
    try:
        findings = run_static_rules(rules=[get_rule("NG005")])
        assert any("ghost_variant" in f.where for f in findings)
    finally:
        T.forget("ghost_variant")
    assert run_static_rules(rules=[get_rule("NG005")]) == []


def test_spec_validation():
    with pytest.raises(ValueError):
        T.AttnSpec(name="bad", mask="diagonal")
    with pytest.raises(ValueError):
        T.AttnSpec(name="bad", mask="window", window=-3)
    pinned = T.make_attention(
        T.AttnSpec(name="pinned_d", mask="full", head_dim=64),
        register=False)
    try:
        q, k, v = _qkv(jax.random.PRNGKey(0), 1, 8, 8, 2, 2, 32)
        with pytest.raises(ValueError):
            pinned(q, k, v, interpret=True)
    finally:
        T.forget("pinned_d")
    win = T.get("window")
    q, k, v = _qkv(jax.random.PRNGKey(0), 1, 8, 8, 2, 2, 32)
    with pytest.raises(ValueError):
        win(q, k, v, window=None, interpret=True)


# ---------------------------------------------------------------------------
# parity sweep: instantiated specs vs the ref oracle
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("hq,hkv", [(8, 8), (8, 4), (8, 1)])
@pytest.mark.parametrize("sq", [64, 67])
def test_causal_spec_sweep(hq, hkv, sq, rng):
    q, k, v = _qkv(rng, 2, sq, sq, hq, hkv, 32)
    got = T.get("causal")(q, k, v, block_q=32, block_k=32, interpret=True)
    want = ref.attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=3e-5)


@pytest.mark.parametrize("window", [8, 16, 128])
def test_window_spec_sweep(window, rng):
    q, k, v = _qkv(rng, 2, 67, 67, 4, 2, 32)
    got = T.get("window")(q, k, v, window=window, block_q=32, block_k=32,
                          interpret=True)
    want = ref.attention(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=3e-5)


@pytest.mark.parametrize("sq,skv", [(13, 67), (67, 13), (1, 40)])
def test_full_spec_cross_attention(sq, skv, rng):
    # detector-style cross attention: query and KV streams of different
    # lengths, no causal structure
    q, k, v = _qkv(rng, 2, sq, skv, 4, 2, 32)
    got = T.get("full")(q, k, v, block_q=32, block_k=32, interpret=True)
    want = ref.attention(q, k, v, causal=False)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=3e-5)


@pytest.mark.parametrize("mask", ["causal", "full"])
def test_spec_dv_neq_dk(mask, rng):
    # MLA shapes: latent values narrower than the (nope+rope) keys
    q, k, v = _qkv(rng, 2, 35, 35, 4, 4, 48, dv=16)
    got = T.get(mask)(q, k, v, block_q=32, block_k=32, interpret=True)
    want = ref.attention(q, k, v, causal=(mask == "causal"))
    assert got.shape == (2, 35, 4, 16)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=3e-5)


@pytest.mark.parametrize("hq,hkv", [(4, 4), (8, 2)])
def test_decode_spec_lengths(hq, hkv, rng):
    q, k, v = _qkv(rng, 4, 1, 40, hq, hkv, 32)
    lengths = jnp.asarray([1, 17, 40, 5], jnp.int32)
    got = T.get("decode")(q, k, v, lengths, interpret=True)
    want = ref.attention(q, k, v, causal=False, lengths=lengths)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=3e-5)


def test_decode_spec_custom_scale_and_softcap(rng):
    q, k, v = _qkv(rng, 2, 1, 24, 4, 1, 32, dv=16)
    lengths = jnp.asarray([10, 24], jnp.int32)
    got = T.get("decode")(q, k, v, lengths, scale=0.25, softcap=20.0,
                          interpret=True)
    want = ref.attention(q, k, v, causal=False, lengths=lengths,
                         scale=0.25, softcap=20.0)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=3e-5)


def test_softcap_parity(rng):
    q, k, v = _qkv(rng, 2, 50, 50, 4, 2, 32)
    got = ops.flash_attention(q, k, v, causal=True, softcap=30.0,
                              block_q=32, block_k=32, interpret=True)
    want = ref.attention(q, k, v, causal=True, softcap=30.0)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=3e-5)


def test_rope_fragment_spec(rng):
    fn = T.make_attention(
        T.AttnSpec(name="rope_test", mask="causal", rope=True),
        register=False)
    try:
        q, k, v = _qkv(rng, 2, 33, 33, 4, 2, 32)
        got = fn(q, k, v, block_q=32, block_k=32, interpret=True)
        want = ref.attention(q, k, v, causal=True, rope=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=3e-5)
    finally:
        T.forget("rope_test")


def test_bf16_parity(rng):
    q, k, v = _qkv(rng, 1, 128, 128, 4, 2, 64, dt=jnp.bfloat16)
    got = T.get("causal")(q, k, v, interpret=True)
    want = ref.attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=5e-2)


def test_interpret_env_override(rng):
    # the template interprets only when asked, and defaults to the Mosaic
    # kernel, which cannot lower for the CPU
    q, k, v = _qkv(rng, 1, 16, 16, 2, 2, 32)
    got = T.get("causal")(q, k, v, interpret=True)
    want = ref.attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=3e-5)
    with pytest.raises(ValueError, match="interpret"):
        T.get("causal")(q, k, v)


# ---------------------------------------------------------------------------
# fully-masked query rows (satellite: epilogue guard regression)
# ---------------------------------------------------------------------------

def test_fully_masked_rows_emit_zeros(rng):
    # a sliding window past the cached KV depth: every key of every query
    # row is masked. NEG_INF is finite, so an unguarded epilogue emits
    # mean(v) garbage — the guard must emit exact zeros (like the oracle).
    q, k, v = _qkv(rng, 1, 8, 16, 2, 2, 32)
    got = ops.flash_attention(q, k, v, causal=True, window=8, q_offset=32,
                              interpret=True)
    assert bool(jnp.all(got == 0.0))
    want = ref.attention(q, k, v, causal=True, window=8, q_offset=32)
    assert bool(jnp.all(want == 0.0))


def test_decode_zero_length_rows_emit_zeros(rng):
    q, k, v = _qkv(rng, 3, 1, 16, 4, 2, 32)
    lengths = jnp.asarray([0, 16, 0], jnp.int32)
    got = T.get("decode")(q, k, v, lengths, interpret=True)
    assert bool(jnp.all(got[0] == 0.0)) and bool(jnp.all(got[2] == 0.0))
    want = ref.attention(q, k, v, causal=False, lengths=lengths)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=3e-5)


def test_jnp_twins_guard_fully_masked_rows(rng):
    # the chunked / flash-VJP jnp twins share the epilogue guard
    q, k, v = _qkv(rng, 1, 8, 16, 2, 2, 32)
    a = A.chunked_attention(q, k, v, causal=True, window=8, q_offset=32,
                            chunk_q=8, chunk_kv=8)
    b = A.flash_attention_jnp(q, k, v, causal=True, window=8, q_offset=32,
                              chunk_q=8, chunk_kv=8)
    assert bool(jnp.all(a == 0.0))
    assert bool(jnp.all(b == 0.0))
    assert np.isfinite(np.asarray(a)).all()
    assert np.isfinite(np.asarray(b)).all()


# ---------------------------------------------------------------------------
# model-level routing: decode / MLA / detector refinement across backends
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kind", ["attn", "local"])
def test_attn_decode_backend_parity(kind, rng):
    cfg = mkcfg(window_size=8 if kind == "local" else 1024)
    params = A.init_attention(jax.random.PRNGKey(1), cfg)
    s = 12
    x = jax.random.normal(rng, (2, s + 1, cfg.d_model))
    pos = jnp.arange(s)[None].repeat(2, 0)
    _, cache = A.attn_prefill(params, x[:, :s], cfg, kind, pos,
                              max_len=s + 4)
    y_jnp, _ = A.attn_decode(params, x[:, s:], cfg, kind, cache,
                             jnp.int32(s))
    with nn.backend("pallas_interpret"):
        y_tpl, _ = A.attn_decode(params, x[:, s:], cfg, kind, cache,
                                 jnp.int32(s))
    np.testing.assert_allclose(np.asarray(y_jnp), np.asarray(y_tpl),
                               atol=2e-5)


@pytest.mark.parametrize("kind", ["attn", "local"])
def test_attn_decode_fused_bit_identical(kind, rng):
    # the jnp fused operator mirrors the unfused op chain exactly — the
    # engine-level fused/unfused token-parity invariant at layer scope
    cfg = mkcfg(window_size=8 if kind == "local" else 1024)
    params = A.init_attention(jax.random.PRNGKey(1), cfg)
    s = 12
    x = jax.random.normal(rng, (2, s + 1, cfg.d_model))
    pos = jnp.arange(s)[None].repeat(2, 0)
    _, cache = A.attn_prefill(params, x[:, :s], cfg, kind, pos,
                              max_len=s + 4)
    y0, _ = A.attn_decode(params, x[:, s:], cfg, kind, cache, jnp.int32(s))
    with nn.fuse():
        y1, _ = A.attn_decode(params, x[:, s:], cfg, kind, cache,
                              jnp.int32(s))
    assert np.array_equal(np.asarray(y0), np.asarray(y1))


def test_mla_decode_backend_parity(rng):
    cfg = reduced(get_config("deepseek-v2-lite-16b")).replace(
        dtype="float32", param_dtype="float32")
    params = A.init_mla(jax.random.PRNGKey(1), cfg)
    s = 10
    x = jax.random.normal(rng, (2, s + 1, cfg.d_model))
    pos = jnp.arange(s + 1)[None].repeat(2, 0)
    full = A.mla_forward(params, x, cfg, pos)
    _, cache = A.mla_prefill(params, x[:, :s], cfg, pos[:, :s],
                             max_len=s + 2)
    y_jnp, _ = A.mla_decode(params, x[:, s:], cfg, cache, jnp.int32(s))
    with nn.backend("pallas_interpret"):
        y_tpl, _ = A.mla_decode(params, x[:, s:], cfg, cache, jnp.int32(s))
    with nn.fuse():
        y_fused, _ = A.mla_decode(params, x[:, s:], cfg, cache,
                                  jnp.int32(s))
    # concatenated-latent scores sum in a different order than the
    # two-einsum unfused path: ulp-level, not bit-identical (docs/kernels)
    np.testing.assert_allclose(np.asarray(y_jnp), np.asarray(y_tpl),
                               atol=2e-4)
    np.testing.assert_allclose(np.asarray(y_jnp), np.asarray(y_fused),
                               atol=2e-4)
    np.testing.assert_allclose(np.asarray(y_jnp),
                               np.asarray(full[:, s:s + 1]), atol=2e-4)


def test_mla_forward_backend_parity(rng):
    cfg = reduced(get_config("deepseek-v2-lite-16b")).replace(
        dtype="float32", param_dtype="float32")
    params = A.init_mla(jax.random.PRNGKey(1), cfg)
    x = jax.random.normal(rng, (2, 9, cfg.d_model))
    pos = jnp.arange(9)[None].repeat(2, 0)
    y_jnp = A.mla_forward(params, x, cfg, pos)
    with nn.backend("pallas_interpret"):
        y_tpl = A.mla_forward(params, x, cfg, pos)
    np.testing.assert_allclose(np.asarray(y_jnp), np.asarray(y_tpl),
                               atol=2e-4)


def test_detector_refine_backend_parity(rng):
    from repro.models.vision import _refine_boxes

    cfg = mkcfg(d_model=32, n_heads=4, n_kv_heads=4)
    d = cfg.d_model
    ks = jax.random.split(rng, 7)
    xp = {
        "wq": _rand(ks[0], (d, d)), "wk": _rand(ks[1], (d, d)),
        "wv": _rand(ks[2], (d, d)), "wo": _rand(ks[3], (d, d)),
        "delta": {"w": _rand(ks[4], (d, 4)), "b": jnp.zeros((4,))},
    }
    tokens = _rand(ks[5], (2, 25, d))
    idx = jnp.asarray([[0, 3, 24, 7, 7], [1, 2, 3, 4, 5]], jnp.int32)
    top_b = _rand(ks[6], (2, 5, 4))
    got_jnp = _refine_boxes(xp, tokens, idx, top_b, 2.0, cfg)
    with nn.backend("pallas_interpret"):
        got_tpl = _refine_boxes(xp, tokens, idx, top_b, 2.0, cfg)
    np.testing.assert_allclose(np.asarray(got_jnp), np.asarray(got_tpl),
                               rtol=2e-5, atol=1e-3)
