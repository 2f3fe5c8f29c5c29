"""Compile the main-path Pallas kernels for a described TPU v5e.

No chip is attached: JAX describes a ``v5e:2x2`` topology and the TPU
compiler lowers each kernel through Mosaic for its first device, at
stablelm-3b widths (d_model 2560, 32 heads of 80, d_ff 6912) plus the
hd-128 GQA decode shape. A kernel Mosaic refuses fails here instead of on
the chip. Nothing runs, so these tests say nothing about results or time.

The topology is described only inside the module fixture: only one process
at a time may load the TPU library, and describing it while the file is
imported would give pytest-xdist workers different tests to collect.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops

BF16 = jnp.bfloat16
D_MODEL, HEADS, HEAD_DIM, D_FF = 2560, 32, 80, 6912
BATCH, MAX_LEN, PROMPT = 8, 512, 64


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    # libtpu would otherwise write its logs outside the checkout
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described-device compile is written to the persistent cache but
    # cannot be read back without a chip: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _s(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _norm(sh):
    x = _s((BATCH, 1, D_MODEL), BF16, sh)
    w = _s((D_MODEL,), BF16, sh)
    return (lambda x, r, w, b: ops.fused_add_layer_norm(x, r, w, b)), \
        (x, x, w, w)


def _rope(sh):
    x = _s((BATCH, 1, HEADS, HEAD_DIM), BF16, sh)
    pos = _s((BATCH, 1), jnp.int32, sh)
    return (lambda x, p: ops.fused_rope(x, p, fraction=0.25)), (x, pos)


def _swiglu(sh):
    g = _s((BATCH, 1, D_FF), BF16, sh)
    return (lambda g, u: ops.swiglu(g, u)), (g, g)


def _flash_causal(sh):
    q = _s((1, PROMPT, HEADS, HEAD_DIM), BF16, sh)
    return (lambda q, k, v: ops.flash_attention(q, k, v, causal=True)), \
        (q, q, q)


def _decode(hq, hkv, hd):
    def build(sh):
        q = _s((BATCH, 1, hq, hd), BF16, sh)
        kv = _s((BATCH, MAX_LEN, hkv, hd), BF16, sh)
        lengths = _s((BATCH,), jnp.int32, sh)
        return (lambda q, k, v, n: ops.attn_decode_template(q, k, v, n)), \
            (q, kv, kv, lengths)
    return build


def _nms(sh):
    boxes = _s((1024, 4), jnp.float32, sh)
    scores = _s((1024,), jnp.float32, sh)
    return (lambda b, s: ops.nms(b, s, iou_threshold=0.5)), (boxes, scores)


CASES = {
    "fused_add_layer_norm": _norm,
    "rope_fraction_0.25_hd80": _rope,
    "swiglu": _swiglu,
    "flash_attention_causal_hd80": _flash_causal,
    "decode_template_mha_hd80": _decode(HEADS, HEADS, HEAD_DIM),
    "decode_template_gqa_hd128": _decode(32, 8, 128),
    "nms_1024": _nms,
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_kernel_compiles_for_v5e(one_chip, case):
    fn, args = CASES[case](one_chip)
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
