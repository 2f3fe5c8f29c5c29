"""Compile the main-path Pallas kernels for a described TPU v5e.

No chip is attached: JAX describes a ``v5e:2x2`` topology and the TPU
compiler lowers each kernel through Mosaic for its first device, at
stablelm-3b widths (d_model 2560, 32 heads of 80, d_ff 6912) plus the
hd-128 GQA decode shape, and the paged decode variant over a
layer-stacked block pool. A kernel Mosaic refuses fails here instead of on
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


def _paged_decode(hq, hkv, hd):
    """The paged variant over a layer-stacked pool, 8 slots x 512 tokens
    in 16-token blocks (stablelm-3b's deployment: 32 layers, 321 blocks)."""
    def build(sh):
        q = _s((BATCH, 1, hq, hd), BF16, sh)
        pool = _s((32, 321, 16, hkv, hd), BF16, sh)
        tables = _s((BATCH, MAX_LEN // 16), jnp.int32, sh)
        lengths = _s((BATCH,), jnp.int32, sh)
        layer = _s((), jnp.int32, sh)
        return (lambda q, k, v, t, n, l:
                ops.attn_decode_paged_template(q, k, v, t, n, l)), \
            (q, pool, pool, tables, lengths, layer)
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
    "paged_decode_mha_hd80": _paged_decode(HEADS, HEADS, HEAD_DIM),
    "paged_decode_gqa_hd128": _paged_decode(32, 8, 128),
    "nms_1024": _nms,
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_kernel_compiles_for_v5e(one_chip, case):
    fn, args = CASES[case](one_chip)
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


#: HLO ops that name a buffer without moving its bytes
VIEWS = ("get-tuple-element", "tuple", "bitcast", "while")


def test_paged_decode_step_moves_no_whole_pool(one_chip, monkeypatch):
    """The paged decode program at stablelm-3b widths (two layers) reads
    the pool in place: the pool keeps its default, row-major layout, and no
    copy, dynamic slice or update, gather or transpose makes a whole pool
    leaf or a whole layer of one; the only pool-sized ops are the in-place
    row writes."""
    import re

    from repro import nn
    from repro.configs import get_config
    from repro.models import init_lm
    from repro.serving.paged import init_pools, make_paged_decode_step

    cfg = get_config("stablelm-3b").replace(n_layers=2,
                                            param_dtype="bfloat16")
    nb, num_blocks = MAX_LEN // 16, 1 + (BATCH + 2) * (MAX_LEN // 16)
    monkeypatch.setattr(nn, "kernel_interpret", lambda: False)

    def shapes(tree):
        return jax.tree_util.tree_map(
            lambda x: _s(x.shape, x.dtype, one_chip), tree)

    with nn.backend("pallas"):
        step = make_paged_decode_step(cfg, MAX_LEN, fused=True)
        params = shapes(jax.eval_shape(
            lambda: init_lm(jax.random.PRNGKey(0), cfg)))
        pools = shapes(jax.eval_shape(
            lambda: init_pools(cfg, num_blocks, 16, "paged")))
        ids = _s((BATCH,), jnp.int32, one_chip)
        text = jax.jit(step, donate_argnums=(3,)).lower(
            params, ids, ids, pools, _s((BATCH, nb), jnp.int32, one_chip),
            _s((2,), jnp.uint32, one_chip)).compile().as_text()
    assert step.kv == "paged" and "tpu_custom_call" in text
    leaf = pools["scan"][0]["k"].shape              # (L, N, bs, Hkv, 128)
    sizes = {",".join(map(str, d)) for d in (leaf, leaf[1:], (1,) + leaf[1:])}
    moved = []
    for line in text.splitlines():
        m = re.match(r"\s*(?:ROOT )?%\S+ = bf16\[([\d,]*)\](\{[^}]*\})? "
                     r"([a-z\-]+)\(", line)
        if m and m.group(1) in sizes:
            if m.group(3) == "parameter":
                assert m.group(2).startswith("{4,3,2,1,0"), line
            elif m.group(3) not in VIEWS and "paged_kv_write" not in line:
                moved.append(line.strip()[:160])
    assert not moved
    assert "paged_kv_gather" not in text

