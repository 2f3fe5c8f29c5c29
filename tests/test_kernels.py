"""Per-kernel shape/dtype sweeps: Pallas (interpret=True) vs ref.py oracle."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref

ATOL = {jnp.float32: 2e-5, jnp.bfloat16: 3e-2}


def _tol(dt):
    return ATOL[dt]


def _rand(key, shape, dt):
    return jax.random.normal(key, shape, jnp.float32).astype(dt)


SHAPES_ND = [(4, 128), (2, 33, 257), (1, 7, 3, 64), (5, 1024)]
DTYPES = [jnp.float32, jnp.bfloat16]


@pytest.mark.parametrize("shape", SHAPES_ND)
@pytest.mark.parametrize("dt", DTYPES)
def test_rms_norm_sweep(shape, dt, rng):
    x = _rand(rng, shape, dt)
    w = _rand(jax.random.PRNGKey(1), (shape[-1],), dt)
    got = ops.rms_norm(x, w, interpret=True)
    want = ref.rms_norm(x, w)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=_tol(dt))


@pytest.mark.parametrize("zero_centered", [False, True])
def test_rms_norm_zero_centered(zero_centered, rng):
    x = _rand(rng, (4, 96), jnp.float32)
    w = _rand(jax.random.PRNGKey(1), (96,), jnp.float32)
    got = ops.rms_norm(x, w, zero_centered=zero_centered, interpret=True)
    want = ref.rms_norm(x, w, zero_centered=zero_centered)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)


@pytest.mark.parametrize("shape", SHAPES_ND[:3])
@pytest.mark.parametrize("dt", DTYPES)
def test_layer_norm_sweep(shape, dt, rng):
    x = _rand(rng, shape, dt)
    w = _rand(jax.random.PRNGKey(1), (shape[-1],), dt)
    b = _rand(jax.random.PRNGKey(2), (shape[-1],), dt)
    got = ops.layer_norm(x, w, b, interpret=True)
    want = ref.layer_norm(x, w, b)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=_tol(dt))


@pytest.mark.parametrize("dt", DTYPES)
def test_fused_add_rms_norm(dt, rng):
    x = _rand(rng, (3, 17, 128), dt)
    r = _rand(jax.random.PRNGKey(1), (3, 17, 128), dt)
    w = _rand(jax.random.PRNGKey(2), (128,), dt)
    gy, gr = ops.fused_add_rms_norm(x, r, w, interpret=True)
    wy, wr = ref.fused_add_rms_norm(x, r, w)
    np.testing.assert_allclose(np.asarray(gy, np.float32),
                               np.asarray(wy, np.float32), atol=_tol(dt))
    np.testing.assert_allclose(np.asarray(gr, np.float32),
                               np.asarray(wr, np.float32), atol=_tol(dt))


@pytest.mark.parametrize("dt", DTYPES)
def test_fused_add_layer_norm(dt, rng):
    x = _rand(rng, (3, 17, 128), dt)
    r = _rand(jax.random.PRNGKey(1), (3, 17, 128), dt)
    w = _rand(jax.random.PRNGKey(2), (128,), dt)
    b = _rand(jax.random.PRNGKey(3), (128,), dt)
    gy, gr = ops.fused_add_layer_norm(x, r, w, b, interpret=True)
    wy, wr = ref.fused_add_layer_norm(x, r, w, b)
    np.testing.assert_allclose(np.asarray(gy, np.float32),
                               np.asarray(wy, np.float32), atol=_tol(dt))
    np.testing.assert_allclose(np.asarray(gr, np.float32),
                               np.asarray(wr, np.float32), atol=_tol(dt))


@pytest.mark.parametrize("shape", [(4, 128), (2, 33, 257), (1, 7, 3, 64)])
@pytest.mark.parametrize("dt", DTYPES)
def test_dequant_add_rms_norm_sweep(shape, dt, rng):
    q = jax.random.randint(rng, shape, -127, 128, jnp.int8)
    qs = jnp.float32(0.031)
    res = _rand(jax.random.PRNGKey(1), shape, dt)
    w = _rand(jax.random.PRNGKey(2), (shape[-1],), dt)
    gy, gr = ops.dequant_add_rms_norm(q, qs, res, w, interpret=True)
    wy, wr = ref.dequant_add_rms_norm(q, qs, res, w)
    np.testing.assert_allclose(np.asarray(gy, np.float32),
                               np.asarray(wy, np.float32), atol=_tol(dt))
    np.testing.assert_allclose(np.asarray(gr, np.float32),
                               np.asarray(wr, np.float32), atol=_tol(dt))


@pytest.mark.parametrize("fraction", [1.0, 0.5, 0.25])
@pytest.mark.parametrize("dt", DTYPES)
def test_fused_rope_sweep(fraction, dt, rng):
    x = _rand(rng, (2, 9, 4, 64), dt)
    pos = jnp.broadcast_to(jnp.arange(9)[None, :], (2, 9))
    got = ops.fused_rope(x, pos, fraction=fraction, interpret=True)
    want = ref.rope(x, pos, fraction=fraction)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=_tol(dt))


def test_fused_rope_decode_positions(rng):
    # per-slot decode: x (B, 1, H, D), positions (B, 1) at distinct depths
    x = _rand(rng, (4, 1, 4, 64), jnp.float32)
    pos = jnp.asarray([[3], [17], [0], [9]], jnp.int32)
    got = ops.fused_rope(x, pos, interpret=True)
    want = ref.rope(x, pos)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


def test_fused_rope_matches_nn_apply_rope(rng):
    from repro import nn
    x = _rand(rng, (1, 16, 8, 64), jnp.float32)
    pos = jnp.arange(16)[None, :]
    got = ops.fused_rope(x, pos, fraction=0.25, interpret=True)
    want = nn.apply_rope(x, pos, fraction=0.25)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


@pytest.mark.parametrize("shape", [(2, 60, 130), (1, 512), (3, 3, 3, 257)])
@pytest.mark.parametrize("dt", DTYPES)
def test_swiglu_sweep(shape, dt, rng):
    g = _rand(rng, shape, dt)
    u = _rand(jax.random.PRNGKey(1), shape, dt)
    got = ops.swiglu(g, u, interpret=True)
    want = ref.swiglu(g, u)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=_tol(dt))


@pytest.mark.parametrize("hq,hkv", [(8, 8), (8, 4), (8, 1)])
@pytest.mark.parametrize("causal,window", [(True, None), (True, 16),
                                           (False, None)])
def test_flash_attention_sweep(hq, hkv, causal, window, rng):
    ks = jax.random.split(rng, 3)
    q = _rand(ks[0], (2, 67, hq, 32), jnp.float32)
    k = _rand(ks[1], (2, 67, hkv, 32), jnp.float32)
    v = _rand(ks[2], (2, 67, hkv, 32), jnp.float32)
    got = ops.flash_attention(q, k, v, causal=causal, window=window,
                              block_q=32, block_k=32, interpret=True)
    want = ref.attention(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=3e-5)


def test_flash_attention_bf16(rng):
    ks = jax.random.split(rng, 3)
    q = _rand(ks[0], (1, 128, 4, 64), jnp.bfloat16)
    k = _rand(ks[1], (1, 128, 2, 64), jnp.bfloat16)
    v = _rand(ks[2], (1, 128, 2, 64), jnp.bfloat16)
    got = ops.flash_attention(q, k, v, causal=True, interpret=True)
    want = ref.attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=5e-2)


@pytest.mark.parametrize("r,v,bv", [(7, 1000, 256), (32, 50304, 2048),
                                    (3, 130, 64)])
def test_softmax_xent_sweep(r, v, bv, rng):
    logits = _rand(rng, (r, v), jnp.float32) * 5
    labels = jax.random.randint(jax.random.PRNGKey(1), (r,), 0, v)
    got = ops.softmax_xent(logits, labels, block_vocab=bv, interpret=True)
    want = ref.softmax_xent(logits, labels)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("n", [37, 300, 1000])
def test_nms_sweep(n, rng):
    ks = jax.random.split(rng, 3)
    centers = jax.random.uniform(ks[0], (n, 2)) * 60
    wh = jax.random.uniform(ks[1], (n, 2)) * 12 + 1
    boxes = jnp.concatenate([centers - wh / 2, centers + wh / 2], -1)
    scores = jax.random.uniform(ks[2], (n,))
    got = ops.nms(boxes, scores, iou_threshold=0.5, interpret=True)
    want = ref.nms(boxes, scores, iou_threshold=0.5)
    assert bool(jnp.all(got == want))


def test_nms_score_threshold(rng):
    boxes = jnp.asarray([[0, 0, 10, 10], [100, 100, 110, 110]], jnp.float32)
    scores = jnp.asarray([0.9, 0.01])
    keep = ops.nms(boxes, scores, score_threshold=0.5, interpret=True)
    assert bool(keep[0]) and not bool(keep[1])


# ---------------------------------------------------------------------------
# Pallas NMS vs nn.nms reference oracle: the RoI-selection parity sweep
# ---------------------------------------------------------------------------

def _random_boxes(rng, n):
    ks = jax.random.split(rng, 3)
    centers = jax.random.uniform(ks[0], (n, 2)) * 60
    wh = jax.random.uniform(ks[1], (n, 2)) * 12 + 1
    boxes = jnp.concatenate([centers - wh / 2, centers + wh / 2], -1)
    return boxes, jax.random.uniform(ks[2], (n,))


def _assert_nms_parity(boxes, scores, **kw):
    got = ops.nms(boxes, scores, interpret=True, **kw)
    want = ref.nms(boxes, scores, **kw)
    assert bool(jnp.all(got == want))


@pytest.mark.parametrize("n", [1, 100, 130, 383])
def test_nms_parity_non_multiple_of_128(n, rng):
    # the kernel pads lanes to a 128 multiple; parity must not depend on it
    _assert_nms_parity(*_random_boxes(rng, n), iou_threshold=0.5)


def test_nms_parity_zero_area_boxes(rng):
    boxes, scores = _random_boxes(rng, 64)
    # degenerate boxes (x2 <= x1 or y2 <= y1): IoU defined as 0 both sides
    degen = jnp.asarray([[5.0, 5.0, 5.0, 5.0], [9.0, 9.0, 3.0, 3.0]])
    boxes = boxes.at[:2].set(degen)
    _assert_nms_parity(boxes, scores, iou_threshold=0.5)


def test_nms_parity_duplicate_scores(rng):
    boxes, _ = _random_boxes(rng, 96)
    # heavy score ties: argsort is stable in both paths, so the greedy
    # order — and therefore the keep mask — must agree exactly
    scores = jnp.asarray([0.5, 0.9, 0.1] * 32)
    _assert_nms_parity(boxes, scores, iou_threshold=0.5)


def test_nms_parity_all_suppressed(rng):
    # N near-identical boxes: only the top-scored survivor remains
    base = jnp.asarray([10.0, 10.0, 20.0, 20.0])
    jitter = jax.random.uniform(rng, (72, 4)) * 0.1
    boxes = base[None] + jitter
    scores = jnp.linspace(0.9, 0.1, 72)
    _assert_nms_parity(boxes, scores, iou_threshold=0.3)
    keep = ops.nms(boxes, scores, iou_threshold=0.3, interpret=True)
    assert int(keep.sum()) == 1


def test_nms_parity_none_suppressed(rng):
    # disjoint boxes on a diagonal: everything above threshold survives
    off = jnp.arange(40, dtype=jnp.float32) * 30
    boxes = jnp.stack([off, off, off + 10, off + 10], axis=-1)
    scores = jax.random.uniform(rng, (40,)) * 0.5 + 0.25
    _assert_nms_parity(boxes, scores, iou_threshold=0.5)
    keep = ops.nms(boxes, scores, interpret=True)
    assert int(keep.sum()) == 40
    # ... and a threshold > 1 can never suppress anything
    _assert_nms_parity(*_random_boxes(rng, 64), iou_threshold=1.5)


def test_nms_parity_under_interpret_env(rng):
    # only an explicit interpret=True interprets; the default is the
    # Mosaic kernel, which cannot lower for the CPU
    boxes, scores = _random_boxes(rng, 200)
    got = ops.nms(boxes, scores, iou_threshold=0.4, interpret=True)
    want = ref.nms(boxes, scores, iou_threshold=0.4)
    assert bool(jnp.all(got == want))
    with pytest.raises(ValueError, match="interpret"):
        ops.nms(boxes, scores, iou_threshold=0.4)
