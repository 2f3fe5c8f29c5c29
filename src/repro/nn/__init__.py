"""repro.nn — scope-tagged operator library.

Every semantic operator used by the model zoo is defined here and wrapped in
``jax.named_scope(scope_tag(group, name))``. The tag is what lets both
profiling views (eager jaxpr interpreter, compiled HLO analyzer) attribute
work to the paper's operator groups — the JAX analogue of the paper pointing
torch.fx at ``nn.Module`` boundaries.

A process-global backend switch selects the implementation:

    "jnp"              pure jax.numpy (reference; used for dry-run/compile)
    "pallas"           fused Pallas TPU kernels where available, lowered
                       through Mosaic; needs a TPU and raises without one
    "pallas_interpret" Pallas kernels in interpret mode (CPU correctness)

Ops without a Pallas kernel always use the jnp path.

A second orthogonal switch, ``nn.fuse()`` (the execution half of
``repro.core.fusion``), routes the fusable call sites through single fused
operators tagged ``ng:fused:<name>``: ``add_rms_norm`` / ``add_layer_norm``
(residual add + following norm), ``swiglu``/``geglu``, ``apply_rope``, the
int8 QDQ round-trip, and the ``dequant_add_rms_norm`` epilogue. Under the
Pallas backends each fused op is one kernel launch; under jnp the same
fused math runs under the fused scope so both profiling views attribute it
to the ``fused`` operator group.
"""

from __future__ import annotations

import contextlib
import functools
import itertools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.taxonomy import OpGroup, scope_tag

_BACKEND = "jnp"


def set_backend(name: str) -> None:
    global _BACKEND
    if name not in ("jnp", "pallas", "pallas_interpret"):
        raise ValueError(f"unknown nn backend {name!r}")
    _BACKEND = name


def get_backend() -> str:
    return _BACKEND


@contextlib.contextmanager
def backend(name: str):
    prev = get_backend()
    set_backend(name)
    try:
        yield
    finally:
        set_backend(prev)


def _kernels():
    from repro.kernels import ops as kops
    return kops


def kernel_interpret() -> bool:
    """Per-call interpret flag for the kernel backends.

    ``pallas_interpret`` runs the kernel bodies in Python on any host;
    ``pallas`` emits the real Mosaic kernels, so it needs a TPU and raises
    rather than quietly interpreting on another platform.
    """
    if _BACKEND == "pallas_interpret":
        return True
    if jax.default_backend() != "tpu":
        raise RuntimeError(
            f"nn backend 'pallas' emits Mosaic TPU kernels but the default "
            f"JAX backend is {jax.default_backend()!r}; use "
            f"'pallas_interpret' to run the kernels in interpret mode")
    return False


#: process-global fusion switch (the execution half of repro.core.fusion):
#: while True, the fusable nn call sites emit single fused operators under
#: ``ng:fused:`` tags instead of their unfused op chains.
_FUSION = False


def set_fusion(enabled: bool) -> None:
    global _FUSION
    _FUSION = bool(enabled)


def fusion_enabled() -> bool:
    return _FUSION


@contextlib.contextmanager
def fuse(enabled: bool = True):
    prev = fusion_enabled()
    set_fusion(enabled)
    try:
        yield
    finally:
        set_fusion(prev)


#: process-global fake-quant switch (None | "int8"), flipped by the
#: QuantizeDequantTransform while a quantized Workload traces/executes.
#: When set, every tagged GEMM site wraps its operands in simulated
#: quantize/dequantize ops — the paper's §4.4 QDQ setting.
_FAKE_QUANT: Optional[str] = None

_QUANT_MODES = ("int8",)


def set_fake_quant(mode: Optional[str]) -> None:
    global _FAKE_QUANT
    if mode is not None and mode not in _QUANT_MODES:
        raise ValueError(f"unknown fake-quant mode {mode!r}; "
                         f"known: {_QUANT_MODES}")
    _FAKE_QUANT = mode


def get_fake_quant() -> Optional[str]:
    return _FAKE_QUANT


@contextlib.contextmanager
def fake_quant(mode: str = "int8"):
    prev = get_fake_quant()
    set_fake_quant(mode)
    try:
        yield
    finally:
        set_fake_quant(prev)


#: debug-mode bounds checking for cache writes (see kv_cache_update):
#: dynamic_update_slice CLAMPS out-of-range start indices, so a bad block
#: table or position silently corrupts the last valid row instead of
#: failing. Flip this on (tests, bring-up) to fail loudly instead.
_DEBUG_BOUNDS = False


def set_debug_bounds(enabled: bool) -> None:
    global _DEBUG_BOUNDS
    _DEBUG_BOUNDS = bool(enabled)


def debug_bounds_enabled() -> bool:
    return _DEBUG_BOUNDS


@contextlib.contextmanager
def debug_bounds(enabled: bool = True):
    prev = debug_bounds_enabled()
    set_debug_bounds(enabled)
    try:
        yield
    finally:
        set_debug_bounds(prev)


#: monotone per-process invocation counter for tagged ops (see below)
_CALLS = itertools.count()


def tagged(group: OpGroup, name: str):
    """Decorator: run the op body under its ``ng:`` named scope.

    An inner ``c<N>`` marker scope makes every *invocation* distinct in
    the name stack: back-to-back calls of the same op (rope on q then on
    k) would otherwise be indistinguishable, and the fusion rewriter
    (``repro.core.fusion``) would merge them into one site run — modeling
    N real kernel launches as one. The marker carries no ``ng:`` tag, so
    classification is unaffected.
    """
    tag = scope_tag(group, name)

    def deco(fn):
        @functools.wraps(fn)
        def wrapper(*args, **kwargs):
            with jax.named_scope(tag), \
                    jax.named_scope(f"c{next(_CALLS)}"):
                return fn(*args, **kwargs)
        wrapper.op_group = group
        wrapper.op_tag = tag
        return wrapper
    return deco


# ---------------------------------------------------------------------------
# Normalization (paper group: Normalization)
# ---------------------------------------------------------------------------

@tagged(OpGroup.NORMALIZATION, "layer_norm")
def layer_norm(x, scale, bias, eps: float = 1e-5):
    if _BACKEND != "jnp":
        return _kernels().layer_norm(x, scale, bias, eps=eps,
                                     interpret=kernel_interpret())
    xf = x.astype(jnp.float32)
    mean = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(xf - mean), axis=-1, keepdims=True)
    y = (xf - mean) * jax.lax.rsqrt(var + eps)
    y = y * scale.astype(jnp.float32) + bias.astype(jnp.float32)
    return y.astype(x.dtype)


@tagged(OpGroup.NORMALIZATION, "rms_norm")
def rms_norm(x, scale, eps: float = 1e-6, zero_centered: bool = False):
    if _BACKEND != "jnp":
        return _kernels().rms_norm(x, scale, eps=eps,
                                   zero_centered=zero_centered,
                                   interpret=kernel_interpret())
    xf = x.astype(jnp.float32)
    ms = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(ms + eps)
    s = scale.astype(jnp.float32)
    y = y * (1.0 + s) if zero_centered else y * s
    return y.astype(x.dtype)


@tagged(OpGroup.NORMALIZATION, "fused_add_rms_norm")
def fused_add_rms_norm(x, residual, scale, eps: float = 1e-6,
                       zero_centered: bool = False):
    """residual += x; y = rms_norm(residual) — a single HBM pass on TPU."""
    if _BACKEND != "jnp":
        return _kernels().fused_add_rms_norm(
            x, residual, scale, eps=eps, zero_centered=zero_centered,
            interpret=kernel_interpret())
    r = (x.astype(jnp.float32) + residual.astype(jnp.float32)).astype(x.dtype)
    return rms_norm(r, scale, eps=eps, zero_centered=zero_centered), r


# ---------------------------------------------------------------------------
# Activation (paper group: Activation)
# ---------------------------------------------------------------------------

@tagged(OpGroup.ACTIVATION, "relu")
def relu(x):
    return jnp.maximum(x, 0)


@tagged(OpGroup.ACTIVATION, "gelu")
def gelu(x, approximate: bool = True):
    return jax.nn.gelu(x, approximate=approximate)


@tagged(OpGroup.ACTIVATION, "silu")
def silu(x):
    return x * jax.nn.sigmoid(x)


@tagged(OpGroup.ACTIVATION, "sigmoid")
def sigmoid(x):
    """Plain sigmoid (detection class scores)."""
    return jax.nn.sigmoid(x.astype(jnp.float32)).astype(x.dtype)


@tagged(OpGroup.ACTIVATION, "swiglu")
def swiglu(gate, up):
    """SiLU(gate) * up — fused Activation + Elem-wise mul."""
    if _FUSION:
        return _fused_swiglu(gate, up)
    if _BACKEND != "jnp":
        return _kernels().swiglu(gate, up,
                                 interpret=kernel_interpret())
    return (gate * jax.nn.sigmoid(gate.astype(jnp.float32)).astype(gate.dtype)
            ) * up


@tagged(OpGroup.ACTIVATION, "geglu")
def geglu(gate, up):
    if _FUSION:
        return _fused_geglu(gate, up)
    return jax.nn.gelu(gate, approximate=True) * up


ACTIVATIONS = {"relu": relu, "gelu": gelu, "silu": silu}


# ---------------------------------------------------------------------------
# Logit computation (paper group: Logit Computation)
# ---------------------------------------------------------------------------

@tagged(OpGroup.LOGIT, "softmax")
def softmax(x, axis: int = -1):
    xf = x.astype(jnp.float32)
    m = jax.lax.stop_gradient(jnp.max(xf, axis=axis, keepdims=True))
    e = jnp.exp(xf - m)
    return (e / jnp.sum(e, axis=axis, keepdims=True)).astype(x.dtype)


@tagged(OpGroup.LOGIT, "softmax_cross_entropy")
def softmax_cross_entropy(logits, labels):
    """Per-position CE. logits (..., V) f32-accumulated; labels (...) int."""
    lf = logits.astype(jnp.float32)
    m = jnp.max(lf, axis=-1, keepdims=True)
    shifted = lf - jax.lax.stop_gradient(m)
    lse = jnp.log(jnp.sum(jnp.exp(shifted), axis=-1)) + jnp.squeeze(
        jax.lax.stop_gradient(m), -1)
    label_logit = jnp.take_along_axis(lf, labels[..., None], axis=-1)[..., 0]
    return lse - label_logit


@tagged(OpGroup.LOGIT, "router_gate")
def router_gate(logits):
    """MoE router probabilities (softmax over experts)."""
    return jax.nn.softmax(logits.astype(jnp.float32), axis=-1)


# ---------------------------------------------------------------------------
# Memory ops (paper group: Memory)
# ---------------------------------------------------------------------------

@tagged(OpGroup.MEMORY, "split_heads")
def split_heads(x, n_heads: int):
    b, s, _ = x.shape
    return x.reshape(b, s, n_heads, -1)


@tagged(OpGroup.MEMORY, "merge_heads")
def merge_heads(x):
    b, s, h, d = x.shape
    return x.reshape(b, s, h * d)


@tagged(OpGroup.MEMORY, "embedding_lookup")
def embedding_lookup(table, ids):
    return jnp.take(table, ids, axis=0)


@tagged(OpGroup.MEMORY, "kv_cache_update")
def kv_cache_update(cache, new, index):
    """Insert ``new`` (B, 1, ...) into ``cache`` (B, S, ...) at ``index``.

    ``index`` is either a scalar (all rows write the same position — the
    lockstep decode of a freshly prefilled batch) or a per-row ``(B,)``
    vector (continuous batching: every slot sits at its own position).

    ``dynamic_update_slice`` CLAMPS out-of-range starts, so a stale block
    table or runaway position would silently overwrite the last valid row.
    Under ``nn.debug_bounds()`` the index is range-checked instead: a
    concrete out-of-range index raises ``ValueError`` immediately; a traced
    one reports through ``jax.debug.callback`` at run time.
    """
    new = new.astype(cache.dtype)
    index = jnp.asarray(index, jnp.int32)
    if _DEBUG_BOUNDS:
        _check_cache_index(index, cache.shape[1] - new.shape[1])
    if index.ndim == 0:
        return jax.lax.dynamic_update_slice_in_dim(cache, new, index, axis=1)
    return jax.vmap(
        lambda c, n, i: jax.lax.dynamic_update_slice_in_dim(c, n, i, axis=0)
    )(cache, new, index)


def _check_cache_index(index, limit: int) -> None:
    """Fail loudly when a cache-write start index falls outside [0, limit]."""
    import numpy as np
    try:
        concrete = np.asarray(index)
    except (jax.errors.TracerArrayConversionError, TypeError):
        concrete = None
    if concrete is not None:
        if concrete.min() < 0 or concrete.max() > limit:
            raise ValueError(
                f"kv_cache_update index {concrete!r} outside [0, {limit}]; "
                "dynamic_update_slice would clamp and corrupt the edge row")
        return

    def _report(idx, lim):
        if idx.min() < 0 or idx.max() > lim:
            raise ValueError(
                f"kv_cache_update index {idx!r} outside [0, {lim}]")

    jax.debug.callback(_report, index, jnp.int32(limit))


@tagged(OpGroup.MEMORY, "paged_kv_gather")
def paged_kv_gather(pool, block_table, max_len: int):
    """Gather paged KV blocks into a contiguous (B, max_len, ...) view.

    ``pool`` is (N, bs, ...) — N fixed-size blocks of bs positions each;
    ``block_table`` is (B, nb) int32 mapping each sequence's logical block
    slots to pool block ids (0 = the reserved scratch block). The gathered
    view feeds the unchanged contiguous-cache decode path, which is what
    makes the paged engine bit-identical to the monolithic one.
    """
    bs = pool.shape[1]
    b, nb = block_table.shape
    g = jnp.take(pool, block_table.reshape(-1), axis=0)
    return g.reshape(b, nb * bs, *pool.shape[2:])[:, :max_len]


@tagged(OpGroup.MEMORY, "paged_kv_write")
def paged_kv_write(pool, new, block_table, index, layer=None):
    """Scatter one decode row per sequence into its paged block.

    ``new`` is (B, 1, ...); ``index`` (B,) is each sequence's position.
    Row ``b`` lands in pool block ``block_table[b, index[b] // bs]`` at
    offset ``index[b] % bs``. Sequences whose table slot is 0 write the
    reserved scratch block (dead/prefilling slots stay harmless). With
    ``layer`` (int32 scalar) the pool is layer-stacked (L, N, bs, ...) and
    only that layer's rows are written, in place.
    """
    bs = pool.shape[1 if layer is None else 2]
    index = jnp.asarray(index, jnp.int32)
    block_ids = jnp.take_along_axis(
        block_table, (index // bs)[:, None], axis=1)[:, 0]
    rows = new[:, 0].astype(pool.dtype)
    if layer is None:
        return pool.at[block_ids, index % bs].set(rows)
    return pool.at[layer, block_ids, index % bs].set(rows)


@tagged(OpGroup.MEMORY, "paged_kv_scatter")
def paged_kv_scatter(pool, rows, block_table, start, lo, hi):
    """Scatter a prefill chunk (R, ...) at positions start + arange(R).

    ``block_table`` is one sequence's (nb,) table row. Positions outside
    [lo, hi) — left overlap with already-cached prefix blocks, right
    padding past the prompt — divert to the reserved scratch block 0, so
    chunk buckets never need to match the prompt length exactly.
    """
    bs = pool.shape[1]
    n = pool.shape[0]
    idx = jnp.asarray(start, jnp.int32) + jnp.arange(rows.shape[0],
                                                     dtype=jnp.int32)
    blk = jnp.take(block_table,
                   jnp.clip(idx // bs, 0, block_table.shape[0] - 1))
    keep = (idx >= lo) & (idx < hi)
    flat = jnp.where(keep, blk * bs + idx % bs, idx % bs)
    out = pool.reshape(n * bs, *pool.shape[2:]).at[flat].set(
        rows.astype(pool.dtype))
    return out.reshape(pool.shape)


@tagged(OpGroup.MEMORY, "apply_rope")
def apply_rope(x, positions, base: float = 10000.0, fraction: float = 1.0):
    """Rotary embedding on (B, S, H, D); optionally on a leading fraction."""
    if _FUSION:
        return _fused_rope(x, positions, base=base, fraction=fraction)
    d = x.shape[-1]
    rot = int(d * fraction) // 2 * 2
    x_rot, x_pass = x[..., :rot], x[..., rot:]
    half = rot // 2
    freq = base ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    theta = positions[..., None].astype(jnp.float32) * freq  # (B,S,half)
    cos = jnp.cos(theta)[:, :, None, :]
    sin = jnp.sin(theta)[:, :, None, :]
    x1, x2 = x_rot[..., :half].astype(jnp.float32), x_rot[..., half:].astype(jnp.float32)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return jnp.concatenate([out.astype(x.dtype), x_pass], axis=-1) \
        if rot < d else out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Element-wise arithmetic (paper group: Elem-wise Arithmetic)
# ---------------------------------------------------------------------------

@tagged(OpGroup.ELEMENTWISE, "residual_add")
def residual_add(x, y):
    return x + y


@tagged(OpGroup.ELEMENTWISE, "scale")
def scale(x, factor):
    return x * factor


@tagged(OpGroup.ELEMENTWISE, "box_decode")
def box_decode(raw, anchors):
    """Anchor-relative box decode: raw (..., 4) offsets -> xyxy (..., 4).

    ``anchors`` are (..., 4) as (cx, cy, w, h). The usual detection-head
    elementwise train (shift centers, exp the log-sizes, corner convert) —
    one op site so the fusion pass can collapse it to a single launch.
    """
    rf = raw.astype(jnp.float32)
    af = anchors.astype(jnp.float32)
    cx = af[..., 0] + rf[..., 0] * af[..., 2]
    cy = af[..., 1] + rf[..., 1] * af[..., 3]
    w = af[..., 2] * jnp.exp(jnp.clip(rf[..., 2], -4.0, 4.0))
    h = af[..., 3] * jnp.exp(jnp.clip(rf[..., 3], -4.0, 4.0))
    out = jnp.stack([cx - w / 2, cy - h / 2, cx + w / 2, cy + h / 2], axis=-1)
    return out.astype(raw.dtype)


# ---------------------------------------------------------------------------
# Quantization (paper §4.4: QDQ operators around accelerated GEMMs)
# ---------------------------------------------------------------------------

def _quantize_int8_impl(x):
    xf = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(xf))
    scale = jnp.maximum(amax, 1e-8) / 127.0
    q = jnp.clip(jnp.round(xf / scale), -127.0, 127.0).astype(jnp.int8)
    return q, scale


def _dequantize_int8_impl(q, scale, dtype=jnp.float32):
    return (q.astype(jnp.float32) * scale).astype(dtype)


@tagged(OpGroup.QUANT, "quantize")
def quantize_int8(x):
    """Simulated symmetric per-tensor int8 quantization.

    Returns ``(q, scale)`` with ``q`` int8 and a scalar f32 scale — the ops
    a dynamic-quantization runtime dispatches before every int8 GEMM
    (absmax reduction, divide, round, clamp, cast).
    """
    return _quantize_int8_impl(x)


@tagged(OpGroup.QUANT, "dequantize")
def dequantize_int8(q, scale, dtype=jnp.float32):
    """Inverse of :func:`quantize_int8` (cast + scale multiply)."""
    return _dequantize_int8_impl(q, scale, dtype)


def fake_quant_int8(x):
    """Round-trip ``x`` through the int8 grid (quantize -> dequantize).

    Under ``nn.fuse()`` the whole round-trip runs as one fused op — the
    QDQ launch train is the §4.4 overhead the fusion pass targets."""
    if _FUSION:
        return _fused_qdq(x)
    q, s = quantize_int8(x)
    return dequantize_int8(q, s, x.dtype)


def _maybe_fake_quant(*operands):
    if _FAKE_QUANT == "int8":
        return tuple(fake_quant_int8(o) for o in operands)
    return operands


# ---------------------------------------------------------------------------
# Fused operators (paper §6; the execution half of repro.core.fusion)
#
# Each is ONE operator — one ng:fused: tag, one Pallas kernel launch on the
# kernel backends — implementing a NonGEMM chain the fusion pass rewrites.
# The jnp fallbacks call the untagged repro.kernels.ref oracles so no inner
# ng: tag shadows the fused attribution.
# ---------------------------------------------------------------------------

def _ref():
    from repro.kernels import ref
    return ref


@tagged(OpGroup.FUSED, "fused_add_rms_norm")
def _fused_add_rms_norm(x, residual, scale, eps: float = 1e-6,
                        zero_centered: bool = False):
    if _BACKEND != "jnp":
        return _kernels().fused_add_rms_norm(
            x, residual, scale, eps=eps, zero_centered=zero_centered,
            interpret=kernel_interpret())
    return _ref().fused_add_rms_norm(x, residual, scale, eps=eps,
                                     zero_centered=zero_centered)


@tagged(OpGroup.FUSED, "fused_add_layer_norm")
def _fused_add_layer_norm(x, residual, scale, bias, eps: float = 1e-5):
    if _BACKEND != "jnp":
        return _kernels().fused_add_layer_norm(
            x, residual, scale, bias, eps=eps, interpret=kernel_interpret())
    return _ref().fused_add_layer_norm(x, residual, scale, bias, eps=eps)


def add_rms_norm(x, residual, scale, eps: float = 1e-6,
                 zero_centered: bool = False):
    """``(rms_norm(x + residual), x + residual)`` — the pre-norm boundary.

    Unfused this is a residual_add op followed by an rms_norm op; under
    ``nn.fuse()`` it is one fused operator (kernel-backed on the Pallas
    backends). The model zoo's blocks call this at every norm that follows
    a residual add, which is what routes ``lm_decode`` (and the serving
    engine built on it) through the fused fast path.
    """
    if _FUSION:
        return _fused_add_rms_norm(x, residual, scale, eps=eps,
                                   zero_centered=zero_centered)
    r = residual_add(x, residual)
    return rms_norm(r, scale, eps=eps, zero_centered=zero_centered), r


def add_layer_norm(x, residual, scale, bias, eps: float = 1e-5):
    """LayerNorm twin of :func:`add_rms_norm` (returns ``(y, x+residual)``)."""
    if _FUSION:
        return _fused_add_layer_norm(x, residual, scale, bias, eps=eps)
    r = residual_add(x, residual)
    return layer_norm(r, scale, bias, eps=eps), r


@tagged(OpGroup.FUSED, "fused_dequant_add_rms_norm")
def dequant_add_rms_norm(q, qscale, residual, scale, eps: float = 1e-6,
                         zero_centered: bool = False):
    """Fused QDQ epilogue: ``rms_norm(q * qscale + residual)`` (+ new res).

    The dequantize→add→norm chain a quantized GEMM epilogue dispatches as
    three HBM passes, as one (the int8 operand read at a quarter of the
    float bytes).
    """
    if _BACKEND != "jnp":
        return _kernels().dequant_add_rms_norm(
            q, qscale, residual, scale, eps=eps,
            zero_centered=zero_centered, interpret=kernel_interpret())
    return _ref().dequant_add_rms_norm(q, qscale, residual, scale, eps=eps,
                                       zero_centered=zero_centered)


@tagged(OpGroup.FUSED, "fused_swiglu")
def _fused_swiglu(gate, up):
    if _BACKEND != "jnp":
        return _kernels().swiglu(gate, up, interpret=kernel_interpret())
    return _ref().swiglu(gate, up)


@tagged(OpGroup.FUSED, "fused_geglu")
def _fused_geglu(gate, up):
    if _BACKEND != "jnp":
        return _kernels().geglu(gate, up, interpret=kernel_interpret())
    return jax.nn.gelu(gate.astype(jnp.float32),
                       approximate=True).astype(gate.dtype) * up


@tagged(OpGroup.FUSED, "fused_rope")
def _fused_rope(x, positions, base: float = 10000.0, fraction: float = 1.0):
    if _BACKEND != "jnp":
        return _kernels().fused_rope(x, positions, base=base,
                                     fraction=fraction,
                                     interpret=kernel_interpret())
    return _ref().rope(x, positions, base=base, fraction=fraction)


@tagged(OpGroup.FUSED, "fused_qdq")
def _fused_qdq(x):
    q, s = _quantize_int8_impl(x)
    return _dequantize_int8_impl(q, s, x.dtype)


@tagged(OpGroup.FUSED, "fused_attn_decode")
def fused_attn_decode(q, k, v, lengths, scale: Optional[float] = None,
                      softcap: Optional[float] = None, tables=None,
                      layer=None):
    """One-query decode attention over a per-row valid KV prefix as ONE
    operator — the ``attn_template:decode`` variant on the kernel backends.

    q: (B, 1, Hq, Dk); k: (B, T, Hkv, Dk); v: (B, T, Hkv, Dv);
    lengths: (B,) int32 attendable prefix -> (B, 1, Hq, Dv) f32.

    Unfused, a decode step dispatches the qk GEMM, mask, softmax and pv
    GEMM as four operators with an HBM round-trip of the (B, H, T) score
    rows between each — the chain ``FUSION_PATTERNS`` rewrites to this
    record. The jnp fallback mirrors the unfused op sequence exactly
    (bit-identical tokens); the Pallas variant agrees to float tolerance.

    With ``tables`` (B, nb) the K/V operands are paged block pools
    (N, bs, Hkv, D), or layer-stacked (L, N, bs, Hkv, D) with ``layer``:
    the ``attn_template:paged_decode`` variant reads the live blocks in
    place, so no contiguous view of the cache is ever made. That form has
    no jnp fallback; the engine gathers a view on the jnp backend
    (``serving.paged.decode_kv_path``).
    """
    if tables is not None:
        if _BACKEND == "jnp":
            raise ValueError("paged decode attention needs a kernel "
                             "backend; the jnp backend gathers a view")
        return _kernels().attn_decode_paged_template(
            q, k, v, tables, lengths, layer, scale=scale, softcap=softcap,
            interpret=kernel_interpret())
    if _BACKEND != "jnp":
        return _kernels().attn_decode_template(
            q, k, v, lengths, scale=scale, softcap=softcap,
            interpret=kernel_interpret())
    return _ref().decode_attention(q, k, v, lengths, scale=scale,
                                   softcap=softcap)


# ---------------------------------------------------------------------------
# Collective sites (manual tensor parallelism inside shard_map bodies).
#
# These are NOT @tagged identities: outside a ``sharding.manual_axis``
# context they return their input untouched — no scope, no primitive — so
# single-device and GSPMD traces are bit-identical to before. Inside a
# shard_map body they emit the real collective under an ``ng:collective``
# tag, which is how the per-block all-reduces of a tensor-parallel decode
# become first-class COLLECTIVE OpRecords in captured graphs.
# ---------------------------------------------------------------------------

def tp_psum(x):
    """All-reduce a partial block output over the manual TP axis.

    The Megatron reduction: attention out-projections and FFN down-
    projections are row-sharded, so each device holds a partial sum that
    must be psum'd before the next residual add / norm reads it.
    """
    from repro import sharding as _sh
    axis = _sh.manual_axis_name()
    if axis is None:
        return x
    with jax.named_scope(scope_tag(OpGroup.COLLECTIVE, "psum")), \
            jax.named_scope(f"c{next(_CALLS)}"):
        return jax.lax.psum(x, axis)


def tp_vocab_gather(logits):
    """All-gather vocab-sharded logit slices along the last dim.

    Only active when the manual context declares the unembedding
    vocab-sharded. Exact by construction: a column-sharded GEMM computes
    every logit element with the full contraction, so the gathered result
    is bit-identical to the replicated computation.
    """
    from repro import sharding as _sh
    axis = _sh.manual_axis_name()
    if axis is None or not _sh.manual_vocab_sharded():
        return logits
    with jax.named_scope(scope_tag(OpGroup.COLLECTIVE, "all_gather")), \
            jax.named_scope(f"c{next(_CALLS)}"):
        return jax.lax.all_gather(logits, axis, axis=logits.ndim - 1,
                                  tiled=True)


# ---------------------------------------------------------------------------
# GEMM sites (tagged so attribution is exact, not heuristic)
# ---------------------------------------------------------------------------

@tagged(OpGroup.GEMM, "linear")
def linear(x, w, b=None):
    x, w = _maybe_fake_quant(x, w)
    y = jnp.einsum("...d,df->...f", x, w,
                   preferred_element_type=jnp.float32).astype(x.dtype)
    if b is not None:
        y = y + b.astype(y.dtype)
    return y


@tagged(OpGroup.GEMM, "einsum")
def einsum(spec: str, *operands):
    dt = operands[0].dtype
    operands = _maybe_fake_quant(*operands)
    return jnp.einsum(spec, *operands,
                      preferred_element_type=jnp.float32).astype(dt)


@tagged(OpGroup.GEMM, "conv2d")
def conv2d(x, w, b=None, stride: int = 1, padding: str = "VALID"):
    """Strided 2D convolution: NCHW input x OIHW kernel -> NHWC output.

    Convolutions are GEMM-group work in the paper's taxonomy (Table 2); the
    NHWC output puts channels last so the vision models feed the result
    straight into the token-major encoder stack. Like ``linear``/``einsum``,
    operands round-trip through the int8 grid under the QDQ transform.
    """
    dt = x.dtype
    x, w = _maybe_fake_quant(x, w)
    s = (stride, stride) if isinstance(stride, int) else tuple(stride)
    y = jax.lax.conv_general_dilated(
        x, w.astype(x.dtype), window_strides=s, padding=padding,
        dimension_numbers=("NCHW", "OIHW", "NHWC"),
        preferred_element_type=jnp.float32).astype(dt)
    if b is not None:
        y = y + b.astype(y.dtype)
    return y


# ---------------------------------------------------------------------------
# RoI selection (paper group: RoI Selection) — TPU-adapted NMS
# ---------------------------------------------------------------------------

@tagged(OpGroup.ROI, "nms")
def nms(boxes, scores, iou_threshold: float = 0.5,
        score_threshold: float = 0.0, max_outputs: Optional[int] = None):
    """Non-maximum suppression with static shapes (TPU-idiomatic).

    Returns a keep mask of shape (N,). Boxes are (N, 4) as (x1, y1, x2, y2).
    Greedy NMS identical to torchvision semantics, expressed as a
    ``fori_loop`` over score-sorted candidates with a vectorized IoU row
    per step — no data-dependent shapes (DESIGN.md §3 hardware adaptation).
    """
    if _BACKEND != "jnp":
        return _kernels().nms(boxes, scores, iou_threshold=iou_threshold,
                              score_threshold=score_threshold,
                              interpret=kernel_interpret())
    n = boxes.shape[0]
    order = jnp.argsort(-scores)
    b = boxes[order]
    s = scores[order]
    x1, y1, x2, y2 = b[:, 0], b[:, 1], b[:, 2], b[:, 3]
    area = jnp.maximum(x2 - x1, 0) * jnp.maximum(y2 - y1, 0)

    ix1 = jnp.maximum(x1[:, None], x1[None, :])
    iy1 = jnp.maximum(y1[:, None], y1[None, :])
    ix2 = jnp.minimum(x2[:, None], x2[None, :])
    iy2 = jnp.minimum(y2[:, None], y2[None, :])
    inter = jnp.maximum(ix2 - ix1, 0) * jnp.maximum(iy2 - iy1, 0)
    union = area[:, None] + area[None, :] - inter
    iou = jnp.where(union > 0, inter / union, 0.0)

    valid = s > score_threshold

    def body(i, keep):
        alive = keep[i] & valid[i]
        suppress = (iou[i] > iou_threshold) & (jnp.arange(n) > i) & alive
        return keep & ~suppress

    keep_sorted = jax.lax.fori_loop(0, n, body, valid)
    keep = jnp.zeros((n,), dtype=bool).at[order].set(keep_sorted)
    return keep


# ---------------------------------------------------------------------------
# Interpolation (paper group: Interpolation)
# ---------------------------------------------------------------------------

@tagged(OpGroup.INTERPOLATION, "interpolate_bilinear")
def interpolate_bilinear(x, out_hw: Tuple[int, int]):
    """Bilinear resize of NCHW, align_corners=False (torch default).

    The two row-gathers are hoisted (each output row pair is gathered once
    and reused by both column corners — the naive four-corner form gathers
    four full copies of ``x``), the lerp runs in float32, and the result is
    cast back to ``x.dtype`` so bf16 activations stay bf16.
    """
    n, c, h, w = x.shape
    oh, ow = out_hw
    ys = (jnp.arange(oh) + 0.5) * (h / oh) - 0.5
    xs = (jnp.arange(ow) + 0.5) * (w / ow) - 0.5
    y0 = jnp.clip(jnp.floor(ys), 0, h - 1)
    x0 = jnp.clip(jnp.floor(xs), 0, w - 1)
    y1 = jnp.clip(y0 + 1, 0, h - 1)
    x1 = jnp.clip(x0 + 1, 0, w - 1)
    wy = jnp.clip(ys - y0, 0.0, 1.0)[:, None]       # (OH, 1)
    wx = jnp.clip(xs - x0, 0.0, 1.0)                # (OW,)
    y0, y1, x0, x1 = (a.astype(jnp.int32) for a in (y0, y1, x0, x1))
    rows0 = x[:, :, y0].astype(jnp.float32)         # (N, C, OH, W)
    rows1 = x[:, :, y1].astype(jnp.float32)
    top = rows0[..., x0] * (1 - wx) + rows0[..., x1] * wx
    bot = rows1[..., x0] * (1 - wx) + rows1[..., x1] * wx
    return (top * (1 - wy) + bot * wy).astype(x.dtype)


# ---------------------------------------------------------------------------
# Pooling / windowed reductions (Reduction group — vision heads & necks)
# ---------------------------------------------------------------------------

def _pool_stride(window: int, stride: Optional[int]) -> int:
    return window if stride is None else stride


@tagged(OpGroup.REDUCTION, "max_pool2d")
def max_pool2d(x, window: int = 2, stride: Optional[int] = None,
               padding: str = "VALID"):
    """2D max pool over NHWC (windowed reduction — paper group Reduction)."""
    s = _pool_stride(window, stride)
    init = jnp.asarray(-jnp.inf, x.dtype)
    return jax.lax.reduce_window(x, init, jax.lax.max,
                                 (1, window, window, 1), (1, s, s, 1),
                                 padding)


@tagged(OpGroup.REDUCTION, "avg_pool2d")
def avg_pool2d(x, window: int = 2, stride: Optional[int] = None,
               padding: str = "VALID"):
    """2D average pool over NHWC; f32 accumulation, result in ``x.dtype``."""
    s = _pool_stride(window, stride)
    acc = jax.lax.reduce_window(x.astype(jnp.float32), 0.0, jax.lax.add,
                                (1, window, window, 1), (1, s, s, 1),
                                padding)
    return (acc / float(window * window)).astype(x.dtype)


@tagged(OpGroup.REDUCTION, "global_avg_pool")
def global_avg_pool(x, axes: Tuple[int, ...] = (1, 2)):
    """Mean over the spatial axes — the classifier-head pooling op."""
    return jnp.mean(x.astype(jnp.float32), axis=axes).astype(x.dtype)
