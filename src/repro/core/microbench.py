"""NonGEMM operator micro-benchmark suite (paper §3.2.4, Table 2).

Each entry runs one NonGEMM operator standalone, with input shapes either
given explicitly (the Table-2 defaults below use the paper's own example
shapes where they exist) or *harvested from a real model trace* via
``repro.core.graph.harvest_shapes`` — the paper's "input argument
specification extracted from real data".

Per op we report:
  * ``jit_us``     — compiled wall time on host CPU (whole-op kernel)
  * ``eager_us``   — per-primitive dispatched wall time (interpreter)
  * ``tpu_model_us`` — modeled TPU-v5e roofline time (bandwidth-bound)
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Dict, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from .graph import dtype_bytes
from .hardware import TPU_V5E, HardwareSpec
from .interpreter import ProfilingInterpreter
from .profiler import _wallclock
from .taxonomy import OpGroup


@dataclasses.dataclass
class MicroOp:
    name: str
    group: OpGroup
    make: Callable            # (shape, dtype, key) -> (fn, args)


@dataclasses.dataclass
class MicroResult:
    name: str
    group: str
    shape: tuple
    dtype: str
    jit_us: float
    eager_us: float
    tpu_model_us: float
    bytes_touched: float


_REGISTRY: Dict[str, MicroOp] = {}


def register(name: str, group: OpGroup):
    def deco(make):
        _REGISTRY[name] = MicroOp(name=name, group=group, make=make)
        return make
    return deco


def registry() -> Dict[str, MicroOp]:
    return dict(_REGISTRY)


def _rng(key, shape, dtype):
    return jax.random.normal(key, shape, dtype=jnp.float32).astype(dtype)


# --- Table-2 operator suite -------------------------------------------------

@register("layer_norm", OpGroup.NORMALIZATION)
def _mk_layer_norm(shape, dtype, key):
    from repro import nn
    x = _rng(key, shape, dtype)
    scale = jnp.ones((shape[-1],), dtype)
    bias = jnp.zeros((shape[-1],), dtype)
    return (lambda x: nn.layer_norm(x, scale, bias)), (x,)


@register("rms_norm", OpGroup.NORMALIZATION)
def _mk_rms_norm(shape, dtype, key):
    from repro import nn
    x = _rng(key, shape, dtype)
    scale = jnp.ones((shape[-1],), dtype)
    return (lambda x: nn.rms_norm(x, scale)), (x,)


@register("gelu", OpGroup.ACTIVATION)
def _mk_gelu(shape, dtype, key):
    from repro import nn
    return nn.gelu, (_rng(key, shape, dtype),)


@register("silu", OpGroup.ACTIVATION)
def _mk_silu(shape, dtype, key):
    from repro import nn
    return nn.silu, (_rng(key, shape, dtype),)


@register("relu", OpGroup.ACTIVATION)
def _mk_relu(shape, dtype, key):
    from repro import nn
    return nn.relu, (_rng(key, shape, dtype),)


@register("softmax", OpGroup.LOGIT)
def _mk_softmax(shape, dtype, key):
    from repro import nn
    return (lambda x: nn.softmax(x, axis=-1)), (_rng(key, shape, dtype),)


@register("add", OpGroup.ELEMENTWISE)
def _mk_add(shape, dtype, key):
    from repro import nn
    k1, k2 = jax.random.split(key)
    return nn.residual_add, (_rng(k1, shape, dtype), _rng(k2, shape, dtype))


@register("mul", OpGroup.ELEMENTWISE)
def _mk_mul(shape, dtype, key):
    k1, k2 = jax.random.split(key)
    return jnp.multiply, (_rng(k1, shape, dtype), _rng(k2, shape, dtype))


@register("true_div", OpGroup.ELEMENTWISE)
def _mk_div(shape, dtype, key):
    x = _rng(key, shape, dtype)
    return (lambda x: x / np.sqrt(shape[-1]).astype(np.float32)), (x,)


@register("neg", OpGroup.ELEMENTWISE)
def _mk_neg(shape, dtype, key):
    return jnp.negative, (_rng(key, shape, dtype),)


@register("reshape_permute", OpGroup.MEMORY)
def _mk_reshape(shape, dtype, key):
    x = _rng(key, shape, dtype)

    def f(x):
        # attention-style (B, S, H*D) -> (B, H, S, D) -> back; forces a copy
        b, s, e = x.shape[0], x.shape[1], int(np.prod(x.shape[2:]))
        h = max(1, e // 64)
        y = x.reshape(b, s, h, e // h).transpose(0, 2, 1, 3)
        return y.reshape(b, h, -1) + 0.0
    return f, (x,)


@register("concat_split", OpGroup.MEMORY)
def _mk_concat(shape, dtype, key):
    k1, k2 = jax.random.split(key)
    a, b = _rng(k1, shape, dtype), _rng(k2, shape, dtype)

    def f(a, b):
        c = jnp.concatenate([a, b], axis=-1)
        lo, hi = jnp.split(c, 2, axis=-1)
        return lo + hi
    return f, (a, b)


@register("rope", OpGroup.MEMORY)
def _mk_rope(shape, dtype, key):
    from repro import nn
    if len(shape) < 4:
        shape = (1, max(shape[0], 1), 8, 64)
    x = _rng(key, shape, dtype)
    pos = jnp.arange(shape[1])[None, :]
    return (lambda x: nn.apply_rope(x, pos)), (x,)


@register("cross_entropy", OpGroup.LOGIT)
def _mk_xent(shape, dtype, key):
    from repro import nn
    if len(shape) < 2:
        shape = (64, 32000)
    logits = _rng(key, shape, dtype)
    labels = jax.random.randint(key, shape[:-1], 0, shape[-1])
    return (lambda l: nn.softmax_cross_entropy(l, labels).mean()), (logits,)


@register("nms", OpGroup.ROI)
def _mk_nms(shape, dtype, key):
    from repro import nn
    n = shape[0] if shape else 1024
    k1, k2 = jax.random.split(key)
    centers = jax.random.uniform(k1, (n, 2)) * 100
    wh = jax.random.uniform(k2, (n, 2)) * 10 + 1
    boxes = jnp.concatenate([centers - wh / 2, centers + wh / 2], -1)
    scores = jax.random.uniform(key, (n,))
    return (lambda b, s: nn.nms(b, s, iou_threshold=0.5)), (boxes, scores)


@register("interpolate", OpGroup.INTERPOLATION)
def _mk_interp(shape, dtype, key):
    from repro import nn
    if len(shape) != 4:
        shape = (2, 256, 64, 64)
    x = _rng(key, shape, dtype)
    out_hw = (shape[2] * 2, shape[3] * 2)
    return (lambda x: nn.interpolate_bilinear(x, out_hw)), (x,)


@register("swiglu", OpGroup.ACTIVATION)
def _mk_swiglu(shape, dtype, key):
    from repro import nn
    k1, k2 = jax.random.split(key)
    return nn.swiglu, (_rng(k1, shape, dtype), _rng(k2, shape, dtype))


# --- fused operators (repro.core.fusion) — unfused twins sit above so the
# --- micro table shows each chain side by side with its fused rewrite


@register("add_rms_norm", OpGroup.NORMALIZATION)
def _mk_add_rms_norm(shape, dtype, key):
    """The unfused residual-add→rms_norm chain as one measurable site."""
    from repro import nn
    k1, k2 = jax.random.split(key)
    x, r = _rng(k1, shape, dtype), _rng(k2, shape, dtype)
    scale = jnp.ones((shape[-1],), dtype)
    return (lambda x, r: nn.add_rms_norm(x, r, scale)[0]), (x, r)


@register("fused_add_rms_norm", OpGroup.FUSED)
def _mk_fused_add_rms_norm(shape, dtype, key):
    from repro import nn
    k1, k2 = jax.random.split(key)
    x, r = _rng(k1, shape, dtype), _rng(k2, shape, dtype)
    scale = jnp.ones((shape[-1],), dtype)

    def f(x, r):
        with nn.fuse():
            return nn.add_rms_norm(x, r, scale)[0]
    return f, (x, r)


@register("fused_rope", OpGroup.FUSED)
def _mk_fused_rope(shape, dtype, key):
    from repro import nn
    if len(shape) < 4:
        shape = (1, max(shape[0], 1), 8, 64)
    x = _rng(key, shape, dtype)
    pos = jnp.arange(shape[1])[None, :]

    def f(x):
        with nn.fuse():
            return nn.apply_rope(x, pos)
    return f, (x,)


@register("fused_dequant_add_rms_norm", OpGroup.FUSED)
def _mk_fused_dequant_add_rms_norm(shape, dtype, key):
    """The QDQ epilogue: int8 operand in, one pass to the normed output."""
    from repro import nn
    k1, k2 = jax.random.split(key)
    q = jax.random.randint(k1, shape, -127, 128, jnp.int8)
    qs = jnp.float32(0.02)
    res = _rng(k2, shape, dtype)
    scale = jnp.ones((shape[-1],), dtype)
    return (lambda q, res: nn.dequant_add_rms_norm(q, qs, res, scale)[0]), \
        (q, res)


# --- attention template family (repro.kernels.attn_template) — one row per
# --- generated variant, so the kernel family is visible in the Table-2
# --- artifact and regression-gated by bench compare


def _attn_maker(variant: str, window: Optional[int] = None,
                decode: bool = False):
    """Micro maker for one generated attention variant.

    ``shape`` is (batch, kv_seq, heads, head_dim); the decode variant uses
    a single query row against the full KV depth.  The rows are modeled
    and timed on the host, so the kernel runs in interpret mode.
    """
    def make(shape, dtype, key):
        from repro.kernels import attn_template
        b, s, h, d = shape
        k1, k2, k3 = jax.random.split(key, 3)
        k = _rng(k2, (b, s, h, d), dtype)
        v = _rng(k3, (b, s, h, d), dtype)
        fn = attn_template.get(variant)
        if decode:
            q = _rng(k1, (b, 1, h, d), dtype)
            lengths = jnp.full((b,), s, jnp.int32)
            return (lambda q, k, v, lengths:
                    fn(q, k, v, lengths, interpret=True)), (q, k, v, lengths)
        q = _rng(k1, shape, dtype)
        if window is not None:
            return (lambda q, k, v: fn(q, k, v, window=window,
                                       interpret=True)), (q, k, v)
        return (lambda q, k, v: fn(q, k, v, interpret=True)), (q, k, v)
    return make


for _name, _variant, _kw in (
        ("attn_template:causal:d64", "causal", {}),
        ("attn_template:causal:d128", "causal", {}),
        ("attn_template:full:d64", "full", {}),
        ("attn_template:full:d128", "full", {}),
        ("attn_template:window64:d64", "window", {"window": 64}),
        ("attn_template:window256:d64", "window", {"window": 256}),
        ("attn_template:decode:d64", "decode", {"decode": True}),
        ("attn_template:decode:d128", "decode", {"decode": True}),
):
    register(_name, OpGroup.FUSED)(_attn_maker(_variant, **_kw))
del _name, _variant, _kw


#: Paper Table 2 example shapes (the realistic defaults).
TABLE2_SHAPES: Dict[str, tuple] = {
    "relu": (2, 64, 533),
    "gelu": (1, 8, 6400),          # GPT2-XL row
    "silu": (1, 10, 11008),        # Llama-2 row
    "layer_norm": (2, 16384, 32),  # Segformer row
    "rms_norm": (1, 10, 4096),     # LlamaRMSNorm row
    "add": (2, 16384, 32),
    "mul": (1, 10, 11008),
    "neg": (1, 32, 10, 64),
    "true_div": (2, 1, 16384, 256),
    "reshape_permute": (1, 8, 1600),
    "concat_split": (1, 8, 2400),
    "softmax": (2, 1, 16384, 256),
    "nms": (4663, 4),
    "interpolate": (2, 256, 64, 64),
    "rope": (1, 128, 32, 128),
    "cross_entropy": (256, 32000),
    "swiglu": (1, 10, 11008),
    # fused operators next to their unfused twins (repro.core.fusion)
    "add_rms_norm": (1, 10, 4096),
    "fused_add_rms_norm": (1, 10, 4096),
    "fused_rope": (1, 128, 32, 128),
    "fused_dequant_add_rms_norm": (1, 10, 4096),
    # generated attention variants (repro.kernels.attn_template): one row
    # per template over head dims {64, 128} and window sizes; shape is
    # (batch, kv_seq, heads, head_dim)
    "attn_template:causal:d64": (1, 256, 8, 64),
    "attn_template:causal:d128": (1, 256, 8, 128),
    "attn_template:full:d64": (1, 256, 8, 64),
    "attn_template:full:d128": (1, 256, 8, 128),
    "attn_template:window64:d64": (1, 512, 8, 64),
    "attn_template:window256:d64": (1, 512, 8, 64),
    "attn_template:decode:d64": (4, 512, 8, 64),
    "attn_template:decode:d128": (4, 512, 8, 128),
}


def _model_tpu_us(args, out, hw: HardwareSpec,
                  group: str = None) -> tuple[float, float]:
    leaves = jax.tree_util.tree_leaves((args, out))
    nbytes = float(sum(np.prod(l.shape) * dtype_bytes(l.dtype) for l in leaves))
    if group is not None:
        # group-aware effective bandwidth; identical to hbm_bw for specs
        # without an efficiency table (tpu_v5e/a100/cpu)
        return 1e6 * hw.group_mem_time(group, nbytes), nbytes
    return 1e6 * nbytes / hw.hbm_bw, nbytes


def run_micro(name: str, shape: Optional[tuple] = None,
              dtype: str = "float32", repeats: int = 20,
              hw: HardwareSpec = TPU_V5E,
              measure_eager: bool = True) -> MicroResult:
    op = _REGISTRY[name]
    shape = tuple(shape or TABLE2_SHAPES.get(name, (1, 1024, 1024)))
    key = jax.random.PRNGKey(0)
    fn, args = op.make(shape, jnp.dtype(dtype), key)
    jit_s = _wallclock(fn, *args, repeats=repeats)
    eager_us = 0.0
    if measure_eager:
        ops = ProfilingInterpreter(repeats=3).run(fn, *args)
        eager_us = 1e6 * sum(t.seconds for t in ops)
    out = jax.jit(fn)(*args)
    tpu_us, nbytes = _model_tpu_us(args, out, hw, group=op.group.value)
    return MicroResult(name=name, group=op.group.value, shape=shape,
                       dtype=str(dtype), jit_us=jit_s * 1e6,
                       eager_us=eager_us, tpu_model_us=tpu_us,
                       bytes_touched=nbytes)


def run_suite(names: Optional[Sequence[str]] = None,
              repeats: int = 10) -> list[MicroResult]:
    names = list(names or TABLE2_SHAPES.keys())
    return [run_micro(n, repeats=repeats) for n in names]
