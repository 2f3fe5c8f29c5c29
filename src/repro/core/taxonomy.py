"""Operator taxonomy — the paper's GEMM / NonGEMM operator groups.

NonGEMM Bench (§2.1.2, Table 2) classifies every operator in an ML graph by
*functionality*:

    GEMM                   dot products / convolutions / linear / BMM
    Normalization          LayerNorm / BatchNorm / RMSNorm / ...
    Activation             ReLU / GELU / SiLU / ...
    Memory                 reshape / view / permute / split / concat / gather ...
    Element-wise Arithmetic add / mul / neg / div / ...
    Logit Computation      softmax (and here: cross-entropy, router gating)
    RoI Selection          NMS and friends
    Interpolation          resize / interpolate

We add three JAX/TPU-native groups that the torch-eager paper did not need:

    Reduction              standalone reduce_{sum,max,...}, cum*, argmax
    Collective             all-gather / all-reduce / all-to-all / ppermute ...
    Control                scan / while / cond higher-order structure

plus the paper's quantization finding (§4.4: QDQ operators aggravate the
NonGEMM bottleneck) as its own bucket:

    Quantization           quantize / dequantize fake-quant ops inserted by
                           the int8 QDQ workload transform (repro.nn)

and the fusion finding (§6: operator fusion reduces but does not eliminate
the NonGEMM bottleneck) as a first-class attribution target:

    Fused                  NonGEMM chains rewritten into single Pallas-
                           kernel launches by the fusion pass
                           (repro.core.fusion) or executed through the
                           fused ``repro.nn`` fast path under ``nn.fuse()``.
                           Still NonGEMM work — the residual share after
                           fusion is exactly the paper's §6 number.

Classification has two sources, in priority order:

1. **Scope tags** — the `repro.nn` operator library wraps every semantic op in
   ``jax.named_scope(scope_tag(group, name))``. Tags survive into jaxpr
   ``eqn.source_info.name_stack`` and into compiled-HLO ``metadata op_name``,
   which is how both the eager interpreter and the HLO analyzer attribute
   work to operator groups. This mirrors the paper's FX-node (nn.Module)
   granularity.
2. **Primitive/opcode fallback** — untagged jaxpr primitives and HLO opcodes
   are classified structurally (``dot_general`` -> GEMM, ``reshape`` ->
   Memory, ...).
"""

from __future__ import annotations

import enum
import re
import warnings
from typing import Dict, Optional, Tuple


class OpGroup(str, enum.Enum):
    GEMM = "gemm"
    NORMALIZATION = "normalization"
    ACTIVATION = "activation"
    MEMORY = "memory"
    ELEMENTWISE = "elementwise"
    LOGIT = "logit"
    QUANT = "quantization"
    FUSED = "fused"
    ROI = "roi"
    INTERPOLATION = "interpolation"
    REDUCTION = "reduction"
    COLLECTIVE = "collective"
    CONTROL = "control"
    OTHER = "other"

    def __str__(self) -> str:  # pragma: no cover - cosmetic
        return self.value


#: The paper's NonGEMM umbrella: everything that is not a GEMM and not pure
#: program structure. Collectives are reported separately (they are a
#: distributed-systems cost, not an operator cost in the paper's sense).
NONGEMM_GROUPS = frozenset(
    {
        OpGroup.NORMALIZATION,
        OpGroup.ACTIVATION,
        OpGroup.MEMORY,
        OpGroup.ELEMENTWISE,
        OpGroup.LOGIT,
        OpGroup.QUANT,
        OpGroup.FUSED,
        OpGroup.ROI,
        OpGroup.INTERPOLATION,
        OpGroup.REDUCTION,
        OpGroup.OTHER,
    }
)

_TAG_PREFIX = "ng:"
_TAG_RE = re.compile(r"ng:([a-z_]+):([A-Za-z0-9_.\-]+)")

_GROUP_BY_VALUE = {g.value: g for g in OpGroup}


def scope_tag(group: OpGroup | str, name: str) -> str:
    """Build the named_scope tag for an operator site."""
    g = group.value if isinstance(group, OpGroup) else str(group)
    if g not in _GROUP_BY_VALUE:
        raise ValueError(f"unknown operator group {g!r}")
    return f"{_TAG_PREFIX}{g}:{name}"


def parse_scope(scope_path: str) -> Optional[Tuple[OpGroup, str]]:
    """Extract the innermost ``ng:<group>:<name>`` tag from a scope path."""
    matches = _TAG_RE.findall(scope_path or "")
    if not matches:
        return None
    g, name = matches[-1]  # innermost tag wins
    group = _GROUP_BY_VALUE.get(g)
    if group is None:
        return None
    return group, name


# --------------------------------------------------------------------------
# jaxpr primitive name -> group (fallback when no scope tag is present)
# --------------------------------------------------------------------------

_PRIM_GROUPS: dict[str, OpGroup] = {}


def _reg(group: OpGroup, *names: str) -> None:
    for n in names:
        _PRIM_GROUPS[n] = group


_reg(OpGroup.GEMM, "dot_general", "conv_general_dilated", "ragged_dot")
_reg(
    OpGroup.ACTIVATION,
    "tanh", "logistic", "erf", "erfc", "erf_inv",
)
_reg(OpGroup.NORMALIZATION, "rsqrt")
_reg(
    OpGroup.MEMORY,
    "reshape", "transpose", "broadcast_in_dim", "concatenate", "slice",
    "dynamic_slice", "dynamic_update_slice", "gather", "scatter",
    "scatter-add", "scatter_add", "scatter_mul", "scatter_min", "scatter_max",
    "pad", "squeeze", "rev", "copy", "convert_element_type",
    "bitcast_convert_type", "iota", "split", "expand_dims",
    # jax's identity marker primitive (jax.nn wraps e.g. softmax/einsum
    # results in name_p); compiles away like copy does
    "name",
)
_reg(
    OpGroup.ELEMENTWISE,
    "add", "sub", "mul", "div", "neg", "max", "min", "pow", "integer_pow",
    "abs", "sign", "floor", "ceil", "round", "rem", "exp", "exp2", "log",
    "log1p", "expm1", "sqrt", "cbrt", "square", "and", "or", "xor", "not",
    "select_n", "clamp", "nextafter", "is_finite", "eq", "ne", "lt", "le",
    "gt", "ge", "atan2", "sin", "cos", "real", "imag", "complex", "conj",
    "stop_gradient",
)
_reg(
    OpGroup.REDUCTION,
    # the whole cum* family lives here, matching the module doc: a scan
    # over a reduction operator is a reduction, not element-wise work
    "reduce_sum", "reduce_max", "reduce_min", "reduce_prod", "reduce_and",
    "reduce_or", "argmax", "argmin", "reduce_precision", "cumsum", "cumprod",
    "cummax", "cummin", "cumlogsumexp", "top_k", "sort",
    # pooling / windowed reductions (max_pool, avg_pool, and the max-pool
    # gradient's scatter) — a reduction over a sliding window is still a
    # reduction, per the module doc
    "reduce_window", "reduce_window_sum", "reduce_window_max",
    "reduce_window_min", "select_and_scatter_add",
)
_reg(
    OpGroup.COLLECTIVE,
    # jax.lax.psum binds "psum" in a shard_map body with check_vma=False
    # and "psum_invariant" under the strict check; "reshard" moves an array
    # to another sharding
    "psum", "psum_invariant", "all_gather", "all_to_all", "ppermute",
    "pmax", "pmin", "psum_scatter", "reduce_scatter", "axis_index",
    "pbroadcast", "reshard",
)

#: Every jaxpr primitive registered under COLLECTIVE — the set the capture
#: path (core/graph.py) and nglint NG010 use to recognize communication ops
#: structurally (the ng:collective scope tag is still the preferred source).
COLLECTIVE_PRIMS = frozenset(
    n for n, g in _PRIM_GROUPS.items() if g is OpGroup.COLLECTIVE
)
_reg(
    OpGroup.CONTROL,
    "scan", "while", "cond", "jit", "closed_call", "core_call", "remat",
    "checkpoint", "custom_jvp_call", "custom_vjp_call",
    "custom_jvp_call_jaxpr", "custom_vjp_call_jaxpr", "custom_lin",
    "shard_map", "smap", "named_call", "pvary",
)
# a pallas_call appearing untagged in a capture is a hand-written fused
# kernel (e.g. an attn_template variant) invoked outside its scope tag
_reg(OpGroup.FUSED, "pallas_call")


#: Higher-order primitives the eager interpreter descends into (inlining
#: their sub-jaxpr under the parent scope) rather than timing opaquely.
INLINE_PRIMS = frozenset(
    {
        "jit", "closed_call", "core_call", "named_call", "remat",
        "checkpoint", "custom_jvp_call", "custom_vjp_call",
        "custom_jvp_call_jaxpr", "custom_vjp_call_jaxpr",
    }
)


#: Primitives that fell through to ``OpGroup.OTHER`` because no ``_reg``
#: entry covers them, with the number of times each was classified. PR 5
#: shipped pooling misbinned as OTHER because this fallback was silent;
#: nglint rule NG001 and the warn-once below make it observable.
UNKNOWN_PRIMS: Dict[str, int] = {}

_WARNED_UNKNOWN: set = set()


def is_known_primitive(prim_name: str) -> bool:
    """True if the primitive has an explicit ``_PRIM_GROUPS`` entry."""
    return prim_name in _PRIM_GROUPS


def lookup_primitive(prim_name: str) -> Optional[OpGroup]:
    """``_PRIM_GROUPS`` lookup *without* the unknown-primitive accounting.

    For introspection (nglint) — unlike :func:`classify_primitive` it
    neither records the miss in :data:`UNKNOWN_PRIMS` nor warns.
    """
    return _PRIM_GROUPS.get(prim_name)


def classify_primitive(prim_name: str) -> OpGroup:
    group = _PRIM_GROUPS.get(prim_name)
    if group is None:
        UNKNOWN_PRIMS[prim_name] = UNKNOWN_PRIMS.get(prim_name, 0) + 1
        if prim_name not in _WARNED_UNKNOWN:
            _WARNED_UNKNOWN.add(prim_name)
            warnings.warn(
                f"primitive {prim_name!r} is not registered in the operator "
                "taxonomy and was binned to OpGroup.OTHER; add it to "
                "_PRIM_GROUPS in repro/core/taxonomy.py "
                "(nglint NG001 flags these records)",
                stacklevel=2,
            )
        return OpGroup.OTHER
    return group


def classify(prim_name: str, scope_path: str = "") -> Tuple[OpGroup, str]:
    """Classify an op, preferring the semantic scope tag over the primitive.

    Returns ``(group, op_site_name)``; untagged ops use the primitive name as
    the site name.
    """
    tagged = parse_scope(scope_path)
    if tagged is not None:
        return tagged
    return classify_primitive(prim_name), prim_name


# --------------------------------------------------------------------------
# HLO opcode -> group (fallback for the compiled-graph analyzer)
# --------------------------------------------------------------------------

COLLECTIVE_OPCODES = frozenset(
    {
        "all-gather", "all-gather-start", "all-gather-done",
        "all-reduce", "all-reduce-start", "all-reduce-done",
        "reduce-scatter",
        "all-to-all", "ragged-all-to-all",
        "collective-permute", "collective-permute-start",
        "collective-permute-done", "collective-broadcast",
    }
)

_HLO_OPCODE_GROUPS: dict[str, OpGroup] = {
    "dot": OpGroup.GEMM,
    "convolution": OpGroup.GEMM,
    "tanh": OpGroup.ACTIVATION,
    "logistic": OpGroup.ACTIVATION,
    "erf": OpGroup.ACTIVATION,
    "rsqrt": OpGroup.NORMALIZATION,
    "reshape": OpGroup.MEMORY,
    "transpose": OpGroup.MEMORY,
    "broadcast": OpGroup.MEMORY,
    "concatenate": OpGroup.MEMORY,
    "slice": OpGroup.MEMORY,
    "dynamic-slice": OpGroup.MEMORY,
    "dynamic-update-slice": OpGroup.MEMORY,
    "gather": OpGroup.MEMORY,
    "scatter": OpGroup.MEMORY,
    "pad": OpGroup.MEMORY,
    "copy": OpGroup.MEMORY,
    "copy-start": OpGroup.MEMORY,
    "copy-done": OpGroup.MEMORY,
    "convert": OpGroup.MEMORY,
    "bitcast": OpGroup.MEMORY,
    "bitcast-convert": OpGroup.MEMORY,
    "iota": OpGroup.MEMORY,
    "reduce": OpGroup.REDUCTION,
    "reduce-window": OpGroup.REDUCTION,
    "select-and-scatter": OpGroup.REDUCTION,  # max-pool gradient
    "sort": OpGroup.REDUCTION,
    "add": OpGroup.ELEMENTWISE,
    "subtract": OpGroup.ELEMENTWISE,
    "multiply": OpGroup.ELEMENTWISE,
    "divide": OpGroup.ELEMENTWISE,
    "negate": OpGroup.ELEMENTWISE,
    "maximum": OpGroup.ELEMENTWISE,
    "minimum": OpGroup.ELEMENTWISE,
    "exponential": OpGroup.ELEMENTWISE,
    "log": OpGroup.ELEMENTWISE,
    "power": OpGroup.ELEMENTWISE,
    "sqrt": OpGroup.ELEMENTWISE,
    "abs": OpGroup.ELEMENTWISE,
    "select": OpGroup.ELEMENTWISE,
    "compare": OpGroup.ELEMENTWISE,
    "clamp": OpGroup.ELEMENTWISE,
    "while": OpGroup.CONTROL,
    "conditional": OpGroup.CONTROL,
    "call": OpGroup.CONTROL,
    "tuple": OpGroup.CONTROL,
    "get-tuple-element": OpGroup.CONTROL,
    "parameter": OpGroup.CONTROL,
    "constant": OpGroup.CONTROL,
    "after-all": OpGroup.CONTROL,
    "partition-id": OpGroup.CONTROL,
    "replica-id": OpGroup.CONTROL,
    "rng-bit-generator": OpGroup.OTHER,
    "fusion": OpGroup.OTHER,  # refined by metadata / fused-root inspection
}


def classify_hlo(opcode: str, op_name: str = "") -> Tuple[OpGroup, str]:
    """Classify a compiled-HLO instruction.

    ``op_name`` is the instruction's ``metadata op_name`` string, which carries
    the jax name-stack (and therefore our ``ng:`` tags) through compilation.
    """
    tagged = parse_scope(op_name)
    if tagged is not None:
        return tagged
    if opcode in COLLECTIVE_OPCODES:
        return OpGroup.COLLECTIVE, opcode
    group = _HLO_OPCODE_GROUPS.get(opcode)
    if group is not None:
        return group, opcode
    # XLA fusions without a tag: fall back to the op_name tail, which XLA
    # sets from the representative (usually root) op of the fusion.
    tail = (op_name or "").rsplit("/", 1)[-1]
    prim_group = _PRIM_GROUPS.get(tail)
    if prim_group is not None:
        return prim_group, tail
    return OpGroup.OTHER, opcode


def is_gemm(group: OpGroup) -> bool:
    return group == OpGroup.GEMM


def is_nongemm(group: OpGroup) -> bool:
    return group in NONGEMM_GROUPS
