"""Graph capture — the torch.fx analogue (paper §3.2.1, "Frontend").

``capture(fn, *args)`` traces ``fn`` with concrete inputs (exactly like the
paper, which feeds preprocessed inputs to the tracer so input-dependent
control flow resolves) and flattens the jaxpr into a list of
:class:`OpRecord`, one per primitive, each attributed to an operator group
via the ``ng:`` scope tags emitted by ``repro.nn`` (falling back to the
primitive-name taxonomy).

Higher-order primitives (``jit``, ``custom_jvp_call``, ``remat`` ...) are
inlined recursively; ``scan``/``while``/``cond`` bodies are descended into as
well, with a ``trip_count`` multiplier recorded so FLOP/byte totals are
loop-aware.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Callable, Iterable, Sequence

import jax
import numpy as np
from jax._src import core as _core

from .taxonomy import COLLECTIVE_PRIMS, INLINE_PRIMS, OpGroup, classify

_DTYPE_BYTES = {
    "float32": 4, "float64": 8, "float16": 2, "bfloat16": 2,
    "int64": 8, "int32": 4, "int16": 2, "int8": 1,
    "uint64": 8, "uint32": 4, "uint16": 2, "uint8": 1,
    "bool": 1, "complex64": 8, "complex128": 16,
    "float8_e4m3fn": 1, "float8_e5m2": 1, "float8_e4m3b11_fnuz": 1,
    "float8_e4m3": 1, "float8_e5m2fnuz": 1, "float8_e4m3fnuz": 1,
    "float4_e2m1fn": 1,
}


def dtype_bytes(dtype: Any) -> int:
    return _DTYPE_BYTES.get(str(np.dtype(dtype).name) if not isinstance(dtype, str) else dtype,
                            _DTYPE_BYTES.get(str(dtype), 4))


@dataclasses.dataclass
class OpRecord:
    """One captured operator (jaxpr primitive) occurrence."""

    index: int
    prim: str
    group: OpGroup
    op_site: str            # semantic operator name from the ng: tag (or prim)
    scope: str              # full name-stack path
    in_shapes: tuple
    in_dtypes: tuple
    out_shapes: tuple
    out_dtypes: tuple
    flops: float            # analytic estimate, trip-count weighted
    bytes_accessed: float   # inputs+outputs, trip-count weighted
    trip_count: int = 1
    params: dict = dataclasses.field(default_factory=dict, repr=False)
    #: jaxpr-var identities (id() ints, literals excluded) — only meaningful
    #: within one captured stream; the fusion pass uses them for an exact
    #: producer->consumer dataflow check instead of a shape heuristic
    in_var_ids: tuple = dataclasses.field(default=(), repr=False)
    out_var_ids: tuple = dataclasses.field(default=(), repr=False)

    @property
    def is_gemm(self) -> bool:
        return self.group == OpGroup.GEMM


def _aval_shape_dtype(v) -> tuple:
    aval = v.aval
    shape = tuple(getattr(aval, "shape", ()) or ())
    dtype = str(getattr(aval, "dtype", "float32"))
    return shape, dtype


def _numel(shape: Sequence[int]) -> int:
    return int(np.prod(shape)) if shape else 1


def estimate_flops(prim: str, params: dict, in_shapes, out_shapes) -> float:
    """Analytic per-primitive FLOP estimate (paper reports FLOPs per op)."""
    if prim == "dot_general":
        dn = params.get("dimension_numbers")
        if dn is None or not in_shapes or len(in_shapes) < 2:
            return 0.0
        (lc, rc), (lb, rb) = dn
        lhs, rhs = in_shapes[0], in_shapes[1]
        batch = _numel([lhs[i] for i in lb])
        contract = _numel([lhs[i] for i in lc])
        m = _numel([d for i, d in enumerate(lhs) if i not in set(lc) | set(lb)])
        n = _numel([d for i, d in enumerate(rhs) if i not in set(rc) | set(rb)])
        return 2.0 * batch * m * n * contract
    if prim == "conv_general_dilated":
        # 2 * out_numel * (in_channels/groups) * prod(kernel_spatial)
        if len(in_shapes) < 2 or not out_shapes:
            return 0.0
        rhs = in_shapes[1]
        out = out_shapes[0]
        groups = params.get("feature_group_count", 1)
        k_spatial = _numel(rhs[2:]) if len(rhs) > 2 else 1
        cin = rhs[1] if len(rhs) > 1 else 1
        return 2.0 * _numel(out) * cin * k_spatial / max(groups, 1)
    if prim.startswith("reduce_") or prim in ("cumsum", "cumprod", "cummax", "cummin"):
        return float(_numel(in_shapes[0])) if in_shapes else 0.0
    if prim in ("tanh", "logistic", "erf", "exp", "log", "rsqrt", "sqrt", "pow"):
        # transcendentals cost a handful of flops each
        return 8.0 * _numel(out_shapes[0]) if out_shapes else 0.0
    if prim in ("sort", "top_k"):
        n = _numel(in_shapes[0]) if in_shapes else 0
        return float(n) * max(1.0, math.log2(max(n, 2)))
    # default: one flop per output element for arithmetic, zero for memory ops
    from .taxonomy import classify_primitive

    g = classify_primitive(prim)
    if g in (OpGroup.ELEMENTWISE, OpGroup.NORMALIZATION, OpGroup.ACTIVATION):
        return float(_numel(out_shapes[0])) if out_shapes else 0.0
    if g == OpGroup.REDUCTION:
        # argmax / select_and_scatter_add / reduce_window variants that don't
        # spell "reduce_": every input element is touched at least once
        return float(_numel(in_shapes[0])) if in_shapes else 0.0
    return 0.0


#: indexed reads touch only slice-sized data, not their full operand
_SLICING_PRIMS = frozenset({"gather", "dynamic_slice", "slice",
                            "dynamic_update_slice", "scatter",
                            "scatter-add", "scatter_add"})


def estimate_bytes(in_shapes, in_dtypes, out_shapes, out_dtypes,
                   prim: str = "") -> float:
    out_total = sum(_numel(s) * dtype_bytes(d)
                    for s, d in zip(out_shapes, out_dtypes))
    if prim in _SLICING_PRIMS:
        # read touched rows + indices, write output (update-sized)
        idx = sum(_numel(s) * dtype_bytes(d)
                  for s, d in zip(in_shapes[1:], in_dtypes[1:]))
        return 2.0 * out_total + idx
    in_total = sum(_numel(s) * dtype_bytes(d)
                   for s, d in zip(in_shapes, in_dtypes))
    if prim in COLLECTIVE_PRIMS:
        # link bytes per device, ring-style: an all-reduce sends and
        # receives ~payload each (2(n-1)/n -> 2), an all-gather receives
        # the full result. in+out bounds both and is never zero, even for
        # axis_index (its scalar output still counts) — the COLLECTIVE
        # group is billed against link_bw, not HBM (profiler/roofline).
        return max(in_total + out_total, 1.0)
    return in_total + out_total


_LOOP_PRIMS = {"scan", "while", "cond"}

#: manual-partitioning higher-order prims: the body jaxpr runs per device
#: with per-shard avals, so descending records the per-device program —
#: the same per-device convention the roofline uses. Collectives inside
#: (psum / all_gather / ...) become first-class records.
_SHARD_MAP_PRIMS = {"shard_map", "smap"}


def _walk(jaxpr: _core.Jaxpr, records: list, scope_prefix: str, trip: int,
          counter: list) -> None:
    for eqn in jaxpr.eqns:
        prim = eqn.primitive.name
        stack = str(eqn.source_info.name_stack)
        scope = "/".join(p for p in (scope_prefix, stack) if p)

        sub_jaxprs: list[tuple[_core.Jaxpr, int]] = []
        if prim in INLINE_PRIMS or prim in _LOOP_PRIMS \
                or prim in _SHARD_MAP_PRIMS:
            mult = 1
            if prim == "scan":
                mult = int(eqn.params.get("length", 1))
            for pv in eqn.params.values():
                if isinstance(pv, _core.ClosedJaxpr):
                    sub_jaxprs.append((pv.jaxpr, mult))
                elif isinstance(pv, _core.Jaxpr):
                    sub_jaxprs.append((pv, mult))
                elif isinstance(pv, (tuple, list)):
                    for item in pv:
                        if isinstance(item, _core.ClosedJaxpr):
                            sub_jaxprs.append((item.jaxpr, mult))
                        elif isinstance(item, _core.Jaxpr):
                            sub_jaxprs.append((item, mult))
        if sub_jaxprs:
            for sub, mult in sub_jaxprs:
                _walk(sub, records, scope, trip * mult, counter)
            continue

        in_sd = [_aval_shape_dtype(v) for v in eqn.invars]
        out_sd = [_aval_shape_dtype(v) for v in eqn.outvars]
        in_shapes = tuple(s for s, _ in in_sd)
        in_dtypes = tuple(d for _, d in in_sd)
        out_shapes = tuple(s for s, _ in out_sd)
        out_dtypes = tuple(d for _, d in out_sd)
        group, op_site = classify(prim, scope)
        flops = estimate_flops(prim, eqn.params, in_shapes, out_shapes) * trip
        nbytes = estimate_bytes(in_shapes, in_dtypes, out_shapes, out_dtypes,
                                prim) * trip
        records.append(
            OpRecord(
                index=counter[0], prim=prim, group=group, op_site=op_site,
                scope=scope, in_shapes=in_shapes, in_dtypes=in_dtypes,
                out_shapes=out_shapes, out_dtypes=out_dtypes, flops=flops,
                bytes_accessed=nbytes, trip_count=trip,
                params=dict(eqn.params) if prim == "dot_general" else {},
                in_var_ids=tuple(id(v) for v in eqn.invars
                                 if not isinstance(v, _core.Literal)),
                out_var_ids=tuple(id(v) for v in eqn.outvars),
            )
        )
        counter[0] += 1


def capture(fn: Callable, *args, **kwargs) -> list[OpRecord]:
    """Trace ``fn`` and return the flattened, classified operator list."""
    closed = jax.make_jaxpr(fn)(*args, **kwargs)
    records: list[OpRecord] = []
    _walk(closed.jaxpr, records, "", 1, [0])
    return records


def harvest_shapes(records: Iterable[OpRecord]) -> dict:
    """Paper Table 2: realistic input shapes per NonGEMM op site.

    Returns ``{(group, op_site): [in_shapes, ...]}`` with duplicates removed,
    harvested from a real trace — the paper's "input argument specification
    extracted from real data".
    """
    out: dict = {}
    for r in records:
        key = (r.group.value, r.op_site)
        shapes = out.setdefault(key, [])
        if r.in_shapes not in shapes:
            shapes.append(r.in_shapes)
    return out
