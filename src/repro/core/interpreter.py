"""Profiling Interpreter — the paper's FX-Interpreter + torch.profiler analogue.

NonGEMM Bench (§3.2.2) executes the captured graph node-by-node in eager mode,
instrumenting each node. Here we walk the jaxpr and ``bind`` each primitive
individually, wall-timing every op (``block_until_ready`` per op). This is the
*unaccelerated eager* view of a model: each operator dispatches as its own
kernel, exactly like PyTorch eager on CPU in the paper's CPU case studies.

Higher-order primitives in :data:`~repro.core.taxonomy.INLINE_PRIMS` are
inlined so a ``jax.nn.gelu`` (a ``jit`` eqn) is timed as its constituent
primitives under the enclosing ``ng:`` scope. ``scan``/``while``/``cond`` are
timed opaquely as single CONTROL (or scope-tagged) records — matching how the
paper times an FX node whose module contains a loop.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Callable

import jax
from jax._src import core as _core

from .graph import (OpRecord, _aval_shape_dtype, estimate_bytes,
                    estimate_flops)
from .taxonomy import INLINE_PRIMS, OpGroup, classify


@dataclasses.dataclass
class TimedOp:
    record: OpRecord
    seconds: float              # best-of-repeats wall time for one execution

    @property
    def group(self) -> OpGroup:
        return self.record.group


def _read(v, env):
    return v.val if isinstance(v, _core.Literal) else env[v]


def _block(x):
    return jax.block_until_ready(x)


class ProfilingInterpreter:
    """Eqn-by-eqn timed evaluation of a traced function."""

    def __init__(self, repeats: int = 3, warmup: int = 1):
        self.repeats = repeats
        self.warmup = warmup

    # -- core walk -----------------------------------------------------
    def _run_jaxpr(self, jaxpr: _core.Jaxpr, consts, args, scope_prefix: str,
                   timings: dict, counter: list):
        env: dict = {}
        for v, c in zip(jaxpr.constvars, consts):
            env[v] = c
        for v, a in zip(jaxpr.invars, args):
            env[v] = a

        for eqn in jaxpr.eqns:
            prim = eqn.primitive.name
            stack = str(eqn.source_info.name_stack)
            scope = "/".join(p for p in (scope_prefix, stack) if p)
            invals = [_read(v, env) for v in eqn.invars]

            if prim in INLINE_PRIMS:
                sub = None
                for key in ("jaxpr", "call_jaxpr", "fun_jaxpr"):
                    if key in eqn.params:
                        sub = eqn.params[key]
                        break
                if sub is not None:
                    if isinstance(sub, _core.ClosedJaxpr):
                        sub_jaxpr, sub_consts = sub.jaxpr, sub.consts
                    else:
                        sub_jaxpr, sub_consts = sub, ()
                    # custom_jvp/vjp pass extra rule args before operands
                    n_in = len(sub_jaxpr.invars)
                    outs = self._run_jaxpr(sub_jaxpr, sub_consts,
                                           invals[-n_in:] if n_in else [],
                                           scope, timings, counter)
                    outs = list(outs)
                    for v, o in zip(eqn.outvars, outs):
                        env[v] = o
                    continue

            subfuns, bind_params = eqn.primitive.get_bind_params(eqn.params)

            def run_once():
                ans = eqn.primitive.bind(*subfuns, *invals, **bind_params)
                _block(ans)
                return ans

            ans = run_once()  # also serves as warmup / correctness value
            best = float("inf")
            for _ in range(self.repeats):
                t0 = time.perf_counter()
                run_once()
                best = min(best, time.perf_counter() - t0)

            in_sd = [_aval_shape_dtype(v) for v in eqn.invars]
            out_sd = [_aval_shape_dtype(v) for v in eqn.outvars]
            in_shapes = tuple(s for s, _ in in_sd)
            in_dtypes = tuple(d for _, d in in_sd)
            out_shapes = tuple(s for s, _ in out_sd)
            out_dtypes = tuple(d for _, d in out_sd)
            group, op_site = classify(prim, scope)
            rec = OpRecord(
                index=counter[0], prim=prim, group=group, op_site=op_site,
                scope=scope, in_shapes=in_shapes, in_dtypes=in_dtypes,
                out_shapes=out_shapes, out_dtypes=out_dtypes,
                flops=estimate_flops(prim, eqn.params, in_shapes, out_shapes),
                bytes_accessed=estimate_bytes(in_shapes, in_dtypes,
                                              out_shapes, out_dtypes, prim),
                in_var_ids=tuple(id(v) for v in eqn.invars
                                 if not isinstance(v, _core.Literal)),
                out_var_ids=tuple(id(v) for v in eqn.outvars),
            )
            counter[0] += 1
            timings.setdefault("ops", []).append(TimedOp(rec, best))

            outs = ans if eqn.primitive.multiple_results else [ans]
            for v, o in zip(eqn.outvars, outs):
                env[v] = o

        return [_read(v, env) for v in jaxpr.outvars]

    # -- public API ----------------------------------------------------
    def run(self, fn: Callable, *args, **kwargs) -> list[TimedOp]:
        closed = jax.make_jaxpr(fn)(*args, **kwargs)
        flat_args = jax.tree_util.tree_leaves((args, kwargs))
        timings: dict = {}
        self._run_jaxpr(closed.jaxpr, closed.consts, flat_args, "",
                        timings, [0])
        return timings.get("ops", [])
