"""From a profiler trace to device time per program, per ``ng:`` group and site.

Reads the ``.xplane.pb`` that ``jax.profiler`` writes with
``jax.profiler.ProfileData``. On a TPU each event of a device plane's
``XLA Ops`` line is named by its HLO instruction's text
(``%fusion.3 = bf16[8,2560]{..} fusion(..), kind=kLoop, ..``) and carries
no ``op_name``; the instruction's ``op_name`` metadata, read from the
compiled program's HLO text, holds the ``ng:<group>:<name>`` scope that the
program's ``repro.nn`` ops put on it. Ops are matched to programs by the
module execution that holds them and to groups by that scope, never by a
kernel's symbol. Where an op has no ``ng:`` scope, its opcode decides, and
for an untagged fusion the primitive that ends its op_name, as in the
taxonomy's fallback (a copy of its tables is kept here so that the
yardstick does not move with the program).

The traced part is the span between the ``window.start`` and
``window.end`` host annotations the benchmark writes; the benchmark stops
the profiler one engine step after ``window.end``, so a whole trace holds
device ops past it. A trace the profiler cut short holds none: the window
the metrics read then ends at its last device op. Busy time is the union of
the device op intervals inside the window; each idle gap is put down to the
benchmark's host span (``submit``, ``engine.step``, ``bookkeeping``,
``idle``) that covers the gap's midpoint.
"""

from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

#: program (jitted function) names of the engine's step programs
DECODE = "paged_step"
ADMIT = ("prefill_step", "_scatter_cold_prefill", "extend_step")
#: the decode attention kernel's scope, as group:name
ATTN_DECODE = "fused:fused_attn_decode"
HOST_SPANS = ("submit", "engine.step", "bookkeeping", "idle")
#: the device plane's lines of program executions and of HLO ops
MODULE_LINE = "XLA Modules"
OP_LINE = "XLA Ops"

_TAG_RE = re.compile(r"ng:([a-z_]+):([A-Za-z0-9_.\-]+)")
_META_RE = re.compile(r'op_name="([^"]*)"')
_INSTR_RE = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s+=\s+\S.*?\s([a-z][a-z0-9\-]*)\(")

# copy of the opcode fallback of repro.core.taxonomy (HLO opcode -> group)
_OPCODE_GROUPS = {
    "dot": "gemm", "convolution": "gemm",
    "tanh": "activation", "logistic": "activation", "erf": "activation",
    "rsqrt": "normalization",
    "reduce": "reduction", "reduce-window": "reduction", "sort": "reduction",
    "select-and-scatter": "reduction",
    "while": "control", "conditional": "control", "call": "control",
    "all-gather": "collective", "all-reduce": "collective",
    "reduce-scatter": "collective", "all-to-all": "collective",
    "collective-permute": "collective",
}
for _op in ("reshape", "transpose", "broadcast", "concatenate", "slice",
            "dynamic-slice", "dynamic-update-slice", "gather", "scatter",
            "pad", "copy", "copy-start", "copy-done", "convert", "bitcast",
            "bitcast-convert", "iota"):
    _OPCODE_GROUPS[_op] = "memory"
for _op in ("add", "subtract", "multiply", "divide", "negate", "maximum",
            "minimum", "exponential", "log", "power", "sqrt", "abs",
            "select", "compare", "clamp"):
    _OPCODE_GROUPS[_op] = "elementwise"

# copy of its primitive table, for an untagged fusion: XLA names it after
# its representative op, the last part of its op_name
_PRIM_GROUPS = {"dot_general": "gemm", "conv_general_dilated": "gemm",
                "ragged_dot": "gemm", "rsqrt": "normalization"}
for _groups, _names in (
        ("activation", "tanh logistic erf erfc erf_inv"),
        ("memory", "reshape transpose broadcast_in_dim concatenate slice "
                   "dynamic_slice dynamic_update_slice gather scatter "
                   "scatter-add scatter_add scatter_mul scatter_min "
                   "scatter_max pad squeeze rev copy convert_element_type "
                   "bitcast_convert_type iota split expand_dims name"),
        ("elementwise", "add sub mul div neg max min pow integer_pow abs "
                        "sign floor ceil round rem exp exp2 log log1p expm1 "
                        "sqrt cbrt square and or xor not select_n clamp "
                        "nextafter is_finite eq ne lt le gt ge atan2 sin cos "
                        "real imag complex conj stop_gradient"),
        ("reduction", "reduce_sum reduce_max reduce_min reduce_prod "
                      "reduce_and reduce_or argmax argmin reduce_precision "
                      "cumsum cumprod cummax cummin cumlogsumexp top_k sort "
                      "reduce_window reduce_window_sum reduce_window_max "
                      "reduce_window_min select_and_scatter_add"),
        ("collective", "psum psum_invariant all_gather all_to_all ppermute "
                       "pmax pmin psum_scatter reduce_scatter axis_index "
                       "pbroadcast reshard")):
    for _n in _names.split():
        _PRIM_GROUPS[_n] = _groups


def program_of(module: str) -> str:
    """``jit_paged_step(12)`` -> ``paged_step``."""
    m = module.split("(", 1)[0]
    return m[4:] if m.startswith("jit_") else m


def classify(op_name: Optional[str], opcode: str) -> Tuple[str, str]:
    """(group, site) from the innermost ``ng:`` tag, else from the opcode,
    else (an untagged fusion) from the primitive that ends its op_name."""
    tags = _TAG_RE.findall(op_name or "")
    if tags:
        return tags[-1]
    base = opcode[:-6] if opcode.endswith("-start") else opcode
    if base in _OPCODE_GROUPS:
        return _OPCODE_GROUPS[base], opcode
    tail = (op_name or "").rsplit("/", 1)[-1]
    if tail in _PRIM_GROUPS:
        return _PRIM_GROUPS[tail], tail
    return "other", opcode


def hlo_op_names(hlo_text: str) -> Dict[str, Tuple[str, str]]:
    """instruction name -> (opcode, op_name metadata) of a compiled module."""
    out = {}
    for line in hlo_text.splitlines():
        m = _INSTR_RE.match(line)
        if m:
            meta = _META_RE.search(line)
            out[m.group(1)] = (m.group(2), meta.group(1) if meta else "")
    return out


def event_op(name: str) -> Tuple[str, str]:
    """(instruction, opcode) of an op event named by its instruction's text
    (``%copy.8 = bf16[4]{0} copy(..)``) or by the instruction alone
    (``copy.8``)."""
    m = _INSTR_RE.match(name)
    if m:
        return m.group(1), m.group(2)
    return name, re.sub(r"\.\d+$", "", name)


@dataclasses.dataclass(slots=True)
class Op:
    program: str
    name: str
    group: str
    site: str
    start: int
    end: int
    #: the op's own time: its span less the ops nested in it on its line
    #: (a loop op spans its body's ops); ``None`` means the whole span
    own: Optional[int] = None


@dataclasses.dataclass
class Reduction:
    #: what the metrics read: the traced part, up to the last device op
    window: Tuple[int, int]
    ops: List[Op]
    runs: List[Tuple[str, int, int]]        # (program, start, end) executions
    host: List[Tuple[str, int, int]]
    #: ``window.start`` to ``window.end``, and to the last device op
    traced_s: float = 0.0
    covered_s: float = 0.0

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9

    def _clip(self, op: Op) -> float:
        """The op's own time that falls inside the window."""
        inside = max(0, min(op.end, self.window[1])
                     - max(op.start, self.window[0]))
        span = op.end - op.start
        if op.own is None or span <= 0:
            return inside
        return op.own * inside / span

    @property
    def busy_s(self) -> float:
        """Union of the device op intervals inside the window."""
        total, cur_s, cur_e = 0, None, None
        for op in sorted(self.ops, key=lambda o: o.start):
            s = max(op.start, self.window[0])
            e = min(op.end, self.window[1])
            if e <= s:
                continue
            if cur_e is None or s > cur_e:
                if cur_e is not None:
                    total += cur_e - cur_s
                cur_s, cur_e = s, e
            else:
                cur_e = max(cur_e, e)
        if cur_e is not None:
            total += cur_e - cur_s
        return total / 1e9

    def program(self, name: str) -> dict:
        """Device time of a program's ops in the window, and its calls that
        started in the window."""
        t = sum(self._clip(o) for o in self.ops if o.program == name)
        lo, hi = self.window
        n = sum(1 for p, s, _ in self.runs if p == name and lo <= s < hi)
        return {"time_s": t / 1e9, "calls": n}

    def group_time(self, program: str) -> Dict[str, float]:
        out: Dict[str, float] = defaultdict(float)
        for o in self.ops:
            if o.program == program:
                out[o.group] += self._clip(o) / 1e9
        return dict(out)

    def site_time(self, program: str, tag: str) -> float:
        return sum(self._clip(o) for o in self.ops if o.program == program
                   and f"{o.group}:{o.site}" == tag) / 1e9

    def gaps(self) -> List[Tuple[int, int]]:
        """Idle intervals of the device inside the window."""
        out, t = [], self.window[0]
        for op in sorted(self.ops, key=lambda o: o.start):
            if op.start > t:
                out.append((t, min(op.start, self.window[1])))
            t = max(t, op.end)
            if t >= self.window[1]:
                break
        if t < self.window[1]:
            out.append((t, self.window[1]))
        return [(a, b) for a, b in out if b > a]

    def breakdown(self, top: int = 10) -> dict:
        ops: Dict[str, int] = defaultdict(int)
        for o in self.ops:
            ops[f"ng:{o.group}:{o.site}"] += self._clip(o)
        idle: Dict[str, int] = defaultdict(int)
        host = sorted(self.host, key=lambda h: h[1])
        for a, b in self.gaps():
            mid = (a + b) // 2
            name = "no host span"
            for h, s, e in host:
                if s <= mid < e:
                    name = h
                if s > mid:
                    break
            idle[name] += b - a
        rank = lambda d: [[k, v / 1e9] for k, v in  # noqa: E731
                          sorted(d.items(), key=lambda kv: -kv[1])[:top]]
        return {"device_ops": rank(ops), "idle_gaps": rank(idle)}


def _own_times(line_ops: List[Op]) -> List[Op]:
    """Set each op's own time: an op that holds others on its line (a while
    loop around its body) keeps only the time none of them covers."""
    stack: List[Op] = []
    for op in sorted(line_ops, key=lambda o: (o.start, -o.end)):
        op.own = op.end - op.start
        while stack and stack[-1].end <= op.start:
            stack.pop()
        if stack:
            stack[-1].own -= op.end - op.start
        stack.append(op)
    for op in line_ops:
        op.own = max(op.own, 0)
    return line_ops


def reduce_profile(pd, hlo: Dict[str, str]) -> Reduction:
    """Reduce a ``ProfileData`` to the window's device ops and host spans.

    On each device plane only two lines are read: ``XLA Modules`` (one event
    per program execution) and ``XLA Ops`` (one event per HLO op). The
    profiler derives further lines from these (steps, name scopes, source
    lines); reading them too would count the same device time twice.
    ``hlo`` maps program names to their compiled HLO text, which gives each
    op's ``op_name``; ops of other programs are grouped by their opcode.
    """
    names = {p: hlo_op_names(t) for p, t in hlo.items()}
    seen: Dict[Tuple[str, str], Tuple[str, str, str]] = {}
    ops: List[Op] = []
    runs: List[Tuple[str, int, int]] = []
    host: List[Tuple[str, int, int]] = []
    marks: Dict[str, int] = {}
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name in HOST_SPANS:
                    host.append((ev.name, int(ev.start_ns), int(ev.end_ns)))
                elif ev.name in ("window.start", "window.end"):
                    marks[ev.name] = int(ev.start_ns)
    for plane in pd.planes:
        if not plane.name.startswith("/device:"):
            continue
        lines = {line.name: line for line in plane.lines}
        modules = sorted((int(ev.start_ns), int(ev.end_ns), ev.name)
                         for ev in getattr(lines.get(MODULE_LINE), "events", []))
        starts = [m[0] for m in modules]
        runs += [(program_of(name), s, e) for s, e, name in modules]
        line_ops: List[Op] = []
        for ev in getattr(lines.get(OP_LINE), "events", []):
            # an op runs inside the module execution that holds it
            i = bisect.bisect_right(starts, int(ev.start_ns)) - 1
            if i < 0 or ev.start_ns >= modules[i][1]:
                continue
            prog = program_of(modules[i][2])
            key = (prog, ev.name)
            if key not in seen:
                instr, opcode = event_op(ev.name)
                opcode, op_name = names.get(prog, {}).get(instr,
                                                          (opcode, None))
                seen[key] = (instr,) + classify(op_name, opcode)
            instr, group, site = seen[key]
            line_ops.append(Op(prog, instr, group, site,
                               int(ev.start_ns), int(ev.end_ns)))
        ops += _own_times(line_ops)
    lo = marks.get("window.start", min((o.start for o in ops), default=0))
    hi = marks.get("window.end", max((o.end for o in ops), default=0))
    last = max((o.end for o in ops), default=hi)
    return Reduction((lo, min(hi, last)), ops, runs, host,
                     traced_s=(hi - lo) / 1e9, covered_s=(last - lo) / 1e9)


def reduce_dir(log_dir: str, hlo: Dict[str, str]) -> Reduction:
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise RuntimeError(f"no trace written under {log_dir}")
    return reduce_profile(ProfileData.from_file(max(paths, key=os.path.getmtime)),
                          hlo)


def program_texts(engine) -> Dict[str, str]:
    """Compiled HLO of the paged-decode program, whose ops the per-layer
    metrics classify: a TPU's op events carry no ``op_name`` of their own.
    Lowered at the engine's own shapes, the executable is the one the
    window ran (the compile cache holds it), so its op names match."""
    import jax
    import jax.numpy as jnp

    compiled = engine._paged_decode.lower(
        engine.params, jnp.asarray(engine._cur), jnp.asarray(engine._pos),
        engine._pools, jnp.asarray(engine._tables),
        jax.random.split(engine.key)[1]).compile()
    return {DECODE: compiled.as_text()}
