"""Where the device's idle time sits inside ``engine.step``: the engine's
own ``serve.*`` spans, read from a profiler trace.

The program opens a span around each part of ``PagedEngine.step`` (see
``docs/serving.md``): ``serve.admit`` with ``serve.prefill`` and
``serve.scatter`` inside it, ``serve.chunk``, and ``serve.decode`` with
``serve.decode.prep``, ``serve.decode.dispatch``, ``serve.decode.wait`` and
``serve.retire`` inside it. They are host events on the profiler's clock,
the clock of the device planes, nested inside the benchmark's
``engine.step``. Read beside the reduction of ``chipbench.trace``:

- ``idle_split``: each idle gap of the device in the window, put down to
  the innermost host span over its midpoint, the benchmark's or the
  engine's (``Reduction.breakdown``'s rule, with the engine's spans added);
- ``idle_overlap``: the same idle time cut exactly where host spans open
  and close, each piece under its innermost span and what the device ran
  when its gap began: ``in`` a program (a gap between its ops) or
  ``after`` the one that ended last;
- ``engine_idle_ms``: idle device time that overlaps the union of the
  engine's spans, per ``serve.decode`` that starts in the window;
- ``dispatch_ms``: mean host time of a ``serve.decode.dispatch``;
- ``admit_ms``: mean host time of a ``serve.admit``;
- ``stalls``: idle gaps of 100 ms or more, with the span that held each,
  what the device ran when it began and the program that ended it.

Each gives ``None`` where the trace holds no ``serve.*`` span, as a
program without them leaves it. To see them on the chip:

    python3 -m chipbench.spans --workload <cell> --seed <n> --seconds <s>

runs the cell once as ``--trace 1`` does and prints its result line with
two more sections: ``serve`` (the above, and the count and mean host time
of each span) and ``traced_window`` (the end-to-end numbers of the traced
part on the benchmark's clock, closed at its last step, for the profiler's
cost). It runs ``run.run_cell`` as it is and
takes the trace and the drive from ``chipbench.trace.reduce_dir`` and
``chipbench.driver.drive``, wrapped while the run lasts.
"""

from __future__ import annotations

import argparse
import bisect
import contextlib
import dataclasses
import glob
import json
import os
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

PREFIX = "serve."
DECODE = "serve.decode"
DISPATCH = "serve.decode.dispatch"
ADMIT = "serve.admit"
#: an idle gap at least this long is a host stall
STALL_S = 0.1


@dataclasses.dataclass(frozen=True)
class Span:
    name: str
    start: int
    end: int
    args: Dict[str, object]


def serve_spans(pd) -> List[Span]:
    """Every ``serve.*`` event of the host planes of a ``ProfileData``."""
    out = []
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(PREFIX):
                    out.append(Span(ev.name, int(ev.start_ns), int(ev.end_ns),
                                    dict(ev.stats)))
    return sorted(out, key=lambda s: (s.start, -s.end))


def _in_window(red, spans: List[Span], name: str) -> List[Span]:
    lo, hi = red.window
    return [s for s in spans if s.name == name and lo <= s.start < hi]


def _mean_ms(spans: List[Span]) -> Optional[float]:
    if not spans:
        return None
    return sum(s.end - s.start for s in spans) / len(spans) / 1e6


def _innermost(red, spans: List[Span]) -> List[Tuple[int, int, str]]:
    """Host time cut at every opening and closing of a span, the
    benchmark's or the engine's: (start, end, innermost span) pieces, in
    order, where some span is open. The innermost is the last to start of
    the spans open there."""
    hosts = sorted([(s, e, h) for h, s, e in red.host]
                   + [(s.start, s.end, s.name) for s in spans],
                   key=lambda t: (t[0], -t[1]))
    cuts = sorted({t for s, e, _ in hosts for t in (s, e)})
    out, stack, i = [], [], 0
    for p, q in zip(cuts, cuts[1:]):
        while i < len(hosts) and hosts[i][0] <= p:
            stack.append(hosts[i])
            i += 1
        while stack and stack[-1][1] <= p:
            stack.pop()
        if stack:
            out.append((p, q, stack[-1][2]))
    return out


def _held_by(red, spans: List[Span]) -> List[Tuple[int, int, str]]:
    """Each idle gap with the innermost span over its midpoint."""
    pieces = _innermost(red, spans)
    starts = [p for p, _, _ in pieces]
    out = []
    for a, b in red.gaps():
        mid = (a + b) // 2
        i = bisect.bisect_right(starts, mid) - 1
        held = i >= 0 and mid < pieces[i][1]
        out.append((a, b, pieces[i][2] if held else "no host span"))
    return out


def _device_at(runs: List[Tuple[int, int, str]], t: int) -> str:
    """What the device's programs, (start, end, program) in order, were
    doing at ``t``: ``in <program>`` between its ops, ``after <program>``
    once it ended, or ``none`` before the first."""
    i = bisect.bisect_right(runs, (t, float("inf"), "")) - 1
    if i < 0:
        return "none"
    s, e, p = runs[i]
    return f"in {p}" if e > t else f"after {p}"


def _overlap(red, spans: List[Span]) -> Dict[Tuple[str, str], int]:
    """Idle ns under each (innermost span, what the device ran when the
    gap began)."""
    runs = sorted((s, e, p) for p, s, e in red.runs)
    pieces = _innermost(red, spans)
    idle: Dict[Tuple[str, str], int] = defaultdict(int)
    j = 0
    for a, b in red.gaps():
        before = _device_at(runs, a)
        while j < len(pieces) and pieces[j][1] <= a:
            j += 1
        k = j
        while k < len(pieces) and pieces[k][0] < b:
            p, q, name = pieces[k]
            idle[(name, before)] += min(b, q) - max(a, p)
            k += 1
    return idle


def idle_overlap(red, spans: List[Span]) -> List[list]:
    """[[span, device, idle seconds], ...], most first, where ``device`` is
    ``in``/``after`` the program that held or ended last before the gap;
    sums to the window's idle less what no span covers."""
    return [[n, p, v / 1e9] for (n, p), v in
            sorted(_overlap(red, spans).items(), key=lambda kv: -kv[1])]


def idle_split(red, spans: List[Span]) -> List[list]:
    """[[span, idle seconds], ...], most first; sums to the window's idle."""
    idle: Dict[str, int] = defaultdict(int)
    for a, b, name in _held_by(red, spans):
        idle[name] += b - a
    return [[k, v / 1e9] for k, v in sorted(idle.items(), key=lambda kv: -kv[1])]


def engine_idle_ms(red, spans: List[Span]) -> Optional[float]:
    """Idle inside the engine's spans (the benchmark's nest none of them)
    per decode step."""
    decodes = _in_window(red, spans, DECODE)
    if not decodes:
        return None
    idle = sum(v for (name, _), v in _overlap(red, spans).items()
               if name.startswith(PREFIX))
    return idle / len(decodes) / 1e6


def dispatch_ms(red, spans: List[Span]) -> Optional[float]:
    return _mean_ms(_in_window(red, spans, DISPATCH))


def admit_ms(red, spans: List[Span]) -> Optional[float]:
    return _mean_ms(_in_window(red, spans, ADMIT))


def stalls(red, spans: List[Span], min_s: float = STALL_S) -> List[list]:
    """[[seconds into the window, idle seconds, span, device at its start,
    program after it], ...] of each gap of at least ``min_s``."""
    runs = sorted((s, e, p) for p, s, e in red.runs)
    out = []
    for a, b, name in _held_by(red, spans):
        if b - a >= min_s * 1e9:
            j = bisect.bisect_left(runs, (b, 0, ""))
            out.append([(a - red.window[0]) / 1e9, (b - a) / 1e9, name,
                        _device_at(runs, a),
                        runs[j][2] if j < len(runs) else "none"])
    return out


def summary(red, spans: List[Span]) -> dict:
    """The ``serve`` section of ``python3 -m chipbench.spans``'s line."""
    lo, hi = red.window
    host: Dict[str, List[int]] = defaultdict(list)
    for s in spans:
        if lo <= s.start < hi:
            host[s.name].append(s.end - s.start)
    return {
        "engine_idle_ms": engine_idle_ms(red, spans),
        "dispatch_ms": dispatch_ms(red, spans),
        "admit_ms": admit_ms(red, spans),
        "idle_gaps": idle_split(red, spans),
        "idle_overlap": idle_overlap(red, spans),
        "stalls": stalls(red, spans),
        "spans": {k: {"count": len(v), "mean_ms": sum(v) / len(v) / 1e6,
                      "total_s": sum(v) / 1e9} for k, v in sorted(host.items())},
    }


@contextlib.contextmanager
def capture():
    """While it lasts, a run of ``run.run_cell`` leaves its drive, its trace
    reduction and the trace's ``serve.*`` spans in the yielded dict."""
    from chipbench import driver, trace

    got: dict = {}
    reduce_dir, drive = trace.reduce_dir, driver.drive

    def reduce_and_keep(log_dir, hlo):
        from jax.profiler import ProfileData
        paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                          recursive=True)
        if not paths:
            raise RuntimeError(f"no trace written under {log_dir}")
        pd = ProfileData.from_file(max(paths, key=os.path.getmtime))
        got["spans"] = serve_spans(pd)
        got["trace"] = trace.reduce_profile(pd, hlo)
        return got["trace"]

    def drive_and_keep(*args, **kw):
        got["drive"] = drive(*args, **kw)
        return got["drive"]

    trace.reduce_dir, driver.drive = reduce_and_keep, drive_and_keep
    try:
        yield got
    finally:
        trace.reduce_dir, driver.drive = reduce_dir, drive


def main(argv=None) -> int:
    from chipbench import run

    ap = argparse.ArgumentParser(prog="python3 -m chipbench.spans",
                                 description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    try:
        with capture() as got:
            out = run.run_cell(run.load_bench(), args.workload, args.seed,
                               args.seconds, True)
    except run.NoChip as e:
        run.log(f"chipbench: {e}; nothing is served without the chip")
        return 2
    out["serve"] = summary(got["trace"], got["spans"])
    d, red = got["drive"], got["trace"]
    # the traced part closes at the return of its last step
    steps = d.steps_within(red.window_s)
    stop = steps[-1].end if steps else d.t0
    out["traced_window"] = run.end_to_end(dataclasses.replace(
        d, end=d.t0 + red.window_s, close=stop))
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
