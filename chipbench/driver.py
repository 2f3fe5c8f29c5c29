"""Open-loop driver on the benchmark's own clock.

Each request carries its due time. At every step boundary the driver sends
(``add_request``) each request that has come due, then calls
``engine.step()`` once. Every token is stamped at the return of the step
that delivered it; nothing is read from the program's own timers. Host
spans (``submit``, ``engine.step``, ``bookkeeping``, ``idle``) go into the
profiler's trace when one is running, so idle device time can be put down
to what the host was doing.
"""

from __future__ import annotations

import dataclasses
import time
from typing import List

from jax.profiler import TraceAnnotation

from chipbench.workload import Req


@dataclasses.dataclass
class Served:
    req: Req
    due: float                          # absolute, on the driver's clock
    sent: float = 0.0
    handle: object = None               # the engine's request object
    stamps: List[float] = dataclasses.field(default_factory=list)

    @property
    def tokens(self) -> List[int]:
        return list(self.handle.output) if self.handle is not None else []

    @property
    def finished(self) -> bool:
        return self.handle is not None and self.handle.done


@dataclasses.dataclass
class StepRecord:
    """One engine call: when it ran and what its decode part computed."""
    start: float
    end: float
    decode_positions: List[int]         # KV depth each decoded token attended
    decode_tokens: int
    admitted: int
    prompt_tokens: List[int]            # prompt lengths admitted this step


@dataclasses.dataclass
class Drive:
    t0: float
    end: float                          # when the window's time is up
    served: List[Served]
    steps: List[StepRecord]
    prefix_hits: int
    prefix_misses: int
    #: when the window closed: the step under way at ``end`` (and, with
    #: ``drain``, every later one) has returned, and all its work counts
    close: float = 0.0

    def in_window(self) -> List[Served]:
        return [s for s in self.served if s.due < self.end]

    def steps_within(self, seconds: float) -> List[StepRecord]:
        """The steps that started in the window and returned within
        ``seconds`` of its start: those of a traced part that long."""
        limit = self.t0 + seconds
        return [r for r in self.steps if self.t0 <= r.start and r.end <= limit]


def _prefix_counters(engine):
    pc = engine.prefix_cache
    return (pc.hits, pc.misses) if pc is not None else (0, 0)


def drive(engine, reqs: List[Req], seconds: float, *, drain: bool,
          clock=time.perf_counter, on_step=None) -> Drive:
    """Serve ``reqs`` open loop for ``seconds`` from now.

    After the window no request is sent any more. With ``drain`` the
    engine keeps stepping until every request due in the window has
    finished, so that each is followed to its first token and its answer
    can be checked; without it the run stops at the first step boundary
    past the window (an offline backlog never drains). The clock read
    there closes the window: the step under way when the time was up
    counts whole, its work and its time.
    """
    t0 = clock()
    end = t0 + seconds
    served = [Served(r, t0 + r.due_s) for r in reqs]
    pending = [s for s in served if s.due < end]
    nxt = 0
    live: List[Served] = []
    steps: List[StepRecord] = []
    hit0, miss0 = _prefix_counters(engine)

    while True:
        now = clock()
        with TraceAnnotation("submit"):
            while nxt < len(pending) and pending[nxt].due <= now:
                s = pending[nxt]
                engine.add_request(s.req.prompt,
                                   max_new_tokens=s.req.max_new_tokens)
                s.handle = engine.queue[-1]
                s.sent = clock()
                live.append(s)
                nxt += 1
        if now >= end and (not drain or (nxt == len(pending) and not live)):
            break
        if not engine.queue and not engine.active:
            # nothing to serve: wait for the next due time or the window end
            due = pending[nxt].due if nxt < len(pending) else end
            with TraceAnnotation("idle"):
                time.sleep(max(0.0, min(due - clock(), 1e-3)))
            continue
        d0 = engine.stats.decode_tokens
        start = clock()
        with TraceAnnotation("engine.step"):
            engine.step()
        stamp = clock()
        with TraceAnnotation("bookkeeping"):
            positions, prompts = [], []
            still = []
            for s in live:
                out = s.handle.output
                for j in range(len(s.stamps), len(out)):
                    s.stamps.append(stamp)
                    if j == 0:
                        prompts.append(len(s.req.prompt))
                    else:
                        # token j came from decoding token j-1 at position
                        # len(prompt) + j - 1, attending one more row
                        positions.append(len(s.req.prompt) + j)
                (still if not s.handle.done else []).append(s)
            live = still
            steps.append(StepRecord(start, stamp, positions,
                                    engine.stats.decode_tokens - d0,
                                    len(prompts), prompts))
            if on_step is not None:
                on_step(steps[-1])

    hit1, miss1 = _prefix_counters(engine)
    return Drive(t0, end, served, steps, hit1 - hit0, miss1 - miss0, now)


def serve_waves(engine, waves: List[List[Req]]) -> None:
    """Serve each wave of (warm-up) requests to its end, one after another."""
    for wave in waves:
        for r in wave:
            engine.add_request(r.prompt, max_new_tokens=r.max_new_tokens)
        while engine.queue or engine.active:
            engine.step()
