"""Serving: continuous-batching KV-cache engines with per-slot positions.

``make_prefill_step`` / ``make_serve_step`` build the two jitted programs the
dry-run lowers for the inference shapes (prefill_32k lowers prefill;
decode_32k / long_500k lower serve_step — one new token per slot against a
seq_len-deep cache, with a vectorized per-slot ``pos``). Each is a model
body run by ``_rules_program``: under the mesh's GSPMD rules and
``nn.fuse(fused)``, on the params cast to the activation dtype. The paged
step programs (``repro.serving.paged``) take their bodies through the same
preamble on one chip, or through ``shard_map`` under tensor parallelism.

``BaseEngine`` is the host side of a continuous-batching engine: a slot
table of ``max_batch`` sequences fed from a FIFO queue (static shapes: TPU
serving engines pad the batch, not the program), the ``EngineStats``
accounting and the admission bookkeeping. Two engines build on it:
``Engine`` (here, used by examples/serve_batched.py and the ``serving``
bench section) over ONE shared contiguous KV cache, and ``PagedEngine``
(``repro.serving.paged``) over pools of KV blocks.

* admission is per-slot: each request is prefilled alone (right-padded to a
  power-of-two bucket so the prefill program compiles once per bucket, with
  a length mask picking the last real token's logits); ``Engine`` writes
  its caches into the shared cache at the slot index via
  ``dynamic_update_slice`` — no other slot is disturbed;
* decode runs one step for the whole slot table with a per-slot position
  vector (``pos: (B,)``), so sequences of different depths coexist;
* a finished slot (EOS / token budget / context full) is refilled from the
  FIFO queue *immediately*, in the same engine step — the batch never
  drains;
* ``EngineStats`` extends throughput accounting with per-request latency:
  time-to-first-token, queue wait, and per-token decode latency.

Every timed region of an engine is one ``BaseEngine._span``: a
``jax.profiler`` trace span named ``serve.<region>`` (recorded only while a
profiler trace runs, on the device planes' clock) that, where it names an
``EngineStats`` field, adds the region's time on the engine's ``clock`` to
it. ``Request`` timestamps read the same clock.

Token accounting: every request's first output token comes from the prefill
argmax and is counted in ``EngineStats.first_tokens``; every token emitted
by a decode step is counted in ``EngineStats.decode_tokens`` at the moment
it is appended to a request's output, so ``decode_tokens`` equals the total
number of emitted decode tokens exactly.
"""

from __future__ import annotations

import contextlib
import dataclasses
import inspect
import time
from typing import Callable, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro import nn, sharding
from repro.models import init_lm_cache, lm_decode, lm_prefill
from repro.models.common import ModelConfig
from repro.runtime import cast_params


def _named_like(program: Callable, body: Callable) -> Callable:
    """Give ``program(params, *args)`` the name and the argument names of
    ``body(cfg, working, *args)``: jit names the compiled module
    (``jit_<name>``), every op's ``op_name`` and each parameter by them."""
    sig = inspect.signature(body)
    params = inspect.Parameter("params",
                               inspect.Parameter.POSITIONAL_OR_KEYWORD)
    program.__name__ = program.__qualname__ = body.__name__
    program.__signature__ = sig.replace(
        parameters=[params, *list(sig.parameters.values())[2:]])
    return program


def _rules_program(body: Callable, cfg: ModelConfig, mesh=None,
                   fused: bool = False) -> Callable:
    """program(params, *args) -> body(cfg, working, *args), where
    ``working`` is ``params`` cast to the activation dtype, traced under
    the mesh's GSPMD rules (``sharding.use_rules``; none without a mesh) and
    ``nn.fuse(fused)``."""
    def program(params, *args):
        with sharding.use_rules(mesh, cfg.fsdp, cfg.seq_shard), \
                nn.fuse(fused):
            working = cast_params(params, cfg.activation_dtype)
            return body(cfg, working, *args)
    return _named_like(program, body)


def _prefill_body(max_len: int) -> Callable:
    """The cold-prefill body: (cfg, working, tokens, lengths) ->
    (last_logits, caches)."""
    def prefill_step(cfg, working, tokens, lengths=None):
        return lm_prefill(working, tokens, cfg, max_len=max_len,
                          lengths=lengths)
    return prefill_step


def make_prefill_step(cfg: ModelConfig, max_len: int, mesh=None,
                      fused: bool = False) -> Callable:
    """prefill_step(params, tokens, lengths=None) -> (last_logits, caches).

    ``fused=True`` traces the model under ``nn.fuse()``: the fusable
    NonGEMM chains (residual-add→norm, SwiGLU, rope) run as single
    Pallas-kernel-backed fused operators (repro.core.fusion).
    """
    return _rules_program(_prefill_body(max_len), cfg, mesh, fused)


def make_serve_step(cfg: ModelConfig, mesh=None,
                    greedy: bool = True, temperature: float = 1.0,
                    fused: bool = False) -> Callable:
    """serve_step(params, token, pos, caches, key) -> (token', caches').

    ``pos`` is a scalar (lockstep batch) or a per-slot ``(B,)`` vector.
    ``fused=True`` routes ``lm_decode`` through the fused fast path
    (fused add+norm and SwiGLU — see repro.core.fusion).
    """
    def serve_step(cfg, working, token, pos, caches, key):
        logits, caches = lm_decode(working, token, pos, caches, cfg)
        lf = logits.astype(jnp.float32)
        if greedy:
            nxt = jnp.argmax(lf, axis=-1).astype(jnp.int32)
        else:
            nxt = jax.random.categorical(
                key, lf / max(temperature, 1e-3), axis=-1).astype(jnp.int32)
        return nxt, caches
    return _rules_program(serve_step, cfg, mesh, fused)


@dataclasses.dataclass
class Request:
    uid: int
    prompt: List[int]
    max_new_tokens: int = 32
    output: List[int] = dataclasses.field(default_factory=list)
    done: bool = False
    # prompt tokens reused from the prefix cache at admission
    cached_len: int = 0
    # wall-clock timeline (engine clock; seconds)
    enqueue_t: float = 0.0
    admit_t: float = 0.0
    first_token_t: float = 0.0
    finish_t: float = 0.0

    @property
    def queue_wait_s(self) -> float:
        return max(self.admit_t - self.enqueue_t, 0.0)

    @property
    def ttft_s(self) -> float:
        """Time-to-first-token: enqueue -> first (prefill-argmax) token."""
        return max(self.first_token_t - self.enqueue_t, 0.0)

    @property
    def decode_tokens(self) -> int:
        """Tokens emitted by decode steps (everything after the first)."""
        return max(len(self.output) - 1, 0)

    @property
    def decode_tok_latency_s(self) -> float:
        """Mean wall time per emitted decode token for this request."""
        n = self.decode_tokens
        return (self.finish_t - self.first_token_t) / n if n else 0.0


@dataclasses.dataclass
class EngineStats:
    prefill_s: float = 0.0
    decode_s: float = 0.0
    prefill_tokens: int = 0           # real (unpadded) prompt tokens
    decode_tokens: int = 0            # tokens emitted by decode steps
    first_tokens: int = 0             # tokens emitted by prefill argmax
    decode_steps: int = 0             # jitted decode dispatches
    overlapped_steps: int = 0         # dispatched before the last was read
    discarded_tokens: int = 0         # lookahead tokens dropped after EOS
    completed: int = 0                # finished requests
    decoded_requests: int = 0         # completed requests that decoded > 0
    ttft_sum_s: float = 0.0
    queue_wait_sum_s: float = 0.0
    decode_tok_latency_sum_s: float = 0.0   # sum of per-request means

    @property
    def decode_tok_per_s(self) -> float:
        return self.decode_tokens / self.decode_s if self.decode_s else 0.0

    @property
    def emitted_tokens(self) -> int:
        return self.first_tokens + self.decode_tokens

    @property
    def mean_ttft_s(self) -> float:
        return self.ttft_sum_s / self.completed if self.completed else 0.0

    @property
    def mean_queue_wait_s(self) -> float:
        return self.queue_wait_sum_s / self.completed if self.completed \
            else 0.0

    @property
    def mean_decode_tok_latency_s(self) -> float:
        """Mean of per-request per-token decode latency, over the requests
        that emitted decode tokens (a request finishing at admission has
        no decode latency and must not drag the mean toward zero)."""
        return self.decode_tok_latency_sum_s / self.decoded_requests \
            if self.decoded_requests else 0.0


def _next_pow2(n: int) -> int:
    p = 1
    while p < n:
        p *= 2
    return p


def _slot_insert(shared: dict, one: dict, slot) -> dict:
    """Write a single-row cache tree into the shared cache at ``slot``.

    lead/trail leaves are batch-leading ``(B, ...)``; scan-stacked leaves
    carry a leading layer dim ``(n_rep, B, ...)`` (see ``init_lm_cache``).
    """
    def ins(axis):
        def f(dst, src):
            return jax.lax.dynamic_update_slice_in_dim(
                dst, src.astype(dst.dtype), slot, axis=axis)
        return f

    return {
        "lead": [jax.tree_util.tree_map(ins(0), d, s)
                 for d, s in zip(shared["lead"], one["lead"])],
        "scan": [jax.tree_util.tree_map(ins(1), d, s)
                 for d, s in zip(shared["scan"], one["scan"])],
        "trail": [jax.tree_util.tree_map(ins(0), d, s)
                  for d, s in zip(shared["trail"], one["trail"])],
    }


class BaseEngine:
    """The host side of a continuous-batching engine, shared by ``Engine``
    and ``PagedEngine``: the FIFO request queue, the slot table of
    ``max_batch`` sequences with each slot's next write position (``_pos``)
    and next input token (``_cur``), the ``EngineStats`` accounting and its
    spans, and the admission bookkeeping. Each engine adds its KV store,
    its programs, ``_admit`` and ``step``.
    """

    def __init__(self, cfg: ModelConfig, params, max_batch: int = 8,
                 max_len: int = 512, eos_id: Optional[int] = None,
                 mesh=None, greedy: bool = True, pad_id: int = 0,
                 seed: int = 0, min_prefill_bucket: int = 8,
                 fused: bool = False,
                 clock: Callable[[], float] = time.perf_counter):
        self.cfg = cfg
        self.params = params
        self.max_batch = max_batch
        self.max_len = max_len
        self.eos_id = eos_id
        self.mesh = mesh
        self.pad_id = pad_id
        self.greedy = greedy
        self.fused = fused
        self.min_prefill_bucket = min_prefill_bucket
        self.key = jax.random.PRNGKey(seed)
        self.queue: List[Request] = []
        self.stats = EngineStats()
        self.clock = clock
        self._uid = 0
        # recurrent/xLSTM prefill folds every input token — pads included —
        # into its running state, so bucketed right-padding would corrupt
        # it: those architectures prefill at exact prompt length (one
        # compiled prefill per distinct length instead of per bucket)
        self._pad_safe = not (set(cfg.layer_kinds())
                              & {"rec", "mlstm", "slstm"})
        # slot table
        self.slots: List[Optional[Request]] = [None] * max_batch
        self._pos = np.zeros((max_batch,), np.int32)
        self._cur = np.full((max_batch,), pad_id, np.int32)

    # -- queue -------------------------------------------------------------
    def add_request(self, prompt: Sequence[int],
                    max_new_tokens: int = 32) -> int:
        prompt = list(prompt)
        if not prompt:
            raise ValueError("empty prompt")
        if len(prompt) > self.max_len:
            raise ValueError(f"prompt length {len(prompt)} exceeds "
                             f"max_len={self.max_len}")
        self._uid += 1
        req = Request(self._uid, prompt, max_new_tokens,
                      enqueue_t=self.clock())
        self.queue.append(req)
        return self._uid

    # -- admission ---------------------------------------------------------
    def _bucket(self, plen: int) -> int:
        if not self._pad_safe:
            return plen
        return min(_next_pow2(max(plen, self.min_prefill_bucket)),
                   self.max_len)

    def _first_token(self, logits) -> int:
        lf = logits.astype(jnp.float32)
        if self.greedy:
            return int(jnp.argmax(lf, axis=-1)[0])
        self.key, k = jax.random.split(self.key)
        return int(jax.random.categorical(k, lf, axis=-1)[0])

    def _prefill_cold(self, req: Request):
        """Prefill ``req``'s whole prompt alone through ``self._prefill``,
        right-padded to its bucket -> (first token, its B=1 caches, bucket).
        """
        plen = len(req.prompt)
        bucket = self._bucket(plen)
        toks = np.full((1, bucket), self.pad_id, np.int32)
        toks[0, :plen] = req.prompt      # right-padded
        with self._span("serve.prefill", "prefill_s", uid=req.uid,
                        bucket=bucket):
            logits, one = self._prefill(self.params, jnp.asarray(toks),
                                        jnp.full((1,), plen, jnp.int32))
            first = self._first_token(logits)
        return first, one, bucket

    def _seat(self, slot: int, req: Request, first: int,
              place: Callable[[], None]) -> bool:
        """Take a prefilled request's first token and seat it in ``slot``,
        or finish it.

        The request decodes on unless the token is the EOS, its budget is
        one token, or its prompt fills the context. If it does, ``place()``
        first puts its prompt's KV where the slot's decode reads it (a
        request finishing here never needs its caches). Returns True if the
        slot is now occupied; False after the request finished and the
        slot was freed.
        """
        plen = len(req.prompt)
        live = not ((self.eos_id is not None and first == self.eos_id)
                    or req.max_new_tokens <= 1
                    or plen >= self.max_len)
        if live:
            place()
        self.stats.prefill_tokens += plen
        req.output.append(first)
        self.stats.first_tokens += 1
        req.first_token_t = self.clock()
        if not live:
            self._finish(req)
            self._free(slot)
            return False
        self.slots[slot] = req
        self._pos[slot] = plen           # next write index == prompt end
        self._cur[slot] = first
        return True

    def _admit_free_slots(self) -> List[Request]:
        """Fill every free slot from the queue; returns requests that
        completed at admission time."""
        done: List[Request] = []
        for i in range(self.max_batch):
            while self.queue and self.slots[i] is None:
                req = self.queue.pop(0)
                # the wait clock runs from submit (enqueue) to this, the
                # request's only admission: a chunked prefill spans several
                # engine steps after it and must not restart the clock
                req.admit_t = self.clock()
                if not self._admit(i, req):
                    done.append(req)
        return done

    def _finish(self, req: Request) -> None:
        req.done = True
        req.finish_t = self.clock()
        s = self.stats
        s.completed += 1
        s.ttft_sum_s += req.ttft_s
        s.queue_wait_sum_s += req.queue_wait_s
        if req.decode_tokens:
            s.decoded_requests += 1
            s.decode_tok_latency_sum_s += req.decode_tok_latency_s

    def _free(self, slot: int) -> None:
        self.slots[slot] = None
        self._pos[slot] = 0
        self._cur[slot] = self.pad_id

    def reset_stats(self) -> None:
        """Zero the accounting (after warmup/priming runs): load drivers
        prime the jit caches with dummy requests, then measure cleanly."""
        self.stats = EngineStats()

    @contextlib.contextmanager
    def _span(self, name: str, stat: Optional[str] = None, **args):
        """One region of the engine's work, the engine's only timer.

        Opens a ``jax.profiler.TraceAnnotation`` named ``name`` with
        ``args``; it is recorded only while a profiler trace runs, and then
        on the clock of the device planes. Where ``stat`` names an
        ``EngineStats`` field, the region's time on ``self.clock`` is added
        to it. Yields the annotation: ``set_metadata`` adds args that are
        known only at the region's end.
        """
        with jax.profiler.TraceAnnotation(name, **args) as ann:
            if stat is None:
                yield ann
                return
            t0 = self.clock()
            yield ann
            setattr(self.stats, stat,
                    getattr(self.stats, stat) + self.clock() - t0)

    # -- stepping ----------------------------------------------------------
    @property
    def active(self) -> int:
        return sum(r is not None for r in self.slots)

    def run(self) -> List[Request]:
        """Serve until the queue and the slot table are empty; returns the
        completed requests in completion order."""
        finished: List[Request] = []
        while self.queue or self.active:
            finished.extend(self.step())
        return finished


class Engine(BaseEngine):
    """Continuous-batching serving engine over one shared static KV cache.

    Takes ``BaseEngine``'s arguments. ``fused=True`` compiles both engine
    programs (prefill + decode) through the operator-fusion fast path:
    residual-add→norm pairs and SwiGLU run as single fused
    Pallas-kernel-backed ops (``repro.core.fusion``), numerically
    equivalent to the unfused programs.
    """

    def __init__(self, cfg: ModelConfig, params, **kw):
        super().__init__(cfg, params, **kw)
        self._prefill = jax.jit(make_prefill_step(cfg, self.max_len,
                                                  self.mesh,
                                                  fused=self.fused))
        # donate the cache through decode (same as the dry-run's lowering):
        # the step updates B rows in place instead of copying the cache
        self._decode = jax.jit(make_serve_step(cfg, self.mesh,
                                               greedy=self.greedy,
                                               fused=self.fused),
                               donate_argnums=(3,))
        # donate the shared cache: the splice updates one row in place
        # instead of copying every (max_batch, max_len, ...) leaf per admit
        self._insert = jax.jit(_slot_insert, donate_argnums=(0,))
        self._caches = init_lm_cache(cfg, self.max_batch, self.max_len)

    def _admit(self, slot: int, req: Request) -> bool:
        """Prefill ``req`` alone and splice it into ``slot``.

        Returns True if the slot is now occupied (False when the request
        completed at admission: single-token budget or immediate EOS).
        """
        with self._span("serve.admit", uid=req.uid,
                        prompt_len=len(req.prompt), cached_len=0,
                        path="cold"):
            first, one, _ = self._prefill_cold(req)

            def splice():
                # block on the result so this full-cache write is charged
                # to the prefill phase, not the next decode step's timed
                # region
                with self._span("serve.scatter", "prefill_s", uid=req.uid):
                    self._caches = self._insert(self._caches, one, slot)
                    jax.block_until_ready(self._caches)
            return self._seat(slot, req, first, splice)

    def step(self) -> List[Request]:
        """One engine iteration: admit, decode one token per live slot,
        retire finished slots (refilled on the next iteration — or by the
        admission phase of this call if slots were already free).
        Returns the requests finished during this call."""
        finished = self._admit_free_slots()

        # invariant: every occupied slot has room for its next KV write —
        # _seat finishes full-context prompts at admission and the decode
        # loop below retires a slot the moment its position hits max_len
        assert all(r is None or self._pos[i] < self.max_len
                   for i, r in enumerate(self.slots))
        if self.active == 0:
            return finished

        with self._span("serve.decode", live=self.active):
            with self._span("serve.decode.prep", "decode_s"):
                self.key, k = jax.random.split(self.key)
                cur, pos = jnp.asarray(self._cur), jnp.asarray(self._pos)
            with self._span("serve.decode.dispatch", "decode_s"):
                nxt, self._caches = self._decode(self.params, cur, pos,
                                                 self._caches, k)
            with self._span("serve.decode.wait", "decode_s"):
                nxt_host = np.asarray(jax.block_until_ready(nxt))
            self.stats.decode_steps += 1
            with self._span("serve.retire") as span:
                n = len(finished)
                for i, r in enumerate(self.slots):
                    if r is None:
                        continue        # pad-fed dead slot: output discarded
                    tok = int(nxt_host[i])
                    r.output.append(tok)
                    self.stats.decode_tokens += 1    # counted where emitted
                    self._pos[i] += 1
                    self._cur[i] = tok
                    if (self.eos_id is not None and tok == self.eos_id) \
                            or len(r.output) >= r.max_new_tokens \
                            or self._pos[i] >= self.max_len:
                        self._finish(r)
                        finished.append(r)
                        self._free(i)
                span.set_metadata(finished=len(finished) - n)
        return finished


from repro.serving.paged import (BlockAllocator, PagedEngine,  # noqa: E402
                                 PrefixCache)

__all__ = [
    "Engine", "EngineStats", "Request", "make_prefill_step",
    "make_serve_step", "BlockAllocator", "PagedEngine", "PrefixCache",
]
