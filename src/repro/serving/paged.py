"""Paged-KV serving: block allocator, prefix cache, chunked prefill.

``PagedEngine`` replaces the contiguous-cache ``Engine``'s single
``(max_batch, max_len, ...)`` KV cache with a pool of fixed-size KV blocks
(``(num_blocks, block_size, ...)`` per cache leaf) managed by a free-list
:class:`BlockAllocator` and addressed through per-sequence block tables —
the vLLM paging scheme, append-only so no copy-on-write is ever needed.

Three mechanisms ride on the block tables:

* **paged decode** — every step gathers each sequence's blocks into a
  contiguous ``(B, max_len, ...)`` view (``nn.paged_kv_gather``), runs the
  UNCHANGED ``lm_decode`` program on it, then scatters the one new KV row
  per sequence back into its block (``nn.paged_kv_write``). Stale rows in
  the view are hidden by decode's per-row ``arange <= pos`` mask, whose
  masked terms are exact zeros — which is what makes paged decode
  bit-identical to the contiguous engine.
* **prefix cache** — full prompt blocks are registered in a hash-chain
  keyed :class:`PrefixCache` at admission; later prompts sharing the
  prefix re-point their table at the cached blocks and prefill only the
  suffix. Shared blocks are protected by refcounts and by the scatter
  guard (``lo``) that diverts any overlapping write to the scratch block.
* **chunked prefill** — long prompts admit as a sequence of
  decode-interleaved ``lm_extend`` chunks instead of stalling the batch:
  one chunk per engine step, each attending the full cached depth at its
  absolute offset.

Block 0 is reserved as a scratch block: unallocated table entries point at
it, so cache writes from dead or still-prefilling slots land harmlessly in
garbage that no masked read ever consumes.
"""

from __future__ import annotations

import time
from collections import OrderedDict
from typing import Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro import nn, sharding
from repro.models import init_lm_cache, lm_decode, lm_extend, lm_prefill
from repro.models import tp as tp_mod
from repro.models.common import ModelConfig
from repro.runtime import cast_params
from repro.serving import Engine, Request, _next_pow2


# ---------------------------------------------------------------------------
# block allocator + prefix cache (host-side bookkeeping)
# ---------------------------------------------------------------------------

class BlockAllocator:
    """Free-list allocator over a fixed pool of KV blocks with refcounts.

    Block 0 is reserved as the scratch block (never handed out): zeroed
    block-table entries alias it, so writes from slots that own no block
    at that position divert there instead of corrupting a neighbor.
    """

    def __init__(self, num_blocks: int, block_size: int):
        if num_blocks < 2:
            raise ValueError("need at least 2 blocks (block 0 is reserved)")
        if block_size < 1:
            raise ValueError("block_size must be positive")
        self.num_blocks = num_blocks
        self.block_size = block_size
        # pop() yields ascending ids — deterministic tables for replay
        self._free = list(range(num_blocks - 1, 0, -1))
        self.refcount: Dict[int, int] = {}

    @property
    def free_blocks(self) -> int:
        return len(self._free)

    def try_allocate(self) -> Optional[int]:
        """Take one free block (refcount 1), or None when exhausted."""
        if not self._free:
            return None
        bid = self._free.pop()
        self.refcount[bid] = 1
        return bid

    def allocate(self, n: int = 1) -> List[int]:
        if self.free_blocks < n:
            raise RuntimeError(
                f"paged KV pool exhausted: need {n} blocks, "
                f"{self.free_blocks} free of {self.num_blocks}")
        return [self.try_allocate() for _ in range(n)]

    def incref(self, bid: int) -> None:
        self.refcount[bid] += 1

    def decref(self, bid: int) -> None:
        rc = self.refcount[bid] - 1
        if rc == 0:
            del self.refcount[bid]
            self._free.append(bid)
        else:
            self.refcount[bid] = rc


class PrefixCache:
    """Hash-chain keyed map from full prompt-prefix blocks to pool blocks.

    Key ``i`` is ``hash((key_{i-1}, tokens_of_block_i))`` — two prompts
    share key ``i`` iff their first ``(i+1) * block_size`` tokens agree.
    The cache holds one refcount on every registered block; ``evict_one``
    drops the least-recently-used entry nobody else references.
    """

    def __init__(self, allocator: BlockAllocator):
        self.allocator = allocator
        self._entries: "OrderedDict[int, int]" = OrderedDict()
        self.hits = 0
        self.misses = 0

    def _chain_keys(self, prompt):
        bs = self.allocator.block_size
        key = 0
        for i in range(len(prompt) // bs):
            key = hash((key, tuple(prompt[i * bs:(i + 1) * bs])))
            yield key

    def lookup(self, prompt) -> Tuple[int, List[int]]:
        """-> (cached_len, blocks); increfs every returned block.

        Reuse is capped at ``(len(prompt) - 1) // block_size`` blocks so at
        least one suffix token always prefills (the first output token
        needs a live forward pass over real query positions).
        """
        bs = self.allocator.block_size
        max_reuse = (len(prompt) - 1) // bs
        blocks: List[int] = []
        for i, key in enumerate(self._chain_keys(prompt)):
            if i >= max_reuse:
                break
            bid = self._entries.get(key)
            if bid is None:
                break
            self._entries.move_to_end(key)
            blocks.append(bid)
        for bid in blocks:
            self.allocator.incref(bid)
        if blocks:
            self.hits += 1
        else:
            self.misses += 1
        return len(blocks) * bs, blocks

    def insert(self, prompt, blocks: List[int]) -> None:
        """Register the prompt's full blocks (called once the prompt KV is
        fully materialized). Existing entries win — a concurrent admission
        of the same prefix keeps the first registered block."""
        for i, key in enumerate(self._chain_keys(prompt)):
            if key not in self._entries:
                self._entries[key] = blocks[i]
                self.allocator.incref(blocks[i])

    def evict_one(self) -> bool:
        """Drop the LRU entry whose block only the cache still references."""
        for key, bid in self._entries.items():
            if self.allocator.refcount.get(bid, 0) == 1:
                del self._entries[key]
                self.allocator.decref(bid)
                return True
        return False

    @property
    def hit_rate(self) -> float:
        n = self.hits + self.misses
        return self.hits / n if n else 0.0

    def reset_counters(self) -> None:
        self.hits = 0
        self.misses = 0

    def __len__(self) -> int:
        return len(self._entries)


# ---------------------------------------------------------------------------
# jitted paged programs (gather view -> unchanged model program -> scatter)
# ---------------------------------------------------------------------------

def _gather_tree(pools: dict, tables, max_len: int) -> dict:
    """Materialize the contiguous (B, max_len, ...) cache view per leaf."""
    def g0(p):
        return nn.paged_kv_gather(p, tables, max_len)

    def g1(p):                      # scan leaves carry a leading layer dim
        return jax.vmap(g0)(p)

    tm = jax.tree_util.tree_map
    return {
        "lead": [tm(g0, c) for c in pools["lead"]],
        "scan": [tm(g1, c) for c in pools["scan"]],
        "trail": [tm(g0, c) for c in pools["trail"]],
    }


def _writeback_tree(pools: dict, caches: dict, tables, pos) -> dict:
    """Scatter each sequence's one new decode row back into its block."""
    def row(cache):
        return jax.vmap(
            lambda leaf, p: jax.lax.dynamic_slice_in_dim(leaf, p, 1, axis=0)
        )(cache, pos)

    def w0(pool, cache):
        return nn.paged_kv_write(pool, row(cache), tables, pos)

    def w1(pool, cache):
        return jax.vmap(w0)(pool, cache)

    tm = jax.tree_util.tree_map
    return {
        "lead": [tm(w0, p, c) for p, c in zip(pools["lead"], caches["lead"])],
        "scan": [tm(w1, p, c) for p, c in zip(pools["scan"], caches["scan"])],
        "trail": [tm(w0, p, c)
                  for p, c in zip(pools["trail"], caches["trail"])],
    }


def _scatter_tree(pools: dict, caches: dict, table_row, start, lo, hi,
                  width: int) -> dict:
    """Scatter view rows [start, start + width) of a B=1 cache tree into
    one sequence's blocks (outside [lo, hi) diverts to the scratch block)."""
    def s0(pool, cache):
        rows = jax.lax.dynamic_slice_in_dim(cache[0], start, width, axis=0)
        return nn.paged_kv_scatter(pool, rows, table_row, start, lo, hi)

    def s1(pool, cache):
        return jax.vmap(s0)(pool, cache)

    tm = jax.tree_util.tree_map
    return {
        "lead": [tm(s0, p, c) for p, c in zip(pools["lead"], caches["lead"])],
        "scan": [tm(s1, p, c) for p, c in zip(pools["scan"], caches["scan"])],
        "trail": [tm(s0, p, c)
                  for p, c in zip(pools["trail"], caches["trail"])],
    }


# ---------------------------------------------------------------------------
# manual tensor parallelism (shard_map: the collectives live in the trace)
# ---------------------------------------------------------------------------

def _tp_shard_map(body, mesh, in_specs, out_specs):
    # check_vma=False: psum-produced outputs defeat static replication
    # inference (and with it, psum binds as the plain `psum` primitive)
    return jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def _tp_cache_struct_specs(cfg: ModelConfig, max_len: int, tp: int):
    """PartitionSpec tree matching lm_prefill's returned cache tree (same
    treedef as ``init_lm_cache`` — specs only need leaf ranks)."""
    struct = jax.eval_shape(lambda: init_lm_cache(cfg, 1, max_len))
    return tp_mod.tp_cache_specs(struct, cfg, tp)


def _make_tp_paged_decode_step(cfg: ModelConfig, max_len: int, mesh,
                               tp: int, greedy: bool,
                               fused: bool) -> Callable:
    """shard_map variant of ``make_paged_decode_step``: every device runs
    the unchanged paged-decode body on its parameter/pool shards under the
    per-device config, and the per-block ``nn.tp_psum`` reductions (plus
    the ``nn.tp_vocab_gather`` on a sharded unembedding) become explicit
    COLLECTIVE primitives in the traced jaxpr."""
    local = tp_mod.tp_local_config(cfg, tp)
    vocab = tp_mod.tp_vocab_sharded(cfg, tp)

    def body(params, token, pos, pools, tables, key):
        with sharding.manual_axis("model", vocab_sharded=vocab), \
                nn.fuse(fused):
            working = cast_params(params, local.activation_dtype)
            caches = _gather_tree(pools, tables, max_len)
            logits, caches = lm_decode(working, token, pos, caches, local)
            pools = _writeback_tree(pools, caches, tables, pos)
            lf = logits.astype(jnp.float32)
            if greedy:
                nxt = jnp.argmax(lf, axis=-1).astype(jnp.int32)
            else:
                nxt = jax.random.categorical(key, lf, axis=-1).astype(jnp.int32)
        return nxt, pools

    rep = P()

    def paged_step(params, token, pos, pools, tables, key):
        pspecs = tp_mod.tp_param_specs(params, cfg, tp)
        cspecs = tp_mod.tp_cache_specs(pools, cfg, tp)
        return _tp_shard_map(
            body, mesh,
            in_specs=(pspecs, rep, rep, cspecs, rep, rep),
            out_specs=(rep, cspecs),
        )(params, token, pos, pools, tables, key)
    return paged_step


def _make_tp_paged_extend_step(cfg: ModelConfig, max_len: int, mesh,
                               tp: int, fused: bool) -> Callable:
    """shard_map variant of ``make_paged_extend_step`` (chunked prefill)."""
    local = tp_mod.tp_local_config(cfg, tp)
    vocab = tp_mod.tp_vocab_sharded(cfg, tp)

    def body(params, tokens, start, pools, table_row, lo, hi):
        with sharding.manual_axis("model", vocab_sharded=vocab), \
                nn.fuse(fused):
            working = cast_params(params, local.activation_dtype)
            caches = _gather_tree(pools, table_row[None, :], max_len)
            logits, caches = lm_extend(working, tokens, start, caches, local)
            pools = _scatter_tree(pools, caches, table_row, start, lo, hi,
                                  tokens.shape[1])
        return logits, pools

    rep = P()

    def extend_step(params, tokens, start, pools, table_row, lo, hi):
        pspecs = tp_mod.tp_param_specs(params, cfg, tp)
        cspecs = tp_mod.tp_cache_specs(pools, cfg, tp)
        return _tp_shard_map(
            body, mesh,
            in_specs=(pspecs, rep, rep, cspecs, rep, rep, rep),
            out_specs=(rep, cspecs),
        )(params, tokens, start, pools, table_row, lo, hi)
    return extend_step


def make_tp_prefill_step(cfg: ModelConfig, max_len: int, mesh,
                         fused: bool = False) -> Callable:
    """shard_map variant of ``serving.make_prefill_step`` for the cold
    admission path: same signature, but the returned B=1 cache tree is
    head-sharded (when TP divides ``n_kv_heads``) so it scatters straight
    into the engine's sharded pools."""
    tp = tp_mod.mesh_tp(mesh)
    local = tp_mod.tp_local_config(cfg, tp)
    vocab = tp_mod.tp_vocab_sharded(cfg, tp)
    cspecs = _tp_cache_struct_specs(cfg, max_len, tp)

    def body(params, tokens, lengths):
        with sharding.manual_axis("model", vocab_sharded=vocab), \
                nn.fuse(fused):
            working = cast_params(params, local.activation_dtype)
            return lm_prefill(working, tokens, local, max_len=max_len,
                              lengths=lengths)

    rep = P()

    def prefill_step(params, tokens, lengths=None):
        if lengths is None:
            lengths = jnp.full((tokens.shape[0],), tokens.shape[1],
                               jnp.int32)
        pspecs = tp_mod.tp_param_specs(params, cfg, tp)
        return _tp_shard_map(
            body, mesh,
            in_specs=(pspecs, rep, rep),
            out_specs=(rep, cspecs),
        )(params, tokens, lengths)
    return prefill_step


def make_paged_decode_step(cfg: ModelConfig, max_len: int, mesh=None,
                           greedy: bool = True,
                           fused: bool = False) -> Callable:
    """paged_step(params, token, pos, pools, tables, key) -> (token', pools').

    Gathers the block tables into a contiguous view, runs the UNCHANGED
    ``lm_decode`` program (same sampling tail as ``make_serve_step``), and
    scatters each sequence's new KV row back into its block.

    With ``fused=True`` the whole body runs under ``nn.fuse()``, which
    routes every layer's attention over the gathered paged KV through the
    ``attn_template:decode`` spec (one fused qk->mask->softmax->pv
    operator per layer, ``fused_attn_decode``) — the per-row ``pos + 1``
    valid-prefix lengths are exactly the decode-1q template's scalar-
    prefetch mask, so paged gather + template kernel compose without any
    paged-specific attention code.

    A mesh whose ``model`` axis is larger than 1 selects the manual-TP
    shard_map path (see ``repro.models.tp``): bit-identical token streams,
    explicit COLLECTIVE primitives in the captured program.
    """
    tp = tp_mod.mesh_tp(mesh)
    if tp > 1:
        return _make_tp_paged_decode_step(cfg, max_len, mesh, tp,
                                          greedy, fused)

    def paged_step(params, token, pos, pools, tables, key):
        with sharding.use_rules(mesh, cfg.fsdp, cfg.seq_shard), \
                nn.fuse(fused):
            working = cast_params(params, cfg.activation_dtype)
            caches = _gather_tree(pools, tables, max_len)
            logits, caches = lm_decode(working, token, pos, caches, cfg)
            pools = _writeback_tree(pools, caches, tables, pos)
            lf = logits.astype(jnp.float32)
            if greedy:
                nxt = jnp.argmax(lf, axis=-1).astype(jnp.int32)
            else:
                nxt = jax.random.categorical(key, lf, axis=-1).astype(jnp.int32)
        return nxt, pools
    return paged_step


def make_paged_extend_step(cfg: ModelConfig, max_len: int, mesh=None,
                           fused: bool = False) -> Callable:
    """extend_step(params, tokens (1, C), start, pools, table_row, lo, hi)
    -> (logits (1, C, V), pools').

    One chunked-prefill step for a single sequence: gather its full-depth
    view, run ``lm_extend`` at absolute offset ``start``, scatter the
    chunk's KV rows into its blocks. Rows outside [lo, hi) — the reused
    prefix on the left, bucket padding on the right — go to scratch.

    A mesh with a ``model`` axis larger than 1 selects the manual-TP
    shard_map path, like ``make_paged_decode_step``.
    """
    tp = tp_mod.mesh_tp(mesh)
    if tp > 1:
        return _make_tp_paged_extend_step(cfg, max_len, mesh, tp, fused)

    def extend_step(params, tokens, start, pools, table_row, lo, hi):
        with sharding.use_rules(mesh, cfg.fsdp, cfg.seq_shard), \
                nn.fuse(fused):
            working = cast_params(params, cfg.activation_dtype)
            caches = _gather_tree(pools, table_row[None, :], max_len)
            logits, caches = lm_extend(working, tokens, start, caches, cfg)
            pools = _scatter_tree(pools, caches, table_row, start, lo, hi,
                                  tokens.shape[1])
        return logits, pools
    return extend_step


def _scatter_cold_prefill(pools, one, table_row, hi, width: int):
    """Scatter a freshly prefilled B=1 cache tree's rows [0, width) into a
    sequence's blocks (pad rows past ``hi`` divert to scratch)."""
    zero = jnp.int32(0)
    return _scatter_tree(pools, one, table_row, zero, zero, hi, width)


# ---------------------------------------------------------------------------
# the engine
# ---------------------------------------------------------------------------

class PagedEngine(Engine):
    """Continuous-batching engine over paged KV blocks (vLLM-style).

    Admission paths:

    * cold prompt, no chunking — the parent's EXACT jitted prefill program
      runs (guaranteeing first-token bit parity with the contiguous
      engine), then its single-row cache is scattered into blocks;
    * prefix hit / long prompt — decode-interleaved ``lm_extend`` chunks:
      one chunk per engine step, the batch keeps decoding in between.

    Only full-depth positional caches page cleanly, so every layer must be
    plain full attention or MLA (no sliding-window ring buffers, no
    recurrent state).
    """

    def __init__(self, cfg: ModelConfig, params, max_batch: int = 8,
                 max_len: int = 512, block_size: int = 16,
                 num_blocks: Optional[int] = None,
                 chunk_size: Optional[int] = None,
                 prefix_caching: bool = True, **kw):
        bad = set(cfg.layer_kinds()) - {"attn"}
        if bad:
            raise ValueError(
                f"PagedEngine needs full-depth positional caches on every "
                f"layer; kinds {sorted(bad)} cannot page")
        mesh = kw.get("mesh")
        self.tp = tp_mod.mesh_tp(mesh)
        if self.tp > 1:
            tp_mod.validate_tp(cfg, self.tp)
        super().__init__(cfg, params, max_batch=max_batch, max_len=max_len,
                         **kw)
        self.block_size = block_size
        self.blocks_per_seq = -(-max_len // block_size)
        if num_blocks is None:
            # every slot's worst case + slack for the prefix cache + scratch
            num_blocks = 1 + (max_batch + 2) * self.blocks_per_seq
        self.allocator = BlockAllocator(num_blocks, block_size)
        self.prefix_cache = PrefixCache(self.allocator) \
            if prefix_caching else None
        self.chunk_size = chunk_size
        # the parent's contiguous shared cache is never used (its decode
        # and insert jits stay untraced — jax.jit is lazy)
        self._caches = None
        self._pools = init_lm_cache(cfg, num_blocks, block_size)
        if self.tp > 1:
            # place shards once at init: TP params (heads/mlp/vocab over
            # the model axis), head-sharded pools when TP divides
            # n_kv_heads (replicated GQA fallback otherwise). The data
            # axis replicates — block ids are global, so the paged batch
            # cannot shard. The cold-path prefill must also produce
            # head-sharded B=1 caches, so swap in the shard_map variant.
            self.params = jax.device_put(
                self.params,
                tp_mod.named_shardings(mesh, tp_mod.tp_param_specs(
                    self.params, cfg, self.tp)))
            self._pools = jax.device_put(
                self._pools,
                tp_mod.named_shardings(mesh, tp_mod.tp_cache_specs(
                    self._pools, cfg, self.tp)))
            self._prefill = jax.jit(
                make_tp_prefill_step(cfg, max_len, mesh, fused=self.fused))
        self._tables = np.zeros((max_batch, self.blocks_per_seq), np.int32)
        self._seq_blocks: List[List[int]] = [[] for _ in range(max_batch)]
        self._prefilling: Dict[int, dict] = {}
        self._paged_decode = jax.jit(
            make_paged_decode_step(cfg, max_len, mesh,
                                   greedy=self.greedy, fused=self.fused),
            donate_argnums=(3,))
        self._paged_extend = jax.jit(
            make_paged_extend_step(cfg, max_len, mesh, fused=self.fused),
            donate_argnums=(3,))
        self._scatter_cold = jax.jit(_scatter_cold_prefill,
                                     static_argnames=("width",),
                                     donate_argnums=(0,))

    # -- bookkeeping -------------------------------------------------------
    def _allocate(self, n: int) -> List[int]:
        out: List[int] = []
        for _ in range(n):
            bid = self.allocator.try_allocate()
            while bid is None and self.prefix_cache is not None \
                    and self.prefix_cache.evict_one():
                bid = self.allocator.try_allocate()
            if bid is None:
                raise RuntimeError(
                    "paged KV pool exhausted (and nothing evictable); "
                    "raise num_blocks or lower max_batch")
            out.append(bid)
        return out

    def _ensure_block(self, slot: int) -> None:
        """Guarantee the block for this slot's next KV write exists."""
        need = int(self._pos[slot]) // self.block_size
        blocks = self._seq_blocks[slot]
        while len(blocks) <= need:
            bid = self._allocate(1)[0]
            blocks.append(bid)
            self._tables[slot, len(blocks) - 1] = bid

    def _free(self, slot: int) -> None:
        for bid in self._seq_blocks[slot]:
            self.allocator.decref(bid)
        self._seq_blocks[slot] = []
        self._tables[slot, :] = 0
        super()._free(slot)

    def reset_stats(self) -> None:
        super().reset_stats()
        if self.prefix_cache is not None:
            self.prefix_cache.reset_counters()

    # -- admission ---------------------------------------------------------
    def _admit(self, slot: int, req: Request) -> bool:
        if req.admit_t == 0.0:
            req.admit_t = self.clock()
        plen = len(req.prompt)
        cached_len, reused = 0, []
        if self.prefix_cache is not None:
            cached_len, reused = self.prefix_cache.lookup(req.prompt)
        if cached_len == 0 and (self.chunk_size is None
                                or plen <= self.chunk_size):
            return self._admit_cold(slot, req)
        return self._start_chunked(slot, req, cached_len, reused)

    def _admit_cold(self, slot: int, req: Request) -> bool:
        """Whole-prompt admission through the parent's prefill program."""
        plen = len(req.prompt)
        bucket = self._bucket(plen)
        toks = np.full((1, bucket), self.pad_id, np.int32)
        toks[0, :plen] = req.prompt
        t0 = time.perf_counter()
        logits, one = self._prefill(self.params, jnp.asarray(toks),
                                    jnp.full((1,), plen, jnp.int32))
        first = self._first_token(logits)
        live = not ((self.eos_id is not None and first == self.eos_id)
                    or req.max_new_tokens <= 1
                    or plen >= self.max_len)
        if live:
            blocks = self._allocate(-(-plen // self.block_size))
            self._seq_blocks[slot] = blocks
            self._tables[slot, :] = 0
            self._tables[slot, :len(blocks)] = blocks
            self._pools = self._scatter_cold(
                self._pools, one, jnp.asarray(self._tables[slot]),
                jnp.int32(plen), width=bucket)
            jax.block_until_ready(self._pools)
            if self.prefix_cache is not None:
                self.prefix_cache.insert(req.prompt, blocks)
        self.stats.prefill_s += time.perf_counter() - t0
        self.stats.prefill_tokens += plen

        req.output.append(first)
        self.stats.first_tokens += 1
        req.first_token_t = self.clock()
        if not live:
            self._finish(req)
            return False
        self.slots[slot] = req
        self._pos[slot] = plen
        self._cur[slot] = first
        return True

    def _chunk_plan(self, cached: int, plen: int) -> List[Tuple[int, int]]:
        """-> [(start, width)] covering [cached, plen); never overlaps the
        cached prefix and never overruns max_len (no silent clamping)."""
        if self.chunk_size is None:
            rem = plen - cached
            w = min(_next_pow2(max(rem, self.min_prefill_bucket)),
                    self.max_len)
            if cached + w > self.max_len:
                w = rem                 # exact width near the context edge
            return [(cached, w)]
        chunks: List[Tuple[int, int]] = []
        pos = cached
        while pos < plen:
            w = self.chunk_size if pos + self.chunk_size <= self.max_len \
                else plen - pos
            chunks.append((pos, w))
            pos += w
        return chunks

    def _start_chunked(self, slot: int, req: Request, cached_len: int,
                       reused: List[int]) -> bool:
        """Begin a decode-interleaved chunked admission (prefix hits land
        here too: only the uncached suffix prefills)."""
        plen = len(req.prompt)
        blocks = list(reused)
        blocks += self._allocate(-(-plen // self.block_size) - len(blocks))
        row = np.zeros((self.blocks_per_seq,), np.int32)
        row[:len(blocks)] = blocks
        self._prefilling[slot] = {
            "req": req, "plen": plen, "cached": cached_len,
            "row": row, "blocks": blocks,
            "chunks": self._chunk_plan(cached_len, plen), "next": 0,
        }
        # occupy the slot, but keep its GLOBAL table row zeroed: batch
        # decode treats it as dead (pad token, pos 0, writes to scratch)
        # until the last chunk lands
        self.slots[slot] = req
        self._seq_blocks[slot] = blocks
        self._pos[slot] = 0
        self._cur[slot] = self.pad_id
        return True

    def _prefill_chunk(self, slot: int) -> Optional[Request]:
        """Run ONE chunk for a prefilling slot; on the last chunk, emit the
        first token and promote the slot to decoding (or finish it).
        Returns the request if it completed at admission."""
        st = self._prefilling[slot]
        req: Request = st["req"]
        plen: int = st["plen"]
        start, w = st["chunks"][st["next"]]
        toks = np.full((1, w), self.pad_id, np.int32)
        real = req.prompt[start:min(start + w, plen)]
        toks[0, :len(real)] = real
        t0 = time.perf_counter()
        logits, self._pools = self._paged_extend(
            self.params, jnp.asarray(toks), jnp.int32(start), self._pools,
            jnp.asarray(st["row"]), jnp.int32(st["cached"]),
            jnp.int32(plen))
        st["next"] += 1
        if st["next"] < len(st["chunks"]):
            jax.block_until_ready(self._pools)
            self.stats.prefill_s += time.perf_counter() - t0
            return None

        # last chunk: the prompt's final real token sits at row plen-1-start
        first = self._first_token(logits[:, plen - 1 - start])
        jax.block_until_ready(self._pools)
        self.stats.prefill_s += time.perf_counter() - t0
        self.stats.prefill_tokens += plen
        del self._prefilling[slot]

        req.output.append(first)
        self.stats.first_tokens += 1
        req.first_token_t = self.clock()
        if self.prefix_cache is not None:
            self.prefix_cache.insert(req.prompt, st["blocks"])
        live = not ((self.eos_id is not None and first == self.eos_id)
                    or req.max_new_tokens <= 1
                    or plen >= self.max_len)
        if not live:
            self._finish(req)
            self._free(slot)
            return req
        self._tables[slot, :] = 0
        self._tables[slot, :len(st["blocks"])] = st["blocks"]
        self._pos[slot] = plen
        self._cur[slot] = first
        return None

    # -- stepping ----------------------------------------------------------
    def step(self) -> List[Request]:
        finished = self._admit_free_slots()

        # one chunk per prefilling slot per step (decode-interleaved)
        for slot in list(self._prefilling):
            done = self._prefill_chunk(slot)
            if done is not None:
                finished.append(done)

        live = [i for i, r in enumerate(self.slots)
                if r is not None and i not in self._prefilling]
        if not live:
            return finished
        for i in live:
            assert self._pos[i] < self.max_len
            self._ensure_block(i)

        t0 = time.perf_counter()
        self.key, k = jax.random.split(self.key)
        nxt, self._pools = self._paged_decode(
            self.params, jnp.asarray(self._cur), jnp.asarray(self._pos),
            self._pools, jnp.asarray(self._tables), k)
        nxt_host = np.asarray(jax.block_until_ready(nxt))
        self.stats.decode_s += time.perf_counter() - t0
        self.stats.decode_steps += 1

        for i in live:
            r = self.slots[i]
            tok = int(nxt_host[i])
            r.output.append(tok)
            self.stats.decode_tokens += 1
            self._pos[i] += 1
            self._cur[i] = tok
            if (self.eos_id is not None and tok == self.eos_id) \
                    or len(r.output) >= r.max_new_tokens \
                    or self._pos[i] >= self.max_len:
                self._finish(r)
                finished.append(r)
                self._free(i)
        return finished
