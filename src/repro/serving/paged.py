"""Paged-KV serving: block allocator, prefix cache, chunked prefill.

``PagedEngine`` shares ``BaseEngine``'s host side (queue, slot table,
stats, admission bookkeeping) with the contiguous ``Engine``, but in place
of its single ``(max_batch, max_len, ...)`` KV cache it owns a pool of
fixed-size KV blocks (``(num_blocks, block_size, ...)`` per cache leaf)
managed by a free-list :class:`BlockAllocator` and addressed through
per-sequence block tables — the vLLM paging scheme, append-only so no
copy-on-write is ever needed.

Its step programs — decode, extend (chunked prefill) and cold prefill —
are each one model body, which :func:`_step_program` runs on one chip
or, for a mesh whose ``model`` axis is larger than 1, under manual tensor
parallelism (``shard_map``, ``repro.models.tp``).

Three mechanisms ride on the block tables:

* **paged decode** — on a kernel backend with ``fused=True`` and plain
  attention layers, the decode program reads the pool in place: the
  stacked pools ride through the layer scan as its carry, and each layer
  writes its sequences' new K/V rows into their blocks
  (``nn.paged_kv_write``) and runs the ``attn_template:paged_decode``
  kernel, whose index maps walk the block tables and copy only live
  blocks. No contiguous view is made. Elsewhere (MLA, TP with replicated
  pools, ``fused=False``, the ``jnp`` backend) every step gathers each
  sequence's blocks into a contiguous ``(B, max_len, ...)`` view
  (``nn.paged_kv_gather``), runs the UNCHANGED ``lm_decode`` program on
  it, then writes the one new KV row per sequence back into its block.
  Stale rows in the view are hidden by decode's per-row ``arange <= pos``
  mask, whose masked terms are exact zeros — which is what makes that
  path bit-identical to the contiguous engine.
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

Decode runs one step ahead of the host (``PagedEngine.step``): step n+1 is
queued, fed with step n's tokens on the device, before step n's tokens are
read, so the device computes while the host retires, admits and prepares.
"""

from __future__ import annotations

from collections import OrderedDict
from typing import Callable, Dict, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro import nn, sharding
from repro.models import init_lm_cache, lm_decode, lm_extend
from repro.models import tp as tp_mod
from repro.models.attention import fit_head
from repro.models.common import ModelConfig
from repro.runtime import cast_params
from repro.serving import (BaseEngine, Request, _named_like, _next_pow2,
                           _prefill_body, _rules_program)


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
# jitted paged programs
# ---------------------------------------------------------------------------

def decode_kv_path(cfg: ModelConfig, fused: bool, tp: int = 1) -> str:
    """How paged decode reaches the KV cache: ``"paged"`` (the kernel reads
    the pool in place) or ``"gathered"`` (a contiguous view per step).

    Decided from what the step program can see: the paged kernel needs
    the fused path on a kernel backend, plain (non-MLA) attention on every
    layer, and a local pool that holds the local config's KV heads (one
    chip, or TP that divides ``n_kv_heads``).
    """
    kernel = fused and nn.get_backend() != "jnp"
    plain = set(cfg.layer_kinds()) == {"attn"} and not cfg.mla
    heads = tp <= 1 or tp_mod.tp_kv_sharded(cfg, tp)
    return "paged" if kernel and plain and heads else "gathered"


#: lane width of the device's vector tiles
LANES = 128


def init_pools(cfg: ModelConfig, num_blocks: int, block_size: int,
               kv: str = "gathered") -> dict:
    """The block pools: ``init_lm_cache(cfg, num_blocks, block_size)``; for
    the ``"paged"`` decode path (:func:`decode_kv_path`) each leaf's last
    (head) dim is zero-padded to a multiple of :data:`LANES`.

    The device's default layout keeps a leaf row-major only where its last
    dim fills whole lane tiles; for an 80-wide head it puts the block dim
    innermost instead (least padding), which strides every block through
    the whole pool, and a program that reads blocks in place converts the
    whole pool first. A layout forced per program does not survive the
    persistent compile cache (a program loaded from it expects the default
    layout), so the pool carries the padding itself. Rows are padded on
    their way in and gathered views cut on their way out
    (``attention.fit_head``); on the gathered path the pools keep the
    model's widths and its programs are unchanged.
    """
    if kv != "paged":
        return init_lm_cache(cfg, num_blocks, block_size)
    width = -(-cfg.resolved_head_dim // LANES) * LANES
    struct = jax.eval_shape(lambda: init_lm_cache(cfg, num_blocks,
                                                  block_size))
    return jax.tree_util.tree_map(
        lambda s: jnp.zeros(s.shape[:-1] + (width,), s.dtype), struct)


def _map_cache(fn: Callable, *trees) -> dict:
    """``fn`` over the leaves of cache trees of one structure (pools or
    caches, ``init_lm_cache``'s ``lead`` / ``scan`` / ``trail`` lists),
    vmapped over the leading layer dim of the stacked ``scan`` leaves."""
    def each(part, f):
        return [jax.tree_util.tree_map(f, *leaves)
                for leaves in zip(*(t[part] for t in trees))]
    return {"lead": each("lead", fn), "scan": each("scan", jax.vmap(fn)),
            "trail": each("trail", fn)}


def _gather_tree(pools: dict, tables, max_len: int,
                 head_dim: Optional[int] = None) -> dict:
    """Materialize the contiguous (B, max_len, ...) cache view per leaf;
    with ``head_dim`` (lane-padded pools) each view is cut back to it."""
    def gather(pool):
        view = nn.paged_kv_gather(pool, tables, max_len)
        return view if head_dim is None else fit_head(view, head_dim)
    return _map_cache(gather, pools)


def _view_head_dim(cfg: ModelConfig, fused: bool, tp: int) -> Optional[int]:
    """The model's head dim where the engine's pools are lane-padded (the
    paged decode path, :func:`init_pools`), else None."""
    if decode_kv_path(cfg, fused, tp) == "paged":
        return cfg.resolved_head_dim
    return None


def _writeback_tree(pools: dict, caches: dict, tables, pos) -> dict:
    """Scatter each sequence's one new decode row back into its block."""
    def write(pool, cache):
        row = jax.vmap(
            lambda leaf, p: jax.lax.dynamic_slice_in_dim(leaf, p, 1, axis=0)
        )(cache, pos)
        return nn.paged_kv_write(pool, row, tables, pos)
    return _map_cache(write, pools, caches)


def _scatter_tree(pools: dict, caches: dict, table_row, start, lo, hi,
                  width: int) -> dict:
    """Scatter view rows [start, start + width) of a B=1 cache tree into
    one sequence's blocks (outside [lo, hi) diverts to the scratch block)."""
    def scatter(pool, cache):
        rows = jax.lax.dynamic_slice_in_dim(cache[0], start, width, axis=0)
        return nn.paged_kv_scatter(pool, fit_head(rows, pool.shape[-1]),
                                   table_row, start, lo, hi)
    return _map_cache(scatter, pools, caches)


# ---------------------------------------------------------------------------
# the step programs: one body each, on one chip or under manual TP
# ---------------------------------------------------------------------------

def _step_program(body: Callable, cfg: ModelConfig, mesh,
                  fused: bool) -> Callable:
    """program(params, *args) running ``body(local_cfg, working, *args)``.

    ``body`` returns ``(array, cache tree)``; its cache-tree arguments (the
    only dict arguments: pools, or prefilled B=1 caches) have the
    structure of ``init_lm_cache``. Without a mesh, or with a ``model``
    axis of 1, this is ``serving._rules_program``: the body under the
    mesh's GSPMD rules on the whole model. A larger ``model`` axis selects
    manual tensor parallelism (``repro.models.tp``): a ``shard_map`` in
    which every device runs the body on its parameter and KV shards under
    ``tp_local_config``, so the per-block ``nn.tp_psum`` reductions (and
    the ``nn.tp_vocab_gather`` of a sharded unembedding) are explicit
    COLLECTIVE primitives in the traced program. Parameters shard by
    ``tp_param_specs``, cache trees by ``tp_cache_specs``, everything else
    replicates.
    """
    tp = tp_mod.mesh_tp(mesh)
    if tp <= 1:
        return _rules_program(body, cfg, mesh, fused)
    local = tp_mod.tp_local_config(cfg, tp)
    vocab = tp_mod.tp_vocab_sharded(cfg, tp)
    # spec trees need only the tree structure and leaf ranks
    cspecs = tp_mod.tp_cache_specs(
        jax.eval_shape(lambda: init_lm_cache(cfg, 1, 1)), cfg, tp)
    rep = P()

    def shard(params, *args):
        with sharding.manual_axis("model", vocab_sharded=vocab), \
                nn.fuse(fused):
            working = cast_params(params, local.activation_dtype)
            return body(local, working, *args)

    def program(params, *args):
        pspecs = tp_mod.tp_param_specs(params, cfg, tp)
        # check_vma=False: psum-produced outputs defeat static replication
        # inference (and with it, psum binds as the plain `psum` primitive)
        return jax.shard_map(
            shard, mesh=mesh,
            in_specs=(pspecs,) + tuple(cspecs if isinstance(a, dict)
                                       else rep for a in args),
            out_specs=(rep, cspecs), check_vma=False,
        )(params, *args)
    return _named_like(program, body)


def make_paged_decode_step(cfg: ModelConfig, max_len: int, mesh=None,
                           greedy: bool = True,
                           fused: bool = False) -> Callable:
    """paged_step(params, token, pos, pools, tables, key) -> (token', pools').

    One decode step over the block pools, with the same sampling tail as
    ``make_serve_step``. The path is fixed here, by
    :func:`decode_kv_path` under the backend in force now, and recorded as
    the returned function's ``kv`` attribute:

    * ``"paged"`` — ``lm_decode(..., tables=tables)``: the layer scan
      carries the pools; each layer writes its sequences' new K/V rows
      into their blocks in place and runs the fused decode operator on the
      pool (the ``attn_template:paged_decode`` kernel reads the live
      blocks through the tables). The only bytes touched per layer are
      the new rows and the live blocks.
    * ``"gathered"`` — gathers the block tables into a contiguous view,
      runs the UNCHANGED ``lm_decode`` program on it, and writes each
      sequence's new KV row back into its block; under ``nn.fuse()`` the
      attention over the view is the ``attn_template:decode`` kernel on a
      kernel backend.

    A mesh whose ``model`` axis is larger than 1 selects the manual-TP
    shard_map path (:func:`_step_program`): bit-identical token streams,
    explicit COLLECTIVE primitives in the captured program.
    """
    kv = decode_kv_path(cfg, fused, tp_mod.mesh_tp(mesh))

    def paged_step(cfg, working, token, pos, pools, tables, key):
        if kv == "paged":
            logits, pools = lm_decode(working, token, pos, pools, cfg,
                                      tables=tables)
        else:
            caches = _gather_tree(pools, tables, max_len)
            logits, caches = lm_decode(working, token, pos, caches, cfg)
            pools = _writeback_tree(pools, caches, tables, pos)
        lf = logits.astype(jnp.float32)
        if greedy:
            nxt = jnp.argmax(lf, axis=-1).astype(jnp.int32)
        else:
            nxt = jax.random.categorical(key, lf, axis=-1).astype(jnp.int32)
        return nxt, pools

    program = _step_program(paged_step, cfg, mesh, fused)
    program.kv = kv
    return program


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
    head_dim = _view_head_dim(cfg, fused, tp_mod.mesh_tp(mesh))

    def extend_step(cfg, working, tokens, start, pools, table_row, lo, hi):
        caches = _gather_tree(pools, table_row[None, :], max_len, head_dim)
        logits, caches = lm_extend(working, tokens, start, caches, cfg)
        pools = _scatter_tree(pools, caches, table_row, start, lo, hi,
                              tokens.shape[1])
        return logits, pools

    return _step_program(extend_step, cfg, mesh, fused)


def _scatter_cold_prefill(pools, one, table_row, hi, width: int):
    """Scatter a freshly prefilled B=1 cache tree's rows [0, width) into a
    sequence's blocks (pad rows past ``hi`` divert to scratch)."""
    zero = jnp.int32(0)
    return _scatter_tree(pools, one, table_row, zero, zero, hi, width)


def _merge_tokens(nxt, keep, host):
    """Step n's device tokens where ``keep``, the host's tokens elsewhere."""
    return jnp.where(keep, nxt, host)


# ---------------------------------------------------------------------------
# the engine
# ---------------------------------------------------------------------------

class _InFlight(NamedTuple):
    """A decode step queued on the device whose tokens are not yet read."""
    nxt: jax.Array                  # (max_batch,) tokens, on the device
    rows: Dict[int, Request]        # slot -> request its token goes to


class PagedEngine(BaseEngine):
    """Continuous-batching engine over paged KV blocks (vLLM-style).

    Takes ``BaseEngine``'s arguments besides its own. Admission paths:

    * cold prompt, no chunking — the whole prompt prefills through the
      same prefill body as the contiguous ``Engine``'s (on one chip the
      same program, which keeps first tokens bit-identical to it), then its
      single-row cache is scattered into blocks;
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
        decode = make_paged_decode_step(cfg, max_len, mesh,
                                        greedy=self.greedy, fused=self.fused)
        #: how decode reaches the KV cache, "paged" or "gathered"
        #: (``decode_kv_path``); the ``serve.decode`` span carries it
        self.kv_path = decode.kv
        self._pools = init_pools(cfg, num_blocks, block_size, self.kv_path)
        if self.tp > 1:
            # place shards once at init: TP params (heads/mlp/vocab over
            # the model axis), head-sharded pools when TP divides
            # n_kv_heads (replicated GQA fallback otherwise). The data
            # axis replicates — block ids are global, so the paged batch
            # cannot shard.
            self.params = jax.device_put(
                self.params,
                tp_mod.named_shardings(mesh, tp_mod.tp_param_specs(
                    self.params, cfg, self.tp)))
            self._pools = jax.device_put(
                self._pools,
                tp_mod.named_shardings(mesh, tp_mod.tp_cache_specs(
                    self._pools, cfg, self.tp)))
        self._tables = np.zeros((max_batch, self.blocks_per_seq), np.int32)
        self._seq_blocks: List[List[int]] = [[] for _ in range(max_batch)]
        self._prefilling: Dict[int, dict] = {}
        # under TP the prefilled B=1 caches come out head-sharded like the
        # pools, so they scatter straight in
        self._prefill = jax.jit(_step_program(_prefill_body(max_len), cfg,
                                              mesh, self.fused))
        self._paged_decode = jax.jit(decode, donate_argnums=(3,))
        self._paged_extend = jax.jit(
            make_paged_extend_step(cfg, max_len, mesh, fused=self.fused),
            donate_argnums=(3,))
        self._scatter_cold = jax.jit(_scatter_cold_prefill,
                                     static_argnames=("width",),
                                     donate_argnums=(0,))
        self._merge = jax.jit(_merge_tokens)
        self._merge_compiled = False
        #: decode steps queued and not yet read, oldest first (at most two,
        #: and one between calls: see ``step``)
        self._inflight: List[_InFlight] = []

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
        plen = len(req.prompt)
        with self._span("serve.admit", uid=req.uid,
                        prompt_len=plen) as span:
            cached_len, reused = 0, []
            if self.prefix_cache is not None:
                cached_len, reused = self.prefix_cache.lookup(req.prompt)
            req.cached_len = cached_len
            cold = cached_len == 0 and (self.chunk_size is None
                                        or plen <= self.chunk_size)
            span.set_metadata(cached_len=cached_len,
                              path="cold" if cold else "chunked")
            if cold:
                return self._admit_cold(slot, req)
            return self._start_chunked(slot, req, cached_len, reused)

    def _admit_cold(self, slot: int, req: Request) -> bool:
        """Whole-prompt admission: prefill, then scatter into new blocks."""
        first, one, bucket = self._prefill_cold(req)

        def scatter():
            n = -(-len(req.prompt) // self.block_size)
            with self._span("serve.scatter", "prefill_s", uid=req.uid,
                            blocks=n):
                blocks = self._allocate(n)
                self._seq_blocks[slot] = blocks
                self._set_table(slot, blocks)
                self._pools = self._scatter_cold(
                    self._pools, one, jnp.asarray(self._tables[slot]),
                    jnp.int32(len(req.prompt)), width=bucket)
                jax.block_until_ready(self._pools)
                if self.prefix_cache is not None:
                    self.prefix_cache.insert(req.prompt, blocks)
        return self._seat(slot, req, first, scatter)

    def _set_table(self, slot: int, blocks: List[int]) -> None:
        """Point ``slot``'s global block-table row at ``blocks``."""
        self._tables[slot, :] = 0
        self._tables[slot, :len(blocks)] = blocks

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
        with self._span("serve.chunk", "prefill_s", uid=req.uid,
                        start=start, width=w):
            logits, self._pools = self._paged_extend(
                self.params, jnp.asarray(toks), jnp.int32(start),
                self._pools, jnp.asarray(st["row"]), jnp.int32(st["cached"]),
                jnp.int32(plen))
            st["next"] += 1
            last = st["next"] == len(st["chunks"])
            if last:
                # the prompt's final real token sits at row plen-1-start
                first = self._first_token(logits[:, plen - 1 - start])
            jax.block_until_ready(self._pools)
        if not last:
            return None
        del self._prefilling[slot]
        # registered before a finishing request frees its blocks
        if self.prefix_cache is not None:
            self.prefix_cache.insert(req.prompt, st["blocks"])
        if self._seat(slot, req, first,
                      lambda: self._set_table(slot, st["blocks"])):
            return None
        return req

    # -- stepping ----------------------------------------------------------
    def _ends_by_length(self, req: Request, n: int) -> bool:
        """Whether ``req`` is done by its budget or the context once it
        holds ``n`` output tokens (its next write would be at position
        ``len(prompt) + n - 1``)."""
        return n >= req.max_new_tokens \
            or len(req.prompt) + n - 1 >= self.max_len

    def _riding(self) -> Dict[int, Request]:
        """slot -> request of every slot whose next decode step yields a
        token it will keep: decoding (not prefilling), and not ended by
        length once the tokens already in flight for it are counted."""
        rows = {}
        for i, r in enumerate(self.slots):
            if r is None or i in self._prefilling:
                continue
            owed = sum(s.rows.get(i) is r for s in self._inflight)
            if not self._ends_by_length(r, len(r.output) + owed):
                rows[i] = r
        return rows

    def _dispatch(self, rows: Dict[int, Request]) -> None:
        """Queue one decode step on the device for the slots in ``rows``.

        Every other slot is a dead row: pad token, position 0, zeroed table
        row, so its write lands in the scratch block. A slot that rode in
        the step queued before, with the same request, is fed that step's
        token as it sits on the device; the host's token (``_cur``: a first
        token, or pad) is merged in only when the set of slots changed."""
        prev = self._inflight[-1] if self._inflight else None
        ride = np.zeros((self.max_batch,), bool)
        ride[list(rows)] = True
        for i in rows:
            assert self._pos[i] < self.max_len
            self._ensure_block(i)
        with self._span("serve.decode.prep", "decode_s"):
            self.key, k = jax.random.split(self.key)
            host = np.where(ride, self._cur, self.pad_id).astype(np.int32)
            if prev is None:
                cur = jnp.asarray(host)
            else:
                keep = np.array([i in rows and prev.rows.get(i) is rows[i]
                                 for i in range(self.max_batch)])
                if keep.sum() == len(rows) == len(prev.rows):
                    cur = prev.nxt          # the same slots and requests
                else:
                    cur = self._merge(prev.nxt, keep, host)
            pos = jnp.asarray(np.where(ride, self._pos, 0))
            tables = jnp.asarray(np.where(ride[:, None], self._tables, 0))
        with self._span("serve.decode.dispatch", "decode_s"):
            nxt, self._pools = self._paged_decode(
                self.params, cur, pos, self._pools, tables, k)
        if not self._merge_compiled:
            # compile the merge on the tokens of the engine's first step:
            # a step that needs it may come long after any warm-up
            self._merge(nxt, ride, host)
            self._merge_compiled = True
        self._pos[ride] += 1
        self.stats.decode_steps += 1
        if prev is not None:
            self.stats.overlapped_steps += 1
        self._inflight.append(_InFlight(nxt, rows))

    def _read_tokens(self, step: _InFlight) -> np.ndarray:
        """Wait for a queued decode step and copy its tokens to the host."""
        return np.asarray(jax.block_until_ready(step.nxt))

    def step(self) -> List[Request]:
        """Admit, then decode with one step of lookahead: queue decode step
        n+1 (fed with step n's tokens on the device) before reading step n,
        so the device computes while the host retires, admits and prepares.
        With nothing in flight, steps n and n+1 go out back to back. Each
        call delivers one token per slot that decoded in step n."""
        finished = self._admit_free_slots()

        # one chunk per prefilling slot per step (decode-interleaved)
        for slot in list(self._prefilling):
            done = self._prefill_chunk(slot)
            if done is not None:
                finished.append(done)

        rows = self._riding()
        if not self._inflight and not rows:
            return finished
        with self._span("serve.decode", kv=self.kv_path) as decode:
            if not self._inflight:
                self._dispatch(rows)
                rows = self._riding()
            if rows:
                self._dispatch(rows)
            step = self._inflight.pop(0)
            decode.set_metadata(
                live=sum(not r.done for r in step.rows.values()),
                ahead=len(self._inflight))
            with self._span("serve.decode.wait", "decode_s"):
                nxt_host = self._read_tokens(step)
            with self._span("serve.retire") as span:
                n = len(finished)
                for i, r in step.rows.items():
                    if r.done:
                        continue        # ended by EOS a step earlier
                    tok = int(nxt_host[i])
                    r.output.append(tok)
                    self.stats.decode_tokens += 1
                    eos = self.eos_id is not None and tok == self.eos_id
                    if eos or self._ends_by_length(r, len(r.output)):
                        # A slot ended by length never rides the step in
                        # flight (_riding counts its owed token). One ended
                        # by EOS does: that token is thrown away, and the
                        # step's KV write lands at the slot's next position
                        # in a block the slot still owned when the step was
                        # queued. Whoever gets the block next writes or
                        # masks that row in programs queued after it, and
                        # decode never writes below len(prompt), so no
                        # block of the prefix cache is touched.
                        if any(s.rows.get(i) is r for s in self._inflight):
                            self.stats.discarded_tokens += 1
                        self._finish(r)
                        finished.append(r)
                        self._free(i)
                span.set_metadata(finished=len(finished) - n)
            if self._inflight and all(
                    r.done for r in self._inflight[0].rows.values()):
                # every token of the step in flight is thrown away: forget
                # it (its pools are already the engine's); a later call
                # starts from the host's tokens
                self._inflight.clear()
        return finished
