"""Paged-KV serving engine: allocator/prefix-cache units, bit-parity with
the contiguous engine, chunked prefill, and the kv_cache_update bounds +
queue-wait-clock regression tests."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import nn
from repro.configs import get_config, reduced
from repro.models import init_lm
from repro.serving import BlockAllocator, Engine, PagedEngine, PrefixCache


def tiny_cfg():
    return reduced(get_config("granite-3-8b")).replace(
        n_layers=2, loss_chunk=0)


@pytest.fixture(scope="module")
def paged_model():
    cfg = tiny_cfg()
    return cfg, init_lm(jax.random.PRNGKey(0), cfg)


def mk_paged(cfg, params, **kw):
    kw.setdefault("max_batch", 3)
    kw.setdefault("max_len", 64)
    kw.setdefault("block_size", 8)
    return PagedEngine(cfg, params, **kw)


def run_both(cfg, params, prompts_and_budgets, **paged_kw):
    """Same requests through the contiguous and paged engines; returns
    (ref_outputs, paged_outputs, paged_engine) keyed by uid."""
    ref = Engine(cfg, params, max_batch=paged_kw.get("max_batch", 3),
                 max_len=paged_kw.get("max_len", 64))
    paged = mk_paged(cfg, params, **paged_kw)
    for prompt, budget in prompts_and_budgets:
        ref.add_request(prompt, max_new_tokens=budget)
        paged.add_request(prompt, max_new_tokens=budget)
    ref_out = {r.uid: r.output for r in ref.run()}
    paged_out = {r.uid: r.output for r in paged.run()}
    return ref_out, paged_out, paged


# -- BlockAllocator --------------------------------------------------------

def test_allocator_reserves_scratch_and_recycles():
    a = BlockAllocator(num_blocks=5, block_size=8)
    assert a.free_blocks == 4                      # block 0 is scratch
    blocks = a.allocate(4)
    assert 0 not in blocks and len(set(blocks)) == 4
    assert a.free_blocks == 0
    for b in blocks:
        a.decref(b)
    assert a.free_blocks == 4
    # refcounted sharing: the block frees only at the last decref
    b = a.allocate(1)[0]
    a.incref(b)
    a.decref(b)
    assert a.free_blocks == 3
    a.decref(b)
    assert a.free_blocks == 4


def test_allocator_exhaustion_raises():
    a = BlockAllocator(num_blocks=3, block_size=8)
    a.allocate(2)
    assert a.try_allocate() is None
    with pytest.raises(RuntimeError):
        a.allocate(1)
    with pytest.raises(ValueError):
        BlockAllocator(num_blocks=1, block_size=8)


# -- PrefixCache -----------------------------------------------------------

def test_prefix_cache_lookup_caps_and_refcounts():
    a = BlockAllocator(num_blocks=8, block_size=4)
    c = PrefixCache(a)
    prompt = list(range(1, 13))                    # 12 tokens = 3 blocks
    blocks = a.allocate(3)
    c.insert(prompt, blocks)
    assert len(c) == 3
    # the sequence finished: the cache becomes the blocks' only holder
    for b in blocks:
        a.decref(b)

    # full-prompt hit is capped: >= 1 suffix token must still prefill
    cached, reused = c.lookup(prompt)
    assert cached == 8 and reused == blocks[:2]
    for b in reused:
        a.decref(b)

    # an unrelated prompt misses entirely
    cached, reused = c.lookup([99, 98, 97, 96, 95])
    assert cached == 0 and reused == []
    assert c.hit_rate == pytest.approx(0.5)

    # eviction only touches entries nobody references
    free_before = a.free_blocks
    cached, reused = c.lookup(prompt)              # pins blocks[0:2]
    assert c.evict_one()                           # drops the unpinned tail
    assert a.free_blocks == free_before + 1
    for b in reused:
        a.decref(b)


def test_prefix_cache_insert_keeps_existing_entries():
    a = BlockAllocator(num_blocks=8, block_size=4)
    c = PrefixCache(a)
    prompt = list(range(1, 9))
    first = a.allocate(2)
    c.insert(prompt, first)
    second = a.allocate(2)
    c.insert(prompt, second)                       # duplicates: no-op
    cached, reused = c.lookup(prompt + [42, 43, 44, 45])
    assert reused == first[:2]


# -- parity with the contiguous engine (ISSUE acceptance) ------------------

def test_paged_parity_mixed_lengths(paged_model):
    """Paged engine outputs are bit-identical to the contiguous engine's
    across mixed prompt lengths and budgets, with more requests than
    slots (EOS-free continuous batching refill)."""
    cfg, params = paged_model
    rng = np.random.RandomState(0)
    reqs = [(rng.randint(1, cfg.vocab_size, size=rng.randint(3, 41)).tolist(),
             int(rng.randint(2, 9))) for _ in range(8)]
    ref_out, paged_out, eng = run_both(cfg, params, reqs)
    assert paged_out == ref_out
    # every allocated block came back when its sequence finished
    assert eng.allocator.free_blocks + len(eng.prefix_cache) == \
        eng.allocator.num_blocks - 1


def test_paged_parity_chunked_prefill(paged_model):
    """Long prompts admitted as decode-interleaved chunks (including the
    unbucketed final chunk at the context edge) stay bit-identical."""
    cfg, params = paged_model
    rng = np.random.RandomState(1)
    reqs = [(rng.randint(1, cfg.vocab_size, size=n).tolist(), 4)
            for n in (3, 17, 33, 40, 23, 9)]
    ref_out, paged_out, _ = run_both(cfg, params, reqs, chunk_size=16)
    assert paged_out == ref_out


def test_paged_parity_prefix_cache_hits(paged_model):
    """Shared-prefix requests reuse cached blocks (hit rate > 0) without
    changing a single output bit vs the cache-disabled engine."""
    cfg, params = paged_model
    rng = np.random.RandomState(2)
    prefix = rng.randint(1, cfg.vocab_size, size=24).tolist()
    reqs = [(prefix + rng.randint(1, cfg.vocab_size, size=6).tolist(), 3)
            for _ in range(4)]

    cold = mk_paged(cfg, params, chunk_size=16, prefix_caching=False)
    warm = mk_paged(cfg, params, chunk_size=16, prefix_caching=True)
    for prompt, budget in reqs:
        cold.add_request(prompt, max_new_tokens=budget)
        warm.add_request(prompt, max_new_tokens=budget)
    cold_out = {r.uid: r.output for r in cold.run()}
    warm_out = {r.uid: r.output for r in warm.run()}
    assert warm_out == cold_out
    assert warm.prefix_cache.hit_rate > 0
    assert cold.prefix_cache is None


def test_requests_count_the_prompt_tokens_the_cache_served(paged_model):
    """Each request records how many of its prompt tokens the prefix cache
    served at admission: the whole blocks it shares with an earlier
    prompt, and none with the cache off."""
    cfg, params = paged_model
    rng = np.random.RandomState(5)
    prefix = rng.randint(1, cfg.vocab_size, size=27).tolist()
    prompts = [prefix + rng.randint(1, cfg.vocab_size, size=n).tolist()
               for n in (6, 3, 9)]
    for caching, want in ((True, [0, 24, 24]), (False, [0, 0, 0])):
        eng = mk_paged(cfg, params, max_batch=1, prefix_caching=caching)
        for prompt in prompts:
            eng.add_request(prompt, max_new_tokens=2)
        done = sorted(eng.run(), key=lambda r: r.uid)
        assert [r.cached_len for r in done] == want


def test_paged_eos_frees_blocks_for_refill(paged_model):
    """A request dying at admission (EOS on its first token) must release
    its blocks and refill the slot from the queue in the same pass."""
    cfg, params = paged_model
    probe = mk_paged(cfg, params, max_batch=1)
    probe.add_request([5, 6, 7], max_new_tokens=4)
    eos = probe.run()[0].output[0]

    eng = mk_paged(cfg, params, max_batch=2, eos_id=eos,
                   prefix_caching=False)
    eng.add_request([5, 6, 7], max_new_tokens=8)       # dies at admission
    for i in range(4):
        eng.add_request([1 + i, 2 + i, 3 + i, 4 + i], max_new_tokens=3)
    done = eng.run()
    assert len(done) == 5 and all(r.done for r in done)
    assert next(r for r in done if r.uid == 1).output == [eos]
    assert eng.allocator.free_blocks == eng.allocator.num_blocks - 1


# -- one step of lookahead in decode ----------------------------------------

@pytest.fixture(scope="module")
def varied_model():
    """The tiny config with its head drawn apart from the embedding: with
    the tied head and the x12 embedding every greedy token repeats the one
    before, which would hide a token fed to the wrong step."""
    cfg = tiny_cfg().replace(tie_embeddings=False)
    return cfg, init_lm(jax.random.PRNGKey(0), cfg)


def _ref_streams(cfg, params, waves, **kw):
    """The contiguous engine's streams, wave after wave: its decode reads
    each step's tokens before it queues the next."""
    eng = Engine(cfg, params, max_batch=kw.get("max_batch", 3),
                 max_len=kw.get("max_len", 64), eos_id=kw.get("eos_id"))
    out = {}
    for wave in waves:
        for prompt, budget in wave:
            eng.add_request(prompt, max_new_tokens=budget)
        out.update({r.uid: r.output for r in eng.run()})
    return out


def _paged_streams(cfg, params, waves, **kw):
    eng = mk_paged(cfg, params, **kw)
    out = {}
    for wave in waves:
        for prompt, budget in wave:
            eng.add_request(prompt, max_new_tokens=budget)
        out.update({r.uid: r.output for r in eng.run()})
    return eng, out


def _lookahead_waves(cfg, case):
    rng = np.random.RandomState(11)

    def toks(n):
        return rng.randint(1, cfg.vocab_size, size=n).tolist()
    if case == "admissions":
        return [[(toks(int(rng.randint(3, 30))), int(rng.randint(2, 12)))
                 for _ in range(9)]]
    if case == "chunked":
        return [[(toks(n), b) for n, b in
                 ((40, 6), (5, 9), (23, 3), (33, 7), (9, 5), (17, 4))]]
    prefix = toks(24)                                   # prefix hits
    return [[(prefix + toks(5), 6)],
            [(prefix + toks(n), b) for n, b in ((3, 7), (8, 4), (6, 9))]]


@pytest.mark.parametrize("case,kw", [
    ("admissions", {}),
    ("chunked", {"chunk_size": 16}),
    ("prefix", {"chunk_size": 16}),
])
def test_lookahead_streams_match_the_serial_engine(varied_model, case, kw):
    """Decode step n+1 is queued before step n is read; the greedy streams
    are the contiguous engine's, whose decode is serial, across
    admissions beyond the slots, chunked prefill and prefix hits."""
    cfg, params = varied_model
    waves = _lookahead_waves(cfg, case)
    want = _ref_streams(cfg, params, waves)
    eng, got = _paged_streams(cfg, params, waves, **kw)
    assert got == want
    assert eng.stats.overlapped_steps > 0
    assert eng.stats.discarded_tokens == 0
    if case == "prefix":
        assert eng.prefix_cache.hits == 3
    assert eng.allocator.free_blocks + len(eng.prefix_cache) == \
        eng.allocator.num_blocks - 1


def _eos_run(cfg, params, prefix_caching):
    """Request 1 meets the EOS mid-run (its third decoded token) while
    requests queue behind it; returns (eos, requests, engine, freed),
    ``freed`` the blocks each request held when its slot was freed."""
    rng = np.random.RandomState(12)
    reqs = [(rng.randint(1, cfg.vocab_size, size=n).tolist(), b)
            for n, b in ((9, 12), (14, 10), (6, 8), (11, 9), (7, 6))]
    probe = _ref_streams(cfg, params, [reqs[:1]], max_batch=1)
    eos = probe[1][3]
    eng = mk_paged(cfg, params, max_batch=2, eos_id=eos,
                   prefix_caching=prefix_caching)
    freed = {}
    free = eng._free

    def spy(slot):
        freed[eng.slots[slot].uid] = list(eng._seq_blocks[slot])
        free(slot)
    eng._free = spy
    return eos, reqs, eng, freed


@pytest.mark.parametrize("prefix_caching", [False, True])
def test_eos_discards_the_lookahead_token_and_reuses_its_blocks(
        varied_model, prefix_caching):
    """A request that meets the EOS has its next token already queued:
    that token is thrown away and counted, the blocks the queued step
    wrote into go to the next request, and every stream is still the one
    a serial greedy engine gives with the same EOS."""
    cfg, params = varied_model
    eos, reqs, eng, freed = _eos_run(cfg, params, prefix_caching)
    want = _ref_streams(cfg, params, [reqs], max_batch=2, eos_id=eos)
    for prompt, budget in reqs:
        eng.add_request(prompt, max_new_tokens=budget)
    got = {r.uid: r.output for r in eng.run()}
    assert got == want
    assert got[1][-1] == eos and len(got[1]) == 4
    # the EOS ended every request that decoded it before its budget
    early = [u for u, out in got.items()
             if len(out) > 1 and out[-1] == eos and len(out) < reqs[u - 1][1]]
    assert 1 in early
    assert eng.stats.discarded_tokens == len(early)
    # a request admitted after request 1 took over one of its blocks
    later = set().union(*(freed[u] for u in got if u > 2))
    assert set(freed[1]) & later
    assert eng.allocator.free_blocks + (len(eng.prefix_cache)
                                        if prefix_caching else 0) == \
        eng.allocator.num_blocks - 1


#: a backlog whose slots never all end in the same step
BACKLOG = [(7, 5), (12, 9), (4, 3), (9, 7), (15, 6), (6, 8), (10, 4),
           (5, 10)]


def test_next_step_is_queued_before_the_last_is_read(varied_model):
    """Over a backlog, every decode step after the first is dispatched
    while the one before it is unread, and on every step that has a
    successor that dispatch comes before the read."""
    cfg, params = varied_model
    rng = np.random.RandomState(13)
    eng = mk_paged(cfg, params)
    log, queued = [], []            # queued: each step's token array
    decode, read = eng._paged_decode, eng._read_tokens

    def dispatch(*args):
        out = decode(*args)
        log.append(("dispatch", len(queued)))
        queued.append(out[0])
        return out

    def read_tokens(step):
        log.append(("read", next(k for k, a in enumerate(queued)
                                 if a is step.nxt)))
        return read(step)
    eng._paged_decode, eng._read_tokens = dispatch, read_tokens
    for n, budget in BACKLOG:
        eng.add_request(rng.randint(1, cfg.vocab_size, size=n).tolist(),
                        max_new_tokens=budget)
    eng.run()
    steps = eng.stats.decode_steps
    assert len(queued) == steps > 1
    assert eng.stats.overlapped_steps == steps - 1
    assert [k for what, k in log if what == "read"] == list(range(steps))
    for k in range(steps - 1):
        assert log.index(("dispatch", k + 1)) < log.index(("read", k))


@pytest.mark.parametrize("eos", [False, True])
def test_decode_tokens_count_the_tokens_appended(varied_model, eos):
    """``stats.decode_tokens`` counts the tokens appended to requests,
    never a thrown-away lookahead token."""
    cfg, params = varied_model
    if eos:
        _, reqs, eng, _ = _eos_run(cfg, params, True)
    else:
        rng = np.random.RandomState(13)
        reqs = [(rng.randint(1, cfg.vocab_size, size=n).tolist(), b)
                for n, b in BACKLOG]
        eng = mk_paged(cfg, params)
    for prompt, budget in reqs:
        eng.add_request(prompt, max_new_tokens=budget)
    done = eng.run()
    assert len(done) == len(reqs)
    assert eng.stats.decode_tokens == sum(len(r.output) - 1 for r in done)
    assert eng.stats.first_tokens == len(done)
    assert (eng.stats.discarded_tokens > 0) == eos


# -- paged decode reads the pool in place -----------------------------------

def _streams(cfg, params, reqs, fused, **kw):
    """Serve ``reqs`` on the interpret backend -> (engine, outputs)."""
    with nn.backend("pallas_interpret"):
        eng = mk_paged(cfg, params, fused=fused, **kw)
        for prompt, budget in reqs:
            eng.add_request(prompt, max_new_tokens=budget)
        return eng, {r.uid: r.output for r in eng.run()}


@pytest.mark.parametrize("kv_heads", [4, 2])
def test_paged_kernel_streams_match_gathered_path(paged_model, kv_heads):
    """``PagedEngine(fused=True)`` on a kernel backend reads the pool in
    place; its greedy streams equal the gathered path's (``fused=False``)
    over mixed lengths, with EOS ending one request early and its slot
    refilled from the queue. MHA and GQA 2:1."""
    cfg, params = paged_model
    if kv_heads != cfg.n_kv_heads:
        cfg = cfg.replace(n_kv_heads=kv_heads)
        params = init_lm(jax.random.PRNGKey(0), cfg)
    rng = np.random.RandomState(3)
    reqs = [(rng.randint(1, cfg.vocab_size, size=n).tolist(), budget)
            for n, budget in ((5, 6), (30, 4), (17, 8), (9, 3), (40, 5),
                              (12, 7), (3, 4))]
    _, probe = _streams(cfg, params, reqs, fused=False)
    eos = probe[1][2]                    # request 1 stops early
    gathered, want = _streams(cfg, params, reqs, fused=False, eos_id=eos)
    paged, got = _streams(cfg, params, reqs, fused=True, eos_id=eos)
    assert (gathered.kv_path, paged.kv_path) == ("gathered", "paged")
    # only the paged path pads the pool's head dim to whole lane tiles
    hd = cfg.resolved_head_dim
    assert gathered._pools["scan"][0]["k"].shape[-1] == hd
    assert paged._pools["scan"][0]["k"].shape[-1] == -(-hd // 128) * 128
    assert len(want[1]) < len(probe[1]) and want[1][-1] == eos
    assert got == want
    assert paged.allocator.free_blocks + len(paged.prefix_cache) == \
        paged.allocator.num_blocks - 1


def _lowered(cfg, fused):
    """(kv path, lowered paged_step text with op names) on the interpret
    backend, at shapes only."""
    from repro.models import init_lm_cache
    from repro.serving.paged import make_paged_decode_step
    with nn.backend("pallas_interpret"):
        step = make_paged_decode_step(cfg, 64, fused=fused)
        params = jax.eval_shape(lambda: init_lm(jax.random.PRNGKey(0), cfg))
        pools = jax.eval_shape(lambda: init_lm_cache(cfg, 9, 8))
        ids = jax.ShapeDtypeStruct((3,), jnp.int32)
        text = jax.jit(step).lower(
            params, ids, ids, pools, jax.ShapeDtypeStruct((3, 8), jnp.int32),
            jax.ShapeDtypeStruct((2,), jnp.uint32)).as_text(debug_info=True)
    return step.kv, text


@pytest.mark.parametrize("case", ["fused", "unfused", "mla"])
def test_paged_step_gathers_only_off_the_kernel_path(case):
    """The fused step on a kernel backend holds no KV gather; the unfused
    step and MLA configs keep the gathered view. All write their rows."""
    cfg = tiny_cfg()
    if case == "mla":
        cfg = reduced(get_config("deepseek-v2-lite-16b")).replace(
            loss_chunk=0)
    kv, text = _lowered(cfg, fused=case != "unfused")
    assert kv == ("paged" if case == "fused" else "gathered")
    assert ("ng:memory:paged_kv_gather" in text) == (kv == "gathered")
    assert "ng:memory:paged_kv_write" in text
    assert ("ng:fused:fused_attn_decode" in text) == (case != "unfused")


def test_engine_programs_keep_their_module_names(paged_model):
    """The device trace finds the engine's step programs by their jitted
    module names: decode, extend, cold prefill and its scatter, as the
    fused engine on a kernel backend builds them."""
    cfg, params = paged_model
    with nn.backend("pallas_interpret"):
        eng = mk_paged(cfg, params, fused=True)
        assert eng.kv_path == "paged"
        i32 = jnp.int32(0)
        row = jnp.asarray(eng._tables[0])
        toks = jnp.zeros((1, 16), jnp.int32)
        lengths = jnp.ones((1,), jnp.int32)
        one = jax.eval_shape(eng._prefill, eng.params, toks, lengths)[1]
        lowered = {
            "paged_step": eng._paged_decode.lower(
                eng.params, jnp.asarray(eng._cur), jnp.asarray(eng._pos),
                eng._pools, jnp.asarray(eng._tables), eng.key),
            "extend_step": eng._paged_extend.lower(
                eng.params, toks, i32, eng._pools, row, i32, i32),
            "prefill_step": eng._prefill.lower(eng.params, toks, lengths),
            "_scatter_cold_prefill": eng._scatter_cold.lower(
                eng._pools, one, row, i32, width=16),
        }
    for name, low in lowered.items():
        assert low.as_text().startswith(f"module @jit_{name} "), name


def test_paged_rejects_non_attention_mixers():
    cfg = reduced(get_config("recurrentgemma-2b")).replace(loss_chunk=0)
    params = init_lm(jax.random.PRNGKey(0), cfg)
    with pytest.raises(ValueError):
        PagedEngine(cfg, params, max_batch=2, max_len=64)


# -- satellite 2: queue-wait clock ----------------------------------------

def test_chunked_prefill_does_not_restart_queue_wait_clock(paged_model):
    """admit_t is stamped once, at first admission — the chunked-prefill
    path must not restart it on later chunks, or queue_wait absorbs
    prefill time and TTFT < queue_wait becomes representable."""
    cfg, params = paged_model
    eng = mk_paged(cfg, params, max_batch=1, chunk_size=8)
    prompt = np.random.RandomState(3).randint(
        1, cfg.vocab_size, size=30).tolist()        # 4 chunks of 8
    eng.add_request(prompt, max_new_tokens=3)
    eng.step()                                       # admits: chunk 1 only
    req = next(r for r in eng.slots if r is not None)
    assert req.admit_t > 0.0
    admit_t = req.admit_t
    done = eng.run()
    assert done[0].admit_t == admit_t                # never restamped
    assert done[0].first_token_t >= admit_t >= done[0].enqueue_t


def test_stats_invariant_ttft_covers_queue_wait(paged_model):
    """For every finished request, TTFT >= queue wait (both clocks start
    at enqueue; the first token cannot precede admission)."""
    cfg, params = paged_model
    eng = mk_paged(cfg, params, max_batch=2, chunk_size=16)
    rng = np.random.RandomState(4)
    for _ in range(6):
        plen = int(rng.randint(3, 36))
        eng.add_request(rng.randint(1, cfg.vocab_size, size=plen).tolist(),
                        max_new_tokens=int(rng.randint(2, 5)))
    done = eng.run()
    assert len(done) == 6
    for r in done:
        assert r.ttft_s >= r.queue_wait_s >= 0.0
    assert eng.stats.mean_ttft_s >= eng.stats.mean_queue_wait_s


# -- satellite 1: kv_cache_update bounds check ----------------------------

def test_kv_cache_update_clamps_silently_without_debug():
    cache = jnp.zeros((1, 4, 2))
    new = jnp.ones((1, 1, 2))
    out = nn.kv_cache_update(cache, new, jnp.array([99], jnp.int32))
    # dynamic_update_slice clamps: the write lands on the LAST row
    assert float(out[0, 3, 0]) == 1.0


def test_kv_cache_update_debug_bounds_rejects_concrete_oob():
    cache = jnp.zeros((1, 4, 2))
    new = jnp.ones((1, 1, 2))
    with nn.debug_bounds():
        # in-range still works
        out = nn.kv_cache_update(cache, new, jnp.array([2], jnp.int32))
        assert float(out[0, 2, 0]) == 1.0
        with pytest.raises(ValueError, match="clamp"):
            nn.kv_cache_update(cache, new, jnp.array([99], jnp.int32))
        with pytest.raises(ValueError, match="clamp"):
            nn.kv_cache_update(cache, new, jnp.array([-1], jnp.int32))
    # the context manager restores the silent-clamp default
    assert not nn.debug_bounds_enabled()
    nn.kv_cache_update(cache, new, jnp.array([99], jnp.int32))


def test_kv_cache_update_debug_bounds_rejects_traced_oob():
    cache = jnp.zeros((1, 4, 2))
    new = jnp.ones((1, 1, 2))

    def write(idx):
        return nn.kv_cache_update(cache, new, idx)

    with nn.debug_bounds():
        fn = jax.jit(write)
        # jax.debug.callback surfaces the ValueError as a runtime error
        with pytest.raises(Exception, match="kv_cache_update|callback"):
            jax.block_until_ready(fn(jnp.array([99], jnp.int32)))
        out = jax.block_until_ready(fn(jnp.array([1], jnp.int32)))
        assert float(out[0, 1, 0]) == 1.0


# -- serve.* spans and the engine's one clock ------------------------------

#: prompts that take the cold path (<= chunk_size 16) and the chunked one
SPAN_REQS = [(5, 4), (20, 3), (9, 1), (12, 5), (33, 2)]


def _span_requests(cfg):
    rng = np.random.RandomState(7)
    return [(rng.randint(1, cfg.vocab_size, size=n).tolist(), budget)
            for n, budget in SPAN_REQS]


def _serve(cfg, params, reqs):
    eng = mk_paged(cfg, params, max_batch=2, chunk_size=16)
    for prompt, budget in reqs:
        eng.add_request(prompt, max_new_tokens=budget)
    return eng, {r.uid: r.output for r in eng.run()}


def _serve_spans(log_dir):
    """(name, start_ns, end_ns, args) of every serve.* host event."""
    import glob
    import os
    from jax.profiler import ProfileData
    path, = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    return [(ev.name, ev.start_ns, ev.end_ns, dict(ev.stats))
            for plane in ProfileData.from_file(path).planes
            if plane.name.startswith("/host:")
            for line in plane.lines for ev in line.events
            if ev.name.startswith("serve.")]


@pytest.fixture(scope="module")
def traced_serve(paged_model, tmp_path_factory):
    """The span requests served once under a profiler trace."""
    cfg, params = paged_model
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    log_dir = str(tmp_path_factory.mktemp("serve-trace"))
    jax.profiler.start_trace(log_dir, profiler_options=opts)
    try:
        eng, out = _serve(cfg, params, _span_requests(cfg))
    finally:
        jax.profiler.stop_trace()
    return eng, out, _serve_spans(log_dir)


def _inside(child, parents):
    return [p for p in parents
            if p[1] <= child[1] and child[2] <= p[2] and p is not child]


def test_serve_spans_nest_and_name_their_requests(traced_serve):
    eng, out, spans = traced_serve
    by = {}
    for s in spans:
        by.setdefault(s[0], []).append(s)
    assert set(by) == {"serve.admit", "serve.prefill", "serve.scatter",
                       "serve.chunk", "serve.decode", "serve.decode.prep",
                       "serve.decode.dispatch", "serve.decode.wait",
                       "serve.retire"}
    # one admission per request, named by its uid, with its path
    admits = {s[3]["uid"]: s[3] for s in by["serve.admit"]}
    assert sorted(admits) == sorted(out)
    for uid, args in admits.items():
        plen = SPAN_REQS[uid - 1][0]
        assert args["prompt_len"] == plen and args["cached_len"] == 0
        assert args["path"] == ("cold" if plen <= 16 else "chunked")
    # prefill and scatter sit inside the admission of the same request
    for s in by["serve.prefill"] + by["serve.scatter"]:
        parent, = _inside(s, by["serve.admit"])
        assert parent[3]["uid"] == s[3]["uid"]
    assert {s[3]["uid"] for s in by["serve.chunk"]} == \
        {uid for uid, a in admits.items() if a["path"] == "chunked"}
    # a request that ends at admission (budget 1) never scatters
    assert 3 not in {s[3]["uid"] for s in by["serve.scatter"]}
    # one serve.decode per decode step; its parts and retirement inside it
    decodes = by["serve.decode"]
    assert len(decodes) == eng.stats.decode_steps
    for name in ("serve.decode.prep", "serve.decode.dispatch",
                 "serve.decode.wait", "serve.retire"):
        assert len(by[name]) == len(decodes)
        for s in by[name]:
            assert len(_inside(s, decodes)) == 1
    assert sum(d[3]["live"] for d in decodes) == eng.stats.decode_tokens
    assert {d[3]["kv"] for d in decodes} == {eng.kv_path} == {"gathered"}
    # every request that decoded finished in some retirement
    assert sum(s[3]["finished"] for s in by["serve.retire"]) == \
        eng.stats.decoded_requests


def test_serve_decode_span_names_the_paged_path(paged_model, tmp_path):
    """On the kernel path every ``serve.decode`` span carries kv="paged"."""
    cfg, params = paged_model
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        eng, _ = _streams(cfg, params, _span_requests(cfg)[:2], fused=True)
    finally:
        jax.profiler.stop_trace()
    decodes = [s for s in _serve_spans(str(tmp_path))
               if s[0] == "serve.decode"]
    assert len(decodes) == eng.stats.decode_steps > 0
    assert {d[3]["kv"] for d in decodes} == {"paged"}


def test_tokens_identical_with_profiler_on_and_off(paged_model,
                                                   traced_serve):
    cfg, params = paged_model
    _, traced, _ = traced_serve
    _, plain = _serve(cfg, params, _span_requests(cfg))
    assert traced == plain


@pytest.mark.parametrize("paged", [False, True])
def test_stats_and_timestamps_share_the_engine_clock(paged_model, paged):
    """With an injected clock that ticks by 1 at every read, each timed
    region (prefill, scatter, decode prep/dispatch/wait) adds exactly one
    tick to EngineStats, and Request timestamps are ticks of the same
    clock: no other clock is read."""
    cfg, params = paged_model
    ticks = iter(range(1, 10 ** 6))
    clock = lambda: float(next(ticks))      # noqa: E731
    kw = dict(max_batch=2, max_len=64, clock=clock)
    eng = mk_paged(cfg, params, **kw) if paged else Engine(cfg, params, **kw)
    budgets = (3, 1, 4, 2)
    rng = np.random.RandomState(8)
    for budget in budgets:
        eng.add_request(rng.randint(1, cfg.vocab_size, size=7).tolist(),
                        max_new_tokens=budget)
    done = eng.run()
    s = eng.stats
    live = sum(b > 1 for b in budgets)      # budget 1 never scatters
    assert s.prefill_s == len(budgets) + live
    assert s.decode_s == 3 * s.decode_steps > 0
    last = next(ticks)
    for r in done:
        stamps = (r.enqueue_t, r.admit_t, r.first_token_t, r.finish_t)
        assert all(t == int(t) for t in stamps)
        assert 0 < r.enqueue_t < r.admit_t < r.first_token_t \
            <= r.finish_t < last
