import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
# simulated host devices only: never take an attached accelerator
os.environ["JAX_PLATFORMS"] = "cpu"

# Mesh-sharded paged serving checks (the device count is process-global, so
# every caller — tests and the `serving_sharded` bench section — runs this
# in a subprocess).
#
# Parity modes assert TOKEN-IDENTICAL outputs between the manual-TP paged
# engine (shard_map over the model axis; see repro/models/tp.py) and the
# single-device paged engine. Row-sharded matmuls reduce in a different
# order, so logits differ in ulps — but the emitted argmax token streams
# must agree exactly, which is the property serving cares about.
#
# Usage: python scripts/sharded_serving_check.py \
#            <parity_decode|parity_paged_kernel|parity_chunked|
#             parity_prefix|parity_granite|parity_lookahead|bench>

import json
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import nn
from repro.bench.cases import sharded_serving_config
from repro.core import get_hardware, model_records
from repro.core.graph import capture
from repro.launch.mesh import make_sim_mesh
from repro.models import init_lm
from repro.serving import Engine, PagedEngine
from repro.serving.paged import make_paged_decode_step

ARCH = "stablelm-3b"
CASE = "sharded stablelm b-4"
MAX_LEN = 64
MAX_BATCH = 4
BLOCK = 8

#: KV heads where an arch keeps GQA at the sweep's 8 query heads
KV_HEADS = {"granite-3-8b": 4}
_models = {}


def cfg_params(arch: str = ARCH):
    if arch not in _models:
        cfg = sharded_serving_config(arch)
        cfg = cfg.replace(n_kv_heads=KV_HEADS.get(arch, cfg.n_kv_heads))
        _models[arch] = cfg, init_lm(jax.random.PRNGKey(0), cfg)
    return _models[arch]


def make_engine(tp: int, arch: str = ARCH, **kw):
    cfg, params = cfg_params(arch)
    mesh = make_sim_mesh(1, tp) if tp > 1 else None
    return PagedEngine(cfg, params, max_batch=MAX_BATCH, max_len=MAX_LEN,
                       block_size=BLOCK, mesh=mesh, **kw)


def prompts(n: int, lo: int, hi: int, seed: int = 7):
    cfg, _ = cfg_params()
    rng = np.random.default_rng(seed)
    return [rng.integers(1, cfg.vocab_size, rng.integers(lo, hi + 1))
            .astype(int).tolist() for _ in range(n)]


def serve(eng, plist, new_tokens: int = 12):
    for p in plist:
        eng.add_request(p, max_new_tokens=new_tokens)
    done = eng.run()
    return {r.uid: list(r.output) for r in done}


def assert_parity(tp: int, **kw):
    plist = prompts(6, kw.pop("plo", 4), kw.pop("phi", 20))
    ref = serve(make_engine(1, **kw), plist)
    out = serve(make_engine(tp, **kw), plist)
    assert out == ref, (
        f"tp={tp} token streams diverge from single-device:\n"
        f"  single: {ref}\n  tp:     {out}")


def main(mode: str) -> int:
    if mode == "parity_decode":
        # cold admission + batched paged decode, TP degrees 2 and 8
        assert_parity(2)
        assert_parity(8)
        print("parity_decode OK")
        return 0

    if mode == "parity_paged_kernel":
        # fused TP engines read the head-sharded pools in place through
        # the paged decode kernel (Pallas interpret mode); their streams
        # must equal the single-device gathered path's (fused=False)
        plist = prompts(6, 4, 20)
        with nn.backend("pallas_interpret"):
            base = make_engine(1)
            ref = serve(base, plist)
            assert base.kv_path == "gathered", base.kv_path
            for tp in (2, 4):
                eng = make_engine(tp, fused=True)
                assert eng.kv_path == "paged", (tp, eng.kv_path)
                out = serve(eng, plist)
                assert out == ref, (
                    f"tp={tp} paged-kernel streams diverge from the "
                    f"gathered path:\n  gathered: {ref}\n  paged:    {out}")
        print("parity_paged_kernel OK")
        return 0

    if mode == "parity_granite":
        # granite's scalar multipliers (embedding, attention, residual,
        # logits) and RMSNorm eps inside shard_map: TP 2 and 4 read the
        # head-sharded GQA pools through the paged kernel, and a second
        # wave hits the prefix cache and prefills through lm_extend; the
        # streams must equal the single-device gathered path's
        cfg, _ = cfg_params("granite-3-8b")
        assert cfg.residual_multiplier is not None and cfg.n_kv_heads == 4
        base = prompts(3, 24, 32)
        wave2 = [p[:16] + q for p, q in zip(base, prompts(3, 4, 8, seed=11))]
        with nn.backend("pallas_interpret"):
            outs = {}
            for tp in (1, 2, 4):
                eng = make_engine(tp, "granite-3-8b", fused=tp > 1)
                assert eng.kv_path == ("paged" if tp > 1 else "gathered")
                outs[tp] = (serve(eng, base), serve(eng, wave2))
                assert eng.prefix_cache.hits > 0, tp
        assert outs[2] == outs[1] and outs[4] == outs[1], outs
        print("parity_granite OK")
        return 0

    if mode == "parity_chunked":
        # long prompts through decode-interleaved chunked prefill
        assert_parity(8, chunk_size=8, plo=18, phi=40)
        print("parity_chunked OK")
        return 0

    if mode == "parity_prefix":
        # two waves sharing 16-token prefixes: the second wave must take
        # the prefix-hit path on BOTH engines and still agree
        base = prompts(3, 24, 32)
        wave2 = [p[:16] + q for p, q in zip(base, prompts(3, 4, 8, seed=11))]
        outs = []
        for tp in (1, 8):
            eng = make_engine(tp, chunk_size=8)
            first = serve(eng, base)
            second = serve(eng, wave2)
            assert eng.prefix_cache.hits > 0, \
                f"tp={tp}: second wave never hit the prefix cache"
            outs.append((first, second))
        assert outs[0] == outs[1], (
            f"prefix-hit token streams diverge:\n"
            f"  single: {outs[0]}\n  tp=8:   {outs[1]}")
        print("parity_prefix OK")
        return 0

    if mode == "parity_lookahead":
        # the TP engine queues decode step n+1 before it reads step n; over
        # more requests than slots, chunked prefill and a prefix-hit wave,
        # its streams must equal the contiguous engine's on one device,
        # whose decode reads each step before it queues the next
        cfg, params = cfg_params()
        base = prompts(6, 18, 32)
        wave2 = [p[:16] + q for p, q in zip(base, prompts(3, 4, 8, seed=11))]
        ref = Engine(cfg, params, max_batch=MAX_BATCH, max_len=MAX_LEN)
        want = (serve(ref, base), serve(ref, wave2))
        eng = make_engine(2, chunk_size=8)
        got = (serve(eng, base), serve(eng, wave2))
        assert eng.prefix_cache.hits > 0
        assert eng.stats.overlapped_steps > 0
        assert got == want, (
            f"tp=2 lookahead streams diverge from the serial engine:\n"
            f"  serial: {want}\n  tp=2:   {got}")
        print("parity_lookahead OK")
        return 0

    if mode == "bench":
        cfg, _ = cfg_params()
        hw = get_hardware("tpu_v5e")
        rows = []
        ref = None
        step1_s = None
        for tp in (1, 2, 4, 8):
            eng = make_engine(tp)
            plist = prompts(6, 4, 20)
            t0 = time.perf_counter()
            outs = serve(eng, plist, new_tokens=16)
            _ = time.perf_counter() - t0
            parity_ok = True if ref is None else outs == ref
            ref = ref or outs

            # modeled per-device decode step: capture the step program at
            # the engine's live shapes (shard_map bodies trace per-shard,
            # so non-collective records are already per-device work).
            # launch_overhead_s=0: per-kernel dispatch constants do not
            # shard and would swamp the reduced-size model — the scaling
            # view isolates the roofline compute/memory/link terms.
            mesh = make_sim_mesh(1, tp) if tp > 1 else None
            step = make_paged_decode_step(cfg, MAX_LEN, mesh, greedy=True)
            records = capture(
                step, eng.params, jnp.asarray(eng._cur),
                jnp.asarray(eng._pos), eng._pools,
                jnp.asarray(eng._tables), jax.random.PRNGKey(0))
            prof = model_records(records, name=CASE, hw=hw,
                                 launch_overhead_s=0.0,
                                 mode=f"modeled_tp{tp}")
            total = prof.total_seconds or 1.0
            if step1_s is None:
                step1_s = prof.total_seconds
            split = prof.split
            rows.append({
                "case": CASE,
                "tp": tp,
                "devices": tp,
                "decode_tok_per_s": eng.stats.decode_tok_per_s,
                "per_device_tok_per_s": eng.stats.decode_tok_per_s / tp,
                "modeled_step_s": prof.total_seconds,
                "modeled_eff": step1_s / (tp * total),
                "collective_frac":
                    prof.group_seconds.get("collective", 0.0) / total,
                "gemm_frac": split["gemm_frac"],
                "nongemm_frac": split["nongemm_frac"],
                "parity_ok": bool(parity_ok),
            })
        print("BENCH_JSON " + json.dumps(rows))
        print("bench OK")
        return 0

    raise SystemExit(f"unknown mode {mode}")


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
