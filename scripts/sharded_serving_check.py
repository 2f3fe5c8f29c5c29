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
#            <parity_decode|parity_chunked|parity_prefix|bench>

import json
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.bench.cases import sharded_serving_config
from repro.core import get_hardware, model_records
from repro.core.graph import capture
from repro.launch.mesh import make_sim_mesh
from repro.models import init_lm
from repro.serving import PagedEngine
from repro.serving.paged import make_paged_decode_step

ARCH = "stablelm-3b"
CASE = "sharded stablelm b-4"
MAX_LEN = 64
MAX_BATCH = 4
BLOCK = 8

_cfg = None
_params = None


def cfg_params():
    global _cfg, _params
    if _cfg is None:
        _cfg = sharded_serving_config(ARCH)
        _params = init_lm(jax.random.PRNGKey(0), _cfg)
    return _cfg, _params


def make_engine(tp: int, **kw):
    cfg, params = cfg_params()
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
