"""Bring-up smoke: serve stablelm-3b on a TPU through PagedEngine's Pallas path.

    python chip_smoke.py            # one chip
    python chip_smoke.py --tp 4     # manual-TP PagedEngine on a (1, 4) mesh

One chip: stablelm-3b at its published widths and full depth, bf16 weights
drawn from ``--seed``, serves seeded requests through ``PagedEngine`` under
``nn.backend("pallas")`` with ``fused=True``; one request shares a prefix
with an earlier one, so the prefix-hit ``lm_extend`` path runs as well.
Then a prefill plus decode steps, teacher-forced on the served tokens, runs
once on the Pallas kernels and once on the plain ``jnp`` backend at the same
dtype, and the logits must agree within ``LOGIT_ATOL + LOGIT_RTOL * max|ref|``.

``--tp 4`` runs only the four-chip phase: the same traffic through the
manual-TP engine on a ``(data=1, model=4)`` mesh, compared token by token
with the single-chip engine on device 0 in the same process.

Without a TPU the script exits non-zero before serving anything. Any failed
phase exits non-zero. Times printed here are smoke timings, not benchmark
results. The last line of stdout is one JSON object naming the device.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time
import traceback

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(REPO, "src"))
# libtpu would otherwise write its logs outside the checkout
os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro import nn  # noqa: E402
from repro.configs import get_config  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.mesh import make_mesh  # noqa: E402
from repro.models import init_lm, lm_decode, lm_prefill  # noqa: E402
from repro.runtime import cast_params  # noqa: E402
from repro.serving import PagedEngine  # noqa: E402

ARCH = "stablelm-3b"
# The compiler sizes the full-depth fused paged decode step at about 7.6 GB
# of arguments plus 6.4 GB of temporaries for 8 slots of 512 tokens; 8 x 1024
# needs 19.0 GB of the chip's 15.75 GB. serve() re-checks the compiled
# programs against the device's own limit before any of them runs.
MAX_BATCH, MAX_LEN = 8, 512
N_REQUESTS, MAX_NEW = 10, 10
# cold prompts stay in one prefill bucket (33..64 tokens -> 64), and the
# prefix-sharing request reuses 3 blocks of 16 and extends by 16
PROMPT_LO, PROMPT_HI, SHARED = 33, 64, 48
# bf16 activations through 32 layers: the fused kernels round in another
# order than the jnp twins, so logits agree to a few bf16 ulps of their range
LOGIT_ATOL, LOGIT_RTOL = 0.05, 0.02
# the teacher-forced check holds a prompt plus its answer, not MAX_LEN: at
# MAX_LEN its contiguous cache for every request would need 15.1 GB
CHECK_LEN = 128


def log(msg: str) -> None:
    print(msg, flush=True)


def expect(ok, what) -> None:
    """A failed check fails the smoke (kept under ``python -O``)."""
    if not ok:
        raise RuntimeError(f"check failed: {what}")


def make_prompts(seed: int, vocab: int):
    """Seeded prompts; request 3 shares its first SHARED tokens with 0."""
    rng = np.random.RandomState(seed)
    prompts = [rng.randint(1, vocab, size=int(rng.randint(PROMPT_LO,
                                                          PROMPT_HI + 1)))
               .tolist() for _ in range(N_REQUESTS)]
    prompts[0] = prompts[0] + rng.randint(
        1, vocab, size=PROMPT_HI - len(prompts[0])).tolist()
    prompts[3] = prompts[0][:SHARED] + rng.randint(1, vocab, size=12).tolist()
    return prompts


def init_params(cfg, seed: int):
    t0 = time.perf_counter()
    # op by op: each weight shape compiles once and is reused across the
    # layers, where one jit of the whole init compiles every layer anew
    params = init_lm(jax.random.PRNGKey(seed), cfg)
    jax.block_until_ready(params)
    n = sum(x.size for x in jax.tree_util.tree_leaves(params))
    log(f"params: {n / 1e9:.3f}B in {cfg.param_dtype}, init "
        f"{time.perf_counter() - t0:.1f}s")
    return params


def _compile(name: str, jitted, *args) -> dict:
    """AOT-compile one engine program at the shapes the traffic uses. The
    engine's own call then hits the same executable."""
    t0 = time.perf_counter()
    compiled = jitted.lower(*args).compile()
    secs = time.perf_counter() - t0
    m = compiled.memory_analysis()
    need = (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes)
    info = {"name": name, "compile_s": secs, "need_bytes": need,
            "tpu_custom_call": "tpu_custom_call" in compiled.as_text()}
    log(f"program {name}: compile {secs:.1f}s, args "
        f"{m.argument_size_in_bytes / 1e9:.2f} GB + temp "
        f"{m.temp_size_in_bytes / 1e9:.2f} GB + out "
        f"{m.output_size_in_bytes / 1e9:.2f} GB - alias "
        f"{m.alias_size_in_bytes / 1e9:.2f} GB = {need / 1e9:.2f} GB; "
        f"tpu_custom_call={info['tpu_custom_call']}")
    return info


def serve(cfg, params, prompts, backend: str, mesh=None, seed: int = 0):
    """Serve ``prompts`` through PagedEngine(fused=True) under ``backend``.

    Returns (outputs in request order, per-program compile info, stats).
    """
    with nn.backend(backend):
        eng = PagedEngine(cfg, params, max_batch=MAX_BATCH, max_len=MAX_LEN,
                          fused=True, mesh=mesh, seed=seed)
        log(f"engine tp={eng.tp}: decode reaches the KV cache by the "
            f"{eng.kv_path} path")
        bucket = eng._bucket(PROMPT_HI)
        width = eng._chunk_plan(SHARED, len(prompts[3]))[0][1]
        row = jnp.zeros((eng.blocks_per_seq,), jnp.int32)
        i32 = jnp.int32(0)
        programs = [
            _compile("prefill", eng._prefill, eng.params,
                     jnp.zeros((1, bucket), jnp.int32),
                     jnp.ones((1,), jnp.int32)),
            _compile("extend", eng._paged_extend, eng.params,
                     jnp.zeros((1, width), jnp.int32), i32, eng._pools, row,
                     i32, i32),
            _compile("decode", eng._paged_decode, eng.params,
                     jnp.asarray(eng._cur), jnp.asarray(eng._pos),
                     eng._pools, jnp.asarray(eng._tables),
                     jax.random.split(eng.key)[1]),
        ]
        for p in prompts:
            eng.add_request(p, max_new_tokens=MAX_NEW)
        step_s = []
        done = []
        compiles = []

        def on_event(event, secs, **kw):
            if event == "/jax/core/compile/backend_compile_duration":
                compiles.append((kw.get("fun_name"), secs))

        jax.monitoring.register_event_duration_secs_listener(on_event)
        try:
            while eng.queue or eng.active:
                t0 = time.perf_counter()
                done += eng.step()
                step_s.append(time.perf_counter() - t0)
        finally:
            jax.monitoring.unregister_event_duration_listener(on_event)
        log(f"compiles while serving: {len(compiles)} "
            + ", ".join(f"{n} {t:.1f}s" for n, t in compiles))
        outputs = [r.output for r in sorted(done, key=lambda r: r.uid)]
        s = eng.stats
        stats = {"step_s": step_s, "decode_steps": s.decode_steps,
                 "decode_s": s.decode_s, "prefill_s": s.prefill_s,
                 "prefix_hits": eng.prefix_cache.hits,
                 "completed": s.completed}
    del eng
    gc.collect()
    return outputs, programs, stats


def check_outputs(outputs, prompts, vocab: int) -> None:
    expect(len(outputs) == len(prompts),
           f"{len(outputs)} of {len(prompts)} requests finished")
    for i, out in enumerate(outputs):
        expect(len(out) == MAX_NEW, f"request {i}: {len(out)} tokens")
        expect(all(0 <= t < vocab for t in out), f"request {i}: {out}")


def make_check(cfg, fused: bool):
    """(params, tokens (B, P) right-padded, lengths (B,), forced (B, n))
    -> logits (n + 1, B, V) in float32: one prefill, then n decode steps
    fed the forced tokens (teacher forcing), on a contiguous cache."""

    def run(params, tokens, lengths, forced):
        with nn.fuse(fused):
            w = cast_params(params, cfg.activation_dtype)
            first, caches = lm_prefill(w, tokens, cfg, max_len=CHECK_LEN,
                                       lengths=lengths)

            def step(carry, tok):
                pos, caches = carry
                logits, caches = lm_decode(w, tok, pos, caches, cfg)
                return (pos + 1, caches), logits.astype(jnp.float32)

            _, rest = jax.lax.scan(step, (lengths, caches), forced.T)
        return jnp.concatenate([first.astype(jnp.float32)[None], rest])
    return jax.jit(run)


def forced_logits(cfg, params, prompts, outputs, backend: str, fused: bool):
    """Teacher-forced logits of ``outputs`` (each prompt's served tokens)."""

    plen = max(len(p) for p in prompts)
    toks = np.zeros((len(prompts), plen), np.int32)
    for i, p in enumerate(prompts):
        toks[i, :len(p)] = p
    lengths = jnp.asarray([len(p) for p in prompts], jnp.int32)
    forced = jnp.asarray([o[:-1] for o in outputs], jnp.int32)
    with nn.backend(backend):
        run = make_check(cfg, fused)
        t0 = time.perf_counter()
        compiled = run.lower(params, jnp.asarray(toks), lengths,
                             forced).compile()
        log(f"check program ({backend}, fused={fused}): compile "
            f"{time.perf_counter() - t0:.1f}s, tpu_custom_call="
            f"{'tpu_custom_call' in compiled.as_text()}")
        logits = compiled(params, jnp.asarray(toks), lengths, forced)
    return np.asarray(logits)


def tolerance(ref) -> float:
    return LOGIT_ATOL + LOGIT_RTOL * float(np.abs(ref).max())


def greedy_report(label: str, ref, outputs, tol: float):
    """Compare served tokens with the reference's greedy choice. A token
    that differs must be a near-tie in the reference: its logit within
    2 * tol of the reference's best. Returns (matches, total, worst gap)."""
    served = np.asarray(outputs).T                       # (n + 1, B)
    best = ref.argmax(-1)
    match = int((best == served).sum())
    gap = np.take_along_axis(ref, best[..., None], -1)[..., 0] \
        - np.take_along_axis(ref, served[..., None], -1)[..., 0]
    worst = float(gap.max())
    log(f"{label}: greedy tokens match {match}/{served.size}; largest "
        f"reference logit gap at a differing token {worst:.4f} "
        f"(near-tie limit {2 * tol:.4f})")
    return match, served.size, worst


def first_divergence(a, b):
    for i, (x, y) in enumerate(zip(a, b)):
        if x != y:
            return i
    return None


def device_memory(devices) -> list:
    out = []
    for d in devices:
        ms = d.memory_stats() or {}
        out.append({"id": d.id, "bytes_in_use": ms.get("bytes_in_use"),
                    "peak_bytes_in_use": ms.get("peak_bytes_in_use"),
                    "bytes_limit": ms.get("bytes_limit")})
    return out


def one_chip(cfg, seed: int) -> None:
    dev = jax.devices()[0]
    params = init_params(cfg, seed)
    prompts = make_prompts(seed, cfg.vocab_size)
    outputs, programs, st = serve(cfg, params, prompts, "pallas", seed=seed)
    check_outputs(outputs, prompts, cfg.vocab_size)
    expect(st["prefix_hits"] >= 1, "no prefix hit: lm_extend never ran")
    mem = device_memory([dev])[0]
    limit = mem["bytes_limit"]
    for p in programs:
        expect(p["tpu_custom_call"], f"{p['name']} has no Pallas kernel")
        expect(limit is None or p["need_bytes"] <= limit,
               f"{p['name']} needs {p['need_bytes']} of {limit}")
    steps = sorted(st["step_s"])
    log(f"served {st['completed']} requests, {st['decode_steps']} decode "
        f"steps, prefix hits {st['prefix_hits']}")
    log(f"smoke timing, not a benchmark result: engine step median "
        f"{steps[len(steps) // 2] * 1e3:.1f} ms over {len(steps)} steps "
        f"(min {steps[0] * 1e3:.1f}, max {steps[-1] * 1e3:.1f}); mean "
        f"decode step {st['decode_s'] / max(st['decode_steps'], 1) * 1e3:.1f}"
        f" ms")
    log(f"memory after serving: peak_bytes_in_use "
        f"{mem['peak_bytes_in_use']} of bytes_limit {limit}")
    expect(limit is None or mem["peak_bytes_in_use"] < limit,
           f"peak {mem['peak_bytes_in_use']} not under {limit}")

    pal = forced_logits(cfg, params, prompts, outputs, "pallas", True)
    ref = forced_logits(cfg, params, prompts, outputs, "jnp", False)
    expect(np.isfinite(pal).all() and np.isfinite(ref).all(),
           "non-finite logits")
    expect(pal.shape == ref.shape == (MAX_NEW, N_REQUESTS, cfg.vocab_size),
           f"logit shapes {pal.shape} {ref.shape}")
    tol = tolerance(ref)
    diff = float(np.abs(pal - ref).max())
    log(f"pallas vs jnp logits ({pal.shape[0]} steps x {pal.shape[1]} "
        f"requests): largest |diff| {diff:.4f}, tolerance {tol:.4f} "
        f"(= {LOGIT_ATOL} + {LOGIT_RTOL} * max|ref| {np.abs(ref).max():.3f})")
    same = int((pal.argmax(-1) == ref.argmax(-1)).sum())
    log(f"pallas vs jnp greedy tokens match {same}/{ref.shape[0] * ref.shape[1]}")
    _, _, worst = greedy_report("served vs jnp", ref, outputs, tol)
    mem = device_memory([dev])[0]
    log(f"memory at end: peak_bytes_in_use {mem['peak_bytes_in_use']} of "
        f"bytes_limit {mem['bytes_limit']}")
    expect(diff <= tol, f"pallas and jnp logits differ by {diff} > {tol}")
    expect(worst <= 2 * tol, f"served token off the reference by {worst}")


def four_chips(cfg, seed: int, tp: int) -> None:
    devices = jax.devices()
    if len(devices) < tp:
        raise RuntimeError(f"--tp {tp} needs {tp} devices, JAX sees "
                           f"{len(devices)}")
    params = init_params(cfg, seed)
    prompts = make_prompts(seed, cfg.vocab_size)
    mesh = make_mesh((1, tp), ("data", "model"), devices=devices[:tp])
    tp_out, tp_prog, tp_st = serve(cfg, params, prompts, "pallas", mesh=mesh,
                                   seed=seed)
    log(f"tp={tp}: served {tp_st['completed']} requests, prefix hits "
        f"{tp_st['prefix_hits']}")
    for m in device_memory(devices[:tp]):
        log(f"device {m['id']}: peak_bytes_in_use {m['peak_bytes_in_use']}, "
            f"bytes_in_use {m['bytes_in_use']}, bytes_limit "
            f"{m['bytes_limit']}")
    peaks = [m["peak_bytes_in_use"] or 0 for m in device_memory(devices[:tp])]
    one_out, one_prog, one_st = serve(cfg, params, prompts, "pallas",
                                      seed=seed)
    check_outputs(tp_out, prompts, cfg.vocab_size)
    check_outputs(one_out, prompts, cfg.vocab_size)
    for p in tp_prog + one_prog:
        expect(p["tpu_custom_call"], f"{p['name']} has no Pallas kernel")
    expect(tp_st["prefix_hits"] >= 1 and one_st["prefix_hits"] >= 1,
           "no prefix hit: lm_extend never ran")
    # every shard holds a real share of the weights, not just device 0
    expect(min(peaks[1:]) > 0.5e9, f"per-device peaks {peaks}")

    same = sum(a == b for a, b in zip(tp_out, one_out))
    log(f"tp={tp} vs one chip: {same}/{len(prompts)} token streams identical")
    div = [(i, first_divergence(a, b))
           for i, (a, b) in enumerate(zip(tp_out, one_out)) if a != b]
    if div:
        # row-sharded reductions round in another order: a stream may only
        # part at a near-tie of the one-chip reference's logits
        ref = forced_logits(cfg, params, prompts, one_out, "pallas", True)
        tol = tolerance(ref)
        for i, k in div:
            gap = float(ref[k, i, one_out[i][k]] - ref[k, i, tp_out[i][k]])
            log(f"request {i} parts at token {k}: reference logit gap "
                f"{gap:.4f} (near-tie limit {2 * tol:.4f})")
            expect(gap <= 2 * tol, f"request {i} diverged off a near-tie")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--tp", type=int, default=1,
                    help="run only the manual-TP phase on this many chips")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a tpu, JAX found {dev.platform!r}; the "
              f"Pallas path is never served here", file=sys.stderr)
        return 1

    log(f"device_kind {dev.device_kind}, device count {len(devices)}, "
        f"jax {jax.__version__}")
    log(f"compile cache: {enable_compile_cache()}")
    cfg = get_config(ARCH).replace(param_dtype="bfloat16")
    log(f"config {cfg.name}: {cfg.n_layers} layers, d_model {cfg.d_model}, "
        f"{cfg.n_heads} heads of {cfg.resolved_head_dim}, d_ff {cfg.d_ff}, "
        f"vocab {cfg.vocab_size}; {MAX_BATCH} slots x {MAX_LEN} tokens")
    try:
        if args.tp > 1:
            four_chips(cfg, args.seed, args.tp)
        else:
            one_chip(cfg, args.seed)
    except Exception:  # noqa: BLE001 — any failed phase fails the smoke
        traceback.print_exc()
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
