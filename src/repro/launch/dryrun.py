import os
import tempfile
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# a CPU-only compile tool: it (and every --sweep child, which inherits this
# environment) must never take an attached accelerator
os.environ["JAX_PLATFORMS"] = "cpu"

# The roofline analyzer reads the post-SPMD-partitioning (pre-optimization)
# module: it has true dtypes (XLA:CPU's optimized module legalizes every
# bf16 buffer to f32 — 2x inflated and misleading for a TPU roofline),
# per-device shapes, and materialized collectives. Dumped per-process.
_DUMP_DIR = os.environ.get("REPRO_DUMP_DIR") or os.path.join(
    tempfile.gettempdir(), f"repro_xla_dump_{os.getpid()}")
os.environ["XLA_FLAGS"] += (
    f" --xla_dump_to={_DUMP_DIR} --xla_dump_hlo_pass_re=spmd-partitioning")

# Multi-pod dry-run (assignment deliverable e): lower + compile every
# (architecture x input shape) cell on the production meshes with
# ShapeDtypeStruct inputs — no allocation — and record memory_analysis /
# cost_analysis / trip-aware collective bytes for the roofline (deliverable
# g). The environment lines above MUST precede any jax import: XLA locks
# the platform and the host device count at first init.
#
# Usage:
#   python -m repro.launch.dryrun --arch stablelm-3b --shape train_4k
#   python -m repro.launch.dryrun --arch gemma3-27b --shape decode_32k --multi-pod
#   python -m repro.launch.dryrun --sweep [--multi-pod] [--jobs N]
#
# One cell per subprocess under --sweep: a pathological cell can neither
# corrupt nor block the rest (compile-time fault isolation mirrors the
# runtime fault-tolerance posture).

import argparse
import glob
import json
import subprocess
import sys
import time
import traceback

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import sharding
from repro.configs import ARCH_IDS, get_config, reduced
from repro.core.hlo import KERNEL_REGION_MARKERS, analyze_partitioned
from repro.core.roofline import roofline_from_hlo
from repro.core.workload import Workload
from repro.launch.mesh import make_production_mesh, mesh_chips
from repro.launch.specs import (abstract_state, input_specs, model_flops,
                                train_microbatches)
from repro.models.common import SHAPES, shape_applicable
from repro.optim import OptimizerConfig
from repro.runtime import TrainState, make_train_step
from repro.serving import make_prefill_step, make_serve_step

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                           "results", "dryrun")


def _spec(mesh, *names):
    return NamedSharding(mesh, P(*names))


def _partitioned_text(compiled) -> str:
    """Read the post-SPMD-partitioning dump of the *step* module.

    Falls back to the optimized module if the dump is missing (e.g. a
    backend that doesn't honor the dump flags)."""
    pattern = os.path.join(_DUMP_DIR,
                           "*after_spmd-partitioning*.txt")
    candidates = [p for p in glob.glob(pattern)
                  if os.path.getsize(p) > 0]
    if not candidates:
        return compiled.as_text()
    # the step module is by far the largest dump in this process
    best = max(candidates, key=os.path.getsize)
    with open(best) as f:
        return f.read()


def _batch_spec(mesh, ndim: int, micro: bool):
    if micro:
        names = (None, ("pod", "data") if "pod" in mesh.axis_names
                 else "data") + (None,) * (ndim - 2)
    else:
        names = (("pod", "data") if "pod" in mesh.axis_names else "data",
                 ) + (None,) * (ndim - 1)
    return NamedSharding(mesh, P(*names))


def _token_batch_sharding(mesh, spec_tree, micro: bool):
    def one(s):
        dim0 = s.shape[1] if micro else s.shape[0]
        n_batch = 1
        for ax in ("pod", "data"):
            if ax in mesh.axis_names:
                n_batch *= dict(zip(mesh.axis_names,
                                    mesh.devices.shape))[ax]
        if dim0 % n_batch:
            return _spec(mesh)  # replicate (e.g. batch=1 long_500k)
        return _batch_spec(mesh, len(s.shape), micro)
    return jax.tree_util.tree_map(one, spec_tree)


def lower_cell(arch: str, shape_name: str, multi_pod: bool,
               use_reduced: bool = False, opt_overrides: dict = None,
               compile_only: bool = False) -> dict:
    cfg = get_config(arch)
    if use_reduced:
        cfg = reduced(cfg)
    shape = SHAPES[shape_name]
    if use_reduced:
        shape = shape.__class__(shape.name, seq_len=256,
                                global_batch=max(shape.global_batch // 8, 8),
                                kind=shape.kind)
    if not shape_applicable(cfg, shape):
        return {"arch": arch, "shape": shape_name,
                "mesh": "multi" if multi_pod else "single",
                "skipped": ("long_500k needs sub-quadratic attention"
                            if shape.name == "long_500k"
                            else "no decode step for encoder-only")}
    if shape.kind == "prefill":
        # Megatron-SP on the prefill residual stream: a pure win for the
        # forward-only serving path (§Perf iteration 3); training keeps
        # plain TP (iterations 4-5 refuted SP under the remat backward).
        cfg = cfg.replace(seq_shard=True)
    if opt_overrides:
        cfg = cfg.replace(**opt_overrides)

    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = mesh_chips(mesh)
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    n_data = sizes.get("data", 1) * sizes.get("pod", 1)

    t0 = time.time()
    if shape.kind == "train":
        n_micro = train_microbatches(cfg, shape, n_data)
        specs = input_specs(cfg, shape, mesh, num_microbatches=n_micro)
        state = abstract_state(cfg)
        state_sh = TrainState(
            sharding.param_sharding(state.params, mesh, cfg.fsdp),
            type(state.opt)(
                step=_spec(mesh),
                mu=sharding.param_sharding(state.opt.mu, mesh, cfg.fsdp),
                nu=sharding.param_sharding(state.opt.nu, mesh, cfg.fsdp),
                err=None))
        batch_sh = _token_batch_sharding(mesh, specs["batch"], n_micro > 1)
        step = make_train_step(cfg, OptimizerConfig(), mesh,
                               num_microbatches=n_micro)
        jitted = jax.jit(step, in_shardings=(state_sh, batch_sh),
                         donate_argnums=(0,))
        lowered = jitted.lower(state, specs["batch"])
        extra = {"num_microbatches": n_micro}
    elif shape.kind == "prefill":
        specs = input_specs(cfg, shape, mesh)
        params = abstract_state(cfg).params
        params_sh = sharding.param_sharding(params, mesh, cfg.fsdp)
        tok_sh = _token_batch_sharding(mesh, specs["tokens"], False)
        step = make_prefill_step(cfg, max_len=shape.seq_len, mesh=mesh)
        jitted = jax.jit(step, in_shardings=(params_sh, tok_sh))
        lowered = jitted.lower(params, specs["tokens"])
        extra = {}
    else:  # decode: serve_step with the engine's per-slot pos vector (B,)
        specs = input_specs(cfg, shape, mesh)
        params = abstract_state(cfg).params
        params_sh = sharding.param_sharding(params, mesh, cfg.fsdp)
        cache_sh = sharding.cache_sharding(specs["caches"], mesh)
        tok_sh = _token_batch_sharding(mesh, specs["token"], False)
        step = make_serve_step(cfg, mesh, greedy=True)
        jitted = jax.jit(step,
                         in_shardings=(params_sh, tok_sh, _spec(mesh),
                                       cache_sh, _spec(mesh)),
                         donate_argnums=(3,))
        lowered = jitted.lower(params, specs["token"], specs["pos"],
                               specs["caches"], specs["key"])
        extra = {}
    t_lower = time.time() - t0

    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0

    mem = {}
    try:
        ma = compiled.memory_analysis()
        for f in ("argument_size_in_bytes", "output_size_in_bytes",
                  "temp_size_in_bytes", "generated_code_size_in_bytes",
                  "alias_size_in_bytes"):
            v = getattr(ma, f, None)
            if v is not None:
                mem[f] = int(v)
        print("memory_analysis:", mem)
    except Exception as e:  # backend without memory analysis
        mem = {"error": str(e)}
    cost = {}
    try:
        ca = compiled.cost_analysis()
        ca = ca[0] if isinstance(ca, (list, tuple)) else ca
        cost = {k: float(v) for k, v in ca.items()
                if isinstance(v, (int, float)) and k in
                ("flops", "bytes accessed", "optimal_seconds",
                 "utilization operand 0 {}", "bytes accessed output {}")}
        print("cost_analysis:", {k: cost[k] for k in list(cost)[:4]})
    except Exception as e:
        cost = {"error": str(e)}

    text = _partitioned_text(compiled)
    mf = model_flops(cfg, shape)
    # two memory models of the same lowered program: XLA-fusion-only (the
    # paper-faithful baseline) and Pallas-kernel regions (the deployed
    # system, kernels/ replacing the tagged NonGEMM hot spots)
    hlo_xla = analyze_partitioned(text)
    hlo = analyze_partitioned(text, kernel_regions=KERNEL_REGION_MARKERS)
    terms = roofline_from_hlo(hlo, chips, model_flops=mf)
    terms_xla = roofline_from_hlo(hlo_xla, chips, model_flops=mf)

    # the cell as a declarative Workload, profiled through the unified
    # compiled backend over the already-partitioned module: the paper's
    # GEMM/NonGEMM split of every production cell, for free
    workload = Workload(name=f"{arch}/{shape_name}", arch=arch,
                        phase=shape.kind, batch=shape.global_batch,
                        seq=shape.seq_len, dtype=cfg.dtype)
    prof = workload.profile("compiled:tpu_v5e", hlo_text=text)

    bytes_per_device = sum(v for k, v in mem.items()
                           if isinstance(v, int) and k != "alias_size_in_bytes"
                           and k != "generated_code_size_in_bytes")
    result = {
        "arch": arch, "shape": shape_name,
        "mesh": "multi" if multi_pod else "single",
        "chips": chips,
        "reduced": use_reduced,
        "lower_s": round(t_lower, 1), "compile_s": round(t_compile, 1),
        "memory_analysis": mem,
        "bytes_per_device": bytes_per_device,
        "cost_analysis": cost,
        "hlo": hlo.to_dict(),
        "hlo_xla_only": hlo_xla.to_dict(),
        "model_flops": mf,
        "roofline": terms.to_dict(),
        "roofline_xla_only": terms_xla.to_dict(),
        "workload": workload.describe(),
        "gemm_nongemm": {
            "gemm_frac": prof.split["gemm_frac"],
            "nongemm_frac": prof.split["nongemm_frac"],
            "mode": prof.mode,
        },
        **extra,
    }
    return result


def cell_path(arch: str, shape_name: str, multi_pod: bool,
              out_dir: str = None) -> str:
    d = os.path.abspath(out_dir or RESULTS_DIR)
    d = os.path.join(d, "multi" if multi_pod else "single")
    os.makedirs(d, exist_ok=True)
    return os.path.join(d, f"{arch}__{shape_name}.json")


def run_one(args) -> int:
    try:
        res = lower_cell(args.arch, args.shape, args.multi_pod,
                         use_reduced=args.reduced,
                         opt_overrides=json.loads(args.overrides)
                         if args.overrides else None)
    except Exception:
        res = {"arch": args.arch, "shape": args.shape,
               "mesh": "multi" if args.multi_pod else "single",
               "error": traceback.format_exc()}
    path = cell_path(args.arch, args.shape, args.multi_pod, args.out)
    with open(path, "w") as f:
        json.dump(res, f, indent=1)
    if "error" in res:
        print(f"FAIL {args.arch} x {args.shape}:\n{res['error']}",
              file=sys.stderr)
        return 1
    if "skipped" in res:
        print(f"SKIP {args.arch} x {args.shape}: {res['skipped']}")
        return 0
    r = res["roofline"]
    print(f"OK {args.arch} x {args.shape} [{res['mesh']}] "
          f"compile {res['compile_s']}s  "
          f"compute {r['compute_s']:.4f}s memory {r['memory_s']:.4f}s "
          f"collective {r['collective_s']:.4f}s -> {r['dominant']}-bound  "
          f"useful_ratio {r['useful_ratio']:.2f} mfu {r['mfu']:.3f}")
    return 0


def run_sweep(args) -> int:
    cells = [(a, s) for a in (args.archs or ARCH_IDS) for s in SHAPES]
    procs = []
    failures = 0
    max_jobs = max(args.jobs, 1)

    def reap(block: bool):
        nonlocal failures
        for p, (a, s) in list(procs):
            if p.poll() is not None or block:
                rc = p.wait()
                failures += int(rc != 0)
                procs.remove((p, (a, s)))

    for a, s in cells:
        if args.skip_done and os.path.exists(
                cell_path(a, s, args.multi_pod, args.out)):
            continue
        cmd = [sys.executable, "-m", "repro.launch.dryrun",
               "--arch", a, "--shape", s]
        if args.multi_pod:
            cmd.append("--multi-pod")
        if args.reduced:
            cmd.append("--reduced")
        if args.out:
            cmd += ["--out", args.out]
        while len(procs) >= max_jobs:
            reap(block=False)
            time.sleep(2)
        print(f"[sweep] launch {a} x {s}", flush=True)
        procs.append((subprocess.Popen(cmd), (a, s)))
    while procs:
        reap(block=False)
        time.sleep(2)
    print(f"[sweep] done; {failures} failures")
    return int(failures > 0)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape", choices=list(SHAPES))
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--reduced", action="store_true",
                    help="tiny config self-test (CI)")
    ap.add_argument("--sweep", action="store_true")
    ap.add_argument("--skip-done", action="store_true")
    ap.add_argument("--jobs", type=int, default=1)
    ap.add_argument("--archs", nargs="*")
    ap.add_argument("--out", default=None)
    ap.add_argument("--overrides", default=None,
                    help="JSON dict of ModelConfig overrides (perf sweeps)")
    args = ap.parse_args()
    if args.sweep:
        return run_sweep(args)
    if not args.arch or not args.shape:
        ap.error("--arch and --shape required (or --sweep)")
    return run_one(args)


if __name__ == "__main__":
    sys.exit(main())
