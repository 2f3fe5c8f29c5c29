"""Readings that set a cell's correctness limit; run on the chip, not by the check.

    python3 -m chipbench.calibrate --workload <cell> --seconds <s> \
        --seeds 1,2,3 [--control] [--trace 0|1]

It runs the cell once per seed in one process (each seed its own
weights, traffic and engine; programs come from the compile cache) and
prints one JSON line per run with the numbers compared and how the gaps
spread. With ``--control`` the program serves with its own int8 fake-quant
path switched on (the nearest precision below the configuration's bf16):
the control that the comparison has to fail.
"""

from __future__ import annotations

import argparse
import gc
import json
import sys
import time
import traceback

import numpy as np

from chipbench import run


def gap_stats(gaps) -> dict:
    """How the gaps of one run spread: the candidates for a number compared."""
    flat = np.concatenate(gaps) if gaps else np.zeros(0)
    if not flat.size:
        return {}
    return {"n": int(flat.size), "mean": float(flat.mean()),
            "p90": float(np.quantile(flat, 0.9)),
            "p99": float(np.quantile(flat, 0.99)),
            "max": float(flat.max()),
            "share_above_0": float((flat > 0).mean()),
            "per_request_max": [float(g.max()) for g in gaps if g.size]}


def seeds(args) -> None:
    bench = run.load_bench()
    for s in (int(x) for x in args.seeds.split(",")):
        t = time.perf_counter()
        gaps = []
        try:
            out = run.run_cell(bench, args.workload, s, args.seconds,
                               bool(args.trace), control=args.control,
                               gaps=gaps)
        except Exception:  # noqa: BLE001 — one seed's failure is a reading
            traceback.print_exc()
            out = {"correct": False, "error": True}
        gc.collect()
        out.pop("breakdown", None)
        out.update(seed=s, control=args.control, gap_stats=gap_stats(gaps),
                   wall_s=time.perf_counter() - t)
        print(json.dumps(out), flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="python3 -m chipbench.calibrate")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        seeds(args)
    except run.NoChip as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 2
    return 0


if __name__ == "__main__":
    sys.exit(main())
