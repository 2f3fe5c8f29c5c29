"""Run one benchmark cell once on the chip.

    python3 -m chipbench --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Steps, in order: find a TPU with the cell's chips (there is no CPU
fallback); turn on JAX's compile cache; make the configuration's bf16
weights on the device from the seed; build ``PagedEngine(fused=True)``
under ``nn.backend("pallas")``; warm up only the cell's own shapes with a
token-remapped shadow of its traffic; serve the seeded traffic open loop for
``--seconds``; check the served tokens against the configuration's plain
reference; print one JSON line. ``--trace 0`` reports the cell's end-to-end
metrics and runs no profiler; ``--trace 1`` traces the first ``TRACE_S``
seconds of the window and reports their per-layer metrics, the device's
busy time, how much of that part the trace covers, and a breakdown.

Everything a cell needs is found by name: ``BENCHMARK.json`` names the
configuration and the traffic mix, whose files sit under ``configs/`` and
``traffic/``; each per-layer metric is read by ``metrics/<name>.py``; the
correctness limit of each cell is in ``limits/<cell>.json``.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402
from types import SimpleNamespace  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
sys.path.insert(0, str(ROOT / "src"))
# libtpu would otherwise write its logs outside the checkout
os.environ.setdefault("TPU_LOG_DIR", "disabled")
# the compile cache sits at a fixed path inside the checkout, whatever the
# machine sets: a cache elsewhere could be shared with another checkout
os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")

import jax  # noqa: E402

from chipbench import check as checkmod  # noqa: E402
from chipbench import driver, model, workload  # noqa: E402


#: seconds of the window that a ``--trace 1`` run traces. The profiler keeps
#: every device op in memory and drops those past a few million: the decode
#: cell, at about 1600 ops a step and 64 steps a second, lost the last 14 s
#: of a 51 s window. 20 s is about a third of that limit, with room for a
#: faster engine. Stopping the profiler holds the host for over a minute
#: there, so a traced run serves little past the traced part; what it
#: served is checked as in any run.
TRACE_S = 20.0


class NoChip(RuntimeError):
    pass


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def load_bench() -> dict:
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def find(entries, name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise SystemExit(f"chipbench: no {what} named {name!r}")


def tpu_devices(chips: int):
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"needs a TPU, JAX found {devices[0].platform!r}")
    if len(devices) < chips:
        raise NoChip(f"needs {chips} chips, JAX sees {len(devices)}")
    return devices[:chips]


def load_peaks(kind: str) -> dict:
    table = json.loads((HERE / "peaks.json").read_text())
    if kind not in table["devices"]:
        raise SystemExit(f"chipbench: no peaks for device kind {kind!r}; "
                         f"known {sorted(table['devices'])}")
    return table["devices"][kind]


def percentile(values, q: float):
    """The ``q``-th percentile (inclusive method) of a non-empty sample."""
    if not values:
        return None
    if len(values) == 1:
        return float(values[0])
    return float(statistics.quantiles(values, n=100, method="inclusive")[
        int(round(q)) - 1])


class CompileCounter:
    """Counts XLA compilations (cache hits included) while it listens, and
    how many of them the persistent compile cache served."""

    EVENT = "/jax/core/compile/backend_compile_duration"
    HIT = "/jax/compilation_cache/cache_hits"
    MISS = "/jax/compilation_cache/cache_misses"

    def __init__(self):
        self.names = []
        self.cache = {self.HIT: 0, self.MISS: 0}
        jax.monitoring.register_event_duration_secs_listener(self)
        jax.monitoring.register_event_listener(self.on_event)

    def __call__(self, event, secs, **kw):
        if event == self.EVENT:
            self.names.append(kw.get("fun_name"))

    def on_event(self, event, **kw):
        if event in self.cache:
            self.cache[event] += 1

    def close(self):
        jax.monitoring.unregister_event_duration_listener(self)
        jax.monitoring.unregister_event_listener(self.on_event)


def build_engine(cfg, conf, params, seed: int):
    from repro.serving import PagedEngine
    e = conf["engine"]
    return PagedEngine(cfg, params, max_batch=e["max_batch"],
                       max_len=e["max_len"], block_size=e["block_size"],
                       fused=True, seed=seed % 2 ** 31)


def end_to_end(d: driver.Drive) -> dict:
    """The end-to-end numbers on the benchmark's clock (not setup_s).

    The window runs from its start to its close, the return of the step
    under way when its time was up: every token stamped in it counts, over
    all of that time, and every gap between tokens that ends in it."""
    window = d.in_window()
    tokens = sum(1 for s in d.served for t in s.stamps
                 if d.t0 <= t <= d.close)
    gaps = [(b - a) * 1e3 for s in d.served
            for a, b in zip(s.stamps, s.stamps[1:]) if d.t0 <= b <= d.close]
    ttft = [(s.stamps[0] - s.due) * 1e3 for s in window if s.stamps]
    return {"tokens_per_s": tokens / (d.close - d.t0),
            "itl_p95_ms": percentile(gaps, 95),
            "ttft_p90_ms": percentile(ttft, 90)}


def _metric_reader(name: str):
    for stem in (name, name.split(".")[0]):
        path = HERE / "metrics" / f"{stem}.py"
        if path.is_file():
            spec = importlib.util.spec_from_file_location(
                f"chipbench.metrics.{stem.replace('.', '_')}", path)
            mod = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(mod)
            return mod.read
    raise SystemExit(f"chipbench: no reader for metric {name!r}")


def cell_metrics(bench: dict, cell: str, kind: str) -> list:
    """The cell's end-to-end or per-layer metric entries."""
    reports = {m["name"] for m in bench["end_to_end"]
               if cell in m.get("workloads", [cell])}
    out = []
    for m in bench[kind]:
        if "workloads" in m:
            if cell in m["workloads"]:
                out.append(m)
        elif kind == "end_to_end" or m["moves"] in reports:
            out.append(m)
    return out


def trace_options():
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    return opts


def run_cell(bench: dict, cell_name: str, seed: int, seconds: float,
             traced: bool, *, backend: str = "pallas", conf=None, mix=None,
             limits=None, devices=None, peaks=None,
             control=False, tamper=None, gaps=None) -> dict:
    """One run of one cell -> the result object (without printing it).

    The keyword arguments let a test drive the same path at a small size on
    the CPU: its own configuration, mix and limits, a kernel backend that
    runs there, and ``tamper`` to break the served path underneath.
    ``control`` serves with the program's int8 fake-quant path switched on.
    ``gaps``, a list, receives each compared request's gaps.
    """
    from repro import nn
    from repro.launch.compile_cache import enable_compile_cache

    cell = find(bench["workloads"], cell_name, "workload")
    conf = conf or model.load_config(cell["config"])
    mix = mix or workload.load_mix(cell["traffic"])
    limits = limits or checkmod.load_limits(cell_name)
    devices = devices or tpu_devices(cell["chips"])
    dev = devices[0]
    log(f"chipbench: {cell_name} seed {seed} on {len(devices)} x "
        f"{dev.device_kind} ({dev.platform}); compile cache "
        f"{enable_compile_cache()}")

    # every program, however quick to compile, goes into the cache, so that
    # only a checkout's first run compiles
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    counter = CompileCounter()
    cfg = model.program_config(conf)
    params = model.make_params(cfg, model.seed_key(seed))
    reqs = workload.generate(mix, seed, seconds, conf["vocab_size"])
    eng_conf = conf["engine"]
    tdir = None
    quant = nn.fake_quant("int8") if control else contextlib.nullcontext()
    with nn.backend(backend), quant:
        eng = build_engine(cfg, conf, params, seed)
        if tamper is not None:
            tamper(eng)
        driver.serve_waves(eng, workload.shadow(
            reqs, conf["vocab_size"], eng_conf["block_size"]))
        eng.reset_stats()
        jax.block_until_ready(eng._pools)
        warm = len(counter.names)
        setup_s = time.perf_counter() - T_START
        log(f"set-up {setup_s:.3f} s, {warm} programs compiled or loaded: "
            f"{counter.cache[counter.HIT]} from the compile cache, "
            f"{counter.cache[counter.MISS]} not found there")

        # the traced part ends at the first step that returns TRACE_S into
        # the window; the profiler stops one step later, so that a trace
        # not cut short holds device ops past window.end
        phase = {"now": None}
        if traced:
            tdir = tempfile.mkdtemp(prefix="chipbench-trace-")
            jax.profiler.start_trace(tdir, profiler_options=trace_options())
            phase["now"] = "tracing"

        def on_step(rec):
            if phase["now"] == "tracing" and rec.end >= trace_until:
                with jax.profiler.TraceAnnotation("window.end"):
                    pass
                phase["now"] = "closing"
            elif phase["now"] == "closing":
                stop_trace()

        def stop_trace():
            t = time.perf_counter()
            jax.profiler.stop_trace()
            phase["now"] = None
            log(f"profiler stopped in {time.perf_counter() - t:.3f} s")

        trace_until = time.perf_counter() + min(TRACE_S, seconds)
        with jax.profiler.TraceAnnotation("window.start"):
            pass
        d = driver.drive(eng, reqs, seconds, drain=bool(mix.get("drain")),
                         on_step=on_step)
        if phase["now"] == "tracing":
            with jax.profiler.TraceAnnotation("window.end"):
                pass
        if phase["now"] is not None:
            stop_trace()
        inside = counter.names[warm:]
        log(f"compiles inside the window: {len(inside)} {inside}")
        peak = max((x.memory_stats() or {}).get("peak_bytes_in_use", 0)
                   for x in devices)
        hlo = {}
        if traced:
            from chipbench import trace as tracemod
            hlo = tracemod.program_texts(eng)
        # a drained window follows every request due in it to its end; a
        # backlog counts the requests the window took up
        taken = [s for s in d.in_window() if s.stamps or mix.get("drain")]
        attempted = len(taken)
        failed = sum(1 for s in taken if not s.stamps)
    counter.close()
    del eng
    gc.collect()

    finished = [s for s in d.served if s.finished]
    numbers = checkmod.compare(conf, params, finished, seed, limits,
                               eng_conf["max_len"], keep=gaps)
    ok = checkmod.passed(numbers) and failed == 0
    out = {"correct": ok, "attempted": attempted, "failed": failed}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": int(peak)}

    if traced:
        from chipbench import trace as tracemod
        red = tracemod.reduce_dir(tdir, hlo)
        shutil.rmtree(tdir, ignore_errors=True)
        ctx = SimpleNamespace(conf=conf, cell=cell, drive=d, trace=red,
                              peaks=peaks or load_peaks(dev.device_kind),
                              costs=checkmod.reference(conf),
                              chips=len(devices), seconds=seconds)
        metrics = {}
        for m in cell_metrics(bench, cell_name, "per_layer"):
            v = _metric_reader(m["name"])(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        device["busy_s"] = red.busy_s
        device["window_s"] = red.window_s
        device["traced_s"] = red.traced_s
        device["covered_s"] = red.covered_s
        out["metrics"] = metrics
        out["device"] = device
        out["breakdown"] = red.breakdown()
    else:
        e2e = end_to_end(d)
        e2e["setup_s"] = setup_s
        metrics = {}
        for m in cell_metrics(bench, cell_name, "end_to_end"):
            if e2e.get(m["name"]) is not None:
                metrics[m["name"]] = {"value": e2e[m["name"]],
                                      "unit": m["unit"]}
        out["metrics"] = metrics
        out["device"] = device
    out["check"] = numbers
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="python3 -m chipbench",
                                 description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    bench = load_bench()
    try:
        out = run_cell(bench, args.workload, args.seed, args.seconds,
                       bool(args.trace))
    except NoChip as e:
        log(f"chipbench: {e}; nothing is served without the chip")
        return 2
    for name, n in out["check"].items():
        log(f"check {name}: {n['value']} (limit {n['limit']})")
    print(json.dumps(out), flush=True)
    return 0
