"""Roofline and peak shares from a synthetic trace never pass 100%.

A synthetic trace holds decode calls that take exactly the least time the
cost functions allow (the roofline itself) and calls that take longer. The
readers must give 100% for the first and less for the second: the FLOPs
and bytes are not counted high, and no part of the work is left out of the
time. A trace cut short is read only as far as it holds device ops.
"""

import json
import math
from pathlib import Path
from types import SimpleNamespace

import pytest

from chipbench import driver, model, run, trace
from chipbench.reference import dense_decoder as dd
from chipbench.tests.test_trace import NS, SYN_HLO, ev

HERE = Path(__file__).resolve().parents[1]
PEAKS = json.loads((HERE / "peaks.json").read_text())["devices"]["TPU v5 lite"]


def config(name):
    """A configuration file, or ``gqa``: stablelm-3b with 8 KV heads."""
    if name == "gqa":
        return dict(model.load_config("stablelm-3b"), num_key_value_heads=8)
    return model.load_config(name)


def _ctx(conf, rows_per_step, stretch, covered=None):
    """One decode call per step, each taking ``stretch`` x its least time,
    in a trace of them all whose device ops stop after the first
    ``covered`` calls, as a trace the profiler cut short."""
    modules, ops, steps, t = [], [], [], 0
    flop_s, byte_s = PEAKS["bf16_flops_per_s"], PEAKS["hbm_bytes_per_s"]
    for i, rows in enumerate(rows_per_step):
        f, b = dd.decode_step_cost(conf, rows)
        fa, ba = dd.decode_attention_cost(conf, rows)
        least = max(f / flop_s, b / byte_s)
        attn = max(fa / flop_s, ba / byte_s)
        dur = math.ceil(least * stretch * 1e9)
        a = math.ceil(attn * stretch * 1e9)
        if covered is None or i < covered:
            modules.append(ev("jit_paged_step(1)", t, dur))
            ops.append(ev("fusion.1", t, dur - a))
            ops.append(ev("custom-call.2", t + dur - a, a))
        steps.append(driver.StepRecord(t / 1e9, (t + dur) / 1e9, list(rows),
                                       len(rows), 0, []))
        t += dur
    host = NS(name="/host:CPU", lines=[NS(name="python", events=[
        ev("window.start", 0, 0), ev("window.end", t, 0)])])
    device = NS(name="/device:TPU:0", lines=[
        NS(name="XLA Modules", events=modules), NS(name="XLA Ops", events=ops)])
    red = trace.reduce_profile(NS(planes=[host, device]), SYN_HLO)
    d = driver.Drive(0.0, t / 1e9, [], steps, 0, 0)
    return SimpleNamespace(conf=conf, drive=d, trace=red, peaks=PEAKS,
                           costs=dd, chips=1, seconds=t / 1e9)


@pytest.mark.parametrize("name", ["stablelm-3b", "gqa"])
@pytest.mark.parametrize("metric", ["decode_roofline", "attn_decode_roofline",
                                    "mfu"])
def test_share_at_the_roofline_is_at_most_100(name, metric):
    conf = config(name)
    rows = [[64 + 37 * i + 5 * j for i in range(8)] for j in range(6)]
    read = run._metric_reader(metric)
    at = read(_ctx(conf, rows, 1.0))
    slow = read(_ctx(conf, rows, 3.0))
    assert 0 < slow < at <= 100.0 + 1e-9
    if metric != "mfu":
        assert at == pytest.approx(100.0, rel=1e-3)


@pytest.mark.parametrize("metric", ["decode_roofline", "attn_decode_roofline",
                                    "mfu"])
def test_a_cut_trace_counts_only_the_covered_steps(metric):
    """The device ops stop after 6 of 8 calls at the roofline: the readers
    stop there too, and read what the 6 calls alone read (100% for the
    rooflines), not 133% (all steps over the covered time) or 75% (the
    covered steps over the whole traced part)."""
    conf = model.load_config("stablelm-3b")
    rows = [[64 + 37 * i + 5 * j for i in range(8)] for j in range(8)]
    ctx = _ctx(conf, rows, 1.0, covered=6)
    assert ctx.trace.covered_s == pytest.approx(0.75 * ctx.trace.traced_s,
                                                rel=0.1)
    assert ctx.trace.window_s == ctx.trace.covered_s
    read = run._metric_reader(metric)
    assert read(ctx) == pytest.approx(read(_ctx(conf, rows[:6], 1.0)),
                                      rel=1e-9)
    if metric != "mfu":
        assert read(ctx) == pytest.approx(100.0, rel=1e-3)


def test_no_trace_no_share():
    conf = model.load_config("stablelm-3b")
    ctx = _ctx(conf, [], 1.0)
    for metric in ("decode_roofline", "attn_decode_roofline", "mfu",
                   "decode_step_ms", "nongemm_share"):
        assert run._metric_reader(metric)(ctx) is None


def test_costs_follow_the_shapes():
    conf = model.load_config("stablelm-3b")
    # 2 x params per token, plus 4 * heads * head_dim per attended row
    per_tok = 2 * dd.matmul_params(conf)
    assert dd.decode_token_flops(conf, 1) - per_tok == 32 * 4 * 32 * 80
    # one KV row of stablelm-3b: 32 layers x (K + V) x 2560 x 2 bytes
    assert dd._kv_row_bytes(conf) == 327_680
    g = config("gqa")
    assert dd._kv_row_bytes(g) == 32 * 2 * 8 * 80 * 2
    # GQA: attention bytes count each K/V row once per KV head
    _, b = dd.decode_attention_cost(g, [100])
    assert b == dd._kv_row_bytes(g) * 100 + 32 * 2 * 32 * 80 * 2
