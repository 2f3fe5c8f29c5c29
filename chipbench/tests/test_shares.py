"""Roofline and peak shares from a synthetic trace never pass 100%.

A synthetic trace holds decode calls that take exactly the least time the
cost functions allow (the roofline itself) and calls that take longer. The
readers must give 100% for the first and less for the second: the FLOPs
and bytes are not counted high, and no part of the work is left out of the
time.
"""

import json
import math
from pathlib import Path
from types import SimpleNamespace

import pytest

from chipbench import driver, model, run, trace
from chipbench.reference import dense_decoder as dd

HERE = Path(__file__).resolve().parents[1]
PEAKS = json.loads((HERE / "peaks.json").read_text())["devices"]["TPU v5 lite"]


def config(name):
    """A configuration file, or ``gqa``: stablelm-3b with 8 KV heads."""
    if name == "gqa":
        return dict(model.load_config("stablelm-3b"), num_key_value_heads=8)
    return model.load_config(name)


def _ctx(conf, rows_per_step, stretch):
    """One decode call per step, each taking ``stretch`` x its least time."""
    ops, steps, t = [], [], 0
    flop_s, byte_s = PEAKS["bf16_flops_per_s"], PEAKS["hbm_bytes_per_s"]
    for rows in rows_per_step:
        f, b = dd.decode_step_cost(conf, rows)
        fa, ba = dd.decode_attention_cost(conf, rows)
        least = max(f / flop_s, b / byte_s)
        attn = max(fa / flop_s, ba / byte_s)
        dur = math.ceil(least * stretch * 1e9)
        a = math.ceil(attn * stretch * 1e9)
        ops.append(trace.Op(trace.DECODE, "fusion.1", "gemm", "linear",
                            t, t + dur - a))
        ops.append(trace.Op(trace.DECODE, "custom-call.2", "fused",
                            "fused_attn_decode", t + dur - a, t + dur))
        steps.append(driver.StepRecord(t / 1e9, (t + dur) / 1e9, list(rows),
                                       len(rows), 0, []))
        t += dur
    red = trace.Reduction((0, t), ops, [(trace.DECODE, int(r.start * 1e9), int(r.end * 1e9)) for r in steps], [])
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
