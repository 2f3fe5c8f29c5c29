"""The trace reduction: programs, groups, busy and idle time, breakdown."""

import gzip
import json
from pathlib import Path
from types import SimpleNamespace as NS

import pytest

from chipbench import trace

DATA = Path(__file__).resolve().parent / "data"


def ev(name, start, dur, **stats):
    return NS(name=name, start_ns=start, duration_ns=dur, end_ns=start + dur,
              stats=list(stats.items()))


def hlo(*instrs):
    """Compiled HLO text of (name, opcode, op_name) instructions."""
    return "\n".join(
        f'  %{n} = bf16[8]{{0}} {op}(bf16[8]{{0}} %p), '
        f'metadata={{op_name="{meta}"}}' for n, op, meta in instrs)


#: the decode program's ops, as its compiled HLO names them
SYN_HLO = {trace.DECODE: hlo(
    ("fusion.1", "fusion", "jit(paged_step)/ng:gemm:linear/c1/dot_general"),
    ("custom-call.2", "custom-call",
     "jit(paged_step)/ng:fused:fused_attn_decode/c7/pallas_call"))}


def synthetic():
    """Two decode executions and one prefill on a device, host spans around.

    decode 1: [100, 200) gemm 60 + attention 30, 10 idle inside the module;
    prefill:  [250, 300) one untagged copy; decode 2: [400, 450) gemm 50;
    decode 3: [510, 530), past ``window.end`` as the benchmark traces one
    step more. On a TPU an op event is named by its instruction's text.
    """
    device = NS(name="/device:TPU:0", lines=[
        NS(name="XLA Modules", events=[
            ev("jit_paged_step(1)", 100, 100), ev("jit_prefill_step(2)", 250, 50),
            ev("jit_paged_step(1)", 400, 50), ev("jit_paged_step(1)", 510, 20)]),
        NS(name="XLA Ops", events=[
            ev("%fusion.1 = bf16[8]{0} fusion(bf16[8]{0} %p), kind=kOutput",
               100, 60),
            ev("%custom-call.2 = bf16[8]{0} custom-call(bf16[8]{0} %q)",
               160, 30),
            ev("%copy.3 = bf16[8]{0} copy(bf16[8]{0} %r)", 250, 50),
            ev("%fusion.1 = bf16[8]{0} fusion(bf16[8]{0} %p), kind=kOutput",
               400, 50),
            ev("%fusion.1 = bf16[8]{0} fusion(bf16[8]{0} %p), kind=kOutput",
               510, 20)]),
    ])
    host = NS(name="/host:CPU", lines=[NS(name="python", events=[
        ev("window.start", 50, 0), ev("engine.step", 90, 120),
        ev("bookkeeping", 210, 30), ev("engine.step", 245, 210),
        ev("idle", 455, 40), ev("window.end", 500, 0)])])
    return NS(planes=[host, device])


def recorded():
    """Events of a v5e trace of the decode cell (one admission, then two
    decode steps) and the compiled HLO of its decode program."""
    with gzip.open(DATA / "v5e_decode_trace.json.gz", "rt") as f:
        rec = json.load(f)
    pd = NS(planes=[NS(name=p["name"], lines=[
        NS(name=ln["name"], events=[ev(n, s, d, **dict(st))
                                    for n, s, d, st in ln["events"]])
        for ln in p["lines"]]) for p in rec["planes"]])
    with gzip.open(DATA / "v5e_paged_step.hlo.txt.gz", "rt") as f:
        return pd, {trace.DECODE: f.read()}


def test_programs_groups_and_sites():
    red = trace.reduce_profile(synthetic(), SYN_HLO)
    assert red.window == (50, 500)
    dec = red.program(trace.DECODE)
    assert dec == {"time_s": pytest.approx(140e-9), "calls": 2}
    assert red.program("prefill_step")["calls"] == 1
    assert red.group_time(trace.DECODE) == {"gemm": pytest.approx(110e-9),
                                            "fused": pytest.approx(30e-9)}
    assert red.site_time(trace.DECODE, trace.ATTN_DECODE) == \
        pytest.approx(30e-9)
    # an op with no ng: scope falls back to its opcode's group
    assert red.group_time("prefill_step") == {"memory": pytest.approx(50e-9)}


def test_busy_idle_and_breakdown():
    red = trace.reduce_profile(synthetic(), SYN_HLO)
    assert red.window_s == pytest.approx(450e-9)
    assert red.busy_s == pytest.approx(190e-9)
    gaps = red.gaps()
    assert gaps == [(50, 100), (190, 250), (300, 400), (450, 500)]
    bd = red.breakdown()
    assert bd["device_ops"][0] == ["ng:gemm:linear", pytest.approx(110e-9)]
    idle = dict(bd["idle_gaps"])
    # midpoints: 75 before any span, 220 in bookkeeping, 350 in the second
    # engine.step, 475 in idle
    assert idle == {"engine.step": pytest.approx(100e-9),
                    "bookkeeping": pytest.approx(60e-9),
                    "idle": pytest.approx(50e-9),
                    "no host span": pytest.approx(50e-9)}
    assert sum(idle.values()) == pytest.approx(red.window_s - red.busy_s)


def test_a_trace_cut_short_ends_at_its_last_device_op():
    """A whole trace holds the step past ``window.end``; one the profiler
    cut short is read only as far as its device ops go."""
    red = trace.reduce_profile(synthetic(), SYN_HLO)
    assert (red.traced_s, red.covered_s) == (pytest.approx(450e-9),
                                             pytest.approx(480e-9))
    assert red.window == (50, 500)
    pd = synthetic()
    for line in pd.planes[1].lines:
        line.events.pop()
    red = trace.reduce_profile(pd, SYN_HLO)
    assert (red.traced_s, red.covered_s) == (pytest.approx(450e-9),
                                             pytest.approx(400e-9))
    assert red.window == (50, 450)
    assert red.busy_s == pytest.approx(190e-9)
    assert red.gaps() == [(50, 100), (190, 250), (300, 400)]


def test_ops_without_module_stat_belong_to_the_enclosing_module():
    red = trace.reduce_profile(synthetic(), SYN_HLO)
    assert {o.program for o in red.ops} == {"paged_step", "prefill_step"}


def test_hlo_text_supplies_missing_op_names():
    text = ('  %fusion.9 = bf16[8,2560]{1,0} fusion(bf16[8,2560]{1,0} %p), '
            'kind=kOutput, calls=%fc, metadata={op_name="jit(f)/'
            'ng:normalization:layer_norm/c3"}')
    pd = NS(planes=[NS(name="/device:TPU:0", lines=[
        NS(name="XLA Modules", events=[ev("jit_f(4)", 0, 10)]),
        NS(name="XLA Ops", events=[ev("fusion.9", 0, 10)])])])
    red = trace.reduce_profile(pd, {"f": text})
    assert red.group_time("f") == {"normalization": pytest.approx(10e-9)}
    assert red.program("f")["calls"] == 1


def test_only_the_op_line_is_read():
    """Lines the profiler derives from the ops (steps, name scopes) hold the
    same device time again: none of it is counted."""
    pd = synthetic()
    device = pd.planes[1]
    device.lines += [
        NS(name="Steps", events=[ev("0", 100, 100), ev("1", 400, 50)]),
        NS(name="Framework Name Scope", events=[
            ev("jit(paged_step)", 100, 90, tf_op="jit(paged_step)")])]
    red = trace.reduce_profile(pd, SYN_HLO)
    assert red.program(trace.DECODE)["time_s"] == pytest.approx(140e-9)
    assert red.busy_s == pytest.approx(190e-9)


def test_a_loop_op_keeps_only_its_own_time():
    """A while op spans its body's ops on the same line: each is counted once."""
    text = hlo(("while.1", "while", "jit(paged_step)/while"),
               ("fusion.2", "fusion", "jit(paged_step)/ng:gemm:linear/c1"))
    pd = NS(planes=[NS(name="/device:TPU:0", lines=[
        NS(name="XLA Modules", events=[ev("jit_paged_step(1)", 0, 100)]),
        NS(name="XLA Ops", events=[
            ev("while.1", 0, 100),
            ev("fusion.2", 10, 30),
            ev("fusion.2", 50, 30)])])])
    red = trace.reduce_profile(pd, {trace.DECODE: text})
    assert red.program(trace.DECODE)["time_s"] == pytest.approx(100e-9)
    assert red.group_time(trace.DECODE) == {"gemm": pytest.approx(60e-9),
                                            "control": pytest.approx(40e-9)}


@pytest.mark.parametrize("name, want", [
    ("%copy.88 = bf16[32,321]{1,0:T(8,128)(2,1)} copy(bf16[32,321]{0,1} "
     "%fusion.5)", ("copy.88", "copy")),
    ("%copy-start.14 = (s32[256]{0:T(256)}, u32[]{:S(2)}) copy-start("
     "s32[256]{0:T(256)} %reshape.40)", ("copy-start.14", "copy-start")),
    ("%attn_template_decode.6 = bf16[256,8,80]{2,1,0:T(8,128)(2,1)S(1)} "
     "custom-call(s32[8]{0} %a, bf16[256,8,80]{2,1,0} %b)",
     ("attn_template_decode.6", "custom-call")),
    ("fusion.12", ("fusion.12", "fusion")),
])
def test_event_names_give_the_instruction(name, want):
    assert trace.event_op(name) == want


def test_an_untagged_fusion_takes_its_primitive_group():
    """As in the taxonomy: an untagged fusion is grouped by the primitive
    that ends its op_name, else it is other."""
    assert trace.classify("jit(f)/while/body/dynamic_slice", "fusion") == \
        ("memory", "dynamic_slice")
    assert trace.classify("jit(f)/ng:gemm:linear/c1/dynamic_slice",
                          "fusion") == ("gemm", "linear")
    assert trace.classify("", "fusion") == ("other", "fusion")
    assert trace.classify(None, "copy") == ("memory", "copy")


def test_recorded_v5e_trace():
    """A slice of a real v5e trace: each decode call's ops account for its
    module execution, the decode attention kernel and the matmuls are found
    by their scopes, and the async copy line is not counted."""
    pd, texts = recorded()
    red = trace.reduce_profile(pd, texts)
    dec = red.program(trace.DECODE)
    assert dec["calls"] == 2
    spans = sum(e - s for p, s, e in red.runs if p == trace.DECODE) / 1e9
    assert dec["time_s"] == pytest.approx(spans, rel=0.01)
    assert red.program("prefill_step")["calls"] == 1
    groups = red.group_time(trace.DECODE)
    assert groups["gemm"] > 0 and groups["memory"] > 0
    assert groups.get("other", 0) < 0.02 * dec["time_s"]
    attn = red.site_time(trace.DECODE, trace.ATTN_DECODE)
    assert 0 < attn < dec["time_s"]
    assert 0 < red.busy_s <= red.window_s
    sites = [name for name, _ in red.breakdown()["device_ops"]]
    assert "ng:fused:fused_attn_decode" in sites
    assert all(name.startswith("ng:") and "=" not in name for name in sites)
