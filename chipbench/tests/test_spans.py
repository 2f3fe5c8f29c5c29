"""The engine's ``serve.*`` spans: collected with their args, idle time put
down to the innermost span, and the three quantities read from them."""

import pytest

from chipbench import spans, trace
from chipbench.tests import tiny
from chipbench.tests.test_trace import SYN_HLO, NS, ev, recorded, synthetic


def nested():
    """One decode module with four ops, one ``engine.step`` around an
    admission and a decode step, and the engine's spans inside it.

    ops [100, 150) [160, 200) [260, 300) [400, 450) in a [50, 500) window,
    and the step past it that the benchmark traces, [510, 530);
    idle gaps (midpoint -> innermost span): (50, 100) 75 none,
    (150, 160) 155 serve.prefill, (200, 260) 230 serve.decode.prep,
    (300, 400) 350 serve.decode, (450, 500) 475 engine.step.
    """
    op = "%fusion.1 = bf16[8]{0} fusion(bf16[8]{0} %p), kind=kOutput"
    device = NS(name="/device:TPU:0", lines=[
        NS(name="XLA Modules", events=[ev("jit_paged_step(1)", 100, 350),
                                       ev("jit_paged_step(1)", 510, 20)]),
        NS(name="XLA Ops", events=[ev(op, 100, 50), ev(op, 160, 40),
                                   ev(op, 260, 40), ev(op, 400, 50),
                                   ev(op, 510, 20)])])
    host = NS(name="/host:CPU", lines=[NS(name="python", events=[
        ev("window.start", 50, 0),
        ev("engine.step", 80, 400),
        ev("serve.admit", 80, 130, uid=7, prompt_len=40, cached_len=0,
           path="cold"),
        ev("serve.prefill", 82, 123, uid=7, bucket=64),
        ev("serve.decode", 215, 255, live=8),
        ev("serve.decode.prep", 215, 25),
        ev("serve.decode.dispatch", 240, 15),
        ev("serve.decode.wait", 255, 55),
        ev("serve.retire", 455, 15, finished=1),
        ev("bookkeeping", 480, 15),
        ev("window.end", 500, 0)])])
    return NS(planes=[host, device])


def test_serve_spans_are_collected_with_their_args():
    got = spans.serve_spans(nested())
    assert [s.name for s in got] == [
        "serve.admit", "serve.prefill", "serve.decode", "serve.decode.prep",
        "serve.decode.dispatch", "serve.decode.wait", "serve.retire"]
    assert got[0] == spans.Span("serve.admit", 80, 210, {
        "uid": 7, "prompt_len": 40, "cached_len": 0, "path": "cold"})
    assert got[1].args == {"uid": 7, "bucket": 64}
    assert got[-1].args == {"finished": 1}


def test_idle_goes_to_the_innermost_span():
    pd = nested()
    red = trace.reduce_profile(pd, SYN_HLO)
    got = spans.serve_spans(pd)
    split = dict(spans.idle_split(red, got))
    assert split == {"serve.decode": pytest.approx(100e-9),
                     "serve.decode.prep": pytest.approx(60e-9),
                     "no host span": pytest.approx(50e-9),
                     "engine.step": pytest.approx(50e-9),
                     "serve.prefill": pytest.approx(10e-9)}
    under_serve = sum(v for k, v in split.items() if k.startswith("serve."))
    outside = sum(v for k, v in split.items() if k in trace.HOST_SPANS)
    assert under_serve + outside + split["no host span"] == \
        pytest.approx(red.window_s - red.busy_s)
    # the benchmark's own breakdown still sees only its spans
    assert dict(red.breakdown()["idle_gaps"]) == {
        "engine.step": pytest.approx(220e-9),
        "no host span": pytest.approx(50e-9)}


def test_the_three_quantities():
    pd = nested()
    red = trace.reduce_profile(pd, SYN_HLO)
    got = spans.serve_spans(pd)
    # idle inside [80, 210) and [215, 470): 20 + 10 + 55 + 100 + 20 ns,
    # over one decode step
    assert spans.engine_idle_ms(red, got) == pytest.approx(205e-6)
    assert spans.dispatch_ms(red, got) == pytest.approx(15e-6)
    assert spans.admit_ms(red, got) == pytest.approx(130e-6)
    assert spans.stalls(red, got, min_s=100e-9) == [
        [pytest.approx(250e-9), pytest.approx(100e-9), "serve.decode",
         "in paged_step", "paged_step"]]
    assert spans.stalls(red, got) == []
    s = spans.summary(red, got)
    assert s["spans"]["serve.decode"] == {"count": 1, "mean_ms": 255e-6,
                                          "total_s": 255e-9}
    assert s["idle_gaps"][0] == ["serve.decode", pytest.approx(100e-9)]


def test_idle_is_cut_where_spans_open_and_close():
    """Each piece of a gap goes to the innermost span over it; the decode
    module runs [100, 450), so the last gap begins after it."""
    pd = nested()
    red = trace.reduce_profile(pd, SYN_HLO)
    got = {(n, p): v for n, p, v in spans.idle_overlap(
        red, spans.serve_spans(pd))}
    ns = {("serve.prefill", "none"): 18, ("serve.admit", "none"): 2,
          ("serve.prefill", "in paged_step"): 15,
          ("serve.admit", "in paged_step"): 5,
          ("engine.step", "in paged_step"): 5,
          ("serve.decode.prep", "in paged_step"): 25,
          ("serve.decode.dispatch", "in paged_step"): 15,
          ("serve.decode.wait", "in paged_step"): 15,
          ("serve.decode", "in paged_step"): 90,
          ("serve.decode", "after paged_step"): 5,
          ("serve.retire", "after paged_step"): 15,
          ("engine.step", "after paged_step"): 10,
          ("bookkeeping", "after paged_step"): 15}
    assert got == {k: pytest.approx(v * 1e-9) for k, v in ns.items()}
    # all the idle but the 35 ns that no span covers
    assert sum(got.values()) == pytest.approx(red.window_s - red.busy_s
                                              - 35e-9)


def test_spans_outside_the_window_are_not_counted():
    pd = nested()
    red = trace.reduce_profile(pd, SYN_HLO)
    got = spans.serve_spans(pd)
    red.window = (220, 500)
    assert spans.admit_ms(red, got) is None
    assert spans.engine_idle_ms(red, got) is None


@pytest.mark.parametrize("source", [lambda: (synthetic(), SYN_HLO), recorded],
                         ids=["synthetic", "recorded"])
def test_a_trace_without_serve_spans_reads_none(source):
    """A program without spans (the recorded v5e slice, the benchmark's
    synthetic trace) gives no quantity, and its idle split is the
    benchmark's own breakdown."""
    pd, hlo = source()
    red = trace.reduce_profile(pd, hlo)
    got = spans.serve_spans(pd)
    assert got == []
    assert spans.engine_idle_ms(red, got) is None
    assert spans.dispatch_ms(red, got) is None
    assert spans.admit_ms(red, got) is None
    want = dict(red.breakdown(top=100)["idle_gaps"])
    assert dict(spans.idle_split(red, got)) == pytest.approx(want)


def test_capture_keeps_the_spans_of_a_traced_run():
    """The tiny cell traced on the CPU: the engine's spans reach the trace,
    a serve.decode for each decode step of the window, and the wrapped
    functions are put back afterwards."""
    from chipbench import driver
    reduce_dir, drive = trace.reduce_dir, driver.drive
    with spans.capture() as got:
        out = tiny.run(traced=True, seconds=1.0)
    assert (trace.reduce_dir, driver.drive) == (reduce_dir, drive)
    assert out["correct"]
    # a window shorter than the traced part is traced whole
    assert out["device"]["traced_s"] == pytest.approx(1.0, abs=0.1)
    assert out["device"]["window_s"] <= out["device"]["traced_s"]
    d, red = got["drive"], got["trace"]
    names = {s.name for s in got["spans"]}
    assert {"serve.admit", "serve.prefill", "serve.scatter", "serve.decode",
            "serve.decode.prep", "serve.decode.dispatch", "serve.decode.wait",
            "serve.retire"} <= names
    decodes = [s for s in got["spans"] if s.name == spans.DECODE]
    steps = sum(1 for r in d.steps if r.decode_tokens)
    assert len(decodes) >= steps > 0
    assert spans.dispatch_ms(red, got["spans"]) > 0
