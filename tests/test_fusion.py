"""Operator-fusion subsystem (paper §6): rewriter pattern semantics,
fused-vs-unfused numerical parity across the quick-tier archs (including
the QDQ-composed 2×2 and the serving engine's decode step), the modeled
direction (fused latency and NonGEMM share strictly lower), and the
compare-gate invariant."""

import copy

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import nn
from repro.core import (NONGEMM_GROUPS, FusionTransform, OpGroup,
                        QuantizeDequantTransform, Workload, capture,
                        fuse_records, parse_scope, scope_tag)
from repro.core.fusion import FUSED_PRIM, FusionPattern, scope_prefix

W64 = jnp.ones((64,), jnp.float32)


def fired(fn, *args):
    _, report = fuse_records(capture(fn, *args))
    return report


# ---------------------------------------------------------------------------
# taxonomy
# ---------------------------------------------------------------------------

def test_fused_group_is_nongemm():
    assert OpGroup.FUSED in NONGEMM_GROUPS
    assert parse_scope(scope_tag(OpGroup.FUSED, "fused_add_rms_norm")) == \
        (OpGroup.FUSED, "fused_add_rms_norm")


def test_scope_prefix():
    assert scope_prefix("ng:elementwise:residual_add") == ""
    # normalized (no trailing slash): a tagged run and an untagged
    # neighbor in the same user scope must compare equal
    assert scope_prefix("layer0/ng:normalization:rms_norm") == "layer0"
    assert scope_prefix("layer0") == "layer0"
    assert scope_prefix("untagged/argmax") == "untagged/argmax"


# ---------------------------------------------------------------------------
# rewriter: each pattern fires on its synthetic chain
# ---------------------------------------------------------------------------

def test_add_rms_norm_chain_fuses():
    def f(x, r):
        return nn.rms_norm(nn.residual_add(x, r), W64)

    rep = fired(f, jnp.ones((4, 64)), jnp.ones((4, 64)))
    assert rep.fired.get("fused_add_rms_norm") == 1
    assert rep.records_after < rep.records_before
    assert rep.bytes_after < rep.bytes_before


def test_add_layer_norm_chain_fuses():
    def f(x, r):
        return nn.layer_norm(nn.residual_add(x, r), W64, W64)

    rep = fired(f, jnp.ones((4, 64)), jnp.ones((4, 64)))
    assert rep.fired.get("fused_add_layer_norm") == 1


def test_dequant_add_rms_norm_chain_fuses():
    def f(q, s, r):
        x = nn.dequantize_int8(q, s)
        return nn.rms_norm(nn.residual_add(x, r), W64)

    q = jnp.ones((4, 64), jnp.int8)
    rep = fired(f, q, jnp.float32(0.1), jnp.ones((4, 64)))
    assert rep.fired.get("fused_dequant_add_rms_norm") == 1


def test_qdq_roundtrip_fuses():
    def f(x):
        return nn.fake_quant_int8(x)

    rep = fired(f, jnp.ones((4, 64)))
    assert rep.fired.get("fused_qdq") == 1


def test_silu_mul_fuses():
    def f(g, u):
        return nn.silu(g) * u

    rep = fired(f, jnp.ones((4, 64)), jnp.ones((4, 64)))
    assert rep.fired.get("fused_swiglu") == 1


def test_softmax_sample_chain_fuses():
    def f(x):
        return jnp.argmax(nn.softmax(x, axis=-1), axis=-1)

    rep = fired(f, jnp.ones((4, 64)))
    assert rep.fired.get("fused_softmax_sample") == 1


def test_rope_site_collapses():
    def f(x):
        return nn.apply_rope(x, jnp.arange(8)[None, :])

    rep = fired(f, jnp.ones((1, 8, 4, 64)))
    assert rep.fired.get("fused_rope") == 1


def test_swiglu_site_collapses():
    rep = fired(nn.swiglu, jnp.ones((4, 64)), jnp.ones((4, 64)))
    assert rep.fired.get("fused_swiglu") == 1


def test_adjacent_invocations_stay_separate_launches():
    # rope on q then on k, back to back under the same scope, must fuse
    # into TWO records (two launches), not be merged into one site run
    pos = jnp.arange(8)[None, :]

    def f(q, k):
        return nn.apply_rope(q, pos), nn.apply_rope(k, pos)

    rep = fired(f, jnp.ones((1, 8, 4, 64)), jnp.ones((1, 8, 4, 64)))
    assert rep.fired.get("fused_rope") == 2


# ---------------------------------------------------------------------------
# rewriter: refusal rules
# ---------------------------------------------------------------------------

def test_no_fusion_across_scope_boundary():
    def f(x, r):
        with jax.named_scope("stage0"):
            y = nn.residual_add(x, r)
        with jax.named_scope("stage1"):
            return nn.rms_norm(y, W64)

    rep = fired(f, jnp.ones((4, 64)), jnp.ones((4, 64)))
    assert "fused_add_rms_norm" not in rep.fired


def test_no_fusion_without_dataflow():
    # adjacent add and norm on UNRELATED tensors of different shapes:
    # the chain pattern must not fire (the norm site may still collapse)
    def f(x, r, z):
        return nn.residual_add(x, r), nn.rms_norm(z, jnp.ones((32,)))

    rep = fired(f, jnp.ones((4, 64)), jnp.ones((4, 64)), jnp.ones((8, 32)))
    assert "fused_add_rms_norm" not in rep.fired


def test_no_fusion_without_dataflow_same_shapes():
    # MHA qk-norm stack: norm(q), norm(k), rope(q), rope(k). The adjacent
    # norm(k) -> rope(q) pair has IDENTICAL shapes but no dataflow — the
    # var-identity check must refuse the chain (sites still collapse)
    pos = jnp.arange(8)[None, :]

    def f(q, k):
        qn = nn.rms_norm(q, W64)
        kn = nn.rms_norm(k, W64)
        return nn.apply_rope(qn, pos), nn.apply_rope(kn, pos)

    rep = fired(f, jnp.ones((1, 8, 4, 64)), jnp.ones((1, 8, 4, 64)))
    assert "fused_rms_norm_rope" not in rep.fired
    assert rep.fired.get("fused_rope") == 2
    assert rep.fired.get("fused_rms_norm") == 2


def test_tagged_untagged_chain_fuses_inside_named_scope():
    # the softmax (tagged) -> argmax (untagged) chain must fuse even when
    # both live inside a user scope (prefix normalization)
    def f(x):
        with jax.named_scope("sampler"):
            return jnp.argmax(nn.softmax(x, axis=-1), axis=-1)

    rep = fired(f, jnp.ones((4, 64)))
    assert rep.fired.get("fused_softmax_sample") == 1


def test_single_record_site_not_relabeled():
    # residual_add alone is one primitive — nothing to collapse
    rep = fired(nn.residual_add, jnp.ones((4, 64)), jnp.ones((4, 64)))
    assert rep.fired == {} and rep.records_after == rep.records_before


def test_empty_pattern_rejected():
    with pytest.raises(ValueError):
        FusionPattern("empty", ())


def test_live_intermediate_still_written():
    # the residual stream r = x + res is consumed downstream of the fused
    # chain, so the fused kernel must still write it to HBM: the fused
    # record's bytes must exceed the dead-intermediate version's
    def dead(x, r):
        return nn.rms_norm(nn.residual_add(x, r), W64)

    def alive(x, r):
        s = nn.residual_add(x, r)
        return nn.rms_norm(s, W64), s * 2.0

    args = (jnp.ones((4, 64)), jnp.ones((4, 64)))
    recs_d, rep_d = fuse_records(capture(dead, *args))
    recs_a, rep_a = fuse_records(capture(alive, *args))
    assert rep_d.fired.get("fused_add_rms_norm") == 1
    assert rep_a.fired.get("fused_add_rms_norm") == 1
    bytes_d = next(r for r in recs_d if r.group == OpGroup.FUSED)
    bytes_a = next(r for r in recs_a if r.group == OpGroup.FUSED)
    # live version pays exactly one extra (4, 64) f32 write
    assert bytes_a.bytes_accessed == bytes_d.bytes_accessed + 4 * 64 * 4


def test_fused_record_shape():
    def f(x, r):
        return nn.rms_norm(nn.residual_add(x, r), W64)

    recs, _ = fuse_records(capture(f, jnp.ones((4, 64)), jnp.ones((4, 64))))
    (rec,) = [r for r in recs if r.group == OpGroup.FUSED]
    assert rec.prim == FUSED_PRIM
    assert rec.op_site == "fused_add_rms_norm"
    assert rec.params["fused_sites"] == ["residual_add", "rms_norm"]
    assert rec.params["kernel"] == "fused_add_rms_norm"
    assert rec.out_shapes == ((4, 64),)


def test_executed_fused_site_collapses_to_one_launch():
    def f(x, r):
        with nn.fuse():
            return nn.add_rms_norm(x, r, W64)[0]

    recs = capture(f, jnp.ones((4, 64)), jnp.ones((4, 64)))
    assert {r.group for r in recs} == {OpGroup.FUSED}
    fused, rep = fuse_records(recs)
    assert len(fused) == 1 and rep.fired.get("fused_add_rms_norm") == 1


# ---------------------------------------------------------------------------
# execution parity: fused == unfused numerically
# ---------------------------------------------------------------------------

QUICK_ARCHS = ("gpt2-xl", "llama2-7b", "bert-base", "stablelm-3b")


@pytest.mark.parametrize("arch", QUICK_ARCHS)
def test_fused_matches_unfused(arch):
    w = Workload(name=arch, arch=arch, batch=1, seq=8)
    fn, args = w.build()
    fn_f, args_f = w.with_transform(FusionTransform()).build()
    a = jax.jit(fn)(*args)
    b = jax.jit(fn_f)(*args_f)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               atol=1e-4, rtol=1e-4)


def test_fused_matches_unfused_qdq_composed():
    w = Workload(name="q", arch="llama2-7b", batch=1, seq=8)
    q = w.with_transform(QuantizeDequantTransform("int8"))
    qf = q.with_transform(FusionTransform())
    assert qf.variant == "int8-qdq+fused"
    fn, args = q.build()
    fn_f, args_f = qf.build()
    np.testing.assert_allclose(np.asarray(jax.jit(fn)(*args)),
                               np.asarray(jax.jit(fn_f)(*args_f)),
                               atol=1e-4, rtol=1e-4)


def test_fused_kernel_path_matches_jnp(rng=jax.random.PRNGKey(0)):
    x = jax.random.normal(rng, (3, 64))
    r = jax.random.normal(jax.random.PRNGKey(1), (3, 64))
    with nn.fuse():
        want = nn.add_rms_norm(x, r, W64)
        with nn.backend("pallas_interpret"):
            got = nn.add_rms_norm(x, r, W64)
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), atol=1e-5)


# ---------------------------------------------------------------------------
# modeled direction: fused strictly faster, NonGEMM share strictly lower
# ---------------------------------------------------------------------------

def test_modeled_fusion_direction():
    w = Workload(name="d", arch="llama2-7b", batch=1, seq=8)
    p = w.profile("eager-modeled:a100")
    pf = w.with_transform(FusionTransform()).profile("eager-modeled:a100")
    assert pf.total_seconds < p.total_seconds
    assert pf.split["nongemm_frac"] < p.split["nongemm_frac"]
    assert pf.group_seconds.get("fused", 0.0) > 0.0
    assert pf.n_ops < p.n_ops


def test_eager_cpu_backend_attributes_executed_fusion():
    # measured backends don't rewrite timings; the fused attribution there
    # comes from the executed ng:fused: scopes instead
    def builder(w):
        x = jnp.ones((2, 64))
        r = jnp.ones((2, 64))
        return (lambda p, x, r: nn.add_rms_norm(x, r, p)[0]), (x, r), W64

    w = Workload(name="d", arch="tiny", builder=builder)
    p = w.profile("eager-cpu", repeats=1)
    pf = w.with_transform(FusionTransform()).profile("eager-cpu", repeats=1)
    assert p.group_seconds.get("fused", 0.0) == 0.0
    assert pf.group_seconds.get("fused", 0.0) > 0.0


# ---------------------------------------------------------------------------
# serving engine decode parity
# ---------------------------------------------------------------------------

def test_engine_fused_decode_matches_unfused():
    from repro.configs import get_config, reduced
    from repro.models import init_lm
    from repro.serving import Engine

    cfg = reduced(get_config("stablelm-3b")).replace(n_layers=2)
    params = init_lm(jax.random.PRNGKey(0), cfg)
    prompts = [[5, 7, 11], [13, 17, 19, 23, 29], [31, 37]]

    outs = []
    for fused in (False, True):
        eng = Engine(cfg, params, max_batch=2, max_len=32, fused=fused)
        for p in prompts:
            eng.add_request(list(p), max_new_tokens=6)
        done = sorted(eng.run(), key=lambda r: r.uid)
        outs.append([r.output for r in done])
    assert outs[0] == outs[1]


# ---------------------------------------------------------------------------
# kernels/ops interpret contract: interpret mode only when asked for
# ---------------------------------------------------------------------------

def test_default_interpret_env_override():
    # no environment knob and no auto-detection: every public kernel
    # defaults to the Mosaic kernel
    import inspect

    from repro.kernels import ops

    for name, spec in ops.KERNEL_SPECS.items():
        param = inspect.signature(spec.fn).parameters["interpret"]
        assert param.default is False, name
    x = jax.random.normal(jax.random.PRNGKey(0), (8, 64))
    with pytest.raises(ValueError, match="interpret"):
        ops.rms_norm(x, W64)       # Mosaic cannot lower for the CPU


def test_pallas_backend_runs_without_tpu():
    # off-TPU the kernels run only under the explicit interpret backend
    x = jax.random.normal(jax.random.PRNGKey(0), (4, 64))
    with nn.backend("pallas_interpret"):
        assert nn.kernel_interpret() is True
        got = nn.rms_norm(x, W64)
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(nn.rms_norm(x, W64)), atol=1e-5)


def test_pallas_backend_off_tpu_raises():
    # "pallas" means real Mosaic kernels: without a TPU it refuses and
    # names the interpret backend instead of quietly interpreting
    assert jax.default_backend() != "tpu"
    x = jax.random.normal(jax.random.PRNGKey(0), (4, 64))
    with nn.backend("pallas"):
        with pytest.raises(RuntimeError, match="pallas_interpret"):
            nn.rms_norm(x, W64)


# ---------------------------------------------------------------------------
# microbench registration
# ---------------------------------------------------------------------------

def test_fused_micro_ops_registered():
    from repro.core.microbench import TABLE2_SHAPES, registry

    reg = registry()
    for name in ("add_rms_norm", "fused_add_rms_norm", "fused_rope",
                 "fused_dequant_add_rms_norm"):
        assert name in reg and name in TABLE2_SHAPES
    assert reg["fused_add_rms_norm"].group == OpGroup.FUSED
    assert reg["add_rms_norm"].group == OpGroup.NORMALIZATION


# ---------------------------------------------------------------------------
# compare gate: the §6 invariant on candidate artifacts
# ---------------------------------------------------------------------------

def _fusion_artifact(rows):
    from repro.bench.schema import BenchCase, BenchResult, SectionResult

    return BenchResult(
        tier="quick", backend="cpu", jax_version="0.4.37",
        cases=[BenchCase("gpt2-xl b-1", "gpt2-xl", 1, 16)],
        sections=[SectionResult(name="fusion", title="§6", status="ok",
                                wall_s=1.0, rows=rows)])


def _fusion_rows(fused_total=0.7, fused_ng=0.25):
    def row(variant, total, ng):
        return {"case": "gpt2-xl b-1", "mode": "eager_a100",
                "variant": variant, "total_s": total, "gemm_frac": 1.0 - ng,
                "nongemm_frac": ng, "group_fracs": {}, "fused_frac": 0.1,
                "n_ops": 10}

    return [row("fp32", 1.0, 0.4), row("fused", fused_total, fused_ng)]


def _regressions(old, new):
    from repro.bench.compare import compare_artifacts

    return [f for f in compare_artifacts(old, new)
            if f.severity == "regression"]


def test_compare_fusion_invariant_passes():
    a = _fusion_artifact(_fusion_rows())
    assert _regressions(a, copy.deepcopy(a)) == []


def test_compare_fusion_latency_regression():
    old = _fusion_artifact(_fusion_rows())
    new = _fusion_artifact(_fusion_rows(fused_total=1.2))
    found = _regressions(old, new)
    assert any("total modeled latency" in f.message for f in found)


def test_compare_fusion_share_regression():
    old = _fusion_artifact(_fusion_rows())
    new = _fusion_artifact(_fusion_rows(fused_ng=0.45))
    found = _regressions(old, new)
    assert any("NonGEMM share" in f.message for f in found)


def test_compare_fusion_residual_floor():
    old = _fusion_artifact(_fusion_rows())
    new = _fusion_artifact(_fusion_rows(fused_ng=0.05))
    found = _regressions(old, new)
    assert any("residual bottleneck" in f.message for f in found)


def test_fusion_rows_validate_against_schema():
    from repro.bench.schema import validate_artifact

    a = _fusion_artifact(_fusion_rows())
    assert validate_artifact(a.to_dict()) == []


def test_summary_markdown_includes_fusion_table():
    from repro.bench.compare import compare_artifacts, render_summary_markdown

    a = _fusion_artifact(_fusion_rows())
    findings = compare_artifacts(a, copy.deepcopy(a))
    md = render_summary_markdown(a, a, findings)
    assert "### fusion" in md
    assert "| gpt2-xl b-1 | eager_a100 | fp32 " in md
    assert "| gpt2-xl b-1 | eager_a100 | fused " in md
