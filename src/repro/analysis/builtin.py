"""The built-in nglint rules (NG001–NG010).

Each rule polices one invariant the repro's headline numbers depend on:

====== ===================================================================
NG001  every captured primitive has an explicit taxonomy entry (no silent
       ``OpGroup.OTHER`` fallback — the PR 5 pooling bug class)
NG002  the fusion rewriter leaves no matchable ``FUSION_PATTERNS`` chain
       in a post-rewrite graph
NG003  tagged low-precision sites do not leak f32 intermediates into the
       surrounding dataflow (the interpolate_bilinear bug class)
NG004  quantize→dequantize round-trips feed a GEMM (anything else is
       cancelling overhead the fake-quant transform never intended)
NG005  Pallas kernel specs are sound: fusion patterns name real kernels,
       every kernel takes the ``interpret`` keyword, block shapes are
       positive and partial blocks are handled (pad/clamp)
NG006  no zero-FLOP / zero-byte records (estimator holes in
       ``estimate_flops`` / ``estimate_bytes``)
NG007  scope-tag discipline: every ``ng:`` tag in a captured scope parses
       back to a known operator group
NG008  per-group latency shares stay within tolerance of the committed
       baseline (``benchmarks/analysis_baseline.json``)
NG009  the paged-KV bookkeeping ops (block-table gather / scatter /
       per-slot write) classify as ``OpGroup.MEMORY`` with nonzero
       modeled bytes — the "NonGEMM share of serving" depends on it
NG010  collective primitives in captured shard_map graphs (the manual-TP
       ``nn.tp_psum`` / ``nn.tp_vocab_gather`` sites) classify as
       ``OpGroup.COLLECTIVE`` with nonzero modeled bytes — the
       ``serving_sharded`` COLLECTIVE horizon depends on it
====== ===================================================================

Rules are registered on import (`repro.analysis` imports this module).
"""

from __future__ import annotations

import inspect
from typing import Dict, List, Optional, Sequence, Tuple

from repro.core import fusion as _fusion
from repro.core import taxonomy as _tax
from repro.core.graph import OpRecord
from repro.core.taxonomy import OpGroup, parse_scope

from .rules import AnalysisContext, Finding, rule

#: dtypes whose presence marks a record as low-precision dataflow (NG003)
LOW_PRECISION_DTYPES = frozenset({
    "bfloat16", "float16",
    "float8_e4m3fn", "float8_e5m2", "float8_e4m3b11_fnuz",
    "float8_e4m3", "float8_e5m2fnuz", "float8_e4m3fnuz",
    "float4_e2m1fn",
})

#: structural groups whose ops always do arithmetic — a 0-FLOP record in
#: one of these is an ``estimate_flops`` hole, not a memory op (NG006)
COMPUTE_GROUPS = frozenset({
    OpGroup.GEMM, OpGroup.ELEMENTWISE, OpGroup.ACTIVATION,
    OpGroup.NORMALIZATION, OpGroup.REDUCTION,
})


def _readers(records: Sequence[OpRecord]) -> Dict[int, List[int]]:
    """var id -> stream positions that read it."""
    readers: Dict[int, List[int]] = {}
    for pos, r in enumerate(records):
        for vid in r.in_var_ids:
            readers.setdefault(vid, []).append(pos)
    return readers


def _numel(shape) -> int:
    n = 1
    for d in shape:
        n *= int(d)
    return n


# ---------------------------------------------------------------------------
# NG001 — unknown primitive binned to OTHER
# ---------------------------------------------------------------------------

@rule("NG001", "unknown primitive binned to OpGroup.OTHER",
      severity="error")
def check_unknown_primitives(ctx: AnalysisContext):
    seen: set = set()
    for r in ctx.records:
        if r.group is not OpGroup.OTHER or r.prim in seen:
            continue
        if parse_scope(r.scope) is not None:
            continue  # deliberately tagged ng:other:<site>
        if _tax.is_known_primitive(r.prim):
            continue
        seen.add(r.prim)
        yield Finding(
            rule="NG001", severity="error", workload=ctx.key,
            where=f"{r.prim} @ {r.scope or '<toplevel>'}",
            message=f"primitive {r.prim!r} has no taxonomy entry and fell "
                    "through to OpGroup.OTHER — its latency is untracked "
                    "in every per-group share",
            fix_hint="register it via _reg(...) in repro/core/taxonomy.py "
                     "(see UNKNOWN_PRIMS for occurrence counts)")


# ---------------------------------------------------------------------------
# NG002 — fusable chain left in a post-rewrite graph
# ---------------------------------------------------------------------------

@rule("NG002", "matchable FUSION_PATTERNS chain left unfused",
      severity="error")
def check_unfused_chains(ctx: AnalysisContext):
    if not ctx.fused:
        return  # only a fused variant promises a fully-rewritten stream
    for pattern, chain in _fusion.find_fusable_chains(ctx.rewritten):
        first = chain[0]
        yield Finding(
            rule="NG002", severity="error", workload=ctx.key,
            where=f"{pattern.name} @ {first.scope or '<toplevel>'}",
            message=f"chain of {len(chain)} record(s) matching fusion "
                    f"pattern {pattern.name!r} survived the rewrite "
                    f"(sites: {[s for _, s in pattern.sites]})",
            fix_hint="the FusionTransform pattern list is narrower than "
                     "FUSION_PATTERNS, or fuse_records skipped the match; "
                     "re-run with the full pattern set")


# ---------------------------------------------------------------------------
# NG003 — f32 leaking out of a low-precision tagged site
# ---------------------------------------------------------------------------

@rule("NG003", "f32 intermediate leaks out of a low-precision site",
      severity="warning")
def check_dtype_drift(ctx: AnalysisContext):
    records = ctx.records
    readers = _readers(records)
    reported: set = set()
    for r in records:
        if parse_scope(r.scope) is None:
            continue  # only tagged sites carry the cast-back contract
        if not any(d in LOW_PRECISION_DTYPES for d in r.in_dtypes):
            continue
        for vid, dtype in zip(r.out_var_ids, r.out_dtypes):
            if dtype != "float32":
                continue
            for pos in readers.get(vid, ()):
                c = records[pos]
                if (c.group, c.op_site) == (r.group, r.op_site):
                    continue  # still inside the site
                key = (r.group, r.op_site, c.op_site)
                if key in reported:
                    continue
                reported.add(key)
                yield Finding(
                    rule="NG003", severity="warning", workload=ctx.key,
                    where=f"{r.op_site} -> {c.op_site} @ {r.scope}",
                    message=f"{r.op_site} ({r.group.value}) takes "
                            "low-precision inputs but hands a float32 "
                            f"result to {c.op_site} — the site dropped "
                            "its cast-back and doubles downstream traffic",
                    fix_hint="cast the site's result back to the input "
                             "dtype (the interpolate_bilinear fix in "
                             "repro/nn)")


# ---------------------------------------------------------------------------
# NG004 — cancelling quantize→dequantize round-trips
# ---------------------------------------------------------------------------

@rule("NG004", "quantize->dequantize round-trip feeds no GEMM",
      severity="warning")
def check_cancelling_qdq(ctx: AnalysisContext):
    records = ctx.records
    readers = _readers(records)
    # tagged fake-quant sites: every dequantize run must feed a GEMM
    runs = _fusion._site_runs(records)
    for run in runs:
        if (run.group, run.op_site) != (OpGroup.QUANT, "dequantize"):
            continue
        lo, hi = run.start, run.stop
        outside = sorted({
            pos
            for r in run.records
            for vid in r.out_var_ids
            for pos in readers.get(vid, ())
            if pos < lo or pos >= hi
        })
        if not outside:
            yield Finding(
                rule="NG004", severity="warning", workload=ctx.key,
                where=f"dequantize @ {run.scope}",
                message="dequantize result is never consumed by another "
                        "op — the quantize->dequantize pair is pure "
                        "cancelling overhead",
                fix_hint="drop the fake-quant wrapper at this site or "
                         "feed the dequantized value into the GEMM it "
                         "was meant for")
        elif not any(records[p].group in (OpGroup.GEMM, OpGroup.FUSED)
                     for p in outside):
            consumers = sorted({records[p].op_site for p in outside})
            yield Finding(
                rule="NG004", severity="warning", workload=ctx.key,
                where=f"dequantize @ {run.scope}",
                message="dequantize feeds only non-GEMM consumers "
                        f"({consumers}) — QDQ outside a fake-quant GEMM "
                        "site cancels out and only adds QUANT-group "
                        "latency",
                fix_hint="fake_quant wraps GEMM operands (nn.linear / "
                         "nn.einsum / nn.conv2d); remove stray "
                         "quantize/dequantize calls elsewhere")
    # untagged cast round-trips: convert X->Y feeding only convert Y->X
    for pos, r in enumerate(records):
        if r.prim != "convert_element_type" or not r.in_dtypes:
            continue
        if parse_scope(r.scope) is not None:
            continue  # tagged sites are policed above / by NG003
        src = r.in_dtypes[0]
        for vid in r.out_var_ids:
            consumer_pos = readers.get(vid, ())
            if len(consumer_pos) != 1:
                continue
            c = records[consumer_pos[0]]
            if (c.prim == "convert_element_type" and c.out_dtypes
                    and c.out_dtypes[0] == src
                    and parse_scope(c.scope) is None):
                yield Finding(
                    rule="NG004", severity="warning", workload=ctx.key,
                    where=f"convert_element_type @ {r.scope or '<toplevel>'}",
                    message=f"cast {src} -> {r.out_dtypes[0]} is undone "
                            f"immediately by the only consumer "
                            "(cast back) — a cancelling round-trip",
                    fix_hint="delete both casts or keep the intermediate "
                             "in one dtype")


# ---------------------------------------------------------------------------
# NG005 — Pallas kernel spec soundness (static)
# ---------------------------------------------------------------------------

@rule("NG005", "Pallas kernel spec soundness", severity="error",
      scope="static")
def check_kernel_specs(_ctx: Optional[AnalysisContext]):
    from repro.kernels.ops import KERNEL_SPECS

    # every FUSION_PATTERNS kernel= name must resolve to a real kernel
    for p in _fusion.FUSION_PATTERNS:
        if p.kernel is not None and p.kernel not in KERNEL_SPECS:
            yield Finding(
                rule="NG005", severity="error", workload="static",
                where=f"FUSION_PATTERNS:{p.name}",
                message=f"pattern {p.name!r} claims kernel {p.kernel!r} "
                        "but repro.kernels.ops.KERNEL_SPECS has no such "
                        "entry — the fused record models a launch that "
                        "cannot execute",
                fix_hint="add the kernel to KERNEL_SPECS or fix the "
                         "pattern's kernel= name")
    for name, spec in KERNEL_SPECS.items():
        try:
            sig = inspect.signature(spec.fn)
        except (TypeError, ValueError):
            sig = None
        if sig is not None and "interpret" not in sig.parameters:
            yield Finding(
                rule="NG005", severity="error", workload="static",
                where=f"kernel:{name}",
                message=f"kernel {name!r} does not accept the "
                        "``interpret`` keyword — the pallas_interpret "
                        "backend cannot run it off-TPU, so CPU-only CI "
                        "fails",
                fix_hint="route the entry point through _autojit with "
                         "'interpret' in its static argnames")
        for arg, default in spec.block_defaults.items():
            if int(default) <= 0:
                yield Finding(
                    rule="NG005", severity="error", workload="static",
                    where=f"kernel:{name}",
                    message=f"block default {arg}={default} is not a "
                            "positive block shape",
                    fix_hint="fix the default in the kernel signature / "
                             "KERNEL_SPECS entry")
        if spec.block_defaults and spec.handles_remainder not in (
                "pad", "clamp"):
            yield Finding(
                rule="NG005", severity="error", workload="static",
                where=f"kernel:{name}",
                message=f"kernel {name!r} declares block shapes "
                        f"({sorted(spec.block_defaults)}) but no partial-"
                        "block handling — operand dims that don't divide "
                        "the block will miscompile or read out of bounds",
                fix_hint="pad operands to a block multiple (_pad_rows) "
                         "or clamp the block to the dim (min(block, dim))")
    # every instantiated attention template spec must be registered: an
    # unregistered variant would execute without any of the static vetting
    # above (and without the interpret-keyword contract)
    from repro.kernels import attn_template as _tmpl
    for aspec in _tmpl.instantiated_specs():
        key = _tmpl.kernel_key(aspec)
        if key not in KERNEL_SPECS:
            yield Finding(
                rule="NG005", severity="error", workload="static",
                where=f"attn_template:{aspec.name}",
                message=f"attention spec {aspec.name!r} (mask="
                        f"{aspec.mask!r}) was instantiated but is missing "
                        "from repro.kernels.ops.KERNEL_SPECS — the "
                        "generated variant escapes static vetting",
                fix_hint="instantiate via attn_template.make_attention("
                         "spec) with register=True (the default), or "
                         "register_template_kernel by hand")


# ---------------------------------------------------------------------------
# NG006 — zero-FLOP / zero-byte records (estimator holes)
# ---------------------------------------------------------------------------

@rule("NG006", "zero-FLOP / zero-byte record (estimator hole)",
      severity="warning")
def check_estimator_holes(ctx: AnalysisContext):
    seen: set = set()
    for r in ctx.rewritten:
        out_numel = sum(_numel(s) for s in r.out_shapes)
        if out_numel == 0:
            continue  # produces nothing (e.g. a zero-width slice):
            # zero bytes / zero flops is the correct estimate
        structural = _tax.lookup_primitive(r.prim)
        hole = None
        if r.bytes_accessed <= 0.0:
            hole = "bytes_accessed == 0"
        elif structural in COMPUTE_GROUPS and r.flops <= 0.0:
            hole = f"flops == 0 for a {structural.value} primitive"
        if hole is None or (r.prim, hole) in seen:
            continue
        seen.add((r.prim, hole))
        yield Finding(
            rule="NG006", severity="warning", workload=ctx.key,
            where=f"{r.prim} @ {r.scope or '<toplevel>'}",
            message=f"record {r.index} ({r.prim}, "
                    f"{r.group.value}): {hole} — the roofline model "
                    "assigns this op no cost, so its latency vanishes "
                    "from every share",
            fix_hint="extend estimate_flops / estimate_bytes in "
                     "repro/core/graph.py to cover this primitive")


# ---------------------------------------------------------------------------
# NG007 — scope-tag discipline
# ---------------------------------------------------------------------------

@rule("NG007", "unresolvable ng: scope tag", severity="error")
def check_scope_tags(ctx: AnalysisContext):
    seen: set = set()
    for r in ctx.records:
        if "ng:" not in r.scope or parse_scope(r.scope) is not None:
            continue
        if r.scope in seen:
            continue
        seen.add(r.scope)
        yield Finding(
            rule="NG007", severity="error", workload=ctx.key,
            where=r.scope,
            message="scope carries an ng: tag the taxonomy cannot parse "
                    "— the record silently falls back to primitive "
                    "classification and the site's latency scatters "
                    "across structural groups",
            fix_hint="emit tags via taxonomy.scope_tag(group, name) "
                     "(group must be an OpGroup value, name "
                     "[A-Za-z0-9_.-]+)")


# ---------------------------------------------------------------------------
# NG008 — per-group share drift vs the committed baseline
# ---------------------------------------------------------------------------

@rule("NG008", "per-group share drift vs committed baseline",
      severity="warning")
def check_share_drift(ctx: AnalysisContext):
    if not ctx.baseline_shares:
        return  # no committed entry for this workload/variant yet
    tol = ctx.share_tolerance
    groups = set(ctx.group_shares) | set(ctx.baseline_shares)
    for g in sorted(groups):
        new = ctx.group_shares.get(g, 0.0)
        old = ctx.baseline_shares.get(g, 0.0)
        if abs(new - old) <= tol:
            continue
        yield Finding(
            rule="NG008", severity="warning", workload=ctx.key,
            where=f"group:{g}",
            message=f"modeled {g} share moved {old:.1%} -> {new:.1%} "
                    f"(|Δ| {abs(new - old):.1%} > tolerance {tol:.1%}) "
                    "vs benchmarks/analysis_baseline.json",
            fix_hint="if intentional, regenerate the baseline with "
                     "`python -m repro.analyze --all --write-baseline`")


# ---------------------------------------------------------------------------
# NG009 — paged-KV bookkeeping ops land in MEMORY with nonzero bytes (static)
# ---------------------------------------------------------------------------

@rule("NG009", "paged-KV bookkeeping ops classify as MEMORY with bytes",
      severity="error", scope="static")
def check_paged_kv_ops(_ctx: Optional[AnalysisContext]):
    """Captures tiny programs over the paged serving ops and asserts every
    tagged record lands in ``OpGroup.MEMORY`` with modeled bytes > 0 — if
    the block-table gather/scatter bookkeeping ever falls out of MEMORY
    (or models zero traffic), the traffic section's "NonGEMM share of
    serving" silently underreports."""
    import jax.numpy as jnp

    from repro import nn
    from repro.core.graph import capture

    pool = jnp.zeros((4, 2, 3), jnp.float32)      # (blocks, block_size, d)
    table = jnp.array([[1, 2]], jnp.int32)        # one sequence, two blocks
    row = jnp.array([1, 2], jnp.int32)
    sites = (
        # max_len is a static python int (slice bound), so it is closed
        # over rather than traced by capture's make_jaxpr
        ("paged_kv_gather", lambda p, t: nn.paged_kv_gather(p, t, 4),
         (pool, table)),
        ("paged_kv_write", nn.paged_kv_write,
         (pool, jnp.ones((1, 1, 3), jnp.float32), table,
          jnp.array([1], jnp.int32))),
        ("paged_kv_scatter", nn.paged_kv_scatter,
         (pool, jnp.ones((2, 3), jnp.float32), row,
          jnp.int32(0), jnp.int32(0), jnp.int32(2))),
    )
    for site, fn, args in sites:
        tagged = [r for r in capture(fn, *args) if r.op_site == site]
        where = f"nn.{site}"
        if not tagged:
            yield Finding(
                rule="NG009", severity="error", workload="static",
                where=where,
                message=f"no captured record carries op_site {site!r} — "
                        "the op lost its taxonomy tag and its latency "
                        "scatters across structural groups",
                fix_hint="keep the @tagged(OpGroup.MEMORY, ...) decorator "
                         "on the op in repro/nn")
            continue
        off_group = sorted({r.prim for r in tagged
                            if r.group is not OpGroup.MEMORY})
        if off_group:
            yield Finding(
                rule="NG009", severity="error", workload="static",
                where=where,
                message=f"record(s) {off_group} inside the {site!r} site "
                        "classify outside OpGroup.MEMORY — paged "
                        "bookkeeping must be attributed to MEMORY for the "
                        "serving NonGEMM share",
                fix_hint="tag the op with OpGroup.MEMORY (repro/nn) and "
                         "keep its primitives in _PRIM_GROUPS' MEMORY set")
        if sum(r.bytes_accessed for r in tagged) <= 0.0:
            yield Finding(
                rule="NG009", severity="error", workload="static",
                where=where,
                message=f"{site!r} records model zero bytes_accessed — "
                        "the gather/scatter traffic vanishes from every "
                        "roofline and share",
                fix_hint="extend estimate_bytes in repro/core/graph.py "
                         "for the slicing/scatter primitives involved")


# ---------------------------------------------------------------------------
# NG010 — manual-TP collectives land in COLLECTIVE with nonzero bytes (static)
# ---------------------------------------------------------------------------

@rule("NG010", "manual-TP collectives classify as COLLECTIVE with bytes",
      severity="error", scope="static")
def check_tp_collectives(_ctx: Optional[AnalysisContext]):
    """Captures a tiny shard_map program over the manual-TP collective
    sites (a 1-device mesh suffices: ``psum`` / ``all_gather`` bind in the
    traced jaxpr regardless of axis size) and asserts every collective
    record classifies as ``OpGroup.COLLECTIVE`` with modeled bytes > 0 —
    if the per-block all-reduces of a tensor-parallel decode fall out of
    COLLECTIVE (or model zero link traffic), the ``serving_sharded``
    section's COLLECTIVE share silently flatlines."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from repro import nn, sharding
    from repro.core.graph import capture
    from repro.core.taxonomy import COLLECTIVE_PRIMS
    from repro.launch.mesh import make_sim_mesh

    mesh = make_sim_mesh(1, 1)

    def body(x, w):
        with sharding.manual_axis("model", vocab_sharded=True):
            y = nn.linear(x, w)
            y = nn.tp_psum(y)        # row-sharded partial-sum reduction
            return nn.tp_vocab_gather(y)   # vocab-sharded logit gather

    fn = jax.shard_map(body, mesh=mesh, in_specs=(P(), P()), out_specs=P(),
                       check_vma=False)
    records = capture(fn, jnp.ones((2, 8), jnp.float32),
                      jnp.ones((8, 8), jnp.float32))

    for site in ("psum", "all_gather"):
        tagged = [r for r in records if r.op_site == site]
        where = f"nn.tp_{'vocab_gather' if site == 'all_gather' else site}"
        if not tagged:
            yield Finding(
                rule="NG010", severity="error", workload="static",
                where=where,
                message=f"no captured record carries op_site {site!r} — "
                        "the collective site emitted nothing inside a "
                        "manual_axis context, so TP traces carry no "
                        "COLLECTIVE records",
                fix_hint="keep the ng:collective scope_tag and the "
                         "jax.lax collective call in the nn site")
            continue
        off_group = sorted({r.prim for r in tagged
                            if r.group is not OpGroup.COLLECTIVE})
        if off_group:
            yield Finding(
                rule="NG010", severity="error", workload="static",
                where=where,
                message=f"record(s) {off_group} inside the {site!r} site "
                        "classify outside OpGroup.COLLECTIVE — TP "
                        "all-reduce latency would be billed to HBM "
                        "instead of link_bw",
                fix_hint="tag the site OpGroup.COLLECTIVE and keep its "
                         "primitives in taxonomy's COLLECTIVE set")
        if sum(r.bytes_accessed for r in tagged) <= 0.0:
            yield Finding(
                rule="NG010", severity="error", workload="static",
                where=where,
                message=f"{site!r} records model zero bytes_accessed — "
                        "the collective's link traffic vanishes from the "
                        "roofline and the COLLECTIVE share",
                fix_hint="extend estimate_bytes in repro/core/graph.py "
                         "for the collective primitives involved")
    untagged = sorted({r.prim for r in records
                       if r.prim in COLLECTIVE_PRIMS
                       and r.group is not OpGroup.COLLECTIVE})
    if untagged:
        yield Finding(
            rule="NG010", severity="error", workload="static",
            where="shard_map capture",
            message=f"collective primitive(s) {untagged} classify outside "
                    "OpGroup.COLLECTIVE in a captured shard_map graph",
            fix_hint="keep every collective primitive registered under "
                     "OpGroup.COLLECTIVE in repro/core/taxonomy.py")


#: Mapping rule id -> short description, for docs / --list-rules
def rule_catalog() -> List[Tuple[str, str, str]]:
    from .rules import all_rules

    return [(r.id, r.severity, r.title) for r in all_rules()]
