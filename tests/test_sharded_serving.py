"""Mesh-sharded paged serving: TP plan unit tests + subprocess parity.

Fast tests exercise the manual-TP plan (``repro.models.tp``), the
simulated-mesh constructor, and the ``serving_sharded`` invariant checker
in-process on the real 1-device topology. The parity tests (marked
``slow`` + ``multidevice``) run ``scripts/sharded_serving_check.py`` in a
subprocess that pins an 8-virtual-device topology before importing jax.
"""

import numpy as np
import pytest

from repro.bench.cases import sharded_serving_config
from repro.bench.schema import check_sharded_invariant
from repro.launch.mesh import make_sim_mesh
from repro.models import tp as tp_mod

CFG = sharded_serving_config("stablelm-3b")


# ---------------------------------------------------------------- sim mesh

def test_make_sim_mesh_single_device_ok():
    mesh = make_sim_mesh(1, 1)
    assert tp_mod.mesh_tp(mesh) == 1
    assert mesh.axis_names == ("data", "model")


def test_make_sim_mesh_too_many_devices_names_the_knob():
    with pytest.raises(RuntimeError) as e:
        make_sim_mesh(1, 1 + len(_devices()))
    msg = str(e.value)
    assert "--xla_force_host_platform_device_count" in msg
    assert "XLA_FLAGS" in msg


def test_make_sim_mesh_rejects_degenerate_axes():
    with pytest.raises(ValueError):
        make_sim_mesh(0, 1)


def _devices():
    import jax
    return jax.devices()


def test_mesh_tp_none_is_one():
    assert tp_mod.mesh_tp(None) == 1


# ------------------------------------------------------------- validate_tp

def test_validate_tp_accepts_divisible_config():
    tp_mod.validate_tp(CFG, 2)
    tp_mod.validate_tp(CFG, 8)


def test_validate_tp_rejects_indivisible_heads():
    with pytest.raises(ValueError, match="n_heads"):
        tp_mod.validate_tp(CFG, 3)


def test_validate_tp_rejects_indivisible_ffn():
    cfg = CFG.replace(n_heads=16, d_ff=CFG.d_ff + 8)
    with pytest.raises(ValueError, match="d_ff"):
        tp_mod.validate_tp(cfg, 16)


def test_validate_tp_rejects_moe():
    cfg = CFG.replace(n_experts=4, top_k=2)
    with pytest.raises(ValueError, match="MoE"):
        tp_mod.validate_tp(cfg, 2)


def test_validate_tp_rejects_ffn_bias():
    cfg = CFG.replace(ffn_bias=True)
    with pytest.raises(ValueError, match="bias"):
        tp_mod.validate_tp(cfg, 2)


def test_validate_tp_rejects_broken_gqa_fallback():
    # 3 kv heads: tp=2 neither divides kv heads nor lets 3 divide the
    # 4 per-device query heads
    cfg = CFG.replace(n_kv_heads=3, n_heads=8)
    with pytest.raises(ValueError, match="GQA"):
        tp_mod.validate_tp(cfg, 2)


def test_tp_local_config_shards_heads_kv_ffn_and_pins_head_dim():
    local = tp_mod.tp_local_config(CFG, 4)
    assert local.n_heads == CFG.n_heads // 4
    assert local.n_kv_heads == CFG.n_kv_heads // 4
    assert local.d_ff == CFG.d_ff // 4
    assert local.resolved_head_dim == CFG.resolved_head_dim


def test_tp_local_config_keeps_the_scalar_multipliers():
    from repro.configs import get_config

    cfg = get_config("granite-3-8b")
    local = tp_mod.tp_local_config(cfg, 4)
    for f in ("embedding_multiplier", "attention_multiplier",
              "residual_multiplier", "logits_scaling", "norm_eps"):
        assert getattr(local, f) == getattr(cfg, f) is not None, f
    assert local.n_heads == 8 and local.resolved_head_dim == 128


def test_tp_local_config_gqa_fallback_keeps_kv_heads():
    # 2 kv heads, tp=4: kv stays replicated, 2 divides the 2 local heads
    cfg = CFG.replace(n_kv_heads=2)
    local = tp_mod.tp_local_config(cfg, 4)
    assert local.n_kv_heads == 2
    assert local.n_heads == 2


# ------------------------------------------------------------- spec trees

def _specs_of(tree, tp):
    return tp_mod.tp_param_specs(tree, CFG, tp)


def test_tp_param_specs_plan():
    tree = {
        "wq": np.zeros((256, 8, 32)),
        "wo": np.zeros((8, 32, 256)),
        "w_up": np.zeros((256, 1024)),
        "w_down": np.zeros((1024, 256)),
        "wk": np.zeros((256, 8, 32)),
        "head": np.zeros((256, 512)),
        "embed": np.zeros((512, 256)),
        "scale": np.zeros((256,)),
    }
    specs = _specs_of(tree, 2)
    assert specs["wq"][-1] == "model"            # column (heads)
    assert specs["wo"][-2] == "model"            # row -> psum
    assert specs["wo"][-1] is None
    assert specs["w_up"][-1] == "model"
    assert specs["w_down"][-2] == "model"
    assert specs["wk"][-1] == "model"            # tp | n_kv_heads here
    assert specs["head"][-1] == "model"          # untied, tp | vocab
    assert all(e is None for e in specs["embed"])
    assert all(e is None for e in specs["scale"])


def test_tp_param_specs_gqa_fallback_replicates_kv():
    cfg = CFG.replace(n_kv_heads=2)
    specs = tp_mod.tp_param_specs({"wk": np.zeros((256, 2, 32))}, cfg, 4)
    assert all(e is None for e in specs["wk"])


def test_tp_param_specs_stacked_blocks_shard_trailing_dims():
    # lax.scan-stacked leaf: leading layer dim must stay unsharded
    specs = _specs_of({"wo": np.zeros((4, 8, 32, 256))}, 2)
    assert specs["wo"][0] is None
    assert specs["wo"][-2] == "model"


def test_tp_param_specs_tp1_replicates_everything():
    specs = _specs_of({"wq": np.zeros((256, 8, 32))}, 1)
    assert all(e is None for e in specs["wq"])


def test_tp_cache_specs_shard_head_dim_iff_kv_sharded():
    pools = {"k": np.zeros((32, 8, 8, 32)), "v": np.zeros((32, 8, 8, 32))}
    sharded = tp_mod.tp_cache_specs(pools, CFG, 2)
    assert sharded["k"][-2] == "model" and sharded["k"][0] is None
    fallback = tp_mod.tp_cache_specs(pools, CFG.replace(n_kv_heads=2), 4)
    assert all(e is None for e in fallback["k"])


# ------------------------------------------------- serving_sharded gate

def _rows(overrides=None):
    eff = {1: 1.0, 2: 0.92, 4: 0.84, 8: 0.7}
    coll = {1: 0.0, 2: 0.06, 4: 0.11, 8: 0.18}
    rows = []
    for tp in (1, 2, 4, 8):
        rows.append({
            "case": "c", "tp": tp, "devices": tp,
            "decode_tok_per_s": 100.0, "per_device_tok_per_s": 100.0 / tp,
            "modeled_step_s": 1e-4, "modeled_eff": eff[tp],
            "collective_frac": coll[tp], "parity_ok": True,
        })
    for tp, kv in (overrides or {}).items():
        rows[[1, 2, 4, 8].index(tp)].update(kv)
    return rows


def test_sharded_invariant_good_rows_pass():
    assert check_sharded_invariant(_rows()) == []


def test_sharded_invariant_missing_degree():
    assert check_sharded_invariant(_rows()[:-1])


def test_sharded_invariant_parity_failure():
    assert check_sharded_invariant(_rows({8: {"parity_ok": False}}))


def test_sharded_invariant_collective_must_be_zero_at_tp1():
    assert check_sharded_invariant(_rows({1: {"collective_frac": 0.01}}))


def test_sharded_invariant_collective_must_grow():
    assert check_sharded_invariant(_rows({4: {"collective_frac": 0.06}}))


def test_sharded_invariant_efficiency_band():
    assert check_sharded_invariant(_rows({8: {"modeled_eff": 0.3}}))
    assert check_sharded_invariant(_rows({2: {"modeled_eff": 1.2}}))


# ------------------------------------------- subprocess parity (8 devices)

@pytest.mark.slow
@pytest.mark.multidevice
def test_sharded_decode_parity(eight_devices):
    out = eight_devices("sharded_serving_check.py", "parity_decode")
    assert "parity_decode OK" in out


@pytest.mark.multidevice
def test_sharded_paged_kernel_parity(eight_devices):
    """TP 2 and 4 with head-sharded pools take the paged decode kernel
    inside shard_map and serve what the gathered path serves."""
    out = eight_devices("sharded_serving_check.py", "parity_paged_kernel")
    assert "parity_paged_kernel OK" in out


@pytest.mark.multidevice
def test_sharded_granite_multipliers_parity(eight_devices):
    """Granite's scalar multipliers and eps reach the TP engines' local
    config: TP 2 and 4 (paged kernel, prefix hits through lm_extend) serve
    what one device serves."""
    out = eight_devices("sharded_serving_check.py", "parity_granite")
    assert "parity_granite OK" in out


@pytest.mark.multidevice
def test_sharded_lookahead_parity(eight_devices):
    """The TP engine queues decode step n+1 before it reads step n: over
    admissions beyond the slots, chunked prefill and prefix hits its
    streams equal the serial contiguous engine's on one device."""
    out = eight_devices("sharded_serving_check.py", "parity_lookahead")
    assert "parity_lookahead OK" in out


@pytest.mark.slow
@pytest.mark.multidevice
def test_sharded_chunked_prefill_parity(eight_devices):
    out = eight_devices("sharded_serving_check.py", "parity_chunked")
    assert "parity_chunked OK" in out


@pytest.mark.slow
@pytest.mark.multidevice
def test_sharded_prefix_cache_parity(eight_devices):
    out = eight_devices("sharded_serving_check.py", "parity_prefix")
    assert "parity_prefix OK" in out
