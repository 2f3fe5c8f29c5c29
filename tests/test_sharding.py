"""Sharding rule tests on an AbstractMesh (no devices needed): greedy
divisibility, param rules, KV-cache fallbacks — the exact cases in the
assigned zoo."""

import jax
import jax.numpy as jnp
from jax.sharding import AbstractMesh, PartitionSpec as P

from repro import sharding as sh
from repro.configs import get_config, reduced
from repro.launch.mesh import make_host_mesh
from repro.models import init_lm

MESH = AbstractMesh((16, 16), ("data", "model"))
POD = AbstractMesh((2, 16, 16), ("pod", "data", "model"))


def test_spec_for_basic_tp():
    # FFN weight: embed x mlp
    assert sh.spec_for((4096, 12800), ("embed", "mlp"), MESH) == \
        P(None, "model")
    # with FSDP the embed dim also shards over data
    assert sh.spec_for((4096, 12800), ("embed", "mlp"), MESH, fsdp=True) == \
        P("data", "model")


def test_spec_for_skips_non_divisible():
    # 60 experts % 16 != 0 -> expert dim unsharded; mlp picks up model
    assert sh.spec_for((60, 2048, 1408), ("expert", "embed", "mlp"),
                       MESH) == P(None, None, "model")
    # 64 experts divide -> EP; mlp then must NOT reuse model
    assert sh.spec_for((64, 2048, 1408), ("expert", "embed", "mlp"),
                       MESH) == P("model", None, None)


def test_spec_for_batch_over_pod_and_data():
    assert sh.spec_for((256, 4096), ("batch", "seq"), POD) == \
        P(("pod", "data"), None)
    # batch=1: greedy drops both axes
    assert sh.spec_for((1, 4096), ("batch", "seq"), POD) == P(None, None)
    # batch=32 on pod mesh: 32 % (2*16) == 0
    assert sh.spec_for((32, 128), ("batch", "seq"), POD) == \
        P(("pod", "data"), None)


def test_spec_for_partial_batch():
    # batch=2 divides pod(2) but not data(16): greedy prefix keeps pod only
    assert sh.spec_for((2, 128), ("batch", "seq"), POD) == P("pod", None)


def test_kv_cache_heads_or_seq():
    # kv heads divide (32 heads): shard heads over model, batch over data
    spec = sh.kv_cache_spec((128, 32768, 32, 80), MESH)
    assert spec == P("data", None, "model", None) or \
        spec == P("data", ("pod", "data"), "model", None)
    # kv=8 < 16: heads can't shard -> sequence-parallel KV
    spec = sh.kv_cache_spec((128, 32768, 8, 128), MESH)
    assert spec[2] is None and spec[1] == "model"
    # long-context batch=1: everything lands on seq
    spec = sh.kv_cache_spec((1, 524288, 16, 128), POD)
    assert spec[0] is None
    assert spec[2] == "model"
    assert set(("pod", "data")) <= set(
        spec[1] if isinstance(spec[1], tuple) else (spec[1],))


def test_param_sharding_covers_real_tree():
    cfg = reduced(get_config("granite-3-8b"))
    params = jax.eval_shape(
        lambda: init_lm(jax.random.PRNGKey(0), cfg))
    tree = sh.param_sharding(params, MESH, fsdp=False)
    leaves = jax.tree_util.tree_leaves(tree)
    assert leaves, "sharding tree not empty"
    specs = [l.spec for l in leaves]
    assert any("model" in str(s) for s in specs), \
        "TP must shard at least some params"


def test_param_sharding_divisibility_safe():
    """Every generated spec must divide its dim (jit would reject it)."""
    for arch in ("qwen2-moe-a2.7b", "deepseek-v2-lite-16b", "xlstm-350m",
                 "recurrentgemma-2b", "gemma3-27b"):
        cfg = get_config(arch)
        params = jax.eval_shape(lambda c=cfg: init_lm(jax.random.PRNGKey(0), c))
        tree = sh.param_sharding(params, MESH, fsdp=cfg.fsdp)
        sizes = dict(MESH.shape)

        def check(path, leafspec, leaf):
            for dim, entry in zip(leaf.shape, leafspec.spec):
                if entry is None:
                    continue
                axes = entry if isinstance(entry, tuple) else (entry,)
                n = 1
                for ax in axes:
                    n *= sizes[ax]
                assert dim % n == 0, (arch, path, leaf.shape, leafspec.spec)

        jax.tree_util.tree_map_with_path(
            lambda p, s, l: check(p, s, l), tree, params)


def test_shard_is_noop_without_rules():
    x = jnp.ones((4, 4))
    assert sh.shard(x, "batch", "seq") is x


def test_use_rules_context():
    mesh = make_host_mesh()
    x = jnp.ones((4, 4))
    with sh.use_rules(mesh, fsdp=False):
        y = sh.shard(x, "batch", "seq")  # 1x1 mesh: fully replicated
    assert y.shape == x.shape
