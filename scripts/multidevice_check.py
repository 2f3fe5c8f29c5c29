import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
# simulated host devices only: never take an attached accelerator
os.environ["JAX_PLATFORMS"] = "cpu"

# Multi-device *execution* checks (the dry-run only compiles): run the real
# sharded programs on 8 host devices and assert numerical parity with the
# unsharded versions. Exercised paths: DP/TP/FSDP train step, shard-local
# MoE dispatch (n_shards > 1), elastic checkpoint reshard.
#
# Usage: python scripts/multidevice_check.py <train_parity|moe_parity|reshard>

import sys
import tempfile

import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import sharding
from repro.checkpoint import CheckpointManager
from repro.configs import get_config, reduced
from repro.data import DataConfig, make_batch
from repro.launch.mesh import make_mesh, make_sim_mesh
from repro.models import init_lm
from repro.optim import OptimizerConfig, init_opt_state
from repro.runtime import TrainState, make_train_step


def mesh_839():
    return make_mesh((2, 2, 2), ("pod", "data", "model"))


def build(arch: str, mesh, fsdp: bool, cf: float = None):
    cfg = reduced(get_config(arch)).replace(fsdp=fsdp)
    if cf is not None:
        cfg = cfg.replace(capacity_factor=cf)
    params = init_lm(jax.random.PRNGKey(0), cfg)
    opt_cfg = OptimizerConfig(peak_lr=1e-3, warmup_steps=2, total_steps=10)
    state = TrainState(params, init_opt_state(params, opt_cfg))
    if mesh is not None:
        psh = sharding.param_sharding(params, mesh, cfg.fsdp)
        state = TrainState(
            jax.device_put(params, psh),
            type(state.opt)(
                step=jax.device_put(state.opt.step,
                                    NamedSharding(mesh, P())),
                mu=jax.device_put(state.opt.mu, psh),
                nu=jax.device_put(state.opt.nu, psh), err=None))
    step_fn = jax.jit(make_train_step(cfg, opt_cfg, mesh,
                                      num_microbatches=1))
    return cfg, state, step_fn


def batches(cfg, n):
    dc = DataConfig(vocab_size=cfg.vocab_size, seq_len=32, global_batch=8,
                    seed=0)
    return [make_batch(dc, i) for i in range(n)]


def place_batch(batch, mesh):
    if mesh is None:
        return batch
    return jax.tree_util.tree_map(
        lambda x: jax.device_put(
            x, NamedSharding(mesh, P(("pod", "data"),
                                     *([None] * (x.ndim - 1))))), batch)


def train_losses(arch, mesh, fsdp, steps=3, cf=None):
    cfg, state, step_fn = build(arch, mesh, fsdp, cf)
    out = []
    for b in batches(cfg, steps):
        state, m = step_fn(state, place_batch(b, mesh))
        out.append(float(m["loss"]))
    return out, state, cfg


def main(mode: str) -> int:
    if mode == "train_parity":
        # FSDP + TP + hierarchical DP on 8 devices vs single device
        sharded, _, _ = train_losses("granite-3-8b", mesh_839(), fsdp=True)
        single, _, _ = train_losses("granite-3-8b", None, fsdp=True)
        np.testing.assert_allclose(sharded, single, rtol=2e-3, atol=2e-3)
        print(f"train_parity OK sharded={sharded} single={single}")
        return 0

    if mode == "moe_parity":
        # shard-local dispatch (n_shards=4) vs global (n_shards=1): with
        # non-binding capacity the routing is identical
        sharded, _, _ = train_losses("qwen2-moe-a2.7b", mesh_839(),
                                     fsdp=False, cf=16.0)
        single, _, _ = train_losses("qwen2-moe-a2.7b", None, fsdp=False,
                                    cf=16.0)
        np.testing.assert_allclose(sharded, single, rtol=2e-3, atol=2e-3)
        print(f"moe_parity OK sharded={sharded} single={single}")
        return 0

    if mode == "reshard":
        # elastic restart: checkpoint from an 8-device mesh, restore onto a
        # 4-device mesh (half the pod axis lost) and keep training
        mesh8 = mesh_839()
        losses, state, cfg = train_losses("granite-3-8b", mesh8, fsdp=True,
                                          steps=2)
        with tempfile.TemporaryDirectory() as d:
            mgr = CheckpointManager(d)
            mgr.save(2, state, async_=False)

            mesh4 = make_sim_mesh(2, 2)
            cfg4, state4, step_fn4 = build("granite-3-8b", mesh4, fsdp=True)
            psh4 = sharding.param_sharding(state4.params, mesh4, True)
            sh4 = TrainState(psh4, type(state4.opt)(
                step=NamedSharding(mesh4, P()), mu=psh4, nu=psh4, err=None))
            restored, step = mgr.restore(state4, shardings=sh4)
            assert step == 2
            b = batches(cfg4, 3)[2]
            b = jax.tree_util.tree_map(
                lambda x: jax.device_put(
                    x, NamedSharding(mesh4, P("data",
                                              *([None] * (x.ndim - 1))))), b)
            restored, m = step_fn4(restored, b)
            loss = float(m["loss"])
            assert np.isfinite(loss)
            print(f"reshard OK pre={losses} post-restore-loss={loss:.4f}")
        return 0

    raise SystemExit(f"unknown mode {mode}")


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
