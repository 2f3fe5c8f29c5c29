"""Training driver.

    PYTHONPATH=src python -m repro.launch.train --arch granite-3-8b \
        --steps 200 --seq 256 --batch 8 --reduced --ckpt /tmp/ckpt

On a real cluster this binary runs once per host (jax.distributed
initializes from the cluster env); in this container it drives the reduced
configs on the local device. ``--resume auto`` restores the latest
committed checkpoint — combined with the step-indexed data pipeline the
restart is bit-exact.
"""

from __future__ import annotations

import argparse

import jax

from repro.configs import get_config, reduced
from repro.data import DataConfig
from repro.launch.compile_cache import enable_compile_cache
from repro.models import init_lm
from repro.optim import OptimizerConfig
from repro.runtime import Trainer


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--micro", type=int, default=1)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--warmup", type=int, default=20)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--ckpt", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--resume", choices=["auto", "never"], default="auto")
    ap.add_argument("--reduced", action="store_true",
                    help="train the reduced config (CPU-scale)")
    ap.add_argument("--compress-grads", action="store_true")
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduced(cfg)
    data_cfg = DataConfig(
        vocab_size=cfg.vocab_size, seq_len=args.seq,
        global_batch=args.batch, seed=args.seed,
        embed_dim=cfg.d_model if cfg.input_mode == "embeddings" else 0)
    opt_cfg = OptimizerConfig(peak_lr=args.lr, warmup_steps=args.warmup,
                              total_steps=args.steps,
                              compress_grads=args.compress_grads)

    trainer = Trainer(
        cfg, opt_cfg, data_cfg,
        init_params_fn=lambda: init_lm(jax.random.PRNGKey(args.seed), cfg),
        ckpt_dir=args.ckpt, ckpt_every=args.ckpt_every,
        num_microbatches=args.micro)
    trainer.install_preemption_handler()
    if args.resume == "auto":
        trainer.try_resume()
    out = trainer.train(args.steps)
    print(f"done: step={out['step']} stragglers={out['stragglers']} "
          f"preempted={out['preempted']}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
