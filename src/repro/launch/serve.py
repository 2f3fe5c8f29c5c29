"""Serving driver: continuous-batching Engine over one shared KV cache.

    PYTHONPATH=src python -m repro.launch.serve --arch stablelm-3b \
        --reduced --requests 12 --max-new 16
"""

from __future__ import annotations

import argparse

import jax
import numpy as np

from repro.configs import get_config, reduced
from repro.launch.compile_cache import enable_compile_cache
from repro.models import init_lm
from repro.serving import Engine


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=256)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduced(cfg)
    if cfg.input_mode != "tokens":
        raise SystemExit(f"{args.arch} takes frame embeddings; the token "
                         "serving driver does not apply (see DESIGN.md)")
    params = init_lm(jax.random.PRNGKey(args.seed), cfg)
    eng = Engine(cfg, params, max_batch=args.max_batch, max_len=args.max_len)
    rng = np.random.RandomState(args.seed)
    for i in range(args.requests):
        plen = int(rng.randint(4, 24))
        prompt = rng.randint(1, cfg.vocab_size, size=plen).tolist()
        eng.add_request(prompt, max_new_tokens=args.max_new)
    done = eng.run()
    for r in done[:4]:
        print(f"req {r.uid}: prompt[{len(r.prompt)}] "
              f"ttft={r.ttft_s*1e3:.1f}ms -> {r.output}")
    s = eng.stats
    print(f"requests={len(done)} prefill={s.prefill_s:.2f}s "
          f"decode={s.decode_s:.2f}s decode_tok/s={s.decode_tok_per_s:.1f} "
          f"mean_ttft={s.mean_ttft_s*1e3:.1f}ms "
          f"mean_queue_wait={s.mean_queue_wait_s*1e3:.1f}ms")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
