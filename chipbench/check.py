"""Whether what the timed path served is correct.

Once the window has closed and the program's state is freed, a sample of
the finished requests, drawn from the seed and always holding the longest
one, goes through the configuration's plain reference: one float32 forward
pass over each prompt with its served tokens. The number compared is the
widest gap by which a served token's logit lies below the reference's best
at that position (0 where the served token is the reference's greedy
choice). The cell's limits file (``chipbench/limits/<cell>.json``) holds its
limit, and the readings it was set from.
"""

from __future__ import annotations

import importlib
import json
from pathlib import Path
from typing import List

import numpy as np

from chipbench.model import seed_rng

HERE = Path(__file__).resolve().parent


def load_limits(cell: str) -> dict:
    path = HERE / "limits" / f"{cell}.json"
    if not path.is_file():
        raise SystemExit(f"chipbench: no limits file {path}")
    return json.loads(path.read_text())


def reference(conf: dict):
    mod = importlib.import_module(f"chipbench.reference.{conf['reference']}")
    mod.check_config(conf)
    return mod


def sample(finished: List, seed: int, n: int) -> List:
    """The longest finished request and ``n - 1`` others drawn from the seed."""
    if not finished:
        return []
    size = [len(s.req.prompt) + len(s.tokens) for s in finished]
    longest = int(np.argmax(size))
    rest = [i for i in range(len(finished)) if i != longest]
    pick = seed_rng(seed, "check").permutation(rest)[:max(n - 1, 0)]
    return [finished[longest]] + [finished[i] for i in sorted(pick)]


def compare(conf: dict, params, finished: List, seed: int, limits: dict,
            pad_to: int, *, keep=None) -> dict:
    """-> {name: {"value", "limit"}} for each number compared. ``keep``, a
    list, receives the per-request gaps."""
    ref = reference(conf)
    gaps = [ref.served_gaps(conf, params, s.req.prompt, s.tokens, pad_to)
            for s in sample(finished, seed, limits["sample_requests"])]
    if keep is not None:
        keep.extend(gaps)
    flat = np.concatenate(gaps) if gaps else np.zeros(0)
    return {
        "max_logit_gap": {"value": float(flat.max()) if flat.size else None,
                          "limit": limits["max_logit_gap"]},
        "tokens_compared": {"value": int(flat.size),
                            "limit": limits["min_tokens_compared"]},
    }


def passed(numbers: dict) -> bool:
    gap, n = numbers["max_logit_gap"], numbers["tokens_compared"]
    return (gap["value"] is not None and np.isfinite(gap["value"])
            and gap["value"] <= gap["limit"] and n["value"] >= n["limit"])
