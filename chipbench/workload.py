"""The one traffic generator: a mix file in, a seeded list of requests out.

A mix (``chipbench/traffic/<name>.json``) gives parameters only:

* ``arrival``: ``{"kind": "backlog", "requests": n}`` queues ``n`` requests
  at time 0 (offline batch); ``{"kind": "poisson", "rate_per_s": r}`` sends
  ``round(r * seconds)`` requests over the window with exponential gaps.
* ``prompt_len`` / ``output_len``: ``{"dist": "loguniform", "lo", "hi"}``.
  With shared prefixes, ``prompt_len`` is the user turn after the prefix.
* ``shared_prefixes`` (optional): ``{"count", "length", "popularity"}`` —
  system prompts that each request starts with, chosen by popularity.
* ``max_total``: prompt + output tokens never exceed it (the output is cut).

Every seed gets the same multiset of lengths, gaps and prefix choices, drawn
at fixed quantiles and then put in a seeded order; the seed changes the
order and the tokens, not the amount of work. Lengths are ordered in strata
(:func:`_stratified`), so that even the first requests of a backlog, all a
window may reach, carry the same work under every seed. The same seed gives
the same requests.
"""

from __future__ import annotations

import dataclasses
import json
import math
from pathlib import Path
from typing import List

import numpy as np

from chipbench.model import seed_rng

HERE = Path(__file__).resolve().parent


@dataclasses.dataclass
class Req:
    due_s: float                # scheduled send time from the window start
    prompt: List[int]
    max_new_tokens: int
    prefix_len: int = 0         # tokens shared with a system prompt


def load_mix(name: str) -> dict:
    path = HERE / "traffic" / f"{name}.json"
    if not path.is_file():
        raise SystemExit(f"chipbench: no traffic file {path}")
    return json.loads(path.read_text())


def _quantiles(n: int, spec: dict) -> np.ndarray:
    """``n`` lengths at the midpoint quantiles of the stated distribution."""
    u = (np.arange(n) + 0.5) / n
    if spec["dist"] != "loguniform":
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    lo, hi = math.log(spec["lo"]), math.log(spec["hi"] + 1)
    return np.floor(np.exp(lo + u * (hi - lo))).astype(np.int64)


#: consecutive requests that together hold one length from each of as many
#: equal bands of the sorted lengths
STRATUM = 16


def _stratified(values: np.ndarray, rng: np.random.Generator) -> np.ndarray:
    """``values`` (ascending) in a seeded order in which each run of
    ``STRATUM`` consecutive requests takes one value from each band."""
    n = len(values)
    per_band = -(-n // STRATUM)
    bands = [rng.permutation(np.arange(b, min(b + per_band, n)))
             for b in range(0, n, per_band)]
    order = []
    for j in range(per_band):
        order += rng.permutation([band[j] for band in bands
                                  if j < len(band)]).tolist()
    return values[np.asarray(order, np.int64)]


def _counts(n: int, popularity) -> np.ndarray:
    """Split ``n`` requests over choices by popularity (largest remainder)."""
    p = np.asarray(popularity, np.float64)
    p = p / p.sum()
    base = np.floor(p * n).astype(np.int64)
    rest = np.argsort(-(p * n - base), kind="stable")[:n - base.sum()]
    base[rest] += 1
    return base


def n_requests(mix: dict, seconds: float) -> int:
    arr = mix["arrival"]
    if arr["kind"] == "backlog":
        return int(arr["requests"])
    if arr["kind"] == "poisson":
        return max(1, int(round(arr["rate_per_s"] * seconds)))
    raise ValueError(f"unknown arrival kind {arr['kind']!r}")


def generate(mix: dict, seed: int, seconds: float, vocab: int) -> List[Req]:
    """The seeded requests of one run, in due order."""
    n = n_requests(mix, seconds)
    order = seed_rng(seed, "order")
    tokens = seed_rng(seed, "tokens")

    if mix["arrival"]["kind"] == "poisson":
        u = (np.arange(n) + 0.5) / n
        gaps = -np.log1p(-u)
        gaps = order.permutation(gaps * (seconds / gaps.sum()))
        due = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    else:
        due = np.zeros(n)

    user = _stratified(_quantiles(n, mix["prompt_len"]), order)
    out = _stratified(_quantiles(n, mix["output_len"]), order)

    shared = mix.get("shared_prefixes")
    prefixes: List[List[int]] = []
    choice = np.full(n, -1)
    if shared:
        prefixes = [tokens.integers(1, vocab, shared["length"]).tolist()
                    for _ in range(shared["count"])]
        choice = order.permutation(np.repeat(
            np.arange(shared["count"]), _counts(n, shared["popularity"])))

    reqs = []
    for i in range(n):
        pre = prefixes[choice[i]] if choice[i] >= 0 else []
        prompt = pre + tokens.integers(1, vocab, int(user[i])).tolist()
        room = mix["max_total"] - len(prompt)
        if room < 1:
            raise ValueError(f"mix leaves no room for output: prompt "
                             f"{len(prompt)} of max_total {mix['max_total']}")
        reqs.append(Req(float(due[i]), prompt, int(min(out[i], room)),
                        len(pre)))
    return reqs


def shadow(reqs: List[Req], vocab: int, block_size: int) -> List[List[Req]]:
    """Warm-up waves: the window's shapes on token-remapped copies.

    1. one request per power-of-two class of prompt length (any request
       could be admitted cold), each remapped on its own so none shares a
       block with another;
    2. where prompts share a prefix, one cold copy per prefix that a hit in
       wave 3 needs, which puts that prefix in the cache;
    3. one request per class of the suffix that a prefix hit leaves to
       prefill: the wave-2 prefix with a suffix remapped once more.

    Remapped tokens share nothing with the window's prompts, so the window
    starts with its own prefixes uncached. Each copy asks for two tokens, so
    the decode program runs too. Each wave is served to its end before the
    next is sent.
    """
    def remap(tokens, offset):
        return [(t + offset) % (vocab - 1) + 1 for t in tokens]

    def cls(n):
        return max(n - 1, 0).bit_length()

    cold, hit = {}, {}
    for r in reqs:
        cold.setdefault(cls(len(r.prompt)), r)
        cached = (min(r.prefix_len, len(r.prompt) - 1) // block_size) \
            * block_size
        if cached:
            hit.setdefault(cls(len(r.prompt) - cached), r)

    def req(r, prompt):
        return Req(0.0, prompt, 2, r.prefix_len)

    waves = [[req(r, remap(r.prompt, vocab // 2 + k))
              for k, r in enumerate(cold.values())]]
    leads = {tuple(r.prompt[:r.prefix_len]): r for r in hit.values()}
    waves.append([req(r, remap(r.prompt, vocab // 3))
                  for r in leads.values()])
    waves.append([req(r, remap(r.prompt[:r.prefix_len], vocab // 3)
                      + remap(r.prompt[r.prefix_len:], vocab // 5))
                  for r in hit.values()])
    return [w for w in waves if w]
