"""Granite's scalar multipliers on the serving path, against the plain reference.

A tiny Granite-shaped model (2 layers, d 64, 4 query and 2 KV heads of 16,
the published multipliers and RMSNorm eps) is served through ``PagedEngine``
on the ``jnp`` and ``pallas_interpret`` backends. Its logits, read where the
engine reads them, are compared with the benchmark's float32 reference
(``chipbench/reference/dense_decoder.py``, through its public
``served_gaps``) on the benchmark's seeded weights: the cold prefill, every
decode step through the paged cache, and a prefix hit served by
``lm_extend``. Logits and not tokens: a greedy token cannot show a missing
``logits_scaling``.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import model as bench_model
from chipbench.reference import dense_decoder as dd
from repro import nn
from repro.models import lm_decode
from repro.runtime import cast_params
from repro.serving import PagedEngine
from repro.serving.paged import (_gather_tree, init_pools,
                                 make_paged_decode_step)

CONF = {
    "name": "tiny-granite", "reference": "dense_decoder",
    "hidden_size": 64, "intermediate_size": 128, "num_hidden_layers": 2,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "vocab_size": 256, "rope_theta": 10000.0, "partial_rotary_factor": 1.0,
    "norm": "rmsnorm", "layer_norm_eps": None, "rms_norm_eps": 1e-5,
    "hidden_act": "silu", "attention_bias": False,
    "tie_word_embeddings": True, "torch_dtype": "float32",
    "embedding_multiplier": 12.0, "attention_multiplier": 0.0078125,
    "residual_multiplier": 0.22, "logits_scaling": 16.0,
    "program": {"arch": "granite-3-8b", "overrides": {
        "n_layers": 2, "d_model": 64, "n_heads": 4, "n_kv_heads": 2,
        "head_dim": 16, "d_ff": 128, "vocab_size": 256,
        "dtype": "float32", "param_dtype": "float32", "fsdp": False}},
}

MAX_LEN, BLOCK, NEW = 64, 8, 4
#: float32 end to end on the CPU: the program's gaps and the reference's
#: differ by rounding only (about 2e-7 of the widest gap); leaving out any
#: one multiplier or the eps moves them by 0.01 of it or more
TOL = 1e-4


def _cfg():
    return bench_model.program_config(CONF)


def _params(cfg, small_embed=False):
    params = bench_model.make_params(cfg, jax.random.PRNGKey(7))
    if small_embed:
        # a residual stream of ~3e-3 (mean square ~1e-5), where the
        # norm's eps is a large part of the denominator
        params = dict(params, embed=params["embed"] * 2e-3)
    return params


def _prompts():
    rng = np.random.default_rng(3)
    shared = rng.integers(1, 256, 35).tolist()
    # the second prompt shares 32 tokens (4 blocks) with the first, so it
    # is admitted as a prefix hit and its suffix prefills through lm_extend
    return shared, shared[:32] + rng.integers(1, 256, 6).tolist()


def serve_logits(cfg, params, backend):
    """Serve the two prompts; -> (requests, [(request, position, logits)])
    for every token served: the first token's logits as the engine passes
    them to its sampler, each decode step's from the paged cache."""
    seen = []
    with nn.backend(backend):
        eng = PagedEngine(cfg, params, max_batch=2, max_len=MAX_LEN,
                          block_size=BLOCK, fused=True)

        @jax.jit
        def decode_logits(params, token, pos, pools, tables):
            # the body of the engine's decode program, without sampling
            with nn.fuse(True):
                w = cast_params(params, cfg.activation_dtype)
                if eng.kv_path == "paged":
                    return lm_decode(w, token, pos, pools, cfg,
                                     tables=tables)[0]
                view = _gather_tree(pools, tables, MAX_LEN)
                return lm_decode(w, token, pos, view, cfg)[0]

        # requests reach the sampler's first call in the order admitted
        admitted, first_token = [], eng._first_token

        def spy_first(logits):
            req = admitted.pop(0)
            seen.append((req, len(req.prompt) - 1, np.asarray(logits)[0]))
            return first_token(logits)

        decode = eng._paged_decode

        def spy_decode(params, cur, pos, pools, tables, key):
            lf = np.asarray(decode_logits(params, cur, pos, pools, tables))
            out = decode(params, cur, pos, pools, tables, key)
            for i, r in enumerate(eng.slots):
                if r is not None and i not in eng._prefilling:
                    seen.append((r, int(pos[i]), lf[i]))
            return out

        eng._first_token = spy_first
        eng._paged_decode = spy_decode
        for p in _prompts():
            eng.add_request(p, max_new_tokens=NEW)
        admitted.extend(eng.queue)
        done = eng.run()
    return eng, done, seen


def worst_gap(conf, params, seen, n_targets=8):
    """Largest disagreement between the program's logits and the
    reference's, over the largest gap the reference reads.

    The reference's public entry point, ``served_gaps``, gives how far a
    token's logit lies below the best at one position. For each position
    served and ``n_targets`` tokens there (the program's worst and the rest
    drawn from a fixed seed), the program's gap (its best logit minus that
    token's) is compared with the reference's over the same context: the
    logits are compared up to the one shift a softmax ignores."""
    rng = np.random.default_rng(11)
    worst, top = 0.0, 0.0
    for req, at, logits in seen:
        k = at - len(req.prompt) + 1
        assert int(np.argmax(logits)) == req.output[k]
        context = req.prompt + req.output[:k]
        targets = [int(np.argmin(logits))] + rng.choice(
            len(logits), n_targets - 1, replace=False).tolist()
        for t in targets:
            ref = float(dd.served_gaps(conf, params, context, [t],
                                       MAX_LEN)[0])
            got = float(np.max(logits) - logits[t])
            worst = max(worst, abs(got - ref))
            top = max(top, ref)
    return worst / top


@pytest.mark.parametrize("backend", ["jnp", "pallas_interpret"])
def test_granite_logits_match_reference_on_every_path(backend):
    cfg = _cfg()
    params = _params(cfg)
    eng, done, seen = serve_logits(cfg, params, backend)
    assert len(done) == 2 and all(len(r.output) == NEW for r in done)
    assert eng.kv_path == ("gathered" if backend == "jnp" else "paged")
    # one cold prefill, one prefix hit of 4 blocks through lm_extend
    assert eng.prefix_cache.hits == 1
    assert len(seen) == 2 * NEW
    assert worst_gap(CONF, params, seen) < TOL


@pytest.mark.parametrize("field", ["embedding_multiplier",
                                   "attention_multiplier",
                                   "residual_multiplier", "logits_scaling",
                                   "norm_eps"])
def test_serving_without_a_multiplier_fails_the_comparison(field):
    cfg = _cfg().replace(**{field: None})
    params = _params(cfg, small_embed=field == "norm_eps")
    _, _, seen = serve_logits(cfg, params, "jnp")
    assert worst_gap(CONF, params, seen) > 10 * TOL


def _decode_text(cfg, names=False):
    """The decode step's lowered text; with ``names``, its locations too,
    which carry each op's ``ng:`` scope."""
    params = jax.eval_shape(lambda: bench_model.make_params(
        cfg, jax.random.PRNGKey(0)))
    i32 = jax.ShapeDtypeStruct((2,), jnp.int32)
    pools = jax.eval_shape(lambda: init_pools(cfg, 9, BLOCK))
    step = make_paged_decode_step(cfg, MAX_LEN, fused=True)
    return jax.jit(step).lower(
        params, i32, i32, pools, jax.ShapeDtypeStruct((2, 8), jnp.int32),
        jax.ShapeDtypeStruct((2,), jnp.uint32)).as_text(debug_info=names)


def test_neutral_multipliers_add_no_op():
    """A stablelm-shaped config (LayerNorm, MHA, untied head) with the
    fields unset lowers to the same decode program as with each set to the
    value it stands for; set to 1 instead of None, the three multipliers
    would each cost a multiply (the layer scan's body counts once)."""
    from repro.configs import get_config

    base = get_config("stablelm-3b").replace(
        n_layers=2, d_model=64, n_heads=4, n_kv_heads=4, d_ff=128,
        vocab_size=256, dtype="float32", param_dtype="float32")
    assert base.embedding_multiplier is None and base.norm_eps is None
    neutral = _decode_text(base)
    assert neutral == _decode_text(base.replace(
        attention_multiplier=1 / math.sqrt(16), norm_eps=1e-5))
    ones = base.replace(embedding_multiplier=1.0, residual_multiplier=1.0,
                        logits_scaling=1.0)
    assert _decode_text(ones).count("stablehlo.multiply") \
        == neutral.count("stablehlo.multiply") + 4
    named = _decode_text(base, names=True), _decode_text(ones, names=True)
    for name in ("embedding_multiplier", "residual_multiplier",
                 "logits_scaling"):
        assert f"ng:elementwise:{name}" not in named[0]
        assert f"ng:elementwise:{name}" in named[1]
