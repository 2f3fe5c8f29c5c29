"""A configuration file -> the program's model config, and weights from the seed.

The file (``chipbench/configs/<name>.json``) holds the configuration as it
is run: the public ``config.json`` keys, with the keys the cell changed
listed under ``reduced`` and the sizes set here under ``assumed``. Its
``program`` entry names the repository's own architecture and the overrides
that make it serve this configuration; :func:`program_config` builds that and
refuses to run when the two disagree on any size or scalar the reference
reads.

The scalars are matched by the program's ``ModelConfig`` field of the name
below, where it has one (``None`` there means the neutral value); a program
without the field serves the neutral value, so the file must hold it:

====================== =========================== =========================
file key               ``ModelConfig`` field       neutral value
====================== =========================== =========================
embedding_multiplier   ``embedding_multiplier``    1
attention_multiplier   ``attention_multiplier``    1 / sqrt(head_dim)
residual_multiplier    ``residual_multiplier``     1
logits_scaling         ``logits_scaling``          1
layer_norm_eps or      ``norm_eps``                1e-5 for LayerNorm, 1e-6
rms_norm_eps, by norm                              for RMSNorm (``repro.nn``)
====================== =========================== =========================

A program that switches on what the reference does not describe (a logit
softcap, a zero-centred or post-block norm, a query/key norm, scaled
embeddings, a feed-forward bias) is refused.

The weights are the benchmark's, not the program's: one jitted call makes
every leaf on the device from the seed, in the type it is served in. Only the
tree's structure (leaf names, shapes, dtypes) is taken from the program,
because the program is what serves them. The reference reads the same tree.
"""

from __future__ import annotations

import json
import math
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

HERE = Path(__file__).resolve().parent

# norm gains and biases are drawn near their usual init, not at it, so that
# a program that dropped or swapped one would not go unnoticed
NORM_SCALE_STD = 0.1
NORM_BIAS_STD = 0.1


def load_config(name: str) -> dict:
    path = HERE / "configs" / f"{name}.json"
    if not path.is_file():
        raise SystemExit(f"chipbench: no configuration file {path}")
    return json.loads(path.read_text())


def seed_key(seed: int):
    """A raw threefry key from any whole number (also past 32 bits)."""
    words = np.random.SeedSequence(seed % 2 ** 64).generate_state(2, np.uint32)
    return jnp.asarray(words, jnp.uint32)


def seed_rng(seed: int, stream: str) -> np.random.Generator:
    """An independent host generator per named stream of one seed."""
    salt = int.from_bytes(stream.encode(), "little") % 2 ** 63
    return np.random.default_rng([seed % 2 ** 64, salt])


#: program switches the reference does not describe, each with its off value
OFF = {"attn_logit_softcap": None, "final_logit_softcap": None,
       "zero_centered_norm": False, "post_norm": False, "qk_norm": False,
       "scale_embeddings": False, "ffn_bias": False}


def _scalars(conf: dict) -> list:
    """(file key, ModelConfig field, neutral value) of each scalar the
    reference reads from ``conf`` (the table of the module's docstring)."""
    eps = ("layer_norm_eps", 1e-5) if conf["norm"] == "layernorm" \
        else ("rms_norm_eps", 1e-6)
    return [("embedding_multiplier", "embedding_multiplier", 1.0),
            ("attention_multiplier", "attention_multiplier",
             1.0 / math.sqrt(conf["head_dim"])),
            ("residual_multiplier", "residual_multiplier", 1.0),
            ("logits_scaling", "logits_scaling", 1.0),
            (eps[0], "norm_eps", eps[1])]


def program_config(conf: dict):
    """The repository's ModelConfig that serves ``conf``, checked against it."""
    from repro.configs import get_config

    prog = conf["program"]
    cfg = get_config(prog["arch"]).replace(**prog.get("overrides", {}))
    want = {
        "d_model": conf["hidden_size"],
        "n_layers": conf["num_hidden_layers"],
        "n_heads": conf["num_attention_heads"],
        "n_kv_heads": conf["num_key_value_heads"],
        "resolved_head_dim": conf["head_dim"],
        "d_ff": conf["intermediate_size"],
        "vocab_size": conf["vocab_size"],
        "rope_base": float(conf["rope_theta"]),
        "rope_fraction": float(conf["partial_rotary_factor"]),
        "norm": conf["norm"],
        "tie_embeddings": conf["tie_word_embeddings"],
        "qkv_bias": conf["attention_bias"],
        "param_dtype": conf["torch_dtype"],
        "dtype": conf["torch_dtype"],
        "ffn": "swiglu" if conf["hidden_act"] == "silu" else conf["hidden_act"],
    }
    got = {k: getattr(cfg, k) for k in want}
    bad = {k: (got[k], v) for k, v in want.items() if got[k] != v}
    for key, field, neutral in _scalars(conf):
        if not hasattr(cfg, field):
            if not math.isclose(conf[key], neutral):
                bad[key] = (f"program has no {field}", conf[key])
            continue
        value = getattr(cfg, field)
        value = neutral if value is None else value
        if not math.isclose(conf[key], value):
            bad[key] = (value, conf[key])
    bad.update({k: (getattr(cfg, k), v) for k, v in OFF.items()
                if getattr(cfg, k, v) != v})
    if bad:
        raise SystemExit(f"chipbench: program config {cfg.name} departs from "
                         f"{conf['name']}: {bad}")
    return cfg


def _leaf_name(path) -> str:
    return str(getattr(path[-1], "key", path[-1]))


def make_params(cfg, key):
    """Every weight of the program's tree, drawn on the device in one call.

    Matrices are N(0, 1/fan_in) (the embedding's fan-in is its width), norm
    gains N(1, 0.1^2) and norm biases N(0, 0.1^2), all in the served dtype.
    """
    from repro.models import init_lm

    shapes = jax.eval_shape(lambda: init_lm(jax.random.PRNGKey(0), cfg))
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)

    def leaf(path, s):
        name = _leaf_name(path)
        if name == "scale":
            mean, std = 1.0, NORM_SCALE_STD
        elif name == "bias":
            mean, std = 0.0, NORM_BIAS_STD
        elif len(s.shape) >= 2:
            fan_in = s.shape[-1] if name == "embed" else s.shape[-2]
            mean, std = 0.0, 1.0 / math.sqrt(fan_in)
        else:
            raise ValueError(f"no rule for weight {jax.tree_util.keystr(path)}")

        def one(k, shape):
            z = jax.random.normal(k, shape, jnp.float32)
            return (mean + std * z).astype(s.dtype)
        return one

    def draw(key):
        out = []
        for i, (path, s) in enumerate(flat):
            k, one = jax.random.fold_in(key, i), leaf(path, s)
            if len(s.shape) >= 3:
                # a stacked leaf one layer at a time: the float32 draw never
                # holds more than a layer, so it does not set the peak
                out.append(jax.lax.map(lambda kk: one(kk, s.shape[1:]),
                                       jax.random.split(k, s.shape[0])))
            else:
                out.append(one(k, s.shape))
        return jax.tree_util.tree_unflatten(treedef, out)

    return jax.jit(draw)(key)
