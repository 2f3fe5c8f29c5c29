"""``program_config`` admits a configuration's scalars only where the program
serves them, and the comparison sees a program that drops them.

Granite (ibm-granite/granite-3.0-8b-instruct) carries four scalar
multipliers and an RMSNorm eps of 1e-5. The program's ``ModelConfig`` has
no fields for them yet, so a stand-in subclass with the contract's field
names plays the program that does.
"""

import dataclasses
from types import SimpleNamespace
from typing import Optional

import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import check, model
from chipbench.reference import dense_decoder as dd

#: the public config.json of granite-3.0-8b-instruct, as a configuration file
GRANITE = {
    "name": "granite-3-8b", "reference": "dense_decoder",
    "hidden_size": 4096, "intermediate_size": 12800, "num_hidden_layers": 40,
    "num_attention_heads": 32, "num_key_value_heads": 8, "head_dim": 128,
    "vocab_size": 49155, "rope_theta": 10000.0, "partial_rotary_factor": 1.0,
    "norm": "rmsnorm", "layer_norm_eps": None, "rms_norm_eps": 1e-5,
    "hidden_act": "silu", "attention_bias": False,
    "tie_word_embeddings": True, "torch_dtype": "bfloat16",
    "embedding_multiplier": 12.0, "attention_multiplier": 0.0078125,
    "residual_multiplier": 0.22, "logits_scaling": 16.0,
    "program": {"arch": "granite-3-8b",
                "overrides": {"param_dtype": "bfloat16"}},
}

#: the contract's field names, with Granite's values
FIELDS = {"embedding_multiplier": 12.0, "attention_multiplier": 0.0078125,
          "residual_multiplier": 0.22, "logits_scaling": 16.0,
          "norm_eps": 1e-5}
#: the file key each field is compared with
KEYS = {"embedding_multiplier": "embedding_multiplier",
        "attention_multiplier": "attention_multiplier",
        "residual_multiplier": "residual_multiplier",
        "logits_scaling": "logits_scaling", "norm_eps": "rms_norm_eps"}


@pytest.fixture
def serves_scalars(monkeypatch):
    """Make ``get_config`` return a ModelConfig with the five fields, set by
    the returned function (``None`` where not given)."""
    import repro.configs
    from repro.models.common import ModelConfig

    stand_in = dataclasses.make_dataclass(
        "ScalarModelConfig",
        [(f, Optional[float], dataclasses.field(default=None))
         for f in FIELDS], bases=(ModelConfig,), frozen=True)
    base = repro.configs.get_config

    def use(**values):
        cfg = base("granite-3-8b")
        fields = {f.name: getattr(cfg, f.name)
                  for f in dataclasses.fields(cfg)}
        monkeypatch.setattr(repro.configs, "get_config",
                            lambda name: stand_in(**fields, **values))
    return use


def test_granite_is_admitted_where_the_program_has_its_scalars(
        serves_scalars):
    serves_scalars(**FIELDS)
    cfg = model.program_config(GRANITE)
    assert {f: getattr(cfg, f) for f in FIELDS} == FIELDS


def test_granite_is_refused_by_a_program_without_the_fields():
    with pytest.raises(SystemExit) as e:
        model.program_config(GRANITE)
    for field in FIELDS:
        assert f"program has no {field}" in str(e.value)


@pytest.mark.parametrize("field", list(FIELDS))
def test_one_differing_scalar_is_refused(serves_scalars, field):
    serves_scalars(**dict(FIELDS, **{field: FIELDS[field] * 1.01}))
    with pytest.raises(SystemExit) as e:
        model.program_config(GRANITE)
    assert f"'{KEYS[field]}'" in str(e.value)


@pytest.mark.parametrize("switch", [{"attn_logit_softcap": 50.0},
                                    {"final_logit_softcap": 30.0},
                                    {"zero_centered_norm": True}])
def test_what_the_reference_does_not_describe_is_refused(serves_scalars,
                                                          switch):
    serves_scalars(**FIELDS)
    conf = dict(GRANITE, program={"arch": "granite-3-8b", "overrides": dict(
        GRANITE["program"]["overrides"], **switch)})
    with pytest.raises(SystemExit) as e:
        model.program_config(conf)
    assert f"'{next(iter(switch))}'" in str(e.value)


def test_a_field_left_none_serves_the_neutral_value(serves_scalars):
    """A program whose fields are unset serves neutral scalars: a neutral
    file is admitted, Granite's is refused on all five."""
    serves_scalars()
    neutral = dict(GRANITE, embedding_multiplier=1.0,
                   attention_multiplier=128 ** -0.5, residual_multiplier=1.0,
                   logits_scaling=1.0, rms_norm_eps=1e-6)
    model.program_config(neutral)
    with pytest.raises(SystemExit) as e:
        model.program_config(GRANITE)
    for key in KEYS.values():
        assert f"'{key}'" in str(e.value)


def test_stablelm_3b_gives_the_config_it_gave():
    from repro.configs import get_config
    cfg = model.program_config(model.load_config("stablelm-3b"))
    assert cfg == get_config("stablelm-3b").replace(param_dtype="bfloat16")


# a Granite-shaped decoder a CPU test holds: 2 layers, d 64, 4 query heads
# and 2 KV heads of 16, vocab 256
TINY = dict(GRANITE, name="tiny-granite", hidden_size=64,
            intermediate_size=128, num_hidden_layers=2,
            num_attention_heads=4, num_key_value_heads=2, head_dim=16,
            vocab_size=256, torch_dtype="float32")
NEUTRAL = dict(TINY, embedding_multiplier=1.0, attention_multiplier=0.25,
               residual_multiplier=1.0, logits_scaling=1.0)
PAD = 64


def _greedy(conf, params, prompt, n):
    """``n`` tokens the reference picks greedily after ``prompt``."""
    seq = list(prompt)
    for _ in range(n):
        toks = np.zeros(PAD, np.int32)
        toks[:len(seq)] = seq
        logits = dd._logits(dd.conf_items(conf), params, jnp.asarray(toks))
        seq.append(int(np.argmax(np.asarray(logits[len(seq) - 1]))))
    return seq[len(prompt):]


def test_the_check_sees_a_program_that_drops_the_multipliers():
    """Tokens chosen greedily without Granite's multipliers read a widest
    logit gap above the 0.17 limit against the reference with them, and
    none against the reference without them."""
    from repro.configs import get_config
    prog = get_config("granite-3-8b").replace(
        n_layers=2, d_model=64, n_heads=4, n_kv_heads=2, head_dim=16,
        d_ff=128, vocab_size=256, dtype="float32", param_dtype="float32",
        fsdp=False)
    seed = 2 ** 31 + 7
    params = model.make_params(prog, model.seed_key(seed))
    rng = model.seed_rng(seed, "prompts")
    served = []
    for _ in range(4):
        prompt = [int(t) for t in rng.integers(0, 256, 8)]
        served.append(SimpleNamespace(req=SimpleNamespace(prompt=prompt),
                                      tokens=_greedy(NEUTRAL, params,
                                                     prompt, 24)))
    limits = {"sample_requests": 4, "min_tokens_compared": 96,
              "max_logit_gap": 0.17}
    dropped = check.compare(TINY, params, served, seed, limits, PAD)
    assert dropped["tokens_compared"]["value"] == 96
    assert dropped["max_logit_gap"]["value"] > 0.17
    assert not check.passed(dropped)
    kept = check.compare(NEUTRAL, params, served, seed, limits, PAD)
    assert kept["max_logit_gap"]["value"] < 1e-5
    assert check.passed(kept)
