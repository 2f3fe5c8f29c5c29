"""A cell at a size a CPU test run can hold: the same harness, a tiny model.

``run`` drives ``chipbench.run.run_cell`` end to end on the CPU: the
configuration is a small stablelm-shaped decoder in float32 (XLA:CPU
cannot run bf16 x bf16 dots), the kernels run through the plain ``jnp``
backend, and the chip look is skipped by handing in the CPU devices.
"""

from __future__ import annotations

import copy

import jax

from chipbench import run as runmod

CONF = {
    "name": "tiny", "reference": "dense_decoder",
    "hidden_size": 128, "intermediate_size": 256, "num_hidden_layers": 2,
    "num_attention_heads": 4, "num_key_value_heads": 4, "head_dim": 32,
    "vocab_size": 512, "rope_theta": 10000, "partial_rotary_factor": 0.25,
    "norm": "layernorm", "layer_norm_eps": 1e-5, "rms_norm_eps": None,
    "hidden_act": "silu", "attention_bias": False,
    "tie_word_embeddings": False, "torch_dtype": "float32",
    "embedding_multiplier": 1.0, "attention_multiplier": 32 ** -0.5,
    "residual_multiplier": 1.0, "logits_scaling": 1.0,
    "engine": {"max_batch": 4, "max_len": 64, "block_size": 8},
    "program": {"arch": "stablelm-3b", "overrides": {
        "n_layers": 2, "d_model": 128, "n_heads": 4, "n_kv_heads": 4,
        "head_dim": 32, "d_ff": 256, "vocab_size": 512,
        "dtype": "float32", "param_dtype": "float32"}},
}

GQA = dict(CONF, name="tiny-gqa", num_key_value_heads=2, norm="rmsnorm",
           layer_norm_eps=None, rms_norm_eps=1e-6, partial_rotary_factor=1.0,
           tie_word_embeddings=True,
           engine={"max_batch": 4, "max_len": 96, "block_size": 8},
           program={"arch": "granite-3-8b", "overrides": {
               "n_layers": 2, "d_model": 128, "n_heads": 4, "n_kv_heads": 2,
               "head_dim": 32, "d_ff": 256, "vocab_size": 512,
               "dtype": "float32", "param_dtype": "float32",
               "fsdp": False}})

DECODE = {"arrival": {"kind": "backlog", "requests": 12},
          "prompt_len": {"dist": "loguniform", "lo": 8, "hi": 32},
          "output_len": {"dist": "loguniform", "lo": 8, "hi": 24},
          "max_total": 64, "drain": False}

CHAT = {"arrival": {"kind": "poisson", "rate_per_s": 120.0},
        "shared_prefixes": {"count": 2, "length": 32,
                            "popularity": [0.7, 0.3]},
        "prompt_len": {"dist": "loguniform", "lo": 4, "hi": 32},
        "output_len": {"dist": "loguniform", "lo": 2, "hi": 16},
        "max_total": 96, "drain": True}

LIMITS = {"sample_requests": 16, "min_tokens_compared": 40,
          "max_logit_gap": 1e-3}

BENCH = {
    "workloads": [
        {"name": "tiny.decode", "config": "tiny", "traffic": "decode",
         "chips": 1},
        {"name": "tiny.chat", "config": "tiny-gqa", "traffic": "chat",
         "chips": 1},
    ],
    "end_to_end": [
        {"name": "tokens_per_s", "unit": "tokens/s",
         "workloads": ["tiny.decode"]},
        {"name": "itl_p95_ms", "unit": "ms"},
        {"name": "ttft_p90_ms", "unit": "ms", "workloads": ["tiny.chat"]},
        {"name": "setup_s", "unit": "s"},
    ],
    "per_layer": [],
}

#: the chip's peaks stand in for the CPU's, which the table does not hold:
#: shares read in a test only exercise the arithmetic
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}

CELLS = {"tiny.decode": (CONF, DECODE), "tiny.chat": (GQA, CHAT)}


def run(cell: str = "tiny.decode", seed: int = 2 ** 31 + 11,
        seconds: float = 2.0, **kw) -> dict:
    conf, mix = CELLS[cell]
    bench = copy.deepcopy(BENCH)
    bench["per_layer"] = kw.pop("per_layer", [])
    return runmod.run_cell(bench, cell, seed, seconds, kw.pop("traced", False),
                           backend="jnp", conf=conf, mix=mix,
                           limits=kw.pop("limits", LIMITS),
                           devices=jax.devices()[:1], peaks=PEAKS, **kw)
