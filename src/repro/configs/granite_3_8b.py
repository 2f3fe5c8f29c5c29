"""granite-3-8b [dense] — 40L d_model=4096 32H (GQA kv=8) d_ff=12800
vocab=49155, from IBM Granite 3.0 8B Instruct
(https://huggingface.co/ibm-granite/granite-3.0-8b-instruct, config.json,
``model_type`` "granite").

Llama-style blocks: RMSNorm (eps 1e-5), RoPE (theta 10000) over the whole
128-wide head, SwiGLU, GQA with 8 KV heads, tied embeddings, no biases.
Four scalar multipliers that plain Llama blocks lack:

* ``embedding_multiplier`` 12.0 — the looked-up embeddings are scaled;
* ``attention_multiplier`` 0.0078125 — the q.k scale, 1/128 and not
  1/sqrt(128);
* ``residual_multiplier`` 0.22 — every attention and FFN branch is scaled
  before its residual add;
* ``logits_scaling`` 16.0 — the logits are divided by it.
"""

from repro.models.common import ModelConfig

CONFIG = ModelConfig(
    remat_policy="proj",
    name="granite-3-8b",
    family="dense",
    n_layers=40,
    d_model=4096,
    n_heads=32,
    n_kv_heads=8,
    head_dim=128,
    d_ff=12800,
    vocab_size=49155,
    block_pattern=("attn",),
    pos_emb="rope",
    rope_base=10000.0,
    max_position=4096,
    norm="rmsnorm",
    norm_eps=1e-5,
    ffn="swiglu",
    causal=True,
    tie_embeddings=True,
    embedding_multiplier=12.0,
    attention_multiplier=0.0078125,
    residual_multiplier=0.22,
    logits_scaling=16.0,
    fsdp=True,
)
