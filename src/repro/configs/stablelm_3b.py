"""stablelm-3b [dense] — 32L d_model=2560 32H (MHA kv=32) d_ff=6912
vocab=50304, from StableLM-3B-4E1T
(https://huggingface.co/stabilityai/stablelm-3b-4e1t, config.json).

StableLM blocks: LayerNorm, partial rotary embedding on 25% of head dims,
SwiGLU FFN, untied embeddings.
"""

from repro.models.common import ModelConfig

CONFIG = ModelConfig(
    remat_policy="proj",
    name="stablelm-3b",
    family="dense",
    n_layers=32,
    d_model=2560,
    n_heads=32,
    n_kv_heads=32,
    d_ff=6912,
    vocab_size=50304,
    block_pattern=("attn",),
    pos_emb="rope",
    rope_fraction=0.25,
    norm="layernorm",
    ffn="swiglu",
    causal=True,
    tie_embeddings=False,
)
