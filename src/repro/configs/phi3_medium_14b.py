"""phi3-medium-14b [dense] — RoPE SwiGLU GQA (arXiv:2404.14219).

40L d_model=5120 40H (GQA kv=10) d_ff=17920 vocab=32064, untied head.
Source: https://huggingface.co/microsoft/Phi-3-medium-4k-instruct/blob/main/config.json
"""
from .base import ModelConfig

FULL = ModelConfig(
    name="phi3-medium-14b", family="dense",
    n_layers=40, d_model=5120, n_heads=40, n_kv_heads=10,
    d_ff=17920, vocab_size=32_064, head_dim=128,
    norm="rmsnorm", mlp="swiglu", rope_style="standard",
    tie_embeddings=False, remat="full", param_dtype="bfloat16", grad_accum_steps=4,
)

# the source's 4:1 query-to-KV group at 5 KV heads, a count no sublane
# tile divides, as 10 does not
SMOKE = ModelConfig(
    name="phi3-medium-14b-smoke", family="dense",
    n_layers=2, d_model=320, n_heads=20, n_kv_heads=5,
    d_ff=1120, vocab_size=512, head_dim=16,
    norm="rmsnorm", mlp="swiglu", rope_style="standard",
    tie_embeddings=False, attn_chunk=16,
)
