"""glm4-9b — GLM-4's dense block (https://huggingface.co/THUDM/glm-4-9b-chat).

[glm4 → dense] 40L d_model=4096 32H (GQA kv=2, kv_channels 128)
d_ffn=13696 padded_vocab=151552, untied output layer; bias on Q/K/V only,
interleaved rotary over the first 64 of 128 head dims at base
10000 · rope_ratio (500), RMSNorm eps 1.5625e-7 (``base.FAMILY_TRAITS``).
"""
from .base import ArchConfig

CONFIG = ArchConfig(
    name="glm4-9b", family="glm4", n_layers=40, d_model=4096, n_heads=32,
    n_kv_heads=2, d_ff=13696, vocab=151552, head_dim=128,
    rope_theta=10_000.0 * 500, tie_embeddings=False,
    source="https://huggingface.co/THUDM/glm-4-9b-chat",
)
