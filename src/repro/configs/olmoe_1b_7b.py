"""olmoe-1b-7b [arXiv:2409.02060; hf:allenai/OLMoE-1B-7B-0924] — MoE, 64
experts top-8.

16L d_model=2048 16H (kv=16) d_ff=1024 (per expert) vocab=50304, SwiGLU;
RMSNorm over the full q and k projections before RoPE; the top-8 router
probabilities are not renormalized (norm_topk_prob false); untied
embeddings.
"""
from repro.models.transformer import ModelConfig, MoEConfig


def full(**ov) -> ModelConfig:
    return ModelConfig(
        name="olmoe-1b-7b", n_layers=16, d_model=2048, n_heads=16, n_kv=16,
        d_ff=1024, vocab=50304, act="swiglu", qk_norm=True,
        tie_embeddings=False, moe=MoEConfig(64, 8, norm_topk=False), **ov)


def smoke(**ov) -> ModelConfig:
    return ModelConfig(
        name="olmoe-1b-7b-smoke", n_layers=2, d_model=64, n_heads=4, n_kv=4,
        d_ff=96, vocab=512, act="swiglu", qk_norm=True, tie_embeddings=False,
        moe=MoEConfig(8, 2, norm_topk=False), **ov)
