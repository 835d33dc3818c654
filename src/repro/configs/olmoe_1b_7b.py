"""OLMoE-1B-7B [moe] — 16L d_model=2048 16H (kv=16) d_ff=1024 vocab=50304,
MoE 64 experts top-8 (softmax router, top-k not renormalised), QK-norm,
untied head.  [arXiv:2409.02060; hf allenai/OLMoE-1B-7B-0924]"""

from repro.models.lm import BlockCfg, ModelCfg
from repro.models.moe import MoECfg


def config() -> ModelCfg:
    return ModelCfg(
        name="olmoe_1b_7b",
        d_model=2048, n_layers=16, n_heads=16, n_kv=16, d_ff=1024,
        vocab=50304,
        pattern=(BlockCfg("attn", "moe"),),
        norm="rmsnorm", mlp_act="silu", mlp_gated=True,
        qk_norm=True,
        moe=MoECfg(n_experts=64, top_k=8, renorm=False),
    )


def smoke_config() -> ModelCfg:
    return ModelCfg(
        name="olmoe_smoke",
        d_model=64, n_layers=2, n_heads=4, n_kv=4, d_ff=32, vocab=512,
        pattern=(BlockCfg("attn", "moe"),),
        norm="rmsnorm", mlp_act="silu", mlp_gated=True,
        qk_norm=True,
        moe=MoECfg(n_experts=8, top_k=2, token_chunk=64,
                   renorm=False),
        q_chunk=64, seq_loss_chunk=64, vocab_pad_to=64,
    )
