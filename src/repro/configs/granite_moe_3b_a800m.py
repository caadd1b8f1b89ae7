"""granite-moe-3b-a800m: IBM Granite 3.0 3B-A800M
[hf:ibm-granite/granite-3.0-3b-a800m-base, model type ``granitemoe``].

32 layers, d 1536; GQA with 24 query heads of 64 and 8 KV heads, RoPE
theta 10,000, no attention bias; a fine-grained MoE in every layer: 40
experts of width 512, top-8, gates by a softmax over the top-8 router
logits, the load-balancing loss over all 8 choices at weight 0.001;
vocabulary 49,155 with the head tied to the embedding; RMSNorm eps 1e-6;
Granite's multipliers (embedding x 12, each branch x 0.22 before its
residual add, attention scores x 1/64, logits / 6).  3.30B parameters,
0.88B active a token.  One chip holds a share of it:
``granite_moe_3b_a800m_1chip``."""

from repro.models.config import ModelConfig


def config() -> ModelConfig:
    return ModelConfig(
        name="granite-moe-3b-a800m", family="moe",
        n_layers=32, d_model=1536, n_heads=24, n_kv_heads=8,
        d_ff=512, vocab=49155, head_dim=64, tie_embeddings=True,
        n_experts=40, experts_per_tok=8, router_aux_weight=0.001,
        norm_eps=1e-6, embedding_multiplier=12.0, residual_multiplier=0.22,
        attention_multiplier=0.015625, logits_scaling=6.0,
    )


def smoke_config() -> ModelConfig:
    return ModelConfig(
        name="granite-moe-smoke", family="moe",
        n_layers=2, d_model=256, n_heads=4, n_kv_heads=2,
        d_ff=128, vocab=1024, head_dim=64, tie_embeddings=True,
        n_experts=4, experts_per_tok=2, router_aux_weight=0.001,
        norm_eps=1e-6, embedding_multiplier=12.0, residual_multiplier=0.22,
        attention_multiplier=0.015625, logits_scaling=6.0,
    )
