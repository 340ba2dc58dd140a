"""granite-4.0-h-micro [hybrid]: 40L d_model=2048, Mamba-2 in 36 layers
and GQA attention (32H kv=8, head_dim 64, no RoPE) in layers 5, 15, 25
and 35, each layer with its own weights and a SwiGLU MLP (d_ff=8192)
after its mixer; vocab=100352, tied embeddings, ssm_state=128, 64 SSD
heads of 64. Embedding x12, attention scale 1/64, residual branches
x0.22, logits /8, RMSNorm eps 1e-5.
[hf:ibm-granite/granite-4.0-h-micro config.json]
"""

from repro.configs.base import ModelConfig, SSMConfig

_PERIOD = ("mamba",) * 5 + ("attention",) + ("mamba",) * 4

CONFIG = ModelConfig(
    name="granite-4.0-h-micro",
    family="hybrid",
    n_layers=40,
    d_model=2048,
    n_heads=32,
    n_kv_heads=8,
    head_dim=64,
    d_ff=8192,
    vocab=100352,
    use_rope=False,
    tie_embeddings=True,
    layer_types=_PERIOD * 4,
    norm_eps=1e-5,
    embedding_multiplier=12.0,
    attention_multiplier=0.015625,
    residual_multiplier=0.22,
    logits_scaling=8.0,
    ssm=SSMConfig(d_state=128, head_dim=64, expand=2, conv_width=4,
                  chunk=256, n_groups=1),
)
