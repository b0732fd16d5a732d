"""Granite-4.0-H-Micro — hybrid: Mamba2 layers and GQA attention layers
without positional encoding, each followed by a SwiGLU MLP, with
muP-style embedding, residual, attention and logit multipliers.
[hf:ibm-granite/granite-4.0-h-micro config.json]"""
from repro.configs.base import ArchConfig, register

#: the published ``layer_types``: attention at layers 5, 15, 25 and 35
GRANITE_LAYER_TYPES = tuple(
    "attention" if i % 10 == 5 else "mamba" for i in range(40))

GRANITE_4_0_H_MICRO = register(ArchConfig(
    name="granite-4.0-h-micro",
    family="hybrid",
    source="https://huggingface.co/ibm-granite/granite-4.0-h-micro/blob/"
           "main/config.json",
    num_layers=40,
    d_model=2048,
    num_heads=32,
    num_kv_heads=8,
    d_ff=8192,                # shared_intermediate_size
    vocab_size=100_352,
    head_dim=64,
    ssm_state=128,            # mamba_d_state; mamba_n_groups 1
    ssm_head_dim=64,          # mamba_d_head; 64 heads = 2 x 2048 / 64
    ssm_expand=2,
    ssm_conv_width=4,         # mamba_d_conv, with bias
    ssm_chunk=256,
    layer_types=GRANITE_LAYER_TYPES,
    embedding_multiplier=12.0,
    residual_multiplier=0.22,
    attention_multiplier=0.015625,
    logits_scaling=8.0,
    use_rope=False,           # position_embedding_type "nope"
    norm_eps=1e-5,
    tie_embeddings=True,
    act="silu",
))
