"""zamba2-7b — Zamba2-7B-Instruct: 81 Mamba-2 layers d_model=3584 (112
heads of 64, state 64, 2 B/C groups, SSD chunk 256) and two SHARED
attention+MLP blocks used in turn before the Mamba layers of
``hybrid_layer_ids``: attention over concat(h, embeddings) (32 heads of
224, scale (224/2)^-1/2), a GELU-gated MLP 14336 with rank-128 adapters
per use, and a linear projection per use into the Mamba layer's input.
Tied embeddings, vocab 32000.
[https://huggingface.co/Zyphra/Zamba2-7B-Instruct/blob/main/config.json]"""

from repro.configs.base import ModelConfig

CONFIG = ModelConfig(
    name="zamba2-7b", family="hybrid",
    n_layers=81, d_model=3584, n_heads=32, n_kv_heads=32, head_dim=224,
    d_ff=14336, vocab=32000,
    norm="rmsnorm", norm_eps=1e-5, mlp="geglu_exact", query_scale=112.0,
    rope_theta=10000.0, tie_embeddings=True,
    ssm_state=64, ssm_headdim=64, ssm_expand=2, ssm_conv=4, ssm_chunk=256,
    ssm_ngroups=2,
    hybrid_layer_ids=[6, 11, 17, 23, 29, 35, 41, 47, 53, 59, 65, 71, 77],
    num_mem_blocks=2, adapter_rank=128,
)
