"""zamba2-7b — Zyphra's published Zamba2 block [arXiv:2411.15242; hf
Zyphra/Zamba2-7B-Instruct config.json].

81 Mamba2 layers of d_model 3584 (state 64, head 64, expand 2, 2 groups);
two shared transformer blocks (num_mem_blocks 2) taken in turn before the
Mamba2 layers at ``hybrid_layer_ids`` (13 applications).  Each attends
over concat(hidden, embedding), 7168 = 2·d wide, with 32 heads of 224 and
softmax scale (224 / 2) ** -0.5; its GeLU-gated MLP (14336) carries a
rank-128 adapter per application, and a per-application linear takes its
output into that Mamba2 layer's input.  Vocabulary 32000, context 4096,
embeddings tied."""

from repro.models.config import ModelConfig

CONFIG = ModelConfig(
    name="zamba2-7b",
    family="zamba2",
    num_layers=81,
    d_model=3584,
    n_heads=32,
    n_kv_heads=32,
    head_dim=224,
    d_ff=14336,
    vocab_size=32000,
    ssm_state=64,
    ssm_headdim=64,
    ssm_expand=2,
    ssm_conv=4,
    ssm_groups=2,
    n_shared_blocks=2,
    hybrid_layer_ids=(6, 11, 17, 23, 29, 35, 41, 47, 53, 59, 65, 71, 77),
    adapter_rank=128,
    rope_theta=10000.0,
    tie_embeddings=True,
    subquadratic=True,
)
