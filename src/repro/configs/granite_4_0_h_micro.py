"""granite-4.0-h-micro [hybrid, interleaved] — 36 Mamba2 + 4 NoPE GQA layers.

40L d_model=2048 vocab=100352 tied, RMSNorm eps 1e-5
[huggingface.co/ibm-granite/granite-4.0-h-micro config.json,
model_type granitemoehybrid]. ``layer_types`` puts attention at layers 5,
15, 25 and 35 and Mamba2 everywhere else; every layer also has a SwiGLU MLP
of 8192 (``shared_intermediate_size``, no experts).

- Mamba2: 64 heads of 64 (expand 2 -> d_inner 4096), d_state 128,
  n_groups 1, causal conv of width 4 with bias, chunk 256; the gated
  RMSNorm ``norm(y * silu(z))`` spans all of d_inner (one group).
- Attention: 32 query heads and 8 kv heads of 64, no position embedding
  (``position_embedding_type: nope``), softmax scale
  ``attention_multiplier`` 1/64.
- muP multipliers: embedding x 12, each residual branch x 0.22, logits / 8.

About 3.2B parameters, 6.4 GB in bf16: one TPU v5e holds the model whole, so
``tp`` is 1 and nothing is padded. FlashBias: the attention layers carry no
bias, so the kernels run their bias-free mode.
"""
from repro.configs.base import ArchConfig

LAYER_KINDS = tuple("attention" if i % 10 == 5 else "mamba"
                    for i in range(40))

CONFIG = ArchConfig(
    name="granite-4.0-h-micro",
    family="hybrid",
    n_layers=40,
    d_model=2048,
    n_heads=32,
    n_kv_heads=8,
    head_dim=64,
    d_ff=8192,
    vocab=100352,
    bias_kind="none",
    ssm_state=128,
    ssm_head_dim=64,
    ssm_expand=2,
    conv_width=4,
    conv_bias=True,
    ssd_chunk=256,
    layer_kinds=LAYER_KINDS,
    embedding_multiplier=12.0,
    residual_multiplier=0.22,
    logits_scaling=8.0,
    attention_multiplier=0.015625,
    norm_eps=1e-5,
    tp=1,
    remat="none",
    notes="interleaved Mamba2 / NoPE GQA layers at published widths",
)

SMOKE = CONFIG.replace(
    n_layers=5, d_model=64, n_heads=4, n_kv_heads=2, head_dim=16, d_ff=128,
    vocab=128, ssm_state=16, ssm_head_dim=16, ssd_chunk=8,
    layer_kinds=("mamba", "mamba", "attention", "mamba", "attention"),
    dtype="float32",
)
