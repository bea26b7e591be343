"""granite-4.0-h-micro [hf:ibm-granite/granite-4.0-h-micro, config.json].

40L d_model=2048 vocab=100352, tied embeddings, RMSNorm eps 1e-5. The
layer types repeat with a period of 10 (five Mamba2, one attention, four
Mamba2): 36 Mamba2 and 4 attention layers, each followed by a SiLU-gated
MLP of 8192 (``shared_intermediate_size``).

Mamba2: 64 heads of 64 (d_inner 4096, expand 2), state 128, one group,
conv width 4 with bias, chunk 256; the gated RMSNorm runs over the one
group, all 4096 channels. Attention: GQA 32 query / 8 KV heads of 64,
no bias, no position embedding (``position_embedding_type: "nope"``),
scores scaled by ``attention_multiplier`` 1/64. muP multipliers:
embeddings x12, each residual branch x0.22, logits /8.
"""

from repro.config import BlockKind, ModelConfig, SSMConfig, register_config

#: ``layer_types``: one attention layer in every 10, at position 5
PERIOD = 10
_PATTERN = tuple(
    BlockKind.ATTN_MLP if i % PERIOD == 5 else BlockKind.MAMBA2_MLP
    for i in range(40)
)

CONFIG = register_config(
    ModelConfig(
        name="granite-4.0-h-micro",
        source="https://huggingface.co/ibm-granite/granite-4.0-h-micro/blob/main/config.json",
        family="hybrid",
        num_layers=40,
        d_model=2048,
        vocab_size=100352,
        num_heads=32,
        num_kv_heads=8,
        head_dim=64,
        rope=False,
        attention_scale=0.015625,
        d_ff=8192,
        ssm=SSMConfig(state_dim=128, num_ssm_heads=64, head_dim=64, expand=2,
                      conv_width=4, chunk_size=256, norm_groups=1),
        block_pattern=_PATTERN,
        norm_eps=1e-5,
        tie_embeddings=True,
        embedding_multiplier=12.0,
        residual_multiplier=0.22,
        logits_scaling=8.0,
    )
)
