"""Assigned architecture configs (one module per architecture).

Importing this package registers every assigned config with
``repro.config.registry``. Each module cites its source in the config's
``source`` field and module docstring.
"""

from repro.configs import (  # noqa: F401
    granite_moe_1b_a400m,
    zamba2_7b,
    paligemma_3b,
    granite_3_8b,
    musicgen_large,
    qwen2_7b,
    llama4_maverick_400b_a17b,
    stablelm_1_6b,
    gemma3_27b,
    rwkv6_1_6b,
    paper_sgemm,
    granite_4_0_h_micro,
)

ASSIGNED_ARCHS = [
    "granite-moe-1b-a400m",
    "zamba2-7b",
    "paligemma-3b",
    "granite-3-8b",
    "musicgen-large",
    "qwen2-7b",
    "llama4-maverick-400b-a17b",
    "stablelm-1.6b",
    "gemma3-27b",
    "rwkv6-1.6b",
]
