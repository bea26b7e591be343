"""Operations and bytes of the served Mamba2 + attention hybrid, by
layer kind, from its shapes.

A multiply-add counts two operations. Bytes are what the work cannot do
without: every weight read once per program; in each Mamba2 layer each
decoded token's conv tails and SSM state read and written once; in each
attention layer the KV cache read up to the token's length and one
position written. Weights and conv tails are in the served dtype; the
SSM state and ``A_log``, ``D``, ``dt_bias`` are float32, as the program
keeps them. The attention layers' counts are ``counts/transformer.py``'s
with this model's heads.
"""

from __future__ import annotations

from typing import Any, Dict, Tuple

from chipbench.counts import transformer as tc

#: bytes of the float32 SSM state and per-head vectors
F32 = 4


class Dims(tc.Dims):
    def __init__(self, c: Dict[str, Any]):
        super().__init__(dict(c, intermediate_size=c["shared_intermediate_size"]))
        types = list(c["layer_types"])
        self.n_mamba = types.count("mamba")
        self.n_attn = types.count("attention")
        if self.n_mamba + self.n_attn != self.layers:
            raise ValueError("layer_types holds a kind other than mamba and "
                             "attention, or not one per layer")
        self.m_heads = int(c["mamba_n_heads"])
        self.m_head_dim = int(c["mamba_d_head"])
        self.m_state = int(c["mamba_d_state"])
        self.m_conv = int(c["mamba_d_conv"])
        self.d_inner = int(c["mamba_expand"]) * self.d
        self.tied = bool(c["tie_word_embeddings"])


# --------------------------------------------------------------- parameters
def mlp_params(z: Dims) -> int:
    return 3 * z.d * z.ff


def attention_matmul_params(z: Dims) -> int:
    """q, k, v, o of one attention layer."""
    q = z.heads * z.head_dim
    kv = z.kv_heads * z.head_dim
    return z.d * q + 2 * z.d * kv + q * z.d


def mamba_matmul_params(z: Dims) -> int:
    """z, x, B, C, dt projections and out_proj of one Mamba2 layer."""
    return (z.d * (2 * z.d_inner + 2 * z.m_state + z.m_heads)
            + z.d_inner * z.d)


def _conv_channels(z: Dims) -> int:
    return z.d_inner + 2 * z.m_state


def mamba_small_params(z: Dims) -> Tuple[int, int]:
    """(in the served dtype, in float32): conv weights and biases and the
    gated norm's scale; ``A_log``, ``D`` and ``dt_bias``."""
    return (z.m_conv * _conv_channels(z) + _conv_channels(z) + z.d_inner,
            3 * z.m_heads)


def _layer_matmul_params(z: Dims) -> int:
    return (z.n_mamba * mamba_matmul_params(z)
            + z.n_attn * attention_matmul_params(z)
            + z.layers * mlp_params(z))


def tenant_params(z: Dims) -> int:
    """Every parameter of one tenant: the layers with their two norm
    scales, the embedding, the final norm and, untied, the head."""
    served, f32 = mamba_small_params(z)
    head = 0 if z.tied else z.d * z.vocab
    return (_layer_matmul_params(z) + z.n_mamba * (served + f32)
            + z.layers * 2 * z.d + z.vocab * z.d + z.d + head)


def tenant_weight_bytes(z: Dims) -> int:
    """One tenant's weights that a decode step reads: every layer, the
    final norm and the output head (the tied embedding table, read whole
    as the head; the embedding gather reads rows only)."""
    served, f32 = mamba_small_params(z)
    return ((_layer_matmul_params(z) + z.n_mamba * served
             + z.layers * 2 * z.d + z.d + z.d * z.vocab) * z.elem
            + z.n_mamba * f32 * F32)


# --------------------------------------------------------------------- state
def mamba_state_bytes(z: Dims) -> int:
    """One slot's state in one Mamba2 layer: the conv tails of x, B and C
    (``d_conv - 1`` positions) and the float32 SSM state (heads x head
    dim x state)."""
    return ((z.m_conv - 1) * _conv_channels(z) * z.elem
            + z.m_heads * z.m_head_dim * z.m_state * F32)


def slot_state_bytes(z: Dims, cache_len: int) -> int:
    """One slot's whole cache: every Mamba2 layer's state and every
    attention layer's keys and values at ``cache_len`` positions."""
    return (z.n_mamba * mamba_state_bytes(z)
            + z.n_attn * tc.kv_bytes(z, cache_len))


def decode_token_bytes(z: Dims, context: int) -> int:
    """The cache traffic of one new token over ``context`` positions
    (itself included): each Mamba2 layer's state read and written, each
    attention layer's keys and values read up to the token and one
    position written."""
    return (2 * z.n_mamba * mamba_state_bytes(z)
            + z.n_attn * (tc.kv_bytes(z, context - 1) + tc.kv_bytes(z, 1)))


# ---------------------------------------------------------------- operations
def recurrence_flops(z: Dims) -> float:
    """One token through one Mamba2 layer's recurrence, beside its
    matmuls: the state update and its readout (4 H P N), and the depthwise
    conv over x, B and C."""
    return (4.0 * z.m_heads * z.m_head_dim * z.m_state
            + 2.0 * z.m_conv * _conv_channels(z))


def decode_token_flops(z: Dims, context: int) -> float:
    """One new token over ``context`` positions (itself included)."""
    return (2.0 * (_layer_matmul_params(z) + z.d * z.vocab)
            + z.n_mamba * recurrence_flops(z)
            + z.n_attn * tc.attention_flops(z, 1, context))


def prefill_flops(z: Dims, prompt: int) -> float:
    """A prompt's prefill: every position through every layer, the
    recurrence in each Mamba2 layer, causal attention in each attention
    layer, and the output head at the last position."""
    return (2.0 * prompt * _layer_matmul_params(z)
            + z.n_mamba * prompt * recurrence_flops(z)
            + z.n_attn * tc.attention_flops(z, prompt, (prompt + 1) / 2.0)
            + 2.0 * z.d * z.vocab)
