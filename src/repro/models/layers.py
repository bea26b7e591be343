"""Shared primitive layers: norms, RoPE, MLPs, embeddings.

All layers are pure functions of (params, inputs); params are nested dicts
of arrays so they stack/shard/vmap trivially (the tenant axis of the
space-time scheduler is a vmap over these pytrees).
"""

from __future__ import annotations

import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.distributed.constraints import constrain

Params = Dict[str, jax.Array]


# --------------------------------------------------------------------------- init
def dense_init(key: jax.Array, d_in: int, d_out: int, dtype) -> jax.Array:
    scale = 1.0 / math.sqrt(d_in)
    return (jax.random.truncated_normal(key, -2.0, 2.0, (d_in, d_out)) * scale).astype(dtype)


def embed_init(key: jax.Array, vocab: int, d: int, dtype) -> jax.Array:
    return (jax.random.truncated_normal(key, -2.0, 2.0, (vocab, d)) * 0.02).astype(dtype)


# --------------------------------------------------------------------------- norm
def rmsnorm_init(d: int, dtype) -> Params:
    return {"scale": jnp.ones((d,), dtype)}


def rmsnorm(params: Params, x: jax.Array, eps: float = 1e-6) -> jax.Array:
    """RMSNorm with f32 kept ONLY for the variance reduction.

    The normalized tensor itself stays in the residual dtype: materializing
    an f32 copy of (B,S,d) makes XLA hoist the convert before GSPMD's
    resharding collectives, doubling all-gather/all-reduce bytes of the
    residual stream (measured ~25 GiB/step on zamba2 train_4k).
    """
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    inv = jax.lax.rsqrt(var + eps).astype(x.dtype)
    return x * inv * params["scale"].astype(x.dtype)


def groupnorm_heads(x: jax.Array, num_heads: int, eps: float = 1e-6) -> jax.Array:
    """RMS normalization over the trailing dim split into ``num_heads``
    equal groups.

    Used by RWKV6 (ln_x over heads) and Mamba2's gated norm (over its
    ``norm_groups``, one group per head by default).
    x: (..., H*P) -> normalized per (H,) group.
    """
    shape = x.shape
    xh = x.reshape(*shape[:-1], num_heads, shape[-1] // num_heads).astype(jnp.float32)
    var = jnp.mean(jnp.square(xh), axis=-1, keepdims=True)
    out = xh * jax.lax.rsqrt(var + eps)
    return out.reshape(shape).astype(x.dtype)


# --------------------------------------------------------------------------- rope
def rope_frequencies(head_dim: int, theta: float) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """Rotary position embedding.

    x: (..., S, D) with D even; positions: broadcastable to (..., S).
    """
    D = x.shape[-1]
    freqs = rope_frequencies(D, theta)  # (D/2,)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (..., S, D/2)
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# --------------------------------------------------------------------------- mlp
def mlp_init(key: jax.Array, d_model: int, d_ff: int, gated: bool, dtype) -> Params:
    keys = jax.random.split(key, 3)
    p: Params = {
        "up": dense_init(keys[0], d_model, d_ff, dtype),
        "down": dense_init(keys[1], d_ff, d_model, dtype),
    }
    if gated:
        p["gate"] = dense_init(keys[2], d_model, d_ff, dtype)
    return p


def mlp(params: Params, x: jax.Array, gated: bool) -> jax.Array:
    up = x @ params["up"]
    if gated:
        h = jax.nn.silu(x @ params["gate"]) * up
    else:
        h = jax.nn.gelu(up)
    if h.ndim == 3:
        h = constrain(h, "batch", None, "model")
    return h @ params["down"]


# --------------------------------------------------------------------------- misc
def softcap(x: jax.Array, cap: float) -> jax.Array:
    if cap <= 0.0:
        return x
    return cap * jnp.tanh(x / cap)


def cross_entropy(
    logits: jax.Array, labels: jax.Array, mask: Optional[jax.Array] = None
) -> jax.Array:
    """Mean next-token cross-entropy. logits (B,S,V), labels (B,S).

    The gold logit is selected with an iota-compare-select reduction rather
    than take_along_axis: a vocab-dim gather forces GSPMD to all-gather
    vocab-sharded logits onto every device, while the select form stays
    elementwise on the shard and reduces with a cheap psum.
    """
    logits = logits.astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    vocab_iota = jax.lax.broadcasted_iota(labels.dtype, logits.shape, logits.ndim - 1)
    gold = jnp.sum(
        jnp.where(vocab_iota == labels[..., None], logits, 0.0), axis=-1
    )
    nll = logz - gold
    if mask is not None:
        return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    return jnp.mean(nll)
