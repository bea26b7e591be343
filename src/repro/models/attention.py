"""GQA attention block: prefill (flash) and single-token decode paths.

Cache contract: each attention layer owns ``{"k": (B, Hkv, S_alloc, D),
"v": (B, Hkv, S_alloc, D)}`` where ``S_alloc`` is the full sequence length
for global layers and ``min(window, S)`` for sliding-window layers (ring
buffer). Keys are stored with RoPE already applied, so ring-buffer slots
stay position-correct. A NoPE model (``cfg.rope`` off) rotates nothing,
and ``cfg.attention_scale``, where set, replaces 1/sqrt(head_dim).
"""

from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.config import AttentionKind, ModelConfig
from repro.distributed.constraints import constrain
from repro.kernels import ops
from repro.models import layers

Params = Dict[str, jax.Array]
Cache = Dict[str, jax.Array]


def attn_init(key: jax.Array, cfg: ModelConfig, dtype) -> Params:
    keys = jax.random.split(key, 4)
    d, hd = cfg.d_model, cfg.head_dim
    p: Params = {
        "wq": layers.dense_init(keys[0], d, cfg.num_heads * hd, dtype),
        "wk": layers.dense_init(keys[1], d, cfg.num_kv_heads * hd, dtype),
        "wv": layers.dense_init(keys[2], d, cfg.num_kv_heads * hd, dtype),
        "wo": layers.dense_init(keys[3], cfg.num_heads * hd, d, dtype),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((cfg.num_heads * hd,), dtype)
        p["bk"] = jnp.zeros((cfg.num_kv_heads * hd,), dtype)
        p["bv"] = jnp.zeros((cfg.num_kv_heads * hd,), dtype)
    return p


def cache_alloc_len(cfg: ModelConfig, kind: AttentionKind, seq_len: int) -> int:
    if kind == AttentionKind.SLIDING and cfg.sliding_window > 0:
        return min(cfg.sliding_window, seq_len)
    return seq_len


def init_cache(
    cfg: ModelConfig, kind: AttentionKind, batch: int, seq_len: int, dtype
) -> Cache:
    s = cache_alloc_len(cfg, kind, seq_len)
    shape = (batch, cfg.num_kv_heads, s, cfg.head_dim)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def _project_qkv(params: Params, x: jax.Array, cfg: ModelConfig):
    q = x @ params["wq"]
    k = x @ params["wk"]
    v = x @ params["wv"]
    if cfg.qkv_bias:
        q = q + params["bq"]
        k = k + params["bk"]
        v = v + params["bv"]
    B = x.shape[0]
    S = x.shape[1] if x.ndim == 3 else 1
    q = q.reshape(B, S, cfg.num_heads, cfg.head_dim).transpose(0, 2, 1, 3)
    k = k.reshape(B, S, cfg.num_kv_heads, cfg.head_dim).transpose(0, 2, 1, 3)
    v = v.reshape(B, S, cfg.num_kv_heads, cfg.head_dim).transpose(0, 2, 1, 3)
    q = constrain(q, "batch", "model", None, None)
    k = constrain(k, "batch", "model", None, None)
    v = constrain(v, "batch", "model", None, None)
    return q, k, v


def _rotate(q: jax.Array, k: jax.Array, positions: jax.Array,
            cfg: ModelConfig) -> Tuple[jax.Array, jax.Array]:
    """Rotary embedding of q and k at ``positions`` (B, S); NoPE models
    (``cfg.rope`` off) take them as they are."""
    if not cfg.rope:
        return q, k
    return (layers.apply_rope(q, positions[:, None, :], cfg.rope_theta),
            layers.apply_rope(k, positions[:, None, :], cfg.rope_theta))


def _scale(cfg: ModelConfig) -> Optional[float]:
    """The score scale, where the model sets one (else the kernels'
    1/sqrt(head_dim))."""
    return cfg.attention_scale or None


def attn_forward(
    params: Params,
    x: jax.Array,
    cfg: ModelConfig,
    kind: AttentionKind,
    positions: Optional[jax.Array] = None,
) -> jax.Array:
    """Full-sequence (train / prefill) attention. x: (B, S, d_model)."""
    B, S, _ = x.shape
    if positions is None:
        positions = jnp.arange(S)[None, :].repeat(B, axis=0)
    q, k, v = _project_qkv(params, x, cfg)
    q, k = _rotate(q, k, positions, cfg)
    window = cfg.sliding_window if kind == AttentionKind.SLIDING else 0
    o = ops.flash_attention(q, k, v, causal=True, window=window,
                            scale=_scale(cfg))
    o = o.transpose(0, 2, 1, 3).reshape(B, S, cfg.num_heads * cfg.head_dim)
    return o @ params["wo"]


def attn_prefill_with_cache(
    params: Params,
    x: jax.Array,
    cfg: ModelConfig,
    kind: AttentionKind,
    cache: Cache,
) -> Tuple[jax.Array, Cache]:
    """Prefill that also fills the KV cache (fresh sequences, positions 0..S)."""
    B, S, _ = x.shape
    positions = jnp.arange(S)[None, :].repeat(B, axis=0)
    q, k, v = _project_qkv(params, x, cfg)
    q, k = _rotate(q, k, positions, cfg)
    window = cfg.sliding_window if kind == AttentionKind.SLIDING else 0
    o = ops.flash_attention(q, k, v, causal=True, window=window,
                            scale=_scale(cfg))
    o = o.transpose(0, 2, 1, 3).reshape(B, S, cfg.num_heads * cfg.head_dim)

    s_alloc = cache["k"].shape[2]
    if s_alloc >= S:
        new_k = jax.lax.dynamic_update_slice(cache["k"], k.astype(cache["k"].dtype), (0, 0, 0, 0))
        new_v = jax.lax.dynamic_update_slice(cache["v"], v.astype(cache["v"].dtype), (0, 0, 0, 0))
    else:
        # sliding ring buffer: keep the last s_alloc keys, aligned to ring
        # slot (S - s_alloc) % s_alloc onward; store rolled so that slot
        # i holds position (S - s_alloc + i) ... ring write index = pos % s_alloc.
        tail_k = k[:, :, S - s_alloc :, :]
        tail_v = v[:, :, S - s_alloc :, :]
        shift = (S - s_alloc) % s_alloc
        new_k = jnp.roll(tail_k, shift, axis=2).astype(cache["k"].dtype)
        new_v = jnp.roll(tail_v, shift, axis=2).astype(cache["v"].dtype)
    return o @ params["wo"], {"k": new_k, "v": new_v}


def attn_prefill_continue(
    params: Params,
    x: jax.Array,
    cfg: ModelConfig,
    kind: AttentionKind,
    cache: Cache,
    start: jax.Array,
) -> Tuple[jax.Array, Cache]:
    """Chunked-prefill continuation: process S new tokens starting at
    absolute position ``start`` (traced scalar, same for all rows), with
    ``start`` tokens already in the cache.

    Linear (non-ring) caches only: slot == position, so causal masking
    against the full cache is exact and stale slots beyond start+S are
    excluded by causality. Sliding-window (ring) layers would need
    per-slot position tracking — not supported; callers fall back to
    exact-length prefill for those architectures.
    """
    if kind == AttentionKind.SLIDING and cfg.sliding_window > 0:
        raise NotImplementedError(
            "chunked prefill is not supported for sliding-window (ring-cache) layers"
        )
    from repro.kernels import ref  # traced q_offset needs the jnp path

    B, S, _ = x.shape
    positions = start + jnp.arange(S)[None, :].repeat(B, axis=0)
    q, k, v = _project_qkv(params, x, cfg)
    q, k = _rotate(q, k, positions, cfg)

    new_k = jax.lax.dynamic_update_slice(
        cache["k"], k.astype(cache["k"].dtype), (0, 0, start, 0)
    )
    new_v = jax.lax.dynamic_update_slice(
        cache["v"], v.astype(cache["v"].dtype), (0, 0, start, 0)
    )

    s_alloc = new_k.shape[2]
    attn_fn = ref.attention_chunked if s_alloc > 2048 else ref.attention
    o = attn_fn(q, new_k, new_v, causal=True, q_offset=start,
                scale=_scale(cfg))
    o = o.transpose(0, 2, 1, 3).reshape(B, S, cfg.num_heads * cfg.head_dim)
    return o @ params["wo"], {"k": new_k, "v": new_v}


def attn_decode(
    params: Params,
    x: jax.Array,
    cfg: ModelConfig,
    kind: AttentionKind,
    cache: Cache,
    lengths: jax.Array,
) -> Tuple[jax.Array, Cache]:
    """One-token decode. x: (B, 1, d_model); lengths: (B,) tokens already cached."""
    B = x.shape[0]
    q, k, v = _project_qkv(params, x, cfg)  # (B, H, 1, D)
    positions = lengths[:, None]  # new token's absolute position
    q, k = _rotate(q, k, positions, cfg)

    s_alloc = cache["k"].shape[2]
    slot = (lengths % s_alloc).astype(jnp.int32)  # ring slot (== lengths when global)

    def write(c, kv):
        # c: (Hkv, S, D), kv: (Hkv, 1, D), slot scalar
        def upd(c, kv, s):
            return jax.lax.dynamic_update_slice(c, kv.astype(c.dtype), (0, s, 0))
        return upd
    new_k = jax.vmap(
        lambda c, kv, s: jax.lax.dynamic_update_slice(c, kv.astype(c.dtype), (0, s, 0))
    )(cache["k"], k, slot)
    new_v = jax.vmap(
        lambda c, kv, s: jax.lax.dynamic_update_slice(c, kv.astype(c.dtype), (0, s, 0))
    )(cache["v"], v, slot)

    live = jnp.minimum(lengths + 1, s_alloc).astype(jnp.int32)
    o = ops.decode_attention(q[:, :, 0, :], new_k, new_v, live,
                             scale=_scale(cfg))
    o = o.reshape(B, 1, cfg.num_heads * cfg.head_dim)
    return o @ params["wo"], {"k": new_k, "v": new_v}
