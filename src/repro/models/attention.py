"""GQA attention block: prefill (flash) and single-token decode paths.

Cache contract: each attention layer owns ``{"k": (B, Hkv, D, S_alloc),
"v": (B, Hkv, D, S_alloc)}``, positions last, where ``S_alloc`` is the
full sequence length for global layers and ``min(window, S)`` for
sliding-window layers (ring buffer). That is the layout the TPU stores
a head_dim-64 cache in, and the one the decode kernel reads, so the
decode step reads every layer where it lies. Keys are stored with RoPE
already applied, so ring-buffer slots stay position-correct. A NoPE
model (``cfg.rope`` off) rotates nothing, and ``cfg.attention_scale``,
where set, replaces 1/sqrt(head_dim).

Prefill writes its positions into the cache. The decode step does not
write inside the layer stack: ``attn_decode`` reads a ``KVRead`` view
(the layer stacks of K and V, which layer and which tenant) and returns
the new token's key and value rows, which ``write_rows`` puts at the
token's slot after the stack, every layer in one kernel call.
"""

from __future__ import annotations

import math
from typing import Dict, NamedTuple, Optional, Tuple

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
    shape = (batch, cfg.num_kv_heads, cfg.head_dim, s)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


class KVRead(NamedTuple):
    """What one attention layer's decode step reads: the stacks of K and
    V, ``(L, T, B, Hkv, D, S)`` (layer, tenant, slot, ...), and the layer
    and tenant of them that are this layer's."""

    k: jax.Array
    v: jax.Array
    layer: jax.Array
    tenant: jax.Array


def write_rows(cache: Cache, rows: Cache, lengths: jax.Array) -> Cache:
    """``cache`` with the new token's rows written at each slot's next
    position (``lengths % S_alloc``: the ring slot, or the position), in
    every layer, in place where the cache is donated.

    ``cache`` leaves are ``(*L, *N, Hkv, D, S)`` and ``rows`` leaves
    ``(*L, *N, Hkv, D)``, where ``N`` is ``lengths``' shape (slots, or
    tenants and slots) and ``L`` a layer axis or none."""
    *tenants, B = lengths.shape
    T = math.prod(tenants)
    layered = rows["k"].ndim - 2 > lengths.ndim
    tenant = jnp.repeat(jnp.arange(T, dtype=jnp.int32), B)
    slot = jnp.tile(jnp.arange(B, dtype=jnp.int32), T)

    def put(c, r):
        L = c.shape[0] if layered else 1
        new = ops.write_rows(
            c.reshape((L, T, B) + c.shape[-3:]),
            r.reshape((L, T * B) + r.shape[-2:]), tenant, slot,
            (lengths.reshape(-1) % c.shape[-1]).astype(jnp.int32))
        return new.reshape(c.shape)

    return {"k": put(cache["k"], rows["k"]), "v": put(cache["v"], rows["v"])}


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

    # the cache keeps positions last: (B, Hkv, D, S)
    k, v = (x.swapaxes(2, 3).astype(cache["k"].dtype) for x in (k, v))
    s_alloc = cache["k"].shape[3]
    if s_alloc >= S:
        new_k = jax.lax.dynamic_update_slice(cache["k"], k, (0, 0, 0, 0))
        new_v = jax.lax.dynamic_update_slice(cache["v"], v, (0, 0, 0, 0))
    else:
        # sliding ring buffer: keep the last s_alloc keys, aligned to ring
        # slot (S - s_alloc) % s_alloc onward; store rolled so that slot
        # i holds position (S - s_alloc + i) ... ring write index = pos % s_alloc.
        shift = (S - s_alloc) % s_alloc
        new_k = jnp.roll(k[..., S - s_alloc:], shift, axis=3)
        new_v = jnp.roll(v[..., S - s_alloc:], shift, axis=3)
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

    new_k, new_v = (
        jax.lax.dynamic_update_slice(
            c, x.swapaxes(2, 3).astype(c.dtype), (0, 0, 0, start))
        for c, x in ((cache["k"], k), (cache["v"], v)))

    s_alloc = new_k.shape[3]
    attn_fn = ref.attention_chunked if s_alloc > 2048 else ref.attention
    o = attn_fn(q, new_k.swapaxes(2, 3), new_v.swapaxes(2, 3), causal=True,
                q_offset=start, scale=_scale(cfg))
    o = o.transpose(0, 2, 1, 3).reshape(B, S, cfg.num_heads * cfg.head_dim)
    return o @ params["wo"], {"k": new_k, "v": new_v}


def attn_decode(
    params: Params,
    x: jax.Array,
    cfg: ModelConfig,
    kind: AttentionKind,
    cache: KVRead,
    lengths: jax.Array,
) -> Tuple[jax.Array, Cache]:
    """One-token decode. x: (B, 1, d_model); lengths: (B,) tokens already
    cached. Reads the cache in place and writes nothing: returns the
    output and the new token's rows ``{"k", "v"}: (B, Hkv, D)`` in the
    cache's dtype, for ``write_rows``."""
    del kind  # a ring cache is told by its length: the kernel masks it
    B = x.shape[0]
    q, k, v = _project_qkv(params, x, cfg)  # (B, H, 1, D)
    positions = lengths[:, None]  # new token's absolute position
    q, k = _rotate(q, k, positions, cfg)
    rows = {"k": k[:, :, 0, :].astype(cache.k.dtype),
            "v": v[:, :, 0, :].astype(cache.v.dtype)}
    o = ops.decode_attention(q[:, :, 0, :], rows["k"], rows["v"], cache.k,
                             cache.v, lengths, cache.layer, cache.tenant,
                             scale=_scale(cfg))
    o = o.reshape(B, 1, cfg.num_heads * cfg.head_dim)
    return o @ params["wo"], rows
