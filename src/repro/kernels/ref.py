"""Pure-jnp oracles for every Pallas kernel.

These are the semantic ground truth: tests assert_allclose each kernel
against these across shape/dtype sweeps, and ``ops.py`` routes to them on
CPU (where Pallas interpret mode would be orders of magnitude slower than
XLA:CPU) and inside the 512-device dry-run (where interpret-mode grids
would unroll into enormous HLO).
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def batched_gemm(x: jax.Array, w: jax.Array) -> jax.Array:
    """out[r] = x[r] @ w[r]; x (R,M,K), w (R,K,N)."""
    return jnp.einsum("rmk,rkn->rmn", x, w)


def grouped_gemm(x: jax.Array, w: jax.Array, block_groups: jax.Array, bm: int) -> jax.Array:
    """out[i-th row block] = x_block @ w[block_groups[i]]."""
    T, K = x.shape
    nblk = T // bm
    xb = x.reshape(nblk, bm, K)
    wb = w[block_groups]  # (nblk, K, N)
    return jnp.einsum("bmk,bkn->bmn", xb, wb).reshape(T, -1)


def attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    window: int = 0,
    scale: Optional[float] = None,
    logit_softcap: float = 0.0,
    q_offset=None,
) -> jax.Array:
    """Dense reference attention. q (B,Hq,Sq,D), k/v (B,Hkv,Skv,D).

    q_offset: absolute position of the first query (default Skv - Sq —
    queries are the suffix). May be a traced scalar (chunked prefill).
    """
    B, Hq, Sq, D = q.shape
    _, Hkv, Skv, _ = k.shape
    q_per_kv = Hq // Hkv
    scale = scale if scale is not None else D ** -0.5
    qg = q.reshape(B, Hkv, q_per_kv, Sq, D)
    s = jnp.einsum("bhgqd,bhkd->bhgqk", qg.astype(jnp.float32), k.astype(jnp.float32))
    s *= scale
    if logit_softcap > 0.0:
        s = logit_softcap * jnp.tanh(s / logit_softcap)
    if q_offset is None:
        q_offset = Skv - Sq
    q_pos = jnp.arange(Sq)[:, None] + q_offset
    kv_pos = jnp.arange(Skv)[None, :]
    mask = jnp.ones((Sq, Skv), dtype=bool)
    if causal:
        mask &= q_pos >= kv_pos
    if window > 0:
        mask &= (q_pos - kv_pos) < window
    s = jnp.where(mask, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhgqk,bhkd->bhgqd", p, v.astype(jnp.float32))
    return o.reshape(B, Hq, Sq, D).astype(q.dtype)


def attention_chunked(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    window: int = 0,
    scale: Optional[float] = None,
    logit_softcap: float = 0.0,
    kv_chunk: int = 1024,
    q_offset=None,
) -> jax.Array:
    """O(S)-memory attention: lax.scan over KV chunks with online softmax.

    This is "flash attention in XLA" — the pure-jnp path for long sequences
    (the dense reference would materialize a (B,H,Sq,Skv) score tensor,
    which at 32k-500k context is unlowerable). Semantics identical to
    ``attention``.
    """
    B, Hq, Sq, D = q.shape
    _, Hkv, Skv, _ = k.shape
    q_per_kv = Hq // Hkv
    scale = scale if scale is not None else D ** -0.5
    C = min(kv_chunk, Skv)
    nc = -(-Skv // C)
    pad = nc * C - Skv
    if pad:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0)))

    qg = q.reshape(B, Hkv, q_per_kv, Sq, D).astype(jnp.float32) * scale
    kc = k.reshape(B, Hkv, nc, C, D).transpose(2, 0, 1, 3, 4)  # (nc,B,Hkv,C,D)
    vc = v.reshape(B, Hkv, nc, C, D).transpose(2, 0, 1, 3, 4)
    if q_offset is None:
        q_offset = Skv - Sq
    q_pos = jnp.arange(Sq) + q_offset

    def step(carry, inp):
        m, l, acc, jc = carry
        kb, vb = inp
        s = jnp.einsum("bhgqd,bhcd->bhgqc", qg, kb.astype(jnp.float32))
        if logit_softcap > 0.0:
            s = logit_softcap * jnp.tanh(s / logit_softcap)
        kv_pos = jc * C + jnp.arange(C)
        mask = kv_pos[None, :] < Skv
        if causal:
            mask &= q_pos[:, None] >= kv_pos[None, :]
        if window > 0:
            mask &= (q_pos[:, None] - kv_pos[None, :]) < window
        s = jnp.where(mask[None, None, None], s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.where(mask[None, None, None], jnp.exp(s - m_new), 0.0)
        alpha = jnp.exp(m - m_new)
        l = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc = acc * alpha + jnp.einsum("bhgqc,bhcd->bhgqd", p, vb.astype(jnp.float32))
        return (m_new, l, acc, jc + 1), None

    m0 = jnp.full((B, Hkv, q_per_kv, Sq, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, Hkv, q_per_kv, Sq, 1), jnp.float32)
    a0 = jnp.zeros((B, Hkv, q_per_kv, Sq, D), jnp.float32)
    (m, l, acc, _), _ = jax.lax.scan(step, (m0, l0, a0, 0), (kc, vc))
    l = jnp.where(l == 0.0, 1.0, l)
    out = (acc / l).reshape(B, Hq, Sq, D)
    return out.astype(q.dtype)


def decode_attention(
    q: jax.Array,
    k_new: jax.Array,
    v_new: jax.Array,
    k_stack: jax.Array,
    v_stack: jax.Array,
    lengths: jax.Array,
    layer,
    tenant,
    *,
    scale: Optional[float] = None,
) -> jax.Array:
    """Single-token decode. q (B,Hq,D); the new token's k_new/v_new
    (B,Hkv,D); stacks (L,T,B,Hkv,D,S) read at ``[layer, tenant]``;
    lengths (B,) tokens cached before the new one. The new token attends
    to the live cache positions and to itself; a full ring (S <= length)
    drops the slot the new token replaces, ``length % S``."""
    B, Hq, D = q.shape
    Hkv, S = k_stack.shape[3], k_stack.shape[5]
    q_per_kv = Hq // Hkv
    scale = scale if scale is not None else D ** -0.5
    k = k_stack[layer, tenant].astype(jnp.float32)  # (B, Hkv, D, S)
    v = v_stack[layer, tenant].astype(jnp.float32)
    qg = q.reshape(B, Hkv, q_per_kv, D).astype(jnp.float32)
    s = jnp.einsum("bhgd,bhds->bhgs", qg, k)
    s_new = jnp.einsum("bhgd,bhd->bhg", qg, k_new.astype(jnp.float32))
    s = jnp.concatenate([s, s_new[..., None]], axis=-1) * scale
    pos = jnp.arange(S + 1)[None, :]
    drop = jnp.where(lengths >= S, lengths % S, -1)[:, None]
    mask = ((pos < jnp.minimum(lengths, S)[:, None]) & (pos != drop)) | (
        pos == S)  # (B, S + 1): the live cache, and the new token
    s = jnp.where(mask[:, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = (jnp.einsum("bhgs,bhds->bhgd", p[..., :S], v)
         + p[..., S:] * v_new.astype(jnp.float32)[:, :, None, :])
    return o.reshape(B, Hq, D).astype(q.dtype)


def write_rows(stack: jax.Array, rows: jax.Array, tenant: jax.Array,
               slot: jax.Array, pos: jax.Array) -> jax.Array:
    """stack (L,T,B,Hkv,D,S) with rows (L,N,Hkv,D) at
    ``[:, tenant[n], slot[n], :, :, pos[n]]``."""
    return stack.at[:, tenant, slot, :, :, pos].set(
        jnp.moveaxis(rows, 1, 0).astype(stack.dtype))


def wkv6_scan(
    r: jax.Array, k: jax.Array, v: jax.Array, w: jax.Array, u: jax.Array,
    init_state: Optional[jax.Array] = None,
) -> jax.Array:
    """Sequential-scan oracle for the WKV6 recurrence.

    r/k/w: (BH, T, N); v: (BH, T, V); u: (BH, N); optional init_state
    (BH, N, V) for continuation prefill -> (BH, T, V).
    """
    BH, T, N = r.shape
    V = v.shape[-1]
    if init_state is None:
        init_state = jnp.zeros((BH, N, V), jnp.float32)

    def head(r_h, k_h, v_h, w_h, u_h, s0):
        def step(state, inputs):
            r_t, k_t, v_t, w_t = inputs
            decay = jnp.exp(-jnp.exp(w_t.astype(jnp.float32)))
            kv = jnp.outer(k_t, v_t).astype(jnp.float32)
            out = r_t.astype(jnp.float32) @ (state + u_h[:, None] * kv)
            new_state = decay[:, None] * state + kv
            return new_state, out

        _, outs = jax.lax.scan(step, s0, (r_h, k_h, v_h, w_h))
        return outs

    out = jax.vmap(head)(r, k, v, w, u.astype(jnp.float32),
                         init_state.astype(jnp.float32))
    return out.astype(r.dtype)


def wkv6_step(
    state: jax.Array,
    r: jax.Array,
    k: jax.Array,
    v: jax.Array,
    w: jax.Array,
    u: jax.Array,
):
    """One decode step of WKV6. state (BH,N,V); r/k/w (BH,N); v (BH,V); u (BH,N)."""
    decay = jnp.exp(-jnp.exp(w.astype(jnp.float32)))
    kv = jnp.einsum("bn,bv->bnv", k.astype(jnp.float32), v.astype(jnp.float32))
    out = jnp.einsum(
        "bn,bnv->bv", r.astype(jnp.float32), state + u[..., None] * kv
    )
    new_state = decay[..., None] * state + kv
    return new_state, out.astype(r.dtype)
