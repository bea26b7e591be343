"""Mamba2 (SSD) block: chunked scan for train/prefill, O(1) decode step.

The state-space recurrence per head (state S in R^{P x N}):

    S_t = exp(A * dt_t) * S_{t-1} + dt_t * x_t (x) B_t
    y_t = S_t . C_t + D * x_t

Train/prefill uses the chunked (SSD) formulation: quadratic within a chunk
(MXU-friendly GEMMs) + a sequential inter-chunk state pass. Decode carries
(conv_state, ssm_state) per layer.
"""

from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from repro.config import ModelConfig
from repro.distributed.constraints import constrain
from repro.models import layers

Params = Dict[str, jax.Array]
Cache = Dict[str, jax.Array]


def _dims(cfg: ModelConfig):
    s = cfg.ssm
    assert s is not None
    d_inner = s.expand * cfg.d_model
    H = s.num_ssm_heads or d_inner // s.head_dim
    return d_inner, H, s.head_dim, s.state_dim


def mamba2_init(key: jax.Array, cfg: ModelConfig, dtype) -> Params:
    # NOTE: z/x/B/C/dt use SEPARATE projection matrices rather than one
    # fused in_proj. A fused (d, 2*d_inner+2N+H) projection splits at
    # offsets that don't align with the model-axis shard grid, and GSPMD
    # reshards every split piece (measured 46 GiB/step of f32 residual
    # all-gathers + odd-width collective-permutes on zamba2 train_4k).
    # Separate outputs are each individually shard-aligned; the extra
    # dispatches are free at MXU scale. Same total params/FLOPs.
    s = cfg.ssm
    d_inner, H, P, N = _dims(cfg)
    keys = jax.random.split(key, 8)
    return {
        "wz": layers.dense_init(keys[0], cfg.d_model, d_inner, dtype),
        "wx": layers.dense_init(keys[1], cfg.d_model, d_inner, dtype),
        "wB": layers.dense_init(keys[2], cfg.d_model, N, dtype),
        "wC": layers.dense_init(keys[3], cfg.d_model, N, dtype),
        "wdt": layers.dense_init(keys[4], cfg.d_model, H, dtype),
        "conv_x_w": (jax.random.normal(keys[5], (s.conv_width, d_inner)) * 0.1).astype(dtype),
        "conv_x_b": jnp.zeros((d_inner,), dtype),
        "conv_B_w": (jax.random.normal(keys[6], (s.conv_width, N)) * 0.1).astype(dtype),
        "conv_B_b": jnp.zeros((N,), dtype),
        "conv_C_w": (jax.random.normal(keys[7], (s.conv_width, N)) * 0.1).astype(dtype),
        "conv_C_b": jnp.zeros((N,), dtype),
        "A_log": jnp.log(jnp.linspace(1.0, 16.0, H)).astype(jnp.float32),
        "D": jnp.ones((H,), jnp.float32),
        "dt_bias": jnp.zeros((H,), jnp.float32),
        "norm": jnp.ones((d_inner,), dtype),
        "out_proj": layers.dense_init(keys[2], d_inner, cfg.d_model, dtype),
    }


def init_cache(cfg: ModelConfig, batch: int, dtype) -> Cache:
    s = cfg.ssm
    d_inner, H, P, N = _dims(cfg)
    w = s.conv_width - 1
    return {
        "conv_x": jnp.zeros((batch, w, d_inner), dtype),
        "conv_B": jnp.zeros((batch, w, N), dtype),
        "conv_C": jnp.zeros((batch, w, N), dtype),
        "ssm": jnp.zeros((batch, H, P, N), jnp.float32),
    }


def _causal_conv(x: jax.Array, w: jax.Array, b: jax.Array) -> jax.Array:
    """Depthwise causal conv. x (B,S,C), w (W,C) -> (B,S,C)."""
    W, C = w.shape
    lhs = x.transpose(0, 2, 1)                       # (B, C, S)
    rhs = w.T[:, None, :]                            # (C, 1, W)  OIH
    out = jax.lax.conv_general_dilated(
        lhs.astype(jnp.float32), rhs.astype(jnp.float32),
        window_strides=(1,), padding=[(W - 1, 0)],
        feature_group_count=C,
        dimension_numbers=("NCH", "OIH", "NCH"),
    )
    return (out.transpose(0, 2, 1) + b.astype(jnp.float32)).astype(x.dtype)


def ssd_scan(
    xh: jax.Array,      # (B, S, H, P)
    dt: jax.Array,      # (B, S, H)  post-softplus
    A: jax.Array,       # (H,)       negative
    Bm: jax.Array,      # (B, S, N)
    Cm: jax.Array,      # (B, S, N)
    chunk: int,
    init_state=None,    # (B, H, P, N) or None
) -> Tuple[jax.Array, jax.Array]:
    """Chunked SSD scan -> (y (B,S,H,P), final_state (B,H,P,N))."""
    B, S, H, P = xh.shape
    N = Bm.shape[-1]
    L = min(chunk, S)
    Sp = -(-S // L) * L
    if Sp != S:
        pad = Sp - S
        xh = jnp.pad(xh, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))  # dt=0 -> no decay, no input
        Bm = jnp.pad(Bm, ((0, 0), (0, pad), (0, 0)))
        Cm = jnp.pad(Cm, ((0, 0), (0, pad), (0, 0)))
    nc = Sp // L

    la = (dt * A[None, None, :]).reshape(B, nc, L, H).astype(jnp.float32)
    xbar = (xh * dt[..., None]).reshape(B, nc, L, H, P).astype(jnp.float32)
    Bc = Bm.reshape(B, nc, L, N).astype(jnp.float32)
    Cc = Cm.reshape(B, nc, L, N).astype(jnp.float32)
    cum = jnp.cumsum(la, axis=2)  # (B, nc, L, H)

    # ---- intra-chunk (quadratic in L, GEMM-shaped). The (B,nc,L,L,H)
    # decay tensor is the memory hot-spot; with H sharded over the `model`
    # mesh axis its per-chip slice is modest (~1.9 GB for zamba2 at
    # train_4k), so we keep the einsum whole and pin the sharding.
    # NOTE a lax.scan over head blocks was tried and REVERTED: the scan
    # iteration space can't carry the model-axis sharding, so every chip
    # recomputed all head blocks and GSPMD re-gathered H (59 GiB/step).
    scores = jnp.einsum("bcln,bcmn->bclm", Cc, Bc)
    mask = (jnp.arange(L)[:, None] >= jnp.arange(L)[None, :])[None, None, :, :, None]
    rel = cum[:, :, :, None, :] - cum[:, :, None, :, :]      # (B,nc,L,M,H)
    # clamp BEFORE exp: masked (l < m) entries have rel >> 0; exp(rel)
    # overflows and the where-VJP turns 0 * inf into NaN gradients.
    rel = jnp.where(mask, rel, 0.0)
    decay = jnp.where(mask, jnp.exp(rel), 0.0)
    decay = constrain(decay, "batch", None, None, None, "model")
    y_intra = jnp.einsum("bclm,bclmh,bcmhp->bclhp", scores, decay, xbar)

    # ---- per-chunk state contribution + decay
    last = cum[:, :, -1:, :]                                      # (B,nc,1,H)
    tail_decay = jnp.exp(last - cum)                              # (B,nc,L,H)
    chunk_state = jnp.einsum("bclh,bcln,bclhp->bchpn", tail_decay, Bc, xbar)
    chunk_decay = jnp.exp(last[:, :, 0, :])                       # (B,nc,H)

    # ---- inter-chunk sequential pass
    if init_state is None:
        init_state = jnp.zeros((B, H, P, N), jnp.float32)

    def step(state, inp):
        cdecay, cstate = inp  # (B,H), (B,H,P,N)
        new = cdecay[..., None, None] * state + cstate
        return new, state  # emit the state *before* this chunk

    final_state, before = jax.lax.scan(
        step,
        init_state.astype(jnp.float32),
        (chunk_decay.transpose(1, 0, 2), chunk_state.transpose(1, 0, 2, 3, 4)),
    )
    before = before.transpose(1, 0, 2, 3, 4)                      # (B,nc,H,P,N)

    y_inter = jnp.exp(cum)[..., None] * jnp.einsum("bcln,bchpn->bclhp", Cc, before)
    y = (y_intra + y_inter).reshape(B, Sp, H, P)[:, :S]
    return y.astype(xh.dtype), final_state


def _gated_norm(params: Params, y: jax.Array, z: jax.Array,
                cfg: ModelConfig) -> jax.Array:
    """RMSNorm of ``y * silu(z)`` over ``ssm.norm_groups`` groups of
    channels (0: one group per SSM head), times the norm's scale."""
    _, H, _, _ = _dims(cfg)
    groups = cfg.ssm.norm_groups or H
    return layers.groupnorm_heads(
        y * jax.nn.silu(z), groups, cfg.norm_eps) * params["norm"]


def _project(params: Params, x: jax.Array, cfg: ModelConfig):
    """Separate, shard-aligned z/x/B/C/dt projections."""
    z = x @ params["wz"]
    xs = x @ params["wx"]
    Bm = x @ params["wB"]
    Cm = x @ params["wC"]
    dt = x @ params["wdt"]
    if x.ndim == 3:
        z = constrain(z, "batch", None, "model")
        xs = constrain(xs, "batch", None, "model")
    return z, xs, Bm, Cm, dt


def mamba2_forward(
    params: Params,
    x: jax.Array,
    cfg: ModelConfig,
    *,
    return_cache: bool = False,
    init_cache_state: Cache = None,
) -> Tuple[jax.Array, Cache]:
    """Train/prefill forward. x: (B, S, d_model).

    init_cache_state: continuation prefill — conv tails and SSM state from
    a previous chunk (same structure as the returned cache).
    """
    s = cfg.ssm
    d_inner, H, P, N = _dims(cfg)
    B, S, _ = x.shape

    z, xs_raw, Bm_raw, Cm_raw, dt_raw = _project(params, x, cfg)

    def conv_with_history(raw, w, b, hist):
        if hist is None:
            return jax.nn.silu(_causal_conv(raw, w, b))
        # prepend the previous chunk's tail, drop the warm-up outputs
        ext = jnp.concatenate([hist.astype(raw.dtype), raw], axis=1)
        full = _causal_conv(ext, w, b)
        return jax.nn.silu(full[:, hist.shape[1]:, :])

    hist = init_cache_state
    xs = conv_with_history(
        xs_raw, params["conv_x_w"], params["conv_x_b"],
        None if hist is None else hist["conv_x"],
    )
    xs = constrain(xs, "batch", None, "model")
    Bm = conv_with_history(
        Bm_raw, params["conv_B_w"], params["conv_B_b"],
        None if hist is None else hist["conv_B"],
    )
    Cm = conv_with_history(
        Cm_raw, params["conv_C_w"], params["conv_C_b"],
        None if hist is None else hist["conv_C"],
    )

    dt = jax.nn.softplus(dt_raw.astype(jnp.float32) + params["dt_bias"])
    A = -jnp.exp(params["A_log"])
    xh = xs.reshape(B, S, H, P)
    y, final_state = ssd_scan(
        xh, dt, A, Bm, Cm, s.chunk_size,
        init_state=None if hist is None else hist["ssm"],
    )
    y = y + params["D"][None, None, :, None] * xh.astype(jnp.float32)
    # drop to the residual dtype BEFORE the gated norm: keeping (B,S,d_inner)
    # in f32 doubles the activation-collective bytes in the backward pass.
    y = y.reshape(B, S, d_inner).astype(x.dtype)
    y = constrain(y, "batch", None, "model")

    y = _gated_norm(params, y, z, cfg)
    out = y.astype(x.dtype) @ params["out_proj"]
    out = constrain(out, "batch", None, None)  # anchor the residual stream

    cache: Cache = {}
    if return_cache:
        W = s.conv_width

        def tail(a, h):
            if h is not None:
                a = jnp.concatenate([h.astype(a.dtype), a], axis=1)
            t = a[:, -(W - 1):, :]
            pad = (W - 1) - t.shape[1]
            return jnp.pad(t, ((0, 0), (pad, 0), (0, 0))) if pad > 0 else t

        cache = {
            "conv_x": tail(xs_raw, None if hist is None else hist["conv_x"]),
            "conv_B": tail(Bm_raw, None if hist is None else hist["conv_B"]),
            "conv_C": tail(Cm_raw, None if hist is None else hist["conv_C"]),
            "ssm": final_state,
        }
    return out, cache


def mamba2_decode(
    params: Params, x: jax.Array, cfg: ModelConfig, cache: Cache
) -> Tuple[jax.Array, Cache]:
    """One-token decode. x: (B, 1, d_model)."""
    s = cfg.ssm
    d_inner, H, P, N = _dims(cfg)
    B = x.shape[0]

    z, xs_raw, Bm_raw, Cm_raw, dt_raw = _project(params, x[:, 0, :], cfg)

    def conv_step(prev, new, w, b):
        window = jnp.concatenate([prev, new[:, None, :]], axis=1)  # (B, W, C)
        out = jnp.einsum("bwc,wc->bc", window.astype(jnp.float32), w.astype(jnp.float32))
        out = jax.nn.silu(out + b.astype(jnp.float32)).astype(x.dtype)
        return out, window[:, 1:, :]

    xs, new_conv_x = conv_step(cache["conv_x"], xs_raw, params["conv_x_w"], params["conv_x_b"])
    Bm, new_conv_B = conv_step(cache["conv_B"], Bm_raw, params["conv_B_w"], params["conv_B_b"])
    Cm, new_conv_C = conv_step(cache["conv_C"], Cm_raw, params["conv_C_w"], params["conv_C_b"])

    dt = jax.nn.softplus(dt_raw.astype(jnp.float32) + params["dt_bias"])  # (B, H)
    A = -jnp.exp(params["A_log"])
    decay = jnp.exp(dt * A[None, :])  # (B, H)
    xh = xs.reshape(B, H, P).astype(jnp.float32)

    state = cache["ssm"]
    state = decay[..., None, None] * state + jnp.einsum(
        "bh,bhp,bn->bhpn", dt, xh, Bm.astype(jnp.float32)
    )
    y = jnp.einsum("bhpn,bn->bhp", state, Cm.astype(jnp.float32))
    y = y + params["D"][None, :, None] * xh
    y = y.reshape(B, d_inner)

    y = _gated_norm(params, y, z, cfg)
    out = (y.astype(x.dtype) @ params["out_proj"])[:, None, :]
    return out, {
        "conv_x": new_conv_x,
        "conv_B": new_conv_B,
        "conv_C": new_conv_C,
        "ssm": state,
    }
