"""RWKV-6 (Finch) WKV recurrence as a chunked Pallas scan.

Recurrence per head (state S in R^{N x V_dim}, data-dependent decay w_t):

    S_t = diag(exp(-exp(w_t))) S_{t-1} + k_t^T (v_t)          [outer product]
    o_t = (r_t S_t') with bonus:  o_t = r_t (S_{t-1} + diag(u) k_t^T v_t)

i.e. the current token's contribution is weighted by the "bonus" u instead
of the decay. TPU adaptation: the grid's time axis executes sequentially,
so the (N, V) state lives in VMEM scratch across chunk steps; inside a
chunk we run a fori_loop over timesteps with rank-1 updates (VPU work) —
the GEMM-heavy r/k/v/g projections stay OUTSIDE this kernel where the
space-time scheduler batches them across tenants.

Grid: (BH, T/chunk). Inputs are laid out (BH, T, N) per tensor.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_CHUNK = 128


def _wkv6_kernel(r_ref, k_ref, v_ref, w_ref, u_ref, o_ref, state_ref, *, chunk: int):
    t_blk = pl.program_id(1)

    @pl.when(t_blk == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    u = u_ref[0, 0]  # (N,)

    def step(i, state):
        r = r_ref[0, i]      # (N,)
        kk = k_ref[0, i]     # (N,)
        vv = v_ref[0, i]     # (V,)
        w = w_ref[0, i]      # (N,) decay logits
        decay = jnp.exp(-jnp.exp(w.astype(jnp.float32)))
        kv = jnp.outer(kk, vv).astype(jnp.float32)          # (N, V)
        out = (r[None, :].astype(jnp.float32) @ (state + u[:, None] * kv))[0]
        o_ref[0, i] = out.astype(o_ref.dtype)
        return decay[:, None] * state + kv

    state_ref[...] = jax.lax.fori_loop(0, chunk, step, state_ref[...])


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def wkv6_scan(
    r: jax.Array,
    k: jax.Array,
    v: jax.Array,
    w: jax.Array,
    u: jax.Array,
    *,
    chunk: int = DEFAULT_CHUNK,
    interpret: bool = False,
) -> jax.Array:
    """WKV6 linear-attention scan.

    Args:
        r, k, w: (BH, T, N) receptance / key / decay-logit per head.
        v: (BH, T, V) values.
        u: (BH, N) per-head bonus.
    Returns:
        (BH, T, V) outputs.
    """
    BH, T, N = r.shape
    V = v.shape[-1]
    out_dtype = r.dtype
    # the kernel reads one timestep row per loop step at a dynamic offset;
    # Mosaic can only prove such an offset aligned for unpacked 32-bit
    # rows, so 16-bit inputs are widened here (bf16 packs two rows per
    # sublane and the row load is refused)
    r, k, v, w, u = (a.astype(jnp.float32) for a in (r, k, v, w, u))
    chunk_ = min(chunk, T)
    Tp = pl.cdiv(T, chunk_) * chunk_
    if Tp != T:
        pad = ((0, 0), (0, Tp - T), (0, 0))
        r, k, v, w = (jnp.pad(a, pad) for a in (r, k, v, w))

    grid = (BH, Tp // chunk_)
    kernel = functools.partial(_wkv6_kernel, chunk=chunk_)

    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, chunk_, N), lambda bh, tb: (bh, tb, 0)),
            pl.BlockSpec((1, chunk_, N), lambda bh, tb: (bh, tb, 0)),
            pl.BlockSpec((1, chunk_, V), lambda bh, tb: (bh, tb, 0)),
            pl.BlockSpec((1, chunk_, N), lambda bh, tb: (bh, tb, 0)),
            # (1, 1, N): the last two block dims equal the array's own,
            # as the TPU lowering requires of a block narrower than (8, 128)
            pl.BlockSpec((1, 1, N), lambda bh, tb: (bh, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, chunk_, V), lambda bh, tb: (bh, tb, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, Tp, V), jnp.float32),
        scratch_shapes=[pltpu.VMEM((N, V), jnp.float32)],
        interpret=interpret,
    )(r, k, v, w, u.reshape(BH, 1, N))
    return out[:, :T, :].astype(out_dtype)
