"""Jit'd dispatch layer over the Pallas kernels.

Backend policy:
    * On TPU: compile the Pallas kernels (interpret=False).
    * On CPU: route to the pure-jnp reference by default — XLA:CPU executes
      the fused einsum far faster than interpret-mode grid emulation, and
      the 512-device dry-run must not unroll interpret grids into HLO.
    * ``REPRO_FORCE_PALLAS=1`` (or force_pallas=True) forces interpret-mode
      Pallas on CPU — used by the kernel-vs-oracle test sweeps.

Every public op takes/returns plain arrays so the scheduler, models and
serving engine never branch on backend themselves.
"""

from __future__ import annotations

import functools
import os
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels import batched_gemm as _bg
from repro.kernels import grouped_gemm as _gg
from repro.kernels import flash_attention as _fa
from repro.kernels import decode_attention as _da
from repro.kernels import wkv6_scan as _wkv


def _use_pallas(force_pallas: Optional[bool]) -> bool:
    if force_pallas is not None:
        return force_pallas
    if os.environ.get("REPRO_FORCE_PALLAS") == "1":
        return True
    return jax.default_backend() == "tpu"


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def batched_gemm(
    x: jax.Array,
    w: jax.Array,
    *,
    bm: int = _bg.DEFAULT_BM,
    bn: int = _bg.DEFAULT_BN,
    bk: int = _bg.DEFAULT_BK,
    force_pallas: Optional[bool] = None,
) -> jax.Array:
    """Space-time super-kernel: out[r] = x[r] @ w[r]."""
    if _use_pallas(force_pallas):
        return _bg.batched_gemm(x, w, bm=bm, bn=bn, bk=bk, interpret=_interpret())
    return ref.batched_gemm(x, w)


def grouped_gemm(
    x: jax.Array,
    w: jax.Array,
    block_groups: jax.Array,
    *,
    bm: int = _gg.DEFAULT_BM,
    bn: int = _gg.DEFAULT_BN,
    bk: int = _gg.DEFAULT_BK,
    force_pallas: Optional[bool] = None,
) -> jax.Array:
    """Ragged super-kernel / MoE expert GEMM."""
    if _use_pallas(force_pallas):
        return _gg.grouped_gemm(
            x, w, block_groups, bm=bm, bn=bn, bk=bk, interpret=_interpret()
        )
    return ref.grouped_gemm(x, w, block_groups, bm)


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    window: int = 0,
    scale: Optional[float] = None,
    logit_softcap: float = 0.0,
    force_pallas: Optional[bool] = None,
) -> jax.Array:
    """Prefill attention (GQA, causal, optional sliding window)."""
    # softcap only implemented on the reference path; gemma3 uses it on
    # logits — the Pallas kernel handles the common no-softcap fast path.
    if logit_softcap == 0.0 and _use_pallas(force_pallas):
        return _fa.flash_attention(
            q, k, v, causal=causal, window=window, scale=scale,
            interpret=_interpret(),
        )
    # XLA path: dense reference for short sequences (fast, exact tests),
    # chunked online-softmax beyond — a (B,H,S,S) score tensor at 32k+ ctx
    # is unlowerable.
    if k.shape[2] > 2048:
        return ref.attention_chunked(
            q, k, v, causal=causal, window=window, scale=scale,
            logit_softcap=logit_softcap,
        )
    return ref.attention(
        q, k, v, causal=causal, window=window, scale=scale,
        logit_softcap=logit_softcap,
    )


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
    force_pallas: Optional[bool] = None,
) -> jax.Array:
    """One-token decode read in place from the KV stacks
    (``decode_attention.decode_attention``)."""
    if _use_pallas(force_pallas):
        return _da.decode_attention(
            q, k_new, v_new, k_stack, v_stack, lengths, layer, tenant,
            scale=scale, interpret=_interpret())
    return ref.decode_attention(q, k_new, v_new, k_stack, v_stack, lengths,
                                layer, tenant, scale=scale)


def write_rows(
    stack: jax.Array,
    rows: jax.Array,
    tenant: jax.Array,
    slot: jax.Array,
    pos: jax.Array,
    *,
    force_pallas: Optional[bool] = None,
) -> jax.Array:
    """The decode step's keys (or values) written into a K/V stack in
    place (``decode_attention.write_rows``)."""
    if _use_pallas(force_pallas):
        return _da.write_rows(stack, rows, tenant, slot, pos,
                              interpret=_interpret())
    return ref.write_rows(stack, rows, tenant, slot, pos)


def wkv6_scan(
    r: jax.Array,
    k: jax.Array,
    v: jax.Array,
    w: jax.Array,
    u: jax.Array,
    *,
    chunk: int = _wkv.DEFAULT_CHUNK,
    force_pallas: Optional[bool] = None,
) -> jax.Array:
    """RWKV6 recurrence over a full sequence."""
    if _use_pallas(force_pallas):
        return _wkv.wkv6_scan(r, k, v, w, u, chunk=chunk, interpret=_interpret())
    return ref.wkv6_scan(r, k, v, w, u)


wkv6_step = ref.wkv6_step  # decode step is a handful of VPU ops; jnp is fine
