"""One-token GQA decode attention, read in place from a stacked KV cache.

serve_step's hot kernel: each sequence has ONE new query token attending to
its cached history. This is memory-bound (roofline: ~2*S*Hkv*D bytes
streamed per token), so the kernel's job is to stream K/V through VMEM in
large blocks straight from where the cache lies.

The cache is a stack ``(L, T, B, Hkv, D, S)``: layer, tenant, slot, KV
head, head dim, position (``models.attention``). Positions are the minor
axis, which is how the TPU stores such an array at ``head_dim`` 64 (as
it stores a ``(.., S, 64)`` one S-minor, so that a kernel that wants it
D-minor costs a relayout of every layer on every call); at ``head_dim``
128 or more it does so where S is a multiple of 128. Each row of a
call names the ``(layer, tenant, slot)`` it reads through scalar-prefetch
operands, so the kernel takes the whole stack and no layer or tenant is
sliced out for it, and ``S`` needs no padding: a block spans all of it
where it fits, and a partial last block is masked.

The new token's key and value are not in the cache: the caller writes
them after the step (``attention.write_rows``), and the kernel folds
them into its online softmax as one more position. Ring caches (sliding
window) drop the slot the new token will overwrite.

Under ``jax.vmap`` the mapped axis folds into the kernel's rows
(``custom_vmap``), so a tenant-vmapped decode step is one kernel call per
layer, not a loop over tenants.

Grid: (rows, Hkv/hb, S/bkv), the KV positions innermost; each step reads
``hb`` heads' ``(D, bkv)`` blocks of K and V, and their ``q_per_kv`` query
heads ride along as the matmul rows.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: one K (or V) block's bytes; both double-buffered stay within VMEM's
#: default scoped limit with room for the scores
BLOCK_BYTES = 2 << 20
LANES = 128
NEG_INF = -1e30


def _blocks(hkv: int, d: int, s: int, itemsize: int,
            bkv: Optional[int]) -> tuple:
    """(heads per block, positions per block) for a cache of ``hkv`` heads
    of ``d`` by ``s``: all ``s`` positions (else runs of 128 lanes, where
    one head's positions pass ``BLOCK_BYTES``), and as many heads as fit
    ``BLOCK_BYTES``. A short cache is then one block per head group."""
    per_pos = d * itemsize
    if bkv is None:
        lanes = pl.cdiv(s, LANES) * LANES
        bkv = s if lanes * per_pos <= BLOCK_BYTES else max(
            BLOCK_BYTES // per_pos // LANES * LANES, LANES)
    bkv = min(bkv, s)
    block = pl.cdiv(bkv, LANES) * LANES * per_pos  # one head's block
    hb = max([h for h in range(1, hkv + 1)
              if hkv % h == 0 and h * block <= BLOCK_BYTES] or [1])
    return hb, bkv


def _decode_kernel(
    layer_ref, tenant_ref, slot_ref, len_ref,
    q_ref, kn_ref, vn_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref,
    *, scale: float, bkv: int, s: int,
):
    del layer_ref, tenant_ref, slot_ref  # read by the index maps
    n, j = pl.program_id(0), pl.program_id(2)
    length = len_ref[n]
    live = jnp.minimum(length, s)
    # a full ring's oldest slot is the one the new token replaces
    drop = jnp.where(length >= s, length % s, -1)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0]  # (hb, q_per_kv, D)

    @pl.when(j * bkv < live)
    def _block():
        k = k_ref[...]  # (hb, D, bkv)
        v = v_ref[...]
        sc = jnp.einsum("hgd,hds->hgs", q, k,
                        preferred_element_type=jnp.float32) * scale
        pos = j * bkv + jax.lax.broadcasted_iota(jnp.int32, (1, 1, bkv), 2)
        mask = (pos < live) & (pos != drop)
        sc = jnp.where(mask, sc, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(sc, axis=-1, keepdims=True))
        p = jnp.where(mask, jnp.exp(sc - m_new), 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        if s % bkv:  # a partial last block reads past S: unspecified values
            v = jnp.where(pos < s, v, jnp.zeros_like(v))
        acc_ref[...] = acc_ref[...] * alpha + jnp.einsum(
            "hgs,hds->hgd", p.astype(v.dtype), v,
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(j == pl.num_programs(2) - 1)
    def _fold_new_token_and_store():
        kn = kn_ref[0].astype(jnp.float32)  # (hb, 1, D)
        vn = vn_ref[0].astype(jnp.float32)
        sc = jnp.sum(q.astype(jnp.float32) * kn, axis=-1,
                     keepdims=True) * scale  # (hb, q_per_kv, 1)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, sc)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(sc - m_new)
        l = l_ref[...] * alpha + p
        acc = acc_ref[...] * alpha + p * vn
        o_ref[0] = (acc / l).astype(o_ref.dtype)


def _rows_call(q, k_new, v_new, k_stack, v_stack, layer, tenant, slot,
               lengths, *, scale, bkv, interpret):
    """The kernel over N rows: q (N, Hq, D); k_new/v_new (N, Hkv, D);
    stacks (L, T, B, Hkv, D, S); layer/tenant/slot/lengths (N,) int32."""
    N, Hq, D = q.shape
    Hkv, S = k_stack.shape[3], k_stack.shape[5]
    g = Hq // Hkv
    hb, bkv = _blocks(Hkv, D, S, k_stack.dtype.itemsize, bkv)
    nk = pl.cdiv(S, bkv)

    def kv_block(n, h, j, layer, tenant, slot, lens):
        # blocks past the row's live length are not fetched again: the
        # index repeats the last live block, and the body skips them
        last = jnp.maximum(pl.cdiv(jnp.minimum(lens[n], S), bkv) - 1, 0)
        return (layer[n], tenant[n], slot[n], h, 0, jnp.minimum(j, last))

    def head_block(n, h, j, *_):
        return (n, h, 0, 0)

    kv_spec = pl.BlockSpec((None, None, None, hb, D, bkv), kv_block)
    row_spec = pl.BlockSpec((1, hb, g, D), head_block)
    new_spec = pl.BlockSpec((1, hb, 1, D), head_block)
    kernel = functools.partial(_decode_kernel, scale=scale, bkv=bkv, s=S)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(N, Hkv // hb, nk),
            in_specs=[row_spec, new_spec, new_spec, kv_spec, kv_spec],
            out_specs=row_spec,
            scratch_shapes=[
                pltpu.VMEM((hb, g, 1), jnp.float32),
                pltpu.VMEM((hb, g, 1), jnp.float32),
                pltpu.VMEM((hb, g, D), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((N, Hkv, g, D), q.dtype),
        interpret=interpret,
    )(layer, tenant, slot, lengths,
      q.reshape(N, Hkv, g, D), k_new[:, :, None, :], v_new[:, :, None, :],
      k_stack, v_stack)
    return out.reshape(N, Hq, D)


@functools.lru_cache(maxsize=None)
def _rows(scale: float, bkv: Optional[int], interpret: bool):
    """``_rows_call`` for fixed statics, with a vmap rule that folds the
    mapped axis into the rows (a mapped stack joins its tenant axis)."""

    @jax.custom_batching.custom_vmap
    def rows(q, k_new, v_new, k_stack, v_stack, layer, tenant, slot, lengths):
        return _rows_call(q, k_new, v_new, k_stack, v_stack, layer, tenant,
                          slot, lengths, scale=scale, bkv=bkv,
                          interpret=interpret)

    @rows.def_vmap
    def _fold(size, batched, q, k_new, v_new, k_stack, v_stack, *idx):
        mapped = lambda x, b: x if b else jnp.broadcast_to(x, (size,) + x.shape)
        per_row = [mapped(x, b) for x, b in zip((q, k_new, v_new) + idx,
                                                batched[:3] + batched[5:])]
        if batched[3] or batched[4]:
            # (size, L, T, ...) -> (L, size * T, ...): mapped stack r's
            # tenant t is tenant r * T + t
            L, T = k_stack.shape[-6:-4]
            k_stack, v_stack = (
                jnp.moveaxis(mapped(x, b), 0, 1).reshape(
                    (L, size * T) + x.shape[-4:])
                for x, b in ((k_stack, batched[3]), (v_stack, batched[4])))
            per_row[4] = per_row[4] + T * jnp.arange(size)[:, None]
        n = per_row[0].shape[1]
        flat = [x.reshape((size * n,) + x.shape[2:]) for x in per_row]
        out = rows(*flat[:3], k_stack, v_stack, *flat[3:])
        return out.reshape((size, n) + out.shape[1:]), True

    return rows


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
    bkv: Optional[int] = None,
    interpret: bool = False,
) -> jax.Array:
    """Single-token decode attention of B slots, read in place.

    Args:
        q: (B, Hq, D) new-token queries.
        k_new, v_new: (B, Hkv, D) the new token's key and value.
        k_stack, v_stack: (L, T, B, Hkv, D, S) the cache stacks.
        lengths: (B,) int32 tokens cached before the new one.
        layer, tenant: the stacks' layer and tenant read (int scalars).
        bkv: positions per block (default: as ``_blocks`` picks).
    Returns:
        (B, Hq, D)
    """
    B, _, D = q.shape
    scale = scale if scale is not None else D ** -0.5
    row = lambda x: jnp.full((B,), x, jnp.int32)
    return _rows(float(scale), bkv, interpret)(
        q, k_new, v_new, k_stack, v_stack, row(layer), row(tenant),
        jnp.arange(B, dtype=jnp.int32), lengths.astype(jnp.int32))


def _write_kernel(tenant_ref, slot_ref, pos_ref, stack_ref, row_ref, out_ref):
    del tenant_ref, slot_ref  # read by the index maps
    lane = jax.lax.broadcasted_iota(jnp.int32, out_ref.shape, 2)
    out_ref[...] = jnp.where(lane == pos_ref[pl.program_id(0)] % LANES,
                             row_ref[...], stack_ref[...])


def write_rows(
    stack: jax.Array,
    rows: jax.Array,
    tenant: jax.Array,
    slot: jax.Array,
    pos: jax.Array,
    *,
    interpret: bool = False,
) -> jax.Array:
    """``stack`` with ``rows[l, n]`` written at ``stack[l, tenant[n],
    slot[n], :, :, pos[n]]`` for every layer ``l``, in place.

    Args:
        stack: (L, T, B, Hkv, D, S) a K (or V) stack, aliased to the
            result: donated, it is updated where it lies.
        rows: (L, N, Hkv, D) the new tokens' keys (or values).
        tenant, slot, pos: (N,) int32 where row ``n`` goes.

    Grid (N, L): each step rewrites the 128 positions around one row's
    position in one layer, a dense lane tile of the stored layout (a
    window one position wide would make the compiler relay the cache).
    """
    L, N, Hkv, D = rows.shape

    def tile(n, l, tenant, slot, pos):
        return (l, tenant[n], slot[n], 0, 0, pos[n] // LANES)

    lanes = min(LANES, stack.shape[-1])
    return pl.pallas_call(
        _write_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(N, L),
            in_specs=[
                pl.BlockSpec((None, None, None, Hkv, D, lanes), tile),
                pl.BlockSpec((None, None, Hkv, D, 1),
                             lambda n, l, *_: (l, n, 0, 0, 0)),
            ],
            out_specs=pl.BlockSpec((None, None, None, Hkv, D, lanes), tile),
        ),
        out_shape=jax.ShapeDtypeStruct(stack.shape, stack.dtype),
        input_output_aliases={3: 0},
        interpret=interpret,
    )(tenant, slot, pos, stack, rows.astype(stack.dtype)[..., None])
