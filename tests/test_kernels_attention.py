"""flash_attention / decode_attention / chunked-ref vs the dense oracle."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.decode_attention import decode_attention, write_rows
from repro.kernels.flash_attention import flash_attention

CASES = [
    # B, Hq, Hkv, S, D
    (2, 4, 4, 128, 64),      # MHA
    (2, 8, 2, 160, 64),      # GQA 4:1, ragged S
    (1, 8, 1, 96, 32),       # MQA
    (2, 4, 2, 64, 128),      # wide head
]


@pytest.mark.parametrize("case", CASES, ids=str)
@pytest.mark.parametrize("window", [0, 32])
def test_flash_vs_dense(case, window, rng_key):
    B, Hq, Hkv, S, D = case
    ks = jax.random.split(rng_key, 3)
    q = jax.random.normal(ks[0], (B, Hq, S, D), jnp.float32)
    k = jax.random.normal(ks[1], (B, Hkv, S, D), jnp.float32)
    v = jax.random.normal(ks[2], (B, Hkv, S, D), jnp.float32)
    got = flash_attention(q, k, v, causal=True, window=window, bq=64, bkv=64, interpret=True)
    want = ref.attention(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-4)


def test_chunked_ref_vs_dense(rng_key):
    """The O(S) XLA fallback must equal the dense reference (incl. softcap)."""
    B, Hq, Hkv, S, D = 2, 4, 2, 200, 32
    ks = jax.random.split(rng_key, 3)
    q = jax.random.normal(ks[0], (B, Hq, S, D), jnp.float32)
    k = jax.random.normal(ks[1], (B, Hkv, S, D), jnp.float32)
    v = jax.random.normal(ks[2], (B, Hkv, S, D), jnp.float32)
    for window, cap in [(0, 0.0), (64, 0.0), (0, 30.0)]:
        got = ref.attention_chunked(q, k, v, causal=True, window=window,
                                    logit_softcap=cap, kv_chunk=64)
        want = ref.attention(q, k, v, causal=True, window=window, logit_softcap=cap)
        np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-4)


def test_suffix_queries(rng_key):
    """Sq < Skv (queries are the suffix) must align causally."""
    B, Hq, Hkv, Skv, Sq, D = 1, 2, 2, 96, 32, 32
    ks = jax.random.split(rng_key, 3)
    q = jax.random.normal(ks[0], (B, Hq, Sq, D), jnp.float32)
    k = jax.random.normal(ks[1], (B, Hkv, Skv, D), jnp.float32)
    v = jax.random.normal(ks[2], (B, Hkv, Skv, D), jnp.float32)
    got = flash_attention(q, k, v, causal=True, bq=32, bkv=32, interpret=True)
    want = ref.attention(q, k, v, causal=True)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-4)


def _plain_decode(q, k_new, v_new, k_stack, v_stack, lengths, layer, tenant,
                  scale=None):
    """Float64 softmax attention of each row's query over its live cache
    positions, with K/V taken back to (Hkv, S, D), and the new token's key
    and value appended; a full ring drops the slot the token replaces."""
    q, k_new, v_new = (np.asarray(x, np.float64) for x in (q, k_new, v_new))
    B, Hq, D = q.shape
    Hkv, S = k_stack.shape[3], k_stack.shape[5]
    scale = D ** -0.5 if scale is None else scale
    out = np.zeros((B, Hq, D))
    for b in range(B):
        k = np.asarray(k_stack[layer, tenant, b], np.float64).swapaxes(1, 2)
        v = np.asarray(v_stack[layer, tenant, b], np.float64).swapaxes(1, 2)
        n = int(lengths[b])
        live = [p for p in range(min(n, S)) if n < S or p != n % S]
        keys = np.concatenate([k[:, live], k_new[b][:, None]], axis=1)
        vals = np.concatenate([v[:, live], v_new[b][:, None]], axis=1)
        for h in range(Hq):
            s = keys[h * Hkv // Hq] @ q[b, h] * scale
            p = np.exp(s - s.max())
            out[b, h] = p @ vals[h * Hkv // Hq] / p.sum()
    return out


def _decode_inputs(key, B, Hq, Hkv, S, D, L=2, T=2, dtype=jnp.float32):
    ks = jax.random.split(key, 5)
    return (jax.random.normal(ks[0], (B, Hq, D), dtype),
            jax.random.normal(ks[1], (B, Hkv, D), dtype),
            jax.random.normal(ks[2], (B, Hkv, D), dtype),
            jax.random.normal(ks[3], (L, T, B, Hkv, D, S), dtype),
            jax.random.normal(ks[4], (L, T, B, Hkv, D, S), dtype))


DECODE_CASES = {
    # name: (B, Hq, Hkv, S, D, scale)
    "mha-d64-s552": (3, 4, 4, 552, 64, None),
    "gqa4-d64-s2060-scale1/64": (3, 32, 8, 2060, 64, 1 / 64),
    "gqa4-d128-s552": (3, 8, 2, 552, 128, None),
}


@pytest.mark.parametrize("bkv", [None, 128], ids=["whole-S", "bkv128"])
@pytest.mark.parametrize("lengths", ["one", "full", "mixed"])
@pytest.mark.parametrize("case", sorted(DECODE_CASES))
def test_decode_vs_dense(case, lengths, bkv, rng_key):
    """The kernel, in interpret mode, against a plain float64 softmax:
    every live length from a single cached token to a full cache, S not a
    multiple of the block (the partial last block reads past S), and the
    stacks read at a layer and tenant other than the first."""
    B, Hq, Hkv, S, D, scale = DECODE_CASES[case]
    lens = {"one": [1, 1, 1], "full": [S - 1] * 3,
            "mixed": [S - 1, 0, S // 3]}[lengths]
    args = _decode_inputs(rng_key, B, Hq, Hkv, S, D)
    lens = jnp.asarray(lens, jnp.int32)
    got = decode_attention(*args, lens, 1, 1, scale=scale, bkv=bkv,
                           interpret=True)
    want = _plain_decode(*args, lens, 1, 1, scale=scale)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(ref.decode_attention(*args, lens, 1, 1,
                                                    scale=scale),
                               want, rtol=2e-5, atol=2e-5)


def test_decode_full_ring_drops_the_replaced_slot(rng_key):
    """A ring cache (sliding window) at or past its size: the slot the new
    token replaces (``length % S``) is left out, every other slot read."""
    B, Hq, Hkv, S, D = 3, 4, 2, 160, 32
    args = _decode_inputs(rng_key, B, Hq, Hkv, S, D)
    lens = jnp.asarray([S, S + 37, 3 * S - 1], jnp.int32)
    want = _plain_decode(*args, lens, 0, 1)
    for bkv in (None, 128):
        got = decode_attention(*args, lens, 0, 1, bkv=bkv, interpret=True)
        np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(ref.decode_attention(*args, lens, 0, 1), want,
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("stacks", ["shared", "mapped"])
def test_decode_under_tenant_vmap(stacks, rng_key):
    """Under ``jax.vmap`` over tenants the kernel's rows take the mapped
    axis: the stacks passed whole with a mapped tenant index (as the
    engine's decode step passes them), or mapped stacks of their own; each
    tenant's rows are the plain softmax of its own cache."""
    R, B, Hq, Hkv, S, D = 3, 2, 8, 2, 300, 64
    ks = jax.random.split(rng_key, R)
    per = [_decode_inputs(k, B, Hq, Hkv, S, D, L=2, T=R) for k in ks]
    q, kn, vn = (jnp.stack([p[i] for p in per]) for i in range(3))
    K, V = per[0][3], per[0][4]
    lens = jnp.asarray([[5, S - 1], [1, 77], [S // 2, 0]], jnp.int32)
    if stacks == "shared":
        got = jax.vmap(lambda q, kn, vn, n, t: decode_attention(
            q, kn, vn, K, V, n, 1, t, bkv=128, interpret=True))(
                q, kn, vn, lens, jnp.arange(R))
    else:
        own = lambda X: jnp.moveaxis(X, 1, 0)[:, :, None]  # (R, L, 1, ...)
        got = jax.vmap(lambda q, kn, vn, Kt, Vt, n: decode_attention(
            q, kn, vn, Kt, Vt, n, 1, 0, bkv=128, interpret=True))(
                q, kn, vn, own(K), own(V), lens)
    for t in range(R):
        want = _plain_decode(q[t], kn[t], vn[t], K, V, lens[t], 1, t)
        np.testing.assert_allclose(got[t], want, rtol=2e-5, atol=2e-5)


def test_decode_ignores_stale_cache(rng_key):
    """Cache positions beyond `lengths` must not affect the output —
    the property slot-reuse in the serving engine relies on."""
    B, Hq, Hkv, S, D = 1, 2, 1, 64, 32
    q, kn, vn, kc, vc = _decode_inputs(rng_key, B, Hq, Hkv, S, D, L=1, T=1)
    lens = jnp.asarray([20], jnp.int32)
    out1 = decode_attention(q, kn, vn, kc, vc, lens, 0, 0, bkv=32,
                            interpret=True)
    kc2 = kc.at[..., 20:].set(99.0)
    vc2 = vc.at[..., 20:].set(-99.0)
    out2 = decode_attention(q, kn, vn, kc2, vc2, lens, 0, 0, bkv=32,
                            interpret=True)
    np.testing.assert_array_equal(np.asarray(out1), np.asarray(out2))


@pytest.mark.parametrize("S", [128, 384])
def test_write_rows_vs_ref(S, rng_key):
    """The in-place position write, in interpret mode, against the jnp
    scatter: each row's key lands at its (tenant, slot, position) in every
    layer, positions at both ends of a 128-lane tile and in each tile."""
    L, T, B, H, D = 3, 2, 2, 4, 64
    ks = jax.random.split(rng_key, 2)
    stack = jax.random.normal(ks[0], (L, T, B, H, D, S), jnp.float32)
    rows = jax.random.normal(ks[1], (L, 4, H, D), jnp.float32)
    tenant = jnp.asarray([0, 0, 1, 1], jnp.int32)
    slot = jnp.asarray([0, 1, 0, 1], jnp.int32)
    pos = jnp.asarray([0, 127, S - 1, S // 2 + 3], jnp.int32)
    want = ref.write_rows(stack, rows, tenant, slot, pos)
    got = write_rows(stack, rows, tenant, slot, pos, interpret=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    np.testing.assert_array_equal(
        np.asarray(want[:, 1, 0, :, :, S - 1]), np.asarray(rows[:, 2]))
