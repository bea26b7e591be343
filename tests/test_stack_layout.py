"""The layer-major stacked layout (``core.tenancy``): leaves under the
top-level ``"unit"`` key carry their tenant on axis 1, every other leaf
on axis 0. The engine's programs read that layout in place and must give
what each tenant's own unstacked tree gives, for every block family the
layer scan knows: dense, a two-block unit (llama4: dense + MoE), the
zamba2 hybrid with its shared attention block (two repeats and an
unrolled remainder), the granite-4.0-h hybrid (Mamba2 and attention
layers, each with its MLP, two repeats), and rwkv6."""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config import get_config, smoke_variant
from repro.core.tenancy import (
    broadcast_tenants, init_stacked, stack_params, take_tenant, tenant_axes,
    tenant_counts, unstack_params)
from repro.models import build_model
from repro.serving.engine import engine_programs

R, B, PROMPT, CACHE_LEN = 2, 2, 5, 16

# (arch, smoke layers): llama4, zamba2 and granite-4.0-h get two repeats
# of their unit, zamba2 also a remainder layer
ARCHS = {"stablelm-1.6b": 2, "llama4-maverick-400b-a17b": 4,
         "zamba2-7b": 5, "granite-4.0-h-micro": 4, "rwkv6-1.6b": 2}


def _model(arch):
    cfg = smoke_variant(get_config(arch), num_layers=ARCHS[arch])
    return build_model(dataclasses.replace(cfg, dtype="float32"))


@functools.lru_cache(maxsize=None)
def _drawn(arch, seed):
    """(model, each tenant's own tree, the stacked draw) for ``seed``."""
    m = _model(arch)
    key = jax.random.PRNGKey(seed)
    init = jax.jit(m.init)
    return (m, [init(jax.random.fold_in(key, t)) for t in range(R)],
            init_stacked(m.init, key, R))


def _close(got, want):
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert g.shape == w.shape
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=1e-5, atol=1e-5)


def _own(m):
    """Each tenant's own programs, on its unstacked tree."""
    prefill = jax.jit(m.forward_prefill, static_argnames="cache_len")
    return (lambda p, toks, **kw: prefill(p, toks, cache_len=CACHE_LEN, **kw),
            jax.jit(m.forward_decode))


@pytest.mark.parametrize("program",
                         ["decode_all", "decode_one", "prefill", "prefill_cont"])
@pytest.mark.parametrize("arch", sorted(ARCHS))
def test_programs_on_stacked_tree_match_each_tenant(arch, program):
    m, params, stacked = _drawn(arch, 3)
    progs = engine_programs(m, CACHE_LEN)
    own_prefill, own_decode = _own(m)
    rng = np.random.RandomState(0)

    if program in ("prefill", "prefill_cont"):
        toks = jnp.asarray(rng.randint(1, m.cfg.vocab_size, (1, PROMPT)),
                           jnp.int32)
        for t in range(R):
            want = own_prefill(params[t], toks)
            got = progs.prefill(stacked, np.int32(t), toks)
            if program == "prefill_cont":
                nxt, start = toks[:, :2], jnp.int32(PROMPT)
                want = own_prefill(params[t], nxt, caches=want[1], start=start)
                got = progs.prefill_cont(stacked, np.int32(t), nxt, got[1],
                                         start)
            _close(got, want)
        return

    filled = [own_prefill(p, jnp.asarray(
        rng.randint(1, m.cfg.vocab_size, (B, PROMPT)), jnp.int32))
        for p in params]
    tokens = jnp.stack([jnp.argmax(lg, -1) for lg, _ in filled]).astype(
        jnp.int32)
    lengths = jnp.full((R, B), PROMPT, jnp.int32)
    caches = stack_params([c for _, c in filled])
    want = [own_decode(params[t], tokens[t], filled[t][1], lengths[t])
            for t in range(R)]
    if program == "decode_all":
        logits, new = progs.decode_all(stacked, tokens, caches, lengths)
        assert tenant_counts(new) == {R}
        got = [(logits[t], take_tenant(new, t)) for t in range(R)]
    else:
        got = [progs.decode_one(stacked, caches, np.int32(t), tokens[t],
                                lengths[t]) for t in range(R)]
    for g, w in zip(got, want):
        _close(g, w)


@pytest.mark.parametrize("arch", sorted(ARCHS))
def test_init_stacked_is_each_tenants_draw_layer_major(arch):
    """The stacked draw holds exactly ``model.init(fold_in(key, t))`` for
    every tenant (compiled, as the draw is), read through the tenant-axis
    helper, with the layer stack's leaves ``[reps, R, ...]`` and the rest
    ``[R, ...]``. Stacking the per-tenant trees gives the same tree bit
    for bit, and so does a tenant-major draw: storage order alone
    changed."""
    m, per_tenant, stacked = _drawn(arch, 3)
    assert tenant_axes(stacked) == {k: int(k == "unit") for k in stacked}
    for k, sub in stacked.items():
        for x, own in zip(jax.tree.leaves(sub),
                          jax.tree.leaves(per_tenant[0][k])):
            ax = int(k == "unit")
            assert x.shape == own.shape[:ax] + (R,) + own.shape[ax:]
    for got, want in zip(unstack_params(stacked, R), per_tenant):
        for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
            np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
    for g, w in zip(jax.tree.leaves(stack_params(per_tenant)),
                    jax.tree.leaves(stacked)):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
    key = jax.random.PRNGKey(3)
    keys = jax.vmap(lambda t: jax.random.fold_in(key, t))(jnp.arange(R))
    tenant_major = jax.jit(jax.vmap(m.init))(keys)
    for t, got in enumerate(unstack_params(stacked, R)):
        for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(tenant_major)):
            np.testing.assert_array_equal(np.asarray(g), np.asarray(w[t]))


def test_broadcast_caches_follow_the_layout():
    m = _model("zamba2-7b")
    single = m.init_caches(B, CACHE_LEN)
    stacked = broadcast_tenants(single, R)
    assert tenant_counts(stacked) == {R}
    for t in range(R):
        _close(take_tenant(stacked, t), single)
