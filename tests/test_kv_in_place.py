"""The decode step reads the KV cache where it lies and writes the new
token's keys and values after the layer scan (``Model.decode_step``,
``Model.write_kv``), into K/V stacks the engine donates. The engine's
donated ``decode_all`` must serve what the per-tenant ``decode_one`` path
(``mode="time_only"``, each tenant's own caches) serves: the same tokens
and the same logits at every step, through slots that are taken again
after their first request left."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config import get_config, smoke_variant
from repro.core.tenancy import init_stacked
from repro.models import build_model
from repro.models.attention import write_rows
from repro.serving import EngineConfig, InferenceRequest, MultiTenantEngine

R, SLOTS = 2, 2
# (tenant, prompt length, new tokens): three requests a tenant on two
# slots, so each tenant's third request takes a slot that a longer or
# shorter request left
REQUESTS = [(0, 9, 3), (0, 4, 6), (1, 7, 5), (1, 3, 2), (0, 5, 4), (1, 6, 4)]


def _served(arch, mode):
    cfg = dataclasses.replace(smoke_variant(get_config(arch)), dtype="float32")
    m = build_model(cfg)
    eng = MultiTenantEngine(
        m, init_stacked(m.init, jax.random.PRNGKey(0), R),
        EngineConfig(num_tenants=R, slots_per_tenant=SLOTS, cache_len=40,
                     mode=mode))
    rng = np.random.RandomState(0)
    reqs = [InferenceRequest(tenant_id=t,
                             prompt=list(rng.randint(1, cfg.vocab_size, n)),
                             max_new_tokens=k) for t, n, k in REQUESTS]
    for r in reqs:
        eng.submit(r)
    eng.run_until_drained()
    return reqs


@pytest.mark.parametrize("arch", ["stablelm-1.6b", "granite-4.0-h-micro"])
def test_donated_decode_all_serves_what_decode_one_serves(arch):
    merged, per_tenant = (_served(arch, mode)
                          for mode in ("space_time", "time_only"))
    slots = [(r.tenant_id, r.slot) for r in merged]
    assert len(set(slots)) < len(slots)  # a slot was taken again
    for a, b in zip(merged, per_tenant):
        assert a.slot == b.slot
        assert len(a.generated) == a.max_new_tokens
        assert a.generated == b.generated
        np.testing.assert_allclose(a.token_logits, b.token_logits,
                                   rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("lead", ["layers-tenants-slots", "layers-slots",
                                  "slots"])
def test_write_rows_puts_each_slot_at_its_next_position(lead):
    """Every layer's row lands at its slot's next position, ``length %
    S`` (a full ring wraps to its first slot), with a tenant axis, a
    layer axis or neither, and nothing else moves."""
    L, H, D, S = 3, 2, 4, 150
    lengths = {"layers-tenants-slots": np.array([[0, 149], [130, 300]]),
               "layers-slots": np.array([21, 128]),
               "slots": np.array([5, 149])}[lead]
    layers = () if lead == "slots" else (L,)
    rng = np.random.RandomState(1)
    cache = rng.randn(*layers, *lengths.shape, H, D, S).astype(np.float32)
    rows = rng.randn(*layers, *lengths.shape, H, D).astype(np.float32)
    got = write_rows({"k": jnp.asarray(cache), "v": jnp.asarray(-cache)},
                     {"k": jnp.asarray(rows), "v": jnp.asarray(-rows)},
                     jnp.asarray(lengths, jnp.int32))
    want = cache.copy()
    for at in np.ndindex(lengths.shape):
        idx = (slice(None),) * len(layers) + at
        want[idx + (slice(None), slice(None), lengths[at] % S)] = rows[idx]
    np.testing.assert_array_equal(np.asarray(got["k"]), want)
    np.testing.assert_array_equal(np.asarray(got["v"]), -want)
