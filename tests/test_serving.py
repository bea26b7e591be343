"""Serving engine integration: multi-tenant space-time decode must be
token-identical to single-tenant execution, slots must recycle, and the
time_only mode must produce the same tokens (slower path, same math)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config import get_config, smoke_variant
from repro.core.tenancy import stack_params
from repro.models import build_model
from repro.serving import EngineConfig, InferenceRequest, MultiTenantEngine


def _setup(arch, R=3, mode="space_time", slots=2, cache_len=64):
    cfg = dataclasses.replace(smoke_variant(get_config(arch)), dtype="float32")
    m = build_model(cfg)
    key = jax.random.PRNGKey(0)
    tenant_params = [m.init(jax.random.fold_in(key, t)) for t in range(R)]
    eng = MultiTenantEngine(
        m, stack_params(tenant_params),
        EngineConfig(num_tenants=R, slots_per_tenant=slots, cache_len=cache_len, mode=mode),
    )
    return cfg, m, tenant_params, eng


def _oracle_tokens(m, params, prompt, n, cache_len=64):
    toks = jnp.asarray(prompt, jnp.int32)[None, :]
    logits, caches = m.forward_prefill(params, toks, cache_len=cache_len)
    out = [int(jnp.argmax(logits[0]))]
    lengths = jnp.asarray([len(prompt)], jnp.int32)
    for _ in range(n - 1):
        logits, caches = m.forward_decode(
            params, jnp.asarray([out[-1]], jnp.int32), caches, lengths
        )
        out.append(int(jnp.argmax(logits[0])))
        lengths = lengths + 1
    return out


@pytest.mark.slow
@pytest.mark.parametrize("arch", ["stablelm-1.6b", "rwkv6-1.6b"])
def test_spacetime_matches_single_tenant(arch):
    cfg, m, tenant_params, eng = _setup(arch)
    rng = np.random.RandomState(0)
    reqs = []
    for t in range(3):
        for j in range(3):  # 3 requests per tenant, only 2 slots -> queueing
            p = list(rng.randint(1, cfg.vocab_size, size=6))
            r = InferenceRequest(tenant_id=t, prompt=p, max_new_tokens=5)
            reqs.append(r)
            eng.submit(r)
    eng.run_until_drained()
    assert len(eng.finished) == 9
    for r in eng.finished:
        want = _oracle_tokens(m, tenant_params[r.tenant_id], r.prompt, len(r.generated))
        assert r.generated == want, (arch, r.request_id)


@pytest.mark.slow
def test_time_only_mode_same_tokens():
    cfg, m, tenant_params, eng_st = _setup("stablelm-1.6b", R=2)
    _, _, _, eng_to = _setup("stablelm-1.6b", R=2, mode="time_only")
    rng = np.random.RandomState(1)
    prompts = [list(rng.randint(1, cfg.vocab_size, size=5)) for _ in range(4)]
    for i, p in enumerate(prompts):
        eng_st.submit(InferenceRequest(tenant_id=i % 2, prompt=p, max_new_tokens=4))
        eng_to.submit(InferenceRequest(tenant_id=i % 2, prompt=p, max_new_tokens=4))
    eng_st.run_until_drained()
    eng_to.run_until_drained()
    st = sorted((r.tenant_id, tuple(r.prompt), tuple(r.generated)) for r in eng_st.finished)
    to = sorted((r.tenant_id, tuple(r.prompt), tuple(r.generated)) for r in eng_to.finished)
    assert st == to


def test_slot_recycling():
    cfg, m, tenant_params, eng = _setup("stablelm-1.6b", R=1, slots=1)
    rng = np.random.RandomState(2)
    for _ in range(3):
        eng.submit(InferenceRequest(
            tenant_id=0, prompt=list(rng.randint(1, cfg.vocab_size, size=4)),
            max_new_tokens=3))
    eng.run_until_drained()
    assert len(eng.finished) == 3
    assert eng.slots.utilization() == 0.0


def test_report_metrics():
    cfg, m, tenant_params, eng = _setup("stablelm-1.6b", R=2)
    rng = np.random.RandomState(3)
    for t in range(2):
        eng.submit(InferenceRequest(
            tenant_id=t, prompt=list(rng.randint(1, cfg.vocab_size, size=4)),
            max_new_tokens=3))
    eng.run_until_drained()
    rep = eng.report()
    assert rep["finished"] == 2.0
    assert rep["decode_tokens"] >= 4.0
    assert "req_mean_latency_s" in rep


@pytest.mark.parametrize("mode", ["space_time", "time_only"])
def test_recorded_steps_and_slot_stamps(mode):
    """With a recorder shard attached, every decode step writes one row
    of ordered host stamps, and each request stamps its slot before its
    first token; without one, nothing is stamped."""
    from repro.obs.recorder import STEP_COLUMNS, ReplicaShard

    cfg, m, tenant_params, eng = _setup("stablelm-1.6b", R=2, mode=mode)
    _, _, _, off = _setup("stablelm-1.6b", R=2, mode=mode)
    shard = eng.recorder = ReplicaShard(0)
    rng = np.random.RandomState(4)
    for e in (eng, off):
        for t in (0, 0, 1):
            e.submit(InferenceRequest(
                tenant_id=t, prompt=list(rng.randint(1, cfg.vocab_size, 4)),
                max_new_tokens=3))
        e.run_until_drained()
    rows = np.array([list(getattr(shard, c)) for c in STEP_COLUMNS]).T
    assert shard.n_steps == eng.steps == len(rows)
    assert np.isfinite(rows).all() and (np.diff(rows, axis=1) >= 0).all()
    assert sum(shard._stp_tokens) == eng.decode_tokens
    assert max(shard._stp_tenants) == 2
    assert all(r.arrival_time <= r.slot_time <= r.first_token_time
               <= r.finish_time for r in eng.finished)
    assert off.recorder is None and off._phases is None
    assert all(r.slot_time is None for r in off.finished)
