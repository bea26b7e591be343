"""The served Mamba2 + attention hybrid against ``reference/hybrid.py`` at
tiny size on the CPU: the engine's prefill, then its decode through the
cache, give the logits of the reference's full forward pass; a slot that
a finished request leaves decodes the next request as a fresh engine
does; and ``counts/hybrid.py`` agrees with the program's own shapes.

``granite-4.0-h-micro-smoke`` is one Mamba2 and one attention layer
(GQA 4:1, width 256), in float32, with the published model's NoPE,
attention scale, muP multipliers and one-group gated norm. The
reference draws float32 weights to match.
"""

import dataclasses
import functools
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.counts import hybrid as hc
from chipbench.reference import hybrid
from chipbench.tests.test_served_cells import HYBRID_SMOKE

CONFIG = json.loads((Path(__file__).resolve().parents[1] / "configs"
                     / "granite-4.0-h-micro-2t.json").read_text())
#: float32 on both sides: the program's chunked SSD scan and its
#: attention differ from the reference's token-by-token recurrence and
#: dense attention only in the order of float32 rounding, which reads at
#: most 5e-7 of the largest logit; the limit is twice that. A carried SSM
#: state rounded to bfloat16 (8 bits of mantissa) reads 3.5e-6 and more.
REL_TOL = 1e-6
SEED = 1_234_567_891   # a 31-bit weight seed, as the driver derives


def _model(**changes):
    from repro.config import get_config
    from repro.models import build_model

    cfg = get_config(HYBRID_SMOKE["arch"])
    return build_model(dataclasses.replace(cfg, **changes))


def _engine(model, seed, slots=2, tenants=2):
    from repro.core.tenancy import init_stacked
    from repro.serving import EngineConfig, MultiTenantEngine

    stacked = init_stacked(model.init, jax.random.PRNGKey(seed), tenants)
    return MultiTenantEngine(model, stacked, EngineConfig(
        num_tenants=tenants, slots_per_tenant=slots, cache_len=80))


def _serve(engine, requests):
    """Run ``requests`` (tenant, prompt, new tokens) through the engine;
    per request, its tokens and the full logits of each, prefill's first
    and then each decode step's row of its slot."""
    from repro.serving import InferenceRequest

    prefill_logits = {}
    orig = engine._prefill

    def prefill(params, t, tokens):
        lg, cache = orig(params, t, tokens)
        prefill_logits[(int(t), tuple(np.asarray(tokens[0]).tolist()))] = (
            np.asarray(lg[0]))
        return lg, cache

    engine._prefill = prefill
    reqs = [InferenceRequest(tenant_id=t, prompt=p, max_new_tokens=n)
            for t, p, n in requests]
    for r in reqs:
        engine.submit(r)
    logits = {r.request_id: [] for r in reqs}
    while engine.queue or engine.active:
        engine._admit()
        decoding = dict(engine.active)
        for r in decoding.values():
            if not logits[r.request_id]:
                logits[r.request_id].append(
                    prefill_logits[(r.tenant_id, tuple(r.prompt))])
        engine.step()
        step = np.asarray(engine._step_logits)
        for (t, s), r in decoding.items():
            logits[r.request_id].append(step[t, s])
    return [(r, np.stack(logits[r.request_id][:len(r.generated)]))
            for r in reqs]


def _requests(seed, n=5):
    rng = np.random.default_rng(seed)
    return [(i % 2, rng.integers(1, 1024, int(rng.integers(20, 50))).tolist(),
             8) for i in range(n)]


def _worst_error(served, seed):
    config = dict(CONFIG, **HYBRID_SMOKE)
    seqs = [(r.tenant_id, r.prompt, r.generated) for r, _ in served]
    ref = hybrid._logits(config, seed, seqs, fp8=False)
    scale = max(np.abs(lg).max() for lg in ref)
    return max(np.abs(got - want).max()
               for (_, got), want in zip(served, ref)) / scale


@pytest.mark.parametrize("seed", [SEED, 7])
def test_engine_prefill_and_decode_match_the_reference(seed):
    """Five requests over two tenants with two slots each: prompts over
    several SSD chunks with ragged tails, slots taken again, both tenants
    in one decode step. Every logit of every served token, prefill's and
    the decode steps', is the reference's within ``REL_TOL``."""
    served = _serve(_engine(_model(), seed), _requests(seed))
    assert all(len(r.generated) == 8 for r, _ in served)
    assert _worst_error(served, seed) <= REL_TOL


def _fresh_programs(monkeypatch):
    """The engine's programs traced anew for this test (and its own
    cache dropped after it), so that a patched model function is seen."""
    from repro.serving import engine

    monkeypatch.setattr(engine, "engine_programs", functools.lru_cache(
        maxsize=16)(engine.engine_programs.__wrapped__))


def _bf16_state(monkeypatch):
    """The SSM state carried between prefill and decode steps is kept in
    bfloat16."""
    from repro.models import ssm

    _fresh_programs(monkeypatch)

    def round_state(cache):
        s = cache["ssm"]
        return {**cache, "ssm": s.astype(jnp.bfloat16).astype(s.dtype)}

    decode, forward = ssm.mamba2_decode, ssm.mamba2_forward

    def mamba2_decode(params, x, cfg, cache):
        y, c = decode(params, x, cfg, round_state(cache))
        return y, round_state(c)

    def mamba2_forward(*args, **kwargs):
        y, c = forward(*args, **kwargs)
        return y, (round_state(c) if c else c)

    monkeypatch.setattr(ssm, "mamba2_decode", mamba2_decode)
    monkeypatch.setattr(ssm, "mamba2_forward", mamba2_forward)
    return {}


def _no_residual_multiplier(monkeypatch):
    return {"residual_multiplier": 1.0}


@pytest.mark.parametrize("fault", [_bf16_state, _no_residual_multiplier])
def test_the_comparison_fails_a_lower_precision_or_a_dropped_multiplier(
        fault, monkeypatch):
    changes = fault(monkeypatch)
    served = _serve(_engine(_model(**changes), SEED), _requests(SEED))
    assert _worst_error(served, SEED) > REL_TOL


def test_a_reused_slot_decodes_as_a_fresh_engine():
    """One slot per tenant: the second request of tenant 0 takes the slot
    the first one freed, whose conv tails and SSM state it left; it must
    give the logits it gives alone on a fresh engine, bit for bit (the
    recurrent state is masked by no length: admission installs it
    whole)."""
    model = _model()
    first, second = _requests(3, n=4)[::2][:2]
    reused = _serve(_engine(model, SEED, slots=1), [first, second])
    fresh = _serve(_engine(model, SEED, slots=1), [second])
    (r_reused, got), (r_fresh, want) = reused[1], fresh[0]
    assert r_reused.slot == r_fresh.slot == 0
    assert r_reused.generated == r_fresh.generated
    np.testing.assert_array_equal(got, want)


def test_a_slot_left_with_its_last_state_decodes_otherwise():
    """The fault the test above guards against: where admission installs
    the second request's keys and values but leaves the slot's conv tails
    and SSM state as the first request left them, the second request's
    logits move away from a fresh engine's."""
    model = _model()
    first, second = _requests(3, n=4)[::2][:2]
    fresh = _serve(_engine(model, SEED, slots=1), [second])[0][1]

    eng = _engine(model, SEED, slots=1)
    install, calls = eng._scatter_slot, []

    def scatter(tenant, slot, single):
        before = eng.caches
        install(tenant, slot, single)
        calls.append(slot)
        if len(calls) > 1:
            eng.caches = jax.tree_util.tree_map_with_path(
                lambda path, new, old: (new if path[-1].key in ("k", "v")
                                        else old), eng.caches, before)

    eng._scatter_slot = scatter
    stale = _serve(eng, [first, second])[1][1]
    assert calls == [0, 0]
    assert np.abs(stale - fresh).max() > 1e-3


# ------------------------------------------------------------------ counts
def test_counts_match_the_programs_parameters_and_state():
    """``counts/hybrid.py`` at the published widths: 3,191,396,096
    parameters a tenant, and a slot's cache bytes, as the program's own
    ``init`` and ``init_caches`` give them under ``eval_shape``."""
    from repro.config import get_config
    from repro.models import build_model

    model = build_model(get_config(CONFIG["arch"]))
    z = hc.Dims(CONFIG)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    assert hc.tenant_params(z) == 3_191_396_096 == sum(
        x.size for x in jax.tree.leaves(params))
    assert hc.tenant_weight_bytes(z) == sum(
        x.size * x.dtype.itemsize for x in jax.tree.leaves(params))
    cache_len = 2048 + 32 + 8
    caches = jax.eval_shape(lambda: model.init_caches(1, cache_len))
    assert hc.slot_state_bytes(z, cache_len) == sum(
        x.size * x.dtype.itemsize for x in jax.tree.leaves(caches))
    # 36 Mamba2 layers of 2.12 MB (conv tails 3 x 4352 x 2 B, state
    # 64 x 64 x 128 x 4 B), 4 attention layers of 8 KV heads of 64
    assert hc.mamba_state_bytes(z) == 3 * 4352 * 2 + 64 * 64 * 128 * 4
    assert (z.n_mamba, z.n_attn) == (36, 4)


def test_decode_and_prefill_counts_by_hand():
    z = hc.Dims(CONFIG)
    mamba = 2048 * (2 * 4096 + 2 * 128 + 64) + 4096 * 2048
    attn = 2048 * 2048 * 2 + 2 * 2048 * 512
    mlp = 3 * 2048 * 8192
    matmuls = 36 * mamba + 4 * attn + 40 * mlp
    recur = 4 * 64 * 64 * 128 + 2 * 4 * 4352
    assert hc.decode_token_flops(z, 300) == (
        2 * (matmuls + 2048 * 100352) + 36 * recur + 4 * 4 * 32 * 64 * 300)
    assert hc.prefill_flops(z, 1024) == (
        2 * 1024 * matmuls + 36 * 1024 * recur
        + 4 * 4 * 32 * 64 * 1024 * 512.5 + 2 * 2048 * 100352)
    # state read and written in 36 layers; 4 layers' keys and values of
    # 299 positions read and one written, 8 heads of 64 in bf16
    assert hc.decode_token_bytes(z, 300) == (
        2 * 36 * hc.mamba_state_bytes(z) + 4 * 2 * 300 * 8 * 64 * 2)
