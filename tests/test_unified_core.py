"""The unified space-time execution core: generic workloads, injected
clocks, pluggable batching policies, admission control — and the serving
engine routing its prefill/decode cohorts through the same scheduler.

These tests run without hypothesis (the property-based variants live in
test_scheduler_properties.py)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config import ScheduleConfig, get_config, smoke_variant
from repro.core import (
    DynamicSpaceTimeScheduler,
    GemmProblem,
    VirtualClock,
    Workload,
)
from repro.core.policy import FixedWindowPolicy, SLOAdaptiveWindowPolicy
from repro.core.tenancy import stack_params
from repro.core.superkernel import SuperKernelCache
from repro.kernels import ref
from repro.models import build_model
from repro.serving import EngineConfig, InferenceRequest, MultiTenantEngine


def mk_problem(tenant, M=32, K=16, N=8, seed=0, slo_s=0.1):
    k = jax.random.PRNGKey(seed * 1000 + tenant)
    return GemmProblem(
        tenant_id=tenant,
        x=jax.random.normal(k, (M, K), jnp.float32),
        w=jax.random.normal(jax.random.fold_in(k, 1), (K, N), jnp.float32),
        slo_s=slo_s,
    )


class TestGenericWorkload:
    def test_callback_workloads_dispatch_through_pump(self):
        sched = DynamicSpaceTimeScheduler(ScheduleConfig(batching_window_s=0.0))
        calls = []

        def execute(batch):
            calls.append(len(batch))
            return [w.payload * 2 for w in batch]

        for t in range(3):
            sched.submit(Workload(tenant_id=t, bucket=("custom", "a"),
                                  cost=1.0, execute=execute, payload=t))
        done = sched.flush()
        assert [w.result for w in done] == [0, 2, 4]
        assert calls == [3]  # ONE merged dispatch for the shared bucket
        assert sched.stats.dispatches == 1
        # the same monitor tracked all three tenants
        assert len(sched.monitor.tenants) == 3

    def test_distinct_buckets_dispatch_separately(self):
        sched = DynamicSpaceTimeScheduler(ScheduleConfig(batching_window_s=0.0))
        execute = lambda batch: [None] * len(batch)
        sched.submit(Workload(tenant_id=0, bucket=("a",), execute=execute))
        sched.submit(Workload(tenant_id=1, bucket=("b",), execute=execute))
        sched.flush()
        assert sched.stats.dispatches == 2

    def test_admission_control_rejects_over_cap(self):
        sched = DynamicSpaceTimeScheduler(
            ScheduleConfig(batching_window_s=1000.0, max_pending_per_tenant=2))
        assert sched.submit(mk_problem(0))
        assert sched.submit(mk_problem(0))
        assert not sched.submit(mk_problem(0))   # third pending rejected
        assert sched.submit(mk_problem(1))       # other tenants unaffected
        assert sched.stats.rejected == 1
        assert len(sched.queue) == 3
        sched.flush()
        assert sched.submit(mk_problem(0))       # capacity freed after dispatch


class TestRaggedFlushDrains:
    def test_flush_drains_family_over_size_cap(self):
        """A merge family larger than max_superkernel_size must drain
        fully across several dispatches, not leave a remainder queued."""
        sched = DynamicSpaceTimeScheduler(ScheduleConfig(
            batching_window_s=0.0, allow_ragged_merge=True,
            max_superkernel_size=4))
        for t in range(9):  # same (K, N, dtype) family, mixed M
            sched.submit(mk_problem(t, M=16 + 16 * (t % 3), K=16, N=8))
        done = sched.flush()
        assert len(done) == 9
        assert len(sched.queue) == 0
        assert sched.stats.dispatches == 3  # 4 + 4 + 1
        for p in done:
            np.testing.assert_allclose(
                np.asarray(p.result), np.asarray(p.x @ p.w), rtol=1e-4, atol=1e-3)


class TestClockAndPolicy:
    def test_virtual_clock_trace_is_deterministic(self):
        def trace():
            clock = VirtualClock()
            sched = DynamicSpaceTimeScheduler(
                ScheduleConfig(batching_window_s=0.002),
                clock=clock,
                cost_model=lambda batch: 1e-4 * len(batch),
            )
            done = []
            rng = np.random.default_rng(0)
            for i in range(40):
                clock.advance_to(i * 0.001)
                for _ in range(rng.poisson(1.0)):
                    sched.submit(mk_problem(int(rng.integers(4))))
                done.extend(sched.pump())
            done.extend(sched.flush())
            return [round(p.completion_time - p.arrival_time, 12) for p in done]

        assert trace() == trace()

    def test_fixed_window_holds_until_elapsed(self):
        clock = VirtualClock()
        sched = DynamicSpaceTimeScheduler(
            ScheduleConfig(batching_window_s=0.010), clock=clock)
        sched.submit(mk_problem(0))
        assert sched.pump() == []
        clock.advance(0.011)
        assert len(sched.pump()) == 1

    def test_adaptive_window_shrinks_with_slack(self):
        pol = SLOAdaptiveWindowPolicy(base_window_s=0.010, slack_fraction=0.5)
        relaxed = mk_problem(0, slo_s=1.0)
        relaxed.arrival_time = 0.0
        assert pol.window_s([relaxed], now=0.0) == pytest.approx(0.010)
        urgent = mk_problem(1, slo_s=0.004)
        urgent.arrival_time = 0.0
        assert pol.window_s([urgent], now=0.0) == pytest.approx(0.002)
        # past the deadline -> no waiting at all
        assert pol.window_s([urgent], now=0.005) == 0.0
        # the most urgent pending item rules the bucket
        assert pol.window_s([relaxed, urgent], now=0.0) == pytest.approx(0.002)

    def test_adaptive_dispatches_urgent_item_before_fixed_window(self):
        clock = VirtualClock()
        sched = DynamicSpaceTimeScheduler(
            ScheduleConfig(batching_window_s=0.010,
                           batching_policy="slo_adaptive"),
            clock=clock)
        sched.submit(mk_problem(0, slo_s=0.002))
        clock.advance(0.001)  # half the SLO gone; fixed window would hold
        assert len(sched.pump()) == 1

    def test_adaptive_p95_not_worse_than_fixed_on_same_trace(self):
        from benchmarks.fig4_predictability import policy_trace

        fixed = policy_trace("fixed", tenants=4, events=120)
        adaptive = policy_trace("slo_adaptive", tenants=4, events=120)
        assert adaptive["p95_ms"] <= fixed["p95_ms"]


class TestRaggedMergeReference:
    def test_mixed_m_matches_ref_outputs(self):
        cache = SuperKernelCache(ScheduleConfig())
        key = jax.random.PRNGKey(3)
        problems = []
        for t, M in enumerate([5, 130, 32, 1]):
            kx, kw = jax.random.split(jax.random.fold_in(key, t))
            problems.append(GemmProblem(
                tenant_id=t,
                x=jax.random.normal(kx, (M, 32), jnp.float32),
                w=jax.random.normal(kw, (32, 24), jnp.float32)))
        outs = cache.execute_ragged(problems)
        for p, out in zip(problems, outs):
            want = ref.batched_gemm(p.x[None], p.w[None])[0]
            np.testing.assert_allclose(
                np.asarray(out), np.asarray(want), rtol=1e-4, atol=1e-3)

    def test_group_count_is_pow2_bucketed(self):
        """Cache key no longer depends on the exact group count: 3 groups
        and 4 groups of the same row geometry share one compiled kernel."""
        cache = SuperKernelCache(ScheduleConfig(r_bucketing="pow2"))
        def run(n_groups):
            key = jax.random.PRNGKey(n_groups)
            probs = [GemmProblem(
                tenant_id=t,
                x=jax.random.normal(jax.random.fold_in(key, t), (16, 8), jnp.float32),
                w=jax.random.normal(jax.random.fold_in(key, 100 + t), (8, 8), jnp.float32))
                for t in range(n_groups)]
            return cache.execute_ragged(probs)
        run(3)   # groups pad 3 -> 4; 3 row-blocks pad to 4
        run(4)   # exactly 4 groups, 4 row-blocks: same key
        assert cache.stats.misses == 1
        assert cache.stats.hits == 1
        # correctness preserved under group padding
        outs = run(3)
        assert all(o.shape == (16, 8) for o in outs)


def _setup_engine(mode, R=2, slots=1, cache_len=32):
    cfg = dataclasses.replace(smoke_variant(get_config("stablelm-1.6b")),
                              dtype="float32")
    m = build_model(cfg)
    key = jax.random.PRNGKey(0)
    params = [m.init(jax.random.fold_in(key, t)) for t in range(R)]
    eng = MultiTenantEngine(m, stack_params(params), EngineConfig(
        num_tenants=R, slots_per_tenant=slots, cache_len=cache_len, mode=mode))
    return cfg, eng


class TestEngineThroughScheduler:
    def test_prefill_and_decode_route_through_shared_core(self):
        cfg, eng = _setup_engine("space_time")
        rng = np.random.RandomState(0)
        for t in range(2):
            eng.submit(InferenceRequest(
                tenant_id=t, prompt=list(rng.randint(1, cfg.vocab_size, 4)),
                max_new_tokens=3))
        eng.run_until_drained()
        assert len(eng.finished) == 2
        # every prefill + decode step went through the scheduler pump:
        # both same-length prefills MERGE into one dispatch, plus one
        # dispatch per decode step
        assert eng.scheduler.stats.dispatches == 3
        # the engine has no private monitor: it IS the scheduler's
        assert eng.monitor is eng.scheduler.monitor
        rep = eng.report()
        assert rep["scheduler_dispatches"] == 3.0
        # headline percentiles keep decode-step semantics; compile-heavy
        # prefill dispatches are reported under their own keys (no
        # ordering assertion: wall-clock latencies are load-dependent)
        assert rep["p95_s"] == eng.monitor.summary_for("decode")["p95_s"]
        assert "prefill_p95_s" in rep

    def test_space_time_and_time_only_identical_greedy_tokens(self):
        rng = np.random.RandomState(7)
        prompts = [list(rng.randint(1, 500, 5)) for _ in range(3)]
        results = {}
        for mode in ("space_time", "time_only"):
            cfg, eng = _setup_engine(mode, R=2)
            for i, p in enumerate(prompts):
                eng.submit(InferenceRequest(
                    tenant_id=i % 2, prompt=p, max_new_tokens=4))
            eng.run_until_drained()
            results[mode] = sorted(
                (r.tenant_id, tuple(r.prompt), tuple(r.generated))
                for r in eng.finished)
        assert results["space_time"] == results["time_only"]

    def test_cohort_split_by_size_cap_decodes_once_per_step(self):
        """Even with max_superkernel_size=1 (cohort workloads split across
        pump batches), caches must advance exactly once per step — tokens
        stay identical to the unconstrained run."""
        cfg = dataclasses.replace(smoke_variant(get_config("stablelm-1.6b")),
                                  dtype="float32")
        m = build_model(cfg)
        key = jax.random.PRNGKey(0)
        params = [m.init(jax.random.fold_in(key, t)) for t in range(2)]
        rng = np.random.RandomState(5)
        prompts = [list(rng.randint(1, cfg.vocab_size, 4)) for _ in range(2)]
        results = {}
        for name, schedule in (
            ("default", None),
            ("split", ScheduleConfig(batching_window_s=0.0, max_superkernel_size=1)),
        ):
            eng = MultiTenantEngine(m, stack_params(params), EngineConfig(
                num_tenants=2, slots_per_tenant=1, cache_len=32,
                mode="space_time", schedule=schedule))
            for t, p in enumerate(prompts):
                eng.submit(InferenceRequest(tenant_id=t, prompt=p, max_new_tokens=4))
            eng.run_until_drained()
            results[name] = sorted(
                (r.tenant_id, tuple(r.generated)) for r in eng.finished)
        assert results["default"] == results["split"]

    def test_admission_rejection_requeues_request(self):
        """A prefill pushed back by admission control must return its slot
        and retry on a later step — no request may be silently dropped."""
        cfg, eng_unused = _setup_engine("space_time")  # build model/config once
        m = eng_unused.model
        params = eng_unused.stacked_params
        eng = MultiTenantEngine(m, params, EngineConfig(
            num_tenants=2, slots_per_tenant=2, cache_len=32, mode="space_time",
            schedule=ScheduleConfig(batching_window_s=0.0,
                                    max_pending_per_tenant=1)))
        rng = np.random.RandomState(9)
        for _ in range(2):  # two same-tenant requests admitted in one pass
            eng.submit(InferenceRequest(
                tenant_id=0, prompt=list(rng.randint(1, cfg.vocab_size, 4)),
                max_new_tokens=3))
        eng.run_until_drained()
        assert len(eng.finished) == 2
        assert eng.scheduler.stats.rejected >= 1
        assert eng.slots.utilization() == 0.0

    def test_time_only_records_positional_latency_skew(self):
        """Sequential per-tenant dispatch: later tenants wait for earlier
        ones, so the shared monitor must see a nonzero spread; the merged
        cohort gives everyone the same completion time by construction."""
        cfg, eng = _setup_engine("time_only", R=3)
        rng = np.random.RandomState(1)
        for t in range(3):
            eng.submit(InferenceRequest(
                tenant_id=t, prompt=list(rng.randint(1, cfg.vocab_size, 4)),
                max_new_tokens=6))
        eng.run_until_drained()
        assert eng.monitor.predictability_spread() > 0.0
        assert len(eng.monitor.tenants) == 3
