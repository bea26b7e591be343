"""Figure 4: inter-tenant latency predictability.

Paper: under MPS space-only sharing, co-located tenants diverge by up to
25% (worse with odd tenant counts) — unpredictability caused by the device
scheduler. Claim for space-time: a merged super-kernel gives every tenant
the SAME step latency by construction; the residual spread comes only from
the queueing layer.

Two measurements:

(a) engine modes — per-tenant mean step latency spread under the engine's
    time_only mode (each tenant's decode run as its own program, one
    after another — spread reflects dispatch order) vs space_time mode
    (one merged dispatch). Each tenant's history also holds its prefill,
    which waits for the prefills ahead of it in the admission pass.

(b) batching-window policies — the SAME Poisson kernel-arrival trace
    replayed on a deterministic VirtualClock against the fixed window and
    the SLO-adaptive window. The adaptive policy shrinks a bucket's
    window as any pending item's slack to its deadline shrinks, so tail
    latency (p95) must come out at or below the fixed window's.
"""

from __future__ import annotations

import dataclasses
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from repro.config import ScheduleConfig, get_config, smoke_variant
from repro.core import DynamicSpaceTimeScheduler, GemmProblem, VirtualClock
from repro.core.tenancy import init_stacked
from repro.models import build_model
from repro.serving import EngineConfig, InferenceRequest, MultiTenantEngine


def policy_trace(
    policy: str,
    tenants: int = 8,
    events: int = 300,
    seed: int = 0,
    slo_s: float = 0.010,
) -> Dict[str, float]:
    """Replay one seeded arrival trace on a virtual clock under ``policy``.

    Execution is real (small GEMMs through the super-kernel cache) but
    time is modeled: the cost model advances the virtual clock by a fixed
    dispatch overhead plus compute at an assumed rate, so latencies are
    fully deterministic and the two policies see the identical trace.
    """
    clock = VirtualClock()
    sched = DynamicSpaceTimeScheduler(
        ScheduleConfig(
            batching_window_s=0.004,
            batching_policy=policy,
            slo_slack_fraction=0.25,
            max_superkernel_size=32,
        ),
        clock=clock,
        cost_model=lambda batch: 50e-6 + sum(p.cost for p in batch) / 2e12,
    )
    key = jax.random.PRNGKey(seed)
    ws = [jax.random.normal(jax.random.fold_in(key, t), (64, 64), jnp.float32)
          for t in range(tenants)]
    x = jax.random.normal(jax.random.fold_in(key, 999), (64, 64), jnp.float32)

    rng = np.random.default_rng(seed)
    tick_s = 0.0005
    for i in range(events):
        clock.advance_to(i * tick_s)
        for _ in range(rng.poisson(1.2)):
            t = int(rng.integers(tenants))
            sched.submit(GemmProblem(tenant_id=t, x=x, w=ws[t], slo_s=slo_s))
        sched.pump()
    sched.flush()

    rep = sched.report()  # monitor percentiles cover the same latency set
    return {
        "p50_ms": rep["p50_s"] * 1e3,
        "p95_ms": rep["p95_s"] * 1e3,
        "mean_ms": rep["mean_s"] * 1e3,
        "dispatches": rep["dispatches"],
        "slo_violations": rep["slo_violations"],
    }


def run(r: int = 5, steps: int = 16, csv_rows=None):
    # odd tenant count on purpose — the paper's worst case for MPS
    print(f"\n=== Fig 4: inter-tenant latency spread (R={r}, odd) ===")
    cfg = dataclasses.replace(smoke_variant(get_config("stablelm-1.6b")), dtype="float32")
    m = build_model(cfg)
    key = jax.random.PRNGKey(0)
    rng = np.random.RandomState(0)
    params = init_stacked(m.init, key, r)

    for mode in ("time_only", "space_time"):
        eng = MultiTenantEngine(
            m, params,
            EngineConfig(num_tenants=r, slots_per_tenant=1, cache_len=64, mode=mode),
        )
        # the engine records each tenant's prefill and decode latencies
        # in its own monitor, in both modes
        for t in range(r):
            eng.submit(InferenceRequest(
                tenant_id=t, prompt=list(rng.randint(1, cfg.vocab_size, 8)),
                max_new_tokens=steps))
        eng.run_until_drained()
        spread = eng.monitor.predictability_spread()
        rep = eng.report()
        print(f"{mode:11s}: spread={spread:7.2%}  p95/p50="
              f"{rep['p95_s']/max(rep['p50_s'],1e-12):5.2f}")
        if csv_rows is not None:
            csv_rows.append((f"fig4/{mode}/spread", spread * 100, "pct (paper MPS: 25%)"))

    print("\n--- batching-window policy on one virtual-clock trace ---")
    results = {}
    for policy in ("fixed", "slo_adaptive"):
        results[policy] = policy_trace(policy)
        rr = results[policy]
        print(f"{policy:12s}: p50={rr['p50_ms']:7.3f}ms p95={rr['p95_ms']:7.3f}ms "
              f"dispatches={rr['dispatches']:.0f} slo_viol={rr['slo_violations']:.0f}")
        if csv_rows is not None:
            csv_rows.append((f"fig4/policy_{policy}/p95", rr["p95_ms"] * 1e3,
                             "us end-to-end (virtual clock)"))
    ok = results["slo_adaptive"]["p95_ms"] <= results["fixed"]["p95_ms"]
    print(f"adaptive p95 <= fixed p95: {ok}")


if __name__ == "__main__":
    run()
