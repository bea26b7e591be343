"""Figure 3: model-level decode latency vs tenant count, space-time vs
time-only multiplexing.

Paper setup: MobileNetV2 (compute-light) + ResNet-50 (heavy) on a V100.
Here: two assigned-arch smoke variants (stablelm = light dense,
granite-moe = heavier routed) decoding concurrently under the serving
engine's two modes. Claim validated: time_only per-step latency grows
~linearly in R (serialized dispatch), space_time grows sub-linearly.
"""

from __future__ import annotations

import dataclasses
import time
from typing import List

import jax
import numpy as np

from repro.config import get_config, smoke_variant
from repro.core.tenancy import init_stacked
from repro.models import build_model
from repro.serving import EngineConfig, InferenceRequest, MultiTenantEngine


def bench_arch(arch: str, tenant_counts=(1, 2, 4, 8), steps: int = 12, csv_rows=None):
    cfg = dataclasses.replace(smoke_variant(get_config(arch)), dtype="float32")
    m = build_model(cfg)
    key = jax.random.PRNGKey(0)
    rng = np.random.RandomState(0)
    print(f"\n--- {arch} (reduced) decode-step latency vs tenants ---")
    print(f"{'R':>3s} {'time_only ms':>14s} {'space_time ms':>14s} {'ratio':>7s}")
    for r in tenant_counts:
        params = init_stacked(m.init, key, r)
        lat = {}
        for mode in ("time_only", "space_time"):
            eng = MultiTenantEngine(
                m, params,
                EngineConfig(num_tenants=r, slots_per_tenant=1, cache_len=48, mode=mode),
            )
            for t in range(r):
                eng.submit(InferenceRequest(
                    tenant_id=t, prompt=list(rng.randint(1, cfg.vocab_size, 8)),
                    max_new_tokens=steps))
            eng.step()  # admission + compile warmup outside timing
            t0 = time.perf_counter()
            n = 0
            while eng.active:
                eng.step()
                n += 1
            lat[mode] = (time.perf_counter() - t0) / max(n, 1)
        ratio = lat["time_only"] / lat["space_time"]
        print(f"{r:3d} {lat['time_only']*1e3:14.2f} {lat['space_time']*1e3:14.2f} "
              f"{ratio:6.2f}x")
        if csv_rows is not None:
            for mode, v in lat.items():
                csv_rows.append((f"fig3/{arch}/R{r}/{mode}", v * 1e6,
                                 f"step_latency_ratio={ratio:.2f}"))


def run(csv_rows=None):
    print("\n=== Fig 3: latency vs tenant count (engine modes) ===")
    for arch in ("stablelm-1.6b", "granite-moe-1b-a400m"):
        bench_arch(arch, csv_rows=csv_rows)


if __name__ == "__main__":
    run()
