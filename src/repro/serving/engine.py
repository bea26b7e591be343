"""Multi-tenant inference engine: space-time scheduled decode loop.

R tenants of the same architecture (different weights) are served by ONE
jitted, tenant-vmapped decode step over stacked params + stacked caches —
every projection/FFN GEMM in the model becomes an inter-model batched
super-kernel, which is the paper's mechanism applied to whole models.

All work flows through the shared ``DynamicSpaceTimeScheduler``: each
admitted prefill and each tenant's decode step is submitted as a generic
``Workload`` (bucket, cost, SLO, execute-callback) and dispatched by the
scheduler's pump. The engine therefore inherits admission control,
per-tenant SLO/latency tracking, and straggler eviction from the core
instead of duplicating its own monitor plumbing.

``mode="time_only"`` provides the contrast case: each tenant's decode
cohort gets its OWN bucket, so the scheduler dispatches them sequentially
(one program per tenant per step), modeling CUDA context time-slicing —
a tenant's recorded latency then includes waiting for every tenant ahead
of it in the dispatch order (the paper's linear-slowdown mechanism).
Used by benchmarks/fig3_latency.py and fig4_predictability.py.
"""

from __future__ import annotations

import dataclasses
import functools
import time
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.config import ScheduleConfig
from repro.core.scheduler import DynamicSpaceTimeScheduler
from repro.core.workload import Workload
from repro.models import Model
from repro.serving.kv_cache import SlotManager
from repro.serving.request import InferenceRequest, RequestState
from repro.serving.sampling import SamplingParams, sample


def _tenant(stacked: Any, t) -> Any:
    """Tenant ``t``'s slice of a stacked tree (``t`` may be traced)."""
    return jax.tree.map(
        lambda x: jax.lax.dynamic_index_in_dim(x, t, keepdims=False), stacked)


@dataclasses.dataclass(frozen=True)
class EnginePrograms:
    """The engine's jitted programs over stacked params and caches."""

    decode_all: Any     # (params, tokens, caches, lengths): every tenant
    decode_one: Any     # (params, caches, t, tokens, lengths): tenant t
    prefill: Any        # (params, t, tokens): tenant t, fresh sequence
    prefill_cont: Any   # (params, t, tokens, caches, start): next chunk


@functools.lru_cache(maxsize=16)
def engine_programs(model: Model, cache_len: int) -> EnginePrograms:
    """Jitted programs for one (model, cache length), shared by every
    engine that serves it, so replicas on one device compile once.

    Per-tenant programs take the whole stacked tree plus a traced tenant
    index and select inside the program: one compile serves every
    tenant, and no eager per-call copy of a tenant's weights is made.
    """

    def decode_all(params, tokens, caches, lengths):
        return jax.vmap(model.forward_decode)(params, tokens, caches, lengths)

    def decode_one(params, caches, t, tokens, lengths):
        return model.forward_decode(
            _tenant(params, t), tokens, _tenant(caches, t), lengths)

    def prefill(params, t, tokens):
        return model.forward_prefill(
            _tenant(params, t), tokens, cache_len=cache_len)

    def prefill_cont(params, t, tokens, caches, start):
        return model.forward_prefill(
            _tenant(params, t), tokens, cache_len=cache_len,
            caches=caches, start=start)

    return EnginePrograms(*(jax.jit(f) for f in (
        decode_all, decode_one, prefill, prefill_cont)))


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    num_tenants: int
    slots_per_tenant: int = 4
    cache_len: int = 256
    mode: str = "space_time"        # "space_time" | "time_only"
    # >0: prefill prompts in fixed-size chunks (one compile per chunk
    # length instead of per prompt length). Requires a non-sliding-window
    # architecture (chunked continuation needs linear caches).
    prefill_chunk: int = 0
    sampling: SamplingParams = dataclasses.field(default_factory=SamplingParams)
    seed: int = 0
    ewma_alpha: float = 0.2
    eviction_ratio: float = 10.0    # effectively off unless benchmarking isolation
    # optional override for the shared scheduler core (batching policy,
    # admission caps, ...); None derives one from the fields above.
    schedule: Optional[ScheduleConfig] = None


class MultiTenantEngine:
    """R tenants' decode loop over ONE stacked weight tree.

    ``stacked_params`` carries every tenant's weights along a leading
    tenant axis (``core.tenancy.stack_params`` / ``init_stacked``); the
    engine holds no per-tenant copy, so engines on one device can share
    the tree. The stacked caches are placed on the device that holds the
    weights, which is what pins a fleet replica to its chip.
    """

    def __init__(self, model: Model, stacked_params: Any, config: EngineConfig):
        lead = {x.shape[0] for x in jax.tree.leaves(stacked_params)}
        if lead != {config.num_tenants}:
            raise ValueError(
                f"stacked params lead with {sorted(lead)} tenants, "
                f"config has {config.num_tenants}")
        self.model = model
        self.cfg = config
        self.stacked_params = stacked_params

        R, B = config.num_tenants, config.slots_per_tenant
        single = model.init_caches(B, config.cache_len)
        device = next(iter(jax.tree.leaves(stacked_params)[0].devices()))
        self.caches = jax.device_put(
            jax.tree.map(
                lambda x: jnp.broadcast_to(x[None], (R,) + x.shape), single),
            device)
        self.slots = SlotManager(R, B)

        # the unified scheduling core: prefill + decode cohorts flow
        # through it as Workloads; it owns latency/SLO tracking.
        schedule = config.schedule or ScheduleConfig(
            batching_window_s=0.0,
            max_superkernel_size=max(128, config.num_tenants),
            latency_ewma_alpha=config.ewma_alpha,
            straggler_eviction_ratio=config.eviction_ratio,
        )
        self.scheduler = DynamicSpaceTimeScheduler(schedule)

        self.queue: List[InferenceRequest] = []
        self.active: Dict[tuple, InferenceRequest] = {}  # (tenant, slot) -> req
        self.finished: List[InferenceRequest] = []
        # flight-recorder shard (repro.obs); the API layer attaches it and
        # taps scheduler.on_dispatch — the engine only records arrivals
        self.recorder = None
        self.last_token = np.zeros((R, B), np.int32)
        self.steps = 0
        self.decode_tokens = 0
        self._sample_key = jax.random.PRNGKey(config.seed)
        self._step_logits: Optional[jax.Array] = None  # (R, B, V)
        self._cohort_step = -1                         # last step decoded merged
        self._pending_caches: Dict[int, Any] = {}      # time_only per-tenant updates
        self._pending_logits: Dict[int, jax.Array] = {}

        progs = engine_programs(model, config.cache_len)
        self._decode_all = progs.decode_all
        self._decode_one = progs.decode_one
        self._prefill = progs.prefill
        self._prefill_cont = progs.prefill_cont

    # ---------------------------------------------------------------- monitor
    @property
    def monitor(self):
        """Per-tenant latency/SLO tracking lives in the shared core."""
        return self.scheduler.monitor

    # ------------------------------------------------------------------ intake
    def submit(self, req: InferenceRequest, now: Optional[float] = None) -> None:
        req.arrival_time = now if now is not None else time.perf_counter()
        req.state = RequestState.QUEUED
        if self.recorder is not None:
            self.recorder.record_arrival(
                req.arrival_time, req.tenant_id,
                ("request", len(req.prompt)), True)
        self.queue.append(req)

    # ------------------------------------------------------------------ prefill
    def _admit(self) -> None:
        # Prefill runs at EXACT prompt length (one compile per distinct
        # length). Padding would corrupt SSM/RWKV recurrent state; callers
        # wanting fewer compiles should bucket their prompt lengths.
        # Each admitted prefill is a Workload bucketed by prompt length so
        # the scheduler accounts its latency per tenant.
        remaining = []
        submitted = False
        for req in self.queue:
            slot = self.slots.acquire(req.tenant_id, req.request_id)
            if slot is None:
                remaining.append(req)
                continue
            req.slot = slot
            req.state = RequestState.PREFILLING
            ok = self.scheduler.submit(Workload(
                tenant_id=req.tenant_id,
                bucket=("prefill", len(req.prompt)),
                cost=float(len(req.prompt)),
                slo_s=req.slo_s,
                execute=self._execute_prefill_batch,
                payload=req,
                kind="prefill",
            ))
            if not ok:
                # admission control pushed back: return the slot, retry later
                self.slots.release(req.tenant_id, slot)
                req.slot = None
                req.state = RequestState.QUEUED
                remaining.append(req)
                continue
            submitted = True
        self.queue = remaining
        if submitted:
            self.scheduler.flush()

    def _execute_prefill_batch(self, batch: List[Workload]) -> List[int]:
        """Scheduler executor: prefill each admitted request, install its
        cache into the stacked cohort, and activate its decode slot."""
        outs = []
        for wl in batch:
            req: InferenceRequest = wl.payload
            tokens = jnp.asarray(np.asarray(req.prompt, np.int32))[None, :]
            logits, cache = self._run_prefill(req.tenant_id, tokens)
            tok = int(jnp.argmax(logits[0]))
            req.generated.append(tok)
            req.first_token_time = time.perf_counter()
            req.prefill_time = req.first_token_time
            self._scatter_slot(req.tenant_id, req.slot, cache)
            self.slots.set_length(req.tenant_id, req.slot, tokens.shape[1])
            self.last_token[req.tenant_id, req.slot] = tok
            req.state = RequestState.DECODING
            self.active[(req.tenant_id, req.slot)] = req
            outs.append(tok)
        return outs

    def _run_prefill(self, tenant: int, tokens):
        """Whole-prompt or chunked prefill (bounded compile count)."""
        C = self.cfg.prefill_chunk
        S = tokens.shape[1]
        params, t = self.stacked_params, np.int32(tenant)
        if C <= 0 or S <= C:
            return self._prefill(params, t, tokens)
        logits, cache = self._prefill(params, t, tokens[:, :C])
        pos = C
        while pos < S:
            n = min(C, S - pos)  # ragged tail compiles once per tail length
            logits, cache = self._prefill_cont(
                params, t, tokens[:, pos:pos + n], cache, jnp.int32(pos))
            pos += n
        return logits, cache

    def _scatter_slot(self, tenant: int, slot: int, single_cache: Any) -> None:
        """Insert a prefilled (batch=1) cache into the stacked cohort cache."""

        def upd(big: jax.Array, small: jax.Array, slot_axis: int) -> jax.Array:
            idx = [0] * big.ndim
            idx[0] = tenant
            idx[slot_axis] = slot
            return jax.lax.dynamic_update_slice(
                big, small[None].astype(big.dtype), tuple(idx)
            )

        # unit caches: leaf (R, reps, B, ...) -> slot axis 2
        self.caches["unit"] = jax.tree.map(
            lambda big, small: upd(big, small, 2),
            self.caches["unit"],
            single_cache["unit"],
        )
        # rem caches: leaf (R, B, ...) -> slot axis 1
        self.caches["rem"] = jax.tree.map(
            lambda big, small: upd(big, small, 1),
            self.caches["rem"],
            single_cache["rem"],
        )

    # ------------------------------------------------------------------ decode
    def _lengths(self) -> np.ndarray:
        R, B = self.cfg.num_tenants, self.cfg.slots_per_tenant
        out = np.zeros((R, B), np.int32)
        for t in range(R):
            out[t] = self.slots.lengths(t)
        return out

    def _execute_decode_cohort(self, batch: List[Workload]) -> List[jax.Array]:
        """space_time executor: ONE tenant-vmapped program for the whole
        cohort — every active tenant in the batch shares the dispatch.

        The decode runs exactly once per engine step even if the scheduler
        splits the cohort's workloads across pump batches (caches must
        advance once); later sub-batches reuse the same step's logits."""
        if self._cohort_step != self.steps:
            lengths = jnp.asarray(self._lengths())
            tokens = jnp.asarray(self.last_token)
            logits, self.caches = self._decode_all(
                self.stacked_params, tokens, self.caches, lengths
            )
            self._step_logits = jax.block_until_ready(logits)
            self._cohort_step = self.steps
        return [self._step_logits[wl.payload] for wl in batch]

    def _execute_decode_tenant(self, batch: List[Workload]) -> List[jax.Array]:
        """time_only executor: a per-tenant program with a device sync per
        dispatch (the CUDA context-switch analogue). Cache/logit updates
        are staged and scattered into the stacked trees once per step."""
        outs = []
        for wl in batch:
            t = wl.payload
            tokens_t = jnp.asarray(self.last_token[t])
            lengths_t = jnp.asarray(self.slots.lengths(t), jnp.int32)
            lg, nc = self._decode_one(self.stacked_params, self.caches,
                                      np.int32(t), tokens_t, lengths_t)
            lg = jax.block_until_ready(lg)
            self._pending_caches[t] = nc
            self._pending_logits[t] = lg
            outs.append(lg)
        return outs

    def _apply_pending_tenant_updates(self) -> None:
        """Scatter time_only per-tenant cache/logit updates in one pass."""
        if not self._pending_caches:
            return
        ts = sorted(self._pending_caches)
        idx = jnp.asarray(ts)
        small = jax.tree.map(
            lambda *xs: jnp.stack(xs), *[self._pending_caches[t] for t in ts]
        )
        self.caches = jax.tree.map(
            lambda big, sm: big.at[idx].set(sm.astype(big.dtype)),
            self.caches, small,
        )
        lgs = jnp.stack([self._pending_logits[t] for t in ts])
        if self._step_logits is None or self._step_logits.shape[-1] != lgs.shape[-1]:
            R, B = self.cfg.num_tenants, self.cfg.slots_per_tenant
            self._step_logits = jnp.zeros((R, B, lgs.shape[-1]), lgs.dtype)
        self._step_logits = self._step_logits.at[idx].set(lgs)
        self._pending_caches.clear()
        self._pending_logits.clear()

    def step(self) -> int:
        """One engine iteration: admit + one decode step. Returns #tokens.

        The decode cohort is submitted to the shared scheduler as one
        Workload per active tenant. In space_time mode they share one
        bucket (one merged dispatch — identical completion time for every
        tenant, predictability by construction); in time_only mode each
        tenant gets its own bucket and the scheduler dispatches them
        sequentially.
        """
        self._admit()
        if not self.active:
            return 0

        slo_by_tenant: Dict[int, float] = {}
        slots_by_tenant: Dict[int, int] = {}
        for (t, _), req in self.active.items():
            slo_by_tenant[t] = min(slo_by_tenant.get(t, float("inf")), req.slo_s)
            slots_by_tenant[t] = slots_by_tenant.get(t, 0) + 1
        for t in sorted(slots_by_tenant):
            merged = self.cfg.mode == "space_time"
            ok = self.scheduler.submit(Workload(
                tenant_id=t,
                bucket=("decode", "cohort") if merged else ("decode", t),
                cost=float(slots_by_tenant[t]),
                slo_s=slo_by_tenant[t],
                execute=(self._execute_decode_cohort if merged
                         else self._execute_decode_tenant),
                payload=t,
                kind="decode",
            ))
            if not ok:
                # a dropped decode workload would silently desync caches
                raise RuntimeError(
                    "decode workload rejected by scheduler admission control; "
                    "max_pending_per_tenant must admit one decode workload "
                    "per tenant per step"
                )
        self.scheduler.flush()
        self._apply_pending_tenant_updates()
        logits = self._step_logits

        if self.cfg.sampling.greedy:
            next_tokens = np.asarray(jnp.argmax(logits, axis=-1), np.int32)
        else:
            self._sample_key, sub = jax.random.split(self._sample_key)
            next_tokens = np.asarray(sample(logits, self.cfg.sampling, sub), np.int32)
        produced = 0
        now = time.perf_counter()
        for (t, s), req in list(self.active.items()):
            tok = int(next_tokens[t, s])
            req.generated.append(tok)
            produced += 1
            self.slots.set_length(t, s, self.slots.slots[(t, s)].length + 1)
            self.last_token[t, s] = tok
            if req.done:
                req.finish_time = now
                req.state = RequestState.FINISHED
                self.finished.append(req)
                self.slots.release(t, s)
                del self.active[(t, s)]
        self.steps += 1
        self.decode_tokens += produced
        return produced

    def run_until_drained(self, max_steps: int = 10_000) -> None:
        for _ in range(max_steps):
            self.step()
            if not self.queue and not self.active:
                return
        raise RuntimeError("engine did not drain")

    # ------------------------------------------------------------------ metrics
    def report(self) -> Dict[str, float]:
        rep = {
            "steps": float(self.steps),
            "decode_tokens": float(self.decode_tokens),
            "finished": float(len(self.finished)),
            "slot_utilization": self.slots.utilization(),
            "scheduler_dispatches": float(self.scheduler.stats.dispatches),
        }
        rep.update(self.monitor.summary())
        # decode-step semantics for the headline percentiles: prefill
        # dispatches (compile-heavy) are tracked too but reported apart
        rep.update(self.monitor.summary_for("decode"))
        rep.update({f"prefill_{k}": v
                    for k, v in self.monitor.summary_for("prefill").items()})
        lats = [r.latency_s for r in self.finished if r.latency_s is not None]
        if lats:
            rep["req_mean_latency_s"] = float(np.mean(lats))
            rep["req_p95_latency_s"] = float(np.percentile(lats, 95))
        return rep
