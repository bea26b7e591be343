"""Multi-tenant inference engine: the decode loop of one served replica.

R tenants of the same architecture (different weights) are served by ONE
jitted, tenant-vmapped decode step over stacked params + stacked caches —
every projection/FFN GEMM in the model becomes an inter-model batched
super-kernel, which is the paper's mechanism applied to whole models.

The engine schedules nothing. In a fleet the pump's space-time
scheduler (``core.scheduler``) decides when and where a cohort runs
(``serving.fleet.EngineReplica``); the engine runs it to completion: it
prefills each queued request that has a free slot, then decodes every
active slot, one step at a time, until the cohort drains. It records
each prefill's and each tenant's decode latency in its own
``LatencyMonitor`` (``monitor``), which ``report`` reads.

``mode="time_only"`` provides the contrast case: each tenant's decode
runs as its OWN program, one after another with a device sync between
them (one program per tenant per step), modeling CUDA context
time-slicing — a tenant's recorded latency then includes waiting for
every tenant ahead of it (the paper's linear-slowdown mechanism). Used
by benchmarks/fig3_latency.py and fig4_predictability.py.
"""

from __future__ import annotations

import dataclasses
import functools
import time
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.slo import LatencyMonitor
from repro.core.tenancy import (
    broadcast_tenants, map_tenant_axis, stack_params, take_tenant,
    tenant_axes, tenant_counts)
from repro.models import Model
from repro.obs.recorder import STEP_STAMPS
from repro.serving.kv_cache import SlotManager
from repro.serving.request import InferenceRequest, RequestState
from repro.serving.sampling import SamplingParams, sample


@jax.jit
def _greedy(logits: jax.Array):
    """The greedy token of each row of ``logits``, and its logit."""
    return (jnp.argmax(logits, axis=-1).astype(jnp.int32),
            jnp.max(logits, axis=-1).astype(jnp.float32))


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

    The stacked trees keep their layer stack layer-major
    (``core.tenancy``): ``decode_all`` maps every tenant over each leaf's
    own tenant axis, so the model's scan over the layers reads the
    weights and recurrent state in place. The attention caches are not
    mapped: every tenant's step reads its rows of the whole K/V stacks in
    place (``Model.decode_step``), and one scatter after the step writes
    the new token's keys and values for every tenant and layer
    (``Model.write_kv``). Per-tenant programs take the whole stacked tree
    plus a traced tenant index and pick the tenant's rows layer by layer
    inside the scan: one compile serves every tenant, and no copy of a
    tenant's whole stack is made.

    Nothing here donates: the engine donates the K/V caches where it
    calls ``decode_all`` (``donate_caches``).
    """

    def decode_all(params, tokens, caches, lengths):
        kv, axes = model.kv_entries(), tenant_axes(caches)
        cache_axes = {g: {k: None if (g, k) in kv else axes[g] for k in sub}
                      for g, sub in caches.items()}
        logits, new = jax.vmap(
            lambda p, tok, c, ln, t: model.decode_step(p, tok, c, ln,
                                                       kv_tenant=t),
            in_axes=(tenant_axes(params), 0, cache_axes, 0, 0),
            out_axes=(0, axes),
        )(params, tokens, caches, lengths, jnp.arange(tokens.shape[0]))
        return logits, model.write_kv(caches, new, lengths)

    def decode_one(params, caches, t, tokens, lengths):
        return model.forward_decode(
            params, tokens, take_tenant(caches, t), lengths, tenant=t)

    def prefill(params, t, tokens):
        return model.forward_prefill(
            params, tokens, cache_len=cache_len, tenant=t)

    def prefill_cont(params, t, tokens, caches, start):
        return model.forward_prefill(
            params, tokens, cache_len=cache_len,
            caches=caches, start=start, tenant=t)

    return EnginePrograms(*(jax.jit(f) for f in (
        decode_all, decode_one, prefill, prefill_cont)))


def split_kv(caches: Any, kv: frozenset) -> tuple:
    """(the ``kv`` entries of a cache tree, its other entries), each a
    tree of the same groups; ``kv`` holds ``(group, key)`` pairs
    (``Model.kv_entries``)."""
    return tuple({g: {k: c for k, c in sub.items() if ((g, k) in kv) == want}
                  for g, sub in caches.items()} for want in (True, False))


@functools.lru_cache(maxsize=16)
def donate_caches(program: Any, kv: frozenset) -> Any:
    """``program`` (an ``engine_programs`` ``decode_all``) with its K/V
    caches donated, called as ``(params, tokens, kv_caches, state,
    lengths)`` on ``split_kv``'s halves of the caches: the step writes
    the new token's keys and values into the stacks it read instead of
    into a copy. The recurrent state (``state``) is not donated: the
    layer scan writes each layer's new state into a fresh stack, which
    a donated input would have to be copied into on every step.

    Cached by program, as ``engine_programs`` is, so replicas on one
    device compile it once. The donation is applied here, over whatever
    ``engine_programs`` returns, and not inside it: a wrapper of
    ``decode_all`` may still read its input caches after calling it."""

    def decode_all(params, tokens, kv_caches, state, lengths):
        caches = {g: {**state[g], **kv_caches[g]} for g in state}
        return program(params, tokens, caches, lengths)

    return jax.jit(decode_all, donate_argnums=2)


class _StepPhases:
    """One decode step's ``STEP_STAMPS`` on the host clock, taken at the
    synchronisation points the step already has. While a profiler session
    is active each phase is also a ``TraceAnnotation`` named after the
    stamp that closes it, so the host phases show under the device rows."""

    __slots__ = ("t", "i", "_open")

    def __init__(self):
        self.t = [time.perf_counter()] + [float("nan")] * (len(STEP_STAMPS) - 1)
        self.i = 0
        self._open = (self._annotate(1)
                      if jax.profiler.TraceAnnotation.is_enabled() else None)

    @staticmethod
    def _annotate(i: int):
        ann = jax.profiler.TraceAnnotation(STEP_STAMPS[i])
        ann.__enter__()
        return ann

    def mark(self, i: int) -> None:
        """Stamp ``STEP_STAMPS[i]``; stamping it again moves it later."""
        self.t[i] = time.perf_counter()
        if i != self.i:
            self.i = i
            if self._open is not None:
                self._open.__exit__(None, None, None)
                self._open = (self._annotate(i + 1)
                              if i + 1 < len(STEP_STAMPS) else None)


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


class MultiTenantEngine:
    """R tenants' decode loop over ONE stacked weight tree.

    ``stacked_params`` carries every tenant's weights along each leaf's
    tenant axis (``core.tenancy.stack_params`` / ``init_stacked``); the
    engine holds no per-tenant copy, so engines on one device can share
    the tree. The stacked caches, in the same layout, are placed on the
    device that holds the weights, which is what pins a fleet replica to
    its chip. Each space_time step donates the K/V stacks to
    ``decode_all`` (``donate_caches``), which writes the new token into
    them in place: ``self.caches`` is replaced on every step, and arrays
    taken from it before a step are deleted by it.
    """

    def __init__(self, model: Model, stacked_params: Any, config: EngineConfig):
        counts = tenant_counts(stacked_params)
        if counts != {config.num_tenants}:
            raise ValueError(
                f"stacked params hold {sorted(counts)} tenants, "
                f"config has {config.num_tenants}")
        self.model = model
        self.cfg = config
        self.stacked_params = stacked_params

        R, B = config.num_tenants, config.slots_per_tenant
        single = model.init_caches(B, config.cache_len)
        device = next(iter(jax.tree.leaves(stacked_params)[0].devices()))
        self.caches = jax.device_put(broadcast_tenants(single, R), device)
        self.slots = SlotManager(R, B)
        # per-tenant prefill and decode latency, against each request's SLO
        self.monitor = LatencyMonitor()

        self.queue: List[InferenceRequest] = []
        self.active: Dict[tuple, InferenceRequest] = {}  # (tenant, slot) -> req
        self.finished: List[InferenceRequest] = []
        # flight-recorder shard (repro.obs): the API layer attaches its
        # replica's shard, and each decode step writes one row into it
        self.recorder = None
        self._phases: Optional[_StepPhases] = None  # the step in flight
        self.last_token = np.zeros((R, B), np.int32)
        self.steps = 0
        self.decode_tokens = 0
        self._sample_key = jax.random.PRNGKey(config.seed)
        self._step_logits: Optional[jax.Array] = None  # (R, B, V)
        self._pending_caches: Dict[int, Any] = {}      # time_only per-tenant updates
        self._pending_logits: Dict[int, jax.Array] = {}

        progs = engine_programs(model, config.cache_len)
        # the K/V caches are donated: self.caches is replaced every step
        self._kv = frozenset(model.kv_entries())
        self._decode_all = donate_caches(progs.decode_all, self._kv)
        self._decode_one = progs.decode_one
        self._prefill = progs.prefill
        self._prefill_cont = progs.prefill_cont

    # ------------------------------------------------------------------ intake
    def submit(self, req: InferenceRequest, now: Optional[float] = None) -> None:
        req.arrival_time = now if now is not None else time.perf_counter()
        req.state = RequestState.QUEUED
        self.queue.append(req)

    # ------------------------------------------------------------------ prefill
    def _admit(self) -> None:
        """Give each queued request whose tenant has a free slot that slot,
        in queue order, then prefill them in the same order: install each
        prompt's cache into the stacked cohort and activate its decode
        slot. A request whose tenant has no free slot stays queued.

        Prefill runs at EXACT prompt length (one compile per distinct
        length). Padding would corrupt SSM/RWKV recurrent state; callers
        wanting fewer compiles should bucket their prompt lengths."""
        rec = self.recorder
        start = time.perf_counter()
        admitted, remaining = [], []
        for req in self.queue:
            slot = self.slots.acquire(req.tenant_id, req.request_id)
            if slot is None:
                remaining.append(req)
                continue
            req.slot = slot
            if rec is not None:
                req.slot_time = time.perf_counter()
            req.state = RequestState.PREFILLING
            admitted.append(req)
        self.queue = remaining
        for req in admitted:
            tokens = jnp.asarray(np.asarray(req.prompt, np.int32))[None, :]
            logits, cache = self._run_prefill(req.tenant_id, tokens)
            tok, value = jax.device_get(_greedy(logits[0]))
            tok = int(tok)
            req.generated.append(tok)
            req.token_logits.append(float(value))
            req.first_token_time = time.perf_counter()
            self._scatter_slot(req.tenant_id, req.slot, cache)
            self.slots.set_length(req.tenant_id, req.slot, tokens.shape[1])
            self.last_token[req.tenant_id, req.slot] = tok
            req.state = RequestState.DECODING
            self.active[(req.tenant_id, req.slot)] = req
            self.monitor.record(req.tenant_id, req.first_token_time - start,
                                req.slo_s, kind="prefill")

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
        """Insert a prefilled (batch=1) cache into the stacked cohort cache.

        Every leaf is installed whole: keys and values over the whole
        cache length, and a recurrent layer's conv tails and SSM state.
        The recurrent state is read by no length mask, so this is what
        keeps a slot's previous request out of the next one.

        Each leaf's slot axis follows its tenant axis: unit caches are
        (reps, R, B, ...), the others (R, B, ...)."""

        def upd(ax: int, big: jax.Array, small: jax.Array) -> jax.Array:
            idx = [0] * big.ndim
            idx[ax] = tenant
            idx[ax + 1] = slot
            return jax.lax.dynamic_update_slice(
                big, jnp.expand_dims(small, ax).astype(big.dtype), tuple(idx)
            )

        self.caches = map_tenant_axis(upd, self.caches, single_cache)

    # ------------------------------------------------------------------ decode
    def _lengths(self) -> np.ndarray:
        R, B = self.cfg.num_tenants, self.cfg.slots_per_tenant
        out = np.zeros((R, B), np.int32)
        for t in range(R):
            out[t] = self.slots.lengths(t)
        return out

    def _decode_cohort(self) -> None:
        """space_time: ONE tenant-vmapped program for the whole cohort —
        every active tenant shares the dispatch and its completion."""
        phases = self._phases
        lengths = jnp.asarray(self._lengths())
        tokens = jnp.asarray(self.last_token)
        logits, self.caches = self._decode_all(
            self.stacked_params, tokens,
            *split_kv(self.caches, self._kv), lengths)
        if phases is not None:
            phases.mark(1)
        self._step_logits = jax.block_until_ready(logits)
        if phases is not None:
            phases.mark(2)

    def _decode_tenant(self, t: int) -> None:
        """time_only: tenant ``t``'s own program, with a device sync after
        it (the CUDA context-switch analogue). Cache/logit updates are
        staged and scattered into the stacked trees once per step. A
        recorded step's ``step.enqueue`` is its first tenant's program
        returning, its ``step.ready`` the last tenant's sync."""
        phases = self._phases
        tokens_t = jnp.asarray(self.last_token[t])
        lengths_t = jnp.asarray(self.slots.lengths(t), jnp.int32)
        lg, nc = self._decode_one(self.stacked_params, self.caches,
                                  np.int32(t), tokens_t, lengths_t)
        if phases is not None and phases.i < 1:
            phases.mark(1)
        lg = jax.block_until_ready(lg)
        if phases is not None:
            phases.mark(2)
        self._pending_caches[t] = nc
        self._pending_logits[t] = lg

    def _apply_pending_tenant_updates(self) -> None:
        """Scatter time_only per-tenant cache/logit updates in one pass."""
        if not self._pending_caches:
            return
        ts = sorted(self._pending_caches)
        idx = jnp.asarray(ts)
        small = stack_params([self._pending_caches[t] for t in ts])
        self.caches = map_tenant_axis(
            lambda ax, big, sm: big.at[(slice(None),) * ax + (idx,)].set(
                sm.astype(big.dtype)),
            self.caches, small)
        lgs = jnp.stack([self._pending_logits[t] for t in ts])
        if self._step_logits is None or self._step_logits.shape[-1] != lgs.shape[-1]:
            R, B = self.cfg.num_tenants, self.cfg.slots_per_tenant
            self._step_logits = jnp.zeros((R, B, lgs.shape[-1]), lgs.dtype)
        self._step_logits = self._step_logits.at[idx].set(lgs)
        self._pending_caches.clear()
        self._pending_logits.clear()

    def step(self) -> int:
        """One engine iteration: admit + one decode step. Returns #tokens.

        In space_time mode every active tenant decodes in one merged
        dispatch (identical completion time for every tenant,
        predictability by construction); in time_only mode the tenants
        decode one after another in ascending order. Each active tenant's
        decode latency (its dispatch's completion minus the step's start)
        is recorded against the tightest SLO of its active requests.

        With a recorder attached the step writes one row of
        ``STEP_STAMPS``; it starts after admission, so prefill is not in
        it.
        """
        self._admit()
        if not self.active:
            return 0
        rec = self.recorder
        phases = self._phases = None if rec is None else _StepPhases()

        slo_by_tenant: Dict[int, float] = {}
        for (t, _), req in self.active.items():
            slo_by_tenant[t] = min(slo_by_tenant.get(t, float("inf")), req.slo_s)
        tenants = sorted(slo_by_tenant)
        start = time.perf_counter()
        if self.cfg.mode == "space_time":
            self._decode_cohort()
            done = [time.perf_counter()] * len(tenants)
        else:
            done = []
            for t in tenants:
                self._decode_tenant(t)
                done.append(time.perf_counter())
            self._apply_pending_tenant_updates()
        for t, t_done in zip(tenants, done):
            self.monitor.record(t, t_done - start, slo_by_tenant[t],
                                kind="decode")
        logits = self._step_logits

        if self.cfg.sampling.greedy:
            next_tokens, values = jax.device_get(_greedy(logits))
        else:
            self._sample_key, sub = jax.random.split(self._sample_key)
            toks = sample(logits, self.cfg.sampling, sub)
            next_tokens, values = jax.device_get(
                (toks, jnp.take_along_axis(logits, toks[..., None], -1)[..., 0]))
        if phases is not None:
            phases.mark(3)
        produced = 0
        now = time.perf_counter()
        for (t, s), req in list(self.active.items()):
            tok = int(next_tokens[t, s])
            req.generated.append(tok)
            req.token_logits.append(float(values[t, s]))
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
        if phases is not None:
            phases.mark(4)
            rec.record_step(phases.t, produced, len(tenants))
            self._phases = None
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
