"""Bring-up check: the served path at full width on one TPU.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # four one-chip replicas vs one

With no option, in one process, it:

1. fails unless JAX's first device is a TPU, and prints its kind;
2. runs one ``SuperKernelCache.execute`` of each paper GEMM shape at
   R=8 and compares it with ``jnp.einsum``;
3. boots the HTTP front door (``launch.serve.FleetServer``) over a live
   fleet of one replica serving stablelm-1.6b at its published widths in
   bf16 for 2 tenants, with random weights from a seed; sends one
   warm-up predict (it compiles prefill and decode), then 6 concurrent
   predicts from both tenants (128-token prompts, 32 new tokens each),
   then reads ``/v1/report``;
4. compares each served request's first-token logits with a plain
   float32 reference: the same weights through ``kernels/ref.py`` at
   highest matmul precision;
5. checks that the compiled decode step holds a Pallas kernel
   (``tpu_custom_call``) and prints the device's peak memory.

``--chips 4`` runs only the replica phase: the same requests through a
fleet of one replica, then through four replicas (one per chip) behind
the ``least_cost`` router; each request's greedy tokens must be equal,
and every replica must serve.

The last line of standard output is one JSON object with ``"ok": true``
and the device as JAX reports it. Any failed check raises, so the
script exits non-zero and prints no such line. These are bring-up
checks, not benchmark numbers.
"""

from __future__ import annotations

import argparse
import contextlib
import gc
import json
import os
import statistics
import sys
import threading
import time
import urllib.request
from concurrent.futures import ThreadPoolExecutor

ARCH = "stablelm-1.6b"
TENANTS = 2
PROMPT_TOKENS = 128
NEW_TOKENS = 32
SEED = 0
REQUESTS = 6
GEMM_R = 8
# served bf16 logits vs the float32 reference: relative L2 error bound
LOGIT_REL_TOL = 5e-2
GEMM_REL_TOL = 1e-2
HBM_BYTES = 16e9


def log(msg: str) -> None:
    print(msg, flush=True)


def require_tpu():
    import jax

    devices = jax.devices()
    d = devices[0]
    log(f"device: platform={d.platform} kind={d.device_kind!r} "
        f"count={len(devices)}")
    if d.platform != "tpu":
        raise SystemExit(f"chip_smoke: needs a TPU; JAX found platform "
                         f"{d.platform!r} ({d.device_kind!r})")
    return devices


# ------------------------------------------------------------- GEMM phase
def gemm_phase() -> None:
    import jax
    import jax.numpy as jnp

    from repro.config import ScheduleConfig
    from repro.configs.paper_sgemm import PAPER_GEMM_SHAPES
    from repro.core.queue import GemmProblem
    from repro.core.superkernel import SuperKernelCache

    cache = SuperKernelCache(ScheduleConfig())
    key = jax.random.PRNGKey(SEED)
    for name, g in sorted(PAPER_GEMM_SHAPES.items()):
        kx, kw = jax.random.split(jax.random.fold_in(key, g.M * g.N * g.K))
        xs = jax.random.normal(kx, (GEMM_R, g.M, g.K), jnp.float32)
        ws = jax.random.normal(kw, (GEMM_R, g.K, g.N), jnp.float32)
        got = jnp.stack(cache.execute(
            [GemmProblem(tenant_id=r, x=xs[r], w=ws[r]) for r in range(GEMM_R)]))
        want = jnp.einsum("rmk,rkn->rmn", xs, ws,
                          precision=jax.lax.Precision.HIGHEST)
        err = float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)))
        log(f"gemm {name} R={GEMM_R} M={g.M} K={g.K} N={g.N}: "
            f"max error / max |ref| = {err:.3g} (limit {GEMM_REL_TOL:g})")
        if not err < GEMM_REL_TOL:
            raise AssertionError(f"gemm {name}: error {err:.3g}")
    log("gemm check: passed")


# ------------------------------------------------------------ serve phase
def serve_spec(replicas: int, batching_window_s: float):
    from repro.api.spec import (
        FleetSpec,
        RouterSpec,
        SchedulerSpec,
        ServeSpec,
        SystemSpec,
        WorkloadSpec,
    )

    system = SystemSpec(
        mode="live",
        # "serving": requests are priced as this model's prefills (one
        # bucket for both tenants, so concurrent requests merge into one
        # engine cohort), which is what least_cost spreads replicas by
        workload=WorkloadSpec(
            mix="serving", tenants=TENANTS, events=REQUESTS, seed=SEED,
            rate_hz=1.0, arch=ARCH,
            prompt_tokens=PROMPT_TOKENS, max_new_tokens=NEW_TOKENS),
        fleet=FleetSpec(replicas=replicas),
        router=RouterSpec(policy="least_cost"),
        # cap admission with no cap: every request is admitted
        scheduler=SchedulerSpec(admission_policy="cap",
                                batching_window_s=batching_window_s),
    )
    # a cold compile of the full-width programs is inside the timeout
    return ServeSpec(system=system, port=0, request_timeout_s=900.0,
                     poll_interval_s=0.01)


@contextlib.contextmanager
def serving(spec):
    from repro.launch.serve import FleetServer

    t0 = time.perf_counter()
    server = FleetServer(spec)
    build_s = time.perf_counter() - t0
    server.start()
    http = threading.Thread(target=server.httpd.serve_forever, daemon=True)
    http.start()
    try:
        yield server, build_s
    finally:
        server.httpd.shutdown()
        server.shutdown()
        http.join(timeout=30)


def post(port: int, path: str, doc=None) -> dict:
    data = None if doc is None else json.dumps(doc).encode()
    req = urllib.request.Request(f"http://127.0.0.1:{port}{path}", data=data)
    with urllib.request.urlopen(req, timeout=1000) as r:
        if r.status != 200:
            raise AssertionError(f"{path}: HTTP {r.status}")
        return json.loads(r.read())


def predict(port: int, tenant: int, prompt) -> dict:
    out = post(port, "/v1/predict", {"tenant_id": tenant, "prompt": prompt})
    if len(out["tokens"]) != NEW_TOKENS:
        raise AssertionError(f"tenant {tenant}: {len(out['tokens'])} tokens, "
                             f"expected {NEW_TOKENS}")
    return out


def make_requests(vocab: int, n: int):
    import numpy as np

    rng = np.random.RandomState(SEED + 1)
    return [(i % TENANTS, rng.randint(1, vocab, PROMPT_TOKENS).tolist())
            for i in range(n)]


def serve_concurrently(port: int, requests):
    with ThreadPoolExecutor(len(requests)) as pool:
        return list(pool.map(lambda r: predict(port, *r), requests))


def decode_program(engine):
    """The engine's decode step over every tenant, compiled as served."""
    import jax.numpy as jnp

    R, B = engine.cfg.num_tenants, engine.cfg.slots_per_tenant
    zeros = jnp.zeros((R, B), jnp.int32)
    return engine._decode_all.lower(
        engine.stacked_params, zeros, engine.caches, zeros).compile()


@contextlib.contextmanager
def reference_kernels():
    """Route ``repro.kernels.ops`` to the jnp references while tracing."""
    from repro.kernels import ops

    saved = ops._use_pallas
    ops._use_pallas = lambda force_pallas: False
    try:
        yield
    finally:
        ops._use_pallas = saved


def reference_check(engine, requests, outs) -> None:
    """Each request's first-token logits, served (bf16, Pallas) vs a
    float32 reference on the same weights (jnp kernels, highest matmul
    precision). Every request must be within ``LOGIT_REL_TOL``; its
    served first token must be the served logits' top-1; and it must be
    the reference's top-1 wherever the reference's top-2 margin exceeds
    the measured error (elsewhere a flip is within the tolerance)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.serving.engine import _tenant

    model = engine.model
    cache_len = engine.cfg.cache_len
    with reference_kernels():
        def ref_prefill(params, t, tokens):
            p = _tenant(params, t)
            # an f32 residual stream makes every matmul promote its bf16
            # weight to f32: the weights are the served ones, exactly
            p = dict(p, embed=p["embed"].astype(jnp.float32))
            return model.forward_prefill(p, tokens, cache_len=cache_len)[0]

        ref_fn = jax.jit(ref_prefill)
        decisive = 0
        for (tenant, prompt), out in zip(requests, outs):
            tokens = jnp.asarray([prompt], jnp.int32)
            t = np.int32(tenant)
            served = np.asarray(engine._prefill(
                engine.stacked_params, t, tokens)[0][0], np.float32)
            with jax.default_matmul_precision("highest"):
                ref = np.asarray(ref_fn(engine.stacked_params, t, tokens)[0],
                                 np.float32)
            err = float(np.linalg.norm(served - ref) / np.linalg.norm(ref))
            diff = float(np.max(np.abs(served - ref)))
            top2 = np.sort(ref)[-2:]
            margin = float(top2[1] - top2[0])
            log(f"reference tenant={tenant}: rel L2 error {err:.4g} "
                f"(limit {LOGIT_REL_TOL:g}), max |diff| {diff:.4g}, "
                f"ref top-2 margin {margin:.4g}, top-1 served "
                f"{int(served.argmax())} ref {int(ref.argmax())}, "
                f"first token {out['tokens'][0]}")
            if not err < LOGIT_REL_TOL:
                raise AssertionError(f"logits error {err:.4g}")
            if out["tokens"][0] != int(served.argmax()):
                raise AssertionError("served first token is not the "
                                     "served logits' top-1")
            if margin > 2 * diff:
                decisive += 1
                if int(served.argmax()) != int(ref.argmax()):
                    raise AssertionError("top-1 differs from the reference")
        if decisive == 0:
            raise AssertionError("no request had a decisive reference top-1")
    log(f"reference check: passed ({decisive}/{len(outs)} decisive top-1 "
        f"matches)")


def serve_phase(devices) -> None:
    from repro.config import get_config

    cfg = get_config(ARCH)
    log(f"config: {cfg.name} ({cfg.source}) layers={cfg.num_layers} "
        f"d_model={cfg.d_model} heads={cfg.num_heads} d_ff={cfg.d_ff} "
        f"vocab={cfg.vocab_size} dtype={cfg.dtype} "
        f"params/tenant={cfg.param_count()} tenants={TENANTS}")
    with serving(serve_spec(1, 0.002)) as (server, build_s):
        engine = server.fleet.engines[0].engine
        leaves = engine.stacked_params
        import jax

        n_params = sum(x.size for x in jax.tree.leaves(leaves))
        log(f"fleet build (random weights, stacked, on device): "
            f"{build_s:.2f} s; stacked params {n_params} "
            f"({n_params // TENANTS} per tenant)")
        requests = make_requests(cfg.vocab_size, REQUESTS + 1)
        t0 = time.perf_counter()
        predict(server.port, *requests[0])
        warm_s = time.perf_counter() - t0
        log(f"compile seconds: fleet build + warm-up predict "
            f"{build_s + warm_s:.2f} (warm-up predict {warm_s:.2f})")
        t0 = time.perf_counter()
        outs = serve_concurrently(server.port, requests[1:])
        wall = time.perf_counter() - t0
        lats = [o["latency_s"] for o in outs]
        log(f"{len(outs)} concurrent predicts, all 200 with {NEW_TOKENS} "
            f"tokens, in {wall:.3f} s; latencies s: "
            + ", ".join(f"{x:.3f}" for x in lats)
            + f"; median {statistics.median(lats):.3f}")
        report = post(server.port, "/v1/report")
        sched = report["metrics"]["scheduler"]
        log(f"report: requests={report['metrics']['http']['requests']} "
            f"completed={sched['completed']:g} "
            f"dispatches={sched['dispatches']:g} "
            f"engine={report['metrics']['engine']}")
        decode = decode_program(engine)
        has_kernel = "tpu_custom_call" in decode.as_text()
        mem = decode.memory_analysis()
        log(f"decode step holds tpu_custom_call: {has_kernel}; compiled "
            f"arguments {mem.argument_size_in_bytes} B, temporaries "
            f"{mem.temp_size_in_bytes} B")
        if not has_kernel:
            raise AssertionError("the decode step runs no Pallas kernel")
        reference_check(engine, requests[1:], outs)
    stats = devices[0].memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    log(f"peak_bytes_in_use: {peak} ({(peak or 0) / 1e9:.3f} GB, "
        f"limit {HBM_BYTES / 1e9:g} GB)")
    if peak is None or not peak < HBM_BYTES:
        raise AssertionError(f"peak_bytes_in_use {peak}")


# ---------------------------------------------------------- replica phase
def replica_phase(devices) -> None:
    """Four one-chip replicas behind least_cost vs one replica."""
    from repro.config import get_config

    if len(devices) < 4:
        raise SystemExit(f"--chips 4 needs 4 devices, found {len(devices)}")
    requests = make_requests(get_config(ARCH).vocab_size, 8)
    tokens = {}
    # a batching window long enough that every request is queued before
    # the first dispatch: least_cost then spreads them by backlog
    for replicas in (1, 4):
        t0 = time.perf_counter()
        with serving(serve_spec(replicas, 0.5)) as (server, build_s):
            outs = serve_concurrently(server.port, requests)
        wall = time.perf_counter() - t0
        served = [o["replica"] for o in outs]
        log(f"replicas={replicas}: {len(outs)} predicts, all 200 with "
            f"{NEW_TOKENS} tokens; routed to replicas {served}; "
            f"build {build_s:.2f} s, total {wall:.2f} s")
        tokens[replicas] = [o["tokens"] for o in outs]
        if replicas == 4 and set(served) != set(range(4)):
            raise AssertionError(f"replicas served: {sorted(set(served))}")
        del server, outs
        gc.collect()
        log(f"device 0 bytes_in_use after teardown: "
            f"{(devices[0].memory_stats() or {}).get('bytes_in_use')}")
    same = [a == b for a, b in zip(tokens[1], tokens[4])]
    log(f"greedy tokens equal to the one-replica run: {sum(same)}/{len(same)}")
    if not all(same):
        raise AssertionError("four-replica tokens differ from one replica")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the four-replica phase")
    args = ap.parse_args(argv)
    sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(
        __file__)), "src"))
    from repro.launch.compile_cache import enable_compile_cache

    log(f"compile cache: {enable_compile_cache()}")
    devices = require_tpu()
    t0 = time.perf_counter()
    if args.chips == 4:
        replica_phase(devices)
    else:
        gemm_phase()
        serve_phase(devices)
    log(f"total seconds: {time.perf_counter() - t0:.1f}")
    d = devices[0]
    print(json.dumps({"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
