"""Served cells of the Mamba2 + attention hybrid: ``served.py``'s path
(the HTTP front door of a live fleet, the open loop, the metrics and the
knee search), checked against ``reference/hybrid.py`` in place of
``reference/lm.py``.

The check reads, beside each served token, the logit the engine picked
it with (``InferenceRequest.token_logits`` of the engines' finished
requests, matched by tenant, prompt and tokens): with random weights
this model's greedy token is the token it was fed, whatever its layers
compute, so the served tokens alone would pass a broken model.
"""

from __future__ import annotations

import gc
import time

from chipbench import harness, traffic as gen, xplane
from chipbench.compiles import CompileCounter
from chipbench.drivers import served

sweep = served.sweep


def run(ctx: harness.Context) -> harness.RunResult:
    from chipbench.reference import hybrid

    cell, config, traffic = ctx.cell, ctx.cell.config, ctx.cell.traffic
    vocab = int(config["vocab_size"])
    out_tokens = int(traffic["output_tokens"])
    tenants = int(config["tenants"])
    compiles = CompileCounter()

    srv = served.Served(served.serve_spec(config, traffic, ctx.seed,
                                          ctx.trace))
    try:
        served.warm_up(srv, config, traffic, vocab, ctx.seed)
        reqs = gen.open_loop(traffic, ctx.seconds, tenants, vocab, ctx.seed)
        before = srv.counters()
        tracer = (xplane.Tracer(ctx.scratch / "trace", len(ctx.devices))
                  if ctx.trace else None)
        setup_s = time.perf_counter() - ctx.t_process
        compiles.start()
        if tracer:
            tracer.start()
        recs, t0, late = served.open_loop(srv, reqs, ctx.seconds)
        if tracer:
            tracer.stop()
        compiles.stop()
        after = srv.counters()
        spans = srv.spans() if ctx.trace else {}
        device = harness.device_doc(ctx.devices)
        served_logits = token_logits(srv)
    finally:
        srv.close()
    del srv
    gc.collect()

    e2e = served.end_to_end(recs, t0, ctx.seconds, out_tokens)
    harness.log(f"window: {len(recs)} requests, {e2e['tokens_per_s']!r} "
                f"tokens/s completed in it, generator at most "
                f"{late * 1e3:.3f} ms late; {compiles.describe()}")
    failed = sum(1 for r in recs
                 if r.tokens is None or len(r.tokens) != out_tokens)
    for r in recs:
        if r.error:
            harness.log(f"request failed: {r.error}")
            break

    sample = served.pick_sample(recs, int(traffic["check_requests"]),
                                ctx.seed)
    t_ref = time.perf_counter()
    seqs = [(r.tenant, r.prompt, r.tokens) for r in sample]
    values = [served_logits.get(_key(*s)) for s in seqs]
    worst = hybrid.widest_error(config, served.weight_seed(ctx.seed), seqs,
                                values)
    harness.log(f"reference: {len(sample)} requests, "
                f"{sum(len(r.tokens) for r in sample)} served tokens, "
                f"{time.perf_counter() - t_ref:.1f} s")
    checks = [harness.Check("served_token_logit_error", worst,
                            float(config["limits"]["logit_error"]))]
    metrics = {"setup_s": setup_s, **e2e}

    readings = None
    if ctx.trace:
        readings = harness.Readings(
            cell=cell, peaks=ctx.peaks,
            trace=tracer.reduce() if tracer else None,
            counters={k: served._delta(before[k], after[k]) for k in before},
            spans=spans,
            work={"requests": [(len(r.prompt), len(r.tokens))
                               for r in recs if r.tokens is not None],
                  "t0": t0, "model": config, "tenants": tenants})
    return harness.RunResult(attempted=len(recs), failed=failed,
                             metrics=metrics, checks=checks, device=device,
                             readings=readings, compared=seqs)


def _key(tenant, prompt, tokens):
    return int(tenant), tuple(prompt), tuple(tokens)


def token_logits(srv: served.Served):
    """The logits each finished request's tokens were picked with, by
    tenant, prompt and tokens, over every replica's engine."""
    return {_key(r.tenant_id, r.prompt, r.generated): list(r.token_logits)
            for e in srv.server.fleet.engines for r in e.engine.finished}


def control_reading(cell, seed: int, compared) -> float:
    """The widest error of the tokens fp8 puts first, with their fp8
    logits, on the same sequences."""
    from chipbench.reference import hybrid

    return float(hybrid.control_errors(
        cell.config, served.weight_seed(seed), compared).max())
