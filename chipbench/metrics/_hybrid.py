"""Sums over a served window of the Mamba2 + attention hybrid that its
per-layer readers share (``counts/hybrid.py``)."""

from __future__ import annotations

from chipbench.counts import hybrid as hc
from chipbench.counts import transformer as tc
from chipbench.metrics import _serve


def dims(r):
    return hc.Dims(r.work["model"])


def decode_work(r):
    """(flops, least bytes) of every decode step in the window: each
    step reads every tenant's weights once, and each new token reads and
    writes its state in every Mamba2 layer, and reads its keys and values
    and writes one position in every attention layer."""
    z = dims(r)
    steps = sum(r.counters["steps"])
    flops = 0.0
    moved = float(steps * r.work["tenants"] * hc.tenant_weight_bytes(z))
    for ctx in tc.decode_contexts(_serve.requests(r)):
        flops += hc.decode_token_flops(z, ctx)
        moved += hc.decode_token_bytes(z, ctx)
    return flops, moved
