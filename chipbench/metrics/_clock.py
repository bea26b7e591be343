"""The flight recorder's rows on the device trace's clock.

The recorder stamps ``time.perf_counter`` seconds; the ``.xplane.pb``
gives nanoseconds from its own session start. ``fit`` finds the one
offset between them from the decode steps: a step's ``decode_all``
execution ends on the device between the step's ``step.enqueue`` and
``step.ready`` stamps, and ``step.ready`` comes just after it (the host
waking from ``block_until_ready``). The coarse start is the window's
``work["t0"]``; the offset that lines the most execution ends up with
``step.ready`` stamps is refined by the median residual over the matched
steps. So the mapping carries the median wake-up latency: it places the
device that much later than it ran.

A program whose recorder has no such rows (no ``_stp_*`` or ``_stg_*``
columns in its shards) gives nothing to read here, and raises nothing.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Tuple

import numpy as np

#: fewer matched decode steps than this and there is no fit
MIN_MATCHED = 20
#: the matched residuals' 90th percentile (absolute, about their median)
#: must stay under this, else there is no fit
MAX_RESIDUAL_S = 1e-3
#: how far from ``work["t0"]`` the trace's session start may lie
SEARCH_S = 5.0
#: the width of the window in which the most candidate offsets gather
MODE_WIDTH_S = 0.5e-3
#: how far after its ``step.ready`` a matched execution's end may map
#: before the refinement (the median wake-up latency moves it there)
MATCH_SLACK_S = 5e-3
#: the program a decode step runs
DECODE = "jit_decode_all"


@dataclasses.dataclass
class Fit:
    offset_s: float          # host second of the trace's nanosecond 0
    matched: int             # decode steps matched to an execution
    residual_p90_s: float    # p90 of |residual - median| over them

    def host(self, ns):
        """Host seconds of trace nanoseconds (a number or an array)."""
        return self.offset_s + np.asarray(ns, np.float64) * 1e-9


# ------------------------------------------------------------------- rows
def _cols(shard: Dict, names) -> Optional[List[np.ndarray]]:
    cols = [shard.get(n) for n in names]
    if any(c is None for c in cols):
        return None
    return [np.asarray(c, np.float64) for c in cols]


def stage_spans(r, start: str, end: str) -> List[float]:
    """``end - start`` seconds (two ``_stg_*`` columns) of every request
    the front door received at or after the window's start."""
    out: List[float] = []
    for shard in r.spans.values():
        cols = _cols(shard, ("_stg_receive", start, end))
        if cols is None:
            continue
        receive, a, b = cols
        keep = (receive >= r.work["t0"]) & np.isfinite(a) & np.isfinite(b)
        out.extend((b[keep] - a[keep]).tolist())
    return out


_STEP = ("_stp_begin", "_stp_enqueue", "_stp_ready", "_stp_sampled",
         "_stp_end")


def steps(r, shard: Dict) -> Optional[Tuple[np.ndarray, ...]]:
    """The ``_stp_*`` columns of a shard's steps that began at or after
    the window's start, or None where the shard has none."""
    cols = _cols(shard, _STEP)
    if cols is None:
        return None
    keep = cols[0] >= r.work["t0"]
    return tuple(c[keep] for c in cols)


# ------------------------------------------------------------------ clock
def fit_offset(enqueue, ready, ends_ns, coarse_s: float) -> Optional[Fit]:
    """The offset mapping trace nanoseconds ``ends_ns`` (decode
    executions' ends) onto host seconds, from steps' ``enqueue`` and
    ``ready`` stamps; ``coarse_s`` is the host second taken for
    nanosecond 0 before the fit."""
    enqueue = np.asarray(enqueue, np.float64)
    ready = np.asarray(ready, np.float64)
    order = np.argsort(ready)
    enqueue, ready = enqueue[order], ready[order]
    ends = coarse_s + np.asarray(ends_ns, np.float64) * 1e-9
    if len(ends) < MIN_MATCHED or len(ready) < MIN_MATCHED:
        return None
    # every (execution, step) pair within the search gives a candidate
    # offset; the true one gathers one per step in a narrow window
    lo = np.searchsorted(ready, ends - SEARCH_S)
    hi = np.searchsorted(ready, ends + SEARCH_S)
    cand = np.concatenate([ready[a:b] - e for a, b, e in zip(lo, hi, ends)])
    if not len(cand):
        return None
    cand.sort()
    count = np.searchsorted(cand, cand + MODE_WIDTH_S, side="right") \
        - np.arange(len(cand))
    best = int(np.argmax(count))
    delta = float(np.median(cand[best:best + count[best]]))
    # each execution to the step whose [enqueue, ready + slack] holds its
    # mapped end; a step takes one execution, the nearest
    mapped = ends + delta
    j = np.searchsorted(ready, mapped - MATCH_SLACK_S)
    ok = j < len(ready)
    j = np.where(ok, j, 0)
    ok &= (enqueue[j] <= mapped) & (mapped <= ready[j] + MATCH_SLACK_S)
    res = ready[j[ok]] - mapped[ok]
    nearest = np.argsort(np.abs(res), kind="stable")
    _, first = np.unique(j[ok][nearest], return_index=True)
    res = res[nearest][first]
    if len(res) < MIN_MATCHED:
        return None
    med = float(np.median(res))
    p90 = float(np.percentile(np.abs(res - med), 90))
    if p90 > MAX_RESIDUAL_S:
        return None
    return Fit(coarse_s + delta + med, len(res), p90)


def fit(r) -> Optional[Fit]:
    """The fitted clock of a traced run, or None (no trace, no step rows,
    too few matches, too wide a spread)."""
    if r.trace is None or not r.spans:
        return None
    enqueue, ready = [], []
    for shard in r.spans.values():
        rows = steps(r, shard)
        if rows is not None:
            enqueue.append(rows[1])
            ready.append(rows[2])
    if not enqueue:
        return None
    ends = [x.end_ns for d in r.trace.devices for x in d.executions
            if x.program.startswith(DECODE)]
    return fit_offset(np.concatenate(enqueue), np.concatenate(ready), ends,
                      r.work["t0"])


# ------------------------------------------------------------- idle split
def _union(spans: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for a, b in sorted(spans):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def _intersect(xs, ys) -> List[Tuple[float, float]]:
    out, i, j = [], 0, 0
    while i < len(xs) and j < len(ys):
        a, b = max(xs[i][0], ys[j][0]), min(xs[i][1], ys[j][1])
        if a < b:
            out.append((a, b))
        if xs[i][1] < ys[j][1]:
            i += 1
        else:
            j += 1
    return out


def _covered(spans, a: np.ndarray, b: np.ndarray) -> np.ndarray:
    """Seconds of each ``[a, b]`` that the disjoint sorted ``spans``
    cover."""
    if not spans:
        return np.zeros_like(a)
    xs = np.array([t for s in spans for t in s], np.float64)
    cum = np.concatenate([[0.0], np.cumsum([e - s for s, e in spans])])
    # the measure covered up to x, piecewise linear through the spans
    fs = np.repeat(cum, 2)[1:-1]
    return np.interp(b, xs, fs) - np.interp(a, xs, fs)


def _idle(device, window_ns: float) -> Tuple[np.ndarray, np.ndarray]:
    """A device's idle intervals inside the traced window: before its
    first execution, between its operations, after its last execution."""
    spans = list(device.gaps)
    if device.executions:
        first = min(x.start_ns for x in device.executions)
        last = max(x.end_ns for x in device.executions)
        spans = [(0.0, first)] + spans + [(last, window_ns - last)]
    a = np.array([g0 for g0, _ in spans], np.float64)
    b = a + np.array([g for _, g in spans], np.float64)
    a, b = np.clip(a, 0.0, window_ns), np.clip(b, 0.0, window_ns)
    keep = b > a
    return a[keep], b[keep]


def idle_split(r) -> Optional[Dict[str, float]]:
    """The device's idle share of the traced window, in percent, split by
    what the host was doing: inside a decode step (``step_host``), inside
    a fleet dispatch but outside its steps (``dispatch_other``), or with
    no dispatch running (``no_dispatch``); mean over the cell's chips.
    Replica ``i`` runs on device ``i % devices``."""
    clock = fit(r)
    if clock is None:
        return None
    n = len(r.trace.devices)
    window_ns = r.trace.window_s * 1e9
    per_device = [([], []) for _ in range(n)]
    for key, shard in r.spans.items():
        rid = int(shard.get("replica_id", key))
        rows = steps(r, shard)
        t0, dur = shard.get("_dsp_t0"), shard.get("_dsp_dur")
        step_spans, dispatch_spans = per_device[rid % n]
        if rows is not None:
            step_spans.extend(zip(rows[0], rows[4]))
        if t0 is not None:
            dispatch_spans.extend((a, a + d) for a, d in zip(t0, dur))
    out = {"step_host": 0.0, "dispatch_other": 0.0, "no_dispatch": 0.0}
    for device, (step_spans, dispatch_spans) in zip(r.trace.devices,
                                                    per_device):
        a_ns, b_ns = _idle(device, window_ns)
        a, b = clock.host(a_ns), clock.host(b_ns)
        in_steps = _union(step_spans)
        in_dispatch = _union(dispatch_spans)
        step = _covered(in_steps, a, b)
        other = (_covered(in_dispatch, a, b)
                 - _covered(_intersect(in_steps, in_dispatch), a, b))
        out["step_host"] += float(step.sum())
        out["dispatch_other"] += float(other.sum())
        out["no_dispatch"] += float((b - a - step - other).sum())
    return {k: 100.0 * v / (r.trace.window_s * n) for k, v in out.items()}
