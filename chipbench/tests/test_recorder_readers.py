"""The readers of the recorder's request-stage and decode-step rows, and
the clock that maps the device trace onto them, on synthetic shards
whose answers are known."""

from array import array

import numpy as np
import pytest

from chipbench.tests.test_metrics import MS, READERS, reduction, served


NEW = ("front_wait_p90_ms.serve", "slot_wait_p90_ms.engine",
       "hold_p90_ms.engine", "step_host_ms.engine", "idle_share.step_host",
       "idle_share.dispatch_other", "idle_share.no_dispatch")


def timeline(offset_s, cohorts=2, steps_per=12, wake=lambda k: 0.0,
             spacing_ms=250.0, first_ms=10.0):
    """A replica's dispatches on the device and in the recorder, the host
    clock reading ``offset_s`` at the trace's nanosecond 0. Each dispatch
    at ``C`` ms: a 3 ms prefill at C+1; steps begin at C+5, 15 ms apart,
    each a 10 ms ``decode_all`` from begin+1, enqueued at begin+1.2,
    ready ``wake(k)`` after the device ends, sampled 2 ms and ended 3 ms
    after it; the dispatch ends 2 ms after its last step."""
    execs, step_rows, dispatches = [], [], []
    k = 0
    for c in range(cohorts):
        C = first_ms + c * spacing_ms
        execs.append(("jit_prefill", (C + 1) * MS, (C + 4) * MS))
        for i in range(steps_per):
            b = C + 5 + 15 * i
            execs.append(("jit_decode_all", (b + 1) * MS, (b + 11) * MS))
            ready = b + 11 + wake(k) * 1e3
            step_rows.append([b, b + 1.2, ready, ready + 2, ready + 3])
            k += 1
        dispatches.append((C, C + 5 + 15 * (steps_per - 1) + 14 + 2))
    host = np.asarray(step_rows) * 1e-3 + offset_s
    shard = {"replica_id": 0,
             "_dsp_t0": array("d", [offset_s + a * 1e-3
                                    for a, _ in dispatches]),
             "_dsp_dur": array("d", [(b - a) * 1e-3 for a, b in dispatches])}
    for j, col in enumerate(("_stp_begin", "_stp_enqueue", "_stp_ready",
                             "_stp_sampled", "_stp_end")):
        shard[col] = array("d", host[:, j])
    return execs, shard


def traced(execs, shard, window_s, t0):
    execs = sorted(execs, key=lambda x: x[1])
    gaps = [(a[2], b[1] - a[2]) for a, b in zip(execs, execs[1:])
            if b[1] > a[2]]
    r = served(trace=reduction(execs, window_s=window_s, gaps=gaps),
               spans={0: shard})
    r.work["t0"] = t0
    return r


@pytest.mark.parametrize("steps_per, jitter_s, found", [
    (12, 50e-6, True),      # 24 steps, wake-ups within 50 us
    (10, 0.0, True),        # 20 steps: just enough
    (9, 50e-6, False),      # 18 steps: too few to fit
    (12, 6e-3, False),      # wake-ups spread over 6 ms: too wide
])
def test_clock_recovers_a_planted_offset(steps_per, jitter_s, found):
    from chipbench.metrics import _clock

    planted = 1234.5678
    rng = np.random.default_rng(7)
    wakes = rng.uniform(0.0, jitter_s, 64)
    execs, shard = timeline(planted, steps_per=steps_per,
                            wake=lambda k: wakes[k], first_ms=60.0)
    # the window starts 47.2 ms after the trace's session does
    fit = _clock.fit(traced(execs, shard, 0.6, planted + 0.0472))
    if not found:
        assert fit is None
        return
    assert fit.matched == 2 * steps_per
    # the mapping carries the median wake-up latency, no more
    assert abs(fit.offset_s - planted) < 0.1e-3
    assert fit.offset_s - planted == pytest.approx(np.median(
        wakes[:2 * steps_per]), abs=1e-6)
    assert fit.residual_p90_s < 1e-3


def stages(rows):
    cols = ("_stg_receive", "_stg_arrive", "_stg_dispatch", "_stg_slot",
            "_stg_first", "_stg_last", "_stg_complete", "_stg_respond")
    out = {"_stg_id": array("q", range(len(rows))),
           "_stg_tenant": array("l", [0] * len(rows))}
    for j, c in enumerate(cols):
        out[c] = array("d", [row[j] for row in rows])
    return out


def test_new_readers_on_a_hand_built_shard():
    t0 = 100.005
    execs, shard = timeline(100.0)
    # receive, arrive, dispatch, slot, first, last, complete, respond:
    # front waits 0.2/0.5/0.1 s, slot waits 10/20/30 ms, holds 0/0.1/0.2 s;
    # the warm-up request before the window start is left out
    shard.update(stages([
        [90.0, 99.0, 99.0, 99.0, 99.1, 99.5, 99.5, 99.6],
        [100.1, 100.3, 100.4, 100.41, 100.5, 101.0, 101.0, 101.1],
        [100.2, 100.7, 100.8, 100.82, 100.9, 101.2, 101.3, 101.4],
        [100.3, 100.4, 100.5, 100.53, 100.6, 100.9, 101.1, 101.2]]))
    r = traced(execs, shard, 0.5, t0)
    got = {n: READERS[n](r) for n in NEW}
    assert got["front_wait_p90_ms.serve"] == pytest.approx(500.0)
    assert got["slot_wait_p90_ms.engine"] == pytest.approx(30.0)
    assert got["hold_p90_ms.engine"] == pytest.approx(200.0)
    # launch 1.2 ms + 3 ms from ready to the step's end
    assert got["step_host_ms.engine"] == pytest.approx(4.2)
    # per dispatch: 4 ms idle in each of 12 steps; 15 ms in the dispatch
    # outside them (1 before the prefill, 1 after it, 11 between steps,
    # 2 after the last); 246 ms busy in a 500 ms window
    assert got["idle_share.step_host"] == pytest.approx(100 * 96 / 500)
    assert got["idle_share.dispatch_other"] == pytest.approx(100 * 30 / 500)
    assert got["idle_share.no_dispatch"] == pytest.approx(100 * 128 / 500)
    assert sum(got[n] for n in NEW[4:]) == pytest.approx(
        READERS["idle_share.serve"](r))


def test_new_readers_read_nothing_from_a_recorder_without_the_rows():
    """A program whose shards lack the stage and step columns (as before
    they existed) gives nothing to read, and raises nothing."""
    execs, shard = timeline(100.0)
    old = {k: v for k, v in shard.items() if not k.startswith("_stp_")}
    old.update(_req_t0=array("d", [100.1]), _req_t1=array("d", [100.3]))
    r = traced(execs, old, 0.5, 100.005)
    assert {n: READERS[n](r) for n in NEW} == dict.fromkeys(NEW)
