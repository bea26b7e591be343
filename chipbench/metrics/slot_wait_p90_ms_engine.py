"""Engine: time from the start of the dispatch that carries a request to
the engine's slot for it, where prefill may start (recorder's request
stages ``fleet.dispatch`` -> ``engine.slot``), p90 over the requests
received in the window."""

from chipbench import traffic
from chipbench.metrics import _clock

NAME = "slot_wait_p90_ms.engine"


def read(r):
    waits = _clock.stage_spans(r, "_stg_dispatch", "_stg_slot")
    return 1e3 * traffic.percentile(waits, 90) if waits else None
