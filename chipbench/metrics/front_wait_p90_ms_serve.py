"""Front door and pump: time from the front door's receipt of a request
to its arrival at the fleet, the wait for the fleet lock (recorder's
request stages ``front.receive`` -> ``fleet.arrive``), p90 over the
requests received in the window."""

from chipbench import traffic
from chipbench.metrics import _clock

NAME = "front_wait_p90_ms.serve"


def read(r):
    waits = _clock.stage_spans(r, "_stg_receive", "_stg_arrive")
    return 1e3 * traffic.percentile(waits, 90) if waits else None
