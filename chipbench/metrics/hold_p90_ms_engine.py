"""Engine: time from a request's last token on the host to the end of
its dispatch, held for its cohort-mates (recorder's request stages
``engine.last_token`` -> ``fleet.complete``), p90 over the requests
received in the window."""

from chipbench import traffic
from chipbench.metrics import _clock

NAME = "hold_p90_ms.engine"


def read(r):
    holds = _clock.stage_spans(r, "_stg_last", "_stg_complete")
    return 1e3 * traffic.percentile(holds, 90) if holds else None
