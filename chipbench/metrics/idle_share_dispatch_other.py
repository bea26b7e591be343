"""Device: share of the traced window in which no operation ran while
a fleet dispatch was running but none of its decode steps: admission,
prefill's host work, the cache scatter. The device's idle intervals are
placed on the recorder's clock by the fitted offset (``_clock.fit``);
mean over the cell's chips. With ``idle_share.step_host`` and
``idle_share.no_dispatch`` it sums to ``idle_share.serve``."""

from chipbench.metrics import _clock

NAME = "idle_share.dispatch_other"


def read(r):
    split = _clock.idle_split(r)
    return None if split is None else split["dispatch_other"]
