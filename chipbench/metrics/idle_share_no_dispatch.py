"""Device: share of the traced window in which no operation ran while
no fleet dispatch was running: nothing had arrived yet, the pump's poll
interval, the fleet lock's handoff. The device's idle intervals are
placed on the recorder's clock by the fitted offset (``_clock.fit``);
mean over the cell's chips. With ``idle_share.step_host`` and
``idle_share.dispatch_other`` it sums to ``idle_share.serve``."""

from chipbench.metrics import _clock

NAME = "idle_share.no_dispatch"


def read(r):
    split = _clock.idle_split(r)
    return None if split is None else split["no_dispatch"]
