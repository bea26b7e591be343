"""Device: share of the traced window in which no operation ran while
the host was inside a decode step's row (launching ``decode_all``,
waking from its sync, argmax, ``np.asarray``, per-slot bookkeeping).
The device's idle intervals are placed on the recorder's clock by the
fitted offset (``_clock.fit``); mean over the cell's chips. With
``idle_share.dispatch_other`` and ``idle_share.no_dispatch`` it sums to
``idle_share.serve``."""

from chipbench.metrics import _clock

NAME = "idle_share.step_host"


def read(r):
    split = _clock.idle_split(r)
    return None if split is None else split["step_host"]
