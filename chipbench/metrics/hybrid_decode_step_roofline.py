"""Model step (Mamba2 + attention hybrid): the least time the window's
decode steps need (every tenant's weights read once a step, each token's
Mamba2 state read and written and its attention cache read with one
position written, against the chip's peaks) over the device time of
``decode_all``."""

from chipbench.metrics import _hybrid, _serve

NAME = "hybrid.decode_step_roofline"


def read(r):
    if r.trace is None:
        return None
    _, seconds = r.trace.program("jit_decode_all")
    return _serve.share(_serve.least_s(*_hybrid.decode_work(r), r.peaks),
                        seconds)
