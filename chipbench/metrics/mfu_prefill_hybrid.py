"""Model step (Mamba2 + attention hybrid): model operations of the
prompts prefilled in the window, the recurrence and attention included,
over (device time of ``prefill`` x bf16 peak)."""

from chipbench.counts import hybrid as hc
from chipbench.metrics import _hybrid, _serve

NAME = "mfu.prefill.hybrid"


def read(r):
    if r.trace is None:
        return None
    _, seconds = r.trace.program("jit_prefill")
    z = _hybrid.dims(r)
    flops = sum(hc.prefill_flops(z, p) for p, _ in _serve.requests(r))
    return _serve.share(flops, seconds * r.peaks["bf16_flops_per_s"])
