"""Kernels: ``decode_attention`` (the Pallas kernel inside
``decode_all``) in the Mamba2 + attention hybrid, GQA 4:1: least time of
the window's decode attention (each token's cache up to its length, in
the attention layers only) over the kernel's device time."""

from chipbench.counts import transformer as tc
from chipbench.metrics import _hybrid, _serve

NAME = "hybrid.decode_attention_roofline"


def read(r):
    if r.trace is None:
        return None
    _, seconds = r.trace.kernel("jit_decode_all")
    z = _hybrid.dims(r)
    least = 0.0
    for ctx in tc.decode_contexts(_serve.requests(r)):
        least += z.n_attn * _serve.least_s(*tc.decode_attention_token(z, ctx),
                                           r.peaks)
    return _serve.share(least, seconds)
