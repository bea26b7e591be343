"""Engine: host time of a decode step outside its device wait, mean over
the steps begun in the window: launching ``decode_all``
(``step.begin`` -> ``step.enqueue``) plus argmax, ``np.asarray`` and the
per-slot bookkeeping (``step.ready`` -> ``step.end``), from the
recorder's step rows."""

from chipbench.metrics import _clock

NAME = "step_host_ms.engine"


def read(r):
    total, n = 0.0, 0
    for shard in r.spans.values():
        rows = _clock.steps(r, shard)
        if rows is None:
            continue
        begin, enqueue, ready, _, end = rows
        total += float(((enqueue - begin) + (end - ready)).sum())
        n += len(begin)
    return 1e3 * total / n if n else None
