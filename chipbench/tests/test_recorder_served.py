"""The served path at tiny size on the CPU, with the flight recorder on
and off: what the front door, the fleet and the engine stamp.

The served cell runs ``stablelm-1.6b-smoke`` (2 layers, width 256).
"""

import numpy as np
import pytest

from chipbench.drivers import served
from chipbench.tests.test_drivers import CHAT, SEED, small_cell


@pytest.mark.parametrize("observe", [True, False])
def test_served_path_records_stages_and_steps_only_when_on(observe,
                                                          monkeypatch):
    """Recorder on: one stage row per answered request, unique ids, the
    stages in their order, one step row per engine step. Off: the front
    door, fleet and engine hold no recorder and stamp nothing."""
    from chipbench import traffic as gen
    from repro.obs.recorder import STAGE_COLUMNS, STEP_COLUMNS
    from repro.serving import engine as engine_mod
    from repro.serving import fleet as fleet_mod

    dispatched, phases = [], []
    orig_execute = fleet_mod.EngineReplica.execute

    def execute(self, batch):
        dispatched.extend(batch)
        return orig_execute(self, batch)

    class Phases(engine_mod._StepPhases):
        def __init__(self):
            super().__init__()
            phases.append(self)

    monkeypatch.setattr(fleet_mod.EngineReplica, "execute", execute)
    monkeypatch.setattr(engine_mod, "_StepPhases", Phases)
    cell = small_cell(CHAT)
    s = served.Served(served.serve_spec(cell.config, cell.traffic, SEED,
                                        observe))
    try:
        served.warm_up(s, cell.config, cell.traffic, 1024, SEED)
        reqs = gen.open_loop(cell.traffic, 1.0, 2, 1024, SEED)
        recs, _, _ = served.open_loop(s, reqs, 1.0)
        spans = s.spans()
        engines = [e.engine for e in s.server.fleet.engines]
    finally:
        s.close()
    answered = [r for r in recs if r.tokens is not None]
    assert answered
    live = [w for w in dispatched if hasattr(w, "stamps")]
    if not observe:
        assert spans == {} and s.server.recorder is None
        assert all(e.recorder is None for e in engines)
        assert phases == []
        assert live and all(w.request_id is None and w.stamps is None
                            for w in live)
        assert all(r.slot_time is None for e in engines for r in e.finished)
        return
    (shard,) = spans.values()
    ids = list(shard["_stg_id"])
    # the warm-up's one predict and every request of the window
    assert len(ids) == len(answered) + 1 == len(live)
    assert len(set(ids)) == len(ids)
    assert sorted(ids) == sorted(w.request_id for w in live)
    rows = np.array([list(shard[c]) for c in STAGE_COLUMNS]).T
    assert np.isfinite(rows).all()
    assert (np.diff(rows, axis=1) >= 0).all()
    steps = np.array([list(shard[c]) for c in STEP_COLUMNS]).T
    assert len(steps) == sum(e.steps for e in engines) == len(phases)
    assert (np.diff(steps, axis=1) >= 0).all()
    assert sum(shard["_stp_tokens"]) == sum(e.decode_tokens for e in engines)
