"""The live fleet (repro.serving.fleet) and its sim↔live parity contract.

One pump/router core serves both executors, so a ``LiveFleet`` on a
virtual clock with the no-op ``NullEngine`` must be a bit-exact twin of
``FleetSimulator``: same routing decision sequence, same admission
reason codes, same frozen metrics bytes. Everything here is jax-free
(NullEngine / FakeEngine); the real-engine smoke is opt-in via
REPRO_LIVE_JAX=1.
"""

import json
import os
import time

import pytest

from repro.config import ScheduleConfig
from repro.core.clock import VirtualClock
from repro.obs.recorder import FlightRecorder
from repro.serving.fleet import FakeEngine, LiveFleet, NullEngine
from repro.sim import (
    FleetSimulator,
    RooflineCostModel,
    estimate_capacity_hz,
    fleet_sgemm_mix,
    make_trace,
)

SCHED = ScheduleConfig(batching_window_s=0.0005, max_superkernel_size=32)
MIX = fleet_sgemm_mix(12)
BASE = RooflineCostModel(strategy="space_time")
OFFERED_HZ = 0.85 * 3 * estimate_capacity_hz(MIX, BASE)


def _trace(events=2000, seed=0, process="mmpp"):
    return make_trace(process, MIX, OFFERED_HZ, events, seed=seed)


def _sim(router="least_cost", recorder=None, schedule=SCHED, **kw):
    return FleetSimulator(replicas=3, router=router, schedule=schedule,
                          cost_model=BASE, compile_s=1e-3,
                          recorder=recorder, **kw)


def _live(router="least_cost", recorder=None, schedule=SCHED, **kw):
    # virtual clocks + the no-result engine = the simulator's exact twin
    return LiveFleet(replicas=3, engine_factory=NullEngine, router=router,
                     schedule=schedule, cost_model=BASE, compile_s=1e-3,
                     recorder=recorder, clock_factory=VirtualClock, **kw)


# -------------------------------------------------------------- sim ↔ live
class TestParity:
    @pytest.mark.parametrize("router", ["round_robin", "jsq", "least_cost",
                                        "affinity"])
    def test_metrics_bytes_match_fleet_simulator(self, router):
        m_sim = _sim(router=router).run(_trace())
        m_live = _live(router=router).run(_trace())
        assert m_live.to_json() == m_sim.to_json()

    def test_router_decision_sequence_matches(self):
        rec_sim, rec_live = FlightRecorder(), FlightRecorder()
        _sim(recorder=rec_sim).run(_trace())
        _live(recorder=rec_live).run(_trace())
        assert rec_sim.n_routes == rec_live.n_routes == 2000
        assert list(rec_live._rt_chosen) == list(rec_sim._rt_chosen)
        assert list(rec_live._rt_price) == list(rec_sim._rt_price)

    def test_admission_reason_codes_match(self):
        # feasibility admission under heavy pressure produces a mix of
        # admit / oversubscribed / infeasible codes; the live fleet must
        # reproduce the simulator's sequence exactly, per replica
        sched = ScheduleConfig(batching_window_s=0.0005,
                               max_superkernel_size=32,
                               admission_policy="feasibility",
                               oversubscription=1.25)
        rec_sim, rec_live = FlightRecorder(), FlightRecorder()
        trace = make_trace("mmpp", MIX, 3 * OFFERED_HZ, 2000, seed=1)
        _sim(recorder=rec_sim, schedule=sched).run(trace)
        _live(recorder=rec_live, schedule=sched).run(trace)
        for rid in range(3):
            s, l = rec_sim.shards[rid], rec_live.shards[rid]
            assert list(l._arr_reason) == list(s._arr_reason)
            assert list(l._arr_admitted) == list(s._arr_admitted)
        reasons = {r for rid in range(3)
                   for r in rec_sim.shards[rid]._arr_reason}
        assert len(reasons) > 1  # the sequence actually exercised codes

    def test_routed_counts_match(self):
        sim, live = _sim(), _live()
        sim.run(_trace(seed=3))
        live.run(_trace(seed=3))
        assert live.routed_counts == sim.routed_counts


# ------------------------------------------------------------- live engines
class TestFakeEngine:
    def test_tokens_deterministic_and_replica_independent(self):
        eng0, eng1 = FakeEngine(0), FakeEngine(1)

        class W:
            tenant_id, payload = 5, [7, 8, 9]

        a, b = eng0.execute([W]), eng1.execute([W])
        assert a == b  # output is a function of (tenant, payload) only
        assert len(a[0]) == 8 and all(0 <= t < 32000 for t in a[0])

    def test_results_land_on_workloads(self):
        fleet = LiveFleet(replicas=2, engine_factory=FakeEngine,
                          schedule=SCHED, cost_model=BASE,
                          clock_factory=VirtualClock)
        done = []
        spec = MIX[0]
        w, rid, admitted, reason = fleet.submit_one(spec, spec.cost,
                                                    payload=[1, 2], t_s=0.0)
        assert admitted and reason == 0
        fleet._drain_until(1.0)
        assert w.result is not None and len(w.result) == 8
        assert w.completion_time is not None

    def test_wall_clock_run_completes(self):
        # the real serving configuration: wall clock, full-speed replay
        fleet = LiveFleet(replicas=2, engine_factory=FakeEngine,
                          schedule=SCHED, cost_model=BASE)
        m = fleet.run(_trace(events=300, seed=2),
                      payload_fn=lambda s: [s.tenant_id])
        assert m.merged.completed == 300
        assert sum(fleet.routed_counts) == 300
        assert m.router == "least_cost"

    @pytest.mark.parametrize("wall", [True, False])
    def test_idle_replica_backlog_is_only_its_clock_lead(self, wall):
        # on the shared wall clock the time since an arrival was stamped
        # is no backlog, or the replica priced last always looks busier;
        # a virtual replica's clock can run ahead, and that lead counts
        fleet = LiveFleet(replicas=2, engine_factory=FakeEngine,
                          schedule=SCHED, cost_model=BASE,
                          clock_factory=None if wall else VirtualClock)
        now = fleet.now()
        if wall:
            time.sleep(0.002)
        else:
            fleet.pumps[1].clock.advance(0.25)
        assert [p.backlog_s(now) for p in fleet.pumps] == (
            [0.0, 0.0] if wall else [0.0, 0.25])


# ---------------------------------------------------------------- end to end
class TestLiveSpec:
    def _spec(self, **over):
        from repro.api.spec import SystemSpec

        doc = {
            "mode": "live",
            "workload": {"mix": "sgemm", "tenants": 4, "events": 300,
                         "seed": 3, "rate_hz": 2000.0, "arch": "fake"},
            "fleet": {"replicas": 2},
            "router": {"policy": "least_cost"},
            "scheduler": {"admission_policy": "feasibility"},
        }
        doc.update(over)
        return SystemSpec.from_dict(doc)

    def test_live_fleet_spec_builds_and_runs(self):
        # the ISSUE acceptance spec: live + fleet + least_cost + feasibility
        from repro.api.build import LiveRun

        run = self._spec().build()
        assert isinstance(run, LiveRun)
        rep = run.run()
        assert rep.executor == "live" and rep.mode == "live"
        sched = rep.metrics["scheduler"]
        assert sched["completed"] + sched["rejected"] == 300
        assert sum(rep.metrics["routed_counts"]) + sched["rejected"] == 300
        assert rep.metrics["engine"] == "fake"
        assert "p95_s" in rep.metrics["summary"]
        assert rep.metrics["schema_version"] == rep.schema_version

    def test_live_check_invariants_pass(self, tmp_path):
        from repro.api.cli import main

        path = tmp_path / "live.json"
        path.write_text(self._spec().to_json())
        assert main(["simulate", "--spec", str(path), "--check"]) == 0

    def test_calibration_saved_and_reloaded(self, tmp_path):
        calib = str(tmp_path / "fleet_calib.json")
        spec = self._spec(cost_model={"fleet_calibration_path": calib})
        spec.build().run()
        doc = json.loads(open(calib).read())
        assert sorted(doc["replicas"]) == ["0", "1"]
        # second run loads the saved tables and still completes
        rep = spec.build().run()
        assert rep.metrics["scheduler"]["completed"] > 0

    def test_sim_fleet_reads_but_never_writes_tables(self, tmp_path):
        calib = str(tmp_path / "fleet_calib.json")
        live = self._spec(cost_model={"fleet_calibration_path": calib})
        live.build().run()
        stamp = os.path.getmtime(calib)
        sim = self._spec(mode="sim",
                         cost_model={"fleet_calibration_path": calib})
        rep = sim.build().run()
        assert rep.executor == "fleet"
        assert os.path.getmtime(calib) == stamp

    @pytest.mark.skipif(not os.environ.get("REPRO_LIVE_JAX"),
                        reason="set REPRO_LIVE_JAX=1 for the jax CPU smoke")
    def test_real_engine_smoke(self):
        spec = self._spec(workload={
            "mix": "sgemm", "tenants": 2, "events": 4, "seed": 0,
            "rate_hz": 50.0, "arch": "stablelm-1.6b-smoke", "prompt_tokens": 4,
            "max_new_tokens": 4})
        rep = spec.build().run()
        assert rep.metrics["engine"] == "jax"
        assert rep.metrics["scheduler"]["completed"] == 4

    def test_replicas_on_one_device_share_stacked_weights(self):
        import jax

        spec = self._spec(workload={
            "mix": "sgemm", "tenants": 2, "events": 4, "seed": 0,
            "rate_hz": 50.0, "arch": "stablelm-1.6b-smoke"})
        factory, name, vocab = spec.build().build_engine_factory()
        assert (name, vocab) == ("jax", 1024)
        a, b = factory(0).engine, factory(1).engine
        assert len(jax.devices()) == 1
        # one stacked tree for both replicas, weights and caches on the
        # device the replica was placed on
        assert a.stacked_params is b.stacked_params
        from repro.core.tenancy import tenant_counts

        device = jax.devices()[0]
        for x in jax.tree.leaves((a.stacked_params, a.caches)):
            assert x.devices() == {device}
        for tree in (a.stacked_params, a.caches):
            assert tenant_counts(tree) == {2}
