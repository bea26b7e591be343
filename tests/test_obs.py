"""The flight recorder (repro.obs): zero overhead when off, byte-exact
determinism when on, and the three read paths (Chrome trace export,
windowed telemetry, CLI).

The load-bearing contracts:

  * recorder OFF: metrics JSON is byte-identical to the committed
    pre-recorder fixtures (tests/data/pre_obs_metrics_*.json) — the
    recorder hooks and the incremental straggler-median rewrite are
    behavior-neutral;
  * recorder ON: metrics are unchanged, and same-seed runs export
    byte-identical traces — including ``workers=K`` sharded fleets,
    whose shards are shipped back from forked workers and merged.
"""

import json
import statistics

import pytest

from repro.api import ObservabilitySpec, SystemSpec
from repro.api.cli import main as cli_main
from repro.core.slo import LatencyMonitor
from repro.obs import (
    FlightRecorder,
    export_chrome_trace,
    windowed_series,
)

SOLO = {"workload.events": 3000, "workload.seed": 7,
        "cost_model.compile_us": 50.0}
FLEET = {"workload.events": 3000, "workload.seed": 11,
         "workload.mix": "fleet", "workload.tenants": 12,
         "fleet.replicas": 3, "fleet.specs": ["v5e", "v5e_half"],
         "fleet.autoscale": {"max_replicas": 5, "interval_s": 0.05},
         "router.policy": "least_cost", "cost_model.compile_us": 200.0}
SHARDED = {"workload.events": 3000, "workload.seed": 13,
           "workload.mix": "fleet", "fleet.replicas": 4,
           "fleet.workers": 2, "router.policy": "round_robin",
           "cost_model.compile_us": 100.0}


def spec_for(overrides, recorder=False, **extra) -> SystemSpec:
    ov = dict(overrides)
    if recorder:
        ov["observability.enabled"] = True
    ov.update(extra)
    return SystemSpec().replace(**ov)


def run_recorded(overrides, **extra):
    ex = spec_for(overrides, recorder=True, **extra).build()
    m = ex.run_metrics()
    return m, ex.last_recorder


# ------------------------------------------------------------- off by default
class TestRecorderOff:
    @pytest.mark.parametrize("name,overrides", [
        ("solo", SOLO), ("fleet", FLEET), ("sharded", SHARDED)])
    def test_metrics_bytes_match_pre_recorder_fixtures(self, name, overrides):
        got = spec_for(overrides).build().run_metrics().to_json() + "\n"
        with open(f"tests/data/pre_obs_metrics_{name}.json") as fh:
            assert got == fh.read()

    def test_no_recorder_attached(self):
        ex = spec_for(SOLO).build()
        ex.run_metrics()
        assert ex.last_recorder is None


# ---------------------------------------------------------------- determinism
class TestDeterminism:
    def test_solo_trace_byte_identical_across_runs(self):
        _, rec1 = run_recorded(SOLO)
        _, rec2 = run_recorded(SOLO)
        assert export_chrome_trace(rec1) == export_chrome_trace(rec2)

    def test_recorder_does_not_change_metrics(self):
        base = spec_for(FLEET).build().run_metrics().to_json()
        recorded, _ = run_recorded(FLEET)
        assert recorded.to_json() == base

    def test_sharded_matches_single_process(self):
        solo_ov = dict(SHARDED, **{"fleet.workers": 1})
        _, rec1 = run_recorded(solo_ov)
        _, reck = run_recorded(SHARDED)
        assert export_chrome_trace(rec1) == export_chrome_trace(reck)
        w = 0.001
        assert (json.dumps(windowed_series(rec1, w), sort_keys=True)
                == json.dumps(windowed_series(reck, w), sort_keys=True))


# ------------------------------------------------------------------- contents
class TestRecordingContents:
    def test_solo_counts_match_metrics(self):
        m, rec = run_recorded(SOLO)
        shard = rec.shards[0]
        assert shard.n_arrivals == SOLO["workload.events"]
        assert shard.n_requests == m.summary()["completed"]
        assert shard.n_dispatches == m.summary()["dispatches"]
        assert shard.strategy == "space_time"

    def test_cold_dispatches_recorded(self):
        _, rec = run_recorded(SOLO)
        cold = sum(rec.shards[0]._dsp_cold)
        # compile_us > 0 with a fresh compile cache: the first dispatch
        # of each distinct bucket is cold
        assert cold > 0

    def test_fleet_routes_and_prices(self):
        m, rec = run_recorded(FLEET)
        assert rec.n_routes == FLEET["workload.events"]
        assert rec.router_name == "least_cost"
        # least_cost records one price per replica active at route time
        assert rec._rt_n[0] == FLEET["fleet.replicas"]
        assert len(rec._rt_price) == sum(rec._rt_n)
        assert len(rec._rt_price_rid) == sum(rec._rt_n)

    def test_round_robin_routes_have_no_prices(self):
        solo_ov = dict(SHARDED, **{"fleet.workers": 1})
        _, rec = run_recorded(solo_ov)
        assert rec.n_routes == SHARDED["workload.events"]
        assert sum(rec._rt_n) == 0

    def test_scale_events_match_metrics(self):
        # the fixture interval (0.05 s) never fires inside the ~5 ms
        # horizon; tick every 0.5 ms so the autoscaler actually acts
        m, rec = run_recorded(
            FLEET, **{"fleet.autoscale": {"max_replicas": 5,
                                          "interval_s": 0.0005}})
        assert rec.scale_events == m.scale_events
        assert len(rec.scale_events) > 0

    def test_rejections_recorded(self):
        m, rec = run_recorded(
            SOLO, **{"scheduler.max_pending_per_tenant": 2})
        shard = rec.shards[0]
        rejected = shard.n_arrivals - sum(shard._arr_admitted)
        assert rejected == m.summary()["rejected"]
        assert rejected > 0


# ------------------------------------------------------------- chrome export
class TestChromeExport:
    def test_schema(self):
        _, rec = run_recorded(
            FLEET, **{"fleet.autoscale": {"max_replicas": 5,
                                          "interval_s": 0.0005}})
        doc = json.loads(export_chrome_trace(rec))
        assert doc["displayTimeUnit"] == "ms"
        events = doc["traceEvents"]
        assert events, "empty trace"
        phs = set()
        for ev in events:
            assert {"ph", "pid", "tid", "name"} <= set(ev)
            phs.add(ev["ph"])
            if ev["ph"] in ("X", "i"):
                assert ev["ts"] >= 0.0
            if ev["ph"] == "X":
                assert ev["dur"] >= 0.0
        assert {"M", "X", "i"} <= phs
        cats = {ev.get("cat") for ev in events}
        assert {"dispatch", "request", "router", "autoscale"} <= cats

    def test_event_counts(self):
        m, rec = run_recorded(SOLO)
        doc = json.loads(export_chrome_trace(rec))
        by_cat = {}
        for ev in doc["traceEvents"]:
            by_cat[ev.get("cat")] = by_cat.get(ev.get("cat"), 0) + 1
        assert by_cat["request"] == m.summary()["completed"]
        assert by_cat["dispatch"] == m.summary()["dispatches"]

    def test_rejected_instants(self):
        _, rec = run_recorded(
            SOLO, **{"scheduler.max_pending_per_tenant": 2})
        doc = json.loads(export_chrome_trace(rec))
        rejected = [ev for ev in doc["traceEvents"]
                    if ev.get("cat") == "admission"]
        assert rejected and all(ev["ph"] == "i" for ev in rejected)


# ------------------------------------------------------------ live recording
class TestLiveRecording:
    def test_stage_and_step_rows_export_as_phase_spans(self):
        from repro.obs.recorder import STAGES, STEP_STAMPS, ReplicaShard

        rec = FlightRecorder()
        s = rec.shard(1)
        nan = float("nan")
        s.record_stages(7, 3, [1.0, 1.1, 1.2, 1.25, 1.3, 1.8, 1.9, 2.0])
        # an engine that stamps nothing leaves its stages NaN
        s.record_stages(8, 3, [1.0, 1.2, nan, nan, nan, nan, 1.9, 2.0])
        s.record_step([1.3, 1.31, 1.35, 1.36, 1.38], 2, 1)
        assert (s.n_stages, s.n_steps, rec.total_events()) == (2, 1, 3)
        back = ReplicaShard.from_payload(s.payload())
        assert list(back._stg_id) == [7, 8]
        assert list(back._stp_end) == [1.38]
        events = json.loads(export_chrome_trace(rec))["traceEvents"]
        stage = [e for e in events if e.get("cat") == "stage"]
        assert [e["name"] for e in stage] == list(STAGES[1:]) + [
            "fleet.arrive", "fleet.complete", "front.respond"]
        assert {(e["pid"], e["tid"]) for e in stage} == {(2, 3)}
        assert sum(e["dur"] for e in stage[:7]) == pytest.approx(1e6)
        assert [e["args"]["request"] for e in stage] == [7] * 7 + [8] * 3
        step = [e for e in events if e.get("cat") == "step"]
        assert [e["name"] for e in step] == list(STEP_STAMPS[1:])
        assert {(e["pid"], e["tid"]) for e in step} == {(1, 1)}
        assert step[0]["args"] == {"step": 0, "tokens": 2, "tenants": 1}
        assert {"ph": "M", "pid": 2, "tid": 3, "name": "thread_name",
                "args": {"name": "tenant 3"}} in events

    def test_front_door_writes_one_stage_row_per_answer(self, tmp_path):
        """Concurrent predicts, with the interpreter switching threads
        often: every answer leaves exactly one whole row (a lost or torn
        append would leave the columns unequal or the ids short)."""
        import sys
        from concurrent.futures import ThreadPoolExecutor

        from repro.api.spec import ServeSpec
        from repro.launch.serve import FleetServer
        from repro.obs.recorder import STAGE_COLUMNS

        trace = tmp_path / "trace.json"
        system = SystemSpec.from_dict({
            "mode": "live",
            "workload": {"mix": "sgemm", "tenants": 2, "events": 10,
                         "seed": 7, "rate_hz": 1000.0, "arch": "fake",
                         "max_new_tokens": 4},
            "fleet": {"replicas": 2},
            "router": {"policy": "round_robin"},
            "observability": {"enabled": True, "trace_path": str(trace)}})
        srv = FleetServer(ServeSpec(system=system, port=0,
                                    request_timeout_s=10.0,
                                    poll_interval_s=0.01))
        n = 64
        interval = sys.getswitchinterval()
        srv.start()
        try:
            sys.setswitchinterval(1e-5)
            with ThreadPoolExecutor(32) as pool:
                outs = list(pool.map(lambda i: srv.predict(i % 2, [1, i]),
                                     range(n)))
            srv.report()
        finally:
            sys.setswitchinterval(interval)
            srv.shutdown()
        assert all(o["status"] == 200 for o in outs)
        shards = srv.recorder.shards.values()
        assert sorted(i for s in shards for i in s._stg_id) == list(range(n))
        for s in shards:
            cols = ("_stg_id", "_stg_tenant") + STAGE_COLUMNS
            assert {len(getattr(s, c)) for c in cols} == {s.n_stages}
            for row in zip(s._stg_receive, s._stg_arrive, s._stg_complete,
                           s._stg_respond):
                assert list(row) == sorted(row)
            assert all(t != t for t in s._stg_slot)  # the fake engine's
        names = [e["name"] for e in json.loads(trace.read_text())
                 ["traceEvents"] if e.get("cat") == "stage"]
        assert names.count("fleet.arrive") == names.count(
            "front.respond") == n


# ------------------------------------------------------------------ telemetry
class TestTelemetry:
    def test_series_sums_match_totals(self):
        m, rec = run_recorded(FLEET)
        t = windowed_series(rec, 0.001)
        s = m.summary()
        assert sum(t["completed"]) == s["completed"]
        assert sum(t["arrivals"]) == FLEET["workload.events"]
        assert sum(t["rejected"]) == s["rejected"]
        assert t["windows"] == len(t["p95_ms"]) == len(t["backlog"])
        assert all(0.0 <= a <= 1.0 for a in t["slo_attainment"])
        assert all(b >= 0 for b in t["backlog"])
        assert len(t["per_replica"]) == len(rec.shards)
        for series in t["per_tenant"].values():
            assert len(series["completed"]) == t["windows"]

    def test_busy_seconds_conserved(self):
        _, rec = run_recorded(SOLO)
        t = windowed_series(rec, 0.0005)
        total_busy = sum(rec.shards[0]._dsp_dur)
        assert sum(t["busy_s"]) == pytest.approx(total_busy)

    def test_rides_in_run_report(self):
        report = spec_for(FLEET, recorder=True).build().run()
        t = report.metrics["telemetry"]
        assert t["schema"] == "telemetry/v1"
        assert t["windows"] > 0
        sched = report.metrics["scheduler"]
        assert "ripe_nudges" in sched
        assert "per_replica_ripe_nudges" in sched
        assert len(sched["per_replica_ripe_nudges"]) >= 1

    def test_bad_window_rejected(self):
        with pytest.raises(ValueError, match="window_s"):
            windowed_series(FlightRecorder(), 0.0)


# ----------------------------------------------------------------------- spec
class TestObservabilitySpec:
    def test_round_trip(self):
        spec = spec_for(SOLO, recorder=True,
                        **{"observability.window_s": 0.25,
                           "observability.per_request": False})
        again = SystemSpec.from_dict(json.loads(json.dumps(spec.to_dict())))
        assert again == spec
        assert again.observability.enabled
        assert again.observability.window_s == 0.25

    def test_off_by_default_and_absent_key_tolerated(self):
        assert not SystemSpec().observability.enabled
        doc = SystemSpec().to_dict()
        del doc["observability"]
        assert SystemSpec.from_dict(doc).observability == ObservabilitySpec()

    def test_bad_window_rejected(self):
        with pytest.raises(ValueError, match="window_s"):
            ObservabilitySpec(window_s=0.0)

    def test_trace_path_written_by_run(self, tmp_path):
        path = tmp_path / "t.json"
        spec = spec_for(SOLO, recorder=True,
                        **{"observability.trace_path": str(path)})
        spec.build().run()
        doc = json.loads(path.read_text())
        assert doc["traceEvents"]


# ------------------------------------------------- incremental median rewrite
class TestIncrementalMedian:
    def test_matches_statistics_median_and_brute_stragglers(self):
        import random

        rng = random.Random(42)
        mon = LatencyMonitor(ewma_alpha=0.3, eviction_ratio=1.5)

        class Item:
            def __init__(self, tid, arr, slo):
                self.tenant_id, self.arrival_time, self.slo_s = tid, arr, slo
                self.kind = "default"

        for step in range(400):
            if step % 3 == 0:
                mon.record(rng.randrange(12), rng.uniform(0.001, 0.05),
                           0.02)
            else:
                batch = [Item(rng.randrange(12), 0.0,
                              rng.uniform(0.005, 0.03))
                         for _ in range(rng.randrange(1, 6))]
                mon.record_batch(batch, rng.uniform(0.001, 0.05))
            ewmas = sorted(t.ewma_s for t in mon.tenants.values()
                           if t.ewma_s is not None)
            assert mon._ewma_sorted == pytest.approx(ewmas)
            assert mon.cohort_median_ewma() == pytest.approx(
                statistics.median(ewmas))
            cut = mon.eviction_ratio * statistics.median(ewmas)
            brute = [tid for tid, t in mon.tenants.items()
                     if t.ewma_s is not None and t.ewma_s > cut]
            assert sorted(mon.stragglers()) == sorted(brute)

    def test_empty_monitor(self):
        mon = LatencyMonitor()
        assert mon.cohort_median_ewma() is None
        assert mon.stragglers() == []


# ------------------------------------------------------------------------ cli
class TestCli:
    def test_trace_check_and_telemetry(self, tmp_path, capsys):
        out = tmp_path / "trace.json"
        tel = tmp_path / "telemetry.json"
        rc = cli_main([
            "trace", "--events", "1200", "--seed", "5",
            "--set", "cost_model.compile_us=50",
            "--out", str(out), "--telemetry", str(tel), "--check"])
        assert rc == 0
        assert json.loads(out.read_text())["traceEvents"]
        series = json.loads(tel.read_text())
        assert series["schema"] == "telemetry/v1"
        assert "byte-identical: True" in capsys.readouterr().out

    def test_trace_rejects_live_mode(self):
        with pytest.raises(SystemExit):
            cli_main(["trace", "--set", "mode=live"])

    def test_report_timeline(self, tmp_path, capsys):
        rep = tmp_path / "report.json"
        rc = cli_main([
            "simulate", "--events", "1200", "--seed", "5",
            "--set", "observability.enabled=true", "--out", str(rep)])
        assert rc == 0
        capsys.readouterr()
        rc = cli_main(["report", str(rep), "--timeline"])
        assert rc == 0
        out = capsys.readouterr().out
        assert "scheduler counters:" in out
        assert "timeline:" in out

    def test_report_timeline_without_telemetry_actionable(
            self, tmp_path, capsys):
        rep = tmp_path / "plain.json"
        assert cli_main(["simulate", "--events", "1200",
                         "--out", str(rep)]) == 0
        with pytest.raises(SystemExit, match="observability.enabled"):
            cli_main(["report", str(rep), "--timeline"])
