"""The HTTP front door (repro.launch.serve): endpoints, replica fan-out,
admission surfacing, and the shutdown report contract — all in-process
on an ephemeral port with the jax-free fake engine."""

import json
import threading
import urllib.error
import urllib.request
from concurrent.futures import ThreadPoolExecutor

import pytest

from repro.api.spec import ServeSpec, SystemSpec
from repro.launch.serve import ADMIT_REASONS, FleetServer


def _serve_spec(report_path=None, **system_over):
    doc = {
        "mode": "live",
        "workload": {"mix": "sgemm", "tenants": 4, "events": 100,
                     "seed": 7, "rate_hz": 2000.0, "arch": "fake",
                     "max_new_tokens": 8},
        "fleet": {"replicas": 2},
        "router": {"policy": "least_cost"},
        "scheduler": {"admission_policy": "feasibility"},
    }
    doc.update(system_over)
    return ServeSpec(system=SystemSpec.from_dict(doc), port=0,
                     report_path=report_path, request_timeout_s=10.0,
                     poll_interval_s=0.01)


@pytest.fixture()
def server():
    srv = FleetServer(_serve_spec())
    srv.start()
    t = threading.Thread(target=srv.httpd.serve_forever, daemon=True)
    t.start()
    yield srv
    srv.httpd.shutdown()
    srv.shutdown()
    t.join(timeout=5)


def _get(srv, path):
    with urllib.request.urlopen(
            f"http://127.0.0.1:{srv.port}{path}", timeout=10) as r:
        return r.status, json.loads(r.read())


def _predict(srv, tenant_id, prompt):
    req = urllib.request.Request(
        f"http://127.0.0.1:{srv.port}/v1/predict",
        data=json.dumps({"tenant_id": tenant_id, "prompt": prompt}).encode())
    with urllib.request.urlopen(req, timeout=10) as r:
        return r.status, json.loads(r.read())


class TestEndpoints:
    def test_healthz(self, server):
        status, doc = _get(server, "/healthz")
        assert status == 200
        assert doc["status"] == "ok" and doc["replicas"] == 2
        assert doc["engine"] == "fake" and doc["router"] == "least_cost"

    def test_predict_returns_tokens(self, server):
        status, doc = _predict(server, 1, [5, 6, 7])
        assert status == 200
        assert len(doc["tokens"]) == 8
        assert doc["replica"] in (0, 1)
        assert doc["latency_s"] > 0

    def test_predict_deterministic_per_tenant_prompt(self, server):
        _, a = _predict(server, 2, [1, 2])
        _, b = _predict(server, 2, [1, 2])
        assert a["tokens"] == b["tokens"]
        _, c = _predict(server, 3, [1, 2])
        assert c["tokens"] != a["tokens"]

    def test_concurrent_predicts_fan_out(self, server):
        def hit(i):
            return _predict(server, i % 4, [1, i])[1]

        with ThreadPoolExecutor(16) as ex:
            outs = list(ex.map(hit, range(48)))
        assert all(len(o["tokens"]) == 8 for o in outs)
        # backlog pressure must spread cohorts over both replicas
        assert len({o["replica"] for o in outs}) == 2

    def test_report_endpoint(self, server):
        for i in range(4):
            _predict(server, i, [i])
        status, doc = _get(server, "/v1/report")
        assert status == 200
        assert doc["executor"] == "serve" and doc["mode"] == "live"
        assert doc["metrics"]["http"]["requests"] >= 4
        assert sum(doc["metrics"]["routed_counts"]) >= 4
        assert "scheduler" in doc["metrics"]

    def test_unknown_route_404(self, server):
        with pytest.raises(urllib.error.HTTPError) as e:
            _get(server, "/nope")
        assert e.value.code == 404

    def test_bad_request_400(self, server):
        req = urllib.request.Request(
            f"http://127.0.0.1:{server.port}/v1/predict",
            data=json.dumps({"prompt": "not-a-list"}).encode())
        with pytest.raises(urllib.error.HTTPError) as e:
            urllib.request.urlopen(req, timeout=10)
        assert e.value.code == 400


class TestAdmission:
    def test_infeasible_rejection_surfaces_as_429(self):
        # an SLO no dispatch can meet makes feasibility admission reject
        # every request with reason code 3 (infeasible deadline)
        srv = FleetServer(_serve_spec(
            workload={"mix": "single", "tenants": 2, "events": 10,
                      "seed": 0, "rate_hz": 100.0, "arch": "fake",
                      "slo_s": 1e-12}))
        srv.start()
        threading.Thread(target=srv.httpd.serve_forever, daemon=True).start()
        try:
            with pytest.raises(urllib.error.HTTPError) as e:
                _predict(srv, 0, [1])
            assert e.value.code == 429
            doc = json.loads(e.value.read())
            assert doc["reason"] == ADMIT_REASONS[3] == "infeasible"
        finally:
            srv.httpd.shutdown()
            srv.shutdown()


class TestShutdown:
    def test_report_written_on_shutdown(self, tmp_path):
        path = str(tmp_path / "report.json")
        srv = FleetServer(_serve_spec(report_path=path))
        srv.start()
        threading.Thread(target=srv.httpd.serve_forever, daemon=True).start()
        _predict(srv, 0, [9])
        srv.httpd.shutdown()
        srv.shutdown()
        doc = json.loads(open(path).read())
        assert doc["executor"] == "serve"
        assert doc["metrics"]["http"]["requests"] == 1
        assert doc["spec"]["mode"] == "live"

    def test_shutdown_idempotent(self):
        srv = FleetServer(_serve_spec())
        srv.start()
        srv.shutdown()
        srv.shutdown()


class TestPumpFailure:
    """A fleet failure (here an injected engine fault) is kept: waiting
    predicts fail at once, /healthz turns 503, and serve exits 1."""

    @pytest.fixture()
    def failing_engine(self, monkeypatch):
        from repro.serving.fleet import FakeEngine

        def execute(self, batch):
            raise RuntimeError("injected engine fault")

        monkeypatch.setattr(FakeEngine, "execute", execute)

    def test_predict_fails_fast_and_healthz_503(self, failing_engine):
        import time

        srv = FleetServer(_serve_spec())
        srv.start()
        threading.Thread(target=srv.httpd.serve_forever, daemon=True).start()
        try:
            t0 = time.perf_counter()
            with pytest.raises(urllib.error.HTTPError) as e:
                _predict(srv, 0, [1, 2])
            assert e.value.code == 500
            assert "injected engine fault" in json.loads(e.value.read())["error"]
            assert time.perf_counter() - t0 < 5.0  # not the 10 s timeout
            with pytest.raises(urllib.error.HTTPError) as e:
                _get(srv, "/healthz")
            assert e.value.code == 503
            assert "injected" in json.loads(e.value.read())["error"]
        finally:
            srv.httpd.shutdown()
            srv.shutdown()

    def test_serve_main_exits_nonzero(self, failing_engine, tmp_path):
        import socket
        import types

        import jax

        from repro.launch.serve import main

        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            srv = types.SimpleNamespace(port=s.getsockname()[1])
        path = tmp_path / "serve.json"
        path.write_text(_serve_spec().to_json())
        rc = []
        cache_dir = jax.config.jax_compilation_cache_dir
        t = threading.Thread(
            target=lambda: rc.append(main(["--spec", str(path),
                                           "--port", str(srv.port)])),
            daemon=True)
        t.start()
        try:
            for _ in range(200):  # wait for the listener
                try:
                    _get(srv, "/healthz")
                    break
                except (urllib.error.URLError, ConnectionError):
                    t.join(0.05)
            with pytest.raises(urllib.error.HTTPError) as e:
                _predict(srv, 0, [1])
            assert e.value.code == 500
            t.join(timeout=10)
            assert rc == [1]
        finally:
            jax.config.update("jax_compilation_cache_dir", cache_dir)


class TestServeSpec:
    def test_round_trip(self):
        spec = _serve_spec()
        again = ServeSpec.from_json(spec.to_json())
        assert again.to_json() == spec.to_json()

    def test_rejects_sim_system(self):
        with pytest.raises(ValueError, match="live"):
            ServeSpec(system=SystemSpec(mode="sim"))

    def test_rejects_bad_port(self):
        with pytest.raises(ValueError, match="port"):
            ServeSpec(system=SystemSpec(mode="live"), port=70000)
