"""HTTP front door over the live fleet (``python -m repro serve``).

A thin stdlib serving loop — ``ThreadingHTTPServer``, no framework —
fanning requests out over the same ``LiveFleet`` a ``simulate`` run of
the spec would build: real ``DynamicSpaceTimeScheduler`` replicas behind
the sim routers, so capacity planning done in sim transfers to the
deployed shape unchanged.

Endpoints:

    GET  /healthz     liveness + fleet shape (replicas, engine, router);
                      503 once the fleet has failed
    POST /v1/predict  {"tenant_id": 0, "prompt": [1,2,3]} — routed,
                      admission-controlled, blocks until the cohort the
                      request merged into completes; 429 with the
                      scheduler's reason code when admission rejects
    GET  /v1/report   the schema-versioned RunReport for traffic so far

Concurrency model: handler threads submit under one fleet lock; a single
pump thread wakes at ``min(next ripeness instant, poll_interval_s)`` and
drives dispatch. Completion is signalled per-request through the pump's
``on_complete`` hook (a ``threading.Event`` on each workload), so a
blocked handler costs one waiting thread, never a spin.

A failure while executing fleet work (an engine raising, a device out of
memory), in the pump thread or in a handler's submit, is kept, not
swallowed: waiting predicts fail at once with 500,
``/healthz`` answers 503 with the error, the server stops, and ``serve``
exits non-zero.

On SIGTERM/SIGINT (or server shutdown) the fleet drains and, when
``report_path`` is set, the final ``RunReport`` JSON lands there — the
serve-smoke CI contract.

    PYTHONPATH=src python -m repro serve --spec examples/specs/serve_smoke.json
"""

from __future__ import annotations

import argparse
import json
import signal
import sys
import threading
import time
import traceback
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Optional

from repro.api.build import LiveRun, _augment_metrics, build_mix, build_recorder
from repro.api.report import RunReport
from repro.api.spec import ServeSpec
from repro.launch.compile_cache import enable_compile_cache

#: scheduler admission codes -> wire names (core.scheduler.admit_reason)
ADMIT_REASONS = {0: "admitted", 1: "oversubscribed", 2: "cap",
                 3: "infeasible"}


class _HttpServer(ThreadingHTTPServer):
    # socketserver's default listen backlog (5) resets connections under
    # concurrent load; predict calls block for a whole cohort, so bursts
    # of pending connects are the normal case here
    request_queue_size = 128
    daemon_threads = True


class FleetServer:
    """One live fleet + pump thread + HTTP server, owned together."""

    def __init__(self, spec: ServeSpec):
        self.spec = spec
        self.run = LiveRun(spec.system)
        self.recorder = build_recorder(spec.system)
        self.fleet, self.vocab = self.run.build_fleet(recorder=self.recorder)
        # a request's class is its tenant's first entry in the mix (the
        # serving mix lists a prefill and a decode entry per tenant; a
        # request starts with its prefill)
        self.mix = []
        for entry in build_mix(spec.system.workload):
            if entry.tenant_id == len(self.mix):
                self.mix.append(entry)
        self.lock = threading.Lock()
        self.started_s = time.perf_counter()
        self.requests = 0
        self.rejected = 0
        # the pump thread's exception, formatted, once it has died
        self.failure: Optional[str] = None
        self._waiting = set()  # done events of predicts in flight
        self._serving = False
        self._stop = threading.Event()
        self._pump_thread = threading.Thread(
            target=self._pump_loop, name="fleet-pump", daemon=True)
        self.httpd = _HttpServer(
            (spec.host, spec.port), _make_handler(self))
        self.port = self.httpd.server_address[1]

    # ------------------------------------------------------------ serving
    def predict(self, tenant_id: int, prompt, max_new_tokens=None) -> dict:
        """Route one request through the fleet and wait for its cohort."""
        spec = self.mix[tenant_id % len(self.mix)]
        done = threading.Event()
        t0 = time.perf_counter()
        try:
            with self.lock:
                if self.failure is not None:
                    return self._failed()
                self.requests += 1
                # submit_one may run ripe work in this thread
                w, replica_id, admitted, reason = self.fleet.submit_one(
                    spec, cost=spec.cost, payload=list(prompt or ()),
                    done=done)
                if admitted:
                    self._waiting.add(done)
                else:
                    self.rejected += 1
        except Exception:
            self._fail(traceback.format_exc())
            return self._failed()
        if not admitted:
            return {"status": 429,
                    "error": f"admission rejected: "
                             f"{ADMIT_REASONS.get(reason, reason)}",
                    "reason": ADMIT_REASONS.get(reason, str(reason)),
                    "replica": replica_id}
        finished = done.wait(self.spec.request_timeout_s)
        with self.lock:
            self._waiting.discard(done)
        if w.result is None and self.failure is not None:
            return self._failed()
        if not finished:
            return {"status": 504,
                    "error": f"request did not complete within "
                             f"{self.spec.request_timeout_s:g}s",
                    "replica": replica_id}
        return {"status": 200,
                "tenant_id": spec.tenant_id,
                "tokens": w.result,
                "replica": replica_id,
                "latency_s": time.perf_counter() - t0}

    def _failed(self) -> dict:
        return {"status": 500, "error": f"fleet failed: {self.failure}"}

    def report(self) -> RunReport:
        """Freeze the traffic served so far into a RunReport."""
        with self.lock:
            horizon = self.fleet.now() - self.fleet.start_s
            m = self.fleet.freeze(horizon)
        doc = _augment_metrics(self.spec.system, m.to_dict(), m,
                               self.recorder)
        doc["arch"] = self.spec.system.workload.arch
        doc["engine"] = self.run.engine_name
        doc["wall_s"] = time.perf_counter() - self.started_s
        doc["http"] = {"requests": self.requests, "rejected": self.rejected}
        return RunReport(executor="serve", mode="live",
                         spec=self.spec.system.to_dict(), metrics=doc)

    # ---------------------------------------------------------- lifecycle
    def _pump_loop(self) -> None:
        try:
            self._pump()
        except Exception:
            self._fail(traceback.format_exc())

    def _pump(self) -> None:
        interval = self.spec.poll_interval_s
        while not self._stop.is_set():
            with self.lock:
                self.fleet.poll()
                t_next = self.fleet.next_ripe_time()
            now = self.fleet.now()
            delay = interval if t_next is None else max(0.0, t_next - now)
            self._stop.wait(min(delay, interval))

    def _fail(self, formatted: str) -> None:
        """Keep a fleet exception (pump thread or a handler's submit),
        fail every waiting predict, and stop a running server so
        ``serve`` exits non-zero."""
        print(f"fleet failed:\n{formatted}", file=sys.stderr, flush=True)
        with self.lock:
            self.failure = formatted.strip().splitlines()[-1]
            waiting = list(self._waiting)
        for done in waiting:
            done.set()
        if self._serving:
            threading.Thread(target=self.httpd.shutdown, daemon=True).start()

    def start(self) -> None:
        self._pump_thread.start()

    def serve_forever(self) -> None:
        self._serving = True
        self.start()
        try:
            self.httpd.serve_forever(poll_interval=0.2)
        finally:
            self.shutdown()

    def shutdown(self) -> None:
        """Stop pumping, drain the fleet, persist the final report."""
        if self._stop.is_set():
            return
        self._stop.set()
        if self._pump_thread.is_alive():
            self._pump_thread.join(timeout=5.0)
        with self.lock:
            if self.failure is None:
                # a failed pump's queues hold work its engine cannot run
                self.fleet._drain_wall_tail(
                    timeout_s=self.spec.request_timeout_s)
            self.run.save_calibration(self.fleet)
        if self.spec.report_path:
            self.report().save(self.spec.report_path)
        self.httpd.server_close()


def _make_handler(server: FleetServer):
    class Handler(BaseHTTPRequestHandler):
        protocol_version = "HTTP/1.1"

        def log_message(self, fmt, *args):  # stay quiet; CI parses stdout
            pass

        def _send(self, code: int, doc: dict) -> None:
            body = (json.dumps(doc, sort_keys=True) + "\n").encode()
            self.send_response(code)
            self.send_header("Content-Type", "application/json")
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)

        def do_GET(self) -> None:
            if self.path == "/healthz":
                if server.failure is not None:
                    self._send(503, {"status": "failed",
                                     "error": server.failure})
                    return
                self._send(200, {
                    "status": "ok",
                    "replicas": len(server.fleet.active),
                    "engine": server.run.engine_name,
                    "router": server.fleet.router.name,
                    "requests": server.requests,
                })
                return
            if self.path == "/v1/report":
                self._send(200, server.report().to_dict())
                return
            self._send(404, {"error": f"no route {self.path!r} (have "
                                      "/healthz, /v1/predict, /v1/report)"})

        def do_POST(self) -> None:
            if self.path != "/v1/predict":
                self._send(404, {"error": f"no route {self.path!r}"})
                return
            try:
                n = int(self.headers.get("Content-Length", 0))
                doc = json.loads(self.rfile.read(n) or b"{}")
                tenant_id = int(doc.get("tenant_id", 0))
                prompt = doc.get("prompt", [])
                if not isinstance(prompt, list):
                    raise ValueError("prompt must be a list of token ids")
            except (ValueError, json.JSONDecodeError) as e:
                self._send(400, {"error": f"bad request: {e}"})
                return
            out = server.predict(tenant_id, prompt)
            self._send(out.pop("status"), out)

    return Handler


def run_server(spec: ServeSpec, ready: Optional[threading.Event] = None,
               ) -> FleetServer:
    """Build the fleet, install signal handlers, serve until stopped."""
    server = FleetServer(spec)
    if threading.current_thread() is threading.main_thread():
        # httpd.shutdown() blocks until serve_forever exits, and the
        # handler runs ON the serve_forever thread — hand it off or the
        # process deadlocks on its own signal
        def stop(signum, frame):
            threading.Thread(target=server.httpd.shutdown,
                             daemon=True).start()

        signal.signal(signal.SIGTERM, stop)
        signal.signal(signal.SIGINT, stop)
    w = spec.system.workload
    print(f"serving {spec.system.fleet.replicas} replica(s) of "
          f"arch={w.arch} behind router={spec.system.router.policy} "
          f"on http://{spec.host}:{server.port}", flush=True)
    if ready is not None:
        ready.set()
    server.serve_forever()
    if spec.report_path:
        print(f"wrote {spec.report_path}", flush=True)
    return server


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="HTTP serving loop over a live fleet (ServeSpec JSON)")
    ap.add_argument("--spec", required=True, help="ServeSpec JSON file")
    ap.add_argument("--port", type=int, default=None,
                    help="override serve.port")
    args = ap.parse_args(argv)
    enable_compile_cache()
    spec = ServeSpec.load(args.spec)
    if args.port is not None:
        spec = ServeSpec.from_dict({**spec.to_dict(), "port": args.port})
    server = run_server(spec)
    return 0 if server.failure is None else 1


if __name__ == "__main__":
    raise SystemExit(main())
