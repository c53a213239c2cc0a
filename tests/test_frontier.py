"""Front-tier router chaos suite (tier-1, `-m frontier`, PR 17).

Two layers, cheap first:

**Fake-backend units** — `_FakeBackend` is a minimal stdlib HTTP stand-in
for a StereoService host (predict/healthz wire format, optional single-
worker timing model) so the routing mechanics are provable in
milliseconds, deterministically, with zero compiles: retry lands on a
*different* backend with exactly-once accounting, deterministic 4xx never
retries, the retry budget caps amplification, hedging fires after the
configured delay and the duplicate's answer wins, the breaker walks a
dead backend failed → (restart) → probation → healthy on probe + real
traffic, and brownout engages above the queue-wait threshold, tightens
forwarded deadlines/iters, keeps shed-vs-brownout counters distinct, and
disengages with hysteresis. The brownout A/B drives an arrival rate that
sheds >10% against the bare backend and shows the browned-out frontier
serving >=99% of the same load with reduced iters recorded per response.

**Real-fleet chaos** — a module-scoped two-backend fleet of real
`StereoService` processes-worth (shared AOT cache populated by a warmer
boot, so backends B and C boot with ZERO compiles — the process-wide
RecompileMonitor means multi-service suites only stay clean through the
cache), mixed plain+stream traffic through the real frontier HTTP server:
killing the stream-pinned backend loses zero plain requests (all answered
via retry, bit-identical to the healthy-path baseline), migrates the
pinned stream with a recorded cold restart (`migrated=True`,
`warm_started=False`), walks the dead backend failed → probation →
healthy after a same-port restart from cache, preserves the
record-before-raise reject ordering through the frontier path, and keeps
`compiles_post_grace == 0` on every backend. Slowloris hardening
(connect-and-stall, stalled-body 408) and drain-then-close run here too;
the module is ORDER-DEPENDENT by design and collection-ordered after
`faults_fleet` (conftest), gated in ci_checks.sh (exit 18).
"""

import json
import os
import socket
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

import numpy as np
import pytest

from fault_injection import http_response_fault

from report_checks import validate_frontier

pytestmark = pytest.mark.frontier

BUCKET = (64, 96)
CHUNK_ITERS = 2
MAX_ITERS = 4

_rng = np.random.default_rng(20260807)
PAIR = (
    _rng.uniform(0, 255, (BUCKET[0], BUCKET[1], 3)).astype(np.float32),
    _rng.uniform(0, 255, (BUCKET[0], BUCKET[1], 3)).astype(np.float32),
)


# -- fake backends: the wire format without the model ------------------------


class _FakeBackend:
    """Stdlib stand-in for one StereoService host: POST /v1/predict and
    GET /healthz in the real wire format, per-stream frame counters (so
    warm_started/stream_frame behave), a settable healthz queue-wait p95
    (the brownout signal), and an optional single-worker timing model
    (`ms_per_iter` > 0): requests serialize through one work lock and a
    request sheds 503 when the queued estimate already blows its
    deadline — the backend-side admission control the brownout A/B needs."""

    def __init__(self, default_iters: int = MAX_ITERS, ms_per_iter: float = 0.0):
        self.default_iters = default_iters
        self.ms_per_iter = ms_per_iter
        self.queue_p95_ms = 0.0
        self.predict_calls = 0
        self.shed_calls = 0
        self.streams = {}
        self._lock = threading.Lock()
        self._work_lock = threading.Lock()
        self._waiting = 0
        self.server = self._make_server(0)
        self.port = self.server.server_address[1]
        self.addr = f"127.0.0.1:{self.port}"
        self._serve()

    def _make_server(self, port: int) -> ThreadingHTTPServer:
        outer = self

        class Handler(BaseHTTPRequestHandler):
            timeout = 10.0

            def log_message(self, fmt, *args):
                pass

            def do_GET(self):
                if self.path.startswith("/healthz"):
                    body = json.dumps(outer.healthz()).encode()
                    self.send_response(200)
                    self.send_header("Content-Type", "application/json")
                    self.send_header("Content-Length", str(len(body)))
                    self.end_headers()
                    self.wfile.write(body)
                else:
                    self.send_error(404)

            def do_POST(self):
                length = int(self.headers.get("Content-Length", "0"))
                payload = json.loads(self.rfile.read(length))
                status, out = outer.predict(payload)
                body = json.dumps(out).encode()
                self.send_response(status)
                self.send_header("Content-Type", "application/json")
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

        return ThreadingHTTPServer(("127.0.0.1", port), Handler)

    def _serve(self):
        self.thread = threading.Thread(
            target=self.server.serve_forever, daemon=True
        )
        self.thread.start()

    def close(self):
        self.server.shutdown()
        self.server.server_close()

    def restart(self):
        """Same-port reboot (HTTPServer sets allow_reuse_address): the
        'operator restarted the host' leg of the breaker walk."""
        self.server = self._make_server(self.port)
        self._serve()

    def healthz(self):
        return {
            "serving": {
                "state": "healthy",
                "attribution": {
                    "queue_wait_ms": {
                        "count": 8,
                        "mean": self.queue_p95_ms,
                        "p50": self.queue_p95_ms,
                        "p95": self.queue_p95_ms,
                    }
                },
                "boot": {"warmup_seconds": 0.01, "cache_enabled": False},
            }
        }

    def predict(self, body):
        with self._lock:
            self.predict_calls += 1
        if body.get("oversize"):
            # Deterministic 4xx: the request, not the host, is at fault.
            return 413, {"error": "input exceeds every bucket"}
        iters = int(body.get("max_iters") or self.default_iters)
        deadline_ms = body.get("deadline_ms")
        if self.ms_per_iter > 0:
            est_ms = self.default_iters * self.ms_per_iter
            with self._lock:
                if (
                    deadline_ms is not None
                    and self._waiting * est_ms > float(deadline_ms)
                ):
                    self.shed_calls += 1
                    return 503, {
                        "error": "deadline infeasible",
                        "state": "healthy",
                    }
                self._waiting += 1
            try:
                with self._work_lock:
                    time.sleep(iters * self.ms_per_iter / 1e3)
            finally:
                with self._lock:
                    self._waiting -= 1
        out = {
            "disparity": [[1.0, 2.0]],
            "iters_completed": iters,
            "early_exit": iters < self.default_iters,
            "latency_ms": 1.0,
            "bucket": list(BUCKET),
            # What the frontier actually forwarded — the brownout
            # tightening proof reads these.
            "echo_max_iters": body.get("max_iters"),
            "echo_deadline_ms": deadline_ms,
        }
        sid = body.get("stream_id")
        if sid is not None:
            with self._lock:
                frames = self.streams.get(sid, 0)
                self.streams[sid] = frames + 1
            out.update(
                stream_id=sid,
                stream_frame=frames,
                warm_started=frames > 0,
                reset=False,
            )
        return 200, out


def _frontier_config(addrs, **kw):
    from raft_stereo_tpu.config import FrontierConfig

    kw.setdefault("backends", tuple(addrs))
    kw.setdefault("health_interval_s", 0.05)
    kw.setdefault("health_timeout_s", 2.0)
    kw.setdefault("request_timeout_s", 60.0)
    kw.setdefault("retry_attempts", 3)
    kw.setdefault("retry_base_delay_s", 0.001)
    kw.setdefault("retry_max_delay_s", 0.002)
    kw.setdefault("breaker_degrade_after", 1)
    kw.setdefault("breaker_fail_after", 2)
    kw.setdefault("breaker_probation", 2)
    kw.setdefault("drain_timeout_s", 30.0)
    return FrontierConfig(**kw)


def _make_frontier(addrs, **kw):
    from raft_stereo_tpu.serving.frontier import Frontier

    return Frontier(_frontier_config(addrs, **kw), sleep=lambda s: None)


def _poll(predicate, timeout_s=10.0, what="condition"):
    deadline = time.monotonic() + timeout_s
    while not predicate():
        assert time.monotonic() < deadline, f"timed out waiting for {what}"
        time.sleep(0.01)


# -- fake-backend units ------------------------------------------------------


def test_retry_lands_on_a_different_backend_exactly_once():
    """A 5xx from the first-routed backend retries on the OTHER backend
    and the client sees exactly one (successful) answer: the exactly-once
    ledger (requests == responses), one counted retry, a breaker debit on
    the faulty host only."""
    b0, b1 = _FakeBackend(), _FakeBackend()
    frontier = _make_frontier([b0.addr, b1.addr])
    try:
        with http_response_fault(b0.server, "5xx", failures=1) as calls:
            status, payload = frontier.handle_predict({"image1": [], "image2": []})
        assert status == 200
        assert calls["calls"] == 1
        assert payload["backend"] == b1.addr  # retried AWAY from the failer
        snap = frontier.metrics()
        assert snap["requests_total"] == snap["responses_total"] == 1
        assert snap["retries_total"] == 1
        assert snap["errors_total"] == 0
        assert snap["per_backend"][b0.addr]["failures_total"] == 1
        assert snap["per_backend"][b1.addr]["failures_total"] == 0
        # degrade_after=1: one failure marks it degraded, not failed.
        assert snap["per_backend"][b0.addr]["state"] == "degraded"
    finally:
        frontier.close()
        b0.close()
        b1.close()


def test_dropped_connection_retries_like_a_dead_host():
    """mode='drop' answers with a bare connection reset — the wire
    signature of a host dying mid-request — and the frontier still
    answers via the surviving backend."""
    b0, b1 = _FakeBackend(), _FakeBackend()
    frontier = _make_frontier([b0.addr, b1.addr])
    try:
        with http_response_fault(b0.server, "drop", failures=1):
            status, payload = frontier.handle_predict({"image1": [], "image2": []})
        assert status == 200
        assert payload["backend"] == b1.addr
        assert frontier.metrics()["retries_total"] == 1
    finally:
        frontier.close()
        b0.close()
        b1.close()


def test_deterministic_4xx_forwards_verbatim_and_never_retries():
    """A 413 is the request's fault: forwarded unchanged, zero retries,
    zero breaker debit — retrying it on another backend could only burn
    capacity to fail again."""
    b0, b1 = _FakeBackend(), _FakeBackend()
    frontier = _make_frontier([b0.addr, b1.addr])
    try:
        status, payload = frontier.handle_predict(
            {"image1": [], "image2": [], "oversize": True}
        )
        assert status == 413
        assert "error" in payload
        snap = frontier.metrics()
        assert snap["retries_total"] == 0
        assert b0.predict_calls + b1.predict_calls == 1
        assert set(snap["backend_states"]) == {"healthy"}
        # Answered by a live backend -> part of the answered ledger.
        assert snap["responses_total"] == 1
    finally:
        frontier.close()
        b0.close()
        b1.close()


def test_retry_budget_caps_amplification():
    """With the budget at its floor (min=1, percent=0), a persistently
    failing fleet gets exactly one retry ever — then requests fail fast
    with 502 instead of melting the backends with retry storms."""
    b0, b1 = _FakeBackend(), _FakeBackend()
    frontier = _make_frontier(
        [b0.addr, b1.addr],
        retry_budget_min=1,
        retry_budget_percent=0.0,
        breaker_fail_after=50,  # keep both admissible: isolate the budget
    )
    try:
        with http_response_fault(b0.server, "5xx"), http_response_fault(
            b1.server, "5xx"
        ):
            s1, _ = frontier.handle_predict({"image1": [], "image2": []})
            s2, _ = frontier.handle_predict({"image1": [], "image2": []})
        assert s1 == 502 and s2 == 502
        snap = frontier.metrics()
        assert snap["retries_total"] == 1  # budget floor, not attempts*2
        assert snap["errors_total"] == 2
    finally:
        frontier.close()
        b0.close()
        b1.close()


def test_hedge_fires_after_floor_delay_and_the_duplicate_wins():
    """Opt-in hedging: the first pick stalls (injected delay), the hedge
    dispatches to the other backend after hedge_floor_ms and its answer
    is returned first — tail cut, exactly one client answer, hedges and
    hedge wins counted (and NOT counted as retries)."""
    b0, b1 = _FakeBackend(), _FakeBackend()
    frontier = _make_frontier(
        [b0.addr, b1.addr], hedge=True, hedge_floor_ms=40.0
    )
    try:
        with http_response_fault(b0.server, "delay", delay_s=1.0, failures=1):
            t0 = time.monotonic()
            status, payload = frontier.handle_predict({"image1": [], "image2": []})
            elapsed = time.monotonic() - t0
        assert status == 200
        assert payload["backend"] == b1.addr  # the hedge answered
        assert elapsed < 0.9  # did not wait out the stalled primary
        snap = frontier.metrics()
        assert snap["hedges_total"] == 1
        assert snap["hedge_wins_total"] == 1
        assert snap["retries_total"] == 0
    finally:
        frontier.close()
        b0.close()
        b1.close()


def test_breaker_walks_failed_probation_healthy_and_sheds_when_all_dead():
    """Kill a fake host: consecutive transport failures trip its breaker
    failed (routing stops considering it); kill BOTH and the frontier
    sheds 503 (distinct shed counter). Restart the host: the health probe
    re-admits it into probation and real forwarded traffic earns healthy
    — the same walk the real-fleet chaos test proves end-to-end."""
    b0, b1 = _FakeBackend(), _FakeBackend()
    frontier = _make_frontier([b0.addr, b1.addr]).start()
    try:
        b0.close()
        # Each request that routes to the dead b0 fails + retries to b1;
        # fail_after=2 transport failures (requests and/or probes) trip it.
        for _ in range(4):
            status, _ = frontier.handle_predict({"image1": [], "image2": []})
            assert status == 200  # zero lost requests while b0 dies
        _poll(
            lambda: frontier.metrics()["per_backend"][b0.addr]["state"]
            == "failed",
            what="b0 breaker to trip failed",
        )

        b1.close()
        _poll(
            lambda: frontier.metrics()["per_backend"][b1.addr]["state"]
            == "failed",
            what="b1 breaker to trip failed",
        )
        status, payload = frontier.handle_predict({"image1": [], "image2": []})
        assert status == 503
        assert frontier.metrics()["shed_total"] >= 1

        b0.restart()
        # Probe success is the ONLY re-admission path, and it lands in
        # probation ('degraded'), never straight back to healthy.
        _poll(
            lambda: frontier.metrics()["per_backend"][b0.addr]["state"]
            == "degraded",
            what="probe to re-admit b0 into probation",
        )
        # Real traffic completes probation.
        for _ in range(3):
            status, payload = frontier.handle_predict({"image1": [], "image2": []})
            assert status == 200 and payload["backend"] == b0.addr
        assert frontier.metrics()["per_backend"][b0.addr]["state"] == "healthy"
    finally:
        frontier.close()
        b1.restart()  # so close() below has a socket to tear down
        b0.close()
        b1.close()


def test_stream_affinity_pins_and_migrates_with_cold_restart():
    """Stream frames pin to one backend (carry state is per-host). When
    that host dies, the session migrates: the forwarded stream id is
    generation-aliased so the new backend COLD-starts (warm_started
    False, frame 0), the response records migrated=True, and the
    migration is counted separately from retries."""
    b0, b1 = _FakeBackend(), _FakeBackend()
    frontier = _make_frontier([b0.addr, b1.addr]).start()
    try:
        frames = [
            frontier.handle_predict(
                {"image1": [], "image2": [], "stream_id": "cam0"}
            )
            for _ in range(3)
        ]
        assert all(s == 200 for s, _ in frames)
        pinned = frames[0][1]["backend"]
        assert [p["backend"] for _, p in frames] == [pinned] * 3
        assert [p["warm_started"] for _, p in frames] == [False, True, True]
        assert [p["stream_frame"] for _, p in frames] == [0, 1, 2]
        assert all(p["migrated"] is False for _, p in frames)

        victim, survivor = (
            (b0, b1) if pinned == b0.addr else (b1, b0)
        )
        victim.close()
        status, payload = frontier.handle_predict(
            {"image1": [], "image2": [], "stream_id": "cam0"}
        )
        assert status == 200
        assert payload["backend"] == survivor.addr
        assert payload["migrated"] is True
        assert payload["warm_started"] is False  # cold restart, recorded
        assert payload["stream_frame"] == 0
        assert payload["stream_id"] == "cam0"  # alias never leaks out
        # The carry is NOT pretended to survive: the survivor saw a brand
        # new (aliased) stream, not a continuation.
        assert "cam0" not in survivor.streams
        snap = frontier.metrics()
        assert snap["migrations_total"] == 1
        assert snap["sessions_active"] == 1

        # Next frame warm-starts on the new pin, no further migration.
        status, payload = frontier.handle_predict(
            {"image1": [], "image2": [], "stream_id": "cam0"}
        )
        assert status == 200
        assert payload["backend"] == survivor.addr
        assert payload["warm_started"] is True
        assert payload["migrated"] is False
        assert frontier.metrics()["migrations_total"] == 1
    finally:
        frontier.close()
        b0.close()
        b1.close()


def test_brownout_engages_tightens_and_recovers_with_hysteresis():
    """Above the queue-wait p95 threshold the frontier tightens forwarded
    deadlines AND iteration caps (the anytime engines early-exit:
    quality, not availability, degrades), annotates responses, counts
    engagements separately from sheds, and only disengages below
    threshold x recover_ratio."""
    b0 = _FakeBackend()
    frontier = _make_frontier(
        [b0.addr],
        brownout_queue_p95_ms=50.0,
        brownout_deadline_ms=25.0,
        brownout_max_iters=1,
        brownout_recover_ratio=0.5,
    ).start()
    try:
        status, payload = frontier.handle_predict({"image1": [], "image2": []})
        assert status == 200 and "brownout" not in payload
        assert payload["echo_max_iters"] is None  # untouched when calm

        b0.queue_p95_ms = 200.0
        _poll(
            lambda: frontier.metrics()["brownout_active"],
            what="brownout to engage",
        )
        status, payload = frontier.handle_predict({"image1": [], "image2": []})
        assert status == 200
        assert payload["brownout"] is True
        assert payload["echo_max_iters"] == 1  # iters capped
        assert payload["echo_deadline_ms"] == 25.0  # deadline tightened
        assert payload["iters_completed"] == 1  # reduced iters recorded
        # A client's own TIGHTER deadline is respected, never loosened.
        status, payload = frontier.handle_predict(
            {"image1": [], "image2": [], "deadline_ms": 10.0}
        )
        assert payload["echo_deadline_ms"] == 10.0

        snap = frontier.metrics()
        assert snap["brownout_engagements_total"] == 1
        assert snap["brownout_requests_total"] == 2
        assert snap["shed_total"] == 0  # brownout is NOT shedding

        # Hysteresis: dropping to just-below-threshold is NOT enough...
        b0.queue_p95_ms = 40.0
        time.sleep(0.2)
        assert frontier.metrics()["brownout_active"] is True
        # ...but falling under threshold x ratio (25) disengages.
        b0.queue_p95_ms = 10.0
        _poll(
            lambda: not frontier.metrics()["brownout_active"],
            what="brownout to disengage",
        )
        assert frontier.metrics()["brownout_engagements_total"] == 1
    finally:
        frontier.close()
        b0.close()


def test_brownout_ab_overload_served_instead_of_shed():
    """The acceptance A/B on the single-worker timing model: an arrival
    rate whose full-iteration service time sheds >10% against the bare
    backend is served >=99% through the browned-out frontier (iters
    capped -> service time shrinks under the arrival interval), with
    reduced iters recorded on every response and engagements vs sheds as
    distinct counters."""
    from raft_stereo_tpu.utils.http import request_json

    n, spacing_s, deadline_ms = 80, 0.004, 24.0

    def drive(send):
        """Fixed-rate open loop: one dispatch thread per request at a
        scheduled arrival time; returns the collected results."""
        results, threads = [], []
        lock = threading.Lock()

        def one():
            out = send()
            with lock:
                results.append(out)

        t0 = time.monotonic()
        for i in range(n):
            while time.monotonic() < t0 + i * spacing_s:
                time.sleep(0.0005)
            t = threading.Thread(target=one, daemon=True)
            t.start()
            threads.append(t)
        for t in threads:
            t.join(timeout=60)
        assert len(results) == n
        return results

    # A leg: bare backend, full iterations (4 x 2 ms service vs 4 ms
    # arrivals) -> the queue builds and deadline admission sheds hard.
    bare = _FakeBackend(ms_per_iter=2.0)
    try:
        statuses = drive(
            lambda: request_json(
                f"http://{bare.addr}/v1/predict",
                method="POST",
                payload={
                    "image1": [],
                    "image2": [],
                    "deadline_ms": deadline_ms,
                },
                timeout_s=30.0,
            ).status
        )
    finally:
        bare.close()
    shed_fraction = statuses.count(503) / n
    assert shed_fraction > 0.10, f"A leg only shed {shed_fraction:.0%}"

    # B leg: same arrival rate through a browned-out frontier — iters
    # capped to 1 (2 ms service < 4 ms arrivals), nothing sheds.
    b0 = _FakeBackend(ms_per_iter=2.0)
    frontier = _make_frontier(
        [b0.addr],
        brownout_queue_p95_ms=50.0,
        brownout_max_iters=1,
        breaker_fail_after=50,
        retry_attempts=2,
    ).start()
    try:
        b0.queue_p95_ms = 200.0  # the overload signal the prober reads
        _poll(
            lambda: frontier.metrics()["brownout_active"],
            what="brownout to engage",
        )
        results = drive(
            lambda: frontier.handle_predict(
                {"image1": [], "image2": [], "deadline_ms": deadline_ms}
            )
        )
        served = [(s, p) for s, p in results if s == 200]
        assert len(served) / n >= 0.99, f"B leg served {len(served)}/{n}"
        assert all(p["iters_completed"] == 1 for _, p in served)
        assert all(p["brownout"] is True for _, p in served)
        snap = frontier.metrics()
        assert snap["brownout_engagements_total"] == 1
        assert snap["brownout_requests_total"] >= n
        assert validate_frontier(snap) == []
    finally:
        frontier.close()
        b0.close()


# -- slowloris hardening (backend HTTP server satellite) ---------------------


def _stalled_recv(sock, timeout_s=5.0):
    sock.settimeout(timeout_s)
    try:
        return sock.recv(65536)
    except (TimeoutError, socket.timeout):
        pytest.fail("server never closed the stalled connection")


def test_backend_server_times_out_connect_and_stall_client():
    """Slowloris leg 1: a client that connects and never speaks is cut
    off by the per-connection socket timeout instead of wedging a handler
    thread forever. The handler never touches the service, so a bare
    object() stands in."""
    from raft_stereo_tpu.serving.service import make_http_server

    server = make_http_server(object(), port=0, handler_timeout_s=0.3)
    thread = threading.Thread(target=server.serve_forever, daemon=True)
    thread.start()
    try:
        t0 = time.monotonic()
        with socket.create_connection(server.server_address, timeout=5) as s:
            assert _stalled_recv(s) == b""  # closed, no bytes
        assert time.monotonic() - t0 < 3.0
    finally:
        server.shutdown()
        server.server_close()


def test_backend_server_answers_408_on_stalled_body():
    """Slowloris leg 2: a client that sends headers promising a body and
    then stalls mid-body gets a clean 408 and a close — it spoke enough
    protocol to deserve an answer, and the thread is freed either way."""
    from raft_stereo_tpu.serving.service import make_http_server

    server = make_http_server(object(), port=0, handler_timeout_s=0.3)
    thread = threading.Thread(target=server.serve_forever, daemon=True)
    thread.start()
    try:
        with socket.create_connection(server.server_address, timeout=5) as s:
            s.sendall(
                b"POST /reload HTTP/1.1\r\nHost: t\r\n"
                b"Content-Length: 64\r\nContent-Type: application/json\r\n"
                b"\r\n{\"partial"  # 9 bytes of a promised 64
            )
            data = _stalled_recv(s)
        assert b"408" in data.split(b"\r\n", 1)[0]
        assert b"timed out" in data
    finally:
        server.shutdown()
        server.server_close()


# -- real-fleet chaos --------------------------------------------------------


def _post_warmup_compiles(service) -> int:
    return service.engine.hygiene.monitor.stats()["compiles_post_grace"]


@pytest.fixture(scope="module")
def fleet(tmp_path_factory):
    """Two REAL backends + the real frontier HTTP server.

    A throwaway warmer boot populates the shared AOT cache first (its
    compiles are the sanctioned ones), then both backends boot
    sequentially from the cache with zero compile events — the
    RecompileMonitor's compile listener is process-wide, so this is the
    only way a multi-service suite keeps per-service compile accounting
    clean. Both serve the SAME variables tree: the cross-backend
    bit-identity the retry/migration proofs rely on."""
    from raft_stereo_tpu.config import ServeConfig, VideoConfig
    from raft_stereo_tpu.models import init_model_variables
    from raft_stereo_tpu.serving.frontier import (
        Frontier,
        make_frontier_http_server,
    )
    from raft_stereo_tpu.serving.service import StereoService, make_http_server

    tmp = tmp_path_factory.mktemp("frontier")
    cfg = ServeConfig(
        buckets=(BUCKET,),
        max_batch=1,
        chunk_iters=CHUNK_ITERS,
        max_iters=MAX_ITERS,
        batch_window_ms=2.0,
        video=VideoConfig(
            chunk_iters=CHUNK_ITERS,
            cold_iters=MAX_ITERS,
            warm_iters=CHUNK_ITERS,
            reset_error_floor=1e9,  # the gate never resets in this suite
        ),
        breaker_degrade_after=1,
        breaker_fail_after=3,
        drain_timeout_s=60.0,
        aot_cache_dir=str(tmp / "aot"),
        log_dir=str(tmp / "logs"),
    )
    variables = init_model_variables(cfg.model)
    warmer = StereoService(cfg, variables).start()
    warmer.close()

    state = {"cfg": cfg, "variables": variables, "backends": {}}

    def boot_backend(port=0):
        service = StereoService(cfg, variables).start()
        assert service.boot_block()["cache_misses"] == 0  # pure deserialize
        server = make_http_server(service, port=port, handler_timeout_s=30.0)
        threading.Thread(target=server.serve_forever, daemon=True).start()
        entry = {
            "service": service,
            "server": server,
            "port": server.server_address[1],
            "addr": f"127.0.0.1:{server.server_address[1]}",
        }
        state["backends"][entry["addr"]] = entry
        return entry

    state["boot_backend"] = boot_backend
    e1 = boot_backend()
    e2 = boot_backend()
    frontier = Frontier(
        _frontier_config(
            [e1["addr"], e2["addr"]],
            retry_base_delay_s=0.01,
            retry_max_delay_s=0.05,
            request_timeout_s=300.0,
            breaker_fail_after=2,
            log_dir=str(tmp / "logs"),
        )
    ).start()
    fserver = make_frontier_http_server(frontier, port=0, handler_timeout_s=30.0)
    threading.Thread(target=fserver.serve_forever, daemon=True).start()
    state["frontier"] = frontier
    state["fserver"] = fserver
    state["furl"] = "http://127.0.0.1:%d" % fserver.server_address[1]
    try:
        yield state
    finally:
        state["fserver"].shutdown()
        state["fserver"].server_close()
        state["frontier"].close()
        for entry in state["backends"].values():
            for closer in (
                lambda: entry["server"].shutdown(),
                lambda: entry["server"].server_close(),
                lambda: entry["service"].close(),
            ):
                try:
                    closer()
                except Exception:
                    pass  # chaos tests legitimately pre-kill backends


def _predict(state, **extra):
    """One request through the real frontier HTTP server, via the shared
    stdlib client (utils/http.py) — the same discipline bench uses."""
    from raft_stereo_tpu.utils.http import request_json

    payload = {
        "image1": PAIR[0].tolist(),
        "image2": PAIR[1].tolist(),
        "max_iters": MAX_ITERS,
        **extra,
    }
    return request_json(
        state["furl"] + "/predict", method="POST", payload=payload, timeout_s=300.0
    )


def test_fleet_serves_bit_identical_through_the_frontier(fleet):
    """Happy path: both cache-booted backends answer through the frontier
    and their disparities are bit-identical (same variables, same warmed
    executables) — the baseline every later chaos assertion compares to."""
    seen = {}
    for _ in range(4):
        resp = _predict(fleet)
        assert resp.status == 200, resp.body
        out = resp.json()
        seen.setdefault(out["backend"], out["disparity"])
    # JSON float round-trip is exact: list equality IS bit-identity.
    first = next(iter(seen.values()))
    for disparity in seen.values():
        assert disparity == first
    fleet["baseline"] = first
    snap = fleet["frontier"].metrics()
    assert snap["requests_total"] == snap["responses_total"] == 4
    assert snap["retries_total"] == 0
    assert validate_frontier(snap) == []


def test_reject_ordering_preserved_through_frontier_path(fleet):
    """The PR-11 pin, one tier up: an oversized input reaching a backend
    through the frontier records the reject BEFORE the 413 surfaces, the
    413 forwards verbatim, and the frontier never retries it (a retry
    would double-count the reject — the ordering pin would still hold
    per-backend, but exactly-once forwarding is part of the contract)."""
    big = np.zeros((BUCKET[0] + 32, BUCKET[1] + 32, 3), np.float32)
    before = {
        addr: e["service"].metrics()["rejected_total"]
        for addr, e in fleet["backends"].items()
    }
    retries_before = fleet["frontier"].metrics()["retries_total"]
    resp = _predict(
        fleet, **{"image1": big.tolist(), "image2": big.tolist()}
    )
    assert resp.status == 413
    assert "exceeds every bucket" in resp.json()["error"]
    after = {
        addr: e["service"].metrics()["rejected_total"]
        for addr, e in fleet["backends"].items()
    }
    assert sum(after.values()) - sum(before.values()) == 1  # recorded once
    assert fleet["frontier"].metrics()["retries_total"] == retries_before


def test_chaos_kill_pinned_backend_under_mixed_traffic(fleet):
    """The chaos acceptance: under mixed plain+stream traffic, killing
    the stream-pinned backend (server AND service — a dead host, not a
    sick one) loses ZERO plain requests — every one is answered via
    exactly-once retry, bit-identical to the healthy baseline — migrates
    the pinned stream with a recorded cold restart, walks the dead
    backend's breaker to sticky-failed, and after a same-port restart
    from the AOT cache walks it probation -> healthy on probe + real
    traffic, with compiles_post_grace == 0 on every backend throughout."""
    frontier = fleet["frontier"]
    baseline = fleet["baseline"]

    # Pin a stream and warm it (frame 0 cold, frame 1 warm).
    frames = [_predict(fleet, stream_id="cam0").json() for _ in range(2)]
    pinned = frames[0]["backend"]
    assert frames[1]["backend"] == pinned
    assert frames[1]["warm_started"] is True
    victim = fleet["backends"][pinned]
    survivor_addr = next(a for a in fleet["backends"] if a != pinned)

    # Freeze ACTIVE probing for the kill window: at the 50 ms probe
    # cadence the prober would trip the corpse's breaker before a single
    # request could route there, and this leg is the proof of the PASSIVE
    # path — request traffic discovering the death and retrying. The
    # probe is restored below for the re-admission leg (the only way back
    # from sticky-failed).
    real_probe = frontier._probe_one
    frontier._probe_one = lambda backend: None

    # Host death: HTTP front and service both go away.
    victim["server"].shutdown()
    victim["server"].server_close()
    victim["service"].close()

    # Plain traffic across the kill: zero lost, all bit-identical. The
    # first ones route to the corpse, fail transport, and retry onto the
    # survivor; once the breaker trips the corpse leaves rotation.
    retries_before = frontier.metrics()["retries_total"]
    for _ in range(6):
        resp = _predict(fleet)
        assert resp.status == 200, resp.body
        out = resp.json()
        assert out["backend"] == survivor_addr
        assert out["disparity"] == baseline  # bit-identical retried path
    assert frontier.metrics()["retries_total"] > retries_before
    # The passive accounting alone (failed forwards) walked the breaker
    # to sticky-failed — the prober is still frozen.
    assert frontier.metrics()["per_backend"][pinned]["state"] == "failed"

    # The pinned stream migrates with an explicit, recorded cold restart.
    out = _predict(fleet, stream_id="cam0").json()
    assert out["backend"] == survivor_addr
    assert out["migrated"] is True
    assert out["warm_started"] is False
    assert out["stream_frame"] == 0
    out = _predict(fleet, stream_id="cam0").json()
    assert out["warm_started"] is True  # re-warmed on the new pin
    assert out["migrated"] is False
    assert frontier.metrics()["migrations_total"] == 1

    # Exactly-once ledger: every client request got exactly one answer.
    snap = frontier.metrics()
    assert snap["responses_total"] == snap["requests_total"]
    assert snap["errors_total"] == 0 and snap["shed_total"] == 0

    # Same-port restart from the shared cache: zero compiles, and the
    # frontier re-admits it probe -> probation -> healthy via traffic.
    frontier._probe_one = real_probe
    del fleet["backends"][pinned]
    reborn = fleet["boot_backend"](port=victim["port"])
    assert reborn["addr"] == pinned
    _poll(
        lambda: frontier.metrics()["per_backend"][pinned]["state"]
        == "degraded",
        timeout_s=15.0,
        what="restarted backend to enter probation",
    )
    deadline = time.monotonic() + 30.0
    while frontier.metrics()["per_backend"][pinned]["state"] != "healthy":
        assert time.monotonic() < deadline, "probation never completed"
        resp = _predict(fleet)
        assert resp.status == 200
        assert resp.json()["disparity"] == baseline
    assert frontier.metrics()["backend_states"].count("healthy") == 2

    # Zero post-warmup compiles fleet-wide: survivor served the chaos,
    # the replacement booted by pure deserialization.
    for entry in fleet["backends"].values():
        assert _post_warmup_compiles(entry["service"]) == 0


def test_frontier_observability_surfaces(fleet):
    """Every counter the chaos produced is machine-visible: /metrics JSON
    passes the bench validator, the prom exposition carries the frontier
    counters + per-backend state codes, /healthz aggregates per-backend
    lifecycle AND boot blocks, and breaker moves landed in the flight
    recorder dumps."""
    from raft_stereo_tpu.obs.prom import PROM_CONTENT_TYPE
    from raft_stereo_tpu.utils.http import request

    resp = request(fleet["furl"] + "/metrics", timeout_s=10.0)
    assert resp.status == 200
    snap = resp.json()
    assert validate_frontier(snap) == []
    assert snap["retries_total"] >= 1
    assert snap["migrations_total"] >= 1

    resp = request(fleet["furl"] + "/metrics?format=prom", timeout_s=10.0)
    assert resp.status == 200
    assert resp.headers.get("Content-Type") == PROM_CONTENT_TYPE
    prom = resp.body.decode()
    assert "raft_frontier_requests_total" in prom
    assert "raft_frontier_retries_total" in prom
    assert "raft_frontier_migrations_total" in prom
    assert "raft_frontier_backend_state_code" in prom

    resp = request(fleet["furl"] + "/healthz", timeout_s=10.0)
    health = resp.json()
    assert health["frontier"]["state"] == "healthy"
    assert set(health["backends"]) == set(fleet["backends"])
    for info in health["backends"].values():
        assert info["state"] in ("healthy", "degraded", "failed", "draining")
        assert info["lifecycle"]["state"] == info["state"]
        # The aggregated boot blocks: both backends were probed healthy
        # at least once since their (re)boot.
        assert info["boot"] is not None
        assert info["boot"]["cache_enabled"] is True

    dump_dir = fleet["cfg"].log_dir
    dump = os.path.join(dump_dir, "frontier_flight_recorder.json")
    assert os.path.exists(dump)  # breaker moves dumped the recorder


def test_drain_then_close_is_graceful(fleet):
    """LAST on purpose: drain stops admission (503, counted as shed),
    waits out in-flight forwards, and reports a clean True — then the
    whole module's teardown closes the backends."""
    frontier = fleet["frontier"]
    assert frontier.drain(timeout_s=30.0) is True
    status, payload = frontier.handle_predict(
        {"image1": [], "image2": []}
    )
    assert status == 503
    assert payload["state"] == "draining"
    resp = _predict(fleet)
    assert resp.status == 503
