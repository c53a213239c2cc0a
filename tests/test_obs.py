"""Observability suite (tier-1, `-m obs`, PR 14).

The acceptance criteria, each machine-checked here:

- the prom text exposition (`obs/prom.py`) round-trips through a minimal
  0.0.4 parser: counters are monotone (set_total refuses regression),
  histogram buckets are cumulative and sum to `_count`, `/metrics?format=prom`
  carries the right Content-Type while the legacy JSON snapshot stays the
  default with a FROZEN key set;
- the flight recorder (`obs/trace.py`) is a bounded ring with honest
  lifetime counters, dumps atomically, and a served request's lifecycle
  (admission -> queue -> stage -> chunk -> finalize -> respond) is
  reconstructible from the ring by trace ID;
- latency percentiles use linear interpolation and return None below two
  samples (a percentile of nothing is not a number);
- device-memory telemetry degrades to a typed `available: false` block on
  CPU and never raises;
- ONE span entry point (`obs.span`): under a profiler session its spans are
  `rs/<name>` events of the host plane carrying their parent, the same spans
  land in the bounded process-wide log (plain scalars only), `Tracer.timed`
  goes through it, and `Evaluator.__call__` gains its three child spans
  without an extra executable, a sync or a change to the seconds it returns;
- THE strict-mode acceptance: a warmed serving run and a short training fit
  with every pillar on (tracing + prom + memory sampling) complete with
  compiles_post_grace == 0 and compile exactly the same executables as an
  obs-off twin — observability is free on the hot path.

The serving integration shares one pair of warmed twin services (smallest
useful config: one bucket, batch 1) and runs dead last in tier-1
(conftest collection order), re-run as the ci_checks exit-16 gate.
"""

import json
import math
import os
import socket
import threading
import urllib.error
import urllib.request

import numpy as np
import pytest

from raft_stereo_tpu.obs import (
    PROM_CONTENT_TYPE,
    FlightRecorder,
    Registry,
    Tracer,
    load_flight_recorder,
    memory_block,
    observability_block,
    process_spans,
    serve_registry,
    set_memory_gauges,
    span,
)
from raft_stereo_tpu.obs import trace as obs_trace
from raft_stereo_tpu.serving.batcher import ServingMetrics

pytestmark = pytest.mark.obs


# -- minimal prom text parser (the round-trip half of the contract) --------


def _parse_prom(text):
    """Parse 0.0.4 exposition text into ({name: kind}, {(name, labels): value}).
    Minimal on purpose: label values in this repo never contain commas, so
    splitting on ',' inside the brace block is sound."""
    assert text.endswith("\n"), "exposition must end with a newline"
    types, samples = {}, {}
    for line in text.splitlines():
        if line.startswith("# TYPE "):
            _, _, name, kind = line.split(" ", 3)
            types[name] = kind
            continue
        if not line or line.startswith("#"):
            continue
        head, _, val = line.rpartition(" ")
        if "{" in head:
            name, labelstr = head.split("{", 1)
            labels = tuple(
                sorted(
                    (k, v.strip('"'))
                    for k, v in (
                        pair.split("=", 1)
                        for pair in labelstr.rstrip("}").split(",")
                    )
                )
            )
        else:
            name, labels = head, ()
        samples[(name, labels)] = float(val)  # float("+Inf") == inf
    return types, samples


# -- prom registry units ---------------------------------------------------


def test_prom_counter_gauge_render_roundtrip():
    reg = Registry()
    c = reg.counter("req_total", "requests")
    c.inc()
    c.inc(2.0)
    c.inc(5.0, bucket="64x96")
    g = reg.gauge("depth", "queue depth")
    g.set(7.0)
    g.set(3.0)  # gauges may go down
    types, samples = _parse_prom(reg.render())
    assert types == {"req_total": "counter", "depth": "gauge"}
    assert samples[("req_total", ())] == 3.0
    assert samples[("req_total", (("bucket", "64x96"),))] == 5.0
    assert samples[("depth", ())] == 3.0
    # counters are monotone: inc rejects negatives, set_total rejects regress
    with pytest.raises(ValueError):
        c.inc(-1.0)
    c.set_total(10.0)
    with pytest.raises(ValueError):
        c.set_total(9.0)
    assert c.value() == 10.0


def test_prom_histogram_buckets_cumulative_and_sum_to_count():
    reg = Registry()
    h = reg.histogram("lat_ms", "latency", buckets=(1.0, 5.0, 10.0))
    for v in (0.5, 3.0, 7.0, 50.0):
        h.observe(v)
    types, samples = _parse_prom(reg.render())
    assert types["lat_ms"] == "histogram"
    bounds = ("1", "5", "10", "+Inf")
    cums = [samples[("lat_ms_bucket", (("le", b),))] for b in bounds]
    assert cums == [1, 2, 3, 4]
    assert cums == sorted(cums), "bucket counts must be cumulative"
    assert cums[-1] == samples[("lat_ms_count", ())] == h.count() == 4
    assert samples[("lat_ms_sum", ())] == pytest.approx(60.5)


def test_prom_registry_idempotent_by_name_kind_conflict_raises():
    reg = Registry()
    assert reg.counter("x", "a") is reg.counter("x", "ignored")
    with pytest.raises(ValueError):
        reg.gauge("x", "same name, different kind")


def test_serve_registry_http_scrape():
    """The trainer-side `--metrics_port` sidecar: GET /metrics serves the
    exposition with the prom Content-Type; other routes 404."""
    reg = Registry()
    reg.counter("raft_train_steps_total", "steps").inc(5.0)
    server = serve_registry(reg, port=0)
    host, port = server.server_address
    try:
        with urllib.request.urlopen(
            f"http://{host}:{port}/metrics", timeout=30
        ) as resp:
            assert resp.status == 200
            assert resp.headers["Content-Type"] == PROM_CONTENT_TYPE
            _, samples = _parse_prom(resp.read().decode())
        assert samples[("raft_train_steps_total", ())] == 5.0
        with pytest.raises(urllib.error.HTTPError) as exc:
            urllib.request.urlopen(f"http://{host}:{port}/other", timeout=30)
        assert exc.value.code == 404
    finally:
        server.shutdown()
        server.server_close()


# -- flight recorder / tracer units ----------------------------------------


def test_flight_recorder_ring_bounds_and_lifetime_counters():
    rec = FlightRecorder(capacity=4)
    for i in range(6):
        rec.append({"kind": "span", "name": f"s{i}"})
    rec.append({"kind": "event", "name": "e0"})
    rec.append({"kind": "event", "name": "e1"})
    records = rec.records()
    assert len(records) == 4  # bounded: O(1) memory forever
    assert [r["name"] for r in records] == ["s4", "s5", "e0", "e1"]  # last-N
    assert rec.counters() == {
        "spans_total": 6,
        "events_total": 2,
        "dropped_total": 4,  # 8 appended - 4 retained
        "dumps_total": 0,
    }


def test_tracer_disabled_at_capacity_zero_still_counts():
    tracer = Tracer(capacity=0, dump_path="/nonexistent/ignored.json")
    assert tracer.enabled is False
    tracer.span("s")
    tracer.event("e")
    assert tracer.recorder.records() == []
    counters = tracer.recorder.counters()
    assert counters["spans_total"] == 1 and counters["events_total"] == 1
    assert counters["dropped_total"] == 2
    assert tracer.dump("whatever") is None  # disabled recorders never dump


def test_tracer_dump_load_roundtrip(tmp_path):
    tracer = Tracer(capacity=8, dump_path=str(tmp_path / "flight_recorder.json"))
    tid = tracer.start_trace()
    tracer.span("admission", trace=tid, t0=1.0, t1=2.0, bucket=[64, 96])
    with tracer.timed("queue", trace=tid):
        pass
    tracer.event("breaker_transition", frm="serving", to="degraded")
    path = tracer.dump("test-reason")
    assert path == tracer.dump_path
    payload = load_flight_recorder(path)
    assert payload["reason"] == "test-reason"
    assert payload["traces_total"] == 1
    assert payload["counters"]["spans_total"] == 2
    assert payload["counters"]["events_total"] == 1
    names = [r["name"] for r in payload["records"]]
    assert names == ["admission", "queue", "breaker_transition"]
    span = payload["records"][0]
    assert span["trace"] == tid
    assert span["ms"] == pytest.approx(1000.0)
    assert span["attrs"]["bucket"] == [64, 96]
    assert tracer.recorder.counters()["dumps_total"] == 1
    # a Tracer with no dump_path skips dumping (returns None, not a crash)
    assert Tracer(capacity=4).dump("no-path") is None
    # version gate: a future/corrupt dump is refused loudly
    bad = dict(payload, flight_recorder_version=99)
    bad_path = tmp_path / "bad.json"
    bad_path.write_text(json.dumps(bad))
    with pytest.raises(ValueError):
        load_flight_recorder(str(bad_path))


def test_observability_block_shape():
    block = observability_block(None)
    assert block == {
        "enabled": False,
        "capacity": 0,
        "traces_total": 0,
        "spans_total": 0,
        "events_total": 0,
        "dropped_total": 0,
        "dumps_total": 0,
    }
    tracer = Tracer(capacity=16)
    tracer.start_trace()
    tracer.span("s")
    live = observability_block(tracer)
    assert live["enabled"] is True and live["capacity"] == 16
    assert live["traces_total"] == 1 and live["spans_total"] == 1
    assert all(isinstance(v, int) for k, v in live.items() if k != "enabled")


# -- the span entry point and the process-wide log --------------------------


def _host_events(trace_dir, prefix="rs/"):
    """{name: [stats dict]} of the host-plane events under `prefix`."""
    import glob

    from jax.profiler import ProfileData

    (path,) = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)
    found = {}
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for event in line.events:
                if event.name.startswith(prefix):
                    found.setdefault(event.name, []).append(
                        dict(event.stats, start_ns=event.start_ns, duration_ns=event.duration_ns))
    return found


@pytest.fixture(scope="module")
def tiny_evaluator():
    import jax
    import jax.numpy as jnp

    from raft_stereo_tpu.config import RAFTStereoConfig
    from raft_stereo_tpu.evaluate import Evaluator
    from raft_stereo_tpu.models import RAFTStereo

    cfg = RAFTStereoConfig(hidden_dims=(32, 32, 32), n_gru_layers=1, corr_levels=2)
    model = RAFTStereo(cfg)
    image = jnp.zeros((1, 32, 64, 3))
    variables = jax.jit(lambda r: model.init(r, image, image, iters=1))(jax.random.PRNGKey(0))
    evaluator = Evaluator(cfg, variables, iters=2)
    pair = [np.random.default_rng(i).uniform(0, 255, (30, 60, 3)).astype(np.float32) for i in (1, 2)]
    evaluator(*pair)  # compile the one shape, and the pad / unpad programs
    return evaluator, pair


def test_span_parent_root_and_attrs_in_the_process_log():
    before = len(process_spans())
    with span("outer/call", frame=3) as outer:
        with span("outer/part") as part:
            pass
        with span("outer/part"):
            with span("outer/leaf") as leaf:
                pass
    mine = process_spans()[before:]
    assert [r["name"] for r in mine] == ["outer/part", "outer/leaf", "outer/part", "outer/call"]
    by_id = {r["id"]: r for r in mine}
    assert by_id[outer.id]["parent"] is None and by_id[outer.id]["attrs"] == {"frame": 3}
    assert by_id[part.id]["parent"] == outer.id
    assert by_id[by_id[leaf.id]["parent"]]["name"] == "outer/part"
    assert {r["root"] for r in mine} == {outer.id}
    assert all(r["t1"] >= r["t0"] for r in mine)
    assert outer.seconds == by_id[outer.id]["t1"] - by_id[outer.id]["t0"]
    assert [r["name"] for r in process_spans("outer/leaf")[-1:]] == ["outer/leaf"]
    # a thread starts with no open span: its spans are roots of their own
    seen = []
    with span("outer/call"):
        worker = threading.Thread(target=lambda: seen.append(span("worker/job").begin()))
        worker.start()
        worker.join()
    assert seen[0].parent is None and seen[0].root == seen[0].id


def test_process_log_is_bounded_and_holds_no_array():
    import jax.numpy as jnp

    with span("log/array", image=jnp.ones((4, 4)), host=np.ones(3), ids=[1, 2], label="x", lr=1e-3):
        pass
    attrs = process_spans("log/array")[-1]["attrs"]
    assert attrs == {"image": "<ArrayImpl>", "host": "<ndarray>", "ids": [1, 2], "label": "x", "lr": 1e-3}
    json.dumps(process_spans())  # plain data, all of it
    for _ in range(obs_trace.PROCESS_LOG_CAPACITY + 10):
        with span("log/fill"):
            pass
    assert len(process_spans()) == obs_trace.PROCESS_LOG_CAPACITY
    # a dropped span, and a span ended twice, leave one record or none
    count = len(process_spans("log/once"))
    dropped = span("log/once").begin()
    dropped.drop()
    once = span("log/once").begin()
    once.end()
    once.end()
    assert len(process_spans("log/once")) == count + 1 and dropped.dropped


def test_tracer_writes_through_to_the_process_log():
    tracer = Tracer(capacity=8)
    with tracer.timed("chunk", trace=7, batch=2):
        tracer.span("stage", trace=7, t0=1.0, t1=2.5)
        tracer.event("compile")
    rows = {r["name"]: r for r in process_spans()[-2:]}
    assert rows["chunk"]["attrs"] == {"batch": 2, "trace": 7}
    assert rows["stage"]["parent"] == rows["chunk"]["id"] and rows["stage"]["t1"] == 2.5
    ring = tracer.recorder.records()
    assert [r["name"] for r in ring] == ["stage", "compile", "chunk"]
    assert ring[2]["trace"] == 7 and ring[2]["attrs"] == {"batch": 2}
    # a disabled ring still counts, and the log still gets the span
    off = Tracer(capacity=0)
    with off.timed("chunk"):
        pass
    assert off.recorder.counters()["spans_total"] == 1 and off.recorder.records() == []
    assert process_spans()[-1]["name"] == "chunk"


def test_evaluator_spans_land_in_the_profile_with_their_parent(tiny_evaluator, tmp_path):
    import jax

    evaluator, pair = tiny_evaluator
    jax.profiler.start_trace(str(tmp_path))
    try:
        evaluator(*pair)
    finally:
        jax.profiler.stop_trace()
    events = _host_events(str(tmp_path))
    assert {"rs/evaluate/call", "rs/evaluate/stage", "rs/evaluate/forward", "rs/evaluate/fetch"} <= set(events)
    (call,) = events["rs/evaluate/call"]
    assert call["parent"] == 0 and call["root"] == call["span"]
    for child in ("stage", "forward", "fetch"):
        (event,) = events[f"rs/evaluate/{child}"]
        assert event["parent"] == call["span"] and event["root"] == call["span"]
        assert call["start_ns"] <= event["start_ns"]
        assert event["start_ns"] + event["duration_ns"] <= call["start_ns"] + call["duration_ns"]
    # the same spans, by the same ids, in the process-wide log
    logged = {r["id"]: r for r in process_spans() if r["root"] == call["span"]}
    assert {r["name"] for r in logged.values()} == {
        "evaluate/call", "evaluate/stage", "evaluate/forward", "evaluate/fetch"}
    assert logged[events["rs/evaluate/forward"][0]["span"]]["name"] == "evaluate/forward"


def test_evaluator_call_returns_forward_seconds_and_adds_no_executable(tiny_evaluator, tmp_path):
    """Tracing on (a live profiler session) or off, a warmed call compiles
    nothing, and the seconds it returns are the forward span's own: dispatch
    to block_until_ready, as before the spans."""
    import jax

    from raft_stereo_tpu.utils.jit_hygiene import RecompileMonitor

    evaluator, pair = tiny_evaluator
    with RecompileMonitor(grace_steps=0) as monitor:
        quiet_map, quiet_s = evaluator(*pair)
        jax.profiler.start_trace(str(tmp_path))
        try:
            traced_map, traced_s = evaluator(*pair)
        finally:
            jax.profiler.stop_trace()
        stats = monitor.stats()
    assert stats["compiles_total"] == 0, stats
    np.testing.assert_array_equal(quiet_map, traced_map)
    forward, previous = process_spans("evaluate/forward")[-1], process_spans("evaluate/forward")[-2]
    assert traced_s == forward["t1"] - forward["t0"] and quiet_s == previous["t1"] - previous["t0"]
    call = process_spans("evaluate/call")[-1]
    parts = [r for r in process_spans() if r["root"] == call["id"] and r["id"] != call["id"]]
    assert [r["name"] for r in parts] == ["evaluate/stage", "evaluate/forward", "evaluate/fetch"]
    assert sum(r["t1"] - r["t0"] for r in parts) <= call["t1"] - call["t0"]
    assert 0 < traced_s < call["t1"] - call["t0"]


def test_prom_histogram_quantile_interpolates_within_a_bucket():
    hist = Registry().histogram("q_ms", "t", buckets=(10.0, 20.0, 40.0))
    assert hist.quantile(0.5) is None
    for value in (12.0, 14.0, 16.0, 18.0):
        hist.observe(value)
    assert hist.quantile(0.5) == 15.0 and hist.quantile(1.0) == 20.0
    hist.observe(1000.0)  # the +Inf bucket reads as the last finite bound
    assert hist.quantile(0.99) == 40.0


# -- percentile semantics --------------------------------------------------


def test_percentile_linear_interpolation_and_small_sample_edges():
    p = ServingMetrics._percentile
    assert p([], 0.50) is None  # a percentile of nothing is not 0.0
    assert p([42.0], 0.50) is None  # one sample is not a distribution
    assert p([0.0, 10.0], 0.50) == pytest.approx(5.0)
    assert p([1.0, 2.0, 3.0, 4.0], 0.50) == pytest.approx(2.5)
    # p95 over 0..19: pos = 0.95 * 19 = 18.05 -> 18 + 0.05 * (19 - 18)
    assert p([float(i) for i in range(20)], 0.95) == pytest.approx(18.05)
    assert p([5.0, 7.0], 0.0) == 5.0 and p([5.0, 7.0], 1.0) == 7.0


def test_snapshot_percentiles_none_below_two_samples():
    m = ServingMetrics()
    snap = m.snapshot()
    assert snap["latency_p50_ms"] is None and snap["latency_p99_ms"] is None
    m.record_response(10.0, early_exit=False, deadline_missed=False)
    assert m.snapshot()["latency_p50_ms"] is None
    m.record_response(20.0, early_exit=False, deadline_missed=False)
    snap = m.snapshot()
    assert snap["latency_p50_ms"] == pytest.approx(15.0)
    assert snap["latency_p99_ms"] == pytest.approx(19.9)


def test_attribution_summary_window_overflow():
    m = ServingMetrics(latency_window=4)
    for v in (100.0, 1.0, 2.0, 3.0, 4.0, 5.0):  # 100.0 falls off the window
        m.record_attribution(v, v * 10.0, v / 10.0)
    summary = m.attribution_summary()
    assert summary["window"] == 4
    qw = summary["queue_wait_ms"]
    assert qw["count"] == 4  # bounded reservoir, not lifetime
    assert qw["mean"] == pytest.approx((2.0 + 3.0 + 4.0 + 5.0) / 4)
    assert qw["p50"] == pytest.approx(3.5)
    assert qw["p50"] <= qw["p95"]
    assert summary["device_ms"]["mean"] == pytest.approx(35.0)
    # empty reservoirs report typed zeros, count disambiguates "no data"
    fresh = ServingMetrics().attribution_summary()
    assert fresh["queue_wait_ms"] == {
        "count": 0, "mean": 0.0, "p50": 0.0, "p95": 0.0,
    }


# -- device memory telemetry -----------------------------------------------


def test_memory_block_is_typed_consistent_and_never_raises():
    block = memory_block()
    assert set(block) == {
        "available",
        "device_count",
        "bytes_in_use",
        "peak_bytes_in_use",
        "peak_bytes_reserved",
        "bytes_limit",
        "live_buffer_count",
        "live_buffer_bytes",
    }
    assert isinstance(block["available"], bool)
    for key in set(block) - {"available"}:
        assert isinstance(block[key], int) and not isinstance(block[key], bool)
        assert block[key] >= 0
    # only stat-bearing devices are counted, so this equivalence is exact
    assert block["available"] == (block["device_count"] > 0)
    assert block["peak_bytes_in_use"] >= block["bytes_in_use"]


def test_set_memory_gauges_populates_registry():
    reg = Registry()
    block = set_memory_gauges(reg)
    assert block == memory_block()
    _, samples = _parse_prom(reg.render())
    for name in (
        "raft_device_memory_bytes_in_use",
        "raft_device_memory_peak_bytes_in_use",
        "raft_device_memory_peak_bytes_reserved",
        "raft_device_memory_bytes_limit",
        "raft_live_buffer_count",
        "raft_live_buffer_bytes",
        "raft_device_memory_available",
    ):
        assert (name, ()) in samples, name
    assert samples[("raft_device_memory_available", ())] == float(
        block["available"]
    )


# -- serving integration: obs-on vs obs-off twins --------------------------

OBS_BUCKET = (64, 96)
OBS_MAX_ITERS = 4
OBS_CHUNK_ITERS = 2
_N_PAIRS = 3


def _serve_cfg(**kw):
    from raft_stereo_tpu.config import ServeConfig

    return ServeConfig(
        buckets=(OBS_BUCKET,),
        max_batch=1,
        chunk_iters=OBS_CHUNK_ITERS,
        max_iters=OBS_MAX_ITERS,
        batch_window_ms=5.0,
        **kw,
    )


@pytest.fixture(scope="module")
def twin_services(tmp_path_factory):
    """Two warmed services from the same model variables (shared init
    cache) with IDENTICAL traffic: first the obs-OFF baseline (recorder
    disabled), stats snapshotted and closed; then the obs-on service with
    every pillar live (tracing + prom + per-batch memory sampling), kept
    alive for the rest of the module. Sequential on purpose: the
    RecompileMonitor observes process-global compile events, so the
    baseline must finish before the obs service's monitor starts — the
    monitors then each see exactly their own service's executables, which
    is what makes the compile-count comparison meaningful."""
    from raft_stereo_tpu.serving.service import StereoService

    log_dir = str(tmp_path_factory.mktemp("obs_serve"))
    rng = np.random.default_rng(20260805)
    h, w = OBS_BUCKET
    pairs = [
        (
            rng.uniform(0, 255, (h, w, 3)).astype(np.float32),
            rng.uniform(0, 255, (h, w, 3)).astype(np.float32),
        )
        for _ in range(_N_PAIRS)
    ]

    def _traffic(svc):
        return [
            svc.submit(i1, i2, max_iters=OBS_MAX_ITERS).result(timeout=300)
            for i1, i2 in pairs
        ]

    off = StereoService(
        _serve_cfg(log_dir=None, flight_recorder_events=0)
    ).start()
    results_off = _traffic(off)
    stats_off = off.engine.hygiene.monitor.stats()
    off_tracer_enabled = off.tracer.enabled
    off.close()

    obs = StereoService(
        _serve_cfg(log_dir=log_dir, flight_recorder_events=512)
    ).start()
    results_obs = _traffic(obs)
    stats_obs = obs.engine.hygiene.monitor.stats()
    yield {
        "obs": obs,
        "results": {"obs": results_obs, "off": results_off},
        "stats": {"obs": stats_obs, "off": stats_off},
        "off_tracer_enabled": off_tracer_enabled,
        "log_dir": log_dir,
    }
    obs.close()


def test_observability_is_free_zero_new_executables_zero_recompiles(
    twin_services,
):
    """THE serving acceptance criterion: with tracing, prom histograms and
    memory sampling all live, the service answers bit-identically to its
    obs-off twin, compiles post-warmup exactly zero times, and its compile
    TOTAL equals the twin's — observability added no executables and no
    device syncs (a sync would show up as drift in the chunked anytime
    path's timings, a new executable in compiles_total)."""
    for r_obs, r_off in zip(
        twin_services["results"]["obs"], twin_services["results"]["off"]
    ):
        assert r_obs["iters_completed"] == r_off["iters_completed"]
        np.testing.assert_array_equal(r_obs["disparity"], r_off["disparity"])
    stats_obs = twin_services["stats"]["obs"]
    stats_off = twin_services["stats"]["off"]
    assert stats_obs["compiles_post_grace"] == 0, stats_obs
    assert stats_off["compiles_post_grace"] == 0, stats_off
    assert stats_obs["compiles_total"] == stats_off["compiles_total"], (
        f"observability changed the executable set: {stats_obs} vs {stats_off}"
    )
    assert twin_services["obs"].tracer.enabled is True
    assert twin_services["off_tracer_enabled"] is False  # capacity 0 = no ring


def test_request_lifecycle_reconstructible_from_ring(twin_services):
    """A served request's full lifecycle is in the ring, joined by trace
    ID: admission/queue/respond spans carry the ID directly; batch-level
    stage/chunk/finalize records carry it in their `traces` list."""
    records = twin_services["obs"].tracer.recorder.records()
    names = {r.get("name") for r in records}
    assert {
        "admission", "queue", "stage", "prelude", "chunk", "finalize", "respond",
    } <= names, names
    by_name = {}
    for r in records:
        by_name.setdefault(r.get("name"), []).append(r)
    respond_tids = {r["trace"] for r in by_name["respond"]}
    assert len(respond_tids) >= _N_PAIRS
    for tid in respond_tids:
        assert any(r["trace"] == tid for r in by_name["admission"])
        assert any(r["trace"] == tid for r in by_name["queue"])
        for batch_kind in ("stage", "chunk", "finalize"):
            assert any(
                tid in (r.get("attrs", {}).get("traces") or [])
                for r in by_name[batch_kind]
            ), f"no {batch_kind} record covers trace {tid}"
    for r in by_name["chunk"] + by_name["respond"]:
        assert r["t1"] >= r["t0"] and r["ms"] >= 0.0


def test_metrics_json_snapshot_key_set_is_frozen(twin_services):
    """The legacy /metrics JSON surface: benchmark/drivers/serve.py and
    operator tooling key off these exact names — prom is the additive surface,
    this one must not drift."""
    assert set(twin_services["obs"].metrics()) == {
        "requests_total",
        "responses_total",
        "rejected_total",
        "shed_total",
        "deadline_infeasible_total",
        "failed_requests_total",
        "deadline_miss_total",
        "early_exit_total",
        "batches_total",
        "stream_requests_total",
        "warm_start_total",
        "stream_resets_total",
        "requeues_total",
        "respawns_total",
        "batches_by_replica",
        "in_flight_by_replica",
        "streams_active",
        "queue_depth",
        "batch_fill_mean",
        "latency_p50_ms",
        "latency_p99_ms",
        "requests_by_bucket",
    }


def test_metrics_http_content_types_and_prom_roundtrip(twin_services):
    """/metrics defaults to the byte-compatible JSON snapshot
    (application/json); ?format=prom opts into the 0.0.4 exposition with
    its Content-Type and values that reconcile with the snapshot; unknown
    formats are a 400, not a silent fallback."""
    from raft_stereo_tpu.serving.service import make_http_server

    service = twin_services["obs"]
    server = make_http_server(service, port=0)
    host, port = server.server_address
    th = threading.Thread(target=server.serve_forever, daemon=True)
    th.start()
    base = f"http://{host}:{port}"
    try:
        with urllib.request.urlopen(f"{base}/metrics", timeout=60) as resp:
            assert resp.status == 200
            assert resp.headers["Content-Type"] == "application/json"
            snap = json.loads(resp.read())
        assert snap["responses_total"] >= _N_PAIRS

        with urllib.request.urlopen(
            f"{base}/metrics?format=prom", timeout=60
        ) as resp:
            assert resp.status == 200
            assert resp.headers["Content-Type"] == PROM_CONTENT_TYPE
            types, samples = _parse_prom(resp.read().decode())
        assert types["raft_serving_responses_total"] == "counter"
        assert (
            samples[("raft_serving_responses_total", ())]
            == snap["responses_total"]
        )
        assert types["raft_serving_queue_wait_ms"] == "histogram"
        inf_key = ("raft_serving_queue_wait_ms_bucket", (("le", "+Inf"),))
        assert samples[inf_key] == samples[
            ("raft_serving_queue_wait_ms_count", ())
        ]
        assert samples[inf_key] >= _N_PAIRS
        assert math.isinf(float("+Inf"))  # the parser's +Inf convention
        assert samples[("raft_serving_state_code", (("replica", "aggregate"),))] >= 0

        # explicit-but-json stays json
        with urllib.request.urlopen(
            f"{base}/metrics?format=json", timeout=60
        ) as resp:
            assert resp.headers["Content-Type"] == "application/json"
            assert set(json.loads(resp.read())) == set(snap)

        with pytest.raises(urllib.error.HTTPError) as exc:
            urllib.request.urlopen(f"{base}/metrics?format=xml", timeout=60)
        assert exc.value.code == 400
    finally:
        server.shutdown()
        server.server_close()
        th.join(timeout=10)


def test_healthz_carries_observability_attribution_memory(twin_services):
    from raft_stereo_tpu.utils.run_report import validate_run_report

    report = twin_services["obs"].healthz()
    assert validate_run_report(report) == [], validate_run_report(report)
    obs_block = report["observability"]
    assert obs_block["enabled"] is True and obs_block["capacity"] == 512
    assert obs_block["spans_total"] > 0 and obs_block["traces_total"] >= _N_PAIRS

    attribution = report["serving"]["attribution"]
    assert attribution["window"] >= 1
    for series in ("queue_wait_ms", "device_ms", "host_gap_ms"):
        stats = attribution[series]
        assert stats["count"] >= _N_PAIRS
        assert stats["mean"] >= 0.0 and stats["p50"] <= stats["p95"]
    # device time was attributed from the existing sync boundaries —
    # nonzero even on CPU (the chunks really ran)
    assert attribution["device_ms"]["mean"] > 0.0

    mem = report["serving"]["memory"]
    assert isinstance(mem["available"], bool)
    assert mem["available"] == (mem["device_count"] > 0)


# -- training integration: strict-mode fit with every pillar on ------------


def _free_port():
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def test_strict_mode_training_fit_with_observability_on(tmp_path):
    """The training half of the acceptance: a strict-mode fit (transfer
    guard `disallow` + recompile hard-fail) with tracing, the prom sidecar
    AND save-boundary memory sampling all live completes with ZERO
    post-grace compiles — run-completion itself proves zero unsanctioned
    transfers. The run report gains the validated `observability` block and
    the clean-exit path leaves a parseable flight_recorder.json covering
    the step lifecycle. The sidecar is scraped mid-run from the validation
    window (host-side networking; invisible to the guard)."""
    import jax
    import jax.numpy as jnp

    from raft_stereo_tpu.config import RAFTStereoConfig, TrainConfig
    from raft_stereo_tpu.train.trainer import Trainer
    from raft_stereo_tpu.utils.run_report import validate_run_report

    port = _free_port()
    small = RAFTStereoConfig(
        hidden_dims=(32, 32, 32), n_gru_layers=1, corr_levels=2
    )
    cfg = TrainConfig(
        model=small,
        batch_size=1,
        num_steps=6,
        train_iters=2,
        mesh_shape=(1, 1),
        checkpoint_dir=str(tmp_path / "ck"),
        log_dir=str(tmp_path / "runs"),
        checkpoint_every=4,
        strict_mode=True,
        recompile_grace=2,
        validate_every=3,
        metrics_port=port,
        flight_recorder_events=128,
    )
    trainer = Trainer(cfg, sample_shape=(32, 48, 3))
    rng = np.random.default_rng(14)
    batches = []
    for _ in range(cfg.num_steps):
        base = rng.uniform(0, 255, (1, 32, 48 + 16, 3)).astype(np.float32)
        batches.append(
            {
                "image1": base[:, :, 4 : 48 + 4],
                "image2": base[:, :, :48],
                "flow": np.full((1, 32, 48, 1), -4.0, np.float32),
                "valid": np.ones((1, 32, 48), np.float32),
            }
        )

    scrapes = []

    def validate_fn(state):
        with urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metrics", timeout=30
        ) as resp:
            assert resp.headers["Content-Type"] == PROM_CONTENT_TYPE
            scrapes.append(_parse_prom(resp.read().decode())[1])
        # and a deliberately syncing metric — legal only inside the window
        val = jax.jit(lambda p: sum(jnp.sum(x) for x in jax.tree.leaves(p)))(
            state.params
        )
        return {"val": float(val)}

    trainer.fit(batches, validate_fn=validate_fn)

    report = trainer.last_run_report
    assert report["stop_cause"] == "completed"
    assert validate_run_report(report) == [], validate_run_report(report)
    assert report["jit_hygiene"]["compiles_post_grace"] == 0
    assert report["jit_hygiene"]["violations"] == []

    obs_block = report["observability"]
    assert obs_block["enabled"] is True and obs_block["capacity"] == 128
    assert obs_block["spans_total"] >= 2 * cfg.num_steps  # data-wait + step
    assert obs_block["dropped_total"] >= 0

    # live scrape happened mid-fit (steps 3 and 6) and saw real series
    assert len(scrapes) == 2
    assert scrapes[-1][("raft_train_steps_total", ())] >= 3
    assert (
        scrapes[-1][("raft_train_step_ms_count", ())]
        <= scrapes[-1][("raft_train_steps_total", ())]
    )
    # save-boundary memory sampling landed in the registry by the last scrape
    assert ("raft_device_memory_available", ()) in scrapes[-1]

    # the clean-exit dump: parseable, and it covers the step lifecycle
    payload = load_flight_recorder(
        os.path.join(cfg.log_dir, "flight_recorder.json")
    )
    assert payload["reason"].startswith("fit-exit")
    names = {r.get("name") for r in payload["records"]}
    assert {"data-wait", "step", "checkpoint-save"} <= names, names
    steps = [
        r for r in payload["records"]
        if r.get("name") == "step" and r.get("kind") == "span"
    ]
    assert len(steps) == cfg.num_steps
    assert all(r["ms"] >= 0.0 for r in steps)

    # the fit's phases, in the process-wide log under one root: start, steps
    # (holding the per-step spans), drain, final save — the strict-mode guard
    # above proves the spans added no transfer and no compile
    fit = process_spans("train/fit")[-1]
    under = [r for r in process_spans() if r["root"] == fit["id"] and r["id"] != fit["id"]]
    phases = [r for r in under if r["parent"] == fit["id"]]
    assert [r["name"] for r in phases] == ["train/start", "train/steps", "train/drain", "train/final_save"]
    assert all(a["t1"] <= b["t0"] for a, b in zip(phases, phases[1:]))
    steps_id = phases[1]["id"]
    per_step = [r for r in under if r["parent"] == steps_id]
    assert [r["name"] for r in per_step if r["name"] == "step"] == ["step"] * cfg.num_steps
    assert sum(r["name"] == "data-wait" for r in per_step) == cfg.num_steps
    assert [r["attrs"]["step"] for r in per_step if r["name"] == "checkpoint-save"] == [4]
    final = [r for r in under if r["name"] == "checkpoint-save" and r["attrs"].get("final")]
    assert len(final) == 1 and final[0]["parent"] == phases[3]["id"]
