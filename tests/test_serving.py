"""Serving-tier tests (tier-1, `-m serving`): the anytime engine, the
micro-batcher, and the stdlib HTTP front, against ONE warmed service.

The acceptance criteria from the serving design, each machine-checked here:

- warmed service, >= 2 concurrent shape buckets, responses BIT-IDENTICAL to
  a direct padded `model.apply(..., iters=N, test_mode=True)` call — the
  chunked prelude/chunk/finalize decomposition costs no accuracy;
- a tight deadline produces a VALID early exit: `iters_completed` is a whole
  number of chunks below the budget, `early_exit` is set, and the disparity
  equals the direct call at that same iteration count (the anytime ladder's
  rungs are real model outputs, not junk);
- ZERO post-warmup recompiles, via the engine's RecompileMonitor: the
  `refs` fixture compiles its direct-model references BEFORE the service
  boots (the monitor starts inside `engine.warm()`), so `compiles_post_grace`
  staying 0 after traffic is attributable to the serving path alone;
- /healthz validates under the run_report schema; /metrics carries the
  counter contract benchmark/drivers/serve.py reads;
- the batcher NEVER mixes buckets in one batch (batch_log audit).

Warmup compiles every (bucket, batch-size) x (prelude, chunk, finalize)
executable — tens of seconds on CPU even at these small buckets — so the
whole module shares one module-scoped service (smallest useful config:
two buckets, max_batch 2, chunk_iters 2, max_iters 4).
"""

import json
import threading
import urllib.error
import urllib.request

import numpy as np
import pytest

pytestmark = pytest.mark.serving

BUCKETS = ((64, 96), (96, 128))
CHUNK_ITERS = 2
MAX_ITERS = 4  # 2 chunks: an early exit can only land at iters_completed=2


def _pairs(rng):
    """Deterministic stereo pairs: per bucket, one exact-fit and one
    smaller-than-bucket shape (so the padding-admission path is exercised,
    not bypassed)."""
    out = []
    for h, w in BUCKETS:
        for dh, dw in ((0, 0), (4, 4)):
            shape = (h - dh, w - dw, 3)
            out.append(
                (
                    rng.uniform(0, 255, shape).astype(np.float32),
                    rng.uniform(0, 255, shape).astype(np.float32),
                )
            )
    return out


@pytest.fixture(scope="module")
def refs():
    """Direct-model reference disparities, compiled BEFORE the service
    boots: the serving RecompileMonitor starts inside `engine.warm()`, so
    these harness compiles are invisible to it and the zero-recompile
    assertions below measure the serving path alone. Shares the model
    variables with the engine through the init_model_variables cache (same
    config -> same parameter tree), which is what makes bit-identity a
    meaningful claim.

    Bit-identity only holds LIKE-FOR-LIKE in batch shape: the batch-2
    executable tiles its reductions differently from batch-1 (~1e-3 drift
    after 4 GRU iterations on CPU), so batch-1 references (`disparity`,
    per pair at one-chunk and full budgets) back the sequential/deadline
    tests, and batch-2 references (`disparity_b2`, each bucket's two pairs
    stacked in submission order) back the coalesced-batch test."""
    import jax

    from raft_stereo_tpu.config import RAFTStereoConfig
    from raft_stereo_tpu.models import RAFTStereo
    from raft_stereo_tpu.models.init_cache import init_model_variables
    from raft_stereo_tpu.utils.padding import InputPadder

    mcfg = RAFTStereoConfig()
    variables = init_model_variables(mcfg)
    model = RAFTStereo(mcfg)
    fwd = {
        iters: jax.jit(
            lambda v, a, b, it=iters: model.apply(
                v, a, b, iters=it, test_mode=True
            )[1]
        )
        for iters in (CHUNK_ITERS, MAX_ITERS)
    }

    rng = np.random.default_rng(20260804)
    pairs = _pairs(rng)
    padders, padded = [], []
    for i1, i2 in pairs:
        h, w, c = i1.shape
        bucket = next(b for b in BUCKETS if b[0] >= h and b[1] >= w)
        padder = InputPadder((1, h, w, c), divis_by=32, target=bucket)
        left, right, top, bottom = padder.pad_amounts
        pad = ((top, bottom), (left, right), (0, 0))
        padders.append(padder)
        padded.append(
            (np.pad(i1, pad, mode="edge"), np.pad(i2, pad, mode="edge"))
        )

    disparity = {}  # (pair_idx, iters) -> (h, w) float32, batch-1
    for idx, (p1, p2) in enumerate(padded):
        for iters, fn in fwd.items():
            up = np.asarray(
                jax.device_get(fn(variables, p1[None], p2[None])), np.float32
            )
            disparity[(idx, iters)] = padders[idx].unpad(up)[0, :, :, 0]

    disparity_b2 = {}  # pair_idx -> (h, w) float32, full budget, batch-2
    for b_idx in range(len(BUCKETS)):
        idxs = [2 * b_idx, 2 * b_idx + 1]  # submission order per bucket
        s1 = np.stack([padded[i][0] for i in idxs])
        s2 = np.stack([padded[i][1] for i in idxs])
        up = np.asarray(
            jax.device_get(fwd[MAX_ITERS](variables, s1, s2)), np.float32
        )
        for row, i in enumerate(idxs):
            disparity_b2[i] = padders[i].unpad(up[row : row + 1])[0, :, :, 0]

    return {"pairs": pairs, "disparity": disparity, "disparity_b2": disparity_b2}


@pytest.fixture(scope="module")
def served(refs):
    """The one warmed service (depends on `refs` so every reference compile
    lands before the monitor starts)."""
    from raft_stereo_tpu.config import ServeConfig
    from raft_stereo_tpu.serving.service import StereoService

    cfg = ServeConfig(
        buckets=BUCKETS,
        max_batch=2,
        chunk_iters=CHUNK_ITERS,
        max_iters=MAX_ITERS,
        batch_window_ms=25.0,
    )
    service = StereoService(cfg).start()
    yield service
    service.close()


def _post_warmup_compiles(service) -> int:
    return service.engine.hygiene.monitor.stats()["compiles_post_grace"]


# -- config / padding units (no device work) -------------------------------


def test_serve_config_validation():
    from raft_stereo_tpu.config import ServeConfig

    cfg = ServeConfig(buckets=BUCKETS, max_batch=4)
    assert cfg.batch_sizes == (1, 2, 4)
    assert ServeConfig(max_batch=3).batch_sizes == (1, 2, 3)
    assert cfg.num_chunks == -(-cfg.max_iters // cfg.chunk_iters)
    with pytest.raises(ValueError):
        ServeConfig(buckets=())
    with pytest.raises(ValueError):
        ServeConfig(buckets=((60, 96),))  # not divis_by-aligned
    with pytest.raises(ValueError):
        ServeConfig(buckets=((64, 96), (64, 96)))  # duplicate
    with pytest.raises(ValueError):
        ServeConfig(chunk_iters=0)


def test_input_padder_target_bucket():
    from raft_stereo_tpu.utils.padding import InputPadder

    padder = InputPadder((1, 60, 92, 3), divis_by=32, target=(64, 96))
    left, right, top, bottom = padder.pad_amounts
    assert (top + bottom, left + right) == (4, 4)
    x = np.arange(64 * 96, dtype=np.float32).reshape(1, 64, 96, 1)
    assert padder.unpad(x).shape == (1, 60, 92, 1)
    with pytest.raises(ValueError):
        InputPadder((1, 70, 92, 3), divis_by=32, target=(64, 96))  # too small
    with pytest.raises(ValueError):
        InputPadder((1, 60, 92, 3), divis_by=32, target=(65, 96))  # misaligned


# -- the e2e acceptance test -----------------------------------------------


def test_sequential_requests_bit_identical_to_direct(served, refs):
    """The anytime decomposition costs no accuracy: each request served
    alone (batch 1) is BIT-identical to a direct
    `model.apply(..., iters=MAX_ITERS, test_mode=True)` call on the same
    padded input — across both buckets, exact-fit and padded shapes."""
    assert served.warm_summary["combos"] == len(BUCKETS) * 2
    pairs = refs["pairs"]
    for idx, (i1, i2) in enumerate(pairs):
        res = served.submit(i1, i2, max_iters=MAX_ITERS).result(timeout=300)
        want = refs["disparity"][(idx, MAX_ITERS)]
        assert res["iters_completed"] == MAX_ITERS
        assert res["early_exit"] is False
        assert res["disparity"].shape == i1.shape[:2]
        assert res["disparity"].dtype == np.float32
        np.testing.assert_array_equal(res["disparity"], want)
        h, w = i1.shape[:2]
        assert tuple(res["bucket"]) == next(
            b for b in BUCKETS if b[0] >= h and b[1] >= w
        )
    assert _post_warmup_compiles(served) == 0, (
        "serving traffic compiled post-warmup: "
        f"{served.engine.hygiene.monitor.stats()}"
    )


def test_concurrent_buckets_coalesce_bit_identical_zero_recompiles(served, refs):
    """THE serving acceptance criterion: four in-flight requests across
    both shape buckets, coalesced into one batch-2 executable per bucket,
    bit-identical to a direct BATCHED model call on the same stacked
    inputs (batch-1 vs batch-2 executables differ in reduction tiling, so
    like-for-like batch shape is the honest bitwise claim) — and the whole
    burst triggers zero post-warmup compiles (absolute: nothing has
    compiled since `warm()` returned)."""
    pairs = refs["pairs"]
    m = served.batcher.metrics
    with m._lock:
        log_before = len(m.batch_log)
    # Rapid-fire, bucket-interleaved: both buckets' queues fill while the
    # stager's batch window (25 ms) is open, so each bucket's two requests
    # ride one real=2 batch in submission order.
    order = [0, 2, 1, 3]
    futures = {i: served.submit(*pairs[i], max_iters=MAX_ITERS) for i in order}
    results = {i: f.result(timeout=300) for i, f in futures.items()}

    with m._lock:
        new_batches = list(m.batch_log)[log_before:]
    assert sorted(
        (tuple(b), real) for b, real, _ in new_batches
    ) == [(BUCKETS[0], 2), (BUCKETS[1], 2)], (
        f"burst did not coalesce into one batch-2 per bucket: {new_batches}"
    )

    for idx, res in results.items():
        assert res["iters_completed"] == MAX_ITERS
        assert res["early_exit"] is False
        np.testing.assert_array_equal(
            res["disparity"], refs["disparity_b2"][idx]
        )

    assert _post_warmup_compiles(served) == 0, (
        "serving traffic compiled post-warmup: "
        f"{served.engine.hygiene.monitor.stats()}"
    )


def test_tight_deadline_early_exit_is_a_valid_rung(served, refs):
    """A deadline no chunk can meet exits after the mandatory first chunk —
    and the early disparity is the REAL 2-iteration model output (the
    anytime ladder's rung), bit-identical to a direct iters=2 call."""
    before = _post_warmup_compiles(served)
    fut = served.submit(
        *refs["pairs"][0], deadline_ms=0.05, max_iters=MAX_ITERS
    )
    res = fut.result(timeout=300)
    assert res["early_exit"] is True
    assert res["iters_completed"] == CHUNK_ITERS  # one chunk, not zero
    assert res["iters_completed"] < MAX_ITERS
    np.testing.assert_array_equal(
        res["disparity"], refs["disparity"][(0, CHUNK_ITERS)]
    )
    assert served.metrics()["early_exit_total"] >= 1
    assert _post_warmup_compiles(served) == before


def test_max_iters_rounds_up_to_whole_chunks(served):
    """`max_iters=1` still runs a whole chunk (the executable is the unit
    of work): iters_completed == chunk_iters, not early-exit."""
    h, w = BUCKETS[0]
    img = np.zeros((h, w, 3), np.float32)
    res = served.submit(img, img, max_iters=1).result(timeout=300)
    assert res["iters_completed"] == CHUNK_ITERS
    assert res["early_exit"] is False  # budget (rounded up) was delivered


# -- batcher behavior ------------------------------------------------------


def test_batcher_never_mixes_buckets(served, refs):
    """Structural audit: every dispatched batch drew from exactly one
    bucket deque, its padded size is a warmed batch size, and per-bucket
    admission counters reconcile with the log."""
    m = served.batcher.metrics
    with m._lock:
        log = list(m.batch_log)
    assert log, "no batches dispatched yet?"
    sizes = served.config.batch_sizes
    for bucket, real, padded in log:
        assert tuple(bucket) in BUCKETS
        assert 1 <= real <= padded <= served.config.max_batch
        assert padded in sizes
    snap = served.metrics()
    assert set(snap["requests_by_bucket"]) <= {
        f"{h}x{w}" for h, w in BUCKETS
    }
    assert sum(real for _, real, _ in log) == snap["responses_total"]


def test_simultaneous_same_bucket_submits_coalesce(served):
    """Two same-bucket requests inside one batch window ride one batch
    (fill 2/2 appears in the log) and both get correct-shape answers."""
    before = _post_warmup_compiles(served)
    h, w = BUCKETS[1]
    rng = np.random.default_rng(7)
    img = lambda: rng.uniform(0, 255, (h, w, 3)).astype(np.float32)  # noqa: E731
    futs = [served.submit(img(), img()) for _ in range(2)]
    for f in futs:
        assert f.result(timeout=300)["disparity"].shape == (h, w)
    m = served.batcher.metrics
    with m._lock:
        log = list(m.batch_log)
    assert any(
        tuple(b) == BUCKETS[1] and real == 2 for b, real, _ in log
    ), f"no coalesced batch in {log}"
    assert _post_warmup_compiles(served) == before


def test_oversized_input_rejected(served):
    from raft_stereo_tpu.serving.service import BucketOverflowError

    big = np.zeros((200, 200, 3), np.float32)
    rejected_before = served.metrics()["rejected_total"]
    with pytest.raises(BucketOverflowError):
        served.submit(big, big)
    assert served.metrics()["rejected_total"] == rejected_before + 1


# -- observability ---------------------------------------------------------


def test_healthz_validates_under_run_report_schema(served):
    from raft_stereo_tpu.utils.run_report import validate_run_report

    report = served.healthz()
    assert validate_run_report(report) == []
    s = report["serving"]
    assert s["warmed"] is True
    assert s["buckets"] == [list(b) for b in BUCKETS]
    assert s["chunk_iters"] == CHUNK_ITERS and s["max_iters"] == MAX_ITERS
    assert report["jit_hygiene"]["compiles_post_grace"] == 0


def test_metrics_snapshot_contract(served):
    """The exact counter surface /metrics serves and benchmark/drivers/serve.py reads."""
    snap = served.metrics()
    for key in (
        "requests_total",
        "responses_total",
        "rejected_total",
        "deadline_miss_total",
        "early_exit_total",
        "batches_total",
        "queue_depth",
        "batch_fill_mean",
        "latency_p50_ms",
        "latency_p99_ms",
        "requests_by_bucket",
    ):
        assert key in snap, key
    assert snap["responses_total"] <= snap["requests_total"]
    assert 0.0 < snap["batch_fill_mean"] <= 1.0
    assert snap["latency_p50_ms"] <= snap["latency_p99_ms"]


# -- HTTP front ------------------------------------------------------------


def test_http_front_end_to_end(served, refs):
    """predict/healthz/metrics over a real ephemeral-port HTTP server,
    bit-identical through the JSON round-trip; bad routes and oversized
    inputs map to their status codes."""
    from raft_stereo_tpu.serving.service import make_http_server

    server = make_http_server(served, port=0)
    host, port = server.server_address
    th = threading.Thread(target=server.serve_forever, daemon=True)
    th.start()
    base = f"http://{host}:{port}"
    try:
        i1, i2 = refs["pairs"][1]
        body = json.dumps(
            {
                "image1": i1.tolist(),
                "image2": i2.tolist(),
                "max_iters": MAX_ITERS,
            }
        ).encode()
        req = urllib.request.Request(
            f"{base}/v1/predict",
            data=body,
            headers={"Content-Type": "application/json"},
        )
        with urllib.request.urlopen(req, timeout=300) as resp:
            assert resp.status == 200
            out = json.loads(resp.read())
        got = np.asarray(out["disparity"], np.float32)
        np.testing.assert_array_equal(
            got, refs["disparity"][(1, MAX_ITERS)]
        )
        assert out["iters_completed"] == MAX_ITERS

        with urllib.request.urlopen(f"{base}/healthz", timeout=60) as resp:
            assert resp.status == 200
            health = json.loads(resp.read())
        assert health["serving"]["warmed"] is True

        with urllib.request.urlopen(f"{base}/metrics", timeout=60) as resp:
            assert resp.status == 200
            assert "latency_p50_ms" in json.loads(resp.read())

        bad = urllib.request.Request(f"{base}/v1/predict", data=b"{}")
        try:
            urllib.request.urlopen(bad, timeout=60)
            raise AssertionError("expected HTTP 400")
        except urllib.error.HTTPError as exc:
            assert exc.code == 400

        big = np.zeros((200, 200, 3), np.float32)
        over = urllib.request.Request(
            f"{base}/v1/predict",
            data=json.dumps(
                {"image1": big.tolist(), "image2": big.tolist()}
            ).encode(),
        )
        try:
            urllib.request.urlopen(over, timeout=60)
            raise AssertionError("expected HTTP 413")
        except urllib.error.HTTPError as exc:
            assert exc.code == 413
    finally:
        server.shutdown()
        server.server_close()
        th.join(timeout=10)


def test_no_compiles_across_whole_module_traffic(served):
    """Runs LAST in the module: after every test above pushed traffic
    through both buckets, both batch sizes, deadlines and the HTTP front,
    the serving monitor still reports zero post-warmup compiles — the
    machine-checked 'zero recompiles in steady state' guarantee."""
    assert _post_warmup_compiles(served) == 0
    report = served.engine.hygiene.report()
    assert report["violations"] == []
