"""Serving-engine telemetry: recorder math + the ISSUE-2 smoke test
(engine drives ≥2 requests; /metrics exposes nonzero TTFT/queue-wait/
occupancy/KV series; /stats percentiles are ordered)."""

import numpy as np
import pytest


# -- recorder primitives ----------------------------------------------------


def test_histogram_observe_and_percentiles():
    from dstack_tpu.telemetry.recorder import (
        Histogram,
        percentiles_from_snapshot,
    )

    h = Histogram("lat", (0.1, 0.5, 1.0))
    for v in (0.05, 0.05, 0.3, 0.7, 2.0):
        h.observe(v)
    snap = h.snapshot()
    assert snap["count"] == 5
    assert snap["sum"] == pytest.approx(3.1)
    # cumulative: <=0.1 -> 2, <=0.5 -> 3, <=1.0 -> 4, +Inf -> 5
    assert snap["buckets"] == [[0.1, 2], [0.5, 3], [1.0, 4], ["+Inf", 5]]
    p = percentiles_from_snapshot(snap)
    assert 0 <= p["p50"] <= 0.5
    assert p["p50"] <= p["p95"] <= p["p99"]
    # +Inf bucket degrades to the last finite edge, never to infinity
    assert p["p99"] <= 1.0


def test_percentiles_empty_histogram_is_zero():
    from dstack_tpu.telemetry.recorder import (
        Histogram,
        percentiles_from_snapshot,
    )

    p = percentiles_from_snapshot(Histogram("x", (1.0,)).snapshot())
    assert p == {"p50": 0.0, "p95": 0.0, "p99": 0.0}


def test_merge_histogram_snapshots_sums_buckets():
    from dstack_tpu.telemetry.recorder import (
        Histogram,
        merge_histogram_snapshots,
        percentiles_from_snapshot,
    )

    a = Histogram("lat", (0.1, 1.0))
    b = Histogram("lat", (0.1, 1.0))
    for v in (0.05,) * 9:
        a.observe(v)
    b.observe(5.0)  # one slow outlier on the other replica
    merged = merge_histogram_snapshots([a.snapshot(), b.snapshot()])
    assert merged["count"] == 10
    assert merged["buckets"][-1] == ["+Inf", 10]
    p = percentiles_from_snapshot(merged)
    assert p["p50"] <= 0.1  # the fast replica dominates the median
    # mismatched bucket edges are skipped, not merged wrong
    c = Histogram("lat", (0.2, 2.0))
    c.observe(0.15)
    merged2 = merge_histogram_snapshots([a.snapshot(), c.snapshot()])
    assert merged2["count"] == 9
    assert merge_histogram_snapshots([]) is None


def test_recorder_registry_and_exposition_roundtrip():
    from dstack_tpu.server.telemetry.exposition import parse, render
    from dstack_tpu.telemetry.recorder import MetricsRecorder

    r = MetricsRecorder()
    r.counter("reqs_total", labels={"outcome": "stop"}).inc(3)
    r.counter("reqs_total", labels={"outcome": "length"}).inc()
    r.gauge("depth").set(7)
    r.histogram("lat", (0.5, 1.0)).observe(0.2)
    # get-or-create: same key returns the same metric
    assert r.counter("reqs_total", labels={"outcome": "stop"}).value == 3
    text = "\n".join(render(r.samples()))
    samples = parse(text, strict=True)  # strict: our own output is valid
    by_name = {}
    for s in samples:
        by_name.setdefault(s.name, []).append(s)
    assert {s.labels["outcome"] for s in by_name["reqs_total"]} == {
        "stop", "length"}
    assert by_name["depth"][0].value == 7
    assert by_name["lat_count"][0].value == 1
    inf = [s for s in by_name["lat_bucket"] if s.labels["le"] == "+Inf"]
    assert inf and inf[0].value == 1


# -- engine smoke (acceptance criterion) ------------------------------------


@pytest.fixture(scope="module")
def setup():
    import jax
    from dstack_tpu.models.llama import LlamaConfig, init_params

    cfg = LlamaConfig.tiny()
    params = init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


def _make_engine(cfg, params, **kw):
    from dstack_tpu.serving.engine import InferenceEngine
    from dstack_tpu.telemetry.serving import EngineTelemetry

    return InferenceEngine(cfg, params=params, batch_size=2, max_len=128,
                           telemetry=EngineTelemetry(), **kw)


async def test_engine_smoke_metrics_and_stats(setup):
    """≥2 requests through the engine; /metrics exposes nonzero
    ttft_seconds, queue-wait, batch-occupancy and KV-utilization series,
    and /stats reports consistent p50 <= p99."""
    from aiohttp.test_utils import TestClient, TestServer

    from dstack_tpu.serving.server import ServingApp
    from dstack_tpu.server.telemetry.exposition import parse

    cfg, params = setup
    engine = _make_engine(cfg, params)
    r1 = engine.generate([1, 2, 3], max_new_tokens=6)
    r2 = engine.generate([9, 8, 7, 6], max_new_tokens=5)
    assert len(r1.output) == 6 and len(r2.output) == 5

    class _Tok:  # the telemetry endpoints never touch the tokenizer
        eos_id = None

    app = ServingApp(engine, _Tok())
    client = TestClient(TestServer(app.make_app()))
    await client.start_server()
    try:
        resp = await client.get("/metrics")
        assert resp.status == 200
        text = await resp.text()
        samples = parse(text, strict=True)  # well-formed exposition
        values = {}
        for s in samples:
            key = s.name + ("" if "le" not in s.labels
                            else f'{{le={s.labels["le"]}}}')
            values[key] = s.value
        assert values["dstack_serving_ttft_seconds_count"] >= 2
        assert values["dstack_serving_queue_wait_seconds_count"] >= 2
        assert values["dstack_serving_batch_occupancy_count"] >= 2
        assert "dstack_serving_kv_utilization" in values
        assert values["dstack_serving_decode_tokens_total"] >= 9
        assert values["dstack_serving_prefill_tokens_total"] >= 7

        resp = await client.get("/stats")
        assert resp.status == 200
        stats = await resp.json()
        for name, p in stats["percentiles"].items():
            assert p["p50"] <= p["p95"] <= p["p99"], name
        assert stats["counters"][
            "dstack_serving_requests_total{outcome=length}"] == 2
        assert stats["histograms"]["dstack_serving_ttft_seconds"][
            "count"] >= 2
    finally:
        await client.close()


def test_queue_wait_and_finish_outcomes(setup):
    from dstack_tpu.serving.engine import Request

    cfg, params = setup
    engine = _make_engine(cfg, params)
    ref = engine.generate([1, 2, 3], max_new_tokens=10)
    eos = ref.output[3]
    req = engine.generate([1, 2, 3], max_new_tokens=10, eos_id=eos)
    assert req.finish_reason == "stop"
    tel = engine.telemetry
    assert tel.recorder.counter(
        "dstack_serving_requests_total", labels={"outcome": "stop"}
    ).value == 1
    # admission stamps survive on the request itself
    assert req.admitted_at is not None
    assert req.admitted_at >= req.submitted_at
    # cancelled-while-queued requests are accounted too
    done = engine.generate([5], max_new_tokens=2)
    assert done.done.is_set()
    r = Request(tokens=[1], max_new_tokens=2)
    r.cancel()
    engine.submit(r)
    while not r.done.is_set():
        engine.step()
    assert tel.recorder.counter(
        "dstack_serving_requests_total", labels={"outcome": "cancelled"}
    ).value >= 1


def test_paged_engine_kv_utilization_and_stall_preemption(setup):
    """Paged engine records KV-block utilization; an admission stall on an
    exhausted pool counts exactly one preemption per request."""
    from dstack_tpu.serving.engine import Request

    cfg, params = setup
    from dstack_tpu.serving.engine import InferenceEngine
    from dstack_tpu.telemetry.serving import EngineTelemetry

    engine = InferenceEngine(
        cfg, params=params, batch_size=2, max_len=128, paged=True,
        kv_block_size=32, total_kv_blocks=5, telemetry=EngineTelemetry())
    # 4 usable blocks; each request needs ceil((3+70+1)/32)=3 — the second
    # must stall until the first releases
    a = Request(tokens=[1, 2, 3], max_new_tokens=70)
    b = Request(tokens=[4, 5, 6], max_new_tokens=70)
    engine.submit(a)
    engine.submit(b)
    for _ in range(300):
        if a.done.is_set() and b.done.is_set():
            break
        engine.step()
    assert a.done.is_set() and b.done.is_set()
    tel = engine.telemetry
    assert tel.kv_utilization.value >= 0.0
    stalls = tel.recorder.counter(
        "dstack_serving_preemptions_total",
        labels={"reason": "kv_blocks_exhausted"}).value
    # with a 5-block pool one of the two must have waited, and the stall
    # is counted once per request no matter how many steps it lasted
    assert 1 <= stalls <= 2


def test_telemetry_disabled_is_free(setup):
    """telemetry=None: no recorder objects anywhere on the engine, no
    admission stamps recorded via telemetry, identical outputs."""
    from dstack_tpu.serving.engine import InferenceEngine

    cfg, params = setup
    eng = InferenceEngine(cfg, params=params, batch_size=1, max_len=64)
    assert eng.telemetry is None
    want = eng.generate([3, 1, 4], max_new_tokens=5).output
    eng2 = _make_engine(cfg, params)
    got = eng2.generate([3, 1, 4], max_new_tokens=5).output
    assert want == got  # recording never perturbs generation


async def test_stats_endpoint_with_telemetry_disabled(setup):
    from aiohttp.test_utils import TestClient, TestServer

    from dstack_tpu.serving.engine import InferenceEngine
    from dstack_tpu.serving.server import ServingApp

    cfg, params = setup
    engine = InferenceEngine(cfg, params=params, batch_size=1, max_len=64)

    class _Tok:
        eos_id = None

    app = ServingApp(engine, _Tok())
    client = TestClient(TestServer(app.make_app()))
    await client.start_server()
    try:
        resp = await client.get("/metrics")
        assert resp.status == 200
        assert (await resp.text()).strip() == ""
        resp = await client.get("/stats")
        assert resp.status == 200
        data = await resp.json()
        assert "percentiles" not in data  # no recorder, no summary
    finally:
        await client.close()


def test_make_engine_telemetry_env_gate():
    from dstack_tpu.telemetry.serving import make_engine_telemetry

    assert make_engine_telemetry({"DSTACK_TPU_SERVING_TELEMETRY": "0"}) \
        is None
    assert make_engine_telemetry({"DSTACK_TPU_SERVING_TELEMETRY": "off"}) \
        is None
    assert make_engine_telemetry({}) is not None


# -- /load + the X-Dstack-Load-* piggyback (gateway routing input) ----------


async def test_load_endpoint_and_header_piggyback(setup):
    """/load serves the O(1) gauge snapshot and every response carries
    the same numbers as X-Dstack-Load-* headers (the gateway's passive
    load feed)."""
    from aiohttp.test_utils import TestClient, TestServer

    from dstack_tpu.serving.server import ServingApp
    from dstack_tpu.telemetry.serving import parse_load_headers

    cfg, params = setup
    engine = _make_engine(cfg, params)
    engine.generate([1, 2, 3], max_new_tokens=4)

    class _Tok:
        eos_id = None

    app = ServingApp(engine, _Tok())
    client = TestClient(TestServer(app.make_app()))
    await client.start_server()
    try:
        resp = await client.get("/load")
        assert resp.status == 200
        load = await resp.json()
        assert load["capacity_slots"] == engine.batch_size == 2
        assert load["active_slots"] >= 0 and load["queue_depth"] == 0
        assert 0.0 <= load["kv_utilization"] <= 1.0
        assert load["prefill_backlog_tokens"] == 0
        assert load["load"] >= 0.0
        # the piggyback rides ordinary responses with identical values
        resp = await client.get("/health")
        snap = parse_load_headers(resp.headers)
        assert snap is not None
        for field in ("active_slots", "queue_depth",
                      "prefill_backlog_tokens", "capacity_slots"):
            assert snap[field] == load[field], field
    finally:
        await client.close()


async def test_load_endpoint_respects_telemetry_gate(setup):
    """Telemetry disabled -> /load 404s and no load headers are attached
    (the gateway then treats the replica as signal-less, like any
    non-dstack model server)."""
    from aiohttp.test_utils import TestClient, TestServer

    from dstack_tpu.serving.engine import InferenceEngine
    from dstack_tpu.serving.server import ServingApp
    from dstack_tpu.telemetry.serving import parse_load_headers

    cfg, params = setup
    engine = InferenceEngine(cfg, params=params, batch_size=1, max_len=64)
    assert engine.telemetry is None

    class _Tok:
        eos_id = None

    app = ServingApp(engine, _Tok())
    client = TestClient(TestServer(app.make_app()))
    await client.start_server()
    try:
        resp = await client.get("/load")
        assert resp.status == 404
        resp = await client.get("/health")
        assert resp.status == 200
        assert parse_load_headers(resp.headers) is None
    finally:
        await client.close()


def test_chunked_prefill_backlog_gauge(setup):
    """A long prompt admitted under prefill chunking raises the backlog
    gauge while chunks remain and drains it to zero at completion."""
    from dstack_tpu.serving.engine import Request

    cfg, params = setup
    engine = _make_engine(cfg, params, prefill_chunk=8)
    req = Request(tokens=list(range(1, 33)), max_new_tokens=3)
    engine.submit(req)
    tel = engine.telemetry
    peak = 0
    for _ in range(200):
        if req.done.is_set():
            break
        engine.step()
        peak = max(peak, int(tel.prefill_backlog.value))
    assert req.done.is_set()
    # 32-token prompt, 8-token chunks, a budget of two chunks a step: the
    # remaining backlog is visible after the first step (16, then 0)
    assert peak >= 8, peak
    assert tel.prefill_backlog.value == 0
    snap = tel.load_snapshot()
    assert snap["prefill_backlog_tokens"] == 0
    assert set(snap) == {"active_slots", "queue_depth", "kv_utilization",
                         "prefill_backlog_tokens"}


@pytest.mark.parametrize("prompts,chunk,chunks,steps,exhausted", [
    ([30], 16, 2, 1, 0),        # inside the budget of batch_size = 2
    ([20, 20], 16, 4, 2, 1),    # the younger prompt waits a step
    ([40], 8, 5, 3, 2),         # one prompt over the budget, twice cut
], ids=["inside-budget", "two-prompts", "over-budget"])
def test_prefill_chunk_counters(setup, prompts, chunk, chunks, steps,
                                exhausted):
    """``prefill_chunks_total`` over ``prefill_chunk_steps_total`` is the
    chunks a scheduling step dispatched, and
    ``prefill_budget_exhausted_total`` counts the steps whose budget
    (``batch_size`` chunks) ran out with a chunk still waiting."""
    from dstack_tpu.serving.engine import Request

    cfg, params = setup
    engine = _make_engine(cfg, params, prefill_chunk=chunk)
    reqs = [engine.submit(Request(tokens=list(range(1, n + 1)),
                                  max_new_tokens=3)) for n in prompts]
    for _ in range(100):
        if all(r.done.is_set() for r in reqs):
            break
        engine.step()
    assert all(r.finish_reason == "length" for r in reqs)
    tel = engine.telemetry
    assert (tel.prefill_chunks.value, tel.prefill_chunk_steps.value,
            tel.prefill_budget_exhausted.value) == (chunks, steps, exhausted)
    counters = tel.recorder.summary()["counters"]
    assert counters["dstack_serving_prefill_chunks_total"] == chunks
    assert counters["dstack_serving_prefill_chunk_steps_total"] == steps
    assert counters["dstack_serving_prefill_budget_exhausted_total"] == \
        exhausted
