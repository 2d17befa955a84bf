"""Request tracing (telemetry/tracing.py): traceparent parsing, the span
ring + tail sampler, histogram exemplars, engine span derivation, the
serving server's /traces endpoints + trace middleware, and the sim-based
overhead pin (<2% on the p95 TTFT proxy)."""

import threading

import pytest


# -- W3C traceparent ---------------------------------------------------------


def test_traceparent_roundtrip_and_malformed():
    from dstack_tpu.telemetry.tracing import (
        format_traceparent,
        new_span_id,
        new_trace_id,
        parse_traceparent,
    )

    tid, sid = new_trace_id(), new_span_id()
    assert len(tid) == 32 and len(sid) == 16
    assert parse_traceparent(format_traceparent(tid, sid)) == (tid, sid)
    # forward-compatible: future versions with extra fields still parse
    assert parse_traceparent(f"01-{tid}-{sid}-01-extra") == (tid, sid)
    for bad in (None, "", "garbage", "00-short-short-01",
                f"ff-{tid}-{sid}-01",            # version ff is invalid
                f"00-{'0' * 32}-{sid}-01",       # all-zero trace id
                f"00-{tid}-{'0' * 16}-01",       # all-zero span id
                f"00-{'g' * 32}-{sid}-01"):      # non-hex
        assert parse_traceparent(bad) is None, bad


# -- tracer / sampler --------------------------------------------------------


def test_span_ring_and_trace_query():
    from dstack_tpu.telemetry.tracing import RequestTracer

    t = RequestTracer(ring_size=8)
    with t.start_span("root", attrs={"k": "v"}) as root:
        tid = root.trace_id
        child = t.record_span("child", tid, start=1.0, end=1.5,
                              parent_id=root.span_id)
    spans = t.trace(tid)
    assert [s["name"] for s in spans] == ["child", "root"]  # start-ordered
    assert spans[0]["parent_id"] == root.span_id
    assert spans[0]["duration"] == pytest.approx(0.5)
    assert spans[1]["attrs"] == {"k": "v"}
    assert child["span_id"] != root.span_id
    # the ring is bounded: old spans rotate out
    for _ in range(20):
        t.record_span("noise", "f" * 32, start=0.0, end=0.1)
    assert len(t.summary()["traces"]) <= 8
    assert t.trace(tid) == []  # rotated out, never retained


def test_span_end_is_idempotent_and_exit_marks_error():
    from dstack_tpu.telemetry.tracing import RequestTracer

    t = RequestTracer()
    s = t.start_span("x")
    s.end()
    s.end()
    with s:  # a with-exit after explicit end must not double-record
        pass
    assert len(t.trace(s.trace_id)) == 1
    try:
        with t.start_span("boom") as s2:
            raise RuntimeError("nope")
    except RuntimeError:
        pass
    assert t.trace(s2.trace_id)[0]["status"] == "error"


def test_tail_sampler_always_keeps_errors_and_slowest():
    from dstack_tpu.telemetry.tracing import TailSampler

    s = TailSampler(sample_rate=0.0, slowest_k=2)
    # errors always kept, regardless of rate/duration
    assert s.decide("a" * 32, 0.001, error=True) == "error"
    assert s.decide("0" * 32, 0.010) == "slow"   # heap warming
    assert s.decide("0" * 32, 0.020) == "slow"
    assert s.decide("0" * 32, 0.001) is None     # below the slow set
    assert s.decide("0" * 32, 0.500) == "slow"   # new tail maximum
    # rate=0, not slow, not error -> dropped
    assert s.decide("f" * 32, 0.001) is None
    # deterministic sampling: same id, same decision
    s2 = TailSampler(sample_rate=0.5, slowest_k=0)
    decisions = {s2.decide("00" + "a" * 30, 0.0),
                 s2.decide("00" + "a" * 30, 0.0)}
    assert len(decisions) == 1


def test_finish_trace_retains_and_upgrades_to_error():
    from dstack_tpu.telemetry.tracing import RequestTracer, TailSampler

    t = RequestTracer(ring_size=4, sampler=TailSampler(sample_rate=0.0,
                                                       slowest_k=1))
    with t.start_span("a") as sp:
        tid = sp.trace_id
    assert t.finish_trace(tid, 0.5) == "slow"
    # spans survive ring rotation once retained
    for _ in range(10):
        t.record_span("noise", "f" * 32, start=0.0, end=0.1)
    assert [s["name"] for s in t.trace(tid)] == ["a"]
    # late spans (e.g. the gateway root, which ends after the replica's
    # finish_trace ran) still join the retained trace
    t.record_span("late", tid, start=0.0, end=0.2)
    assert {s["name"] for s in t.trace(tid)} == {"a", "late"}
    # a later error finish upgrades the retention reason
    assert t.finish_trace(tid, 0.5, error=True) == "error"
    summary = t.summary()
    entry = [e for e in summary["traces"] if e["trace_id"] == tid][0]
    assert entry["retained"] == "error"
    assert summary["retained_traces"] == 1


def test_make_tracer_env_gate():
    from dstack_tpu.telemetry.tracing import make_tracer

    assert make_tracer({"DSTACK_TPU_TRACING": "0"}) is None
    assert make_tracer({"DSTACK_TPU_TRACING": "off"}) is None
    assert make_tracer({}) is not None


# -- exemplars ---------------------------------------------------------------


def test_histogram_exemplars_render_openmetrics_only():
    from dstack_tpu.server.telemetry.exposition import parse, render
    from dstack_tpu.telemetry.recorder import Histogram

    h = Histogram("lat_seconds", (0.1, 1.0))
    h.observe(0.05)                          # no exemplar
    h.observe(0.5, exemplar="ab" * 16)
    classic = "\n".join(render(h.samples()))
    assert " # " not in classic
    parse(classic, strict=True)
    om = "\n".join(render(h.samples(), openmetrics=True))
    assert ' # {trace_id="' + "ab" * 16 + '"}' in om
    samples = parse(om, strict=True)
    with_ex = [s for s in samples if s.exemplar is not None]
    assert len(with_ex) == 1
    assert with_ex[0].labels["le"] == "1"
    assert with_ex[0].exemplar["labels"] == {"trace_id": "ab" * 16}
    assert with_ex[0].exemplar["value"] == pytest.approx(0.5)
    assert with_ex[0].exemplar["timestamp"] is not None


def test_exposition_rejects_malformed_exemplar():
    from dstack_tpu.server.telemetry.exposition import (
        ExpositionError,
        parse,
    )

    for bad in ('m_bucket{le="1"} 3 # notlabels 0.5',
                'm_bucket{le="1"} 3 # {trace_id="x"}',
                'm_bucket{le="1"} 3 # {trace_id="x"} 0.5 1.0 extra'):
        with pytest.raises(ExpositionError):
            parse(bad, strict=True)
        assert parse(bad, strict=False) == []  # lenient scrape skips


# -- engine span derivation --------------------------------------------------


@pytest.fixture(scope="module")
def setup():
    import jax

    from dstack_tpu.models.llama import LlamaConfig, init_params

    cfg = LlamaConfig.tiny()
    params = init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


def _traced_engine(cfg, params, **kw):
    from dstack_tpu.serving.engine import InferenceEngine
    from dstack_tpu.telemetry.serving import EngineTelemetry
    from dstack_tpu.telemetry.tracing import RequestTracer

    return InferenceEngine(
        cfg, params=params, batch_size=2, max_len=128,
        telemetry=EngineTelemetry(tracer=RequestTracer()), **kw)


def test_engine_records_request_spans(setup):
    from dstack_tpu.telemetry.tracing import new_trace_id

    cfg, params = setup
    engine = _traced_engine(cfg, params)
    tid = new_trace_id()
    req = engine.generate([1, 2, 3], max_new_tokens=5)  # untraced: no spans
    assert engine.telemetry.tracer.trace(getattr(req, "trace_id", "") or
                                         "0" * 32) == []
    from dstack_tpu.serving.engine import Request

    req = Request(tokens=[4, 5, 6], max_new_tokens=5, trace_id=tid,
                  parent_span_id="ab" * 8)
    engine.submit(req)
    while not req.done.is_set():
        engine.step()
    spans = engine.telemetry.tracer.trace(tid)
    by_name = {s["name"]: s for s in spans}
    assert {"engine.request", "engine.queue_wait", "engine.prefill",
            "engine.decode"} <= set(by_name)
    root = by_name["engine.request"]
    assert root["parent_id"] == "ab" * 8
    for child in ("engine.queue_wait", "engine.prefill", "engine.decode"):
        assert by_name[child]["parent_id"] == root["span_id"]
        assert by_name[child]["trace_id"] == tid
    assert by_name["engine.decode"]["attrs"]["tokens_out"] == 5
    assert by_name["engine.prefill"]["attrs"]["prompt_tokens"] == 3
    # exemplars: the TTFT histogram bucket points at this trace
    exemplars = [e for e in engine.telemetry.ttft.exemplars if e]
    assert any(e[0] == tid for e in exemplars)


def test_engine_kv_stall_span(setup):
    from dstack_tpu.serving.engine import InferenceEngine, Request
    from dstack_tpu.telemetry.serving import EngineTelemetry
    from dstack_tpu.telemetry.tracing import RequestTracer, new_trace_id

    cfg, params = setup
    engine = InferenceEngine(
        cfg, params=params, batch_size=2, max_len=128, paged=True,
        kv_block_size=32, total_kv_blocks=5,
        telemetry=EngineTelemetry(tracer=RequestTracer()))
    a = Request(tokens=[1, 2, 3], max_new_tokens=70,
                trace_id=new_trace_id())
    b = Request(tokens=[4, 5, 6], max_new_tokens=70,
                trace_id=new_trace_id())
    engine.submit(a)
    engine.submit(b)
    for _ in range(300):
        if a.done.is_set() and b.done.is_set():
            break
        engine.step()
    assert a.done.is_set() and b.done.is_set()
    stalled = [r for r in (a, b) if getattr(r, "_kv_stalled_at", None)]
    assert stalled, "one of the two must have stalled on the 5-block pool"
    spans = engine.telemetry.tracer.trace(stalled[0].trace_id)
    kv = [s for s in spans if s["name"] == "engine.kv_wait"]
    assert kv and kv[0]["attrs"]["reason"] == "kv_blocks_exhausted"
    assert kv[0]["duration"] >= 0.0


def test_tracing_off_requests_have_no_spans(setup):
    """telemetry on, tracer off: requests record aggregates only and the
    hot path's extra cost is the single tracer `is None` check."""
    from dstack_tpu.serving.engine import InferenceEngine, Request
    from dstack_tpu.telemetry.serving import EngineTelemetry
    from dstack_tpu.telemetry.tracing import new_trace_id

    cfg, params = setup
    engine = InferenceEngine(cfg, params=params, batch_size=1, max_len=64,
                             telemetry=EngineTelemetry(tracer=None))
    req = Request(tokens=[1, 2, 3], max_new_tokens=4,
                  trace_id=new_trace_id())
    engine.submit(req)
    while not req.done.is_set():
        engine.step()
    assert engine.telemetry.ttft.count == 1  # aggregates still record
    # exemplar DID attach (trace id was present) — but no span ring exists
    assert engine.telemetry.tracer is None


# -- serving server: middleware + /traces ------------------------------------


class _Tok:
    eos_id = None

    def encode(self, text):
        return [ord(c) % 250 + 1 for c in text][:16] or [1]

    def decode(self, ids):
        return "".join(chr(96 + (i % 26)) for i in ids)

    def apply_chat_template(self, messages):
        return " ".join(m.get("content", "") for m in messages)


async def _serving_client(engine):
    from aiohttp.test_utils import TestClient, TestServer

    from dstack_tpu.serving.server import ServingApp

    app = ServingApp(engine, _Tok())
    client = TestClient(TestServer(app.make_app()))
    await client.start_server()
    return client, app


async def test_server_traces_endpoints_and_header(setup):
    from dstack_tpu.telemetry.tracing import (
        TRACE_ID_HEADER,
        format_traceparent,
        new_span_id,
        new_trace_id,
    )

    cfg, params = setup
    engine = _traced_engine(cfg, params)
    client, app = await _serving_client(engine)
    worker = threading.Thread(target=engine.run_forever, daemon=True)
    worker.start()
    try:
        tid, sid = new_trace_id(), new_span_id()
        resp = await client.post(
            "/v1/completions",
            json={"prompt": "hi", "max_tokens": 4},
            headers={"traceparent": format_traceparent(tid, sid)})
        assert resp.status == 200
        # the replica advertises the trace id (internal header; proxies
        # strip it from client responses)
        assert resp.headers[TRACE_ID_HEADER] == tid
        engine.stop()
        worker.join(timeout=10)
        resp = await client.get(f"/traces/{tid}")
        assert resp.status == 200
        data = await resp.json()
        names = {s["name"] for s in data["spans"]}
        assert {"replica.request", "engine.request", "engine.queue_wait",
                "engine.prefill", "engine.decode"} <= names
        by_name = {s["name"]: s for s in data["spans"]}
        # the inbound traceparent is the HTTP span's parent; the engine
        # root parents to the HTTP span
        assert by_name["replica.request"]["parent_id"] == sid
        assert by_name["engine.request"]["parent_id"] == \
            by_name["replica.request"]["span_id"]
        resp = await client.get("/traces")
        listing = await resp.json()
        assert any(e["trace_id"] == tid for e in listing["traces"])
        # streaming responses carry the header too (set pre-prepare)
        resp = await client.get("/traces/" + "0" * 32)
        assert resp.status == 404
    finally:
        engine.stop()
        worker.join(timeout=10)
        assert not worker.is_alive()
        await client.close()


async def test_server_traces_404_when_tracing_off(setup):
    from dstack_tpu.serving.engine import InferenceEngine
    from dstack_tpu.telemetry.serving import EngineTelemetry
    from dstack_tpu.telemetry.tracing import TRACE_ID_HEADER

    cfg, params = setup
    engine = InferenceEngine(cfg, params=params, batch_size=1, max_len=64,
                             telemetry=EngineTelemetry(tracer=None))
    client, app = await _serving_client(engine)
    try:
        assert app.tracer is None
        resp = await client.get("/traces")
        assert resp.status == 404
        resp = await client.get("/v1/models")
        assert TRACE_ID_HEADER not in resp.headers
    finally:
        await client.close()


async def test_stream_carries_trace_header_and_completes_span(setup):
    from dstack_tpu.telemetry.tracing import TRACE_ID_HEADER

    cfg, params = setup
    engine = _traced_engine(cfg, params)
    client, app = await _serving_client(engine)
    worker = threading.Thread(target=engine.run_forever, daemon=True)
    worker.start()
    try:
        resp = await client.post(
            "/v1/completions",
            json={"prompt": "hello", "max_tokens": 4, "stream": True})
        assert resp.status == 200
        tid = resp.headers.get(TRACE_ID_HEADER)
        assert tid, "SSE response must carry the trace id header"
        body = await resp.text()
        assert "[DONE]" in body
        engine.stop()
        worker.join(timeout=10)
        spans = app.tracer.trace(tid)
        http = [s for s in spans if s["name"] == "replica.request"]
        assert http, spans
        # the HTTP span closed AFTER the stream drained: it covers the
        # engine decode span entirely (submit -> stream-complete)
        decode = [s for s in spans if s["name"] == "engine.decode"]
        assert decode
        assert (http[0]["start"] + http[0]["duration"]
                >= decode[0]["start"] + decode[0]["duration"] - 1e-6)
    finally:
        engine.stop()
        worker.join(timeout=10)
        assert not worker.is_alive()
        await client.close()


# -- overhead pin ------------------------------------------------------------


def test_sim_tracing_overhead_under_two_percent():
    """The acceptance pin: real span recording charged into the routing
    sim's service times moves the p95 TTFT proxy by < 2%."""
    from dstack_tpu.gateway.routing_sim import tracing_overhead

    ov = tracing_overhead(n_requests=1200)
    assert ov["p95_ttft_ms_off"] > 0
    assert abs(ov["p95_ttft_overhead_pct"]) < 2.0, ov
    assert ov["span_us_per_request"] < 2000, ov  # sanity: µs, not ms
    assert ov["retained_traces"] > 0  # the sampler actually retained
