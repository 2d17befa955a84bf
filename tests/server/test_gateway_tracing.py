"""End-to-end request tracing across the data plane: gateway traceparent
mint/propagation, internal X-Dstack-Trace-* header hygiene on every proxy
leg, failover-retry trace continuity, PD two-phase cross-replica
continuity, 429 tail retention, /api/traces stitching, and the server's
/traces/get persistence + CLI span tree."""

import asyncio

from aiohttp import web
from aiohttp.test_utils import TestClient, TestServer

from dstack_tpu.gateway.app import TRACING_KEY, create_gateway_app
from dstack_tpu.gateway.routing import AdmissionController
from dstack_tpu.telemetry.tracing import (
    TRACE_HEADER_PREFIX,
    TRACE_ID_HEADER,
    format_traceparent,
    new_span_id,
    new_trace_id,
    parse_traceparent,
)

TOKEN = "gw-test-token"


def auth():
    return {"Authorization": f"Bearer {TOKEN}"}


async def _start_replica(handler):
    app = web.Application()
    app.router.add_route("*", "/{tail:.*}", handler)
    client = TestClient(TestServer(app))
    await client.start_server()
    return client, f"http://127.0.0.1:{client.server.port}"


async def _register(gw, project, run, replicas):
    r = await gw.post("/api/registry/register",
                      json={"project": project, "run_name": run},
                      headers=auth())
    assert r.status == 200
    for job_id, url, role in replicas:
        r = await gw.post(
            "/api/registry/replica/add",
            json={"project": project, "run_name": run, "job_id": job_id,
                  "url": url, "role": role},
            headers=auth())
        assert r.status == 200


async def _gateway(tmp_path, **kw):
    gw_app = create_gateway_app(TOKEN, state_dir=tmp_path, **kw)
    gw = TestClient(TestServer(gw_app))
    await gw.start_server()
    return gw, gw_app


# -- traceparent mint / preserve / strip ------------------------------------


async def test_gateway_mints_traceparent_and_strips_trace_headers(tmp_path):
    """No inbound traceparent -> the gateway mints a valid one for the
    upstream leg; the replica's internal X-Dstack-Trace-* response
    headers never reach the client (like X-Dstack-Load-*)."""
    seen = {}

    async def handler(request):
        seen["traceparent"] = request.headers.get("traceparent")
        return web.json_response(
            {"ok": True},
            headers={TRACE_ID_HEADER: "deadbeef" * 4,
                     "X-Custom": "stays"})

    rep, url = await _start_replica(handler)
    gw, gw_app = await _gateway(tmp_path)
    try:
        await _register(gw, "main", "svc", [("j1", url, "any")])
        r = await gw.get("/services/main/svc/ping")
        assert r.status == 200
        ctx = parse_traceparent(seen["traceparent"])
        assert ctx is not None, seen
        # stripped from the client response; ordinary headers survive
        assert not any(k.lower().startswith(TRACE_HEADER_PREFIX.lower())
                       for k in r.headers)
        assert r.headers["X-Custom"] == "stays"
        # the gateway recorded the request + upstream spans in that trace
        tracer = gw_app[TRACING_KEY]
        names = {s["name"] for s in tracer.trace(ctx[0])}
        assert {"gateway.request", "gateway.admission",
                "gateway.upstream"} <= names
    finally:
        await gw.close()
        await rep.close()


async def test_gateway_preserves_inbound_traceparent(tmp_path):
    """An inbound traceparent is CONTINUED: same trace id upstream, new
    (gateway-owned) parent span id."""
    seen = {}

    async def handler(request):
        seen["traceparent"] = request.headers.get("traceparent")
        return web.json_response({"ok": True})

    rep, url = await _start_replica(handler)
    gw, gw_app = await _gateway(tmp_path)
    try:
        await _register(gw, "main", "svc", [("j1", url, "any")])
        tid, sid = new_trace_id(), new_span_id()
        r = await gw.get("/services/main/svc/ping",
                         headers={"traceparent":
                                  format_traceparent(tid, sid)})
        assert r.status == 200
        up_tid, up_sid = parse_traceparent(seen["traceparent"])
        assert up_tid == tid
        assert up_sid != sid  # the gateway's own span, not the client's
        root = [s for s in gw_app[TRACING_KEY].trace(tid)
                if s["name"] == "gateway.request"][0]
        assert root["parent_id"] == sid
    finally:
        await gw.close()
        await rep.close()


async def test_tracing_disabled_forwards_client_traceparent_verbatim(
        tmp_path, monkeypatch):
    monkeypatch.setenv("DSTACK_TPU_TRACING", "0")
    seen = {}

    async def handler(request):
        seen["traceparent"] = request.headers.get("traceparent")
        return web.json_response({"ok": True})

    rep, url = await _start_replica(handler)
    gw, gw_app = await _gateway(tmp_path)
    try:
        assert gw_app[TRACING_KEY] is None
        await _register(gw, "main", "svc", [("j1", url, "any")])
        header = format_traceparent(new_trace_id(), new_span_id())
        r = await gw.get("/services/main/svc/ping",
                         headers={"traceparent": header})
        assert r.status == 200
        assert seen["traceparent"] == header  # untouched pass-through
        r = await gw.get("/api/traces", headers=auth())
        assert r.status == 404  # tracing off, same contract as /load
    finally:
        await gw.close()
        await rep.close()


# -- failover continuity (satellite) ----------------------------------------


async def test_failover_retry_continues_same_trace_new_span(tmp_path):
    """The retry after a dead replica must CONTINUE the client's trace
    (same trace id, fresh attempt span) — never mint a new one — and the
    failover trace is always tail-retained."""
    seen = {}

    async def handler(request):
        seen["traceparent"] = request.headers.get("traceparent")
        return web.json_response({"ok": True})

    live, live_url = await _start_replica(handler)
    gw, gw_app = await _gateway(tmp_path)
    try:
        await _register(gw, "main", "svc",
                        [("dead", "http://127.0.0.1:1", "any"),
                         ("live", live_url, "any")])
        tid, sid = new_trace_id(), new_span_id()
        for i in range(3):  # every rotation position fails over
            r = await gw.post(
                "/services/main/svc/v1/completions",
                json={"prompt": f"p{i}"},
                headers={"traceparent": format_traceparent(tid, sid)})
            assert r.status == 200
            up_tid, _ = parse_traceparent(seen["traceparent"])
            assert up_tid == tid  # retry continued the SAME trace
        tracer = gw_app[TRACING_KEY]
        spans = tracer.trace(tid)
        attempts = [s for s in spans if s["name"] == "gateway.upstream"]
        failed = [s for s in attempts if s["status"] == "error"]
        ok = [s for s in attempts if s["status"] == "ok"]
        assert failed and ok, attempts
        assert len({s["span_id"] for s in attempts}) == len(attempts)
        # at least one round hit the dead replica first -> failover flag
        roots = [s for s in spans if s["name"] == "gateway.request"]
        assert any(s["attrs"].get("failover") for s in roots), roots
        # failover traces are always retained by the tail sampler
        summary = tracer.summary()
        entry = [e for e in summary["traces"] if e["trace_id"] == tid][0]
        assert entry["retained"] == "error"
    finally:
        await gw.close()
        await live.close()


async def test_429_trace_is_always_retained(tmp_path):
    """Admission-queue rejection (429) marks the trace error-retained —
    the tail sampler must never drop a shed request."""
    release = asyncio.Event()

    async def slow_handler(request):
        await release.wait()
        return web.json_response({"ok": True})

    rep, url = await _start_replica(slow_handler)
    gw, gw_app = await _gateway(
        tmp_path,
        admission=AdmissionController(max_inflight_per_replica=1,
                                      max_queue=1, deadline_s=0.3))
    from dstack_tpu.gateway import app as app_mod
    old_default = app_mod.DEFAULT_SLOTS_PER_REPLICA
    app_mod.DEFAULT_SLOTS_PER_REPLICA = 1
    try:
        await _register(gw, "main", "svc", [("j1", url, "any")])
        first = asyncio.ensure_future(gw.get("/services/main/svc/gen"))
        await asyncio.sleep(0.05)
        second = asyncio.ensure_future(gw.get("/services/main/svc/gen"))
        await asyncio.sleep(0.05)
        tid = new_trace_id()
        r = await asyncio.wait_for(
            gw.get("/services/main/svc/gen",
                   headers={"traceparent":
                            format_traceparent(tid, new_span_id())}), 5)
        assert r.status == 429
        tracer = gw_app[TRACING_KEY]
        spans = tracer.trace(tid)
        adm = [s for s in spans if s["name"] == "gateway.admission"]
        assert adm and adm[0]["status"] == "error"
        assert adm[0]["attrs"].get("saturated") is True
        entry = [e for e in tracer.summary()["traces"]
                 if e["trace_id"] == tid][0]
        assert entry["retained"] == "error"
        await asyncio.wait_for(second, 5)
        release.set()
        await asyncio.wait_for(first, 5)
    finally:
        app_mod.DEFAULT_SLOTS_PER_REPLICA = old_default
        await gw.close()
        await rep.close()


# -- PD two-phase continuity (satellite) ------------------------------------


async def test_pd_two_phase_trace_continuity(tmp_path):
    """The prefill replica and the decode replica must see the SAME trace
    id with DIFFERENT parent span ids — each leg parents to its own
    gateway-side span (gateway.pd_prefill / gateway.pd_decode), both
    children of the gateway root."""
    seen = {}

    def make(name):
        async def handler(request):
            seen[name] = request.headers.get("traceparent")
            if request.headers.get("X-DStack-Router-Phase") == "prefill":
                return web.json_response({"object": "prefill_result",
                                          "first_token": 7, "length": 3})
            return web.json_response(
                {"ok": name},
                headers={TRACE_ID_HEADER: "feedface" * 4})
        return handler

    prefill, p_url = await _start_replica(make("prefill"))
    decode, d_url = await _start_replica(make("decode"))
    gw, gw_app = await _gateway(tmp_path)
    try:
        await _register(gw, "main", "svc",
                        [("p0", p_url, "prefill"), ("d0", d_url, "decode")])
        tid = new_trace_id()
        r = await gw.post(
            "/services/main/svc/v1/completions",
            json={"prompt": "shared"},
            headers={"traceparent": format_traceparent(tid,
                                                       new_span_id())})
        assert r.status == 200
        # the PD relay leg strips internal trace headers too
        assert not any(k.lower().startswith(TRACE_HEADER_PREFIX.lower())
                       for k in r.headers)
        p_tid, p_parent = parse_traceparent(seen["prefill"])
        d_tid, d_parent = parse_traceparent(seen["decode"])
        assert p_tid == d_tid == tid      # one trace across both replicas
        assert p_parent != d_parent       # each leg has its own span
        spans = {s["span_id"]: s for s in gw_app[TRACING_KEY].trace(tid)}
        assert spans[p_parent]["name"] == "gateway.pd_prefill"
        assert spans[d_parent]["name"] == "gateway.pd_decode"
        root_id = spans[p_parent]["parent_id"]
        assert spans[root_id]["name"] == "gateway.request"
        assert spans[d_parent]["parent_id"] == root_id
    finally:
        await gw.close()
        await prefill.close()
        await decode.close()


# -- /api/traces stitching ---------------------------------------------------


async def test_api_traces_stitches_replica_spans(tmp_path):
    """GET /api/traces?trace_id= merges the gateway's spans with every
    replica's /traces/{id} payload into one start-ordered timeline."""
    async def handler(request):
        tail = request.path
        if tail.startswith("/traces/"):
            tid = tail.rsplit("/", 1)[1]
            if tid in store:
                return web.json_response({"trace_id": tid,
                                          "spans": store[tid]})
            return web.json_response({"detail": "unknown"}, status=404)
        tp = request.headers.get("traceparent")
        tid, parent = parse_traceparent(tp)
        store[tid] = [{
            "trace_id": tid, "span_id": "ab" * 8, "parent_id": parent,
            "name": "engine.request", "start": 0.0, "duration": 0.5,
            "status": "ok", "attrs": {},
        }]
        return web.json_response({"ok": True})

    store = {}
    rep, url = await _start_replica(handler)
    gw, gw_app = await _gateway(tmp_path)
    try:
        await _register(gw, "main", "svc", [("j1", url, "any")])
        tid = new_trace_id()
        r = await gw.get("/services/main/svc/gen",
                         headers={"traceparent":
                                  format_traceparent(tid, new_span_id())})
        assert r.status == 200
        r = await gw.get(f"/api/traces?trace_id={tid}", headers=auth())
        assert r.status == 200
        data = await r.json()
        names = {s["name"] for s in data["spans"]}
        assert {"gateway.request", "gateway.upstream",
                "engine.request"} <= names
        assert data["replicas_reporting"] == 1
        # listing without a trace_id: summary shape
        r = await gw.get("/api/traces", headers=auth())
        listing = await r.json()
        assert any(e["trace_id"] == tid for e in listing["traces"])
        r = await gw.get("/api/traces?trace_id=" + "0" * 32,
                         headers=auth())
        assert r.status == 404
    finally:
        await gw.close()
        await rep.close()


# -- live gateway + real replica (acceptance) --------------------------------


async def test_live_gateway_replica_trace_has_full_span_set(tmp_path):
    """The acceptance pin: one request through a REAL gateway + serving
    replica (tiny engine) yields one trace id whose stitched
    /api/traces view carries the full span set — gateway leg, admission,
    queue wait, prefill, decode, and the replica's stream-complete HTTP
    span (>= 6 spans)."""
    import threading

    import jax

    from dstack_tpu.models.llama import LlamaConfig, init_params
    from dstack_tpu.serving.engine import InferenceEngine
    from dstack_tpu.serving.server import ServingApp
    from dstack_tpu.telemetry.serving import EngineTelemetry
    from dstack_tpu.telemetry.tracing import RequestTracer

    cfg = LlamaConfig.tiny()
    params = init_params(jax.random.PRNGKey(0), cfg)
    engine = InferenceEngine(
        cfg, params=params, batch_size=2, max_len=128,
        telemetry=EngineTelemetry(tracer=RequestTracer()))

    class _Tok:
        eos_id = None

        def encode(self, text):
            return [ord(c) % 250 + 1 for c in text][:16] or [1]

        def decode(self, ids):
            return "".join(chr(97 + (i % 26)) for i in ids)

        def apply_chat_template(self, messages):
            return " ".join(m.get("content", "") for m in messages)

    serving = ServingApp(engine, _Tok())
    replica = TestClient(TestServer(serving.make_app()))
    await replica.start_server()
    replica_url = f"http://127.0.0.1:{replica.server.port}"
    worker = threading.Thread(target=engine.run_forever, daemon=True)
    worker.start()
    gw, gw_app = await _gateway(tmp_path)
    try:
        await _register(gw, "main", "svc", [("j1", replica_url, "any")])
        r = await gw.post("/services/main/svc/v1/completions",
                          json={"prompt": "hello world", "max_tokens": 4})
        assert r.status == 200, await r.text()
        # the internal trace header never reaches the client...
        assert TRACE_ID_HEADER not in r.headers
        # ...but the gateway's tracer knows the trace
        summary = gw_app[TRACING_KEY].summary()
        assert summary["traces"], summary
        tid = summary["traces"][0]["trace_id"]
        engine.stop()
        worker.join(timeout=15)
        r = await gw.get(f"/api/traces?trace_id={tid}", headers=auth())
        assert r.status == 200
        data = await r.json()
        names = {s["name"] for s in data["spans"]}
        assert {"gateway.request", "gateway.admission", "gateway.upstream",
                "replica.request", "engine.request", "engine.queue_wait",
                "engine.prefill", "engine.decode"} <= names, names
        assert len(data["spans"]) >= 6
        # every span shares the one trace id, parents resolve in-trace
        by_id = {s["span_id"]: s for s in data["spans"]}
        for s in data["spans"]:
            assert s["trace_id"] == tid
            if s["parent_id"] is not None:
                assert s["parent_id"] in by_id, s
        # and the replica's TTFT histogram carries this trace as exemplar
        exemplars = [e for e in engine.telemetry.ttft.exemplars if e]
        assert any(e[0] == tid for e in exemplars)
    finally:
        engine.stop()
        worker.join(timeout=15)
        assert not worker.is_alive()
        await gw.close()
        await replica.close()


# -- server persistence + CLI ------------------------------------------------


def _replica_trace_payload(tid, retained="slow"):
    root = {"trace_id": tid, "span_id": "11" * 8, "parent_id": None,
            "name": "engine.request", "start": 10.0, "duration": 1.0,
            "status": "ok", "attrs": {"tokens_out": 4}}
    child = {"trace_id": tid, "span_id": "22" * 8,
             "parent_id": "11" * 8, "name": "engine.decode",
             "start": 10.2, "duration": 0.8, "status": "ok", "attrs": {}}
    summary = {"traces": [{"trace_id": tid, "spans": 2, "start": 10.0,
                           "duration_ms": 1000.0, "status": "ok",
                           "retained": retained}],
               "ring_spans": 2, "retained_traces": 1,
               "finished_traces": 1}
    return summary, [root, child]


async def test_server_traces_get_persists_and_survives_replica_loss():
    from dstack_tpu.server import db as dbm
    from dstack_tpu.server.app import create_app
    from dstack_tpu.server.db import Database

    tid = new_trace_id()
    summary, spans = _replica_trace_payload(tid)

    async def traces_handler(request):
        return web.json_response(summary)

    async def trace_detail_handler(request):
        return web.json_response({"trace_id": tid, "spans": spans})

    replica_app = web.Application()
    replica_app.router.add_get("/traces", traces_handler)
    replica_app.router.add_get("/traces/{tid}", trace_detail_handler)
    replica = TestClient(TestServer(replica_app))
    await replica.start_server()
    replica_url = f"http://127.0.0.1:{replica.server.port}"

    db = Database(":memory:")
    app = create_app(db=db, background=False, admin_token="tok")
    client = TestClient(TestServer(app))
    await client.start_server()
    h = {"Authorization": "Bearer tok"}
    try:
        await client.post("/api/projects/create",
                          json={"project_name": "main"}, headers=h)
        prow = await db.fetchone("SELECT * FROM projects")
        urow = await db.fetchone("SELECT * FROM users")
        rid, jid = dbm.new_id(), dbm.new_id()
        await db.insert("runs", id=rid, project_id=prow["id"],
                        user_id=urow["id"], run_name="svc", run_spec="{}",
                        status="running", submitted_at=dbm.now())
        await db.insert("jobs", id=jid, run_id=rid, project_id=prow["id"],
                        run_name="svc", status="running", job_spec="{}",
                        submitted_at=dbm.now())
        await db.execute(
            "INSERT INTO service_replicas "
            "(job_id, run_id, url, registered_at, role) VALUES (?,?,?,?,?)",
            (jid, rid, replica_url, dbm.now(), "any"))
        # a lifecycle span shares the timeline in the detail payload
        await db.insert("job_lifecycle_spans", id=dbm.new_id(),
                        project_id=prow["id"], job_id=jid, run_name="svc",
                        phase="provisioning", duration=12.5,
                        recorded_at=dbm.now())

        # listing persists the retained trace
        r = await client.post("/api/project/main/traces/get",
                              json={"run_name": "svc"}, headers=h)
        assert r.status == 200, await r.text()
        data = await r.json()
        assert any(t["trace_id"] == tid for t in data["traces"])
        rows = await db.fetchall(
            "SELECT * FROM request_trace_spans WHERE trace_id=?", (tid,))
        assert len(rows) == 2  # persisted on the listing sweep

        # detail stitches + includes lifecycle spans
        r = await client.post("/api/project/main/traces/get",
                              json={"run_name": "svc", "trace_id": tid},
                              headers=h)
        data = await r.json()
        assert [s["name"] for s in data["spans"]] == [
            "engine.request", "engine.decode"]
        assert data["lifecycle"][0]["phase"] == "provisioning"

        # a persisted span whose replica is GONE (the PD dead-leg case)
        # must still merge into the detail even though a live replica
        # answered with its own half
        await db.execute(
            "INSERT OR REPLACE INTO request_trace_spans "
            "(span_id, trace_id, project_id, run_name, parent_id, name, "
            " start, duration, status, attrs, recorded_at) "
            "VALUES (?,?,?,?,?,?,?,?,?,?,?)",
            ("33" * 8, tid, prow["id"], "svc", "11" * 8,
             "engine.prefill", 10.05, 0.1, "ok", "{}", dbm.now()))
        r = await client.post("/api/project/main/traces/get",
                              json={"run_name": "svc", "trace_id": tid},
                              headers=h)
        data = await r.json()
        assert {s["name"] for s in data["spans"]} == {
            "engine.request", "engine.prefill", "engine.decode"}

        # replica gone: the persisted store still answers
        await replica.close()
        r = await client.post("/api/project/main/traces/get",
                              json={"run_name": "svc", "trace_id": tid},
                              headers=h)
        data = await r.json()
        assert len(data["spans"]) == 3
        assert data["replicas_reporting"] == 0
        # listing falls back to the store too, marked "persisted"
        r = await client.post("/api/project/main/traces/get",
                              json={"run_name": "svc"}, headers=h)
        data = await r.json()
        entry = [t for t in data["traces"] if t["trace_id"] == tid][0]
        assert entry["retained"] == "persisted"

        r = await client.post("/api/project/main/traces/get",
                              json={"run_name": "nope"}, headers=h)
        assert r.status == 404
    finally:
        await client.close()
        if not replica.server.closed:
            await replica.close()
        db.close()


def test_cli_span_tree_renders_nested_durations(capsys):
    """The `dstack-tpu trace` tree: children indent under parents,
    orphaned parents degrade to roots, durations render in ms."""
    from dstack_tpu.cli.main import _render_span_tree

    spans = [
        {"trace_id": "t", "span_id": "a", "parent_id": None,
         "name": "gateway.request", "start": 0.0, "duration": 1.0,
         "status": "ok", "attrs": {"service": "main/svc"}},
        {"trace_id": "t", "span_id": "b", "parent_id": "a",
         "name": "engine.request", "start": 0.1, "duration": 0.8,
         "status": "ok", "attrs": {}},
        {"trace_id": "t", "span_id": "c", "parent_id": "b",
         "name": "engine.decode", "start": 0.3, "duration": 0.6,
         "status": "error", "attrs": {"tokens_out": 9}},
        {"trace_id": "t", "span_id": "d", "parent_id": "missing",
         "name": "stray", "start": 0.5, "duration": 0.1,
         "status": "ok", "attrs": {}},
    ]
    _render_span_tree(spans)
    out = capsys.readouterr().out
    lines = [ln for ln in out.splitlines() if ln.strip()]
    assert "gateway.request" in lines[0]
    assert lines[1].startswith("  ") and "engine.request" in lines[1]
    assert lines[2].startswith("    ") and "engine.decode" in lines[2]
    assert "tokens_out=9" in lines[2]
    assert "stray" in lines[3] and not lines[3].startswith("  ")
    assert "1,000.0 ms" in lines[0]
