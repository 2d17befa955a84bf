"""Chaos: replica loss mid-decode — drain-and-migrate with zero drops.

The serving-plane recovery invariants:

- a migration registers the SUCCESSOR before the victim stops serving —
  at no instant does the service have zero routable replicas, so a
  request fired at any point during the migration succeeds;
- a stream accepted by the victim before the migration runs to
  completion ([DONE] received) — draining finishes in-flight work;
- the victim is unregistered only once drained, and new requests land on
  the successor.

The invariant tests use fake instant replicas (cheap, deterministic);
the flagship runs a REAL tiny engine pair and migrates mid-SSE-stream.
"""

import asyncio
import threading

from aiohttp import web
from aiohttp.test_utils import TestClient, TestServer

from dstack_tpu.gateway.app import create_gateway_app

TOKEN = "chaos-token"


def auth():
    return {"Authorization": f"Bearer {TOKEN}"}


async def _start_replica(handler):
    app = web.Application()
    app.router.add_route("*", "/{tail:.*}", handler)
    client = TestClient(TestServer(app))
    await client.start_server()
    return client, f"http://127.0.0.1:{client.server.port}"


async def _start_gateway(tmp_path):
    gw_app = create_gateway_app(TOKEN, state_dir=tmp_path)
    gw = TestClient(TestServer(gw_app))
    await gw.start_server()
    return gw, gw_app


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


async def _replica_ids(gw, project, run):
    r = await gw.get("/api/registry/list", headers=auth())
    services = await r.json()
    for s in services:
        if s["project"] == project and s["run_name"] == run:
            return {rep["job_id"]: rep for rep in s["replicas"]}
    return {}


# -- invariants with fake replicas (fast tier) -------------------------------


async def test_drain_routes_new_requests_away(tmp_path):
    counts = {"a": 0, "b": 0}

    def make(name):
        async def handler(request):
            # the gateway also POSTs /drain at the replica (best-effort
            # notify) — only count the actual routed traffic
            if request.path.endswith("/ping"):
                counts[name] += 1
            return web.json_response({"served_by": name})
        return handler

    ca, url_a = await _start_replica(make("a"))
    cb, url_b = await _start_replica(make("b"))
    gw, _ = await _start_gateway(tmp_path)
    try:
        await _register(gw, "main", "svc",
                        [("a", url_a, "any"), ("b", url_b, "any")])
        r = await gw.post("/api/registry/replica/drain",
                          json={"project": "main", "run_name": "svc",
                                "job_id": "a"},
                          headers=auth())
        assert r.status == 200
        counts["a"] = counts["b"] = 0
        for _ in range(8):
            r = await gw.get("/services/main/svc/ping")
            assert r.status == 200
        assert counts == {"a": 0, "b": 8}
        # draining replica stays registered (in-flight accounting) but
        # flagged
        reps = await _replica_ids(gw, "main", "svc")
        assert reps["a"]["draining"] is True
        # unknown replica -> 404, not a silent no-op
        r = await gw.post("/api/registry/replica/drain",
                          json={"project": "main", "run_name": "svc",
                                "job_id": "nope"},
                          headers=auth())
        assert r.status == 404
    finally:
        await gw.close()
        await ca.close()
        await cb.close()


async def test_migrate_never_leaves_zero_replicas(tmp_path):
    """Fire requests continuously across a migration: every one must
    succeed — the successor registers before the victim stops serving,
    and the victim is removed only after it drains."""
    def make(name):
        async def handler(request):
            await asyncio.sleep(0.005)
            return web.json_response({"served_by": name})
        return handler

    ca, url_a = await _start_replica(make("a"))
    cb, url_b = await _start_replica(make("b"))
    gw, _ = await _start_gateway(tmp_path)
    try:
        await _register(gw, "main", "svc", [("a", url_a, "any")])

        results = []

        async def hammer():
            for _ in range(60):
                r = await gw.get("/services/main/svc/ping")
                results.append(r.status)
                await asyncio.sleep(0.003)

        task = asyncio.ensure_future(hammer())
        await asyncio.sleep(0.02)
        r = await gw.post(
            "/api/registry/replica/migrate",
            json={"project": "main", "run_name": "svc",
                  "victim_job_id": "a",
                  "successor": {"job_id": "b", "url": url_b},
                  "timeout": 5},
            headers=auth())
        assert r.status == 200
        body = await r.json()
        assert body["status"] == "migrating"
        # zero-drop invariant visible immediately: successor present
        # while the victim still drains
        reps = await _replica_ids(gw, "main", "svc")
        assert "b" in reps
        await task
        assert set(results) == {200}, results
        # victim removed once drained (bounded wait)
        for _ in range(100):
            reps = await _replica_ids(gw, "main", "svc")
            if "a" not in reps:
                break
            await asyncio.sleep(0.05)
        assert "a" not in reps
        assert reps["b"]["draining"] is False
    finally:
        await gw.close()
        await ca.close()
        await cb.close()


async def test_migrate_unknown_victim_still_registers_successor(tmp_path):
    """Replacing a replica that already vanished (hard host loss before
    the drain could start) must still bring the successor up."""
    async def handler(request):
        return web.json_response({})

    cb, url_b = await _start_replica(handler)
    gw, _ = await _start_gateway(tmp_path)
    try:
        await _register(gw, "main", "svc", [])
        r = await gw.post(
            "/api/registry/replica/migrate",
            json={"project": "main", "run_name": "svc",
                  "victim_job_id": "gone",
                  "successor": {"job_id": "b", "url": url_b}},
            headers=auth())
        assert r.status == 200
        assert (await r.json())["status"] == "registered"
        reps = await _replica_ids(gw, "main", "svc")
        assert "b" in reps and "gone" not in reps
        r = await gw.get("/services/main/svc/ping")
        assert r.status == 200
    finally:
        await gw.close()
        await cb.close()


# -- real engines: migrate mid-decode (compile-heavy) ------------------------


class _Tok:
    eos_id = None
    vocab_size = 64

    def encode(self, text):
        return [ord(c) % 60 + 1 for c in text][:16] or [1]

    def decode(self, ids):
        return "".join(chr(97 + (i % 26)) for i in ids)

    def apply_chat_template(self, messages):
        return " ".join(m.get("content", "") for m in messages)


async def test_drain_rewrites_nginx_conf(tmp_path):
    """Flipping a replica to draining must re-apply the nginx conf at
    once: render_site skips draining replicas, but only a rewrite makes
    nginx stop balancing NEW requests onto one (it would 503 them, and
    proxy_next_upstream does not retry 503)."""
    from dstack_tpu.gateway.app import create_gateway_app

    class FakeWriter:
        def __init__(self):
            self.writes = []

        def write_service(self, service):
            self.writes.append(
                {r.job_id: r.draining for r in service.replicas})

        def remove_service(self, service):
            pass

    writer = FakeWriter()
    gw_app = create_gateway_app(TOKEN, state_dir=tmp_path,
                                nginx_writer=writer)
    gw = TestClient(TestServer(gw_app))
    await gw.start_server()
    try:
        r = await gw.post(
            "/api/registry/register",
            json={"project": "main", "run_name": "svc",
                  "domain": "svc.example.test"},
            headers=auth())
        assert r.status == 200
        for job_id in ("a", "b"):
            r = await gw.post(
                "/api/registry/replica/add",
                json={"project": "main", "run_name": "svc",
                      "job_id": job_id, "url": f"http://{job_id}:1"},
                headers=auth())
            assert r.status == 200
        writes_before = len(writer.writes)

        r = await gw.post(
            "/api/registry/replica/drain",
            json={"project": "main", "run_name": "svc", "job_id": "a"},
            headers=auth())
        assert r.status == 200
        assert len(writer.writes) > writes_before
        assert writer.writes[-1] == {"a": True, "b": False}
    finally:
        await gw.close()


def _real_replica_app(name):
    import jax

    from dstack_tpu.models.llama import LlamaConfig, init_params
    from dstack_tpu.serving.engine import InferenceEngine
    from dstack_tpu.serving.server import ServingApp
    from dstack_tpu.telemetry.serving import EngineTelemetry

    cfg = LlamaConfig.tiny()
    params = init_params(jax.random.PRNGKey(0), cfg)
    engine = InferenceEngine(cfg, params=params, batch_size=2, max_len=128,
                             telemetry=EngineTelemetry())
    serving = ServingApp(engine, _Tok(), model_name=name)
    worker = threading.Thread(target=engine.run_forever, daemon=True,
                              name=f"engine-{name}")
    worker.start()
    return engine, serving, worker


def _stop_replica(engine, worker):
    """Stop a ``_real_replica_app`` loop and wait for its thread: a loop
    left running idles in the worker's later test files too."""
    engine.stop()
    worker.join(timeout=30)
    assert not worker.is_alive()


async def test_standalone_drain_is_reversible(tmp_path):
    """`{"draining": false}` undoes a maintenance drain — without it a
    stray drain would shun a healthy replica until a process restart."""
    gw, _ = await _start_gateway(tmp_path)
    try:
        await _register(gw, "main", "svc",
                        [("a", "http://127.0.0.1:1", "any")])
        r = await gw.post("/api/registry/replica/drain",
                          json={"project": "main", "run_name": "svc",
                                "job_id": "a"},
                          headers=auth())
        assert (await r.json())["status"] == "draining"
        r = await gw.post("/api/registry/replica/drain",
                          json={"project": "main", "run_name": "svc",
                                "job_id": "a", "draining": False},
                          headers=auth())
        assert (await r.json())["status"] == "accepting"
        reps = await _replica_ids(gw, "main", "svc")
        assert not reps["a"]["draining"] and not reps["a"]["removing"]
    finally:
        await gw.close()


async def test_migrate_rejects_successor_same_as_victim(tmp_path):
    """Replace-in-place (successor job_id == victim) would drain and
    remove the replica just registered, ending at zero replicas — the
    gateway must refuse it outright."""
    gw, _ = await _start_gateway(tmp_path)
    try:
        await _register(gw, "main", "svc", [("a", "http://a:1", "any")])
        r = await gw.post(
            "/api/registry/replica/migrate",
            json={"project": "main", "run_name": "svc",
                  "victim_job_id": "a",
                  "successor": {"job_id": "a", "url": "http://a2:1"}},
            headers=auth())
        assert r.status == 400
        reps = await _replica_ids(gw, "main", "svc")
        assert "a" in reps and not reps["a"].get("draining")
    finally:
        await gw.close()


async def test_gateway_restart_resumes_interrupted_drain(tmp_path):
    """draining/removing flags are persisted with the registry, but the
    removal task is in-memory — a restart mid-MIGRATION must re-spawn it
    (else the victim stays registered forever with no API to clear it),
    while a standalone maintenance drain survives as just draining."""
    from dstack_tpu.gateway.registry import Registry, Replica, Service

    # seed the state a crashed gateway would leave behind: a migration
    # victim mid-drain plus a standalone-drained replica
    reg = Registry(tmp_path / "state.json")
    reg.register_service(Service(project="main", run_name="svc"))
    for job, port in (("a", 1), ("c", 3)):
        reg.add_replica("main", "svc",
                        Replica(job_id=job, url=f"http://127.0.0.1:{port}"))
    reg.migrate_replica("main", "svc", "a",
                        Replica(job_id="b", url="http://127.0.0.1:2"))
    reg.set_draining("main", "svc", "c", True)  # standalone drain

    gw, _ = await _start_gateway(tmp_path)  # the "restarted" gateway
    try:
        # the resumed removal finds victim a unreachable (dead host) and
        # completes; the successor and the maintenance-drained replica stay
        for _ in range(100):
            reps = await _replica_ids(gw, "main", "svc")
            if "a" not in reps:
                break
            await asyncio.sleep(0.05)
        assert "a" not in reps
        assert "b" in reps and not reps["b"]["draining"]
        assert "c" in reps and reps["c"]["draining"]
    finally:
        await gw.close()


def test_drained_never_true_mid_admission():
    """`drained` must stay False while a request is mid-admission (popped
    from the queue, prefill compiling, slot not yet claimed) — in exactly
    that window has_work() used to see nothing and an orchestrator
    polling /drain would have torn the replica down mid-request."""
    import jax

    from dstack_tpu.models.llama import LlamaConfig, init_params
    from dstack_tpu.serving.engine import InferenceEngine, Request

    cfg = LlamaConfig.tiny()
    eng = InferenceEngine(cfg, params=init_params(jax.random.PRNGKey(0), cfg),
                          batch_size=2, max_len=64)
    req = Request(tokens=[1, 2, 3], max_new_tokens=2)
    eng.submit(req)
    assert not eng.drained  # queued

    observed = {}
    orig_prefill = eng._prefill

    def probing_prefill(slot_id, r):
        # what a concurrent /drain poll would see mid-admission
        observed["has_work"] = eng.has_work()
        observed["drained"] = eng.drained
        orig_prefill(slot_id, r)

    eng._prefill = probing_prefill
    eng.begin_drain()
    while not req.done.is_set():
        eng.step()
    assert observed == {"has_work": True, "drained": False}
    assert eng.drained  # finished now: teardown is safe
    assert eng._admitting is None

    # drain is reversible (aborted migration / maintenance over): the
    # engine admits again with warm caches
    eng.end_drain()
    req2 = Request(tokens=[1, 2, 3], max_new_tokens=1)
    eng.submit(req2)
    while not req2.done.is_set():
        eng.step()
    assert req2.output


async def test_drain_race_after_admission_check_still_503(tmp_path):
    """The check-then-submit race: a drain that begins AFTER the
    top-of-handler draining check (handlers await the body / tokenize in
    between) must still surface as the documented 503 + Retry-After, not
    an unhandled EngineDraining 500."""
    eng, serving, worker = _real_replica_app("rep-race")
    c = TestClient(TestServer(serving.make_app()))
    await c.start_server()
    try:
        # simulate the race window: the top-of-handler check passes, then
        # the drain flips before engine.submit
        serving._refuse_if_draining = lambda: None
        eng.draining = True
        for payload in (
            {"prompt": "x", "max_tokens": 2},
            {"prompt": "x", "max_tokens": 2, "stream": True},
        ):
            r = await c.post("/v1/completions", json=payload)
            assert r.status == 503, await r.text()
            assert r.headers.get("Retry-After")
    finally:
        _stop_replica(eng, worker)
        await c.close()


async def test_replica_kill_mid_decode_stream_completes(tmp_path):
    """The flagship: an SSE stream is mid-decode on replica A when the
    control plane migrates A -> B.  The accepted stream must complete
    ([DONE] seen, no connection reset), A must refuse NEW work while
    draining and be unregistered once drained, and new requests must land
    on B."""
    engines = []
    clients = []
    try:
        eng_a, app_a, worker_a = _real_replica_app("rep-a")
        engines.append((eng_a, worker_a))
        eng_b, app_b, worker_b = _real_replica_app("rep-b")
        engines.append((eng_b, worker_b))
        for serving in (app_a, app_b):
            c = TestClient(TestServer(serving.make_app()))
            await c.start_server()
            clients.append(c)
        url_a = f"http://127.0.0.1:{clients[0].server.port}"
        url_b = f"http://127.0.0.1:{clients[1].server.port}"
        gw, _ = await _start_gateway(tmp_path)
        clients.append(gw)
        await _register(gw, "main", "svc", [("a", url_a, "any")])

        async def consume_stream():
            chunks = []
            async with gw.post(
                "/services/main/svc/v1/completions",
                json={"prompt": "hello", "max_tokens": 40, "stream": True},
            ) as resp:
                assert resp.status == 200
                async for line in resp.content:
                    chunks.append(line.decode())
            return "".join(chunks)

        stream_task = asyncio.ensure_future(consume_stream())
        # let the stream get admitted and produce some tokens on A
        for _ in range(200):
            await asyncio.sleep(0.05)
            if eng_a.telemetry.load_snapshot()["active_slots"] > 0:
                break
        assert not stream_task.done()

        r = await gw.post(
            "/api/registry/replica/migrate",
            json={"project": "main", "run_name": "svc",
                  "victim_job_id": "a",
                  "successor": {"job_id": "b", "url": url_b},
                  "timeout": 60},
            headers=auth())
        assert r.status == 200

        body = await asyncio.wait_for(stream_task, timeout=120)
        assert "data: [DONE]" in body  # the accepted stream COMPLETED
        assert eng_a.draining  # drain reached the replica itself

        # new requests go to the successor (victim refuses while draining)
        r = await gw.post("/services/main/svc/v1/completions",
                          json={"prompt": "again", "max_tokens": 4})
        assert r.status == 200
        out = await r.json()
        assert out["model"] == "rep-b"

        # victim unregisters once drained — zero-drop teardown complete
        for _ in range(200):
            reps = await _replica_ids(gw, "main", "svc")
            if "a" not in reps:
                break
            await asyncio.sleep(0.1)
        assert "a" not in reps and "b" in reps
    finally:
        for eng, worker in engines:
            _stop_replica(eng, worker)
        for c in clients:
            await c.close()
