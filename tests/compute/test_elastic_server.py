"""Serving-server HTTP surfaces of the elasticity subsystem: warming is
reported distinct from draining on /load, the compile-cache and weight
seed routes serve peers, and the standby lifecycle runs over HTTP."""

import json
import threading

import pytest
from aiohttp.test_utils import TestClient, TestServer

from dstack_tpu.telemetry.serving import parse_load_headers


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


class _Tok:
    eos_id = None

    def encode(self, text):
        return [1, 2, 3]

    def decode(self, ids):
        return "x"


async def _serve(app):
    client = TestClient(TestServer(app.make_app()))
    await client.start_server()
    return client


async def test_load_reports_warming_distinct_from_draining(setup):
    """A warming replica is healthy-but-not-capacity; a draining one is
    capacity-being-retired.  Conflating them makes orchestrators tear
    down replicas that are about to serve — the two flags must be
    independent on /load and in the X-Dstack-Load-* headers."""
    from dstack_tpu.serving.server import ServingApp

    cfg, params = setup
    app = ServingApp(_make_engine(cfg, params), _Tok())
    app.warming = True
    client = await _serve(app)
    try:
        r = await client.get("/load")
        assert r.status == 200
        body = await r.json()
        assert body["warming"] == 1 and body["draining"] == 0
        hdrs = parse_load_headers(r.headers)
        assert hdrs["warming"] == 1 and hdrs["draining"] == 0

        # generation refused with 503 while warming (engine loop is not
        # running yet — accepting would hang the request)
        r = await client.post("/v1/completions",
                              json={"prompt": "hi", "max_tokens": 1})
        assert r.status == 503
        assert "warming" in (await r.json())["detail"]

        # health says warming, not draining, not ok
        r = await client.get("/health")
        assert (await r.json())["status"] == "warming"

        app.warming = False
        r = await client.get("/load")
        body = await r.json()
        assert body["warming"] == 0 and body["draining"] == 0
    finally:
        await client.close()


async def test_load_and_stats_surface_compile_cache_counters(setup, tmp_path):
    from dstack_tpu.elastic.compile_cache import CompileCache
    from dstack_tpu.serving.server import ServingApp

    cfg, params = setup
    engine = _make_engine(cfg, params, compile_cache=CompileCache(tmp_path))
    app = ServingApp(engine, _Tok())
    client = await _serve(app)
    try:
        r = await client.get("/load")
        body = await r.json()
        assert body["compile_cache_hits"] == 0
        assert body["compile_cache_misses"] == 0
        r = await client.get("/stats")
        stats = await r.json()
        assert "compile_cache_misses" in stats["compile_cache"]
        assert stats["warming"] is False and stats["standby"] is False
    finally:
        await client.close()


async def test_elastic_compile_route_serves_cache_bytes(setup, tmp_path):
    from dstack_tpu.elastic.compile_cache import CompileCache
    from dstack_tpu.serving.server import ServingApp

    cfg, params = setup
    cache = CompileCache(tmp_path)
    key = "ab" * 32
    cache.put_bytes(key, b"serialized-executable-bytes")
    app = ServingApp(_make_engine(cfg, params, compile_cache=cache), _Tok())
    client = await _serve(app)
    try:
        r = await client.get(f"/elastic/compile/{key}")
        assert r.status == 200
        assert await r.read() == b"serialized-executable-bytes"
        assert r.headers["Content-Type"] == "application/octet-stream"
        # unknown key -> 404; non-hex (traversal-shaped) key -> 400
        r = await client.get(f"/elastic/compile/{'cd' * 32}")
        assert r.status == 404
        r = await client.get("/elastic/compile/..%2fsecrets")
        assert r.status == 400
    finally:
        await client.close()


async def test_elastic_compile_404_when_cache_disabled(setup):
    from dstack_tpu.serving.server import ServingApp

    cfg, params = setup
    app = ServingApp(_make_engine(cfg, params), _Tok())
    client = await _serve(app)
    try:
        r = await client.get(f"/elastic/compile/{'ab' * 32}")
        assert r.status == 404
        assert "disabled" in (await r.json())["detail"]
    finally:
        await client.close()


async def test_elastic_weights_routes_seed_published_snapshot(
        setup, tmp_path):
    """The seeder side of weight streaming: manifest + shard bytes come
    back verbatim from the latest published snapshot, and only
    manifest-format shard names are served (no path traversal)."""
    import jax

    from dstack_tpu.models import checkpoint as ckpt
    from dstack_tpu.serving.server import ServingApp

    cfg, params = setup
    state = {"w": jax.numpy.arange(12.0).reshape(3, 4)}
    ckpt.write_snapshot(tmp_path, ckpt.snapshot_train_state(state), 4,
                        process_index=0, num_processes=1)
    step_dir = tmp_path / "step_00000004"
    app = ServingApp(_make_engine(cfg, params), _Tok(),
                     snapshot_dir=str(tmp_path))
    client = await _serve(app)
    try:
        r = await client.get("/elastic/weights/manifest")
        assert r.status == 200
        manifest = json.loads(await r.read())
        assert manifest["step"] == 4
        assert "host_00000.npz" in manifest["checksums"]

        r = await client.get("/elastic/weights/host_00000.npz")
        assert r.status == 200
        assert await r.read() == (step_dir / "host_00000.npz").read_bytes()

        r = await client.get("/elastic/weights/host_00099.npz")
        assert r.status == 404
        r = await client.get("/elastic/weights/manifest.json")
        assert r.status == 400  # only host_NNNNN.npz names are shards
    finally:
        await client.close()


async def test_elastic_weights_404_without_snapshot_dir(setup):
    from dstack_tpu.serving.server import ServingApp

    cfg, params = setup
    app = ServingApp(_make_engine(cfg, params), _Tok())
    client = await _serve(app)
    try:
        r = await client.get("/elastic/weights/manifest")
        assert r.status == 404
    finally:
        await client.close()


async def test_standby_activation_over_http(setup):
    """The replica half of the gateway scale-up path: a standby refuses
    /v1 until POST /elastic/standby/activate flips it live; activation
    while still warming is a 409 so the caller falls back instead of
    waiting out a compile."""
    from dstack_tpu.serving.server import ServingApp

    cfg, params = setup
    app = ServingApp(_make_engine(cfg, params), _Tok(), standby=True)
    client = await _serve(app)
    try:
        r = await client.get("/elastic/standby")
        assert await r.json() == {"standby": True, "warming": False,
                                  "activated_at": None}
        # standby is visible as warming on /load — never routable
        r = await client.get("/load")
        assert (await r.json())["warming"] == 1
        r = await client.post("/v1/completions",
                              json={"prompt": "hi", "max_tokens": 1})
        assert r.status == 503

        # 409 while the warmup is still running
        app.warming = True
        r = await client.post("/elastic/standby/activate")
        assert r.status == 409
        assert r.headers["Retry-After"] == "2"
        app.warming = False

        r = await client.post("/elastic/standby/activate")
        assert r.status == 200
        body = await r.json()
        assert body["activated"] is True and body["standby"] is False

        r = await client.get("/load")
        assert (await r.json())["warming"] == 0
        r = await client.get("/health")
        assert (await r.json())["status"] == "ok"
        status = await (await client.get("/elastic/standby")).json()
        assert status["standby"] is False
        assert status["activated_at"] is not None

        # idempotent: a second activate succeeds but reports no flip
        r = await client.post("/elastic/standby/activate")
        assert (await r.json())["activated"] is False
    finally:
        await client.close()


async def test_failed_warmup_never_reports_ok(setup):
    """A warmup that raises (a program the chip's compiler refuses) is what
    every request would hit next: the replica must not log it and serve.
    /health answers 503 with the error, /v1 keeps refusing, /load keeps it
    out of routable capacity, and the engine loop is never started."""
    from dstack_tpu.serving.server import ServingApp

    cfg, params = setup
    engine = _make_engine(cfg, params)

    def refused(*a, **kw):
        raise ValueError("Pallas TPU lowering: block shape refused")

    engine.warmup = refused
    app = ServingApp(engine, _Tok())
    app.start_engine(warm=True)
    for t in threading.enumerate():
        if t.name == "engine-warm":
            t.join(timeout=30)
    client = await _serve(app)
    try:
        r = await client.get("/health")
        assert r.status == 503
        body = await r.json()
        assert body["status"] == "error"
        assert "block shape refused" in body["error"]
        assert not app._thread.is_alive()
        r = await client.post("/v1/completions",
                              json={"prompt": "hi", "max_tokens": 1})
        assert r.status == 503
        assert (await (await client.get("/load")).json())["warming"] == 1
    finally:
        await client.close()


async def test_health_names_the_device(setup):
    """/health carries the device the serving process computes on, as JAX
    reports it — what chip_smoke.py and the benchmark read."""
    import jax

    from dstack_tpu.serving.server import ServingApp

    cfg, params = setup
    app = ServingApp(_make_engine(cfg, params), _Tok())
    client = await _serve(app)
    try:
        body = await (await client.get("/health")).json()
        d = jax.devices()
        assert body["device"] == {"platform": d[0].platform,
                                  "kind": d[0].device_kind, "count": len(d)}
    finally:
        await client.close()
