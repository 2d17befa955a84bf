"""wirelint (DT9xx) — fixture pairs for the cross-plane wire-contract
rules: DT901 route/client path drift, DT902 header literals outside
serving/wire.py, DT903 proxy legs bypassing copy_upstream_headers,
DT904 env-knob registry + default drift, DT905 dead routes, DT906
metric families vs the exposition gate.

In-memory fixtures exercise the contract-index extraction (f-string
templates, wrapper prefix composition, route tables, partial-bound env
helpers); DT906 and the CLI probes use real tmp trees because the gate
script is located relative to the scanned tree root.
"""

from __future__ import annotations

import json
import textwrap
from pathlib import Path

from dstack_tpu.analysis.callgraph import Project
from dstack_tpu.analysis.core import Module
from dstack_tpu.analysis.rules import wire_contracts as wl


def wfind(*files):
    """DT9xx findings over a fixture project of (relpath, src) pairs,
    pragma-filtered the same way the engine filters them."""
    mods = [Module(Path("<snippet>"), rp, textwrap.dedent(src))
            for rp, src in files]
    project = Project(mods)
    return [f for f in wl.check(project)
            if not project.by_relpath[f.path].is_suppressed(f)]


def wcodes(*files):
    return sorted({f.code for f in wfind(*files)})


#: a control-plane route table, registered the way server/app.py does it
SERVER = ("dstack_tpu/server/app.py", """
    from aiohttp import web

    async def list_users(request):
        return web.json_response([])

    async def get_info(request):
        return web.json_response({})

    def create_app():
        app = web.Application()
        app.router.add_post("/api/users/list", list_users)
        app.router.add_get("/api/server/get_info", get_info)
        return app
""")

#: the api/client.py wrapper stack: post() forwards its path to the
#: session verbatim, project_post() composes the project prefix
API_CLIENT = ("dstack_tpu/api/client.py", """
    class Client:
        def __init__(self, http, project):
            self._http = http
            self.project = project

        def post(self, path, body=None):
            return self._http.post(path, json=body or {})

        def project_post(self, path, body=None):
            return self.post(f"/api/project/{self.project}{path}", body)
""")


# -- DT901: client path without a registered route ---------------------------


def test_dt901_typoed_client_path():
    bad = ("dstack_tpu/api/calls.py", """
        async def list_users(session):
            return await session.post("/api/users/listt")

        async def info(session):
            return await session.get("/api/server/get_info")
    """)
    fs = [f for f in wfind(SERVER, bad) if f.code == "DT901"]
    assert len(fs) == 1 and "/api/users/listt" in fs[0].message

    good = ("dstack_tpu/api/calls.py", """
        async def list_users(session):
            return await session.post("/api/users/list")

        async def info(session):
            return await session.get("/api/server/get_info")
    """)
    assert wcodes(SERVER, good) == []


def test_dt901_placeholder_segments_are_wildcards():
    server = ("dstack_tpu/server/app.py", """
        def setup(app, handler):
            app.router.add_post(
                "/api/project/{project_name}/runs/list", handler)
    """)
    good = ("dstack_tpu/api/calls.py", """
        async def runs(session, name):
            return await session.post(f"/api/project/{name}/runs/list")
    """)
    assert wcodes(server, good) == []


def test_dt901_wrapper_prefix_expansion():
    """project_post('/runs/list') resolves through two wrapper levels to
    /api/project/{*}/runs/list — a typo in the forwarded tail is caught
    against the placeholder route."""
    server = ("dstack_tpu/server/app.py", """
        def setup(app, handler):
            app.router.add_post(
                "/api/project/{project_name}/runs/list", handler)
    """)
    bad = ("dstack_tpu/cli/runs.py", """
        def list_runs(client):
            return client.project_post("/runs/listt")
    """)
    fs = [f for f in wfind(server, API_CLIENT, bad) if f.code == "DT901"]
    assert len(fs) == 1
    assert "/api/project/{*}/runs/listt" in fs[0].message
    assert fs[0].path == "dstack_tpu/cli/runs.py"

    good = ("dstack_tpu/cli/runs.py", """
        def list_runs(client):
            return client.project_post("/runs/list")
    """)
    assert wcodes(server, API_CLIENT, good) == []


def test_dt901_external_and_dynamic_bases_stay_silent():
    """MAY analysis: a path against a scheme'd or unresolvable base is
    never judged (the route may live on a replica or a cloud API)."""
    snip = ("dstack_tpu/gateway/legs.py", """
        async def poke(session, base):
            await session.get("http://metadata.internal/v1/token")
            await session.get(f"{base}/api/replica/only/path")
    """)
    assert wcodes(snip) == []


def test_dt901_web_route_table_entries():
    """web.get(...) route-table lists register the same as add_get."""
    server = ("dstack_tpu/serving/app.py", """
        from aiohttp import web

        def make_app(h):
            app = web.Application()
            app.add_routes([
                web.get("/v1/models", h),
                web.post("/v1/completions", h),
            ])
            return app
    """)
    bad = ("dstack_tpu/tests_helper.py", """
        async def call(session):
            await session.post("/v1/completion")
            await session.get("/v1/models")
    """)
    fs = [f for f in wfind(server, bad) if f.code == "DT901"]
    assert len(fs) == 1 and "/v1/completion" in fs[0].message
    good = ("dstack_tpu/tests_helper.py", """
        async def call(session):
            await session.post("/v1/completions")
            await session.get("/v1/models")
    """)
    assert wcodes(server, good) == []


# -- DT902: X-Dstack-* header literals outside serving/wire.py ---------------


def test_dt902_header_literal_pair():
    bad = ("dstack_tpu/gateway/app.py", """
        def tag(resp):
            resp.headers["X-Dstack-Deadline"] = "1.5"
    """)
    fs = wfind(bad)
    assert [f.code for f in fs] == ["DT902"]
    assert "X-Dstack-Deadline" in fs[0].message

    good = ("dstack_tpu/gateway/app.py", """
        from dstack_tpu.serving.wire import DEADLINE_HEADER

        def tag(resp):
            resp.headers[DEADLINE_HEADER] = "1.5"
    """)
    assert wcodes(good) == []


def test_dt902_wire_module_and_docstrings_exempt():
    wire = ("dstack_tpu/serving/wire.py", """
        DEADLINE_HEADER = "X-Dstack-Deadline"
    """)
    assert wcodes(wire) == []
    doc = ("dstack_tpu/gateway/app.py", '''
        def tag(resp):
            "X-Dstack-Deadline is attached by the caller."
            return resp
    ''')
    assert wcodes(doc) == []


def test_dt902_case_insensitive_literal():
    bad = ("dstack_tpu/server/routers/proxy.py", """
        HOP = {"x-dstack-router-phase"}
    """)
    assert wcodes(bad) == ["DT902"]


# -- DT903: proxy legs must go through copy_upstream_headers -----------------


def test_dt903_forwarding_loop_pair():
    """The trace/load header-leak incident shape: a proxy leg copying
    upstream response headers verbatim instead of calling the stripping
    helper."""
    bad = ("dstack_tpu/serving/pd_protocol.py", """
        async def forward(resp, upstream):
            for k, v in upstream.headers.items():
                resp.headers[k] = v
    """)
    assert wcodes(bad) == ["DT903"]

    good = ("dstack_tpu/serving/pd_protocol.py", """
        from dstack_tpu.serving.wire import TRACE_HEADER_PREFIX

        def copy_upstream_headers(resp, upstream):
            for k, v in upstream.headers.items():
                if k.lower().startswith(TRACE_HEADER_PREFIX.lower()):
                    continue
                resp.headers[k] = v

        async def forward(resp, upstream):
            copy_upstream_headers(resp, upstream)
    """)
    assert wcodes(good) == []


def test_dt903_update_and_constructor_shapes():
    upd = ("dstack_tpu/gateway/app.py", """
        async def leg(resp, upstream):
            resp.headers.update(upstream.headers)
    """)
    assert wcodes(upd) == ["DT903"]
    ctor = ("dstack_tpu/server/routers/proxy.py", """
        from aiohttp import web

        async def leg(upstream):
            return web.StreamResponse(headers=dict(upstream.headers))
    """)
    assert wcodes(ctor) == ["DT903"]


def test_dt903_request_headers_and_out_of_plane_exempt():
    """Copying the CLIENT request's headers outward is not a leak, and
    the rule only patrols the proxying planes."""
    req = ("dstack_tpu/gateway/app.py", """
        async def leg(out, request):
            for k, v in request.headers.items():
                out.headers[k] = v
    """)
    assert wcodes(req) == []
    elsewhere = ("dstack_tpu/backends/gcp/compute.py", """
        async def leg(resp, upstream):
            resp.headers.update(upstream.headers)
    """)
    assert wcodes(elsewhere) == []


# -- DT904: env-knob registry and default drift ------------------------------

KNOBS = ("dstack_tpu/core/knobs.py", """
    class Knob:
        def __init__(self, name, default=None, parser="str", doc=""):
            self.name = name
            self.default = default

    REGISTRY = [
        Knob("DSTACK_SERVER_PORT", default="3000"),
        Knob("DSTACK_GATEWAY_DRAIN_TIMEOUT", default="600"),
        Knob("DSTACK_HEDGE_RATE", default="0.05"),
    ]
""")


def test_dt904_unregistered_knob():
    bad = ("dstack_tpu/server/app.py", """
        import os
        PORT = os.environ.get("DSTACK_SERVRE_PORT", "3000")
    """)
    fs = wfind(KNOBS, bad)
    assert [f.code for f in fs] == ["DT904"]
    assert "DSTACK_SERVRE_PORT" in fs[0].message
    good = ("dstack_tpu/server/app.py", """
        import os
        PORT = os.environ.get("DSTACK_SERVER_PORT", "3000")
    """)
    assert wcodes(KNOBS, good) == []


def test_dt904_default_drift_regression():
    """The drain-timeout incident: two planes read the same knob with
    different literal defaults, so behaviour depends on which plane you
    ask.  Numerically equal spellings ("600" vs 600) do not drift."""
    a = ("dstack_tpu/gateway/app.py", """
        import os
        DRAIN = os.environ.get("DSTACK_GATEWAY_DRAIN_TIMEOUT", "600")
    """)
    b = ("dstack_tpu/compute/compile_cache.py", """
        import os
        DRAIN = os.getenv("DSTACK_GATEWAY_DRAIN_TIMEOUT", "900")
    """)
    fs = wfind(KNOBS, a, b)
    assert [f.code for f in fs] == ["DT904", "DT904"]
    assert {f.path for f in fs} == {"dstack_tpu/gateway/app.py",
                                    "dstack_tpu/compute/compile_cache.py"}
    assert all("600" in f.message and "900" in f.message for f in fs)

    b_same = ("dstack_tpu/compute/compile_cache.py", """
        import os
        DRAIN = int(os.getenv("DSTACK_GATEWAY_DRAIN_TIMEOUT", 600))
    """)
    assert wcodes(KNOBS, a, b_same) == []


def test_dt904_partial_bound_helper_sites():
    """settings._env-style helpers: the key is the helper's parameter,
    so the read (and its default) belongs to each CALL site."""
    helper = ("dstack_tpu/core/settings.py", """
        import os

        def _env_float(name, default):
            return float(os.environ.get(name, default))
    """)
    drift_a = ("dstack_tpu/gateway/routing.py", """
        from dstack_tpu.core.settings import _env_float
        RATE = _env_float("DSTACK_HEDGE_RATE", 0.05)
    """)
    drift_b = ("dstack_tpu/serving/engine.py", """
        from dstack_tpu.core.settings import _env_float
        RATE = _env_float("DSTACK_HEDGE_RATE", 0.10)
    """)
    fs = wfind(KNOBS, helper, drift_a, drift_b)
    assert [f.code for f in fs] == ["DT904", "DT904"]
    assert {f.path for f in fs} == {"dstack_tpu/gateway/routing.py",
                                    "dstack_tpu/serving/engine.py"}
    assert wcodes(KNOBS, helper, drift_a) == []


def test_dt904_silent_without_registry_module():
    """File-scoped runs that do not include core/knobs.py must not
    invent 'unregistered' findings."""
    read = ("dstack_tpu/server/app.py", """
        import os
        PORT = os.environ.get("DSTACK_ANYTHING", "1")
    """)
    assert wcodes(read) == []


def test_dt904_dynamic_default_never_drifts():
    a = ("dstack_tpu/gateway/app.py", """
        import os
        DRAIN = os.environ.get("DSTACK_GATEWAY_DRAIN_TIMEOUT", "600")
    """)
    b = ("dstack_tpu/server/app.py", """
        import os

        def drain(fallback):
            return os.environ.get("DSTACK_GATEWAY_DRAIN_TIMEOUT", fallback)
    """)
    assert wcodes(KNOBS, a, b) == []


# -- DT905: dead routes and the external-surface pragma ----------------------


def test_dt905_dead_route_and_pragma_forms():
    dead = ("dstack_tpu/server/app.py", """
        def setup(app, handler):
            app.router.add_post("/api/users/ghost", handler)
    """)
    fs = wfind(dead)
    assert [f.code for f in fs] == ["DT905"]
    assert "/api/users/ghost" in fs[0].message

    same_line = ("dstack_tpu/server/app.py", """
        def setup(app, handler):
            app.router.add_post("/api/users/ghost", handler)  # dtlint: external-surface
    """)
    assert wcodes(same_line) == []

    line_above = ("dstack_tpu/server/app.py", """
        def setup(app, handler):
            # dtlint: external-surface
            app.router.add_post("/api/users/ghost", handler)
    """)
    assert wcodes(line_above) == []


def test_dt905_open_template_needs_literal_anchor():
    """A client template with a literal prefix covers the routes under
    it; a fully-dynamic forwarding leg (/{*}/{*}) covers nothing —
    otherwise every proxy would mark the whole surface as called."""
    server = ("dstack_tpu/server/app.py", """
        def setup(app, handler):
            app.router.add_post("/api/tasks/submit", handler)
    """)
    anchored = ("dstack_tpu/server/pipelines/jobs.py", """
        async def call(session, job):
            op = job.next_op()
            await session.post(f"/api/tasks/{op}")
    """)
    assert wcodes(server, anchored) == []

    forwarding = ("dstack_tpu/server/routers/proxy.py", """
        async def leg(session, project, rest):
            await session.post(f"/{project}/{rest}")
    """)
    assert wcodes(server, forwarding) == ["DT905"]


def test_dt905_catch_all_routes_exempt():
    snip = ("dstack_tpu/gateway/app.py", """
        def setup(app, handler):
            app.router.add_get("/{tail:.*}", handler)
            app.router.add_get("/ui/{tail:.*}", handler)
    """)
    assert wcodes(snip) == []


# -- DT906: metric families vs the exposition gate (real tmp trees) ----------

SERVING_TELEMETRY = textwrap.dedent("""
    PREFIX = "dstack_serving_"

    class EngineTelemetry:
        def __init__(self, r):
            self._ttft = r.histogram(PREFIX + "ttft_seconds")
            self._slots = r.gauge(PREFIX + "active_slots")
""")


def _write_metric_tree(tmp_path, gate_families):
    root = tmp_path / "tree"
    (root / "dstack_tpu" / "telemetry").mkdir(parents=True)
    (root / "scripts").mkdir()
    (root / "pyproject.toml").write_text("")
    (root / "dstack_tpu" / "telemetry" / "serving.py").write_text(
        SERVING_TELEMETRY)
    entries = ",\n    ".join(repr(f) for f in gate_families)
    (root / "scripts" / "check_metrics_exposition.py").write_text(
        f"REQUIRED = (\n    {entries},\n)\n")
    return root


def test_dt906_gate_in_sync_is_clean(tmp_path, capsys):
    from dstack_tpu.analysis.__main__ import main

    root = _write_metric_tree(tmp_path, [
        "dstack_serving_ttft_seconds_bucket", "dstack_serving_active_slots"])
    assert main([str(root), "--no-baseline"]) == 0
    capsys.readouterr()


def test_dt906_recorded_but_not_gated(tmp_path, capsys):
    from dstack_tpu.analysis.__main__ import main

    root = _write_metric_tree(tmp_path, ["dstack_serving_ttft_seconds_bucket"])
    rc = main([str(root), "--no-baseline"])
    out = capsys.readouterr().out
    assert rc == 1
    assert "DT906" in out and "dstack_serving_active_slots" in out


def test_dt906_gated_but_never_recorded(tmp_path, capsys):
    from dstack_tpu.analysis.__main__ import main

    root = _write_metric_tree(tmp_path, [
        "dstack_serving_ttft_seconds_bucket", "dstack_serving_active_slots",
        "dstack_serving_departed_total"])
    rc = main([str(root), "--no-baseline"])
    out = capsys.readouterr().out
    assert rc == 1
    assert "DT906" in out and "dstack_serving_departed_total" in out


def test_dt906_counter_named_like_a_histogram_sample(tmp_path, capsys):
    """A counter whose own name ends in ``_sum`` is gated under that name,
    and its gate entry is not taken for the ``_sum`` of a histogram."""
    from dstack_tpu.analysis.__main__ import main

    root = _write_metric_tree(tmp_path, [
        "dstack_serving_ttft_seconds_sum", "dstack_serving_active_slots",
        "dstack_serving_load_max_sum"])
    telemetry = root / "dstack_tpu" / "telemetry" / "serving.py"
    telemetry.write_text(SERVING_TELEMETRY + (
        '        self._load = r.counter(PREFIX + "load_max_sum")\n'))
    assert main([str(root), "--no-baseline"]) == 0
    capsys.readouterr()


# -- CLI drift probes (the acceptance shapes, as regression fixtures) --------


def test_cli_wire_probes_exit_one_with_right_code(tmp_path, capsys):
    from dstack_tpu.analysis.__main__ import main

    probes = {
        "DT902": ("dstack_tpu/gateway/app.py", """
            PROBE_HEADER = "X-Dstack-Probe"
        """),
        "DT903": ("dstack_tpu/serving/pd_protocol.py", """
            async def forward(resp, upstream):
                for k, v in upstream.headers.items():
                    resp.headers[k] = v
        """),
        "DT905": ("dstack_tpu/server/app.py", """
            def setup(app, handler):
                app.router.add_get("/api/server/probe_dead_route", handler)
        """),
    }
    for code, (relpath, src) in probes.items():
        root = tmp_path / code
        target = root / relpath
        target.parent.mkdir(parents=True)
        (root / "pyproject.toml").write_text("")
        target.write_text(textwrap.dedent(src))
        rc = main([str(root), "--no-baseline"])
        out = capsys.readouterr().out
        assert rc == 1, (code, out)
        assert code in out, (code, out)


# -- inventory dump ----------------------------------------------------------


def test_contract_inventory_shape():
    mods = [Module(Path("<snippet>"), rp, textwrap.dedent(src))
            for rp, src in (SERVER, API_CLIENT, KNOBS)]
    inv = wl.contract_inventory(Project(mods))
    assert set(inv) == {"routes", "clients", "headers", "knobs", "metrics"}
    assert {r["path"] for r in inv["routes"]} == {
        "/api/users/list", "/api/server/get_info"}
    assert {k["name"] for k in inv["knobs"]} == {
        "DSTACK_SERVER_PORT", "DSTACK_GATEWAY_DRAIN_TIMEOUT",
        "DSTACK_HEDGE_RATE"}


def test_inventory_cli_writes_json(tmp_path):
    src = tmp_path / "dstack_tpu" / "server"
    src.mkdir(parents=True)
    (tmp_path / "pyproject.toml").write_text("")
    (src / "app.py").write_text(
        'def setup(app, h):\n'
        '    app.router.add_get("/api/x", h)  # dtlint: external-surface\n')
    out = tmp_path / "inv.json"
    assert wl.main([str(tmp_path), "--out", str(out)]) == 0
    inv = json.loads(out.read_text())
    assert inv["routes"] == [{"path": "/api/x",
                              "file": "dstack_tpu/server/app.py", "line": 2}]


def test_dt9xx_family_registered():
    from dstack_tpu.analysis.core import registered_families

    assert "DT9xx" in registered_families()
