#!/usr/bin/env python
"""CI gate: /metrics must emit well-formed Prometheus exposition — BOTH
planes.

Control plane: boots the server app in-process against an in-memory DB,
seeds a running job with scraped custom metrics and a lifecycle span,
scrapes /metrics with an authorized client, and validates the full output
with the strict exposition parser (server/telemetry/exposition.py).

Compute plane: spins the serving app in-process over a stub engine whose
telemetry recorder carries one observation of every serving metric, and
strict-parses its /metrics plus sanity-checks /stats percentile ordering.

A malformed republish — broken label escaping, a TYPE line out of place, a
histogram missing its +Inf bucket — fails the build instead of silently
breaking every real Prometheus scraper pointed at either plane.

Run directly: ``python scripts/check_metrics_exposition.py``
"""

from __future__ import annotations

import asyncio
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

ADMIN = "ci-token"


async def main() -> int:
    from aiohttp.test_utils import TestClient, TestServer

    from dstack_tpu.server import db as dbm
    from dstack_tpu.server.app import create_app
    from dstack_tpu.server.db import Database
    from dstack_tpu.server.telemetry import exposition, spans

    db = Database(":memory:")
    app = create_app(db=db, background=False, admin_token=ADMIN)
    client = TestClient(TestServer(app))
    await client.start_server()
    try:
        h = {"Authorization": f"Bearer {ADMIN}"}
        r = await client.post("/api/projects/create",
                              json={"project_name": "ci"}, headers=h)
        assert r.status == 200, await r.text()
        prow = await db.fetchone("SELECT * FROM projects")
        urow = await db.fetchone("SELECT * FROM users")
        rid, jid = dbm.new_id(), dbm.new_id()
        # the run declares an SLO so the real evaluator populates the
        # dstack_slo_* gauge families below
        run_spec = json.dumps({"configuration": {
            "type": "service",
            "slo": {"objectives": [
                {"metric": "p95_ttft_ms", "target": 200},
                {"metric": "availability", "target": 0.99},
            ], "fast_window": 600, "slow_window": 3600},
        }})
        await db.insert("runs", id=rid, project_id=prow["id"],
                        user_id=urow["id"], run_name="ci-run",
                        run_spec=run_spec,
                        status="running", submitted_at=dbm.now())
        await db.insert("jobs", id=jid, run_id=rid, project_id=prow["id"],
                        run_name="ci-run", status="running", job_spec="{}",
                        submitted_at=dbm.now())
        # scraped custom metrics incl. a label value that needs escaping and
        # a histogram family — the republish hot spots
        now = dbm.now()
        rows = [
            ("steps_total", "counter", {"phase": 'tr"ain\\x'}, 17.0),
            ("loss", "gauge", {}, 1.5),
            ("lat_bucket", "histogram", {"le": "0.5"}, 2.0),
            ("lat_bucket", "histogram", {"le": "+Inf"}, 3.0),
            ("lat_sum", "histogram", {}, 0.8),
            ("lat_count", "histogram", {}, 3.0),
        ]
        for name, mtype, labels, value in rows:
            await db.insert("job_prometheus_metrics", job_id=jid,
                            collected_at=now, name=name, type=mtype,
                            labels=json.dumps(labels, sort_keys=True),
                            value=value)
        # per-job resource point + lifecycle span so every /metrics section
        # renders
        await db.insert("job_metrics_points", job_id=jid,
                        timestamp_micro=int(now * 1e6),
                        memory_usage_bytes=1 << 30)
        run_row = await db.fetchone("SELECT * FROM runs WHERE id=?", (rid,))
        await spans.run_span(app["ctx"], run_row,
                             spans.RUN_PROVISIONING_PHASE, 12.5)
        job_row = await db.fetchone("SELECT * FROM jobs WHERE id=?", (jid,))
        await spans.job_transition(app["ctx"], job_row, "terminating")

        # SLO substrate: seed degraded latency history, run the REAL
        # evaluator (burn gauges + an alerts row), and tick the scraper
        # drop counters — every new /metrics family must render and parse
        from dstack_tpu.server.services import slo as slo_svc
        from dstack_tpu.server.services import timeseries

        snap = {"buckets": [[0.1, 0], [0.25, 5], [0.5, 100],
                            ["+Inf", 100]], "sum": 40.0, "count": 100}
        await timeseries.record(app["ctx"], [
            {"project_id": prow["id"], "run_name": "ci-run",
             "name": "ttft_seconds", "ts": now - off, "hist": snap}
            for off in (5, 60, 600)
        ])
        slo_stats = await slo_svc.evaluate(app["ctx"])
        assert slo_stats["fired"] >= 1, slo_stats
        app["ctx"].scrape_stats["errors"] += 2
        app["ctx"].scrape_stats["dropped_samples"] += 7

        r = await client.get("/metrics", headers=h)
        assert r.status == 200, f"/metrics returned {r.status}"
        text = await r.text()
        samples = exposition.parse(text, strict=True)  # raises on any defect
        names = {s.name for s in samples}
        for required in (
            "dstack_runs",
            "dstack_job_memory_usage_bytes",
            "dstack_run_provisioning_duration_seconds_count",
            "dstack_job_phase_duration_seconds_count",
            "steps_total",
            "lat_bucket",
            "dstack_slo_burn_rate",
            "dstack_slo_error_budget_remaining",
            "dstack_alerts_firing",
            "dstack_control_scrape_errors_total",
            "dstack_control_scrape_dropped_samples_total",
        ):
            assert required in names, f"/metrics is missing {required}"
        burn = [s for s in samples if s.name == "dstack_slo_burn_rate"
                and s.labels.get("objective") == "p95_ttft_ms"]
        assert burn and burn[0].value > 0, "ttft burn rate not exported"
        assert burn[0].labels["project"] == "ci"
        firing = [s for s in samples if s.name == "dstack_alerts_firing"
                  and s.labels.get("run") == "ci-run"]
        assert firing and firing[0].value >= 1, "firing alert not exported"
        errs = [s for s in samples
                if s.name == "dstack_control_scrape_errors_total"]
        assert errs and errs[0].value == 2, "scrape error counter wrong"
        republished = [s for s in samples if s.name == "steps_total"][0]
        assert republished.labels["project"] == "ci", republished.labels
        assert republished.labels["run"] == "ci-run"
        assert republished.labels["phase"] == 'tr"ain\\x'  # escape round-trip
        assert republished.type == "counter"
        print(f"OK: /metrics emitted {len(samples)} well-formed samples "
              f"({len(names)} series names), identity labels + escaping "
              "verified")
    finally:
        await client.close()
        db.close()
    return await check_serving_metrics()


async def check_serving_metrics() -> int:
    """Compute-plane half of the gate: the serving server's /metrics must
    strict-parse and /stats must report ordered percentiles.  A stub
    engine (no JAX, no weights) keeps this instant — only the telemetry
    and rendering layers are under test."""
    from aiohttp.test_utils import TestClient, TestServer

    from dstack_tpu.server.telemetry import exposition
    from dstack_tpu.serving.server import ServingApp
    from dstack_tpu.telemetry.serving import EngineTelemetry
    from dstack_tpu.telemetry.tracing import RequestTracer

    tracer = RequestTracer()
    tel = EngineTelemetry(tracer=tracer)
    trace_id = None
    # a finished span + trace so /traces has real content to gate
    with tracer.start_span("replica.request",
                           attrs={"path": "/v1/completions"}) as span:
        trace_id = span.trace_id
    tracer.finish_trace(trace_id, span.duration, error=True)  # retained
    # one observation through every recording path the engine exercises
    tel.record_queue_depth(3)
    tel.record_admitted(0.002, trace_id=trace_id)
    tel.record_first_token(0.04, trace_id=trace_id)
    tel.record_prefill(100, 128)
    tel.record_window(6, 8)
    tel.record_window_chain(None)
    tel.record_window_chain("admission")
    tel.record_phase("pull", 0.4)
    tel.record_drain(64, 0.5, steps=64, batch_size=8)
    tel.record_kv_utilization(0.4)
    tel.record_prefill_backlog(512)
    tel.record_preemption("kv_blocks_exhausted")
    tel.record_program_built("decode")
    tel.record_expert_load(200.0, 600.0, 9.0, 2.0, 90.0, 1024.0)
    tel.record_recurrent_state_bytes(1 << 20)
    tel.record_loop_passes(256.0, [0.0, 3.0, 0.0, 500.0])
    tel.record_ssm_steps(64 * 4 * 300.0)
    tel.record_ssm_scan_chunks(4 * 4)
    tel.record_kv_geometry(192, 1572864)

    class _Req:
        submitted_at = 1.0
        admitted_at = 1.002
        first_token_at = 1.04
        finished_at = 2.0
        finish_reason = "stop"
        output = list(range(64))

    tel.record_finished(_Req())

    class _StubEngine:
        telemetry = tel
        batch_size = 8  # capacity_slots in the /load snapshot

        def run_forever(self):  # the app's engine-thread target
            pass

    class _Tok:
        eos_id = None

    serving = ServingApp(_StubEngine(), _Tok())
    client = TestClient(TestServer(serving.make_app()))
    await client.start_server()
    try:
        r = await client.get("/metrics")
        assert r.status == 200, f"serving /metrics returned {r.status}"
        text = await r.text()
        samples = exposition.parse(text, strict=True)  # raises on defects
        names = {s.name for s in samples}
        # one entry per family EngineTelemetry records — wirelint DT906
        # cross-checks this tuple against telemetry/serving.py, so a
        # family added there without a gate entry (or vice versa) fails
        # static analysis before this script ever runs
        for required in (
            "dstack_serving_ttft_seconds_bucket",
            "dstack_serving_queue_wait_seconds_count",
            "dstack_serving_inter_token_seconds_sum",
            "dstack_serving_e2e_seconds_count",
            "dstack_serving_batch_occupancy_bucket",
            "dstack_serving_kv_utilization",
            "dstack_serving_kv_utilization_peak",
            "dstack_serving_active_slots",
            "dstack_serving_queue_depth",
            "dstack_serving_prefill_backlog_tokens",
            "dstack_serving_prefill_tokens_total",
            "dstack_serving_prefill_chunks_total",
            "dstack_serving_prefill_chunk_steps_total",
            "dstack_serving_prefill_budget_exhausted_total",
            "dstack_serving_decode_tokens_total",
            "dstack_serving_decode_steps_total",
            "dstack_serving_decode_slot_steps_total",
            "dstack_serving_engine_phase_seconds_total",
            "dstack_serving_engine_phases_total",
            "dstack_serving_windows_dispatched_ahead_total",
            "dstack_serving_engine_slot_update_programs_total",
            "dstack_serving_engine_slot_updates_total",
            "dstack_serving_window_chain_breaks_total",
            "dstack_serving_programs_built_total",
            "dstack_serving_preemptions_total",
            "dstack_serving_moe_pairs_total",
            "dstack_serving_moe_expert_load_max_sum",
            "dstack_serving_moe_expert_load_mean_sum",
            "dstack_serving_moe_experts_touched_sum",
            "dstack_serving_moe_rows_computed_total",
            "dstack_serving_recurrent_state_bytes",
            "dstack_serving_loop_passes_total",
            "dstack_serving_loop_exit_tokens_total",
            "dstack_serving_ssm_slot_layer_steps_total",
            "dstack_serving_ssm_scan_chunks_total",
            "dstack_serving_kv_cache_layers",
            "dstack_serving_kv_bytes_per_token",
            "dstack_serving_requests_total",
        ):
            assert required in names, f"serving /metrics missing {required}"
        # every histogram family must close with a +Inf bucket
        for s in samples:
            if s.name.endswith("_bucket"):
                assert "le" in s.labels, s.name
        # the CLASSIC page must be exemplar-free: the classic text format
        # has no exemplar syntax, and a trailing "# {...}" would break
        # every non-OpenMetrics Prometheus scraper pointed here
        for line in text.splitlines():
            assert " # " not in line, f"exemplar on classic page: {line!r}"
        # OpenMetrics negotiation: exemplars appear, strict-parse, and
        # reference the REAL trace id recorded on the TTFT observation
        r = await client.get(
            "/metrics",
            headers={"Accept": "application/openmetrics-text"})
        assert r.status == 200
        om_text = await r.text()
        assert om_text.rstrip().endswith("# EOF"), "OpenMetrics needs # EOF"
        om_samples = exposition.parse(om_text, strict=True)
        ttft_ex = [
            s for s in om_samples
            if s.name == "dstack_serving_ttft_seconds_bucket"
            and s.exemplar is not None
        ]
        assert ttft_ex, "TTFT buckets carry no exemplars on OpenMetrics"
        for s in ttft_ex:
            ex = s.exemplar
            assert ex["labels"].get("trace_id") == trace_id, ex
            assert isinstance(ex["value"], float), ex
        # /traces: strict shape, gated exactly like /load (a drifted
        # payload breaks the gateway stitcher and the server persister)
        r = await client.get("/traces")
        assert r.status == 200, f"/traces returned {r.status}"
        traces = await r.json()
        assert set(traces) == {"traces", "ring_spans", "retained_traces",
                               "finished_traces"}, sorted(traces)
        assert traces["retained_traces"] >= 1  # the error trace is kept
        entry_shape = {
            "trace_id": str, "spans": int, "start": (int, float),
            "duration_ms": (int, float), "status": str,
        }
        for entry in traces["traces"]:
            assert set(entry) == set(entry_shape) | {"retained"}, entry
            for key, want in entry_shape.items():
                assert isinstance(entry[key], want) and not isinstance(
                    entry[key], bool), (key, entry)
            assert entry["retained"] in (None, "error", "slow", "sampled")
        r = await client.get(f"/traces/{trace_id}")
        assert r.status == 200
        detail = await r.json()
        assert detail["trace_id"] == trace_id
        span_shape = {"trace_id", "span_id", "parent_id", "name", "start",
                      "duration", "status", "attrs"}
        for s in detail["spans"]:
            assert set(s) == span_shape, sorted(s)
        r = await client.get("/traces/" + "0" * 32)
        assert r.status == 404
        r = await client.get("/stats")
        assert r.status == 200
        stats = await r.json()
        for name, p in stats["percentiles"].items():
            assert p["p50"] <= p["p95"] <= p["p99"], (name, p)
        # the load-header piggyback rides EVERY response (gateway's
        # passive load feed) and must round-trip the snapshot exactly
        from dstack_tpu.telemetry.serving import (
            LOAD_HEADER_PREFIX,
            parse_load_headers,
        )

        hdr_snap = parse_load_headers(r.headers)
        assert hdr_snap is not None, (
            f"/stats response lacks {LOAD_HEADER_PREFIX}* headers")
        # /load: strict shape — exactly the documented keys, right types,
        # sane ranges (a drifted payload breaks every load-aware gateway)
        r = await client.get("/load")
        assert r.status == 200, f"/load returned {r.status}"
        load = await r.json()
        shape = {
            "active_slots": int, "queue_depth": int,
            "prefill_backlog_tokens": int, "capacity_slots": int,
            "kv_utilization": (int, float), "load": (int, float),
            # drain-and-migrate: 1 once /drain flipped the replica — the
            # gateway stops routing NEW work there on the next header/poll
            "draining": int,
            # elasticity: 1 while still compiling/warming or an
            # unactivated standby — healthy but not routable capacity
            "warming": int,
        }
        # compile_cache_* counters join the payload only when the cache
        # is configured — this stub engine runs without one
        assert set(load) == set(shape), (
            f"/load keys drifted: {sorted(load)} != {sorted(shape)}")
        for key, want in shape.items():
            assert isinstance(load[key], want) and not isinstance(
                load[key], bool), (key, load[key])
            assert load[key] >= 0, (key, load[key])
        assert 0.0 <= load["kv_utilization"] <= 1.0, load
        for field in ("active_slots", "queue_depth", "kv_utilization",
                      "prefill_backlog_tokens", "capacity_slots",
                      "draining", "warming"):
            assert hdr_snap[field] == load[field], (field, hdr_snap, load)
        print(f"OK: serving /metrics emitted {len(samples)} well-formed "
              f"samples ({len(names)} series names); /stats percentiles "
              "ordered; /load shape + load-header round-trip verified; "
              "OpenMetrics exemplars + /traces shape gated")
        return 0
    finally:
        await client.close()


if __name__ == "__main__":
    sys.exit(asyncio.run(main()))
