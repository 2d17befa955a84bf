"""The readers of what the program says about itself (its ``engine.*`` spans,
its program names, its decode and KV counters), each on a hand-made trace and
counter pair, and one CPU rehearsal with the new entries appended to a copy
of the fixture.  No number from here is a device number."""

import io
import json
import shutil
from pathlib import Path
from types import SimpleNamespace

import pytest

ROOT = Path(__file__).resolve().parents[2]
FIXTURE = Path(__file__).resolve().parent / "fixture"
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())

from benchmarks.harness import program_spans  # noqa: E402
from benchmarks.harness.cell import Files, run_cell  # noqa: E402

MS = 1_000_000
NEW = ("decode_steps_per_s", "decode_useful_share", "prefill_wait_p95_ms",
       "prefill_device_share", "kv_peak_utilization", "idle_in_admission")


def _reader(name):
    return Files(ROOT, SPEC).reader("layer_metrics", name)


def _trace(spans=True, prefill_name="jit_prefill_paged_b256(7)"):
    """100 ms on one chip: a decode window, 20 ms of nothing, a prefill
    program, 10 ms of nothing, the next window.  The host admits from 45 to
    75 ms: the first idle gap is half inside that span, the second whole."""
    host = {"python3": [("np.asarray(jax.Array)", 0, 41 * MS)]}
    if spans:
        host["python3"] += [
            ("engine.pull", 0, 41 * MS), ("engine.emit", 41 * MS, 4 * MS),
            ("engine.admit", 45 * MS, 30 * MS),
            ("engine.prefill", 46 * MS, 28 * MS),
            ("engine.dispatch_window", 75 * MS, 2 * MS)]
    return {
        "devices": [{"name": "/device:TPU:0", "lines": {
            "XLA Modules": [("jit_decode_w64_s0_kb24(3)", 0, 40 * MS),
                            (prefill_name, 60 * MS, 10 * MS),
                            ("jit_decode_w64_s0_kb24(3)", 80 * MS, 20 * MS)],
            "XLA Ops": [("%while.1", 0, 40 * MS), ("%fusion.3", 5 * MS, MS),
                        ("%dot.7", 60 * MS, 10 * MS),
                        ("%while.1", 80 * MS, 20 * MS)]}}],
        "host": host,
    }


def _run(trace=None, t0=None, t1=None, requests=()):
    return SimpleNamespace(trace=trace, t0=10.0, t1=40.0,
                           counters={"t0": t0 or {}, "t1": t1 or {}},
                           requests=list(requests))


def test_interval_arithmetic():
    assert program_spans.complement([[2, 4], [6, 9]], 0, 10) == \
        [[0, 2], [4, 6], [9, 10]]
    assert program_spans.complement([[0, 10]], 0, 10) == []
    assert program_spans.complement([], 3, 5) == [[3, 5]]
    assert program_spans.complement([[0, 4], [8, 20]], 2, 10) == [[4, 8]]
    assert program_spans.overlap_ns([[0, 5], [10, 20]], [[3, 12], [19, 30]]) \
        == 2 + 2 + 1
    assert program_spans.overlap_ns([], [[0, 9]]) == 0
    trace = _trace()
    assert program_spans.host_spans(trace, "engine.admit") == \
        [[45 * MS, 75 * MS]]
    assert program_spans.host_spans(trace, "engine.chunk") == []
    assert program_spans.host_spans(trace, "engine.emit", "engine.admit",
                                    "engine.prefill") == [[41 * MS, 75 * MS]]
    assert program_spans.idle_intervals(trace, trace["devices"][0]) == \
        [[40 * MS, 60 * MS], [70 * MS, 80 * MS]]


def test_idle_in_admission_is_the_part_of_idle_under_the_admit_spans():
    from benchmarks.harness.trace_reduce import idle_share

    trace = _trace()
    whole = 100.0 * idle_share(trace)
    part = _reader("idle_in_admission.chat").read(_run(trace))
    assert whole == pytest.approx(30.0)
    # 15 of the first gap's 20 ms and 5 of the second's 10
    assert part == pytest.approx(20.0) and part <= whole
    # the profiler drops a span that crosses an edge of the traced window:
    # with the admission pass cut, the per-request spans inside it remain
    cut = _trace()
    cut["host"]["python3"] = [e for e in cut["host"]["python3"]
                              if e[0] != "engine.admit"]
    assert _reader("idle_in_admission.chat").read(_run(cut)) == \
        pytest.approx(14.0 + 4.0)
    # a program with spans and no admission in the traced span reads 0 ...
    quiet = _trace()
    quiet["host"]["python3"] = [e for e in quiet["host"]["python3"]
                                if e[0] not in ("engine.admit",
                                                "engine.prefill")]
    assert _reader("idle_in_admission.chat").read(_run(quiet)) == 0.0
    # ... a program without spans (the parent commit) has nothing to read
    assert _reader("idle_in_admission.chat").read(_run(_trace(False))) is None
    assert _reader("idle_in_admission.chat").read(_run(None)) is None


def test_prefill_device_share_reads_the_programs_named_prefill():
    read = _reader("prefill_device_share.chat").read
    assert read(_run(_trace())) == pytest.approx(10.0)
    chunk = _trace(prefill_name="jit_prefill_prefix_b512(9)")
    assert read(_run(chunk)) == pytest.approx(10.0)
    # the parent commit's programs: jit_fn and jit__unknown
    assert read(_run(_trace(prefill_name="jit_fn(7)"))) is None
    assert read(_run(None)) is None
    assert read(_run({"devices": [], "host": {}})) is None


def test_the_gap_labels_of_the_breakdown_carry_the_engines_spans():
    """The accepted reducer gives a gap to the host event that covers most
    of it: with the engine's spans on the host line, an ``engine.*`` name
    where the runtime's own events (a pull, an execute call) cover less."""
    from benchmarks.harness.trace_reduce import breakdown

    trace = _trace()
    gaps = breakdown(trace)["idle_gaps"]
    assert gaps == [["python3: engine.admit", pytest.approx(0.020)],
                    ["python3: engine.admit", pytest.approx(0.010)]]


def test_counter_readers_on_a_hand_made_counter_pair():
    steps, slots = "dstack_serving_decode_steps_total", \
        "dstack_serving_decode_slot_steps_total"
    t0 = {steps: 640.0, slots: 640.0 * 32,
          "dstack_serving_decode_tokens_total": 9000.0,
          "dstack_serving_kv_utilization_peak": 0.5}
    t1 = {steps: 640.0 + 512, slots: (640.0 + 512) * 32,
          "dstack_serving_decode_tokens_total": 9000.0 + 8192,
          "dstack_serving_kv_utilization_peak": 0.875}
    run = _run(t0=t0, t1=t1)
    assert _reader("decode_steps_per_s.chat").read(run) == \
        pytest.approx(512 / 30.0)
    assert _reader("decode_useful_share.batch").read(run) == \
        pytest.approx(50.0)
    assert _reader("kv_peak_utilization.chat").read(run) == \
        pytest.approx(87.5)
    # the parent commit's telemetry has none of the three
    old = _run(t0={"dstack_serving_decode_tokens_total": 1.0},
               t1={"dstack_serving_decode_tokens_total": 9.0})
    for name in ("decode_steps_per_s.chat", "decode_useful_share.chat",
                 "kv_peak_utilization.batch"):
        assert _reader(name).read(old) is None


def test_prefill_wait_reads_the_requests_own_stamps():
    def rec(admitted, first):
        return SimpleNamespace(handle=SimpleNamespace(
            admitted_at=admitted, first_token_at=first))

    requests = [rec(100.0, 100.0 + k / 10) for k in range(1, 21)]
    requests += [rec(None, None), SimpleNamespace(handle=None)]
    read = _reader("prefill_wait_p95_ms.chat").read
    assert read(_run(requests=requests)) == pytest.approx(1905.0)
    assert read(_run(requests=[rec(None, None)])) is None
    assert read(_run()) is None


@pytest.mark.parametrize("name", NEW)
def test_new_metric_is_declared_for_the_cells_its_reader_can_read(name):
    entries = [m for m in SPEC["per_layer"]
               if m["name"].rsplit(".", 1)[0] == name]
    cells = {c for m in entries for c in m["workloads"]}
    chat_only = name in ("prefill_wait_p95_ms", "idle_in_admission")
    assert cells == ({"smollm2-1.7b.chat"} if chat_only else
                     {"smollm2-1.7b.chat", "mistral-7b-v0.3-16l.batch"})
    traffic = {w["name"]: w["traffic"] for w in SPEC["workloads"]}
    for m in entries:
        assert m["moves"] == "output_tokens_per_s"
        assert [m["name"]] == [f"{name}.{traffic[c]}" for c in m["workloads"]]
        assert hasattr(_reader(m["name"]), "read")
    # appended: the accepted entries stand where they stood
    assert [m["name"] for m in SPEC["per_layer"][:13]][-1] == \
        "paged_attn_roofline.batch"


def test_cpu_rehearsal_reports_the_counters_and_leaves_the_device_out(
        tmp_path):
    """A copy of the fixture with the new entries appended: what the
    program counts is reported on any device, what only a device trace can
    say is left out, never 0."""
    root = tmp_path / "fixture"
    shutil.copytree(FIXTURE, root)
    spec = json.loads((root / "BENCHMARK.json").read_text())
    cell = "tiny-dense.closed"
    for name in NEW:
        template = next(m for m in SPEC["per_layer"]
                        if m["name"].startswith(name + "."))
        spec["per_layer"].append({**template, "name": f"{name}.closed",
                                  "workloads": [cell]})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    out, err = io.StringIO(), io.StringIO()
    result = run_cell(root, cell, 11, 2.0, True, allow_cpu=True, out=out,
                      err=err)
    metrics = {k: v["value"] for k, v in result["metrics"].items()}
    assert set(metrics) == {
        "batch_occupancy.closed", "decode_steps_per_s.closed",
        "decode_useful_share.closed", "prefill_wait_p95_ms.closed",
        "kv_peak_utilization.closed"}
    assert result["correct"] is True
    assert metrics["decode_steps_per_s.closed"] > 0
    assert 0 < metrics["decode_useful_share.closed"] <= 100
    assert 0 < metrics["kv_peak_utilization.closed"] <= 100
    assert metrics["prefill_wait_p95_ms.closed"] >= 0
