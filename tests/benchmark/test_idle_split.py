"""The split of ``device_idle_share`` by what the host was doing and the two
readers of the engine thread's whole window: each on a hand-made trace or
counter pair, the identity of the split to the nanosecond, the twelve entries
where they were appended, and one CPU rehearsal.  No number from here is a
device number."""

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
from benchmarks.harness.trace_reduce import idle_share  # noqa: E402

MS = 1_000_000
TRACE_READERS = ("idle_in_chunks", "idle_in_handover", "idle_unattributed",
                 "idle_inside_programs")
COUNTER_READERS = ("host_busy_share", "window_ahead_share")
SECONDS = "dstack_serving_engine_phase_seconds_total{phase=%s}"
AHEAD = "dstack_serving_windows_dispatched_ahead_total"
WINDOWS = "dstack_serving_batch_occupancy_count{phase=decode}"


def _reader(name):
    return Files(ROOT, SPEC).reader("layer_metrics", name)


def _trace(cut=(), spans=True):
    """100 ms on one chip.  A decode window whose last operation starts
    2 ms after its loop ends (30-32: idle INSIDE a program), 10 ms of
    nothing, a chunk's program, 20 ms of nothing, the next window.  The host
    pulls and emits the first window (to 44), is outside every phase for
    1 ms, sends the chunk and activates its prompt (45-52), admits two
    requests (53-78, prefills 54-66 and 67-77), dispatches the window
    (78-80) and goes into a pull that crosses the trace's end and is
    dropped.  ``cut`` drops further spans by name, as an edge would."""
    host = [("np.asarray(jax.Array)", 0, 41 * MS)]
    if spans:
        host += [
            ("engine.pull", 0, 41 * MS), ("engine.emit", 41 * MS, 3 * MS),
            ("engine.chunk", 45 * MS, 7 * MS),
            ("engine.first_token", 48 * MS, 3 * MS),
            ("engine.admit", 53 * MS, 25 * MS),
            ("engine.prefill", 54 * MS, 12 * MS),
            ("engine.first_token", 62 * MS, 3 * MS),
            ("engine.prefill", 67 * MS, 10 * MS),
            ("engine.first_token", 73 * MS, 3 * MS),
            ("engine.dispatch_window", 78 * MS, 2 * MS),
            ("engine.build_program", 78 * MS + 500_000, MS),
            ("engine.wait_for_work", 81 * MS, MS)]
    return {
        "devices": [{"name": "/device:TPU:0", "lines": {
            "XLA Modules": [("jit_decode_w64_s0_kb64(3)", 0, 40 * MS),
                            ("jit_prefill_prefix_b512(9)", 50 * MS, 10 * MS),
                            ("jit_decode_w64_s0_kb64(3)", 80 * MS, 20 * MS)],
            "XLA Ops": [("%while.1", 0, 30 * MS), ("%fusion.9", 32 * MS, 8 * MS),
                        ("%dot.7", 50 * MS, 10 * MS),
                        ("%while.1", 80 * MS, 20 * MS)]}}],
        "host": {"python3": [e for e in host if e[0] not in cut]},
    }


def _run(trace=None, t0=None, t1=None):
    return SimpleNamespace(trace=trace, t0=10.0, t1=40.0,
                           counters={"t0": t0 or {}, "t1": t1 or {}})


def _read(name, run):
    return _reader(name).read(run)


def test_each_trace_reader_by_hand():
    run = _run(_trace())
    assert program_spans.idle_intervals(run.trace, run.trace["devices"][0]) \
        == [[30 * MS, 32 * MS], [40 * MS, 50 * MS], [60 * MS, 80 * MS]]
    # under the pull 30-32 and 40-41, the emit 41-44, the dispatch 78-80
    assert _read("idle_in_handover.chat", run) == pytest.approx(8.0)
    # 45-50 of the chunk span: its program starts at 50
    assert _read("idle_in_chunks.batch", run) == pytest.approx(5.0)
    # 44-45: between the emit and the chunk, outside every phase
    assert _read("idle_unattributed.chat", run) == pytest.approx(1.0)
    # the 2 ms between the first window's two operations
    assert _read("idle_inside_programs.batch", run) == pytest.approx(2.0)
    assert _read("idle_in_admission.chat", run) == pytest.approx(18.0)


@pytest.mark.parametrize("cut,admission,unattributed", [
    ((), 18.0, 1.0),
    # the admission pass crossed an edge: its prefills remain, and what lay
    # between them (66-67, 77-78) is under no span
    (("engine.admit",), 16.0, 3.0),
    # every chunk span dropped: its 5 ms are unattributed, nobody else's
    (("engine.chunk", "engine.first_token"), 18.0, 6.0),
], ids=["whole", "admit-cut", "chunk-cut"])
def test_the_split_adds_up_to_the_idle_share_to_the_nanosecond(
        cut, admission, unattributed):
    from benchmarks.layer_metrics import idle_split

    trace = _trace(cut)
    run = _run(trace)
    assert _read("idle_in_admission.chat", run) == pytest.approx(admission)
    assert _read("idle_unattributed.chat", run) == pytest.approx(unattributed)
    terms = [("engine.admit", "engine.prefill"), ("engine.chunk",),
             ("engine.pull", "engine.emit", "engine.dispatch_window")]
    device = trace["devices"][0]
    idle = program_spans.idle_intervals(trace, device)
    parts = [program_spans.overlap_ns(
        idle, program_spans.host_spans(trace, *names)) for names in terms]
    parts.append(program_spans.overlap_ns(
        idle, idle_split.outside_every_phase(trace)))
    # wait_for_work (81-82) and the build inside the dispatch hide no idle
    assert sum(parts) == sum(e - s for s, e in idle) == 32 * MS
    whole = 100.0 * idle_share(trace)
    split = sum(_read(f"{name}.chat", run) for name in (
        "idle_in_admission", "idle_in_chunks", "idle_in_handover",
        "idle_unattributed"))
    assert split == pytest.approx(whole) and whole == pytest.approx(32.0)


def test_idle_under_wait_for_work_is_the_one_term_left_out():
    """An engine that ran out of work: the idle time under its
    ``engine.wait_for_work`` is attributed (not ``idle_unattributed``) and
    is no term of the four: the sum falls short by exactly that."""
    trace = _trace()
    trace["host"]["python3"].append(("engine.wait_for_work", 70 * MS, 4 * MS))
    trace["host"]["python3"] = [e for e in trace["host"]["python3"]
                                if e[0] not in ("engine.admit",
                                                "engine.prefill",
                                                "engine.first_token")]
    run = _run(trace)
    assert _read("idle_in_admission.chat", run) == 0.0
    # 60-70 and 74-78 of the second gap, and 44-45
    assert _read("idle_unattributed.chat", run) == pytest.approx(15.0)
    split = sum(_read(f"{name}.chat", run) for name in (
        "idle_in_admission", "idle_in_chunks", "idle_in_handover",
        "idle_unattributed"))
    assert split == pytest.approx(100.0 * idle_share(trace) - 4.0)


@pytest.mark.parametrize("name", TRACE_READERS)
def test_trace_reader_finds_nothing_to_read(name):
    read = _reader(f"{name}.chat").read
    assert read(_run(None)) is None                       # not traced
    assert read(_run({"devices": [], "host": {}})) is None  # no device plane
    bare = read(_run(_trace(spans=False)))    # a program without spans
    if name == "idle_inside_programs":
        assert bare == pytest.approx(2.0)     # the device's own: no span read
    else:
        assert bare is None


def test_idle_inside_programs_needs_the_programs_line():
    trace = _trace()
    del trace["devices"][0]["lines"]["XLA Modules"]
    assert _read("idle_inside_programs.chat", _run(trace)) is None


def test_counter_readers_on_a_hand_made_counter_pair():
    t0 = {SECONDS % "pull": 100.0, SECONDS % "first_token": 5.0,
          SECONDS % "wait_for_work": 1.0, SECONDS % "emit": 7.0,
          AHEAD: 10.0, WINDOWS: 40.0}
    t1 = {SECONDS % "pull": 112.0, SECONDS % "first_token": 8.0,
          SECONDS % "wait_for_work": 1.0, SECONDS % "emit": 20.0,
          AHEAD: 13.0, WINDOWS: 70.0}
    run = _run(t0=t0, t1=t1)
    # the thread waited 12 + 3 + 0 of the window's 30 s
    assert _read("host_busy_share.chat", run) == pytest.approx(50.0)
    # 3 of 30 windows went out ahead of their predecessor's drain
    assert _read("window_ahead_share.batch", run) == pytest.approx(10.0)
    # no window in the 30 s: nothing to take a share of
    still = _run(t0=t1, t1=t1)
    assert _read("window_ahead_share.chat", still) is None
    assert _read("host_busy_share.chat", still) == pytest.approx(100.0)


@pytest.mark.parametrize("name", COUNTER_READERS)
def test_counter_reader_reads_none_from_a_program_without_the_counters(name):
    """The parent commit under this benchmark: ``counter_delta`` gives 0.0
    for a name that is not there, which would read 100% busy and 0% ahead."""
    old = _run(t0={WINDOWS: 40.0, "dstack_serving_decode_tokens_total": 1.0},
               t1={WINDOWS: 70.0, "dstack_serving_decode_tokens_total": 9.0})
    assert _read(f"{name}.chat", old) is None
    assert _read(f"{name}.batch", _run()) is None


def test_the_twelve_entries_are_appended_for_the_two_dense_cells():
    names = [f"{name}.{traffic}" for name in TRACE_READERS + COUNTER_READERS
             for traffic in ("chat", "batch")]
    entries = SPEC["per_layer"][-12:]
    assert [m["name"] for m in entries] == names
    cells = {"chat": "smollm2-1.7b.chat", "batch": "mistral-7b-v0.3-16l.batch"}
    for m in entries:
        assert m["workloads"] == [cells[m["name"].rsplit(".", 1)[1]]]
        assert m["moves"] == "output_tokens_per_s" and m["unit"] == "%"
        assert m["better"] == ("higher" if m["name"].startswith(
            "window_ahead_share") else "lower")
        assert m["layer"] == ("device" if m["name"].startswith(
            "idle_inside_programs") else "scheduler")
        assert hasattr(_reader(m["name"]), "read")
    # the accepted entries stand where they stood
    assert SPEC["per_layer"][-13]["name"] == "paged_attn_roofline.lfm2.reason"
    assert len(SPEC["per_layer"]) == 67 + 12


def test_cpu_rehearsal_reports_the_two_counters_and_no_idle_term(tmp_path):
    """A copy of the fixture with the six entries appended: what the
    program counts about its thread is reported on any device, the idle
    terms need a device trace and are left out, never 0."""
    root = tmp_path / "fixture"
    shutil.copytree(FIXTURE, root)
    spec = json.loads((root / "BENCHMARK.json").read_text())
    cell = "tiny-dense.closed"
    for name in TRACE_READERS + COUNTER_READERS:
        template = next(m for m in SPEC["per_layer"]
                        if m["name"] == f"{name}.chat")
        spec["per_layer"].append({**template, "name": f"{name}.closed",
                                  "workloads": [cell]})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    out, err = io.StringIO(), io.StringIO()
    result = run_cell(root, cell, 11, 2.0, True, allow_cpu=True, out=out,
                      err=err)
    metrics = {k: v["value"] for k, v in result["metrics"].items()}
    assert set(metrics) == {"batch_occupancy.closed",
                            "host_busy_share.closed",
                            "window_ahead_share.closed"}
    assert result["correct"] is True
    assert 0 < metrics["host_busy_share.closed"] <= 100
    assert 0 <= metrics["window_ahead_share.closed"] <= 100
