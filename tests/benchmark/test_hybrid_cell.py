"""A whole run of a hybrid-decoder cell on the CPU at a toy size
(``fixture_hybrid``: half of 16 experts held), through ``run_cell`` and the
benchmark's own reference file, as ``test_benchmark.py`` rehearses the dense
one.  No number from here is a device number."""

import io
import json
from pathlib import Path

from benchmarks.harness.cell import passes, run_cell

FIXTURE = Path(__file__).resolve().parent / "fixture_hybrid"


def _rehearse(seed, trace=False, control=False):
    out, err = io.StringIO(), io.StringIO()
    result = run_cell(FIXTURE, "tiny-hybrid.closed", seed, 2.0, trace,
                      allow_cpu=True, out=out, err=err, control=control)
    lines = [json.loads(line) for line in out.getvalue().strip().splitlines()]
    infos = {k: v for line in lines[:-1] for k, v in line["info"].items()}
    return lines[-1], err.getvalue(), infos


def test_whole_run_of_the_hybrid_cell_is_correct():
    last, err, infos = _rehearse(2**31 + 11)
    assert last["correct"] is True and last["failed"] == 0
    assert last["attempted"] > 3
    assert set(last["metrics"]) == {"output_tokens_per_s", "setup_s"}
    assert infos["comparison"]["tokens"] > 100
    assert infos["comparison"]["mismatches"] == 0
    assert err.strip().splitlines()[-1] == "correct: True"


def test_traced_run_reads_the_expert_counters():
    last, _, _ = _rehearse(12, trace=True)
    assert last["correct"] is True
    assert last["checks"]["programs_built_in_window"] == {"value": 0,
                                                          "limit": 0}
    metrics = {k: v["value"] for k, v in last["metrics"].items()}
    assert set(metrics) == {"batch_occupancy.closed", "moe_held_share.closed",
                            "expert_load_imbalance.closed",
                            "kv_peak_utilization.closed"}
    # 8 of 16 experts held: about half of the pairs, never all or none
    assert 25 < metrics["moe_held_share.closed"] < 75
    assert metrics["expert_load_imbalance.closed"] >= 1.0
    assert 0 < metrics["kv_peak_utilization.closed"] <= 100


def test_the_lower_precision_control_fails_the_hybrid_comparison():
    last, err, infos = _rehearse(13, control=True)
    assert last["correct"] is False and last["failed"] == 0
    assert err.strip().splitlines()[-1] == "correct: False"
    mean = last["checks"]["served_gap_mean"]
    assert mean["value"] > 3 * mean["limit"]
    program = infos["comparison"]
    assert program["mean_gap"] <= mean["limit"]
    assert program["gap"] <= last["checks"]["served_gap"]["limit"]
    assert all(passes(c) for name, c in last["checks"].items()
               if not name.startswith("served_gap"))


def _decode_trace(per_step, cut_steps=10.5, whole_steps=4):
    """One chip, 1 ms a step: a window cut by the span's start that shows
    ``cut_steps`` of its steps, then a whole 4-step window, then a prefill.
    A step runs ``per_step`` instructions one after another, the last of
    them twice (an inner loop), and the window one more outside its loop."""
    ms = 1_000_000
    body = [f"%fusion.{k} = f32[8]{{0}} fusion(...)" for k in range(per_step)]
    body.append(body[-1])
    ops = []

    def window(start, steps):
        slot = ms // len(body)
        for i, t in enumerate(range(int(steps * len(body)))):
            # a cut window shows the END of its first step
            name = body[(len(body) - int(steps * len(body)) + i) % len(body)]
            ops.append((name, start + t * slot, slot))
        ops.append(("%scatter.9 = bf16[64]{0} scatter(...)",
                    start + int(steps * ms), 1000))

    window(0, cut_steps)
    whole = int(cut_steps * ms) + 2 * ms
    window(whole, whole_steps)
    modules = [("jit_decode_w64_s0_kb64(5)", -40 * ms,
                40 * ms + int(cut_steps * ms) + 1000),
               ("jit_prefill_paged_b256(7)", whole - ms, ms // 2),
               (f"jit_decode_w{whole_steps}_s0_kb64(3)", whole - 1000,
                whole_steps * ms + 3000)]
    ops.append(("%dot.1 = bf16[8]{0} dot(...)", whole - ms, ms // 2))
    ops.append(("%copy.1 = s32[1]{0} copy(...)", whole + 5 * ms, 1000))
    return {"devices": [{"name": "/device:TPU:0", "lines": {
        "XLA Modules": modules, "XLA Ops": ops}}], "host": {}}


def test_decode_step_time_counts_steps_by_name_and_by_what_a_cut_window_shows():
    """The steps of a whole window are its name's; a window cut by the
    traced span's edge counts the part inside and the steps shown there,
    whatever a step is made of (40 or 100 instructions, one of them looped)."""
    from benchmarks.layer_metrics.hybrid_decode_trace import decode_step_ms

    for per_step in (40, 100):
        got = decode_step_ms(_decode_trace(per_step))
        assert abs(got - 1.0) < 0.01, got
    lone = _decode_trace(40)
    lone["devices"][0]["lines"]["XLA Modules"].pop()     # the cut one alone
    assert abs(decode_step_ms(lone) - 1.0) < 0.02
    other = _decode_trace(40)
    other["devices"][0]["lines"]["XLA Modules"] = [
        ("jit_prefill_paged_b256(7)", 0, 1000)]
    assert decode_step_ms(other) is None
    assert decode_step_ms({"devices": [], "host": {}}) is None
    assert decode_step_ms(None) is None
