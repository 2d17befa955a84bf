"""The benchmark's own arithmetic, its files, and one rehearsal of a whole
run on the CPU at a toy size.  No number from here is a device number."""

import io
import json
import os
import re
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
FIXTURE = Path(__file__).resolve().parent / "fixture"
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())

from benchmarks.harness import flops_bytes, loadgen, metrics, trace_reduce  # noqa: E402
from benchmarks.harness.cell import Files, passes, run_cell  # noqa: E402
from benchmarks.harness.sizes import load_config, program_config, sizes_of  # noqa: E402


def _traffic(path):
    return json.loads(Path(path).read_text())


@pytest.mark.parametrize("path,load", [
    (ROOT / "benchmarks/traffic/chat.json", {"clients": 8}),
    (ROOT / "benchmarks/traffic/batch.json", {"clients": 4}),
    (FIXTURE / "cells/traffic/open.json", {"rate_per_s": 5.0})],
    ids=["chat", "batch", "open"])
def test_generator_repeats_for_a_seed_and_permutes_for_another(path, load):
    traffic = dict(_traffic(path))
    fixed_order = traffic.pop("order_seed", 26)
    big = 2**31 + 77          # the driver's seeds pass 32 signed bits
    a = loadgen.plan(traffic, load, 1000, big, 20.0)
    b = loadgen.plan(traffic, load, 1000, big, 20.0)
    c = loadgen.plan(traffic, load, 1000, 3, 20.0)
    assert [r.prompt.tolist() for r in a] == [r.prompt.tolist() for r in b]
    assert [(r.max_new, r.due_rel) for r in a] == \
        [(r.max_new, r.due_rel) for r in b]
    assert [r.prompt.tolist() for r in a] != [r.prompt.tolist() for r in c]
    # another seed: the same SET of prompt lengths, in another order
    assert sorted(len(r.prompt) for r in a) == sorted(len(r.prompt) for r in c)
    assert [len(r.prompt) for r in a] != [len(r.prompt) for r in c]
    lo, hi = traffic["prompt_len"]["min"], traffic["prompt_len"]["max"]
    assert all(lo <= len(r.prompt) <= hi for r in a)
    if traffic["kind"] == "open":
        gaps = np.diff([0.0] + [r.due_rel for r in a])
        assert abs(gaps.mean() - 1 / load["rate_per_s"]) < 1e-9
    else:
        # every round (one request of each client) holds the same lengths
        n = load["clients"]
        rounds = [sorted(len(r.prompt) for r in a[k:k + n])
                  for k in range(0, len(a), n)]
        assert all(r == rounds[0] for r in rounds) and len(rounds) > 20
        assert [r.client for r in a[:2 * n]] == 2 * list(range(n))
    # with the file's own order_seed the order is the file's: seeds differ in
    # token ids alone
    traffic["order_seed"] = fixed_order
    d = loadgen.plan(traffic, load, 1000, big, 20.0)
    e = loadgen.plan(traffic, load, 1000, 3, 20.0)
    assert [(len(r.prompt), r.max_new, r.due_rel) for r in d] == \
        [(len(r.prompt), r.max_new, r.due_rel) for r in e]
    assert [r.prompt.tolist() for r in d] != [r.prompt.tolist() for r in e]


def test_arrival_processes_keep_the_rate_and_differ_in_burstiness():
    poisson = loadgen.arrival_gaps({"process": "poisson"}, 5.0, 4000)
    bursty = loadgen.arrival_gaps({"process": "gamma", "cv": 3.0}, 5.0, 4000)
    for gaps in (poisson, bursty):
        assert gaps.mean() == pytest.approx(0.2)
    assert poisson.std() / poisson.mean() == pytest.approx(1.0, abs=0.05)
    assert bursty.std() / bursty.mean() == pytest.approx(3.0, abs=0.4)
    with pytest.raises(ValueError, match="unknown arrival process"):
        loadgen.arrival_gaps({"process": "weibull"}, 5.0, 10)


def test_timeline_arithmetic_on_a_timeline_with_a_stall():
    # bursts of four tokens 0.1 s apart, one stall of 0.5 s before the third
    stamps = [1.0] * 4 + [1.1] * 4 + [1.6] * 4
    assert metrics.longest_gap(stamps) == pytest.approx(0.5)
    assert metrics.longest_gap([2.0]) == 0.0
    assert metrics.percentile([10, 20, 30, 40, 50], 95) == pytest.approx(48.0)
    assert metrics.percentile([7.0], 95) == 7.0
    # tokens are placed where they were produced: a burst evenly over the
    # time since the request's previous burst, its first token at its stamp
    n = metrics.tokens_in_window([stamps], 1.0, 1.5)
    assert n == pytest.approx(4 + 4 + 4 * (1.5 - 1.1) / 0.5)
    # both edges are pro-rated: a burst that straddles the window's start,
    # and one produced before its end and received after it
    late = metrics.tokens_in_window([[0.5, 1.2, 9.0]], 1.0, 1.5)
    assert late == pytest.approx(0 + 0.2 / 0.7 + 0.3 / 7.8)
    shares = list(metrics.burst_shares([0.5] + [1.2] * 3 + [2.0] * 2, 1.0, 1.5))
    assert shares == [(0, 1, 0.0), (1, 3, pytest.approx(0.2 / 0.7)),
                      (4, 2, pytest.approx(0.3 / 0.8))]
    # a hand-over stamps a request's tokens microseconds apart, never at
    # one instant: they are one burst all the same, standing at its first
    handed = [0.5] + [1.2 + 2e-4 * k for k in range(3)] + [2.0, 2.0003]
    assert [(i, m) for i, m, _ in metrics.burst_shares(handed, 1.0, 1.5)] == \
        [(0, 1), (1, 3), (4, 2)]
    assert metrics.tokens_in_window([handed], 1.0, 1.5) == pytest.approx(
        3 * 0.2 / 0.7 + 2 * 0.3 / 0.8)
    assert metrics.tokens_in_window([stamps], 0.0, 2.0) == 12
    assert metrics.rate(9, 1.0, 1.5) == pytest.approx(18.0)


def test_trace_reduction_on_a_hand_made_trace():
    ms = 1_000_000
    trace = {
        "devices": [{"name": "/device:TPU:0", "lines": {
            "XLA Modules": [("jit_decode(1)", 0, 40 * ms),
                            ("jit_fn(2)", 60 * ms, 20 * ms)],
            # the fusion lies inside the loop that holds it: a union, not a sum
            "XLA Ops": [("while.1", 0, 40 * ms), ("fusion.3", 5 * ms, 10 * ms),
                        ("dot.7", 60 * ms, 20 * ms)]}}],
        "host": {"engine": [("drain", 40 * ms, 18 * ms),
                            ("tracer", 0, 100 * ms)]},
    }
    busy = trace_reduce.busy_and_window(trace)
    assert busy["busy_s"] == pytest.approx(0.060)
    assert busy["window_s"] == pytest.approx(0.100)
    assert trace_reduce.idle_share(trace) == pytest.approx(0.40)
    mods = trace_reduce.events_named(trace, "XLA Modules", "decode")
    assert trace_reduce.total_s(mods) == pytest.approx(0.040)
    down = trace_reduce.breakdown(trace)
    assert down["device_ops"][0] == ["while.1", pytest.approx(0.040)]
    assert down["idle_gaps"][0] == ["engine: tracer", pytest.approx(0.020)]
    assert trace_reduce.union([(5, 9), (0, 3), (2, 4)]) == [[0, 4], [5, 9]]


@pytest.mark.parametrize("entry", SPEC["configs"], ids=lambda e: e["name"])
def test_config_file_loads_into_the_program_at_published_sizes(entry):
    published = {
        "smollm2-1.7b": dict(hidden_size=2048, intermediate_size=8192,
                             num_layers=24, num_heads=32, num_kv_heads=32,
                             head_dim=64, vocab_size=49152,
                             tie_embeddings=True, params=1_711_376_384),
        "mistral-7b-v0.3-16l": dict(hidden_size=4096, intermediate_size=14336,
                                    num_layers=16, num_heads=32,
                                    num_kv_heads=8, head_dim=128,
                                    vocab_size=32768, tie_embeddings=False,
                                    params=3_758_231_552),
    }[entry["name"]]
    config = load_config(ROOT / entry["file"])
    assert config["source"] == entry["source"]
    assert sorted(config["reduced"]) == sorted(entry["reduced"])
    cfg = program_config(config)
    params = published.pop("params")
    for field, value in published.items():
        assert getattr(cfg, field) == value, field
    # the benchmark's own count agrees with the program's and the model card
    sizes = sizes_of(config)
    assert flops_bytes.num_params(sizes) == cfg.num_params() == params
    assert flops_bytes.weight_bytes(sizes) == 2 * params
    # a decode token costs 2 FLOPs per matrix parameter plus its attention
    assert flops_bytes.token_flops(sizes, 0) == \
        2 * flops_bytes.matmul_params(sizes)
    one = flops_bytes.sequence_flops(sizes, 100, 1)
    more = flops_bytes.sequence_flops(sizes, 100, 2)
    assert more - one == pytest.approx(flops_bytes.token_flops(sizes, 101))


def test_benchmark_json_points_at_files_that_exist():
    files = Files(ROOT, SPEC)
    name = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
    e2e = {m["name"]: m for m in SPEC["end_to_end"]}
    for cell in SPEC["workloads"]:
        assert name.match(cell["name"]) and cell["chips"] in (1, 4)
        files.find(f"workloads/{cell['name']}.json")
        files.find(f"traffic/{cell['traffic']}.json")
        reported = [m for m in e2e.values()
                    if cell["name"] in m.get("workloads", [cell["name"]])]
        assert {"setup_s"} < {m["name"] for m in reported}
    for m in SPEC["end_to_end"]:
        assert name.match(m["name"]) and m["bound"] <= 0.1
        assert hasattr(files.reader("end_to_end", m["name"]), "read")
    for m in SPEC["per_layer"]:
        assert name.match(m["name"])
        # one reader file serves every cell: the name without its suffix
        assert hasattr(files.reader("layer_metrics", m["name"]), "read")
        moved = e2e[m["moves"]]
        for cell in m["workloads"]:
            assert cell in moved.get("workloads", [cell])


def test_unknown_device_has_no_peaks():
    from benchmarks.harness.peaks import peaks_for

    assert peaks_for("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError, match="no published peaks"):
        peaks_for("cpu")


def _rehearse(workload, seed, trace=False, control=False):
    out, err = io.StringIO(), io.StringIO()
    result = run_cell(FIXTURE, workload, seed, 2.0, trace, allow_cpu=True,
                      out=out, err=err, control=control)
    lines = [json.loads(line) for line in out.getvalue().strip().splitlines()]
    assert lines[-1] == json.loads(json.dumps(result))
    infos = {k: v for line in lines[:-1] for k, v in line["info"].items()}
    return lines[-1], err.getvalue(), infos


def test_whole_run_on_the_cpu_at_a_toy_size():
    last, err, _ = _rehearse("tiny-dense.open", 2**31 + 5)
    assert list(last)[-1] == "checks" and last["device"]["platform"] == "cpu"
    assert set(last) >= {"correct", "attempted", "failed", "metrics", "device"}
    assert last["correct"] is True and last["failed"] == 0
    assert last["attempted"] > 3
    assert set(last["metrics"]) == {"output_tokens_per_s", "setup_s"}
    assert all(m["value"] > 0 for m in last["metrics"].values())
    assert err.strip().splitlines()[-1] == "correct: True"
    assert "check served_gap:" in err


def test_traced_run_reports_the_layer_metrics_it_can_read():
    last, _, infos = _rehearse("tiny-dense.closed", 11, trace=True)
    # counters and host stamps read on any device; a share of a peak or of a
    # device trace has nothing to read on the CPU and is left out, never 0
    assert set(last["metrics"]) == {"batch_occupancy.closed"}
    assert 0 < last["metrics"]["batch_occupancy.closed"]["value"] <= 100
    assert last["correct"] is True
    assert last["checks"]["programs_built_in_window"] == {"value": 0,
                                                          "limit": 0}
    assert sum(infos["tokens_produced_by_sixth"]) > 0


def test_the_lower_precision_control_fails_the_comparison():
    """The reference in the next lower precision (bfloat16 for this float32
    toy; the int8 grid for the bfloat16 cells) put in the program's place
    goes through the run's own checks and comes out not correct, on the
    sample on which the program itself reads within the limits."""
    last, err, infos = _rehearse("tiny-dense.closed", 11, control=True)
    assert last["correct"] is False and last["failed"] == 0
    assert err.strip().splitlines()[-1] == "correct: False"
    mean = last["checks"]["served_gap_mean"]
    assert mean["value"] > 3 * mean["limit"]
    program = infos["comparison"]
    assert program["tokens"] > 100
    assert program["control"]["mean_gap"] == mean["value"]
    assert program["mean_gap"] <= mean["limit"]
    assert program["gap"] <= last["checks"]["served_gap"]["limit"]
    # nothing but the comparison failed
    assert all(passes(c) for name, c in last["checks"].items()
               if not name.startswith("served_gap"))


def test_int8_grid_of_the_bfloat16_control_has_255_levels():
    from benchmarks.references.dense_decoder import _int8_grid

    x = np.random.default_rng(0).normal(size=(4, 1000)).astype(np.float32)
    g = np.asarray(_int8_grid(x, -1))
    assert all(len(np.unique(row)) <= 255 for row in g)
    assert np.abs(g - x).max() <= np.abs(x).max() / 127 / 2 * 1.001


def test_a_token_altered_where_it_is_produced_is_not_correct(monkeypatch):
    """The rest of a run with the timed path broken underneath: every
    emitted token is moved to its neighbour in the vocabulary."""
    from dstack_tpu.serving.engine import InferenceEngine

    emit = InferenceEngine._emit

    def altered(self, slot_id, req, token):
        return emit(self, slot_id, req, (token + 1) % self.cfg.vocab_size)

    monkeypatch.setattr(InferenceEngine, "_emit", altered)
    last, err, _ = _rehearse("tiny-dense.closed", 12)
    assert last["correct"] is False and last["failed"] == 0
    gap = last["checks"]["served_gap"]
    assert gap["value"] > 3 * gap["limit"]
    assert err.strip().splitlines()[-1] == "correct: False"


def test_run_py_refuses_to_run_off_the_chip():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    done = subprocess.run(
        [sys.executable, str(ROOT / "benchmarks" / "run.py"), "--workload",
         SPEC["workloads"][0]["name"], "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=ROOT, env=env, capture_output=True, text=True,
        timeout=300)
    assert done.returncode != 0
    assert "no accelerator" in done.stderr
    assert '"correct"' not in done.stdout
