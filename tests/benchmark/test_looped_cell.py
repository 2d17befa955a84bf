"""The looped decoder's cell: a whole run on the CPU at a toy size
(``fixture_looped``: 3 layers run 3 times, exit threshold 0.6) through
``run_cell`` and the benchmark's own reference file, the configuration file
at its published sizes, the counts file, and the cell's own readers on
hand-made runs.  No number from here is a device number."""

import io
import json
from pathlib import Path
from types import SimpleNamespace

import pytest

from benchmarks.harness.cell import Files, passes, run_cell
from benchmarks.harness.sizes import load_config, program_config, sizes_of
from benchmarks.references import ouro_looped, ouro_looped_counts as counts

ROOT = Path(__file__).resolve().parents[2]
FIXTURE = Path(__file__).resolve().parent / "fixture_looped"
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
CELL = "ouro-2.6b.chat"


def _rehearse(seed, trace=False, control=False):
    out, err = io.StringIO(), io.StringIO()
    result = run_cell(FIXTURE, "tiny-looped.closed", seed, 2.0, trace,
                      allow_cpu=True, out=out, err=err, control=control)
    lines = [json.loads(line) for line in out.getvalue().strip().splitlines()]
    infos = {k: v for line in lines[:-1] for k, v in line["info"].items()}
    return lines[-1], err.getvalue(), infos


def test_whole_run_of_the_looped_cell_is_correct():
    last, err, infos = _rehearse(2**31 + 11)
    assert last["correct"] is True and last["failed"] == 0
    assert last["attempted"] > 3
    assert set(last["metrics"]) == {"output_tokens_per_s", "setup_s"}
    assert infos["comparison"]["tokens"] > 100
    assert infos["comparison"]["mismatches"] == 0
    assert infos["programs_built_in_window"] == 0
    assert err.strip().splitlines()[-1] == "correct: True"


def test_traced_run_reads_the_loop_counter():
    last, _, _ = _rehearse(12, trace=True)
    assert last["correct"] is True
    metrics = {k: v["value"] for k, v in last["metrics"].items()}
    assert set(metrics) == {
        "batch_occupancy.closed", "loop_passes_per_step.closed",
        "decode_useful_share.closed", "kv_peak_utilization.closed"}
    # every pass ran for every step, whatever pass the gate took (0.6)
    assert metrics["loop_passes_per_step.closed"] == 3.0
    assert 0 < metrics["decode_useful_share.closed"] <= 100


def test_the_lower_precision_control_fails_the_looped_comparison():
    last, err, infos = _rehearse(13, control=True)
    assert last["correct"] is False and last["failed"] == 0
    assert err.strip().splitlines()[-1] == "correct: False"
    for name in ("served_gap", "served_gap_mean"):
        assert last["checks"][name]["value"] > \
            3 * last["checks"][name]["limit"]
    program = infos["comparison"]
    assert program["mean_gap"] <= last["checks"]["served_gap_mean"]["limit"]
    assert all(passes(c) for name, c in last["checks"].items()
               if not name.startswith("served_gap"))


def test_a_token_altered_where_it_is_produced_is_not_correct(monkeypatch):
    from dstack_tpu.serving.engine import InferenceEngine

    emit = InferenceEngine._emit
    monkeypatch.setattr(
        InferenceEngine, "_emit", lambda self, slot_id, req, token: emit(
            self, slot_id, req, (token + 1) % self.cfg.vocab_size))
    last, err, _ = _rehearse(14)
    assert last["correct"] is False and last["failed"] == 0
    gap = last["checks"]["served_gap"]
    assert gap["value"] > 3 * gap["limit"]
    assert err.strip().splitlines()[-1] == "correct: False"


def _entry(kind, name):
    return next(e for e in SPEC[kind] if e["name"] == name)


def test_configuration_file_loads_at_the_published_sizes():
    """What ``test_benchmark.py``'s table test would hold this configuration
    to (its table is inside a file this PR may not edit): the file's keys
    are the catalog row's, nothing is reduced, the program's config takes
    them, and three counts of the parameters agree."""
    entry = _entry("configs", "ouro-2.6b")
    config = load_config(ROOT / entry["file"])
    assert config["source"] == entry["source"]
    assert config["reduced"] == {} and entry["reduced"] == []
    assert {"assumed", "deployment"} <= set(config)
    published = dict(
        head_dim=128, hidden_act="silu", hidden_size=2048,
        intermediate_size=5632, layer_types=["full_attention"] * 48,
        max_position_embeddings=65536, max_window_layers=48,
        model_type="ouro", num_attention_heads=16, num_hidden_layers=48,
        num_key_value_heads=16, rms_norm_eps=1e-06, rope_scaling=None,
        rope_theta=1000000, sliding_window=None, tie_word_embeddings=False,
        total_ut_steps=4, early_exit_threshold=1, use_sliding_window=False,
        vocab_size=49152)
    assert {k: config[k] for k in published} == published
    cfg = program_config(config)
    assert (cfg.num_layers, cfg.ut_steps, cfg.cache_layers,
            cfg.early_exit_threshold) == (48, 4, 192, 1)
    assert (cfg.hidden_size, cfg.intermediate_size, cfg.num_heads,
            cfg.num_kv_heads, cfg.head_dim, cfg.vocab_size,
            cfg.tie_embeddings) == (2048, 5632, 16, 16, 128, 49152, False)
    sizes = sizes_of(config)
    assert counts.num_params(sizes) == cfg.num_params() == 2_667_974_657
    assert ouro_looped._loop() == (4, 1.0)
    assert counts.kv_bytes_per_token(sizes, 4) == 1_572_864


def test_cell_is_the_issue_s():
    cell = _entry("workloads", CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        ("ouro-2.6b", "chat", 1)
    files = Files(ROOT, SPEC)
    load = files.json(f"workloads/{CELL}.json")
    engine = load["engine"]
    assert (engine["batch_size"], load["clients"], engine["max_len"],
            engine["kv_block_size"], engine["paged"],
            engine["prefill_chunk"]) == (8, 8, 2048, 32, True, "tuned")
    assert engine["total_kv_blocks"] > 2048 // 32
    assert (load["settle_s"], load["trace_s"]) == (12.0, 3.0)
    mine = {m["name"]: m for m in SPEC["per_layer"] if CELL in m["workloads"]}
    assert len(mine) == 16 and all(
        name.endswith(".ouro.chat") and m["workloads"] == [CELL]
        and m["moves"] == "output_tokens_per_s" for name, m in mine.items())
    own = {"decode_step_ms", "mfu", "decode_bandwidth_share"}
    for name in mine:
        reader = Path(files.reader("layer_metrics", name).__file__).name
        stem = name[:-len(".ouro.chat")]
        assert reader == (f"{stem}.ouro.py" if stem in own else f"{stem}.py")


def test_counts_of_a_step_and_a_token():
    sizes = sizes_of(load_config(ROOT / "benchmarks/configs/ouro-2.6b.json"))
    matrices = 48 * (4 * 2048 ** 2 + 3 * 2048 * 5632)
    assert counts.layer_matrices(sizes) * 48 == matrices == 2_466_250_752
    # a decode token: 2 FLOPs a matrix parameter a PASS, the head once
    assert counts.decode_token_flops(sizes, 4, 0) == \
        2.0 * (4 * (matrices + 2048) + 49152 * 2048)
    per_context = counts.decode_token_flops(sizes, 4, 1) - \
        counts.decode_token_flops(sizes, 4, 0)
    assert per_context == 4.0 * 192 * 2048
    one = counts.prefill_flops(sizes, 4, 100)
    assert counts.prefill_flops(sizes, 4, 101) - one == pytest.approx(
        counts.body_flops(sizes, 4) + counts.attention_flops(sizes, 4, 101))
    # a step with nothing cached moves the layers four times and the head
    assert counts.decode_step_bytes(sizes, 4, 0) == \
        2 * (4 * matrices + 49152 * 2048)
    assert counts.decode_step_bytes(sizes, 4, 1000) - \
        counts.decode_step_bytes(sizes, 4, 0) == 1000 * 1_572_864
    # one pass is the plain decoder's count of the same sizes
    from benchmarks.harness import flops_bytes

    assert counts.decode_token_flops(sizes, 1, 7) - 2.0 * 2048 == \
        flops_bytes.token_flops(sizes, 7)


KERNEL = ('%paged_decode_attention.10 = (f32[8,16,128]{2,1,0}, f32[8,16]) '
          'custom-call(...), custom_call_target="tpu_custom_call"')


def _looped_trace(step_ns=1_000_000, calls_per_step=12, shown=5.5):
    """One chip: a decode window that began before the traced span and shows
    its last ``shown`` steps, a prefill, then one that the span's end cuts
    after 3 steps.  A step is ``calls_per_step`` kernel calls, evenly
    spaced, and other work between them."""
    ops, call_ns = [], step_ns // calls_per_step
    first_end = int(shown * step_ns)

    def calls(start, end):
        t = end - call_ns
        found = []
        while t >= start:
            found.append((KERNEL, t, call_ns // 2))
            found.append(("%fusion.1 = bf16[8]{0} fusion(...)",
                          t + call_ns // 2, call_ns // 4))
            t -= call_ns
        return found

    ops += calls(0, first_end)
    ops.append(("%dot.1 = bf16[8]{0} dot(...)", first_end, step_ns))
    second = first_end + 2 * step_ns
    ops += [(n, s, d) for n, s, d in calls(second - 61 * step_ns,
                                           second + 3 * step_ns)
            if s >= second]
    modules = [("jit_decode_w64_s0_kb64(5)", first_end - 64 * step_ns,
                64 * step_ns),
               ("jit_prefill_paged_b256(7)", first_end, step_ns),
               ("jit_decode_w64_s0_kb32(3)", second, 64 * step_ns)]
    return {"devices": [{"name": "/device:TPU:0", "lines": {
        "XLA Modules": modules, "XLA Ops": sorted(ops, key=lambda e: e[1])}}],
        "host": {}}


def test_decode_step_time_counts_a_step_as_passes_times_layers_calls():
    from benchmarks.layer_metrics.looped_decode_trace import decode_step_ms

    for calls_per_step in (12, 192):
        got = decode_step_ms(_looped_trace(calls_per_step=calls_per_step),
                             calls_per_step)
        assert got == pytest.approx(1.0, rel=0.02), got
    # the generic count of a step, layers calls, would read 4 x off
    assert decode_step_ms(_looped_trace(calls_per_step=192), 48) == \
        pytest.approx(0.25, rel=0.02)
    prefill_only = _looped_trace()
    prefill_only["devices"][0]["lines"]["XLA Modules"] = [
        ("jit_prefill_paged_b256(7)", 0, 1000)]
    assert decode_step_ms(prefill_only, 12) is None
    no_kernel = _looped_trace()
    no_kernel["devices"][0]["lines"]["XLA Ops"] = [
        e for e in no_kernel["devices"][0]["lines"]["XLA Ops"]
        if "custom_call_target" not in e[0]]
    assert decode_step_ms(no_kernel, 12) is None
    assert decode_step_ms({"devices": [], "host": {}}, 12) is None
    assert decode_step_ms(None, 12) is None


def _run(trace, counters=None, requests=()):
    sizes = sizes_of(load_config(ROOT / "benchmarks/configs/ouro-2.6b.json"))
    zero = {k: 0.0 for k in (counters or {})}
    return SimpleNamespace(
        trace=trace, trace_span=(10.0, 13.0), sizes=sizes, chips=1, slots=8,
        peaks={"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
        t0=0.0, t1=30.0, all_requests=list(requests), requests=list(requests),
        counters={"t0": zero, "t1": counters or {}})


def test_the_cell_s_own_readers_on_hand_made_runs():
    files = Files(ROOT, SPEC)
    read = lambda name, run: files.reader(
        "layer_metrics", f"{name}.ouro.chat").read(run)
    # a 50 ms step of 192 calls; one request holds 1,000 + 2 tokens in the
    # traced span
    trace = _looped_trace(step_ns=50_000_000, calls_per_step=192)
    streaming = SimpleNamespace(prompt=[0] * 1000, stamps=[9.0, 9.001, 20.0])
    run = _run(trace, requests=[streaming])
    assert read("decode_step_ms", run) == pytest.approx(50.0, rel=0.02)
    need = counts.decode_step_bytes(run.sizes, 4, 1002)
    assert need == pytest.approx(19.73e9 + 0.2e9 + 1.576e9, rel=0.01)
    assert read("decode_bandwidth_share", run) == pytest.approx(
        100 * need / 0.050 / 819e9, rel=0.02)
    assert 50 < read("decode_bandwidth_share", run) < 60
    # one prompt, and a request decoding in bursts of 64 every 3 s: the two
    # bursts at or before the window's start count nothing, nine count whole
    first = SimpleNamespace(prompt=[0] * 1000, stamps=[1.0])
    decoding = SimpleNamespace(prompt=[0] * 1000, stamps=[
        3.0 * k + 1e-4 * i for k in range(-1, 10) for i in range(64)])
    flops = counts.prefill_flops(run.sizes, 4, 1000) + sum(
        counts.decode_token_flops(run.sizes, 4, 1000 + j)
        for j in range(128, 704))
    got = read("mfu", _run(None, requests=[first, decoding]))
    assert got == pytest.approx(100 * flops / 30 / 197e12, rel=1e-3)
    assert 0.4 < got < 0.7
    # passes a step: the program's own counters; absent (a plain decoder,
    # the parent commit) reads nothing
    passes_c = "dstack_serving_loop_passes_total{phase=decode}"
    steps_c = "dstack_serving_decode_steps_total"
    assert read("loop_passes_per_step", _run(None, {passes_c: 2304.0,
                                                     steps_c: 576.0})) == 4.0
    assert read("loop_passes_per_step", _run(None, {steps_c: 576.0})) is None
    for name in ("decode_step_ms", "decode_bandwidth_share", "mfu"):
        assert read(name, _run(None)) is None
