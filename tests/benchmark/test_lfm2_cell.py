"""The LFM2-MoE cell: a whole run on the CPU at a toy size (``fixture_lfm2``:
convolutions, GQA, 16 experts all held) through ``run_cell`` and the
benchmark's own reference file, the configuration file key by key against
ISSUE 35's values, the counts file, the cell's files, and the cell's own
readers on hand-made runs.  No number from here is a device number.

(``test_benchmark.py`` looks a configuration's published sizes up in a table
of its own, which has the two dense ones: its case ``[lfm2-24b-a2b-9l]``
fails with a ``KeyError`` as the Ling and Ouro cases do, PERF.md section 7;
``test_configuration_file_key_by_key`` holds the file instead.)"""

import io
import json
from pathlib import Path
from types import SimpleNamespace

import pytest

from benchmarks.harness import loadgen
from benchmarks.harness.cell import Files, passes, run_cell
from benchmarks.harness.sizes import load_config, program_config, sizes_of
from benchmarks.references import lfm2_moe as ref
from benchmarks.references import lfm2_moe_counts as counts

ROOT = Path(__file__).resolve().parents[2]
FIXTURE = Path(__file__).resolve().parent / "fixture_lfm2"
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = "lfm2-24b-a2b-9l"
CELL = NAME + ".reason"
PARAMS = 5_177_950_976

#: the catalog row's ``config`` for ``LFM2-24B-A2B`` (the ``config.json`` the
#: source names), ``layer_types`` written out from its pattern
PUBLISHED = {
    "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048,
    "intermediate_size": 11776,
    "layer_types": ["full_attention" if i % 4 == 2 else "conv"
                    for i in range(40)],
    "max_position_embeddings": 128000, "model_type": "lfm2_moe",
    "moe_intermediate_size": 1536, "norm_eps": 1e-05, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_dense_layers": 2, "num_experts": 64,
    "num_experts_per_tok": 4, "num_hidden_layers": 40,
    "num_key_value_heads": 8,
    "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
    "routed_scaling_factor": 1, "use_expert_bias": True, "vocab_size": 65536,
}
HERE = {"num_hidden_layers": 9, "num_dense_layers": 1,
        "layer_types": PUBLISHED["layer_types"][1:10]}


def _entry(kind, name):
    return next(e for e in SPEC[kind] if e["name"] == name)


@pytest.fixture(scope="module")
def config():
    return load_config(ROOT / _entry("configs", NAME)["file"])


@pytest.fixture(scope="module")
def shape(config):
    return ref._shape(sizes_of(config))


# -- the configuration --------------------------------------------------------

def test_configuration_file_key_by_key(config):
    entry = _entry("configs", NAME)
    assert config["source"] == entry["source"] == (
        "https://huggingface.co/LiquidAI/LFM2-24B-A2B/blob/main/config.json")
    assert sorted(config["reduced"]) == sorted(entry["reduced"]) == \
        sorted(HERE)
    assert PUBLISHED["layer_types"].count("full_attention") == 10
    for key, value in PUBLISHED.items():
        assert config[key] == HERE.get(key, value), key
    for key, here in HERE.items():
        assert config["reduced"][key]["here"] == here
        assert config["reduced"][key]["why"]
    assert config["reduced"]["num_hidden_layers"]["published"] == 40
    assert config["reduced"]["num_dense_layers"]["published"] == 2
    # no width among the cuts
    assert not [k for k in HERE if k.endswith(("_dim", "_rank", "_size"))]
    # the flat keys sizes_of reads, each an assumption that is written down
    flat = {"head_dim": 64, "rope_theta": 1000000, "rms_norm_eps": 1e-05,
            "tie_word_embeddings": True, "torch_dtype": "bfloat16"}
    for key, value in flat.items():
        assert config[key] == value and config["assumed"][key], key
    for key in ("rope", "qk_norm", "conv", "conv_bias", "router", "weights",
                "num_hidden_layers"):
        assert config["assumed"][key], key
    assert "1e-6" in config["assumed"]["router"]
    assert "268 MB" in config["assumed"]["tie_word_embeddings"]
    assert "5,177,950,976" in config["deployment"]
    assert "five pipeline stages" in config["deployment"]
    assert config["reference"] == "lfm2_moe"
    # the cut: a whole period (two) and 8 >= 4 layers behind the dense one,
    # every expert, the whole vocabulary
    assert config["layer_types"][1:5] == config["layer_types"][5:9] == [
        "full_attention", "conv", "conv", "conv"]


def test_file_loads_into_the_program_at_the_published_widths(config):
    cfg = program_config(config)
    widths = dict(
        hidden_size=2048, intermediate_size=11776, num_attention_heads=32,
        num_key_value_heads=8, head_dim=64, conv_L_cache=3,
        moe_intermediate_size=1536, num_experts=64, num_experts_per_tok=4,
        routed_scaling_factor=1, norm_eps=1e-5, rope_theta=1e6,
        vocab_size=65536, max_position_embeddings=128000)
    for field, value in widths.items():
        assert getattr(cfg, field) == value, field
    assert (cfg.num_hidden_layers, cfg.num_dense_layers, cfg.experts_held,
            cfg.expert_offset) == (9, 1, 64, 0)
    assert (cfg.conv_layers, cfg.attention_layers, cfg.kv_lanes) == (7, 2,
                                                                     512)
    assert str(cfg.dtype) == "bfloat16" and cfg.tie_word_embeddings
    from dstack_tpu.models.lfm2 import Lfm2MoeConfig

    assert cfg == Lfm2MoeConfig.lfm2_24b_a2b_9l()


def test_counts_file_program_and_the_stated_size_agree(config, shape):
    cfg = program_config(config)
    assert counts.num_params(shape) == cfg.num_params() == PARAMS
    assert round(2 * PARAMS / 1e9, 2) == 10.36
    # ISSUE 35's arithmetic, part by part
    assert counts.mixer_matrices(shape, "conv") \
        + counts.mixer_small(shape, "conv") == 16_783_360
    assert counts.mixer_matrices(shape, "full_attention") \
        + counts.mixer_small(shape, "full_attention") == 10_485_888
    assert counts.ffn_dense_matrices(shape, 0) == 72_351_744
    assert counts.expert_params(shape) == 9_437_184
    assert counts.ffn_dense_matrices(shape, 1) + 64 \
        + 64 * counts.expert_params(shape) == 604_110_912
    assert PARAMS == (134_217_728 + 2_048 + 89_139_200 + 2 * 614_600_896
                      + 6 * 620_898_368)
    assert counts.kv_bytes_per_token(shape) == 4096
    assert counts.state_bytes_per_slot(shape) == 57_344
    assert counts.state_bytes_per_slot(shape) * 256 == \
        cfg.recurrent_state_bytes(256)
    # one token: 2 FLOPs a matrix parameter it meets, the taps and gates,
    # its 4 pairs in each of 8 expert layers, the head
    matrices = sum(counts.mixer_matrices(shape, kind)
                   + counts.ffn_dense_matrices(shape, i)
                   for i, kind in enumerate(shape["types"]))
    assert counts.body_flops(shape) == 2 * matrices + 7 * 8 * 2048
    assert counts.expected_held_pairs(shape) == 4.0
    assert counts.pair_flops(shape) == 2 * 3 * 2048 * 1536
    more = counts.prefill_flops(shape, 101) - counts.prefill_flops(shape, 100)
    assert more == pytest.approx(
        counts.decode_token_flops(shape, 101) - counts.head_flops(shape)
        + 8 * 4.0 * counts.pair_flops(shape))
    assert counts.attention_flops(shape, 1) == 2 * 4 * 2048
    # a step with every slot live and every expert touched moves the
    # weights once, twice the tails, the K and V rows: 9.9 GB of experts
    step = counts.decode_step_bytes(shape, 256, 200_000, 8 * 64)
    assert step == pytest.approx(
        2 * PARAMS + 2 * 256 * 57_344 + 200_000 * 4096, rel=1e-4)
    assert 2 * 8 * 64 * counts.expert_params(shape) == pytest.approx(
        9.66e9, rel=1e-3)
    # the kernel at this geometry: 512-lane rows of one layer
    call = counts.paged_attention_call(shape, 1000, 256)
    assert call == {"bytes": 2 * 1000 * 512 * 2 + 2 * 256 * 2048 * 2,
                    "flops": 4.0 * 2048 * 1000}


def test_cell_files_and_the_metrics_it_lists():
    files = Files(ROOT, SPEC)
    cell = _entry("workloads", CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        (NAME, "reason", 1)
    load = files.json(f"workloads/{CELL}.json")
    traffic = files.json(f"traffic/{cell['traffic']}.json")
    engine = load["engine"]
    assert load["clients"] == engine["batch_size"] == 256
    assert (engine["max_len"], engine["kv_block_size"], engine["paged"],
            engine["prefill_chunk"]) == (4096, 32, True, "tuned")
    assert 8192 <= engine["total_kv_blocks"] <= 16384
    assert (load["settle_s"], load["trace_s"]) == (15.0, 3.0)
    assert load["engine_why"] and load["correct_why"]
    assert traffic["order_seed"] == 29 and traffic["kind"] == "closed"
    mine = {m["name"]: m for m in SPEC["per_layer"]
            if CELL in m.get("workloads", [])}
    assert set(mine) == {n + ".lfm2.reason" for n in (
        "batch_occupancy", "decode_useful_share", "decode_steps_per_s",
        "gap_p95_ms", "prefill_wait_p95_ms", "idle_in_admission",
        "kv_peak_utilization", "device_idle_share", "prefill_device_share",
        "expert_load_imbalance", "decode_step_ms", "mfu",
        "decode_bandwidth_share", "paged_attn_roofline")}
    assert all(m["workloads"] == [CELL]
               and m["moves"] == "output_tokens_per_s" for m in mine.values())
    own = {"decode_step_ms", "mfu", "decode_bandwidth_share",
           "paged_attn_roofline"}
    for name in mine:
        reader = Path(files.reader("layer_metrics", name).__file__).name
        stem = name[:-len(".lfm2.reason")]
        assert reader == (f"{stem}.lfm2.py" if stem in own else f"{stem}.py")
    assert files.find("references/lfm2_moe.py").is_file()
    recs = loadgen.plan(traffic, load, 65536, 2**31 + 5, 45.0)
    assert len(recs) == 256 * 40
    assert all(32 <= len(r.prompt) <= 2048 and r.max_new <= 1024
               for r in recs)
    over = sum(len(r.prompt) > 512 for r in recs[:256])
    assert 32 <= over <= 56          # about a sixth drive the chunk path
    assert max(int(r.prompt.max()) for r in recs[:64]) < 65536
    # every warm-up request fits the engine it warms
    assert all(p + n < engine["max_len"]
               for p, n in load["warmup"]["requests"])
    # the pool in the units the issue sizes it in
    tokens = engine["total_kv_blocks"] * engine["kv_block_size"]
    assert tokens * 4096 <= 2.15e9


# -- the rehearsal ------------------------------------------------------------

def _rehearse(seed, trace=False, control=False):
    out, err = io.StringIO(), io.StringIO()
    result = run_cell(FIXTURE, "tiny-lfm2.closed", seed, 2.0, trace,
                      allow_cpu=True, out=out, err=err, control=control)
    lines = [json.loads(line) for line in out.getvalue().strip().splitlines()]
    infos = {k: v for line in lines[:-1] for k, v in line["info"].items()}
    assert result == lines[-1]
    return lines[-1], err.getvalue(), infos


def test_whole_run_of_the_lfm2_cell_is_correct():
    last, err, infos = _rehearse(2**31 + 11)
    assert last["correct"] is True and last["failed"] == 0
    assert last["attempted"] > 3
    assert set(last["metrics"]) == {"output_tokens_per_s", "setup_s"}
    assert infos["comparison"]["tokens"] > 100
    assert infos["comparison"]["mismatches"] == 0
    assert infos["programs_built_in_window"] == 0
    assert err.strip().splitlines()[-1] == "correct: True"


def test_traced_run_reads_the_counters_and_leaves_out_what_needs_a_chip():
    last, _, _ = _rehearse(12, trace=True)
    assert last["correct"] is True
    assert last["checks"]["programs_built_in_window"] == {"value": 0,
                                                          "limit": 0}
    metrics = {k: v["value"] for k, v in last["metrics"].items()}
    # no device plane on the CPU and no published peaks: the trace readers
    # and the shares of a peak return nothing and the line leaves them out
    assert set(metrics) == {"batch_occupancy.lfm2.closed",
                            "expert_load_imbalance.lfm2.closed",
                            "kv_peak_utilization.lfm2.closed"}
    assert metrics["expert_load_imbalance.lfm2.closed"] >= 1.0
    assert 0 < metrics["kv_peak_utilization.lfm2.closed"] <= 100


def test_the_lower_precision_control_fails_the_lfm2_comparison():
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


# -- the cell's own readers on hand-made runs ---------------------------------

KERNEL = ('%paged_decode_attention.18 = (f32[256,32,64]{2,1,0}, '
          'f32[256,32,1]) custom-call(...), '
          'custom_call_target="tpu_custom_call"')
GROUPED = ('%ragged-dot-none.4 = bf16[1024,1536]{1,0} custom-call(...), '
           'custom_call_target="tpu_custom_call"')


def _trace(step_ns, steps=4):
    """One chip: a whole ``steps``-step decode window, each step 2 kernel
    calls (a tenth of the step together), 24 grouped products and other
    work; then a prefill."""
    ops = []
    for i in range(steps):
        t = i * step_ns
        for k in range(2):
            ops.append((KERNEL, t + k * step_ns // 4, step_ns // 20))
        for k in range(24):
            ops.append((GROUPED, t + step_ns // 2 + k * step_ns // 60,
                        step_ns // 80))
        ops.append(("%fusion.1 = bf16[8]{0} fusion(...)",
                    t + step_ns * 9 // 10, step_ns // 20))
    end = steps * step_ns
    ops.append(("%dot.1 = bf16[8]{0} dot(...)", end + 2000, step_ns))
    ops.append(("%copy.1 = s32[1]{0} copy(...)", -5000, 1000))
    modules = [(f"jit_decode_w{steps}_s0_kb64(5)", -1000, end + 2000),
               ("jit_prefill_paged_b256(7)", end + 1500, step_ns + 1000)]
    return {"devices": [{"name": "/device:TPU:0", "lines": {
        "XLA Modules": modules, "XLA Ops": sorted(ops, key=lambda e: e[1])}}],
        "host": {}}


def _run(trace, counters=None, requests=()):
    sizes = sizes_of(load_config(ROOT / f"benchmarks/configs/{NAME}.json"))
    zero = {k: 0.0 for k in (counters or {})}
    return SimpleNamespace(
        trace=trace, trace_span=(10.0, 13.0), sizes=sizes, chips=1,
        slots=256,
        peaks={"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
        t0=0.0, t1=30.0, all_requests=list(requests), requests=list(requests),
        counters={"t0": zero, "t1": counters or {}})


def test_the_cell_s_own_readers_on_hand_made_runs(shape):
    files = Files(ROOT, SPEC)
    read = lambda name, run: files.reader(
        "layer_metrics", f"{name}.lfm2.reason").read(run)
    # a 40 ms step; one request holds 1,000 + 2 tokens in the traced span;
    # 4 decode steps of one window at full occupancy, every expert touched
    trace = _trace(step_ns=40_000_000)
    streaming = SimpleNamespace(prompt=[0] * 1000, stamps=[9.0, 9.001, 20.0])
    counters = {
        "dstack_serving_moe_experts_touched_sum": 4 * 8 * 64.0,
        "dstack_serving_decode_steps_total": 4.0,
        "dstack_serving_batch_occupancy_count{phase=decode}": 1.0,
        "dstack_serving_batch_occupancy_sum{phase=decode}": 1.0,
        "dstack_serving_moe_pairs_total{where=held}": 0.0}
    run = _run(trace, counters, [streaming])
    assert read("decode_step_ms", run) == pytest.approx(40.0, rel=0.01)
    need = counts.decode_step_bytes(shape, 256, 1002, 8 * 64)
    assert need == pytest.approx(2 * (PARAMS) + 2 * 256 * 57_344
                                 + 1002 * 4096, rel=1e-4)
    share = read("decode_bandwidth_share", run)
    assert share == pytest.approx(100 * need / 0.040 / 819e9, rel=0.01)
    assert 30 < share < 33
    # the kernel: 8 calls of 2 ms; one call needs the live K/V of a layer
    # and the queries; the 96 grouped products are custom calls too and are
    # not counted
    call = counts.paged_attention_call(shape, 1002, 256)
    least = max(call["bytes"] / 819e9, call["flops"] / 197e12)
    assert read("paged_attn_roofline", run) == pytest.approx(
        100 * 8 * least / (8 * 0.002), rel=1e-6)
    assert 0 < read("paged_attn_roofline", run) < 100
    # one prompt, and a request decoding in bursts of 64 every 3 s: the two
    # bursts at or before the window's start count nothing, nine count whole;
    # the windows' routed pairs by the program's counter
    first = SimpleNamespace(prompt=[0] * 1000, stamps=[1.0])
    decoding = SimpleNamespace(prompt=[0] * 1000, stamps=[
        3.0 * k + 1e-4 * i for k in range(-1, 10) for i in range(64)])
    pairs = 576 * 8 * 4.0
    flops = counts.prefill_flops(shape, 1000) + sum(
        counts.decode_token_flops(shape, 1000 + j)
        for j in range(128, 704)) + pairs * counts.pair_flops(shape)
    got = read("mfu", _run(None, {
        "dstack_serving_moe_pairs_total{where=held}": pairs},
        [first, decoding]))
    assert got == pytest.approx(100 * flops / 30.0 / 197e12, rel=1e-6)
    # nothing to read: no trace, no device plane, no peaks, another program
    empty = _run(None, counters)
    for name in ("decode_step_ms", "decode_bandwidth_share",
                 "paged_attn_roofline"):
        assert read(name, empty) is None
        assert read(name, _run({"devices": [], "host": {}}, counters)) is None
    no_peaks = _run(trace, counters, [streaming])
    no_peaks.peaks = None
    for name in ("mfu", "decode_bandwidth_share", "paged_attn_roofline"):
        assert read(name, no_peaks) is None
    assert read("mfu", _run(None)) is None
    other = _trace(step_ns=40_000_000)
    other["devices"][0]["lines"]["XLA Modules"] = [
        ("jit_prefill_paged_b256(7)", 0, 1000)]
    assert read("decode_step_ms", _run(other, counters)) is None
    only_grouped = _trace(step_ns=40_000_000)
    only_grouped["devices"][0]["lines"]["XLA Ops"] = [
        e for e in only_grouped["devices"][0]["lines"]["XLA Ops"]
        if "paged_decode_attention" not in e[0]]
    assert read("paged_attn_roofline", _run(only_grouped, counters)) is None
