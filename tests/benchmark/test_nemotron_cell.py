"""The Nemotron-H cell: a whole run on the CPU at a toy size
(``fixture_nemotron``: Mamba-2, GQA without positions, 16 relu-squared
experts beside a shared one) through ``run_cell`` and the benchmark's own
reference file, the configuration file key by key against the catalog row,
the parameter counts by three counts, the cell's files, and the cell's own
readers on hand-made runs.  No number from here is a device number.

(``test_benchmark.py`` looks a configuration's published sizes up in a table
of its own, which has the two dense ones: its case
``[nemotron-3-nano-30b-a3b-9l-ep2]`` fails with a ``KeyError`` as the Ling,
Ouro and LFM2 cases do, PERF.md section 7;
``test_configuration_file_key_by_key`` holds the file instead.)"""

import io
import json
from pathlib import Path
from types import SimpleNamespace

import pytest

from benchmarks.harness import loadgen
from benchmarks.harness.cell import Files, passes, run_cell
from benchmarks.harness.sizes import load_config, program_config, sizes_of
from benchmarks.references import nemotron_h as ref
from benchmarks.references import nemotron_h_counts as counts

ROOT = Path(__file__).resolve().parents[2]
FIXTURE = Path(__file__).resolve().parent / "fixture_nemotron"
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = "nemotron-3-nano-30b-a3b-9l-ep2"
CELL = NAME + ".reason"
SUFFIX = ".nemotron.reason"
PARAMS, WHOLE = 3_166_244_352, 31_577_940_288
PATTERN = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"

#: the catalog row's ``config`` for ``NVIDIA-Nemotron-3-Nano-30B-A3B-BF16``
#: (the ``config.json`` the source names)
PUBLISHED = {
    "attention_bias": False, "chunk_size": 128, "conv_kernel": 4,
    "expand": 2, "head_dim": 128, "hidden_size": 2688,
    "hybrid_override_pattern": PATTERN, "intermediate_size": 1856,
    "layer_norm_epsilon": 1e-05, "mamba_head_dim": 64,
    "mamba_hidden_act": "silu", "mamba_num_heads": 64,
    "mamba_proj_bias": False, "max_position_embeddings": 262144,
    "mlp_bias": False, "mlp_hidden_act": "relu2", "model_type": "nemotron_h",
    "moe_intermediate_size": 1856,
    "moe_shared_expert_intermediate_size": 3712, "n_group": 1, "n_groups": 8,
    "n_routed_experts": 128, "n_shared_experts": 1, "norm_eps": 1e-05,
    "norm_topk_prob": True, "num_attention_heads": 32,
    "num_experts_per_tok": 6, "num_hidden_layers": 52,
    "num_key_value_heads": 2, "num_logits_to_keep": 1,
    "partial_rotary_factor": 1, "rescale_prenorm_residual": True,
    "residual_in_fp32": False, "rope_theta": 10000,
    "routed_scaling_factor": 2.5, "sliding_window": None,
    "ssm_state_size": 128, "tie_word_embeddings": False,
    "time_step_floor": 0.0001, "time_step_max": 0.1, "time_step_min": 0.001,
    "topk_group": 1, "use_bias": False, "use_conv_bias": True,
    "use_mamba_kernels": True, "vocab_size": 131072,
}
HERE = {"num_hidden_layers": 9, "hybrid_override_pattern": "MEMEM*EME",
        "n_routed_experts": 64, "vocab_size": 65536}


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
        "https://huggingface.co/nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16"
        "/blob/main/config.json")
    assert sorted(config["reduced"]) == sorted(entry["reduced"]) == \
        sorted(HERE)
    assert len(PUBLISHED) == 46
    assert (PATTERN.count("M"), PATTERN.count("E"), PATTERN.count("*")) == \
        (23, 23, 6) and len(PATTERN) == 52
    for key, value in PUBLISHED.items():
        assert config[key] == HERE.get(key, value), key
    for key, here in HERE.items():
        assert config["reduced"][key]["here"] == here
        assert config["reduced"][key]["published"] == PUBLISHED[key]
        assert config["reduced"][key]["why"]
    # the cut is the pattern's first nine letters, the kinds at 4 : 4 : 1
    assert PATTERN.startswith(config["hybrid_override_pattern"])
    # no width among the cuts
    assert not [k for k in HERE if k.endswith(("_dim", "_rank", "_size"))
                and k != "vocab_size"]
    # the router keeps its published width; the file's extra keys
    assert (config["router_experts"], config["expert_offset"]) == (128, 0)
    flat = {"rms_norm_eps": 1e-05, "torch_dtype": "bfloat16"}
    for key, value in flat.items():
        assert config[key] == value and config["assumed"][key], key
    for key in ("d_inner", "in_proj_split", "conv", "ssm", "gated_norm",
                "attention", "router", "experts", "norms", "unread_keys",
                "weights", "router_experts"):
        assert config["assumed"][key], key
    assert "1e-20" in config["assumed"]["router"]
    assert "float32" in config["assumed"]["ssm"]
    assert "no rotary" in config["assumed"]["attention"]
    for unread in ("num_logits_to_keep", "use_mamba_kernels",
                   "rescale_prenorm_residual"):
        assert unread in config["assumed"]["unread_keys"]
    assert "3,166,244,352" in config["deployment"]
    assert "31,577,940,288" in config["deployment"]
    assert "EP 2" in config["deployment"]
    assert config["reference"] == "nemotron_h"


def test_file_loads_into_the_program_at_the_published_widths(config):
    cfg = program_config(config)
    widths = dict(
        hidden_size=2688, mamba_num_heads=64, mamba_head_dim=64, n_groups=8,
        ssm_state_size=128, conv_kernel=4, chunk_size=128,
        num_attention_heads=32, num_key_value_heads=2, head_dim=128,
        moe_intermediate_size=1856, moe_shared_expert_intermediate_size=3712,
        n_routed_experts=128, num_experts_per_tok=6,
        routed_scaling_factor=2.5, layer_norm_epsilon=1e-5,
        max_position_embeddings=262144)
    for field, value in widths.items():
        assert getattr(cfg, field) == value, field
    assert (cfg.num_hidden_layers, cfg.experts_held, cfg.expert_offset,
            cfg.vocab_size) == (9, 64, 0, 65536)
    assert (cfg.mamba_layers, cfg.attention_layers, cfg.kv_lanes,
            cfg.d_inner, cfg.conv_dim) == (4, 1, 256, 4096, 6144)
    assert str(cfg.dtype) == "bfloat16" and not cfg.tie_word_embeddings
    from dstack_tpu.models.nemotron_h import NemotronHConfig

    assert cfg == NemotronHConfig.nemotron_3_nano_30b_a3b_9l_ep2()


def test_three_counts_agree_on_the_cut_and_on_the_whole_model(config, shape):
    """3,166,244,352 held here and 31,577,940,288 published, by the
    program's count, by ``nemotron_h_counts.py``'s and by the reference's
    (the shapes of the tree its ``init_weights`` would make)."""
    import jax

    from dstack_tpu.models.nemotron_h import NemotronHConfig

    whole_file = dict(config, **{k: PUBLISHED[k] for k in HERE},
                      router_experts=128)
    whole = ref._shape(sizes_of(whole_file), whole_file)
    assert counts.num_params(shape) == program_config(config).num_params() \
        == PARAMS
    assert counts.num_params(whole) == NemotronHConfig().num_params() == WHOLE
    assert round(2 * PARAMS / 1e9, 2) == 6.33
    assert round(2 * WHOLE / 1e9, 2) == 63.16
    for file, want in ((config, PARAMS), (whole_file, WHOLE)):
        tree = _tree_shapes(sizes_of(file), file)
        assert sum(a.size for a in jax.tree.leaves(tree)) == want
    # ISSUE 39's arithmetic, part by part
    block = lambda kind: (counts.block_matrices(shape, kind)
                          + counts.block_small(shape, kind))
    assert block("mamba") == 38_744_896
    assert block("attention") == 23_399_040
    assert counts.expert_params(shape) == 9_977_856
    assert block("experts") + 64 * counts.expert_params(shape) == 658_885_376
    assert PARAMS == (4 * 38_744_896 + 4 * 658_885_376 + 23_399_040
                      + 2 * 65536 * 2688 + 2688)


def _tree_shapes(sizes, file):
    """The reference's weight tree by its shapes alone: its ``init_weights``
    traced, never run, and without the fit of the routers' bias, which
    changes no shape."""
    import jax

    patch = pytest.MonkeyPatch()
    patch.setattr(ref, "_fit_selection_bias", lambda tree, s, key: None)
    try:
        return jax.eval_shape(lambda: ref.init_weights(sizes, 0, config=file))
    finally:
        patch.undo()


def test_counts_file_and_the_stated_sizes_agree(config, shape):
    cfg = program_config(config)
    assert counts.kv_bytes_per_token(shape) == 1024
    assert counts.state_bytes_per_slot(shape) == 8_536_064
    assert counts.state_bytes_per_slot(shape) * 384 == \
        cfg.recurrent_state_bytes(384)
    assert round(cfg.recurrent_state_bytes(384) / 1e9, 2) == 3.28
    assert counts.state_elements(shape) * 4 == 2 * 2**20       # 2 MiB
    # one token: 2 FLOPs a matrix parameter it meets; in a Mamba layer the
    # taps and 5 an element of the state; its 3 held pairs (6 x 64 / 128)
    # in each of 4 expert blocks; the head
    matrices = sum(counts.block_matrices(shape, kind)
                   for kind in shape["types"])
    assert counts.body_flops(shape) == 2 * matrices + 4 * (
        2 * 4 * 6144 + 5 * 64 * 64 * 128)
    assert counts.expected_held_pairs(shape) == 3.0
    assert counts.pair_flops(shape) == 2 * 2 * 2688 * 1856
    more = counts.prefill_flops(shape, 101) - counts.prefill_flops(shape, 100)
    assert more == pytest.approx(
        counts.decode_token_flops(shape, 101) - counts.head_flops(shape)
        + 4 * 3.0 * counts.pair_flops(shape))
    assert counts.attention_flops(shape, 1) == 4 * 4096
    # ISSUE 39's step at 384 live slots and every held expert touched:
    # state 6.44 GB of 12.8
    update = counts.ssm_step_bytes(shape, 384 * 4)
    assert update == pytest.approx(384 * 4 * (2 * 2 * 2**20 + 20_736))
    assert round(384 * 4 * 2 * 2 * 2**20 / 1e9, 2) == 6.44
    assert round(4 * 64 * counts.expert_matrices_bytes(shape) / 1e9, 2) == 5.11
    step = counts.decode_step_bytes(shape, 384, 345_600, 4 * 64)
    assert round(step / 1e9, 1) == 12.9
    assert step == pytest.approx(
        2 * (PARAMS - 65536 * 2688) + 2 * 384 * 8_536_064 + 345_600 * 1024,
        rel=1e-3)
    # the kernel at this geometry: 256-lane rows of the one layer
    call = counts.paged_attention_call(shape, 1000, 384)
    assert call == {"bytes": 2 * 1000 * 256 * 2 + 2 * 384 * 4096 * 2,
                    "flops": 4.0 * 4096 * 1000}


def test_cell_files_and_the_metrics_it_lists():
    files = Files(ROOT, SPEC)
    cell = _entry("workloads", CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        (NAME, "reason", 1)
    load = files.json(f"workloads/{CELL}.json")
    traffic = files.json(f"traffic/{cell['traffic']}.json")
    engine = load["engine"]
    assert load["clients"] == engine["batch_size"] == 384
    assert (engine["max_len"], engine["kv_block_size"], engine["paged"],
            engine["prefill_chunk"], engine["total_kv_blocks"]) == \
        (4096, 32, True, "tuned", 24576)
    assert (load["settle_s"], load["trace_s"]) == (15.0, 3.0)
    assert load["engine_why"] and load["correct_why"]
    assert load["correct"]["requests"] == 6
    assert set(load["correct"]) == {"requests", "served_gap_mean_limit"}
    assert traffic["order_seed"] == 29 and traffic["kind"] == "closed"
    lfm2 = files.json("workloads/lfm2-24b-a2b-9l.reason.json")
    assert load["warmup"]["requests"] == lfm2["warmup"]["requests"]
    mine = {m["name"]: m for m in SPEC["per_layer"]
            if CELL in m.get("workloads", [])}
    own = {"ssm_step_roofline", "moe_experts_roofline", "paged_attn_roofline",
           "decode_bandwidth_share", "mfu", "decode_step_ms"}
    shared = {"batch_occupancy", "decode_useful_share", "decode_steps_per_s",
              "gap_p95_ms", "prefill_wait_p95_ms", "kv_peak_utilization",
              "device_idle_share", "prefill_device_share",
              "expert_load_imbalance", "idle_in_admission", "idle_in_chunks",
              "idle_in_handover", "idle_unattributed", "idle_inside_programs",
              "host_busy_share", "window_ahead_share"}
    assert set(mine) == {n + SUFFIX for n in own | shared}
    assert all(m["workloads"] == [CELL]
               and m["moves"] == "output_tokens_per_s" for m in mine.values())
    for name in ("ssm_step_roofline", "moe_experts_roofline",
                 "paged_attn_roofline"):
        assert (mine[name + SUFFIX]["layer"], mine[name + SUFFIX]["unit"],
                mine[name + SUFFIX]["source"]) == ("kernels", "%",
                                                   "device_trace")
    for name in mine:
        reader = Path(files.reader("layer_metrics", name).__file__).name
        stem = name[:-len(SUFFIX)]
        assert reader == (f"{stem}.nemotron.py" if stem in own
                          else f"{stem}.py")
    # the new cell is the last of its list and the lists before it stand
    assert SPEC["workloads"][-1]["name"] == CELL
    assert SPEC["configs"][-1]["name"] == NAME
    assert [m["name"] for m in SPEC["per_layer"][-22:]] == list(mine)
    assert files.find("references/nemotron_h.py").is_file()
    recs = loadgen.plan(traffic, load, 65536, 2**31 + 5, 45.0)
    assert len(recs) == 384 * 40
    assert all(32 <= len(r.prompt) <= 2048 and r.max_new <= 1024
               for r in recs)
    assert max(len(r.prompt) + r.max_new for r in recs) <= 3072
    over = sum(len(r.prompt) > 512 for r in recs[:384])
    assert 48 <= over <= 84          # about a sixth drive the chunk path
    assert max(int(r.prompt.max()) for r in recs[:64]) < 65536
    # every warm-up request fits the engine it warms
    assert all(p + n < engine["max_len"]
               for p, n in load["warmup"]["requests"])
    # the pool in the units the issue sizes it in: 1 KiB a token, 0.81 GB
    tokens = engine["total_kv_blocks"] * engine["kv_block_size"]
    assert round(tokens * 1024 / 1e9, 2) == 0.81


# -- the rehearsal ------------------------------------------------------------

def _rehearse(seed, trace=False, control=False):
    out, err = io.StringIO(), io.StringIO()
    result = run_cell(FIXTURE, "tiny-nemotron.closed", seed, 2.0, trace,
                      allow_cpu=True, out=out, err=err, control=control)
    lines = [json.loads(line) for line in out.getvalue().strip().splitlines()]
    infos = {k: v for line in lines[:-1] for k, v in line["info"].items()}
    assert result == lines[-1]
    return lines[-1], err.getvalue(), infos


def test_whole_run_of_the_nemotron_cell_is_correct():
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
    assert set(metrics) == {"batch_occupancy.nemotron.closed",
                            "expert_load_imbalance.nemotron.closed",
                            "kv_peak_utilization.nemotron.closed"}
    assert metrics["expert_load_imbalance.nemotron.closed"] >= 1.0
    assert 0 < metrics["kv_peak_utilization.nemotron.closed"] <= 100


def test_the_lower_precision_control_fails_the_nemotron_comparison():
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

KERNEL = ('%paged_decode_attention.18 = (f32[384,32,128]{2,1,0}, '
          'f32[384,32,1]) custom-call(...), '
          'custom_call_target="tpu_custom_call"')
GROUPED = ('%grouped_matmul.4 = bf16[2304,1856]{1,0} custom-call(...), '
           'custom_call_target="tpu_custom_call"')
#: a device event as the chip names it (PR 39): the HLO line, no metadata
UPDATE = ('%multiply_reduce_fusion.4 = (f32[384,64,64]{2,1,0:T(8,128)S(1)}, '
          'f32[384,64,64,128]{3,2,1,0:T(8,128)}) fusion(f32[384,64,128]'
          '{2,1,0:T(8,128)S(1)} %broadcast_bitcast_fusion.4, '
          'f32[384,64,64,128]{3,2,1,0:T(8,128)} %get-tuple-element.47), '
          'kind=kLoop, calls=%fused_computation.clone.clone')
#: reads the state and yields something else: not the update
READER = ('%fusion.9 = bf16[384,4096]{1,0} fusion(f32[384,64,64,128]'
          '{3,2,1,0:T(8,128)} %get-tuple-element.47), kind=kLoop')


def _trace(step_ns, steps=4):
    """One chip: a whole ``steps``-step decode window, each step one kernel
    call (a twentieth of the step), 4 state-space updates (a quarter of the
    step together), 8 grouped products (a fifth) and other work; then a
    prefill."""
    ops = []
    for i in range(steps):
        t = i * step_ns
        ops.append((KERNEL, t, step_ns // 20))
        for k in range(4):
            ops.append((UPDATE, t + step_ns // 10 + k * step_ns // 10,
                        step_ns // 16))
        for k in range(8):
            ops.append((GROUPED, t + step_ns // 2 + k * step_ns // 20,
                        step_ns // 40))
        ops.append((READER, t + step_ns * 19 // 20, step_ns // 40))
    end = steps * step_ns
    # the step loop itself: its carry holds the states, its events its body's
    ops.append(("%while.3 = (s32[384]{0}, f32[384,64,64,128]{3,2,1,0}) "
                "while(...), condition=%cond, body=%body", 0, end))
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
        slots=384,
        peaks={"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
        t0=0.0, t1=30.0, all_requests=list(requests), requests=list(requests),
        counters={"t0": zero, "t1": counters or {}})


def test_the_cell_s_own_readers_on_hand_made_runs(shape):
    files = Files(ROOT, SPEC)
    read = lambda name, run: files.reader(
        "layer_metrics", name + SUFFIX).read(run)
    # a 20 ms step; one request holds 1,000 + 2 tokens in the traced span;
    # 4 decode steps of one window at full occupancy, every expert touched,
    # 300 of the 384 slots live in the state-space updates
    trace = _trace(step_ns=20_000_000)
    streaming = SimpleNamespace(prompt=[0] * 1000, stamps=[9.0, 9.001, 20.0])
    counters = {
        "dstack_serving_moe_experts_touched_sum": 4 * 4 * 64.0,
        "dstack_serving_ssm_slot_layer_steps_total": 4 * 4 * 300.0,
        "dstack_serving_decode_steps_total": 4.0,
        "dstack_serving_batch_occupancy_count{phase=decode}": 1.0,
        "dstack_serving_batch_occupancy_sum{phase=decode}": 1.0,
        "dstack_serving_moe_pairs_total{where=held}": 0.0}
    run = _run(trace, counters, [streaming])
    assert read("decode_step_ms", run) == pytest.approx(20.0, rel=0.01)
    need = counts.decode_step_bytes(shape, 384, 1002, 4 * 64)
    share = read("decode_bandwidth_share", run)
    assert share == pytest.approx(100 * need / 0.020 / 819e9, rel=0.01)
    assert 70 < share < 80
    # the update: 4 calls of 1.25 ms a step, a quarter of the step, for 300
    # live slots x 4 layers
    update = read("ssm_step_roofline", run)
    least = counts.ssm_step_bytes(shape, 4 * 300) / 819e9
    assert update == pytest.approx(100 * least / 0.005, rel=0.01)
    assert 100 < update < 105 * 1.2     # a hand-made 5 ms: under the floor
    slow = _run(_trace(step_ns=40_000_000), counters, [streaming])
    assert read("ssm_step_roofline", slow) == pytest.approx(update / 2,
                                                            rel=0.01)
    # the grouped products: 8 calls of 0.5 ms a step for 256 touched
    # experts of two matrices each
    experts = read("moe_experts_roofline", run)
    assert experts == pytest.approx(
        100 * (256 * 2 * 2688 * 1856 * 2 / 819e9) / 0.004, rel=0.01)
    # the kernel: 4 calls of 1 ms; one call needs the live K/V of the one
    # layer and the queries; the grouped products are custom calls too and
    # are not counted
    call = counts.paged_attention_call(shape, 1002, 384)
    least = max(call["bytes"] / 819e9, call["flops"] / 197e12)
    assert read("paged_attn_roofline", run) == pytest.approx(
        100 * 4 * least / (4 * 0.001), rel=1e-6)
    assert 0 < read("paged_attn_roofline", run) < 100
    # one prompt, and a request decoding in bursts of 64 every 3 s: the two
    # bursts at or before the window's start count nothing, nine count whole;
    # the windows' routed pairs by the program's counter
    first = SimpleNamespace(prompt=[0] * 1000, stamps=[1.0])
    decoding = SimpleNamespace(prompt=[0] * 1000, stamps=[
        3.0 * k + 1e-4 * i for k in range(-1, 10) for i in range(64)])
    pairs = 576 * 4 * 3.0
    flops = counts.prefill_flops(shape, 1000) + sum(
        counts.decode_token_flops(shape, 1000 + j)
        for j in range(128, 704)) + pairs * counts.pair_flops(shape)
    got = read("mfu", _run(None, {
        "dstack_serving_moe_pairs_total{where=held}": pairs},
        [first, decoding]))
    assert got == pytest.approx(100 * flops / 30.0 / 197e12, rel=1e-6)
    # nothing to read: no trace, no device plane, no peaks, another program,
    # a program without the counter or the named update (the parent commit)
    empty = _run(None, counters)
    for name in ("decode_step_ms", "decode_bandwidth_share",
                 "paged_attn_roofline", "ssm_step_roofline",
                 "moe_experts_roofline"):
        assert read(name, empty) is None
        assert read(name, _run({"devices": [], "host": {}}, counters)) is None
    no_peaks = _run(trace, counters, [streaming])
    no_peaks.peaks = None
    for name in ("mfu", "decode_bandwidth_share", "paged_attn_roofline",
                 "ssm_step_roofline", "moe_experts_roofline"):
        assert read(name, no_peaks) is None
    assert read("mfu", _run(None)) is None
    other = _trace(step_ns=20_000_000)
    other["devices"][0]["lines"]["XLA Modules"] = [
        ("jit_prefill_paged_b256(7)", 0, 1000)]
    assert read("decode_step_ms", _run(other, counters)) is None
    uncounted = {k: v for k, v in counters.items() if "ssm" not in k}
    assert read("ssm_step_roofline", _run(trace, uncounted)) is None
    without = _trace(step_ns=20_000_000)
    without["devices"][0]["lines"]["XLA Ops"] = [
        e for e in without["devices"][0]["lines"]["XLA Ops"]
        if "multiply_reduce" not in e[0] and "paged_decode" not in e[0]]
    assert read("ssm_step_roofline", _run(without, counters)) is None
    assert read("paged_attn_roofline", _run(without, counters)) is None
    # at another slot count the states have another shape
    other_slots = _run(trace, counters, [streaming])
    other_slots.slots = 256
    assert read("ssm_step_roofline", other_slots) is None
