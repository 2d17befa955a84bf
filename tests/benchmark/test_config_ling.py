"""The configuration ``ling-3.0-flash-vl-7l-ep4``: its file against the
published numbers, the program's config class and the benchmark's counts;
its cell's files.  (``test_benchmark.py`` looks a configuration's published
sizes up in a table of its own, which has the two dense ones: PERF.md
section 7.)"""

import json
from pathlib import Path

import pytest

from benchmarks.harness import loadgen
from benchmarks.harness.cell import Files
from benchmarks.harness.sizes import load_config, program_config, sizes_of
from benchmarks.references import ling_hybrid as ref
from benchmarks.references import ling_hybrid_counts as counts

ROOT = Path(__file__).resolve().parents[2]
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = "ling-3.0-flash-vl-7l-ep4"
CELL = NAME + ".reason"
ENTRY = next(c for c in SPEC["configs"] if c["name"] == NAME)

#: the language model's settings as published (the catalog's ``config`` for
#: ``Ling-3.0-flash-VL``, from the ``config.json`` the source names)
PUBLISHED = {
    "image_patch_token": 157157, "video_patch_token": 156909,
    "image_start_token": 157158, "video_start_token": 157160,
    "num_hidden_layers": 42, "hidden_size": 2560, "intermediate_size": 6144,
    "first_k_dense_replace": 2, "max_position_embeddings": 131072,
    "moe_intermediate_size": 768, "num_experts_per_tok": 8,
    "num_attention_heads": 32, "q_lora_rank": None, "kv_lora_rank": 512,
    "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "v_head_dim": 128,
    "num_experts": 512, "num_key_value_heads": 32, "rope_theta": 6000000,
    "rms_norm_eps": 1e-06, "head_dim": 128, "vocab_size": 157184,
    "partial_rotary_factor": 0.5, "moe_router_enable_expert_bias": True,
    "routed_scaling_factor": 2.5, "n_group": 8, "topk_group": 4,
    "use_qk_norm": True, "score_function": "sigmoid",
    "moe_shared_expert_intermediate_size": 768, "layer_group_size": 6,
    "num_kv_heads_for_linear_attn": 0, "group_norm_size": 1,
    "linear_silu": True, "rotary_dim": 64, "use_mla_nope": False,
    "short_conv_kernel_size": 4, "use_nGPT": False,
    "scale_router_input": False, "value_norm": False, "up_proj_norm": False,
    "gated_attention_proj_granularity_type": "head_wise",
    "mtp_use_kda": False, "no_kda_lora": True, "use_kda_lora": False,
    "kda_safe_gate": True, "kda_lower_bound": -5, "norm_topk_prob": True,
    "expert_swiglu_limit_list": [0] * 35 + [4] * 7,
    "share_expert_swiglu_limit_list": [0] * 34 + [5] * 6 + [7] * 2,
}
HERE = {"num_hidden_layers": 7, "first_k_dense_replace": 1,
        "num_experts": 128, "vocab_size": 39296}


@pytest.fixture(scope="module")
def config():
    return load_config(ROOT / ENTRY["file"])


def test_file_carries_every_published_key(config):
    assert config["source"] == ENTRY["source"]
    assert sorted(config["reduced"]) == sorted(ENTRY["reduced"]) == \
        sorted(HERE)
    for key, value in PUBLISHED.items():
        assert config[key] == HERE.get(key, value), key
    for key, here in HERE.items():
        assert config["reduced"][key]["published"] == PUBLISHED[key]
        assert config["reduced"][key]["here"] == here
    # no width among the cuts
    assert not [k for k in HERE if k.endswith(("_dim", "_rank", "_size"))
                and k != "vocab_size"]
    for key in ("assumed", "deployment", "layer_types"):
        assert config[key], key
    assert config["layer_types"] == [
        "mla" if (i + 1) % PUBLISHED["layer_group_size"] == 0 else "kda"
        for i in range(1, 8)]


def test_file_loads_into_the_program_at_the_published_widths(config):
    cfg = program_config(config)
    widths = dict(
        hidden_size=2560, intermediate_size=6144, num_heads=32, head_dim=128,
        conv_kernel=4, kv_lora_rank=512, qk_nope_head_dim=128,
        qk_rope_head_dim=64, v_head_dim=128, moe_intermediate_size=768,
        shared_expert_intermediate_size=768, num_experts_per_tok=8,
        num_experts=512, n_group=8, topk_group=4, routed_scaling_factor=2.5,
        kda_lower_bound=-5, rope_theta=6e6, rms_eps=1e-6)
    for field, value in widths.items():
        assert getattr(cfg, field) == value, field
    assert (cfg.num_layers, cfg.first_k_dense, cfg.experts_held,
            cfg.expert_offset, cfg.vocab_size) == (7, 1, 128, 0, 39296)
    assert (cfg.kda_layers, cfg.mla_layers) == (6, 1)
    assert (cfg.latent_dim, cfg.latent_lanes) == (576, 640)
    # the floors of a cut: a whole period and four layers behind the dense
    # one, 8 experts, an eighth of the vocabulary
    assert cfg.num_layers - cfg.first_k_dense >= max(
        4, PUBLISHED["layer_group_size"])
    assert cfg.experts_held >= 8
    assert 8 * cfg.vocab_size >= PUBLISHED["vocab_size"]


def test_counts_file_program_and_the_stated_size_agree(config):
    cfg = program_config(config)
    shape = ref._shape(sizes_of(config))
    assert counts.num_params(shape) == cfg.num_params() == 5_169_285_056
    assert "5,169,285,056" in config["deployment"]
    assert round(2 * cfg.num_params() / 1e9, 2) == 10.34
    assert counts.state_bytes_per_slot(shape) * 128 == \
        cfg.recurrent_state_bytes(128) == 1_667_235_840
    # one token: 2 FLOPs a matrix parameter it meets, the KDA state's three
    # products, its pairs on held experts, the head
    matrices = sum(counts.mixer_matrices(shape, kind)
                   + counts.ffn_dense_matrices(shape, i)
                   for i, kind in enumerate(shape["types"]))
    assert counts.body_flops(shape) == \
        2 * matrices + 6 * 6 * 32 * 128 * 128
    assert counts.expected_held_pairs(shape) == 2.0
    assert counts.pair_flops(shape) == 2 * 3 * 2560 * 768
    more = counts.prefill_flops(shape, 101) - counts.prefill_flops(shape, 100)
    assert more == pytest.approx(
        counts.decode_token_flops(shape, 101) - counts.head_flops(shape)
        + 6 * 2.0 * counts.pair_flops(shape))
    # a step with every slot live and every held expert touched moves the
    # weights but the embedding, twice the state, the latent rows
    step = counts.decode_step_bytes(shape, 128, 100_000, 6 * 128)
    assert step == pytest.approx(
        2 * (cfg.num_params() - 39296 * 2560) + 2 * 1_667_235_840
        + 100_000 * 576 * 2, rel=1e-3)


def test_cell_files_and_the_metrics_it_lists():
    files = Files(ROOT, SPEC)
    cell = next(w for w in SPEC["workloads"] if w["name"] == CELL)
    assert cell["chips"] == 1 and cell["config"] == NAME
    load = files.json(f"workloads/{CELL}.json")
    traffic = files.json(f"traffic/{cell['traffic']}.json")
    assert load["clients"] == load["engine"]["batch_size"] == 128
    assert traffic["order_seed"] == 29 and traffic["kind"] == "closed"
    listed = {m["name"] for m in SPEC["per_layer"]
              if CELL in m.get("workloads", [])}
    # six shared readers under a three-part name: test_program_spans.py
    # holds "<those six>.<traffic>" to the dense cells
    assert listed == {n + ".reason" for n in (
        "batch_occupancy", "decode_useful_share.ling", "gap_p95_ms",
        "decode_steps_per_s.ling", "prefill_device_share.ling",
        "decode_step_ms", "decode_bandwidth_share", "mfu",
        "device_idle_share", "kv_peak_utilization.ling", "moe_held_share",
        "expert_load_imbalance", "prefill_wait_p95_ms.ling",
        "idle_in_admission.ling")}
    # readers that count a dense decoder's layers and KV rows stay off it
    for m in SPEC["per_layer"]:
        reader = files.reader("layer_metrics", m["name"]).__name__
        if m["name"] in listed and m["name"].split(".")[0] in (
                "mfu", "decode_step_ms"):
            assert reader.endswith("_reason_py"), (m["name"], reader)
    recs = loadgen.plan(traffic, load, 39296, 2**31 + 5, 45.0)
    assert len(recs) == 128 * 40
    assert all(32 <= len(r.prompt) <= 2048 and r.max_new <= 1024
               for r in recs)
    assert all(128 <= r.max_new for r in recs[128:])
    over = sum(len(r.prompt) > 512 for r in recs[:128])
    assert 16 <= over <= 28          # about a sixth drive the chunk path
    assert max(int(r.prompt.max()) for r in recs[:64]) < 39296
    # every warm-up request fits the engine it warms
    assert all(p + n < load["engine"]["max_len"]
               for p, n in load["warmup"]["requests"])
