"""The seam between the scheduler (``serving/engine.py``) and a family's
provider (``serving/dense.py``, ``serving/hybrid.py``, ``serving/lfm2.py``,
``serving/nemotron_h.py``):
the providers answer the same calls, the engine drives each through admission, chunked prefill
and decode windows to the tokens of the family's plain forward, the state
trees are the size the provider says, each provider refuses what its model
is not served with, and the engine's source names no model.  CPU, toy
sizes."""

import ast
import dataclasses
import inspect
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dstack_tpu.serving.dense import DensePrograms
from dstack_tpu.serving.engine import InferenceEngine
from dstack_tpu.serving.families import programs_for
from dstack_tpu.serving.hybrid import HybridPrograms
from dstack_tpu.serving.lfm2 import Lfm2Programs
from dstack_tpu.serving.nemotron_h import NemotronHPrograms

ROOT = Path(__file__).resolve().parents[2]
SERVING = ROOT / "dstack_tpu" / "serving"
#: the calls of the seam and how many arguments each takes
SEAM = {"prepare_params": 2, "init_state": 0, "recurrent_state_bytes": 0,
        "kv_geometry": 0, "slot_target": 2, "prefill_fn": 1, "chunk_fn": 1,
        "decode_window_fn": 3, "record_window_counts": 2,
        "record_prompt_program": 2, "export_fn": 1, "insert_rows": 5}
PAGED = dict(paged=True, kv_block_size=16, total_kv_blocks=20)
BUILT_WITH = dict(batch_size=2, max_len=64, paged=True, block_size=16,
                  num_blocks=9, prefix_cache=False, quantize=None,
                  kv_quantize=None, mesh=None, sharding_policy=None,
                  sample=None)


def _llama():
    from dstack_tpu.models import llama

    cfg = dataclasses.replace(llama.LlamaConfig.tiny(), dtype=jnp.float32)
    weights = llama.init_params(jax.random.PRNGKey(0), cfg)
    return cfg, weights, lambda seq: llama.forward(
        weights, jnp.asarray([seq]), cfg)[0]


def _routed_mlp():
    from dstack_tpu.models import moe

    # dropless at any length: the whole forward and the served chunks and
    # steps route alike (tests/compute/test_serving.py moe_setup)
    cfg = dataclasses.replace(moe.MoEConfig.tiny_moe(), capacity_factor=4.0,
                              dtype=jnp.float32)
    weights = moe.init_params(jax.random.PRNGKey(0), cfg)
    return cfg, weights, lambda seq: moe.forward(
        weights, jnp.asarray([seq]), cfg)[0]


def _hybrid():
    from benchmarks.harness.sizes import program_config, sizes_of
    from benchmarks.references import ling_hybrid as ref

    toy = json.loads((ROOT / "tests/benchmark/fixture_hybrid/cells/configs"
                      / "tiny-hybrid.json").read_text())
    uncut = dict(toy, num_experts=toy["num_routed_experts"], expert_offset=0)
    sizes = sizes_of(uncut)
    weights = ref.init_weights(sizes, 5, config=uncut)
    return program_config(uncut), weights, lambda seq: ref.logits(
        weights, sizes, np.asarray(seq), 0, len(seq), config=uncut)


def _lfm2():
    from benchmarks.harness.sizes import program_config, sizes_of
    from benchmarks.references import lfm2_moe as ref

    toy = json.loads((ROOT / "tests/benchmark/fixture_lfm2/cells/configs"
                      / "tiny-lfm2.json").read_text())
    sizes = sizes_of(toy)
    weights = ref.init_weights(sizes, 5, config=toy)
    return program_config(toy), weights, lambda seq: ref.logits(
        weights, sizes, np.asarray(seq), 0, len(seq), config=toy)


def _nemotron():
    from benchmarks.harness.sizes import program_config, sizes_of
    from benchmarks.references import nemotron_h as ref

    toy = json.loads((ROOT / "tests/benchmark/fixture_nemotron/cells/configs"
                      / "tiny-nemotron.json").read_text())
    sizes = sizes_of(toy)
    weights = ref.init_weights(sizes, 5, config=toy)
    return program_config(toy), weights, lambda seq: ref.logits(
        weights, sizes, np.asarray(seq), 0, len(seq), config=toy)


def _looped():
    from benchmarks.harness.sizes import program_config, sizes_of
    from benchmarks.references import ouro_looped as ref

    toy = json.loads((ROOT / "tests/benchmark/fixture_looped/cells/configs"
                      / "tiny-looped.json").read_text())
    sizes = sizes_of(toy)
    weights = ref.init_weights(sizes, 5)
    return program_config(toy), weights, lambda seq: ref.logits(
        weights, sizes, np.asarray(seq), 0, len(seq), config=toy)


FAMILIES = {
    "llama-rows": (_llama, {}, DensePrograms),
    "looped-rows": (_looped, {}, DensePrograms),
    "looped-paged": (_looped, PAGED, DensePrograms),
    "llama-paged": (_llama, PAGED, DensePrograms),
    "routed-mlp-paged": (_routed_mlp, PAGED, DensePrograms),
    "hybrid-paged": (_hybrid, PAGED, HybridPrograms),
    "lfm2-paged": (_lfm2, PAGED, Lfm2Programs),
    "nemotron-paged": (_nemotron, PAGED, NemotronHPrograms),
}


@pytest.mark.parametrize("family", FAMILIES)
def test_engine_drives_the_provider_to_the_plain_forward_s_tokens(family):
    """Admission, three chunks of 16, two 8-step decode windows: every
    served token is the plain forward's first choice at its position."""
    from dstack_tpu.telemetry.serving import EngineTelemetry

    make, kwargs, provider = FAMILIES[family]
    cfg, weights, plain_logits = make()
    engine = InferenceEngine(cfg, params=weights, batch_size=2, max_len=128,
                             prefill_chunk=16, telemetry=EngineTelemetry(),
                             **kwargs)
    assert type(engine._programs) is provider
    for name, arity in SEAM.items():
        params = inspect.signature(getattr(engine._programs, name)).parameters
        assert len(params) == arity, (name, tuple(params))
        assert all(p.kind is p.POSITIONAL_OR_KEYWORD
                   for p in params.values()), name
    prompt = np.random.default_rng(3).integers(1, 200, size=40).tolist()
    served = engine.generate(prompt, max_new_tokens=17).output
    assert len(served) == 17
    counters = dict(engine.telemetry.recorder.summary()["counters"])
    assert counters["dstack_serving_prefill_chunks_total"] == 3
    assert counters["dstack_serving_decode_steps_total"] == 16
    assert {fn.__name__.rsplit("_b", 1)[0]
            for fn in engine._prefill_jit.values()} == {
        "prefill_prefix" if engine.paged else "prefill_chunk",
        "first_token_sample", "slot_update"}
    scores = np.asarray(plain_logits(prompt + served[:-1]))[len(prompt) - 1:]
    assert scores.shape[0] == 17 and scores.std() > 0.1
    gaps = scores.max(-1) - scores[np.arange(17), served]
    assert gaps.max() < 1e-4, gaps


def test_the_providers_are_built_with_the_same_keywords():
    keywords = lambda cls: [
        (p.name, p.kind) for p in
        inspect.signature(cls.__init__).parameters.values()]
    assert keywords(DensePrograms) == keywords(HybridPrograms) == \
        keywords(Lfm2Programs) == keywords(NemotronHPrograms)
    assert {name for name, _ in keywords(DensePrograms)[2:]} == \
        set(BUILT_WITH)


@pytest.mark.parametrize("family", ["llama-paged-int8", "hybrid-paged"])
def test_state_trees_are_the_size_the_provider_says(family):
    """The first tree is the paged pool, a row a token; the second is the V
    pool (no recurrent state) or the recurrent state, and the provider
    reports that one's bytes."""
    if family == "hybrid-paged":
        from dstack_tpu.models.ling_hybrid import LingHybridConfig

        cfg = LingHybridConfig.tiny()
        rows = cfg.mla_layers * cfg.latent_lanes \
            * jnp.dtype(cfg.dtype).itemsize
        kwargs = {}
    else:
        from dstack_tpu.models.llama import LlamaConfig

        cfg = LlamaConfig.tiny()
        # int8 values and one float32 scale a kv head
        rows = cfg.num_layers * cfg.num_kv_heads * (cfg.head_dim + 4)
        kwargs = dict(kv_quantize="int8")
    engine = InferenceEngine(cfg, params={"layers": {}}, batch_size=3,
                             max_len=64, **PAGED, **kwargs)
    nbytes = lambda tree: sum(a.size * a.dtype.itemsize
                              for a in jax.tree.leaves(tree))
    pool, second = engine._state
    assert nbytes(pool) == 20 * 16 * rows
    layers, token_bytes = engine._programs.kv_geometry()
    assert layers == pool.shape[0] if family == "hybrid-paged" else \
        (layers, token_bytes) == (cfg.num_layers, 2 * rows)
    recurrent = engine._programs.recurrent_state_bytes()
    if family == "hybrid-paged":
        assert nbytes(second) == recurrent == cfg.recurrent_state_bytes(3) > 0
    else:
        assert nbytes(second) == nbytes(pool) and recurrent == 0


@pytest.mark.parametrize("changed,config,message", [
    (dict(paged=False, prefix_cache=True), {}, "prefix_cache requires paged"),
    (dict(kv_quantize="int3"), {}, "unsupported kv_quantize"),
    (dict(kv_quantize="int4"), dict(head_dim=15), "even head_dim"),
    (dict(mesh="tp3"), {}, "divisible by the tensor degree"),
], ids=["prefix_cache-unpaged", "kv_quantize-int3", "int4-odd-head_dim",
        "heads-tensor-3"])
def test_the_dense_provider_refuses_with_its_messages(changed, config,
                                                      message):
    from jax.sharding import Mesh

    from dstack_tpu.models.llama import LlamaConfig

    cfg = dataclasses.replace(LlamaConfig.tiny(), **config)
    if changed.get("mesh") == "tp3":
        changed = dict(mesh=Mesh(np.array(jax.devices()[:3]), ("tensor",)))
    with pytest.raises(ValueError, match=message):
        programs_for(cfg, **{**BUILT_WITH, **changed})


def test_the_hybrid_provider_refuses_the_pd_wire_itself():
    """Its table of refused options is ``test_ling_hybrid.py``'s (through
    the engine); the wire's two calls answer with the refusal."""
    from dstack_tpu.models.ling_hybrid import LingHybridConfig

    programs = programs_for(LingHybridConfig.tiny(), **BUILT_WITH)
    assert type(programs) is HybridPrograms
    assert DensePrograms.pd_refusal is None
    with pytest.raises(ValueError, match="disaggregation"):
        programs.export_fn(32)
    with pytest.raises(ValueError, match="disaggregation"):
        programs.insert_rows(None, None, {}, 0, None)


def test_the_scheduler_names_no_model():
    """``engine.py`` imports nothing of ``dstack_tpu.models`` or
    ``dstack_tpu.ops`` and asks no ``isinstance`` of its config; under
    ``serving/`` only the providers, ``families.py`` (one function) and the
    server's table of configs import a model."""
    def imported(path):
        tree = ast.parse(path.read_text())
        return tree, {
            (node.module if isinstance(node, ast.ImportFrom)
             else alias.name) or ""
            for node in ast.walk(tree)
            if isinstance(node, (ast.Import, ast.ImportFrom))
            for alias in node.names}

    tree, modules = imported(SERVING / "engine.py")
    assert not [m for m in modules
                if m.startswith(("dstack_tpu.models", "dstack_tpu.ops"))]
    for node in ast.walk(tree):
        if isinstance(node, ast.Call) and \
                getattr(node.func, "id", "") == "isinstance":
            assert "cfg" not in ast.unparse(node.args[0]), ast.unparse(node)
    name_a_model = {
        path.name for path in SERVING.glob("*.py")
        if any(m.startswith("dstack_tpu.models") for m in imported(path)[1])}
    assert name_a_model == {"dense.py", "hybrid.py", "lfm2.py",
                            "nemotron_h.py", "families.py", "server.py"}
    families = ast.parse((SERVING / "families.py").read_text())
    assert [node.name for node in ast.walk(families)
            if isinstance(node, ast.FunctionDef)] == ["programs_for"]


def test_families_is_a_table_and_a_subclass_goes_to_its_base_s_provider():
    from dstack_tpu.models.lfm2 import Lfm2MoeConfig
    from dstack_tpu.models.ling_hybrid import LingHybridConfig
    from dstack_tpu.models.llama import LlamaConfig
    from dstack_tpu.models.moe import MoEConfig
    from dstack_tpu.models.nemotron_h import NemotronHConfig
    from dstack_tpu.models.ouro import OuroConfig
    from dstack_tpu.serving.families import PROVIDERS

    assert PROVIDERS == {LlamaConfig: DensePrograms,
                         LingHybridConfig: HybridPrograms,
                         Lfm2MoeConfig: Lfm2Programs,
                         NemotronHConfig: NemotronHPrograms}
    for make, provider in ((MoEConfig.tiny_moe, DensePrograms),
                           (OuroConfig.tiny, DensePrograms),
                           (Lfm2MoeConfig.tiny, Lfm2Programs),
                           (NemotronHConfig.tiny, NemotronHPrograms)):
        assert type(programs_for(make(), **BUILT_WITH)) is provider
    with pytest.raises(TypeError, match="no provider serves"):
        programs_for(object(), **BUILT_WITH)


def test_what_the_families_share_exists_once():
    """The sorted grouped expert product, the logsumexp merge of the cache
    half with the window half and the page arithmetic of the end-of-window
    scatter are each defined in one file of the tree and called from the
    others."""
    sources = {path: path.read_text()
               for path in (ROOT / "dstack_tpu").rglob("*.py")}
    where = lambda needle: sorted(
        str(path.relative_to(ROOT / "dstack_tpu"))
        for path, text in sources.items() if needle in text)
    # the grouped product is the repo's own kernel: one pallas_call under its
    # name, reached through held_experts alone; XLA's ragged_dot is what
    # held_experts keeps for the CPU backend (the tests' reference at toy
    # sizes: no backend a cell runs on takes it) and is nowhere else
    assert where("ragged_dot(") == ["models/experts.py"]
    kernel = sources[ROOT / "dstack_tpu" / "ops" / "grouped_matmul.py"]
    assert kernel.count("pl.pallas_call(") == 1
    assert where("grouped_swiglu(") == ["models/experts.py",
                                        "ops/grouped_matmul.py"]
    assert where("grouped_relu2(") == ["models/experts.py",
                                       "ops/grouped_matmul.py"]
    assert where("grouped_matmul(") == ["ops/grouped_matmul.py"]
    # one router for the three dropless families
    assert where("jax.nn.sigmoid(jnp.matmul(") == ["models/experts.py"]
    assert where("experts.route(") == ["models/lfm2.py",
                                       "models/ling_hybrid.py",
                                       "models/nemotron_h.py"]
    assert where("jnp.logaddexp(lse_c") == ["serving/paged_window.py"]
    assert where("jnp.take_along_axis(tables") == ["serving/paged_window.py"]
    assert where("def masked_attention(") == ["serving/paged_window.py"]
    for caller in ("serving/dense.py", "serving/lfm2.py",
                   "serving/nemotron_h.py"):
        assert caller in where("paged_window.attend_pages_and_window(")
        assert caller in where("paged_window.attend_view_and_window(")
    for caller in ("serving/dense.py", "serving/hybrid.py",
                   "serving/lfm2.py", "serving/nemotron_h.py"):
        assert caller in where("paged_window.window_rows(")
        assert caller in where("paged_window.chunk_pages(")
    assert where("held_experts(") == ["models/experts.py", "models/lfm2.py",
                                      "models/ling_hybrid.py",
                                      "models/nemotron_h.py"]
