"""The looped decoder (``models/ouro.py``) through the Llama family's programs
(``serving/dense.py``): the engine's logits, prefill then decode, against the
benchmark's plain reference (``benchmarks/references/ouro_looped.py``) on
seeded weights; the options it is served with; what it counts.  CPU, toy
sizes: 3 layers run 3 times, hidden 64."""

import dataclasses
import logging

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.harness.sizes import Sizes
from benchmarks.references import ouro_looped as ref
from dstack_tpu.models import llama, ouro
from dstack_tpu.models.ouro import OuroConfig
from dstack_tpu.serving.dense import DensePrograms
from dstack_tpu.serving.engine import InferenceEngine, Request
from dstack_tpu.telemetry.serving import EngineTelemetry

PAGED = dict(paged=True, kv_block_size=16, total_kv_blocks=20)
PROMPT = np.random.default_rng(3).integers(1, 500, size=40).tolist()
NEW = 12
#: float32 program against the float32 reference at ``highest``: the two
#: differ in the order of their sums (a cache and a window buffer merged by
#: logsumexp against one softmax over the row, a padded bucket against a
#: padded quantum), which reads 3e-6 to 4e-6 on logits of spread 1 after 9
#: layer-passes (five times of room); the bfloat16 control reads 0.09
LOGIT_TOL = 2e-5


def _toy(passes=3, threshold=1.0, dtype="float32", seed=7):
    """(program config, reference sizes, its two loop keys, weights)."""
    cfg = dataclasses.replace(OuroConfig.tiny(), ut_steps=passes,
                              early_exit_threshold=threshold,
                              dtype=jnp.dtype(dtype))
    sizes = Sizes(hidden=64, ffn=128, layers=3, heads=4, kv_heads=4,
                  head_dim=16, vocab=512, rope_theta=1e4, rms_eps=1e-6,
                  tied=False, dtype=dtype)
    loop = {"total_ut_steps": passes, "early_exit_threshold": threshold}
    return cfg, sizes, loop, ref.init_weights(sizes, seed)


def _served_logits(engine, prompt, new):
    """Serve ``prompt`` greedily and return (tokens, the logits each was
    taken from [new, vocab]): the engine's sampler is replaced, where the
    programs call it, by one that hands its logits out and picks the
    largest."""
    seen = []

    def greedy(logits, temps, top_ps, top_ks, rng):
        jax.debug.callback(lambda a: seen.append(np.asarray(a)), logits)
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)

    engine._sample_on_device = engine._programs._sample = greedy
    req = engine.generate(prompt, max_new_tokens=new, temperature=1.0)
    jax.effects_barrier()
    assert req.finish_reason == "length" and len(req.output) == new
    slot = 0    # an idle engine admits into its first slot
    rows = [seen[0][0]] + [step[slot] for step in seen[1:new]]
    return req.output, np.stack(rows)


def _reference(weights, sizes, loop, prompt, served, **kw):
    return ref.logits(weights, sizes, prompt + served[:-1], len(prompt) - 1,
                      len(served), config=loop, **kw)


ENGINES = {
    "paged-prefill": dict(PAGED),
    "paged-chunked": dict(PAGED, prefill_chunk=16),
    "rows-prefill": {},
    "rows-chunked": dict(prefill_chunk=16),
}


@pytest.mark.parametrize("engine_kw", ENGINES.values(), ids=ENGINES.keys())
def test_prefill_then_decode_gives_the_reference_logits(engine_kw):
    """Whole-prompt and chunked prefill (three chunks of 16), then decode
    windows through the paged pool and through the dense rows: every logit
    the engine sampled from is the reference's full forward's."""
    cfg, sizes, loop, weights = _toy()
    engine = InferenceEngine(cfg, params=weights, batch_size=2, max_len=128,
                             **engine_kw)
    served, logits = _served_logits(engine, PROMPT, NEW)
    want = _reference(weights, sizes, loop, PROMPT, served)
    assert want.std() > 0.5
    assert np.abs(logits - want).max() < LOGIT_TOL
    assert served == want.argmax(-1).tolist()


def test_paged_kernel_reads_every_pass_s_cache_layers(monkeypatch):
    """The Pallas block-table kernel (interpreted here) addresses cache
    layer t*L + l of 9 over 3 layers of weights by its scalar-prefetched
    index, no kernel change: the logits are the reference's, two windows
    deep, where the second window reads what the first wrote."""
    monkeypatch.setenv("DSTACK_TPU_PAGED_ATTN_KERNEL", "1")
    cfg, sizes, loop, weights = _toy()
    engine = InferenceEngine(cfg, params=weights, batch_size=2, max_len=128,
                             **PAGED)
    assert engine._programs._paged_kernel
    pool = engine._state[0]
    assert pool.shape == (cfg.cache_layers, 20, 16, 64) and \
        cfg.cache_layers == 9 > cfg.num_layers
    served, logits = _served_logits(engine, PROMPT, 18)
    want = _reference(weights, sizes, loop, PROMPT, served)
    assert np.abs(logits - want).max() < LOGIT_TOL


def test_threshold_below_one_takes_the_reference_s_exit_passes():
    """At threshold 0.5 tokens leave at different passes: the engine's
    logits are the reference's (which selects per position), and its
    counter of tokens by exit pass is the reference's histogram."""
    cfg, sizes, loop, weights = _toy(threshold=0.5)
    telemetry = EngineTelemetry()
    engine = InferenceEngine(cfg, params=weights, batch_size=2, max_len=128,
                             telemetry=telemetry, **PAGED)
    served, logits = _served_logits(engine, PROMPT, 17)
    want, exits = _reference(weights, sizes, loop, PROMPT, served,
                             exits=True)
    assert len(set(exits.tolist())) > 1, "the toy's gate picks one pass only"
    assert np.abs(logits - want).max() < LOGIT_TOL
    at_one, _ = ref.logits(weights, sizes, PROMPT + served[:-1],
                           len(PROMPT) - 1, 17, exits=True,
                           config=dict(loop, early_exit_threshold=1.0))
    assert np.abs(at_one - want).max() > 100 * LOGIT_TOL
    counters = dict(telemetry.recorder.summary()["counters"])
    by_pass = [counters["dstack_serving_loop_exit_tokens_total{step=%d}" % t]
               for t in range(3)]
    # the windows decoded tokens 2..17 (the first came from the prefill)
    # and nothing else: one slot was active
    assert by_pass == [int((exits[1:] == t).sum()) for t in range(3)]
    assert counters["dstack_serving_loop_passes_total{phase=decode}"] == \
        3 * counters["dstack_serving_decode_steps_total"] == 3 * 16


def test_exit_rule_on_hand_made_gates():
    """p = lam_t * prod(1 - lam_j), the last pass the remainder; the first
    pass whose cumulative p reaches the threshold."""
    lams = np.array([[0.2, 0.6, 0.0, 1.0], [0.5, 0.9, 0.0, 0.3],
                     [0.9, 0.1, 0.0, 0.7]], np.float32)
    # cumulative p: [.2, .6, 1], [.6, .96, 1], [0, 0, 1], [1, 1, 1]
    assert ref.exit_steps(lams, 1.0).tolist() == [2, 2, 2, 0]
    assert ref.exit_steps(lams, 0.6).tolist() == [1, 0, 2, 0]
    assert ref.exit_steps(lams, 0.1).tolist() == [0, 0, 2, 0]
    cfg = dataclasses.replace(OuroConfig.tiny(), early_exit_threshold=0.6)
    logit = np.log(np.maximum(lams, 1e-30) / np.maximum(1 - lams, 1e-30))
    # states whose first coordinate is the gate's logit, w = e_0, b = 0
    states = jnp.zeros((3, 4, 64)).at[:, :, 0].set(jnp.clip(logit, -80, 80))
    gate = {"w": jnp.zeros((64,)).at[0].set(1.0), "b": jnp.zeros((1,))}
    picked, exit_step = ouro.exit_select({"exit_gate": gate}, cfg, states)
    assert exit_step.tolist() == [1, 0, 2, 0]
    assert np.array_equal(picked, states[exit_step, np.arange(4)])
    for bad in (0.0, 1.5):
        with pytest.raises(ValueError, match="early_exit_threshold"):
            dataclasses.replace(cfg, early_exit_threshold=bad)


def test_one_pass_without_the_extra_norms_is_the_llama_path_bit_for_bit():
    """``ut_steps`` 1 and no sandwich takes the plain stack's code: the same
    weights under a ``LlamaConfig`` give the same logits to the bit; four
    passes over those weights give other logits (the loop runs)."""
    plain = dataclasses.replace(
        llama.LlamaConfig.tiny(), num_kv_heads=8, dtype=jnp.float32)
    weights = llama.init_params(jax.random.PRNGKey(1), plain)
    fields = dataclasses.asdict(plain)

    def logits_of(cfg, w):
        engine = InferenceEngine(cfg, params=w, batch_size=2, max_len=128,
                                 **PAGED)
        return _served_logits(engine, PROMPT, 10)[1]

    want = logits_of(plain, weights)
    looped = dict(weights, exit_gate=ouro.init_params(
        jax.random.PRNGKey(1), OuroConfig(**fields))["exit_gate"])
    once = logits_of(OuroConfig(**fields, ut_steps=1, sandwich_norm=False),
                     looped)
    assert np.array_equal(once, want)
    four = logits_of(OuroConfig(**fields, ut_steps=4, sandwich_norm=False),
                     looped)
    assert np.abs(four - want).max() > 0.1


def test_a_bfloat16_program_fails_the_tolerance():
    """The control: the same weights served in bfloat16 miss the float32
    reference by a thousand times the tolerance."""
    cfg, sizes, loop, weights = _toy()
    low = jax.tree.map(lambda a: a.astype(jnp.bfloat16), weights)
    engine = InferenceEngine(dataclasses.replace(cfg, dtype=jnp.bfloat16),
                             params=low, batch_size=2, max_len=128, **PAGED)
    served, logits = _served_logits(engine, PROMPT, NEW)
    want = _reference(weights, sizes, loop, PROMPT, served)
    assert np.abs(logits - want).max() > 100 * LOGIT_TOL


def test_the_reference_lower_precision_moves_its_logits():
    cfg, sizes, loop, weights = _toy()
    seq = PROMPT + [5, 6, 7]
    full = ref.logits(weights, sizes, seq, 39, 4, config=loop)
    low = ref.logits(weights, sizes, seq, 39, 4, config=loop, lower=True)
    assert 100 * LOGIT_TOL < np.abs(full - low).max() < 1.0


def _tokens(cfg, weights, prompts, new=10, **engine_kw):
    engine = InferenceEngine(cfg, params=weights, batch_size=2, max_len=128,
                             **engine_kw)
    return [engine.generate(p, max_new_tokens=new).output for p in prompts]


def test_prefix_cache_reuses_blocks_of_every_cache_layer():
    """A second prompt behind a shared 32-token prefix prefills its suffix
    only, against the reused blocks of all 9 cache layers, to the tokens of
    an engine that shares nothing."""
    cfg, _, _, weights = _toy()
    shared = PROMPT[:32]
    prompts = [shared + [7, 8, 9], shared + [11, 12, 13, 14]]
    want = _tokens(cfg, weights, prompts, **PAGED)
    telemetry = EngineTelemetry()
    got = _tokens(cfg, weights, prompts, prefix_cache=True,
                  telemetry=telemetry, **PAGED)
    assert got == want
    counters = dict(telemetry.recorder.summary()["counters"])
    # the second prompt prefilled 4 tokens, not 36
    assert counters["dstack_serving_prefill_tokens_total"] == 35 + 4


@pytest.mark.parametrize("kv_quantize,paged", [
    ("int8", True), ("int4", True), ("int8", False)])
def test_quantized_cache_holds_every_cache_layer(kv_quantize, paged):
    """int8 and int4 K/V over 9 cache layers: the pool's leaves are sized by
    ``cache_layers``, the provider's bytes a token say so, and greedy
    tokens start as the plain engine's (a quantized cache drifts later)."""
    cfg, _, _, weights = _toy()
    kw = PAGED if paged else {}
    engine = InferenceEngine(cfg, params=weights, batch_size=2, max_len=128,
                             kv_quantize=kv_quantize, **kw)
    layers, token_bytes = engine._programs.kv_geometry()
    tokens_held = 20 * 16 if paged else 2 * 128
    held = sum(a.size * a.dtype.itemsize
               for a in jax.tree.leaves(engine._state))
    assert layers == 9 and held == tokens_held * token_bytes
    got = engine.generate(PROMPT, max_new_tokens=10).output
    want = _tokens(cfg, weights, [PROMPT], **kw)[0]
    agree = 10 if kv_quantize == "int8" else 3
    assert got[:agree] == want[:agree]


def test_int8_weights_leave_norms_and_gate_alone():
    cfg, _, _, weights = _toy()
    engine = InferenceEngine(cfg, params=weights, batch_size=2, max_len=128,
                             quantize="int8", **PAGED)
    layers = engine.params["layers"]
    assert set(layers["wq"]) == {"q", "s"}
    assert layers["attn_out_norm"].dtype == jnp.float32
    assert engine.params["exit_gate"]["w"].dtype == jnp.float32
    got = engine.generate(PROMPT, max_new_tokens=6).output
    assert got[:3] == _tokens(cfg, weights, [PROMPT], new=6, **PAGED)[0][:3]


@pytest.mark.parametrize("paged", [True, False], ids=["paged", "rows"])
def test_tensor_mesh_serves_the_same_tokens(paged):
    """Two-way tensor parallelism: the Llama specs plus replicated extra
    norms and gate (``ouro.param_specs``); weights initialised sharded or
    handed in; the tokens of the one-device engine."""
    from jax.sharding import Mesh

    cfg, _, _, weights = _toy()
    kw = PAGED if paged else {}
    mesh = Mesh(np.array(jax.devices()[:2]), ("tensor",))
    assert _tokens(cfg, weights, [PROMPT], mesh=mesh, **kw) == \
        _tokens(cfg, weights, [PROMPT], **kw)
    engine = InferenceEngine(cfg, batch_size=2, max_len=128, mesh=mesh,
                             rng_seed=3, **kw)
    specs = ouro.param_specs(cfg)
    assert jax.tree.structure(specs, is_leaf=lambda x: not isinstance(
        x, dict)) == jax.tree.structure(engine.params)
    assert engine.params["layers"]["wq"].sharding.spec[-1] == "tensor"
    assert len(engine.generate(PROMPT, max_new_tokens=4).output) == 4


@pytest.mark.parametrize("paged", [True, False], ids=["paged", "rows"])
def test_pd_wire_carries_every_cache_layer(paged):
    """Prefill on one engine, decode on another: the export holds 9 cache
    layers of K and V rows, the insert puts them into the slot, and the
    tokens are the colocated engine's."""
    cfg, _, _, weights = _toy()
    kw = PAGED if paged else {}
    make = lambda: InferenceEngine(cfg, params=weights, batch_size=2,
                                   max_len=128, **kw)
    want = make().generate(PROMPT, max_new_tokens=10).output
    exported = make().prefill_export(PROMPT, max_new_tokens=10)
    assert exported["ks"].shape == (9, 40, 4, 16) == exported["vs"].shape
    decode = make()
    req = decode.submit(Request(tokens=PROMPT, max_new_tokens=10,
                                prefill=exported))
    while not req.done.is_set():
        decode.step()
    assert req.output == want


def test_published_sizes_and_parameter_count():
    cfg = OuroConfig.ouro_2_6b()
    assert (cfg.num_layers, cfg.ut_steps, cfg.cache_layers) == (48, 4, 192)
    per_layer = 4 * 2048 ** 2 + 3 * 2048 * 5632 + 4 * 2048
    assert per_layer == 51_388_416
    assert cfg.num_params() == (48 * per_layer + 2 * 49_152 * 2048 + 2048
                                + 2049) == 2_667_974_657
    shapes = jax.eval_shape(
        lambda: ouro.init_params(jax.random.PRNGKey(0), cfg))
    assert sum(a.size for a in jax.tree.leaves(shapes)) == cfg.num_params()
    # weight layers and cache layers are told apart in the provider's sizes
    programs = DensePrograms(
        cfg, batch_size=8, max_len=2048, paged=True, block_size=32,
        num_blocks=128, prefix_cache=False, quantize=None, kv_quantize=None,
        mesh=None, sharding_policy=None, sample=None)
    assert programs.kv_geometry() == (192, 1_572_864)
    toy = OuroConfig.tiny()
    assert sum(a.size for a in jax.tree.leaves(ouro.init_params(
        jax.random.PRNGKey(0), toy))) == toy.num_params()
    with pytest.raises(ValueError, match="ut_steps"):
        dataclasses.replace(toy, ut_steps=0)


def test_engine_reports_the_pool_in_tokens_and_bytes(caplog):
    """A 192-cache-layer pool far under slots x max_len is announced with
    its size in tokens and bytes from the provider's bytes a token, and the
    two gauges carry what a pool is sized from; a pool as large as the
    slots could ask for is no warning."""
    cfg = dataclasses.replace(OuroConfig.tiny(), ut_steps=4, num_layers=2)
    telemetry = EngineTelemetry()
    with caplog.at_level(logging.INFO, logger="dstack_tpu.serving.engine"):
        InferenceEngine(cfg, params={"layers": {}}, batch_size=4,
                        max_len=128, telemetry=telemetry, paged=True,
                        kv_block_size=16, total_kv_blocks=9)
        InferenceEngine(cfg, params={"layers": {}}, batch_size=1,
                        max_len=128, paged=True, kv_block_size=16,
                        total_kv_blocks=9)
    small, full = caplog.records
    token_bytes = 2 * 8 * 4 * 16 * 2
    assert small.levelno == logging.WARNING and full.levelno == logging.INFO
    text = small.getMessage()
    assert f"hold 128 tokens in {9 * 16 * token_bytes / 1e9:.2f} GB" in text
    assert f"({token_bytes} B a token over 8 cache layers)" in text
    assert "4 slots at max_len 128 could ask for 512" in text
    assert "overcommitted" not in text
    gauges = dict(telemetry.recorder.summary()["gauges"])
    assert gauges["dstack_serving_kv_cache_layers"] == 8
    assert gauges["dstack_serving_kv_bytes_per_token"] == token_bytes


def test_server_presets_build_the_looped_configs():
    from dstack_tpu.serving.server import CONFIGS

    assert CONFIGS["ouro-tiny"]() == OuroConfig.tiny()
    assert CONFIGS["ouro-2.6b"]().cache_layers == 192


def test_one_copy_of_the_decode_window_serves_both():
    """The looped decoder brought no second decode window: the window
    buffer and the end-of-window scatter each stand once under ``serving/``,
    in the Llama family's provider, the attention merge once in what the
    providers share (``paged_window.py``, since PR 35), and the model file
    holds no program."""
    from pathlib import Path

    serving = Path(ouro.__file__).resolve().parents[1] / "serving"
    text = {p.name: p.read_text() for p in serving.glob("*.py")}
    for mark, home in (
            ("jnp.logaddexp(lse_c, lse_w)", "paged_window.py"),
            ("win_shape = (", "dense.py"),
            ("def _decode_window_fn_buffered(", "dense.py"),
            ("@jax.named_scope(\"kv_window_write\")\n            def",
             "dense.py")):
        assert [name for name, t in text.items() if mark in t] == \
            [home] and text[home].count(mark) == 1, mark
    model = Path(ouro.__file__).read_text()
    assert "lax.scan" not in model and "def decode" not in model
    assert "ouro" not in text["engine.py"].lower()
