"""The LFM2-MoE decoder (gated short convolutions + GQA + sigmoid-routed
experts) at a toy size on seeded weights: the program's pieces and the
engine's programs against the benchmark's plain reference
(``benchmarks/references/lfm2_moe.py``), which imports nothing of the
program.

Tolerances.  The toy is float32 and so is the reference; they differ in the
order of their sums (a chunk's convolution over a carried tail, attention
over pages merged by logsumexp, the experts' grouped product against every
expert masked), which moves a logit by 1e-6 to 3e-5 here.  Logits are held
to ``ATOL`` 2e-4 and a served token's score to ``GAP`` 1e-4 under the
reference's best: ``test_bfloat16_in_place_of_float32_fails_the_tolerances``
shows the reference's own bfloat16 control outside both by more than ten
times.
"""

import dataclasses
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.harness.sizes import program_config, sizes_of
from benchmarks.references import lfm2_moe as ref
from dstack_tpu.models import lfm2 as model
from dstack_tpu.serving.engine import InferenceEngine, Request
from dstack_tpu.serving.lfm2 import Lfm2Programs

ROOT = Path(__file__).resolve().parents[2]
TOY = json.loads((ROOT / "tests/benchmark/fixture_lfm2/cells/configs"
                  / "tiny-lfm2.json").read_text())
ATOL, GAP = 2e-4, 1e-4


@pytest.fixture(scope="module")
def toy():
    sizes = sizes_of(TOY)
    return sizes, ref.init_weights(sizes, 5, config=TOY), program_config(TOY)


def _engine(cfg, weights, **kw):
    args = dict(batch_size=4, max_len=256, paged=True, kv_block_size=16,
                total_kv_blocks=60, prefill_chunk=32)
    args.update(kw)
    return InferenceEngine(cfg, params=weights, **args)


def _prompt(n, seed=0):
    return np.random.default_rng(seed).integers(0, 256, size=n)


def _reference(weights, sizes, seq, first, count, **kw):
    return ref.logits(weights, sizes, np.asarray(seq), first, count,
                      config=TOY, **kw)


def _gaps(weights, sizes, prompt, served):
    seq = np.concatenate([prompt, served[:-1]])
    scores = _reference(weights, sizes, seq, len(prompt) - 1, len(served))
    return scores, scores.max(-1) - scores[np.arange(len(served)), served]


# -- the pieces ---------------------------------------------------------------

def test_config_is_the_published_one_and_the_cut():
    whole = model.Lfm2MoeConfig()
    assert (whole.conv_layers, whole.attention_layers) == (30, 10)
    assert whole.layer_types[:3] == ("conv", "conv", "full_attention")
    assert (whole.head_dim, whole.kv_lanes, whole.experts_held) == (64, 512,
                                                                    64)
    cut = model.Lfm2MoeConfig.lfm2_24b_a2b_9l()
    assert cut.layer_types == whole.layer_types[1:10]
    assert (cut.conv_layers, cut.attention_layers) == (7, 2)
    assert cut.num_params() == 5_177_950_976
    assert cut.recurrent_state_bytes(1) == 7 * 2 * 2048 * 2 == 57_344
    # the whole model: the card's "24B"
    assert round(whole.num_params() / 1e9, 2) == 23.84
    for bad in (dict(conv_bias=True), dict(tie_word_embeddings=False),
                dict(norm_topk_prob=False), dict(use_expert_bias=False),
                dict(layer_types=("conv", "mla")),
                dict(experts_held=8, expert_offset=60)):
        with pytest.raises(ValueError):
            model.Lfm2MoeConfig.tiny(**bad)


def test_parameter_count_is_the_tree(toy):
    _, weights, cfg = toy
    assert cfg.num_params() == sum(a.size for a in jax.tree.leaves(weights))
    program = model.init_params(jax.random.key(0), cfg)
    assert jax.tree.map(lambda a: (a.shape, a.dtype), program) == \
        jax.tree.map(lambda a: (a.shape, a.dtype), weights)


def test_init_weights_follow_the_seed_and_fit_a_bias_a_layer(toy):
    sizes, weights, _ = toy
    other = ref.init_weights(sizes, 6, config=TOY)
    routed = [lw for lw in weights["layers"] if "router" in lw]
    assert len(routed) == len(TOY["layer_types"]) - TOY["num_dense_layers"]
    for lw, lo in zip(routed, [lw for lw in other["layers"]
                               if "router" in lw]):
        assert float(jnp.abs(lw["router_bias"]).max()) > 0
        assert not np.allclose(lw["router"], lo["router"])


@pytest.mark.parametrize("cuts", [(5, 12), (1, 1, 1, 14), (17,)],
                         ids=["two", "single-tokens", "whole"])
def test_convolution_in_pieces_equals_the_whole(toy, cuts):
    """A sequence fed in pieces with the tail carried (pieces of ONE token
    split the kernel's reach: the new tail is one old row and one new),
    then token by token as a decode step does, against the reference's
    convolution over the whole sequence."""
    sizes, weights, cfg = toy
    lp = weights["layers"][0]
    x = jax.random.normal(jax.random.key(2), (17, cfg.hidden_size))
    h = ref._rms_norm(x, lp["operator_norm"], cfg.norm_eps)
    want = ref._conv(h, lp, ref._shape(sizes, TOY), None)
    tail = jnp.zeros((cfg.conv_reach, cfg.hidden_size))
    outs, start = [], 0
    for n in cuts:
        padded = jnp.zeros((20, cfg.hidden_size)).at[:n].set(
            x[start:start + n])
        y, tail = model.conv_sequence(padded, lp, cfg, n, tail)
        outs.append(y[:n])
        start += n
    np.testing.assert_allclose(jnp.concatenate(outs), want, atol=2e-5)
    tails = jnp.zeros((2, cfg.conv_reach, cfg.hidden_size))
    live = jnp.array([True, False])
    for t in range(17):
        y, tails = model.conv_token(jnp.stack([x[t], x[t]]), lp, cfg, live,
                                    tails)
        np.testing.assert_allclose(y[0], want[t], atol=2e-5)
    np.testing.assert_allclose(tails[0], tail, atol=1e-6)
    assert float(jnp.abs(tails[1]).max()) == 0      # not live: tail kept


def test_attention_projection_norms_each_head_then_rotates(toy):
    sizes, weights, cfg = toy
    lp = weights["layers"][1]
    x = jax.random.normal(jax.random.key(3), (6, cfg.hidden_size))
    pos = jnp.arange(10, 16)
    q, k, v = model.attention_project(x, lp, cfg, pos)
    h = ref._rms_norm(x, lp["operator_norm"], cfg.norm_eps)
    want_q = ref._rope(ref._rms_norm(
        (h @ lp["wq"]).reshape(6, cfg.num_attention_heads, cfg.head_dim),
        lp["q_norm"], cfg.norm_eps), pos, cfg.rope_theta)
    np.testing.assert_allclose(q, want_q, atol=2e-5)
    assert k.shape == v.shape == (6, cfg.num_key_value_heads, cfg.head_dim)


EXPERTS_64 = dict(TOY, num_experts=64, layer_types=["conv"],
                  num_hidden_layers=1, num_dense_layers=0)


@pytest.mark.parametrize("masked", [False, True])
@pytest.mark.parametrize("share", [8, 16, 64])
def test_expert_shares_add_up_to_the_uncut_layer(share, masked):
    """Shares of 8, 16 and all 64 experts (offsets 0, share, ...): what the
    shares' grouped products give adds up to the reference's uncut expert
    layer, pair for pair."""
    sizes = sizes_of(EXPERTS_64)
    weights = ref.init_weights(sizes, 7, config=EXPERTS_64)
    cfg = program_config(EXPERTS_64)
    lp = weights["layers"][0]
    h = jax.random.normal(jax.random.key(9), (24, cfg.hidden_size))
    mask = (jnp.arange(24) < 17) if masked else None
    ids, w = model.route(h, lp, cfg)
    total, pairs = 0.0, 0
    for offset in range(0, 64, share):
        part = dataclasses.replace(cfg, experts_held=share,
                                   expert_offset=offset)
        mine = {k: (v[offset:offset + share] if k.startswith("we_") else v)
                for k, v in lp.items()}
        y, counts = model.held_experts(h, ids, w, mine, part, mask)
        total, pairs = total + y, pairs + int(counts.sum())
    whole, load = model.moe_ffn(h, lp, cfg, mask)
    want = ref._experts(h, lp, ref._shape(sizes, EXPERTS_64), None)
    rows = slice(0, 17 if masked else 24)
    np.testing.assert_allclose(total[rows], want[rows], atol=2e-5)
    np.testing.assert_allclose(whole[rows], want[rows], atol=2e-5)
    assert pairs == int(load[0]) == (17 if masked else 24) * 4
    assert float(load[1]) == 0.0 and float(load[2]) >= float(load[3])
    # the reference given one share computes that share
    part = dict(EXPERTS_64, num_experts_held=share, expert_offset=0)
    mine = {k: (v[:share] if k.startswith("we_") else v)
            for k, v in lp.items()}
    first, _ = model.held_experts(
        h, ids, w, mine, dataclasses.replace(cfg, experts_held=share), None)
    np.testing.assert_allclose(
        first, ref._experts(h, mine, ref._shape(sizes, part), None),
        atol=2e-5)


# -- the engine's programs against the reference ------------------------------

def _serve(engine, prompt, new):
    """One request to its end: ``(served tokens, the pages its slot held)``
    (a released slot's table row is cleared)."""
    req = engine.submit(Request(tokens=list(map(int, prompt)),
                                max_new_tokens=new))
    pages = []
    while not req.done.is_set():
        engine.step()
        pages = list(engine._slot_blocks[0]) or pages
    return np.asarray(req.output), pages


def test_prefill_logits_are_the_reference_s(toy):
    sizes, weights, cfg = toy
    engine = _engine(cfg, weights)
    prompt = _prompt(50)
    padded = np.zeros((64,), np.int32)
    padded[:50] = prompt
    logits, pool, rec = engine._prefill_program(64)(
        engine.params, jnp.asarray(padded), jnp.int32(50), *engine._state,
        (jnp.arange(1, 5, dtype=jnp.int32), jnp.int32(2)))
    want = _reference(weights, sizes, prompt, 49, 1)[0]
    np.testing.assert_allclose(logits, want, atol=ATOL)
    assert want.std() > 0.5
    # the slot's tails and pages are written, the others' are not
    assert float(jnp.abs(rec[:, 2]).max()) > 0
    assert float(jnp.abs(rec[:, :2]).max()) == 0
    assert float(jnp.abs(pool["k"][:, 1:5]).max()) > 0
    assert float(jnp.abs(pool["v"][:, 5:]).max()) == 0


@pytest.mark.parametrize("prompt_len", [49, 50, 75],
                         ids=["last-chunk-of-1", "last-chunk-of-2", "75"])
def test_chunked_prefill_logits_are_the_reference_s(toy, prompt_len):
    """Chunks of 16 through the chunk queue: every boundary splits a
    convolution's reach, and a last chunk of one token makes its new tail
    from one carried row and one of its own.  The last chunk's logits
    against the reference; tails and pages against one whole prefill."""
    sizes, weights, cfg = toy
    prompt = _prompt(prompt_len, seed=4)
    chunked = _engine(cfg, weights, prefill_chunk=16)
    logits = None
    tables = jnp.arange(1, 17, dtype=jnp.int32)
    state = chunked._state
    for start in range(0, prompt_len, 16):
        piece = prompt[start:start + 16]
        padded = np.zeros((16,), np.int32)
        padded[:len(piece)] = piece
        logits, *state = chunked._chunk_program(16)(
            chunked.params, jnp.asarray(padded), jnp.int32(len(piece)),
            jnp.int32(start), *state, (tables, jnp.int32(1)))
    want = _reference(weights, sizes, prompt, prompt_len - 1, 1)[0]
    np.testing.assert_allclose(logits, want, atol=ATOL)
    whole = _engine(cfg, weights, prefill_chunk=None)
    bucket = whole._bucket(prompt_len)
    padded = np.zeros((bucket,), np.int32)
    padded[:prompt_len] = prompt
    _, pool, rec = whole._prefill_program(bucket)(
        whole.params, jnp.asarray(padded), jnp.int32(prompt_len),
        *whole._state, (tables[:bucket // 16], jnp.int32(1)))
    np.testing.assert_allclose(state[1][:, 1], rec[:, 1], atol=2e-5)
    for key in ("k", "v"):
        rows = lambda p: p[key][:, 1:9].reshape(2, -1, p[key].shape[-1])
        np.testing.assert_allclose(rows(state[0])[:, :prompt_len],
                                   rows(pool)[:, :prompt_len], atol=2e-5)


@pytest.mark.parametrize("kernel", [False, True], ids=["gathered", "kernel"])
@pytest.mark.parametrize("prompt_len,new", [(20, 40), (75, 20), (100, 80)])
def test_served_tokens_are_the_reference_s_first_choice(toy, monkeypatch,
                                                        prompt_len, new,
                                                        kernel):
    """Whole-prompt prefill (20), chunked prefill (75, 100: chunks of 32)
    and paged decode through 8-, 32- and 64-step windows and a change of the
    table bucket (100 + 80 tokens pass 8 columns of 16), with the cache half
    read through the gathered view and through the block-table kernel
    (interpreted here).  Every served token is the reference's first choice
    to ``GAP``; after the run the slot's pages are what one prefill of the
    whole sequence leaves."""
    monkeypatch.setenv("DSTACK_TPU_PAGED_ATTN_KERNEL", "1" if kernel else "0")
    sizes, weights, cfg = toy
    engine = _engine(cfg, weights)
    assert engine._programs._paged_kernel is kernel
    prompt = _prompt(prompt_len)
    served, pages = _serve(engine, prompt, new)
    scores, gaps = _gaps(weights, sizes, prompt, served)
    assert len(served) == new and scores.std() > 0.5
    assert gaps.max() < GAP
    assert {w for w, _, _ in engine._decode_jit} >= {8, 64} or new < 64
    # decode through the cache leaves the K and V rows of one whole forward
    # (the tails run on past the last served token with the window's spare
    # steps, so they are held by the tests above)
    seq = np.concatenate([prompt, served[:-1]])
    whole = _engine(cfg, weights, prefill_chunk=None)
    _, whole_pages = _serve(whole, seq, 2)   # 1 would end in its admission
    n, blocks = len(seq), -(-len(seq) // 16)
    for key in ("k", "v"):
        rows = lambda e, ids: e._state[0][key][:, np.asarray(ids[:blocks])] \
            .reshape(2, -1, cfg.kv_lanes)[:, :n]
        np.testing.assert_allclose(rows(engine, pages),
                                   rows(whole, whole_pages), atol=2e-5)


@pytest.mark.parametrize("second", [30, 90], ids=["whole", "chunked"])
def test_a_reused_slot_holds_nothing_of_its_last_request(toy, second):
    """A shorter request in a used slot starts its tails from zeros, by
    whole-prompt prefill and by its first chunk."""
    _, weights, cfg = toy
    used = _engine(cfg, weights, batch_size=1, total_kv_blocks=20)
    used.generate(_prompt(80, seed=1).tolist(), max_new_tokens=30)
    assert float(jnp.abs(used._state[1]).max()) > 0
    fresh = _engine(cfg, weights, batch_size=1, total_kv_blocks=20)
    prompt = _prompt(second, seed=2).tolist()
    assert used.generate(prompt, max_new_tokens=20).output == \
        fresh.generate(prompt, max_new_tokens=20).output
    np.testing.assert_array_equal(used._state[1], fresh._state[1])


def test_inactive_slots_keep_tail_and_pages(toy):
    """A decode window leaves the tails and the pages of slots that are not
    active bit for bit (their window rows land in the NULL block)."""
    _, weights, cfg = toy
    engine = _engine(cfg, weights)
    for slot, n in ((0, 30), (2, 45)):
        engine.generate(_prompt(n, seed=slot).tolist(), max_new_tokens=2)
    pool, rec = engine._state
    pool = jax.tree.map(lambda a: a.at[:, 1:].add(0.5), pool)
    rec = rec + 0.25
    before = jax.tree.map(np.asarray, (pool, rec))
    b = engine.batch_size
    active = jnp.array([False, True, False, False])
    tables = jnp.asarray(np.arange(1, 1 + 4 * b, dtype=np.int32).reshape(b, 4))
    out = engine._decode_window_program(8, False, 4)(
        engine.params, jnp.zeros((b,), jnp.int32),
        jnp.full((b,), 20, jnp.int32), active, pool, rec,
        jnp.zeros((b,)), jnp.ones((b,)), jnp.zeros((b,), jnp.int32), tables,
        jax.random.PRNGKey(0))
    _, _, lengths, pool_after, rec_after, load = out
    assert lengths.tolist() == [20, 28, 20, 20]
    np.testing.assert_array_equal(rec_after[:, [0, 2, 3]],
                                  before[1][:, [0, 2, 3]])
    assert not np.array_equal(rec_after[:, 1], before[1][:, 1])
    mine = np.asarray(tables[1])
    others = np.setdiff1d(np.arange(1, pool_after["k"].shape[1]), mine)
    for key in ("k", "v"):
        np.testing.assert_array_equal(pool_after[key][:, others],
                                      before[0][key][:, others])
        assert not np.array_equal(pool_after[key][:, mine],
                                  before[0][key][:, mine])
    # one live slot, 8 steps, 4 expert layers, 4 experts a token
    assert float(load[0]) == 8 * 4 * 4 and float(load[1]) == 0


def test_slots_decode_together_as_they_do_alone(toy):
    """Four requests of different lengths in one batch (a chunking one
    among them) get the tokens each gets alone."""
    _, weights, cfg = toy
    prompts = [_prompt(n, seed=n).tolist() for n in (18, 40, 70, 120)]
    alone = [_engine(cfg, weights).generate(p, max_new_tokens=24).output
             for p in prompts]
    engine = _engine(cfg, weights)
    reqs = [engine.submit(Request(tokens=p, max_new_tokens=24))
            for p in prompts]
    while not all(r.done.is_set() for r in reqs):
        engine.step()
    assert [r.output for r in reqs] == alone


def test_engine_built_as_the_server_builds_it_serves_the_reference(toy):
    """``serving/server.py``'s construction (the config by its ``--config``
    name's class, paged, the tuned chunk, the engine's telemetry) with the
    reference's weights: tokens, the expert load and the gauges."""
    from dstack_tpu.serving.server import CONFIGS
    from dstack_tpu.telemetry.serving import make_engine_telemetry

    assert CONFIGS["lfm2-tiny"]() == model.Lfm2MoeConfig.tiny()
    assert CONFIGS["lfm2-24b-a2b-9l"]().num_params() == 5_177_950_976
    sizes, weights, cfg = toy
    engine = InferenceEngine(
        cfg, params=weights, batch_size=2, max_len=256, quantize=None,
        mesh=None, paged=True, kv_block_size=16, total_kv_blocks=None,
        prefix_cache=False, kv_quantize=None,
        prefill_chunk=InferenceEngine.TUNED_PREFILL_CHUNK,
        telemetry=make_engine_telemetry(), compile_cache=None)
    assert type(engine._programs) is Lfm2Programs
    prompt = _prompt(20)
    served = np.asarray(engine.generate(prompt.tolist(),
                                        max_new_tokens=9).output)
    _, gaps = _gaps(weights, sizes, prompt, served)
    assert gaps.max() < GAP
    got = {(s.name, tuple(sorted(s.labels.items()))): s.value
           for s in engine.telemetry.prometheus_samples()}
    pairs = lambda where: got[("dstack_serving_moe_pairs_total",
                               (("where", where),))]
    # one 8-step window, one live slot, 4 expert layers, 4 experts a token
    assert pairs("held") == 8 * 4 * 4 and pairs("absent") == 0
    assert got[("dstack_serving_moe_experts_touched_sum", ())] <= \
        pairs("held")
    assert got[("dstack_serving_recurrent_state_bytes", ())] == \
        engine._programs.recurrent_state_bytes() == \
        engine._state[1].size * engine._state[1].dtype.itemsize
    assert engine._programs.kv_geometry() == (2, 2 * 2 * 32 * 4)
    pool = engine._state[0]
    assert set(pool) == {"k", "v"}
    assert pool["k"].shape == (2, 2 * 16 + 1, 16, 32)


def test_bfloat16_in_place_of_float32_fails_the_tolerances(toy):
    """The same forward with both operands of every matrix product rounded
    to bfloat16 (the reference's control for a float32 model) moves the
    logits by more than ten times ``ATOL`` and its first choices lie more
    than ten times ``GAP`` under the float32 best."""
    sizes, weights, _ = toy
    seq = _prompt(80, seed=3)
    exact = _reference(weights, sizes, seq, 40, 40)
    low = _reference(weights, sizes, seq, 40, 40, lower=True)
    assert np.abs(low - exact).max() > 10 * ATOL
    gaps = exact.max(-1) - exact[np.arange(40), low.argmax(-1)]
    assert gaps.max() > 10 * GAP


# -- what the model is not served with ----------------------------------------

@pytest.mark.parametrize("option,value,reason", [
    ("paged", False, "paged=False"), ("prefix_cache", True, "prefix_cache"),
    ("kv_quantize", "int8", "kv_quantize"), ("quantize", "int8", "quantize"),
    ("mesh", "a mesh", "a mesh")])
def test_options_the_model_cannot_be_served_with_raise(option, value, reason):
    cfg = model.Lfm2MoeConfig.tiny()
    args = dict(params={"layers": {}}, batch_size=2, max_len=64, paged=True,
                kv_block_size=16)
    args[option] = value
    with pytest.raises(ValueError, match=f"is not served with {reason}"):
        InferenceEngine(cfg, **args)


def test_disaggregated_prefill_is_refused(toy):
    _, weights, cfg = toy
    engine = _engine(cfg, weights)
    with pytest.raises(ValueError, match="disaggregation"):
        engine.prefill_export([1, 2, 3])
    with pytest.raises(ValueError, match="disaggregation"):
        engine.submit(Request(tokens=[1, 2, 3], prefill={"length": 3}))
