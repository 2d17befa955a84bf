"""The Nemotron-H decoder (Mamba-2 + routed relu-squared experts with a
shared one + GQA without positions; one mixer a block) at a toy size on
seeded weights: the program's pieces and the engine's programs against the
benchmark's plain reference (``benchmarks/references/nemotron_h.py``), which
imports nothing of the program and runs the state-space layer as its
token-by-token recurrence.

Tolerances.  The toy is float32 and so is the reference; they differ in the
order of their sums (the chunked scan's matrix products against the
recurrence, a chunk's convolution over a carried tail, attention over pages
merged by logsumexp, the experts' grouped product against every expert
masked), which moves a logit by 1e-6 to 5e-5 here.  Logits are held to
``ATOL`` 2e-4 and a served token's score to ``GAP`` 1e-4 under the
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
from benchmarks.references import nemotron_h as ref
from dstack_tpu.models import nemotron_h as model
from dstack_tpu.serving.engine import InferenceEngine, Request
from dstack_tpu.serving.mixed import MixedPrograms
from tests.compute.test_prompt_groups import send_group

ROOT = Path(__file__).resolve().parents[2]
TOY = json.loads((ROOT / "tests/benchmark/fixture_nemotron/cells/configs"
                  / "tiny-nemotron.json").read_text())
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


def _slot(rec, slot):
    return jax.tree.map(lambda a: np.asarray(a[slot]), rec)


# -- the pieces ---------------------------------------------------------------

def test_config_is_the_published_one_and_the_cut():
    whole = model.NemotronHConfig()
    kinds = whole.layer_kinds
    assert (kinds.count("mamba"), kinds.count("experts"),
            kinds.count("attention")) == (23, 23, 6)
    assert (whole.d_inner, whole.conv_dim, whole.kv_lanes,
            whole.experts_held) == (4096, 6144, 256, 128)
    assert whole.num_params() == 31_577_940_288
    assert (whole.block_params("mamba"), whole.block_params("attention")) \
        == (38_744_896, 23_399_040)
    cut = model.NemotronHConfig.nemotron_3_nano_30b_a3b_9l_ep2()
    assert cut.hybrid_override_pattern == "MEMEM*EME"
    assert (cut.mamba_layers, cut.attention_layers) == (4, 1)
    assert cut.block_params("experts") == 658_885_376
    assert cut.num_params() == 3_166_244_352
    # a slot: 4 layers x (2 MiB of float32 state + 3 rows of 6,144 bf16)
    assert cut.recurrent_state_bytes(1) == 4 * (64 * 64 * 128 * 4
                                                + 3 * 6144 * 2) == 8_536_064
    for bad in (dict(use_conv_bias=False), dict(tie_word_embeddings=True),
                dict(norm_topk_prob=False), dict(attention_bias=True),
                dict(mlp_bias=True), dict(n_shared_experts=2),
                dict(hybrid_override_pattern="MEM-EM"),
                dict(hybrid_override_pattern="MEM"),
                dict(experts_held=8, expert_offset=12), dict(n_groups=3)):
        with pytest.raises(ValueError):
            model.NemotronHConfig.tiny(**bad)


def test_parameter_count_is_the_tree(toy):
    _, weights, cfg = toy
    assert cfg.num_params() == sum(a.size for a in jax.tree.leaves(weights))
    program = model.init_params(jax.random.key(0), cfg)
    assert jax.tree.map(lambda a: (a.shape, a.dtype), program) == \
        jax.tree.map(lambda a: (a.shape, a.dtype), weights)


def test_init_weights_follow_the_seed_and_the_published_draws(toy):
    """A bias fitted for every router; ``A`` in 1..heads, the steps
    ``softplus(dt_bias)`` in ``time_step_min..max``, ``D`` ones: a head's
    per-token decay ``exp(-dt A)`` lies in (0.4, 1), so its state remembers
    one to hundreds of tokens."""
    sizes, weights, cfg = toy
    other = ref.init_weights(sizes, 6, config=TOY)
    routed = [lw for lw in weights["layers"] if "router" in lw]
    assert len(routed) == TOY["hybrid_override_pattern"].count("E")
    for lw, lo in zip(routed, [lw for lw in other["layers"]
                               if "router" in lw]):
        assert float(jnp.abs(lw["router_bias"]).max()) > 0
        assert not np.allclose(lw["router"], lo["router"])
    for lw in weights["layers"]:
        if "A_log" not in lw:
            continue
        a, step = jnp.exp(lw["A_log"]), jax.nn.softplus(lw["dt_bias"])
        assert 1.0 <= float(a.min()) and float(a.max()) <= cfg.mamba_num_heads
        assert cfg.time_step_min * 0.999 <= float(step.min())
        assert float(step.max()) <= cfg.time_step_max * 1.001
        assert float(jnp.exp(-step * a).min()) > 0.4
        assert bool((lw["D"] == 1).all())


@pytest.mark.parametrize("cuts", [(5, 27), (1, 1, 1, 29), (32,), (20, 12)],
                         ids=["two", "single-tokens", "whole", "20-12"])
def test_mamba_mixer_in_pieces_equals_the_recurrence(toy, cuts):
    """A sequence fed in pieces with the state and the tail carried (pieces
    of ONE token split the convolution's reach), each piece padded to a
    bucket of 32 whose padding must not advance the state; then token by
    token as a decode step does; against the reference's recurrence over the
    whole sequence."""
    sizes, weights, cfg = toy
    lp = weights["layers"][0]
    x = jax.random.normal(jax.random.key(2), (32, cfg.hidden_size))
    h = ref._rms_norm(x, lp["norm"], cfg.layer_norm_epsilon)
    want = ref._mamba(h, lp, ref._shape(sizes, TOY), None)
    state = jnp.zeros((cfg.mamba_num_heads, cfg.mamba_head_dim,
                       cfg.ssm_state_size))
    tail = jnp.zeros((cfg.conv_reach, cfg.conv_dim))
    outs, start = [], 0
    for n in cuts:
        padded = jnp.full((32, cfg.hidden_size), 3.0).at[:n].set(
            h[start:start + n])
        y, state, tail = model.mamba_sequence(padded, lp, cfg, n, state, tail)
        outs.append(y[:n])
        start += n
    np.testing.assert_allclose(jnp.concatenate(outs), want, atol=5e-5)
    states = jnp.zeros((2,) + state.shape)
    tails = jnp.zeros((2,) + tail.shape)
    live = jnp.array([True, False])
    for t in range(32):
        y, states, tails = model.mamba_token(
            jnp.stack([h[t], h[t]]), lp, cfg, live, states, tails)
        np.testing.assert_allclose(y[0], want[t], atol=5e-5)
    np.testing.assert_allclose(states[0], state, atol=5e-5)
    np.testing.assert_allclose(tails[0], tail, atol=1e-6)
    # not live: state and tail kept
    assert float(jnp.abs(states[1]).max()) == 0
    assert float(jnp.abs(tails[1]).max()) == 0


def test_attention_projection_applies_no_position(toy):
    sizes, weights, cfg = toy
    lp = weights["layers"][3]
    h = jax.random.normal(jax.random.key(3), (6, cfg.hidden_size))
    q, k, v = model.attention_project(h, lp, cfg)
    np.testing.assert_allclose(
        q, (h @ lp["wq"]).reshape(6, cfg.num_attention_heads, cfg.head_dim),
        atol=2e-5)
    assert k.shape == v.shape == (6, cfg.num_key_value_heads, cfg.head_dim)
    # the same rows whatever their order: no position enters
    q2, _, _ = model.attention_project(h[::-1], lp, cfg)
    np.testing.assert_allclose(q2[::-1], q, atol=2e-5)


EXPERTS_16 = dict(TOY, hybrid_override_pattern="E", num_hidden_layers=1)


@pytest.mark.parametrize("masked", [False, True])
@pytest.mark.parametrize("share", [4, 8, 16])
def test_expert_shares_add_up_to_the_uncut_layer(share, masked):
    """Shares of 4, 8 (experts 0-7 and 8-15 of the toy's 16: the cell's EP
    2) and all 16 experts: what the shares' grouped products give, with the
    shared expert counted ONCE, adds up to the reference's uncut expert
    block, pair for pair."""
    sizes = sizes_of(EXPERTS_16)
    weights = ref.init_weights(sizes, 7, config=EXPERTS_16)
    cfg = program_config(EXPERTS_16)
    lp = weights["layers"][0]
    h = jax.random.normal(jax.random.key(9), (24, cfg.hidden_size))
    mask = (jnp.arange(24) < 17) if masked else None
    ids, w = model.route(h, lp, cfg)
    total, pairs = model.relu2(h, lp["ws_up"], lp["ws_down"]), 0
    for offset in range(0, 16, share):
        part = dataclasses.replace(cfg, experts_held=share,
                                   expert_offset=offset)
        mine = {k: (v[offset:offset + share] if k.startswith("we_") else v)
                for k, v in lp.items()}
        y, counts = model.held_experts(h, ids, w, mine, part, mask,
                                       form="relu2")
        total, pairs = total + y, pairs + int(counts.sum())
    whole, load = model.moe_block(h, lp, cfg, mask)
    want = ref._experts(h, lp, ref._shape(sizes, EXPERTS_16), None)
    rows = slice(0, 17 if masked else 24)
    np.testing.assert_allclose(total[rows], want[rows], atol=5e-5)
    np.testing.assert_allclose(whole[rows], want[rows], atol=5e-5)
    assert pairs == int(load[0]) == (17 if masked else 24) * 3
    assert float(load[1]) == 0.0 and float(load[2]) >= float(load[3])
    # the reference given one share computes that share (and the shared
    # expert, which every chip computes alike)
    part = dict(EXPERTS_16, n_routed_experts=share, expert_offset=0)
    mine = {k: (v[:share] if k.startswith("we_") else v)
            for k, v in lp.items()}
    first, _ = model.moe_block(
        h, mine, dataclasses.replace(cfg, experts_held=share), None)
    np.testing.assert_allclose(
        first, ref._experts(h, mine, ref._shape(sizes, part), None),
        atol=5e-5)


def test_the_routed_stacks_lie_width_by_hidden(toy):
    """Both of an expert's matrices are kept ``[experts, width, hidden]``
    (up as ``nn.Linear`` keeps it), so that the published width, 1856, no
    whole number of 128-lane tiles, lies on the second-minor dim, where the
    chip pads nothing and the grouped product copies whole rows."""
    _, weights, cfg = toy
    lp = weights["layers"][1]
    want = (cfg.experts_held, cfg.moe_intermediate_size, cfg.hidden_size)
    assert lp["we_up"].shape == lp["we_down"].shape == want
    h = jax.random.normal(jax.random.key(4), (10, cfg.hidden_size))
    ids, w = model.route(h, lp, cfg)
    got, _ = model.held_experts(h, ids, w, lp, cfg, None, form="relu2")
    plain = sum(
        w[:, j, None] * jax.vmap(lambda x, e: model.relu2(
            x, lp["we_up"][e].T, lp["we_down"][e]))(h, ids[:, j])
        for j in range(cfg.num_experts_per_tok))
    np.testing.assert_allclose(got, plain, atol=5e-5)


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
    padded = np.zeros((1, 64), np.int32)
    padded[0, :50] = prompt
    bids = jnp.arange(1, 5, dtype=jnp.int32)
    # the provider's group of one prompt gives the logits; the engine's
    # program (a group of one live prompt) samples its first token from them
    logits, _, _ = jax.jit(engine._programs.prefill_batch_fn(64))(
        engine.params, jnp.asarray(padded), jnp.asarray([50], jnp.int32),
        *engine._state, bids[None], jnp.asarray([2], jnp.int32))
    want = _reference(weights, sizes, prompt, 49, 1)[0]
    np.testing.assert_allclose(logits[0], want, atol=ATOL)
    assert want.std() > 0.5
    pool, rec, first = send_group(engine, 64, [(prompt, 2, bids)])
    assert int(first[2]) == int(np.argmax(logits[0]))
    # the slot's states, tails and pages are written, the others' are not
    for leaf in jax.tree.leaves(rec):
        assert float(jnp.abs(leaf[2]).max()) > 0
        assert float(jnp.abs(leaf[:2]).max()) == 0
        assert float(jnp.abs(leaf[3:]).max()) == 0
    assert float(jnp.abs(pool["k"][:, 1:5]).max()) > 0
    assert float(jnp.abs(pool["v"][:, 5:]).max()) == 0


@pytest.mark.parametrize("prompt_len", [49, 50, 75],
                         ids=["last-chunk-of-1", "last-chunk-of-2", "75"])
def test_chunked_prefill_logits_are_the_reference_s(toy, prompt_len):
    """Chunks of 16 (one block of the toy's scan) through the chunk
    program: every boundary hands a state on and splits a convolution's
    reach, and a last chunk of one token makes its new tail from two
    carried rows and one of its own.  The last chunk's logits against the
    reference; states, tails and pages against one whole prefill."""
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
        logits, *state = jax.jit(chunked._programs.chunk_fn(16))(
            chunked.params, jnp.asarray(padded), jnp.int32(len(piece)),
            jnp.int32(start), *state, (tables, jnp.int32(1)))
    want = _reference(weights, sizes, prompt, prompt_len - 1, 1)[0]
    np.testing.assert_allclose(logits, want, atol=ATOL)
    whole = _engine(cfg, weights, prefill_chunk=None)
    bucket = whole._bucket(prompt_len)
    pool, rec, _ = send_group(whole, bucket,
                              [(prompt, 1, tables[:bucket // 16])])
    for got, one in zip(jax.tree.leaves(_slot(state[1], 1)),
                        jax.tree.leaves(_slot(rec, 1))):
        np.testing.assert_allclose(got, one, atol=5e-5)
    for key in ("k", "v"):
        rows = lambda p: p[key][:, 1:9].reshape(1, -1, p[key].shape[-1])
        np.testing.assert_allclose(rows(state[0])[:, :prompt_len],
                                   rows(pool)[:, :prompt_len], atol=2e-5)


@pytest.mark.parametrize("kernel", [False, True], ids=["gathered", "kernel"])
@pytest.mark.parametrize("prompt_len,new", [(20, 40), (75, 20), (100, 80)])
def test_served_tokens_are_the_reference_s_first_choice(toy, monkeypatch,
                                                        prompt_len, new,
                                                        kernel):
    """Whole-prompt prefill (20), chunked prefill (75, 100: chunks of 32)
    and decode through the engine's cache (the states carried through 8-,
    32- and 64-step windows, the one attention layer's pages through the
    gathered view and through the block-table kernel, interpreted here; 100
    + 80 tokens pass 8 table columns of 16).  Every served token is the
    first choice of the reference's full forward (logits, not tokens:
    ``GAP`` under its best); after the run the slot's pages are what one
    prefill of the whole sequence leaves."""
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
    seq = np.concatenate([prompt, served[:-1]])
    whole = _engine(cfg, weights, prefill_chunk=None)
    _, whole_pages = _serve(whole, seq, 2)   # 1 would end in its admission
    n, blocks = len(seq), -(-len(seq) // 16)
    for key in ("k", "v"):
        rows = lambda e, ids: e._state[0][key][:, np.asarray(ids[:blocks])] \
            .reshape(1, -1, cfg.kv_lanes)[:, :n]
        np.testing.assert_allclose(rows(engine, pages),
                                   rows(whole, whole_pages), atol=2e-5)


@pytest.mark.parametrize("second", [30, 90], ids=["whole", "chunked"])
def test_a_reused_slot_starts_from_zeros(toy, second):
    """A shorter request in a used slot starts its states and tails from
    zeros, by whole-prompt prefill and by its first chunk."""
    _, weights, cfg = toy
    used = _engine(cfg, weights, batch_size=1, total_kv_blocks=20)
    used.generate(_prompt(80, seed=1).tolist(), max_new_tokens=30)
    assert all(float(jnp.abs(a).max()) > 0
               for a in jax.tree.leaves(used._state[1]))
    fresh = _engine(cfg, weights, batch_size=1, total_kv_blocks=20)
    prompt = _prompt(second, seed=2).tolist()
    assert used.generate(prompt, max_new_tokens=20).output == \
        fresh.generate(prompt, max_new_tokens=20).output
    for a, b in zip(jax.tree.leaves(used._state[1]),
                    jax.tree.leaves(fresh._state[1])):
        np.testing.assert_array_equal(a, b)


def test_inactive_slots_keep_state_tail_and_pages(toy):
    """A decode window leaves the states, the tails and the pages of slots
    that are not active bit for bit (their step is 0; their window rows
    land in the NULL block), and counts the live slot's updates."""
    _, weights, cfg = toy
    engine = _engine(cfg, weights)
    for slot, n in ((0, 30), (2, 45)):
        engine.generate(_prompt(n, seed=slot).tolist(), max_new_tokens=2)
    pool, rec = engine._state
    pool = jax.tree.map(lambda a: a.at[:, 1:].add(0.5), pool)
    rec = jax.tree.map(lambda a: a + 0.25, rec)
    before = jax.tree.map(np.asarray, (pool, rec))
    b = engine.batch_size
    active = jnp.array([False, True, False, False])
    tables = jnp.asarray(np.arange(1, 1 + 4 * b, dtype=np.int32).reshape(b, 4))
    out = engine._decode_window_program(8, False, 4)(
        engine.params, jnp.zeros((b,), jnp.int32),
        jnp.full((b,), 20, jnp.int32), active, pool, rec,
        jnp.zeros((b,)), jnp.ones((b,)), jnp.zeros((b,), jnp.int32), tables,
        jax.random.PRNGKey(0))
    _, _, lengths, pool_after, rec_after, counts = out
    assert lengths.tolist() == [20, 28, 20, 20]
    for after, was in zip(jax.tree.leaves(rec_after),
                          jax.tree.leaves(before[1])):
        np.testing.assert_array_equal(after[jnp.array([0, 2, 3])],
                                      was[[0, 2, 3]])
        assert not np.array_equal(after[1], was[1])
    mine = np.asarray(tables[1])
    others = np.setdiff1d(np.arange(1, pool_after["k"].shape[1]), mine)
    for key in ("k", "v"):
        np.testing.assert_array_equal(pool_after[key][:, others],
                                      before[0][key][:, others])
        assert not np.array_equal(pool_after[key][:, mine],
                                  before[0][key][:, mine])
    # one live slot, 8 steps: 2 expert blocks x 3 experts a token; 3 Mamba
    # layers
    assert counts.shape == (model.LOAD_FIELDS + 1,)
    assert float(counts[0]) == 8 * 2 * 3 and float(counts[1]) == 0
    assert float(counts[-1]) == 8 * 3


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
    reference's weights: tokens, the expert load, the state-space counters
    and the gauges."""
    from dstack_tpu.serving.server import CONFIGS
    from dstack_tpu.telemetry.serving import make_engine_telemetry

    assert CONFIGS["nemotron-h-tiny"]() == model.NemotronHConfig.tiny()
    assert CONFIGS["nemotron-3-nano-30b-a3b-9l-ep2"]().num_params() == \
        3_166_244_352
    sizes, weights, cfg = toy
    engine = InferenceEngine(
        cfg, params=weights, batch_size=2, max_len=256, quantize=None,
        mesh=None, paged=True, kv_block_size=16, total_kv_blocks=None,
        prefix_cache=False, kv_quantize=None,
        prefill_chunk=InferenceEngine.TUNED_PREFILL_CHUNK,
        telemetry=make_engine_telemetry(), compile_cache=None)
    assert type(engine._programs) is MixedPrograms
    assert engine._programs.model is model
    prompt = _prompt(20)
    served = np.asarray(engine.generate(prompt.tolist(),
                                        max_new_tokens=9).output)
    _, gaps = _gaps(weights, sizes, prompt, served)
    assert gaps.max() < GAP
    got = {(s.name, tuple(sorted(s.labels.items()))): s.value
           for s in engine.telemetry.prometheus_samples()}
    pairs = lambda where: got[("dstack_serving_moe_pairs_total",
                               (("where", where),))]
    # one 8-step window, one live slot, 2 expert blocks, 3 experts a token
    assert pairs("held") == 8 * 2 * 3 and pairs("absent") == 0
    assert got[("dstack_serving_moe_experts_touched_sum", ())] <= \
        pairs("held")
    # 3 Mamba layers: 8 updates each; the prompt's bucket of 32 is 2 blocks
    # of the toy's 16 in each
    assert got[("dstack_serving_ssm_slot_layer_steps_total", ())] == 8 * 3
    assert got[("dstack_serving_ssm_scan_chunks_total", ())] == 2 * 3
    rec = engine._state[1]
    assert got[("dstack_serving_recurrent_state_bytes", ())] == \
        engine._programs.recurrent_state_bytes() == \
        sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(rec))
    assert [a.dtype for a in rec["ssm"]] == [jnp.float32] * 3
    assert engine._programs.kv_geometry() == (1, 2 * 1 * 32 * 4)
    pool = engine._state[0]
    assert set(pool) == {"k", "v"}
    assert pool["k"].shape == (1, 2 * 16 + 1, 16, 32)


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
    cfg = model.NemotronHConfig.tiny()
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
