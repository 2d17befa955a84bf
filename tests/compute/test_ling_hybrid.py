"""The hybrid decoder (KDA + MLA + sigmoid-routed experts) at a toy size on
seeded weights: the program's pieces against each other, and the engine's
served tokens against the benchmark's plain reference
(``benchmarks/references/ling_hybrid.py``), which imports nothing of the
program."""

import dataclasses
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.harness.sizes import program_config, sizes_of
from benchmarks.references import ling_hybrid as ref
from dstack_tpu.models import ling_hybrid as model
from dstack_tpu.ops import kda, mla
from dstack_tpu.serving.engine import InferenceEngine, Request

ROOT = Path(__file__).resolve().parents[2]
TOY = json.loads((ROOT / "tests/benchmark/fixture_hybrid/cells/configs"
                  / "tiny-hybrid.json").read_text())
#: the toy with every routed expert held: the uncut model
UNCUT = dict(TOY, num_experts=TOY["num_routed_experts"], expert_offset=0)


@pytest.fixture(scope="module")
def uncut():
    sizes = sizes_of(UNCUT)
    return sizes, ref.init_weights(sizes, 5, config=UNCUT), \
        program_config(UNCUT)


def _engine(cfg, weights, **kw):
    args = dict(batch_size=4, max_len=256, paged=True, kv_block_size=16,
                total_kv_blocks=60, prefill_chunk=32)
    args.update(kw)
    return InferenceEngine(cfg, params=weights, **args)


def _prompt(n, seed=0):
    return np.random.default_rng(seed).integers(0, 256, size=n)


# -- the pieces ----------------------------------------------------------------

def _loads(score, bias, shape):
    chosen = np.asarray(ref._chosen(score + bias, shape)).reshape(-1)
    return np.bincount(chosen, minlength=score.shape[1])


def test_the_fitted_selection_bias_evens_the_experts_loads():
    """Scores whose experts differ by a fixed offset (what a random bias, or
    the hidden states' common direction, does to a router) load a few experts
    many times the mean; under the bias ``_even_bias`` fits every expert gets
    its share, on the tokens it was fitted on and on fresh ones."""
    shape = {"routed": 64, "groups": 8, "keep": 4, "topk": 4}
    keys = jax.random.split(jax.random.key(3), 3)
    offset = 0.6 * jax.random.normal(keys[0], (64,))
    seen, fresh = (jax.nn.sigmoid(jax.random.normal(k, (4096, 64)) + offset)
                   for k in keys[1:])
    skewed = _loads(seen, 0.0, shape)
    assert skewed.max() > 4 * skewed.mean()
    bias = ref._even_bias(seen, shape=tuple(sorted(shape.items())))
    for score, most, least in ((seen, 1.1, 0.9), (fresh, 1.4, 0.6)):
        load = _loads(score, bias, shape)
        assert load.max() < most * load.mean()
        assert load.min() > least * load.mean()


def test_init_weights_follow_the_seed_and_fit_a_bias_a_layer(uncut):
    sizes, weights, _ = uncut
    other = ref.init_weights(sizes, 6, config=UNCUT)
    routed = [lw for lw in weights["layers"] if "router" in lw]
    assert len(routed) == len(UNCUT["layer_types"]) \
        - UNCUT["first_k_dense_replace"]
    for lw, lo in zip(routed, [lw for lw in other["layers"]
                               if "router" in lw]):
        assert float(jnp.abs(lw["router_bias"]).max()) > 0
        assert not np.allclose(lw["router"], lo["router"])
        assert not np.allclose(lw["router_bias"], lo["router_bias"])


@pytest.mark.parametrize("tokens", [16, 64, 192])
def test_kda_block_parallel_prefill_equals_the_recurrence(tokens):
    h, d = 4, 16
    keys = jax.random.split(jax.random.key(tokens), 6)
    q = kda.l2_normalize(jax.random.normal(keys[0], (tokens, h, d))) * d ** -.5
    k = kda.l2_normalize(jax.random.normal(keys[1], (tokens, h, d)))
    v = jax.random.normal(keys[2], (tokens, h, d))
    # gates down to the bound: 64 tokens of -5 would overflow exp(-G)
    g = -5.0 * jax.nn.sigmoid(4 * jax.random.normal(keys[3], (tokens, h, d)))
    beta = jax.nn.sigmoid(jax.random.normal(keys[4], (tokens, h)))
    state = jax.random.normal(keys[5], (h, d, d))
    step, outs = state, []
    for t in range(tokens):
        o, step = kda.kda_step(step, q[t], k[t], v[t], g[t], beta[t])
        outs.append(o)
    o, last = jax.jit(kda.kda_chunked)(state, q, k, v, g, beta)
    assert float(g.min()) < -4.9
    np.testing.assert_allclose(o, jnp.stack(outs), atol=2e-5)
    np.testing.assert_allclose(last, step, atol=2e-5)


def test_kda_token_with_no_gate_and_no_beta_leaves_the_state():
    state = jax.random.normal(jax.random.key(0), (3, 4, 16, 16))
    x = jax.random.normal(jax.random.key(1), (3, 4, 16))
    zero = jnp.zeros((3, 4))
    _, after = kda.kda_step(state, x, x, x, jnp.zeros_like(x), zero)
    np.testing.assert_array_equal(after, state)


def test_mla_absorbed_equals_expanded():
    h, d_n, d_r, d_v, r, lanes, span, window = 4, 16, 8, 16, 32, 128, 48, 8
    keys = jax.random.split(jax.random.key(3), 6)
    w_ukv = jax.random.normal(keys[0], (r, h, d_n + d_v)) * r ** -0.5
    rows = mla.latent_rows(jax.random.normal(keys[1], (span + window, r)),
                           jax.random.normal(keys[2], (span + window, d_r)),
                           lanes)
    q_nope = jax.random.normal(keys[3], (1, h, d_n))
    q_rope = jax.random.normal(keys[4], (1, h, d_r))
    seen, step = 37, 5                  # cached rows, rows of the window
    pos = jnp.concatenate([jnp.arange(span), seen + jnp.arange(window)])
    pos = jnp.where(jnp.arange(span + window) < span,
                    jnp.where(pos < seen, pos, 10_000), pos)
    want = mla.expanded(q_nope, q_rope, rows, w_ukv,
                        jnp.array([seen + step]), pos)
    got = mla.absorbed(q_nope, q_rope, rows[None, :span],
                       (jnp.arange(span) < seen)[None],
                       rows[span:, None], jnp.arange(window) <= step, w_ukv)
    np.testing.assert_allclose(got, want, atol=2e-5)


@pytest.mark.parametrize("masked", [False, True])
def test_expert_shares_add_up_to_the_uncut_layer(uncut, masked):
    """Offsets 0, 4, 8, 12 of 16 experts, the shared expert counted once,
    against the reference's uncut expert layer."""
    sizes, weights, cfg = uncut
    lp = weights["layers"][1]
    h = jax.random.normal(jax.random.key(9), (24, cfg.hidden_size))
    mask = (jnp.arange(24) < 17) if masked else None
    ids, w = model.route(h, lp, cfg)
    total, pairs = 0.0, 0
    for offset in (0, 4, 8, 12):
        part = dataclasses.replace(cfg, experts_held=4, expert_offset=offset)
        mine = {k: (v[offset:offset + 4] if k.startswith("we_") else v)
                for k, v in lp.items()}
        y, counts = model.held_experts(h, ids, w, mine, part, mask)
        total, pairs = total + y, pairs + int(counts.sum())
    total = total + model._swiglu(h, lp["ws_gate"], lp["ws_up"],
                                  lp["ws_down"])
    whole, load = model.moe_ffn(h, lp, cfg, mask)
    want = ref._experts(h, lp, ref._shape(sizes, UNCUT), None)
    rows = slice(0, 17 if masked else 24)
    np.testing.assert_allclose(total[rows], want[rows], atol=2e-5)
    np.testing.assert_allclose(whole[rows], want[rows], atol=2e-5)
    assert pairs == int(load[0]) == (17 if masked else 24) * 4
    assert float(load[1]) == 0.0 and float(load[2]) >= float(load[3])


def test_parameter_count_is_the_tree(uncut):
    _, weights, cfg = uncut
    assert cfg.num_params() == sum(a.size for a in jax.tree.leaves(weights))
    program = model.init_params(jax.random.key(0), cfg)
    assert jax.tree.map(lambda a: (a.shape, a.dtype), program) == \
        jax.tree.map(lambda a: (a.shape, a.dtype), weights)


# -- the engine against the reference -------------------------------------------

def _gaps(weights, sizes, prompt, served, config):
    seq = np.concatenate([prompt, served[:-1]])
    scores = ref.logits(weights, sizes, seq, len(prompt) - 1, len(served),
                        config=config)
    return scores, scores.max(-1) - scores[np.arange(len(served)), served]


@pytest.mark.parametrize("config", [UNCUT, TOY], ids=["uncut", "half-held"])
@pytest.mark.parametrize("prompt_len,new", [(20, 40), (75, 20), (100, 70)])
def test_served_tokens_are_the_reference_s_first_choice(config, prompt_len,
                                                        new):
    """Whole-prompt prefill (20), chunked prefill (75, 100: chunks of 32)
    and paged decode through 8-, 32- and 64-step windows."""
    sizes = sizes_of(config)
    weights = ref.init_weights(sizes, 5, config=config)
    engine = _engine(program_config(config), weights)
    prompt = _prompt(prompt_len)
    served = np.asarray(engine.generate(prompt.tolist(),
                                        max_new_tokens=new).output)
    scores, gaps = _gaps(weights, sizes, prompt, served, config)
    assert len(served) == new and scores.std() > 0.5
    assert gaps.max() < 1e-4


def test_prefill_logits_are_the_reference_s(uncut):
    sizes, weights, cfg = uncut
    engine = _engine(cfg, weights)
    prompt = _prompt(50)
    padded = np.zeros((64,), np.int32)
    padded[:50] = prompt
    logits, _, rec = engine._prefill_program(64)(
        engine.params, jnp.asarray(padded), jnp.int32(50), *engine._state,
        (jnp.arange(1, 5, dtype=jnp.int32), jnp.int32(2)))
    want = ref.logits(weights, sizes, prompt, 49, 1, config=UNCUT)[0]
    np.testing.assert_allclose(logits, want, atol=2e-4)
    assert float(jnp.abs(rec["state"][:, 2]).max()) > 0
    assert float(jnp.abs(rec["state"][:, :2]).max()) == 0


def test_chunked_prefill_carries_the_state(uncut):
    """A prompt in chunks of 32 leaves the slot's recurrent state, its
    latent rows and its tokens as one whole-prompt prefill does."""
    _, weights, cfg = uncut
    prompt = _prompt(100, seed=4).tolist()
    whole = _engine(cfg, weights, prefill_chunk=None)
    chunked = _engine(cfg, weights, prefill_chunk=32)
    a = whole.generate(prompt, max_new_tokens=1)
    b = chunked.generate(prompt, max_new_tokens=1)
    assert a.output == b.output
    for key in ("state", "tail"):
        np.testing.assert_allclose(chunked._state[1][key][:, 0],
                                   whole._state[1][key][:, 0], atol=2e-5)
    rows = lambda e: e._state[0][0, 1:8].reshape(-1, e._state[0].shape[-1])
    np.testing.assert_allclose(rows(chunked)[:100], rows(whole)[:100],
                               atol=2e-5)
    assert whole.generate(prompt, max_new_tokens=24).output == \
        chunked.generate(prompt, max_new_tokens=24).output


@pytest.mark.parametrize("second", [30, 90], ids=["whole", "chunked"])
def test_a_reused_slot_holds_nothing_of_its_last_request(uncut, second):
    _, weights, cfg = uncut
    used = _engine(cfg, weights, batch_size=1, total_kv_blocks=20)
    used.generate(_prompt(80, seed=1).tolist(), max_new_tokens=30)
    assert float(jnp.abs(used._state[1]["state"]).max()) > 0
    fresh = _engine(cfg, weights, batch_size=1, total_kv_blocks=20)
    prompt = _prompt(second, seed=2).tolist()
    assert used.generate(prompt, max_new_tokens=20).output == \
        fresh.generate(prompt, max_new_tokens=20).output
    np.testing.assert_array_equal(used._state[1]["state"],
                                  fresh._state[1]["state"])


def test_slots_decode_together_as_they_do_alone(uncut):
    """Four requests of different lengths in one batch (a chunking one
    among them) get the tokens each gets alone: idle and chunking slots
    leave the others' state alone, and theirs is left alone."""
    _, weights, cfg = uncut
    prompts = [_prompt(n, seed=n).tolist() for n in (18, 40, 70, 120)]
    alone = [_engine(cfg, weights).generate(p, max_new_tokens=24).output
             for p in prompts]
    engine = _engine(cfg, weights)
    reqs = [engine.submit(Request(tokens=p, max_new_tokens=24))
            for p in prompts]
    while not all(r.done.is_set() for r in reqs):
        engine.step()
    assert [r.output for r in reqs] == alone


def test_two_chunked_prompts_keep_their_own_state(uncut):
    """Two prompts chunk at once on a budget of three chunks a step, a short
    request decoding beside them: the older prompt's last chunk and the
    younger's first go out back to back, and each slot's recurrent state
    stays its own (the tokens are those each request gets alone)."""
    _, weights, cfg = uncut
    prompts = [_prompt(n, seed=n).tolist() for n in (18, 100, 120)]
    alone = [_engine(cfg, weights).generate(p, max_new_tokens=24).output
             for p in prompts]
    engine = _engine(cfg, weights, batch_size=3)
    order = []
    dispatch = engine._dispatch_chunk
    engine._dispatch_chunk = lambda slot, st: (order.append(slot),
                                               dispatch(slot, st))[1]
    reqs = [engine.submit(Request(tokens=p, max_new_tokens=24))
            for p in prompts]
    steps = 0
    while not all(r.done.is_set() for r in reqs):
        engine.step()
        steps += 1
        assert steps < 100
    assert order == [1] * 4 + [2] * 4   # oldest first, each to its end
    assert [r.output for r in reqs] == alone


@pytest.mark.parametrize("option,value", [
    ("paged", False), ("prefix_cache", True),
    ("kv_quantize", "int8"), ("quantize", "int8"), ("mesh", "a mesh")])
def test_options_the_model_cannot_be_served_with_raise(option, value):
    cfg = model.LingHybridConfig.tiny()
    args = dict(params={"layers": {}}, batch_size=2, max_len=64, paged=True,
                kv_block_size=16)
    args[option] = value
    with pytest.raises(ValueError, match="is not served with"):
        InferenceEngine(cfg, **args)


def test_disaggregated_prefill_is_refused(uncut):
    _, weights, cfg = uncut
    engine = _engine(cfg, weights)
    with pytest.raises(ValueError, match="disaggregation"):
        engine.prefill_export([1, 2, 3])
    with pytest.raises(ValueError, match="disaggregation"):
        engine.submit(Request(tokens=[1, 2, 3], prefill={"length": 3}))


def test_expert_load_reaches_the_telemetry():
    from dstack_tpu.telemetry.serving import EngineTelemetry

    sizes = sizes_of(TOY)
    telemetry = EngineTelemetry()
    engine = _engine(program_config(TOY),
                     ref.init_weights(sizes, 5, config=TOY),
                     telemetry=telemetry)
    engine.generate(_prompt(20).tolist(), max_new_tokens=9)
    got = {(s.name, tuple(sorted(s.labels.items()))): s.value
           for s in telemetry.prometheus_samples()}
    pairs = lambda where: got[("dstack_serving_moe_pairs_total",
                               (("where", where),))]
    # one 8-step window, one live slot, 3 expert layers, 4 experts a token
    assert pairs("held") + pairs("absent") == 8 * 3 * 4
    assert 0 < pairs("held") < 8 * 3 * 4
    assert got[("dstack_serving_moe_experts_touched_sum", ())] <= \
        pairs("held")
    assert got[("dstack_serving_recurrent_state_bytes", ())] == \
        engine._programs.recurrent_state_bytes() == sum(
            a.size * a.dtype.itemsize
            for a in jax.tree.leaves(engine._state[1]))
