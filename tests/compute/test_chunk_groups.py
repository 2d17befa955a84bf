"""The chunk queue's chunks go out in rounds, a group of chunks of different
slots a program, where the provider has ``chunk_batch_fn`` (the LFM2 and
Nemotron-H families: a 512-token chunk routes pairs to every held expert, so
a program a chunk streams every expert once a chunk).  Held here: a group's
program leaves what one-chunk programs run in turn leave (logits, the first
token a last chunk samples, pages, tails and states) and its unused entry
writes nothing; the engine serves the tokens of one program a chunk in
fewer programs than chunks; and a provider without the form sends the chunk
programs it always sent, in the same order.  CPU, toy sizes."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dstack_tpu.serving.engine import InferenceEngine, Request
from dstack_tpu.telemetry.serving import EngineTelemetry
from tests.compute.test_family_seam import _lfm2, _llama, _nemotron

FAMILIES = {"lfm2": _lfm2, "nemotron": _nemotron}
PAGED = dict(paged=True, kv_block_size=16, total_kv_blocks=60)


@pytest.fixture(scope="module")
def models():
    built = {}

    def get(name):
        if name not in built:
            built[name] = FAMILIES[name]()[:2]      # config and weights
        return built[name]

    return get


def _engine(model, **kwargs):
    cfg, weights = model
    args = dict(batch_size=6, max_len=256, rng_seed=7, prefill_chunk=32,
                **PAGED)
    args.update(kwargs)
    return InferenceEngine(cfg, params=weights, **args)


def _slot(rec, slot):
    return [np.asarray(leaf[:, slot] if isinstance(rec, jax.Array)
                       else leaf[slot]) for leaf in jax.tree.leaves(rec)]


def _with_nan_slot(rec, slot):
    if isinstance(rec, jax.Array):          # LFM2: [conv layers, slots, ...]
        return rec.at[:, slot].set(jnp.nan)
    return jax.tree.map(lambda a: a.at[slot].set(jnp.nan), rec)


def _copy(tree):
    return jax.tree.map(jnp.copy, tree)


#: (slot, prompt length, chunk of it in the group): a prompt's first chunk
#: (from zeros), a middle chunk, and a last chunk of 13 tokens, which
#: samples the prompt's first token
ENTRIES = ((0, 80, 0), (2, 96, 1), (4, 77, 2))


@pytest.mark.parametrize("sampling", ["greedy", "sampled"])
@pytest.mark.parametrize("family", FAMILIES)
def test_a_group_leaves_what_one_chunk_programs_leave(models, family,
                                                      sampling):
    """Three chunks of three slots (chunks of 32) and an unused entry in one
    program, against each chunk through the one-chunk program in turn from
    the same state: the same logits, the same first token for the prompt
    the group ends (the sampler's key and constants as the one-chunk path's
    sampler takes them), the same pages, tails and states.  The unused
    entry writes no block, no slot and no first token: its slot and the
    blocks no entry owns, filled with NaN, stay NaN, the other slots stay
    as they were, and no NaN reaches the entries."""
    engine = _engine(models(family))
    engine.PREFILL_BATCH_TOKENS = 128           # 4 chunks at 32
    assert engine._prompts_per_program(32) == 4
    one_chunk = jax.jit(engine._programs.chunk_fn(32))
    rng = np.random.default_rng(5)
    prompts = {slot: rng.integers(1, 256, size=n) for slot, n, _ in ENTRIES}
    tables = {slot: np.zeros((engine._blocks_per_slot,), np.int32)
              for slot, _, _ in ENTRIES}
    for i, (slot, n, _) in enumerate(ENTRIES):
        tables[slot][:-(-n // 16)] = 1 + 8 * i + np.arange(-(-n // 16))

    def run_chunk(state, slot, chunk):
        prompt = prompts[slot]
        piece = prompt[32 * chunk:32 * chunk + 32]
        padded = np.zeros((32,), np.int32)
        padded[:len(piece)] = piece
        logits, *state = one_chunk(
            engine.params, jnp.asarray(padded), jnp.int32(len(piece)),
            jnp.int32(32 * chunk), *state,
            (jnp.asarray(tables[slot]), jnp.int32(slot)))
        return logits, state

    pool, rec = engine._state
    pool = jax.tree.map(lambda a: a.at[:, 25:].set(jnp.nan), pool)
    rec = _with_nan_slot(rec, 5)
    state = [pool, rec]
    # the chunks before the group's, and a stale tail in the first chunk's
    # slot (a first chunk starts from zeros, whatever its slot holds)
    for slot, _, chunk in ENTRIES:
        for before in range(chunk):
            _, state = run_chunk(state, slot, before)
    state[1] = _with_nan_slot(state[1], 0)
    before = _copy(state)

    alone, logits_alone = _copy(state), []
    for slot, _, chunk in ENTRIES:
        logits, alone = run_chunk(alone, slot, chunk)
        logits_alone.append(logits)

    g = 4
    tokens = np.zeros((g, 32), np.int32)
    lengths = np.zeros((g,), np.int32)
    starts = np.zeros((g,), np.int32)
    rows = np.full((g, engine._blocks_per_slot), engine._alloc.num_blocks,
                   np.int32)
    slots = np.full((g,), engine.batch_size, np.int32)
    for i, (slot, _, chunk) in enumerate(ENTRIES):
        piece = prompts[slot][32 * chunk:32 * chunk + 32]
        tokens[i, :len(piece)] = piece
        lengths[i], starts[i], slots[i] = len(piece), 32 * chunk, slot
        rows[i] = tables[slot]
    target = (jnp.asarray(rows), jnp.asarray(slots))
    logits, *_ = jax.jit(engine._programs.chunk_batch_fn(32))(
        engine.params, tokens, lengths, starts, *_copy(before), target)
    for got, want in zip(np.asarray(logits), logits_alone):
        np.testing.assert_allclose(got, want, atol=5e-5)

    # the engine's program: the last chunk (slot 4) samples its first token
    temp = 0.0 if sampling == "greedy" else 0.9
    key = jax.random.PRNGKey(11)
    consts = np.zeros((g, 3), np.float32)
    consts[2] = (temp, 0.95, 0)
    first_to = np.full((g,), engine.batch_size, np.int32)
    first_to[2] = 4
    pool, rec, first = engine._chunk_program(32)(
        engine.params, tokens, lengths, starts, *_copy(before), target,
        consts, (engine._rng_key, engine._rng_key, key, engine._rng_key),
        first_to, jnp.full((6,), -1, jnp.int32))
    want = engine._first_token_program()(
        logits_alone[2], jnp.asarray([temp, 0.95, 0, 4], jnp.float32), key,
        jnp.full((6,), -1, jnp.int32))
    assert np.asarray(first).tolist() == np.asarray(want).tolist()
    assert np.asarray(first)[[0, 1, 2, 3, 5]].tolist() == [-1] * 5
    if sampling == "greedy":
        assert int(first[4]) == int(np.argmax(logits_alone[2]))

    for slot in range(6):
        got = _slot(rec, slot)
        want = _slot(alone[1] if slot in prompts else before[1], slot)
        for a, b in zip(got, want):
            np.testing.assert_allclose(a, b, atol=5e-5)
    assert all(np.isnan(leaf).all() for leaf in _slot(rec, 5))
    owned = {0} | {int(b) for row in tables.values() for b in row}
    others = [b for b in range(engine._alloc.num_blocks) if b not in owned]
    for key_ in ("k", "v"):
        got, want = np.asarray(pool[key_]), np.asarray(alone[0][key_])
        # the blocks no entry owns (the NaN ones from 25 on among them)
        # stay as they were
        np.testing.assert_array_equal(got[:, others],
                                      np.asarray(before[0][key_])[:, others])
        assert np.isnan(got[:, 25:]).all()
        for slot, n, chunk in ENTRIES:
            ids = tables[slot][:-(-n // 16)]
            real = min(n, 32 * chunk + 32)
            rows_of = lambda p: p[:, ids].reshape(p.shape[0], -1,
                                                  p.shape[-1])[:, :real]
            np.testing.assert_allclose(rows_of(got), rows_of(want),
                                       atol=2e-5)


def _counters(engine) -> dict:
    prefix = "dstack_serving_"
    return {name[len(prefix):]: value for name, value in
            engine.telemetry.recorder.summary()["counters"].items()}


#: (prompt length, new tokens): whole prompts and chunked ones (chunks of
#: 32) of one, two and four chunks, with last chunks of 32, 64 and 128
#: buckets' worth mixed in one round
REQUESTS = ((10, 9), (70, 6), (40, 11), (130, 5), (33, 12), (100, 7),
            (12, 5), (64, 10), (95, 3), (47, 8))


@pytest.mark.parametrize("family", FAMILIES)
def test_served_tokens_are_those_of_one_chunk_programs(models, family):
    """Ten greedy requests on six slots, whole and chunked prompts mixed,
    slots reused: rounds of groups of chunks of different slots serve every
    request the tokens it gets from one program a chunk, in fewer programs
    than chunks."""
    def serve(grouped):
        engine = _engine(models(family), telemetry=EngineTelemetry())
        engine.PREFILL_BATCH_TOKENS = 128       # 4 chunks a program at 32
        engine.DECODE_WINDOWS = (8,)
        engine._batched_chunks = grouped
        rng = np.random.default_rng(13)
        requests = [engine.submit(Request(
            tokens=rng.integers(1, 200, size=n).tolist(), max_new_tokens=new))
            for n, new in REQUESTS]
        for _ in range(500):
            if all(r.done.is_set() for r in requests):
                break
            engine.step()
        counters = _counters(engine)
        return ([r.output for r in requests],
                counters["prefill_chunks_total"],
                counters["prefill_chunk_programs_total"])

    grouped, chunks, programs = serve(True)
    alone, chunks_alone, programs_alone = serve(False)
    assert grouped == alone
    assert [len(out) for out in grouped] == [new for _, new in REQUESTS]
    assert chunks == chunks_alone == programs_alone
    assert programs < chunks


def test_a_provider_without_the_form_sends_the_chunk_programs_it_sent():
    """A dense engine's chunk queue: one program a chunk, the oldest
    admitted prompt's chunks first and each prompt to its end, until the
    step's budget (8 chunks) is spent; then each completed prompt's sampler
    in admission order and one slot update; chunks and programs counted
    alike."""
    cfg, weights, _ = _llama()
    engine = InferenceEngine(cfg, params=weights, batch_size=8, max_len=128,
                             prefill_chunk=32, telemetry=EngineTelemetry(),
                             **PAGED)
    assert not engine._batched_chunks
    sent = []
    run = engine._run_program

    def record(table, key, make, *args):
        if key[0] == "chunk":
            # (the slot whose table row the chunk writes, its start)
            row = np.asarray(args[-1])
            slot = next(s for s in range(engine.batch_size)
                        if (engine._tables_host[s] == row).all())
            key = key + (slot, int(args[3]))
        sent.append(key)
        return run(table, key[:2] if key[0] == "chunk" else key, make, *args)

    engine._run_program = record
    for n in (70, 20, 40, 100, 50):
        engine.submit(Request(tokens=list(range(1, n + 1)), max_new_tokens=3))
    engine._admit()
    sent.clear()
    assert engine._advance_chunks(engine.batch_size) == (8, True)
    engine._finish_chunked()
    assert sent == [("chunk", 32, 0, 0), ("chunk", 32, 0, 32),
                    ("chunk", 32, 0, 64), ("chunk", 32, 2, 0),
                    ("chunk", 32, 2, 32), ("chunk", 32, 3, 0),
                    ("chunk", 32, 3, 32), ("chunk", 32, 3, 64),
                    "first_token", "first_token", "slot_update"]
    counters = _counters(engine)
    assert counters["prefill_chunks_total"] == 8
    assert counters["prefill_chunk_programs_total"] == 8
    assert counters["prefill_budget_exhausted_total"] == 1
