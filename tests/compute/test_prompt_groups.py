"""An admission pass's whole prompts go out a group a bucket, in one program
each, where the provider has ``prefill_batch_fn`` (the LFM2 and Nemotron-H
families: every prompt routes pairs to every held expert, so a program a
prompt streams every expert once a prompt).  Held here: a group's program
leaves what one-prompt programs leave (first tokens, pages, tails and
states) and its unused entries write nothing; a pass sends a program a full
group and one a partial group at its end (the counters); the served tokens
are those of one-prompt programs; a group whose program raises fails every
request it held; and a provider without the form sends the programs it
always sent, in the same order.  CPU, toy sizes."""

import threading

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


def send_group(engine, bucket, entries, state=None, first_tokens=None):
    """The engine's prefill program of ``bucket`` on ``entries``, each
    ``(prompt, slot, block ids)``, greedy; the program's other entries
    unused.  Returns ``(pool, rec, first_tokens)`` (the state and the
    vector passed in are donated)."""
    p = engine._prompts_per_program(bucket)
    tokens = np.zeros((p, bucket), np.int32)
    lengths = np.zeros((p,), np.int32)
    bids = np.full((p, bucket // engine._block_size), engine._alloc.num_blocks,
                   np.int32)
    slots = np.full((p,), engine.batch_size, np.int32)
    for i, (prompt, slot, ids) in enumerate(entries):
        tokens[i, :len(prompt)] = prompt
        lengths[i] = len(prompt)
        bids[i] = ids
        slots[i] = slot
    consts = np.tile(np.float32([0.0, 1.0, 0.0]), (p, 1))
    return engine._prefill_program(bucket)(
        engine.params, tokens, lengths, *(state or engine._state), bids,
        slots, consts, (jax.random.PRNGKey(0),) * p,
        engine._first_tokens if first_tokens is None else first_tokens)


def _engine(model, **kwargs):
    cfg, weights = model
    args = dict(batch_size=6, max_len=256, rng_seed=7, **PAGED)
    args.update(kwargs)
    return InferenceEngine(cfg, params=weights, **args)


def _slot(rec, slot):
    return [np.asarray(leaf[:, slot] if isinstance(rec, jax.Array)
                       else leaf[slot]) for leaf in jax.tree.leaves(rec)]


def _with_nan_slot(rec, slot):
    if isinstance(rec, jax.Array):          # LFM2: [conv layers, slots, ...]
        return rec.at[:, slot].set(jnp.nan)
    return jax.tree.map(lambda a: a.at[slot].set(jnp.nan), rec)


@pytest.mark.parametrize("family", FAMILIES)
def test_a_group_leaves_what_one_prompt_programs_leave(models, family):
    """Three prompts of one bucket (50, 33 and 64 tokens) and an unused
    entry in one program against each prompt alone through the chunk
    program from position 0: the same first token (the argmax of the
    prompt's logits), pages, tails and states.  The unused entry writes no
    block, no slot and no first token: a slot and blocks filled with NaN
    stay NaN, and nothing of them reaches the others."""
    model = models(family)
    group = _engine(model)
    group.PREFILL_BATCH_TOKENS = 256            # 4 prompts at 64
    assert group._prompts_per_program(64) == 4
    rng = np.random.default_rng(2)
    prompts = [rng.integers(1, 256, size=n) for n in (50, 33, 64)]
    slots = (0, 2, 4)
    blocks = [np.arange(1 + 4 * i, 5 + 4 * i, dtype=np.int32)
              for i in range(3)]
    pool, rec = group._state
    pool = jax.tree.map(lambda a: a.at[:, 13:].set(jnp.nan), pool)
    rec = _with_nan_slot(rec, 5)
    first = jnp.full((6,), -1, jnp.int32)
    pool, rec, first = send_group(group, 64, list(zip(prompts, slots, blocks)),
                                  (pool, rec), first)
    first = np.asarray(first)
    assert first[[1, 3, 5]].tolist() == [-1, -1, -1]
    assert all(np.isnan(leaf).all() for leaf in _slot(rec, 5))
    for leaf in jax.tree.leaves(pool):
        assert np.isnan(np.asarray(leaf[:, 13:])).all()
        assert not np.isnan(np.asarray(leaf[:, :13])).any()

    alone = _engine(model)
    for prompt, slot, ids in zip(prompts, slots, blocks):
        table = np.zeros((alone._blocks_per_slot,), np.int32)
        table[:4] = ids
        padded = np.zeros((64,), np.int32)
        padded[:len(prompt)] = prompt
        logits, *alone._state = jax.jit(alone._programs.chunk_fn(64))(
            alone.params, jnp.asarray(padded), jnp.int32(len(prompt)),
            jnp.int32(0), *alone._state,
            alone._programs.slot_target(jnp.int32(slot), jnp.asarray(table)))
        assert first[slot] == int(np.argmax(logits))
        for got, want in zip(_slot(rec, slot), _slot(alone._state[1], slot)):
            np.testing.assert_allclose(got, want, atol=5e-5)
        for key in ("k", "v"):
            rows = lambda p: np.asarray(p[key][:, ids]).reshape(
                p[key].shape[0], -1, p[key].shape[-1])[:, :len(prompt)]
            np.testing.assert_allclose(rows(pool), rows(alone._state[0]),
                                       atol=2e-5)


def _counters(engine) -> dict:
    prefix = "dstack_serving_"
    return {name[len(prefix):]: value for name, value in
            engine.telemetry.recorder.summary()["counters"].items()}


@pytest.mark.parametrize("family", FAMILIES)
def test_a_pass_sends_a_program_a_group(models, family):
    """Five prompts at 32, two at 64 and a chunked one in one pass, two
    prompts a program at 32 and one at 64: ceil(5 / 2) + 2 programs for
    seven prompts, each under a ``prefill`` span of its own; the chunked
    prompt goes to the chunk queue as before.  A state-space decoder counts
    the scan blocks of live prompts only."""
    engine = _engine(models(family), batch_size=8, prefill_chunk=64,
                     telemetry=EngineTelemetry())
    engine.PREFILL_BATCH_TOKENS = 64
    engine.DECODE_WINDOWS = (8,)
    lengths = (20, 50, 9, 31, 100, 12, 64, 25)
    requests = [engine.submit(Request(tokens=list(range(1, n + 1)),
                                      max_new_tokens=5)) for n in lengths]
    engine.step()
    counters = _counters(engine)
    assert counters["prefill_batches_total"] == 3 + 2
    assert counters["prefill_batch_prompts_total"] == 7
    assert counters["engine_phases_total{phase=prefill}"] == 5 + 1
    assert counters["prefill_chunks_total"] == 2      # 100 = 64 + 36
    # (the chunked prompt's last chunk samples its first token itself)
    assert {key for key in engine._prefill_jit} == {
        ("prefill", 32), ("prefill", 64), ("chunk", 64), "slot_update"}
    if family == "nemotron":
        # the scan's blocks of a live prompt at 32 and at 64, and of two
        # chunk programs of 64
        blocks = lambda bucket: -(-bucket // min(engine.cfg.chunk_size,
                                                 bucket))
        assert counters["ssm_scan_chunks_total"] == \
            engine.cfg.mamba_layers * (5 * blocks(32) + 4 * blocks(64))
    while not all(r.done.is_set() for r in requests):
        engine.step()
    assert [len(r.output) for r in requests] == [5] * 8


@pytest.mark.parametrize("family", FAMILIES)
def test_served_tokens_are_those_of_one_prompt_programs(models, family):
    """Eight greedy requests on four slots, whole and chunked prompts mixed,
    slots reused: a group's program serves every request the tokens it
    gets from programs of one prompt each."""
    def serve(prompts_a_program):
        engine = _engine(models(family), batch_size=4, prefill_chunk=32,
                         telemetry=EngineTelemetry())
        engine.PREFILL_BATCH_TOKENS = prompts_a_program * 32
        engine.DECODE_WINDOWS = (8,)
        rng = np.random.default_rng(11)
        requests = [engine.submit(Request(
            tokens=rng.integers(1, 200, size=n).tolist(), max_new_tokens=new))
            for n, new in ((10, 9), (40, 1), (24, 17), (50, 12), (12, 5),
                           (30, 20), (7, 3), (40, 11))]
        for _ in range(500):
            if all(r.done.is_set() for r in requests):
                break
            engine.step()
        counters = _counters(engine)
        return ([r.output for r in requests],
                counters["prefill_batch_prompts_total"]
                / counters["prefill_batches_total"])

    grouped, per_program = serve(4)
    alone, one = serve(1)
    assert grouped == alone
    assert [len(out) for out in grouped] == [9, 1, 17, 12, 5, 20, 3, 11]
    assert one == 1 and per_program > 1


def _pool_is_whole(engine) -> bool:
    return engine._alloc.free_blocks == engine._alloc.num_blocks - 1


def test_a_group_that_raises_fails_every_request_it_held(models):
    """The pass's first group (two prompts) raises when it is sent, with
    the third request still queued: the crash handler fails both (their
    slots were claimed when they joined the group), every block comes back,
    and the third request is served."""
    engine = _engine(models("lfm2"), batch_size=4)
    engine.PREFILL_BATCH_TOKENS = 64            # two prompts at 32
    requests = [Request(tokens=list(range(1, 20 + i)), max_new_tokens=4)
                for i in range(3)]
    send = engine._send_prompts

    def failing(bucket):
        if any(req is requests[1] for _, req, _, _ in
               engine._prompt_groups[bucket]):
            raise RuntimeError("simulated prefill failure")
        send(bucket)

    engine._send_prompts = failing
    for r in requests:
        engine.submit(r)
    loop = threading.Thread(target=engine.run_forever, daemon=True)
    loop.start()
    try:
        for r in requests:
            assert r.done.wait(120)
    finally:
        engine.stop()
        loop.join(timeout=30)
    assert not loop.is_alive()
    assert [r.finish_reason for r in requests] == ["error", "error",
                                                   "length"]
    assert requests[0].output == [] and len(requests[2].output) == 4
    assert not engine._prompt_groups and not engine._first_pending
    assert engine._slots == [None] * 4 and _pool_is_whole(engine)


def test_a_provider_without_the_form_sends_the_programs_it_sent():
    """A dense pass: a program and a sampler a whole prompt, in admission
    order, the chunked prompt to the chunk queue, one slot update at the
    end; no batched program is counted."""
    cfg, weights, _ = _llama()
    engine = InferenceEngine(cfg, params=weights, batch_size=4, max_len=128,
                             prefill_chunk=48, telemetry=EngineTelemetry(),
                             **dict(PAGED, total_kv_blocks=40))
    assert not engine._batched_prefill
    sent = []
    run = engine._run_program

    def record(table, key, make, *args):
        sent.append(key)
        return run(table, key, make, *args)

    engine._run_program = record
    for n in (20, 50, 40, 9):
        engine.submit(Request(tokens=list(range(1, n + 1)), max_new_tokens=3))
    engine._admit()
    assert sent == [("prefill", 32), "first_token", ("prefill", 64),
                    "first_token", ("prefill", 32), "first_token",
                    "slot_update"]
    counters = _counters(engine)
    assert "prefill_batches_total" not in counters or \
        counters["prefill_batches_total"] == 0
