"""An admission pass sends its prompts back to back and waits for the device
once; what it and a drain change in the slots' device state goes out as one
program each.  Held here: the served tokens are those of handing every first
token over at once (the order of host events before the pass existed), for
every provider, greedy and sampled; requests that end on their first token,
are cancelled while their pass is in flight, or whose prefill raises; and
the counters of the slot-update program.  CPU, toy sizes."""

import threading

import numpy as np
import pytest

from dstack_tpu.serving.engine import InferenceEngine, Request
from dstack_tpu.telemetry.serving import EngineTelemetry
from tests.compute.test_family_seam import _hybrid, _lfm2, _llama

PAGED = dict(paged=True, kv_block_size=16, total_kv_blocks=40)

#: provider and engine options: whole-prompt and chunked admissions in one
#: pass, slots reused, a prefix shared by every third prompt
CASES = {
    "dense-paged": (_llama, PAGED),
    "dense-chunked": (_llama, dict(prefill_chunk=16)),
    "dense-prefix": (_llama, dict(PAGED, prefix_cache=True,
                                  prefill_chunk=32)),
    "hybrid": (_hybrid, dict(PAGED, prefill_chunk=16)),
    "lfm2": (_lfm2, dict(PAGED, prefill_chunk=16)),
}


@pytest.fixture(scope="module")
def models():
    built = {}

    def get(name):
        make, kwargs = CASES[name]
        if make not in built:
            built[make] = make()[:2]        # config and weights
        return built[make], kwargs

    return get


class SerialHandOver(InferenceEngine):
    """Every request's first token pulled and handed over before the next
    prompt is sent: the host's order of events before a pass existed (a
    pass of one request each, through the same programs)."""

    def _activate(self, *args, **kwargs):
        super()._activate(*args, **kwargs)
        self._hand_over_first_tokens()


def _requests(sampled: bool):
    rng = np.random.default_rng(11)
    shared = rng.integers(1, 200, size=32).tolist()
    lengths = ((10, 9), (40, 1), (24, 17), (50, 12), (12, 5), (33, 20),
               (7, 3), (40, 11))
    for i, (n, new) in enumerate(lengths):
        tokens = rng.integers(1, 200, size=n).tolist()
        if i % 3 == 0:
            tokens = shared + tokens
        sampling = dict(temperature=0.8 + 0.1 * (i % 3), top_p=0.9,
                        top_k=(0, 20, 5)[i % 3]) if sampled else {}
        yield Request(tokens=tokens, max_new_tokens=new, **sampling)


def _drive(engine, requests, steps=500) -> None:
    for _ in range(steps):
        if all(r.done.is_set() for r in requests):
            return
        engine.step()
    raise AssertionError(f"requests did not finish in {steps} steps")


def _serve(cls, model, kwargs, sampled):
    cfg, weights = model
    engine = cls(cfg, params=weights, batch_size=4, max_len=128, rng_seed=7,
                 telemetry=EngineTelemetry(), **kwargs)
    engine.DECODE_WINDOWS = (8,)
    requests = [engine.submit(r) for r in _requests(sampled)]
    _drive(engine, requests)
    return engine, requests


def _counters(engine) -> dict:
    prefix = "dstack_serving_"
    return {name[len(prefix):]: value for name, value in
            engine.telemetry.recorder.summary()["counters"].items()}


@pytest.mark.parametrize("sampled", [False, True], ids=["greedy", "sampled"])
@pytest.mark.parametrize("case", CASES)
def test_served_tokens_are_those_of_one_hand_over_a_request(models, case,
                                                            sampled):
    """Eight requests on four slots, whole and chunked prompts: the same
    programs run on the same inputs in the same order, so every request is
    served the tokens it got when each first token was pulled at once.  A
    sampled request's key is split on the host in the order it was."""
    model, kwargs = models(case)
    serial, want = _serve(SerialHandOver, model, kwargs, sampled)
    engine, got = _serve(InferenceEngine, model, kwargs, sampled)
    assert [r.output for r in got] == [r.output for r in want]
    assert [r.finish_reason for r in got] == ["length"] * 8
    assert [len(r.output) for r in got] == [9, 1, 17, 12, 5, 20, 3, 11]
    # the sampler was on where it was asked for: (window, sampling, columns)
    assert {key[1] for key in engine._decode_jit} == {sampled}
    # the pass gathers: fewer pulls and fewer slot-update programs than the
    # serial order's one a request, for the same slots written
    pulls = "engine_phases_total{phase=first_token}"
    mine, theirs = _counters(engine), _counters(serial)
    assert theirs[pulls] == 8 and mine[pulls] < 8
    assert mine["engine_slot_updates_total"] == \
        theirs["engine_slot_updates_total"]
    assert mine["engine_slot_update_programs_total"] < \
        theirs["engine_slot_update_programs_total"]
    assert not engine._first_pending and not engine._slot_updates
    assert not np.asarray(engine._active).any()
    assert not np.asarray(engine._lengths).any()


@pytest.fixture(scope="module")
def dense(models):
    return models("dense-paged")


def _engine(dense, **kwargs):
    (cfg, weights), paged = dense
    return InferenceEngine(cfg, params=weights, telemetry=EngineTelemetry(),
                           **{**dict(batch_size=4, max_len=128), **paged,
                              **kwargs})


def _pool_is_whole(engine) -> bool:
    return engine._alloc.free_blocks == engine._alloc.num_blocks - 1


def test_a_request_of_one_token_ends_in_its_pass(dense):
    """It is activated and released before any window: the slot ends
    inactive with no length, its first token the last token."""
    engine = _engine(dense)
    one = engine.submit(Request(tokens=list(range(1, 30)), max_new_tokens=1))
    more = engine.submit(Request(tokens=list(range(1, 20)),
                                 max_new_tokens=3))
    engine.step()
    assert one.done.is_set() and one.finish_reason == "length"
    assert len(one.output) == 1 and len(more.output) == 1
    assert engine._slots[0] is None and engine._slots[1] is more
    # the window of that step was dispatched behind one more flush
    assert not engine._slot_updates
    assert np.asarray(engine._active).tolist() == [False, True, False,
                                                   False]
    assert int(np.asarray(engine._first_tokens)[0]) == one.output[0]
    _drive(engine, [more])
    assert len(more.output) == 3 and _pool_is_whole(engine)
    counters = _counters(engine)
    # the pass's activations; the release behind its pull; the drain's
    assert counters["engine_slot_update_programs_total"] == 3
    assert counters["engine_slot_updates_total"] == 2 + 1 + 1


def test_a_first_token_that_is_the_eos_ends_the_request(dense):
    engine = _engine(dense)
    prompt = list(range(3, 40))
    first = engine.generate(prompt, max_new_tokens=4).output[0]
    req = engine.generate(prompt, max_new_tokens=4, eos_id=first)
    assert req.output == [first] and req.finish_reason == "stop"
    assert engine._slots == [None] * 4 and _pool_is_whole(engine)
    assert engine._pending is None          # no window ran for it


def test_a_request_cancelled_while_its_pass_is_in_flight(dense):
    """The cancel lands between the prompt's program and the pass's pull:
    the token is discarded, the slot and its blocks come back, the other
    requests of the pass are served."""
    engine = _engine(dense)
    want = engine.generate(list(range(1, 20)), max_new_tokens=6).output
    requests = [Request(tokens=list(range(1, 20)), max_new_tokens=6)
                for _ in range(3)]
    hand_over = engine._hand_over_first_tokens

    def cancelled_in_flight():
        requests[1].cancel()
        hand_over()

    engine._hand_over_first_tokens = cancelled_in_flight
    for r in requests:
        engine.submit(r)
    _drive(engine, requests)
    assert requests[1].finish_reason == "cancelled"
    assert requests[1].output == [] and requests[1].first_token_at is None
    assert [r.output for r in (requests[0], requests[2])] == [want, want]
    assert engine._slots == [None] * 4 and _pool_is_whole(engine)


def test_a_prefill_that_raises_mid_pass_fails_what_was_in_flight(dense):
    """The second prompt of a pass of three raises: the crash handler fails
    it and the request already sent (its slot was claimed when its program
    went out, its first token never pulled), every block comes back, and
    the third request, still queued, is served."""
    engine = _engine(dense)
    requests = [Request(tokens=list(range(1, 20 + i)), max_new_tokens=4)
                for i in range(3)]
    prefill = engine._prefill

    def failing(slot_id, req):
        if req is requests[1]:
            raise RuntimeError("simulated prefill failure")
        prefill(slot_id, req)

    engine._prefill = failing
    for r in requests:
        engine.submit(r)
    loop = threading.Thread(target=engine.run_forever, daemon=True)
    loop.start()
    try:
        for r in requests:
            assert r.done.wait(60)
    finally:
        engine.stop()
        loop.join(timeout=30)
    assert not loop.is_alive()
    assert [r.finish_reason for r in requests] == ["error", "error",
                                                   "length"]
    assert requests[0].output == [] and len(requests[2].output) == 4
    assert not engine._first_pending and not engine._slot_updates
    assert engine._slots == [None] * 4 and _pool_is_whole(engine)


def test_a_pass_and_a_drain_run_one_slot_update_program_each(dense):
    """Four requests admitted in one pass and ended by one drain: two
    programs for eight slot writes, one pull for four first tokens."""
    engine = _engine(dense)
    engine.DECODE_WINDOWS = (8,)
    handed_over = []
    requests = [engine.submit(Request(
        tokens=list(range(1, 12 + i)), max_new_tokens=5,
        on_token=lambda token, i=i: handed_over.append(i))) for i in range(4)]
    engine.step()           # the pass, and the window behind it
    counters = _counters(engine)
    assert counters["engine_slot_update_programs_total"] == 1
    assert counters["engine_slot_updates_total"] == 4
    assert counters["engine_phases_total{phase=first_token}"] == 1
    assert all(len(r.output) == 1 for r in requests)
    assert handed_over == [0, 1, 2, 3]      # in admission order
    engine.step()           # the drain
    assert all(r.done.is_set() for r in requests)
    counters = _counters(engine)
    assert counters["engine_slot_update_programs_total"] == 2
    assert counters["engine_slot_updates_total"] == 8
    assert counters["engine_phases_total{phase=first_token}"] == 1
    assert not np.asarray(engine._active).any()


def test_pd_first_tokens_go_through_the_same_vector(dense):
    """The decode side of PD: logits from the wire are sampled into the
    vector, a bare first token goes out with the slot update."""
    engine = _engine(dense)
    prompt = [3, 14, 15, 92, 6, 5]
    want = engine.generate(prompt, max_new_tokens=6).output
    exported = engine.prefill_export(prompt, max_new_tokens=6)
    bare = {k: v for k, v in exported.items() if k != "logits"}
    requests = [engine.submit(Request(tokens=prompt, max_new_tokens=6,
                                      prefill=p)) for p in (exported, bare)]
    _drive(engine, requests)
    assert [r.output for r in requests] == [want, want]
    assert _counters(engine)["engine_phases_total{phase=first_token}"] == 2
