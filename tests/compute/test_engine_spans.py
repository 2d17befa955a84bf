"""What the engine says about itself: its programs carry their compile-cache
tags as names, the phases of its scheduling step are ``engine.*`` spans on
the profiler's clock (on the engine's own thread) that say whose they are
and are counted, with their self time, by the telemetry; the window chain
and the decode counters add up, and request stamps never run backwards.
CPU, toy size: names and counts only, no device number."""

import threading
import time
from pathlib import Path

import pytest

ENGINE_ARGS = dict(batch_size=4, max_len=128, paged=True, kv_block_size=16,
                   total_kv_blocks=40, prefill_chunk=64)
#: the span names of ``InferenceEngine``'s loop (docs/concepts/observability.md)
SPANS = ("engine.wait_for_work", "engine.admit", "engine.prefill",
         "engine.chunk", "engine.first_token", "engine.dispatch_window",
         "engine.pull", "engine.emit", "engine.build_program")


@pytest.fixture(scope="module")
def model():
    import jax
    from dstack_tpu.models.llama import LlamaConfig, init_params

    cfg = LlamaConfig.tiny()
    return cfg, init_params(jax.random.PRNGKey(0), cfg)


def _engine(model, **kw):
    from dstack_tpu.serving.engine import InferenceEngine
    from dstack_tpu.telemetry.serving import EngineTelemetry

    cfg, params = model
    return InferenceEngine(cfg, params=params, telemetry=EngineTelemetry(),
                           **{**ENGINE_ARGS, **kw})


def _in_scope(text: str, scope: str) -> bool:
    """An operation of the lowered program sits under the named scope (the
    path is relative inside a loop body: ``attn/mul``)."""
    return f'"{scope}/' in text or f"/{scope}/" in text


def _counters(engine) -> dict:
    return dict(engine.telemetry.recorder.summary()["counters"])


@pytest.fixture(scope="module")
def traced(model, tmp_path_factory):
    """One short run of the serving loop under the profiler: a warm-up
    request outside the trace, then the loop's whole life inside it (short
    and chunked prompts, then idle), so that every phase the counters
    count between ``before`` and ``after`` is a span of the profile."""
    import jax
    from jax.profiler import ProfileData

    from dstack_tpu.serving.engine import Request

    engine = _engine(model)
    engine.generate(list(range(1, 41)), max_new_tokens=9)
    before = _counters(engine)
    loop = threading.Thread(target=engine.run_forever, name="engine",
                            daemon=True)
    trace_dir = tmp_path_factory.mktemp("trace")
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    jax.profiler.start_trace(str(trace_dir), profiler_options=options)
    started = time.perf_counter()
    try:
        with jax.profiler.TraceAnnotation("test.main_thread"):
            # all three wait when the loop starts: one admission pass
            requests = [engine.submit(Request(tokens=list(range(1, n + 1)),
                                              max_new_tokens=9))
                        for n in (40, 40, 100)]
            loop.start()
            for r in requests:
                assert r.done.wait(120)
        time.sleep(0.15)            # the loop goes idle: wait_for_work
    finally:
        engine.stop()
        loop.join(timeout=30)
        wall = time.perf_counter() - started
        jax.profiler.stop_trace()
    assert not loop.is_alive()
    path = sorted(Path(trace_dir).rglob("*.xplane.pb"))[-1]
    host = [line for plane in ProfileData.from_file(str(path)).planes
            if plane.name.startswith("/host:") for line in plane.lines]
    lines = [[e.name for e in line.events] for line in host]
    # the engine's spans with the arguments that say whose they are
    spans = [(e.name, dict(e.stats)) for line in host for e in line.events
             if e.name.startswith("engine.")]
    # the engine's thread: every event with its interval
    timed = [[(e.name, e.start_ns, e.start_ns + e.duration_ns)
              for e in line.events] for line in host
             if any(e.name == "engine.dispatch_window" for e in line.events)]
    return {"engine": engine, "requests": requests, "lines": lines,
            "spans": spans, "timed": timed, "loop_wall_s": wall,
            "before": before, "after": _counters(engine)}


def _delta(traced, name):
    return traced["after"]["dstack_serving_" + name] - traced["before"].get(
        "dstack_serving_" + name, 0.0)


@pytest.mark.parametrize("span", SPANS)
def test_scheduler_phase_is_a_span_on_the_engines_thread(traced, span):
    holding = [names for names in traced["lines"] if span in names]
    assert len(holding) == 1, f"{span} on {len(holding)} threads"
    assert "test.main_thread" not in holding[0]
    # every engine span sits on that one thread
    engine_line = [names for names in traced["lines"]
                   if "engine.dispatch_window" in names]
    assert holding[0] is engine_line[0]


def test_no_span_encloses_the_step_or_a_token(traced):
    """An enclosing span would take every idle gap's label, and a span per
    token would be 2,000 a window."""
    names = [n for line in traced["lines"] for n in line
             if n.startswith("engine.")]
    assert set(names) <= set(SPANS)
    tokens = sum(len(r.output) for r in traced["requests"])
    assert names.count("engine.emit") == names.count("engine.pull") < tokens


@pytest.mark.parametrize("span", SPANS)
def test_phase_counter_counts_the_spans_of_its_name(traced, span):
    """``engine_phases_total{phase}`` grows by one a span: the counter and
    the span are opened by the same context."""
    phase = span[len("engine."):]
    in_profile = sum(1 for name, _ in traced["spans"] if name == span)
    assert in_profile > 0
    assert _delta(traced, f"engine_phases_total{{phase={phase}}}") == \
        in_profile
    assert _delta(traced, f"engine_phase_seconds_total{{phase={phase}}}") > 0


def test_phase_seconds_are_self_time_within_the_loops_wall_time(traced):
    """``admit`` holds ``prefill`` holds ``first_token`` (holds
    ``build_program``) in the fixture's run: counted as whole spans they
    would pass the thread's wall time between them; as self time all the
    phases together stay under it."""
    from dstack_tpu.telemetry.serving import PHASES

    assert {"engine." + phase for phase in PHASES} == set(SPANS)
    total = sum(_delta(traced, f"engine_phase_seconds_total{{phase={p}}}")
                for p in PHASES)
    assert 0 < total <= traced["loop_wall_s"]
    assert traced["engine"]._phase_stack == []      # every phase closed


def test_self_time_by_hand_on_a_stepped_clock(model, monkeypatch):
    """admit 0-15 s holding prefill 1-10 s holding first_token 3-6 s:
    1 + 5, 2 + 4 and 3 s of self time, 15 s in all, no second twice."""
    engine = _engine(model)
    clock = iter([0.0, 1.0, 3.0, 6.0, 10.0, 15.0])
    monkeypatch.setattr(time, "perf_counter", lambda: next(clock))
    with engine._phase("admit"):
        with engine._phase("prefill", slot=0, tokens=40):
            with engine._phase("first_token", slot=0, tokens=40):
                pass
    monkeypatch.undo()
    counters = _counters(engine)
    seconds = {p: counters[
        f"dstack_serving_engine_phase_seconds_total{{phase={p}}}"]
        for p in ("admit", "prefill", "first_token", "pull")}
    assert seconds == {"admit": 6.0, "prefill": 6.0, "first_token": 3.0,
                       "pull": 0.0}
    assert counters["dstack_serving_engine_phases_total{phase=prefill}"] == 1


def test_an_admission_pass_pulls_once_and_a_steps_completed_prompts_once(
        traced):
    """The device->host syncs of the loop: one ``engine.first_token`` for
    the admission pass of three requests (two whole prompts), one for the
    chunked prompt the next step completed, one ``engine.pull`` a window."""
    tel = traced["engine"].telemetry
    # less the warm-up request's, before the traced part
    assert len(traced["requests"]) == tel.queue_wait.count - 1 == 3
    assert _delta(traced, "engine_phases_total{phase=first_token}") == 2
    handed_over = sorted(args["requests"] for name, args in traced["spans"]
                         if name == "engine.first_token")
    assert handed_over == [1, 2]
    windows = tel.decode_occupancy.count - 1
    assert _delta(traced, "engine_phases_total{phase=pull}") == windows


def _calls_inside(traced, span: str) -> list:
    """For each span of the given name, the jitted calls that began inside
    it (``PjitFunction(<name>)`` on the engine's thread: the engine's
    programs by their tags, an eager operation by its primitive's).  The
    profile shows a call twice, one event inside the other."""
    (events,) = traced["timed"]
    calls, last_end = [], {}
    for name, start, end in sorted(events, key=lambda e: e[1]):
        if name.startswith("PjitFunction(") and start >= last_end.get(name, 0):
            last_end[name] = end
            calls.append((name[len("PjitFunction("):-1], start))
    return [[n for n, start in calls if lo <= start < hi]
            for name, lo, hi in events if name == span]


def test_a_pass_and_a_drain_send_one_slot_update_program_each(traced):
    """No eager ``x.at[i].set(v)`` (a ``scatter`` or a
    ``dynamic_update_slice`` program of its own) runs inside
    ``engine.admit`` or ``engine.emit``: the pass's activations and the
    drain's releases are one ``slot_update`` program each, counted with the
    slots they wrote."""
    (admit,) = _calls_inside(traced, "engine.admit")
    assert admit.count("slot_update") == 1
    assert admit.count("first_token_sample") == 2       # the whole prompts
    assert admit.count("prefill_paged_b64") == 2
    emits = _calls_inside(traced, "engine.emit")
    # the three requests end together: one drain releases them all
    assert sorted(emits) == [[]] * (len(emits) - 1) + [["slot_update"]]
    chunks = _calls_inside(traced, "engine.chunk")
    assert chunks[-1] == ["first_token_sample", "slot_update"]  # activation
    for calls in (admit, *chunks, *emits):
        assert not [n for n in calls
                    if "scatter" in n or "dynamic" in n], calls
    # the pass's two, the chunked prompt's activation, the drain's three
    assert _delta(traced, "engine_slot_update_programs_total") == 3
    assert _delta(traced, "engine_slot_updates_total") == 2 + 1 + 3


def test_spans_say_whose_they_are(traced):
    """A window's dispatch, pull and emit spans carry its sequence number;
    a prompt's spans its slot and the tokens they put to the device."""
    by_window = {}
    for name, args in traced["spans"]:
        if name in ("engine.dispatch_window", "engine.pull", "engine.emit"):
            by_window.setdefault(args["window"], []).append(name)
    # window 1 was the warm-up request's, outside the trace
    windows = traced["engine"].telemetry.decode_occupancy.count
    assert sorted(by_window) == list(range(2, windows + 1))
    for names in by_window.values():
        assert sorted(names) == ["engine.dispatch_window", "engine.emit",
                                 "engine.pull"]
    prompts = [(name, args["slot"], args["tokens"])
               for name, args in traced["spans"] if "slot" in args]
    assert {name for name, _, _ in prompts} == {"engine.prefill",
                                                "engine.chunk"}
    slots = {slot for _, slot, _ in prompts}
    assert len(slots) == 3 and slots <= set(range(4))
    # the 100-token prompt: chunks of 64 and 36
    assert sorted((n, t) for n, _, t in prompts) == [
        ("engine.chunk", 36), ("engine.chunk", 64),
        ("engine.prefill", 40), ("engine.prefill", 40)]
    # what closes the pass and what activates the chunked prompt send no
    # prompt tokens and say how many requests they hand over
    closing = sorted((name, args["requests"], args.get("tokens"))
                     for name, args in traced["spans"] if "requests" in args)
    assert closing == [("engine.chunk", 1, 0), ("engine.first_token", 1, None),
                       ("engine.first_token", 2, None),
                       ("engine.prefill", 2, 0)]


def test_span_arguments_are_formatted_only_under_a_trace(model):
    """With no trace being taken a phase's arguments are never turned
    into text: the cost of saying whose a span is falls on traced runs."""
    class Loud:
        shown = 0

        def __str__(self):
            Loud.shown += 1
            return "loud"

    engine = _engine(model)
    with engine._phase("pull", window=Loud()):
        pass
    assert Loud.shown == 0


def _chain(engine) -> dict:
    c = _counters(engine)
    out = {"ahead": c["dstack_serving_windows_dispatched_ahead_total"]}
    for reason in ("admission", "prompt_completed", "drained"):
        out[reason] = c[
            f"dstack_serving_window_chain_breaks_total{{reason={reason}}}"]
    return out


def test_window_chain_by_hand_for_one_request_alone(model):
    """24 tokens behind the prefill's first, in windows of 8: the first
    window is dispatched with nothing in flight, the two behind it each
    ahead of its predecessor's drain, and the step after the last finds
    nothing left to decode."""
    engine = _engine(model)
    engine.DECODE_WINDOWS = (8,)
    assert _chain(engine) == {"ahead": 0, "admission": 0,
                              "prompt_completed": 0, "drained": 0}
    req = engine.generate(list(range(1, 41)), max_new_tokens=25)
    assert len(req.output) == 25
    windows = engine.telemetry.decode_occupancy.count
    assert windows == 3
    assert _chain(engine) == {"ahead": windows - 1, "admission": 0,
                              "prompt_completed": 0, "drained": 1}


def _drive(engine, requests) -> None:
    for _ in range(200):
        if all(r.done.is_set() for r in requests):
            return
        engine.step()
    raise AssertionError("requests did not finish in 200 steps")


def test_window_chain_breaks_to_admit_a_waiting_request(model):
    """More requests than slots, one of them short: its slot comes free
    with a window in flight and a request waiting, and the next step
    breaks the chain to admit."""
    from dstack_tpu.serving.engine import Request

    engine = _engine(model)
    engine.DECODE_WINDOWS = (8,)
    requests = [engine.submit(Request(tokens=list(range(1, 41)),
                                      max_new_tokens=new))
                for new in (9, 25, 25, 25, 9, 9)]
    _drive(engine, requests)
    assert [len(r.output) for r in requests] == [9, 25, 25, 25, 9, 9]
    chain = _chain(engine)
    assert chain["admission"] >= 1 and chain["prompt_completed"] == 0
    # a step with a window in flight is counted once, under one name
    windows = engine.telemetry.decode_occupancy.count
    assert 0 < chain["ahead"] < windows
    assert sum(chain.values()) <= windows


def test_window_chain_breaks_where_a_chunked_prompt_completes(model):
    """A prompt of seven chunks beside a decoding request: four go out
    with the first window (a step's budget), the last three behind it,
    and the step that completes the prompt dispatches no window ahead, so
    that the new slot decodes in the next one."""
    from dstack_tpu.serving.engine import Request

    engine = _engine(model, prefill_chunk=16)
    engine.DECODE_WINDOWS = (8,)
    requests = [engine.submit(Request(tokens=list(range(1, n + 1)),
                                      max_new_tokens=new))
                for n, new in ((10, 25), (100, 9))]
    _drive(engine, requests)
    assert [len(r.output) for r in requests] == [25, 9]
    chain = _chain(engine)
    assert chain["prompt_completed"] == 1 and chain["admission"] == 0


def test_engine_without_telemetry_serves_the_same_tokens(model):
    from dstack_tpu.serving.engine import InferenceEngine

    cfg, params = model
    plain = InferenceEngine(cfg, params=params, telemetry=None,
                            **ENGINE_ARGS)
    counted = _engine(model)
    for prompt, new in ((40, 12), (100, 9)):
        tokens = list(range(1, prompt + 1))
        assert plain.generate(tokens, max_new_tokens=new).output == \
            counted.generate(tokens, max_new_tokens=new).output
    assert plain._phase_stack == []          # nothing is kept without it


def test_programs_are_named_by_their_compile_cache_tags(traced):
    engine = traced["engine"]
    decode = {fn.__name__ for fn in engine._decode_jit.values()}
    prefill = {fn.__name__ for fn in engine._prefill_jit.values()}
    assert decode and all(n.startswith("decode_w8_s0_kb") for n in decode)
    assert prefill == {"prefill_paged_b64", "prefill_prefix_b64",
                       "first_token_sample", "slot_update"}
    # the CPU trace has no XLA Modules line; its host line names each call
    called = {n for line in traced["lines"] for n in line
              if n.startswith("PjitFunction(")}
    for name in prefill:
        assert f"PjitFunction({name})" in called
    # which table buckets the traced windows need is the schedule's; each
    # decode call carries one of the engine's tags
    ran = {n for n in called if n.startswith("PjitFunction(decode_")}
    assert ran and ran <= {f"PjitFunction({name})" for name in decode}
    assert not {n for n in called if "unknown" in n or n in (
        "PjitFunction(fn)", "PjitFunction(<lambda>)")}


@pytest.mark.parametrize("kwargs,tags", [
    (dict(paged=False, total_kv_blocks=None, prefill_chunk=32),
     {"prefill_b32", "prefill_chunk_b32", "first_token_sample",
      "slot_update", "decode_w8_s0"}),
], ids=["dense-chunked"])
def test_every_program_kind_lowers_under_its_tag(model, kwargs, tags):
    engine = _engine(model, **kwargs)
    engine.generate(list(range(1, 41)), max_new_tokens=5)
    engine.generate(list(range(1, 21)), max_new_tokens=5)
    programs = {**engine._prefill_jit, **engine._decode_jit}
    assert {fn.__name__ for fn in programs.values()} == tags
    built = _counters(engine)
    assert built["dstack_serving_programs_built_total{kind=prefill}"] == \
        len(engine._prefill_jit)
    assert built["dstack_serving_programs_built_total{kind=decode}"] == \
        len(engine._decode_jit)


@pytest.fixture
def lowered(monkeypatch):
    """The debug-info text of every program the engine jits, by its tag."""
    from dstack_tpu.serving import engine as engine_mod

    texts = {}
    named_jit = engine_mod.named_jit

    def recording(fn, name, **kw):
        jitted = named_jit(fn, name, **kw)

        def call(*args):
            if name not in texts:
                texts[name] = jitted.lower(*args).as_text(debug_info=True)
            return jitted(*args)

        call.__name__ = name
        return call

    monkeypatch.setattr(engine_mod, "named_jit", recording)
    return texts


def test_module_name_and_scopes_reach_the_lowered_program(model, lowered):
    """``jit_<tag>`` is the module's name and the coarse regions of a step
    are named scopes in its debug info."""
    from dstack_tpu.serving import engine as engine_mod

    engine = _engine(model)
    engine.generate(list(range(1, 41)), max_new_tokens=5)
    engine.generate(list(range(1, 101)), max_new_tokens=5, temperature=0.7)
    assert "jax.jit(" not in Path(engine_mod.__file__).read_text().replace(
        "return jax.jit(named, **jit_kwargs)", "")
    decode = next(t for n, t in lowered.items()
                  if n.startswith("decode_w8_s1"))
    assert "module @jit_decode_w8_s1_kb" in decode
    for scope in ("qkv", "attn", "mlp", "lm_head", "sample",
                  "kv_window_write"):
        assert _in_scope(decode, scope), scope
    assert "module @jit_prefill_paged_b64" in lowered["prefill_paged_b64"]
    for scope in ("qkv", "attn", "mlp", "lm_head", "kv_insert"):
        assert _in_scope(lowered["prefill_paged_b64"], scope), scope
    assert _in_scope(lowered["prefill_prefix_b64"], "kv_insert")


def test_paged_kernel_and_its_scope_are_named(model, lowered, monkeypatch):
    """With the Pallas decode kernel on (interpreted here), its call sits
    under the ``paged_attn`` scope and carries its own name."""
    monkeypatch.setenv("DSTACK_TPU_PAGED_ATTN_KERNEL", "1")
    engine = _engine(model)
    assert engine._programs._paged_kernel
    engine.generate(list(range(1, 41)), max_new_tokens=3)
    decode = next(t for n, t in lowered.items() if n.startswith("decode_"))
    assert _in_scope(decode, "paged_attn")
    assert "paged_decode_attention" in decode


@pytest.mark.parametrize("kernel", ["1", "0"], ids=["kernel", "gather"])
def test_no_program_slices_a_layer_out_of_the_pool(model, lowered,
                                                   monkeypatch, kernel):
    """The pool is stored ``[L, blocks, block, Hkv*D]`` and every paged
    program addresses a layer in place: no lowered program holds a tensor
    of ONE layer of it (what a scan over the pool as ``xs`` slices out
    and, as ``ys``, stacks back), and the decode window calls the kernel
    once, in its layer loop."""
    monkeypatch.setenv("DSTACK_TPU_PAGED_ATTN_KERNEL", kernel)
    engine = _engine(model)
    engine.generate(list(range(1, 41)), max_new_tokens=3)
    engine.generate(list(range(1, 101)), max_new_tokens=3)  # chunked
    layers, blocks, block, lanes = engine._state[0].shape
    cfg = engine.cfg
    assert (layers, lanes) == (cfg.num_layers,
                               cfg.num_kv_heads * cfg.head_dim)
    assert {"prefill_paged_b64", "prefill_prefix_b64"} <= set(lowered)
    for name, text in lowered.items():
        if not name.startswith(("decode_", "prefill_")):
            continue
        assert f"tensor<{layers}x{blocks}x{block}x{lanes}x" in text, name
        assert f"tensor<1x{blocks}x{block}x{lanes}x" not in text, name
        assert f"tensor<{blocks}x{block}x{lanes}x" not in text, name
    decode = next(t for n, t in lowered.items() if n.startswith("decode_"))
    assert decode.count("paged_decode_attention") >= int(kernel)
    if kernel == "0":
        assert "paged_decode_attention" not in decode


def test_decode_counters_add_up(traced):
    """Slot-steps are steps x all slots (what the device computes), tokens
    handed over never exceed them, and a window's steps are counted where
    it is dispatched."""
    engine = traced["engine"]
    steps, slot_steps = _delta(traced, "decode_steps_total"), _delta(
        traced, "decode_slot_steps_total")
    assert steps > 0 and steps % 8 == 0            # whole 8-step windows
    assert slot_steps == steps * engine.batch_size
    assert 0 < _delta(traced, "decode_tokens_total") <= slot_steps
    # every request of the traced part: one first token from prefill, the
    # rest from decode windows
    tokens = sum(len(r.output) for r in traced["requests"])
    assert _delta(traced, "decode_tokens_total") == \
        tokens - len(traced["requests"])
    windows = engine.telemetry.decode_occupancy.count
    assert traced["after"]["dstack_serving_decode_steps_total"] == \
        8 * windows


def test_every_chunk_has_its_span(traced):
    """The 100-token prompt goes out as two chunks of one scheduling step
    (a budget of ``batch_size`` = 4): one ``engine.chunk`` span a chunk,
    and one more where the completed prompt is activated."""
    assert _delta(traced, "prefill_chunks_total") == 2
    assert _delta(traced, "prefill_chunk_steps_total") == 1
    assert _delta(traced, "prefill_budget_exhausted_total") == 0
    names = [n for line in traced["lines"] for n in line]
    assert names.count("engine.chunk") == 2 + 1


def test_kv_peak_is_recorded_where_the_pool_grows(model):
    """The peak is taken at reservation, so a request that comes and goes
    between two decode windows still shows; the gauge itself falls back."""
    engine = _engine(model)
    tel = engine.telemetry
    engine.generate(list(range(1, 101)), max_new_tokens=1)  # no window runs
    assert tel.decode_occupancy.count == 0
    usable = engine._alloc.num_blocks - 1
    # 100 tokens are written as a 128-token bucket: 8 blocks of 16
    assert tel.kv_utilization_peak.value == pytest.approx(8 / usable)
    engine.generate(list(range(1, 21)), max_new_tokens=4)
    assert tel.kv_utilization.value < tel.kv_utilization_peak.value
    assert tel.kv_utilization_peak.value == pytest.approx(8 / usable)


def test_stamps_are_monotonic_when_the_wall_clock_jumps_back(model,
                                                             monkeypatch):
    """Every stamp after ``submitted_at`` is that anchor plus monotonic
    time: a wall clock stepped backwards mid-request cannot make a queue
    wait, a prefill or a decode span negative."""
    from dstack_tpu.serving.engine import Request

    engine = _engine(model)
    engine.generate(list(range(1, 41)), max_new_tokens=4)   # build programs
    real = time.time()
    ticks = iter(range(1, 10**6))
    req = Request(tokens=list(range(1, 41)), max_new_tokens=6)
    monkeypatch.setattr(time, "time", lambda: real - 3600.0 * next(ticks))
    engine.submit(req)
    while not req.done.is_set():
        engine.step()
    monkeypatch.undo()
    assert abs(req.submitted_at - real) < 5.0       # the wall-clock anchor
    assert req.submitted_at <= req.admitted_at <= req.first_token_at \
        <= req.finished_at < req.submitted_at + 120.0
    hist = engine.telemetry.recorder.summary()["histograms"]
    assert hist["dstack_serving_e2e_seconds"]["sum"] < 240.0
    assert req.finish_reason == "length" and len(req.output) == 6


def test_deadline_stays_on_the_wall_clock(model):
    from dstack_tpu.serving.engine import Request

    engine = _engine(model)
    req = engine.submit(Request(tokens=[1, 2, 3], max_new_tokens=4,
                                deadline=time.time() - 1.0))
    while not req.done.is_set():
        engine.step()
    assert req.finish_reason == "deadline" and req.admitted_at is None
    assert req.finished_at >= req.submitted_at
