"""What the engine says about itself: its programs carry their compile-cache
tags as names, the phases of its scheduling step are ``engine.*`` spans on
the profiler's clock (on the engine's own thread), its decode counters add
up, and request stamps never run backwards.  CPU, toy size: names and
counts only, no device number."""

import threading
import time
from pathlib import Path

import pytest

ENGINE_ARGS = dict(batch_size=4, max_len=128, paged=True, kv_block_size=16,
                   total_kv_blocks=40, prefill_chunk=64)
#: the span names of ``InferenceEngine``'s loop (docs/concepts/observability.md)
SPANS = ("engine.wait_for_work", "engine.admit", "engine.prefill",
         "engine.chunk", "engine.dispatch_window", "engine.pull",
         "engine.emit", "engine.build_program")


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
    request outside the trace, then short and chunked prompts inside it."""
    import jax
    from jax.profiler import ProfileData

    from dstack_tpu.serving.engine import Request

    engine = _engine(model)
    engine.generate(list(range(1, 41)), max_new_tokens=9)
    before = _counters(engine)
    loop = threading.Thread(target=engine.run_forever, name="engine",
                            daemon=True)
    loop.start()
    trace_dir = tmp_path_factory.mktemp("trace")
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    jax.profiler.start_trace(str(trace_dir), profiler_options=options)
    try:
        with jax.profiler.TraceAnnotation("test.main_thread"):
            requests = [engine.submit(Request(tokens=list(range(1, n + 1)),
                                              max_new_tokens=9))
                        for n in (40, 40, 100)]
            for r in requests:
                assert r.done.wait(120)
        time.sleep(0.15)            # the loop goes idle: wait_for_work
    finally:
        jax.profiler.stop_trace()
        engine.stop()
        loop.join(timeout=30)
    path = sorted(Path(trace_dir).rglob("*.xplane.pb"))[-1]
    lines = [[e.name for e in line.events]
             for plane in ProfileData.from_file(str(path)).planes
             if plane.name.startswith("/host:") for line in plane.lines]
    return {"engine": engine, "requests": requests, "lines": lines,
            "before": before, "after": _counters(engine)}


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


def test_programs_are_named_by_their_compile_cache_tags(traced):
    engine = traced["engine"]
    decode = {fn.__name__ for fn in engine._decode_jit.values()}
    prefill = {fn.__name__ for fn in engine._prefill_jit.values()}
    assert decode and all(n.startswith("decode_w8_s0_kb") for n in decode)
    assert prefill == {"prefill_paged_b64", "prefill_prefix_b64",
                       "first_token_sample"}
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
      "decode_w8_s0"}),
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
    engine, after, before = (traced["engine"], traced["after"],
                             traced["before"])

    def delta(name):
        return after["dstack_serving_" + name] - before.get(
            "dstack_serving_" + name, 0.0)

    steps, slot_steps = delta("decode_steps_total"), delta(
        "decode_slot_steps_total")
    assert steps > 0 and steps % 8 == 0            # whole 8-step windows
    assert slot_steps == steps * engine.batch_size
    assert 0 < delta("decode_tokens_total") <= slot_steps
    # every request of the traced part: one first token from prefill, the
    # rest from decode windows
    tokens = sum(len(r.output) for r in traced["requests"])
    assert delta("decode_tokens_total") == tokens - len(traced["requests"])
    windows = engine.telemetry.decode_occupancy.count
    assert after["dstack_serving_decode_steps_total"] == 8 * windows


def test_paged_walk_pages_by_hand(model):
    """``paged_walk_pages_total``: per decode window, the pages a step's
    table walk covers (all 4 slots x the window's bucket of columns) and
    the pages the decoding slots' rows lie in, worked out by hand for one
    request at a time over pages of 16 rows and windows of 8 steps."""
    engine = _engine(model)

    def walk():
        c = _counters(engine)
        return (c["dstack_serving_paged_walk_pages_total{kind=live}"],
                c["dstack_serving_paged_walk_pages_total{kind=walked}"])

    assert walk() == (0, 0)
    # 40 rows lie in 3 pages; the window ends at 48 rows: 3 columns, in a
    # bucket of 4, for each of the 4 slots.  One window: 8 tokens follow
    # the prefill's first
    engine.generate(list(range(1, 41)), max_new_tokens=9)
    assert walk() == (3, 4 * 4)
    # 100 rows (7 pages), two windows: 108 rows end in column 7 and 116
    # in column 8, a bucket of 8 both; the second starts from 108 rows,
    # still 7 pages
    engine.generate(list(range(1, 101)), max_new_tokens=17)
    live, walked = walk()
    assert (live, walked) == (3 + 7 + 7, 16 + 2 * 4 * 8)
    assert live <= walked
    assert engine.telemetry.decode_occupancy.count == 3  # one inc a window


def test_paged_walk_pages_are_zero_for_a_rows_cache(model):
    """An engine that is not paged walks no table: both series stay 0
    (and are there, so a dashboard's ratio has its terms)."""
    engine = _engine(model, paged=False, total_kv_blocks=None,
                     prefill_chunk=32)
    engine.generate(list(range(1, 41)), max_new_tokens=9)
    counters = _counters(engine)
    assert engine.telemetry.decode_occupancy.count >= 1
    assert counters["dstack_serving_paged_walk_pages_total{kind=live}"] == 0
    assert counters["dstack_serving_paged_walk_pages_total{kind=walked}"] == 0


def test_every_chunk_has_its_span(traced):
    """The 100-token prompt goes out as two chunks of one scheduling step
    (a budget of ``batch_size`` = 4): one ``engine.chunk`` span a chunk,
    and one more where the completed prompt is activated."""
    after, before = traced["after"], traced["before"]

    def delta(name):
        return after["dstack_serving_" + name] - before.get(
            "dstack_serving_" + name, 0.0)

    assert delta("prefill_chunks_total") == 2
    assert delta("prefill_chunk_steps_total") == 1
    assert delta("prefill_budget_exhausted_total") == 0
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
