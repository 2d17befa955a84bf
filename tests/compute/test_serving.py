"""Continuous-batching engine: correctness vs the full-forward reference."""

import numpy as np
import pytest


@pytest.fixture(scope="module")
def setup():
    import jax
    from dstack_tpu.models.llama import LlamaConfig, forward, init_params
    from dstack_tpu.serving.engine import InferenceEngine

    cfg = LlamaConfig.tiny()
    params = init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


def reference_greedy(cfg, params, prompt, n):
    """Greedy decode via repeated FULL forward passes (slow but exact)."""
    import jax.numpy as jnp
    from dstack_tpu.models.llama import forward

    tokens = list(prompt)
    for _ in range(n):
        logits = forward(params, jnp.asarray([tokens]), cfg)
        tokens.append(int(np.argmax(np.asarray(logits[0, -1]))))
    return tokens[len(prompt):]


@pytest.mark.slow
def test_engine_matches_full_forward_greedy(setup):
    from dstack_tpu.serving.engine import InferenceEngine

    cfg, params = setup
    engine = InferenceEngine(cfg, params=params, batch_size=2, max_len=128)
    prompt = [1, 5, 9, 42, 7]
    want = reference_greedy(cfg, params, prompt, 8)
    req = engine.generate(prompt, max_new_tokens=8)
    assert req.output == want
    assert req.finish_reason == "length"


@pytest.mark.slow
def test_engine_interleaves_multiple_requests(setup):
    from dstack_tpu.serving.engine import InferenceEngine, Request

    cfg, params = setup
    engine = InferenceEngine(cfg, params=params, batch_size=4, max_len=128)
    prompts = [[1, 2, 3], [9, 8, 7, 6], [100, 50]]
    wants = [reference_greedy(cfg, params, p, 6) for p in prompts]
    reqs = [Request(tokens=p, max_new_tokens=6) for p in prompts]
    for r in reqs:
        engine.submit(r)
    # run until all done — all three decode in the SAME batch
    for _ in range(100):
        if all(r.done.is_set() for r in reqs):
            break
        engine.step()
    for r, want in zip(reqs, wants):
        assert r.output == want


@pytest.mark.slow
def test_slot_reuse_does_not_leak_state(setup):
    """A released slot's stale KV cache must not corrupt the next request."""
    from dstack_tpu.serving.engine import InferenceEngine

    cfg, params = setup
    engine = InferenceEngine(cfg, params=params, batch_size=1, max_len=128)
    # long first request fills cache deep
    engine.generate([3, 1, 4, 1, 5, 9, 2, 6], max_new_tokens=20)
    # short second request reuses slot 0
    prompt = [7, 7, 7]
    want = reference_greedy(cfg, params, prompt, 10)
    req = engine.generate(prompt, max_new_tokens=10)
    assert req.output == want


@pytest.mark.slow
def test_eos_stops_generation(setup):
    from dstack_tpu.serving.engine import InferenceEngine

    cfg, params = setup
    engine = InferenceEngine(cfg, params=params, batch_size=1, max_len=128)
    ref = reference_greedy(cfg, params, [1, 2, 3], 12)
    eos = ref[4]  # pretend the 5th generated token is EOS
    req = engine.generate([1, 2, 3], max_new_tokens=12, eos_id=eos)
    assert req.output == ref[:5]
    assert req.finish_reason == "stop"


def test_streaming_callback(setup):
    from dstack_tpu.serving.engine import InferenceEngine, Request

    cfg, params = setup
    engine = InferenceEngine(cfg, params=params, batch_size=1, max_len=128)
    seen = []
    req = Request(tokens=[5, 5], max_new_tokens=4, on_token=seen.append)
    engine.submit(req)
    while not req.done.is_set():
        engine.step()
    assert seen == req.output and len(seen) == 4


def test_oversized_max_tokens_does_not_kill_engine(setup):
    """Review regression: max_tokens > max_len must degrade, not crash."""
    from dstack_tpu.serving.engine import InferenceEngine

    cfg, params = setup
    engine = InferenceEngine(cfg, params=params, batch_size=1, max_len=64)
    req = engine.generate([1, 2, 3], max_new_tokens=5000)
    assert req.done.is_set()
    assert 0 < len(req.output) <= 62
    # engine still serves subsequent requests
    req2 = engine.generate([4, 5], max_new_tokens=4)
    assert len(req2.output) == 4


@pytest.mark.slow
def test_paged_engine_matches_dense():
    """Paged KV mode is a layout change only: in float32 (no bf16
    tie-breaks — the gathered-view program fuses differently than the
    dense one) greedy output matches the full-forward reference exactly,
    for BOTH modes."""
    import dataclasses

    import jax
    import jax.numpy as jnp

    from dstack_tpu.models.llama import LlamaConfig, init_params
    from dstack_tpu.serving.engine import InferenceEngine, Request

    cfg = dataclasses.replace(LlamaConfig.tiny(), dtype=jnp.float32)
    params = init_params(jax.random.PRNGKey(0), cfg)
    prompts = [[1, 2, 3], [9, 8, 7, 6], list(range(40, 80))]
    wants = [reference_greedy(cfg, params, p, 6) for p in prompts]
    for paged in (False, True):
        engine = InferenceEngine(cfg, params=params, batch_size=4,
                                 max_len=128, paged=paged)
        reqs = [Request(tokens=p, max_new_tokens=6) for p in prompts]
        for r in reqs:
            engine.submit(r)
        for _ in range(100):
            if all(r.done.is_set() for r in reqs):
                break
            engine.step()
        for r, want in zip(reqs, wants):
            assert r.output == want, f"paged={paged}"
        if paged:
            # all blocks returned after release
            assert engine._alloc.free_blocks == engine._alloc.num_blocks - 1


#: what the paged pool's stored form (kv heads folded into the lane dim,
#: every program addressing a layer in place) must not change: the tokens.
#: One prompt per paged prefill bucket of a 128-token engine (32, 64, 128:
#: two, four and eight blocks of 16), so every ``prefill_paged_b*`` program
#: writes a pool the decode window then reads.
_BUCKET_PROMPTS = [[(7 * i + k) % 500 + 1 for i in range(n)]
                   for k, n in enumerate((5, 40, 100))]
_SHARED = [(i * 7) % 50 + 1 for i in range(37)]  # two whole blocks + 5
_POOL_FORM_CASES = {
    # name: (engine kwargs beyond paged=True, dense kwargs, prompts)
    "buckets": ({}, {}, _BUCKET_PROMPTS),
    "chunked": ({"prefill_chunk": 16}, {},
                [[(i * 13) % 50 + 1 for i in range(45)], [3, 1, 4]]),
    "prefix-cache": ({"prefix_cache": True}, {},
                     [_SHARED + [1, 2, 3], _SHARED + [4, 5]]),
    "int8": ({"kv_quantize": "int8"}, {"kv_quantize": "int8"},
             _BUCKET_PROMPTS),
    "tensor-mesh": ({"mesh": 2}, {}, _BUCKET_PROMPTS),
}


@pytest.mark.parametrize("attention", ["gather", "kernel"])
@pytest.mark.parametrize("case", sorted(_POOL_FORM_CASES))
def test_paged_pool_form_matches_dense_cache(monkeypatch, case, attention):
    """A paged engine's greedy tokens equal the dense-cache engine's
    (float32: no bf16 tie-breaks) through every writer and reader of the
    pool: each paged prefill bucket, the chunk program, the prefix cache's
    suffix prefill, int8 pages and a tensor mesh, on the XLA gather path
    and through the block-table kernel (interpreted here)."""
    import dataclasses

    import jax
    import jax.numpy as jnp

    from dstack_tpu.models.llama import LlamaConfig, init_params
    from dstack_tpu.serving.engine import InferenceEngine, Request

    monkeypatch.setenv("DSTACK_TPU_PAGED_ATTN_KERNEL",
                       "1" if attention == "kernel" else "0")
    cfg = dataclasses.replace(LlamaConfig.tiny(), dtype=jnp.float32)
    params = init_params(jax.random.PRNGKey(0), cfg)
    paged_kw, dense_kw, prompts = _POOL_FORM_CASES[case]
    paged_kw = dict(paged_kw)
    if "mesh" in paged_kw:
        paged_kw["mesh"] = _tp_mesh(paged_kw["mesh"])

    def run(engine, sequential):
        reqs = [Request(tokens=list(p), max_new_tokens=6) for p in prompts]
        for r in reqs:
            engine.submit(r)
            if sequential:  # the second prompt must find the first's blocks
                for _ in range(100):
                    if r.done.is_set():
                        break
                    engine.step()
        for _ in range(200):
            if all(r.done.is_set() for r in reqs):
                break
            engine.step()
        return [r.output for r in reqs]

    want = run(InferenceEngine(cfg, params=params, batch_size=4, max_len=128,
                               **dense_kw), False)
    engine = InferenceEngine(cfg, params=params, batch_size=4, max_len=128,
                             paged=True, kv_block_size=16, **paged_kw)
    # the stored form: [L, blocks, block, Hkv*D], scales [.., Hkv]
    pool = engine._state[0]["q"] if case == "int8" else engine._state[0]
    assert pool.shape == (cfg.num_layers, engine._alloc.num_blocks, 16,
                          cfg.num_kv_heads * cfg.head_dim)
    assert run(engine, case == "prefix-cache") == want
    assert all(len(out) == 6 for out in want)
    programs = {k[0] if isinstance(k, tuple) else k
                for k in engine._prefill_jit}
    if case in ("prefix-cache", "chunked"):
        assert "chunk" in programs  # the suffix/chunk program ran
    if case == "buckets":
        assert {k[1] for k in engine._prefill_jit
                if isinstance(k, tuple)} == {32, 64, 128}
    assert engine._alloc.available_blocks == engine._alloc.num_blocks - 1


@pytest.mark.parametrize("kv_heads,kernel,warns", [
    (4, "1", True),    # 4 x 16 = 64 lanes a pool row
    (8, "1", False),   # 128: a whole tile
    (4, "0", False),   # the gather path reads any form
], ids=["64-lanes", "128-lanes", "gather"])
def test_paged_pool_warns_when_rows_are_not_whole_lane_tiles(
        monkeypatch, caplog, kv_heads, kernel, warns):
    """The pool is the kernel's operand as stored only in whole 128-lane
    tiles (tests/compute/test_tpu_compile.py shows what the chip's compiler
    does otherwise); the engine says so at start, it has no switch."""
    import dataclasses
    import logging

    from dstack_tpu.models.llama import LlamaConfig
    from dstack_tpu.serving.engine import InferenceEngine

    monkeypatch.setenv("DSTACK_TPU_PAGED_ATTN_KERNEL", kernel)
    cfg = dataclasses.replace(LlamaConfig.tiny(), num_kv_heads=kv_heads)
    with caplog.at_level(logging.WARNING, logger="dstack_tpu.serving.dense"):
        InferenceEngine(cfg, params={"layers": {}}, batch_size=2,
                        max_len=64, paged=True, kv_block_size=16)
    said = [r for r in caplog.records if "multiple of 128" in r.getMessage()]
    assert bool(said) == warns


@pytest.mark.slow
def test_paged_engine_slot_reuse(setup):
    from dstack_tpu.serving.engine import InferenceEngine

    cfg, params = setup
    engine = InferenceEngine(cfg, params=params, batch_size=1, max_len=128,
                             paged=True)
    engine.generate([3, 1, 4, 1, 5, 9, 2, 6], max_new_tokens=20)
    prompt = [7, 7, 7]
    want = reference_greedy(cfg, params, prompt, 10)
    req = engine.generate(prompt, max_new_tokens=10)
    assert req.output == want


@pytest.mark.slow
def test_paged_overcommit_admission_stalls_not_fails(setup):
    """With a block pool smaller than batch_size * max_len, admission must
    queue requests when the pool is exhausted and run them once blocks
    free — never fail them or stall decode mid-stream."""
    from dstack_tpu.serving.engine import InferenceEngine, Request

    cfg, params = setup
    # 2 slots x 4 blocks-per-slot, but a pool of only 5 usable blocks:
    # two 64-token-reserving requests cannot coexist
    engine = InferenceEngine(cfg, params=params, batch_size=2, max_len=128,
                             paged=True, kv_block_size=32, total_kv_blocks=6)
    reqs = [Request(tokens=[11 * (i + 1), 5, 3], max_new_tokens=40)
            for i in range(3)]
    # expected output from a PAGED engine with an ample pool: the identical
    # decode path makes the comparison byte-exact (the dense engine's
    # buffered-window decode reorders fp ops and can tie-break differently)
    ample = InferenceEngine(cfg, params=params, batch_size=2, max_len=128,
                            paged=True, kv_block_size=32)
    wants = [ample.generate(list(r.tokens), max_new_tokens=40).output
             for r in reqs]
    for r in reqs:
        engine.submit(r)
    for _ in range(300):
        if all(r.done.is_set() for r in reqs):
            break
        engine.step()
    for r, want in zip(reqs, wants):
        assert r.output == want
        assert r.finish_reason == "length"
    assert engine._alloc.free_blocks == engine._alloc.num_blocks - 1


def test_pd_insert_into_paged_engine(setup):
    """PD disaggregation decode side works on a paged engine."""
    from dstack_tpu.serving.engine import InferenceEngine, Request

    cfg, params = setup
    prompt = [3, 14, 15, 92, 6, 5]
    # compare against a colocated PAGED engine (same decode kernel path as
    # the PD decoder — byte-exact; dense now uses the buffered-window decode
    # whose fp reordering can tie-break near-equal logits differently)
    colocated = InferenceEngine(cfg, params=params, batch_size=2,
                                max_len=128, paged=True)
    want = colocated.generate(prompt, max_new_tokens=8).output
    prefiller = InferenceEngine(cfg, params=params, batch_size=2, max_len=128)
    decoder = InferenceEngine(cfg, params=params, batch_size=2, max_len=128,
                              paged=True)
    result = prefiller.prefill_export(prompt, max_new_tokens=8)
    req = Request(tokens=prompt, max_new_tokens=8, prefill=result)
    decoder.submit(req)
    while not req.done.is_set():
        decoder.step()
    assert req.output == want


def test_engine_recovers_after_device_error(setup):
    """A device-side decode failure must not brick the engine: the decode
    jit donates the KV caches, so the handler has to reallocate them
    (review regression: deleted-buffer errors on every later request)."""
    import threading

    from dstack_tpu.serving.engine import InferenceEngine, Request

    cfg, params = setup
    engine = InferenceEngine(cfg, params=params, batch_size=1, max_len=64)
    orig_step = engine.step
    state = {"failed": False}

    def failing_step():
        if not state["failed"]:
            state["failed"] = True
            engine._admit()  # put the request in flight
            # simulate an XLA error AFTER the caches were donated
            for tree in engine._state:
                tree.delete()
            raise RuntimeError("simulated device failure")
        orig_step()

    engine.step = failing_step
    runner = threading.Thread(target=engine.run_forever, daemon=True)
    runner.start()
    try:
        bad = Request(tokens=[1, 2, 3], max_new_tokens=4)
        engine.submit(bad)
        assert bad.done.wait(30)
        assert bad.finish_reason == "error"
        good = Request(tokens=[4, 5], max_new_tokens=4)
        engine.submit(good)
        assert good.done.wait(30)
        assert len(good.output) == 4 and good.finish_reason == "length"
    finally:
        engine.stop()
        runner.join(timeout=10)


def test_pd_prefill_export_matches_colocated(setup):
    """PD disaggregation correctness: prefill on engine A, decode on a
    SEPARATE engine B via the exported KV — identical greedy output to a
    single colocated engine."""
    from dstack_tpu.serving.engine import InferenceEngine, Request

    cfg, params = setup
    prompt = [3, 14, 15, 92, 6, 5]
    colocated = InferenceEngine(cfg, params=params, batch_size=2, max_len=128)
    want = colocated.generate(prompt, max_new_tokens=8).output

    prefill_engine = InferenceEngine(cfg, params=params, batch_size=2,
                                     max_len=128)
    decode_engine = InferenceEngine(cfg, params=params, batch_size=2,
                                    max_len=128)
    result = prefill_engine.prefill_export(prompt, max_new_tokens=8)
    assert result["length"] == len(prompt)
    assert result["ks"].shape == (cfg.num_layers, len(prompt),
                                  cfg.num_kv_heads, cfg.head_dim)
    # the first token from prefill matches the colocated engine's first
    assert result["first_token"] == want[0]

    req = Request(tokens=prompt, max_new_tokens=8, prefill=result)
    decode_engine.submit(req)
    while not req.done.is_set():
        decode_engine.step()
    assert req.output == want


@pytest.mark.slow
def test_engine_stress_mixed_requests(setup):
    """Round-4 integration stress: run_forever thread serving a burst of
    mixed requests (greedy, temperature, nucleus, EOS, oversized) on a
    paged + int8 engine — every request completes with a sane result and
    the block pool drains clean."""
    import threading

    from dstack_tpu.serving.engine import InferenceEngine, Request

    cfg, params = setup
    engine = InferenceEngine(cfg, params=params, batch_size=4, max_len=128,
                             paged=True, total_kv_blocks=9,
                             quantize="int8")
    runner = threading.Thread(target=engine.run_forever, daemon=True)
    runner.start()
    try:
        reqs = []
        for i in range(12):
            kind = i % 4
            # sizes chosen to exercise every admission path: most requests
            # reserve 2 blocks (max_new 40), every 5th reserves 3 (70) so
            # 4 concurrent slots want up to 9 of the 8 usable blocks and
            # the head-of-line stall triggers; every 6th is OVERSIZED
            # (max_new 5000 > max_len) to hit the clamp + out_of_room path
            max_new = 5000 if i % 6 == 5 else (70 if i % 5 == 4 else 40)
            reqs.append(engine.submit(Request(
                tokens=[(i * 13 + j) % 500 + 1 for j in range(3 + i % 5)],
                max_new_tokens=max_new,
                temperature=0.0 if kind == 0 else 0.8,
                top_p=1.0 if kind != 2 else 0.9,
                eos_id=7 if kind == 3 else None,
            )))
        for r in reqs:
            assert r.done.wait(240), "request did not finish"
        for r in reqs:
            assert r.finish_reason in ("length", "stop")
            assert 1 <= len(r.output) <= min(r.max_new_tokens, 126)
            assert all(0 <= t < cfg.vocab_size for t in r.output)
        # the pool drained: every block returned
        assert engine._alloc.free_blocks == engine._alloc.num_blocks - 1
    finally:
        engine.stop()
        runner.join(timeout=15)


# -- MoE serving --------------------------------------------------------------


@pytest.fixture(scope="module")
def moe_setup():
    import dataclasses

    import jax
    from dstack_tpu.models import moe

    # capacity_factor >= E/k makes routing dropless at ANY length, so the
    # full-forward reference and the engine's per-token decode see identical
    # routing and greedy outputs must match exactly.  (At the default 1.25
    # the full forward drops clustered tokens that per-token decode keeps —
    # a semantic difference, not a bug.)
    cfg = dataclasses.replace(moe.MoEConfig.tiny_moe(), capacity_factor=4.0)
    params = moe.init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


def moe_reference_greedy(cfg, params, prompt, n):
    import jax.numpy as jnp
    from dstack_tpu.models.moe import forward

    tokens = list(prompt)
    for _ in range(n):
        logits = forward(params, jnp.asarray([tokens]), cfg)
        tokens.append(int(np.argmax(np.asarray(logits[0, -1]))))
    return tokens[len(prompt):]


@pytest.mark.slow
def test_engine_serves_moe_greedy(moe_setup):
    """The engine serves Mixtral-style MoE checkpoints: decode routes each
    token through the experts (dropless) and matches the full-forward
    reference exactly under a dropless capacity_factor (see moe_setup)."""
    from dstack_tpu.serving.engine import InferenceEngine

    cfg, params = moe_setup
    engine = InferenceEngine(cfg, params=params, batch_size=2, max_len=128)
    prompt = [1, 5, 9, 42, 7]
    want = moe_reference_greedy(cfg, params, prompt, 8)
    req = engine.generate(prompt, max_new_tokens=8)
    assert req.output == want
    assert req.finish_reason == "length"


@pytest.mark.slow
def test_engine_serves_moe_paged_multi_request(moe_setup):
    from dstack_tpu.serving.engine import InferenceEngine, Request

    cfg, params = moe_setup
    engine = InferenceEngine(cfg, params=params, batch_size=4, max_len=128,
                             paged=True, kv_block_size=32)
    prompts = [[1, 2, 3], [9, 8, 7, 6], [100, 50]]
    wants = [moe_reference_greedy(cfg, params, p, 6) for p in prompts]
    reqs = [Request(tokens=p, max_new_tokens=6) for p in prompts]
    for r in reqs:
        engine.submit(r)
    for _ in range(100):
        if all(r.done.is_set() for r in reqs):
            break
        engine.step()
    for r, want in zip(reqs, wants):
        assert r.output == want


@pytest.mark.slow
def test_engine_serves_moe_int8(moe_setup):
    """int8 weight-only quantization covers routed-expert weights too (the
    per-channel scales broadcast through the expert einsums): greedy output
    matches the bf16 MoE engine for a short horizon."""
    from dstack_tpu.serving.engine import InferenceEngine

    cfg, params = moe_setup
    want = InferenceEngine(cfg, params=params, batch_size=2, max_len=64
                           ).generate([1, 5, 9, 2], max_new_tokens=5).output
    engine = InferenceEngine(cfg, params=params, batch_size=2, max_len=64,
                             quantize="int8")
    # expert weights really are int8 in HBM
    layers = engine.params["layers"]
    lp = layers[0] if isinstance(layers, (list, tuple)) else layers
    import jax.numpy as jnp
    assert lp["w_gate"]["q"].dtype == jnp.int8
    got = engine.generate([1, 5, 9, 2], max_new_tokens=5).output
    assert got == want


# -- Tensor-parallel (multi-chip) serving -------------------------------------


def _tp_mesh(n=4):
    import jax

    from dstack_tpu.parallel.mesh import MeshSpec, build_mesh

    return build_mesh(MeshSpec(tensor=n), jax.devices("cpu")[:n])


@pytest.mark.slow
def test_engine_tensor_parallel_matches_single_device(setup):
    """A mesh-sharded engine (Megatron-style TP over 4 virtual devices,
    KV cache sharded over KV heads) must reproduce the single-device
    engine's greedy output."""
    from dstack_tpu.serving.engine import InferenceEngine

    cfg, params = setup  # tiny: 8 q heads / 4 kv heads
    want = reference_greedy(cfg, params, [3, 1, 4, 1, 5], 8)
    engine = InferenceEngine(cfg, params=params, batch_size=2, max_len=128,
                             mesh=_tp_mesh(4))
    req = engine.generate([3, 1, 4, 1, 5], max_new_tokens=8)
    assert req.output == want


@pytest.mark.slow
def test_engine_tensor_parallel_paged_int8(setup):
    """TP composes with the paged KV cache and int8 quantization (the
    realistic big-model serving config)."""
    from dstack_tpu.serving.engine import InferenceEngine

    cfg, params = setup
    ref_engine = InferenceEngine(cfg, params=params, batch_size=2,
                                 max_len=128, paged=True, kv_block_size=32,
                                 quantize="int8")
    want = ref_engine.generate([9, 8, 7], max_new_tokens=6).output
    engine = InferenceEngine(cfg, params=params, batch_size=2, max_len=128,
                             paged=True, kv_block_size=32, quantize="int8",
                             mesh=_tp_mesh(2))
    req = engine.generate([9, 8, 7], max_new_tokens=6)
    assert req.output == want


def test_engine_tensor_parallel_rejects_indivisible_heads(setup):
    import dataclasses

    from dstack_tpu.models.llama import LlamaConfig
    from dstack_tpu.serving.engine import InferenceEngine

    cfg = dataclasses.replace(LlamaConfig.tiny(), num_kv_heads=2, num_heads=8)
    with pytest.raises(ValueError, match="tensor"):
        InferenceEngine(cfg, batch_size=2, max_len=64, mesh=_tp_mesh(4))


@pytest.mark.slow
def test_engine_serves_moe_expert_parallel(moe_setup):
    """MoE serving over a mesh: experts shard over the `expert` axis (the
    GShard dispatch/combine resharding is inserted by GSPMD) and greedy
    output matches the single-device MoE engine."""
    import jax

    from dstack_tpu.parallel.mesh import MeshSpec, build_mesh
    from dstack_tpu.serving.engine import InferenceEngine

    cfg, params = moe_setup  # tiny_moe, 4 experts, dropless cf
    ref = InferenceEngine(cfg, params=params, batch_size=2, max_len=128)
    want = ref.generate([1, 5, 9, 42, 7], max_new_tokens=6).output

    mesh = build_mesh(MeshSpec(expert=2, tensor=2), jax.devices("cpu")[:4])
    engine = InferenceEngine(cfg, params=params, batch_size=2, max_len=128,
                             mesh=mesh)
    assert "expert" in (engine.params["layers"]["w_gate"].sharding.spec[1]
                        or ())
    got = engine.generate([1, 5, 9, 42, 7], max_new_tokens=6).output
    assert got == want


def test_engine_moe_expert_parallel_rejects_indivisible_experts(moe_setup):
    import dataclasses

    import jax

    from dstack_tpu.models.moe import MoEConfig
    from dstack_tpu.parallel.mesh import MeshSpec, build_mesh
    from dstack_tpu.serving.engine import InferenceEngine

    cfg, _ = moe_setup
    cfg3 = dataclasses.replace(cfg, num_experts=3)
    mesh = build_mesh(MeshSpec(expert=2), jax.devices("cpu")[:2])
    with pytest.raises(ValueError, match="expert"):
        InferenceEngine(cfg3, batch_size=2, max_len=64, mesh=mesh)


def test_engine_mesh_missing_tensor_axis_rejected_eagerly(setup):
    import jax
    import numpy as np_mod
    from jax.sharding import Mesh

    from dstack_tpu.serving.engine import InferenceEngine

    cfg, params = setup
    mesh = Mesh(np_mod.asarray(jax.devices("cpu")[:2]), ("model",))
    with pytest.raises(ValueError, match="tensor"):
        InferenceEngine(cfg, params=params, batch_size=2, max_len=64,
                        mesh=mesh)


@pytest.mark.slow
def test_engine_mesh_inits_params_sharded(setup):
    """With no params given, init must produce sharded arrays directly
    (big models can't materialize on one device first)."""
    from dstack_tpu.serving.engine import InferenceEngine

    cfg, _ = setup
    engine = InferenceEngine(cfg, batch_size=2, max_len=64, mesh=_tp_mesh(4))
    wq = engine.params["layers"]["wq"]
    assert "tensor" in (wq.sharding.spec[-1] or ())
    assert engine._state[0].sharding.spec[3] == "tensor"
    req = engine.generate([1, 2, 3], max_new_tokens=4)
    assert len(req.output) == 4


def test_decode_window_selection_minimizes_tail_cost(setup):
    """Window choice weighs wasted device steps AGAINST the fixed
    per-window dispatch overhead — neither splitting every tail (round-trip
    storm) nor always covering (step waste)."""
    from dstack_tpu.serving.engine import InferenceEngine

    cfg, params = setup
    engine = InferenceEngine(cfg, params=params, batch_size=1, max_len=64)
    assert engine.DECODE_WINDOWS == (8, 32, 64)
    assert engine._pick_window(200) == 64   # steady state
    assert engine._pick_window(64) == 64
    assert engine._pick_window(60) == 64    # 4 wasted beats 32+dispatch
    assert engine._pick_window(33) == 32    # 32 then 8: 7 wasted + 1 extra
                                            # dispatch beats 31 wasted
    assert engine._pick_window(30) == 32    # 2 wasted: cover
    assert engine._pick_window(20) == 32    # 12 wasted beats 3 dispatches
    assert engine._pick_window(7) == 8      # smallest covers
    assert engine._pick_window(1) == 8
    # robust to an unsorted override
    engine.DECODE_WINDOWS = (64, 8)
    assert engine._pick_window(200) == 64
    assert engine._pick_window(5) == 8


# -- Cancellation + stop sequences --------------------------------------------


@pytest.mark.slow
def test_cancel_mid_generation_frees_slot(setup):
    """Cancelling a request stops generation early and frees the slot for
    the next queued request; a concurrent request is unaffected."""
    from dstack_tpu.serving.engine import InferenceEngine, Request

    cfg, params = setup
    engine = InferenceEngine(cfg, params=params, batch_size=1, max_len=128)
    victim = Request(tokens=[1, 2, 3], max_new_tokens=100)
    victim.on_token = lambda t: victim.cancel("stop") \
        if len(victim.output) >= 3 else None
    follower = Request(tokens=[9, 8], max_new_tokens=4)
    engine.submit(victim)
    engine.submit(follower)
    for _ in range(100):
        if victim.done.is_set() and follower.done.is_set():
            break
        engine.step()
    assert victim.done.is_set() and victim.finish_reason == "stop"
    assert 3 <= len(victim.output) < 100  # stopped well short
    # the single slot was freed for the follower, which ran to completion
    # (compare engine-vs-engine: the full-forward reference can tie-break
    # bf16 near-ties differently on this tiny random model)
    fresh = InferenceEngine(cfg, params=params, batch_size=1, max_len=128)
    want = fresh.generate([9, 8], max_new_tokens=4).output
    assert follower.output == want


def test_cancel_while_queued_never_occupies_slot(setup):
    from dstack_tpu.serving.engine import InferenceEngine, Request

    cfg, params = setup
    engine = InferenceEngine(cfg, params=params, batch_size=1, max_len=64)
    blocker = Request(tokens=[1], max_new_tokens=8)
    queued = Request(tokens=[2], max_new_tokens=8)
    engine.submit(blocker)
    engine.submit(queued)
    queued.cancel()
    for _ in range(50):
        if blocker.done.is_set() and queued.done.is_set():
            break
        engine.step()
    assert queued.done.is_set()
    assert queued.output == [] and queued.finish_reason == "cancelled"
    assert len(blocker.output) == 8


def _serving_app(cfg, params):
    from dstack_tpu.serving.engine import InferenceEngine
    from dstack_tpu.serving.server import ServingApp
    from dstack_tpu.serving.tokenizer import load_tokenizer

    engine = InferenceEngine(cfg, params=params, batch_size=2, max_len=128)
    app = ServingApp(engine, load_tokenizer(None), model_name="t")
    app.start_engine()
    return app


def _stop_serving_app(app):
    """Stop the loop ``_serving_app`` started and wait for its thread: a
    loop left running idles in the worker's later test files too."""
    app.engine.stop()
    app._thread.join(timeout=30)
    assert not app._thread.is_alive()


async def test_stop_sequences_clip_completion(setup):
    """OpenAI `stop`: generation halts at the first stop-string match and
    the response text excludes it."""
    from aiohttp.test_utils import TestClient, TestServer

    cfg, params = setup
    app = _serving_app(cfg, params)
    client = TestClient(TestServer(app.make_app()))
    await client.start_server()
    try:
        r = await client.post("/v1/completions", json={
            "model": "t", "prompt": "hi", "max_tokens": 24,
            "temperature": 0.0})
        full = (await r.json())["choices"][0]["text"]
        assert len(full) > 4
        stop = full[2:4]  # a substring the same greedy run will reproduce
        r2 = await client.post("/v1/completions", json={
            "model": "t", "prompt": "hi", "max_tokens": 24,
            "temperature": 0.0, "stop": stop})
        body = await r2.json()
        clipped = body["choices"][0]["text"]
        assert clipped == full[:full.find(stop)]
        assert stop not in clipped
        assert body["choices"][0]["finish_reason"] == "stop"
    finally:
        _stop_serving_app(app)
        await client.close()


async def test_stop_sequences_clip_stream(setup):
    """Streamed chunks never emit past a stop match even though decode
    windows overshoot it."""
    from aiohttp.test_utils import TestClient, TestServer

    cfg, params = setup
    app = _serving_app(cfg, params)
    client = TestClient(TestServer(app.make_app()))
    await client.start_server()
    try:
        r = await client.post("/v1/completions", json={
            "model": "t", "prompt": "yo", "max_tokens": 24,
            "temperature": 0.0})
        full = (await r.json())["choices"][0]["text"]
        stop = full[3:5]
        r2 = await client.post("/v1/completions", json={
            "model": "t", "prompt": "yo", "max_tokens": 24,
            "temperature": 0.0, "stream": True, "stop": stop})
        raw = (await r2.read()).decode()
        import json as _json

        texts = []
        for line in raw.splitlines():
            if line.startswith("data: ") and "[DONE]" not in line:
                chunk = _json.loads(line[6:])
                t = chunk["choices"][0].get("text")
                if t:
                    texts.append(t)
        streamed = "".join(texts)
        assert streamed == full[:full.find(stop)]
    finally:
        _stop_serving_app(app)
        await client.close()


@pytest.mark.slow
def test_chunked_prefill_matches_whole_prompt(setup):
    from dstack_tpu.serving.engine import InferenceEngine

    cfg, params = setup
    prompt = [(i * 13) % 50 + 1 for i in range(40)]
    whole = InferenceEngine(cfg, params=params, batch_size=2, max_len=128)
    want = whole.generate(prompt, max_new_tokens=6).output
    chunked = InferenceEngine(cfg, params=params, batch_size=2, max_len=128,
                              prefill_chunk=16)
    req = chunked.generate(prompt, max_new_tokens=6)
    assert req.output == want
    assert req.finish_reason == "length"


def _count_chunks(engine):
    """Spy on the engine's chunk dispatch: the returned list gets the slot
    of every chunk, in dispatch order."""
    slots = []
    dispatch = engine._dispatch_chunk

    def counting(slot_id, st):
        slots.append(slot_id)
        return dispatch(slot_id, st)

    engine._dispatch_chunk = counting
    return slots


@pytest.mark.slow
def test_chunked_prefill_interleaves_with_decode(setup):
    """A long prompt prefilling in chunks must not stop an active slot from
    emitting tokens between chunk steps, and a prompt of more chunks than
    the step's budget (``batch_size``) takes more than one step."""
    from dstack_tpu.serving.engine import InferenceEngine, Request

    cfg, params = setup
    engine = InferenceEngine(cfg, params=params, batch_size=2, max_len=128,
                             prefill_chunk=16)
    engine.DECODE_WINDOWS = (8,)  # several windows while the prompt chunks
    chunks = _count_chunks(engine)
    short = Request(tokens=[1, 2, 3], max_new_tokens=30)
    engine.submit(short)
    engine.step()  # admit + first window dispatched
    long_req = Request(tokens=[(i * 7) % 50 + 1 for i in range(96)],
                       max_new_tokens=4)
    engine.submit(long_req)
    per_step, emitted = [], []
    for _ in range(200):
        if long_req.done.is_set() and short.done.is_set():
            break
        before = len(chunks)
        engine.step()
        if len(chunks) > before:
            per_step.append(len(chunks) - before)
            emitted.append(len(short.output))
    assert short.done.is_set() and long_req.done.is_set()
    # six chunks on a budget of two a step: the budget binds
    assert per_step == [2, 2, 2]
    # the short request got a window's tokens at every one of those steps
    assert emitted[0] > 1 and emitted == sorted(set(emitted))
    assert short.finish_reason == "length" and len(short.output) == 30
    assert long_req.finish_reason == "length"
    # both produced correct greedy continuations (short horizons: longer
    # ones can flip argmax ties between the incremental and full-forward
    # paths — pre-existing float reduction-order noise, see the 8-token
    # cap in the tests above)
    assert short.output[:8] == reference_greedy(cfg, params, short.tokens, 8)
    assert long_req.output == reference_greedy(
        cfg, params, long_req.tokens, 4)


PAGED = {"dense": {}, "paged": dict(paged=True, kv_block_size=16)}


@pytest.mark.parametrize("cache", PAGED)
def test_two_chunked_prompts_finish_in_admission_order(setup, cache):
    """Two long prompts admitted together: the chunk queue finishes the
    older one before it starts the younger (a finished prompt is a slot
    that decodes), across steps whose budget cuts it short."""
    from dstack_tpu.serving.engine import InferenceEngine, Request

    cfg, params = setup
    whole = InferenceEngine(cfg, params=params, batch_size=2, max_len=128,
                            **PAGED[cache])
    engine = InferenceEngine(cfg, params=params, batch_size=2, max_len=128,
                             prefill_chunk=16, **PAGED[cache])
    chunks = _count_chunks(engine)
    reqs = [Request(tokens=[(i * k) % 50 + 1 for i in range(40)],
                    max_new_tokens=5) for k in (7, 11)]
    for r in reqs:
        engine.submit(r)
    firsts = []
    for _ in range(100):
        if all(r.done.is_set() for r in reqs):
            break
        engine.step()
        firsts.append([bool(r.output) for r in reqs])
    assert chunks == [0, 0, 0, 1, 1, 1]
    assert [True, False] in firsts  # the older prompt's first token first
    assert reqs[0].first_token_at < reqs[1].first_token_at
    for r in reqs:
        assert r.finish_reason == "length"
        assert r.output == whole.generate(list(r.tokens),
                                          max_new_tokens=5).output


@pytest.mark.parametrize("cache", PAGED)
def test_completed_prompt_decodes_in_the_next_window(setup, cache):
    """A prompt whose last chunk is dispatched behind an in-flight window
    is activated before the NEXT window is dispatched: that window decodes
    for it, and its drain hands the prompt's decode tokens over."""
    from dstack_tpu.serving.engine import InferenceEngine, Request

    cfg, params = setup
    engine = InferenceEngine(cfg, params=params, batch_size=2, max_len=128,
                             prefill_chunk=16, **PAGED[cache])
    engine.DECODE_WINDOWS = (8,)
    incumbent = Request(tokens=[1, 2, 3], max_new_tokens=60)
    engine.submit(incumbent)
    engine.step()
    long_req = Request(tokens=[(i * 7) % 50 + 1 for i in range(64)],
                       max_new_tokens=8)
    engine.submit(long_req)
    for _ in range(100):
        in_flight = engine._pending is not None
        engine.step()
        if long_req.output:
            break
    # the last chunk went out in the pipelined branch, behind a window
    assert in_flight and len(long_req.output) == 1
    slot = engine._slots.index(long_req)
    assert slot in engine._pending["decoding"]
    engine.step()  # drains that window: one token from prefill, seven here
    assert long_req.done.is_set() and len(long_req.output) == 8
    whole = InferenceEngine(cfg, params=params, batch_size=2, max_len=128,
                            **PAGED[cache])
    assert long_req.output == whole.generate(list(long_req.tokens),
                                             max_new_tokens=8).output
    while not incumbent.done.is_set():
        engine.step()
    assert len(incumbent.output) == 60


@pytest.mark.parametrize("cache", PAGED)
def test_cancel_of_second_chunking_prompt_mid_budget(setup, cache):
    """The younger of two chunking prompts is cancelled with chunks of it
    already written: the queue releases its slot (and its blocks) and the
    older prompt's output is untouched."""
    from dstack_tpu.serving.engine import InferenceEngine, Request

    cfg, params = setup
    engine = InferenceEngine(cfg, params=params, batch_size=2, max_len=128,
                             prefill_chunk=8, **PAGED[cache])
    chunks = _count_chunks(engine)
    older = Request(tokens=[(i * 7) % 50 + 1 for i in range(20)],
                    max_new_tokens=5)
    younger = Request(tokens=[(i * 11) % 50 + 1 for i in range(30)],
                      max_new_tokens=5)
    engine.submit(older)
    engine.submit(younger)
    engine.step()  # both admitted; the budget's two chunks go to the older
    engine.step()  # its last chunk, and the younger's first
    assert chunks == [0, 0, 0, 1] and 1 in engine._chunking
    younger.cancel()
    while not older.done.is_set():
        engine.step()
    assert younger.done.is_set() and younger.finish_reason == "cancelled"
    assert not younger.output and chunks == [0, 0, 0, 1]
    assert engine._slots == [None, None] and not engine._chunking
    if engine.paged:
        assert engine._alloc.free_blocks == engine._alloc.num_blocks - 1
    whole = InferenceEngine(cfg, params=params, batch_size=2, max_len=128,
                            **PAGED[cache])
    assert older.output == whole.generate(list(older.tokens),
                                          max_new_tokens=5).output
    # the released slot serves the next request
    follow = engine.generate(list(younger.tokens), max_new_tokens=5)
    assert follow.output == whole.generate(list(younger.tokens),
                                           max_new_tokens=5).output


@pytest.mark.slow
def test_chunked_prefill_int8_kv(setup):
    from dstack_tpu.serving.engine import InferenceEngine

    cfg, params = setup
    prompt = [(i * 11) % 50 + 1 for i in range(33)]
    whole = InferenceEngine(cfg, params=params, batch_size=2, max_len=128,
                            kv_quantize="int8")
    want = whole.generate(prompt, max_new_tokens=5).output
    chunked = InferenceEngine(cfg, params=params, batch_size=2, max_len=128,
                              kv_quantize="int8", prefill_chunk=8)
    assert chunked.generate(prompt, max_new_tokens=5).output == want


@pytest.mark.slow
def test_chunked_prefill_cancel_releases_slot(setup):
    from dstack_tpu.serving.engine import InferenceEngine, Request

    cfg, params = setup
    engine = InferenceEngine(cfg, params=params, batch_size=1, max_len=128,
                             prefill_chunk=8)
    long_req = Request(tokens=list(range(1, 50)), max_new_tokens=8)
    engine.submit(long_req)
    engine.step()  # admits + first chunk
    assert engine._chunking
    long_req.cancel()
    for _ in range(20):
        if long_req.done.is_set():
            break
        engine.step()
    assert long_req.done.is_set()
    assert not engine._chunking
    # slot is reusable afterwards
    follow = engine.generate([1, 2, 3], max_new_tokens=3)
    assert follow.output == reference_greedy(cfg, params, [1, 2, 3], 3)


@pytest.mark.slow
def test_chunk_completion_mid_pipeline_does_not_emit_junk(setup):
    """Review regression: a window dispatched in the same step a slot's
    FINAL chunk completes carries junk for that slot; its tokens must not
    be emitted as the request's output once the slot leaves _chunking."""
    from dstack_tpu.serving.engine import InferenceEngine, Request

    cfg, params = setup
    engine = InferenceEngine(cfg, params=params, batch_size=2, max_len=128,
                             prefill_chunk=16)
    # incumbent keeps windows in flight the whole time the long prompt
    # chunks through prefill (remaining stays > 0 at every chunk step)
    incumbent = Request(tokens=[1, 2, 3], max_new_tokens=60)
    engine.submit(incumbent)
    engine.step()
    long_req = Request(tokens=[(i * 7) % 50 + 1 for i in range(64)],
                       max_new_tokens=4)
    engine.submit(long_req)
    for _ in range(300):
        if long_req.done.is_set() and incumbent.done.is_set():
            break
        engine.step()
    assert long_req.done.is_set()
    assert long_req.output == reference_greedy(
        cfg, params, long_req.tokens, 4)
    assert len(incumbent.output) == 60


@pytest.mark.slow
def test_chunk_bucket_overshoot_does_not_corrupt_cache(setup):
    """Review regression: a final chunk whose padded bucket crosses
    max_len must drop the overshoot rows, not clamp them onto earlier
    valid KV rows."""
    from dstack_tpu.serving.engine import InferenceEngine

    cfg, params = setup
    # 113-token prompt, chunk 16: last chunk is 1 token, bucket 32,
    # write start 112 + 32 > 128
    prompt = [(i * 5) % 50 + 1 for i in range(113)]
    whole = InferenceEngine(cfg, params=params, batch_size=1, max_len=128)
    want = whole.generate(prompt, max_new_tokens=6).output
    chunked = InferenceEngine(cfg, params=params, batch_size=1, max_len=128,
                              prefill_chunk=16)
    assert chunked.generate(prompt, max_new_tokens=6).output == want


@pytest.mark.slow
def test_chunked_prefill_paged_matches_whole_prompt(setup):
    """Paged chunked prefill (suffix-prefill blocks per chunk) must match
    the whole-prompt paged engine, including across block boundaries."""
    from dstack_tpu.serving.engine import InferenceEngine

    cfg, params = setup
    prompt = [(i * 13) % 50 + 1 for i in range(45)]  # crosses 32-blocks
    whole = InferenceEngine(cfg, params=params, batch_size=2, max_len=128,
                            paged=True, kv_block_size=32)
    want = whole.generate(list(prompt), max_new_tokens=6).output
    chunked = InferenceEngine(cfg, params=params, batch_size=2, max_len=128,
                              paged=True, kv_block_size=32,
                              prefill_chunk=16)
    req = chunked.generate(list(prompt), max_new_tokens=6)
    assert req.output == want
    # all blocks returned after release
    assert chunked._alloc.free_blocks == chunked._alloc.num_blocks - 1


@pytest.mark.slow
def test_chunked_prefill_composes_with_prefix_cache(setup):
    """A second long prompt sharing a prefix skips the reused rows'
    chunks entirely and still decodes correctly."""
    from dstack_tpu.serving.engine import InferenceEngine

    cfg, params = setup
    shared = [(i * 7) % 50 + 1 for i in range(64)]
    p1 = shared + [1, 2, 3]
    p2 = shared + [4, 5]
    ref = InferenceEngine(cfg, params=params, batch_size=2, max_len=256,
                          paged=True, kv_block_size=32)
    wants = [ref.generate(list(p), max_new_tokens=5).output
             for p in (p1, p2)]
    eng = InferenceEngine(cfg, params=params, batch_size=2, max_len=256,
                          paged=True, kv_block_size=32, prefix_cache=True,
                          prefill_chunk=16)
    got1 = eng.generate(list(p1), max_new_tokens=5)
    # count chunk steps for the SECOND request
    from dstack_tpu.serving.engine import Request
    r2 = Request(tokens=list(p2), max_new_tokens=5)
    eng.submit(r2)
    steps_with_chunking = 0
    for _ in range(200):
        if r2.done.is_set():
            break
        eng.step()
        if eng._chunking:
            steps_with_chunking += 1
    assert [got1.output, r2.output] == wants
    # 64 shared tokens = 2 full 32-blocks reused -> the second prompt
    # chunked only its ~suffix (a couple of steps), not the whole prompt
    assert steps_with_chunking <= 2, steps_with_chunking


def test_prefill_chunk_must_be_positive(setup):
    from dstack_tpu.serving.engine import InferenceEngine

    cfg, params = setup
    with pytest.raises(ValueError, match=">= 1"):
        InferenceEngine(cfg, params=params, batch_size=1, max_len=64,
                        prefill_chunk=0)

