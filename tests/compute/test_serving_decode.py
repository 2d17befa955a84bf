"""Decode hot-loop pass (PR 18): ragged paged attention, quantized KV,
fused sampling, tuned overlap defaults.

The contract under test: every raw-speed path (ragged buckets, the paged
block-table kernel, quantized KV) is a LAYOUT/SCHEDULE change — greedy
tokens must match the exact engine (f32 where bit-exactness is claimed),
fused sampling must be greedy-bit-identical to argmax and seed-
deterministic when sampling, and the sweep-tuned defaults must not drift.
"""

import os

import numpy as np
import pytest


@pytest.fixture(scope="module")
def setup():
    import dataclasses

    import jax
    import jax.numpy as jnp

    from dstack_tpu.models.llama import LlamaConfig, init_params

    cfg = dataclasses.replace(LlamaConfig.tiny(), dtype=jnp.float32)
    params = init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


def reference_greedy(cfg, params, prompt, n):
    import jax.numpy as jnp

    from dstack_tpu.models.llama import forward

    tokens = list(prompt)
    for _ in range(n):
        logits = forward(params, jnp.asarray([tokens]), cfg)
        tokens.append(int(np.argmax(np.asarray(logits[0, -1]))))
    return tokens[len(prompt):]


def run_greedy(cfg, params, prompts, n, env=None, **kw):
    from dstack_tpu.serving.engine import InferenceEngine, Request

    saved = {k: os.environ.get(k) for k in (env or {})}
    os.environ.update(env or {})
    try:
        engine = InferenceEngine(cfg, params=params, batch_size=4,
                                 max_len=128, paged=True, **kw)
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
    reqs = [Request(tokens=list(p), max_new_tokens=n) for p in prompts]
    for r in reqs:
        engine.submit(r)
    for _ in range(300):
        if all(r.done.is_set() for r in reqs):
            break
        engine.step()
    return [r.output for r in reqs]


PROMPTS = [[1, 2, 3], [9, 8, 7, 6], list(range(40, 80))]


# -- ragged buckets ----------------------------------------------------------


@pytest.mark.slow
def test_ragged_matches_fullspan_and_reference(setup):
    """The ragged bucketed program and the full-span program emit the same
    tokens (masked columns contribute exact zeros in f32), and both match
    the full-forward reference."""
    cfg, params = setup
    wants = [reference_greedy(cfg, params, p, 6) for p in PROMPTS]
    ragged = run_greedy(cfg, params, PROMPTS, 6,
                        env={"DSTACK_TPU_RAGGED_DECODE": "1"})
    full = run_greedy(cfg, params, PROMPTS, 6,
                      env={"DSTACK_TPU_RAGGED_DECODE": "0"})
    assert ragged == wants
    assert full == wants


@pytest.mark.slow
def test_ragged_dispatch_uses_small_buckets(setup):
    """Short sequences must actually get small buckets: the compiled
    decode-program keys carry the table-column bucket, and for ~46-token
    slots in a 128-len/16-block engine it must be well under the full
    8-column span."""
    from dstack_tpu.serving.engine import InferenceEngine, Request

    cfg, params = setup
    engine = InferenceEngine(cfg, params=params, batch_size=2, max_len=128,
                             paged=True, kv_block_size=16)
    req = Request(tokens=PROMPTS[2], max_new_tokens=6)  # 40 + 6 tokens
    engine.submit(req)
    for _ in range(100):
        if req.done.is_set():
            break
        engine.step()
    buckets = {k[2] for k in engine._decode_jit}
    assert buckets, "no buffered decode program was compiled"
    assert all(b is not None and b < 8 for b in buckets), buckets


# -- paged block-table kernel ------------------------------------------------


@pytest.mark.slow
def test_kernel_path_matches_reference(setup):
    """Env-forced Pallas block-table kernel (interpret mode off-TPU): the
    logsumexp merge of (cache half, window half) emits the same greedy
    tokens as the reference."""
    cfg, params = setup
    wants = [reference_greedy(cfg, params, p, 6) for p in PROMPTS]
    got = run_greedy(cfg, params, PROMPTS, 6,
                     env={"DSTACK_TPU_PAGED_ATTN_KERNEL": "1"})
    assert got == wants


@pytest.mark.slow
def test_kernel_path_int8_matches_xla_int8(setup):
    """int8 pages through the kernel (in-kernel dequant) vs int8 through
    the XLA gather path: same quantized cache, same tokens."""
    cfg, params = setup
    kern = run_greedy(cfg, params, PROMPTS, 6, kv_quantize="int8",
                      env={"DSTACK_TPU_PAGED_ATTN_KERNEL": "1"})
    xla = run_greedy(cfg, params, PROMPTS, 6, kv_quantize="int8",
                     env={"DSTACK_TPU_PAGED_ATTN_KERNEL": "0"})
    assert kern == xla


@pytest.mark.parametrize("kv_quantize", [None, "int8"])
def test_kernel_path_under_tensor_parallel_mesh(setup, kv_quantize):
    """Under a mesh the kernel runs per device inside shard_map over the
    kv-head shards (a Pallas call cannot be partitioned by GSPMD): same
    tokens as the single-device kernel path."""
    import jax

    from dstack_tpu.parallel.mesh import MeshSpec, build_mesh

    cfg, params = setup  # tiny: 8 q heads / 4 kv heads
    env = {"DSTACK_TPU_PAGED_ATTN_KERNEL": "1"}
    want = run_greedy(cfg, params, PROMPTS[:2], 4, env=env,
                      kv_quantize=kv_quantize)
    mesh = build_mesh(MeshSpec(tensor=2), jax.devices("cpu")[:2])
    got = run_greedy(cfg, params, PROMPTS[:2], 4, env=env,
                     kv_quantize=kv_quantize, mesh=mesh)
    assert got == want


# -- quantized KV ------------------------------------------------------------


def test_kv_quant_roundtrip_error_bounds():
    import jax
    import jax.numpy as jnp

    from dstack_tpu.serving.quant import (dequantize_kv, dequantize_kv4,
                                          quantize_kv, quantize_kv4)

    x = jax.random.normal(jax.random.PRNGKey(1), (64, 4, 32), jnp.float32)
    q8, s8 = quantize_kv(x)
    r8 = np.asarray(dequantize_kv(q8, s8, jnp.float32))
    q4, s4 = quantize_kv4(x)
    assert q4.shape == (64, 4, 16)  # two values per byte
    r4 = np.asarray(dequantize_kv4(q4, s4, jnp.float32))
    xn = np.asarray(x)
    rms = np.sqrt(np.mean((xn - r8) ** 2)) / np.sqrt(np.mean(xn ** 2))
    rms4 = np.sqrt(np.mean((xn - r4) ** 2)) / np.sqrt(np.mean(xn ** 2))
    assert rms < 0.02, rms          # int8: sub-percent
    assert rms4 < 0.10, rms4        # int4: single-digit percent
    assert rms < rms4               # and strictly ordered


def test_kv_quant_int4_negative_values_roundtrip_sign():
    import jax.numpy as jnp

    from dstack_tpu.serving.quant import dequantize_kv4, quantize_kv4

    x = jnp.asarray([[-7.0, 7.0, -3.0, 0.0, 1.0, -1.0, 5.0, -5.0]])
    q4, s = quantize_kv4(x)
    r = np.asarray(dequantize_kv4(q4, s, jnp.float32))
    np.testing.assert_allclose(r, np.asarray(x), atol=1e-5)


def test_kv_quantize_validation(setup):
    from dstack_tpu.serving.engine import InferenceEngine

    cfg, params = setup
    with pytest.raises(ValueError, match="kv_quantize"):
        InferenceEngine(cfg, params=params, batch_size=1, max_len=64,
                        kv_quantize="int2")


@pytest.mark.slow
def test_int4_engine_generates(setup):
    """int4 KV is lossy — no exact-match claim — but the engine must run
    every path (prefill insert, ragged decode, scatter) and emit valid
    tokens, with the first token exact (prefill logits are computed from
    unquantized activations)."""
    from dstack_tpu.serving.engine import InferenceEngine

    cfg, params = setup
    engine = InferenceEngine(cfg, params=params, batch_size=2, max_len=128,
                             paged=True, kv_quantize="int4")
    want = reference_greedy(cfg, params, [1, 2, 3, 4], 1)
    req = engine.generate([1, 2, 3, 4], max_new_tokens=8)
    assert len(req.output) == 8
    assert all(0 <= t < cfg.vocab_size for t in req.output)
    assert req.output[0] == want[0]


# -- fused sampling ----------------------------------------------------------


@pytest.mark.slow
def test_greedy_fused_bit_identical_to_argmax(setup):
    """Acceptance pin: greedy decoding through the fused sampler (temp=0
    short-circuits to lax.top_k's argmax) is BIT-identical to the
    pre-fusion greedy path — np.argmax over the same logits, first token
    and every decode-window token."""
    import jax.numpy as jnp

    from dstack_tpu.models.llama import forward
    from dstack_tpu.serving.engine import InferenceEngine

    cfg, params = setup
    engine = InferenceEngine(cfg, params=params, batch_size=1, max_len=128)
    prompt = [5, 6, 7]
    req = engine.generate(prompt, max_new_tokens=6)
    # first token: the on-device first-token sampler vs host argmax of
    # the same prefill logits
    logits = forward(params, jnp.asarray([prompt]), cfg)[0, -1]
    assert req.output[0] == int(np.argmax(np.asarray(logits)))
    # whole stream: the decode windows' argmax path
    assert req.output == reference_greedy(cfg, params, prompt, 6)


def test_sample_on_device_top_k_one_is_greedy(setup):
    """top_k=1 leaves a single candidate, so even at high temperature the
    fused sampler must return the argmax — exercises the rank mask
    without a full engine run."""
    import jax
    import jax.numpy as jnp

    from dstack_tpu.serving.engine import InferenceEngine

    cfg, params = setup
    engine = InferenceEngine(cfg, params=params, batch_size=2, max_len=64)
    logits = jax.random.normal(jax.random.PRNGKey(3), (2, cfg.vocab_size))
    toks = engine._sample_on_device(
        logits, jnp.asarray([2.0, 2.0]), jnp.asarray([1.0, 1.0]),
        jnp.asarray([1, 1], jnp.int32), jax.random.PRNGKey(7))
    assert list(np.asarray(toks)) == list(np.argmax(np.asarray(logits), -1))


@pytest.mark.slow
def test_sampled_decoding_seed_deterministic(setup):
    """Same rng_seed => identical sampled streams across fresh engines
    (the seeded jax.random chain threads through engine state); a
    different seed diverges."""
    from dstack_tpu.serving.engine import InferenceEngine, Request

    cfg, params = setup

    def sampled(seed):
        eng = InferenceEngine(cfg, params=params, batch_size=2, max_len=128,
                              paged=True, rng_seed=seed)
        reqs = [Request(tokens=[1, 2, 3], max_new_tokens=10,
                        temperature=0.9, top_p=0.95, top_k=40),
                Request(tokens=[7, 8], max_new_tokens=10, temperature=1.3)]
        for q in reqs:
            eng.submit(q)
        for _ in range(300):
            if all(q.done.is_set() for q in reqs):
                break
            eng.step()
        return [q.output for q in reqs]

    a, b, c = sampled(0), sampled(0), sampled(1)
    assert a == b
    assert a != c


# -- tuned overlap defaults --------------------------------------------------


def test_tuned_overlap_defaults_pinned():
    """The chunked-prefill sweep winner (PR 18) is recorded as the engine's
    default; changing it means re-running a sweep, not drift."""
    from dstack_tpu.serving.engine import InferenceEngine

    assert InferenceEngine.TUNED_PREFILL_CHUNK == 512
