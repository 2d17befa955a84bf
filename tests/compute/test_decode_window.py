"""The decode window buffer of ``serving/dense.py``: the W rows a window
produces live in scan CARRIES and take one row a layer-step in place.

Two contracts.  Structure: in the traced decode window no ``scan`` takes a
window buffer (or a layer's or a pass's slab of one) as an ``xs`` operand or
gives one back as a ``ys``: a scanned buffer is sliced into a buffer of its
own and stacked into a fresh one every iteration, which on the chip was a
third of a decode step (PERF.md section 6, PR 34).  Equivalence: a window of
W steps emits the tokens of W one-step windows and leaves the same cache
rows behind, whatever else rides in the batch.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dstack_tpu.models import ouro
from dstack_tpu.models.llama import LlamaConfig, init_params
from dstack_tpu.serving.engine import InferenceEngine

SLOTS, MAX_LEN, BLOCK = 4, 128, 16
BLOCKS_PER_SLOT = MAX_LEN // BLOCK


def _config(model: str):
    if model == "mha":
        return dataclasses.replace(LlamaConfig.tiny(), num_kv_heads=8,
                                   dtype=jnp.float32)
    if model == "gqa":
        return dataclasses.replace(LlamaConfig.tiny(), dtype=jnp.float32)
    assert model.startswith("looped"), model
    return dataclasses.replace(ouro.OuroConfig.tiny(), dtype=jnp.float32,
                               ut_steps=int(model[len("looped"):]))


@pytest.fixture(scope="module")
def weights():
    """``weights(model)`` -> (cfg, params), made once a model."""
    made = {}

    def get(model: str):
        if model not in made:
            cfg = _config(model)
            init = (ouro.init_params if isinstance(cfg, ouro.OuroConfig)
                    else init_params)
            made[model] = cfg, init(jax.random.PRNGKey(0), cfg)
        return made[model]

    return get


def _engine(cfg, params, cache: str, monkeypatch):
    """An engine whose decode windows take the ``cache`` path: ``gather``
    (paged pool through a gathered view), ``kernel`` (paged pool through the
    Pallas kernel, interpreted here) or ``dense`` (a [B, max_len] row)."""
    monkeypatch.setenv("DSTACK_TPU_PAGED_ATTN_KERNEL",
                       "1" if cache == "kernel" else "0")  # read at init
    return InferenceEngine(
        cfg, params=params, batch_size=SLOTS, max_len=MAX_LEN,
        paged=cache != "dense", kv_block_size=BLOCK,
        total_kv_blocks=1 + SLOTS * BLOCKS_PER_SLOT)


def _window_args(engine, cache_k, cache_v, last, lengths, active, tables):
    b = SLOTS
    return (engine.params, last, lengths, active, cache_k, cache_v,
            jnp.zeros((b,), jnp.float32), jnp.ones((b,), jnp.float32),
            jnp.zeros((b,), jnp.int32), tables, jax.random.PRNGKey(0))


# -- structure ---------------------------------------------------------------


def _scans(jaxpr):
    """Every ``scan`` equation of a jaxpr, nested ones included."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "scan":
            yield eqn
        for value in eqn.params.values():
            for sub in (value if isinstance(value, (list, tuple))
                        else [value]):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    yield from _scans(inner)


@pytest.mark.parametrize("cache", ["gather", "dense"])
@pytest.mark.parametrize("model", ["mha", "gqa", "looped2", "looped4"])
def test_window_buffer_is_carried_never_scanned(weights, monkeypatch, model,
                                                cache):
    """The step scan, the layer scan and a looped decoder's pass scan all
    hold the two window buffers in their carries, and none of them has an
    ``xs`` or ``ys`` whose trailing dims are a window buffer's (the whole
    buffer, a pass's share or a layer's slab of it)."""
    cfg, params = weights(model)
    engine = _engine(cfg, params, cache, monkeypatch)
    window = 8
    fn = engine._programs.decode_window_fn(
        window, False, BLOCKS_PER_SLOT if engine.paged else None)
    tables = jnp.zeros((SLOTS, BLOCKS_PER_SLOT), jnp.int32)
    zeros = jnp.zeros((SLOTS,), jnp.int32)
    jaxpr = jax.make_jaxpr(fn)(*_window_args(
        engine, *engine._state, zeros, zeros, zeros.astype(bool), tables))
    scans = list(_scans(jaxpr.jaxpr))
    # step scan + layer scan, and the pass scan between them when looped
    assert len(scans) == (3 if cfg.ut_steps > 1 else 2), len(scans)
    buffer_elems = (cfg.cache_layers * window * SLOTS * cfg.num_kv_heads
                    * cfg.head_dim)
    for eqn in scans:
        consts, carries = eqn.params["num_consts"], eqn.params["num_carry"]
        carried = [v.aval for v in eqn.invars[consts:consts + carries]]
        buffers = [a for a in carried if a.size == buffer_elems]
        assert len(buffers) == 2, [a.shape for a in carried]
        tail = buffers[0].shape[1:]
        assert buffers[1].shape[1:] == tail and window in tail
        scanned = ([v.aval for v in eqn.invars[consts + carries:]]
                   + [v.aval for v in eqn.outvars[carries:]])
        for aval in scanned:
            assert aval.shape[-len(tail):] != tail, (
                "a scan slices or stacks the window buffer", aval.shape)


# -- equivalence -------------------------------------------------------------


def _random_state(engine, rng):
    """The engine's cache trees filled with random rows (what earlier
    prefills and windows left there does not matter to the comparison)."""
    leaves, tree = jax.tree.flatten(engine._state)
    keys = jax.random.split(rng, len(leaves))
    return jax.tree.unflatten(tree, [
        jax.random.normal(k, a.shape, a.dtype) for k, a in zip(keys, leaves)])


@pytest.mark.parametrize("window", [8, 32, 64])
@pytest.mark.parametrize("model,cache", [
    ("gqa", "gather"), ("gqa", "kernel"), ("gqa", "dense"),
    ("looped2", "gather"), ("looped2", "kernel")])
def test_window_equals_one_step_windows(weights, monkeypatch, model, cache,
                                        window):
    """One window of W steps against W windows of one step (float32):
    the same tokens for the decoding slots and the same cache afterwards.
    Slot 0 decodes from a short context; slot 1 is free; slot 2 is in the
    middle of a chunked prefill (inactive, its rows must stay as they are);
    slot 3 runs past the end of its span inside the window (its rows past
    the span are dropped, and it is compared as far as the span goes)."""
    cfg, params = weights(model)
    engine = _engine(cfg, params, cache, monkeypatch)
    nbk = BLOCKS_PER_SLOT if engine.paged else None
    state = _random_state(engine, jax.random.PRNGKey(window))
    tables = (1 + jnp.arange(SLOTS * BLOCKS_PER_SLOT, dtype=jnp.int32)
              ).reshape(SLOTS, BLOCKS_PER_SLOT)
    in_span = window // 2                      # steps slot 3 has room for
    start = [5, 0, 40, MAX_LEN - in_span]
    lengths = jnp.asarray(start, jnp.int32)
    active = jnp.asarray([True, False, False, True])
    last = jnp.asarray([3, 0, 0, 7], jnp.int32)

    whole = jax.jit(engine._programs.decode_window_fn(window, False, nbk))
    tokens, w_last, w_lengths, w_k, w_v, *_ = whole(*_window_args(
        engine, *state, last, lengths, active, tables))

    one = jax.jit(engine._programs.decode_window_fn(1, False, nbk))
    cache_k, cache_v = state
    stepped = []
    for _ in range(window):
        toks, last, lengths, cache_k, cache_v, *_ = one(*_window_args(
            engine, cache_k, cache_v, last, lengths, active, tables))
        stepped.append(np.asarray(toks[0]))
    stepped = np.stack(stepped)
    tokens = np.asarray(tokens)

    np.testing.assert_array_equal(tokens[:, 0], stepped[:, 0])
    # at the span's last row the two differ by design (one-step windows
    # clamp onto it, a window drops what lies past it): compare before it
    np.testing.assert_array_equal(tokens[:in_span, 3], stepped[:in_span, 3])
    np.testing.assert_array_equal(
        np.asarray(w_lengths), np.asarray(start) + window * np.asarray(active))

    def rows(cache, slot, lo, hi):
        """Rows [lo, hi) of a slot over all cache layers, heads folded."""
        if engine.paged:
            flat = cache[:, tables[slot]].reshape(
                cfg.cache_layers, MAX_LEN, -1)
            return np.asarray(flat[:, lo:hi])
        return np.asarray(cache[:, slot, lo:hi]).reshape(
            cfg.cache_layers, hi - lo, -1)

    for got, want, before in ((w_k, cache_k, state[0]),
                              (w_v, cache_v, state[1])):
        np.testing.assert_allclose(rows(got, 0, 0, 5 + window),
                                   rows(want, 0, 0, 5 + window),
                                   rtol=1e-5, atol=1e-5)
        # the last row of the span is where the one-step windows clamp
        np.testing.assert_allclose(rows(got, 3, 0, MAX_LEN - 1),
                                   rows(want, 3, 0, MAX_LEN - 1),
                                   rtol=1e-5, atol=1e-5)
        for idle in (1, 2):
            np.testing.assert_array_equal(rows(got, idle, 0, MAX_LEN),
                                          rows(before, idle, 0, MAX_LEN))
        # the window wrote rows of its own, not the ones it was handed
        assert not np.allclose(rows(got, 0, 5, 5 + window),
                               rows(before, 0, 5, 5 + window))
