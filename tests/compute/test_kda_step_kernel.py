"""The KDA decode step's Pallas kernel (``ops/kda.py`` ``kda_step_into``),
interpreted on the CPU, against the recurrence's XLA form ``kda_step``: the
same outputs and updated layer within float32 rounding, idle rows' tiles and
the stack's other layers bitwise as they were, and the hybrid decoder's
decode step through the kernel the same as through the CPU's form."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dstack_tpu.models import ling_hybrid as model
from dstack_tpu.ops import kda, mla

#: (layers of the stack, slots, heads, head dim): a toy, and the hybrid
#: cell's heads (32 x 128 x 128) at a few slots
GEOMETRIES = {"toy": (3, 5, 4, 16), "cell-heads": (2, 3, 32, 128)}


def _inputs(seed, layers, slots, heads, d, idle=()):
    """A random stack and one token of every slot, as the model forms
    them; the slots in ``idle`` get ``g = 0, beta = 0``."""
    keys = jax.random.split(jax.random.key(seed), 6)
    states = jax.random.normal(keys[0], (layers, slots, heads, d, d))
    q = kda.l2_normalize(jax.random.normal(keys[1], (slots, heads, d))) \
        * d ** -0.5
    k = kda.l2_normalize(jax.random.normal(keys[2], (slots, heads, d)))
    v = jax.random.normal(keys[3], (slots, heads, d))
    g = -5.0 * jax.nn.sigmoid(4 * jax.random.normal(keys[4],
                                                    (slots, heads, d)))
    beta = jax.nn.sigmoid(jax.random.normal(keys[5], (slots, heads)))
    live = ~jnp.isin(jnp.arange(slots), jnp.asarray(idle, jnp.int32))
    g = jnp.where(live[:, None, None], g, 0.0)
    beta = jnp.where(live[:, None], beta, 0.0)
    return states, (q, k, v, g, beta)


@pytest.mark.parametrize("geometry", sorted(GEOMETRIES))
def test_kernel_step_equals_the_recurrence(geometry):
    layers, slots, heads, d = GEOMETRIES[geometry]
    states, step = _inputs(1, layers, slots, heads, d)
    for layer in range(layers):
        o, after = jax.jit(kda.kda_step_into, static_argnums=1)(
            states, layer, *step)
        want_o, want = kda.kda_step(states[layer], *step)
        np.testing.assert_allclose(o, want_o, atol=2e-5, rtol=2e-5)
        np.testing.assert_allclose(after[layer], want, atol=2e-5, rtol=2e-5)
        others = np.array([j for j in range(layers) if j != layer])
        np.testing.assert_array_equal(after[others], states[others])


@pytest.mark.parametrize("geometry", sorted(GEOMETRIES))
def test_idle_rows_leave_their_tiles_bitwise(geometry):
    """``g = 0, beta = 0`` (a slot that is free or mid-chunk) keeps the
    slot's tiles to the bit, as the XLA form does."""
    layers, slots, heads, d = GEOMETRIES[geometry]
    idle = (0, slots - 1)
    states, step = _inputs(2, layers, slots, heads, d, idle=idle)
    _, after = kda.kda_step_into(states, layers - 1, *step)
    _, want = kda.kda_step(states[layers - 1], *step)
    for slot in idle:
        np.testing.assert_array_equal(after[layers - 1, slot],
                                      states[layers - 1, slot])
        np.testing.assert_array_equal(want[slot], states[layers - 1, slot])
    busy = np.array([s for s in range(slots) if s not in idle])
    assert not np.array_equal(after[layers - 1, busy],
                              states[layers - 1, busy])


@pytest.mark.parametrize("block_bytes", [1, 4 << 10, 1 << 30])
def test_slots_a_block_do_not_change_the_result(block_bytes):
    """One slot a grid step, a few, and all of them in one block: the same
    numbers to the bit."""
    states, step = _inputs(3, *GEOMETRIES["toy"])
    o, after = kda.kda_step_into(states, 1, *step, block_bytes=block_bytes)
    o_one, after_one = kda.kda_step_into(states, 1, *step, block_bytes=1)
    np.testing.assert_array_equal(o, o_one)
    np.testing.assert_array_equal(after, after_one)


def _decode(params, cfg, x, states, tails, live, steps):
    """``steps`` decode steps of the whole stack, each step's hidden
    states fed back as the next one's input; MLA attends the window's own
    rows (no cache before it)."""
    b = x.shape[0]
    cache = jnp.zeros((b, 1, cfg.latent_lanes), cfg.dtype)
    seen = jnp.zeros((b, 1), jnp.bool_)
    win = jnp.zeros((cfg.mla_layers, steps, b, cfg.latent_lanes), cfg.dtype)
    hidden = []
    for i in range(steps):
        def attend(m, q_nope, q_rope, rows, w_ukv, i=i):
            nonlocal win
            win = win.at[m, i].set(rows)
            return mla.absorbed(q_nope, q_rope, cache, seen, win[m],
                                jnp.arange(steps) <= i, w_ukv)

        x, states, tails, _ = model.decode_step(
            params, cfg, x, jnp.full((b,), i), live, states, tails, attend)
        hidden.append(x)
    return jnp.stack(hidden), states, tails


def test_decode_step_through_the_kernel_is_the_cpu_path(monkeypatch):
    cfg = model.LingHybridConfig.tiny()
    params = model.init_params(jax.random.key(0), cfg)
    b, steps = 3, 3
    keys = jax.random.split(jax.random.key(1), 3)
    x = jax.random.normal(keys[0], (b, cfg.hidden_size))
    states = 0.1 * jax.random.normal(
        keys[1], (cfg.kda_layers, b, cfg.num_heads, cfg.head_dim,
                  cfg.head_dim))
    tails = jax.random.normal(
        keys[2], (cfg.kda_layers, b, cfg.conv_kernel - 1, 3 * cfg.kda_dim))
    live = jnp.array([True, False, True])
    run = jax.jit(lambda *a: _decode(params, cfg, *a, live, steps))
    assert not model._on_chip()
    want = run(x, states, tails)
    monkeypatch.setattr(model, "_on_chip", lambda: True)
    layers, into = [], kda.kda_step_into

    def spy(states, layer, *a):
        layers.append(layer)
        return into(states, layer, *a)

    monkeypatch.setattr(kda, "kda_step_into", spy)
    run = jax.jit(lambda *a: _decode(params, cfg, *a, live, steps))
    got = run(x, states, tails)
    assert layers == list(range(cfg.kda_layers)) * steps
    for g, w in zip(got, want):
        np.testing.assert_allclose(g, w, atol=2e-5, rtol=2e-5)
    # the slot that is not live keeps its state to the bit
    np.testing.assert_array_equal(got[1][:, 1], states[:, 1])
