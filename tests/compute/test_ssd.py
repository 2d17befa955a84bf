"""The Mamba-2 recurrence of ``ops/ssd.py`` at toy sizes on the CPU: the
chunked scan and the single-token update against the recurrence written
token by token (float32; they differ in the order of their sums and in
``exp(a) exp(b)`` for ``exp(a + b)``: 1e-5 of outputs of size 30)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dstack_tpu.ops import ssd

T, H, P, G, N = 384, 8, 4, 2, 16
ATOL = 2e-4


def _operands(seed=0, t=T, dt_scale=0.5):
    k = jax.random.split(jax.random.PRNGKey(seed), 6)
    return dict(
        x=jax.random.normal(k[0], (t, H, P)),
        dt=jax.nn.softplus(jax.random.normal(k[1], (t, H))) * dt_scale,
        A=-jnp.exp(jax.random.normal(k[2], (H,))),
        B=jax.random.normal(k[3], (t, G, N)),
        C=jax.random.normal(k[4], (t, G, N)),
        D=jnp.ones((H,)), state0=jax.random.normal(k[5], (H, P, N)))


def _recurrence(x, dt, A, B, C, D, state0, length):
    """``S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t; y_t = S_t C_t + D
    x_t``, a token at a time; head h reads group h // (H / G)."""
    r = H // G

    def token(state, now):
        x_t, dt_t, b_t, c_t, i = now
        b_t, c_t = jnp.repeat(b_t, r, 0), jnp.repeat(c_t, r, 0)
        new = (jnp.exp(dt_t * A)[:, None, None] * state
               + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :])
        y = (new * c_t[:, None, :]).sum(-1) + D[:, None] * x_t
        return jnp.where(i < length, new, state), y

    state, y = jax.lax.scan(token, state0,
                            (x, dt, B, C, jnp.arange(x.shape[0])))
    return y, state


@pytest.mark.parametrize("length", [384, 256, 300, 129, 128, 1])
def test_chunked_scan_is_the_token_recurrence(length):
    """Lengths that are and are not whole blocks of 128 inside a bucket of
    384: outputs at the real positions and the state after the last one."""
    ops = _operands()
    y, state = ssd.ssd_chunked(**ops, length=length)
    want_y, want_state = _recurrence(**ops, length=length)
    assert float(jnp.abs(want_y).max()) > 5
    np.testing.assert_allclose(y[:length], want_y[:length], atol=ATOL)
    np.testing.assert_allclose(state, want_state, atol=ATOL)


@pytest.mark.parametrize("t,chunk", [(32, 128), (64, 16), (128, 128)])
def test_a_bucket_shorter_than_a_block_is_one_block(t, chunk):
    ops = _operands(seed=1, t=t)
    y, state = ssd.ssd_chunked(**ops, length=t - 5, chunk=chunk)
    want_y, want_state = _recurrence(**ops, length=t - 5)
    np.testing.assert_allclose(y[:t - 5], want_y[:t - 5], atol=ATOL)
    np.testing.assert_allclose(state, want_state, atol=ATOL)
    with pytest.raises(ValueError, match="whole blocks"):
        ssd.ssd_chunked(**_operands(t=40), length=40, chunk=16)


def test_a_state_handed_across_two_pieces_is_one_pass():
    """A prompt in two chunk programs (256 + 128 positions, the first with
    200 real tokens) against one pass over the 328 real tokens."""
    ops = _operands(seed=2)
    first = {k: (v[:256] if v.shape[:1] == (T,) else v)
             for k, v in ops.items()}
    y1, mid = ssd.ssd_chunked(**first, length=200)
    second = {k: (v[200:328] if v.shape[:1] == (T,) else v)
              for k, v in ops.items()}
    y2, end = ssd.ssd_chunked(**dict(second, state0=mid), length=128)
    whole = {k: (v[:328] if v.shape[:1] == (T,) else v)
             for k, v in ops.items()}
    want_y, want_state = _recurrence(**whole, length=328)
    np.testing.assert_allclose(jnp.concatenate([y1[:200], y2]), want_y,
                               atol=ATOL)
    np.testing.assert_allclose(end, want_state, atol=ATOL)


def test_padded_positions_leave_the_state():
    """Whatever a bucket's padding holds, the state after it is the state
    after the last real token: a padded position's ``dt`` is 0, so its
    decay is 1 and its input nothing."""
    ops = _operands(seed=3)
    _, state = ssd.ssd_chunked(**ops, length=140)
    junk = dict(ops, x=ops["x"].at[140:].set(1e4),
                dt=ops["dt"].at[140:].set(50.0),
                B=ops["B"].at[140:].set(-1e3))
    _, same = ssd.ssd_chunked(**junk, length=140)
    np.testing.assert_array_equal(state, same)
    # nothing real: the state as it came
    _, untouched = ssd.ssd_chunked(**ops, length=0)
    np.testing.assert_array_equal(untouched, ops["state0"])


def test_step_after_the_scan_is_the_recurrence_one_token_further():
    ops = _operands(seed=4)
    _, state = ssd.ssd_chunked(**ops, length=300)
    at = lambda a: a[300][None]
    y, after = ssd.ssd_step(at(ops["x"]), at(ops["dt"]), ops["A"],
                            at(ops["B"]), at(ops["C"]), ops["D"],
                            state[None])
    want_y, want_state = _recurrence(**ops, length=301)
    np.testing.assert_allclose(y[0], want_y[300], atol=ATOL)
    np.testing.assert_allclose(after[0], want_state, atol=ATOL)


def test_a_slot_with_no_step_keeps_its_state_bit_for_bit():
    ops = _operands(seed=5)
    states = jnp.stack([ops["state0"], ops["state0"] * 3])
    at = lambda a: jnp.stack([a[7], a[8]])
    dt = at(ops["dt"]).at[1].set(0.0)
    _, after = ssd.ssd_step(at(ops["x"]), dt, ops["A"], at(ops["B"]),
                            at(ops["C"]), ops["D"], states)
    np.testing.assert_array_equal(after[1], states[1])
    assert not np.array_equal(after[0], states[0])


def test_every_exponent_is_a_difference_at_most_zero(monkeypatch):
    """Run eagerly with ``exp`` watched: no operand above 0, in the scan or
    in the step, so a block of any length under any decay cannot overflow;
    and a decay strong enough to underflow a whole block's sum of ``dt A``
    (-1e5) still gives the recurrence's finite numbers."""
    seen = []
    real_exp = jnp.exp

    def watched(a):
        seen.append(float(jnp.max(a)))
        return real_exp(a)

    ops = _operands(seed=6, t=64)
    monkeypatch.setattr(ssd.jnp, "exp", watched)
    with jax.disable_jit():
        ssd.ssd_chunked(**ops, length=50, chunk=16)
        ssd.ssd_step(ops["x"][:2], ops["dt"][:2], ops["A"], ops["B"][:2],
                     ops["C"][:2], ops["D"], jnp.stack([ops["state0"]] * 2))
    monkeypatch.undo()
    assert len(seen) >= 5 and max(seen) <= 0.0
    strong = _operands(seed=7, dt_scale=400.0)
    y, state = ssd.ssd_chunked(**strong, length=T)
    want_y, want_state = _recurrence(**strong, length=T)
    assert bool(jnp.isfinite(y).all()) and bool(jnp.isfinite(state).all())
    np.testing.assert_allclose(y, want_y, rtol=2e-4, atol=2e-2)
    np.testing.assert_allclose(state, want_state, rtol=2e-4, atol=2e-2)


def test_the_two_forms_carry_their_names_into_the_lowered_program():
    """Each form is jitted on its own, so a program that calls it carries
    its name in the ``op_name`` of every operation it lowers to:
    ``tests/compute/test_tpu_compile.py`` finds the decode update by
    ``jit(ssm_step)`` in the compiled HLO."""
    ops = _operands(t=32)
    scan = jax.jit(lambda: ssd.ssd_chunked(**ops, length=32)).lower()
    step = jax.jit(lambda: ssd.ssd_step(
        ops["x"][:2], ops["dt"][:2], ops["A"], ops["B"][:2], ops["C"][:2],
        ops["D"], jnp.stack([ops["state0"]] * 2))).lower()
    assert "ssm_scan" in scan.as_text(debug_info=True)
    assert "ssm_step" in step.as_text(debug_info=True)
