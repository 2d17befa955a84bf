"""The experts' grouped product (``ops/grouped_matmul.py``), interpreted on
the CPU, against a plain loop over the experts: every way the sorted rows can
fall on the row tiles, both dtypes, gate/up fused and the down product.  Rows
nobody owns (the dead tail of absent experts and masked tokens) are exactly
zero, never NaN, whatever garbage the rows themselves hold."""

from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dstack_tpu.models import experts
from dstack_tpu.ops import grouped_matmul as gm

TILE = gm.ROW_TILE

#: name -> (rows, hidden K, expert width N, counts of the experts in order)
PATTERNS = {
    "even-groups-of-16": (256, 256, 32, [16] * 16),
    "one-group-holds-every-row": (256, 64, 32, [0, 256, 0, 0]),
    "empty-groups-between-full-ones": (384, 64, 32,
                                       [0, TILE, 0, 0, TILE, 0, TILE, 0]),
    "every-row-in-the-dead-tail": (256, 64, 32, [0, 0, 0, 0]),
    "a-group-straddles-a-row-tile": (256, 64, 32, [100, 60, 3, 0, 40]),
    "a-group-spans-three-tiles": (512, 32, 256, [5, 300, 0, 90]),
    "rows-no-multiple-of-the-tile": (203, 64, 32, [7, 0, 120, 1, 40]),
    "fewer-rows-than-a-tile": (10, 32, 16, [3, 0, 4]),
    "two-rows-an-expert-most-rows-dead": (512, 64, 32,
                                          [2, 0, 3, 1, 0, 0, 2, 4] * 4),
    "lfm2-width-ratio": (256, 128, 96, [16, 26, 9, 13] * 4),      # 2048:1536
    "ling-width-ratio": (256, 160, 48, [2, 0, 1, 5] * 8),         # 2560:768
    "nemotron-width-ratio": (256, 168, 116, [9, 0, 2, 25] * 4),   # 2688:1856
    "last-expert-ends-on-a-tile-edge": (256, 64, 32, [28, 100, 128]),
}


def _case(pattern, dtype, product="gate-up"):
    """``(x, weights, counts)``: ``x`` against the gate and up stacks, or
    rows of the experts' width against the down stack."""
    rows, k, n, counts = PATTERNS[pattern]
    e = len(counts)
    keys = jax.random.split(jax.random.PRNGKey(0), 4)
    shapes = ([(e, k, n), (e, k, n)] if product == "gate-up"
              else [(e, n, k)])
    x = jax.random.normal(keys[0], (rows, shapes[0][1]), jnp.float32)
    # the rows nobody owns hold what must not reach the output
    live = sum(counts)
    x = x.at[live:].set(jnp.where(jnp.arange(rows - live)[:, None] % 2,
                                  jnp.inf, jnp.nan))
    w = [jax.random.normal(key, shape, jnp.float32) * shape[1] ** -0.5
         for key, shape in zip(keys[1:], shapes)]
    return (x.astype(dtype), tuple(m.astype(dtype) for m in w),
            jnp.asarray(counts, jnp.int32))


def _loop(x, weights, counts, dtype):
    """Expert by expert in float32 on the operands as stored, rounded once
    to ``dtype``; zeros past the last owned row."""
    x = np.asarray(x.astype(jnp.float32))
    weights = [np.asarray(w.astype(jnp.float32)) for w in weights]
    out = np.zeros((x.shape[0], weights[0].shape[2]), np.float32)
    start = 0
    for e, c in enumerate(np.asarray(counts)):
        rows = x[start:start + c]
        y = rows @ weights[0][e]
        if len(weights) == 2:
            y = y / (1.0 + np.exp(-y)) * (rows @ weights[1][e])
        out[start:start + c] = y
        start += c
    return np.asarray(jnp.asarray(out).astype(dtype).astype(jnp.float32))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("product", ["gate-up", "down"])
@pytest.mark.parametrize("pattern", list(PATTERNS))
def test_grouped_product_is_the_loop_over_experts(pattern, product, dtype):
    x, weights, counts = _case(pattern, dtype, product)
    # blocks of 128 columns where the width allows, so that the column grid
    # has more than one step
    got = jax.jit(lambda x, w, c: gm.grouped_matmul(
        x, w, c, block_bytes=1))(x, weights, counts)
    assert got.shape == (x.shape[0], weights[0].shape[2])
    assert got.dtype == x.dtype
    got = np.asarray(got.astype(jnp.float32))
    live = int(counts.sum())
    assert np.isfinite(got).all()
    assert (got[live:] == 0).all()
    want = _loop(x.at[live:].set(0), weights, counts, dtype)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(got, want, atol=tol, rtol=tol)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("pattern", ["nemotron-width-ratio",
                                     "a-group-spans-three-tiles",
                                     "rows-no-multiple-of-the-tile",
                                     "every-row-in-the-dead-tail",
                                     "fewer-rows-than-a-tile"])
def test_transposed_stack_is_the_same_product(pattern, dtype):
    """``transposed=True`` takes the stack ``[E, N, K]`` (an expert's matrix
    as ``nn.Linear`` keeps it) and gives ``x @ w[e].T``: the loop's result
    on the stack's transpose, in whole-width and in 128-row blocks."""
    x, (w, _), counts = _case(pattern, dtype)
    stack = jnp.swapaxes(w, 1, 2)                         # [E, N, K]
    live = int(counts.sum())
    want = _loop(x.at[live:].set(0), (w,), counts, dtype)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    for block_bytes in (gm.BLOCK_BYTES, 1):
        got = jax.jit(lambda x, w, c: gm.grouped_matmul(
            x, w, c, transposed=True, block_bytes=block_bytes))(
                x, stack, counts)
        assert got.shape == (x.shape[0], w.shape[2]) and got.dtype == x.dtype
        got = np.asarray(got.astype(jnp.float32))
        assert np.isfinite(got).all() and (got[live:] == 0).all()
        np.testing.assert_allclose(got, want, atol=tol, rtol=tol)


@pytest.mark.parametrize("pattern", ["a-group-straddles-a-row-tile",
                                     "rows-no-multiple-of-the-tile",
                                     "every-row-in-the-dead-tail"])
def test_the_gated_mlp_shares_one_plan(pattern):
    """``grouped_swiglu`` is the two calls one after the other."""
    x, (w_gate, w_up), counts = _case(pattern, jnp.float32)
    (w_down,) = _case(pattern, jnp.float32, "down")[1]
    got = np.asarray(jax.jit(gm.grouped_swiglu)(x, w_gate, w_up, w_down,
                                                counts))
    live = int(counts.sum())
    gated = _loop(x.at[live:].set(0), (w_gate, w_up), counts, jnp.float32)
    want = _loop(jnp.asarray(gated), (w_down,), counts, jnp.float32)
    assert (got[live:] == 0).all()
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("pattern", list(PATTERNS))
def test_the_plan_visits_every_tile_once_and_in_order(pattern):
    """The work items: each expert with a row gets the tiles its rows reach
    into, in order; the tiles nobody reaches follow, each once; the rest of
    the static grid repeats the last tile (no fetch, no write); an expert's
    first item is marked, knows the expert with a row after it (the last
    one's is the first again), and the rows the product computes are the
    computing items' tiles."""
    rows, _, _, counts = PATTERNS[pattern]
    tiles = -(-rows // TILE)
    expert, out_tile, x_tile, lead, following, starts, ends, items = (
        np.asarray(a) for a in gm.plan(jnp.asarray(counts, jnp.int32), tiles))
    assert len(expert) == tiles + len(counts) - 1
    computing, valid = items
    want = [(e, t) for e, c in enumerate(counts) if c
            for t in range(starts[e] // TILE, (ends[e] - 1) // TILE + 1)]
    assert list(zip(expert[:computing], out_tile[:computing])) == want
    assert (x_tile[:computing] == out_tile[:computing]).all()
    reached = {t for _, t in want}
    assert sorted(out_tile[computing:valid]) == sorted(
        set(range(tiles)) - reached)
    assert (np.diff(out_tile) >= 0).all()          # a tile's visits adjoin
    assert (out_tile[valid:] == tiles - 1).all()
    if computing:                                   # nothing new to fetch
        assert (expert[computing:] == expert[computing - 1]).all()
        assert (x_tile[computing:] == x_tile[computing - 1]).all()
    assert int(gm.row_tiles_visited(jnp.asarray(counts))) == computing
    with_rows = [e for e, c in enumerate(counts) if c]
    firsts = [i for i in range(computing)
              if i == 0 or expert[i] != expert[i - 1]]
    assert [i for i in range(len(lead)) if lead[i]] == firsts
    assert [int(expert[i]) for i in firsts] == with_rows
    assert [int(lead[i]) for i in firsts] == [1] * (len(firsts) - 1) + [2] * (
        len(firsts) > 0)
    assert [int(following[i]) for i in firsts] == with_rows[1:] + with_rows[:1]


def test_column_blocks_follow_the_shapes():
    """All of the columns while a copy stays within the budget, else the
    widest divisor in whole lane tiles that does."""
    block = lambda k, n, size, matrices: gm._column_block(
        k, n, size, matrices, gm.BLOCK_BYTES)
    assert block(2048, 1536, 2, 2) == 768       # LFM2 gate and up: halves
    assert block(1536, 2048, 2, 1) == 2048      # LFM2 down: whole
    assert block(2560, 768, 2, 2) == 768        # Ling gate and up: whole
    assert block(768, 2560, 2, 1) == 2560       # Ling down: whole
    assert block(64, 32, 4, 2) == 32            # a toy: whole
    assert block(2688, 1856, 2, 1) == 1856      # Nemotron up: no lane tiles
    assert block(1856, 2688, 2, 1) == 896       # Nemotron down: thirds
    assert block(4096, 14336, 2, 2) == 512      # wide experts: 4 lane tiles
    assert gm._column_block(64, 96, 4, 1, 1) == 96    # no lane tiles: whole
    assert gm._column_block(64, 256, 4, 1, 1) == 128  # never under a tile


@pytest.mark.parametrize("form", ["swiglu", "relu2"])
@pytest.mark.parametrize("held", ["every-expert-held", "a-quarter-held"])
@pytest.mark.parametrize("masked", ["no-mask", "half-the-tokens-masked"])
def test_held_experts_is_the_same_through_the_kernel(monkeypatch, held,
                                                     masked, form):
    """``models/experts.py`` ``held_experts`` through the kernel (what every
    backend but the CPU's runs; interpreted here) against its CPU path
    (XLA's ``ragged_dot``, the absent and masked pairs computed in the last
    expert's group), in both of the experts' forms (gate, up, down; up,
    relu squared, down): the same sums, the same counts, and the load
    vector's sixth field counts the kernel's rows.  The two-matrix form
    against a plain loop over the experts besides."""
    routed, k, d, f, t = 16, 4, 64, 32, 37
    cfg = SimpleNamespace(
        num_experts_per_tok=k,
        experts_held=routed if held == "every-expert-held" else routed // 4,
        expert_offset=0 if held == "every-expert-held" else routed // 2)
    keys = jax.random.split(jax.random.PRNGKey(3), 6)
    e = cfg.experts_held
    lp = {"we_gate": jax.random.normal(keys[0], (e, d, f)) * d ** -0.5,
          "we_up": jax.random.normal(keys[1], (e, d, f)) * d ** -0.5,
          "we_down": jax.random.normal(keys[2], (e, f, d)) * f ** -0.5}
    h = jax.random.normal(keys[3], (t, d))
    ids = jax.vmap(lambda key: jax.random.permutation(key, routed)[:k])(
        jax.random.split(keys[4], t)).astype(jnp.int32)
    weights = jax.nn.softmax(jax.random.normal(keys[5], (t, k)))
    mask = None if masked == "no-mask" else jnp.arange(t) % 2 == 0
    if form == "relu2":     # two stacks, both [experts, width, hidden]
        del lp["we_gate"]
        lp["we_up"] = jnp.swapaxes(lp["we_up"], 1, 2)
    plain, plain_counts = jax.jit(
        lambda *a: experts.held_experts(*a, lp, cfg, mask, form))(
            h, ids, weights)
    assert not experts._on_chip()
    monkeypatch.setattr(experts, "_on_chip", lambda: True)
    ours, counts = jax.jit(
        lambda *a: experts.held_experts(*a, lp, cfg, mask, form))(
            h, ids, weights)
    if form == "relu2":
        local = np.asarray(ids) - cfg.expert_offset
        want = np.zeros((t, d), np.float32)
        for i in range(t):
            for j in range(k):
                if 0 <= local[i, j] < e and (mask is None or bool(mask[i])):
                    want[i] += float(weights[i, j]) * np.asarray(
                        experts.relu2(h[i], lp["we_up"][local[i, j]].T,
                                      lp["we_down"][local[i, j]]))
        np.testing.assert_allclose(np.asarray(ours), want, atol=2e-5,
                                   rtol=2e-5)
    assert (np.asarray(counts) == np.asarray(plain_counts)).all()
    assert 0 < int(counts.sum()) <= (t if mask is None else (t + 1) // 2) * k
    np.testing.assert_allclose(np.asarray(ours), np.asarray(plain),
                               atol=2e-5, rtol=2e-5)
    load = experts.expert_load(counts, jnp.float32(t), cfg)
    assert load.shape == (experts.LOAD_FIELDS,) == (6,)
    assert float(load[5]) == TILE * int(gm.row_tiles_visited(counts))
    assert float(load[0]) == int(counts.sum()) <= float(load[5])
