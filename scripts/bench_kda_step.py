"""Time the KDA decode step on the chip at the hybrid cell's shapes (6 KDA
layers, 128 slots, 32 heads, d_k = d_v = 128, float32 state): XLA's form
(``ops/kda.py`` ``kda_step`` on a layer of the stack, then the layer written
back) against ``kda_step_into`` (one Pallas kernel a layer, in place) over
a sweep of the bytes a grid step reads.  A step is all six layers, the state
donated from step to step as the decode window carries it; GB/s counts the
state read and written once a layer (2 x 1.61 GB a step), what the floor
counts.  Chip only; prints one JSON line a measurement and writes them all
to ``chiprun_out/kda_step_bench.json``.

    python scripts/bench_kda_step.py [--quick]
"""

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from dstack_tpu.ops import kda  # noqa: E402

LAYERS, SLOTS, HEADS, D = 6, 128, 32, 128
#: slots whose rows are idle (g = 0, beta = 0): their tiles must come back
#: bitwise as they were
IDLE = 16


def step_inputs(key):
    """One token of every slot and layer: ``(q, k, v, g, beta)``, each
    [LAYERS, ...], as ``models/ling_hybrid.py`` forms them."""
    ks = jax.random.split(key, 5)
    q = kda.l2_normalize(jax.random.normal(
        ks[0], (LAYERS, SLOTS, HEADS, D))) * D ** -0.5
    k = kda.l2_normalize(jax.random.normal(ks[1], (LAYERS, SLOTS, HEADS, D)))
    v = jax.random.normal(ks[2], (LAYERS, SLOTS, HEADS, D))
    g = kda.kda_gate(jax.random.normal(ks[3], (LAYERS, SLOTS, HEADS, D)),
                     jnp.zeros((HEADS,)), jnp.zeros((HEADS * D,)), -5.0)
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (LAYERS, SLOTS, HEADS)))
    live = jnp.arange(SLOTS) >= IDLE
    g = jnp.where(live[None, :, None, None], g, 0.0)
    beta = jnp.where(live[None, :, None], beta, 0.0)
    return q, k, v, g, beta


def xla_step(states, q, k, v, g, beta):
    outs = []
    for j in range(LAYERS):
        o, s = kda.kda_step(states[j], q[j], k[j], v[j], g[j], beta[j])
        states = states.at[j].set(s)
        outs.append(o)
    return jnp.stack(outs), states


def kernel_step(states, q, k, v, g, beta, *, block_bytes):
    outs = []
    for j in range(LAYERS):
        o, states = kda.kda_step_into(states, j, q[j], k[j], v[j], g[j],
                                      beta[j], block_bytes=block_bytes)
        outs.append(o)
    return jnp.stack(outs), states


def timed(fn, states, inputs, reps=20):
    """Seconds a call, the state carried from call to call (donated)."""
    for _ in range(2):
        o, states = fn(states, *inputs)
    jax.block_until_ready(states)
    t = time.perf_counter()
    for _ in range(reps):
        o, states = fn(states, *inputs)
    jax.block_until_ready(states)
    return (time.perf_counter() - t) / reps


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    args = ap.parse_args()
    if jax.devices()[0].platform != "tpu":
        raise SystemExit("chip only")
    results = []

    def report(**row):
        results.append(row)
        print(json.dumps(row), flush=True)

    state_bytes = LAYERS * SLOTS * HEADS * D * D * 4
    shape = dict(layers=LAYERS, slots=SLOTS, heads=HEADS, d=D,
                 state_gb=state_bytes / 1e9, device=jax.devices()[0].device_kind)
    inputs = step_inputs(jax.random.PRNGKey(43))
    start = jax.random.normal(jax.random.PRNGKey(1),
                              (LAYERS, SLOTS, HEADS, D, D), jnp.float32)
    fresh = jax.jit(lambda s: s + 0.0)

    xla = jax.jit(xla_step, donate_argnums=0)
    want_o, want_s = xla(fresh(start), *inputs)
    s = timed(xla, fresh(start), inputs)
    report(**shape, impl="xla", step_ms=s * 1e3,
           gbs=2 * state_bytes / s / 1e9)

    blocks = (kda.BLOCK_BYTES >> 20,) if args.quick else (2, 4, 8)
    for mb in blocks:
        fn = jax.jit(lambda st, *a, bb=mb << 20: kernel_step(
            st, *a, block_bytes=bb), donate_argnums=0)
        got_o, got_s = fn(fresh(start), *inputs)
        idle_same = bool((got_s[:, :IDLE] == start[:, :IDLE]).all())
        row = dict(
            err_o=float(jnp.abs(got_o - want_o).max()),
            err_state=float(jnp.abs(got_s - want_s).max()),
            idle_tiles_unchanged=idle_same,
            finite=bool(jnp.isfinite(got_s).all()))
        del got_o, got_s
        s = timed(fn, fresh(start), inputs)
        report(**shape, impl=f"kda_step_into(block={mb}MB)",
               step_ms=s * 1e3, gbs=2 * state_bytes / s / 1e9, **row)
    out = ROOT / "chiprun_out"
    out.mkdir(exist_ok=True)
    (out / "kda_step_bench.json").write_text(json.dumps(results))


if __name__ == "__main__":
    main()
