"""Time the experts' grouped product on the chip at the two sparse cells'
shapes: XLA's ``ragged_dot``, jax's ``megablox.gmm`` at its default and at
large tiles (a yardstick) and ``ops/grouped_matmul.py`` over a sweep of the
bytes one copy brings.  Chip only; prints one JSON line a measurement and
writes them all to ``chiprun_out/grouped_matmul_bench.json``.

    python scripts/bench_grouped_matmul.py [--quick]
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
import numpy as np  # noqa: E402

from dstack_tpu.ops import grouped_matmul as gm  # noqa: E402

#: (name, rows, live rows, experts, hidden, expert width)
SHAPES = [
    ("lfm2.decode", 1024, 1024, 64, 2048, 1536),
    ("lfm2.chunk512", 2048, 2048, 64, 2048, 1536),
    ("lfm2.prefill64", 256, 256, 64, 2048, 1536),
    ("ling.decode", 1024, 256, 128, 2560, 768),
    ("ling.chunk512", 4096, 1024, 128, 2560, 768),
]


def timed(fn, *args, reps=20):
    out = fn(*args)
    jax.block_until_ready(out)
    jax.block_until_ready(fn(*args))
    t = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t) / reps, out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    args = ap.parse_args()
    if jax.devices()[0].platform != "tpu":
        raise SystemExit("chip only")
    from jax.experimental.pallas.ops.tpu.megablox import gmm as megablox_gmm

    results = []

    def report(**row):
        results.append(row)
        print(json.dumps(row), flush=True)

    blocks = (gm.BLOCK_BYTES >> 20,) if args.quick else (2, 4, 8, 16)
    rng = np.random.default_rng(36)
    made = {}
    for name, rows, live, e, d, f in SHAPES:
        if (e, d, f) not in made:
            made.clear()
            key = jax.random.PRNGKey(e)
            made[e, d, f] = [
                (jax.random.normal(k, s, jnp.bfloat16) * s[1] ** -0.5)
                for k, s in zip(jax.random.split(key, 3),
                                [(e, d, f), (e, d, f), (e, f, d)])]
        wg, wu, wd = made[e, d, f]
        counts = np.bincount(rng.integers(0, e, size=live), minlength=e)
        touched = int((counts > 0).sum())
        mat_bytes = touched * d * f * 2
        c = jnp.asarray(counts, jnp.int32)
        # the parent's group sizes: the dead tail in the last expert's group
        tail = c.at[e - 1].add(rows - live)
        x = jax.random.normal(jax.random.PRNGKey(1), (rows, d), jnp.bfloat16)
        h = jax.random.normal(jax.random.PRNGKey(2), (rows, f), jnp.bfloat16)
        base = dict(shape=name, rows=rows, live=live, touched=touched,
                    max_count=int(counts.max()))

        def gbs(seconds, matrices):
            return matrices * mat_bytes / seconds / 1e9

        rd = jax.jit(jax.lax.ragged_dot)
        s_up, ref_up = timed(rd, x, wg, tail)
        s_dn, ref_dn = timed(rd, h, wd, tail)
        report(**base, impl="ragged_dot", up_ms=s_up * 1e3, down_ms=s_dn * 1e3,
               mlp_ms=(2 * s_up + s_dn) * 1e3, up_gbs=gbs(s_up, 1),
               down_gbs=gbs(s_dn, 1))
        live_mask = (jnp.arange(rows) < live)[:, None]

        for tm, tk, tn in ((128, 128, 128), (128, d, 512), (128, 512, 512),
                           (256, d, 512)):
            try:
                fn = jax.jit(lambda a, b, g, t=(tm, tk, tn): megablox_gmm(
                    a, b, g, jnp.bfloat16, t))
                s, _ = timed(fn, x, wg, tail)
                s2, _ = timed(jax.jit(
                    lambda a, b, g, t=(tm, min(tk, f), tn): megablox_gmm(
                        a, b, g, jnp.bfloat16, t)), h, wd, tail)
                report(**base, impl=f"megablox{(tm, tk, tn)}", up_ms=s * 1e3,
                       down_ms=s2 * 1e3, mlp_ms=(2 * s + s2) * 1e3,
                       up_gbs=gbs(s, 1), down_gbs=gbs(s2, 1))
            except Exception as ex:  # a tiling the compiler refuses
                report(**base, impl=f"megablox{(tm, tk, tn)}",
                       error=repr(ex)[:300])

        for mb in blocks:
            kw = dict(block_bytes=mb << 20)
            fused = jax.jit(lambda a, g, u, n, kw=kw:
                            gm.grouped_matmul(a, (g, u), n, **kw))
            one = jax.jit(lambda a, w, n, kw=kw:
                          gm.grouped_matmul(a, w, n, **kw))
            s_f, got_f = timed(fused, x, wg, wu, c)
            s_1, got_up = timed(one, x, wg, c)
            s_d, got_dn = timed(one, h, wd, c)
            err_up = float(jnp.abs(jnp.where(
                live_mask, got_up.astype(jnp.float32)
                - ref_up.astype(jnp.float32), 0)).max())
            err_dn = float(jnp.abs(jnp.where(
                live_mask, got_dn.astype(jnp.float32)
                - ref_dn.astype(jnp.float32), 0)).max())
            dead = float(jnp.abs(jnp.where(
                live_mask, 0, got_f.astype(jnp.float32))).max())
            report(**base, impl=f"grouped_matmul(block={mb}MB)",
                   gate_up_ms=s_f * 1e3, up_ms=s_1 * 1e3,
                   down_ms=s_d * 1e3, mlp_ms=(s_f + s_d) * 1e3,
                   gate_up_gbs=gbs(s_f, 2), up_gbs=gbs(s_1, 1),
                   down_gbs=gbs(s_d, 1), err_up=err_up, err_down=err_dn,
                   dead_rows_max=dead, finite=bool(jnp.isfinite(
                       got_f.astype(jnp.float32)).all()))
    out = ROOT / "chiprun_out"
    out.mkdir(exist_ok=True)
    (out / "grouped_matmul_bench.json").write_text(json.dumps(results))


if __name__ == "__main__":
    main()
