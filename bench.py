"""Headline benchmark: Llama-3 training throughput, tokens/sec/chip.

Runs the full sharded train step (bf16, remat, adamw) on the local
accelerator(s).  The north-star metric (BASELINE.json) is Llama-3-8B
tokens/sec/chip on a v5e-64 slice; a single v5e chip (16 GB HBM) cannot hold
8B training state, so the single-chip bench uses the Llama-3.2-1B shape and
reports tokens/sec/chip plus model FLOPs utilization (on stderr).  There is
no reference-published number (the reference is an orchestrator —
BASELINE.md), so the first recorded run is persisted to
``BENCH_BASELINE.json`` and later runs report ``vs_baseline`` against it.

``python bench.py`` needs the chip: ``main()`` fails at once on any other
platform, runs one fixed configuration per phase, and the first phase that
fails ends the run non-zero — a number is never printed for a device or a
size it was not measured on.  (The ``run_*`` functions stay importable on
the CPU; ``scripts/ci.sh`` gates their keys at ``small=True``.)

Prints exactly one JSON line on stdout.
"""

from __future__ import annotations

import json
import os
import sys
import time

# flash-attention reads this at TRACE time (flash_attention._block_sizes),
# so per-bench overrides work; 1024 is the measured-best for the 1B shape
os.environ.setdefault("DSTACK_TPU_FLASH_BLOCK", "1024")

import jax
import jax.numpy as jnp

from dstack_tpu.models import llama, train
# per-chip bf16 peaks keyed by device_kind — the single table, shared with
# TrainTelemetry's MFU gauge so the two can never diverge.
from dstack_tpu.telemetry.training import PEAK_BF16_FLOPS
from dstack_tpu.utils.jax_runtime import device_report, enable_persistent_cache


def log(*a):
    print(*a, file=sys.stderr, flush=True)


def _measure(cfg, batch: int, seq: int, steps: int, warmup: int,
             capture_telemetry: bool = True):
    """Shared train-step measurement harness: (tok/s/chip, MFU, telemetry).

    Measured-best single-chip configuration (v5e, r3 profiling):
    unstacked+unrolled layers (no stacked-weight scatter/gather), no
    redundant grad-norm pass; flash block comes from the env (trace-time).

    The timed region stays UN-instrumented (the telemetry wrapper blocks
    per step, which would serialize the dispatch pipeline the headline
    number depends on); a few wrapped steps run AFTER it to capture the
    per-step histogram/MFU telemetry for the bench payload.
    """
    opt = train.default_optimizer()
    state = train.create_state(jax.random.PRNGKey(0), cfg, opt, unstacked=True)
    step_fn = train.make_train_step(
        cfg, opt, remat=True, scan_layers=False, unstacked=True,
        with_grad_norm=False,
    )
    tokens = jax.random.randint(jax.random.PRNGKey(1), (batch, seq + 1), 0,
                                cfg.vocab_size)
    batch_d = {"tokens": tokens}

    t0 = time.perf_counter()
    for _ in range(warmup):
        state, metrics = step_fn(state, batch_d)
    jax.block_until_ready(metrics["loss"])
    log(f"compile+warmup: {time.perf_counter()-t0:.1f}s "
        f"loss={float(metrics['loss']):.3f}")

    t0 = time.perf_counter()
    for _ in range(steps):
        state, metrics = step_fn(state, batch_d)
    jax.block_until_ready(metrics["loss"])
    dt = time.perf_counter() - t0

    n_chips = max(len(jax.devices()), 1)
    tok_per_sec_chip = batch * seq * steps / dt / n_chips
    # MFU only against the peak of the device this ran on: None (not the
    # v5e's figure) where the table does not know the device
    peak = PEAK_BF16_FLOPS.get(jax.devices()[0].device_kind)
    mfu = (None if peak is None else
           6 * cfg.num_params() * batch * seq * steps / dt / n_chips / peak)
    log(f"{steps} steps in {dt:.3f}s -> {tok_per_sec_chip:,.0f} tok/s/chip, "
        + ("MFU not computed (no peak for this device)" if mfu is None
           else f"MFU≈{mfu*100:.1f}% ({jax.devices()[0].device_kind} peak)"))

    telemetry = None
    if not capture_telemetry:
        return tok_per_sec_chip, mfu, telemetry
    from dstack_tpu.telemetry.recorder import percentiles_from_snapshot
    from dstack_tpu.telemetry.training import TrainTelemetry

    tel = TrainTelemetry(log_every=0)
    # wrapping an already-warm step: the cache baseline keeps these
    # from reading as recompiles
    tel_step = tel.wrap(step_fn, cfg, n_devices=n_chips)
    for _ in range(3):
        state, metrics = tel_step(state, batch_d)
    p = percentiles_from_snapshot(tel.step_seconds.snapshot())
    telemetry = {
        "step_time_p50_ms": round(p["p50"] * 1e3, 2),
        "step_time_p99_ms": round(p["p99"] * 1e3, 2),
        "tokens_per_sec": round(tel.tokens_per_sec.value, 1),
        "mfu": round(tel.mfu.value, 4) if tel.peak_flops else None,
        "recompiles": int(tel.recompiles_total.value),
    }
    log(f"telemetry: step p50 {telemetry['step_time_p50_ms']}ms "
        f"MFU {telemetry['mfu']} recompiles {telemetry['recompiles']}")
    return tok_per_sec_chip, mfu, telemetry


def run_bench(batch: int, seq: int, steps: int = 5, warmup: int = 2):
    cfg = llama.LlamaConfig.llama3_1b()
    log(f"model: llama3-1b shape, {cfg.num_params()/1e9:.2f}B params; "
        f"batch={batch} seq={seq} devices={jax.devices()}")
    tok_per_sec_chip, _, telemetry = _measure(cfg, batch, seq, steps, warmup)
    return tok_per_sec_chip, telemetry


def run_bench_8b(steps: int = 3, warmup: int = 2):
    """North-star shape: Llama-3-8B LAYER GEOMETRY (hidden 4096, ffn 14336,
    GQA 32/8, head_dim 128) at the depth whose bf16 AdamW state fits one
    16 GB v5e chip (L=6 of 32; full-depth state is ~48 GB — see ROOFLINE.md).
    Reports measured tok/s/chip + MFU on this shape, plus the full-depth-8B
    projection at the measured MFU (conservative: the embed/CE fraction —
    the least MXU-efficient part — shrinks 5x at L=32).
    """
    prev_block = os.environ.get("DSTACK_TPU_FLASH_BLOCK")
    os.environ["DSTACK_TPU_FLASH_BLOCK"] = "512"  # best for d=128 (r4 sweep)
    try:
        batch, seq = 4, 2048
        cfg = llama.LlamaConfig.llama3_8b_fit(num_layers=6)
        log(f"8B-shape: d=4096 f=14336 L={cfg.num_layers} "
            f"({cfg.num_params()/1e9:.2f}B params) batch={batch} seq={seq}")
        # the 1B headline run already captured step telemetry; don't pay
        # for 3 more blocking 8B-shape steps whose result nobody reads
        tok_s, mfu, _ = _measure(cfg, batch, seq, steps, warmup,
                                 capture_telemetry=False)
        if mfu is None:
            return tok_s, None, None
        full = llama.LlamaConfig.llama3_8b()
        projected = (mfu * PEAK_BF16_FLOPS[jax.devices()[0].device_kind]
                     / (6 * full.num_params()))
        log(f"projected full-8B @ this MFU: {projected:,.0f} tok/s/chip")
        return tok_s, mfu, projected
    finally:
        if prev_block is None:
            os.environ.pop("DSTACK_TPU_FLASH_BLOCK", None)
        else:
            os.environ["DSTACK_TPU_FLASH_BLOCK"] = prev_block


def run_serving_bench(steps_budget: float = 60.0, quantize=None,
                      concurrency: int = 8, telemetry: str = "none"):
    """Serving throughput: InferenceEngine continuous batching on the chip.

    ``concurrency`` concurrent sequences, 128-token prompts, decode until
    the budget; reports generated tokens/sec (decode-dominated, the
    serving regime).

    ``telemetry``: "none" (bare engine), "on" (EngineTelemetry, no
    tracer), or "trace" (telemetry + RequestTracer with every request
    carrying a trace id — the full span-recording path).  The on/trace
    pair is the ``serving_tracing_overhead_*`` tok/s comparison.
    """
    from dstack_tpu.serving.engine import InferenceEngine, Request
    from dstack_tpu.telemetry.serving import EngineTelemetry
    from dstack_tpu.telemetry.tracing import RequestTracer, new_trace_id

    tel = None
    if telemetry == "on":
        tel = EngineTelemetry()
    elif telemetry == "trace":
        tel = EngineTelemetry(tracer=RequestTracer())
    cfg = llama.LlamaConfig.llama3_1b()
    engine = InferenceEngine(cfg, batch_size=concurrency, max_len=512,
                             quantize=quantize, telemetry=tel)
    prompts = [[(7 * i + j) % 1000 + 1 for j in range(128)]
               for i in range(concurrency)]

    def submit_all():
        rs = [Request(tokens=list(p), max_new_tokens=256) for p in prompts]
        for r in rs:
            if telemetry == "trace":
                r.trace_id = new_trace_id()
            engine.submit(r)
        return rs

    # full warm round first: compiles every program AND settles the
    # dispatch pipeline — single-shot timing right after compile was the
    # dominant run-to-run variance (±15%) in earlier rounds
    warm = submit_all()
    t0 = time.perf_counter()
    while (not all(r.done.is_set() for r in warm)
           and time.perf_counter() - t0 < steps_budget):
        engine.step()
    if not all(r.done.is_set() for r in warm):
        # unfinished warm requests would occupy slots and contaminate the
        # timed round with queueing — fail rather than underreport
        raise RuntimeError(
            f"serving warm round did not finish within {steps_budget}s")
    reqs = submit_all()
    engine.step()  # prefill outside the timed window
    t0 = time.perf_counter()
    n0 = sum(len(r.output) for r in reqs)
    while (not all(r.done.is_set() for r in reqs)
           and time.perf_counter() - t0 < steps_budget):
        engine.step()
    dt = time.perf_counter() - t0
    generated = sum(len(r.output) for r in reqs) - n0
    tok_s = generated / dt
    log(f"serving{f' {quantize}' if quantize else ''}: {generated} tokens "
        f"in {dt:.2f}s -> {tok_s:,.0f} tok/s "
        f"({concurrency}-way continuous batching)")
    return tok_s


def run_ttft_bench(quantize="int8"):
    """TTFT under mixed load: 7 slots decoding long generations, then a
    LONG-prompt (1024-token) request arrives.  Chunked prefill interleaves
    the newcomer's prefill with the incumbents' decode windows; reports the
    newcomer's time-to-first-token and the background decode rate while it
    was prefilling (the number chunking exists to protect).
    """
    from dstack_tpu.serving.engine import InferenceEngine, Request

    cfg = llama.LlamaConfig.llama3_1b()
    engine = InferenceEngine(cfg, batch_size=8, max_len=2048,
                             quantize=quantize, prefill_chunk=512)
    bg = [Request(tokens=[(7 * i + j) % 1000 + 1 for j in range(128)],
                  max_new_tokens=1500)
          for i in range(7)]
    for r in bg:
        engine.submit(r)
    # warm the steady state (compiles the bg prefill + decode windows AND
    # the chunk-prefill jit via a throwaway long prompt)
    warm = Request(tokens=[(5 * j) % 1000 + 1 for j in range(1024)],
                   max_new_tokens=1)
    engine.submit(warm)
    while not warm.done.is_set():
        engine.step()
    probe = Request(tokens=[(3 * j) % 1000 + 1 for j in range(1024)],
                    max_new_tokens=8)
    bg0 = sum(len(r.output) for r in bg)
    t0 = time.time()  # Request.first_token_at is a time.time() stamp
    engine.submit(probe)
    while probe.first_token_at is None and time.time() - t0 < 60:
        engine.step()
    ttft = (probe.first_token_at or time.time()) - t0
    bg_rate = (sum(len(r.output) for r in bg) - bg0) / max(ttft, 1e-9)
    while not probe.done.is_set() and time.time() - t0 < 60:
        engine.step()
    log(f"TTFT mixed load (1024-tok prompt vs 7 decoding slots, "
        f"chunk=512): {ttft*1e3:,.0f} ms; background decode "
        f"{bg_rate:,.0f} tok/s during prefill")
    return ttft, bg_rate


def run_decode_bench(steps_budget: float = 30.0, small: bool = False):
    """Decode hot-loop arms, one workload each (PR 18 raw-speed pass).

    Four paged-engine arms over the same greedy prompts: the dense-paged
    baseline (full block-table span gathered every window —
    DSTACK_TPU_RAGGED_DECODE=0), ragged buckets (power-of-two table slice
    sized to the longest active slot), ragged+int8 KV, and ragged+int4 KV.
    Short prompts against a long max_len make the span cost visible: the
    baseline gathers/attends the whole span while ragged touches only the
    occupied buckets, and quantized KV shrinks the bytes the gather (or
    the TPU block-table kernel) streams.  Reports tok/s per arm plus the
    batch TTFT (admission -> last first-token) for the baseline and int8
    arms — the acceptance pair for "faster at equal or better TTFT".

    ``small``: the caller's explicit choice, never guessed from the backend —
    False is the bench model (llama3_1b, 32-way) for the chip; True is a
    scaled-down config whose keys CI's CPU gate stage checks in seconds
    (its numbers are CPU numbers and never enter the bench payload).
    """
    import dataclasses

    from dstack_tpu.serving.engine import InferenceEngine, Request

    if small:
        # prompts long enough that KV reads are a visible share of the
        # step (the int8-vs-bf16 arm difference IS those bytes), max_len
        # far above them so the full-span baseline pays for the slack
        cfg = dataclasses.replace(llama.LlamaConfig.tiny(), max_seq_len=2048)
        concurrency, max_len, prompt_len, max_new = 8, 2048, 192, 64
    else:
        cfg = llama.LlamaConfig.llama3_1b()
        concurrency, max_len, prompt_len, max_new = 32, 1024, 128, 256
    params = None
    prompts = [[(7 * i + j) % 1000 + 1 for j in range(prompt_len)]
               for i in range(concurrency)]

    def run_arm(kv_quantize=None, ragged=True):
        nonlocal params
        prev = os.environ.get("DSTACK_TPU_RAGGED_DECODE")
        os.environ["DSTACK_TPU_RAGGED_DECODE"] = "1" if ragged else "0"
        try:
            engine = InferenceEngine(
                cfg, params=params, batch_size=concurrency, max_len=max_len,
                paged=True, kv_quantize=kv_quantize)
        finally:
            if prev is None:
                os.environ.pop("DSTACK_TPU_RAGGED_DECODE", None)
            else:
                os.environ["DSTACK_TPU_RAGGED_DECODE"] = prev
        params = engine.params  # share weights across arms

        def round_once():
            rs = [Request(tokens=list(p), max_new_tokens=max_new)
                  for p in prompts]
            t0 = time.time()  # Request.first_token_at is a time.time() stamp
            for r in rs:
                engine.submit(r)
            while (not all(r.done.is_set() for r in rs)
                   and time.time() - t0 < steps_budget):
                engine.step()
            dt = time.time() - t0
            ttft = max((r.first_token_at or t0) for r in rs) - t0
            return sum(len(r.output) for r in rs) / dt, ttft * 1e3

        round_once()                      # compile + settle the pipeline
        return round_once()

    out = {}
    for name, kw in (
            ("dense", {"ragged": False}),
            ("ragged", {}),
            ("int8", {"kv_quantize": "int8"}),
            ("int4", {"kv_quantize": "int4"})):
        tok_s, ttft_ms = run_arm(**kw)
        out[f"serving_decode_{name}_tok_s"] = round(tok_s, 1)
        if name in ("dense", "int8"):
            out[f"serving_decode_{name}_ttft_ms"] = round(ttft_ms, 1)
        log(f"decode {name}: {tok_s:,.0f} tok/s"
            f" (ttft {ttft_ms:,.0f} ms)" if name in ("dense", "int8")
            else f"decode {name}: {tok_s:,.0f} tok/s")
    return out


def run_gateway_routing_bench():
    """Routing-policy comparison on the seeded multi-replica simulator
    (gateway/routing_sim.py — drives the REAL ReplicaLoadTracker): p95
    queue wait + TTFT proxy for round-robin vs P2C least-loaded vs
    +prefix-affinity at equal offered load.  Pure CPU, <1 s."""
    from dstack_tpu.gateway.routing_sim import compare_policies

    out = compare_policies()
    for policy, m in out.items():
        log(f"routing {policy}: p95 wait {m['p95_wait_ms']:,.0f} ms, "
            f"p95 TTFT {m['p95_ttft_ms']:,.0f} ms, "
            f"cache hit {m['cache_hit_rate']*100:.0f}%")
    return out


def run_twin_bench():
    """Fleet-twin replay cost + fidelity: the committed golden workload
    through the full twin (real tracker/breaker/hedge/admission under
    the seeded event clock), clean and under a grey-slow fault.  The
    wall clock lives HERE — dtlint DT106 bans it inside the twin, so
    replay stays byte-deterministic.  Pure CPU, <2 s."""
    from pathlib import Path
    from time import perf_counter

    from dstack_tpu.twin import (
        FleetTwin,
        TwinConfig,
        load_workload,
        run_fault_scenario,
        synthetic_workload,
    )

    golden = Path(__file__).parent / "tests/data/golden_workload.jsonl"
    if golden.exists():
        wl, _ = load_workload(golden)
    else:
        wl = synthetic_workload(400, seed=0, rps=25.0)
    cfg = TwinConfig(seed=0, deadline_s=8.0)
    t0 = perf_counter()
    clean = FleetTwin(wl, cfg).run()
    wall_ms = (perf_counter() - t0) * 1e3
    fault = run_fault_scenario(wl, ["slow_replica"], cfg)
    log(f"twin replay: {clean['requests']} reqs in {wall_ms:,.0f} ms "
        f"wall ({clean['virtual_wall_s']:.0f} s virtual), p95 TTFT "
        f"{clean['p95_ttft_ms']:,.1f} ms, {clean['tok_s']:,.0f} tok/s; "
        f"slow-replica p99 {fault['baseline']['p99_e2e_ms']:,.0f} ms -> "
        f"{fault['breaker']['p99_e2e_ms']:,.0f} ms defended")
    return {
        "twin_replay_p95_ttft_ms": clean["p95_ttft_ms"],
        "twin_replay_tok_s": clean["tok_s"],
        "twin_replay_requests": clean["requests"],
        "twin_replay_wall_ms": round(wall_ms, 1),
        "twin_fault_breaker_p99_ms": fault["breaker"]["p99_e2e_ms"],
        "twin_fault_deadline_misses": fault["breaker"]["deadline_misses"],
    }


def run_provision_bench():
    """North-star #1: provision -> first step latency on the local backend.

    Full control-plane loop against THIS machine: submit a task, the local
    backend spawns the real C++ shim, the shim execs the real runner, the
    runner runs the job's first command.  Measures submit->RUNNING seconds.
    No reference precedent (reference never measured it; BASELINE.md).
    """
    import asyncio
    import subprocess
    import tempfile
    from pathlib import Path

    native = Path(__file__).resolve().parent / "native"
    shim = native / "build" / "dstack-tpu-shim"
    runner = native / "build" / "dstack-tpu-runner"
    if not (shim.exists() and runner.exists()):
        subprocess.run(["make", "-C", str(native)], capture_output=True,
                       check=True)

    async def run():
        from dstack_tpu.core.models.backends import BackendType
        from dstack_tpu.core.models.configurations import (
            parse_apply_configuration,
        )
        from dstack_tpu.core.models.runs import ApplyRunPlanInput, RunSpec
        from dstack_tpu.server.app import register_pipelines
        from dstack_tpu.server.context import ServerContext
        from dstack_tpu.server.db import Database, migrate_conn
        from dstack_tpu.server.services import backends as backends_svc
        from dstack_tpu.server.services import projects as projects_svc
        from dstack_tpu.server.services import runs as runs_svc
        from dstack_tpu.server.services import users as users_svc
        from dstack_tpu.server.services.logs import FileLogStorage

        tmp = Path(tempfile.mkdtemp(prefix="dstack-bench-"))
        db = Database(":memory:")
        db.run_sync(migrate_conn)
        ctx = ServerContext(db, data_dir=tmp)
        ctx.log_storage = FileLogStorage(tmp)
        register_pipelines(ctx)
        admin = await users_svc.create_user(db, "admin")
        await projects_svc.create_project(db, admin, "main")
        project_row = await projects_svc.get_project_row(db, "main")
        await backends_svc.create_backend(
            ctx, project_row["id"], BackendType.LOCAL,
            {"shim_binary": str(shim), "runner_binary": str(runner)},
        )
        spec = RunSpec(
            run_name="bench-provision",
            configuration=parse_apply_configuration(
                {"type": "task", "commands": ["echo first-step"]}
            ),
        )
        t0 = time.perf_counter()
        await runs_svc.submit_run(
            ctx, project_row, admin, ApplyRunPlanInput(run_spec=spec)
        )
        names = ["runs", "jobs_submitted", "instances", "jobs_running",
                 "jobs_terminating"]
        latency = None
        for _ in range(600):
            for name in names:
                await ctx.pipelines.pipelines[name].run_once()
            row = await db.fetchone(
                "SELECT status FROM jobs WHERE run_name='bench-provision'"
            )
            if row and row["status"] in ("running", "terminating", "done"):
                latency = time.perf_counter() - t0
                break
            await asyncio.sleep(0.05)
        # drain to completion so agents shut down
        for _ in range(200):
            run = await runs_svc.get_run(ctx, project_row, "bench-provision")
            if run.status.is_finished():
                break
            for name in names:
                await ctx.pipelines.pipelines[name].run_once()
            await asyncio.sleep(0.05)
        # close the loop-bound aiohttp sessions the runner client opened, so
        # the bench exits without "Unclosed client session" noise
        from dstack_tpu.server.services.runner.client import close_sessions
        await close_sessions()
        return latency

    latency = asyncio.run(run())
    if latency is None:
        raise RuntimeError("provision bench: the job never reached RUNNING")
    log(f"provision -> first step (local backend): {latency:.2f}s")
    return latency


METRIC = "llama3_1b_train_tokens_per_sec_per_chip"
BASELINE_FILE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                             "BENCH_BASELINE.json")


def _vs_baseline(value: float) -> float:
    """First recorded run becomes the baseline; later runs report the ratio."""
    try:
        with open(BASELINE_FILE) as f:
            baseline = json.load(f).get(METRIC)
        if baseline:
            return round(value / baseline, 4)
    except FileNotFoundError:
        pass
    try:
        with open(BASELINE_FILE, "w") as f:
            json.dump({METRIC: value}, f)
    except OSError as e:
        log(f"could not persist baseline: {e}")
    return 1.0


def run_resume_overhead_bench(steps: int = 24, every: int = 6,
                              batch: int = 8, seq: int = 256):
    """``train_resume_overhead_*``: what preemption-safety costs.

    Same train step timed bare vs with an `AsyncCheckpointer` publishing
    every ``every`` steps (the async write path — the loop pays only the
    device->host shard copy), plus the one-off costs a recovery actually
    pays: a blocking emergency publish and a restore.  Uses the tiny
    config: the MECHANISM cost (snapshot copy + atomic publish machinery)
    is what's pinned; state-size scaling is linear and obvious.
    """
    import tempfile

    from dstack_tpu.models import checkpoint as ckpt_mod

    cfg = llama.LlamaConfig.tiny()
    opt = train.default_optimizer()
    step_fn = train.make_train_step(cfg, opt, with_grad_norm=False)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (batch, seq + 1), 0,
                                cfg.vocab_size)
    batch_d = {"tokens": tokens}

    def timed_loop(checkpointer):
        state = train.create_state(jax.random.PRNGKey(0), cfg, opt)
        state, m = step_fn(state, batch_d)  # compile + warm
        jax.block_until_ready(m["loss"])
        t0 = time.perf_counter()
        for i in range(steps):
            state, m = step_fn(state, batch_d)
            jax.block_until_ready(m["loss"])
            if checkpointer is not None:
                checkpointer.maybe_save(state, i + 1)
        if checkpointer is not None:
            checkpointer.flush()
        return time.perf_counter() - t0, state

    bare_s, state = timed_loop(None)
    with tempfile.TemporaryDirectory() as d:
        cp = ckpt_mod.AsyncCheckpointer(d, keep_last=2, every_steps=every)
        ckpt_s, _ = timed_loop(cp)
        t0 = time.perf_counter()
        cp.save(state, steps + 1, block=True)  # the emergency-flush path
        flush_ms = (time.perf_counter() - t0) * 1e3
        template = train.state_template(cfg, opt)
        t0 = time.perf_counter()
        ckpt_mod.read_snapshot(d, template)
        restore_ms = (time.perf_counter() - t0) * 1e3
        cp.close()
    pct = (ckpt_s - bare_s) / bare_s * 100.0 if bare_s > 0 else 0.0
    return {
        "step_overhead_pct": round(pct, 2),
        "emergency_flush_ms": round(flush_ms, 1),
        "restore_ms": round(restore_ms, 1),
    }


def run_drain_migrate_bench(concurrency: int = 8, gen_tokens: int = 64,
                            config: str = "llama3-1b"):
    """``serving_drain_migrate_*``: the cost of zero-drop replica
    replacement at the engine level — how long a loaded victim takes to
    finish its in-flight streams after ``begin_drain()`` (the migration's
    dead time), how many of those streams drop (must be 0), and the gap
    before a pre-warmed successor serves its first token.
    """
    import threading

    from dstack_tpu.serving.engine import InferenceEngine, Request

    cfg = (llama.LlamaConfig.tiny() if config == "tiny"
           else llama.LlamaConfig.llama3_1b())
    victim = InferenceEngine(cfg, batch_size=concurrency, max_len=512)
    successor = InferenceEngine(cfg, batch_size=concurrency, max_len=512)
    # pre-warm both (compile prefill/decode) — migration assumes a warm
    # successor, that's what "register successor BEFORE unregister" buys
    for eng in (victim, successor):
        eng.generate([1, 2, 3], max_new_tokens=4)
    prompts = [[(7 * i + j) % 1000 + 1 for j in range(128)]
               for i in range(concurrency)]
    reqs = [Request(tokens=p, max_new_tokens=gen_tokens) for p in prompts]
    for r in reqs:
        victim.submit(r)
    worker = threading.Thread(target=victim.run_forever, daemon=True)
    worker.start()
    # half-way through the decode: the preemption notice arrives.
    # Bounded wait: if the engine thread dies (device error), bail out so
    # main()'s try/except logs the failure instead of wedging the run
    deadline = time.monotonic() + 300
    while sum(len(r.output) for r in reqs) < concurrency * gen_tokens // 2:
        if not worker.is_alive() or time.monotonic() > deadline:
            victim.stop()
            raise RuntimeError("victim engine stalled before half-way mark")
        time.sleep(0.005)
    t_drain = time.perf_counter()
    victim.begin_drain()
    # successor takes the new traffic immediately
    succ_req = successor.generate([5, 6, 7], max_new_tokens=1)
    gap_ms = (time.perf_counter() - t_drain) * 1e3
    for r in reqs:
        r.done.wait(timeout=300)
    drain_ms = (time.perf_counter() - t_drain) * 1e3
    victim.stop()
    worker.join(timeout=10)
    dropped = sum(1 for r in reqs
                  if not r.done.is_set() or len(r.output) < gen_tokens)
    assert succ_req.done.is_set()
    return {
        "drain_ms": round(drain_ms, 1),
        "successor_gap_ms": round(gap_ms, 1),
        "dropped_streams": dropped,
    }


def run_coldstart_bench(config: str = "tiny"):
    """``serving_coldstart_*``: the three legs of a scale-up cold start
    (weights, compile, warmup) for three arms —

    - **cold**: peer weight stream into an empty dir + first compile
      against an EMPTY compile cache + first warm generation;
    - **cachehit**: same legs with the compile cache now holding the
      serialized executables (the second replica of a fleet, or the
      first after a restart);
    - **standby**: everything paid ahead of time — the measured total is
      activation + first token on the already-warm engine, the
      ``elastic/standby.py`` fast path.

    The weights leg streams a real published snapshot through
    ``stream_snapshot`` (sha256-verified, same code a joining replica
    runs) with a filesystem-backed fetch standing in for the peer HTTP
    hop, so the measured cost is the full chunk/verify/publish path.
    """
    import shutil
    import tempfile
    from pathlib import Path

    from dstack_tpu.elastic.compile_cache import CompileCache
    from dstack_tpu.elastic.standby import StandbyPool
    from dstack_tpu.elastic.weight_stream import stream_snapshot
    from dstack_tpu.models import checkpoint as ckpt
    from dstack_tpu.serving.engine import InferenceEngine

    cfg = (llama.LlamaConfig.tiny() if config == "tiny"
           else llama.LlamaConfig.llama3_1b())
    root = Path(tempfile.mkdtemp(prefix="coldstart-bench-"))
    try:
        # the "seeder": a published snapshot exactly as a live replica
        # holds it (manifest + checksums + host shard)
        donor = InferenceEngine(cfg, batch_size=1, max_len=128)
        seed_dir = root / "seeder"
        ckpt.write_snapshot(seed_dir,
                            ckpt.snapshot_train_state(donor.params),
                            step=0, process_index=0, num_processes=1)
        src = seed_dir / "step_00000000"

        def local_fetch(url: str):
            name = url.rsplit("/", 1)[1]
            path = src / ("manifest.json" if name == "manifest" else name)
            with open(path, "rb") as f:
                while True:
                    block = f.read(1 << 20)
                    if not block:
                        return
                    yield block

        cache_dir = root / "compile-cache"

        def one_arm(arm: str) -> dict:
            dest = root / f"weights-{arm}"
            t0 = time.perf_counter()
            step = stream_snapshot("http://seeder", dest,
                                   fetch=local_fetch)
            ckpt.read_snapshot(dest, donor.params, step=step, verify=True)
            weights_ms = (time.perf_counter() - t0) * 1e3
            cache = CompileCache(cache_dir)
            engine = InferenceEngine(cfg, batch_size=1, max_len=128,
                                     compile_cache=cache)
            t0 = time.perf_counter()
            engine.warmup()
            first_ms = (time.perf_counter() - t0) * 1e3
            t0 = time.perf_counter()
            engine.warmup()
            warmup_ms = (time.perf_counter() - t0) * 1e3
            compile_ms = max(first_ms - warmup_ms, 0.0)
            return {
                "weights_ms": round(weights_ms, 1),
                "compile_ms": round(compile_ms, 1),
                "warmup_ms": round(warmup_ms, 1),
                "total_ms": round(weights_ms + first_ms, 1),
                "cache": cache.snapshot(),
            }

        out = {}
        for arm in ("cold", "cachehit"):
            m = one_arm(arm)
            for k in ("weights_ms", "compile_ms", "warmup_ms",
                      "total_ms"):
                out[f"serving_coldstart_{arm}_{k}"] = m[k]
            log(f"coldstart {arm}: weights {m['weights_ms']:.0f} ms, "
                f"compile {m['compile_ms']:.0f} ms, warmup "
                f"{m['warmup_ms']:.0f} ms (cache {m['cache']})")

        # standby: weights + compile + warmup all paid BEFORE the spike;
        # the spike-time cost is activation + one already-warm token
        def factory():
            eng = InferenceEngine(cfg, batch_size=1, max_len=128,
                                  compile_cache=CompileCache(cache_dir))
            eng.warmup()
            return eng

        pool = StandbyPool(factory, size=1)
        pool.warm(1)
        t0 = time.perf_counter()
        record = pool.activate()
        record.engine.generate([1, 2, 3], max_new_tokens=1)
        activation_ms = (time.perf_counter() - t0) * 1e3
        out["serving_coldstart_standby_weights_ms"] = 0.0
        out["serving_coldstart_standby_compile_ms"] = 0.0
        out["serving_coldstart_standby_warmup_ms"] = 0.0
        out["serving_coldstart_standby_total_ms"] = round(activation_ms, 1)
        log(f"coldstart standby: activation+first-token "
            f"{activation_ms:.0f} ms "
            f"(vs cold {out['serving_coldstart_cold_total_ms']:.0f} ms)")
        return out
    finally:
        shutil.rmtree(root, ignore_errors=True)


def main():
    device = device_report()
    if device["platform"] != "tpu":
        # a CPU (or any other) run is not a smaller benchmark, it is a
        # different measurement: refuse instead of printing its numbers
        # under the chip's key names
        raise SystemExit(
            f"bench.py measures the TPU; this process runs on {device}. "
            "Unset JAX_PLATFORMS (or set it to tpu) on a machine with a chip.")
    enable_persistent_cache()
    # one fixed configuration (r03-r05's: the largest batch that fit the
    # 16 GB chip); no shrinking — a size that does not fit is a failure
    value, train_telemetry = run_bench(14, 1024)

    extra = {}
    if train_telemetry is not None:
        # measured per-step telemetry (dstack_tpu/telemetry/training.py):
        # the perf trajectory carries measured MFU, not just throughput
        extra["train_step_telemetry"] = train_telemetry
    if os.environ.get("DSTACK_BENCH_TRAIN_ONLY") != "1":
        tok_s_8b, mfu_8b, projected = run_bench_8b()
        extra["llama3_8b_shape_tokens_per_sec_per_chip"] = round(tok_s_8b, 1)
        if mfu_8b is not None:  # a TPU whose peak the table does not hold
            extra["llama3_8b_shape_mfu"] = round(mfu_8b, 4)
            extra["llama3_8b_projected_full_depth_tokens_per_sec_per_chip"] \
                = round(projected, 1)
        extra["serving_tokens_per_sec"] = round(run_serving_bench(), 1)
        extra["serving_tokens_per_sec_int8"] = round(
            run_serving_bench(quantize="int8"), 1)
        extra["serving_tokens_per_sec_int8_32way"] = round(
            run_serving_bench(quantize="int8", concurrency=32), 1)
        ttft, bg_rate = run_ttft_bench()
        extra["serving_ttft_mixed_load_ms"] = round(ttft * 1e3, 1)
        extra["serving_decode_during_prefill_tokens_per_sec"] = \
            round(bg_rate, 1)
        # decode hot-loop arms: dense-paged baseline vs ragged buckets
        # vs quantized KV, plus the TTFT pair (PR 18)
        extra.update(run_decode_bench())
        # routing comparison keys: gateway_routing_<policy>_<metric>
        # (short policy names keep the payload readable)
        short = {"round_robin": "rr", "least_loaded": "p2c",
                 "least_loaded_affinity": "affinity"}
        for policy, m in run_gateway_routing_bench().items():
            p = short.get(policy, policy)
            extra[f"gateway_routing_{p}_p95_wait_ms"] = m["p95_wait_ms"]
            extra[f"gateway_routing_{p}_p95_ttft_ms"] = m["p95_ttft_ms"]
            extra[f"gateway_routing_{p}_cache_hit_rate"] = \
                m["cache_hit_rate"]
        # grey-failure defense keys: one 20x-slow replica out of four —
        # no-breaker baseline vs breaker vs breaker+hedge
        # (gateway/routing_sim.py simulate_degraded drives the real
        # tracker/breaker/hedge-budget logic)
        from dstack_tpu.gateway.routing_sim import (
            degraded_comparison,
            tracing_overhead,
        )

        deg = degraded_comparison()
        extra["gateway_breaker_baseline_p99_ms"] = deg["baseline"]["p99_ms"]
        extra["gateway_breaker_p99_ms"] = deg["breaker"]["p99_ms"]
        extra["gateway_breaker_opened"] = deg["breaker"]["breaker_opened"]
        extra["gateway_breaker_deadline_misses"] = \
            deg["breaker"]["deadline_misses"]
        extra["gateway_hedge_p99_ms"] = deg["breaker_hedge"]["p99_ms"]
        extra["gateway_hedge_max_ms"] = deg["breaker_hedge"]["max_ms"]
        extra["gateway_hedge_issued"] = deg["breaker_hedge"]["hedges_issued"]
        log(f"degraded-replica sim: p99 baseline "
            f"{deg['baseline']['p99_ms']:,.0f} ms -> breaker "
            f"{deg['breaker']['p99_ms']:,.0f} ms -> breaker+hedge "
            f"{deg['breaker_hedge']['p99_ms']:,.0f} ms "
            f"(max {deg['breaker_hedge']['max_ms']:,.0f} ms, "
            f"{deg['breaker_hedge']['hedges_issued']:.0f} hedges)")
        # tracing overhead, sim side: REAL span recording charged into
        # the seeded routing sim's service times — pins the <2% p95
        # TTFT claim with numbers in the payload
        ov = tracing_overhead()
        extra["serving_tracing_overhead_p95_ttft_ms_off"] = \
            ov["p95_ttft_ms_off"]
        extra["serving_tracing_overhead_p95_ttft_ms_on"] = \
            ov["p95_ttft_ms_on"]
        extra["serving_tracing_overhead_p95_ttft_pct"] = \
            ov["p95_ttft_overhead_pct"]
        extra["serving_tracing_overhead_span_us"] = ov["span_us_per_request"]
        log(f"tracing overhead (sim): p95 TTFT "
            f"{ov['p95_ttft_ms_off']:,.1f} -> "
            f"{ov['p95_ttft_ms_on']:,.1f} ms "
            f"({ov['p95_ttft_overhead_pct']:+.3f}%, "
            f"{ov['span_us_per_request']:.1f} us/req)")
        # tracing overhead, engine side: telemetry-on vs telemetry+
        # tracer tok/s on the real decode loop
        tok_tel = run_serving_bench(telemetry="on")
        tok_trace = run_serving_bench(telemetry="trace")
        extra["serving_tracing_overhead_tok_s_off"] = round(tok_tel, 1)
        extra["serving_tracing_overhead_tok_s_on"] = round(tok_trace, 1)
        extra["serving_tracing_overhead_tok_s_pct"] = round(
            (tok_tel - tok_trace) / tok_tel * 100.0, 2)
        # robustness cost, train side: checkpoint cadence overhead +
        # emergency-flush/restore latency (docs/concepts/resilience.md
        # quotes these keys)
        ro = run_resume_overhead_bench()
        extra["train_resume_overhead_step_pct"] = ro["step_overhead_pct"]
        extra["train_resume_overhead_emergency_flush_ms"] = \
            ro["emergency_flush_ms"]
        extra["train_resume_overhead_restore_ms"] = ro["restore_ms"]
        # robustness cost, control-plane side: intent-journal recovery
        # machinery — orphan-sweep latency, crash->restart convergence
        # and the planted-orphan count (docs/concepts/resilience.md
        # "Crash consistency" quotes these keys)
        from dstack_tpu.server.recovery_bench import control_recovery_metrics

        cr = control_recovery_metrics()
        extra["control_recovery_orphan_sweep_ms"] = cr["orphan_sweep_ms"]
        extra["control_recovery_restart_converge_ms"] = \
            cr["restart_converge_ms"]
        extra["control_recovery_orphans_swept"] = cr["orphans_swept"]
        log(f"control recovery: sweep {cr['orphan_sweep_ms']:.1f} ms, "
            f"restart-converge {cr['restart_converge_ms']:.1f} ms, "
            f"{cr['orphans_swept']} orphans swept")
        # scale, control-plane side: N server replicas over one DB
        # under submit/preempt churn — cycle latency, scheduling
        # throughput per replica count, and kill-one-of-two failover
        # convergence (docs/concepts/resilience.md "Running N server
        # replicas" quotes these keys)
        from dstack_tpu.server.scale_bench import control_scale_metrics

        cs = control_scale_metrics()
        extra["control_scale_pipeline_cycle_ms"] = cs["pipeline_cycle_ms"]
        extra["control_scale_runs_per_s"] = cs["runs_per_s"]
        extra["control_scale_converge_ms"] = cs["converge_ms"]
        extra["control_scale_converge_bound_ms"] = cs["converge_bound_ms"]
        for n, m in cs["per_replicas"].items():
            extra[f"control_scale_runs_per_s_{n}r"] = m["runs_per_s"]
            extra[f"control_scale_pipeline_cycle_ms_{n}r"] = \
                m["pipeline_cycle_ms"]
        log(f"control scale: {cs['runs_per_s']:,.0f} runs/s @2r, "
            f"cycle {cs['pipeline_cycle_ms']:.1f} ms, kill-converge "
            f"{cs['converge_ms']:.0f} ms "
            f"(bound {cs['converge_bound_ms']:.0f} ms)")
        # observability cost, control-plane side: one SLO evaluator
        # cycle (burn-rate math over timeseries window queries) at a
        # 10k-series store load, plus the raw->1m->10m rollup fold
        # (docs/concepts/observability.md "SLOs & alerting" quotes
        # these keys)
        from dstack_tpu.server.slo_bench import slo_eval_metrics

        se = slo_eval_metrics()
        extra["slo_eval_cycle_ms"] = se["slo_eval_cycle_ms"]
        extra["slo_eval_series"] = se["slo_eval_series"]
        extra["slo_eval_alerts_checked"] = se["slo_eval_alerts_checked"]
        extra["slo_rollup_ms"] = se["slo_rollup_ms"]
        log(f"slo eval: cycle {se['slo_eval_cycle_ms']:.1f} ms over "
            f"{se['slo_eval_series']:,} series "
            f"({se['slo_eval_alerts_checked']} objectives checked), "
            f"rollup {se['slo_rollup_ms']:.1f} ms")
        # robustness cost, serving side: drain-and-migrate dead time
        # and the zero-drop invariant as a measured number
        dm = run_drain_migrate_bench()
        extra["serving_drain_migrate_drain_ms"] = dm["drain_ms"]
        extra["serving_drain_migrate_successor_gap_ms"] = \
            dm["successor_gap_ms"]
        extra["serving_drain_migrate_dropped_streams"] = \
            dm["dropped_streams"]
        # elasticity cost: cold start vs compile-cache hit vs
        # pre-warmed standby activation, decomposed into the
        # weights/compile/warmup legs (docs/concepts/elasticity.md
        # quotes these keys)
        extra.update(run_coldstart_bench())
        # digital-twin replay: golden-workload percentiles + wall
        # cost, and the defended-vs-baseline grey-slow ordering on
        # replayed load (docs/concepts/simulation.md quotes these)
        extra.update(run_twin_bench())
        extra["provision_to_first_step_sec"] = round(
            run_provision_bench(), 2)

    out = {
        "metric": METRIC,
        "value": round(value, 1),
        "unit": "tokens/sec/chip",
        "vs_baseline": _vs_baseline(value),
        "device": device,
    }
    if extra:
        out["extra"] = extra
    print(json.dumps(out))


if __name__ == "__main__":
    main()
