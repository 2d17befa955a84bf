"""One run of one cell: set-up, the measured window, the comparison with the
plain reference, the result line.  Nothing here names a cell, a
configuration or a metric: they are files, found by the names in
``BENCHMARK.json``."""

import importlib.util
import json
import re
import shutil
import sys
import threading
import time
from pathlib import Path
from types import SimpleNamespace

HARNESS_DIR = Path(__file__).resolve().parents[1]
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_MISS_EVENT = "/jax/compilation_cache/cache_misses"
EDGE_LIMIT_S = 15.0     # longest wait after the window for its last bursts


class NoChip(SystemExit):
    """No accelerator, too few chips, or a chip with no published peaks."""


class Files:
    """Finds a cell's files: under the directories ``BENCHMARK.json`` lists
    in ``paths``, then under the harness's own directory."""

    def __init__(self, root, spec):
        self.root = Path(root)
        self.bases = [self.root / p for p in spec["paths"]] + [HARNESS_DIR]

    def find(self, rel: str) -> Path:
        for base in self.bases:
            if (base / rel).is_file():
                return base / rel
        raise FileNotFoundError(f"{rel} under none of "
                                f"{[str(b) for b in self.bases]}")

    def json(self, rel: str) -> dict:
        return json.loads(self.find(rel).read_text())

    def module(self, rel: str):
        """Load a Python file of the benchmark by its path (metric names
        hold dots, so these files are not importable by name)."""
        path = self.find(rel)
        spec = importlib.util.spec_from_file_location(
            "benchmarks._" + re.sub(r"\W", "_", rel), path)
        module = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(module)
        return module

    def reader(self, kind: str, metric: str):
        """The reader of a metric: ``<kind>/<metric>.py``, or the file of the
        name with its last dotted parts taken off, so that ``mfu.chat`` and
        ``mfu.batch`` are both read by ``mfu.py`` unless a cell brings a
        file of its own."""
        parts = metric.split(".")
        for k in range(len(parts), 0, -1):
            try:
                return self.module(f"{kind}/{'.'.join(parts[:k])}.py")
            except FileNotFoundError:
                continue
        raise FileNotFoundError(f"no reader for {metric!r} under {kind}/")


def _entry(entries, name, what):
    for e in entries:
        if e["name"] == name:
            return e
    raise KeyError(f"BENCHMARK.json has no {what} named {name!r}")


def _metrics_of(spec_list, cell_name):
    """Metrics of a list that apply to the cell: those that list it under
    ``workloads``, and those with no such key."""
    return [m for m in spec_list
            if cell_name in m.get("workloads", [cell_name])]


def passes(check: dict) -> bool:
    """A compared number holds its limit (``limit``: at most; ``at_least``)."""
    if "limit" in check:
        return check["value"] <= check["limit"]
    return check["value"] >= check["at_least"]


def _info(out, **fields):
    print(json.dumps({"info": fields}), file=out, flush=True)


def _check_devices(chips: int, allow_cpu: bool):
    import jax

    from benchmarks.harness.peaks import PEAKS, peaks_for

    devices = jax.devices()
    platform, kind = devices[0].platform, devices[0].device_kind
    if allow_cpu:
        return devices, PEAKS.get(kind)
    if platform != "tpu":
        raise NoChip(f"no accelerator: JAX reports platform {platform!r}")
    if len(devices) < chips:
        raise NoChip(f"the cell asks for {chips} chips, JAX has "
                     f"{len(devices)}")
    try:
        return devices, peaks_for(kind)
    except KeyError as e:
        raise NoChip(str(e)) from None


def _memory_peak(devices) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(peaks))


def _trace_window(trace_dir: Path, at: float, seconds: float, done: dict):
    """Profile ``seconds`` of the window from perf_counter time ``at``, on a
    thread of its own so that the load goes on meanwhile."""
    import jax

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    time.sleep(max(at - time.perf_counter(), 0))
    done["start"] = time.perf_counter()
    jax.profiler.start_trace(str(trace_dir), profiler_options=options)
    time.sleep(seconds)
    done["stop"] = time.perf_counter()
    jax.profiler.stop_trace()
    done["written"] = time.perf_counter()


def _warm_up(system, requests, vocab: int, seed: int) -> None:
    """Drive the cell's warm-up requests, ``[prompt_len, new_tokens]`` each,
    one at a time: the list in the workload file is chosen so that every
    program the cell's lengths can reach is built (or loaded from the
    cache) here, and none inside the window."""
    import numpy as np

    from benchmarks.harness.loadgen import Rec

    rng = np.random.default_rng([int(seed), 0x3A])
    for i, (plen, new) in enumerate(requests):
        rec = Rec(index=-1 - i, max_new=int(new), prompt=rng.integers(
            0, vocab, size=int(plen), dtype=np.int32))
        system.submit(rec, lambda r: None)
        if not system.wait(rec, 600) or system.ended_badly(rec):
            raise RuntimeError(
                f"warm-up request {i} ({plen}, {new}) did not end well: "
                f"{rec.handle.finish_reason!r}, {len(rec.served)} tokens")


def run_cell(root, workload: str, seed: int, seconds: float, trace: bool,
             allow_cpu: bool = False, t_process: float = None,
             out=sys.stdout, err=sys.stderr, control: bool = False) -> dict:
    """Run one cell and print its result as the last line of ``out``.
    ``allow_cpu`` is for the CPU rehearsal in the tests and is passed in
    code only: no flag and no environment variable sets it.  ``control``
    puts the reference computed in the next lower precision in the program's
    place for the comparison: its tokens on the same sample are what
    ``checks`` and ``correct`` judge, and ``correct`` has to come out false
    (``benchmarks/tools/limits.py`` and the tests; the benchmark's own
    command never does)."""
    t_process = time.perf_counter() if t_process is None else t_process
    root = Path(root)
    spec = json.loads((root / "BENCHMARK.json").read_text())
    cell = _entry(spec["workloads"], workload, "workload")
    config_entry = _entry(spec["configs"], cell["config"], "config")
    files = Files(root, spec)

    from benchmarks.harness import loadgen, reference as compare
    from benchmarks.harness.metrics import SAME_HANDOVER_S, tokens_in_window
    from benchmarks.harness.sizes import load_config, program_config, sizes_of

    config = load_config(root / config_entry["file"])
    sizes = sizes_of(config)
    load = files.json(f"workloads/{workload}.json")
    traffic = files.json(f"traffic/{cell['traffic']}.json")
    chips = int(cell["chips"])

    import jax
    from jax import monitoring

    devices, peaks = _check_devices(chips, allow_cpu)
    builds = []           # perf_counter time of every program built or loaded
    misses = []

    def on_duration(event, duration, **_):
        if event == COMPILE_EVENT:
            builds.append(time.perf_counter())

    def on_event(event, **_):
        if event == CACHE_MISS_EVENT:
            misses.append(time.perf_counter())

    monitoring.register_event_duration_secs_listener(on_duration)
    monitoring.register_event_listener(on_event)
    system, recs = None, []
    try:
        # ---- set-up: weights, the system, every shape the traffic reaches
        ref = files.module(f"references/{config['reference']}.py")
        weights = ref.init_weights(sizes, seed)
        jax.block_until_ready(weights)
        t_weights = time.perf_counter()
        from benchmarks.harness.serving_system import ServingSystem

        system = ServingSystem(program_config(config), weights,
                               load["engine"], chips)
        system.start()
        _warm_up(system, load.get("warmup", {}).get("requests", []),
                 sizes.vocab, seed)
        t_warm = time.perf_counter()

        settle = float(load.get("settle_s", 0.0))
        recs = loadgen.plan(traffic, load, sizes.vocab, seed,
                            settle + seconds)
        t_gen = time.perf_counter()
        t0, t1 = t_gen + settle, t_gen + settle + seconds
        tracing = {}
        tracer = None
        trace_dir = root / ".bench_work" / f"trace-{workload}"
        if trace:
            shutil.rmtree(trace_dir, ignore_errors=True)
            trace_dir.mkdir(parents=True)
            span = min(float(load.get("trace_s", 3.0)), seconds / 2)
            tracer = threading.Thread(
                target=_trace_window, name="tracer",
                args=(trace_dir, t0 + (seconds - span) / 2, span, tracing))
            tracer.start()

        counters = {}

        def snapshot_at(t, key):
            time.sleep(max(t - time.perf_counter(), 0))
            counters[key] = system.counters()

        snaps = [threading.Thread(target=snapshot_at, args=at)
                 for at in ((t0, "t0"), (t1, "t1"))]
        for snap in snaps:
            snap.start()
        # a burst produced before t1 is handed over after it: the load stays
        # on and stamps are taken until every request that has had a token
        # has had a whole burst after t1 (or has ended), so that the rate
        # pro-rates both edges alike
        edge = t1 + EDGE_LIMIT_S

        def burst_due(r, now):
            if not r.stamps or r.complete or system.ended_badly(r):
                return False
            return r.stamps[-1] < t1 or now - r.stamps[-1] < SAME_HANDOVER_S

        def hold(sent):
            now = time.perf_counter()
            return (system.alive and now < edge
                    and any(burst_due(r, now) for r in sent))

        # ---- the window: the generator runs from the end of warm-up, the
        # window opens ``settle`` later on a queue that is already steady
        driven = loadgen.drive(recs, traffic, load, system.submit, t_gen, t1,
                               system.ended_badly, hold)
        t_edge = time.perf_counter()
        for snap in snaps:
            snap.join()
        sent = driven["submitted"]
        # the window's requests are those that FINISHED in it; one that the
        # engine ended without all its tokens, at any time, has failed
        in_window = [r for r in sent
                     if r.complete and t0 <= r.stamps[-1] < t1]
        bad = [r for r in sent if system.ended_badly(r)]
        for r in sent:
            system.cancel(r)
        if tracer is not None:
            tracer.join()
        alive = system.alive
        memory_peak = _memory_peak(devices[:chips])
        failed = len(bad)
        attempted = len({r.index for r in in_window + bad})
        queue_waits = {r.index: system.queue_wait_s(r) for r in in_window}
        done = [r for r in in_window if not system.ended_badly(r)]
        slots = system.slots
        system.stop_and_free()
        system = None
        t_freed = time.perf_counter()

        run = SimpleNamespace(
            workload=workload, sizes=sizes, peaks=peaks, chips=chips,
            seconds=seconds, t0=t0, t1=t1, setup_s=t0 - t_process,
            requests=in_window, all_requests=sent, queue_waits=queue_waits,
            counters=counters, slots=slots, load=load, traffic=traffic,
            lateness_s=driven["lateness_s"], trace=None)
        device = {"platform": devices[0].platform,
                  "kind": devices[0].device_kind,
                  "count": len(devices), "memory_peak_bytes": memory_peak}
        breakdown = None
        if trace:
            from benchmarks.harness import trace_reduce

            run.trace = trace_reduce.load(trace_dir, chips)
            run.trace_span = (tracing["start"], tracing["stop"])
            if run.trace["devices"] or not allow_cpu:
                busy = trace_reduce.busy_and_window(run.trace)
                device.update(busy_s=busy["busy_s"],
                              window_s=busy["window_s"])
            breakdown = trace_reduce.breakdown(run.trace)
            shutil.rmtree(trace_dir, ignore_errors=True)

        kind, listed = (("layer_metrics", spec["per_layer"]) if trace
                        else ("end_to_end", spec["end_to_end"]))
        metrics = {}
        for m in _metrics_of(listed, workload):
            value = files.reader(kind, m["name"]).read(run)
            if value is not None:
                metrics[m["name"]] = {"value": float(value),
                                      "unit": m["unit"]}

        in_win_builds = sum(1 for t in builds if t0 <= t < t1)

        def backlog(t):
            """Requests sent by ``t`` and not yet complete at ``t``."""
            return sum(1 for r in sent if r.submitted <= t and not (
                r.complete and r.stamps[-1] <= t))
        late = sorted(driven["lateness_s"])
        sixth = (t1 - t0) / 6
        _info(out, workload=workload, seed=seed, seconds=seconds,
              trace=bool(trace), setup_parts_s={
                  "imports_and_files": t_weights - t_process,
                  "system_and_warmup": t_warm - t_weights,
                  "settle": t0 - t_warm},
              programs_built=len(builds), cache_misses=len(misses),
              programs_built_in_window=in_win_builds,
              requests_sent=len(sent), requests_in_window=attempted,
              backlog={"window_start": backlog(t0), "window_end": backlog(t1)},
              edge_wait_s=t_edge - t1,
              tokens_produced_by_sixth=[
                  tokens_in_window((r.stamps for r in sent), t0 + k * sixth,
                                   t0 + (k + 1) * sixth) for k in range(6)],
              generator_lateness_ms={
                  "max": 1e3 * late[-1] if late else 0.0,
                  "p50": 1e3 * late[len(late) // 2] if late else 0.0},
              memory_peak_bytes=memory_peak)

        # ---- the comparison: after the window, the peak read, the
        # engine's state freed
        limits = load["correct"]
        sample = compare.pick_sample(done, int(limits["requests"]), seed)
        program = compare.served_gap(ref, weights, sizes, sample)
        lower = (compare.served_gap(ref, weights, sizes, sample, control=True)
                 if control else None)
        judged = lower or program
        t_checked = time.perf_counter()
        # a cell compares the numbers its workload file gives a limit for
        checks = {name: {"value": judged[key],
                         "limit": float(limits[name + "_limit"])}
                  for name, key in (("served_gap", "gap"),
                                    ("served_gap_mean", "mean_gap"))
                  if name + "_limit" in limits}
        checks.update({
            "requests_failed": {"value": failed, "limit": 0},
            "requests_finished_in_window": {"value": len(in_window),
                                            "at_least": 1},
            "programs_built_in_window": {"value": in_win_builds, "limit": 0},
            "tokens_compared": {"value": judged["tokens"], "at_least": 1},
            "engine_thread_alive": {"value": int(alive), "at_least": 1},
        })
        correct = all(passes(c) for c in checks.values())
        _info(out, comparison={
            "requests": [r.index for r in sample], **program,
            "reference_s": t_checked - t_freed, "control": lower})
        result = {"correct": bool(correct), "attempted": attempted,
                  "failed": failed, "metrics": metrics, "device": device}
        if breakdown is not None:
            result["breakdown"] = breakdown
        result["checks"] = checks
        print(json.dumps(result), file=out, flush=True)
        for name, c in checks.items():
            print(f"check {name}: {json.dumps(c)}", file=err)
        print(f"correct: {bool(correct)}", file=err, flush=True)
        return result
    finally:
        monitoring.unregister_event_duration_listener(on_duration)
        monitoring.unregister_event_listener(on_event)
        if system is not None:
            for r in recs:
                system.cancel(r)
            system.stop_and_free()
