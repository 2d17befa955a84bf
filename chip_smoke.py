#!/usr/bin/env python3
"""Chip smoke: the compute main path, once, on the real TPU.

    python chip_smoke.py                # one chip: serve-dense, serve-paged, train
    python chip_smoke.py --four-chips   # four chips: TP parity + the 8B TP server

The quickest proof that the system still starts on the chip.  It serves
Llama-3.2-1B (published widths and depth, random weights from ``--seed``)
through ``python -m dstack_tpu.serving.server`` and trains it through
``models.train.run_train_loop``, and checks what comes out.  Times are
printed as information; nothing here is a benchmark.

One process per chip: this parent never imports jax.  Every phase is one
child process with ``JAX_PLATFORMS=tpu`` forced into its environment (JAX
then fails where there is no chip instead of computing on the CPU),
started after the previous child was waited for.  Any failed check or
timeout kills the child, prints the phase, the child's last output and a
failing last line, and exits 1.

Output: one JSON object per line; the last line is
``{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}``.
"""

from __future__ import annotations

import argparse
import collections
import json
import math
import os
import random
import signal
import socket
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request
from concurrent.futures import ThreadPoolExecutor

HERE = os.path.dirname(os.path.abspath(__file__))
#: the driver allows 1200 s; every wait below is cut to what is left of this
BUDGET_S = 1150.0
NEW_TOKENS = 64
TRAIN_STEPS = 4
TRAIN_BATCH, TRAIN_SEQ = 8, 1024
#: four-chip parity: bf16 keeps 8 significant bits, and tensor parallelism
#: only reorders sums — last-position logits must agree to a few ulps of
#: the largest logit
LOGIT_REL_TOL = 2.0 ** -5
_T0 = time.monotonic()


class SmokeFailure(Exception):
    pass


def emit(obj: dict) -> None:
    print(json.dumps(obj), flush=True)


def left(limit: float) -> float:
    """``limit`` seconds, cut to what remains of the whole run's budget."""
    remaining = BUDGET_S - (time.monotonic() - _T0)
    if remaining <= 0:
        raise SmokeFailure("the run's time budget is spent")
    return min(limit, remaining)


def require(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def require_tpu(device: dict) -> None:
    require(device.get("platform") == "tpu",
            f"phase ran on {device!r}, not on a TPU")


# -- child processes ----------------------------------------------------------


class Child:
    """One phase's process: own session (so its whole group can be killed),
    combined output drained by a thread into a bounded tail."""

    def __init__(self, argv: list) -> None:
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "tpu"  # whatever was inherited
        env["PYTHONUNBUFFERED"] = "1"
        self.tail: collections.deque = collections.deque(maxlen=60)
        self.reports: list = []  # JSON lines the child printed
        self.proc = subprocess.Popen(
            [sys.executable] + argv, cwd=HERE, env=env,
            stdin=subprocess.DEVNULL, stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT, text=True, errors="replace",
            start_new_session=True)
        self._reader = threading.Thread(target=self._drain, daemon=True)
        self._reader.start()

    def _drain(self) -> None:
        for line in self.proc.stdout:
            line = line.rstrip("\n")
            self.tail.append(line)
            if line.startswith('{"phase"'):
                try:
                    self.reports.append(json.loads(line))
                except ValueError:
                    pass  # stays in the tail; the phase's count check fails

    def wait(self, limit: float) -> int:
        try:
            rc = self.proc.wait(timeout=left(limit))
        except subprocess.TimeoutExpired:
            raise SmokeFailure(f"child still running after {limit:.0f}s")
        self._reader.join(timeout=10)
        return rc

    def stop(self) -> None:
        """Terminate the child's whole process group and reap it."""
        if self.proc.poll() is None:
            for sig, grace in ((signal.SIGTERM, 20), (signal.SIGKILL, 20)):
                try:
                    os.killpg(self.proc.pid, sig)
                except ProcessLookupError:
                    break
                try:
                    self.proc.wait(timeout=grace)
                    break
                except subprocess.TimeoutExpired:
                    continue
        self._reader.join(timeout=10)


def run_phase(phase: str, argv: list, body) -> dict:
    """Start the phase's child, run ``body(child)``, always stop the child.
    A failure is reported with the child's last lines and ends the run."""
    child = Child(argv)
    try:
        return body(child)
    except Exception as e:  # noqa: BLE001 — every failure ends the run
        child.stop()
        error = str(e) if isinstance(e, SmokeFailure) \
            else f"{type(e).__name__}: {e}"
        print(f"---- phase {phase} failed; its child's last output ----")
        for line in child.tail:
            print(line)
        print("---- end ----", flush=True)
        emit({"ok": False, "phase": phase, "error": error})
        sys.exit(1)
    finally:
        child.stop()


# -- HTTP against the serving server ------------------------------------------


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def http(url: str, payload=None, timeout: float = 30.0):
    """(status, body bytes); a refused connection is status 0."""
    data = None if payload is None else json.dumps(payload).encode()
    req = urllib.request.Request(
        url, data=data, headers={"Content-Type": "application/json"})
    try:
        with urllib.request.urlopen(req, timeout=timeout) as r:
            return r.status, r.read()
    except urllib.error.HTTPError as e:
        return e.code, e.read()
    except (urllib.error.URLError, ConnectionError, socket.timeout) as e:
        return 0, str(e).encode()


def wait_ready(child: Child, base: str, limit: float) -> dict:
    """Poll /health until ``status: ok``; returns its body."""
    deadline = time.monotonic() + left(limit)
    last = b""
    while time.monotonic() < deadline:
        rc = child.proc.poll()
        require(rc is None, f"server exited with code {rc} before ready")
        status, last = http(base + "/health", timeout=5)
        if status == 200:
            body = json.loads(last)
            if body.get("status") == "ok":
                return body
        elif status == 503 and b'"error"' in last:
            raise SmokeFailure(f"server reports a failed start: {last[:400]!r}")
        time.sleep(1.0)
    raise SmokeFailure(f"/health not ok after {limit:.0f}s; last: {last[:200]!r}")


def text_of(rng: random.Random, n_tokens: int) -> str:
    """ASCII text that the byte tokenizer turns into ``n_tokens`` ids
    (one per byte, plus BOS)."""
    return "".join(rng.choice("abcdefghij klmnopqrst uvwxyz")
                   for _ in range(n_tokens - 1))


def complete(base: str, prompt: str, limit: float) -> dict:
    t0 = time.monotonic()
    status, body = http(base + "/v1/completions",
                        {"prompt": prompt, "max_tokens": NEW_TOKENS,
                         "temperature": 0.0}, timeout=left(limit))
    require(status == 200, f"/v1/completions answered {status}: {body[:300]!r}")
    out = json.loads(body)
    finish = out["choices"][0]["finish_reason"]
    n = out["usage"]["completion_tokens"]
    require(finish in ("length", "stop"), f"finish_reason {finish!r}")
    require(n == NEW_TOKENS if finish == "length" else 1 <= n <= NEW_TOKENS,
            f"{n} completion tokens for {NEW_TOKENS} asked ({finish})")
    return {"tokens": n, "finish": finish,
            "seconds": round(time.monotonic() - t0, 3)}


def complete_stream(base: str, prompt: str, limit: float) -> dict:
    t0 = time.monotonic()
    status, body = http(base + "/v1/completions",
                        {"prompt": prompt, "max_tokens": NEW_TOKENS,
                         "temperature": 0.0, "stream": True},
                        timeout=left(limit))
    require(status == 200, f"stream answered {status}: {body[:300]!r}")
    events = [line[len("data: "):] for line in body.decode().splitlines()
              if line.startswith("data: ")]
    require(len(events) >= 2 and events[-1] == "[DONE]",
            f"stream did not end in [DONE]: {events[-2:]!r}")
    finish = json.loads(events[-2])["choices"][0]["finish_reason"]
    require(finish in ("length", "stop"), f"stream finish_reason {finish!r}")
    return {"finish": finish, "seconds": round(time.monotonic() - t0, 3)}


def check_metrics(base: str, answered: list, streamed: int) -> dict:
    """/metrics parses strictly, counts every request as finished with
    length/stop, none as an error, and — the SSE stream carries text, not
    counts — accounts for the streamed requests' tokens too."""
    from dstack_tpu.server.telemetry.exposition import parse

    status, body = http(base + "/metrics", timeout=left(30))
    require(status == 200, f"/metrics answered {status}")
    samples = parse(body.decode(), strict=True)

    def total(name: str, **labels) -> float:
        return sum(s.value for s in samples if s.name == name
                   and all(s.labels.get(k) == v for k, v in labels.items()))

    finished = (total("dstack_serving_requests_total", outcome="length")
                + total("dstack_serving_requests_total", outcome="stop"))
    everything = total("dstack_serving_requests_total")
    engine_errors = total("dstack_serving_preemptions_total",
                          reason="engine_error")
    n_requests = len(answered) + streamed
    require(finished == n_requests and everything == n_requests,
            f"{finished} finished of {everything} counted, {n_requests} sent")
    require(engine_errors == 0, f"{engine_errors} engine_error preemptions")
    # every request's first token comes from its prefill, the rest are
    # decode tokens: what the non-streamed requests do not explain is the
    # streamed requests' share
    decode = total("dstack_serving_decode_tokens_total")
    stream_tokens = decode + n_requests - sum(a["tokens"] for a in answered)
    require(streamed == 0 or 1 <= stream_tokens <= streamed * NEW_TOKENS,
            f"streamed requests produced {stream_tokens} tokens")
    return {"finished": int(finished), "engine_errors": int(engine_errors),
            "stream_tokens": int(stream_tokens)}


def serve_phase(phase: str, server_args: list, seed: int, *,
                shared_prefix: bool = False, concurrent: int = 8,
                stream: bool = True, memory: bool = False) -> dict:
    port = free_port()
    base = f"http://127.0.0.1:{port}"
    argv = ["-m", "dstack_tpu.serving.server", "--port", str(port)] \
        + server_args

    def body(child: Child) -> dict:
        t0 = time.monotonic()
        device = wait_ready(child, base, 420.0)["device"]
        ready_s = round(time.monotonic() - t0, 3)
        emit({"phase": phase, "platform": device["platform"],
              "kind": device["kind"], "count": device["count"]})
        require_tpu(device)
        rng = random.Random(seed)
        prompts = [text_of(rng, rng.randint(100, 600))
                   for _ in range(concurrent)]
        t1 = time.monotonic()
        with ThreadPoolExecutor(concurrent) as pool:
            futures = [pool.submit(complete, base, p, 600.0)
                       for p in prompts]
            answered = [f.result() for f in futures]
        batch_s = round(time.monotonic() - t1, 3)
        info = {"ready_s": ready_s, "first_batch_s": batch_s,
                "request_s": [a["seconds"] for a in answered]}
        if shared_prefix:
            # the second request arrives after the first one's prefill
            # published its blocks: its leading 256 tokens must hit
            prefix = text_of(rng, 256)
            for _ in range(2):
                answered.append(complete(
                    base, prefix + text_of(rng, 101), 300.0))
        streamed = 0
        if stream:
            s = complete_stream(base, text_of(rng, 200), 300.0)
            info["stream_s"] = s["seconds"]
            streamed = 1
        result = {"phase": phase, "requests": len(answered) + streamed,
                  "finish": sorted({a["finish"] for a in answered}),
                  "completion_tokens": sum(a["tokens"] for a in answered)}
        result.update(check_metrics(base, answered, streamed))
        if shared_prefix or memory:
            status, raw = http(base + "/stats", timeout=left(30))
            require(status == 200, f"/stats answered {status}")
            stats = json.loads(raw)
        if shared_prefix:
            hits = stats["prefix_cache"]["hit_blocks"]
            require(hits > 0, "no prefix-cache hit for a shared 256-token "
                              f"prefix: {stats['prefix_cache']}")
            result["prefix_hit_blocks"] = hits
        if memory:
            in_use = stats["device_memory"]
            require(len(in_use) == device["count"] and all(in_use),
                    f"device memory not reported per device: {in_use}")
            # tensor parallelism shards the weights: no chip may hold more
            # than half of what all hold together
            require(max(in_use) <= 0.5 * sum(in_use),
                    f"weights are not spread over the chips: {in_use}")
            result["bytes_in_use"] = in_use
        result["info"] = info
        emit(result)
        return device

    return run_phase(phase, argv, body)


# -- phases that compute in a child of this file ------------------------------


def child_phase(phase: str, seed: int, check) -> dict:
    argv = [os.path.abspath(__file__), "--child", phase, "--seed", str(seed)]

    def body(child: Child) -> dict:
        rc = child.wait(600.0)
        require(rc == 0, f"child exited with code {rc}")
        require(len(child.reports) == 2,
                f"expected a device line and a result line, got "
                f"{len(child.reports)}")
        device, result = child.reports
        emit(device)
        require_tpu(device)
        check(result)
        emit(result)
        return {"platform": device["platform"], "kind": device["kind"],
                "count": device["count"]}

    return run_phase(phase, argv, body)


def check_train(result: dict) -> None:
    losses = result["losses"]
    require(len(losses) == TRAIN_STEPS, f"{len(losses)} losses")
    require(all(math.isfinite(x) for x in losses), f"losses {losses}")
    require(losses[-1] < losses[0], f"loss did not fall: {losses}")
    require(result["tpu_custom_call"] is True,
            "no tpu_custom_call in the lowered train step: the flash "
            "kernel is not in the program")


def check_tp_parity(result: dict) -> None:
    require(result["logit_rel_diff"] <= LOGIT_REL_TOL,
            f"one-chip and tensor-parallel logits differ by "
            f"{result['logit_rel_diff']:.4g} of the largest logit "
            f"(tolerance {LOGIT_REL_TOL:.4g})")
    require(all(n >= 1 for n in result["agree_tokens"]),
            f"first greedy token differs: {result['agree_tokens']}")


def _child_device(phase: str) -> None:
    from dstack_tpu.utils.jax_runtime import (
        device_report,
        enable_persistent_cache,
    )

    enable_persistent_cache()
    emit({"phase": phase, **device_report()})


def child_train(seed: int) -> None:
    """Four steps of the 1B trainer through run_train_loop, with the step
    options bench.py measures (remat, unrolled unstacked layers)."""
    _child_device("train")
    import jax
    import jax.numpy as jnp

    from dstack_tpu.models import train
    from dstack_tpu.models.llama import LlamaConfig

    cfg = LlamaConfig.llama3_1b()
    opt = train.default_optimizer()
    step_kw = dict(remat=True, scan_layers=False, unstacked=True,
                   with_grad_norm=False)
    tokens = jax.random.randint(
        jax.random.PRNGKey(seed + 1), (TRAIN_BATCH, TRAIN_SEQ + 1), 0,
        cfg.vocab_size)
    lowered = train.make_train_step(cfg, opt, **step_kw).lower(
        train.state_template(cfg, opt, unstacked=True),
        {"tokens": jax.ShapeDtypeStruct(tokens.shape, jnp.int32)})
    t0 = time.monotonic()
    result = train.run_train_loop(
        cfg, opt, lambda step: {"tokens": tokens}, steps=TRAIN_STEPS,
        rng=jax.random.PRNGKey(seed), **step_kw)
    emit({"phase": "train", "losses": result.losses,
          "tpu_custom_call": "tpu_custom_call" in lowered.as_text(),
          "info": {"loop_s": round(time.monotonic() - t0, 3)}})


def child_tp_parity(seed: int) -> None:
    """The same weights and greedy prompts through a one-chip engine and a
    --tensor-parallel 4 engine (the mesh serving/server.py builds), compared
    like tests/compute/test_serving.py compares them on the CPU — plus the
    last-position logits, which say HOW close bf16 on the chip is."""
    _child_device("tp-parity")
    import jax
    import numpy as np

    from dstack_tpu.models.llama import LlamaConfig
    from dstack_tpu.parallel.mesh import MeshSpec, build_mesh
    from dstack_tpu.serving.engine import InferenceEngine

    cfg = LlamaConfig.llama3_1b()
    rng = random.Random(seed)
    prompts = [[rng.randrange(1, cfg.vocab_size)
                for _ in range(rng.randint(100, 600))] for _ in range(4)]

    def run(engine):
        logits = [engine.prefill_export(p, 32)["logits"] for p in prompts]
        return logits, [engine.generate(p, max_new_tokens=32).output
                        for p in prompts]

    one = InferenceEngine(cfg, batch_size=4, max_len=1024, rng_seed=seed)
    logits_1, tokens_1 = run(one)
    mesh = build_mesh(MeshSpec(tensor=4), jax.devices()[:4])
    tp = InferenceEngine(cfg, params=one.params, batch_size=4, max_len=1024,
                         mesh=mesh)
    logits_4, tokens_4 = run(tp)
    agree = []
    for a, b in zip(tokens_1, tokens_4):
        n = next((i for i, (x, y) in enumerate(zip(a, b)) if x != y), len(a))
        agree.append(n)
    rel = max(float(np.max(np.abs(a - b)) / np.max(np.abs(a)))
              for a, b in zip(logits_1, logits_4))
    emit({"phase": "tp-parity", "agree_tokens": agree, "of": 32,
          "logit_rel_diff": rel, "logit_rel_tol": LOGIT_REL_TOL})


CHILDREN = {"train": child_train, "tp-parity": child_tp_parity}


# -- the run --------------------------------------------------------------------


def main() -> None:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--four-chips", action="store_true",
                        help="run only the tensor-parallel path and its "
                             "one-chip comparison (needs four chips)")
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--child", choices=sorted(CHILDREN),
                        help=argparse.SUPPRESS)
    args = parser.parse_args()
    if args.child:
        CHILDREN[args.child](args.seed)
        return

    one_b = ["--config", "llama3-1b", "--batch-size", "8", "--max-len", "1024"]
    if args.four_chips:
        devices = [
            child_phase("tp-parity", args.seed, check_tp_parity),
            # examples/serving-tensor-parallel's own line, random weights in
            # place of the checkpoint
            serve_phase("serve-8b-tp4", [
                "--config", "llama3-8b", "--tensor-parallel", "4",
                "--quantize", "int8", "--kv-quantize", "int8", "--paged",
                "--batch-size", "16", "--max-len", "4096"], args.seed,
                concurrent=4, stream=False, memory=True),
        ]
    else:
        devices = [
            serve_phase("serve-dense", one_b, args.seed),
            serve_phase("serve-paged", one_b + [
                "--paged", "--prefix-cache", "--kv-quantize", "int8"],
                args.seed, shared_prefix=True),
            child_phase("train", args.seed, check_train),
        ]
    device = devices[0]
    if any(d != device for d in devices):
        emit({"ok": False, "error": f"phases disagree on the device: {devices}"})
        sys.exit(1)
    emit({"ok": True, "device": device})


if __name__ == "__main__":
    main()
