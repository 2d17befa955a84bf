"""Train-step telemetry: opt-in wall-clock/MFU wrapper for make_train_step.

The bare train step is dispatch-only (callers pipeline steps and block
once at the end — that is where the bench throughput comes from), so the
wrapper is OPT-IN: it blocks on the loss every step to get a true
per-step wall time, which serializes the dispatch pipeline.  Use it in
monitoring-grade training loops and calibration runs, not in the timed
region of a throughput bench.

Metric names (prefix ``dstack_train_``, scraped/republished like the
serving set):

- ``step_seconds``      histogram — per-step wall time (compile steps
  excluded: a recompile's trace+compile time would poison every
  percentile; it is counted in ``recompiles_total`` instead)
- ``steps_total`` / ``tokens_total`` / ``recompiles_total`` counters
- ``tokens_per_sec`` / ``mfu`` gauges — from the last measured step;
  MFU = 6 * params * tokens / wall / peak (the ROOFLINE.md convention),
  the peak being that of the device the step runs on
  (:data:`PEAK_BF16_FLOPS`); on a device the table does not know the
  gauge stays 0 rather than borrowing another chip's peak
"""

from __future__ import annotations

import logging
import time
from typing import Optional

from dstack_tpu.telemetry.recorder import MetricsRecorder

logger = logging.getLogger(__name__)

#: v5e per-chip bf16 matmul peak (Google Cloud documentation, "TPU v5e")
V5E_PEAK_BF16_FLOPS = 197e12

#: per-chip bf16 matmul peaks keyed by ``jax.Device.device_kind`` — the one
#: table bench.py's MFU column and this module's gauge divide by.  A device
#: that is not here has NO peak: MFU is then not computed.
PEAK_BF16_FLOPS = {"TPU v5 lite": V5E_PEAK_BF16_FLOPS}

#: step-time buckets: 10 ms .. 120 s (covers tiny CPU test shapes through
#: full-depth multi-chip steps)
STEP_BUCKETS = (0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0,
                30.0, 60.0, 120.0)

PREFIX = "dstack_train_"


class TrainTelemetry:
    """Recorder + the ``wrap()`` factory that instruments a jitted step."""

    def __init__(self, num_params: Optional[int] = None,
                 peak_flops: Optional[float] = None,
                 log_every: int = 50) -> None:
        self.num_params = num_params
        #: None until :meth:`wrap` looks the running device up in
        #: :data:`PEAK_BF16_FLOPS` (and still None if it is not there)
        self.peak_flops = peak_flops
        self.log_every = log_every
        self.recorder = MetricsRecorder()
        r = self.recorder
        self.step_seconds = r.histogram(PREFIX + "step_seconds",
                                        STEP_BUCKETS)
        self.steps_total = r.counter(PREFIX + "steps_total")
        self.tokens_total = r.counter(PREFIX + "tokens_total")
        self.recompiles_total = r.counter(PREFIX + "recompiles_total")
        self.tokens_per_sec = r.gauge(PREFIX + "tokens_per_sec")
        self.mfu = r.gauge(PREFIX + "mfu")
        self._cache_size = None

    def wrap(self, step_fn, cfg=None, n_devices: int = 1):
        """Wrap a (jitted) ``(state, batch) -> (state, metrics)`` step.

        ``cfg`` supplies ``num_params()`` when the telemetry was built
        without an explicit parameter count; without either — or on a
        device with no known peak — MFU stays 0 and the timing metrics
        still record.  ``n_devices`` divides the model FLOPs for per-chip
        MFU under a mesh.
        """
        import jax

        if self.peak_flops is None:
            self.peak_flops = PEAK_BF16_FLOPS.get(
                jax.devices()[0].device_kind)
        if self.num_params is None and cfg is not None:
            try:
                self.num_params = int(cfg.num_params())
            except Exception:  # config families without the helper
                self.num_params = None
        # baseline the jit cache at wrap time: a step compiled (warmed)
        # BEFORE wrapping must not read as a recompile on its first
        # instrumented call
        cache_size_fn = getattr(step_fn, "_cache_size", None)
        if callable(cache_size_fn):
            try:
                self._cache_size = cache_size_fn()
            except Exception:
                pass

        def instrumented(state, batch):
            t0 = time.perf_counter()
            state, metrics = step_fn(state, batch)
            jax.block_until_ready(metrics["loss"])
            wall = time.perf_counter() - t0
            # recompile detection: the jit cache grew during this call
            # (covers the first compile AND shape-change retraces)
            recompiled = False
            cache_size_fn = getattr(step_fn, "_cache_size", None)
            if callable(cache_size_fn):
                try:
                    size = cache_size_fn()
                except Exception:
                    size = None
                if size is not None:
                    if self._cache_size is not None and \
                            size > self._cache_size:
                        recompiled = True
                    self._cache_size = size
            self.record_step(wall, _batch_tokens(batch), n_devices,
                             recompiled=recompiled)
            return state, metrics

        return instrumented

    def record_step(self, wall: float, tokens: int, n_devices: int = 1,
                    recompiled: bool = False) -> None:
        """Record one measured step (also the entry point for callers
        that time steps themselves instead of using ``wrap``)."""
        self.steps_total.inc()
        self.tokens_total.inc(tokens)
        if recompiled:
            self.recompiles_total.inc()
            return  # compile time must not enter the step-time histogram
        self.step_seconds.observe(wall)
        if wall > 0 and tokens:
            per_chip = tokens / wall / max(n_devices, 1)
            self.tokens_per_sec.set(tokens / wall)
            if self.num_params and self.peak_flops:
                self.mfu.set(6.0 * self.num_params * per_chip
                             / self.peak_flops)
        n = int(self.steps_total.value)
        if self.log_every and n % self.log_every == 0:
            from dstack_tpu.telemetry.recorder import (
                percentiles_from_snapshot,
            )

            p = percentiles_from_snapshot(self.step_seconds.snapshot())
            logger.info(
                "train step %d: %.3fs (p50 %.3fs) %.0f tok/s MFU %.1f%% "
                "recompiles %d", n, wall, p["p50"],
                self.tokens_per_sec.value, self.mfu.value * 100,
                int(self.recompiles_total.value))

    def prometheus_samples(self):
        return self.recorder.samples()

    def stats(self) -> dict:
        return self.recorder.summary()


def _batch_tokens(batch) -> int:
    """Loss-bearing tokens in a train batch: [B, S+1] inputs predict S
    targets each."""
    try:
        b, s1 = batch["tokens"].shape
        return int(b * (s1 - 1))
    except Exception:
        return 0
