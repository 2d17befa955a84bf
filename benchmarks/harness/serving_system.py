"""The system under test, and the only module of the benchmark that touches
the program: ``InferenceEngine`` built as ``serving/server.py`` builds it
(paged KV, the tuned prefill chunk, ``EngineTelemetry``, the kernel choice
left on ``auto``, no environment knob), its loop on a thread as
``ServingApp.start_engine`` starts it, requests through ``engine.submit``.
The HTTP front is not in the path (its byte tokenizer drops ids >= 256, so
served tokens could not be compared through it)."""

import gc
import threading
import time

import numpy as np


def persistent_compile_cache() -> str:
    """The program's own cache set-up (``JAX_COMPILATION_CACHE_DIR`` if set,
    else ``<checkout>/.jax_cache``), told to keep every program however
    quick its compile, so that a run's set-up is the same work every time.
    Process-wide: entry points call it, ``run_cell`` never does."""
    import jax
    from dstack_tpu.utils.jax_runtime import enable_persistent_cache

    where = enable_persistent_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return where


class ServingSystem:
    def __init__(self, program_cfg, weights, engine_args: dict, chips: int):
        import jax
        from dstack_tpu.serving.engine import InferenceEngine
        from dstack_tpu.telemetry.serving import EngineTelemetry

        args = dict(engine_args)
        if args.get("prefill_chunk") == "tuned":
            args["prefill_chunk"] = InferenceEngine.TUNED_PREFILL_CHUNK
        tp = int(args.pop("tensor_parallel", 1))
        mesh = None
        if tp > 1:
            from jax.sharding import Mesh

            mesh = Mesh(np.array(jax.devices()[:chips]).reshape(tp),
                        ("tensor",))
        self.telemetry = EngineTelemetry()
        self.engine = InferenceEngine(program_cfg, params=weights, mesh=mesh,
                                      telemetry=self.telemetry, **args)
        self.slots = self.engine.batch_size
        self._thread = threading.Thread(target=self.engine.run_forever,
                                        daemon=True, name="engine")

    def start(self) -> None:
        self._thread.start()

    @property
    def alive(self) -> bool:
        return self._thread.is_alive()

    def submit(self, rec, on_complete) -> None:
        """Hand one planned request to the engine.  Token times are taken
        here, in the callback the HTTP layer would stream from."""
        from dstack_tpu.serving.engine import Request

        def on_token(token: int) -> None:
            rec.stamps.append(time.perf_counter())
            rec.served.append(int(token))
            if len(rec.served) == rec.max_new:
                on_complete(rec)

        rec.handle = self.engine.submit(Request(
            tokens=rec.prompt.tolist(), max_new_tokens=rec.max_new,
            temperature=0.0, eos_id=None, on_token=on_token))

    def ended_badly(self, rec) -> bool:
        """The engine is done with the request and it did not end
        ``length`` with all its tokens."""
        h = rec.handle
        return h is not None and h.done.is_set() and (
            h.finish_reason != "length" or len(rec.served) != rec.max_new)

    def wait(self, rec, timeout: float) -> bool:
        return rec.handle.done.wait(timeout)

    def cancel(self, rec) -> None:
        if rec.handle is not None and not rec.handle.done.is_set():
            rec.handle.cancel()

    def queue_wait_s(self, rec):
        """Submit -> slot admission, on the engine's own (wall) clock."""
        h = rec.handle
        if h is None or h.admitted_at is None:
            return None
        return max(h.admitted_at - h.submitted_at, 0.0)

    def counters(self) -> dict:
        """The telemetry's exposition as ``{name{labels}: value}``: the same
        numbers ``/metrics`` serves."""
        out = {}
        for s in self.telemetry.prometheus_samples():
            if s.name.endswith("_bucket"):
                continue
            labels = ",".join(f"{k}={v}" for k, v in sorted(s.labels.items()))
            out[f"{s.name}{{{labels}}}" if labels else s.name] = s.value
        return out

    def stop_and_free(self) -> None:
        """Stop the loop, wait for its thread and drop the engine's device
        state (KV pool, slot state); the weights stay with the caller."""
        self.engine.stop()
        self._thread.join(timeout=60)
        if self._thread.is_alive():
            raise RuntimeError("the engine thread did not stop")
        self.engine = None
        gc.collect()
