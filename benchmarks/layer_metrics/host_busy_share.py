"""The share of the WHOLE window in which the engine's thread was neither
waiting for the device (``engine.pull``, ``engine.first_token``) nor for
work (``engine.wait_for_work``): 100 x (1 - those phases' self seconds /
(t1 - t0)), from ``engine_phase_seconds_total{phase}`` at both ends.  An
upper bound on the idle time that is the host's fault, free of the trace's
phase and edges.  A phase still open when the counters are read is added
when it closes, so an edge can move the reading by one window's pull over
the window's length.  ``None`` for a program without the counters."""

from benchmarks.harness.metrics import counter_delta

PHASE_SECONDS = "dstack_serving_engine_phase_seconds_total{phase=%s}"
WAITING = ("pull", "first_token", "wait_for_work")


def read(run):
    names = [PHASE_SECONDS % phase for phase in WAITING]
    if any(name not in run.counters["t1"] for name in names):
        return None
    waited = sum(counter_delta(run, name) for name in names)
    return 100.0 * (1.0 - waited / (run.t1 - run.t0))
