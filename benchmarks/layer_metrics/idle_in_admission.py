"""Device-idle time inside the engine's admission / traced span: the part of
``device_idle_share`` in which the device stood still because the host was
admitting requests.  Admission is the ``engine.admit`` spans together with
the ``engine.prefill`` spans inside them: an admission pass lasts a second
or more and the profiler drops a span that crosses either edge of the
traced window, while its per-request children survive."""

from benchmarks.harness.program_spans import idle_share_inside


def read(run):
    if run.trace is None:
        return None
    share = idle_share_inside(run.trace, "engine.admit", "engine.prefill")
    return None if share is None else 100.0 * share
