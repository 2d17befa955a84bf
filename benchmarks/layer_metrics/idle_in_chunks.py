"""Device-idle time inside the engine's ``engine.chunk`` spans / traced
span: the part of ``device_idle_share`` in which the device stood still while
the host built and enqueued the chunks of long prompts, or activated a prompt
whose last chunk had gone out (the ``engine.first_token`` inside that span
included: there the host waits for the chunk's program, and the device is
idle only once it has run dry).  A term of the sum in ``idle_split.py``."""

from benchmarks.harness.program_spans import idle_share_inside


def read(run):
    if run.trace is None:
        return None
    share = idle_share_inside(run.trace, "engine.chunk")
    return None if share is None else 100.0 * share
