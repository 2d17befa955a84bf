"""Device-idle time inside ``engine.pull``, ``engine.emit`` and
``engine.dispatch_window`` spans / traced span: from a decode window's end
to the next one's enqueue, the part of ``device_idle_share`` the window chain
costs where no window was dispatched ahead.  A term of the sum in
``idle_split.py``."""

from benchmarks.harness.program_spans import idle_share_inside


def read(run):
    if run.trace is None:
        return None
    share = idle_share_inside(run.trace, "engine.pull", "engine.emit",
                              "engine.dispatch_window")
    return None if share is None else 100.0 * share
