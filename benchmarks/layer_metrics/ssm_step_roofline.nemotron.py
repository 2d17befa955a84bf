"""Least time the chip could take for the state-space decode update of a
decode step (``nemotron_h_counts.ssm_step_bytes``: each live slot's state of
each Mamba layer read once and written once, 2 x 2 MiB, and the step's
operands beside it, at the memory bandwidth) over the time the update took.
Percent.

The update is found by what it yields.  A device event is named by its HLO
line without the metadata (a chip run of PR 39: no ``named_scope`` and no
inner jit's name reaches the trace, though ``ops/ssd.py`` jits the update on
its own as ``ssm_step`` and the compiled HLO says so), so the update's events
are those inside the decode-window programs (``XLA Modules`` named
``jit_decode_w<steps>_...``) whose RESULT holds a whole layer's states,
``f32[slots, heads, head_dim, state]``: today one ``multiply_reduce_fusion``
a layer-step (``tests/compute/test_tpu_compile.py`` holds that each such
operation is ``jit(ssm_step)``'s); an update that took three passes over the
state would show three and read a third.  Their share of those programs'
device time x the step's time (``hybrid_decode_trace``) is the update's time
a step, every call counted, live slot or not; the live slot-layer-steps are
the program's counter ``ssm_slot_layer_steps_total`` over its decode steps.
``None`` where the trace holds no such event or the program has no such
counter (the parent commit, another model, the CPU)."""

from benchmarks.harness.metrics import counter_delta
from benchmarks.harness.trace_reduce import CONTAINERS, MODULES_LINE, OPS_LINE
from benchmarks.layer_metrics.hybrid_decode_trace import (
    PROGRAM,
    decode_step_ms,
)
from benchmarks.references import nemotron_h, nemotron_h_counts as counts

SLOT_LAYER_STEPS = "dstack_serving_ssm_slot_layer_steps_total"
STEPS = "dstack_serving_decode_steps_total"


def yields(name: str, shape: str) -> bool:
    """Whether the operation ``name`` (an HLO line: ``%x = <result>
    opcode(operands), ...``) has ``shape`` in its result.  A loop holds the
    operations of its body, which are on the line too: it yields nothing of
    its own."""
    if name.startswith(CONTAINERS):
        return False
    result = name.partition(" = ")[2]
    if result.startswith("("):          # a tuple: up to its closing bracket
        return shape in result[:result.find(") ") + 1]
    return shape in result.split(" ", 1)[0]


def update_share(trace, states: str):
    """Device time of the events that yield ``states`` inside the
    decode-window programs over those programs' device time, both inside
    the traced span."""
    if trace is None or not trace["devices"]:
        return None
    inside_ns = programs_ns = 0
    for dev in trace["devices"]:
        ops = dev["lines"].get(OPS_LINE, [])
        if not ops:
            continue
        first = min(s for _, s, _ in ops)
        last = max(s + d for _, s, d in ops)
        windows = [(max(s, first), min(s + d, last))
                   for name, s, d in dev["lines"].get(MODULES_LINE, [])
                   if PROGRAM.match(name)]
        programs_ns += sum(hi - lo for lo, hi in windows if hi > lo)
        inside_ns += sum(
            d for name, s, d in ops if yields(name, states)
            and any(lo <= s < hi for lo, hi in windows))
    if not inside_ns or not programs_ns:
        return None
    return inside_ns / programs_ns


def read(run):
    shape = nemotron_h._shape(run.sizes)
    share = update_share(run.trace, "f32[%d,%d,%d,%d]" % (
        run.slots, shape["m_heads"], shape["m_hd"], shape["state"]))
    step_ms = decode_step_ms(run.trace)
    steps = counter_delta(run, STEPS)
    slot_layer_steps = counter_delta(run, SLOT_LAYER_STEPS)
    if (run.peaks is None or not share or not step_ms or steps <= 0
            or slot_layer_steps <= 0):
        return None
    least_s = (counts.ssm_step_bytes(shape, slot_layer_steps / steps)
               / run.peaks["hbm_bytes_per_s"])
    return 100.0 * least_s / (share * step_ms / 1e3)
