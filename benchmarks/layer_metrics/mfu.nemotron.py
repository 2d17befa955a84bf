"""Model FLOPs of what the chip computed for the tokens produced in the
window / window / bf16 peak: the share of the whole step.  A request's
first token carries its prompt (``nemotron_h_counts.prefill_flops``: the
state-space recurrence as it is defined, the routed experts at the
even-routing expectation, the prefill programs return no count); every
later token a decode step at its context, and the decode windows' routed
experts by the program's own count of pairs."""

from benchmarks.harness.metrics import burst_shares, counter_delta
from benchmarks.references import nemotron_h, nemotron_h_counts as counts

HELD = "dstack_serving_moe_pairs_total{where=held}"


def read(run):
    if run.peaks is None:
        return None
    shape = nemotron_h._shape(run.sizes)
    total = counter_delta(run, HELD) * counts.pair_flops(shape)
    for r in run.all_requests:
        n = len(r.prompt)
        for i, m, share in burst_shares(r.stamps, run.t0, run.t1):
            if share:
                total += share * sum(
                    counts.prefill_flops(shape, n) if j == 0
                    else counts.decode_token_flops(shape, n + j)
                    for j in range(i, i + m))
    if not total:
        return None
    peak = run.chips * run.peaks["bf16_flops_per_s"]
    return 100.0 * total / (run.t1 - run.t0) / peak
