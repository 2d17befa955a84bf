"""Model FLOPs of what the window produced / window / bf16 peak, for the
looped decoder: a request's first token carries its prompt
(``ouro_looped_counts.prefill_flops``), every later token a decode step at
its context, the layers and the attention counted once a pass, the head
once."""

from benchmarks.harness.metrics import burst_shares
from benchmarks.references import ouro_looped, ouro_looped_counts as counts


def read(run):
    if run.peaks is None:
        return None
    passes, _ = ouro_looped._loop()
    total = 0.0
    for r in run.all_requests:
        n = len(r.prompt)
        for i, m, share in burst_shares(r.stamps, run.t0, run.t1):
            if share:
                total += share * sum(
                    counts.prefill_flops(run.sizes, passes, n) if j == 0
                    else counts.decode_token_flops(run.sizes, passes, n + j)
                    for j in range(i, i + m))
    if not total:
        return None
    peak = run.chips * run.peaks["bf16_flops_per_s"]
    return 100.0 * total / (run.t1 - run.t0) / peak
