"""The comparison that decides ``correct`` for a served model.

After the window, a sample of the requests it finished (drawn from the
seed, the longest always in it) is run once through the configuration's
plain reference: the prompt with the served tokens behind it, one causal
forward.  At every served position the reference scores the whole
vocabulary; what is compared is how far the SERVED token's score lies below
the reference's best one there: the widest such gap over the sample, and
the mean gap, each held to a limit of the cell's.  A served token that is
the reference's own first choice reads 0; bfloat16 rounding in the program
flips near-ties and reads a small gap; a wrong token reads the distance
between a random logit and the largest of a vocabulary, some logit standard
deviations.  The widest gap is an extreme value: it grows only in
proportion to the rounding noise and swings with the near-ties a sample
happens to hold.  The mean grows with the noise's square (more flips, each
wider) and separates a lower precision far more cleanly.

``control`` puts the reference computed in the next lower precision in the
program's place: the token IT would put first at each position, read
against the same float32 scores.
"""

import numpy as np


def pick_sample(finished, count: int, seed: int):
    """``count`` of the finished requests: the longest (prompt and output
    together), the rest drawn from the seed."""
    if not finished:
        return []
    order = sorted(finished, key=lambda r: (-(len(r.prompt) + len(r.served)),
                                            r.index))
    chosen = [order[0]]
    rest = order[1:]
    rng = np.random.default_rng([int(seed), 0x5A])
    for i in rng.permutation(len(rest))[:max(count - 1, 0)]:
        chosen.append(rest[int(i)])
    return chosen


def served_gap(reference, weights, sizes, sample, control: bool = False):
    """Widest and mean gap over the sample, with where the widest was found.
    Returns ``{"gap", "mean_gap", "tokens", "mismatches", "request",
    "position", "logit_std"}``; ``control=True`` reads the lower-precision
    reference's own first choices in place of the served tokens."""
    widest = {"gap": 0.0, "request": None, "position": None}
    tokens = mismatches = 0
    total = 0.0
    stds = []
    for rec in sample:
        served = np.asarray(rec.served, np.int64)
        if len(served) == 0:
            continue
        seq = np.concatenate([np.asarray(rec.prompt, np.int64), served[:-1]])
        first = len(rec.prompt) - 1
        ref = reference.logits(weights, sizes, seq, first, len(served))
        judged = served
        if control:
            low = reference.logits(weights, sizes, seq, first, len(served),
                                   lower=True)
            judged = low.argmax(-1)
        best = ref.max(-1)
        gaps = best - ref[np.arange(len(judged)), judged]
        tokens += len(judged)
        total += float(gaps.sum())
        mismatches += int((judged != ref.argmax(-1)).sum())
        stds.append(float(ref.std()))
        j = int(gaps.argmax())
        if float(gaps[j]) > widest["gap"]:
            widest = {"gap": float(gaps[j]), "request": rec.index,
                      "position": j}
    widest.update(mean_gap=total / tokens if tokens else 0.0,
                  tokens=tokens, mismatches=mismatches,
                  logit_std=float(np.mean(stds)) if stds else None)
    return widest
