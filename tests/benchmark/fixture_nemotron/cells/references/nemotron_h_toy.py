"""The benchmark's plain reference of the Nemotron-H decoder
(``benchmarks/references/nemotron_h.py``) at this fixture's toy size: the
same file, handed the toy configuration in place of the one it reads by
default."""

import json
from pathlib import Path

from benchmarks.references import nemotron_h as plain

CONFIG = json.loads((Path(__file__).resolve().parents[1] / "configs"
                     / "tiny-nemotron.json").read_text())


def init_weights(sizes, seed):
    return plain.init_weights(sizes, seed, config=CONFIG)


def logits(weights, sizes, tokens, first, count, lower=False):
    return plain.logits(weights, sizes, tokens, first, count, lower=lower,
                        config=CONFIG)
