"""The benchmark's plain reference of the LFM2-MoE decoder
(``benchmarks/references/lfm2_moe.py``) at this fixture's toy size: the same
file, handed the toy configuration in place of the one it reads by
default."""

import json
from pathlib import Path

from benchmarks.references import lfm2_moe as plain

CONFIG = json.loads((Path(__file__).resolve().parents[1] / "configs"
                     / "tiny-lfm2.json").read_text())


def init_weights(sizes, seed):
    return plain.init_weights(sizes, seed, config=CONFIG)


def logits(weights, sizes, tokens, first, count, lower=False):
    return plain.logits(weights, sizes, tokens, first, count, lower=lower,
                        config=CONFIG)
