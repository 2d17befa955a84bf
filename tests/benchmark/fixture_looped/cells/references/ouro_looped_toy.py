"""The benchmark's plain reference of the looped decoder
(``benchmarks/references/ouro_looped.py``) at this fixture's toy size: the
same file, handed the toy configuration (3 passes, exit threshold 0.6) in
place of the one it reads by default."""

import json
from pathlib import Path

from benchmarks.references import ouro_looped as plain

CONFIG = json.loads((Path(__file__).resolve().parents[1] / "configs"
                     / "tiny-looped.json").read_text())


init_weights = plain.init_weights


def logits(weights, sizes, tokens, first, count, lower=False):
    return plain.logits(weights, sizes, tokens, first, count, lower=lower,
                        config=CONFIG)
