"""The programs of the families the benchmark already held, pinned by their
lowered text.

``serving/paged_window.py`` holds what the providers share (the decode
window's cache-half/window-half attention, the page arithmetic of a chunk
and of the end-of-window scatter).  Lifting it out of ``serving/dense.py``
and ``serving/hybrid.py`` must leave their programs as they were: each
program here is lowered at a toy size through the engine's own builders and
its StableHLO text (no locations) hashed; ``lowered_programs.json`` holds
the hashes, written from the tree BEFORE the lift (PR 35: commit 0781848).
One entry is the tree's AFTER it: the Ling decode window's text differs from
the parent's in the PLACE of one operation (the end-of-window scatter's
``pos % block_size`` is now computed before the flat index's first product,
where ``serving/dense.py`` always had it; same operations, same operands:
the compiled CPU program is the same multiset of instructions).  PR 36
rewrote the three Ling entries: the expert-load vector the programs return
has a sixth field (``models/experts.py`` ``LOAD_FIELDS``); the grouped
product itself is the parent's on the CPU backend (``ragged_dot``).
PR 39 added ``nemotron-paged`` (the fourth provider's three programs, so that
the lift of the providers' shared scaffolding, ROADMAP D13, has all four
families under it) and left the 18 hashes before it as they were: the
experts' form and the shared router (``models/experts.py``) lower the Ling
programs to the same text.
PR 40 rewrote ``nemotron-paged``'s prefill alone: the family's whole prompts
go out a group a bucket (``prefill_batch_fn``), and the entry is that
program, a group of two.
Where the two families send their chunks a group of different slots a
program (``chunk_batch_fn``), ``nemotron-paged``'s chunk entry is that
program, a group of two, and ``lfm2-paged`` holds the third provider's
three programs the same way; the other 18 hashes stayed as they were.
The Ling decode window's entry was rewritten when its KDA mixer came to
take the whole stacked state (``models/ling_hybrid.py`` ``kda_token``, so
that the chip's step kernel can update a layer in place): on the CPU the
same operations, with two ``where`` helpers numbered two lower because the
layer is now sliced inside the mixer.
When one provider came to serve LFM2 and Nemotron-H (``serving/mixed.py``,
ROADMAP D13), ``nemotron-paged``'s decode entry alone was rewritten: the
window carries each slot's length through the step scan (LFM2's form, one
more ``[slots]`` int32 carry and a ``where`` a step) where it added the
window's steps once after the scan, and the state-space slot-layer-steps are
summed after the end-of-window scatter, not before it (the model's
``window_counts``); the positions the step now receives are read by nothing
and lower to no operation.  The other 23 hashes stayed as they were.
A change that is meant to alter one of these programs rewrites the file:

    JAX_PLATFORMS=cpu python tests/compute/test_lowered_programs.py --write

and says so in ``CHANGES.md``.  (A Pallas kernel lowered for a chip carries
file paths; here it is interpreted, so its body is plain operations.)
"""

import hashlib
import json
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

ROOT = Path(__file__).resolve().parents[2]
PINNED = Path(__file__).with_name("lowered_programs.json")
PAGED = dict(paged=True, kv_block_size=16, total_kv_blocks=20)
ENGINE = dict(batch_size=2, max_len=64, prefill_chunk=16)


def _llama():
    from dstack_tpu.models.llama import LlamaConfig

    return LlamaConfig.tiny()


def _ouro():
    from dstack_tpu.models.ouro import OuroConfig

    return OuroConfig.tiny()


def _nemotron():
    from dstack_tpu.models.nemotron_h import NemotronHConfig

    return NemotronHConfig.tiny()


def _lfm2():
    from dstack_tpu.models.lfm2 import Lfm2MoeConfig

    return Lfm2MoeConfig.tiny()


def _ling():
    from dstack_tpu.models.ling_hybrid import LingHybridConfig

    return LingHybridConfig.tiny()


#: name -> (config, engine options, force the block-table kernel)
CASES = {
    "llama-paged": (_llama, PAGED, False),
    "llama-paged-kernel": (_llama, PAGED, True),
    "llama-paged-int8kv": (_llama, dict(PAGED, kv_quantize="int8"), False),
    "llama-rows": (_llama, {}, False),
    "ouro-paged": (_ouro, PAGED, False),
    "ling-paged": (_ling, PAGED, False),
    "nemotron-paged": (_nemotron, PAGED, False),
    "lfm2-paged": (_lfm2, PAGED, False),
}


def _texts(case: str) -> dict:
    """Program name -> lowered text, for the prefill, the chunk and the
    decode window of one case."""
    from dstack_tpu.serving.engine import InferenceEngine

    make, options, kernel = CASES[case]
    patch = pytest.MonkeyPatch()
    patch.setenv("DSTACK_TPU_PAGED_ATTN_KERNEL", "1" if kernel else "0")
    try:
        engine = InferenceEngine(make(), rng_seed=0, **ENGINE, **options)
    finally:
        patch.undo()
    b, i32, f32 = engine.batch_size, jnp.int32, jnp.float32
    paged = engine.paged
    state = tuple(engine._state)
    bids = jnp.arange(1, 3, dtype=i32) if paged else None
    row = jnp.arange(1, 5, dtype=i32) if paged else None
    target = engine._programs.slot_target
    nbk = 2 if paged else None
    tables = (jnp.ones((b, nbk), i32) if paged
              else jnp.zeros((b, 1), i32))
    if engine._batched_prefill:
        p = engine._prompts_per_program(32)
        prefill = (engine.params, jnp.zeros((p, 32), i32),
                   jnp.full((p,), 20, i32), *state, jnp.ones((p, 2), i32),
                   jnp.arange(p, dtype=i32), jnp.zeros((p, 3), f32),
                   (jax.random.PRNGKey(0),) * p, jnp.zeros((b,), i32))
    else:
        prefill = (engine.params, jnp.zeros((32,), i32), i32(20), *state,
                   target(1, bids))
    if engine._batched_chunks:
        g = engine._prompts_per_program(16)
        chunk = (engine.params, jnp.zeros((g, 16), i32),
                 jnp.full((g,), 9, i32), jnp.full((g,), 16, i32), *state,
                 (jnp.ones((g, engine._blocks_per_slot), i32),
                  jnp.arange(g, dtype=i32)), jnp.zeros((g, 3), f32),
                 (jax.random.PRNGKey(0),) * g, jnp.arange(g, dtype=i32),
                 jnp.zeros((b,), i32))
    else:
        chunk = (engine.params, jnp.zeros((16,), i32), i32(9), i32(16),
                 *state, target(i32(1), row))
    programs = {
        "prefill": (engine._prefill_program(32), prefill),
        "chunk": (engine._chunk_program(16), chunk),
        "decode": (engine._decode_window_program(8, False, nbk), (
            engine.params, jnp.zeros((b,), i32), jnp.full((b,), 5, i32),
            jnp.ones((b,), jnp.bool_), *state, jnp.zeros((b,), f32),
            jnp.ones((b,), f32), jnp.zeros((b,), i32), tables,
            jax.random.PRNGKey(0))),
    }
    return {name: fn.lower(*args).as_text()
            for name, (fn, args) in programs.items()}


def _hashes(case: str) -> dict:
    return {name: hashlib.sha256(text.encode()).hexdigest()
            for name, text in _texts(case).items()}


@pytest.mark.parametrize("case", sorted(CASES))
def test_program_lowers_to_the_pinned_text(case):
    pinned = json.loads(PINNED.read_text())
    assert _hashes(case) == pinned[case]


if __name__ == "__main__":
    if sys.argv[1:] != ["--write"]:
        sys.exit(__doc__)
    sys.path.insert(0, str(Path.cwd()))
    PINNED.write_text(json.dumps(
        {case: _hashes(case) for case in sorted(CASES)}, indent=1) + "\n")
    print(PINNED.read_text())
