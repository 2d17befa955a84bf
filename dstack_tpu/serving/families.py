"""Which provider serves a configuration: the one place that maps a config's
class to the state and the programs the engine schedules, and to the model
module that computes it.  Three providers answer the seam:

* ``LlamaConfig`` and its subclasses (``MoEConfig``, ``OuroConfig``):
  ``serving/dense.py``, K and V rows of every layer (of every pass), dense
  or paged, plain or quantized, on one chip or a mesh;
* ``LingHybridConfig``: ``serving/hybrid.py``, a paged pool of MLA's latent
  rows beside the KDA layers' recurrent state;
* ``Lfm2MoeConfig`` and ``NemotronHConfig``: ``serving/mixed.py``
  ``MixedPrograms``, built with the config's model module, a paged K and V
  pool over the attention layers beside a fixed state a slot over the rest
  (LFM2's convolution tails; Nemotron-H's Mamba-2 states and tails).

``MixedPrograms`` names no model.  The model module answers one contract:
``init_rec(cfg, slots)``, the state zeroed; ``read_slots(rec, slots
[G], fresh [G])`` and ``write_slots(rec, slots, mine)``, a vector of slots'
part of the state, read (zeros where ``fresh``) and written (a slot past the
last dropped), so that where the slot axis lies is the model's alone;
``sequence_forward(params, cfg, tokens [G, T], lengths [G], starts [G],
rec_or_None, attend) -> (logits [G, V], mine)``, ``prompts_forward(params,
cfg, tokens, lengths, attend)`` (from position 0 and zeros) and
``decode_step(params, cfg, x, positions, live, rec, attend) -> (x, rec,
load)``, with ``output_logits`` and ``LOAD_FIELDS`` (a model without
positions ignores ``starts`` and ``positions``); ``window_counts(cfg, load,
live, steps)``, the vector a decode window returns, the expert load first
and what the model counts beside it after (Nemotron-H: the state-space
slot-layer-steps; LFM2: nothing), and ``scan_chunks(cfg, bucket)``, the
blocks a prompt program runs through a chunked scan (LFM2: 0).  A new family of this
shape is a model file and one line of ``PROVIDERS``; a family of another
shape is a model file, a programs file and one line.

A provider answers the calls ``tests/compute/test_family_seam.py`` lists
(``DensePrograms`` documents each).  One is optional: ``prefill_batch_fn
(bucket)`` in place of ``prefill_fn(bucket)``, a group of whole prompts in
one program, ``fn(params, tokens [P, bucket], lengths [P], pool, rec, block
ids [P, bucket / block_size], slots [P]) -> (logits [P, V], pool, rec)``,
where an unused entry (length 0, slot ``batch_size``, block ids past the
pool) writes nothing and routes no pair to an expert.  The engine asks
whether the provider has it, and nothing else: with it, an admission
pass's whole prompts go out a group a bucket
(``InferenceEngine.PREFILL_BATCH_TOKENS``), which ``MixedPrograms`` offers
(its families' every prompt routes pairs to every held expert); without
it, one program a prompt.  ``MixedPrograms`` offers
``chunk_batch_fn(cbucket)`` beside ``chunk_fn`` too, a group of chunks of
different slots in one program, ``fn(params, tokens [G, cbucket],
chunk_lens [G], prefix_lens [G], pool, rec, (table rows [G, blocks a slot],
slots [G])) -> (logits [G, V], pool, rec)``, where an unused entry (length
0, slot ``batch_size``, a table row past the pool's blocks) writes nothing:
with it the chunk queue sends its chunks in rounds, a group a program;
without it, one program a chunk.

What the families' programs share of the paged pool, of a group of whole
prompts or of chunks and of the decode window is
``serving/paged_window.py``; the sorted
grouped expert product is ``models/experts.py``.
"""

from __future__ import annotations

from functools import partial

from dstack_tpu.models import lfm2, nemotron_h
from dstack_tpu.models.ling_hybrid import LingHybridConfig
from dstack_tpu.models.llama import LlamaConfig
from dstack_tpu.serving.dense import DensePrograms
from dstack_tpu.serving.hybrid import HybridPrograms
from dstack_tpu.serving.mixed import MixedPrograms

#: config class -> provider; a subclass is served by its nearest base's
PROVIDERS = {
    LlamaConfig: DensePrograms,
    LingHybridConfig: HybridPrograms,
    lfm2.Lfm2MoeConfig: partial(MixedPrograms, model=lfm2),
    nemotron_h.NemotronHConfig: partial(MixedPrograms, model=nemotron_h),
}


def programs_for(cfg, **built_with):
    """The provider of ``cfg``'s family, built with what the engine was
    (``DensePrograms.__init__`` names the keywords); it refuses the options
    its model is not served with."""
    for cls in type(cfg).__mro__:
        if cls in PROVIDERS:
            return PROVIDERS[cls](cfg, **built_with)
    raise TypeError(f"no provider serves {type(cfg).__name__}: "
                    "serving/families.py PROVIDERS lists the families")
