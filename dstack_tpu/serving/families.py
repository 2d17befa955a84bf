"""Which provider serves a configuration: the one place that maps a config's
class to the state and the programs the engine schedules.  A new family is
a model file, a programs file and one line of ``PROVIDERS``.

* ``LlamaConfig`` and its subclasses (``MoEConfig``, ``OuroConfig``):
  ``serving/dense.py``, K and V rows of every layer (of every pass), dense
  or paged, plain or quantized, on one chip or a mesh;
* ``LingHybridConfig``: ``serving/hybrid.py``, a paged pool of MLA's latent
  rows beside the KDA layers' recurrent state;
* ``Lfm2MoeConfig``: ``serving/lfm2.py``, a paged K and V pool over the
  attention layers beside the convolution layers' tails;
* ``NemotronHConfig``: ``serving/nemotron_h.py``, a paged K and V pool over
  the attention layers beside the Mamba-2 layers' state-space states and
  convolution tails.

A provider answers the calls ``tests/compute/test_family_seam.py`` lists
(``DensePrograms`` documents each).  One is optional: ``prefill_batch_fn
(bucket)`` in place of ``prefill_fn(bucket)``, a group of whole prompts in
one program, ``fn(params, tokens [P, bucket], lengths [P], pool, rec, block
ids [P, bucket / block_size], slots [P]) -> (logits [P, V], pool, rec)``,
where an unused entry (length 0, slot ``batch_size``, block ids past the
pool) writes nothing and routes no pair to an expert.  The engine asks
whether the provider has it, and nothing else: with it, an admission
pass's whole prompts go out a group a bucket
(``InferenceEngine.PREFILL_BATCH_TOKENS``), which the families whose every
prompt routes pairs to every held expert offer (``serving/lfm2.py``,
``serving/nemotron_h.py``); without it, one program a prompt.  The same
families offer ``chunk_batch_fn(cbucket)`` beside ``chunk_fn``, a group of
chunks of different slots in one program, ``fn(params, tokens [G,
cbucket], chunk_lens [G], prefix_lens [G], pool, rec, (table rows [G,
blocks a slot], slots [G])) -> (logits [G, V], pool, rec)``, where an unused
entry (length 0, slot ``batch_size``, a table row past the pool's blocks)
writes nothing: with it the chunk queue sends its chunks in rounds, a group
a program; without it, one program a chunk.

What the families' programs share of the paged pool, of a group of whole
prompts or of chunks and of the decode window is
``serving/paged_window.py``; the sorted
grouped expert product is ``models/experts.py``.
"""

from __future__ import annotations

from dstack_tpu.models.lfm2 import Lfm2MoeConfig
from dstack_tpu.models.ling_hybrid import LingHybridConfig
from dstack_tpu.models.llama import LlamaConfig
from dstack_tpu.models.nemotron_h import NemotronHConfig
from dstack_tpu.serving.dense import DensePrograms
from dstack_tpu.serving.hybrid import HybridPrograms
from dstack_tpu.serving.lfm2 import Lfm2Programs
from dstack_tpu.serving.nemotron_h import NemotronHPrograms

#: config class -> provider; a subclass is served by its nearest base's
PROVIDERS = {
    LlamaConfig: DensePrograms,
    LingHybridConfig: HybridPrograms,
    Lfm2MoeConfig: Lfm2Programs,
    NemotronHConfig: NemotronHPrograms,
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
