"""Which provider serves a configuration: the one place that maps a config's
class to the state and the programs the engine schedules.  A new family is
a model file, a programs file and one line here."""

from __future__ import annotations

from dstack_tpu.models.ling_hybrid import LingHybridConfig
from dstack_tpu.serving.dense import DensePrograms
from dstack_tpu.serving.hybrid import HybridPrograms


def programs_for(cfg, **built_with):
    """The provider of ``cfg``'s family, built with what the engine was
    (``DensePrograms.__init__`` names the keywords); it refuses the options
    its model is not served with."""
    family = (HybridPrograms if isinstance(cfg, LingHybridConfig)
              else DensePrograms)
    return family(cfg, **built_with)
