"""Instant elasticity: kill the three legs of replica cold start.

An earlier v5e run (2026-08-01) showed 11.8-17.4 s of XLA compile+warmup
per replica, and a real scale-up additionally pays provision + image
pull + cold GCS weight load.  This package makes each leg skippable:

``compile_cache``
    Persistent content-addressed cache of serialized XLA executables,
    keyed by hash(HLO module + topology + jax/jaxlib version).  A
    scaling-up replica never recompiles a program any peer has already
    compiled — it deserializes in milliseconds instead.

``weight_stream``
    Peer-to-peer weight streaming: a new replica pulls the host-shard
    snapshot (the ``models/checkpoint.py`` manifest format, verbatim)
    over HTTP from a live replica, chunked and integrity-checked
    against the manifest's per-shard checksums, rate-limited below
    serving traffic, with cold-GCS fallback.

``standby``
    Pre-warmed standby engines: a small pool of compiled-but-idle
    engines per service that the autoscaler activates in O(seconds)
    instead of provisioning.  While warming, a standby reports
    ``warming`` on ``/load`` so the router never counts it toward
    routable capacity.

See docs/concepts/elasticity.md for the lifecycle and env knobs.
"""

from dstack_tpu.elastic.compile_cache import (
    CachedJit,
    CompileCache,
    cache_key,
    maybe_cached,
    topology_fingerprint,
)
from dstack_tpu.elastic.standby import StandbyPool, StandbyRecord
from dstack_tpu.elastic.weight_stream import (
    TokenBucket,
    WeightStreamError,
    pull_weights,
    stream_snapshot,
)

__all__ = [
    "CachedJit",
    "CompileCache",
    "StandbyPool",
    "StandbyRecord",
    "TokenBucket",
    "WeightStreamError",
    "cache_key",
    "maybe_cached",
    "pull_weights",
    "stream_snapshot",
    "topology_fingerprint",
]
