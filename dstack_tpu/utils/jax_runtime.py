"""Process-level JAX set-up shared by the entry points (serving server,
trainer, bench): where compiled programs persist, and what device this
process really runs on."""

from __future__ import annotations

import os
from pathlib import Path

import jax

#: used when ``JAX_COMPILATION_CACHE_DIR`` is not set.  One fixed directory
#: inside the checkout: the directory is part of JAX's cache key, so a
#: temporary name, a pid or a timestamp here would never hit.
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_persistent_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself and nothing is
    set in code, so whoever runs the process places the cache.  Unset:
    :data:`DEFAULT_CACHE_DIR`.  (The fleet-level `elastic.CompileCache` is
    a separate, opt-in product feature layered above this.)
    """
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return str(DEFAULT_CACHE_DIR)


def device_report() -> dict:
    """``{"platform", "kind", "count"}`` as JAX reports the backend this
    process computes on — carried by ``/health``, the bench JSON and
    ``chip_smoke.py`` so no number is read without its device."""
    devices = jax.devices()
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind,
            "count": len(devices)}


def device_memory() -> list:
    """``bytes_in_use`` of each local device, in device order (None where
    the backend keeps no count, as the CPU's does not)."""
    return [(d.memory_stats() or {}).get("bytes_in_use")
            for d in jax.local_devices()]


def named_jit(fn, name: str, **jit_kwargs):
    """``jax.jit`` of ``fn`` under ``name``: the compiled program shows as
    ``jit_<name>`` on a profiler trace's ``XLA Modules`` line and in HLO
    dumps (a ``functools.partial`` or a local ``fn`` would read
    ``jit__unknown`` / ``jit_fn``).  The only ``jax.jit`` the serving
    engine and its families' providers go through."""
    def named(*args, **kwargs):
        return fn(*args, **kwargs)

    named.__name__ = named.__qualname__ = name
    return jax.jit(named, **jit_kwargs)
