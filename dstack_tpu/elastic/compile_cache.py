"""Persistent content-addressed cache of compiled XLA executables.

The compile leg of a replica cold start is pure waste after the first
replica: every peer lowers the *same* HLO on the *same* topology and
pays the same 11.8-17.4 s (an earlier v5e run, 2026-08-01) to get the
byte-identical executable.  This cache serializes the executable once
(``jax.experimental.serialize_executable``) and keys it by content —
``sha256(HLO text + topology fingerprint + jax/jaxlib versions)`` — so
a hit is correct by construction: any input that would compile
differently hashes differently.

Storage is a flat content-addressed directory (``<root>/<k[:2]>/<k>.xc``),
written atomically (tmp + ``os.replace``) so a crashed writer never
publishes a torn entry, designed to live next to checkpoints on the
shared volume.  A miss can also be filled over HTTP from peer replicas
(``GET /elastic/compile/<key>`` on the serving server) before falling
back to a real compile — the fetched bytes are persisted locally so the
fleet converges to everyone having everything.

Env knobs (read by :meth:`CompileCache.from_env`):

``DSTACK_COMPILE_CACHE``
    cache root directory; unset → caching disabled
``DSTACK_COMPILE_CACHE_PEERS``
    comma-separated peer base URLs to try on local miss

Serialization is capability-gated: on a jax build without
``serialize_executable`` the cache degrades to a no-op (every call
compiles, counters still tick) instead of failing the engine.
"""

from __future__ import annotations

import hashlib
import json
import os
import pickle
import tempfile
import threading
from pathlib import Path
from typing import Callable, Dict, Optional, Sequence, Tuple

__all__ = [
    "CachedJit",
    "CompileCache",
    "cache_key",
    "maybe_cached",
    "topology_fingerprint",
]

ENV_CACHE_DIR = "DSTACK_COMPILE_CACHE"
ENV_CACHE_PEERS = "DSTACK_COMPILE_CACHE_PEERS"

#: entry file suffix — pickled (payload, in_tree, out_tree) triple
ENTRY_SUFFIX = ".xc"

_FETCH_TIMEOUT_S = 10.0


def _serialization():
    """(serialize, deserialize_and_load) or (None, None) when absent."""
    try:
        from jax.experimental.serialize_executable import (
            deserialize_and_load,
            serialize,
        )
        return serialize, deserialize_and_load
    except Exception:  # pragma: no cover - depends on jax build
        return None, None


def topology_fingerprint() -> str:
    """What must match for a serialized executable to be loadable.

    Platform + device kind + device count + process count + jax/jaxlib
    versions: a different value for any of these can change the
    compiled artifact or make it unloadable, so all of them feed the
    cache key.
    """
    import jax

    try:
        import jaxlib
        jaxlib_version = getattr(jaxlib, "__version__", "unknown")
    except Exception:  # pragma: no cover
        jaxlib_version = "unknown"
    try:
        devs = jax.devices()
        platform = devs[0].platform
        kind = getattr(devs[0], "device_kind", "") or ""
        n_devices = len(devs)
    except Exception:  # pragma: no cover - no backend at all
        platform, kind, n_devices = "none", "", 0
    try:
        n_processes = jax.process_count()
    except Exception:  # pragma: no cover
        n_processes = 1
    return (f"{platform}/{kind}/d{n_devices}/p{n_processes}"
            f"/jax-{jax.__version__}/jaxlib-{jaxlib_version}")


def cache_key(hlo_text: str, topology: Optional[str] = None) -> str:
    """Content address for one lowered program on one topology."""
    topo = topology_fingerprint() if topology is None else topology
    h = hashlib.sha256()
    h.update(hlo_text.encode("utf-8"))
    h.update(b"\x00")
    h.update(topo.encode("utf-8"))
    return h.hexdigest()


def _default_fetch(url: str, timeout: float = _FETCH_TIMEOUT_S) -> bytes:
    import urllib.request

    with urllib.request.urlopen(url, timeout=timeout) as resp:  # noqa: S310
        return resp.read()


class CompileCache:
    """Content-addressed store of serialized executables, local + peer.

    Thread-safe; counters (``hits``/``misses``/``peer_hits``/``puts``/
    ``errors``) surface on ``/load`` and ``/stats`` via
    :meth:`snapshot`.  ``hits`` means *deserialized instead of
    compiled* — an engine start with ``misses == 0`` did zero XLA
    compiles.
    """

    def __init__(self, root: Optional[os.PathLike] = None,
                 peers: Sequence[str] = (),
                 fetch: Optional[Callable[[str], bytes]] = None) -> None:
        self.root = Path(root) if root else None
        self.peers = [p.rstrip("/") for p in peers if p]
        self._fetch = fetch or _default_fetch
        self._lock = threading.Lock()
        self._serialize, self._deserialize = _serialization()
        self.hits = 0
        self.misses = 0
        self.peer_hits = 0
        self.puts = 0
        self.errors = 0

    # -- construction -------------------------------------------------

    @classmethod
    def from_env(cls, env: Optional[Dict[str, str]] = None
                 ) -> Optional["CompileCache"]:
        """Cache per env knobs, or None when both knobs are unset."""
        env = os.environ if env is None else env
        root = env.get(ENV_CACHE_DIR, "").strip()
        peers = [p.strip() for p in
                 env.get(ENV_CACHE_PEERS, "").split(",") if p.strip()]
        if not root and not peers:
            return None
        return cls(root or None, peers)

    @property
    def serialization_supported(self) -> bool:
        return self._serialize is not None

    # -- keying/paths -------------------------------------------------

    def key_for(self, lowered) -> str:
        """Key for a ``jax.stages.Lowered`` on the current topology."""
        return cache_key(lowered.as_text())

    def _path(self, key: str) -> Optional[Path]:
        if self.root is None:
            return None
        return self.root / key[:2] / (key + ENTRY_SUFFIX)

    # -- byte-level store (also backs the HTTP seed path) -------------

    def get_bytes(self, key: str) -> Optional[bytes]:
        """Raw entry bytes from the local store only (seed path)."""
        path = self._path(key)
        if path is None:
            return None
        try:
            return path.read_bytes()
        except FileNotFoundError:
            return None
        except OSError:
            with self._lock:
                self.errors += 1
            return None

    def put_bytes(self, key: str, data: bytes) -> bool:
        """Atomically persist raw entry bytes (tmp + ``os.replace``)."""
        path = self._path(key)
        if path is None:
            return False
        try:
            path.parent.mkdir(parents=True, exist_ok=True)
            fd, tmp = tempfile.mkstemp(dir=str(path.parent),
                                       prefix=".tmp-", suffix=ENTRY_SUFFIX)
            try:
                with os.fdopen(fd, "wb") as f:
                    f.write(data)
                    f.flush()
                    os.fsync(f.fileno())
                os.replace(tmp, path)
            finally:
                if os.path.exists(tmp):
                    os.unlink(tmp)
            return True
        except OSError:
            with self._lock:
                self.errors += 1
            return False

    def _fetch_from_peers(self, key: str) -> Optional[bytes]:
        for peer in self.peers:
            try:
                data = self._fetch(f"{peer}/elastic/compile/{key}")
            except Exception:
                continue
            if data:
                with self._lock:
                    self.peer_hits += 1
                self.put_bytes(key, data)
                return data
        return None

    # -- executable-level API -----------------------------------------

    def load(self, key: str):
        """Deserialized executable for ``key``, or None on miss.

        Local store first, then peers (persisting what they return).
        Counter accounting is the caller's job (see :class:`CachedJit`)
        so a probe doesn't double-count.
        """
        if self._deserialize is None:
            return None
        data = self.get_bytes(key)
        if data is None:
            data = self._fetch_from_peers(key)
        if data is None:
            return None
        try:
            payload, in_tree, out_tree = pickle.loads(data)
            return self._deserialize(payload, in_tree, out_tree)
        except Exception:
            with self._lock:
                self.errors += 1
            return None

    def store(self, key: str, compiled) -> bool:
        """Serialize a ``jax.stages.Compiled`` into the local store."""
        if self._serialize is None:
            return False
        try:
            payload, in_tree, out_tree = self._serialize(compiled)
            data = pickle.dumps((payload, in_tree, out_tree))
        except Exception:
            with self._lock:
                self.errors += 1
            return False
        ok = self.put_bytes(key, data)
        if ok:
            with self._lock:
                self.puts += 1
        return ok

    def contains(self, key: str) -> bool:
        path = self._path(key)
        return path is not None and path.exists()

    def snapshot(self) -> Dict[str, int]:
        with self._lock:
            return {
                "compile_cache_hits": self.hits,
                "compile_cache_misses": self.misses,
                "compile_cache_peer_hits": self.peer_hits,
                "compile_cache_puts": self.puts,
                "compile_cache_errors": self.errors,
            }


class CachedJit:
    """A jitted callable that consults the compile cache before lowering.

    First call lowers the function against the actual arguments, hashes
    the HLO, and either deserializes a cached executable (zero XLA
    compile) or compiles and stores it for the fleet.  Subsequent calls
    go straight to the pinned executable.  The engine's bucketing keeps
    shapes fixed per instance; if a call ever arrives with a different
    signature, the pinned executable raises and we fall back to the
    original jitted function (shape-polymorphic, correct, slower).
    """

    def __init__(self, jitted, cache: Optional[CompileCache],
                 tag: str = "") -> None:
        self._jitted = jitted
        self._cache = cache
        self.tag = tag
        self.key: Optional[str] = None
        #: "cache" (deserialized), "compile" (built + stored), or
        #: "jit" (cache unusable, plain jax.jit path)
        self.source: Optional[str] = None
        self._compiled = None
        self._lock = threading.Lock()

    def _resolve(self, args: Tuple, kwargs: Dict):
        cache = self._cache
        try:
            lowered = self._jitted.lower(*args, **kwargs)
            key = cache.key_for(lowered)
        except Exception:
            self.source = "jit"
            return self._jitted
        self.key = key
        loaded = cache.load(key)
        if loaded is not None:
            with cache._lock:
                cache.hits += 1
            self.source = "cache"
            return loaded
        with cache._lock:
            cache.misses += 1
        compiled = lowered.compile()
        cache.store(key, compiled)
        self.source = "compile"
        return compiled

    def __call__(self, *args, **kwargs):
        compiled = self._compiled
        if compiled is None:
            if (self._cache is None
                    or not self._cache.serialization_supported):
                self.source = "jit"
                return self._jitted(*args, **kwargs)
            with self._lock:
                if self._compiled is None:
                    self._compiled = self._resolve(args, kwargs)
                compiled = self._compiled
        try:
            return compiled(*args, **kwargs)
        except Exception:
            if compiled is self._jitted:
                raise
            # signature drift (different shapes/dtypes than first call):
            # the plain jitted path handles it, at recompile cost
            return self._jitted(*args, **kwargs)


def maybe_cached(jitted, cache: Optional[CompileCache], tag: str = ""):
    """Wrap ``jitted`` with the cache, or return it untouched when
    caching is disabled — the zero-risk default path."""
    if cache is None:
        return jitted
    return CachedJit(jitted, cache, tag=tag)
