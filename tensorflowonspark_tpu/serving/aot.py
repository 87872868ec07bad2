"""AOT serve-step executable cache: compiled dispatches as disk artifacts.

The fleet's persistent XLA compilation cache (``enable_serving_compile_
cache``) already dedupes *compiles* across processes, but every process
still pays trace + lower + (cache-hit) load through the full ``jax.jit``
machinery on its first call of every serve-step shape — and a cache MISS
is a full compile inside the heal window.  This module takes the
remaining step: each serve-step executable (``decode``, the ``final``/
``chunk`` prefill buckets, ``verify``, park/seat/export helpers) is
``lower().compile()``-d once, serialized via
``jax.experimental.serialize_executable``, and written to a content-
addressed file under the cache directory.  Every later process —
a cold replica, a warm standby paying its bucket×group sweep, the
``scripts/tfos_warmcache.py`` pre-bake CLI — resolves the same site to a
``deserialize_and_load`` call: a cache READ, no tracing, no XLA.

Keying: one file per (jax version, backend platform, the ids of the
devices the executable runs on, call-site id, caller context, argument
avals) — an entry is loaded onto exactly those devices
(``execution_devices``), never spread over every device of the host, so
a one-chip replica's executable stays on its chip.  The caller context is
the batcher's config/mesh identity (``ContinuousBatcher`` passes its
``GPTConfig`` repr + batch/speculation knobs; a gang leader's cache adds
the mesh axes), so two models or two shardings never collide.  A corrupt
or incompatible entry falls back to compile-and-overwrite: the cache can
only ever cost a recompile, never a wrong executable (deserialization
either fails loudly or yields the byte-identical program).

Opt-in: a batcher built without ``aot_cache=`` uses plain ``jax.jit``
exactly as before.  ``ServingCluster.run(aot_cache=...)`` arms the whole
tier (directory: ``util.aot_cache_dir()``, beside the persistent XLA cache).
"""

from __future__ import annotations

import hashlib
import logging
import os
import pickle
import tempfile

logger = logging.getLogger(__name__)

#: bump when the on-disk entry layout changes; stale-version entries
#: simply miss (the filename carries it)
_FORMAT = 1


class AOTExecutableCache:
    """Load-or-compile wrapper factory over a serialized-executable dir.

    ``wrap(site, fn, donate_argnums=...)`` returns a callable with the
    same signature as ``jax.jit(fn, donate_argnums=...)``; on its first
    call it resolves an executable — deserialized from disk when a
    matching entry exists, else compiled ahead-of-time and serialized
    for the next process — and every later call dispatches straight to
    it.  Counters: :attr:`loads` (disk hits), :attr:`compiles` (misses
    paid with a compile), :attr:`errors` (corrupt/incompatible entries
    or failed writes — each degrades to a compile, never a crash).
    """

    def __init__(self, cache_dir: str, *, extra_key=None):
        self.cache_dir = str(cache_dir)
        os.makedirs(self.cache_dir, exist_ok=True)
        #: mixed into every entry key (e.g. a gang's mesh axes) so one
        #: directory can back differently-sharded tiers
        self.extra_key = extra_key
        self.loads = 0
        self.compiles = 0
        self.errors = 0

    def stats(self) -> dict:
        return {"dir": self.cache_dir, "loads": self.loads,
                "compiles": self.compiles, "errors": self.errors}

    def wrap(self, site, fn, donate_argnums=()):
        return _AOTCallable(self, site, fn, tuple(donate_argnums))

    # -- internals ---------------------------------------------------------
    def _entry_path(self, site, args, devices) -> str:
        import jax
        import numpy as np

        leaves, treedef = jax.tree_util.tree_flatten(args)
        avals = [(tuple(int(d) for d in np.shape(x)),
                  str(getattr(x, "dtype", type(x).__name__)))
                 for x in leaves]
        key = repr((_FORMAT, jax.__version__, jax.default_backend(),
                    [d.id for d in devices], repr(self.extra_key),
                    repr(site), str(treedef), avals))
        digest = hashlib.sha256(key.encode()).hexdigest()
        return os.path.join(self.cache_dir, f"v{_FORMAT}-{digest}.aotx")

    def _load(self, path: str, devices):
        """Deserialize one entry onto ``devices``, or None (counting the
        error) when the file is missing/corrupt/incompatible — the
        caller compiles."""
        if not os.path.exists(path):
            return None
        from jax.experimental.serialize_executable import \
            deserialize_and_load

        try:
            with open(path, "rb") as f:
                payload, in_tree, out_tree = pickle.load(f)
            compiled = deserialize_and_load(payload, in_tree, out_tree,
                                            execution_devices=devices)
            self.loads += 1
            return compiled
        # tfos: ignore[broad-except] — a corrupt or cross-version entry
        # must degrade to a recompile (which overwrites it), never crash
        # the replica that tripped on it
        except Exception:
            self.errors += 1
            logger.warning("AOT cache entry %s unusable; recompiling",
                           os.path.basename(path), exc_info=True)
            return None

    def _store(self, path: str, compiled, devices) -> None:
        """Serialize + verify + atomic-rename; a failed write only costs
        the next process a compile.  The verify round-trip
        (``deserialize_and_load`` on the fresh payload) guarantees no
        entry is ever written that a later process cannot load — an
        executable that came out of XLA's own persistent compilation
        cache, for example, serializes without its symbol table."""
        from jax.experimental.serialize_executable import (
            deserialize_and_load, serialize)

        try:
            payload, in_tree, out_tree = serialize(compiled)
            deserialize_and_load(payload, in_tree, out_tree,
                                 execution_devices=devices)
            fd, tmp = tempfile.mkstemp(dir=self.cache_dir, suffix=".tmp")
            try:
                with os.fdopen(fd, "wb") as f:
                    pickle.dump((payload, in_tree, out_tree), f,
                                protocol=pickle.HIGHEST_PROTOCOL)
                os.replace(tmp, path)
            finally:
                if os.path.exists(tmp):     # replace succeeded -> gone
                    os.unlink(tmp)
        # tfos: ignore[broad-except] — an unserializable executable or a
        # full/readonly disk leaves the in-memory compile serving; the
        # cache write is strictly an optimization for the NEXT process
        except Exception:
            self.errors += 1
            logger.warning("AOT cache write for %s failed",
                           os.path.basename(path), exc_info=True)


def _execution_devices(args) -> list:
    """The devices a jitted call on ``args`` runs on, in device-assignment
    order: those of the widest-sharded argument (a gang's mesh), else the
    process's first device — what ``jax.jit`` itself would pick."""
    import jax

    widest = None
    for leaf in jax.tree_util.tree_leaves(args):
        sharding = getattr(leaf, "sharding", None)
        if sharding is not None and (
                widest is None
                or len(sharding.device_set) > len(widest.device_set)):
            widest = sharding
    if widest is None:
        return [jax.local_devices()[0]]
    return list(widest._device_assignment)


class _AOTCallable:
    """One call site's lazily-resolved executable (see
    :meth:`AOTExecutableCache.wrap`).  Shape-monomorphic by contract:
    the serving batcher keys its executable registry per shape, so every
    call after the first carries the avals the first call resolved
    with — exactly the ``jax.jit`` cache-hit fast path, minus the
    signature re-hash."""

    __slots__ = ("cache", "site", "fn", "donate", "_compiled")

    def __init__(self, cache: AOTExecutableCache, site, fn, donate):
        self.cache = cache
        self.site = site
        self.fn = fn
        self.donate = donate
        self._compiled = None

    def __call__(self, *args):
        compiled = self._compiled
        if compiled is None:
            compiled = self._resolve(args)
        return compiled(*args)

    def _resolve(self, args):
        import jax

        devices = _execution_devices(args)
        path = self.cache._entry_path(self.site, args, devices)
        compiled = self.cache._load(path, devices)
        if compiled is None:
            from jax.experimental.compilation_cache.compilation_cache import \
                reset_cache

            jitted = jax.jit(self.fn, donate_argnums=self.donate)
            # bypass XLA's persistent compilation cache for this compile:
            # an executable deserialized from THAT cache loses its symbol
            # table under re-serialization, and this cache replaces it
            # for serve-step sites anyway (a hit here is a full load).
            # jax memoizes its is-the-cache-in-use decision at the first
            # compile of the process, so flipping the flag alone is not
            # enough — reset_cache() drops that memo (and again in the
            # finally, so non-AOT compiles re-arm the persistent cache)
            prev = jax.config.jax_enable_compilation_cache
            try:
                jax.config.update("jax_enable_compilation_cache", False)
                reset_cache()
                compiled = jitted.lower(*args).compile()
            finally:
                jax.config.update("jax_enable_compilation_cache", prev)
                reset_cache()
            self.cache.compiles += 1
            self.cache._store(path, compiled, devices)
        self._compiled = compiled
        return compiled
