"""Persistent XLA compilation cache — compile once, load afterwards.

A fresh serving replica pays one XLA compile per (model topology,
shape bucket) before it can flip ready, and a cold training run pays
one per window program.  This module wires jax's *persistent*
compilation cache so those executables are compiled ONCE: the first
process to build a program writes the serialized executable to the
cache directory (a shared volume in production), and every later
process of the same program deserializes it instead of recompiling.

**Where the cache lives.**  ``JAX_COMPILATION_CACHE_DIR`` decides when
it is set: jax reads it itself at import, so this module then leaves
``jax_compilation_cache_dir`` alone and every entry point — trainer,
``serve``, each fleet replica, ``chip_smoke.py`` — uses that one
directory, whatever config or ``--compile-cache DIR`` say; setting the
variable also turns the cache on.  When it is not set the directory is
``root.common.compile_cache.dir`` or ``<checkout>/.cache/xla_cache``:
a fixed path, never a temp name, because the path is part of what a
later process must find again.

**Accounting — what "zero fresh compiles" means.**  The installed jax
records a ``backend_compile`` duration event around the whole
compile-*or-load* step, so ``jax.backend_compiles`` ticks even when
the executable came from the persistent cache; the cache hit
additionally fires ``jax.persistent_cache_hits`` (PR 1 wired both).  A
**fresh** compile — actual XLA work — is therefore
``backend_compiles - persistent_cache_hits``, and that is the number a
warm start must hold at ZERO (pinned by
``tests/functional/test_compile_cache.py``).  :class:`watch` snapshots
the three counters and exposes the delta.

The cache key covers the serialized computation + jaxlib version +
compile options, NOT array values — so the engine's params-as-argument
design (serving/engine.py) means every model version bump and every
replica of the same topology share one cache entry per bucket.

Pairs with the **warmup manifest** (``export.serving_manifest``): every
deployment package / snapshot topology records the bucket ladder and
sample shape it should be warmed for, so a replica knows its full
compile set ahead of the first request.

Off unless ``JAX_COMPILATION_CACHE_DIR`` or
``root.common.compile_cache.enabled`` is set (``serve
--compile-cache`` also turns it on); the training launcher and
``serve`` both call :func:`maybe_enable`, and the off path is one
config read.
"""

import glob
import os

from znicz_tpu.core.config import root
from znicz_tpu.core import telemetry
from znicz_tpu.analysis import locksmith

_lock = locksmith.lock("compile_cache")
#: the active cache directory (None = not wired into jax)
_dir = None


def env_dir():
    """``JAX_COMPILATION_CACHE_DIR`` when the process was started with
    it (a cache placed from outside), else None."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or None


def configured_dir():
    """The directory the cache uses when nothing is passed to
    :func:`enable`: ``JAX_COMPILATION_CACHE_DIR``, else
    ``root.common.compile_cache.dir``, else ``<cache>/xla_cache``."""
    explicit = env_dir() or root.common.compile_cache.get("dir", None)
    if explicit:
        return os.fspath(explicit)
    return os.path.join(root.common.dirs.cache, "xla_cache")


def enabled():
    """True once :func:`enable` wired a cache directory."""
    return _dir is not None


def active_dir():
    return _dir


def enable(cache_dir=None):
    """Point jax's persistent compilation cache at ``cache_dir``
    (default: :func:`configured_dir`).  Idempotent; calling again with
    a different directory re-points the cache.  Returns the directory.

    With ``JAX_COMPILATION_CACHE_DIR`` set, that directory is used
    whatever ``cache_dir`` says and ``jax_compilation_cache_dir`` is
    not touched (jax already holds the variable's value).

    ``min_compile_time_secs``/``min_entry_size_bytes`` default to
    cache-everything (0 / -1): serving executables are small and the
    whole point is that NO bucket recompiles on restart.
    """
    global _dir
    import jax
    cfg = root.common.compile_cache
    placed = env_dir()
    with _lock:
        d = os.path.abspath(placed or (
            os.fspath(cache_dir) if cache_dir else configured_dir()))
        os.makedirs(d, exist_ok=True)
        if not placed:
            jax.config.update("jax_compilation_cache_dir", d)
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          float(cfg.get("min_compile_time_secs", 0.0)))
        jax.config.update("jax_persistent_cache_min_entry_size_bytes",
                          int(cfg.get("min_entry_size_bytes", -1)))
        _dir = d
    telemetry.record_event("compile_cache.enable", dir=d)
    return d


def disable():
    """Unwire the cache (tests): jit compiles stop touching disk —
    except for a cache placed by ``JAX_COMPILATION_CACHE_DIR``, which
    jax keeps using."""
    global _dir
    import jax
    with _lock:
        if not env_dir():
            jax.config.update("jax_compilation_cache_dir", None)
            # jax decides once a process whether it uses the cache and
            # keeps the opened directory: without this a later compile
            # still loads what an earlier one stored (and with it the
            # earlier program's op names)
            from jax.experimental.compilation_cache import \
                compilation_cache
            compilation_cache.reset_cache()
        _dir = None


def maybe_enable():
    """Enable when ``JAX_COMPILATION_CACHE_DIR`` or
    ``root.common.compile_cache.enabled`` asks for it (the training
    launcher, the ``serve`` CLI and every fleet replica call this);
    returns the directory or None."""
    if env_dir() or root.common.compile_cache.get("enabled", False):
        return enable()
    return None


def _counter_values():
    return {
        "backend_compiles":
            telemetry.counter("jax.backend_compiles").value,
        "persistent_cache_hits":
            telemetry.counter("jax.persistent_cache_hits").value,
        "persistent_cache_misses":
            telemetry.counter("jax.persistent_cache_misses").value,
    }


class watch(object):
    """Snapshot of the compile counters; ``fresh_compiles()`` is the
    number of ACTUAL XLA compiles since construction (compile-or-load
    events minus persistent-cache loads).  Requires telemetry to be
    enabled — the counters only tick then."""

    def __init__(self):
        self._at = _counter_values()

    def delta(self):
        now = _counter_values()
        return {k: int(now[k] - self._at[k]) for k in now}

    def fresh_compiles(self):
        d = self.delta()
        return d["backend_compiles"] - d["persistent_cache_hits"]


def stats():
    """The cache's observable state — stamped into serving ``stats()``
    and the bench cold-start block."""
    out = {
        "enabled": enabled(),
        "dir": _dir,
    }
    if _dir and os.path.isdir(_dir):
        entries = [p for p in glob.glob(os.path.join(_dir, "*"))
                   if os.path.isfile(p) and not p.endswith("-atime")]
        out["entries"] = len(entries)
        out["bytes"] = sum(os.path.getsize(p) for p in entries)
    if telemetry.enabled():
        out.update(_counter_values())
    return out
