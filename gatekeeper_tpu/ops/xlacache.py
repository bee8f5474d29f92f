"""Persistent XLA compilation cache (SURVEY.md §5.4: device buffers and
executables are derived state; an on-disk compile cache is the one
optimization kept across restarts).

A gatekeeper restart rebuilds all engine state from the API server, but
the fused executables' XLA compiles dominate cold start (~20s+ for a
500-template corpus).  With the cache enabled, a restarted pod reloads
each executable from disk in milliseconds as long as its HLO is unchanged
(same template set/shapes/jax version).

Where the cache lives is decided HERE and nowhere else
(:func:`resolve_cache_dir`): ``$JAX_COMPILATION_CACHE_DIR`` when set —
jax reads that variable itself, so the code then never points jax at a
directory — else the ``--xla-cache-dir`` flag, else
``<checkout>/.xla-cache``.  The path is part of jax's cache key, so a
directory that moves between runs never hits: no caller may name one
from ``tempfile``, a pid or a clock.  The serialized-executable (AOT)
cache rides in ``<that dir>/aot`` (ops/aotcache.py).

Importing this module does not import jax (launchers that must stay off
the chip resolve the directory for their children through it)."""

from __future__ import annotations

import logging
import os
from typing import Optional

log = logging.getLogger("gatekeeper.xlacache")

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
#: the cache of a checkout that was given no other place (gitignored)
DEFAULT_CACHE_DIR = os.path.join(REPO_ROOT, ".xla-cache")

_enabled_dir = None
_listener_installed = False
_listener_failed = False  # logged-once guard for the absence warning


def _install_cache_listener():
    """Best-effort hit/miss counters for jax's persistent compile cache:
    jax emits monitoring events on every cache consult; mirror them into
    the metrics catalog's cache_requests_total counter and the compile
    telemetry (obs/compilestats.py cold-vs-warm provenance).

    Absence contract (ISSUE 13 satellite, per the PR 10 counted-drops
    discipline): on jax builds without the monitoring events this
    instrumentation used to vanish SILENTLY — an operator staring at a
    missing cache_requests_total{cache="xlacache"} row could not tell
    "no cache traffic" from "no counters".  Now the absence logs once at
    warning and exports ``xlacache_counters_available`` 0/1 either way."""
    global _listener_installed, _listener_failed
    if _listener_installed or _listener_failed:
        return
    from ..obs import compilestats

    try:
        from jax._src import monitoring

        from ..metrics.catalog import record_cache

        def _on_event(event, **_kw):
            if event == "/jax/compilation_cache/cache_hits":
                record_cache("xlacache", True)
                compilestats.get_stats().note_xla_event(True)
            elif event == "/jax/compilation_cache/cache_misses":
                record_cache("xlacache", False)
                compilestats.get_stats().note_xla_event(False)

        monitoring.register_event_listener(_on_event)
        _listener_installed = True
        compilestats.get_stats().set_xla_counters_available(True)
    except Exception:
        _listener_failed = True
        # logged ONCE (the guard above keeps re-enables out) and
        # exported: cache hit/miss telemetry is absent on this build,
        # and compile provenance degrades to "unknown"
        log.warning(
            "jax persistent-cache monitoring events unavailable: "
            "cache_requests_total{cache=\"xlacache\"} will not be "
            "recorded and compile provenance degrades to 'unknown' "
            "(xlacache_counters_available=0)", exc_info=True,
        )
        compilestats.get_stats().set_xla_counters_available(False)


def resolve_cache_dir(flag: Optional[str] = None) -> str:
    """The compile-cache directory of this process: the standard
    variable wins, then an explicit ``--xla-cache-dir`` value (an empty
    one means no cache), then ``<checkout>/.xla-cache``."""
    env = os.environ.get(ENV_VAR, "")
    if env:
        return env
    if flag is not None:
        return flag
    return DEFAULT_CACHE_DIR


def enable(cache_dir: str) -> bool:
    """Idempotently turn on jax's persistent compilation cache at
    cache_dir (a :func:`resolve_cache_dir` answer).  With
    ``$JAX_COMPILATION_CACHE_DIR`` set jax already holds that directory
    and it is left untouched."""
    global _enabled_dir
    if not cache_dir or _enabled_dir == cache_dir:
        return _enabled_dir is not None
    import jax

    if not os.environ.get(ENV_VAR):
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    _enabled_dir = cache_dir
    _install_cache_listener()
    # cache every executable: the fused policy programs are small by XLA
    # standards but the whole cold start to rebuild
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    log.info("persistent XLA compilation cache at %s", cache_dir)
    return True


def enable_caches(flag: Optional[str] = None) -> str:
    """Resolve the directory and enable BOTH caches under it: jax's
    compile cache and, in ``<dir>/aot``, the serialized executables that
    let a warm restart skip the fused programs' trace as well.  Every
    process entry (main.py, bench.py, chip_smoke.py) calls this; returns
    the directory, ``""`` when the flag disabled caching."""
    cache_dir = resolve_cache_dir(flag)
    if cache_dir:
        from .aotcache import enable as enable_aot_cache

        enable(cache_dir)
        enable_aot_cache(os.path.join(cache_dir, "aot"))
    return cache_dir
