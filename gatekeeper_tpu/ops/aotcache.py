"""Ahead-of-time executable cache: serialized compiled XLA programs.

SURVEY.md §5.4: all engine state is derived and rebuilt on boot; the one
artifact worth keeping across restarts is the compiled evaluation
program.  jax's persistent compilation cache (ops/xlacache.py) already
skips the XLA *compile*, but a restarted process still re-TRACES every
fused function (pure Python, seconds for a 500-template corpus) before
the cache can even be consulted — measured as the dominant share of cold
start.  This module serializes the whole compiled executable
(jax.experimental.serialize_executable) keyed by the trace-equivalence
signature + concrete input layout, so a warm restart skips trace AND
compile: deserialize is ~ms.

Scope and safety:
- Keys include the jax version, backend kind, a fingerprint of this
  package's kernel SOURCE (an executable serialized by an older build
  must never serve a binary whose kernel semantics changed), and a hash
  of the structure signature plus every input leaf's shape/dtype — any
  mismatch is a miss and the caller falls back to the normal jit path.
- Entries are pickles, and unpickling attacker-supplied bytes is code
  execution: the cache directory is created 0700 and every entry is
  sealed with the shared HMAC scheme (util/seal.py — the same trust
  model the snapshot manifest uses, documented in docs/snapshots.md).
  An entry whose seal does not verify is dropped and treated as a
  miss BEFORE any pickle byte is parsed.
- Single-device executables only (the mesh path's device assignment
  does not survive a process restart; it stays on the jit path).
- A deserialized executable that rejects its args is deleted and its
  key blacklisted, so a bad entry costs one reload, not one per call.
- XLA:CPU AOT results are machine-feature-pinned: restoring on a
  different host may refuse or warn — also treated as a miss.  The
  production restart scenario is the same pod image on the same node.

The wrapper (aot_jit) mimics the narrow jit surface the driver uses:
call with concrete arrays, get outputs; no static/donated args.
"""

from __future__ import annotations

import hashlib
import io
import logging
import os
import pickle
import threading
from typing import Any, Callable, Optional

import jax

from ..metrics.catalog import record_aot_lookup

log = logging.getLogger("gatekeeper.aotcache")

_dir: Optional[str] = None
_lock = threading.Lock()
# read-mostly consumer mode (docs/fleet.md trust model): fleet webhook
# replicas SHARE the cache dir with the rest of the fleet.  They may add
# entries (atomic rename, additive) but must never delete shared ones —
# a replica on a newer code fingerprint sees every older build's seal
# fail, and auto-dropping would strip the warmth the still-running old
# replicas restore from.
_read_mostly = False


def _record_cache(cache: str, hit: bool):
    """Observability counters, isolated so a metrics problem can never
    break the compile path."""
    try:
        from ..metrics.catalog import record_cache

        record_cache(cache, hit)
    except Exception:  # pragma: no cover - metrics must never block eval
        log.debug("cache metric recording failed", exc_info=True)


def _record_compile(seconds: float, path: str):
    try:
        from ..metrics.catalog import COMPILE_M, record_stage

        record_stage(COMPILE_M, seconds, {"path": path})
    except Exception:  # pragma: no cover
        log.debug("compile metric recording failed", exc_info=True)


def _cost_analysis(compiled):
    """(flops, bytes_accessed) from XLA's cost model; a backend that
    reports neither key (or refuses the query) yields Nones — telemetry
    never blocks a compile."""
    try:
        ca = compiled.cost_analysis()
        flops = ca.get("flops")
        nbytes = ca.get("bytes accessed")
        return (
            float(flops) if flops is not None else None,
            float(nbytes) if nbytes is not None else None,
        )
    except Exception:
        return None, None


def enable(cache_dir: str, read_mostly: Optional[bool] = None) -> bool:
    global _dir, _read_mostly
    try:
        from ..util import seal as _seal

        _seal.secure_makedirs(cache_dir)
    except OSError:
        log.exception("aot cache dir unavailable: %s", cache_dir)
        return False
    _dir = cache_dir
    if read_mostly is None:
        read_mostly = os.environ.get("GK_AOT_READ_MOSTLY", "") not in (
            "", "0", "false",
        )
    _read_mostly = bool(read_mostly)
    return True


def enabled() -> bool:
    return _dir is not None


def _code_fingerprint() -> str:
    """Digest of every source file in this package (shared with the
    snapshot manifest — util/seal.py): a build whose kernel code changed
    must never reuse an older build's executables (they would silently
    reproduce pre-fix semantics)."""
    from ..util.seal import code_fingerprint

    return code_fingerprint()


# sealed-entry framing: one hex HMAC line, then the pickle payload
_SEAL_HEADER_LEN = 64


def _seal_entry(payload: bytes) -> bytes:
    from ..util import seal as _seal

    return _seal.seal(payload).encode("ascii") + b"\n" + payload


def _open_sealed(blob: bytes) -> Optional[bytes]:
    """Payload bytes iff the seal verifies; None otherwise (including
    pre-seal legacy entries, which are simply re-written on next save)."""
    if len(blob) < _SEAL_HEADER_LEN + 1 or blob[_SEAL_HEADER_LEN] != 0x0A:
        return None
    from ..util import seal as _seal

    tag = blob[:_SEAL_HEADER_LEN].decode("ascii", "replace")
    payload = blob[_SEAL_HEADER_LEN + 1:]
    if not _seal.verify(payload, tag):
        return None
    return payload


def _leaf_sig(x) -> str:
    if hasattr(x, "shape") and hasattr(x, "dtype"):
        return f"{tuple(x.shape)}:{x.dtype}"
    return f"py:{type(x).__name__}:{x!r}"


def _leaf_layout(x):
    """What _leaf_sig formats, as objects: hashed, never formatted."""
    if hasattr(x, "shape") and hasattr(x, "dtype"):
        return x.shape, x.dtype
    return type(x), repr(x)


def load(key: str):
    """-> compiled executable or None."""
    if _dir is None:
        return None
    path = os.path.join(_dir, key + ".aot")
    try:
        with open(path, "rb") as f:
            blob = f.read()
    except FileNotFoundError:
        return None
    except Exception:
        log.exception("aot cache entry unreadable: %s", key)
        return None
    payload_bytes = _open_sealed(blob)
    if payload_bytes is None:
        # unauthenticated bytes are never unpickled; drop the entry so
        # the cost is one miss, and the next save re-writes it sealed
        log.warning("aot cache entry failed seal verification "
                    "(dropped, treated as miss): %s", key)
        drop(key)
        return None
    try:
        payload, in_tree, out_tree = pickle.loads(payload_bytes)
    except Exception:
        log.exception("aot cache entry undecodable: %s", key)
        return None
    try:
        from jax.experimental import serialize_executable as se

        return se.deserialize_and_load(payload, in_tree, out_tree)
    except Exception:
        log.warning("aot cache entry failed to load (treated as miss): %s",
                    key)
        return None


def save(key: str, compiled) -> bool:
    if _dir is None:
        return False
    try:
        from jax.experimental import serialize_executable as se

        payload, in_tree, out_tree = se.serialize(compiled)
        buf = io.BytesIO()
        pickle.dump((payload, in_tree, out_tree), buf,
                    protocol=pickle.HIGHEST_PROTOCOL)
        path = os.path.join(_dir, key + ".aot")
        # pid AND thread id: two threads of one process saving the same
        # key (e.g. review + audit shapes compiling concurrently) must
        # not interleave writes into one tmp file
        tmp = path + f".tmp.{os.getpid()}.{threading.get_ident()}"
        with open(tmp, "wb") as f:
            f.write(_seal_entry(buf.getvalue()))
        os.replace(tmp, path)  # atomic: concurrent writers race benignly
        return True
    except Exception:
        log.exception("aot cache save failed: %s", key)
        return False


def drop(key: str) -> None:
    """Remove one entry — unless this process is a read-mostly consumer
    of a SHARED dir, where a locally-unusable entry (stale seal, host
    mismatch) is someone else's warmth: it stays, and the local miss is
    the whole cost."""
    if _dir is None or _read_mostly:
        return
    try:
        os.remove(os.path.join(_dir, key + ".aot"))
    except OSError:
        pass


class aot_jit:
    """jit with executable persistence.

    First call per input layout: try the AOT cache (deserialize, ~ms);
    miss -> lower+compile via the normal jit machinery and persist the
    executable.  Executables are memoized per layout key (one aot_jit
    instance serves multiple shape buckets — admission batches and the
    audit-capacity shape — without thrashing); a key whose executable
    rejects its args is blacklisted and its file dropped.
    """

    def __init__(self, fn: Callable, tag: str, sig: Any = None):
        self._fn = fn
        self._jitted = jax.jit(fn)
        self._tag = tag
        # the expensive, per-instance-constant key components hash once
        h = hashlib.sha256()
        h.update(jax.__version__.encode())
        h.update(_code_fingerprint().encode())
        h.update(tag.encode())
        h.update(repr(sig).encode())
        self._prefix = h
        # layout (backend, treedef, every leaf's shape and dtype, as
        # objects) -> key: a layout met before names its executable by
        # one dict lookup; _key's SHA-256 over one string a leaf runs
        # only for a layout that is new
        self._keys: dict = {}
        self._compiled: dict = {}  # key -> executable
        self._validated: set = set()  # keys whose output was block-checked
        self._bad: set = set()
        self._mu = threading.Lock()
        # jax.jit attribute parity for wrappers that reach for it
        self.__wrapped__ = fn

    def _key(self, args, flat=None) -> str:
        """The executable's on-disk name."""
        h = self._prefix.copy()
        h.update(jax.default_backend().encode())
        leaves, treedef = flat or jax.tree_util.tree_flatten(args)
        h.update(str(treedef).encode())
        for leaf in leaves:
            h.update(_leaf_sig(leaf).encode())
        return f"{self._tag}-{h.hexdigest()[:32]}"

    def _lookup(self, args):
        """-> (layout, key), through the memo."""
        flat = jax.tree_util.tree_flatten(args)
        layout = (jax.default_backend(), flat[1],
                  tuple(map(_leaf_layout, flat[0])))
        key = self._keys.get(layout)
        record_aot_lookup("hashed" if key is None else "memo")
        if key is None:
            key = self._keys[layout] = self._key(args, flat)
        return layout, key

    def __call__(self, *args):
        if not enabled():
            return self._jitted(*args)  # tests/no-cache: plain jit
        layout, key = self._lookup(args)
        with self._mu:
            compiled = self._compiled.get(key)
            bad = key in self._bad
            validated = key in self._validated
        if compiled is None and not bad:
            import time as _time

            from ..obs import compilestats

            t_load = _time.perf_counter()
            compiled = load(key)
            if compiled is not None:
                log.info("aot cache hit: %s", key)
                _record_cache("aotcache", True)
                # provenance telemetry: an AOT deserialize is the cheap
                # restart path — /debug/compilez attributes cold start
                # between it, persistent-cache compiles and cold compiles
                compilestats.record_compile(
                    self._tag, _time.perf_counter() - t_load, "aot",
                )
            else:
                _record_cache("aotcache", False)
                # one trace+compile for this layout (the .compile()
                # consults jax's persistent XLA cache when enabled), then
                # persist the executable so the NEXT process skips the
                # trace too
                from ..obs import trace as obstrace

                xla_hits0 = compilestats.get_stats().xla_counters()[0]
                t0 = _time.perf_counter()
                compiled = self._jitted.lower(*args).compile()
                t1 = _time.perf_counter()
                obstrace.record_span(
                    "xla.compile", t0, t1, stage=obstrace.COMPILE,
                    tag=self._tag,
                )
                _record_compile(t1 - t0, self._tag)
                # cold vs persistent-cache-warm: jax's monitoring counters
                # tick during .compile() when the persistent cache
                # answered; without the counters the split is unknowable
                # (ops/xlacache.py exports that absence explicitly)
                stats = compilestats.get_stats()
                if stats.xla_counters_available:
                    prov = (
                        "persistent"
                        if stats.xla_counters()[0] > xla_hits0 else "cold"
                    )
                else:
                    prov = "unknown"
                flops, nbytes = _cost_analysis(compiled)
                compilestats.record_compile(
                    self._tag, t1 - t0, prov,
                    flops=flops, bytes_accessed=nbytes,
                )
                save(key, compiled)
                with self._mu:
                    self._validated.add(key)  # it just compiled here
            with self._mu:
                self._compiled[key] = compiled
        if compiled is not None:
            try:
                out = compiled(*args)
                if not validated:
                    # dispatch is ASYNC: a deserialized executable that
                    # cannot run on this host (XLA:CPU AOT results are
                    # machine-feature-pinned) fails at block time, which
                    # would otherwise surface far from here in the
                    # caller's fetch.  Validate loaded entries once.
                    jax.block_until_ready(out)
                    with self._mu:
                        self._validated.add(key)
                return out
            except Exception:
                # layout drift, loader refusal, or a host-incompatible
                # executable: drop the entry and blacklist the key so the
                # cost is one reload, not per call.  The jit fallback
                # below re-runs the work; it is BLOCKED here so a failure
                # that was never about this executable (e.g. a transient
                # device OOM) still surfaces at the call site rather than
                # asynchronously in the caller's fetch — blacklisting a
                # healthy entry on such a failure costs one re-trace, a
                # deliberate trade against serving a broken executable.
                log.warning("aot executable rejected args; blacklisting "
                            "and falling back to jit: %s", key)
                drop(key)
                with self._mu:
                    self._keys.pop(layout, None)
                    self._compiled.pop(key, None)
                    self._validated.discard(key)
                    self._bad.add(key)
                out = self._jitted(*args)
                jax.block_until_ready(out)
                return out
        return self._jitted(*args)
