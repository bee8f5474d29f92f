"""Native (C++) packing fast path — build-on-first-use loader.

The extension accelerates the host-side ingest pipeline (review packing and
columnar extraction, the profiled cold-path cost of a device sweep).  It is
OPTIONAL: every consumer keeps the pure-Python implementation both as the
fallback and as the differential-test oracle (tests/test_native.py).

Set GK_NATIVE=0 to force the Python path; GK_NATIVE=require to fail hard
when the extension can't be built (CI lane for the native path).
"""

from __future__ import annotations

import hashlib
import importlib.util
import os
import subprocess
import sys
import sysconfig
import threading

_lock = threading.Lock()
_mod = None
_tried = False

_SRC = os.path.join(os.path.dirname(__file__), "_gknative.cpp")


def _so_path() -> str:
    """The built file is keyed by a hash of its SOURCE: a copied tree
    (file copies keep no meaningful mtimes) can then never load a binary
    built from another revision of _gknative.cpp — a stale name simply
    does not exist and the build runs."""
    with open(_SRC, "rb") as f:
        digest = hashlib.sha256(f.read()).hexdigest()[:12]
    suffix = sysconfig.get_config_var("EXT_SUFFIX") or ".so"
    return os.path.join(
        os.path.dirname(__file__), f"_gknative-{digest}{suffix}")


def build(force: bool = False) -> str:
    """Compile the extension with g++; returns the .so path."""
    so = _so_path()
    if not force and os.path.exists(so):
        return so
    include = sysconfig.get_paths()["include"]
    # build beside the target and rename: concurrent first loads (a
    # fleet of replicas on a fresh checkout) never see a half-written file
    tmp = f"{so}.{os.getpid()}.tmp"
    cmd = [
        "g++", "-O2", "-shared", "-fPIC", "-std=c++17",
        f"-I{include}", _SRC, "-o", tmp,
    ]
    try:
        subprocess.run(cmd, check=True, capture_output=True)
        os.replace(tmp, so)
    finally:
        if os.path.exists(tmp):
            os.remove(tmp)
    return so


def load():
    """The extension module, or None if unavailable/disabled."""
    global _mod, _tried
    if _mod is not None:
        return _mod
    with _lock:
        if _mod is not None or _tried:
            return _mod
        _tried = True
        mode = os.environ.get("GK_NATIVE", "1")
        if mode == "0":
            return None
        try:
            # gklint: disable=blocking-under-lock -- the lock EXISTS to
            # serialize the one-time native-extension compile; concurrent
            # first callers must wait for the single build, and every
            # later call is a cached-path no-op
            so = build()
            spec = importlib.util.spec_from_file_location("_gknative", so)
            mod = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(mod)
            _mod = mod
        except Exception:
            if mode == "require":
                raise
            print(
                "gatekeeper_tpu: native packing unavailable, "
                "using Python fallback",
                file=sys.stderr,
            )
            return None
        return _mod
