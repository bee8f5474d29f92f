"""What only the process that holds the chip can do, for both of them
(the audit role's child, the replica's launcher): refuse another
platform than the one asked for, read the device's peak memory, and
record the interpreter's full garbage collections."""

from __future__ import annotations

import gc
import sys
import time


def device_or_die(platform: str) -> dict:
    """First touch of jax: the platform must be the one asked for (the
    harness asks for "tpu"); nothing continues on another."""
    import jax

    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    if info["platform"] != platform:
        print(f"benchmark: jax reports {info}, need platform "
              f"{platform!r}: refusing to run", file=sys.stderr)
        sys.exit(3)
    return info


def memory_peak_bytes() -> int:
    """peak_bytes_in_use on the fullest device."""
    import jax

    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in jax.devices())


class GcPauses:
    """The interpreter's full (generation 2) collections from now on:
    `events` holds (time.monotonic() at start, seconds) of each."""

    def __init__(self):
        self.events, self._t = [], None
        gc.callbacks.append(self._cb)

    def _cb(self, phase, info):
        if info.get("generation") != 2:
            return
        if phase == "start":
            self._t = time.monotonic()
        elif self._t is not None:
            self.events.append((self._t, time.monotonic() - self._t))
            self._t = None

    def stop(self):
        gc.callbacks.remove(self._cb)


def pauses_in(events: list, t_open: float, t_close: float) -> dict:
    """The collections that began inside [t_open, t_close)."""
    mine = [(a, d) for a, d in events if t_open <= a < t_close]
    return {"count": len(mine), "ms": sum(d for _a, d in mine) * 1e3,
            "at_s": [round(a - t_open, 3) for a, _d in mine]}
