"""Heap discipline of a process that sweeps an inventory.

A 100,000-Pod store under 500 constraints is ~3 M collector-tracked
objects, nearly all long-lived.  CPython starts a full collection
whenever the objects promoted since the last one pass a quarter of the
old generation: every ~40 sweeps of 200 changed rows it walks all 3 M
(1.5-2 s), in the middle of whatever stage allocated last, and the
young collections in between walk each sweep's survivors on their way
there.  None of it finds anything: reference counting frees what a
sweep makes.

So the sweeping process does what the serving side does
(webhook/server.py WebhookServer.start): collect once, freeze what is
alive, turn automatic collection off, and collect at its own boundary
— between sweeps, at most every PERIOD_S, re-freezing the survivors so
each is walked once.  Frozen objects are still freed by reference
counting; only a cycle formed among frozen objects is kept until
release().  With automatic collection off a cycle waits for the next
sweep's end (the audit interval, where that is longer than PERIOD_S).

The collector is the process's, so this is module state: engage() /
after_sweep() from the sweeping thread (Client._sweep_done), release()
from whoever stops it (AuditManager.stop, tests).
"""

from __future__ import annotations

import gc
import threading
import time
from typing import Optional, Tuple

# An explicit collection at most this often: the webhook's own period.
# Every survivor is walked once whatever the period; it bounds how long
# a cycle waits.
PERIOD_S = 5.0

# A sweep engages the discipline when full collections held its stages
# (and the ingest before it) for at least this long in all.  The
# observable is the harm itself, not the store's size, because the cost
# does not follow the size.  At 200 rows churned a sweep (this
# sandbox's CPU, several probes sharing it; generation-2 ms a sweep |
# one pause | sweeps between two): 500 constraints x 100,000 Pods 60.6 |
# 2,181 | 36; x 30,000 56.9 | 995 | 17.5; x 10,000 57.6 | 665 | 11.5;
# x 1,000 55.1 | 657 | 11.9; 50 x 10,000 20.3 | 637 | 31; 6 x 20,000
# 14.9 | 931 | 63; 20 x 5,000 11.9 | 561 | 47; 50 x 1,000 15.2 | 476 |
# 31.  CPython starts a full collection when the objects promoted since
# the last pass a quarter of the old generation, so a smaller heap is
# walked sooner: the amortised cost is ~4 x (objects a sweep promotes)
# x (0.6-1 us), whatever the heap holds.  One came every 11-63 sweeps
# everywhere, so a pause under 20 ms is under 1-2 ms a sweep, and
# engaging costs one collection as long as the one just seen: worth it
# from the first.  No process that holds jax has one that short (356 ms
# the least measured), so in effect the first sweep a full collection
# lands in engages.  (tests/conftest.py pins this to infinity: a stray
# collection in a long-lived pytest worker engages nothing.)
ENGAGE_MIN_PAUSE_S = 0.02

_LOCK = threading.Lock()
# whether the collector was enabled when engage() found it; None while
# not engaged.  Found disabled, the heap is already somebody's (the
# webhook server's, in an all-roles process): theirs to enable and thaw.
_was_enabled: Optional[bool] = None
_collected_at = 0.0
_collections = 0   # explicit collections run, engage()'s included
_frozen = 0        # objects the engagement froze; 0 once released


def engaged() -> bool:
    return _was_enabled is not None


def engage(now: Optional[float] = None) -> None:
    """Collect, freeze, disable — once; a no-op while engaged."""
    global _was_enabled, _collected_at, _collections, _frozen
    with _LOCK:
        if _was_enabled is not None:
            return
        _was_enabled = gc.isenabled()
        gc.collect()
        # everything alive, all in the old generation now: what this
        # freeze takes out of the collector's reach.  Counted once (0.7 s
        # over 3 M objects on this sandbox's CPU); a boundary's survivors
        # are not (it cost 15 % on top of walking them).
        _frozen = len(gc.get_objects(generation=2))
        gc.freeze()
        gc.disable()
        _collected_at = time.perf_counter() if now is None else now
        _collections += 1


def after_sweep(now: float) -> bool:
    """The sweep's boundary: collect what was allocated since the last
    freeze and is still alive, and freeze it, if PERIOD_S have passed.
    True if it collected."""
    global _was_enabled, _collected_at, _collections
    if _was_enabled is None or now - _collected_at < PERIOD_S:
        return False
    with _LOCK:
        if _was_enabled is None:
            return False
        if gc.isenabled():
            # whoever had the collector off before engage() has let go
            # of it (WebhookServer.stop enables and unfreezes): it is
            # this module's to hand back now
            _was_enabled = True
            gc.disable()
        gc.collect()
        gc.freeze()
        _collected_at = now
        _collections += 1
    return True


def release() -> None:
    """Hand the collector back as engage() found it: enabled and thawed
    if it was enabled, left alone if it was not.  (gc.unfreeze() thaws
    everything, the few hundred objects an interpreter starts with
    frozen included: no more is left frozen than was found.)"""
    global _was_enabled, _frozen
    with _LOCK:
        if _was_enabled is None:
            return
        if _was_enabled:
            gc.enable()
            gc.unfreeze()
        _was_enabled = None
        _frozen = 0


def counters() -> Tuple[int, int]:
    """(explicit collections so far, objects the engagement froze) for
    the scrape-time push (obs/trace.py collect_hook)."""
    return _collections, _frozen
