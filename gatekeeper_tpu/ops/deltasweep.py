"""Incremental (O(changes)) audit sweep state.

After one full device sweep, the per-constraint audit reduction can be
maintained incrementally: a steady-state sweep re-evaluates ONLY the rows
whose packed content changed (a [C, d] delta evaluation, d = dirty rows)
and folds the before/after candidate columns into host-side state:

  counts[ci]   — device-candidate count per constraint (same semantics as
                 the full sweep's on-device reduction)
  cand[ci]     — sorted known candidate rows, complete up to horizon[ci]
  horizon[ci]  — None when every candidate row is known (count fit within
                 the top-K prefetch at the last full sweep); else the K-th
                 candidate row index: rows beyond it are unknown territory

The full sweep's [C, R] mask stays DEVICE-resident; the delta path reads
the before-columns of newly-dirtied rows from it with one small gather
(row_cols caches the after-columns of rows dirtied earlier).  When capped
rendering exhausts the known candidates of a constraint that still has
unknown ones (NeedsFullSweep), the driver falls back to a full sweep, which
rebuilds this state.

This makes the production audit loop's cost proportional to cluster churn,
not cluster size — the reference re-evaluates everything every interval
(pkg/audit/manager.go:406-431): the delta program's intermediates are
[C, d], not [C, R].
"""

from __future__ import annotations

from bisect import insort
from typing import Dict, List, NamedTuple, Optional, Sequence, Tuple

import numpy as np


class NeedsFullSweep(Exception):
    """Capped rendering needs candidates beyond the known horizon."""


class RenderEntry(NamedTuple):
    """What one constraint's capped walk READ, and the Result slice it
    produced (DeltaState.render_cache, driver._render_capped): the slice
    is a pure function of the cap, the constraint (the state's cs_epoch)
    and the packed content of the rows the walk visited, in order.  Rows
    past the one at which the cap was reached, and the cluster-wide
    candidate count, move only the reported total, which a hit
    recomputes — so churn elsewhere in the cluster never costs a
    re-render."""

    cap: int
    walked: Tuple[int, ...]  # rows visited in order, tombstoned included
    gens: Tuple[int, ...]    # row_gens() of them when the walk ran
    capped: bool             # a further candidate stood behind the cap
    n_cand: int              # the count the walk saw
    results: tuple           # the kept Result slice

    def serves(self, cap: int, cand: Sequence[int], n_cand: int,
               row_gen: Sequence[int]) -> bool:
        """True when a walk over `cand` (the constraint's known candidate
        rows now) against the pack's `row_gen` would visit the same rows
        with the same content and end the same way."""
        n = len(self.walked)
        if self.cap != cap:
            return False
        if self.capped:
            # the walk ends capped only while a candidate stands behind
            # the walked prefix (any: the cap check precedes the row's)
            if len(cand) <= n:
                return False
        elif len(cand) != n or n_cand != self.n_cand:
            # the walk consumed every candidate: one appended after its
            # last row (or one unknown past the horizon) extends it
            return False
        return (tuple(cand[:n]) == self.walked
                and row_gens(row_gen, self.walked) == self.gens)


def row_gens(row_gen: Sequence[int], rows: Sequence[int]) -> Tuple[int, ...]:
    """The pack generations of `rows`; -1 where the pack has no such row
    (the mask's padding past its last row)."""
    n = len(row_gen)
    return tuple([row_gen[r] if r < n else -1 for r in rows])


import atexit as _atexit
import threading as _threading
import weakref as _weakref
from ..util import join_thread

_BG_THREADS = _weakref.WeakSet()

# Set at interpreter exit (and by App.stop): long-lived cooperative
# workers (the routing-calibration loop) wait on this instead of
# sleeping, so a process that exits without App.stop() — signal, short
# CLI run — does not stall shutdown for a full sleep interval per live
# thread (advisor r4).
BG_STOP = _threading.Event()


@_atexit.register
def _join_bg_threads():
    # interpreter exit JOINS background workers first: a thread killed
    # mid-jax-dispatch aborts the runtime's teardown (observed as a gloo
    # terminate in the multi-host lane).  atexit hooks run LIFO, so this
    # one (registered after jax's import-time hooks) runs before jax
    # tears down.
    BG_STOP.set()
    for t in list(_BG_THREADS):
        join_thread(t, 120.0, "background mask resolution")


def spawn_bg(name: str, target):
    """Daemon worker for background warm-ups, joined at interpreter exit
    (see _join_bg_threads)."""
    t = _threading.Thread(target=target, daemon=True, name=name)
    _BG_THREADS.add(t)
    t.start()
    return t


class MaskSource:
    """Device-resident [C, R] base candidate mask, dispatched LAZILY and
    (on the capped path) never fetched.

    Why lazy: the capped full sweep fetches only the [C, 1+K] reduction.
    Materializing the [C, R] mask as a co-output of that dispatch writes
    a C x R array to HBM on every sweep that nothing may ever read.
    Instead the mask is its own dispatch, issued only when the delta path
    (or the uncapped audit) first needs it, against the SAME committed
    device input buffers the reduction ran on — the scatter updater never
    donates, so those buffers stay valid as the base state even after
    later host-side row packs."""

    #: peek() sentinel: a background resolver owns the resolution
    BUSY = object()

    def __init__(self, thunk):
        import threading

        self._lock = threading.Lock()
        self._thunk = thunk
        self._val = None
        self._done = threading.Event()
        # flipped before the resolver thread starts (cleared by it on
        # failure) so peek() can distinguish "resolver scheduled" from
        # "nobody is resolving" — without it a caller racing
        # Thread.start() would pay the whole trace/compile synchronously
        self._resolving = False

    @classmethod
    def resolved(cls, val):
        src = cls(None)
        src._val = val
        src._done.set()
        return src

    def get(self):
        with self._lock:
            if self._val is None:
                try:
                    self._val = self._thunk()
                except Exception:
                    # wake peek() waiters: _val stays None and _resolving
                    # clears, so they fall into the contained sync-get
                    # path instead of sleeping out the full timeout
                    self._done.set()
                    raise
                finally:
                    self._resolving = False
                self._thunk = None
                self._done.set()
            return self._val

    def peek(self, wait_s: float = 0.0):
        """The mask if it resolves within wait_s; None if unresolved with
        no resolver running (the caller should get() synchronously); BUSY
        when a background resolver is still working past wait_s (the
        caller should fall back to a full sweep rather than block behind
        the trace/compile)."""
        if self._done.wait(wait_s if self._resolving else 0):
            return self._val
        return self.BUSY if self._resolving else None

    def prefetch(self, after=None):
        """Resolve on a daemon thread: the mask executable's trace/compile
        (and its dispatch) happen in the background right after the full
        sweep instead of landing on the first delta sweep's latency.
        `after(mask)` runs on the same thread once resolved (best-effort;
        used to warm downstream executables against the mask)."""
        self._resolving = True

        def run():
            try:
                val = self.get()
            except Exception:
                return  # next get() retries; peek no longer reports BUSY
            if after is not None:
                try:
                    after(val)
                except Exception:
                    # the after-hook warms downstream executables; a
                    # defect there costs the warm start, not correctness
                    # — but it must be visible when it happens
                    import logging

                    logging.getLogger("gatekeeper.deltasweep").warning(
                        "mask prefetch after-hook failed", exc_info=True,
                    )

        spawn_bg("gk-mask-prefetch", run)


class DeltaState:
    """Host-side incremental reduction state for one (constraint side,
    pack layout) generation.  All access under the driver lock."""

    def __init__(self, counts: np.ndarray, topk: np.ndarray, K: int,
                 mask_src: "MaskSource", cs_epoch: int, layout_gen: int,
                 store_epoch: int, crow=None, mesh_width: int = 1):
        self.K = K
        # topology stamp: the basis's mask placement is only valid under
        # the sweep sharding it was produced by (driver._try_delta refuses
        # a drifted basis and rebases via a full sweep)
        self.mesh_width = int(mesh_width)
        self.counts = counts.astype(np.int64).copy()
        self.cand: List[List[int]] = []
        self.horizon: List[Optional[int]] = []
        for ci in range(len(counts)):
            idxs = [int(r) for r in topk[ci] if r >= 0]
            self.cand.append(idxs)  # ascending (stable top_k of 0/1 mask)
            if counts[ci] <= len(idxs):
                self.horizon.append(None)  # complete knowledge
            else:
                self.horizon.append(idxs[-1] if idxs else -1)
        # after-columns of rows dirtied since the full sweep; the
        # before-column of a newly-dirtied row is gathered from mask_dev
        self.row_cols: Dict[int, np.ndarray] = {}
        # lazily-fetched host copy of the base mask for the UNCAPPED audit
        # path: fetched once per state generation, then kept current by
        # overwriting only the columns dirtied since the last patch
        # (pending_mask_rows; absolute values, so patching is idempotent)
        self.host_mask: Optional[np.ndarray] = None
        self.pending_mask_rows: set = set()
        # per-constraint rendered-result reuse across sweeps: (kind, name)
        # -> RenderEntry, keyed on the rows the capped walk read (driver
        # _render_capped); traced renders bypass it
        self.render_cache: Dict = {}
        self.mask_src = mask_src
        # ordered-constraint -> group-major mask row (device mask/delta
        # outputs are [C_total]-row; host state here is per ordered
        # constraint)
        self.crow = crow if crow is not None else np.arange(
            len(counts), dtype=np.int64)
        self.cs_epoch = cs_epoch
        self.layout_gen = layout_gen
        self.store_epoch = store_epoch

    @classmethod
    def from_restore(cls, counts, cand, horizon, crow, K, mask_src,
                     row_cols, render_cache, cs_epoch, layout_gen,
                     store_epoch, mesh_width: int = 1):
        """Rebuild a state persisted by the snapshot subsystem
        (gatekeeper_tpu/snapshot/): fields are installed verbatim rather
        than derived from a fresh device reduction, so a restarted
        process's first capped sweep can run the O(churn) delta path
        against the restored basis instead of a full [C, R] dispatch."""
        st = cls.__new__(cls)
        st.K = K
        st.counts = np.asarray(counts, np.int64).copy()
        st.cand = [list(map(int, c)) for c in cand]
        st.horizon = list(horizon)
        st.row_cols = dict(row_cols)
        st.host_mask = None
        st.pending_mask_rows = set()
        st.render_cache = dict(render_cache)
        st.mask_src = mask_src
        st.crow = np.asarray(crow, np.int64)
        st.cs_epoch = cs_epoch
        st.layout_gen = layout_gen
        st.store_epoch = store_epoch
        st.mesh_width = int(mesh_width)
        return st

    # ---- incremental update ----------------------------------------------

    def old_column(self, r: int) -> Optional[np.ndarray]:
        """The current candidate column for row r, or None when it must be
        gathered from the resident full-sweep mask."""
        return self.row_cols.get(r)

    def apply_row(self, r: int, old_col: np.ndarray, new_col: np.ndarray):
        delta = new_col.astype(np.int64) - old_col.astype(np.int64)
        changed = np.nonzero(delta)[0]
        self.counts[changed] += delta[changed]
        for ci in changed:
            h = self.horizon[ci]
            lst = self.cand[ci]
            if h is not None and r > h:
                continue  # beyond known territory; counts tracked only
            if delta[ci] < 0:
                try:
                    lst.remove(r)
                except ValueError:
                    pass
            else:
                insort(lst, r)
        self.row_cols[r] = new_col.astype(bool)
        self.pending_mask_rows.add(r)
