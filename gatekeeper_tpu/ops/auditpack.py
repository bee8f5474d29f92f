"""Incremental audit packing: the inventory as resident columnar arrays.

The production audit loop sweeps a mostly-unchanged inventory every interval
(reference pkg/audit/manager.go:406-431 re-lists everything; here the
replicated store IS the source).  Rebuilding reviews + packed tensors for
100k resources costs seconds; this cache keeps the packed row-major arrays
resident and applies only the store's change log per sweep:

  - one row per cached object, stable across sweeps (tombstoned on delete,
    reused from a free list)
  - one batched re-pack per sync: every row the change log touched goes
    through ONE pack_reviews and ONE extract_columns call, and each
    resident leaf takes the batch in one fancy-indexed write (widths grow
    once per leaf, to the batch's widest row)
  - Namespace objects re-pack every row in that namespace, in that same
    batch: packed rows bake in namespaceSelector label resolution +
    autoreject against the cached Namespace (ops/pack.py ns_mode), and a
    stale row could UNDER-approximate the device mask, which the exactness
    filter cannot repair
  - wipes, subtree deletions, layout changes (new column specs) and
    change-log overruns fall back to a full rebuild

Array shapes are bucketed (powers of two) so the fused executable survives
row growth until a bucket boundary.  SURVEY.md section 7 stage 4:
"inventory store as columnar host arrays with incremental device updates".
"""

from __future__ import annotations

from typing import Dict, List, Optional, Tuple

import numpy as np

from .columns import extract_columns
from .interning import Interner
from .pack import PAD, UNDEF, pack_reviews

# fill values per review-pack key: what an empty/padded row must contain
_RP_FILL = {
    "group": UNDEF,
    "kind": UNDEF,
    "ns_name": UNDEF,
    "ns_mode": 0,
    "always": False,
    "ns_empty": False,
    "is_ns": False,
    "obj_empty": True,
    "old_empty": True,
    "autoreject": False,
    "valid": False,
    "obj_labels": PAD,
    "old_labels": PAD,
    "ns_labels": PAD,
}

# fill values per column leaf (ops/columns.py encoding)
_COL_FILL = {
    "tcode": 0,  # T_UNDEF
    "sid": Interner.MISSING,
    "num": 0.0,
    "mask": False,
    "ids": Interner.PAD,
}

_NS_PATH_PREFIX = ("cluster", "v1", "Namespace")


def _bucket(n: int, minimum: int = 8) -> int:
    b = max(minimum, 1)
    while b < n:
        b *= 2
    return b


def _path_identity(seg: Tuple[str, ...]) -> Optional[Tuple[str, str, str, str]]:
    """(api, kind, name, namespace) for an object-depth path, else None."""
    if seg[0] == "cluster" and len(seg) == 4:
        return seg[1], seg[2], seg[3], ""
    if seg[0] == "namespace" and len(seg) == 5:
        return seg[2], seg[3], seg[4], seg[1]
    return None  # subtree-depth path: caller falls back to rebuild


class AuditPackCache:
    """Resident packed audit inputs, synced to an InventoryStore's change
    log.  All access happens under the owning driver's lock."""

    # past max(1024, n_rows / REBUILD_FRACTION) changed paths sync rebuilds
    # instead of patching.  The value dates from per-row packing (~330us a
    # row on the chip host: ledger, PR 27) and was not retuned when the
    # patch became one batch (~22us a row there, the whole pack stage over
    # its 200 rows: chip run, PR 28; PERF.md section 5).  A rebuild also
    # resets every row generation (the render caches start over) and forces
    # a re-upload, so the break-even is not the packers' alone.
    REBUILD_FRACTION = 8

    def __init__(self):
        self.synced_epoch = -1
        self.col_keys: Optional[tuple] = None
        self.reviews: List[Optional[dict]] = []
        self.row_of: Dict[Tuple[str, ...], int] = {}
        self.row_path: List[Optional[Tuple[str, ...]]] = []
        self.row_ns: List[str] = []
        self.row_gen: List[int] = []  # bumped per re-pack; memo invalidation
        self.ns_rows: Dict[str, set] = {}
        self.free: List[int] = []
        self.rp: Optional[Dict[str, np.ndarray]] = None
        self.cols: Optional[Dict[Tuple, Dict[str, np.ndarray]]] = None
        self.capacity = 0
        self.n_rows = 0
        self._gen = 0
        # device-residency bookkeeping (consumed by the driver): rows whose
        # packed contents changed since the last take_dirty(), and a layout
        # generation bumped whenever array identities/shapes change (rebuild,
        # capacity growth, width growth, new column leaf) — a layout bump
        # means per-row scatter updates can no longer patch the device copy
        # and a full re-upload is required.
        self.dirty: set = set()
        self.layout_gen = 0
        # bumped ONLY when row identities are reassigned (full rebuild /
        # snapshot adoption) — distinct from layout_gen, which also bumps
        # on capacity/width growth where row ids stay stable.  The join
        # index (ops/joinkernel.py JoinState) keys on this: across growth
        # it can diff old-vs-new key groups by row id; across a rebuild
        # it must start fresh (every row generation was reset anyway).
        self.rebuild_gen = 0
        # second dirty channel, drained by the incremental delta sweep
        # (ops/deltasweep.py) independently of the device-scatter channel
        # above, so neither consumer starves the other and the delta path
        # never rescans cumulative churn (advisor r3)
        self.delta_dirty: set = set()
        # third channel, drained by the join index (ops/joinkernel.py
        # JoinState.commit) wherever it is brought current: a sweep's
        # join_commit, or the review path between sweeps
        self.join_dirty: set = set()
        # rows packed (batch re-packs and rebuilds) since the last
        # take_packed_rows(): the driver publishes it as `pack_rows`
        self.packed_rows = 0

    # ---- snapshot restore (gatekeeper_tpu/snapshot/) ----------------------

    def adopt_restored(self, rp, cols, col_keys, reviews, row_path, row_ns,
                       row_gen, free, n_rows, synced_epoch):
        """Install state deserialized from a snapshot (under the owning
        driver's lock).  Arrays arrive writable and exactly as a previous
        process's _rebuild/_pack_rows left them; reviews and row
        generations are restored verbatim (generations key the render
        caches, so preserving them is what lets an unchanged constraint
        reuse its persisted rendered results).  layout_gen bumps so
        device copies re-place."""
        self.rp = rp
        self.cols = cols
        self.col_keys = col_keys
        self.capacity = len(next(iter(rp.values())))
        self.n_rows = n_rows
        self.reviews = list(reviews)
        self.row_path = [tuple(p) if p is not None else None for p in row_path]
        self.row_of = {
            p: i for i, p in enumerate(self.row_path) if p is not None
        }
        self.row_ns = list(row_ns)
        self.row_gen = [int(g) for g in row_gen]
        self._gen = max(self.row_gen, default=0)
        self.ns_rows = {}
        for i, ns in enumerate(self.row_ns):
            if ns:
                self.ns_rows.setdefault(ns, set()).add(i)
        self.free = list(free)
        self.synced_epoch = synced_epoch
        self.dirty = set()
        self.delta_dirty = set()
        self.join_dirty = set()
        self.layout_gen += 1
        self.rebuild_gen += 1

    def bump_row_gen(self, rows):
        """Invalidate the render-cache generations of `rows` WITHOUT
        marking them dirty: their packed content is unchanged (the device
        state is current), but something they render from — a join key
        group's aggregate — moved (ops/joinkernel.py)."""
        for r in rows:
            if 0 <= r < len(self.row_gen):
                self._gen += 1
                self.row_gen[r] = self._gen

    def take_dirty(self) -> set:
        d = self.dirty
        self.dirty = set()
        return d

    def take_delta_dirty(self) -> set:
        d = self.delta_dirty
        self.delta_dirty = set()
        return d

    def take_join_dirty(self) -> set:
        d = self.join_dirty
        self.join_dirty = set()
        return d

    def take_packed_rows(self) -> int:
        n = self.packed_rows
        self.packed_rows = 0
        return n

    # ---- public -----------------------------------------------------------

    def sync(self, driver, col_specs) -> bool:
        """Bring the resident arrays up to date with driver.store.  Returns
        True when anything changed (mask-level caches must invalidate)."""
        store = driver.store
        keys = tuple(sorted(s.key for s in col_specs))
        if self.rp is None or self.col_keys != keys:
            self._rebuild(driver, col_specs)
            self.col_keys = keys
            return True
        if store.epoch == self.synced_epoch:
            return False
        changes = store.changes_since(self.synced_epoch)
        if changes is None:
            self._rebuild(driver, col_specs)
            return True
        seen = set()
        ordered_changes = []
        for seg in reversed(changes):  # keep only the LAST change per path
            if seg is None or _path_identity(seg) is None:
                self._rebuild(driver, col_specs)
                return True
            if seg in seen:
                continue
            seen.add(seg)
            ordered_changes.append(seg)
        # threshold on UNIQUE paths (a flapping object logs many entries
        # for one row; the rebuild-vs-patch tradeoff is about rows touched)
        if len(ordered_changes) > max(
            1024, self.n_rows // self.REBUILD_FRACTION
        ):
            self._rebuild(driver, col_specs)
            return True
        # pass 1, bookkeeping per path in log order: tombstones land now
        # (a freed row may be re-used further down this same log), upserts
        # only claim their row and queue it
        rows: List[int] = []
        ns_repack: set = set()
        for seg in reversed(ordered_changes):
            row = self._apply(driver, seg)
            if row is not None:
                rows.append(row)
            if seg[:3] == _NS_PATH_PREFIX:
                ns_repack.add(seg[3])
        if ns_repack:
            queued = set(rows)
            for ns in sorted(ns_repack):
                for r in sorted(self.ns_rows.get(ns, ())):
                    if r not in queued and self.reviews[r] is not None:
                        queued.add(r)
                        rows.append(r)
        # pass 2: one batch for everything queued
        if rows:
            self._pack_rows(driver, rows, col_specs)
        self.synced_epoch = store.epoch
        return True

    # ---- rebuild ----------------------------------------------------------

    def _rebuild(self, driver, col_specs):
        from ..engine.value import thaw

        store = driver.store
        objs = list(store.iter_objects())
        reviews = []
        paths = []
        for obj_frozen, api, kind, name, ns in objs:
            reviews.append(
                driver.target.make_audit_review(thaw(obj_frozen), api, kind, name, ns)
            )
            if ns:
                paths.append(("namespace", ns, api, kind, name))
            else:
                paths.append(("cluster", api, kind, name))
        rp = pack_reviews(reviews, driver.interner, store.cached_namespace)
        rows = len(rp.arrays["valid"])
        cols = extract_columns(reviews, col_specs, driver.interner, rows)
        self.rp = dict(rp.arrays)
        self.cols = {k: dict(v) for k, v in cols.items()}
        self.capacity = rows
        self.n_rows = len(reviews)
        self.reviews = list(reviews)
        self.row_path = list(paths)
        self.row_of = {p: i for i, p in enumerate(paths)}
        self.row_ns = [r.get("namespace", "") or "" for r in reviews]
        self._gen += 1
        self.row_gen = [self._gen] * len(reviews)
        self.ns_rows = {}
        for i, ns in enumerate(self.row_ns):
            if ns:
                self.ns_rows.setdefault(ns, set()).add(i)
        self.free = []
        self.synced_epoch = store.epoch
        self.dirty = set()
        self.delta_dirty = set()
        self.join_dirty = set()
        self.layout_gen += 1
        self.rebuild_gen += 1
        self.packed_rows += len(reviews)

    # ---- incremental ------------------------------------------------------

    def _apply(self, driver, seg: Tuple[str, ...]) -> Optional[int]:
        """Row bookkeeping for one changed path.  A deletion tombstones
        its row at once; an upsert installs the new review in its row
        (allocating one if the path is new) and returns the row for the
        sync's batch pack."""
        from ..engine.value import thaw

        api, kind, name, ns = _path_identity(seg)
        obj = driver.store.get(seg)
        row = self.row_of.get(seg)
        if obj is None:
            if row is not None:
                self._tombstone(row, seg)
            return None
        review = driver.target.make_audit_review(thaw(obj), api, kind, name, ns)
        if row is None:
            row = self._alloc_row()
            self.row_of[seg] = row
            self.row_path[row] = seg
        self.reviews[row] = review
        old_ns = self.row_ns[row]
        if old_ns and old_ns != ns:
            self.ns_rows.get(old_ns, set()).discard(row)
        self.row_ns[row] = ns
        if ns:
            self.ns_rows.setdefault(ns, set()).add(row)
        return row

    def _tombstone(self, row: int, seg: Tuple[str, ...]):
        self.reviews[row] = None
        self.row_of.pop(seg, None)
        self.row_path[row] = None
        ns = self.row_ns[row]
        if ns:
            self.ns_rows.get(ns, set()).discard(row)
        self.row_ns[row] = ""
        self.rp["valid"][row] = False
        self._gen += 1
        self.row_gen[row] = self._gen
        self.dirty.add(row)
        self.delta_dirty.add(row)
        self.join_dirty.add(row)
        self.free.append(row)

    def _alloc_row(self) -> int:
        if self.free:
            return self.free.pop()
        if self.n_rows >= self.capacity:
            self._grow_rows(_bucket(self.n_rows + 1))
        r = self.n_rows
        self.n_rows += 1
        self.reviews.append(None)
        self.row_path.append(None)
        self.row_ns.append("")
        self.row_gen.append(0)
        return r

    def _grow_rows(self, new_capacity: int):
        def grow(arr: np.ndarray, fill):
            out = np.full((new_capacity,) + arr.shape[1:], fill, dtype=arr.dtype)
            out[: arr.shape[0]] = arr
            return out

        self.rp = {k: grow(v, _RP_FILL[k]) for k, v in self.rp.items()}
        self.cols = {
            ck: {leaf: grow(arr, _COL_FILL[leaf]) for leaf, arr in leaves.items()}
            for ck, leaves in self.cols.items()
        }
        self.capacity = new_capacity
        self.layout_gen += 1

    def _write_rows(self, holder: dict, key, idx: np.ndarray,
                    src: np.ndarray, fill):
        """Write a packed batch src[i] -> row idx[i] of one resident leaf.
        The leaf's trailing (width) dims grow once, to the batch's, when
        the batch is wider; a narrower batch resets its rows to the fill
        value first so no row keeps a stale tail."""
        dst = holder.get(key)
        width = src.shape[1:]
        if dst is None:
            dst = holder[key] = np.full(
                (self.capacity,) + width, fill, dtype=src.dtype
            )
            self.layout_gen += 1  # new leaf: device tree is stale
        else:
            target = tuple(max(d, s) for d, s in zip(dst.shape[1:], width))
            if target != dst.shape[1:]:
                grown = np.full(
                    (dst.shape[0],) + target, fill, dtype=dst.dtype
                )
                grown[tuple(slice(0, d) for d in dst.shape)] = dst
                dst = holder[key] = grown
                self.layout_gen += 1  # shape changed: device copy is stale
        if width == dst.shape[1:]:
            dst[idx] = src
        else:
            dst[idx] = fill
            dst[(idx,) + tuple(slice(0, s) for s in width)] = src

    def _pack_rows(self, driver, rows: List[int], col_specs):
        """Re-pack `rows` (distinct, each holding its current review) as
        one batch: the same packers _rebuild uses, one call each, and one
        write per leaf.  Every row gets a generation of its own (the
        render caches key on it)."""
        reviews = [self.reviews[r] for r in rows]
        idx = np.asarray(rows, dtype=np.intp)
        rp = pack_reviews(
            reviews, driver.interner, driver.store.cached_namespace,
            bucket_rows=False,
        )
        for key, src in rp.arrays.items():
            self._write_rows(self.rp, key, idx, src, _RP_FILL[key])
        cols = extract_columns(
            reviews, col_specs, driver.interner, len(reviews)
        )
        for ckey, leaves in cols.items():
            holder = self.cols.setdefault(ckey, {})
            for leaf, src in leaves.items():
                self._write_rows(holder, leaf, idx, src, _COL_FILL[leaf])
        for r in rows:
            self._gen += 1
            self.row_gen[r] = self._gen
        self.dirty.update(rows)
        self.delta_dirty.update(rows)
        self.join_dirty.update(rows)
        self.packed_rows += len(rows)
