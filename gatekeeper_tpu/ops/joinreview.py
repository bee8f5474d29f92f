"""The review path's join binding: an admission batch resolves its
referential cells through the join index (ops/joinkernel.py JoinState),
not through an interpreter walk of the inventory.

A cell is (constraint of a template that reads ``data.inventory``,
review) where the constraint is a candidate for the review: flagged by
the device or numpy mask, or matched by the interpreter tier's walk.
For a join-safe template (every inventory read a classified plan,
TpuDriver._join_safe) the cell is resolved from the index, brought
current with the store first (TpuDriver._join_index_current):

- the review object's keys are read with the plan's own column spec and
  looked up (JoinState.review_lookup); where the program's join
  conditions are exactly false the cell cannot raise: its mask bit is
  cleared, the interpreter is never called (``cleared``);
- otherwise the interpreter renders the cell against the pruned
  inventory of the keys' provider rows: O(group), and byte for byte what
  the full inventory gives, because a join-safe program reads nothing
  else (``rendered``).

Any other cell — a template with an unclassified inventory read, a key
the normalizer refuses, a provider row outside the pack — falls back to
what the review path did before: the full inventory and the interpreter
(``fallback``).  All of it runs inside the batch clock's ``join_lookup``
stage; the counts reach the registry once per batch.
"""

from __future__ import annotations

from typing import Dict, Optional, Tuple

import numpy as np

from . import joinkernel

#: resolve()'s answer for a cell the index proved cannot raise
CLEARED = object()


class ReviewJoins:
    """One admission batch's referential cells.  Made (and used) under
    the driver's lock; ``flush`` books its counts."""

    __slots__ = ("driver", "reviews", "full", "_js", "_cells", "_lookups",
                 "n_cleared", "n_rendered", "n_fallback", "n_rows")

    def __init__(self, driver, reviews, full_inventory):
        self.driver = driver
        self.reviews = reviews
        self.full = full_inventory
        self._js = False   # the current index, fetched on first need
        self._cells: Dict[Tuple[str, int], object] = {}
        self._lookups: Dict[Tuple[str, int], object] = {}
        self.n_cleared = self.n_rendered = self.n_fallback = 0
        self.n_rows = 0

    def referential(self, kind: str) -> bool:
        return kind in self.driver._inventory_kinds()

    def resolve(self, kind: str, ri: int):
        """CLEARED, or the inventory to render cell (kind, review ri)
        against.  One lookup per (kind, review): the constraints of one
        kind share it, each counted as a cell of its own."""
        key = (kind, ri)
        got = self._cells.get(key)
        if got is None:
            got = self._cells[key] = self._resolve(kind, ri)
        if got is CLEARED:
            self.n_cleared += 1
        elif got is self.full:
            self.n_fallback += 1
        else:
            self.n_rendered += 1
            self.n_rows += len(self._lookups[key].rows)
        return got

    def _resolve(self, kind: str, ri: int):
        d = self.driver
        if not d._join_safe(kind):
            return self.full
        if self._js is False:
            self._js = d._join_index_current()
        js = self._js
        if js is None:
            return self.full
        look = js.review_lookup(
            d.programs[kind], self.reviews[ri], d._audit_pack, d.interner
        )
        if look is None:
            return self.full
        self._lookups[(kind, ri)] = look
        if look.verdict is False:
            return CLEARED
        pruned = d._inventory_of_rows(look.rows)
        return self.full if pruned is None else pruned

    def refine(self, ordered, mask_np: np.ndarray) -> Dict:
        """The mask tiers: every flagged cell of a referential kind gets
        its exact join value (a cleared cell leaves ``mask_np``) and the
        rest their inventories -> {(review, constraint index):
        inventory} for _render_masked."""
        out: Dict[Tuple[int, int], object] = {}
        for i, (kind, _name, _constraint) in enumerate(ordered):
            if not self.referential(kind):
                continue
            for ri in np.nonzero(mask_np[i])[0].tolist():
                inv = self.resolve(kind, ri)
                if inv is CLEARED:
                    mask_np[i, ri] = False
                else:
                    out[(ri, i)] = inv
        return out

    def note_empty(self, kind: str, name: str, constraint: dict, ri: int):
        """A mask-flagged cell rendered nothing: where the index said
        the join conditions hold exactly and the constraint is strict
        (TpuDriver._join_strict), that is a divergence of index and
        oracle (GK_JOIN_ASSERT raises), unless two provider rows of the
        group are one object under two groupVersions (the documented
        corner, docs/referential.md)."""
        look = self._lookups.get((kind, ri))
        if look is None or look.verdict is not True:
            return
        if not self.driver._join_strict(kind, constraint):
            return
        if joinkernel.share_an_identity(self.driver._audit_pack, look.rows):
            return
        joinkernel.note_false_positive(kind, name, ri)

    def flush(self):
        if self.n_cleared or self.n_rendered or self.n_fallback:
            from ..metrics.catalog import record_admission_join

            record_admission_join(
                self.n_cleared, self.n_rendered, self.n_fallback,
                self.n_rows,
            )
            self.n_cleared = self.n_rendered = self.n_fallback = 0
            self.n_rows = 0

