"""String interning: the bridge between JSON documents and integer tensors.

All strings that participate in device-side comparisons (kinds, groups,
namespaces, names, label keys/values, image strings, ...) are interned into
one global vocabulary.  String predicates against constraint parameters
(startswith, regex, ...) become host-precomputed boolean lookup tables over
the vocabulary, gathered on device — the classic dictionary-encoding trick,
which turns per-string work into O(unique values) host work and O(1) device
gathers.
"""

from __future__ import annotations

import threading
from typing import Callable, Dict, List

import numpy as np


class Interner:
    """Append-only string -> int32 id table.  id 0 is reserved for the empty
    string; negative ids are sentinels (-1 missing, -2 pad, ...)."""

    MISSING = -1
    PAD = -2
    NON_STRING = -3

    def __init__(self):
        self._ids: Dict[str, int] = {"": 0}
        self._strings: List[str] = [""]
        self._lock = threading.Lock()

    def __len__(self) -> int:
        return len(self._strings)

    def intern(self, s: str) -> int:
        i = self._ids.get(s)
        if i is not None:
            return i
        with self._lock:
            i = self._ids.get(s)
            if i is None:
                i = len(self._strings)
                self._ids[s] = i
                self._strings.append(s)
            return i

    def find(self, s: str) -> int:
        """The id of an already-interned string, MISSING otherwise: a
        lookup that must not grow the vocabulary (admission-time join
        keys — a key nobody interned is a key no inventory row holds)."""
        return self._ids.get(s, self.MISSING)

    def intern_value(self, v) -> int:
        """Intern strings; map non-strings to sentinels so id-equality stays
        sound (two equal strings share an id; a non-string never equals)."""
        if isinstance(v, str):
            return self.intern(v)
        return self.NON_STRING

    def lookup(self, i: int) -> str:
        return self._strings[i]

    def snapshot_size(self) -> int:
        return len(self._strings)


class PredicateTable:
    """Lazy bool table over the vocabulary for a unary string predicate
    (e.g. 'startswith with prefix P').  Grows with the vocabulary; the
    device side sees a dense uint8 vector indexed by string id."""

    def __init__(self, interner: Interner, fn: Callable[[str], bool]):
        self.interner = interner
        self.fn = fn
        self._table = np.zeros(0, dtype=np.uint8)

    def dense(self) -> np.ndarray:
        n = self.interner.snapshot_size()
        if len(self._table) < n:
            old = len(self._table)
            grown = np.zeros(n, dtype=np.uint8)
            grown[:old] = self._table
            for i in range(old, n):
                try:
                    grown[i] = 1 if self.fn(self.interner.lookup(i)) else 0
                except Exception:
                    grown[i] = 0
            self._table = grown
        return self._table


class ValueMap:
    """Lazy float/flag map over the vocabulary for a pure unary function of a
    string value (e.g. canonify_cpu): host computes once per unique value,
    device gathers per row."""

    def __init__(self, interner: Interner, fn: Callable[[str], float]):
        self.interner = interner
        self.fn = fn  # returns float or raises/None for "undefined"
        self._vals = np.zeros(0, dtype=np.float64)
        self._ok = np.zeros(0, dtype=np.uint8)

    def dense(self):
        n = self.interner.snapshot_size()
        if len(self._vals) < n:
            old = len(self._vals)
            vals = np.zeros(n, dtype=np.float64)
            ok = np.zeros(n, dtype=np.uint8)
            vals[:old] = self._vals
            ok[:old] = self._ok
            for i in range(old, n):
                try:
                    v = self.fn(self.interner.lookup(i))
                    if v is not None:
                        vals[i] = float(v)
                        ok[i] = 1
                # gklint: disable=swallowed-exception -- by contract a
                # per-value extractor failure means "feature absent":
                # ok[i] stays 0 and the kernel masks the cell out
                except Exception:
                    pass
            self._vals, self._ok = vals, ok
        return self._vals, self._ok
