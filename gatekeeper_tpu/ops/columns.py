"""Columnar feature extraction: JSON resources -> padded numpy arrays.

Column kinds:
- scalar: one value per resource at a []-free path                   -> [R]
- slot:   per-entity values, where entities come from iteration
          paths (arrays, flattened across all [] levels and unioned
          over paths — e.g. containers[] + initContainers[]) and the
          value is read at a []-free path relative to the entity.
          All slot columns sharing the same iteration paths are
          ALIGNED on the slot axis                                   -> [R, S]
- keyset: the set of (truthy) object keys found at paths (arrays
          allowed), minus excluded literals, per resource            -> [R, K]

Scalar/slot columns carry a type code per cell plus the representation
arrays the predicates need:

  tcode: 0 undefined, 1 null, 2 false, 3 true, 4 number, 5 string, 6 composite
  sid:   interned string id (tcode 5)
  num:   float value (tcode 4)

Rego statement truthiness == tcode not in {0, 2}; OPA's cross-type ordering
(null < bool < number < string < composites) maps to tcode rank for exact
vectorized comparisons.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, List, Sequence, Tuple

import numpy as np

from .interning import Interner

Path = Tuple[str, ...]

T_UNDEF, T_NULL, T_FALSE, T_TRUE, T_NUM, T_STR, T_COMP = range(7)


def parse_path(dotted: str) -> Path:
    """'spec.containers[].image' -> ('spec', 'containers', '[]', 'image')."""
    out: List[str] = []
    for seg in dotted.split("."):
        while seg.endswith("[]"):
            seg = seg[:-2]
            if seg:
                out.append(seg)
            out.append("[]")
            seg = ""
        if seg:
            out.append(seg)
    return tuple(out)


def _walk(obj: Any, path: Path, i: int, out: List[Any]):
    if i == len(path):
        out.append(obj)
        return
    seg = path[i]
    if seg == "[]":
        if isinstance(obj, list):
            for item in obj:
                _walk(item, path, i + 1, out)
        return
    if isinstance(obj, dict) and seg in obj:
        _walk(obj[seg], path, i + 1, out)


def _get_rel(obj: Any, path: Path):
    """[]-free relative path; returns _ABSENT when missing."""
    cur = obj
    for seg in path:
        if isinstance(cur, dict) and seg in cur:
            cur = cur[seg]
        else:
            return _ABSENT
    return cur


class _Absent:
    def __repr__(self):
        return "<absent>"


_ABSENT = _Absent()


@dataclass(frozen=True)
class ColumnSpec:
    kind: str  # "scalar" | "slot" | "keyset" | "joinkey"
    iter_paths: Tuple[Path, ...]  # slot/keyset entity sources ([] allowed)
    rel_path: Path = ()  # []-free value path (scalar: the full path)
    exclude: Tuple[str, ...] = ()  # keyset: excluded key literals
    # joinkey: how the value at the path becomes the key.  () is the value
    # itself; ("joinpairs", kv_sep, item_sep) is a key COMPUTED from a map
    # (joinkernel.join_pairs — upstream's flatten_selector)
    form: Tuple[str, ...] = ()

    @property
    def key(self):
        base = (self.kind, self.iter_paths, self.rel_path, self.exclude)
        return base + (self.form,) if self.form else base

    @property
    def iter_key(self):
        """Slot-axis alignment group."""
        return self.iter_paths


def _bucket(n: int, minimum: int = 1) -> int:
    b = max(minimum, 1)
    while b < n:
        b *= 2
    return b


def _encode(values: List[Any], interner: Interner, shape) -> Dict[str, np.ndarray]:
    n = len(values)
    tcode = np.zeros(n, np.int8)
    sid = np.full(n, Interner.MISSING, np.int32)
    num = np.zeros(n, np.float64)
    for i, v in enumerate(values):
        if v is _ABSENT:
            tcode[i] = T_UNDEF
        elif v is None:
            tcode[i] = T_NULL
        elif v is True:
            tcode[i] = T_TRUE
        elif v is False:
            tcode[i] = T_FALSE
        elif isinstance(v, str):
            tcode[i] = T_STR
            sid[i] = interner.intern(v)
        elif isinstance(v, (int, float)):
            tcode[i] = T_NUM
            num[i] = float(v)
        else:
            tcode[i] = T_COMP
    return {
        "tcode": tcode.reshape(shape),
        "sid": sid.reshape(shape),
        "num": num.reshape(shape),
    }


def joinkey_values(resource, spec: "ColumnSpec") -> List[Any]:
    """The raw join-key values one resource yields under a joinkey spec,
    before normalization: one per iterated entity for a slot key
    (``_ABSENT`` where the entity lacks the path), one or none for a
    scalar key, exactly one for a computed key (an absent map flattens
    to the empty string, as the Rego's does).  The packed column
    (:func:`_extract_joinkey`) and the admission path's index lookup
    (joinkernel.JoinState.review_lookup) both read keys through this."""
    if not spec.iter_paths:
        hits: List[Any] = []
        _walk(resource, spec.rel_path, 0, hits)
        if spec.form:
            from .joinkernel import join_pairs

            return [join_pairs(hits[0] if hits else None, *spec.form[1:])]
        return hits[:1]
    if spec.form:
        raise ValueError("computed join keys are scalar")
    ents: List[Any] = []
    for p in spec.iter_paths:
        _walk(resource, p, 0, ents)
    return [_get_rel(ent, spec.rel_path) for ent in ents]


def _extract_joinkey(
    resources, spec: "ColumnSpec", interner: Interner, rows: int
) -> Dict[str, np.ndarray]:
    """Cross-resource join-key column (ops/joinkernel.py): values at the
    spec's path, NORMALIZED through the one type-tagged key form
    (normalize_join_key) and interned — so an int label value and its
    string twin can never coerce into one key group.  Scalar keys ->
    {"sid" [R]}; slot keys (iteration paths) -> {"sid", "mask"} [R, S]
    with the slot width bucketed exactly like slot columns over the same
    iteration group (shared axes stay aligned)."""
    from .joinkernel import intern_join_key

    if not spec.iter_paths:  # scalar key
        sid = np.full(rows, Interner.MISSING, np.int32)
        for i, r in enumerate(resources):
            for v in joinkey_values(r, spec):
                sid[i] = intern_join_key(v, interner)
        return {"sid": sid}
    ents = [joinkey_values(r, spec) for r in resources]
    width = _bucket(max((len(e) for e in ents), default=0), 1)
    sid = np.full((rows, width), Interner.MISSING, np.int32)
    mask = np.zeros((rows, width), bool)
    for i, row_ents in enumerate(ents):
        for j, v in enumerate(row_ents):
            mask[i, j] = True
            if v is not _ABSENT:
                sid[i, j] = intern_join_key(v, interner)
    return {"sid": sid, "mask": mask}


def _extract_columns_native(
    native, resources, specs, interner, rows
) -> Dict[Tuple, Dict[str, np.ndarray]]:
    """C++ extraction (same layout/semantics as the Python body below;
    differentially tested in tests/test_native.py)."""
    out: Dict[Tuple, Dict[str, np.ndarray]] = {}
    resources = list(resources)
    n = len(resources)
    ids, strings = interner._ids, interner._strings

    slot_groups: Dict[Tuple, List[ColumnSpec]] = {}
    for spec in specs:
        if spec.kind == "slot":
            slot_groups.setdefault(spec.iter_key, []).append(spec)
    group_entities: Dict[Tuple, list] = {}
    group_width: Dict[Tuple, int] = {}
    for ik in slot_groups:
        ents, maxw = native.slot_entities(resources, tuple(ik))
        group_entities[ik] = ents
        group_width[ik] = _bucket(maxw, 1)

    for spec in specs:
        if spec.kind == "scalar":
            tcode = np.zeros(rows, np.int8)
            sid = np.full(rows, Interner.MISSING, np.int32)
            num = np.zeros(rows, np.float64)
            native.extract_scalar(
                resources, spec.rel_path, tcode, sid, num, ids, strings
            )
            out[spec.key] = {"tcode": tcode, "sid": sid, "num": num}
        elif spec.kind == "slot":
            width = group_width[spec.iter_key]
            tcode = np.zeros((rows, width), np.int8)
            sid = np.full((rows, width), Interner.MISSING, np.int32)
            num = np.zeros((rows, width), np.float64)
            mask = np.zeros((rows, width), bool)
            native.encode_slots(
                group_entities[spec.iter_key], spec.rel_path, width,
                tcode, sid, num, mask, ids, strings,
            )
            out[spec.key] = {"tcode": tcode, "sid": sid, "num": num,
                             "mask": mask}
        elif spec.kind == "keyset":
            flat, counts = native.keyset(
                resources, tuple(spec.iter_paths), spec.rel_path,
                tuple(spec.exclude), ids, strings,
            )
            width = _bucket(int(counts.max()) if n else 0, 1)
            arr = np.full((rows, width), Interner.PAD, np.int32)
            if len(flat):
                starts = np.cumsum(counts) - counts
                rows_idx = np.repeat(np.arange(n), counts)
                cols_idx = np.arange(len(flat)) - np.repeat(starts, counts)
                arr[rows_idx, cols_idx] = flat
            out[spec.key] = {"ids": arr}
        elif spec.kind == "joinkey":
            # normalized-key extraction stays host-Python on the native
            # path too: the normalization contract lives in ONE place
            # (joinkernel.normalize_join_key), and join columns are a
            # small fraction of a referential corpus's column set
            out[spec.key] = _extract_joinkey(resources, spec, interner, rows)
        else:
            raise ValueError(f"unknown column kind {spec.kind}")
    return out


def extract_columns(
    resources: Sequence[dict],
    specs: Sequence[ColumnSpec],
    interner: Interner,
    rows: int,
) -> Dict[Tuple, Dict[str, np.ndarray]]:
    """Extract requested columns over `resources`, padded to `rows` rows.
    Slot columns in the same iter group share entity extraction and width."""
    from ..native import load as _load_native

    native = _load_native()
    if native is not None:
        return _extract_columns_native(
            native, resources, specs, interner, rows
        )

    out: Dict[Tuple, Dict[str, np.ndarray]] = {}

    # Group slot specs by iteration source so their slot axes align.
    slot_groups: Dict[Tuple, List[ColumnSpec]] = {}
    for spec in specs:
        if spec.kind == "slot":
            slot_groups.setdefault(spec.iter_key, []).append(spec)

    group_entities: Dict[Tuple, List[List[Any]]] = {}
    group_width: Dict[Tuple, int] = {}
    for ik in slot_groups:
        ents: List[List[Any]] = []
        for r in resources:
            hits: List[Any] = []
            for p in ik:
                _walk(r, p, 0, hits)
            ents.append(hits)
        group_entities[ik] = ents
        group_width[ik] = _bucket(max((len(e) for e in ents), default=0), 1)

    for spec in specs:
        if spec.kind == "scalar":
            values = []
            for r in resources:
                hits: List[Any] = []
                _walk(r, spec.rel_path, 0, hits)
                values.append(hits[0] if hits else _ABSENT)
            values += [_ABSENT] * (rows - len(resources))
            out[spec.key] = _encode(values, interner, (rows,))
        elif spec.kind == "slot":
            ik = spec.iter_key
            ents = group_entities[ik]
            width = group_width[ik]
            mask = np.zeros((rows, width), bool)
            values = []
            for i in range(rows):
                row_ents = ents[i] if i < len(ents) else []
                for j in range(width):
                    if j < len(row_ents):
                        mask[i, j] = True
                        values.append(_get_rel(row_ents[j], spec.rel_path))
                    else:
                        values.append(_ABSENT)
            arrs = _encode(values, interner, (rows, width))
            arrs["mask"] = mask
            out[spec.key] = arrs
        elif spec.kind == "keyset":
            per_row_keys: List[List[int]] = []
            for r in resources:
                hits = []
                for p in spec.iter_paths:
                    _walk(r, p, 0, hits)
                keys: List[int] = []
                seen = set()
                for h in hits:
                    target = _get_rel(h, spec.rel_path) if spec.rel_path else h
                    if isinstance(target, dict):
                        for k, v in target.items():
                            # key enumeration is a body statement: a
                            # false-valued key fails it and is excluded
                            if (
                                isinstance(k, str)
                                and v is not False
                                and k not in spec.exclude
                                and k not in seen
                            ):
                                seen.add(k)
                                keys.append(interner.intern(k))
                per_row_keys.append(keys)
            width = _bucket(max((len(k) for k in per_row_keys), default=0), 1)
            ids = np.full((rows, width), Interner.PAD, np.int32)
            for i, keys in enumerate(per_row_keys):
                ids[i, : len(keys)] = keys
            out[spec.key] = {"ids": ids}
        elif spec.kind == "joinkey":
            out[spec.key] = _extract_joinkey(resources, spec, interner, rows)
        else:
            raise ValueError(f"unknown column kind {spec.kind}")
    return out
