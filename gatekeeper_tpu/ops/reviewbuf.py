"""The review side of a dispatch as ONE host array.

A review dispatch used to hand the jit call every review-side leaf as its
own host array (14 of `rv`, 19-32 of `cols`): one host-to-device transfer
each, for one to three kilobytes in all.  Every such leaf leads with the
padded row count, so they lie side by side as the columns of one
`[rows, width]` int32 array: int32 as it is, bool and int8 widened on the
host and narrowed in the trace, float leaves as their bits (float64 is
brought to float32 on the host exactly as the jit boundary canonicalises
it with x64 off, then viewed as int32; `lax.bitcast_convert_type` in the
trace).  The executable so receives, bit for bit, the values it received
as separate arguments.

The Layout is static: it is fixed by the tree's structure (the column
specs) and every leaf's shape after the row axis and dtype, which are
what key a compiled executable already.  A leaf that cannot travel in
the buffer (it does not lead with the row axis, or its canonical dtype
is not one of _BITS) stays an argument of its own: `extras`.

Row-major on purpose: parallel/mesh.py partitions the same array on
"data", so the mesh path takes the same form.

A Writer fills the buffer from the reviews without the arrays: the native
extension extracts every field's values (interning in the order
`pack_reviews` and then `extract_columns` intern), reports the padded
widths the batch's data decides, and lays the fields into their columns
by a write plan.  A plan is derived from the Layout the arrays made the
first time a width tuple was met (`Writer.plan`), so the buffer it writes
is that Layout's, byte for byte.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from .columns import _extract_joinkey

_I32 = np.dtype(np.int32)
_F32 = np.dtype(np.float32)
_BOOL = np.dtype(np.bool_)
# canonical dtypes a buffer column can carry exactly
_BITS = frozenset((_I32, _F32, _BOOL, np.dtype(np.int8)))


def leaf_metas(leaves: Sequence[Any], rows: int) -> tuple:
    """Per leaf what the layout depends on: (shape after the row axis,
    dtype) of a leaf that leads with `rows`, None of any other.  With the
    treedef this is the hashable that names a Layout."""
    return tuple(
        (x.shape[1:], x.dtype)
        if getattr(x, "ndim", 0) and x.shape[0] == rows else None
        for x in leaves
    )


class Layout:
    """Where each leaf of one (rv, cols) tree lies in the buffer.

    slots[i] is (column offset, columns, shape after the row axis,
    canonical dtype) of leaf i, or None where the leaf is an extra."""

    __slots__ = ("treedef", "slots", "width", "sig")

    def __init__(self, tree, rows: int):
        flat, treedef = jax.tree_util.tree_flatten_with_path(tree)
        metas = leaf_metas([x for _p, x in flat], rows)
        slots: List[Any] = []
        sig: List[Tuple] = []
        off = 0
        for (path, _x), meta in zip(flat, metas):
            path = jax.tree_util.keystr(path)
            dtype = None
            if meta is not None:
                dtype = np.dtype(jax.dtypes.canonicalize_dtype(meta[1]))
            if dtype not in _BITS:
                slots.append(None)
                sig.append((path, None))
                continue
            tail = tuple(meta[0])
            n = int(np.prod(tail, dtype=np.int64)) if tail else 1
            slots.append((off, n, tail, dtype))
            sig.append((path, tail, dtype.name, off))
            off += n
        self.treedef = treedef
        self.slots = tuple(slots)
        self.width = off
        # what an executable's on-disk name is derived from: a plain
        # tuple of strings and ints, the same in every process
        self.sig = tuple(sig)

    def pack(self, leaves: Sequence[np.ndarray], rows: int):
        """Host side -> (buf [rows, width] int32, extras)."""
        buf = np.empty((rows, self.width), _I32)  # every column is written
        extras = []
        for x, slot in zip(leaves, self.slots):
            if slot is None:
                extras.append(x)
                continue
            off, n, _tail, dtype = slot
            x = x.reshape(rows, n)
            if dtype == _F32:
                x = x.astype(_F32, copy=False).view(_I32)
            buf[:, off:off + n] = x
        return buf, tuple(extras)

    def unpack(self, buf, extras):
        """In the trace: the (rv, cols) tree back, by static slices."""
        rows = buf.shape[0]
        rest = iter(extras)
        leaves = []
        for slot in self.slots:
            if slot is None:
                leaves.append(next(rest))
                continue
            off, n, tail, dtype = slot
            x = buf[:, off:off + n]
            if dtype == _F32:
                x = lax.bitcast_convert_type(x, jnp.float32)
            elif dtype == _BOOL:
                x = x != 0
            elif dtype != _I32:
                x = x.astype(dtype)
            leaves.append(x.reshape((rows,) + tail))
        return jax.tree_util.tree_unflatten(self.treedef, leaves)


# ---- the direct writer ------------------------------------------------------

# the review side's fields in the order the native extension emits them:
# pack_reviews' arrays, then each column spec's (columns.py) in spec order
REVIEW_FIELDS = ("group", "kind", "ns_name", "ns_mode", "always", "ns_empty",
                 "is_ns", "obj_empty", "old_empty", "autoreject", "valid",
                 "obj_labels", "old_labels", "ns_labels")
_SPEC_FIELDS = {"scalar": ("tcode", "sid", "num"),
                "slot": ("tcode", "sid", "num", "mask"),
                "keyset": ("ids",)}
_KIND = {"scalar": 0, "slot": 1, "keyset": 2, "joinkey": 3}


def spec_fields(spec) -> Tuple[str, ...]:
    if spec.kind == "joinkey":
        return ("sid", "mask") if spec.iter_paths else ("sid",)
    return _SPEC_FIELDS[spec.kind]


def widths_of(rv: Dict[str, np.ndarray], cols: Dict, specs) -> tuple:
    """The padded widths of a packed review side, as the native extension
    reports them for the same batch: the three label groups', then one a
    column spec (its second axis; 0 for a scalar column)."""
    firsts = [next(iter(cols[s.key].values())) for s in specs]
    return tuple(rv[f].shape[1] for f in REVIEW_FIELDS[-3:]) + tuple(
        a.shape[1] if a.ndim == 2 else 0 for a in firsts)


class Writer:
    """Writes one list of column specs' review side straight into the
    dispatch buffer.  `extract` is the first pass (values and widths),
    `write` the second (a plan's columns); `plan` derives the plan of a
    width tuple from the Layout its arrays made."""

    __slots__ = ("native", "specs", "descs", "groups")

    def __init__(self, native, specs):
        self.native = native
        self.specs = specs
        groups: Dict[Any, int] = {}
        descs = []
        for s in specs:
            g = groups.setdefault(s.iter_key, len(groups)) \
                if s.kind == "slot" else -1
            descs.append((_KIND[s.kind], s.rel_path, tuple(s.iter_paths),
                          tuple(s.exclude), g))
        self.descs = tuple(descs)
        self.groups = tuple(tuple(ik) for ik in groups)

    def extract(self, reviews: List[dict], interner, cached_namespace,
                rows: int):
        """-> (scratch, widths).  Interns what the arrays path interns, in
        its order; a join key column keeps its Python extraction."""
        reviews = list(reviews)
        specs = self.specs

        def joinkey(i):
            return _extract_joinkey(reviews, specs[i], interner, rows)

        return self.native.review_scratch(
            reviews, interner._ids, interner._strings, cached_namespace,
            self.descs, self.groups, joinkey)

    def write(self, scratch, plan: np.ndarray, rows: int, width: int):
        return self.native.review_write(scratch, plan, rows, width)

    def plan(self, layout: Layout, leaves: Sequence[np.ndarray],
             rv: Dict[str, np.ndarray], cols: Dict) -> Optional[np.ndarray]:
        """The write plan of `layout`: int32 [slots, 3] of (field, column
        offset, columns), one row per leaf, `leaves` the flattened (rv,
        cols) the layout was made from.  None where a leaf is an extra:
        the arrays then serve every batch of this layout."""
        field = {id(rv[f]): i for i, f in enumerate(REVIEW_FIELDS)}
        for s in self.specs:
            for f in spec_fields(s):
                field[id(cols[s.key][f])] = len(field)
        if len(field) != len(leaves) or any(s is None for s in layout.slots):
            return None
        return np.asarray([(field[id(x)], slot[0], slot[1])
                           for x, slot in zip(leaves, layout.slots)],
                          np.int32)
