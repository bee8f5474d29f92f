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
"""

from __future__ import annotations

from typing import Any, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

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
