"""Constraint-parameter packing for vectorized violation programs."""

from __future__ import annotations

import re
from typing import Dict, List, Optional, Tuple

import numpy as np

from .columns import T_COMP, T_FALSE, T_NULL, T_NUM, T_STR, T_TRUE, T_UNDEF
from .interning import Interner, PredicateTable
from .vexpr import Lit, ParamElemRef, ParamRef, StrPred, VProgram

_PRED_FNS = {
    "startswith": lambda s, v: s.startswith(v),
    "endswith": lambda s, v: s.endswith(v),
    "contains": lambda s, v: v in s,
    "re_match": lambda s, v: re.search(v, s) is not None,
}


def _walk_params(constraint: dict, ppath: Tuple[str, ...]):
    spec = constraint.get("spec")
    cur = spec.get("parameters") if isinstance(spec, dict) else None
    for seg in ppath:
        if isinstance(cur, dict) and seg in cur:
            cur = cur[seg]
        else:
            return None, False
    return cur, True


def _encode_scalar(values: List, interner: Interner):
    n = len(values)
    tcode = np.zeros(n, np.int8)
    sid = np.full(n, Interner.MISSING, np.int32)
    num = np.zeros(n, np.float64)
    for i, (v, present) in enumerate(values):
        if not present:
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
    return {"tcode": tcode, "sid": sid, "num": num}


def _bucket(n: int, minimum: int = 1) -> int:
    b = max(minimum, 1)
    while b < n:
        b *= 2
    return b


def fill_table_columns(mat: np.ndarray, stack: Dict[Tuple[str, str], int],
                       pred_cache: Dict[Tuple[str, str], PredicateTable],
                       lo: int, hi: int) -> None:
    """Bring columns [lo, hi) of one string-predicate table current: row
    `stack[key]` reads `pred_cache[key]` over those string ids.  The one
    fill rule of every tier: pack_params builds a table with it (lo 0),
    and the device side (driver._constraint_side) and the numpy side
    (npside.refresh_tables) extend theirs in place with it when the
    vocabulary grew — PredicateTable.dense() itself evaluates only the
    strings it has not seen."""
    if hi <= lo:
        return
    for key, row in stack.items():
        mat[row, lo:hi] = pred_cache[key].dense()[lo:hi]


def pack_params(
    constraints: List[dict],
    prog: VProgram,
    interner: Interner,
    pred_cache: Dict[Tuple[str, str], PredicateTable],
    rows: int,
    meta_out: Optional[dict] = None,
):
    """-> (params, elems, tables) for EvalEnv.  `rows` >= len(constraints)
    (padded rows read as undefined).  When `meta_out` is given, it receives
    {"stacks": {pred_id: {(pred, value): table row}}} — the row
    identities: the incremental host side (ops/npside.py) merges a single
    constraint's tables into its growing group buffers with them, and
    both tiers extend their tables over new strings with them
    (fill_table_columns)."""
    pad = [(None, False)] * (rows - len(constraints))

    params: Dict[Tuple, Dict[str, np.ndarray]] = {}
    for ppath in prog.param_scalars:
        vals = [_walk_params(c, ppath) for c in constraints] + pad
        params[ppath] = _encode_scalar(vals, interner)
    for s in prog.literals:
        params[("__lit__", s)] = _encode_scalar([(s, True)], interner)

    elems: Dict[Tuple, Dict[str, np.ndarray]] = {}
    elem_values: Dict[Tuple, List[List]] = {}
    for ppath, subpaths in prog.param_arrays:
        per_c: List[List] = []
        for c in constraints:
            v, ok = _walk_params(c, ppath)
            per_c.append(v if ok and isinstance(v, list) else [])
        per_c += [[] for _ in pad]
        elem_values[ppath] = per_c
        width = _bucket(max((len(x) for x in per_c), default=0), 1)
        mask = np.zeros((rows, width), bool)
        for i, xs in enumerate(per_c):
            mask[i, : len(xs)] = True
        subpaths = set(subpaths) | {()}
        for sub in subpaths:
            flat: List = []
            for xs in per_c:
                for j in range(width):
                    if j < len(xs):
                        v = xs[j]
                        for seg in sub:
                            v = v.get(seg) if isinstance(v, dict) else None
                            if v is None:
                                break
                        flat.append((v, True))
                    else:
                        flat.append((None, False))
            enc = _encode_scalar(flat, interner)
            enc = {k: a.reshape(rows, width) for k, a in enc.items()}
            enc["mask"] = mask
            elems[(ppath, sub)] = enc

    # string-predicate lookup tables (built after all interning above)
    tables: Dict[int, Tuple[np.ndarray, np.ndarray]] = {}
    for node in prog.str_preds:
        fn = _PRED_FNS[node.pred]

        def table_for(value) -> int:
            # returns index into this node's table stack; 0 = all-false
            if not isinstance(value, str):
                return 0
            key = (node.pred, value)
            if key not in pred_cache:
                pred_cache[key] = PredicateTable(
                    # bind via default args to avoid late-binding bugs
                    interner,
                    (lambda s, _f=fn, _v=value: _f(s, _v)),
                )
            uniq = stack.setdefault(key, len(stack) + 1)
            return uniq

        stack: Dict[Tuple[str, str], int] = {}
        if isinstance(node.rhs, Lit):
            idx = np.full(rows, table_for(node.rhs.value), np.int32)
        elif isinstance(node.rhs, ParamRef):
            idx = np.zeros(rows, np.int32)
            for i, c in enumerate(constraints):
                v, ok = _walk_params(c, node.rhs.ppath)
                idx[i] = table_for(v) if ok else 0
        elif isinstance(node.rhs, ParamElemRef):
            per_c = elem_values[node.rhs.ppath]
            width = elems[(node.rhs.ppath, ())]["mask"].shape[1]
            idx = np.zeros((rows, width), np.int32)
            for i, xs in enumerate(per_c):
                for j, v in enumerate(xs):
                    sv = v
                    for seg in node.rhs.subpath:
                        sv = sv.get(seg) if isinstance(sv, dict) else None
                    idx[i, j] = table_for(sv)
        else:
            raise ValueError("unsupported StrPred rhs")
        vocab = interner.snapshot_size()
        # bucket both table dims so compiled executables survive vocabulary
        # growth and new predicate values (shape-stable jit cache)
        mat = np.zeros((_bucket(len(stack) + 1), _bucket(vocab, 256)), np.uint8)
        fill_table_columns(mat, stack, pred_cache, 0, vocab)
        tables[node.pred_id] = (mat, idx)
        if meta_out is not None:
            meta_out.setdefault("stacks", {})[node.pred_id] = dict(stack)

    return params, elems, tables
