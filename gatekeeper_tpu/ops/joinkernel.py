"""Referential policies: the cross-resource join/aggregate kernel subsystem.

Every workload before this module was row-local: a cell's verdict depended
only on (constraint, resource).  Gatekeeper's real capability surface also
includes constraints that need data *across* rows — unique ingress hosts,
required owner references, quota-by-label — which templates express by
iterating ``data.inventory``.  The interpreter answers those exactly but at
O(inventory) per evaluated cell, so a referential audit sweep is O(R^2).

This module keeps referential templates inside the vectorized sweep:

- ``classify_join_clause`` (called from ops/vectorizer.py) pattern-matches a
  violation clause against three referential plan families —
  duplicate-key detection (unique ingress host), existence-of-referenced-row
  (required storage class), and count/group-by vs a parameter quota — and
  compiles it to a :class:`JoinPlan` + a ``JoinCmp`` IR node
  (ops/vexpr.py) instead of bailing to the interpreter.  The
  duplicate-key family takes the upstream ``K8sUniqueServiceSelector``
  clause as written: a key COMPUTED from a map (:func:`join_pairs`), an
  inventory iteration that binds its namespace / name variables, and a
  message that names the other row — sound under the identity rule
  (``_check_benign_guards``), which :class:`JoinState` upholds by
  tracking every provider row's identity.
- all three families reduce to ONE aggregate: **distinct provider rows per
  interned join key**.  Key values are normalized type-tagged strings
  (:func:`normalize_join_key`) interned into the global vocabulary, so
  int-vs-str label values can never coerce into one group (the engine's
  ``values_equal`` is type-strict; the packed path must be too).
- device-side kernels build the per-key table inside the packed [C, R]
  sweep: in-row dedup of slot keys, a sort + segment-reduce group-by over
  the interned key column, and under a mesh a per-shard segment-reduce
  followed by an ``all_gather`` cross-shard merge (the [C, 1+K]-style
  reduce-then-merge idiom from parallel/mesh.py) for keys spanning shards.
  Verdicts are then one ``searchsorted`` gather + the engine's exact
  total-order comparison.
- :class:`JoinState` is the host-side join-group index (key -> provider
  rows, key -> reader rows) that gives the delta sweep O(churn) dispatch:
  a churned row invalidates only its key group (old keys + new keys), and
  only those readers re-evaluate / re-render.  The index is persisted in
  the snapshot sweep basis (gatekeeper_tpu/snapshot/) so warm restores
  keep the delta path; plan drift drops the basis for a rebase.

Soundness: a JoinCmp inside the REVIEW path's mask executables (admission
batches — no inventory on the device) resolves to its polarity's
``unknown_default``; the host then resolves every flagged cell of a
join-safe template from this index (:meth:`JoinState.review_lookup`,
ops/joinreview.py): exactly false cells are cleared, the rest render
against the pruned inventory of their key group, and what cannot be
proven falls back to the interpreter on the full inventory.  On the
AUDIT path the plan is exact modulo one documented corner (two inventory
objects of the same kind/namespace/name under different groupVersions count
as two provider rows where the reference's ``identical`` helper sees one) —
over-approximation only, filtered by the interpreter render.

Divergence assertion (GK_JOIN_ASSERT=1, disabled by GK_BUG_COMPAT=1): a
cell an exact join plan flagged whose interpreter render comes back empty
raises :class:`JoinDivergence` — the fuzz-oracle posture of docs/parity.md
applied to the referential tier.  See docs/referential.md.
"""

from __future__ import annotations

import json
import operator
import os
from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from .interning import Interner

#: in-trace sentinel for "no key at this position": sorts past every real
#: interned id, so sort-based kernels compact invalid entries to the tail
KEY_INVALID = np.int32(2**31 - 1)

#: packed-column sentinel for a PRESENT key value the normalizer cannot
#: represent faithfully (NaN-bearing values: NaN != NaN under values_equal,
#: but any table key would equal itself).  JoinCmp resolves these cells to
#: the polarity's unknown_default — over-approximation, interpreter-exact.
UNKNOWN_KEY = -5

#: minimum padded width of a (uk, uc) key table
TABLE_MIN = 8

# ONE power-of-two bucketing helper repo-wide: joinkey slot widths
# (columns.py) and delta-table widths must stay consistent with the
# executables' shape buckets, so they share the same implementation
from .columns import _bucket as _pow2_bucket  # noqa: E402


# ---------------------------------------------------------------------------
# Key normalization (the interned-key contract)
# ---------------------------------------------------------------------------


def normalize_join_key(v: Any) -> Optional[str]:
    """Canonical type-tagged string for a JSON value used as a join key,
    or None when the value cannot be normalized faithfully (NaN anywhere).

    Injective over the engine's ``values_equal`` equivalence classes:
    two values normalize to the same string iff the interpreter oracle
    would consider them equal — ``5`` and ``5.0`` share ``n:5`` (numbers
    compare by value), but ``5`` / ``"5"`` / ``true`` stay distinct
    (type-strict equality, engine/value.py).  The packed path and any
    host-side oracle twin MUST share this one function; a second
    normalization is how int-vs-str label coercion bugs are born."""
    if isinstance(v, str):
        return "s:" + v
    if isinstance(v, bool):
        return "b:1" if v else "b:0"
    if isinstance(v, (int, float)):
        if isinstance(v, float):
            if v != v:  # NaN: self-unequal, no faithful table key exists
                return None
            if v.is_integer():
                v = int(v)
        return "n:" + repr(v)
    if v is None:
        return "z:"
    # composite (dict/list): canonical JSON — sorted keys, no whitespace,
    # and NESTED numbers canonicalized like the scalar branch (the
    # interpreter pools {"a": 5} with {"a": 5.0}; json.dumps alone would
    # split them into two keys and the aggregate would UNDER-approximate).
    # allow_nan=False so a nested NaN degrades to UNKNOWN instead of
    # producing a self-equal key the oracle would never match.
    try:
        return "j:" + json.dumps(
            _canon_numbers(v), sort_keys=True, separators=(",", ":"),
            allow_nan=False,
        )
    except (TypeError, ValueError):
        return None


def _canon_numbers(v: Any):
    """Recursively collapse int-valued floats to ints (the engine's
    numeric equality classes) inside a composite key value."""
    if isinstance(v, bool):
        return v
    if isinstance(v, float) and v == v and v.is_integer():
        return int(v)
    if isinstance(v, list):
        return [_canon_numbers(x) for x in v]
    if isinstance(v, tuple):
        return [_canon_numbers(x) for x in v]
    if isinstance(v, dict):
        return {k: _canon_numbers(x) for k, x in v.items()}
    return v


def join_pairs(v: Any, kv_sep: str, item_sep: str) -> str:
    """A key COMPUTED from a map, as the Rego idiom the matcher
    recognizes (upstream's ``flatten_selector``) evaluates it:
    ``concat(item_sep, sort([concat(kv_sep, [k, x]) | x = v[k]]))``.

    Always defined.  Only the string-valued entries of an object
    contribute: ``concat`` refuses any other element, a builtin error is
    undefined, and that fails ONE iteration of the comprehension, not the
    comprehension; an absent, scalar or array ``v`` therefore gives the
    empty string (array indices are numbers).  The string is the key —
    two maps whose flattened forms coincide collide in the Rego too — and
    it goes through :func:`normalize_join_key` like every other key."""
    if not isinstance(v, dict):
        return ""
    return item_sep.join(sorted(
        k + kv_sep + x for k, x in v.items()
        if isinstance(k, str) and isinstance(x, str)
    ))


def intern_join_key(v: Any, interner: Interner) -> int:
    """Packed-column id for one extracted key value (ops/columns.py)."""
    norm = normalize_join_key(v)
    if norm is None:
        return UNKNOWN_KEY
    return interner.intern(norm)


# ---------------------------------------------------------------------------
# Plans
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class JoinPlan:
    """One classified cross-resource aggregate.

    ``agg`` names the family for observability ('dup' | 'exists' |
    'count'); the aggregate itself is always *distinct provider rows per
    key*.  ``local_colkey`` / ``remote_colkey`` are joinkey
    ColumnSpec.key tuples (ops/columns.py); providers are the inventory
    rows of ``remote_kind`` in ``remote_scope`` ('namespace' | 'cluster')
    whose remote key column yields the key."""

    agg: str
    local_colkey: Tuple
    local_slot: bool
    remote_scope: str
    remote_kind: str
    remote_colkey: Tuple
    remote_slot: bool

    @property
    def sig(self) -> str:
        """Stable identity for snapshot drift checks and dedup."""
        return repr((
            self.agg, self.local_colkey, self.local_slot,
            self.remote_scope, self.remote_kind,
            self.remote_colkey, self.remote_slot,
        ))


# ---------------------------------------------------------------------------
# Device kernels
# ---------------------------------------------------------------------------


def _scatter_add(n: int, idx, w, xp):
    if xp is np:
        tot = np.zeros(n, np.int64)
        np.add.at(tot, idx, w)
        return tot
    import jax.numpy as jnp

    return jnp.zeros(n, jnp.int32).at[idx].add(w)


def compact_key_table(keys, weights, xp):
    """Sort + segment-reduce group-by: ``(keys [N], weights [N])`` ->
    ``(uk [N], uc [N])`` where uk holds each distinct valid key once
    (ascending, KEY_INVALID-padded tail) and uc its summed weight.

    The segment reduce is the classic sorted-run trick: sort, mark run
    starts, scatter-add weights per run id.  Shape-stable (no nonzero/
    compaction), so it traces once per column layout."""
    n = keys.shape[0]
    order = xp.argsort(keys)
    sk = keys[order]
    w = weights[order]
    first = xp.concatenate(
        [xp.ones(1, bool), sk[1:] != sk[:-1]]
    )
    run = xp.cumsum(first.astype(xp.int32)) - 1
    tot = _scatter_add(n, run, w, xp)
    valid = sk != KEY_INVALID
    uk = xp.where(first & valid, sk, KEY_INVALID)
    uc = xp.where(first & valid, tot[run], 0)
    o2 = xp.argsort(uk)
    return uk[o2], uc[o2].astype(xp.int32)


def row_distinct_slot_keys(sid, mask, xp):
    """[R, S] slot key ids + validity mask -> flat [R*S] keys with each
    row's duplicate keys collapsed to one entry (a row providing the same
    host twice is ONE provider for that host — the reference's
    ``identical`` self-exclusion is object-level, not entry-level)."""
    s = xp.where(mask, sid, KEY_INVALID)
    ss = xp.sort(s, axis=1)
    keep = xp.concatenate(
        [xp.ones((ss.shape[0], 1), bool), ss[:, 1:] != ss[:, :-1]],
        axis=1,
    )
    return xp.where(keep & (ss != KEY_INVALID), ss, KEY_INVALID).reshape(-1)


def provider_key_table(plan: JoinPlan, kind_id, rv, cols, xp,
                       axis_name: Optional[str] = None):
    """The per-key distinct-provider-row table, computed INSIDE the packed
    sweep from the resident columns.  Single device: one segment-reduce
    over the full row axis.  Mesh (``axis_name`` set): each shard
    segment-reduces its own row slab to a compact (keys, counts) table,
    then an ``all_gather`` + second segment-reduce merges the per-shard
    tables — counts for keys spanning shards sum exactly, so the merged
    table is bit-identical at every width."""
    valid = xp.asarray(rv["valid"])
    part = valid & (xp.asarray(rv["kind"]) == kind_id)
    ns_empty = xp.asarray(rv["ns_empty"])
    if plan.remote_scope == "namespace":
        part = part & ~ns_empty
    else:
        part = part & ns_empty
    rcol = cols[plan.remote_colkey]
    sid = xp.asarray(rcol["sid"])
    if plan.remote_slot:
        ok = xp.asarray(rcol["mask"]) & (sid >= 0) & part[:, None]
        flat = row_distinct_slot_keys(sid, ok, xp)
    else:
        flat = xp.where(part & (sid >= 0), sid, KEY_INVALID)
    uk, uc = compact_key_table(
        flat, (flat != KEY_INVALID).astype(xp.int32), xp
    )
    if axis_name is not None:
        from jax import lax

        ku = lax.all_gather(uk, axis_name).reshape(-1)
        cu = lax.all_gather(uc, axis_name).reshape(-1)
        uk, uc = compact_key_table(ku, cu, xp)
    return uk, uc


def lookup_counts(uk, uc, q, xp):
    """Gather per-key counts at query ids ``q`` (any shape): one
    ``searchsorted`` into the compact table; absent or invalid keys
    answer 0."""
    n = uk.shape[0]
    i = xp.clip(xp.searchsorted(uk, q), 0, n - 1)
    found = (uk[i] == q) & (q >= 0)
    return xp.where(found, uc[i], 0)


class JoinBinding:
    """Per-evaluation join context attached to an EvalEnv (vexpr).

    mode 'trace':  tables are computed in-trace from the resident columns
                   (full audit sweeps; ``plan_args[i]`` carries the
                   runtime ``kind_id`` scalar so interner ids are never
                   baked into a cached executable).
    mode 'tables': tables arrive as runtime arrays (delta sweeps — the
                   dispatched rows are a churn slice, so the global
                   aggregate must come from the host join index).
    ``cache`` is shared across the sweep's program groups: 500 template
    clones of one referential family cost ONE table build."""

    __slots__ = ("mode", "plans", "plan_args", "rv", "axis_name", "cache")

    def __init__(self, mode: str, plans, plan_args, rv=None,
                 axis_name: Optional[str] = None, cache: Optional[dict] = None):
        self.mode = mode
        self.plans = plans
        self.plan_args = plan_args
        self.rv = rv
        self.axis_name = axis_name
        self.cache = cache if cache is not None else {}

    def table(self, plan_id: int, env):
        plan = self.plans[plan_id]
        hit = self.cache.get(plan)
        if hit is None:
            xp = env.xp
            arg = self.plan_args[plan_id]
            if self.mode == "tables":
                hit = (xp.asarray(arg["uk"]), xp.asarray(arg["uc"]))
            else:
                import jax

                # the scope names the build's ops in a device trace
                with jax.named_scope("gk.join.table"):
                    hit = provider_key_table(
                        plan, xp.asarray(arg["kind_id"]), self.rv,
                        env.cols, xp, axis_name=self.axis_name,
                    )
            self.cache[plan] = hit
        return hit

    def self_mask(self, plan_id: int, env):
        """[R] bool: does the row itself participate in the aggregate
        (JoinCmp.exclude_self)?  Both modes carry the review arrays —
        delta dispatches slice them row-aligned with the columns."""
        plan = self.plans[plan_id]
        xp = env.xp
        arg = self.plan_args[plan_id]
        rv = self.rv
        part = xp.asarray(rv["valid"]) & (
            xp.asarray(rv["kind"]) == xp.asarray(arg["kind_id"])
        )
        ns_empty = xp.asarray(rv["ns_empty"])
        if plan.remote_scope == "namespace":
            return part & ~ns_empty
        return part & ns_empty


# ---------------------------------------------------------------------------
# Host-side join-group index (delta-sweep locality + table source)
# ---------------------------------------------------------------------------


def _pairs_for_side(plan: JoinPlan, colkey: Tuple, slot: bool, ap,
                    part: Optional[np.ndarray]) -> np.ndarray:
    """(row, key_sid) pairs for one side of a plan over the resident
    audit pack, distinct per row.  ``part`` masks participating rows
    (None = every valid row)."""
    col = ap.cols.get(colkey)
    if col is None:
        return np.empty((0, 2), np.int64)
    sid = np.asarray(col["sid"])
    if part is None:
        part = np.asarray(ap.rp["valid"])
    if slot:
        ok = np.asarray(col["mask"]) & (sid >= 0) & part[:, None]
        rows, slots = np.nonzero(ok)
        pairs = np.stack([rows, sid[rows, slots]], axis=1)
        if len(pairs):
            pairs = np.unique(pairs, axis=0)
        return pairs.astype(np.int64)
    rows = np.nonzero(part & (sid >= 0))[0]
    return np.stack([rows, sid[rows]], axis=1).astype(np.int64)


def _provider_part(plan: JoinPlan, ap, interner: Interner) -> np.ndarray:
    kind_id = interner.intern(plan.remote_kind)
    part = np.asarray(ap.rp["valid"]) & (
        np.asarray(ap.rp["kind"]) == kind_id
    )
    ns_empty = np.asarray(ap.rp["ns_empty"])
    if plan.remote_scope == "namespace":
        return part & ~ns_empty
    return part & ns_empty


def _keys_of_row(plan, colkey, slot, ap, row, part_ok: bool) -> Tuple[int, ...]:
    if not part_ok:
        return ()
    col = ap.cols.get(colkey)
    if col is None:
        return ()
    sid = np.asarray(col["sid"])
    if slot:
        ok = np.asarray(col["mask"])[row] & (sid[row] >= 0)
        return tuple(sorted(set(int(s) for s in sid[row][ok])))
    s = int(sid[row])
    return (s,) if s >= 0 else ()


def _row_identity(ap, row: int) -> Optional[Tuple]:
    """The identity of the object a pack row holds — the inventory
    path's (namespace, name) and the object's own metadata.namespace /
    metadata.name: all a dup-family message may name of a provider (the
    identity rule, ``_check_benign_guards``)."""
    rv = ap.reviews[row] if row < len(ap.reviews) else None
    if rv is None:
        return None
    meta = (rv.get("object") or {}).get("metadata") or {}
    return (rv.get("namespace", ""), rv.get("name", ""),
            meta.get("namespace"), meta.get("name"))


@dataclass(frozen=True)
class ReviewJoin:
    """What the index says of one admission review under one join-safe
    program (JoinState.review_lookup): the provider rows of the review
    object's keys — the whole inventory its render can read — and the
    exact value of the program's join conditions where it could be
    decided (None: the interpreter decides)."""

    rows: Tuple[int, ...]
    verdict: Optional[bool]


_CMP = {"==": operator.eq, "!=": operator.ne, "<": operator.lt,
        "<=": operator.le, ">": operator.gt, ">=": operator.ge}


class JoinState:
    """The join-group index: per plan, key -> provider rows (drives the
    aggregate) and key -> reader rows (rows whose verdict/message reads
    that key's aggregate).  All access under the owning driver's lock.

    Full sweeps rebuild it (O(R) numpy grouping) and DIFF against the
    previous index: keys whose provider set changed have their readers'
    row generations bumped, so the render caches (driver._render_memo +
    the per-constraint render_cache) can never serve a message whose
    group aggregate moved underneath it.  Delta sweeps update it
    incrementally (O(churn)) and return the affected reader rows — the
    key-group locality contract ``tools/check_join_parity.py`` asserts."""

    def __init__(self, plans: Tuple[JoinPlan, ...], rebuild_gen: int):
        self.plans = tuple(plans)
        self.sig = tuple(p.sig for p in self.plans)
        self.rebuild_gen = rebuild_gen
        self.built = False
        n = len(self.plans)
        self.providers: List[Dict[int, set]] = [{} for _ in range(n)]
        self.readers: List[Dict[int, set]] = [{} for _ in range(n)]
        self.row_pkeys: List[Dict[int, Tuple[int, ...]]] = [
            {} for _ in range(n)
        ]
        self.row_rkeys: List[Dict[int, Tuple[int, ...]]] = [
            {} for _ in range(n)
        ]
        # provider row -> the identity it held when indexed.  Rows are
        # reused from a free list, so "delete B, create C with B's key"
        # can leave a key's provider ROW set as it was while a reader's
        # message ("same selector as <B>") went stale: a provider row
        # whose identity changed counts as leaving and joining its keys
        self.row_ident: List[Dict[int, Tuple]] = [
            {} for _ in range(n)
        ]
        # reader rows whose key group a commit OUTSIDE a sweep changed
        # (the review path bringing the index current after a write):
        # their generations are bumped at once, but the sweep's mask
        # still holds their old verdict, so the next delta sweep takes
        # them with its own affected rows; a rebuild drops them
        self.pending: set = set()

    # ---- build / diff ------------------------------------------------------

    @staticmethod
    def _index(pairs: np.ndarray):
        by_key: Dict[int, set] = {}
        by_row: Dict[int, Tuple[int, ...]] = {}
        if len(pairs):
            order = np.lexsort((pairs[:, 1], pairs[:, 0]))
            pairs = pairs[order]
            rows = pairs[:, 0]
            starts = np.concatenate(
                [[0], np.nonzero(rows[1:] != rows[:-1])[0] + 1, [len(rows)]]
            )
            for a, b in zip(starts[:-1], starts[1:]):
                r = int(rows[a])
                ks = tuple(int(k) for k in pairs[a:b, 1])
                by_row[r] = ks
                for k in ks:
                    by_key.setdefault(k, set()).add(r)
        return by_key, by_row

    def rebuild(self, ap, interner: Interner) -> set:
        """Re-derive the index from the resident packed columns; returns
        the reader rows whose key group changed since the previous index
        (empty on first build — nothing was cached against it)."""
        bump: set = set()
        for i, plan in enumerate(self.plans):
            part = _provider_part(plan, ap, interner)
            prov_pairs = _pairs_for_side(
                plan, plan.remote_colkey, plan.remote_slot, ap, part
            )
            new_prov, new_rowp = self._index(prov_pairs)
            read_pairs = _pairs_for_side(
                plan, plan.local_colkey, plan.local_slot, ap, None
            )
            new_read, new_rowr = self._index(read_pairs)
            new_ident = {r: _row_identity(ap, r) for r in new_rowp}
            if self.built:
                old_prov, old_read = self.providers[i], self.readers[i]
                moved = {
                    k for k in set(old_prov) | set(new_prov)
                    if old_prov.get(k) != new_prov.get(k)
                }
                old_ident, old_rowp = self.row_ident[i], self.row_pkeys[i]
                for r in set(old_ident) & set(new_ident):
                    if old_ident[r] != new_ident[r]:
                        moved.update(old_rowp.get(r, ()), new_rowp[r])
                for k in moved:
                    bump |= old_read.get(k, set())
                    bump |= new_read.get(k, set())
            self.row_ident[i] = new_ident
            self.providers[i] = new_prov
            self.readers[i] = new_read
            self.row_pkeys[i] = new_rowp
            self.row_rkeys[i] = new_rowr
        self.built = True
        self.pending = set()
        return bump

    # ---- delta -------------------------------------------------------------

    def affected(self, ap, interner: Interner, dirty) -> set:
        """Reader rows (beyond the dirty set) whose key-group aggregate a
        churn batch changes — WITHOUT mutating the index (eligibility
        preview; ``commit`` applies)."""
        out: set = set()
        for i, plan in enumerate(self.plans):
            part = _provider_part(plan, ap, interner)
            changed: set = set()
            for r in dirty:
                changed |= self._moved_keys(i, plan, ap, r, bool(part[r]))[0]
            readers = self.readers[i]
            for k in changed:
                out |= readers.get(k, set())
        return out - set(dirty)

    def _moved_keys(self, i: int, plan: JoinPlan, ap, r: int,
                    part_ok: bool):
        """(keys whose group dirty row ``r`` leaves or joins, its old
        provider keys, its new ones, its new identity).  A row that keeps
        its keys under another identity leaves and joins them all."""
        old = set(self.row_pkeys[i].get(r, ()))
        new = set(_keys_of_row(
            plan, plan.remote_colkey, plan.remote_slot, ap, r, part_ok
        ))
        ident = _row_identity(ap, r) if new else None
        if old and new and self.row_ident[i].get(r) != ident:
            return old | new, old, new, ident
        return old ^ new, old, new, ident

    def commit(self, ap, interner: Interner, dirty,
               sweep: bool = True) -> set:
        """Apply a churn batch to the index; returns the affected reader
        rows (beyond the dirty set) and bumps their pack row generations
        so stale rendered results cannot be reused.  A sweep's commit
        also returns (and forgets) the readers that commits outside a
        sweep left ``pending``; a commit outside a sweep (``sweep``
        False: the review path after a write) adds its readers to
        them."""
        out: set = set()
        dirty = set(dirty)
        for i, plan in enumerate(self.plans):
            part = _provider_part(plan, ap, interner)
            prov, read = self.providers[i], self.readers[i]
            rowp, rowr = self.row_pkeys[i], self.row_rkeys[i]
            changed: set = set()
            for r in dirty:
                moved, old, new, ident = self._moved_keys(
                    i, plan, ap, r, bool(part[r])
                )
                changed |= moved
                if ident is None:
                    self.row_ident[i].pop(r, None)
                else:
                    self.row_ident[i][r] = ident
                for k in old - new:
                    s = prov.get(k)
                    if s is not None:
                        s.discard(r)
                        if not s:
                            del prov[k]
                for k in new - old:
                    prov.setdefault(k, set()).add(r)
                if new:
                    rowp[r] = tuple(sorted(new))
                else:
                    rowp.pop(r, None)
                # reader side: the row's own local keys
                oldr = set(rowr.get(r, ()))
                valid = bool(np.asarray(ap.rp["valid"])[r])
                newr = set(_keys_of_row(
                    plan, plan.local_colkey, plan.local_slot, ap, r, valid
                ))
                for k in oldr - newr:
                    s = read.get(k)
                    if s is not None:
                        s.discard(r)
                        if not s:
                            del read[k]
                for k in newr - oldr:
                    read.setdefault(k, set()).add(r)
                if newr:
                    rowr[r] = tuple(sorted(newr))
                else:
                    rowr.pop(r, None)
            for k in changed:
                out |= read.get(k, set())
        out -= dirty
        if out:
            ap.bump_row_gen(out)
        if not sweep:
            self.pending |= out
        elif self.pending:
            out |= self.pending - dirty
            self.pending = set()
        return out

    # ---- admission ---------------------------------------------------------

    def review_lookup(self, prog, review: dict, ap,
                      interner: Interner) -> Optional["ReviewJoin"]:
        """Resolve one admission review against the index: for every
        join plan of ``prog`` (a join-safe program: each of its inventory
        reads is a classified plan) the review object's own keys — the
        plan's local column read off the review (columns.joinkey_values),
        the one normalize_join_key — and the provider rows that hold
        them.  None where a key cannot be normalized (UNKNOWN_KEY) or
        the index does not know a plan: the caller falls back to the
        full inventory.

        The verdict of the program's JoinCmp conditions is exact where
        it can be decided: a clause holding a JoinCmp that is false for
        every key cannot raise, and a program whose clauses all hold one
        is exactly False.  The review object itself is dropped from a
        ``exclude_self`` count by identity, and only where the Rego's
        ``identical`` helper is sure to agree (one provider row of the
        review's namespace, name, kind and apiVersion, the request's own
        name / namespace equal to the object's): anything else leaves
        the condition undecided, and the interpreter, which is handed
        these provider rows as its whole inventory, decides."""
        from .columns import _ABSENT, joinkey_values
        from .vexpr import JoinCmp

        by_sig = {p.sig: i for i, p in enumerate(self.plans)}
        specs = {sp.key: sp for sp in prog.column_specs}
        rows: set = set()
        per_plan: List[Tuple[int, List[set]]] = []  # (index plan, groups)
        for plan in prog.join_plans:
            i = by_sig.get(plan.sig)
            spec = specs.get(plan.local_colkey)
            if i is None or spec is None:
                return None
            groups: List[set] = []
            for v in joinkey_values(review, spec):
                if v is _ABSENT:
                    continue
                norm = normalize_join_key(v)
                if norm is None:
                    return None
                got = self.providers[i].get(interner.find(norm), ())
                groups.append(set(got))
                rows.update(got)
            per_plan.append((i, groups))
        obj = review.get("object")
        verdict: Optional[bool] = None
        if isinstance(obj, dict) or hasattr(obj, "get"):
            me = self._review_self(review, obj)
            possible, certain = False, True
            for clause in prog.clauses:
                vals = [
                    self._joincmp_value(c, per_plan[c.plan_id], me, ap)
                    for c in clause.conds if isinstance(c, JoinCmp)
                ]
                if not any(v is False for v in vals):
                    possible = True
                if not vals or not all(v is True for v in vals):
                    certain = False
            verdict = False if not possible else (
                True if certain else None
            )
        return ReviewJoin(tuple(sorted(rows)), verdict)

    @staticmethod
    def _review_self(review: dict, obj) -> Optional[Tuple]:
        """(namespace, name, kind, apiVersion) of the reviewed object
        where the request and the object agree on them, else None (no
        row can then be told to be the review's own)."""
        meta = obj.get("metadata") or {}
        ns, name = meta.get("namespace") or "", meta.get("name")
        kind = review.get("kind") or {}
        if (not name or review.get("name") != name
                or (review.get("namespace") or "") != ns
                or kind.get("kind") != obj.get("kind")):
            return None
        group, version = kind.get("group") or "", kind.get("version")
        api = f"{group}/{version}" if group else version
        if api != obj.get("apiVersion"):
            return None
        return ns, name, obj.get("kind"), api

    def _joincmp_value(self, node, plan_groups, me, ap) -> Optional[bool]:
        """One JoinCmp of a review: True / False where exact, None where
        undecided (no key, a parameter on the right, or a self row that
        cannot be told apart)."""
        from .vexpr import Lit

        i, groups = plan_groups
        rhs = node.rhs
        if not groups or not isinstance(rhs, Lit) or isinstance(
            rhs.value, bool
        ) or not isinstance(rhs.value, (int, float)):
            return None
        results = []
        for got in groups:
            n = len(got)
            if node.exclude_self and got:
                if me is None:
                    return None
                own = [r for r in got if self._is_self(i, r, me, ap)]
                if len(own) > 1:
                    return None  # groupVersion twins: the Rego decides
                n -= len(own)
            results.append(_CMP[node.op](n, rhs.value))
        return any(results)

    def _is_self(self, i: int, row: int, me: Tuple, ap) -> bool:
        ident = self.row_ident[i].get(row)
        if ident is None or (ident[2] or "") != me[0] or ident[3] != me[1]:
            return False
        rv = ap.reviews[row] if row < len(ap.reviews) else None
        o = (rv or {}).get("object") or {}
        return o.get("kind") == me[2] and o.get("apiVersion") == me[3]

    # ---- tables ------------------------------------------------------------

    def delta_tables(self) -> List[Dict[str, np.ndarray]]:
        """The per-plan (uk, uc) runtime tables for 'tables'-mode
        dispatches, padded to power-of-two widths so the delta executable
        survives group-count drift."""
        out = []
        for prov in self.providers:
            n = len(prov)
            width = _pow2_bucket(n, TABLE_MIN)
            uk = np.full(width, KEY_INVALID, np.int32)
            uc = np.zeros(width, np.int32)
            if n:
                keys = np.fromiter(prov.keys(), np.int64, n)
                counts = np.fromiter(
                    (len(prov[int(k)]) for k in keys), np.int64, n
                )
                order = np.argsort(keys)
                uk[:n] = keys[order]
                uc[:n] = counts[order]
            out.append({"uk": uk, "uc": uc})
        return out

    def shapes(self) -> List[dict]:
        """Observability summary for /debug/routez (bounded, cheap)."""
        out = []
        for i, plan in enumerate(self.plans):
            prov = self.providers[i]
            out.append({
                "agg": plan.agg,
                "kind": plan.remote_kind,
                "scope": plan.remote_scope,
                "slot_key": plan.local_slot,
                "groups": len(prov),
                "provider_rows": sum(len(s) for s in prov.values()),
                "reader_rows": sum(
                    len(s) for s in self.readers[i].values()
                ),
            })
        return out

    # ---- snapshot persistence ---------------------------------------------

    def persist(self) -> dict:
        """Pickle-friendly form for the snapshot sweep basis."""
        return {
            "sig": list(self.sig),
            "providers": [
                {int(k): sorted(v) for k, v in prov.items()}
                for prov in self.providers
            ],
            "readers": [
                {int(k): sorted(v) for k, v in read.items()}
                for read in self.readers
            ],
            "row_pkeys": [
                {int(r): list(ks) for r, ks in rp.items()}
                for rp in self.row_pkeys
            ],
            "row_rkeys": [
                {int(r): list(ks) for r, ks in rr.items()}
                for rr in self.row_rkeys
            ],
            "row_ident": [
                {int(r): list(ident) for r, ident in ri.items()}
                for ri in self.row_ident
            ],
        }

    @classmethod
    def restore(cls, plans: Tuple[JoinPlan, ...], data: dict,
                rebuild_gen: int) -> Optional["JoinState"]:
        """Rebuild a persisted index; None on plan drift (the caller then
        drops the whole sweep basis and rebases via a full sweep)."""
        st = cls(plans, rebuild_gen)
        if list(st.sig) != list(data.get("sig", ())):
            return None
        try:
            st.providers = [
                {int(k): set(v) for k, v in prov.items()}
                for prov in data["providers"]
            ]
            st.readers = [
                {int(k): set(v) for k, v in read.items()}
                for read in data["readers"]
            ]
            st.row_pkeys = [
                {int(r): tuple(ks) for r, ks in rp.items()}
                for rp in data["row_pkeys"]
            ]
            st.row_rkeys = [
                {int(r): tuple(ks) for r, ks in rr.items()}
                for rr in data["row_rkeys"]
            ]
            st.row_ident = [
                {int(r): tuple(ident) for r, ident in ri.items()}
                for ri in data["row_ident"]
            ]
        except (KeyError, TypeError, ValueError):
            return None
        if (
            len(st.providers) != len(st.plans)
            or len(st.readers) != len(st.plans)
        ):
            return None
        st.built = True
        return st


# ---------------------------------------------------------------------------
# Divergence assertion (satellite: interned-key parity oracle)
# ---------------------------------------------------------------------------


class JoinDivergence(AssertionError):
    """An exact join plan flagged a cell the interpreter oracle renders
    empty — the packed aggregate and the oracle disagree."""


def assert_enabled() -> bool:
    """GK_JOIN_ASSERT=1 arms the divergence assertion (parity tools and
    tests); GK_BUG_COMPAT=1 disarms it even then — compat mode reproduces
    reference quirks the strict tables deliberately do not."""
    if os.environ.get("GK_JOIN_ASSERT", "0") != "1":
        return False
    from ..engine.compat import bug_compat_enabled

    return not bug_compat_enabled()


def gv_twin_corner(js: "JoinState", plans, ap, row: int) -> bool:
    """True when a flagged-but-renders-empty cell is explained by the
    DOCUMENTED over-approximation corner (docs/referential.md "Known
    limits"): a dup/count plan's key group for this row contains two
    provider ROWS sharing one object identity (namespace, name) — two
    groupVersions of one object, which the reference's ``identical``
    helper and the count comprehension's [ns, name] head see as one.
    Such cells are legitimate filter work, not a divergence."""
    for plan in plans:
        if plan.agg not in ("dup", "count"):
            continue
        try:
            i = js.plans.index(plan)
        except ValueError:
            continue
        for k in js.row_rkeys[i].get(int(row), ()):
            if share_an_identity(ap, js.providers[i].get(k, ())):
                return True
    return False


def share_an_identity(ap, rows) -> bool:
    """Do two of these provider rows hold one object identity
    (namespace, name): the groupVersion twins of the corner above?"""
    idents = set()
    for r in rows:
        rv = ap.reviews[r] if r < len(ap.reviews) else None
        if rv is not None:
            idents.add((rv.get("namespace", ""), rv.get("name", "")))
    return len(idents) < len(rows)


def note_false_positive(kind: str, name: str, row: int):
    """Record (and under GK_JOIN_ASSERT raise on) an exact-join-plan cell
    whose interpreter render produced nothing."""
    from ..metrics.catalog import record_join_divergence

    record_join_divergence(kind)
    if assert_enabled():
        raise JoinDivergence(
            f"join plan flagged ({kind}/{name}, row {row}) but the "
            "interpreter oracle renders no violation — interned-key "
            "normalization or aggregate divergence"
        )


# ---------------------------------------------------------------------------
# Clause classification (called from ops/vectorizer.py)
# ---------------------------------------------------------------------------


def _is_wild(op) -> bool:
    from ..rego.ast import Var

    return isinstance(op, Var) and op.is_wildcard


def _scalar_str(op) -> Optional[str]:
    from ..rego.ast import Scalar

    if isinstance(op, Scalar) and isinstance(op.value, str):
        return op.value
    return None


def _inventory_iter(rhs) -> Optional[Tuple[str, str, Dict[str, Any]]]:
    """Recognize ``data.inventory.namespace[ns][gv][Kind][name]`` /
    ``data.inventory.cluster[gv][Kind][name]`` -> (scope, kind,
    {"ns": var|None, "name": var|None}).  Non-kind operands must be
    wildcards or plain vars (bound only inside a comprehension head)."""
    from ..rego.ast import Ref, Var

    if not (isinstance(rhs, Ref) and isinstance(rhs.head, Var)
            and rhs.head.name == "data"):
        return None
    ops = rhs.operands
    if not ops or _scalar_str(ops[0]) != "inventory":
        return None
    ops = ops[1:]
    scope = _scalar_str(ops[0]) if ops else None
    if scope == "namespace" and len(ops) == 5:
        ns_op, gv_op, kind_op, name_op = ops[1], ops[2], ops[3], ops[4]
    elif scope == "cluster" and len(ops) == 4:
        ns_op, gv_op, kind_op, name_op = None, ops[1], ops[2], ops[3]
    else:
        return None
    kind = _scalar_str(kind_op)
    if kind is None:
        return None

    def var_or_wild(op):
        return op is None or isinstance(op, Var)

    if not (var_or_wild(ns_op) and var_or_wild(gv_op)
            and var_or_wild(name_op)):
        return None
    return scope, kind, {"ns": ns_op, "gv": gv_op, "name": name_op}


def _remote_rel_path(rhs, inv_var: str) -> Optional[Tuple[str, ...]]:
    """``other.spec.rules[_].host`` -> ('spec', 'rules', '[]', 'host')."""
    from ..rego.ast import Ref, Var

    if not (isinstance(rhs, Ref) and isinstance(rhs.head, Var)
            and rhs.head.name == inv_var):
        return None
    segs: List[str] = []
    for op in rhs.operands:
        s = _scalar_str(op)
        if s is not None:
            segs.append(s)
        elif _is_wild(op):
            segs.append("[]")
        else:
            return None
    return tuple(segs)


def _remote_colspec(rel: Tuple[str, ...], form: Tuple[str, ...] = ()):
    """Remote rel path (object-relative) -> joinkey ColumnSpec over the
    packed review rows (which nest the raw object under 'object')."""
    from .columns import ColumnSpec

    segs = ("object",) + rel
    if form:
        return ColumnSpec("joinkey", (), segs, form=form), False
    if "[]" in segs:
        last = len(segs) - 1 - segs[::-1].index("[]")
        return ColumnSpec(
            "joinkey", (tuple(segs[: last + 1]),), tuple(segs[last + 1:])
        ), True
    return ColumnSpec("joinkey", (), segs), False


def _vars_in(node) -> set:
    """Non-wildcard variable names referenced anywhere under a term."""
    from ..rego.ast import (
        ArrayCompr, ArrayTerm, BinOp, Call, ObjectCompr, ObjectTerm, Ref,
        SetCompr, SetTerm, UnaryMinus, Var,
    )

    out: set = set()
    stack = [node]
    while stack:
        n = stack.pop()
        if isinstance(n, Var):
            if not n.is_wildcard:
                out.add(n.name)
        elif isinstance(n, Ref):
            stack.append(n.head)
            stack.extend(n.operands)
        elif isinstance(n, Call):
            stack.extend(n.args)
        elif isinstance(n, (ArrayTerm, SetTerm)):
            stack.extend(n.items)
        elif isinstance(n, ObjectTerm):
            for k, v in n.pairs:
                stack.append(k)
                stack.append(v)
        elif isinstance(n, (ArrayCompr, SetCompr)):
            stack.append(n.head)
            for e in n.body:
                stack.extend(e.terms)
        elif isinstance(n, ObjectCompr):
            stack.append(n.key)
            stack.append(n.value)
            for e in n.body:
                stack.extend(e.terms)
        elif isinstance(n, BinOp):
            stack.append(n.lhs)
            stack.append(n.rhs)
        elif isinstance(n, UnaryMinus):
            stack.append(n.operand)
    return out


class _NoMatch(Exception):
    pass


class _ClauseScan:
    """Order-insensitive partition of a violation clause body into the
    roles the family matchers consume.  The rego safety pass may reorder
    statements, so nothing here depends on source order."""

    def __init__(self, vec, rule):
        self.vec = vec
        self.rule = rule
        self.assigns: List = []        # (lhs_name, rhs, stmt)
        self.conds: List = []          # plain term statements
        self.nots: List = []           # 'not' statements
        for stmt in rule.body:
            if stmt.withs:
                raise _NoMatch()  # document patching: interpreter-only
            if stmt.kind == "some":
                continue
            if stmt.kind in ("assign", "unify"):
                from ..rego.ast import Var

                lhs = stmt.terms[0]
                if isinstance(lhs, Var):
                    self.assigns.append((lhs.name, stmt.terms[1], stmt))
                    continue
                raise _NoMatch()
            if stmt.kind == "not":
                self.nots.append(stmt)
                continue
            self.conds.append(stmt)


def _pairs_key_helper(vec, name: str):
    """A key COMPUTED by a helper of the shape of upstream's
    ``flatten_selector``::

        f(obj) = out {
          xs := [s | s = concat(KV, [k, v]); v = obj.<path>[k]]
          out := concat(SEP, sort(xs))
        }

    -> (path, ("joinpairs", KV, SEP)) — the key form ops/columns.py
    extracts through :func:`join_pairs` — else None.  Statement order is
    free (the safety pass reorders)."""
    from ..rego.ast import ArrayCompr, ArrayTerm, Call, Ref, Var

    rules = vec.cm.rules.get(name) or []
    if len(rules) != 1:
        return None
    r = rules[0]
    if not (r.is_function and len(r.args or ()) == 1 and r.els is None
            and isinstance(r.args[0], Var) and isinstance(r.value, Var)
            and len(r.body) == 2):
        return None

    def bound(body) -> dict:
        """{var: term} of a body made of `var := term` statements only
        (fewer entries than statements otherwise)."""
        return {
            stmt.terms[0].name: stmt.terms[1] for stmt in body
            if stmt.kind in ("assign", "unify") and not stmt.withs
            and isinstance(stmt.terms[0], Var)
        }

    def concat_of(t):
        if isinstance(t, Call) and t.path == ("concat",) \
                and len(t.args) == 2:
            return _scalar_str(t.args[0]), t.args[1]
        return None, None

    obj, out = r.args[0].name, r.value.name
    got = bound(r.body)
    if len(got) != 2 or out not in got:
        return None
    (xs, compr), = ((n, t) for n, t in got.items() if n != out)
    item_sep, sorted_xs = concat_of(got[out])
    if not (item_sep is not None and isinstance(sorted_xs, Call)
            and sorted_xs.path == ("sort",) and len(sorted_xs.args) == 1
            and isinstance(sorted_xs.args[0], Var)
            and sorted_xs.args[0].name == xs):
        return None
    if not (isinstance(compr, ArrayCompr) and isinstance(compr.head, Var)
            and len(compr.body) == 2):
        return None
    inner = bound(compr.body)
    s_var = compr.head.name
    if len(inner) != 2 or s_var not in inner:
        return None
    (v_var, ref), = ((n, t) for n, t in inner.items() if n != s_var)
    kv_sep, pair = concat_of(inner[s_var])
    if not (kv_sep is not None and isinstance(pair, ArrayTerm)
            and len(pair.items) == 2
            and all(isinstance(x, Var) for x in pair.items)
            and pair.items[1].name == v_var):
        return None
    k_var = pair.items[0].name
    if not (isinstance(ref, Ref) and isinstance(ref.head, Var)
            and ref.head.name == obj and len(ref.operands) >= 2
            and isinstance(ref.operands[-1], Var)
            and ref.operands[-1].name == k_var
            and len({obj, out, xs, s_var, v_var, k_var}) == 6):
        return None
    path = tuple(_scalar_str(op) for op in ref.operands[:-1])
    if None in path:
        return None
    return path, ("joinpairs", kv_sep, item_sep)


def _computed_key(vec, rhs):
    """``f(<arg>)`` with ``f`` a computed-key helper -> (arg term, path
    under the argument, form), else None."""
    from ..rego.ast import Call

    if not (isinstance(rhs, Call) and len(rhs.path) == 1
            and len(rhs.args) == 1):
        return None
    got = _pairs_key_helper(vec, rhs.path[0])
    if got is None:
        return None
    return rhs.args[0], got[0], got[1]


def _local_key_operand(vec, rhs, state):
    """Resolve a local key source (iteration -> slot, a review-rooted
    scalar path, or a key computed from a review-rooted object) and
    register its joinkey column.  Returns (colkey, slot?)."""
    from .columns import ColumnSpec
    from .vectorizer import SPath, _Unsupported

    computed = _computed_key(vec, rhs)
    if computed is not None:
        arg, path, form = computed
        try:
            sym = vec._resolve(arg, {}, state)
        except _Unsupported:
            raise _NoMatch()
        if not (isinstance(sym, SPath) and sym.root == "review"):
            raise _NoMatch()
        spec = ColumnSpec("joinkey", (), tuple(sym.segs) + path, form=form)
        vec.columns[spec.key] = spec
        return spec.key, False
    try:
        it = vec._try_iteration(rhs, {}, state)
    except _Unsupported:
        it = None
    if it is not None:
        spec = ColumnSpec("joinkey", it.root[1], tuple(it.segs))
        vec.columns[spec.key] = spec
        return spec.key, True
    try:
        sym = vec._resolve(rhs, {}, state)
    except _Unsupported:
        raise _NoMatch()
    if isinstance(sym, SPath) and sym.root == "review":
        spec = ColumnSpec("joinkey", (), tuple(sym.segs))
        vec.columns[spec.key] = spec
        return spec.key, False
    raise _NoMatch()


def _strip_identity(node, inv_var: Optional[str]):
    """The term with every ``<inv_var>.metadata.name`` /
    ``.metadata.namespace`` ref replaced by a constant: what is left of
    the provider row after its IDENTITY is taken out."""
    from ..rego.ast import ArrayTerm, Call, Ref, Scalar, SetTerm, Var

    if isinstance(node, Ref):
        if (inv_var is not None and isinstance(node.head, Var)
                and node.head.name == inv_var
                and [_scalar_str(op) for op in node.operands]
                in (["metadata", "name"], ["metadata", "namespace"])):
            return Scalar("")
        return node
    if isinstance(node, Call):
        return Call(node.path, tuple(
            _strip_identity(a, inv_var) for a in node.args))
    if isinstance(node, (ArrayTerm, SetTerm)):
        return type(node)(tuple(
            _strip_identity(x, inv_var) for x in node.items))
    return node


def _check_benign_guards(scan, consumed: set, remote_vars: set,
                         inv_var: Optional[str] = None):
    """Assignments the matcher did not consume must be benign calls
    (sprintf & friends).  A message that embeds the OTHER row's fields
    depends on group content the delta invalidation cannot see, so such
    clauses stay on the interpreter tier — with ONE exception, the
    identity rule: the provider's identity (the inventory iteration's own
    namespace / name variables, which the caller leaves out of
    ``remote_vars``, and ``<inv_var>.metadata.name`` /
    ``.metadata.namespace``) may be named.
    Which identities share a key changes only when a row joins or leaves
    the key's group or a group's row changes identity, and every such
    row is a dirty row of that group, whose readers JoinState re-renders
    (``affected`` / ``commit`` / ``rebuild``).  Any other remote field
    can change under a reader without its group changing.  The violation
    head is checked the same way."""
    from ..rego.ast import Call

    from .vectorizer import _BENIGN_CALLS

    for name, rhs, _stmt in scan.assigns:
        if name in consumed:
            continue
        if not (isinstance(rhs, Call)
                and ".".join(rhs.path) in _BENIGN_CALLS):
            raise _NoMatch()
        if _vars_in(_strip_identity(rhs, inv_var)) & remote_vars:
            raise _NoMatch()
    if scan.rule.key is not None and _vars_in(
        _strip_identity(scan.rule.key, inv_var)
    ) & remote_vars:
        raise _NoMatch()


def _match_dup(vec, scan: _ClauseScan):
    """unique-key family: a local key (slot, scalar, or computed from a
    map), an inventory iteration of the same kind, a remote key of the
    same form equal to the local key, and an object-identity
    self-exclusion helper under ``not``.  The iteration may BIND its
    namespace / name variables when the clause uses them only to name the
    provider (the identity rule, ``_check_benign_guards``); conditions on
    the local row alone (``input.review.kind.kind == "Service"``) compile
    through the vectorizer and AND with the aggregate."""
    from ..rego.ast import BinOp, Call, Ref, Var

    state = {"slot": None}
    inv = None
    inv_var = None
    identity_vars: set = set()
    for name, rhs, _stmt in scan.assigns:
        got = _inventory_iter(rhs)
        if got is not None:
            if inv is not None:
                raise _NoMatch()
            scope, kind, vs = got
            if not vs["gv"].is_wildcard:
                raise _NoMatch()
            # a bound namespace / name var is the provider's identity;
            # used anywhere but a message it would correlate with the
            # local row (checked below: conditions may not mention it)
            identity_vars = {
                v.name for v in (vs["ns"], vs["name"])
                if v is not None and not v.is_wildcard
            }
            inv, inv_var = (scope, kind), name
    if inv is None:
        raise _NoMatch()
    scope, kind = inv
    # remote key: a var assigned from `other.<path>[_]...` or from a
    # computed-key helper applied to `other`, or a direct
    # `other.<path> == key` comparison side
    remote_key_vars: Dict[str, Tuple] = {}
    for name, rhs, _stmt in scan.assigns:
        if name == inv_var:
            continue
        rel = _remote_rel_path(rhs, inv_var)
        if rel is not None:
            remote_key_vars[name] = (rel, ())
            continue
        computed = _computed_key(vec, rhs)
        if computed is not None and isinstance(computed[0], Var) \
                and computed[0].name == inv_var:
            remote_key_vars[name] = (computed[1], computed[2])
    remote_vars = {inv_var} | set(remote_key_vars) | identity_vars

    # the equality condition joining local and remote keys decides which
    # local var is the key; every other condition is on the local row
    remote_key = None
    local_var = None
    local_conds: List = []
    for stmt in scan.conds:
        t = stmt.terms[0]
        if not _vars_in(t) & remote_vars:
            local_conds.append(stmt)
            continue
        if not (isinstance(t, BinOp) and t.op == "=="):
            raise _NoMatch()
        for a, b in ((t.lhs, t.rhs), (t.rhs, t.lhs)):
            if not isinstance(a, Var) or a.name in remote_vars:
                continue
            if isinstance(b, Var):
                rk = remote_key_vars.get(b.name)
            else:
                rel = _remote_rel_path(b, inv_var)
                rk = None if rel is None else (rel, ())
            if rk is not None:
                if remote_key is not None:
                    raise _NoMatch()  # one join equality per clause
                remote_key, local_var = rk, a.name
                break
        else:
            raise _NoMatch()
    if remote_key is None or local_var is None:
        raise _NoMatch()
    local_key = None
    for name, rhs, _stmt in scan.assigns:
        if name == local_var:
            local_key = _local_key_operand(vec, rhs, state)
    if local_key is None:
        raise _NoMatch()

    # the self-exclusion: not identical(other, input.review)
    if len(scan.nots) != 1:
        raise _NoMatch()
    inner = scan.nots[0].terms[0]
    t = inner.terms[0] if getattr(inner, "kind", None) == "term" else None
    if not (isinstance(t, Call) and len(t.path) == 1 and len(t.args) == 2):
        raise _NoMatch()
    a0, a1 = t.args
    if not (isinstance(a0, Var) and a0.name == inv_var):
        raise _NoMatch()
    if not (isinstance(a1, Ref) and isinstance(a1.head, Var)
            and a1.head.name == "input"
            and [_scalar_str(op) for op in a1.operands] == ["review"]):
        raise _NoMatch()
    _check_identity_helper(vec, t.path[0], scope)

    _check_benign_guards(
        scan, {local_var, inv_var} | set(remote_key_vars),
        remote_vars - identity_vars, inv_var=inv_var,
    )

    from .vectorizer import _Unsupported
    from .vexpr import Clause, JoinCmp, Lit

    rspec, rslot = _remote_colspec(*remote_key)
    if (rspec.key, rslot) != (local_key[0], local_key[1]):
        # self-exclusion (counts - own contribution) is only exact when
        # the local key IS the row's provider key — different local and
        # remote paths or forms stay on the interpreter tier
        raise _NoMatch()
    conds: List = []
    for stmt in local_conds:
        try:
            vec._compile_stmt(stmt, {}, conds, state, exact_required=True)
        except _Unsupported:
            raise _NoMatch()
    if state["slot"] is not None and not local_key[1]:
        raise _NoMatch()  # a local condition opened a slot axis
    vec.columns[rspec.key] = rspec
    plan = JoinPlan(
        agg="dup", local_colkey=local_key[0], local_slot=local_key[1],
        remote_scope=scope, remote_kind=kind,
        remote_colkey=rspec.key, remote_slot=rslot,
    )
    pid = _register_plan(vec, plan)
    # "another object provides my key": distinct provider rows at the
    # key, minus this row's own contribution, >= 1
    node = JoinCmp(pid, ">=", Lit(1), slot=local_key[1],
                   exclude_self=True)
    return Clause(conds=tuple(conds) + (node,), slot_iter=state["slot"])


def _is_apiversion_helper(vec, name: str) -> bool:
    """``make_apiversion(kind)``: "<group>/<version>", or the bare version
    for the core group — the apiVersion of the object a review is of, so
    ``other.apiVersion == make_apiversion(review.kind)`` holds when
    ``other`` is the reviewed row itself."""
    from ..rego.ast import ArrayTerm, BinOp, Call, Ref, Var

    rules = vec.cm.rules.get(name) or []
    forms = set()
    for r in rules:
        if not (r.is_function and len(r.args or ()) == 1
                and r.els is None and isinstance(r.args[0], Var)
                and isinstance(r.value, Var)):
            return False
        k, out = r.args[0].name, r.value.name
        alias: Dict[str, str] = {}

        def field(t):
            if isinstance(t, Var):
                return alias.get(t.name)
            if (isinstance(t, Ref) and isinstance(t.head, Var)
                    and t.head.name == k and len(t.operands) == 1):
                f = _scalar_str(t.operands[0])
                return f if f in ("group", "version") else None
            return None

        group_empty = None
        form = None
        for stmt in r.body:
            t0 = stmt.terms[0]
            if stmt.kind in ("assign", "unify") and isinstance(t0, Var):
                rhs = stmt.terms[1]
                if t0.name != out:
                    if field(rhs) is None:
                        return False
                    alias[t0.name] = field(rhs)
                elif field(rhs) == "version":
                    form = "v"
                elif (isinstance(rhs, Call) and rhs.path == ("sprintf",)
                        and len(rhs.args) == 2
                        and _scalar_str(rhs.args[0]) == "%v/%v"
                        and isinstance(rhs.args[1], ArrayTerm)
                        and [field(x) for x in rhs.args[1].items]
                        == ["group", "version"]):
                    form = "gv"
                else:
                    return False
            elif (stmt.kind == "term" and isinstance(t0, BinOp)
                    and t0.op in ("==", "!=")):
                sides = {field(t0.lhs), field(t0.rhs)}
                lits = {_scalar_str(t0.lhs), _scalar_str(t0.rhs)}
                if sides != {"group", None} or "" not in lits:
                    return False
                group_empty = t0.op == "=="
            else:
                return False
        if (form, group_empty) not in (("v", True), ("gv", False)):
            return False
        forms.add(form)
    return forms == {"v", "gv"}


def _check_identity_helper(vec, name: str, scope: str):
    """The self-exclusion helper must compare exactly the fields that
    identify an object in the plan's scope: metadata.name (+ namespace
    when namespace-scoped), against the review's object or the review's
    own name / namespace.  It may also hold the provider to the review's
    kind (``o.kind == review.kind.kind``) and apiVersion
    (``o.apiVersion == make_apiversion(review.kind)``): both hold when
    ``o`` is the reviewed row itself, so the row is still its own only
    exclusion; the apiVersion check makes groupVersion twins two
    objects, as the distinct-row aggregate counts them.  Anything else
    narrows or widens identity in ways the aggregate cannot express."""
    from ..rego.ast import BinOp, Call, Ref, Var

    rules = vec.cm.rules.get(name) or []
    if len(rules) != 1:
        raise _NoMatch()
    r = rules[0]
    if not r.is_function or len(r.args or ()) != 2 or r.els is not None:
        raise _NoMatch()
    if r.value is not None:
        from ..rego.ast import Scalar

        if not (isinstance(r.value, Scalar) and r.value.value is True):
            raise _NoMatch()
    o_var, rv_var = r.args
    if not (isinstance(o_var, Var) and isinstance(rv_var, Var)):
        raise _NoMatch()

    def segs_of(t, head):
        if not (isinstance(t, Ref) and isinstance(t.head, Var)
                and t.head.name == head):
            return None
        segs = [_scalar_str(op) for op in t.operands]
        return None if None in segs else segs

    def same_kind_or_version(a, b2) -> bool:
        o = segs_of(a, o_var.name)
        if o == ["kind"]:
            return segs_of(b2, rv_var.name) == ["kind", "kind"]
        if o == ["apiVersion"]:
            return (isinstance(b2, Call) and len(b2.path) == 1
                    and len(b2.args) == 1
                    and segs_of(b2.args[0], rv_var.name) == ["kind"]
                    and _is_apiversion_helper(vec, b2.path[0]))
        return False

    fields = set()
    for stmt in r.body:
        if stmt.kind != "term" or not isinstance(stmt.terms[0], BinOp):
            raise _NoMatch()
        b = stmt.terms[0]
        if b.op != "==":
            raise _NoMatch()
        for a, b2 in ((b.lhs, b.rhs), (b.rhs, b.lhs)):
            o = segs_of(a, o_var.name)
            rv = segs_of(b2, rv_var.name)
            if (o is not None and len(o) == 2 and o[0] == "metadata"
                    and rv in (["object"] + o, [o[1]])):
                fields.add(o[1])
                break
            if same_kind_or_version(a, b2):
                break
        else:
            raise _NoMatch()
    want = {"name", "namespace"} if scope == "namespace" else {"name"}
    if fields != want:
        raise _NoMatch()


def _match_exists(vec, scan: _ClauseScan):
    """required-reference family: a local reference value and a ``not
    exists(ref)`` helper iterating the inventory for a row whose remote
    key equals it."""
    from ..rego.ast import BinOp, Call, Ref, Var

    if len(scan.nots) != 1 or scan.conds:
        raise _NoMatch()
    inner = scan.nots[0].terms[0]
    t = inner.terms[0] if getattr(inner, "kind", None) == "term" else None
    if not (isinstance(t, Call) and len(t.path) == 1 and len(t.args) == 1):
        raise _NoMatch()
    arg = t.args[0]
    if not isinstance(arg, Var):
        raise _NoMatch()
    local_var = arg.name
    state = {"slot": None}
    local_key = None
    for name, rhs, _stmt in scan.assigns:
        if name == local_var:
            local_key = _local_key_operand(vec, rhs, state)
    if local_key is None:
        raise _NoMatch()

    # the helper: one clause, one inventory iteration + one equality
    rules = vec.cm.rules.get(t.path[0]) or []
    if len(rules) != 1:
        raise _NoMatch()
    r = rules[0]
    if not r.is_function or len(r.args or ()) != 1 or r.els is not None:
        raise _NoMatch()
    p = r.args[0]
    if not isinstance(p, Var):
        raise _NoMatch()
    inv = None
    inv_var = None
    eqs = []
    for stmt in r.body:
        if stmt.withs:
            raise _NoMatch()
        if stmt.kind in ("assign", "unify") and isinstance(
            stmt.terms[0], Var
        ):
            got = _inventory_iter(stmt.terms[1])
            if got is not None and inv is None:
                scope, kind, vs = got
                for v in (vs["ns"], vs["gv"], vs["name"]):
                    if v is not None and not v.is_wildcard:
                        raise _NoMatch()
                inv, inv_var = (scope, kind), stmt.terms[0].name
                continue
            raise _NoMatch()
        if stmt.kind == "term" and isinstance(stmt.terms[0], BinOp):
            eqs.append(stmt.terms[0])
            continue
        raise _NoMatch()
    if inv is None or len(eqs) != 1:
        raise _NoMatch()
    scope, kind = inv
    b = eqs[0]
    if b.op != "==":
        raise _NoMatch()
    remote_rel = None
    for a, c in ((b.lhs, b.rhs), (b.rhs, b.lhs)):
        rel = _remote_rel_path(a, inv_var)
        if rel is not None and isinstance(c, Var) and c.name == p.name:
            remote_rel = rel
    if remote_rel is None:
        raise _NoMatch()

    _check_benign_guards(scan, {local_var}, set())

    from .vexpr import Clause, JoinCmp, Lit

    rspec, rslot = _remote_colspec(remote_rel)
    vec.columns[rspec.key] = rspec
    plan = JoinPlan(
        agg="exists", local_colkey=local_key[0], local_slot=local_key[1],
        remote_scope=scope, remote_kind=kind,
        remote_colkey=rspec.key, remote_slot=rslot,
    )
    pid = _register_plan(vec, plan)
    node = JoinCmp(pid, "==", Lit(0), slot=local_key[1])
    return Clause(conds=(node,), slot_iter=state["slot"])


def _match_count(vec, scan: _ClauseScan):
    """count-quota family: ``n := count({ident | p := data.inventory...;
    p.<path> == key})`` compared against a parameter (or literal)."""
    from ..rego.ast import ArrayTerm, BinOp, Call, SetCompr, Var

    from .vectorizer import SConst, SPath, _Unsupported

    if scan.nots:
        raise _NoMatch()
    count_var = None
    compr = None
    for name, rhs, _stmt in scan.assigns:
        if (isinstance(rhs, Call) and rhs.path == ("count",)
                and len(rhs.args) == 1
                and isinstance(rhs.args[0], SetCompr)):
            if count_var is not None:
                raise _NoMatch()
            count_var, compr = name, rhs.args[0]
    if compr is None:
        raise _NoMatch()

    # the comprehension body: inventory iteration (scope vars may bind)
    # + one equality between a remote rel path and an outer-scope key
    inv = None
    inv_var = None
    inv_vars: Dict[str, Any] = {}
    eqs = []
    for stmt in compr.body:
        if stmt.withs:
            raise _NoMatch()
        if stmt.kind in ("assign", "unify") and isinstance(
            stmt.terms[0], Var
        ):
            got = _inventory_iter(stmt.terms[1])
            if got is not None and inv is None:
                scope, kind, vs = got
                inv, inv_var = (scope, kind), stmt.terms[0].name
                inv_vars = vs
                continue
            raise _NoMatch()
        if stmt.kind == "term" and isinstance(stmt.terms[0], BinOp):
            eqs.append(stmt.terms[0])
            continue
        raise _NoMatch()
    if inv is None or len(eqs) != 1:
        raise _NoMatch()
    scope, kind = inv
    b = eqs[0]
    if b.op != "==":
        raise _NoMatch()
    remote_rel = None
    key_var = None
    for a, c in ((b.lhs, b.rhs), (b.rhs, b.lhs)):
        rel = _remote_rel_path(a, inv_var)
        if rel is not None and isinstance(c, Var):
            remote_rel, key_var = rel, c.name
    if remote_rel is None:
        raise _NoMatch()

    # the head must enumerate object IDENTITY so count() counts distinct
    # inventory rows: [ns, name] when namespaced, the name var clusterwide
    def head_ok():
        ns_v = inv_vars.get("ns")
        name_v = inv_vars.get("name")
        name_name = name_v.name if isinstance(name_v, Var) and not \
            name_v.is_wildcard else None
        if name_name is None:
            return False
        if scope == "cluster":
            h = compr.head
            return isinstance(h, Var) and h.name == name_name
        ns_name = ns_v.name if isinstance(ns_v, Var) and not \
            ns_v.is_wildcard else None
        h = compr.head
        if ns_name is None or not isinstance(h, ArrayTerm):
            return False
        names = [
            x.name for x in h.items
            if isinstance(x, Var) and not x.is_wildcard
        ]
        return len(h.items) == 2 and sorted(names) == sorted(
            [ns_name, name_name]
        )

    if not head_ok():
        raise _NoMatch()

    # local key: the outer assignment the comprehension's key var names
    state = {"slot": None}
    local_key = None
    for name, rhs, _stmt in scan.assigns:
        if name == key_var:
            local_key = _local_key_operand(vec, rhs, state)
    if local_key is None or local_key[1]:
        raise _NoMatch()  # quota keys are scalar (one group per row)

    # the threshold comparison: n <op> parameter/literal
    cmp_node = None
    for stmt in scan.conds:
        t = stmt.terms[0]
        if not isinstance(t, BinOp):
            raise _NoMatch()
        from .vectorizer import _CMP_OPS, _flip

        if t.op not in _CMP_OPS:
            raise _NoMatch()
        for a, c, op in ((t.lhs, t.rhs, t.op), (t.rhs, t.lhs, _flip(t.op))):
            if isinstance(a, Var) and a.name == count_var:
                try:
                    sym = vec._resolve(c, {}, state)
                except _Unsupported:
                    raise _NoMatch()
                from .vexpr import Lit, ParamRef

                if isinstance(sym, SPath) and sym.root == "params":
                    vec.param_scalars.add(sym.segs)
                    rhs_op = ParamRef(sym.segs)
                elif isinstance(sym, SConst) and isinstance(
                    sym.value, (int, float)
                ) and not isinstance(sym.value, bool):
                    rhs_op = Lit(sym.value)
                else:
                    raise _NoMatch()
                if cmp_node is not None:
                    raise _NoMatch()
                cmp_node = (op, rhs_op)
                break
        else:
            raise _NoMatch()
    if cmp_node is None:
        raise _NoMatch()

    _check_benign_guards(scan, {key_var, count_var}, set())

    from .vexpr import Clause, JoinCmp

    rspec, rslot = _remote_colspec(remote_rel)
    vec.columns[rspec.key] = rspec
    plan = JoinPlan(
        agg="count", local_colkey=local_key[0], local_slot=False,
        remote_scope=scope, remote_kind=kind,
        remote_colkey=rspec.key, remote_slot=rslot,
    )
    pid = _register_plan(vec, plan)
    node = JoinCmp(pid, cmp_node[0], cmp_node[1], slot=False)
    return Clause(conds=(node,), slot_iter=None)


def _register_plan(vec, plan: JoinPlan) -> int:
    plans = vec.join_plans
    for i, p in enumerate(plans):
        if p == plan:
            return i
    plans.append(plan)
    return len(plans) - 1


def classify_join_clause(vec, rule):
    """Try every referential family matcher against a violation clause.
    Returns a vexpr Clause (with the JoinPlan registered on the
    vectorizer) or None when no family matches — the caller then falls
    back to the generic (over-approximate) compilation."""
    try:
        scan = _ClauseScan(vec, rule)
    except _NoMatch:
        return None
    for matcher in (_match_count, _match_dup, _match_exists):
        try:
            return matcher(vec, scan)
        except _NoMatch:
            continue
    return None
