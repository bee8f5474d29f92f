"""TpuDriver: the vectorized JAX/XLA evaluation backend.

Pipeline per Review/Audit:
  1. pack reviews + constraints to integer tensors (host, incremental interner)
  2. device: match kernel -> bool[C, R]; per-kind violation programs
     (vectorizer output) -> bool[C_k, R]; combined candidate mask
  3. host: for each positive cell, exact native match re-check + violation
     rendering — via the compiled render plan (ops/renderplan.py: exact
     direct-value evaluation + message assembly, the bulk path) when the
     template's program is exact and its message AST compiled, else the
     interpreter (the residual tail, drained by a bounded worker pool)

Correctness therefore never depends on the device mask being tight — only
throughput does.  Templates with no vectorized program get all-true columns
(pure interpreter fallback for their cells).  Per-cell render tiers are
exported as render_cells_total{plan=static|slots|interp}.
"""

from __future__ import annotations

import copy
import logging
import os
import sys
import threading as _threading_mod
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import deadline as _deadline
from .. import faults
from ..metrics.catalog import (
    DISPATCH_M,
    record_cache,
    record_cs_refresh,
    record_dispatch_upload,
    record_join_upkeep,
    record_render_cells,
    record_review_pack,
    record_stage,
)
from ..obs import costs as obscosts
from ..obs import trace as obstrace
from ..client.drivers import (
    CompiledTemplate,
    InterpDriver,
    Result,
    constraint_match_spec,
    constraint_parameters,
)
from ..target.match import constraint_matches, needs_autoreject
from ..target.target import K8sValidationTarget
from . import joinreview, reviewbuf
from .columns import extract_columns
from .interning import Interner, PredicateTable
from .matchkernel import match_kernel
from .pack import _bucket as _bucket_pow2, pack_constraints, pack_reviews
from .params import fill_table_columns, pack_params
from .vectorizer import vectorize
from .vexpr import EvalEnv, VProgram, eval_program

log = logging.getLogger("gatekeeper_tpu.driver")


def _tree_sig(tree):
    """Shape/dtype/structure signature of a pytree: two sides with equal
    signatures produce identical traces for the same program structure."""
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    return (
        str(treedef),
        tuple(
            (tuple(getattr(l, "shape", ())), str(getattr(l, "dtype", "")))
            for l in leaves
        ),
    )


_REDUCTION_BLOCK = 64

# _bound_plans miss sentinel (None is a valid cached "no plan")
_PLAN_MISS = object()


class _PackedSide:
    """The packed constraint side with what it was packed for: the
    constraint epoch, and — for the string-predicate tables, the only
    leaves that read the vocabulary — the padded width they share and
    `table_vocab`, the prefix of string ids their columns cover.  The
    host cache, the device copy and the async compile thread's cs_key
    all key on `key()` and ask `covers()`; nothing compares vocabulary
    sizes at a call site."""

    __slots__ = ("epoch", "width", "table_vocab", "side", "sig", "tables")

    def __init__(self, epoch, side, tables, table_vocab):
        self.epoch = epoch
        self.side = side
        self.sig = None  # TpuDriver._structure_sig(side), set once packed
        # [(mat, {(pred, value): row})]: one per str-pred node and group
        self.tables = tables
        # a side without tables reads no string id: any vocabulary is
        # inside its width and covered
        self.width = min((m.shape[1] for m, _s in tables),
                         default=sys.maxsize)
        self.table_vocab = table_vocab if tables else sys.maxsize

    def covers(self, epoch: int, vocab: int) -> bool:
        """Do the tables hold a column for every interned string?"""
        return self.epoch == epoch and vocab <= self.table_vocab

    def extend(self, pred_cache, vocab: int) -> None:
        """Fill the tables' columns [table_vocab, vocab) in place;
        `vocab` is inside `width`, so no shape changes."""
        for mat, stack in self.tables:
            fill_table_columns(mat, stack, pred_cache, self.table_vocab,
                               vocab)
        self.table_vocab = max(self.table_vocab, vocab)

    def key(self) -> tuple:
        """What a device copy of this side is keyed on (epoch, width) and
        tested against (table_vocab).  Read under the driver lock."""
        return (self.epoch, self.width, self.table_vocab)


def _constraint_semantics(constraint: dict):
    """What evaluation reads of a constraint: its spec (match,
    parameters, enforcementAction) and labels — not status,
    resourceVersion or managed metadata."""
    md = constraint.get("metadata")
    labels = md.get("labels") if isinstance(md, dict) else None
    return constraint.get("spec"), labels


def _constraint_name(constraint: dict) -> str:
    md = constraint.get("metadata")
    if isinstance(md, dict):
        return str(md.get("name", ""))
    return ""


def _packed_reduction(mask, K: int):
    """[C] counts + first-K candidate row indices -> one [C, 1+K] int32.
    lax.top_k is stable (equal elements keep index order), so the K
    largest of the 0/1 mask are the K smallest true indices, ascending —
    exactly the first-k walk order the host renders.

    A flat top_k over the full row axis is a width-R sort per constraint
    — measured as 91% of the on-device sweep at 500x100k (r4 verdict #4,
    the "2.25x roofline gap").  The hierarchical form runs two narrow
    top_ks instead: block-OR the mask into R/W blocks, take the first K
    TRUE blocks (every true block holds >= 1 candidate, so the first K
    candidates live in the first <= K true blocks), gather just those
    K x W segments, and resolve the exact first-K within them.  Every
    shape is static; total traffic approaches the one-pass mask read."""
    C, R = mask.shape
    counts = jnp.sum(mask, axis=1, dtype=jnp.int32)
    k = min(K, R)
    W = _REDUCTION_BLOCK
    if k * W * 2 >= R or R % W != 0:
        # small rows (or huge K): the flat sort is already cheap/cheaper
        vals, idx = jax.lax.top_k(mask.astype(jnp.int8), k)
        idx = jnp.where(vals > 0, idx, -1)
        return jnp.concatenate(
            [counts[:, None], idx.astype(jnp.int32)], axis=1
        )
    B = R // W
    blocks = mask.reshape(C, B, W)
    blk_any = jnp.any(blocks, axis=2)
    bvals, bidx = jax.lax.top_k(blk_any.astype(jnp.int8), k)  # first-k blocks
    # gather the K candidate blocks' segments: [C, k, W]
    segs = jnp.take_along_axis(blocks, bidx[:, :, None], axis=1)
    # blocks beyond the true-block count gather arbitrary (all-false)
    # blocks; mask them out explicitly for clarity
    segs = segs & (bvals > 0)[:, :, None]
    flat = segs.reshape(C, k * W)  # ascending global order (bidx sorted)
    gcol = (bidx[:, :, None] * W
            + jnp.arange(W, dtype=jnp.int32)[None, None, :]).reshape(C, k * W)
    vals, pos = jax.lax.top_k(flat.astype(jnp.int8), k)
    idx = jnp.take_along_axis(gcol, pos, axis=1)
    idx = jnp.where(vals > 0, idx, -1)
    return jnp.concatenate([counts[:, None], idx.astype(jnp.int32)], axis=1)


def _merge_sharded_packed(packed_all: np.ndarray, K: int) -> np.ndarray:
    """[N shards, C, 1+K'] per-shard capped reductions -> global
    [C, 1+K].  Counts sum; candidate indices are already global rows
    (-1 padded) and each shard's list is ascending within its contiguous
    row slab, so shard-major concatenation preserves global ascending
    order — the merge keeps the first K valid entries per constraint.
    K' = min(K, rows per shard) may be smaller than K (each shard then
    contributes its COMPLETE row slab, so the merge is still exact);
    the output is padded back to width K for the single-device shape
    contract."""
    counts = packed_all[:, :, 0].sum(axis=0, dtype=np.int32)
    cand = np.transpose(packed_all[:, :, 1:], (1, 0, 2))
    cand = cand.reshape(cand.shape[0], -1)  # [C, N*K'], shard-major
    if cand.shape[1] < K:
        cand = np.pad(cand, ((0, 0), (0, K - cand.shape[1])),
                      constant_values=-1)
    order = np.argsort(cand == -1, axis=1, kind="stable")[:, :K]
    merged = np.take_along_axis(cand, order, axis=1)
    return np.concatenate([counts[:, None], merged], axis=1)


def _scatter_rows_impl(dev_tree, idx, rows_tree):
    """Patch dirty rows into the device-resident audit input trees in ONE
    dispatch (one launch for the whole tree, not one per array leaf)."""
    return jax.tree_util.tree_map(
        lambda d, r: d.at[idx].set(r), dev_tree, rows_tree
    )


_scatter_rows = jax.jit(_scatter_rows_impl)
# Mesh twin: the pre-scatter placement is dead the moment the driver swaps
# its cache entry, and (unlike the single-device path) no lazy MaskSource
# dispatch ever re-reads it — the mesh sweep's mask is an eager co-output.
# Donating lets XLA patch the owning shards' slabs in place instead of
# copying every R-sized buffer per churn sweep.
_scatter_rows_mesh = jax.jit(_scatter_rows_impl, donate_argnums=0)


def _strip_request_meta(frozen_review):
    """The memo key for a review: identical content minus per-request
    metadata (uid), so repeated admissions of the same object hit the
    memo despite fresh uids.  memo_safe policies provably never read
    the stripped fields (engine/interp.py _validate).  ONE implementation
    shared with RowView.memo_frozen — both feed the same _review_memo, so
    the key normalization must never diverge."""
    from .renderplan import strip_request_meta

    return strip_request_meta(frozen_review)


class TpuDriver(InterpDriver):
    """Drop-in Driver with device-side batched evaluation.  Inherits state
    management (templates/constraints/store) and render fallback from
    InterpDriver."""

    def __init__(
        self,
        target: Optional[K8sValidationTarget] = None,
        async_compile: Optional[bool] = None,
        breaker_threshold: Optional[int] = None,
        breaker_cooldown_s: Optional[float] = None,
        mesh_watchdog_s: Optional[float] = None,
    ):
        super().__init__(target)
        # eager native build/load: the g++ compile must happen here, not
        # inside the first admission review under the driver lock
        from ..native import load as _load_native

        _load_native()
        self.interner = Interner()
        self.programs: Dict[str, Optional[VProgram]] = {}
        self.pred_cache: Dict[Tuple[str, str], PredicateTable] = {}
        self._fused = None
        self._fused_key = None
        # the fused fn as the review path calls it (_packed_variant): one
        # review-side buffer in, one [2C, ceil(R/8)] uint8 fetch out; per
        # review-side layout -> (executable, layout), while `fn` stands
        self._fused_packed: dict = {}
        self._fused_packed_src = None
        # beside it, per width tuple of the review side's data, the direct
        # writer's (writer, executable, layout, write plan); and the writer
        # of the column specs now in use
        self._fused_plans: dict = {}
        self._writer = None
        # multi-chip: data-parallel mesh over every visible device (None on
        # single-chip).  GK_MESH=0 forces the single-device path, GK_MESH=1
        # (the default) meshes every visible device, GK_MESH=<n> for n >= 2
        # pins the mesh to the first n devices (a pinned width of 1 is only
        # reachable via set_mesh(True, width=1), which is the single-device
        # path); tests pin bit-parity across settings.  Mutate via
        # set_mesh(), which invalidates every cache keyed on the topology.
        _mesh_env = os.environ.get("GK_MESH", "1")
        self.mesh_enabled = _mesh_env != "0"
        try:
            _w = int(_mesh_env)
        except ValueError:
            _w = -1
        if _w < 0:
            # fail loudly at construction rather than silently meshing
            # every visible device off a typo'd width
            raise ValueError(
                f"GK_MESH={_mesh_env!r} is not a non-negative integer"
            )
        if _w > 1 and _w > len(jax.devices()):
            # same contract as set_mesh: a width the host cannot satisfy
            # would otherwise error on every sweep and silently degrade
            # the whole mesh family to the interpreter tier via the
            # circuit breaker.  (_w <= 1 skips the check so construction
            # does not force JAX backend initialization.)
            raise ValueError(
                f"GK_MESH={_mesh_env} exceeds visible devices "
                f"({len(jax.devices())})"
            )
        self.mesh_width: Optional[int] = _w if _w > 1 else None
        self._mesh_cache: Optional[tuple] = None
        self._device_info: Optional[dict] = None
        # device placement of the replicated constraint side (mesh path):
        # re-uploading vocab-sized tables to N chips every call would cost
        # N host->device transfers; cached on the constraint epoch
        self._cs_device_cache = None
        self._cs_uploaded = 0  # arrays _constraint_device_side last put
        # resident incremental audit packing (ops/auditpack.py) + rendered
        # cell memo: violations for an unchanged (constraint, row) pair are
        # deterministic unless the template reads data.inventory
        from .auditpack import AuditPackCache

        self._audit_pack = AuditPackCache()
        self._render_memo: Dict[Tuple, Tuple[int, list]] = {}
        self._render_memo_epoch = -1
        # compiled render plans (ops/renderplan.py) bound per constraint:
        # (kind, name) -> BoundPlan | None, valid for one constraint-side
        # epoch.  GK_RENDER_PLAN=0 forces every cell to the interpreter.
        self.render_plan_enabled = os.environ.get("GK_RENDER_PLAN", "1") != "0"
        self._bound_plans: Dict[Tuple[str, str], object] = {}
        self._bound_plans_epoch = -1
        self._uses_inventory_cache: Optional[Tuple[int, frozenset]] = None
        self._n_constraints_cache: Optional[Tuple[int, int]] = None
        # per-template constraint counts for the cost ledger's dispatch
        # apportioning (obs/costs.py), cached per constraint-side epoch —
        # attribution must never walk 500 kinds per admission batch
        self._cost_kinds_cache: Optional[Tuple[int, Dict[str, int]]] = None
        # per-pass render-tier counters, flushed to
        # render_cells_total{plan=...} at each render-pass boundary so the
        # hot loop pays a dict increment, not a registry record, per cell
        self._tier_counts = {"static": 0, "slots": 0, "interp": 0}
        # per-pass render instrumentation (read by bench.py's render config)
        self.last_render_stats: Dict[str, float] = {}
        # review-path render memo, keyed by CONTENT (kind, constraint name,
        # frozen review): admission streams are full of identical objects
        # (deployment replicas, retried requests), and an unchanged
        # (constraint, object) cell renders identically unless the template
        # reads data.inventory.  FrozenDict caches its hash, so the review
        # is hashed once and each constraint lookup is O(1).
        self._review_memo: Dict[Tuple, list] = {}
        self._review_memo_epoch = -1
        # whole-request memo (see _request_memoable): content ->
        # (epoch, {(kind, name): [(msg, details, action), ...]}, flat
        # replay list).  Entries from older epochs are REPAIRED via the
        # constraint-side change log (only changed constraints
        # re-evaluate) instead of discarded — a template-ingest storm then
        # costs O(changed) per admission, not O(installed templates) —
        # and current-epoch replays walk the flat list, O(violations).
        self._request_memo: Dict[Tuple, tuple] = {}
        self._request_memo_epoch = -1
        self._request_memo_ok = None
        # (kind, name) of constraints whose cells are NOT content-
        # determined; maintained incrementally by the mutators
        self._memoable_false: set = set()
        self._cs_change_log: List[Tuple[int, str, Optional[str]]] = []
        self._cs_log_floor = 0  # entries with epoch > floor are complete
        # constraint-side packing is invalidated on any template/constraint
        # mutation and on vocabulary growth (str-pred tables are vocab-sized)
        self._cs_epoch = 0
        self._cs_cache = None
        self._ordered_cache = None  # (epoch, sorted constraint list)
        self._gvk_cache = None  # (epoch, {(group, kind): entries}, nssel)
        # bumped only when the fused executable is actually rebuilt (its
        # structure signature changed); dependent jits key on this, so
        # shape-stable constraint churn preserves every warm executable
        self._fused_gen = 0
        # audit-side sweep cache: the production audit loop sweeps a
        # mostly-unchanged inventory every interval; the device is
        # dispatched only when the inventory or constraint side changed.
        # Shape: (key, sweep tuple, host-mask memo or None)
        self._audit_cache = None
        # device-resident review-side audit arrays: [layout_gen, tree].
        # Refreshed by one jitted scatter of just the dirty rows per sweep
        # (full re-upload only on pack layout changes) so a steady-state
        # sweep uploads ~KBs, not the whole 100k-row pack, across the link.
        self._audit_dev = None
        # the mesh twin: [layout_gen, mesh id, sharded (rv, cols)]
        self._audit_dev_mesh = None
        # capped-audit fused fns: packed-only (single-device; the mask is
        # a separate lazy dispatch) and two-output (mesh)
        self._fused_audit = None
        self._fused_audit_key = None
        self._fused_audit_mesh = None
        self._fused_audit_mesh_key = None
        # incremental O(changes) sweep (ops/deltasweep.py): steady-state
        # capped audits evaluate only dirty rows on-device and fold them
        # into host-side counts/candidate state; GK_DELTA=0 forces every
        # sweep down the full-dispatch path
        self.delta_enabled = os.environ.get("GK_DELTA", "1") != "0"
        self._delta_state = None
        self._delta_jit = None
        self._delta_jit_key = None
        # referential-policy state (ops/joinkernel.py): the host-side
        # join-group index (key -> provider/reader rows) that gives the
        # delta sweep O(churn) key-group invalidation, the per-epoch
        # unique-plan cache, and the audit-mode mask executable (the
        # review-mode fused fn resolves JoinCmp to unknown and must
        # never back the delta fold's base mask)
        self._join_state = None
        self._join_plans_cache: Optional[tuple] = None
        self._join_safe_cache: Optional[tuple] = None
        self._fused_mask = None
        self._fused_mask_key = None
        # per-sweep instrumentation (read by bench.py): pack/dispatch/fetch/
        # render wall-times, transferred bytes, rendered cells
        self.last_sweep_stats: Dict[str, float] = {}
        # measured routing cost model (calibrate_routing); None -> the
        # static DEVICE_MIN_CELLS prior decides interp-vs-device
        self._route_cal: Optional[Dict[str, float]] = None
        # offered-load hint (reviews/s, monotonic stamp) from the
        # micro-batcher: with it, routing prices sustainable THROUGHPUT
        # under saturation instead of this batch's latency alone
        self._offered_load: Optional[tuple] = None
        # brownout pin (obs/brownout.py level 3): routing locked to the
        # cheapest SUSTAINABLE (max-throughput) tier regardless of
        # per-batch latency or hint freshness — drain the queue first
        self._brownout_pin = False
        # route-decision ledger (obs/routeledger.py): every batch's
        # pricing decision — shape, offered λ, the priced tier table,
        # chosen tier, overriding reason — bounded, serving
        # /debug/routez and route_decisions_total{tier,reason}.
        # GK_ROUTE_LEDGER=0 disables recording (bench overhead arm).
        from ..obs.routeledger import RouteLedger, set_active

        self.route_ledger = RouteLedger().attach(self)
        self.route_ledger.enabled = (
            os.environ.get("GK_ROUTE_LEDGER", "1") != "0"
        )
        set_active(self.route_ledger)
        # incremental host-serving constraint side (ops/npside.py):
        # admission-sized batches evaluate the same VExpr IR in numpy —
        # no dispatch RTT, no compile, O(1) maintenance per mutation.
        # GK_NP_SERVE=0 disables (reviews then interp-walk as before).
        from .npside import NpSide

        self.np_serve_enabled = os.environ.get("GK_NP_SERVE", "1") != "0"
        self._np_side = NpSide()
        # async ingestion (SURVEY §7 hard-part 3): template/constraint
        # mutations hand the XLA re-compile to a background thread and
        # reviews serve from the interpreter until the new fused
        # executable is warm (ops/asynccompile.py)
        self._compiler = None
        if async_compile is None:
            async_compile = os.environ.get("GK_ASYNC_COMPILE", "0") == "1"
        if async_compile:
            from .asynccompile import AsyncCompiler

            self._compiler = AsyncCompiler(self)
        # circuit breaker over the device compile/dispatch seams: after N
        # consecutive backend failures every evaluation trips to the
        # inherited interpreter tier (semantically identical — the device
        # mask only ever prunes the interpreter walk); a background probe
        # re-tries a tiny real dispatch and one success returns evaluation
        # to the device (ops/breaker.py, docs/failure-modes.md)
        from .breaker import CircuitBreaker

        if breaker_threshold is None:
            breaker_threshold = int(os.environ.get("GK_BREAKER_THRESHOLD", "3"))
        if breaker_cooldown_s is None:
            breaker_cooldown_s = float(
                os.environ.get("GK_BREAKER_COOLDOWN_S", "5.0")
            )
        self.breaker = CircuitBreaker(
            failure_threshold=breaker_threshold,
            cooldown_s=breaker_cooldown_s,
            probe_fn=self._breaker_probe,
            on_transition=self._on_breaker_transition,
        )
        # mesh dispatch watchdog (docs/failure-modes.md): a stuck mesh
        # collective otherwise wedges the sweep thread AND the dispatch
        # gate forever (the breaker trips on exceptions, not on hangs).
        # With a budget set, guarded mesh-audit dispatches run under a
        # bounded join; a timeout raises MeshDispatchStall, which trips
        # the breaker and re-shards the sweep narrower (set_mesh), the
        # abandoned dispatch's gate generation revoked.  0/None disables
        # (the default: no extra thread on the sweep path).  The budget
        # must cover a COLD SPMD trace+compile, not just the dispatch —
        # the first sweep at a new topology compiles inside the guarded
        # region (this jax cannot pre-populate the jit call cache from
        # lower().compile()) — hence the tens-of-seconds production
        # default (main.py --mesh-watchdog-s).
        if mesh_watchdog_s is None:
            mesh_watchdog_s = float(
                os.environ.get("GK_MESH_WATCHDOG_S", "0") or 0
            )
        self.mesh_watchdog_s = mesh_watchdog_s

    # ---- lifecycle --------------------------------------------------------

    def _epoch_bumped(self):
        if self._compiler is not None:
            self._compiler.kick()
            # the async-compile backlog, observable: mutation epoch vs
            # compiled epoch (obs/compilestats.py; compile_epoch_lag)
            from ..obs import compilestats

            compilestats.record_epoch_lag(self._compiler.epoch_lag())

    # ---- circuit breaker ---------------------------------------------------

    # minimal synthetic review the recovery probe dispatches: exercises the
    # real compile + dispatch path without depending on installed templates
    _PROBE_REVIEW = {
        "kind": {"group": "", "version": "v1", "kind": "Pod"},
        "name": "gk-breaker-probe", "namespace": "default",
        "operation": "CREATE",
        "object": {
            "apiVersion": "v1", "kind": "Pod",
            "metadata": {"name": "gk-breaker-probe",
                         "namespace": "default", "labels": {}},
            "spec": {"containers": [{"name": "c", "image": "probe.io/x:1"}]},
        },
    }

    def _breaker_probe(self):
        """One real device round trip (half-open recovery).  Runs the same
        compile/dispatch seams production traffic does — including any
        installed fault-plane schedule — so the breaker closes exactly when
        the backend actually answers again."""
        with self._lock:
            n = sum(len(v) for v in self.constraints.values())
            if n == 0:
                # nothing to evaluate: the compile seam is the best probe
                self._fused_fn()
                return
            # gklint: disable=blocking-under-lock -- compute_masks has
            # always fetched its result under the driver lock (the lock
            # is what keeps the packed inputs and the epoch they were
            # packed for together); the wait is explicit since the stage
            # clock split it from the fetch
            self.compute_masks([copy.deepcopy(self._PROBE_REVIEW)])

    def _on_breaker_transition(self, old: str, new: str):
        # also invoked with old == new by the probe loop as a periodic
        # metrics refresh while degraded — record always, log on change
        if old != new:
            log.warning(
                "tpu circuit breaker %s -> %s%s", old, new,
                " (serving from the interpreter tier)"
                if new != "closed" else "",
            )
            # flight recorder (obs/flightrec.py): the trip/recovery edge
            # lands in the incident ring, and an OPEN edge dumps the ring
            # to disk — the one artifact a post-mortem starts from.
            # Guarded: this runs INSIDE the device-failure handling path,
            # where a recorder defect must degrade, never crash a request
            try:
                from ..obs import flightrec

                flightrec.record(
                    flightrec.BREAKER_TRANSITION, old=old, new=new,
                    trips=self.breaker.trips,
                )
                if new == "open":
                    flightrec.dump("breaker_open")
            except Exception:
                log.debug("flight-recorder feed failed on breaker edge",
                          exc_info=True)
        try:
            from ..metrics.catalog import record_breaker

            record_breaker(self.breaker.status())
        except Exception:
            log.debug("breaker state metric recording failed",
                      exc_info=True)

    def breaker_status(self) -> dict:
        """Health-endpoint view of the degradation ladder."""
        return self.breaker.status()

    def device_info(self) -> dict:
        """Where this process evaluates, as jax reports it — read once
        (the first call initialises the backend if nothing has yet).
        Served on /statusz and the replica ready line: the answers
        cannot show a dead device (the interpreter is the oracle), so
        the process says what it runs on."""
        if self._device_info is None:
            from ..parallel.mesh import device_info

            self._device_info = device_info()
        return dict(self._device_info)

    def chip_info(self) -> dict:
        """{"chip", "device_kind"}: which chip this process holds
        (parallel/mesh.py chip_info), beside device_info() on /statusz
        and the replica ready line."""
        from ..parallel.mesh import chip_info

        return chip_info()

    # review-memo entry bound: each entry retains a frozen admission object
    # (~KBs); 16k entries keeps worst-case memory in the tens of MB and a
    # wholesale clear in the low ms
    REVIEW_MEMO_MAX = 16_384

    # Audit-path compile wait: long enough that no realistic template storm
    # (bench: 500 templates ≈ tens of seconds) ever falls through to the
    # synchronous compile under the driver lock (advisor r2), but bounded so
    # pathological epoch churn (mutations forever outpacing compiles) cannot
    # wedge the audit loop permanently.
    AUDIT_COMPILE_WAIT_S = 600.0

    def wait_ready(self, timeout: Optional[float] = 120.0) -> bool:
        """Block until the fused executable for the current constraint-side
        epoch is compiled (no-op when async compile is off).  timeout=None
        waits indefinitely."""
        if self._compiler is None:
            return True
        return self._compiler.wait(timeout)

    def _wait_ready_for_audit(self):
        import time

        t0 = time.monotonic()
        if not self.wait_ready(timeout=self.AUDIT_COMPILE_WAIT_S):
            import logging

            waited = time.monotonic() - t0
            stopped = self._compiler is not None and self._compiler._stopped
            logging.getLogger("gatekeeper_tpu.driver").warning(
                "audit waited %.1fs for the background compile without it "
                "becoming ready (%s); proceeding with a synchronous compile "
                "under the driver lock",
                waited,
                "compiler stopped" if stopped
                else "sustained template/constraint churn?",
            )

    # constraint-side change log: (epoch-after-change, kind, name-or-None
    # for kind-wide).  Lets the whole-request memo repair entries by
    # re-evaluating ONLY the constraints that changed since the entry was
    # stored — the fix for interp-served admission latency growing O(N)
    # during a template-ingest storm.
    CS_LOG_MAX = 4096

    def _log_cs_change(self, kind: str, name: Optional[str]):
        self._cs_change_log.append((self._cs_epoch, kind, name))
        if len(self._cs_change_log) > self.CS_LOG_MAX:
            drop = len(self._cs_change_log) // 2
            self._cs_log_floor = self._cs_change_log[drop - 1][0]
            del self._cs_change_log[:drop]

    def _memoable_update(self, kind: str, name: Optional[str]):
        """Incrementally maintain the set of constraints whose cells are
        NOT content-determined — _request_memoable is then O(1) instead
        of an O(installed constraints) all() per epoch bump, which
        measurably taxed every mid-storm admission (caller holds lock)."""
        tmpl = self.templates.get(kind)
        names = (
            [name] if name is not None
            else list(self.constraints.get(kind, {}))
        )
        for n in names:
            c = self.constraints.get(kind, {}).get(n)
            key = (kind, n)
            if c is not None and not self._cell_memoable(tmpl, c):
                self._memoable_false.add(key)
            else:
                self._memoable_false.discard(key)

    def _ordered_update(self, kind: str, name: str):
        """Incrementally maintain the sorted constraint list (bisect):
        template churn must not re-sort 500 constraints per admission."""
        cached = self._ordered_cache
        if cached is None:
            return
        lst = cached[1]
        from bisect import bisect_left

        cur = self.constraints.get(kind, {}).get(name)
        i = bisect_left(lst, (kind, name), key=lambda e: (e[0], e[1]))
        present = i < len(lst) and lst[i][:2] == (kind, name)
        if cur is None:
            if present:
                del lst[i]
        elif present:
            lst[i] = (kind, name, cur)
        else:
            lst.insert(i, (kind, name, cur))
        self._ordered_cache = (self._cs_epoch, lst)

    def put_template(self, kind: str, artifact: CompiledTemplate):
        # all mutators hold the driver lock for their FULL body (the async
        # compiler snapshots under this lock) and bump the epoch last, so a
        # kicked compile never sees half-applied state
        with self._lock:
            super().put_template(kind, artifact)
            self.programs[kind] = vectorize(artifact.policy)
            self._cs_epoch += 1
            self._memoable_update(kind, None)
            if self._ordered_cache is not None:
                self._ordered_cache = (self._cs_epoch, self._ordered_cache[1])
            self._log_cs_change(kind, None)
        self._epoch_bumped()

    def delete_template(self, kind: str) -> bool:
        with self._lock:
            self.programs.pop(kind, None)
            out = super().delete_template(kind)
            self._cs_epoch += 1
            # the base delete cascades the kind's constraints away, so the
            # incremental caches must drop them too (not just re-stamp):
            # stale entries would keep evaluating deleted constraints and
            # permanently disable the request memo (advisor r5)
            self._memoable_false = {
                key for key in self._memoable_false if key[0] != kind
            }
            self._ordered_cache = None
            self._log_cs_change(kind, None)
        self._epoch_bumped()
        return out

    def put_constraint(self, kind: str, name: str, constraint: dict):
        with self._lock:
            stored = self.constraints.get(kind, {}).get(name)
            if (
                stored is not None and stored is not constraint
                and _constraint_semantics(stored)
                == _constraint_semantics(constraint)
            ):
                # semantically unchanged (the reference's
                # constraintSemanticEquals, frameworks client.go
                # AddConstraint: spec + labels): a controller re-list
                # after a restart, or — every audit interval — the
                # MODIFIED event of the audit's OWN status write coming
                # back through the constraint controller.  Everything
                # evaluation reads lives in spec and labels, so skipping
                # the epoch bump preserves warm state: the sweep cache,
                # the delta basis and every compiled executable.  Bumping
                # here made every sweep of an all-roles pod a full one.
                # The identity guard matters: re-putting the SAME dict
                # object after mutating it in place would compare equal
                # to itself and silently skip invalidation.
                return
            super().put_constraint(kind, name, constraint)
            self._cs_epoch += 1
            self._memoable_update(kind, name)
            self._ordered_update(kind, name)
            self._log_cs_change(kind, name)
        self._epoch_bumped()

    def delete_constraint(self, kind: str, name: str) -> bool:
        with self._lock:
            out = super().delete_constraint(kind, name)
            self._cs_epoch += 1
            self._memoable_update(kind, name)
            self._ordered_update(kind, name)
            self._log_cs_change(kind, name)
        self._epoch_bumped()
        return out

    def reset(self):
        with self._lock:
            super().reset()
            self.programs.clear()
            self._cs_cache = None
            self._cs_device_cache = None
            self._fused = None
            self._fused_key = None
            from .auditpack import AuditPackCache

            self._audit_pack = AuditPackCache()
            self._render_memo.clear()
            self._bound_plans.clear()
            self._bound_plans_epoch = -1
            self._audit_cache = None
            self._audit_dev = None  # layout gens restart with the new pack
            self._audit_dev_mesh = None
            self._fused_audit = None
            self._fused_audit_key = None
            self._fused_audit_mesh = None
            self._fused_audit_mesh_key = None
            self._delta_state = None
            self._delta_jit = None
            self._delta_jit_key = None
            self._cs_epoch += 1
            # wholesale wipe: the change log cannot describe a reset
            self._request_memo.clear()
            self._memoable_false.clear()
            self._ordered_cache = None
            self._cs_change_log.clear()
            self._cs_log_floor = self._cs_epoch
        self._epoch_bumped()

    # ---- device evaluation ------------------------------------------------

    def _ordered_constraints(self) -> List[Tuple[str, str, dict]]:
        cached = self._ordered_cache
        if cached is not None and cached[0] == self._cs_epoch:
            return cached[1]
        out = []
        for kind in sorted(self.constraints):
            for name in sorted(self.constraints[kind]):
                out.append((kind, name, self.constraints[kind][name]))
        self._ordered_cache = (self._cs_epoch, out)
        return out

    def _constraint_side(self):
        """Cached constraint-side packing: match pack + violation-program
        groups.  Programs are grouped by STRUCTURE, so template clones (the
        synthetic 500-template config) share one traced subgraph with their
        constraints batched on the C axis.  Keyed on what it depends on:
        the constraint epoch and the padded width of the str-pred tables.
        A vocabulary that grew inside that width extends the tables' new
        columns in place (the rule npside.refresh_tables has) and leaves
        the rest of the pack as it is; an epoch change or a vocabulary
        past the width re-packs.  Caller holds the driver lock: an
        extension must land after the packing that interned the strings
        and before the dispatch that can carry their ids."""
        ps = self._cs_cache
        vocab = self.interner.snapshot_size()
        if ps is not None and ps.covers(self._cs_epoch, vocab):
            return ps.side
        if ps is not None and ps.epoch == self._cs_epoch \
                and vocab <= ps.width:
            outcome = "extend"
        else:
            outcome = "repack"
            while True:
                ps = self._pack_constraint_side()
                # packing interns parameter and match strings itself: the
                # extension below fills the columns they added; pack again
                # in the (rare) case they carried the vocabulary past a
                # table's width mid-pack
                vocab = self.interner.snapshot_size()
                if vocab <= ps.width:
                    break
            ps.sig = self._structure_sig(ps.side)
            self._cs_cache = ps
        ps.extend(self.pred_cache, vocab)
        record_cs_refresh(outcome)
        return ps.side

    def _pack_constraint_side(self) -> _PackedSide:
        ordered = self._ordered_constraints()
        vocab0 = self.interner.snapshot_size()  # every table covers this
        specs = {}
        by_struct: Dict[str, list] = {}
        ungrouped: List[int] = []
        for i, (kind, _n, _c) in enumerate(ordered):
            prog = self.programs.get(kind)
            if not prog:
                ungrouped.append(i)  # match-only rows (no template program)
                continue
            sk = prog.structure_key()
            by_struct.setdefault(sk, [prog, []])[1].append(i)
        # GROUP-MAJOR constraint layout with per-group padded blocks: each
        # group occupies mask rows [start, start+B) where B buckets the
        # group size, so the fused per-group update is a STATIC SLICE —
        # no dynamic-index gather/scatter (constructs the TPU fusion
        # emitter nondeterministically rejects) — and a template clone
        # added inside an existing bucket keeps every shape, preserving
        # the compiled executable.  Pad rows pack as None (valid=False:
        # the match kernel keeps them all-False, so whatever a group's
        # padded program rows compute is ANDed away).
        # crow[i] = the padded-layout mask row of sorted constraint i, so
        # every host-side gather (masks, counts, topk) lands in sorted
        # (kind, name) order — per-review violation ordering is then
        # identical across the device, interp, memo-replay, and traced
        # paths (advisor r4).
        padded_cs: List[Optional[dict]] = []
        crow: List[int] = [0] * len(ordered)
        groups = []
        tables = []
        for _sk, (prog, idxs) in sorted(by_struct.items()):
            for spec in prog.column_specs:
                specs[spec.key] = spec
            kcs = [ordered[i][2] for i in idxs]
            B = _bucket_pow2(len(kcs))
            start = len(padded_cs)
            for i in idxs:
                crow[i] = len(padded_cs)
                padded_cs.append(ordered[i][2])
            padded_cs.extend([None] * (B - len(kcs)))
            meta: dict = {}
            packed = pack_params(
                kcs, prog, self.interner, self.pred_cache, B, meta_out=meta
            )
            groups.append((prog, start, B, packed))
            for pred_id, stack in meta.get("stacks", {}).items():
                tables.append((packed[2][pred_id][0], stack))
        for i in ungrouped:
            crow[i] = len(padded_cs)
            padded_cs.append(ordered[i][2])
        cp = pack_constraints(padded_cs, self.interner)
        side = (
            ordered, cp, groups, list(specs.values()),
            np.asarray(crow, np.int64),
        )
        return _PackedSide(self._cs_epoch, side, tables, vocab0)

    def _structure_sig(self, side):
        """Trace signature of the fused fn for this constraint side: group
        program structures, block layout, and every constraint-side array
        shape/dtype.  Two sides with equal signatures share one compiled
        executable — group parameters are runtime arguments and the block
        starts/sizes are layout-determined, so adding a template clone
        inside existing shape buckets costs no retrace/recompile (the
        ingest-storm latency fix)."""
        ordered, cp, groups, col_specs, _crow = side
        return (
            _tree_sig(cp.arrays),
            tuple(
                (prog.structure_key(), start, B, _tree_sig(packed))
                for prog, start, B, packed in groups
            ),
            tuple(sorted(s.key for s in col_specs)),
        )

    def _eval_body(self, side, join_mode: Optional[str] = None,
                   axis_name: Optional[str] = None):
        """The one match-kernel + violation-program-groups evaluation,
        parameterized by the JOIN mode (ops/joinkernel.py):

        - ``None`` (the review path): JoinCmp nodes resolve to their
          polarity's unknown_default — sound over-approximation, no extra
          arguments, signature identical to the pre-referential body.
        - ``'trace'`` (full audit sweeps): per-key aggregate tables are
          computed in-trace from the resident columns (segment-reduce
          group-by; per-shard + all_gather merge when ``axis_name`` names
          the mesh axis); the trailing ``joins`` argument carries runtime
          kind ids so interner ids are never baked into a cached
          executable.
        - ``'tables'`` (delta sweeps): the trailing ``joins`` argument
          carries the host join index's (uk, uc) tables — a churn-slice
          dispatch cannot derive the global aggregate from its rows.

        Returns (body, has_joins): ``body(rv, cs, cols, group_params
        [, joins])``."""
        _ordered, _cp, groups, _col_specs, _crow = side
        static = [(prog, start, B) for prog, start, B, _packed in groups]
        plans = self._active_join_plans()
        has_joins = bool(plans) and join_mode is not None
        pidx = {p: i for i, p in enumerate(plans)}

        def body(rv, cs, cols, group_params, joins=None):
            match, autoreject = match_kernel(rv, cs)
            mask = match
            R = match.shape[1]
            # join tables shared ACROSS groups: N template clones of one
            # referential family cost one table build per sweep
            shared_tables: dict = {}
            for (prog, start, B), (params, elems, tables) in zip(
                static, group_params
            ):
                keysets = {
                    spec.key: cols[spec.key]["ids"]
                    for spec in prog.column_specs
                    if spec.kind == "keyset"
                }
                prog_cols = {
                    spec.key: cols[spec.key]
                    for spec in prog.column_specs
                    if spec.kind != "keyset"
                }
                env = EvalEnv(
                    prog_cols, params, elems, tables, keysets, B, R
                )
                if has_joins and prog.join_plans:
                    from .joinkernel import JoinBinding

                    env.joins = JoinBinding(
                        join_mode, prog.join_plans,
                        [joins[pidx[p]] for p in prog.join_plans],
                        rv=rv, axis_name=axis_name, cache=shared_tables,
                    )
                vmask = eval_program(prog, env)  # [B, R], B = block size
                # STATIC SLICE update: the group-major layout gives every
                # group a contiguous [start, start+B) block, so no
                # dynamic-index gather/scatter exists anywhere in this
                # program (dynamic forms nondeterministically crash the
                # TPU fusion emitter); padded block rows are match-False
                # and AND whatever their program rows computed away
                mask = mask.at[start:start + B].set(
                    mask[start:start + B] & vmask
                )
            return mask, autoreject

        return body, has_joins

    def _fused_fn(self):
        """One jitted function for the whole sweep: match kernel + every
        violation-program group, combined into the candidate mask.  ONE
        dispatch and ONE device->host fetch per evaluation: every fetch
        is a host synchronization, so the hot path keeps them few and
        small.

        Keyed on the STRUCTURE signature, not the epoch: params, string
        tables (vocab-bucketed) and group index vectors are all runtime
        arguments, so constraint churn that keeps shapes inside their
        power-of-two buckets reuses the warm executable as-is."""
        side = self._constraint_side()
        # the signature is a function of the side's shapes, which an
        # in-place extension keeps: computed once, when the side is packed
        sig = self._cs_cache.sig
        if self._fused is not None and self._fused_key == sig:
            return self._fused, side
        if faults.ENABLED:
            faults.fire(faults.TPU_COMPILE)
        body, _has_joins = self._eval_body(side)  # review mode

        def fused(rv, cs, cols, group_params):
            return body(rv, cs, cols, group_params)

        from .aotcache import aot_jit

        self._fused = aot_jit(fused, "fused", sig)
        self._fused_key = sig
        self._fused_gen += 1
        return self._fused, side

    # ---- referential policies (ops/joinkernel.py) -------------------------

    def _active_join_plans(self) -> tuple:
        """Ordered unique JoinPlans across every installed program,
        cached per constraint-side epoch.  Index order is the ``joins``
        runtime-argument order of every join-bearing executable."""
        cached = self._join_plans_cache
        if cached is not None and cached[0] == self._cs_epoch:
            return cached[1]
        plans: List = []
        for kind in sorted(self.programs):
            prog = self.programs.get(kind)
            for p in getattr(prog, "join_plans", ()) or ():
                if p not in plans:
                    plans.append(p)
        out = tuple(plans)
        self._join_plans_cache = (self._cs_epoch, out)
        return out

    def _join_trace_args(self) -> Optional[tuple]:
        """Runtime arguments for 'trace'-mode join executables: the
        interned remote-kind id per plan (runtime, never baked — AOT
        cache entries are shared across processes whose interners
        assigned different ids)."""
        plans = self._active_join_plans()
        if not plans:
            return None
        return tuple(
            {"kind_id": np.asarray(
                self.interner.intern(p.remote_kind), np.int32
            )}
            for p in plans
        )

    def _join_delta_tables(self) -> Optional[tuple]:
        """'tables'-mode runtime arguments from the host join index
        (per-plan uk/uc tables + the kind id JoinCmp.exclude_self
        needs)."""
        js = self._join_state
        if js is None or not js.built:
            return None
        plans = self._active_join_plans()
        out = []
        for p, tab in zip(plans, js.delta_tables()):
            tab = dict(tab)
            tab["kind_id"] = np.asarray(
                self.interner.intern(p.remote_kind), np.int32
            )
            out.append(tab)
        return tuple(out)

    def _ensure_join_state(self):
        """Bring the host join-group index current with the audit pack
        (full-sweep path).  The rebuild DIFFS against the previous index
        and bumps the row generations of readers whose key group
        changed, so the render caches can never replay a message whose
        aggregate (a quota count, a duplicate set) moved underneath it."""
        plans = self._active_join_plans()
        ap = self._audit_pack
        if not plans:
            if self._join_state is not None:
                # the last referential template left: retract the gauge
                # so /metrics never shows phantom active join plans
                self._join_state = None
                from ..metrics.catalog import set_join_plans

                set_join_plans(0)
            ap.join_dirty.clear()  # nobody to drain it
            return None
        from .joinkernel import JoinState

        js = self._join_state
        sig = tuple(p.sig for p in plans)
        if js is None or js.sig != sig or js.rebuild_gen != ap.rebuild_gen:
            # a pack rebuild reassigned row ids (and reset every row
            # generation with it), so a fresh index starts diff-free
            js = JoinState(plans, ap.rebuild_gen)
            self._join_state = js
        bump = js.rebuild(ap, self.interner)
        ap.join_dirty.clear()  # the rebuild read every row
        if bump:
            ap.bump_row_gen(bump)
        from ..metrics.catalog import set_join_plans

        set_join_plans(len(plans))
        return js

    def _join_index_current(self):
        """The join index brought current with the store OUTSIDE a sweep
        (the review path, before a batch that holds a referential cell;
        caller holds the lock), or None without join plans.  A
        webhook-only replica never sweeps, so the write that moved a
        provider is folded in here: the pack re-packs the rows the
        store's change log names, the index commits them (O(changed
        rows)); an index that is missing, of another plan set or of
        another pack generation is rebuilt.  Readers whose key group
        moved are left ``pending`` for the next delta sweep, which
        therefore does not do this work again."""
        plans = self._active_join_plans()
        if not plans:
            return None
        ap = self._audit_pack
        js = self._join_state
        sig = tuple(p.sig for p in plans)
        fresh = (
            js is not None and js.built and js.sig == sig
            and js.rebuild_gen == ap.rebuild_gen
        )
        if (fresh and ap.rp is not None and not ap.join_dirty
                and ap.synced_epoch == self.store.epoch):
            return js
        import time as _time

        t0 = _time.perf_counter()
        ap.sync(self, self._constraint_side()[3])
        if fresh and js.rebuild_gen == ap.rebuild_gen:
            dirty = ap.take_join_dirty()
            if dirty:
                js.commit(ap, self.interner, dirty, sweep=False)
        else:
            js = self._ensure_join_state()
        record_join_upkeep("write", _time.perf_counter() - t0)
        return js

    def warm_join_index(self) -> bool:
        """Build the join index where the data arrives (the loader at
        the end of a restore, a serving pod before it reports ready:
        main.App._admission_ready) -> True where the bundle holds join
        plans.  The review path then only folds in the rows written
        since (``join_dirty``); a replica that holds no pack would
        otherwise pack the whole cluster and build the index under this
        lock in its first referential review, every other review
        waiting behind it."""
        with self._lock:
            return self._join_index_current() is not None

    def _review_joins(self, reviews, inventory):
        """This batch's join binding (ops/joinreview.py), or None for a
        bundle no template of which reads the inventory (the per-epoch
        set of _inventory_kinds)."""
        if not self._inventory_kinds():
            return None
        return joinreview.ReviewJoins(self, reviews, inventory)

    def _join_safe(self, kind: str) -> bool:
        """True when a referential template's rendered results are
        reusable across sweeps: every inventory read is a classified
        join plan (prog.exact survived compilation), so verdict+message
        depend only on (row content, key-group aggregate) — and the join
        index bumps reader row generations whenever a group changes."""
        cached = self._join_safe_cache
        if cached is None or cached[0] != self._cs_epoch:
            cached = (self._cs_epoch, {})
            self._join_safe_cache = cached
        hit = cached[1].get(kind)
        if hit is None:
            prog = self.programs.get(kind)
            # same determinism bar as the row-local audit memo (which
            # keys on pack row generations, not review content): an
            # EXACT program's clauses compiled entirely from the
            # wall-clock-free vectorized fragment, so the render is a
            # function of (row content, key-group aggregate) — both
            # covered by the generation bumps.  memo_safe is deliberately
            # NOT required: it trips on whole-review aliasing (the
            # `identical(other, input.review)` helper), which is
            # harmless here — the review IS the row content.
            hit = bool(
                prog is not None
                and getattr(prog, "join_plans", ())
                and prog.exact
                and kind in self.templates
            )
            cached[1][kind] = hit
        return hit

    def _join_strict(self, kind: str, constraint: dict) -> bool:
        """A flagged-but-renders-empty cell for this constraint is a
        genuine plan-vs-oracle divergence (not a legitimate match or
        mask over-approximation): exact join program, selector-free
        match (the packed match is exact without label selectors)."""
        prog = self.programs.get(kind)
        if prog is None or not getattr(prog, "join_plans", ()) \
                or not prog.exact:
            return False
        match = constraint_match_spec(constraint)
        return not match.get("labelSelector") and not match.get(
            "namespaceSelector"
        )

    def _note_join_false_positive(self, kind: str, name: str, ri: int):
        """A strict-eligible join cell whose interpreter render came back
        empty: count/raise it as a divergence UNLESS the documented
        groupVersion-twin corner explains it (legitimate filter work —
        raising there would crash armed audits on valid clusters)."""
        from . import joinkernel

        prog = self.programs.get(kind)
        js = self._join_state
        if (
            js is not None and prog is not None
            and joinkernel.gv_twin_corner(
                js, getattr(prog, "join_plans", ()), self._audit_pack, ri
            )
        ):
            return
        joinkernel.note_false_positive(kind, name, ri)

    def _join_render_inventory(self, kind: str, rows) -> Optional[object]:
        """ONE grouped inventory for rendering this kind's flagged join
        cells (the PR 14 REMAINING item, docs/referential.md): the
        interpreter re-runs the Rego body per flagged cell, and its
        ``data.inventory`` iterate walks the FULL provider collection —
        O(R) per cell.  For a join-safe kind (every inventory read is an
        exact classified plan) the verdict and message depend only on
        the provider rows in the flagged readers' key groups, so one
        pass builds a pruned tree holding exactly those rows and every
        flagged cell renders byte-identically against it — total render
        cost O(flagged + union of group sizes), not O(flagged x R).

        Returns the frozen pruned tree, or None when equivalence cannot
        be proven (no current join index, unknown plan, provider row
        outside the pack) — the caller then falls back to the full
        inventory.  Soundness backstop: a pruning defect surfaces as a
        flagged-but-renders-empty cell, which the GK_JOIN_ASSERT-armed
        divergence assertion (and tools/check_join_parity.py, tier-1)
        turns into a loud failure, never a silent wrong message."""
        js = self._join_state
        prog = self.programs.get(kind)
        if js is None or not js.built or prog is None:
            return None
        plans = getattr(prog, "join_plans", ()) or ()
        if not plans:
            return None
        by_sig = {p.sig: i for i, p in enumerate(js.plans)}
        provider_rows: set = set()
        for plan in plans:
            i = by_sig.get(plan.sig)
            if i is None:
                return None  # index predates this plan set: rebase path
            row_rkeys = js.row_rkeys[i]
            providers = js.providers[i]
            keys: set = set()
            for r in rows:
                keys.update(row_rkeys.get(int(r), ()))
            for k in keys:
                provider_rows |= providers.get(k, set())
        return self._inventory_of_rows(provider_rows)

    def _inventory_of_rows(self, provider_rows) -> Optional[object]:
        """The frozen inventory tree that holds exactly the objects of
        these pack rows (a key group's providers), or None where a row
        lies outside the pack."""
        reviews = self._audit_pack.reviews
        tree: Dict[str, dict] = {}
        for ri in sorted(provider_rows):
            if ri >= len(reviews):
                return None  # index/pack drift: never render against it
            rev = reviews[ri]
            if rev is None:
                continue  # tombstoned provider: contributes nothing
            obj = rev.get("object")
            if not isinstance(obj, (dict,)) and not hasattr(obj, "get"):
                return None
            meta = obj.get("metadata") or {}
            api = obj.get("apiVersion") or ""
            okind = obj.get("kind") or ""
            name = meta.get("name") or ""
            # placement mirrors target.py inventory_segments: the
            # OBJECT's namespace decides cluster- vs namespace-scope
            ns = meta.get("namespace") or ""
            if ns:
                node = (
                    tree.setdefault("namespace", {})
                    .setdefault(ns, {})
                    .setdefault(api, {})
                    .setdefault(okind, {})
                )
            else:
                node = (
                    tree.setdefault("cluster", {})
                    .setdefault(api, {})
                    .setdefault(okind, {})
                )
            node[name] = obj
        from ..engine.value import freeze

        return freeze(tree)

    def _lazy_join_inventory(self, kind: str, rows, full_inventory):
        """Thunk form of _join_render_inventory, memoized on first call:
        the grouped-tree build runs only when a cell actually MISSES the
        render memo — a steady-state sweep whose join cells all replay
        cached renders never pays it.  Falls back to the full inventory
        when pruning cannot be proven equivalent."""
        box: list = []

        def get():
            if not box:
                pruned = self._join_render_inventory(kind, rows)
                box.append(full_inventory if pruned is None else pruned)
            return box[0]

        return get

    def join_plan_shapes(self) -> List[dict]:
        """Join-plan observability summary (served by /debug/routez via
        the route ledger, obs/routeledger.py)."""
        js = self._join_state
        if js is not None and js.built:
            return js.shapes()
        return [
            {
                "agg": p.agg, "kind": p.remote_kind,
                "scope": p.remote_scope, "slot_key": p.local_slot,
                "groups": None, "provider_rows": None, "reader_rows": None,
            }
            for p in self._active_join_plans()
        ]

    def _fused_mask_fn(self):
        """Audit-mode [C, R] mask executable (single-device path), or
        None when no join plans exist (the plain fused fn is then
        byte-identical and its warm executable serves).  The lazy
        MaskSource dispatch must compute join verdicts exactly like the
        capped reduction it backs: the review-mode fused fn resolves
        JoinCmp to unknown_default and would corrupt the delta fold's
        before-columns."""
        fused, side = self._fused_fn()
        if self._fused_mask is not None and \
                self._fused_mask_key == self._fused_gen:
            return self._fused_mask
        body, has_joins = self._eval_body(side, join_mode="trace")
        if not has_joins:
            self._fused_mask = None
            self._fused_mask_key = self._fused_gen
            return None

        def fused_mask(rv, cs, cols, gp, joins):
            return body(rv, cs, cols, gp, joins)[0]

        from .aotcache import aot_jit

        self._fused_mask = aot_jit(
            fused_mask, "fused-mask", self._fused_key
        )
        self._fused_mask_key = self._fused_gen
        return self._fused_mask

    def _tables_cover_vocab(self) -> bool:
        """The one question every input path asks after its row packing,
        which may have interned new strings: do the constraint side's
        str-pred tables cover the vocabulary?  When not, the caller asks
        for the side again, and _constraint_side extends the tables in
        place (or re-packs past their width) before any dispatch can carry
        the new ids."""
        ps = self._cs_cache
        return ps is not None and ps.covers(
            self._cs_epoch, self.interner.snapshot_size()
        )

    def _review_arrays(self, reviews: List[dict]):
        """Pack review-side arrays + columns; bring the constraint side's
        str-pred tables current if these reviews interned new strings.
        -> (fn, side, the column specs the columns were extracted for, rp,
        cols)."""
        fn, side = self._fused_fn()
        col_specs = side[3]
        rp = pack_reviews(reviews, self.interner, self.store.cached_namespace)
        rows = len(rp.arrays["valid"])
        cols = extract_columns(reviews, col_specs, self.interner, rows)
        if not self._tables_cover_vocab():
            fn, side = self._fused_fn()
        return fn, side, col_specs, rp, cols

    @staticmethod
    def _side_args(side):
        """-> (ordered, cp, group_params, crow) of a constraint side."""
        ordered, cp, groups, _col_specs, crow = side
        return ordered, cp, [packed for *_s, packed in groups], crow

    def _device_inputs(self, reviews: List[dict]):
        fn, side, _specs, rp, cols = self._review_arrays(reviews)
        ordered, cp, group_params, crow = self._side_args(side)
        return fn, ordered, rp, cp, cols, group_params, crow

    def _review_writer(self, col_specs):
        """The direct writer of these column specs (ops/reviewbuf.py
        Writer), or None without the native extension."""
        writer = self._writer
        if writer is None or writer.specs is not col_specs:
            from ..native import load as _load_native

            native = _load_native()
            if native is None:
                return None
            writer = self._writer = reviewbuf.Writer(native, col_specs)
        return writer

    def _packed_inputs(self, reviews: List[dict]):
        """_device_inputs in the form a review dispatch takes: the review
        side as one [rows, width] buffer (ops/reviewbuf.py) beside the
        executable compiled for its layout.  -> (packed fn, ordered, buf,
        extras, cp, group_params, crow).

        A width tuple met before is written straight into the buffer by
        the native writer and its plan (`path="direct"`); the first of a
        width tuple, or any batch without the native extension, packs the
        arrays, lays them out, and leaves the plan behind (`"arrays"`)."""
        fn, side = self._fused_fn()
        writer = self._review_writer(side[3])
        if writer is not None:
            rows = _bucket_pow2(len(reviews), 8)
            scratch, widths = writer.extract(
                reviews, self.interner, self.store.cached_namespace, rows
            )
            if not self._tables_cover_vocab():
                fn, side = self._fused_fn()
            hit = (self._fused_plans.get(widths)
                   if self._fused_packed_src is fn else None)
            if hit is not None and hit[0] is writer:
                _writer, pv, layout, plan = hit
                ordered, cp, group_params, crow = self._side_args(side)
                buf = writer.write(scratch, plan, rows, layout.width)
                record_review_pack("direct")
                return pv, ordered, buf, (), cp, group_params, crow
        # the arrays path: re-interning what the writer interned changes
        # nothing, so the ids are those of a single pass
        fn, side, col_specs, rp, cols = self._review_arrays(reviews)
        ordered, cp, group_params, crow = self._side_args(side)
        rows = len(rp.arrays["valid"])
        tree = (rp.arrays, cols)
        leaves, treedef = jax.tree_util.tree_flatten(tree)
        pv, layout = self._packed_variant(
            fn, tree, rows, (treedef, reviewbuf.leaf_metas(leaves, rows))
        )
        buf, extras = layout.pack(leaves, rows)
        writer = self._review_writer(col_specs)
        plan = writer and writer.plan(layout, leaves, rp.arrays, cols)
        if plan is not None:
            self._fused_plans[reviewbuf.widths_of(
                rp.arrays, cols, col_specs)] = (writer, pv, layout, plan)
        record_review_pack("arrays")
        return pv, ordered, buf, extras, cp, group_params, crow

    def _mesh(self):
        """The production device mesh: all visible devices (or the pinned
        mesh_width), data-parallel on the resource axis (parallel/mesh.py).
        None on single-chip, width 1, or when mesh_enabled is off."""
        if not self.mesh_enabled:
            return None
        if self._mesh_cache is None:
            from ..parallel.mesh import audit_mesh, maybe_audit_mesh

            if self.mesh_width is not None:
                mesh = (
                    audit_mesh(self.mesh_width) if self.mesh_width > 1
                    else None
                )
            else:
                mesh = maybe_audit_mesh()
            self._mesh_cache = (mesh,)
        return self._mesh_cache[0]

    def set_mesh(self, enabled: bool, width: Optional[int] = None):
        """Switch the mesh topology (on/off, or a pinned device count) and
        invalidate EVERY cache keyed on it: the mesh object itself, the
        device-resident constraint side and sharded audit inputs, the
        compiled mesh audit executable, the delta-sweep basis (its resident
        base mask carries the old topology's layout), the sweep cache and
        the delta executable (its compiled entries pin the old mask
        sharding).  This replaces the ad-hoc `_mesh_cache = None` /
        `mesh_enabled = False` pokes — partial pokes left stale
        device placements serving the new topology.

        width=None uses every visible device; width=1 forces the
        single-device path even when enabled."""
        if enabled and width is not None and width > len(jax.devices()):
            raise ValueError(
                f"mesh width {width} exceeds visible devices "
                f"({len(jax.devices())})"
            )
        with self._lock:
            self.mesh_enabled = bool(enabled)
            self.mesh_width = width
            self._mesh_cache = None
            self._cs_device_cache = None
            self._audit_dev = None
            self._audit_dev_mesh = None
            self._audit_cache = None
            self._delta_state = None
            self._delta_jit = None
            self._delta_jit_key = None
            self._fused_audit_mesh = None
            self._fused_audit_mesh_key = None
        from ..metrics.catalog import record_mesh_width

        # outside the driver lock (the gauge is advisory); mesh_layout()
        # resolves the new topology, initializing it on first use
        record_mesh_width(self.mesh_layout() if enabled else 1)

    def mesh_layout(self) -> int:
        """The row-sharding width serving production sweeps: device count
        of the active mesh, 1 on the single-device path.  Persisted in the
        snapshot sweep basis; a restore whose live layout differs drops
        the basis (width drift invalidation, gatekeeper_tpu/snapshot/)."""
        mesh = self._mesh()
        return 1 if mesh is None else int(mesh.devices.size)

    def _guarded_mesh_dispatch(self, mesh, thunk, enter: bool = True):
        """Run one mesh-collective enqueue under the dispatch gate with
        the stall watchdog (docs/failure-modes.md).  Without a watchdog
        budget this is exactly `with DISPATCH_LOCK, mesh: thunk()`.  With
        one, the guarded enqueue runs on a worker thread the caller joins
        with the budget; a timeout (the gate never freed, or the enqueue
        itself wedged — a stuck collective rendezvous) revokes the gate's
        generation (abandoning the wedged holder so narrower-topology
        dispatches can proceed) and raises MeshDispatchStall, which the
        audit paths convert into breaker trip + re-shard.

        Cost model: each guarded dispatch pays one worker-thread spawn
        (microseconds against a sweep's ms-to-s dispatch), and an
        ABANDONED worker necessarily pins its operand buffers until the
        wedged collective ever returns — they are live inputs of the
        in-flight call, not freeable from outside.  Acceptable because
        abandonment coincides with the breaker tripping and the mesh
        narrowing: the degraded state the pinned memory rides out."""
        from ..parallel.mesh import DISPATCH_LOCK, MeshDispatchStall

        import contextlib

        # `enter` mirrors each pre-watchdog call site exactly: the fused
        # audit dispatch ran inside `with mesh:`, the delta dispatch did
        # not (its executable was traced without the ambient mesh, and
        # entering it here would miss the background-warmed jit cache)
        mesh_ctx = mesh if enter else contextlib.nullcontext()
        timeout = self.mesh_watchdog_s or 0.0
        if timeout <= 0:
            with DISPATCH_LOCK, mesh_ctx:
                if faults.ENABLED:
                    faults.fire(faults.MESH_DISPATCH_STALL)
                return thunk()

        def _stall(where: str) -> MeshDispatchStall:
            DISPATCH_LOCK.revoke()
            from ..metrics.catalog import record_mesh_stall

            record_mesh_stall()
            log.warning(
                "mesh dispatch watchdog: %s exceeded %.3fs at width %d",
                where, timeout, self.mesh_layout(),
            )
            return MeshDispatchStall(
                f"mesh dispatch {where} exceeded the {timeout:.3f}s "
                f"watchdog budget"
            )

        token = DISPATCH_LOCK.acquire(timeout=timeout)
        if token is None:
            # a previous dispatch is wedged holding the gate
            raise _stall("gate wait")
        done = _threading_mod.Event()
        box: dict = {}

        def run():
            try:
                with mesh_ctx:
                    if faults.ENABLED:
                        faults.fire(faults.MESH_DISPATCH_STALL)
                    box["out"] = thunk()
            except BaseException as e:  # surfaced on the caller's side
                box["err"] = e
            finally:
                done.set()
                # released from the worker: a late (post-revoke) release
                # of an abandoned generation is harmless by design
                DISPATCH_LOCK.release(token)

        t = _threading_mod.Thread(
            target=run, name="gk-mesh-dispatch", daemon=True
        )
        t.start()
        if not done.wait(timeout):
            raise _stall("collective enqueue")
        if "err" in box:
            raise box["err"]
        return box["out"]

    def _record_device_failure(self, e: BaseException):
        """Feed one device-path failure to the breaker.  A MeshDispatchStall
        is decisive — a wedged collective will wedge every subsequent mesh
        dispatch too, so it trips the breaker immediately (no
        threshold-counting through repeated watchdog budgets) and
        re-shards the sweep narrower; the rebasing full sweep runs at the
        new width once the breaker's recovery probe closes it."""
        from ..parallel.mesh import MeshDispatchStall

        self.breaker.record_failure(e)
        if isinstance(e, MeshDispatchStall):
            self.breaker.trip()
            try:
                self.degrade_mesh()
            except Exception:
                log.exception("mesh degradation after a stall failed")

    def degrade_mesh(self) -> int:
        """Re-shard the audit sweep one step narrower after a stalled
        collective: width w -> w // 2, bottoming out at the single-device
        path.  set_mesh() drops every topology-keyed cache including the
        delta basis, so the next device sweep is one full dispatch that
        rebases the incremental state — parity preserved by construction
        (the narrower sweep computes the identical [C, R] masks).
        Returns the new width (1 = single-device)."""
        width = self.mesh_layout()
        new = width // 2
        if new >= 2:
            self.set_mesh(True, width=new)
        else:
            new = 1
            self.set_mesh(True, width=1)
        log.warning(
            "mesh degraded after dispatch stall: width %d -> %d%s",
            width, new,
            " (single-device path)" if new == 1 else "",
        )
        try:  # guarded: degradation must proceed even recorder-less
            from ..obs import flightrec

            flightrec.record(
                flightrec.MESH_DEGRADE, from_width=width, to_width=new,
            )
        except Exception:
            log.debug("flight-recorder feed failed on mesh degrade",
                      exc_info=True)
        return new

    def _dispatch(self, fn, buf, extras, cp_arrays, group_params,
                  cs_key=None):
        """Call a packed fused device function (_packed_inputs) with
        mesh-aware placement: on a multi-chip mesh the review side's
        buffer is padded + sharded on "data" and the replicated constraint
        side is served from the epoch-keyed device cache (re-uploading
        vocab-sized tables to N chips every call would cost N
        host->device transfers).

        cs_key: the side's key (_PackedSide.key: epoch, table width,
        table_vocab) the inputs were packed for, read under the driver lock.  The
        async compile thread dispatches UNLOCKED, so reading the live
        epoch here could key stale constraint arrays under a newer one
        (advisor r2); callers that hold the lock may omit it."""
        if faults.ENABLED:
            faults.fire(faults.TPU_DISPATCH)
        from .aotcache import aot_jit

        mesh = self._mesh()
        cs_p, gp_p = self._constraint_device_side(
            cp_arrays, group_params, cs_key, mesh
        )
        record_dispatch_upload("review", 1 + len(extras))
        if self._cs_uploaded:
            record_dispatch_upload("constraint", self._cs_uploaded)
        if mesh is None:
            # the buffer is a host array: its upload is inside this call
            return fn(buf, extras, cs_p, gp_p)
        if isinstance(fn, aot_jit):
            # serialized executables pin a single-device layout; the mesh
            # path must go through the jit machinery's SPMD compile
            fn = fn._jitted
        from ..parallel.mesh import shard_review_side

        from ..parallel.mesh import DISPATCH_LOCK

        buf_p, extras_p, _target = shard_review_side(
            mesh, buf.shape[0], buf, extras,
            record_shard=self._record_shard("review"),
        )
        with DISPATCH_LOCK, mesh:
            return fn(buf_p, extras_p, cs_p, gp_p)

    def _constraint_device_side(self, cp_arrays, group_params, cs_key, mesh):
        """The constraint-side trees committed on-device (replicated across
        the mesh when one exists), kept for the life of (epoch, table
        width, mesh).  Only the str-pred tables read the vocabulary: when
        the side's `table_vocab` moved past the copy's, the table leaves
        alone are uploaded again and every other leaf stays the array it
        was.  `_cs_uploaded` is the number of arrays this call uploaded (0
        on a hit)."""
        if cs_key is None:
            cs_key = self._cs_cache.key()
        epoch, width, table_vocab = cs_key
        key = (epoch, width, id(mesh) if mesh is not None else 0)
        # single read: the compile thread runs unlocked, and a concurrent
        # reset() may None the cache between a check and a re-read
        cache = self._cs_device_cache
        if cache and cache[0] == key and cache[2] >= table_vocab:
            self._cs_uploaded = 0
            return cache[1]
        if mesh is None:
            put = jax.device_put
        else:
            from ..parallel.mesh import replicate_tree

            def put(tree):
                return replicate_tree(mesh, tree)

        if cache and cache[0] == key:
            # the vocabulary grew inside the tables' width: their host
            # arrays were extended in place (_constraint_side), so the
            # device needs them and nothing else.  A dispatch in flight
            # keeps the (immutable) arrays it was given, and carries only
            # ids below the table_vocab it was packed for
            cs_p, gp_p = cache[1]
            mats = put([
                {pid: mat for pid, (mat, _idx) in tables.items()}
                for _params, _elems, tables in group_params
            ])
            placed = (cs_p, [
                (params, elems,
                 {pid: (new[pid], idx) for pid, (_mat, idx) in tables.items()})
                for (params, elems, tables), new in zip(gp_p, mats)
            ])
            self._cs_uploaded = sum(len(m) for m in mats)
        else:
            placed = put((cp_arrays, group_params))
            self._cs_uploaded = len(jax.tree_util.tree_leaves(placed))
            # device-memory accounting (obs/compilestats.py): the
            # replicated constraint side's footprint, refreshed per full
            # placement (epoch / width churn, not the hot path)
            from ..obs import compilestats

            compilestats.record_device_bytes(
                "constraint_side",
                compilestats.tree_nbytes((cp_arrays, group_params)),
                replicas=1 if mesh is None else int(mesh.devices.size),
            )
        # never cache under a key the live epoch has moved past: a later
        # eval with an unchanged vocab would hit misaligned mask rows
        if epoch == self._cs_epoch:
            self._cs_device_cache = (key, placed, table_vocab)
        return placed

    def _packed_variant(self, fn, tree, rows, key):
        """The fused fn as a review dispatch calls it, for one layout of
        the review side: it takes the side as ONE [rows, width] buffer
        (plus the leaves that cannot lie in it), rebuilds `rv` and `cols`
        by static slices, and returns mask+autoreject as ONE bit-packed
        uint8 array: one upload and one fetch instead of dozens and two,
        the fetch's payload cut 8x.  Unpacking and packing run inside the
        same jitted dispatch.  -> (executable, layout); one of each per
        `key` (the tree's structure and every leaf's shape after the row
        axis and dtype), which is fixed by the column specs and the padded
        widths that key an executable anyway."""
        if self._fused_packed_src is not fn:
            self._fused_packed = {}
            self._fused_plans = {}
            self._fused_packed_src = fn
        got = self._fused_packed.get(key)
        if got is not None:
            return got
        layout = reviewbuf.Layout(tree, rows)
        raw = fn.__wrapped__

        def fused_packed(buf, extras, cs, gp):
            rv, cols = layout.unpack(buf, extras)
            mask, autoreject = raw(rv, cs, cols, gp)
            return jnp.packbits(
                jnp.concatenate([mask, autoreject], axis=0), axis=1
            )

        from .aotcache import aot_jit

        got = self._fused_packed[key] = (
            aot_jit(fused_packed, "fused-packed",
                    (self._fused_key, layout.sig)),
            layout,
        )
        return got

    @staticmethod
    def _split_masks(both, crow, rows):
        """A packed dispatch's unpacked bits -> (mask, autoreject), each
        [constraints, rows] bool.  crow maps each ordered constraint to
        its group-major mask row (pad block rows drop out here)."""
        c = both.shape[0] // 2
        return (both[:c][crow][:, :rows].astype(bool),
                both[c:][crow][:, :rows].astype(bool))

    def compute_masks(self, reviews: List[dict]):
        """-> (ordered constraints, match&violation candidate mask [C, R],
        autoreject mask [C, R]) as numpy arrays.

        Multi-chip: when a mesh is available the row axis is padded to a
        mesh multiple and committed sharded (input placement drives the
        SPMD compile of the SAME fused jit); results come back trimmed so
        callers see identical shapes on 1 or N devices."""
        # stages on the caller's batch clock (obs/trace.py StageClock;
        # the no-op clock when nobody is timing this thread): the old
        # tpu.dispatch interval split where the work changes hands
        clock = obstrace.running_clock(obstrace.PATH_BATCH)
        t0 = clock.mark("pack")
        fn, ordered, buf, extras, cp, group_params, crow = \
            self._packed_inputs(reviews)
        rows = buf.shape[0]
        t1 = clock.mark("enqueue")  # the buffer's upload + launch
        packed = self._dispatch(fn, buf, extras, cp.arrays, group_params)
        # the fetch is requested behind the compute before the host waits
        # for either: block_until_ready alone would put a host round trip
        # between the two (+0.18 ms per dispatch, measured: PERF.md PR 27)
        packed.copy_to_host_async()
        clock.mark("device_wait")
        packed = jax.block_until_ready(packed)
        clock.mark("fetch")
        both = np.unpackbits(np.asarray(packed), axis=1)
        t2 = clock.mark("account")
        # stage telemetry: spans mirror into every request trace this
        # batch serves; the histograms double-record the same intervals
        obstrace.record_span("tpu.pack", t0, t1, stage=obstrace.PACK,
                             reviews=len(reviews))
        obstrace.record_span(
            "tpu.dispatch", t1, t2, stage=obstrace.DISPATCH,
            tier="tpu", breaker=self.breaker.state, rows=rows,
        )
        record_stage(DISPATCH_M, t2 - t1, {"path": "review", "tier": "tpu"})
        if obscosts.enabled():
            obscosts.record_dispatch(
                self._cost_kind_counts(), t2 - t1, len(reviews),
                path="review",
            )
        return (ordered,) + self._split_masks(both, crow, rows)

    # ---- render (exactness filter) ---------------------------------------

    def _render_plan_for(self, kind: str, name: str, constraint: dict):
        """The constraint's bound render plan (ops/renderplan.py), or None
        when the template is plan-ineligible.  Cached per constraint-side
        epoch (binding is cheap but not free; rendering a drifted cluster
        touches every constraint).  Caller holds the lock."""
        if not self.render_plan_enabled:
            return None
        if self._bound_plans_epoch != self._cs_epoch:
            self._bound_plans.clear()
            self._bound_plans_epoch = self._cs_epoch
        key = (kind, name)
        got = self._bound_plans.get(key, _PLAN_MISS)
        if got is not _PLAN_MISS:
            return got
        plan = None
        tmpl = self.templates.get(kind)
        prog = self.programs.get(kind)
        if tmpl is not None and prog is not None:
            from . import renderplan

            try:
                plan = renderplan.bind(prog, tmpl.policy, constraint)
            except Exception:  # a plan bug must degrade, never fail a cell
                log.exception("render-plan bind failed for %s/%s", kind, name)
                plan = None
        self._bound_plans[key] = plan
        return plan

    def _render_plan_tiers(self) -> Dict[str, str]:
        """Per-constraint render-plan classification ("kind/name" ->
        tier), shared by the snapshot writer (persists it in the sweep
        basis) and loader (validates the rebuilt classification against
        it).  Caller holds the lock."""
        out: Dict[str, str] = {}
        for kind, name, constraint in self._ordered_constraints():
            try:
                plan = self._render_plan_for(kind, name, constraint)
            except Exception:
                plan = None
            out[f"{kind}/{name}"] = (
                plan.tier if plan is not None else "interp"
            )
        return out

    def _cost_kind_counts(self) -> Dict[str, int]:
        """{template kind: live constraint count} for cost-ledger
        dispatch apportioning, cached per constraint-side epoch."""
        cached = self._cost_kinds_cache
        if cached is not None and cached[0] == self._cs_epoch:
            return cached[1]
        counts = {k: len(v) for k, v in self.constraints.items() if v}
        self._cost_kinds_cache = (self._cs_epoch, counts)
        return counts

    def _flush_render_counts(self):
        """Export the pass's per-tier cell counts to
        render_cells_total{plan=...} (one registry record per tier per
        pass, not per cell)."""
        counts = self._tier_counts
        if counts["static"] or counts["slots"] or counts["interp"]:
            record_render_cells(counts)
            self._tier_counts = {"static": 0, "slots": 0, "interp": 0}

    def _eval_cell(
        self, constraint: dict, kind: str, review: dict, frozen_review,
        inventory, rowview=None, allow_plan: bool = True,
        count: bool = True,
    ) -> list:
        """Exact evaluation of one (constraint, review) cell: native match
        re-check + violation rendering — via the compiled render plan when
        this constraint has one (byte-identical to the interpreter by
        construction, tests/test_render_parity.py), else the interpreter.
        Returns the violation dicts ([] when the device mask
        over-approximated)."""
        from ..engine.value import freeze

        tmpl = self.templates.get(kind)
        if tmpl is None:
            return []
        if not constraint_matches(constraint, review, self.store.cached_namespace):
            return []  # device over-approximation filtered here
        if allow_plan:
            plan = self._render_plan_for(
                kind, _constraint_name(constraint), constraint
            )
            if plan is not None:
                if rowview is None:
                    from .renderplan import RowView

                    rowview = RowView(review, frozen_review)
                if count:
                    self._tier_counts[plan.tier] += 1
                return plan.apply(rowview)
        if count:
            self._tier_counts["interp"] += 1
        params = constraint_parameters(constraint)
        if frozen_review is None:
            frozen_review = (
                rowview.frozen() if rowview is not None else freeze(review)
            )
        return tmpl.policy.eval_violations(
            frozen_review, freeze(params), inventory
        )

    @staticmethod
    def _cell_memoable(tmpl, constraint: dict) -> bool:
        """A (constraint, object) verdict is content-determined iff the
        template's policy is memo-safe and inventory-free and the match
        spec carries no namespaceSelector — PRESENCE check, not truthiness:
        an empty selector ({}) still consults the mutable namespace cache
        (target/match.py presence semantics), so a memoized verdict could
        outlive a namespace sync."""
        if tmpl is None:
            return False
        if not getattr(tmpl.policy, "memo_safe", False):
            return False
        if getattr(tmpl.policy, "uses_inventory", True):
            return False
        match = constraint_match_spec(constraint)
        return "namespaceSelector" not in match

    def _cell_violations(
        self, constraint: dict, kind: str, review: dict, frozen_review,
        inventory, memo_review=None, rowview=None,
    ) -> list:
        # content-keyed memo: identical (constraint, object) cells render
        # identically while the constraint side is unchanged, PROVIDED the
        # cell depends only on its inputs: excluded are templates reading
        # data.inventory, policies that are not memo_safe (wall-clock
        # builtins or per-request metadata reads, engine/interp.py), and
        # constraints with a namespaceSelector (whose match consults the
        # MUTABLE cached-namespace store, target/match.py) — a memoized
        # verdict must never outlive a namespace relabel.  The key strips
        # per-request metadata (uid) so real admission traffic, where every
        # request has a fresh uid, still hits.
        tmpl = self.templates.get(kind)
        if self._cell_memoable(tmpl, constraint):
            if self._review_memo_epoch != self._cs_epoch:
                self._review_memo.clear()
                self._review_memo_epoch = self._cs_epoch
            if memo_review is None:
                if frozen_review is None:
                    frozen_review = rowview.frozen()
                memo_review = _strip_request_meta(frozen_review)
            mkey = (kind, _constraint_name(constraint), memo_review)
            violations = self._review_memo.get(mkey)
            if violations is None:
                violations = self._eval_cell(
                    constraint, kind, review, frozen_review, inventory,
                    rowview,
                )
                # bounded: unique objects (pod names) make keys unbounded
                # on a busy cluster; clearing 16k entries is ~ms, far below
                # the interp evals the memo saves
                if len(self._review_memo) >= self.REVIEW_MEMO_MAX:
                    self._review_memo.clear()
                self._review_memo[mkey] = violations
        else:
            violations = self._eval_cell(
                constraint, kind, review, frozen_review, inventory, rowview
            )
        return violations

    def _render_cell(
        self,
        results: List[Result],
        constraint: dict,
        kind: str,
        review: dict,
        frozen_review,
        inventory,
        tracing_log,
        memo_review=None,
        rowview=None,
    ):
        violations = self._cell_violations(
            constraint, kind, review, frozen_review, inventory,
            memo_review=memo_review, rowview=rowview,
        )
        self._append_violation_results(
            results, violations, constraint, kind, review, tracing_log
        )

    def _append_violation_results(self, results, violations, constraint,
                                  kind, review, tracing_log=None):
        """The ONE violation-dict -> Result shaping (msg/str coercion,
        details default, per-constraint enforcement action), shared by
        the per-cell and bulk masked render paths."""
        if not violations:
            return
        action = self._enforcement_action(constraint)
        for v in violations:
            results.append(
                Result(
                    msg=str(v.get("msg", "")),
                    metadata={"details": v.get("details", {})},
                    constraint=constraint,
                    review=review,
                    enforcement_action=action,
                )
            )
            if tracing_log is not None:
                tracing_log.append(
                    f"violation {kind}/{constraint['metadata']['name']}: {v.get('msg')}"
                )

    def review(self, review: dict, tracing: bool = False):
        return self.review_batch([review], tracing=tracing)[0]

    # whole-request memo size bound (entries are per unique object content)
    REQUEST_MEMO_MAX = 8192

    def _request_memoable(self) -> bool:
        """True when a whole request's verdict depends ONLY on its content:
        every installed template's policy is memo-safe and inventory-free,
        and no constraint carries a namespaceSelector (whose match — and
        autoreject — consult the mutable namespace cache).  Then the entire
        C-constraint walk can be served from one dict hit, which is what
        keeps p50 flat for replica/retry storms at large constraint counts
        (the reference re-runs the full Rego scan per request,
        target_template_source.go:27-44).  O(1): the mutators maintain
        _memoable_false incrementally (_memoable_update)."""
        flag = not self._memoable_false
        self._request_memo_ok = flag
        return flag

    def _gvk_walk_list(self, review: dict) -> List[Tuple[str, str, dict]]:
        """The sorted constraint subset an interp walk must visit for this
        review: constraints whose match.kinds could possibly hit the
        review's (group, kind) — exact pairs plus wildcard buckets — and
        every namespaceSelector-carrying constraint (autoreject is kind-
        independent, target_template_source.go:12-25).  The index mirrors
        pack_constraints' kind-pair semantics (an entry with empty
        apiGroups or kinds contributes no pairs and never matches).
        This is the reference's matching_constraints linear scan replaced
        by a GVK index so a 500-template install does not tax reviews of
        unrelated kinds (audit already kind-pre-filters)."""
        idx = self._gvk_cache
        if idx is None or idx[0] != self._cs_epoch:
            by_pair: Dict[Tuple[str, str], list] = {}
            nssel: list = []
            for entry in self._ordered_constraints():
                _kind, _name, c = entry
                # non-dict spec/match degrade to {} (constraint_match_spec
                # mirrors target/match.py _get): one malformed constraint
                # must not fail every interp-path review
                match = constraint_match_spec(c)
                if "namespaceSelector" in match:
                    nssel.append(entry)
                kinds = match.get("kinds")
                if kinds is None:
                    # missing OR explicit null both mean wildcard — the
                    # oracle's _get and pack.py:298 treat them identically
                    kinds = [{"apiGroups": ["*"], "kinds": ["*"]}]
                if isinstance(kinds, list):
                    for ks in kinds:
                        if not isinstance(ks, dict):
                            continue
                        for g in ks.get("apiGroups") or []:
                            for k in ks.get("kinds") or []:
                                by_pair.setdefault(
                                    (str(g), str(k)), []
                                ).append(entry)
            idx = (self._cs_epoch, by_pair, nssel)
            self._gvk_cache = idx
        _epoch, by_pair, nssel = idx
        rk = review.get("kind")
        g = rk.get("group") if isinstance(rk, dict) else None
        k = rk.get("kind") if isinstance(rk, dict) else None
        probes = [("*", "*")]
        if isinstance(k, str):
            probes.append(("*", k))
        if isinstance(g, str):
            probes.append((g, "*"))
            if isinstance(k, str):
                probes.append((g, k))
        out: Dict[Tuple[str, str], Tuple[str, str, dict]] = {}
        for p in probes:
            for entry in by_pair.get(p, ()):
                out[entry[:2]] = entry
        for entry in nssel:
            out[entry[:2]] = entry
        return [out[key] for key in sorted(out)]


    def _inventory_for_render(self):
        """The frozen inventory handed to render paths, or an empty
        FrozenDict when NO installed template reads data.inventory: the
        exact render can then never touch it, and a restart's first
        sweep skips freezing the whole cluster tree (O(cluster), ~5s at
        20k objects — the dominant share of warm-restart time for
        inventory-free corpora).  Templates that do read inventory keep
        the full (incrementally re-spined) snapshot.  The any-template
        scan is cached per constraint-side epoch: it ran per np-served
        review, which at 500 installed templates was a measurable slice
        of the admission path."""
        uses = bool(self._inventory_kinds())
        if uses:
            return self.store.frozen()
        from ..engine.value import freeze

        return freeze({})

    def _inventory_kinds(self) -> frozenset:
        """The kinds of the installed templates that read
        data.inventory.  Cached per constraint-side epoch."""
        cached = self._uses_inventory_cache
        if cached is not None and cached[0] == self._cs_epoch:
            return cached[1]
        kinds = frozenset(
            kind for kind, t in self.templates.items()
            if getattr(t.policy, "uses_inventory", True)
        )
        self._uses_inventory_cache = (self._cs_epoch, kinds)
        return kinds

    def _interp_review_memo(self, review: dict, memo_key=None):
        """InterpDriver.review semantics served through the content-keyed
        render memos: the hybrid small-batch path and the async-compile
        fallback — i.e. ordinary single admission requests — skip
        re-evaluating (constraint, object) cells they have seen before,
        and when every cell is content-determined the whole constraint
        walk collapses to one request-level memo hit.
        Traced reviews go to the oracle directly (drivers.py review)."""
        import time as _time

        from ..engine.value import freeze

        t_enter = _time.perf_counter()
        with self._lock:
            t_locked = _time.perf_counter()
            # lock-wait vs evaluation breakdown (read by bench.py's ingest
            # config): distinguishes queueing behind a concurrent template
            # compile from actual interp evaluation cost
            self.last_review_stats = {
                "lock_wait_ms": (t_locked - t_enter) * 1e3,
            }
            # the interp walk has no masked render pass: stale stats from
            # a previous _render_masked must not be re-read by bench
            self.last_render_stats = {}
            inventory = self._inventory_for_render()
            cached_ns = self.store.cached_namespace
            if memo_key is not None:
                frozen_review, memo_review = memo_key
            else:
                frozen_review = freeze(review)
                memo_review = _strip_request_meta(frozen_review)
            # synced under THIS lock hold: the store below must never run
            # on a memoable verdict from a pre-epoch-bump constraint side
            memoable = self._memoable_synced()
            from .renderplan import RowView

            rowview = RowView(review, frozen_review)
            results: List[Result] = []
            joins = self._review_joins((review,), inventory)
            for kind, name, constraint in self._gvk_walk_list(review):
                if needs_autoreject(constraint, review, cached_ns):
                    results.append(
                        Result(
                            msg="Namespace is not cached in OPA.",
                            metadata={"details": {}},
                            constraint=constraint,
                            review=review,
                            enforcement_action=self._enforcement_action(
                                constraint
                            ),
                        )
                    )
                cell_inv = inventory
                if joins is not None and joins.referential(kind):
                    # a referential cell resolves through the join index
                    # (ops/joinreview.py): cleared, or rendered against
                    # its key group; only a matching constraint is a cell
                    if not constraint_matches(constraint, review,
                                              cached_ns):
                        continue
                    clock = obstrace.running_clock(obstrace.PATH_BATCH)
                    was = clock.stage
                    clock.mark("join_lookup")
                    cell_inv = joins.resolve(kind, 0)
                    if was is not None:
                        clock.mark(was)
                    if cell_inv is joinreview.CLEARED:
                        continue
                # _render_cell re-checks the match and returns nothing
                # for non-matching constraints or missing templates —
                # identical semantics to the oracle's walk
                self._render_cell(
                    results, constraint, kind, review, frozen_review,
                    cell_inv, None, memo_review=memo_review,
                    rowview=rowview,
                )
            if joins is not None:
                joins.flush()
            if memoable:
                self._store_request_memo(review, results, memo_review)
            self._flush_render_counts()
            self.last_review_stats["eval_ms"] = (
                _time.perf_counter() - t_locked) * 1e3
            return results, None

    def _request_memo_hit(self, review: dict):
        """Serve a review wholly from the request memo — repairing a
        stale entry through the constraint-side change log — or (None,
        memo key) on miss, (None, None) when unmemoable.  review_batch
        consults this BEFORE routing, so repeat-content admissions
        (replica/retry storms) stay at memo speed regardless of which
        path unique content would take; the (frozen review, stripped memo
        key) pair travels to the miss path so the review is frozen
        exactly once whichever path serves it."""
        import time as _time

        from ..engine.value import freeze

        t_enter = _time.perf_counter()
        with self._lock:
            t_locked = _time.perf_counter()
            if not self._memoable_synced():
                return None, None
            frozen_review = freeze(review)
            memo_review = _strip_request_meta(frozen_review)
            memo_key = (frozen_review, memo_review)
            hit = self._request_memo.get(memo_review)
            if hit is None:
                return None, memo_key
            if hit[0] != self._cs_epoch:
                per_key = self._repair_memo_entry(
                    hit[0], hit[1], review, frozen_review, memo_review,
                    self._inventory_for_render(),
                    self.store.cached_namespace,
                )
                if per_key is None:
                    return None, memo_key  # log overran: full re-eval
                # flatten ONCE per repair (O(C)); every replay at this
                # epoch is then O(violations)
                flat = [
                    (kind, name, entry)
                    for kind in sorted(self.constraints)
                    for name in sorted(self.constraints[kind])
                    for entry in per_key.get((kind, name), ())
                ]
                hit = (self._cs_epoch, per_key, flat)
                self._request_memo[memo_review] = hit
            # rebuilt per hit down to the details object: handing out any
            # cached mutable by reference would let a consumer's mutation
            # corrupt every later replay
            out = [
                Result(
                    msg=msg,
                    metadata={"details": copy.deepcopy(details)},
                    constraint=self.constraints[kind][name],
                    review=review,
                    enforcement_action=action,
                )
                for kind, name, (msg, details, action) in hit[2]
            ]
            self.last_review_stats = {
                "lock_wait_ms": (t_locked - t_enter) * 1e3,
                "eval_ms": (_time.perf_counter() - t_locked) * 1e3,
            }
            return out, memo_key

    def _eval_one_key(self, kind, name, review, frozen_review, memo_review,
                      inventory, cached_ns, rowview=None):
        """Evaluate a single constraint for the request memo's repair
        path: the same autoreject + render walk _interp_review_memo runs
        per key, returning the memoized tuple list (None when the
        constraint no longer exists)."""
        constraint = self.constraints.get(kind, {}).get(name)
        if constraint is None:
            return None
        out: List[Result] = []
        if needs_autoreject(constraint, review, cached_ns):
            out.append(
                Result(
                    msg="Namespace is not cached in OPA.",
                    metadata={"details": {}},
                    constraint=constraint, review=review,
                    enforcement_action=self._enforcement_action(constraint),
                )
            )
        self._render_cell(
            out, constraint, kind, review, frozen_review, inventory, None,
            memo_review=memo_review, rowview=rowview,
        )
        return [
            (r.msg, copy.deepcopy((r.metadata or {}).get("details", {})),
             r.enforcement_action)
            for r in out
        ]

    def _repair_memo_entry(self, entry_epoch, per_key, review,
                           frozen_review, memo_review, inventory,
                           cached_ns):
        """Bring a stale request-memo entry current by re-evaluating ONLY
        the constraints the change log records after entry_epoch.  Returns
        the repaired per-key dict, or None when the log no longer covers
        the entry (caller falls back to a full evaluation)."""
        if entry_epoch < self._cs_log_floor:
            return None
        from .renderplan import RowView

        rowview = RowView(review, frozen_review)
        changed_kinds = set()
        changed_keys = set()
        for ep, kind, name in reversed(self._cs_change_log):
            if ep <= entry_epoch:
                break
            if name is None:
                changed_kinds.add(kind)
            else:
                changed_keys.add((kind, name))
        per_key = dict(per_key)
        for kind in changed_kinds:
            for k in [k for k in per_key if k[0] == kind]:
                del per_key[k]
            for name in self.constraints.get(kind, {}):
                res = self._eval_one_key(
                    kind, name, review, frozen_review, memo_review,
                    inventory, cached_ns, rowview=rowview,
                )
                if res:
                    per_key[(kind, name)] = res
        for kind, name in changed_keys:
            if kind in changed_kinds:
                continue
            res = self._eval_one_key(
                kind, name, review, frozen_review, memo_review, inventory,
                cached_ns, rowview=rowview,
            )
            if res:
                per_key[(kind, name)] = res
            else:
                per_key.pop((kind, name), None)
        self._flush_render_counts()
        return per_key

    # Below this many constraint x review cells the device dispatch costs
    # more than it saves (kernel launch + host<->device transfer); small
    # batches evaluate host-side with the exact native matcher +
    # interpreter.  This static threshold is the PRIOR:
    # calibrate_routing() replaces it with a measured cost model
    # (dispatch floor + per-cell device rate vs per-cell interp rate), so
    # the crossover adapts to how the chip is attached.
    DEVICE_MIN_CELLS = int(os.environ.get("GK_DEVICE_MIN_CELLS", "4096"))

    def calibrate_routing(self, runs: int = 3) -> Optional[dict]:
        """Measure once: affine cost models for all THREE evaluation paths
        — device (dispatch floor + per-cell rate, fitted from the REAL
        compute_masks path at a 1-review probe — the admission shape —
        and a large batch; a synthetic ping would not pay the pack and
        fetch a real request does), host numpy serving (floor + per-cell), and
        the per-cell interpreter rate.  review_batch then routes each
        request by predicted cost instead of static priors.  Explicit call
        (main.py startup / bench): never triggered implicitly, so test
        paths stay deterministic.  Returns the calibration dict, or None
        when no constraints are installed."""
        import time as _time

        with self._lock:
            n_constraints = sum(len(v) for v in self.constraints.values())
            if n_constraints == 0:
                return None

        seq = [0]

        def cal_review():
            seq[0] += 1
            i = seq[0]
            return {
                "kind": {"group": "", "version": "v1", "kind": "Pod"},
                "name": f"gk-route-cal-{i}", "namespace": "default",
                "operation": "CREATE",
                "object": {
                    "apiVersion": "v1", "kind": "Pod",
                    "metadata": {"name": f"gk-route-cal-{i}",
                                 "namespace": "default",
                                 "labels": {"cal": str(i)}},
                    "spec": {"containers": [
                        {"name": "c", "image": f"cal.io/x:{i}"}]},
                },
            }

        def device_ms(batch):
            ts = []
            for _ in range(runs + 1):  # first run absorbs compiles/warmup
                reviews = [cal_review() for _ in range(batch)]
                with self._lock:
                    t0 = _time.perf_counter()
                    # gklint: disable=blocking-under-lock -- as in
                    # _breaker_probe: the fetch under the lock is the
                    # dispatch being priced
                    self.compute_masks(reviews)
                    ts.append(_time.perf_counter() - t0)
            # median, deliberately asymmetric with the host paths' min:
            # a dispatch's run-to-run variance (interconnect, queueing) is
            # intrinsic cost every real request pays, so the route should
            # price its expectation; host-path variance is scheduler noise
            # a real request mostly does NOT pay
            return float(np.median(ts[1:])) * 1e3

        def affine(ms_small, ms_large, cells_small, cells_large):
            per_cell = max(
                (ms_large - ms_small) / max(cells_large - cells_small, 1),
                1e-9,
            )
            floor = max(ms_small - per_cell * cells_small, 1e-3)
            return floor, per_cell

        # device: 1-review probe (the admission shape the r4 routing model
        # extrapolated to, badly) + a large batch for the slope
        b_large = 128
        dev_floor, dev_per_cell = affine(
            device_ms(1), device_ms(b_large),
            n_constraints, b_large * n_constraints,
        )

        np_floor = np_per_cell = None
        if self.np_serve_enabled:
            def np_ms(batch):
                ts = []
                for _ in range(runs + 1):
                    reviews = [cal_review() for _ in range(batch)]
                    t0 = _time.perf_counter()
                    self._np_review(reviews)
                    ts.append(_time.perf_counter() - t0)
                # min, not median: pure host work — the minimum is the
                # true cost, everything above it is scheduler noise that
                # would bias the route away from the numpy path
                return float(min(ts[1:])) * 1e3

            np_floor, np_per_cell = affine(
                np_ms(1), np_ms(8), n_constraints, 8 * n_constraints,
            )

        # warm first, then MEDIAN of the warm samples — the r05 curve
        # misrouted N=50 to interp (6.28ms measured vs np's 2.11ms)
        # because min() over three samples that include cold parser/
        # freeze caches prices the interpreter at its best-case rate,
        # which real unique-content requests do not pay.  Like the
        # device probe above, the route should price the expectation;
        # the np path keeps min() deliberately (its floor is what the
        # route must not be biased away from).
        self._interp_review_memo(cal_review())  # warm: parser/freeze/caches
        interp_ts = []
        for _ in range(max(runs, 3) + 2):
            rv = cal_review()  # unique: the request memo cannot serve it
            t0 = _time.perf_counter()
            self._interp_review_memo(rv)
            interp_ts.append(_time.perf_counter() - t0)
        interp_ms = float(np.median(interp_ts)) * 1e3
        interp_cells_per_ms = n_constraints / max(interp_ms, 1e-3)

        cal = {
            "rtt_ms": dev_floor,  # affine intercept: dispatch+fetch floor
            "device_cells_per_ms": 1.0 / dev_per_cell,
            "interp_cells_per_ms": interp_cells_per_ms,
        }
        if np_floor is not None:
            cal["np_floor_ms"] = np_floor
            cal["np_cells_per_ms"] = 1.0 / np_per_cell
        self._route_cal = cal
        return cal

    # uncalibrated prior for np-vs-interp: the numpy serve has a ~1-2ms
    # floor (pack + mats + mask), the interpreter walks ~10-20 cells/ms —
    # below this many cells the walk wins
    NP_MIN_CELLS = int(os.environ.get("GK_NP_MIN_CELLS", "24"))

    # a load hint older than this is stale (the batcher refreshes every
    # dispatch; a gone batcher must not pin throughput routing forever)
    LOAD_HINT_TTL_S = 5.0
    # feasibility margin: a tier must sustain the offered load with this
    # much headroom before latency-routing may pick it — running a tier
    # at 100% of its measured capacity queues unboundedly
    LOAD_HEADROOM = 1.25

    def set_offered_load(self, rps: Optional[float]):
        """Offered-load hint from the micro-batcher (reviews/s).  With a
        fresh hint and a calibration, _route_eval prices SUSTAINABLE
        throughput: the latency-optimal tier is only chosen while it can
        actually carry the offered rate (docs/fleet.md)."""
        import time as _time

        if rps and rps > 0:
            self._offered_load = (float(rps), _time.monotonic())
        else:
            self._offered_load = None

    def _load_hint(self) -> Optional[float]:
        h = self._offered_load
        if h is None:
            return None
        import time as _time

        rps, t = h
        return rps if _time.monotonic() - t <= self.LOAD_HINT_TTL_S else None

    def set_brownout_pin(self, active: bool):
        """Brownout ladder level 3 (obs/brownout.py): pin routing to the
        cheapest sustainable tier — the max-throughput choice the
        saturated branch of _route_eval makes, but unconditionally, so
        the pin holds even between batcher dispatches (a stale load
        hint must not un-pin a declared brownout)."""
        self._brownout_pin = bool(active)

    def _tier_models(self, per_review_cells: int):
        """[(tier, floor_ms, per_review_ms)] from the calibration — the
        affine service model shared by latency routing, load-aware
        routing, and the batcher's adaptation loop."""
        cal = self._route_cal
        if cal is None:
            return []
        out = [
            ("interp", 0.0, per_review_cells / cal["interp_cells_per_ms"]),
            ("device", cal["rtt_ms"],
             per_review_cells / cal["device_cells_per_ms"]),
        ]
        if self.np_serve_enabled and "np_floor_ms" in cal:
            out.append(
                ("np", cal["np_floor_ms"],
                 per_review_cells / cal["np_cells_per_ms"])
            )
        return out

    # the largest batch the serving layer coalesces (MicroBatcher
    # max_batch default): tier capacity is measured at this batch size
    ROUTE_MAX_BATCH = 256.0

    def _route_decision(self, cells: int, n_reviews: int = 1,
                        want_priced: bool = True):
        """The pricing behind :meth:`_route_eval` -> (route, reason,
        lam, priced): the chosen tier, the reason that decided it
        (obs/routeledger.py REASONS), the offered-load hint consulted,
        and the priced tier table [{tier, floor_ms, per_review_ms,
        predicted_ms, mu_rps}] — what `/debug/routez` explains a
        decision with.  Pure: recording is the caller's job, so the
        breaker/compile overrides in _review_batch_eval can amend the
        effective tier before one ledger entry lands.

        want_priced=False (a disabled ledger) skips the table build, and
        the service models/mu are computed lazily — the calibrated
        latency fast path then pays exactly what it did pre-ledger."""
        if self.DEVICE_MIN_CELLS == 0:
            return "device", "forced_device", None, []
        cal = self._route_cal
        np_on = self.np_serve_enabled
        if cal is None:
            if cells >= self.DEVICE_MIN_CELLS:
                return "device", "uncalibrated_prior", None, []
            route = (
                "np" if np_on and cells >= self.NP_MIN_CELLS else "interp"
            )
            return route, "uncalibrated_prior", None, []
        device_ms = cal["rtt_ms"] + cells / cal["device_cells_per_ms"]
        interp_ms = cells / cal["interp_cells_per_ms"]
        costs = [(interp_ms, "interp"), (device_ms, "device")]
        if np_on and "np_floor_ms" in cal:
            costs.append(
                (cal["np_floor_ms"] + cells / cal["np_cells_per_ms"], "np")
            )
        per_review = max(cells // max(n_reviews, 1), 1)
        B = self.ROUTE_MAX_BATCH
        state: dict = {}

        def tier_mu():
            if "mu" not in state:
                state["models"] = self._tier_models(per_review)
                state["mu"] = {
                    tier: B / max(floor + B * per_ms, 1e-9)
                    for tier, floor, per_ms in state["models"]
                }
            return state["mu"]

        def priced():
            if not want_priced:
                return []
            mu = tier_mu()
            predicted = {tier: ms for ms, tier in costs}
            return [
                {
                    "tier": tier,
                    "floor_ms": round(floor, 4),
                    "per_review_ms": round(per_ms, 6),
                    "predicted_ms": round(predicted.get(tier, 0.0), 4),
                    # mu is per-ms; export reviews/s for readability
                    "mu_rps": round(mu[tier] * 1e3, 1),
                }
                for tier, floor, per_ms in state["models"]
            ]

        if self._brownout_pin:
            # brownout pin: the max-throughput tier at the coalesced
            # batch size, unconditionally — the queue drains fastest
            # there, which is the only latency that matters mid-brownout
            mu = tier_mu()
            if mu:
                chosen = max(mu.items(), key=lambda kv: kv[1])[0]
                return chosen, "brownout_pin", self._load_hint(), priced()
        lam = self._load_hint()
        if lam:
            mu = tier_mu()
            lam_pms = lam / 1e3  # reviews per ms
            sustainable = [
                (ms, tier) for ms, tier in costs
                if mu.get(tier, 0.0) >= lam_pms * self.LOAD_HEADROOM
            ]
            if sustainable:
                return min(sustainable)[1], "load_aware", lam, priced()
            if mu:  # saturated everywhere: drain via max throughput
                chosen = max(mu.items(), key=lambda kv: kv[1])[0]
                return chosen, "saturated", lam, priced()
        return min(costs)[1], "latency", lam, priced()

    def _route_eval(self, cells: int, n_reviews: int = 1) -> str:
        """Predicted-cheapest path for a request of `cells` =
        reviews x constraints: "device" | "np" | "interp".
        DEVICE_MIN_CELLS = 0 always forces the device (tests rely on it);
        uncalibrated, the static DEVICE_MIN_CELLS / NP_MIN_CELLS priors
        decide.

        With a fresh offered-load hint (set_offered_load) the choice is
        LOAD-aware, not size-only: each tier's sustainable throughput is
        mu = B / (floor + B*per_review_ms) at the max coalesced batch B;
        tiers that cannot carry the offered rate (with headroom) are
        excluded even when they'd win this batch's latency, and when no
        tier sustains it the highest-throughput tier is chosen so the
        queue drains fastest.

        Every decision lands in the route ledger (obs/routeledger.py —
        /debug/routez, route_decisions_total)."""
        route, reason, lam, priced = self._route_decision(
            cells, n_reviews, want_priced=self.route_ledger.enabled
        )
        self.route_ledger.record(
            route, reason, cells, n_reviews, lam, priced
        )
        return route


    # batches up to this size are admission traffic: they probe and feed
    # the whole-request memo; larger (streaming) chunks skip both so the
    # sparse render keeps its zero-per-review host cost
    REQUEST_MEMO_BATCH_MAX = 64

    def review_batch(self, reviews: List[dict], tracing: bool = False):
        """N concurrent admission reviews in ONE device dispatch: the mask
        is [C, N], then each review's positive cells render host-side.
        This is the micro-batching seam the webhook server drives.

        Hybrid dispatch: batches too small to amortize a device call run
        through the interpreter path (identical semantics — the device mask
        is only ever a pruning over-approximation of it)."""
        if not reviews:
            return []
        if tracing or len(reviews) > self.REQUEST_MEMO_BATCH_MAX:
            return self._review_batch_eval(reviews, tracing)
        # repeat-content fast path BEFORE routing: a memoized request must
        # never pay a device dispatch (or an interp walk); misses are
        # evaluated as one sub-batch while the hits replay as-is.  The
        # frozen memo keys computed by the probe ride along so the miss
        # path never re-freezes the same review (freeze is ~0.5ms on a
        # real Pod — pure waste twice per unique admission).
        import time as _time

        t0 = obstrace.running_clock(obstrace.PATH_BATCH).mark("route")
        probed = [self._request_memo_hit(r) for r in reviews]
        served: List = [p[0] for p in probed]
        misses = [i for i, s in enumerate(served) if s is None]
        obstrace.record_span(
            "memo.lookup", t0, _time.perf_counter(),
            stage=obstrace.CACHE_LOOKUP,
            hits=len(reviews) - len(misses), misses=len(misses),
        )
        record_cache("request_memo", True, len(reviews) - len(misses))
        record_cache("request_memo", False, len(misses))
        if misses:
            evaled = self._review_batch_eval(
                [reviews[i] for i in misses], tracing,
                memo_reviews=[probed[i][1] for i in misses],
            )
            for j, i in enumerate(misses):
                served[i] = evaled[j]
        return [s if isinstance(s, tuple) else (s, None) for s in served]

    def _n_constraints_total(self) -> int:
        """Installed constraint count, cached per epoch (summing 500
        kinds per admission is real).  Caller need not hold the lock."""
        with self._lock:  # concurrent ingest may resize the dicts (RLock)
            cached = self._n_constraints_cache
            if cached is not None and cached[0] == self._cs_epoch:
                return cached[1]
            n_constraints = sum(
                len(v) for v in self.constraints.values()
            )
            self._n_constraints_cache = (self._cs_epoch, n_constraints)
            return n_constraints

    def predicted_batch_ms(self, n_reviews: int) -> Optional[float]:
        """Predicted service time (ms) of an n-review coalesced batch on
        its cheapest tier — the micro-batcher's adaptation model.  None
        until calibrate_routing has run."""
        if self._route_cal is None:
            return None
        per_review = max(self._n_constraints_total(), 1)
        models = self._tier_models(per_review)
        if not models:
            return None
        return min(
            floor + n_reviews * per_ms for _t, floor, per_ms in models
        )

    def _review_batch_eval(self, reviews: List[dict], tracing: bool,
                           memo_reviews: Optional[list] = None):
        """Route and evaluate (no memo probe: review_batch already served
        the hits)."""
        clock = obstrace.running_clock(obstrace.PATH_BATCH)
        if clock.stage != "route":   # review_batch's memo probe opened it
            clock.mark("route")
        n_constraints = self._n_constraints_total()
        cells = len(reviews) * max(n_constraints, 1)
        route, reason, lam, priced = self._route_decision(
            cells, n_reviews=len(reviews),
            want_priced=self.route_ledger.enabled,
        )
        effective = route
        if route == "device":
            if self._compiler is not None and not self._compiler.ready():
                # async ingestion: while the background XLA compile for
                # the latest template/constraint epoch is in flight,
                # admission reviews serve from the host paths instead of
                # blocking
                effective = "np" if self.np_serve_enabled else "interp"
                reason = "compile_pending"
            elif not self.breaker.allow():
                # circuit breaker: while open, every evaluation serves
                # from the host tiers below — the degradation ladder's
                # middle rung (docs/failure-modes.md); the background
                # probe brings the device back without real traffic
                # paying failed dispatches.  Checked LAST (and only for a
                # device route) so a granted half-open trial is always
                # followed by the device attempt below (which records its
                # outcome) — an earlier divert would leak the trial token
                effective = "np" if self.np_serve_enabled else "interp"
                reason = "breaker_open"
        # one ledger entry per batch, recorded at the SERVE site so the
        # entry names the tier that actually evaluated — override
        # reasons (breaker_open/compile_pending) explain why a priced
        # device win served host-side, and an np-ineligible batch that
        # falls through to the interpreter is attributed to interp, not
        # to the tier the pricing predicted (obs/routeledger.py)
        def _record(tier):
            self.route_ledger.record(
                tier, reason, cells, len(reviews), lam, priced
            )

        if effective != "device":
            if tracing:
                _record("interp")  # traced runs take the interp walk
                return [
                    InterpDriver.review(self, r, tracing=True)
                    for r in reviews
                ]
            if effective != "interp":  # np predicted cheaper or diverted
                out = self._np_review(reviews, memo_reviews)
                if out is not None:
                    _record("np")
                    return out
            _record("interp")
            return self._interp_serve(reviews, memo_reviews)
        _record("device")
        with self._lock:
            try:
                # gklint: disable=blocking-under-lock -- as in
                # _breaker_probe: the device tier evaluates and fetches
                # under the driver lock by design
                ordered, mask, autoreject = self.compute_masks(reviews)
            except Exception as e:
                # backend failure: feed the breaker and degrade THIS batch
                # to the interpreter tier instead of poisoning the whole
                # window — callers always get an answer or a deadline.
                # Only the flagging happens under the lock; the fallback
                # walk below runs OUTSIDE it (per-review locking, like the
                # normal interp divert path) so concurrent ingest and the
                # audit thread don't stall behind a failed batch's render
                self.breaker.record_failure(e)
                log.warning(
                    "device evaluation failed (%s: %s); serving %d "
                    "review(s) from the interpreter tier",
                    type(e).__name__, e, len(reviews),
                )
                device_failed = True
            else:
                device_failed = False
                self.breaker.record_success()
            if not device_failed:
                inventory = self._inventory_for_render()
                mask_np = np.asarray(mask)
                rej_np = np.asarray(autoreject)
                if tracing:
                    return self._review_batch_traced(
                        reviews, ordered, mask_np, rej_np, inventory
                    )
                joins = self._review_joins(reviews, inventory)
                join_inv = None
                if joins is not None:
                    # referential cells: exact mask bits and pruned
                    # inventories from the join index
                    clock.mark("join_lookup")
                    join_inv = joins.refine(ordered, mask_np)
                clock.mark("render")
                with obstrace.span("render", stage=obstrace.RENDER,
                                   tier="tpu"):
                    out = self._render_masked(
                        reviews, ordered, mask_np, rej_np, inventory,
                        memo_keys=memo_reviews, joins=joins,
                        join_inv=join_inv,
                    )
                if joins is not None:
                    joins.flush()
                clock.mark("account")  # request-memo stores
                # admission-sized batches feed the request memo from the
                # device path too, so repeat content (replica/retry
                # storms — including repeat ALLOWS, the common case)
                # replays at memo speed next time; the 1M-review
                # streaming path (large chunks) never reaches here
                # (review_batch routes them straight to
                # _review_batch_eval)
                if (
                    len(reviews) <= self.REQUEST_MEMO_BATCH_MAX
                    and self._memoable_synced()
                ):
                    for ri, review in enumerate(reviews):
                        mk = memo_reviews[ri] if memo_reviews else None
                        self._store_request_memo(
                            review, out[ri][0], mk[1] if mk else None,
                        )
                return out
        # device failed: interpreter-tier fallback, lock released.  The
        # amended, SERVE-SITE ledger entry makes the fallback
        # attributable — a breaker-trip flight recording shows device ->
        # device_failed -> breaker_open in causal order — and names the
        # tier that actually evaluated (np may be ineligible for this
        # batch).  No entry lands when the deadline check below raises:
        # nothing served.
        reason = "device_failed"
        # The budget check covers SAME-THREAD callers (embedders using
        # deadline.budget() around client.review); webhook traffic is
        # bounded upstream — the micro-batcher's event-wait timeout and
        # its per-request fallback deadline checks (webhook/server.py),
        # since the batcher thread does not carry the handler thread's
        # deadline ContextVar
        if _deadline.expired():
            raise _deadline.DeadlineExceeded(
                "deadline exhausted during device-failure fallback"
            )
        if tracing:
            # traced runs must still emit their trace lines
            _record("interp")
            return [
                InterpDriver.review(self, r, tracing=True) for r in reviews
            ]
        # prefer the vectorized numpy host tier (same preference order as
        # the breaker-open divert above) — the degraded window is exactly
        # when fallback latency matters most
        out = self._np_review(reviews, memo_reviews)
        if out is not None:
            _record("np")
            return out
        _record("interp")
        return self._interp_serve(reviews, memo_reviews)

    def _interp_serve(self, reviews: List[dict],
                      memo_reviews: Optional[list] = None):
        """Interpreter-tier serving with the stage span every evaluation
        path emits: tier + breaker state make degraded traffic (breaker
        open, compile in flight) attributable in the trace."""
        obstrace.running_clock(obstrace.PATH_BATCH).mark("render")
        with obstrace.span("eval.interp", stage=obstrace.RENDER,
                           tier="interp", breaker=self.breaker.state):
            return [
                self._interp_review_memo(
                    r, memo_reviews[i] if memo_reviews else None
                )
                for i, r in enumerate(reviews)
            ]

    def _render_masked(self, reviews, ordered, mask_np, rej_np, inventory,
                       memo_keys=None, joins=None, join_inv=None):
        """Bulk sparse render shared by the device and host (numpy) mask
        paths: iterate only (review, constraint) cells the mask marked
        positive, review-major so per-review result ordering matches the
        dense loop.  Reviews with no positive cell (the common admission
        case) cost zero host work — in particular no freeze().

        Three sub-passes, assembled back in mask order (caller holds the
        lock):
          1. plan pass — review-memo probes and compiled render plans
             (ops/renderplan.py) resolve cells without the interpreter;
             one RowView per flagged review shares every walked path
             across its constraints
          2. interp tail — the remaining cells evaluate through the
             bounded render pool
          3. assembly — Results built in the original cell order
             (autoreject entries first per cell), memo stores applied on
             this (lock-holding) thread only"""
        import time as _time

        from .renderplan import RenderPool, RowView

        # reset up front: an early return (no flagged cells) must not
        # leave the previous pass's stats for bench/telemetry readers
        self.last_render_stats = {}
        out: List = [([], None) for _ in reviews]
        ris, iis = np.nonzero((mask_np | rej_np).T)
        cells = list(zip(ris.tolist(), iis.tolist()))
        if not cells:
            return out
        # one vectorized gather instead of two scalar numpy indexings per
        # cell (each is ~300ns of fancy-indexing machinery)
        mfl = mask_np[iis, ris]
        mflags = mfl.tolist()
        rflags = rej_np[iis, ris].tolist()
        # cost-ledger attribution (obs/costs.py): flagged cells per
        # constraint come from one vectorized bincount; the loops below
        # only pay a dict add on the RARE events (violations, memo hits)
        cost_on = obscosts.enabled()
        if cost_on:
            cells_by_i = np.bincount(iis[mfl], minlength=len(ordered))
            attv: Dict[int, int] = {}
            attm: Dict[int, int] = {}
        t0 = _time.perf_counter()
        cached_ns = self.store.cached_namespace
        rows: Dict[int, RowView] = {}
        resolved: Dict[int, list] = {}
        stores: List[Tuple] = []  # (mkey, cell idx) review-memo writes
        deferred: List[Tuple] = []  # (cell idx, ri, i, mkey)
        # intra-batch dedup: a micro-batch of identical replica pods must
        # evaluate each memoable (constraint, content) cell ONCE even
        # though memo stores land only after the render passes
        seen_mkey: Dict[Tuple, int] = {}
        aliases: Dict[int, int] = {}
        memo_hits = 0
        if self._review_memo_epoch != self._cs_epoch:
            self._review_memo.clear()
            self._review_memo_epoch = self._cs_epoch
        for idx, (ri, i) in enumerate(cells):
            if not mflags[idx]:
                continue  # autoreject-only cell: handled at assembly
            kind, name, constraint = ordered[i]
            review = reviews[ri]
            row = rows.get(ri)
            if row is None:
                # seed from the request-memo probe's frozen forms when the
                # caller already paid for them (freeze is ~0.5ms per pod)
                mk = memo_keys[ri] if memo_keys else None
                row = RowView(review, mk[0] if mk else None)
                if mk is not None:
                    row._memo_frozen = mk[1]
                rows[ri] = row
            mkey = None
            # memoability via the incrementally-maintained complement set
            # (_memoable_update): O(1) per cell vs the getattr chain of
            # _cell_memoable
            if (kind, name) not in self._memoable_false and (
                kind in self.templates
            ):
                mkey = (kind, name, row.memo_frozen())
                hit = self._review_memo.get(mkey)
                if hit is not None:
                    resolved[idx] = hit
                    memo_hits += 1
                    if cost_on:
                        attm[i] = attm.get(i, 0) + 1
                        if hit:
                            attv[i] = attv.get(i, 0) + len(hit)
                    continue
                src = seen_mkey.get(mkey)
                if src is not None:
                    aliases[idx] = src  # same batch, same content cell
                    memo_hits += 1
                    if cost_on:
                        attm[i] = attm.get(i, 0) + 1
                    continue
                seen_mkey[mkey] = idx
            plan = self._render_plan_for(kind, name, constraint)
            if plan is not None:
                # the mask cell already includes the packed match; the
                # native re-check is only needed where packing can
                # over-approximate it (label/namespace selectors)
                if plan.match_exact or constraint_matches(
                    constraint, review, cached_ns
                ):
                    self._tier_counts[plan.tier] += 1
                    violations = plan.apply(row)
                else:
                    violations = []  # device over-approximated the match
                resolved[idx] = violations
                if cost_on and violations:
                    attv[i] = attv.get(i, 0) + len(violations)
                if mkey is not None:
                    stores.append((mkey, idx))
                continue
            deferred.append((idx, ri, i, mkey))
        t1 = _time.perf_counter()
        if deferred:
            # a referential cell renders against the pruned inventory of
            # its key group (joins.refine), every other against the whole
            thunks = [
                (lambda c=ordered[i][2], k=ordered[i][0], r=reviews[ri],
                        f=rows[ri].frozen(),
                        inv=(join_inv.get((ri, i), inventory)
                             if join_inv else inventory):
                 self._eval_cell(c, k, r, f, inv,
                                 allow_plan=False, count=False))
                for _idx, ri, i, _mkey in deferred
            ]
            evaled = RenderPool.map_ordered(thunks)
            self._tier_counts["interp"] += len(deferred)
            for (idx, ri, i, mkey), violations in zip(deferred, evaled):
                if join_inv and not violations and (ri, i) in join_inv:
                    joins.note_empty(*ordered[i], ri)
                resolved[idx] = violations
                if cost_on and violations:
                    attv[i] = attv.get(i, 0) + len(violations)
                if mkey is not None:
                    stores.append((mkey, idx))
        t2 = _time.perf_counter()
        for idx, src in aliases.items():
            resolved[idx] = resolved[src]
            if cost_on and resolved[src]:
                i = cells[idx][1]
                attv[i] = attv.get(i, 0) + len(resolved[src])
        for mkey, idx in stores:
            if len(self._review_memo) >= self.REVIEW_MEMO_MAX:
                self._review_memo.clear()
            self._review_memo[mkey] = resolved[idx]
        for idx, (ri, i) in enumerate(cells):
            kind, _name, constraint = ordered[i]
            review = reviews[ri]
            results = out[ri][0]
            if rflags[idx] and needs_autoreject(
                constraint, review, cached_ns
            ):
                results.append(
                    Result(
                        msg="Namespace is not cached in OPA.",
                        metadata={"details": {}},
                        constraint=constraint,
                        review=review,
                        enforcement_action=self._enforcement_action(constraint),
                    )
                )
            self._append_violation_results(
                results, resolved.get(idx), constraint, kind, review
            )
        t3 = _time.perf_counter()
        n_interp = len(deferred)
        n_plan = len(resolved) - n_interp - memo_hits
        obstrace.record_span(
            "render.plan", t0, t1, stage=obstrace.RENDER, plan="compiled",
            cells=n_plan, memo_hits=memo_hits,
        )
        if n_interp:
            obstrace.record_span(
                "render.interp", t1, t2, stage=obstrace.RENDER,
                plan="interp", cells=n_interp,
            )
        self.last_render_stats = {
            "cells": float(len(resolved)),
            "plan_cells": float(n_plan),
            "interp_cells": float(n_interp),
            "memo_hits": float(memo_hits),
            "plan_ms": (t1 - t0) * 1e3,
            "interp_ms": (t2 - t1) * 1e3,
            "assemble_ms": (t3 - t2) * 1e3,
        }
        if cost_on:
            # one ledger record per pass: per-constraint flagged cells,
            # bound plan tier, violation + memo counts; render seconds
            # apportioned by cells inside the ledger
            entries = []
            for i in np.nonzero(cells_by_i)[0].tolist():
                kind, name, _constraint = ordered[i]
                plan = self._bound_plans.get((kind, name))
                entries.append((
                    kind, name, int(cells_by_i[i]),
                    getattr(plan, "tier", None) or "interp",
                    attv.get(i, 0), attm.get(i, 0),
                ))
            obscosts.record_render(entries, t1 - t0, t2 - t1)
        self._flush_render_counts()
        return out

    def _np_review(self, reviews: List[dict],
                   memo_reviews: Optional[list] = None):
        """Serve an admission batch from the incremental host-side numpy
        constraint side (ops/npside.py): the same over-approximating mask
        + exact render as the device path, with no dispatch RTT and no
        compile anywhere — in particular not during template-ingest
        storms, where the device executable is perpetually behind.
        Returns None when disabled or empty (caller falls back)."""
        if not self.np_serve_enabled:
            return None
        import time as _time

        # host tiers on the batch clock: pack = the side's sync, render =
        # the host evaluation and the render; no enqueue / device_wait /
        # fetch stage exists for them
        clock = obstrace.running_clock(obstrace.PATH_BATCH)
        t_enter = _time.perf_counter()
        with self._lock:
            t_locked = clock.mark("pack")
            ns = self._np_side
            ns.sync(self)
            t_synced = clock.mark("render")
            got = ns.serve(self, reviews)
            if got is None:
                return None
            t_served = _time.perf_counter()
            obstrace.record_span("np.pack", t_locked, t_synced,
                                 stage=obstrace.PACK)
            obstrace.record_span(
                "np.eval", t_synced, t_served, stage=obstrace.DISPATCH,
                tier="numpy", breaker=self.breaker.state,
            )
            record_stage(
                DISPATCH_M, t_served - t_synced,
                {"path": "review", "tier": "numpy"},
            )
            if obscosts.enabled():
                obscosts.record_dispatch(
                    self._cost_kind_counts(), t_served - t_synced,
                    len(reviews), path="review",
                )
            ordered, mask, rej = got
            inventory = self._inventory_for_render()
            joins = self._review_joins(reviews, inventory)
            join_inv = None
            if joins is not None:
                clock.mark("join_lookup")
                mask = np.array(mask, dtype=bool)  # refine() clears bits
                join_inv = joins.refine(ordered, mask)
                clock.mark("render")
            with obstrace.span("render", stage=obstrace.RENDER,
                               tier="numpy"):
                out = self._render_masked(
                    reviews, ordered, mask, rej, inventory,
                    memo_keys=memo_reviews, joins=joins,
                    join_inv=join_inv,
                )
            if joins is not None:
                joins.flush()
            clock.mark("account")  # request-memo stores
            if (
                len(reviews) <= self.REQUEST_MEMO_BATCH_MAX
                and self._memoable_synced()
            ):
                for ri, review in enumerate(reviews):
                    mk = memo_reviews[ri] if memo_reviews else None
                    self._store_request_memo(
                        review, out[ri][0], mk[1] if mk else None,
                    )
            self.last_review_stats = {
                "lock_wait_ms": (t_locked - t_enter) * 1e3,
                "eval_ms": (_time.perf_counter() - t_locked) * 1e3,
                "path": "np",
            }
            return out

    def _memoable_synced(self) -> bool:
        """Epoch-sync the request-memo bookkeeping, then answer whether
        the CURRENT constraint side is memoable.  Must run under the SAME
        lock hold as the evaluation whose results will be stored: a
        concurrent epoch bump between an earlier sync and the store would
        otherwise let a stale memoable=True verdict bless entries whose
        results depend on mutable state (advisor race)."""
        if self._request_memo_epoch != self._cs_epoch:
            # do NOT clear the memo: stale entries repair incrementally
            self._request_memo_ok = None
            self._request_memo_epoch = self._cs_epoch
        return self._request_memoable()

    def _store_request_memo(self, review: dict, results: List[Result],
                            memo_review=None):
        """Store one review's exact results as a request-memo entry
        (caller holds the lock and has verified memoability via
        _memoable_synced).  The flat replay list is sorted by
        (kind, name) so replays order identically whichever evaluation
        path populated or repaired the entry.  memo_review: the frozen
        uid-stripped key when a caller already computed it."""
        from ..engine.value import freeze

        if len(self._request_memo) >= self.REQUEST_MEMO_MAX:
            self._request_memo.clear()
        if memo_review is None:
            memo_review = _strip_request_meta(freeze(review))
        per_key: Dict[Tuple[str, str], list] = {}
        for r in results:
            key = (r.constraint.get("kind", ""),
                   (r.constraint.get("metadata") or {}).get("name", ""))
            entry = (r.msg,
                     copy.deepcopy((r.metadata or {}).get("details", {})),
                     r.enforcement_action)
            per_key.setdefault(key, []).append(entry)
        flat = [
            (kind, name, entry)
            for kind, name in sorted(per_key)
            for entry in per_key[(kind, name)]
        ]
        self._request_memo[memo_review] = (self._cs_epoch, per_key, flat)

    def _review_batch_traced(self, reviews, ordered, mask_np, rej_np, inventory):
        """Dense per-cell walk kept for tracing runs: trace lines must name
        every constraint in order, including non-matching ones."""
        from ..engine.value import freeze

        from .renderplan import RowView

        out = []
        for ri, review in enumerate(reviews):
            frozen_review = freeze(review)
            memo_review = _strip_request_meta(frozen_review)
            rowview = RowView(review, frozen_review)
            results: List[Result] = []
            trace: List[str] = []
            for i, (kind, name, constraint) in enumerate(ordered):
                if rej_np[i, ri]:
                    if needs_autoreject(constraint, review, self.store.cached_namespace):
                        results.append(
                            Result(
                                msg="Namespace is not cached in OPA.",
                                metadata={"details": {}},
                                constraint=constraint,
                                review=review,
                                enforcement_action=self._enforcement_action(constraint),
                            )
                        )
                        trace.append(f"autoreject {kind}/{name}")
                if mask_np[i, ri]:
                    self._render_cell(
                        results, constraint, kind, review, frozen_review,
                        inventory, trace, memo_review=memo_review,
                        rowview=rowview,
                    )
            out.append((results, "\n".join(trace)))
        self._flush_render_counts()
        return out

    # Fetched candidate indices per constraint for the capped audit: at
    # least this many, and at least 2x the cap (oversampling absorbs device
    # over-approximation without a fallback row fetch).  Power-of-two so the
    # fused executable's output shape stays stable across cap settings.
    AUDIT_TOPK_MIN = 32

    def _audit_topk(self, cap: int) -> int:
        k = self.AUDIT_TOPK_MIN
        while k < 2 * cap:
            k *= 2
        return min(k, 4096)

    def _fused_audit_fn(self, K: int):
        """The capped-audit fused function: the full evaluation step PLUS
        the per-constraint reduction on-device — violation-candidate counts
        and the first K candidate row indices, packed into one [C, 1+K]
        int32 array.  ONLY that small array is an output: the [C, R] mask
        stays an XLA-internal intermediate, so the sweep never writes a
        C x R array to HBM and the fetch stays KB-sized.  The mask the
        delta path and the uncapped audit need is a separate lazy dispatch
        of the plain fused fn over the same committed device buffers
        (MaskSource).  This is what keeps the 500x100k sweep's
        device->host traffic small (reference cap contract:
        pkg/audit/manager.go:49)."""
        fused, side = self._fused_fn()
        if (
            self._fused_audit is not None
            and self._fused_audit_key == (self._fused_gen, K)
        ):
            return self._fused_audit, side
        body, has_joins = self._eval_body(side, join_mode="trace")
        if has_joins:
            # join-bearing corpora take a trailing `joins` runtime arg
            # (kind ids) and compute the per-key aggregate tables
            # in-trace (ops/joinkernel.py)
            def fused_audit(rv, cs, cols, gp, joins):
                mask, _autoreject = body(rv, cs, cols, gp, joins)
                return _packed_reduction(mask, K)
        else:
            raw = fused.__wrapped__

            def fused_audit(rv, cs, cols, gp):
                mask, _autoreject = raw(rv, cs, cols, gp)
                return _packed_reduction(mask, K)

        from .aotcache import aot_jit

        self._fused_audit = aot_jit(
            fused_audit, "fused-audit", (self._fused_key, K)
        )
        self._fused_audit_key = (self._fused_gen, K)
        return self._fused_audit, side

    def _fused_audit_mesh_fn(self, K: int, mesh=None):
        """Two-output (mask, per-shard packed) capped-audit variant for
        the mesh path, built with shard_map: each shard evaluates ONLY
        its row slab and reduces it locally to [C, 1+K] (counts + first-K
        candidates translated to GLOBAL row indices); the host merges the
        N small per-shard reductions (_merge_sharded_packed).  Letting
        GSPMD partition the naive jit instead all-gathers the mask for
        the order-dependent top-k — every device then re-reduces the FULL
        row axis, which measured as ~8x single-device time on an
        8-virtual-device mesh (r4 verdict weak #5).  The mask output
        stays device-resident and row-sharded."""
        from jax.sharding import PartitionSpec as _P

        fused, side = self._fused_fn()
        key_now = self._fused_audit_mesh_key
        if (
            self._fused_audit_mesh is not None
            and key_now is not None
            and key_now[0] == self._fused_gen
            and key_now[1] == K
            and key_now[2] is mesh  # identity-is-liveness, not id()
        ):
            return self._fused_audit_mesh
        # join-bearing corpora evaluate in 'trace' mode with the mesh
        # axis named: each shard segment-reduces its own row slab to a
        # compact per-key table and an all_gather merges them — the
        # [C, 1+K]-reduce-then-merge idiom applied to join groups, so a
        # key spanning shards counts once per provider row at any width
        eval_body, has_joins = self._eval_body(
            side, join_mode="trace", axis_name="data"
        )
        raw = fused.__wrapped__

        def body(rv, cs, cols, gp, joins=None):
            if has_joins:
                mask, _autoreject = eval_body(rv, cs, cols, gp, joins)
            else:
                mask, _autoreject = raw(rv, cs, cols, gp)
            packed = _packed_reduction(mask, K)
            shard = jax.lax.axis_index("data")
            idx = packed[:, 1:]
            idx = jnp.where(idx >= 0, idx + shard * mask.shape[1], -1)
            packed = jnp.concatenate([packed[:, :1], idx], axis=1)
            return mask, packed[None]  # leading shard axis for out_specs

        sharded = [None]  # built on first call: specs follow arg trees

        def _build(rv, cs, cols, gp, joins=None):
            def row_spec(a):
                return _P("data", *([None] * (a.ndim - 1)))

            repl = _P()
            in_specs = (
                jax.tree_util.tree_map(row_spec, rv),
                jax.tree_util.tree_map(lambda a: repl, cs),
                jax.tree_util.tree_map(row_spec, cols),
                jax.tree_util.tree_map(lambda a: repl, gp),
            )
            if has_joins:
                in_specs = in_specs + (
                    jax.tree_util.tree_map(lambda a: repl, joins),
                )
            out_specs = (_P(None, "data"), _P("data", None, None))
            if has_joins:
                inner = body
            else:
                def inner(rv, cs, cols, gp):
                    return body(rv, cs, cols, gp)
            sharded[0] = jax.jit(jax.shard_map(
                inner, mesh=mesh, in_specs=in_specs,
                out_specs=out_specs, check_vma=False,
            ))

        def fused_audit_mesh(rv, cs, cols, gp, joins=None):
            if sharded[0] is None:
                _build(rv, cs, cols, gp, joins)
            if has_joins:
                return sharded[0](rv, cs, cols, gp, joins)
            return sharded[0](rv, cs, cols, gp)

        self._fused_audit_mesh = fused_audit_mesh
        self._fused_audit_mesh_key = (self._fused_gen, K, mesh)
        return self._fused_audit_mesh

    def _audit_inputs(self, K: int):
        """Sync the resident incremental audit pack (ops/auditpack.py) and
        return the current fused audit fn + constraint side aligned with
        it."""
        fn, side = self._fused_audit_fn(K)
        self._audit_pack.sync(self, side[3])
        if not self._tables_cover_vocab():
            fn, side = self._fused_audit_fn(K)
        ordered, cp, groups, _col_specs, crow = side
        group_params = [packed for *_s, packed in groups]
        return fn, ordered, cp, group_params, crow

    # Scatter width buckets: one executable covers every dirty count up to
    # 256 (then powers of 4).  A per-power-of-two bucket recompiles the
    # many-leaf scatter (~3-5s XLA) on the first full sweep after each new
    # churn magnitude — measured as the dominant cost of r3's warm full
    # resweep.  The wider bucket trades a few hundred KB of inline row
    # upload (rare: full sweeps only) for compile stability.
    SCATTER_WIDTH_MIN = 256

    def _scatter_width(self, n: int) -> int:
        width = self.SCATTER_WIDTH_MIN
        while width < n:
            width *= 4
        return width

    def _warm_scatter(self, placed):
        """Compile+dispatch the width-SCATTER_WIDTH_MIN scatter in the
        background right after a full upload (result discarded; writes row
        0's own values).  The first timed full resweep then finds the
        executable warm instead of paying its XLA compile."""
        ap = self._audit_pack
        if ap.capacity == 0:
            return
        rows = np.zeros(self.SCATTER_WIDTH_MIN, np.int32)
        host_rows = jax.tree_util.tree_map(
            lambda a: a[rows], (ap.rp, ap.cols)
        )

        def warm():
            try:
                _scatter_rows(placed, rows, host_rows)
            except Exception:  # pragma: no cover - warm-up is best-effort
                log.debug("scatter warm-up failed; first churn patch "
                          "pays the compile instead", exc_info=True)

        from .deltasweep import spawn_bg

        spawn_bg("gk-scatter-warm", warm)

    def _audit_device_inputs(self):
        """Device-resident review-side audit arrays (single-device path).
        Full upload when the pack layout changed (rebuild, growth, new
        leaf); otherwise ONE jitted scatter patches just the dirty rows, so
        a steady-state sweep's host->device traffic is proportional to the
        number of changed objects, not the inventory size."""
        ap = self._audit_pack
        dirty = ap.take_dirty()
        if dirty:
            # the dirty set is consumed HERE; the mesh twin can no longer
            # patch itself and must re-place on its next use
            self._audit_dev_mesh = None
        cache = self._audit_dev
        if cache is None or cache[0] != ap.layout_gen:
            tree = (ap.rp, ap.cols)
            if jax.default_backend() == "cpu":
                # CPU jax.device_put may be ZERO-COPY: the "device"
                # buffers then alias these numpy arrays, and later
                # in-place row packs would silently mutate the captured
                # base state the lazy mask dispatch reads (observed as a
                # per-allocation-alignment-dependent delta under-count).
                # Real devices always copy across the transfer.
                tree = jax.tree_util.tree_map(np.array, tree)
            placed = jax.device_put(tree)
            self._audit_dev = [ap.layout_gen, placed]
            from ..obs import compilestats

            compilestats.record_device_bytes(
                "audit_pack", compilestats.tree_nbytes(tree),
                rows=int(ap.capacity),
            )
            self._warm_scatter(placed)
            return placed
        if dirty:
            rows = np.fromiter(sorted(dirty), np.int32, len(dirty))
            # bucket the scatter width (repeat the last row; duplicate
            # indices write identical values) so the jitted updater does
            # not recompile per distinct dirty count
            width = self._scatter_width(len(rows))
            rows = np.pad(rows, (0, width - len(rows)), mode="edge")
            host_rows = jax.tree_util.tree_map(
                lambda a: a[rows], (ap.rp, ap.cols)
            )
            placed = _scatter_rows(cache[1], rows, host_rows)
            self._audit_dev = [ap.layout_gen, placed]
        return self._audit_dev[1]

    def _record_shard(self, path: str):
        """Per-shard pipeline telemetry hook for pipelined_shard_commit:
        a pack + dispatch span per shard (they overlap by design — the
        packer thread works one slab ahead of the transfers) and the
        audit_shard_* stage histograms."""
        from ..metrics.catalog import record_audit_shard

        def record(shard, rows, pt0, pt1, ct0, ct1):
            # NOT stage-tagged: stage_breakdown's contract is disjoint
            # stage spans summing toward the root duration, and these are
            # sub-intervals of the enclosing pack/dispatch stages (they
            # also overlap each other by design — the pipeline packs
            # shard i+1 while shard i's transfer is in flight)
            obstrace.record_span(
                "audit.shard_pack", pt0, pt1,
                shard=int(shard), rows=int(rows), path=path,
            )
            obstrace.record_span(
                "audit.shard_dispatch", ct0, ct1,
                shard=int(shard), rows=int(rows), path=path,
            )
            record_audit_shard(int(rows), pt1 - pt0, ct1 - ct0, path=path)

        return record

    def _audit_device_inputs_mesh(self, mesh):
        """Shard-resident review-side audit arrays (mesh path): the
        padded, row-sharded placement is committed once per pack layout —
        slab by slab through the double-buffered pipeline (packing shard
        i+1 overlaps the transfer of shard i, parallel/mesh.py) — and
        steady-state sweeps patch just the dirty rows with one jitted
        scatter (donating the dead pre-scatter placement), so host->device
        traffic is proportional to churn on every topology."""
        from ..parallel.mesh import shard_review_side

        ap = self._audit_pack
        dirty = ap.take_dirty()
        if dirty:
            # consumed here; the single-device twin must re-place next use
            self._audit_dev = None
        cache = self._audit_dev_mesh
        if cache is None or cache[0] != ap.layout_gen or cache[1] is not mesh:
            tree = (ap.rp, ap.cols)
            if jax.default_backend() == "cpu":
                # CPU device_put may be zero-copy (see the single-device
                # path): copy so later in-place row packs cannot mutate
                # the committed base state
                tree = jax.tree_util.tree_map(np.array, tree)
            rv_p, cols_p, _target = shard_review_side(
                mesh, ap.capacity, tree[0], tree[1],
                record_shard=self._record_shard("audit"),
            )
            # the mesh OBJECT rides in the cache: identity-is-liveness (a
            # recycled id() could alias a dead mesh, advisor r5)
            self._audit_dev_mesh = [ap.layout_gen, mesh, (rv_p, cols_p)]
            from ..obs import compilestats

            width = int(mesh.devices.size)
            total = compilestats.tree_nbytes(tree)
            compilestats.record_device_bytes(
                "audit_pack_mesh", total, shards=width,
                per_shard_bytes=total // max(width, 1),
                rows=int(ap.capacity),
            )
            return rv_p, cols_p
        if dirty:
            rows = np.fromiter(sorted(dirty), np.int32, len(dirty))
            width = self._scatter_width(len(rows))
            rows = np.pad(rows, (0, width - len(rows)), mode="edge")
            host_rows = jax.tree_util.tree_map(
                lambda a: a[rows], (ap.rp, ap.cols)
            )
            from ..parallel.mesh import DISPATCH_LOCK

            # the pre-scatter placement is donated (dead after the swap);
            # drop the cache first so a failed dispatch cannot leave a
            # consumed tree serving the next sweep
            self._audit_dev_mesh = None
            with DISPATCH_LOCK, mesh:
                placed = _scatter_rows_mesh(cache[2], rows, host_rows)
            self._audit_dev_mesh = [ap.layout_gen, mesh, placed]
        return self._audit_dev_mesh[2]

    def _audit_sweep(self, K: int, reuse_any_k: bool = False):
        """One device sweep over the resident audit pack ->
        (reviews, ordered, mask_src MaskSource for the device-resident
        [C, R'] mask, counts [C] int64, topk [C, K] int32 with -1 padding),
        or None when the inventory is empty.  Cached on (store epoch,
        constraint epoch, K): the device is dispatched only when the
        inventory or the constraint side actually changed.  reuse_any_k
        accepts a cached sweep of any K (the uncapped path only needs the
        mask)."""
        from .deltasweep import DeltaState, MaskSource

        key = (self.store.epoch, self._cs_epoch, K)
        if self._audit_cache is not None:
            ckey = self._audit_cache[0]
            if ckey == key or (reuse_any_k and ckey[:2] == key[:2]):
                self.last_sweep_stats = {
                    "pack_ms": 0.0, "pack_rows": 0.0, "device_ms": 0.0,
                    "fetch_ms": 0.0, "fetch_bytes": 0.0, "cached": 1.0,
                }
                return self._audit_cache[1]
        import time as _time

        if faults.ENABLED:
            faults.fire(faults.TPU_DISPATCH, path="audit")
        clock = obstrace.running_clock(obstrace.PATH_AUDIT)
        t0 = self._audit_clock_pack()
        fn, ordered, cp, group_params, crow = self._audit_inputs(K)
        ap = self._audit_pack
        if ap.n_rows == 0:
            return None
        # referential policies: bring the host join-group index current
        # (diff-bumps reader row generations for changed key groups) and
        # build the trace-mode runtime args the join-bearing executables
        # take (ops/joinkernel.py)
        t_join = clock.mark("join_commit") \
            if self._active_join_plans() else None
        self._ensure_join_state()
        if t_join is not None:
            record_join_upkeep("sweep", _time.perf_counter() - t_join)
        jargs = self._join_trace_args()
        mesh = self._mesh()
        t1 = clock.mark("enqueue")  # dirty-row scatter, placement, launch
        t_packed = t1 if t_join is None else t_join
        if mesh is None:
            rv_d, cols_d = self._audit_device_inputs()
            cs_d, gp_d = self._constraint_device_side(
                cp.arrays, group_params, None, None
            )
            cs_uploaded = self._cs_uploaded
            if jargs is None:
                packed_dev = fn(rv_d, cs_d, cols_d, gp_d)
                # lazy: the [C, R] mask is its own (never-fetched)
                # dispatch against the SAME committed buffers, issued
                # only when the delta path or the uncapped audit first
                # needs it — keeping it out of the capped dispatch means
                # the sweep never materializes the C x R mask in HBM
                fused = self._fused  # this epoch's compiled plain fused fn
                mask_src = MaskSource(
                    lambda: fused(rv_d, cs_d, cols_d, gp_d)[0]
                )
            else:
                packed_dev = fn(rv_d, cs_d, cols_d, gp_d, jargs)
                # the mask dispatch must be AUDIT-mode too: the review
                # fused fn resolves JoinCmp to unknown and would corrupt
                # the delta fold's before-columns
                mask_fn = self._fused_mask_fn()
                mask_src = MaskSource(
                    lambda: mask_fn(rv_d, cs_d, cols_d, gp_d, jargs)
                )
            # background-resolve the mask, then warm the width-8 delta
            # executable against it: both trace/compiles happen off the
            # sweep path, so neither this sweep's fetch nor the first
            # delta sweep pays them (delta falls back to a full sweep
            # while this runs — peek/BUSY in _try_delta)
            self._warm_delta_async(mask_src, cs_d, gp_d)
        else:
            # mesh path: ONE two-output dispatch (mask stays device-
            # resident, only packed is fetched) over SHARD-RESIDENT audit
            # inputs: like the single-device path, the padded+sharded
            # review side is committed once per pack layout and patched
            # by a jitted scatter of just the dirty rows — re-placing the
            # full row pack across N shards every sweep was the measured
            # ~4x sharded-path overhead (r4 verdict weak #5)
            rv_p, cols_p = self._audit_device_inputs_mesh(mesh)
            cs_p, gp_p = self._constraint_device_side(
                cp.arrays, group_params, None, mesh
            )
            cs_uploaded = self._cs_uploaded
            fn_mesh = self._fused_audit_mesh_fn(K, mesh)
            if jargs is None:
                mask_dev, packed_dev = self._guarded_mesh_dispatch(
                    mesh, lambda: fn_mesh(rv_p, cs_p, cols_p, gp_p)
                )
            else:
                from ..parallel.mesh import replicate_tree

                j_p = replicate_tree(mesh, jargs)
                mask_dev, packed_dev = self._guarded_mesh_dispatch(
                    mesh, lambda: fn_mesh(rv_p, cs_p, cols_p, gp_p, j_p)
                )
            mask_src = MaskSource.resolved(mask_dev)
            # warm the mesh-specialized delta executable off the sweep
            # path (the mask is already resolved; only the trace/compile
            # rides the background thread) so the first O(churn) delta
            # sweep under the mesh pays a dispatch, not an SPMD compile
            self._warm_delta_async(mask_src, cs_p, gp_p, mesh)
        t_wait = clock.mark("device_wait")
        packed_dev.block_until_ready()
        t2 = clock.mark("fetch")
        # the ONE small fetch per sweep; crow folds the group-major pad
        # rows out so all host-side state is per ordered constraint
        if mesh is None:
            packed = np.asarray(packed_dev)[crow]
        else:
            # merge to the SAME width K the single-device reduction
            # produces (per-shard lists may be narrower when a shard's
            # row slab is smaller than K)
            packed = _merge_sharded_packed(np.asarray(packed_dev), K)[crow]
        t3 = clock.mark("apply")  # the incremental state rebased
        counts = packed[:, 0].astype(np.int64)
        sweep = (ap.reviews, ordered, mask_src, counts, packed[:, 1:])
        # re-read the epochs: packing may have interned new strings and
        # bumped the constraint-side cache, but the INPUTS are these epochs'
        self._audit_cache = (key, sweep, None)
        # a full sweep (re)bases the incremental state: its inputs include
        # every dirty row the scatter just applied
        # the mesh_width stamp pins the basis to the sweep sharding that
        # produced it: _try_delta refuses a drifted basis, so even code
        # that pokes mesh_enabled directly (instead of set_mesh, which
        # clears the state) rebases via a full sweep rather than
        # dispatching across topologies.
        self._delta_state = DeltaState(
            counts, packed[:, 1:], K, mask_src,
            cs_epoch=self._cs_epoch, layout_gen=ap.layout_gen,
            store_epoch=self.store.epoch, crow=crow,
            mesh_width=1 if mesh is None else int(mesh.devices.size),
        )
        # the full sweep's inputs already reflect every pending change;
        # drop the delta channel so those rows aren't re-applied
        ap.delta_dirty.clear()
        self.last_sweep_stats = {
            "full": 1.0,
            "pack_ms": (t_packed - t0) * 1e3,
            "pack_rows": float(ap.take_packed_rows()),
            "device_ms": (t2 - t1) * 1e3,
            "fetch_ms": (t3 - t2) * 1e3,
            "slice_ms": 0.0,  # a full sweep gathers no dirty-row slice
            # constraint-side arrays this sweep uploaded: 0 on a hit, the
            # str-pred tables when the vocabulary grew inside their width
            "cs_upload_arrays": float(cs_uploaded),
            "enqueue_ms": (t_wait - t1) * 1e3,
            "device_wait_ms": (t2 - t_wait) * 1e3,
            "apply_ms": (_time.perf_counter() - t3) * 1e3,
            "fetch_bytes": float(packed.nbytes),
            "rows": float(ap.n_rows),
            "cells": float(len(ordered) * ap.n_rows),
            "shards": 1.0 if mesh is None else float(mesh.devices.size),
        }
        from ..parallel.mesh import slab_rows

        # capacity-slab based at EVERY width (width 1 included) so the
        # bench scaling curve compares like with like across widths
        self.last_sweep_stats["rows_per_shard"] = float(
            slab_rows(
                ap.capacity, 1 if mesh is None else int(mesh.devices.size)
            )[1]
        )
        if jargs is not None:
            # a join-bearing sweep is its own routing event: without the
            # explicit reason the dispatch would read as an ordinary
            # row-local device sweep in route_decisions_total/routez
            self.last_sweep_stats["join_plans"] = float(len(jargs))
            # the join index rebuilt and diffed against the previous one
            # (JoinState.rebuild); a full sweep previews no delta
            self.last_sweep_stats["join_affected_ms"] = 0.0
            self.last_sweep_stats["join_commit_ms"] = (t1 - t_join) * 1e3
            # tier "device" (the documented taxonomy), flip-exempt: an
            # audit-class dispatch interleaved with np/interp review
            # traffic is not a serving-tier change
            self.route_ledger.record(
                "device", "join_plan", cells=len(ordered) * ap.n_rows,
                n_reviews=int(ap.n_rows), lam=None, track_flips=False,
            )
        obstrace.record_span("audit.pack", t0, t1, stage=obstrace.PACK,
                             rows=ap.n_rows)
        obstrace.record_span(
            "audit.dispatch", t1, t2, stage=obstrace.DISPATCH,
            tier="tpu", breaker=self.breaker.state,
            shards=(1 if mesh is None else int(mesh.devices.size)),
        )
        obstrace.record_span("audit.fetch", t2, t3, stage=obstrace.FETCH,
                             fetch_bytes=float(packed.nbytes))
        record_stage(DISPATCH_M, t2 - t1, {"path": "audit", "tier": "tpu"})
        if obscosts.enabled():
            obscosts.record_dispatch(
                self._cost_kind_counts(), t2 - t1, int(ap.n_rows),
                path="audit",
            )
        return sweep

    def _audit_masks(self):
        """Full host candidate mask for the uncapped audit path.

        Steady state is incremental like the capped path: the base mask is
        fetched ONCE per full sweep, and subsequent audits overwrite just
        the columns of rows the delta sweep re-evaluated (absolute values,
        so reapplying is idempotent) — no full-mask transfer and no full
        device re-execution per store change."""
        got = self._try_delta(self.AUDIT_TOPK_MIN)
        if got is not None:
            reviews, ordered, st = got
            ap = self._audit_pack
            if st.host_mask is None:
                # capacity cannot have changed while the state is valid
                # (a capacity change bumps layout_gen, invalidating it);
                # copy: np.asarray of a jax array is a read-only view
                st.host_mask = np.asarray(
                    st.mask_src.get()
                )[st.crow][:, : ap.capacity]
                st.pending_mask_rows = set(st.row_cols)
            for r in st.pending_mask_rows:
                st.host_mask[:, r] = st.row_cols[r][: st.host_mask.shape[0]]
            st.pending_mask_rows = set()
            return reviews, ordered, st.host_mask
        sweep = self._audit_sweep(self.AUDIT_TOPK_MIN, reuse_any_k=True)
        if sweep is None:
            return [], [], None
        reviews, ordered, mask_src, _counts, _topk = sweep
        key, cached_sweep, host = self._audit_cache
        if host is None:
            st0 = self._delta_state
            crow0 = st0.crow if st0 is not None and st0.mask_src is mask_src \
                else self._constraint_side()[4]
            host = np.asarray(
                mask_src.get()
            )[crow0][:, : self._audit_pack.capacity]
            self._audit_cache = (key, cached_sweep, host)
        # a full sweep just rebased the incremental state; seed its host
        # mask from this fetch so the next delta-path audit doesn't
        # transfer the identical [C, R] mask a second time
        st = self._delta_state
        if (
            st is not None
            and st.host_mask is None
            and st.mask_src is mask_src
        ):
            st.host_mask = host.copy()
            st.pending_mask_rows = set(st.row_cols)
        return reviews, ordered, host

    def audit(self, tracing: bool = False):
        if not self.breaker.allow():
            # breaker open: the inherited interpreter sweep is slower but
            # always answers — the audit loop must not die with the device
            return InterpDriver.audit(self, tracing=tracing)
        self.last_sweep_stats = {}  # stale stats must not decide `cached`
        try:
            out = self._audit_device(tracing)
        except Exception as e:
            from .joinkernel import JoinDivergence

            if isinstance(e, JoinDivergence):
                # the armed (GK_JOIN_ASSERT) join-parity assertion is a
                # diagnostic, not a device failure: serving the interp
                # fallback here would hide exactly the divergence the
                # caller armed the flag to catch
                raise
            self._record_device_failure(e)
            log.warning(
                "device audit failed (%s: %s); serving from the "
                "interpreter tier", type(e).__name__, e,
            )
            return InterpDriver.audit(self, tracing=tracing)
        # only a sweep that actually dispatched resets the breaker's
        # failure streak: a cache-served sweep (cached=1.0) or an
        # empty-inventory sweep (stats left empty — cleared before the
        # call) never contacted the device, and in a quiet cluster either
        # would otherwise keep a failing device's breaker from tripping
        # while admission traffic pays failed dispatches
        stats = self.last_sweep_stats
        if stats and not stats.get("cached"):
            self.breaker.record_success()
        return out

    def _audit_device(self, tracing: bool = False):
        from .renderplan import RowView

        # audit is the throughput path: prefer waiting for the background
        # compile (which holds the driver lock only for host packing) over
        # an interpreter sweep of the whole inventory (advisor r2)
        self._wait_ready_for_audit()
        with self._lock:
            # gklint: disable=blocking-under-lock -- the audit sweep is
            # the exclusive device owner by design: the driver lock holds
            # for the [C,R] dispatch+fetch so admissions route to the
            # np/interp tier instead of interleaving device work; a
            # wedged dispatch is bounded by the mesh watchdog
            reviews, ordered, mask = self._audit_masks()
            if not reviews:
                return [], ("" if tracing else None)
            obstrace.running_clock(obstrace.PATH_AUDIT).mark("render")
            inventory = self._inventory_for_render()
            results: List[Result] = []
            trace: List[str] = [] if tracing else None
            # grouped join renders (docs/referential.md): per join-safe
            # kind, ONE pruned inventory over the union of its flagged
            # rows — the interpreter's per-cell O(R) inventory walk
            # becomes O(group); built lazily on the kind's first cell
            kind_cis: Dict[str, list] = {}
            for i, (k, _n, _c) in enumerate(ordered):
                kind_cis.setdefault(k, []).append(i)
            join_inv: Dict[str, object] = {}

            def _inv_for(kind):
                got = join_inv.get(kind)
                if got is None:
                    got = inventory
                    if self._join_safe(kind):
                        rows = np.nonzero(
                            mask[kind_cis[kind]].any(axis=0)
                        )[0]
                        pruned = self._join_render_inventory(kind, rows)
                        if pruned is not None:
                            got = pruned
                    join_inv[kind] = got
                return got

            # resource-major order, matching InterpDriver.audit; only
            # reviews with a positive cell pay any render cost (plan
            # cells skip even the freeze — the RowView freezes lazily,
            # only when a cell falls back to the interpreter or memo)
            hot_reviews = np.nonzero(mask.any(axis=0))[0]
            for ri in hot_reviews:
                review = reviews[ri] if ri < len(reviews) else None
                if review is None:  # tombstoned row (valid=False anyway)
                    continue
                rowview = RowView(review)
                for i in np.nonzero(mask[:, ri])[0]:
                    kind, name, constraint = ordered[i]
                    violations = self._cell_violations(
                        constraint, kind, review, None, _inv_for(kind),
                        rowview=rowview,
                    )
                    if not violations and self._join_strict(
                        kind, constraint
                    ):
                        self._note_join_false_positive(kind, name, int(ri))
                    self._append_violation_results(
                        results, violations, constraint, kind, review,
                        trace,
                    )
            self._flush_render_counts()
            return results, ("\n".join(trace) if tracing else None)

    # render-memo bound + eviction chunk: at the cap, the OLDEST 1/16 of
    # entries (dict insertion order) are deleted instead of a wholesale
    # clear() — the clear was a guaranteed latency cliff (one sweep
    # suddenly re-rendering 2M cells) exactly on the largest clusters.
    # Segmented FIFO, not LRU: hits don't reorder, so eviction is by
    # insertion age; epoch invalidation (below) is unchanged.
    RENDER_MEMO_MAX = 2_000_000

    def _evict_render_memo(self):
        from itertools import islice

        drop = max(1, self.RENDER_MEMO_MAX // 16)
        for k in list(islice(iter(self._render_memo), drop)):
            del self._render_memo[k]

    def _memo_cell(
        self, kind, name, ri, constraint, review, rowviews, inventory,
        uses_inv, row_gen,
    ) -> list:
        """Violations for one cell, memoized across sweeps: an unchanged
        (constraint side, packed row) pair renders identically unless the
        template reads data.inventory (then any store write invalidates)."""
        mkey = (kind, name, ri)
        if not uses_inv:
            hit = self._render_memo.get(mkey)
            if hit is not None and hit[0] == row_gen:
                return hit[1]
        if callable(inventory):
            # lazy grouped join inventory (_lazy_join_inventory):
            # resolved only on this miss path, never on a memo hit
            inventory = inventory()
        row = rowviews.get(ri)
        if row is None:
            from .renderplan import RowView

            row = RowView(review)
            rowviews[ri] = row
        violations = self._eval_cell(
            constraint, kind, review, None, inventory, rowview=row
        )
        if not uses_inv:
            if len(self._render_memo) >= self.RENDER_MEMO_MAX:
                self._evict_render_memo()
            self._render_memo[mkey] = (row_gen, violations)
        return violations

    def _count_exact(self, kind: str, constraint: dict) -> bool:
        """True when the device-counted violating resources provably equal
        the reference's totalViolations for this constraint: the vectorized
        program is exact with a single non-iterating clause (so a violating
        resource yields exactly one violation), and the match spec uses no
        label selectors (the packed match can only over-approximate through
        non-string labels, ops/pack.py:7-10)."""
        prog = self.programs.get(kind)
        if prog is None or not prog.exact:
            return False
        if getattr(prog, "join_plans", ()):
            # the distinct-provider-row aggregate can over-approximate in
            # one documented corner (same kind/ns/name under two
            # groupVersions, docs/referential.md) — never report its
            # device count as the reference-exact total past the cap
            return False
        if len(prog.clauses) != 1 or prog.clauses[0].slot_iter is not None:
            return False
        match = constraint_match_spec(constraint)
        return not match.get("labelSelector") and not match.get(
            "namespaceSelector"
        )

    def _capped_total(self, kind: str, constraint: dict, capped: bool,
                      n_cand: int, kept: int) -> Tuple[int, str]:
        """The total a capped walk reports for one constraint (the rule
        of audit_capped's docstring, for a rendered walk and a replayed
        one alike): `kept` violations rendered, `n_cand` device
        candidates."""
        if not capped:
            return kept, "exact"
        if self._count_exact(kind, constraint):
            # device count == violation count, provably: report the
            # full total past the cap (manager.go:188 semantics)
            return n_cand, "exact"
        return max(n_cand, kept), "resources"

    # dirty rows per steady-state sweep beyond which a full device sweep
    # is cheaper than the delta evaluation + host merge
    DELTA_MAX_ROWS = 256
    # cumulative rows tracked since the last full sweep beyond which the
    # state is rebased (bounds row_cols host memory at ~ROWS_MAX x C bytes)
    DELTA_ROW_COLS_MAX = 8192
    # how long a delta sweep waits for the background base-mask resolution
    # before falling back to a full sweep.  This wait happens UNDER the
    # driver lock (admission reviews queue behind it), so production keeps
    # it near zero — a sub-second full sweep beats any stall; the test
    # conftest raises it for CPU-backend determinism.
    DELTA_MASK_WAIT_S = 0.05

    def _delta_dispatch_fn(self, mesh):
        """The delta executable for this topology: the AOT wrapper on a
        single device; its plain jit twin under a mesh (serialized
        executables pin a single-device layout — the sharded base mask
        must go through the jit machinery's SPMD compile)."""
        from .aotcache import aot_jit

        dfn = self._delta_fn()
        if mesh is not None and isinstance(dfn, aot_jit):
            return dfn._jitted
        return dfn

    def _warm_delta_async(self, mask_src, cs_d, gp_d, mesh=None):
        """Resolve the base mask, then compile+dispatch the width-8 delta
        executable against it, on the MaskSource's resolver thread.  All
        state it needs is captured here under the driver lock; the thread
        itself only calls thread-safe jax entry points.  On the mesh path
        the mask is already resolved — the prefetch then only warms the
        mesh-specialized delta executable off the sweep path."""
        ap = self._audit_pack
        if not self.delta_enabled or ap.n_rows == 0:
            # no delta path will consume the mask: leave it lazy (the
            # uncapped audit resolves it on demand) instead of paying a
            # background full evaluation nobody may read
            return
        delta_jit = self._delta_dispatch_fn(mesh)  # cached per epoch
        rows_pad = np.zeros(8, np.int32)
        rv_slice = {k: a[rows_pad] for k, a in ap.rp.items()}
        cols_slice = {
            ck: {leaf: a[rows_pad] for leaf, a in leaves.items()}
            for ck, leaves in ap.cols.items()
        }
        jt = self._join_delta_tables()
        jtail = (jt,) if jt is not None else ()
        if mesh is not None:
            from ..parallel.mesh import DISPATCH_LOCK

            def _warm(m):
                # collective-bearing executable dispatched off-thread:
                # take the mesh dispatch lock AND drain the result before
                # releasing it, so the warm's psums can never interleave
                # with a foreground sweep's on any device.  The first warm
                # per (epoch, topology) holds the lock across the SPMD
                # trace+compile too — jit's call cache cannot be populated
                # from a lock-free lower().compile() (measured: the next
                # call still recompiles) — a bounded one-time stall the
                # foreground delta sweep would otherwise pay itself.
                with DISPATCH_LOCK:
                    # gklint: disable=blocking-under-lock -- PR 6 design:
                    # the background warm must drain INSIDE the gate so
                    # its collective launch order can never interleave
                    # with a foreground sweep (the AllReduce rendezvous
                    # deadlock this gate exists to prevent); the stall is
                    # one bounded cold compile
                    delta_jit(
                        m, rows_pad, rv_slice, cs_d, cols_slice, gp_d,
                        *jtail
                    ).block_until_ready()
        else:
            def _warm(m):
                delta_jit(m, rows_pad, rv_slice, cs_d, cols_slice, gp_d,
                          *jtail)

        mask_src.prefetch(after=_warm)

    def _delta_fn(self):
        """Jitted fused evaluation restricted to a [d]-row slice of the
        audit pack, plus the gather of the same rows' BEFORE-columns from
        the resident full-sweep mask, in ONE dispatch ->
        [C, 2d] (old | new) int8.  Same traced computation as the full
        sweep, tiny intermediates, one round trip."""
        fused, side = self._fused_fn()
        if self._delta_jit is not None and self._delta_jit_key == self._fused_gen:
            return self._delta_jit
        body, has_joins = self._eval_body(side, join_mode="tables")
        if has_joins:
            # a churn-slice dispatch cannot derive the global join
            # aggregate from its own rows: the host join index supplies
            # the per-key tables as a trailing runtime argument
            def delta(mask_dev, idx, rv, cs, cols, gp, joins):
                new = body(rv, cs, cols, gp, joins)[0]
                old = mask_dev[:, idx]
                return jnp.concatenate(
                    [old.astype(jnp.int8), new.astype(jnp.int8)], axis=1
                )
        else:
            raw = fused.__wrapped__

            def delta(mask_dev, idx, rv, cs, cols, gp):
                new = raw(rv, cs, cols, gp)[0]
                old = mask_dev[:, idx]
                return jnp.concatenate(
                    [old.astype(jnp.int8), new.astype(jnp.int8)], axis=1
                )

        from .aotcache import aot_jit

        self._delta_jit = aot_jit(delta, "delta", self._fused_key)
        self._delta_jit_key = self._fused_gen
        return self._delta_jit

    @staticmethod
    def _audit_clock_pack() -> float:
        """Open `pack` on the sweeping thread's audit clock (the Client
        opened it already when it owns the clock) -> the instant."""
        clock = obstrace.running_clock(obstrace.PATH_AUDIT)
        if clock.stage == "pack":
            import time as _time

            return _time.perf_counter()
        return clock.mark("pack")

    def _try_delta(self, K: int):
        """Bring the incremental sweep state current with an O(dirty-rows)
        device evaluation (ops/deltasweep.py).  Returns
        (reviews, ordered, state) or None when the delta path is
        ineligible (disabled, no base state, layout changed, or too many
        dirty rows — then the caller runs a full sweep).  Runs under the
        mesh too: the [C, d] dirty-row evaluation is dispatched against
        the shard-resident base mask, so steady-state cost stays O(churn)
        on every topology and only the owning shards' slabs see traffic."""
        if not self.delta_enabled:
            return None
        st = self._delta_state
        if st is None or st.cs_epoch != self._cs_epoch:
            return None
        if st.mesh_width != self.mesh_layout():
            # the basis was produced under a different sweep sharding
            # (someone poked mesh_enabled/_mesh_cache directly instead of
            # set_mesh): its mask placement belongs to the old topology —
            # dispatching against it raises, so rebase via a full sweep.
            # The sweep cache rides the same topology and must go too, or
            # _audit_sweep would serve it without recreating the state.
            self._delta_state = None
            self._audit_cache = None
            return None
        import time as _time

        t0 = self._audit_clock_pack()
        side = self._constraint_side()
        self._audit_pack.sync(self, side[3])
        if not self._tables_cover_vocab():
            side = self._constraint_side()
        ordered, cp, groups, _col_specs, _crow = side
        ap = self._audit_pack
        if st.layout_gen != ap.layout_gen or ap.n_rows == 0:
            return None
        if len(st.row_cols) > self.DELTA_ROW_COLS_MAX:
            return None  # too much cumulative churn: rebase via full sweep
        if not ap.delta_dirty:
            st.store_epoch = self.store.epoch
            self.last_sweep_stats = {
                "pack_ms": (_time.perf_counter() - t0) * 1e3,
                "pack_rows": float(ap.take_packed_rows()),
                "device_ms": 0.0, "fetch_ms": 0.0, "fetch_bytes": 0.0,
                "cached": 1.0,
            }
            return ap.reviews, ordered, st
        if len(ap.delta_dirty) > self.DELTA_MAX_ROWS:
            return None
        # referential policies: the delta dispatch must also re-evaluate
        # the READERS of every key group the churn touched (a churn row
        # invalidates only its key group — never the cluster).  Without a
        # current join index the aggregate cannot be maintained
        # incrementally, so rebase via a full sweep.
        js = None
        clock = obstrace.running_clock(obstrace.PATH_AUDIT)
        join_affected_s = 0.0
        t_commit = None
        if self._active_join_plans():
            js = self._join_state
            if (
                js is None or not js.built
                or js.sig != tuple(
                    p.sig for p in self._active_join_plans()
                )
                or js.rebuild_gen != ap.rebuild_gen
            ):
                return None
            t_aff = clock.mark("join_affected")
            # with the readers a commit outside a sweep left pending
            affected = js.affected(ap, self.interner, ap.delta_dirty) | (
                js.pending - ap.delta_dirty
            )
            join_affected_s = clock.mark("pack") - t_aff
            if len(ap.delta_dirty) + len(affected) > self.DELTA_MAX_ROWS:
                return None
        from .deltasweep import MaskSource

        got = st.mask_src.peek(wait_s=self.DELTA_MASK_WAIT_S)
        if got is MaskSource.BUSY:
            # the base mask is still tracing/compiling in the prefetch
            # thread: a full sweep (sub-second now) beats blocking the
            # audit behind that compile; the delta path resumes once it
            # lands (the full sweep rebases state with a resolved-or-
            # prefetching source either way)
            return None
        if got is None:
            # no resolver running (prefetch crashed or was never kicked):
            # resolve here, with the same failure containment as
            # _apply_delta — a dispatch error must degrade to a full
            # sweep, not crash the audit
            try:
                st.mask_src.get()
            except Exception:
                import logging

                logging.getLogger("gatekeeper_tpu.driver").exception(
                    "base-mask resolution failed; rebasing via a full sweep"
                )
                self._delta_state = None
                return None
        # drained only once eligibility is certain; any failure past this
        # point must invalidate the state (the caller then runs a full
        # sweep, which rebases knowledge and clears both dirty channels)
        rows = sorted(ap.take_delta_dirty())
        ap.join_dirty.clear()  # a subset of `rows`, committed below
        join_rows = 0
        if js is not None:
            # commit the churn to the join index: updates provider/reader
            # maps, bumps affected readers' row generations (stale render
            # reuse), and returns the key-group rows to co-dispatch;
            # the stage stays open over _apply_delta's delta_tables
            t_commit = clock.mark("join_commit")
            extra = js.commit(ap, self.interner, rows)
            record_join_upkeep("sweep", _time.perf_counter() - t_commit)
            if extra:
                join_rows = len(extra)
                rows = sorted(set(rows) | extra)
                from ..metrics.catalog import record_join_affected

                record_join_affected(join_rows)
        try:
            return self._apply_delta(st, ap, rows, ordered, cp, groups, t0,
                                     join_rows=join_rows,
                                     join_affected_s=join_affected_s,
                                     t_commit=t_commit)
        except Exception:
            import logging

            logging.getLogger("gatekeeper_tpu.driver").exception(
                "delta sweep failed for %d rows; rebasing via a full sweep",
                len(rows),
            )
            self._delta_state = None
            return None

    def _apply_delta(self, st, ap, rows, ordered, cp, groups, t0,
                     join_rows: int = 0, join_affected_s: float = 0.0,
                     t_commit: Optional[float] = None):
        import time as _time

        # post-commit join tables: the [C, d] dispatch evaluates the
        # churned rows AND the affected key-group readers against the
        # UPDATED global aggregate (ops/joinkernel.py 'tables' mode);
        # built inside the `join_commit` stage _try_delta opened
        jt = self._join_delta_tables()
        jtail = (jt,) if jt is not None else ()
        # the audit clock's stages (obs/trace.py): slice (host gather of
        # the dirty rows from every column), enqueue (implicit upload +
        # launch), device_wait, fetch, apply
        clock = obstrace.running_clock(obstrace.PATH_AUDIT)
        t1 = clock.mark("slice")
        join_commit_s = 0.0 if t_commit is None else t1 - t_commit
        # ONE dispatch: the fused evaluation on the dirty-row slice AND the
        # gather of the same rows' before-columns from the resident
        # full-sweep mask; one [C, 2d] int8 fetch
        width = 8
        while width < len(rows):
            width *= 2
        rows_pad = np.asarray(rows + [rows[-1]] * (width - len(rows)), np.int32)
        rv_slice = {k: a[rows_pad] for k, a in ap.rp.items()}
        cols_slice = {
            ck: {leaf: a[rows_pad] for leaf, a in leaves.items()}
            for ck, leaves in ap.cols.items()
        }
        group_params = [p for *_s, p in groups]
        mesh = self._mesh()
        cs_d, gp_d = self._constraint_device_side(
            cp.arrays, group_params, None, mesh
        )
        cs_uploaded = self._cs_uploaded
        t_enq = clock.mark("enqueue")
        # [C_total, 2d] from the device; crow folds pad rows out so the
        # incremental state stays per ordered constraint
        if mesh is not None:
            delta_fn = self._delta_dispatch_fn(mesh)
            mask_in = st.mask_src.get()
            both_dev = self._guarded_mesh_dispatch(
                mesh,
                lambda: delta_fn(
                    mask_in, rows_pad, rv_slice, cs_d, cols_slice, gp_d,
                    *jtail
                ),
                enter=False,
            )
        else:
            both_dev = self._delta_dispatch_fn(mesh)(
                st.mask_src.get(), rows_pad, rv_slice, cs_d, cols_slice,
                gp_d, *jtail
            )
        both_dev.copy_to_host_async()  # see compute_masks
        t_wait = clock.mark("device_wait")
        both_dev = jax.block_until_ready(both_dev)
        t_fetch = clock.mark("fetch")
        both = np.asarray(both_dev).astype(bool)[st.crow]
        fetch_bytes = both.nbytes
        base_old, dmask = both[:, :width], both[:, width:]
        t2 = clock.mark("apply")
        for j, r in enumerate(rows):
            # rows dirtied since the base sweep carry their current column
            # in the state cache; the device gather serves the rest
            old = st.old_column(r)
            if old is None:
                old = base_old[:, j]
            st.apply_row(r, old, dmask[:, j])
        st.store_epoch = self.store.epoch
        # device_ms + fetch_ms is the interval slice -> fetched (what
        # device_ms alone was before the fetch had a reading of its own)
        self.last_sweep_stats = {
            "full": 0.0,
            # the join stages are read apart (below): not pack's
            "pack_ms": (t1 - t0 - join_affected_s - join_commit_s) * 1e3,
            "pack_rows": float(ap.take_packed_rows()),
            "device_ms": (t_fetch - t1) * 1e3,
            "fetch_ms": (t2 - t_fetch) * 1e3,
            "slice_ms": (t_enq - t1) * 1e3,
            "cs_upload_arrays": float(cs_uploaded),
            "enqueue_ms": (t_wait - t_enq) * 1e3,
            "device_wait_ms": (t_fetch - t_wait) * 1e3,
            "apply_ms": (_time.perf_counter() - t2) * 1e3,
            "fetch_bytes": float(fetch_bytes),
            "delta_rows": float(len(rows)),
            "rows": float(ap.n_rows),
            "cells": float(len(ordered) * len(rows)),
            "shards": 1.0 if mesh is None else float(mesh.devices.size),
        }
        if jt is not None:
            # key-group locality: how many of the dispatched rows were
            # affected readers rather than content churn (the quantity
            # tools/check_join_parity.py pins to the exact group size)
            self.last_sweep_stats["join_affected_rows"] = float(join_rows)
            self.last_sweep_stats["join_plans"] = float(len(jt))
            self.last_sweep_stats["join_affected_ms"] = join_affected_s * 1e3
            self.last_sweep_stats["join_commit_ms"] = join_commit_s * 1e3
        if mesh is not None:
            # churn locality: the dirty rows' slabs are the only shards
            # whose resident state the next full placement must touch
            from ..parallel.mesh import owning_shards

            self.last_sweep_stats["delta_shards"] = float(
                len(owning_shards(rows, ap.capacity, mesh.devices.size))
            )
        return ap.reviews, ordered, st

    def audit_capped(self, cap: int, tracing: bool = False):
        """Cap-aware end-to-end audit: the status write-back keeps at most
        `cap` violations per constraint (--constraint-violations-limit,
        reference manager.go:49).

        Steady state is INCREMENTAL: only rows whose packed content changed
        since the last sweep are re-evaluated on device ([C, d] delta), and
        the per-constraint counts + first-K candidate lists are maintained
        host-side (ops/deltasweep.py) — per-sweep cost is O(churn), not
        O(cluster), on the single-device path AND under the mesh (the
        delta dispatch runs against the shard-resident base mask).  The
        first sweep (and any sweep after a template or
        layout change, or with too much churn) is a FULL
        device sweep whose on-device reduction ships only [C] counts +
        [C, K] candidate indices to the host (never the [C, R] mask).
        When capped rendering needs candidates beyond the known horizon it
        fetches that one constraint's mask row (base state fresh) or falls
        back to one full sweep (NeedsFullSweep).

        Returns (results, totals, trace) with totals
        {(kind, name): (count, how)}: "exact" when the count equals the
        reference's totalViolations semantics — every candidate rendered,
        or the cap was hit but the program is provably count-exact
        (_count_exact); "resources" when the cap cut rendering short and
        the count is device-candidate resources, an over-approximation."""
        if cap is None or cap <= 0:
            return InterpDriver.audit_capped(self, cap or 0, tracing=tracing)
        if not self.breaker.allow():
            return InterpDriver.audit_capped(self, cap, tracing=tracing)
        self.last_sweep_stats = {}  # stale stats must not decide `cached`
        try:
            out = self._audit_capped_device(cap, tracing)
        except Exception as e:
            from .joinkernel import JoinDivergence

            if isinstance(e, JoinDivergence):
                # armed join-parity assertion: surface it (see audit())
                raise
            self._record_device_failure(e)
            log.warning(
                "device capped audit failed (%s: %s); serving from the "
                "interpreter tier", type(e).__name__, e,
            )
            return InterpDriver.audit_capped(self, cap, tracing=tracing)
        # see audit(): only a sweep that actually dispatched counts as a
        # breaker success (cache-served and empty-inventory sweeps don't)
        stats = self.last_sweep_stats
        if stats and not stats.get("cached"):
            self.breaker.record_success()
        return out

    def _audit_capped_device(self, cap: int, tracing: bool = False):
        from .deltasweep import NeedsFullSweep

        self._wait_ready_for_audit()
        with self._lock:
            K = self._audit_topk(cap)
            trace: List[str] = [] if tracing else None
            for _attempt in (0, 1):
                # gklint: disable=blocking-under-lock -- same audit
                # exclusive-device-ownership contract as _audit_device
                # above; the delta dispatch always fetched under the lock
                got = self._try_delta(K)
                if got is None:
                    # gklint: disable=blocking-under-lock -- same audit
                    # exclusive-device-ownership contract as
                    # _audit_device above (watchdog-bounded)
                    sweep = self._audit_sweep(K)
                    if sweep is None:
                        # same contract as InterpDriver: every registered
                        # constraint reports an exact zero on an empty
                        # inventory
                        empty = {
                            (kind, cname): (0, "exact")
                            for kind in self.constraints
                            for cname in self.constraints[kind]
                        }
                        return [], empty, (
                            "\n".join(trace) if tracing else None
                        )
                    got = (self._audit_pack.reviews, sweep[1],
                           self._delta_state)
                try:
                    return self._render_capped(
                        got[0], got[1], got[2], cap, trace
                    )
                except NeedsFullSweep:
                    # the state's known candidates ran out while unknown
                    # ones exist and the base mask is stale: rebase
                    self._delta_state = None
                    self._audit_cache = None
            raise AssertionError("fresh full sweep cannot need another")

    def _render_capped(self, reviews, ordered, st, cap, trace):
        """Render up to `cap` violations per constraint from the
        incremental state's candidate lists (identical for a
        fresh-from-full-sweep state and a delta-updated one).

        Per-constraint result reuse (st.render_cache, one RenderEntry per
        constraint): an entry is keyed on what the walk READ — the cap,
        the candidate rows it visited in order up to the one at which
        the cap was reached, their pack row generations, and whether a
        further candidate stood behind the cap.  The kept slice is a
        pure function of those (for a template that reads no inventory,
        or whose every inventory read is a join plan: the join index
        bumps the reader rows' generations), so a hit replays the
        identical Result objects.  The candidate count n_cand is NOT
        part of the key: churn anywhere in the cluster moves it for
        almost every constraint while the walked prefix stands, and it
        decides only the reported total, which a hit recomputes from the
        current count (_capped_total).  Only a walk that consumed every
        candidate also keys on the count: a candidate appended after its
        last row would extend it.  Render cost is O(churn inside the
        walked prefixes), not O(constraints whose count moved)."""
        from .deltasweep import NeedsFullSweep, RenderEntry, row_gens

        import time as _time

        clock = obstrace.running_clock(obstrace.PATH_AUDIT)
        t0 = clock.mark("render")
        ap = self._audit_pack
        if self._render_memo_epoch != self._cs_epoch:
            self._render_memo.clear()
            self._render_memo_epoch = self._cs_epoch
        reuse = st.render_cache if trace is None else {}
        new_cache: Dict[Tuple, RenderEntry] = {}
        inventory = self._inventory_for_render()
        rowviews: Dict[int, object] = {}
        results: List[Result] = []
        totals: Dict[Tuple[str, str], Tuple[int, str]] = {}
        R = len(reviews)
        rendered_cells = 0
        render_reused = 0
        fallback_rows = 0
        fallback_bytes = 0
        tiers0 = dict(self._tier_counts)
        cost_on = obscosts.enabled()
        cost_entries: List[Tuple] = []

        def render(ri, kind, name, constraint, uses_inv, action,
                   join_strict=False, inv=None):
            violations = self._memo_cell(
                kind, name, ri, constraint, reviews[ri], rowviews,
                inventory if inv is None else inv, uses_inv,
                ap.row_gen[ri],
            )
            if join_strict and not violations:
                # an exact join plan flagged this cell but the oracle
                # renders nothing: interned-key/aggregate divergence
                # (counted always; raises under GK_JOIN_ASSERT=1), with
                # the documented gv-twin corner filtered out
                self._note_join_false_positive(kind, name, int(ri))
            for v in violations:
                results.append(
                    Result(
                        msg=str(v.get("msg", "")),
                        metadata={"details": v.get("details", {})},
                        constraint=constraint,
                        review=reviews[ri],
                        enforcement_action=action,
                    )
                )
                if trace is not None:
                    trace.append(f"violation {kind}/{name}: {v.get('msg')}")

        def candidates(ci, n_cand):
            """Known candidate rows ascending; beyond the horizon, fetch
            the constraint's mask row when the base mask is still fresh
            (no delta applied), else escalate to a full sweep."""
            nonlocal fallback_rows, fallback_bytes
            lst = st.cand[ci]
            yield from lst
            if st.horizon[ci] is None or n_cand <= len(lst):
                return
            if st.row_cols:
                raise NeedsFullSweep(ci)
            row = np.asarray(st.mask_src.get()[int(st.crow[ci])])[:R]
            fallback_rows += 1
            fallback_bytes += row.nbytes
            full = [int(x) for x in np.nonzero(row)[0]]
            st.cand[ci] = full  # complete knowledge for future sweeps
            st.horizon[ci] = None
            for ri in full[len(lst):]:
                yield ri

        # ONE pruned join inventory per kind, shared by its constraints
        # (the full-sweep path's _inv_for argument: a provider SUPERSET
        # is equivalence-safe, so the union of the kind's KNOWN
        # candidate rows serves every constraint) — K same-kind
        # constraints missing the memo in one sweep build one tree, not
        # K.  A cell renders against it only if its row is in the union
        # (render() below): the rows a horizon fetch adds past the known
        # candidates keep the full tree.  Candidate knowledge need not
        # be complete: a capped walk that ends inside the known
        # candidates (the usual case past the cap) never leaves the
        # union, and one cell against the full tree is O(inventory).
        join_union: Dict[str, set] = {}
        for ci, (kind, _name, _c) in enumerate(ordered):
            if int(st.counts[ci]) == 0 or not self._join_safe(kind):
                continue
            tmpl = self.templates.get(kind)
            if tmpl is None or getattr(tmpl.policy, "uses_inventory",
                                       True):
                join_union.setdefault(kind, set()).update(st.cand[ci])
        join_inv_by_kind: Dict[str, object] = {}

        for ci, (kind, name, constraint) in enumerate(ordered):
            ckey = (kind, name)
            n_cand = int(st.counts[ci])
            if n_cand == 0:
                totals[ckey] = (0, "exact")
                continue
            tmpl = self.templates.get(kind)
            uses_inv = (
                True if tmpl is None
                else getattr(tmpl.policy, "uses_inventory", True)
            )
            join_strict = False
            join_inv = None
            join_rows = ()  # the rows join_inv covers
            if uses_inv and self._join_safe(kind):
                # every inventory read is a classified join plan: the
                # join index bumps reader row generations when a key
                # group changes, so rendered results are content-keyed
                # like inventory-free templates — O(churn) rendering
                uses_inv = False
                join_strict = self._join_strict(kind, constraint)
                # grouped interpreter pass (docs/referential.md): every
                # flagged cell of a known candidate row renders against
                # ONE pruned inventory holding the kind's key groups'
                # provider rows — the interp's O(R) per-cell inventory
                # walk becomes O(group).  LAZY: built on the first
                # render MISS, so steady-state memo-hit sweeps never
                # pay it.
                join_rows = join_union.get(kind, ())
                join_inv = join_inv_by_kind.get(kind)
                if join_inv is None:
                    join_inv = self._lazy_join_inventory(
                        kind, sorted(join_rows), inventory,
                    )
                    join_inv_by_kind[kind] = join_inv
            # traced calls and templates that read the whole inventory
            # bypass the reuse
            cacheable = trace is None and not uses_inv
            if cacheable:
                hit = reuse.get(ckey)
                # an entry of any other shape (a snapshot an older
                # process wrote) is a miss: re-rendered and replaced
                if isinstance(hit, RenderEntry) and hit.serves(
                        cap, st.cand[ci], n_cand, ap.row_gen):
                    results.extend(hit.results)
                    totals[ckey] = self._capped_total(
                        kind, constraint, hit.capped, n_cand,
                        len(hit.results),
                    )
                    new_cache[ckey] = hit
                    render_reused += 1
                    if cost_on:
                        # wholesale render-cache reuse: zero cells walked,
                        # one memo hit, the cached violations replayed
                        cost_entries.append((
                            kind, name, 0, "interp", len(hit.results), 1,
                        ))
                    continue
            action = self._enforcement_action(constraint)
            start = len(results)
            r_start = rendered_cells
            capped = False
            walked: List[int] = []
            for ri in candidates(ci, n_cand):
                if len(results) - start >= cap:
                    capped = True
                    break
                walked.append(ri)
                if ri >= R or reviews[ri] is None:
                    continue  # tombstoned row (valid=False on device too)
                render(ri, kind, name, constraint, uses_inv, action,
                       join_strict=join_strict,
                       inv=join_inv if ri in join_rows else None)
                rendered_cells += 1
            totals[ckey] = self._capped_total(
                kind, constraint, capped, n_cand, len(results) - start,
            )
            if cacheable:
                new_cache[ckey] = RenderEntry(
                    cap, tuple(walked), row_gens(ap.row_gen, walked),
                    capped, n_cand, tuple(results[start:]),
                )
            if cost_on:
                plan = self._render_plan_for(kind, name, constraint)
                cost_entries.append((
                    kind, name, rendered_cells - r_start,
                    getattr(plan, "tier", None) or "interp",
                    len(results) - start, 0,
                ))
        if trace is None:
            st.render_cache = new_cache
        tiers = {
            k: self._tier_counts[k] - tiers0.get(k, 0)
            for k in self._tier_counts
        }
        obstrace.record_span(
            "audit.render", t0, _time.perf_counter(),
            stage=obstrace.RENDER, tier="tpu",
            rendered_cells=rendered_cells, render_reused=render_reused,
            plan_static=tiers["static"], plan_slots=tiers["slots"],
            plan_interp=tiers["interp"],
        )
        self.last_sweep_stats.update(
            render_ms=(_time.perf_counter() - t0) * 1e3,
            rendered_cells=float(rendered_cells),
            render_reused=float(render_reused),
            render_plan_cells=float(tiers["static"] + tiers["slots"]),
            render_interp_cells=float(tiers["interp"]),
            fallback_rows=float(fallback_rows),
            fallback_bytes=float(fallback_bytes),
            results=float(len(results)),
        )
        # cap: the capped answer assembled — cost entries here, then the
        # Client's resource rebuild and Responses
        clock.mark("cap")
        if cost_on and cost_entries:
            obscosts.record_render(
                cost_entries, _time.perf_counter() - t0, 0.0
            )
        self._flush_render_counts()
        return results, totals, ("\n".join(trace) if trace is not None else None)
