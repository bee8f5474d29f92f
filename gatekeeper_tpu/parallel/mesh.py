"""Multi-chip scaling: shard the resource axis over a device mesh.

The audit sweep is data-parallel over resources (SURVEY.md section 2.4): the
review-side arrays (leading dim R) shard across the mesh's "data" axis over
ICI, the constraint-side arrays replicate, and the [C, R] masks come back
sharded on R.  XLA inserts any collectives; per-constraint reductions
(violation counts) become psums over the data axis.

Integration model (idiomatic JAX): sharding is decided by INPUT PLACEMENT —
`shard_args` commits the argument trees to the mesh with `jax.device_put`,
and the driver's ONE fused jitted function compiles an SPMD executable from
those committed shardings.  No separate "distributed" code path exists for
the kernels themselves.

This is the framework's distributed backend — the analogue of what the
reference simply lacks (its audit is one goroutine; multi-pod scale-out is
independent re-evaluation, pkg/controller/constraintstatus).
"""

from __future__ import annotations

import os as _os
import queue as _queue
import threading as _threading
import time as _time
from typing import Callable, Optional

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from ..util import join_thread
from ..util.chips import held_chips


class MeshDispatchStall(RuntimeError):
    """A mesh-collective dispatch exceeded the watchdog budget (either the
    gate never freed — a previous dispatch is wedged holding it — or the
    guarded enqueue itself never returned).  The driver treats it as a
    backend failure: trips the breaker and re-shards the sweep narrower
    (docs/failure-modes.md, fleet failure matrix)."""


class DispatchGate:
    """The mesh-collective dispatch serializer, revocable.

    Lock semantics are the original DISPATCH_LOCK's: hold it across every
    collective-bearing enqueue so per-device launch order stays globally
    consistent (an inconsistent interleave deadlocks the AllReduce
    rendezvous — see the PR 6 notes below).  On top of a plain lock it
    adds what the dispatch watchdog needs:

    - ``acquire(timeout)`` returns a token (or None on timeout) so a
      bounded wait can distinguish "busy" from "wedged";
    - ``revoke()`` abandons the current holder: the gate swaps in a fresh
      generation, so after a stuck dispatch is written off, subsequent
      (narrower-topology) dispatches proceed instead of queueing forever
      behind a thread that will never release.  A waiter that was already
      blocked on the OLD generation when it was revoked re-checks the
      generation after acquiring and migrates to the current one — it can
      never end up holding an abandoned lock while a new-generation
      holder dispatches concurrently (that interleave is exactly the
      rendezvous deadlock the gate exists to prevent).  The abandoned
      holder's own eventual release is then harmless.

    Plain ``with DISPATCH_LOCK:`` keeps working (blocking acquire of the
    current generation), so every pre-existing dispatch site is
    unchanged.
    """

    def __init__(self):
        self._mu = _threading.Lock()        # guards the generation swap
        self._lock = _threading.Lock()      # the actual gate
        self._gen = 0
        self._tokens = _threading.local()   # per-thread ctx-manager stack
        self.revocations = 0                # observability (tests, stats)

    def _current(self):
        with self._mu:
            return self._lock, self._gen

    def acquire(self, timeout: Optional[float] = None):
        """-> opaque token for release(), or None when `timeout` elapsed.

        Generation-checked: if a revoke() landed while we waited, the
        lock we just acquired is the ABANDONED one — release it and
        re-acquire the current generation (within the same deadline for
        timed acquires).  Without this, a waiter woken by the wedged
        holder's late release would dispatch its collective under the
        old lock, unserialized against new-generation dispatches."""
        deadline = (
            _time.monotonic() + timeout if timeout is not None else None
        )
        while True:
            lock, gen = self._current()
            if deadline is None:
                got = lock.acquire()
            else:
                remaining = deadline - _time.monotonic()
                got = remaining > 0 and lock.acquire(timeout=remaining)
            if not got:
                return None
            with self._mu:
                if gen == self._gen:
                    return (lock, gen)
            # revoked while we waited: this lock is abandoned — drop it
            # and serialize against the CURRENT generation instead
            lock.release()

    def release(self, token):
        """Idempotent for abandoned holders: releasing a revoked
        generation's lock is safe (nothing acquires it again)."""
        lock, _gen = token
        try:
            lock.release()
        except RuntimeError:
            pass  # already released (defensive; should not happen)

    def revoke(self):
        """Abandon the current holder: fresh lock, new generation."""
        with self._mu:
            self._lock = _threading.Lock()
            self._gen += 1
            self.revocations += 1

    def locked(self) -> bool:
        return self._current()[0].locked()

    def __enter__(self):
        token = self.acquire()
        stack = getattr(self._tokens, "stack", None)
        if stack is None:
            stack = self._tokens.stack = []
        stack.append(token)
        return self

    def __exit__(self, *exc):
        self.release(self._tokens.stack.pop())
        return False


# Process-wide mesh-collective dispatch gate.  Two collective-bearing
# SPMD executables enqueued concurrently from different threads can
# interleave their per-device launch order (A before B on one device,
# B before A on another) and deadlock the cross-device rendezvous —
# observed as a hung AllReduce between the background delta-executable
# warm and a foreground sweep on the virtual CPU mesh, and the same
# hazard exists on any single-process multi-device topology (webhook
# request threads dispatch reviews while the audit thread sweeps).
# Hold it across the enqueue (the jitted call), not the result fetch:
# per-device execution is in-order, so a globally consistent enqueue
# order suffices, and device work still overlaps the host.
DISPATCH_LOCK = DispatchGate()


def device_info() -> dict:
    """Where this process evaluates, as jax reports it (the call
    initialises the backend if nothing has yet): the one description
    /statusz, the replica ready line, bench.py and chip_smoke.py give."""
    devs = jax.devices()
    return {
        "platform": devs[0].platform,
        "device_kind": devs[0].device_kind,
        "count": len(devs),
    }


def chip_info() -> dict:
    """Which chip this process holds, for the replica ready line,
    /statusz and `replica_chip_info`: `chip` is the number of the chip
    device file it has open once the backend is up (the lowest, if it
    holds several; util/chips.py: what it got, not what a launcher
    asked for), and is left out where it holds none (off a TPU): the
    jax device id is not a stand-in, every placed replica calls its
    one device 0."""
    devs = jax.devices()
    held = held_chips()
    return {**({"chip": held[0]} if held else {}),
            "device_kind": devs[0].device_kind}


def audit_mesh(n_devices: Optional[int] = None) -> Mesh:
    devs = jax.devices()
    n = n_devices or len(devs)
    if len(devs) < n:
        raise RuntimeError(f"need {n} devices, have {len(devs)}")
    return Mesh(np.array(devs[:n]), ("data",))


def maybe_audit_mesh() -> Optional[Mesh]:
    """The production mesh: data-parallel over every visible device, or
    None when only one device exists (single-chip fast path)."""
    return audit_mesh() if len(jax.devices()) > 1 else None


def pad_rows(rows: int, multiple: int) -> int:
    """Smallest row count >= rows divisible by the mesh size."""
    return ((rows + multiple - 1) // multiple) * multiple


def virtual_mesh_env(n_devices: int, base: Optional[dict] = None) -> dict:
    """Subprocess environment for an ``n_devices`` virtual CPU mesh — the
    one recipe every bench/tool mesh lane uses: force the CPU platform
    and replace any existing ``xla_force_host_platform_device_count`` XLA
    flag with ours.  Built over ``base`` (default: ``os.environ``); the
    caller's own process is never touched — pass the result to
    ``subprocess``."""
    env = dict(_os.environ if base is None else base)
    env["JAX_PLATFORMS"] = "cpu"
    kept = [f for f in env.get("XLA_FLAGS", "").split()
            if "xla_force_host_platform_device_count" not in f]
    kept.append(f"--xla_force_host_platform_device_count={n_devices}")
    env["XLA_FLAGS"] = " ".join(kept)
    return env


def shardings_for(mesh: Mesh, rows: int, args):
    """Shardings for the fused-fn argument tuple
    (review_arrays, constraint_arrays, cols, group_params): sharding is
    decided BY POSITION — only the review-side trees (args 0 and 2) shard
    their row-major arrays on "data"; the constraint side (args 1 and 3)
    replicates unconditionally, so a constraint-side array whose bucketed
    leading dim coincides with the row bucket can never be mis-sharded."""
    repl = NamedSharding(mesh, P())

    def row_sharded(x):
        if hasattr(x, "shape") and x.ndim >= 1 and x.shape[0] == rows:
            return NamedSharding(mesh, P("data", *([None] * (x.ndim - 1))))
        return repl  # e.g. vocab-sized keyset id tables

    def replicated(_x):
        return repl

    rv, cs, cols, group_params = args
    return (
        jax.tree_util.tree_map(row_sharded, rv),
        jax.tree_util.tree_map(replicated, cs),
        jax.tree_util.tree_map(row_sharded, cols),
        jax.tree_util.tree_map(replicated, group_params),
    )


def replicate_tree(mesh: Mesh, tree):
    """Commit a tree fully replicated onto the mesh (the constraint side —
    cacheable across calls while the constraint-side epoch is unchanged)."""
    repl = NamedSharding(mesh, P())
    return jax.tree_util.tree_map(lambda x: jax.device_put(x, repl), tree)


# Slab size below which pipelined_shard_commit skips the packer thread:
# slicing a few thousand rows costs microseconds, so the 2-deep pipeline
# would only add thread-spawn + queue overhead (admission batches routed
# to the device path land here; the audit's 100k-row placements don't).
PIPELINE_MIN_SLAB_ROWS = 2048


def slab_rows(rows: int, mesh_size: int) -> tuple:
    """(padded row count, rows per shard) for a row axis laid over the
    mesh in contiguous slabs."""
    target = pad_rows(rows, mesh_size)
    return target, target // mesh_size


def owning_shards(rows, capacity: int, mesh_size: int) -> set:
    """The set of shard indices whose contiguous row slab holds any of
    `rows` — the shards a churn batch actually touches (everything else
    keeps its resident slab untouched)."""
    _target, slab = slab_rows(capacity, mesh_size)
    return {int(r) // slab for r in rows}


def _row_blocks(mesh: Mesh, target: int):
    """Authoritative (device, lo, hi) row-slab assignment for P("data")
    over a [target, ...] array, in ascending-row order — derived from the
    sharding's own index map, never assumed from device iteration order."""
    sh = NamedSharding(mesh, P("data"))
    blocks = []
    for dev, idx in sh.addressable_devices_indices_map((target,)).items():
        s = idx[0]
        lo = s.start or 0
        hi = s.stop if s.stop is not None else target
        blocks.append((dev, lo, hi))
    blocks.sort(key=lambda b: b[1])
    return blocks


def _slab_of(x: np.ndarray, lo: int, hi: int) -> np.ndarray:
    """Rows [lo, hi) of a (possibly shorter) row-major host array, zero-
    padded past its end.  The in-range case is a VIEW — the pipeline's
    host cost per slab is the device_put copy, nothing extra.  Zero
    padding is semantically inert: the match kernel ANDs every cell with
    the review-side `valid` flag (ops/matchkernel.py), which pads to
    False, so a padded row can never produce a positive cell."""
    if hi <= x.shape[0]:
        return x[lo:hi]
    live = x[lo: min(hi, x.shape[0])]
    widths = [(0, (hi - lo) - live.shape[0])] + [(0, 0)] * (x.ndim - 1)
    return np.pad(live, widths)


def pipelined_shard_commit(
    mesh: Mesh, rows: int, trees,
    record_shard: Optional[Callable] = None,
):
    """Commit row-major trees to the mesh slab-by-slab with a two-deep
    host-pack / device-commit pipeline: a packer thread slices+pads shard
    i+1's row slab while the main thread's `jax.device_put` of shard i is
    in flight (transfers are asynchronous, so the device DMA of slab i
    also overlaps the packing of i+1).  This replaces the serial
    pad-everything-then-put placement whose Python packing cost sat ahead
    of every dispatch.  Placements whose slabs are at most
    PIPELINE_MIN_SLAB_ROWS rows commit serially (same slabs, same
    telemetry): there the packing cost the pipeline would hide is smaller
    than the thread+queue overhead.

    trees: tuple of pytrees; leaves with leading dim == rows shard on
    "data" in contiguous slabs, everything else (vocab-sized tables)
    replicates.  record_shard(shard, n_rows, pack_t0, pack_t1, commit_t0,
    commit_t1) is invoked on the calling thread per committed shard.
    Returns (placed_trees, padded_rows)."""
    n = mesh.devices.size
    target, _slab = slab_rows(rows, n)
    repl = NamedSharding(mesh, P())
    leaves, treedef = jax.tree_util.tree_flatten(trees)
    row_idx = [
        i for i, x in enumerate(leaves)
        if hasattr(x, "shape") and getattr(x, "ndim", 0) >= 1
        and x.shape[0] == rows
    ]
    row_set = set(row_idx)
    placed = [
        x if i in row_set else jax.device_put(x, repl)
        for i, x in enumerate(leaves)
    ]
    if row_idx:
        row_leaves = [np.asarray(leaves[i]) for i in row_idx]
        blocks = _row_blocks(mesh, target)
        per_shard = [[] for _ in row_leaves]
        _slab_n = target // n
        if _slab_n <= PIPELINE_MIN_SLAB_ROWS:
            # small placement (e.g. an admission batch routed to the
            # device path): the packing cost the pipeline hides is
            # microseconds here, so the thread+queue machinery would be
            # pure overhead — commit serially, same telemetry
            for shard, (dev, lo, hi) in enumerate(blocks):
                pt0 = _time.perf_counter()
                slabs = [_slab_of(x, lo, hi) for x in row_leaves]
                pt1 = ct0 = _time.perf_counter()
                puts = jax.device_put(slabs, dev)  # async transfer
                for li, arr in enumerate(puts):
                    per_shard[li].append(arr)
                ct1 = _time.perf_counter()
                if record_shard is not None:
                    record_shard(shard, hi - lo, pt0, pt1, ct0, ct1)
        else:
            q: _queue.Queue = _queue.Queue(maxsize=1)  # pack i+1 / commit i
            stop = _threading.Event()

            def _put(item) -> bool:
                # bounded put: if the consumer died, its finally sets
                # `stop` and we bail instead of blocking forever on the
                # full queue (which would also stall the consumer's join)
                while not stop.is_set():
                    try:
                        q.put(item, timeout=0.05)
                        return True
                    except _queue.Full:
                        continue
                return False

            def packer():
                try:
                    for shard, (dev, lo, hi) in enumerate(blocks):
                        t0 = _time.perf_counter()
                        slabs = [_slab_of(x, lo, hi) for x in row_leaves]
                        if not _put((shard, dev, lo, hi, slabs,
                                     t0, _time.perf_counter())):
                            return
                    _put(None)
                except BaseException as e:  # surfaced on the consumer side
                    _put(e)

            t = _threading.Thread(target=packer, daemon=True,
                                  name="gk-shard-pack")
            t.start()
            try:
                while True:
                    item = q.get()
                    if item is None:
                        break
                    if isinstance(item, BaseException):
                        raise item
                    shard, dev, lo, hi, slabs, pt0, pt1 = item
                    ct0 = _time.perf_counter()
                    puts = jax.device_put(slabs, dev)  # async transfer
                    for li, arr in enumerate(puts):
                        per_shard[li].append(arr)
                    ct1 = _time.perf_counter()
                    if record_shard is not None:
                        record_shard(shard, hi - lo, pt0, pt1, ct0, ct1)
            finally:
                stop.set()
                join_thread(t, 5.0, "shard packer")
        for li, i in enumerate(row_idx):
            x = row_leaves[li]
            shape = (target,) + x.shape[1:]
            sh = NamedSharding(mesh, P("data", *([None] * (x.ndim - 1))))
            placed[i] = jax.make_array_from_single_device_arrays(
                shape, sh, per_shard[li]
            )
    out = jax.tree_util.tree_unflatten(treedef, placed)
    return out, target


def shard_review_side(mesh: Mesh, rows: int, rv, cols, record_shard=None):
    """Pad the row axis to a mesh multiple and commit the review-side trees
    (the audit path's `rv` and `cols`; a review dispatch's one buffer and
    its extras, ops/reviewbuf.py) with row-major arrays partitioned on
    "data" in contiguous slabs (everything else, e.g. vocab-sized tables,
    replicated) — slab by slab through the double-buffered pipeline
    (pipelined_shard_commit).  Returns (rv, cols, padded_rows)."""
    (rv_p, cols_p), target = pipelined_shard_commit(
        mesh, rows, (rv, cols), record_shard=record_shard
    )
    return rv_p, cols_p, target


def shard_args(mesh: Mesh, rows: int, args):
    """Pad the row axis to a mesh multiple and commit every argument to the
    mesh (row-major review arrays partitioned on "data", everything else
    replicated).  Returns (sharded_args, padded_rows).  Calling the driver's
    fused jit on these committed inputs yields an SPMD executable."""
    rv, cs, cols, group_params = args
    rv_p, cols_p, target = shard_review_side(mesh, rows, rv, cols)
    cs_p, gp_p = replicate_tree(mesh, (cs, group_params))
    return (rv_p, cs_p, cols_p, gp_p), target


def sharded_masks(driver, reviews, mesh: Mesh):
    """compute_masks, sharded over the mesh: the full evaluation step (match
    kernel + all violation-program groups) jitted once over the mesh with
    the resource axis partitioned, in the form a review dispatch takes (the
    review side as one buffer, partitioned on "data").  Returns (ordered,
    mask, autoreject) like TpuDriver.compute_masks (R axis trimmed back to
    the single-device bucket so results compare bit-for-bit)."""
    fn, ordered, buf, extras, cp, group_params, crow = \
        driver._packed_inputs(reviews)
    rows = buf.shape[0]
    buf_p, extras_p, _target = shard_review_side(mesh, rows, buf, extras)
    cs_p, gp_p = replicate_tree(mesh, (cp.arrays, group_params))
    with mesh:
        # the jit machinery's SPMD compile: a serialized executable pins
        # a single-device layout
        packed = fn._jitted(buf_p, extras_p, cs_p, gp_p)
    both = np.unpackbits(np.asarray(packed), axis=1)
    mask, autoreject = driver._split_masks(both, crow, rows)
    return ordered, mask, autoreject


def sharded_violation_counts(driver, reviews, mesh: Mesh):
    """Per-constraint violation counts with the reduction on-device:
    sum over the sharded R axis (an XLA psum over ICI) so only [C] ints
    cross back to the host."""
    fn, ordered, rp, cp, cols, group_params, crow = driver._device_inputs(
        reviews
    )
    rows = len(rp.arrays["valid"])
    args = (rp.arrays, cp.arrays, cols, group_params)
    placed, target = shard_args(mesh, rows, args)
    raw = fn.__wrapped__

    def counted(rv, cs, c, gp):
        mask, autoreject = raw(rv, cs, c, gp)
        return mask.sum(axis=1), autoreject.sum(axis=1)

    sharded = jax.jit(
        counted,
        out_shardings=(NamedSharding(mesh, P()), NamedSharding(mesh, P())),
    )
    with mesh:
        counts, rejects = sharded(*placed)
    return ordered, np.asarray(counts)[crow], np.asarray(rejects)[crow]
