"""Background XLA compilation for the fused evaluation executable.

Template/constraint mutation bumps the driver's constraint-side epoch and
discards the fused executable; without this module the NEXT review or audit
blocks on re-trace + XLA compile (seconds — reference ingestion budget is
~ms, pkg/controller/constrainttemplate/stats_reporter.go:33-37 buckets
1ms-5s).  SURVEY.md §7 hard-part 3 prescribes the fix implemented here:
serve evaluations from the interpreter oracle (identical semantics — the
device mask is only ever a pruning over-approximation of it) while the
vectorize+jit runs in a background thread, then swap atomically.

Locking contract: the compile thread holds the driver lock only for the
host-side input build (packing, ms); the XLA trace+compile — the seconds —
runs with the lock RELEASED, so interpreter-path evaluations are never
starved.  A storm of N template ingests coalesces: only the latest epoch is
ever compiled.
"""

from __future__ import annotations

import logging
import threading
from typing import Optional

import jax

# A minimal-but-valid AdmissionRequest probe: packing it exercises every
# review-side array and column extractor, so the warmed executable covers
# the smallest row bucket (8) that real micro-batches land in.
_PROBE_REVIEW = {
    "uid": "__gk_probe__",
    "kind": {"group": "", "version": "v1", "kind": "Pod"},
    "name": "__gk_probe__",
    "namespace": "default",
    "operation": "CREATE",
    "userInfo": {"username": "system:gk-probe"},
    "object": {
        "apiVersion": "v1",
        "kind": "Pod",
        "metadata": {
            "name": "__gk_probe__",
            "namespace": "default",
            "labels": {"app": "__gk_probe__"},
        },
        "spec": {"containers": []},
    },
}


class AsyncCompiler:
    """Owns the background compile thread for one TpuDriver.

    ready()      -> the fused executable matches the driver's current epoch
    kick()       -> a mutation happened; (re)start compilation
    wait(t)      -> block until ready (audit path: throughput over latency)
    """

    def __init__(self, driver):
        self._driver = driver
        self._cond = threading.Condition()
        self._ready_epoch = driver._cs_epoch
        self._thread = None
        self._stopped = False

    # -- state ---------------------------------------------------------------

    def ready(self) -> bool:
        return self._ready_epoch == self._driver._cs_epoch

    def kick(self):
        with self._cond:
            if self._stopped:
                return
            if self._thread is None or not self._thread.is_alive():
                self._thread = threading.Thread(
                    target=self._run, name="gk-async-compile", daemon=True
                )
                self._thread.start()
            self._cond.notify_all()

    def wait(self, timeout: Optional[float] = 120.0) -> bool:
        """Block until the fused executable matches the live epoch.
        timeout=None waits indefinitely; the audit path instead uses the
        driver's bounded AUDIT_COMPILE_WAIT_S so pathological epoch churn
        can never wedge the audit loop permanently (driver.py:100-105).  A
        stopped compiler returns False immediately: the sync path is then
        the only one left."""
        import time

        deadline = None if timeout is None else time.monotonic() + timeout
        with self._cond:
            while not self.ready():
                if self._stopped:
                    return False
                if deadline is None:
                    left = 0.05
                else:
                    left = deadline - time.monotonic()
                    if left <= 0:
                        return False
                # bounded wait: the target epoch itself can move under us
                self._cond.wait(min(left, 0.05))
        return True

    def stop(self):
        with self._cond:
            self._stopped = True
            self._cond.notify_all()

    # -- compile loop --------------------------------------------------------

    # Debounce: wait for the epoch to hold still this long before tracing.
    # During a template-ingest storm every mutation bumps the epoch; eagerly
    # compiling each one keeps this thread perpetually TRACING — pure-Python
    # work that holds the GIL and measurably taxes concurrent admission
    # serving (the numpy serving path needs no executable, so there is
    # nothing to gain from compiling mid-storm).  Bounded so sustained
    # churn still compiles at least every DEBOUNCE_MAX_S.
    DEBOUNCE_S = 0.25
    DEBOUNCE_MAX_S = 10.0

    def _run(self):
        import time as _time

        d = self._driver
        while True:
            with self._cond:
                while self.ready() and not self._stopped:
                    self._cond.wait()
                if self._stopped:
                    return
            t0 = _time.monotonic()
            while _time.monotonic() - t0 < self.DEBOUNCE_MAX_S:
                epoch = d._cs_epoch
                with self._cond:
                    if self._stopped:
                        return
                    self._cond.wait(self.DEBOUNCE_S)
                if d._cs_epoch == epoch:
                    break  # settled
            epoch = d._cs_epoch
            try:
                self._compile_epoch(epoch)
            except Exception:
                # fail open: a broken background compile must not wedge
                # evaluation off-device forever — the synchronous path will
                # surface the error on the next direct call.  Logged loudly:
                # a persistently broken compile otherwise stays invisible
                # until it resurfaces as a blocking sync compile (advisor r2)
                logging.getLogger("gatekeeper_tpu.asynccompile").exception(
                    "background XLA compile failed for epoch %d; "
                    "falling open to the synchronous path", epoch,
                )
                with self._cond:
                    if d._cs_epoch == epoch:
                        self._ready_epoch = epoch
                        self._cond.notify_all()

    def epoch_lag(self) -> int:
        """Mutation epochs the compiled executable is behind the live
        constraint side (0 = current) — the compile_epoch_lag gauge's
        source (obs/compilestats.py)."""
        return max(self._driver._cs_epoch - self._ready_epoch, 0)

    def _compile_epoch(self, epoch: int):
        import time as _time

        d = self._driver
        t_start = _time.perf_counter()
        # host-side build under the driver lock (ms): constraint-side pack +
        # probe review pack + column extraction.  The produced arrays are
        # fresh locals (packing always allocates), safe to use un-locked.
        with d._lock:
            if d._cs_epoch != epoch:
                return  # superseded mid-storm; outer loop re-reads
            n_constraints = sum(len(v) for v in d.constraints.values())
            if n_constraints == 0:
                with self._cond:
                    self._ready_epoch = epoch
                    self._cond.notify_all()
                return
            fn, _ordered, buf, extras, cp, group_params, _crow = \
                d._packed_inputs([dict(_PROBE_REVIEW)])
            # the constraint-side cache key the inputs were packed for —
            # read under the lock; _dispatch must not key the device cache
            # on a LATER epoch a concurrent mutation may have created
            cs_key = d._cs_cache.key()
        # XLA trace + compile OUTSIDE the lock — the whole point.  Warm the
        # PACKED variant of the probe's layout, through the packing
        # compute_masks goes through: warming only the unpacked fused fn
        # would leave the first real review to pay the full synchronous
        # compile anyway.
        out = d._dispatch(
            fn, buf, extras, cp.arrays, group_params, cs_key=cs_key,
        )
        jax.block_until_ready(out)
        with self._cond:
            if d._cs_epoch == epoch:
                self._ready_epoch = epoch
                self._cond.notify_all()
        # per-epoch compile telemetry (obs/compilestats.py): the whole
        # warm dispatch's wall time (pack + trace + XLA compile + first
        # dispatch) attributed to this epoch, plus the backlog AFTER it
        # landed — per-executable cold/warm classification is recorded
        # separately by aot_jit inside the dispatch
        from ..metrics.catalog import COMPILE_M, record_stage
        from ..obs import compilestats

        epoch_s = _time.perf_counter() - t_start
        compilestats.record_compile("epoch", epoch_s, "async", epoch=epoch)
        record_stage(COMPILE_M, epoch_s, {"path": "epoch"})
        compilestats.record_epoch_lag(self.epoch_lag())
