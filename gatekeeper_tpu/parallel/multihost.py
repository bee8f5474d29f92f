"""Multi-host distributed audit: the resource axis sharded across hosts
over DCN and across each host's chips over ICI.

This is the framework's answer to SURVEY §5.8 ("a distributed communication
backend … scales to multi-host the way the reference's NCCL/MPI backend
does" — the reference itself has none; its multi-pod story is independent
re-evaluation, pkg/controller/constraintstatus).  Design:

- every pod replicates the inventory (the store is derived state, rebuilt
  from the API server — same model as single-host), so no host ever needs
  another host's rows to PACK; sharding is purely a device-placement
  decision
- `jax.distributed.initialize` wires the processes; the global mesh lays
  the row axis over (host, local-device): contiguous row blocks live on one
  host's chips, so the fused sweep's only cross-host traffic is the final
  [C, 1+K] reduction (an all-reduce/all-gather of KBs over DCN) — the
  [C, R] intermediates never cross hosts
- inputs are built with `jax.make_array_from_callback`: each process
  materializes exactly its addressable row shards from its local (full)
  host arrays; the constraint side replicates
- outputs come back fully replicated, so every pod can render and write
  status for the constraints it owns

Validated without hardware by tests/test_multihost.py: two real OS
processes, four virtual CPU devices each, one 8-device global mesh, with
bit-parity against the single-process sweep.
"""

from __future__ import annotations

from typing import Optional

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def init_distributed(coordinator: str, num_processes: int,
                     process_id: int) -> None:
    """Join the process group (idempotent).  coordinator: "host:port" of
    process 0 — the DCN control plane (jax.distributed uses gRPC; the data
    plane is XLA collectives).  Must run before ANY backend-touching JAX
    call, so idempotency is detected from the error, not jax state."""
    try:
        jax.distributed.initialize(
            coordinator_address=coordinator,
            num_processes=num_processes,
            process_id=process_id,
        )
    except RuntimeError as e:
        if "already initialized" not in str(e):
            raise


def multihost_audit_mesh() -> Mesh:
    """Global 2D mesh (host, data): row blocks are contiguous per host so
    the sweep's heavy traffic stays on ICI; only reductions ride DCN."""
    procs = jax.process_count()
    devs = sorted(jax.devices(), key=lambda d: (d.process_index, d.id))
    per_host = len(devs) // procs
    grid = np.array(devs).reshape(procs, per_host)
    return Mesh(grid, ("host", "data"))


def _row_sharding(mesh: Mesh, ndim: int) -> NamedSharding:
    # rows partitioned over BOTH mesh axes (host-major, then local device)
    return NamedSharding(mesh, P(("host", "data"), *([None] * (ndim - 1))))


def shard_rows_global(mesh: Mesh, rows: int, tree):
    """Commit a host-local tree as GLOBAL arrays: row-major leaves
    partitioned over (host, data), everything else replicated.  Every
    process holds the full host arrays (replicated store), so the callback
    just slices — each process materializes only its addressable shards."""
    n = mesh.devices.size
    target = ((rows + n - 1) // n) * n

    def place(x):
        x = np.asarray(x)
        if x.ndim >= 1 and x.shape[0] == rows:
            if target != rows:
                pad = [(0, target - rows)] + [(0, 0)] * (x.ndim - 1)
                x = np.pad(x, pad)
            sh = _row_sharding(mesh, x.ndim)
            return jax.make_array_from_callback(
                x.shape, sh, lambda idx, x=x: x[idx]
            )
        sh = NamedSharding(mesh, P())
        return jax.make_array_from_callback(
            x.shape, sh, lambda idx, x=x: x[idx]
        )

    return jax.tree_util.tree_map(place, tree), target


def multihost_capped_sweep(driver, K: int):
    """The full capped-audit device sweep over the multi-host mesh, built
    with shard_map: every shard evaluates ONLY its contiguous row slab and
    reduces it locally to [C, 1+K] (counts + first-K candidates translated
    to global row indices); an all_gather of those KB-scale reductions —
    the only DCN data-plane traffic — replicates them to every host, and
    the host-side merge (ops/driver._merge_sharded_packed) produces the
    global capped result.  Letting GSPMD partition a naive replicated-out
    jit instead all-gathers the [C, R] mask for the order-dependent top-k,
    making every shard re-reduce the full row axis (the r4 verdict's
    sharded-overhead finding).  -> (ordered, counts [C], topk [C, K])."""
    import jax.numpy as jnp

    from ..ops.driver import _merge_sharded_packed

    fn, ordered, cp, group_params, crow = driver._audit_inputs(K)
    if getattr(driver, "_active_join_plans", lambda: ())():
        # referential join plans take a trailing `joins` runtime arg and
        # (in trace mode) an all_gather over the in-process mesh axis;
        # the multi-host lane has not grown that plumbing — fail loudly
        # rather than sweep with a silently mis-shaped executable
        raise NotImplementedError(
            "referential join plans are not supported on the multi-host "
            "audit lane (docs/referential.md)"
        )
    ap = driver._audit_pack
    if ap.n_rows == 0:
        return [], None, None
    mesh = multihost_audit_mesh()
    (rv_g, cols_g), _target = shard_rows_global(
        mesh, ap.capacity, (ap.rp, ap.cols)
    )
    (cs_g, gp_g), _t2 = shard_rows_global(mesh, -1, (cp.arrays, group_params))
    # jit cached on the driver per (constraint epoch, K, mesh shape): a
    # fresh lambda per call would re-trace + recompile the fused kernel
    # every sweep (advisor r3)
    key = (driver._cs_epoch, K, tuple(sorted(mesh.shape.items())))
    cached = getattr(driver, "_multihost_jit", None)
    if cached is not None and cached[0] == key:
        sharded = cached[1]
    else:
        raw = fn.__wrapped__  # fused_audit: already packed-only, local rows

        def body(rv, cs, c, gp):
            packed = raw(rv, cs, c, gp)  # [C, 1+K'], local row indices
            rows_local = rv["valid"].shape[0]
            shard = jax.lax.axis_index(("host", "data"))
            idx = packed[:, 1:]
            idx = jnp.where(idx >= 0, idx + shard * rows_local, -1)
            packed = jnp.concatenate([packed[:, :1], idx], axis=1)
            # [N, C, 1+K'] replicated: the KB-scale DCN crossing
            return jax.lax.all_gather(packed, ("host", "data"))

        def row_spec(a):
            return P(("host", "data"), *([None] * (a.ndim - 1)))

        repl = P()
        in_specs = (
            jax.tree_util.tree_map(lambda a: row_spec(a), rv_g),
            jax.tree_util.tree_map(lambda a: repl, cs_g),
            jax.tree_util.tree_map(lambda a: row_spec(a), cols_g),
            jax.tree_util.tree_map(lambda a: repl, gp_g),
        )
        sharded = jax.jit(jax.shard_map(
            body, mesh=mesh, in_specs=in_specs, out_specs=repl,
            check_vma=False,
        ))
        driver._multihost_jit = (key, sharded)
    with mesh:
        allp = sharded(rv_g, cs_g, cols_g, gp_g)
    allp = np.asarray(allp.addressable_data(0))  # replicated [N, C, 1+K']
    # crow folds group-major pad rows out (driver._constraint_side);
    # merge back to the single-device width K
    packed = _merge_sharded_packed(allp, K)[crow]
    return ordered, packed[:, 0].astype(np.int64), packed[:, 1:]
