"""Shared small utilities (reference pkg/util/).

- enforcement-action enum + validation (enforcement_action.go:11-47)
- GVK packing of reconcile requests for type-erased controllers (pack.go:17-57)
- pod identity from env (pod_info.go:5-21)
"""

from __future__ import annotations

import os
from typing import Any, Optional, Tuple

DENY = "deny"
DRYRUN = "dryrun"
UNRECOGNIZED = "unrecognized"

SUPPORTED_ENFORCEMENT_ACTIONS = (DENY, DRYRUN)
KNOWN_ENFORCEMENT_ACTIONS = (DENY, DRYRUN, UNRECOGNIZED)


class EnforcementActionError(ValueError):
    pass


def validate_enforcement_action(action: str) -> None:
    """enforcement_action.go:20-27: only deny/dryrun are supported."""
    if action not in SUPPORTED_ENFORCEMENT_ACTIONS:
        raise EnforcementActionError(
            f"could not find the provided enforcementAction value within the "
            f"supported list {list(SUPPORTED_ENFORCEMENT_ACTIONS)}"
        )


def get_enforcement_action(constraint: dict) -> str:
    """enforcement_action.go:29-46: default deny; anything unsupported is
    classified as 'unrecognized' (never an error)."""
    spec = constraint.get("spec")
    if not isinstance(spec, dict):
        spec = {}
    action = spec.get("enforcementAction") or DENY
    if not isinstance(action, str):
        return UNRECOGNIZED
    if action not in SUPPORTED_ENFORCEMENT_ACTIONS:
        return UNRECOGNIZED
    return action


# ---- request packing (pack.go) -------------------------------------------
#
# Dynamic (type-erased) controllers receive events for many GVKs over one
# queue; the GVK rides inside the request name as "gvk:Kind.Version.Group:Name".


def pack_request(gvk: Tuple[str, str, str], name: str, namespace: str = "") -> Tuple[str, str]:
    """EventPacker.Map (pack.go:33-57) -> (packed_name, namespace)."""
    group, version, kind = gvk
    version = version or "v1"
    encoded = f"{kind}.{version}.{group}"
    return f"gvk:{encoded}:{name}", namespace


def unpack_request(packed_name: str, namespace: str = ""):
    """UnpackRequest (pack.go:17-31) -> (gvk, name, namespace)."""
    fields = packed_name.split(":", 2)
    if len(fields) != 3 or fields[0] != "gvk":
        raise ValueError(f"invalid packed name: {packed_name}")
    parts = fields[1].split(".", 2)
    if len(parts) != 3:
        raise ValueError(f"unable to parse gvk: {fields[1]}")
    kind, version, group = parts
    return (group, version, kind), fields[2], namespace


# ---- pod identity (pod_info.go) ------------------------------------------


def get_pod_name() -> str:
    return os.environ.get("POD_NAME", "")


def get_id() -> str:
    return get_pod_name()


def get_namespace() -> str:
    return os.environ.get("POD_NAMESPACE", "gatekeeper-system")


# ---- fleet replica identity (docs/fleet.md) -------------------------------
#
# One process = one serving replica.  The id is stamped into root spans,
# the replica-labelled metrics series, the SLO engine's /statusz payload
# and every "started" log line, so a fleet's telemetry separates by
# replica without relying on scrape-time instance labels.  Empty means
# "not part of a fleet" (single-process deployments stay label-free).

_replica_id: Optional[str] = None


def set_replica_id(rid: str) -> None:
    global _replica_id
    _replica_id = str(rid or "")


def replica_id() -> str:
    """The process's fleet replica id: --replica-id, else $GK_REPLICA_ID,
    else empty."""
    if _replica_id is not None:
        return _replica_id
    return os.environ.get("GK_REPLICA_ID", "")


def join_thread(thread, timeout: float, what: str = "") -> bool:
    """Bounded join with a post-join liveness check: returns True when
    the thread actually exited, False (and logs a warning naming it) when
    it is still alive after `timeout` — the caller proceeds with shutdown
    instead of hanging behind a wedged worker (the PR 8 wedge class; the
    static twin of this rule is gklint's `bare-join`).  None threads are
    trivially 'joined'."""
    if thread is None:
        return True
    thread.join(timeout=timeout)
    if thread.is_alive():
        import logging

        logging.getLogger("gatekeeper.util").warning(
            "thread %s still alive %.1fs after join%s — proceeding with "
            "shutdown; it is daemonized and cannot pin exit",
            thread.name, timeout, f" ({what})" if what else "",
        )
        return False
    return True


def close_listener(server, thread) -> None:
    """Tear down a socketserver-based listener for an idempotent
    ``start()``: ``shutdown()`` only when its serve_forever thread
    actually runs (on a loop that never started it would block forever),
    then close the socket.  Callers null their own references afterwards
    — a double ``start()`` replaces the previous listener instead of
    leaking its thread and socket (the WebhookServer / MetricsExporter
    contract; used by HealthServer and ProfileServer)."""
    if server is None:
        return
    if thread is not None and thread.is_alive():
        server.shutdown()
    server.server_close()


def nested_get(obj: Any, *path: str, default: Any = None) -> Any:
    """unstructured.Nested* analogue: walk dict path, default on miss."""
    node = obj
    for seg in path:
        if not isinstance(node, dict) or seg not in node:
            return default
        node = node[seg]
    return node
