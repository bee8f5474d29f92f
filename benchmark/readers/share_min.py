"""The least-served backend's share of the window's good reviews, in
percent (25 = four replicas served evenly): the growth over the window
of the door's frontdoor_requests_total{outcome="ok",backend=...} per
backend (lib/fleet.py), the least over the sum.  A backend the role
names (`window.replica_ids`) that served nothing reads 0."""

from lib import fleet


def read(raw: dict, args: dict):
    if "before" not in raw or "after" not in raw:
        return None
    ok = fleet.ok_by_replica(raw["before"]["door_metrics"],
                             raw["after"]["door_metrics"])
    if sum(ok.values()) <= 0:
        return None
    ids = (raw.get("window") or {}).get("replica_ids") or list(ok)
    return 100.0 * fleet.share_min(ok, ids)
