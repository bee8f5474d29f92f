"""What a role over several replicas needs and a role over one does
not: the readings of N replicas folded into the one set of surfaces the
metric readers know, and the guarantees only a fleet can break."""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor

from lib import chip, procs


# a series that only grows: a counter, a histogram's sum, count, buckets
CUMULATIVE = ("_total", "_sum", "_count", "_bucket")


def add_pages(pages: dict) -> dict:
    """{replica_id: scraped /metrics page} folded into one page.  A
    cumulative series two replicas both carry
    (process_cpu_seconds_total, a histogram's _sum and _count) is added
    up and becomes the fleet's; one that carries its replica_id stays
    apart, and a reader that sums a name's series (prom_ratio) reads
    the fleet-wide mean either way.  A gauge is never added up
    (brownout_level 3 in four replicas is 3 four times, not 12): it is
    kept once a replica, under that replica's id."""
    out = {}
    for rid, page in pages.items():
        for key, v in page.items():
            name, brace, labels = key.partition("{")
            if name.endswith(CUMULATIVE):
                out[key] = out.get(key, 0.0) + v
            elif 'replica_id="' in labels:
                out[key] = v
            else:
                out[f'{name}{{replica_id="{rid}"'
                    + ("," + labels if brace else "}")] = v
    return out


def add_counts(dicts: list) -> dict:
    """{key: n} dicts added key by key (/debug/routez counts,
    /debug/compilez provenance_mix)."""
    out = {}
    for d in dicts:
        for k, n in (d or {}).items():
            out[k] = out.get(k, 0) + n
    return out


def surfaces(readies: list, door_port: int) -> dict:
    """roles/webhook.py surfaces() over a fleet: every replica scraped
    at once, the pages and the ledgers added up; `by_replica` keeps the
    pages apart."""
    def one(ready):
        return {
            "metrics": procs.scrape(ready["metrics_port"]),
            "routez": procs.get_json(ready["port"],
                                     "/debug/routez?limit=0"),
            "compilez": procs.get_json(ready["port"],
                                       "/debug/compilez?limit=0")}

    with ThreadPoolExecutor(len(readies)) as pool:
        got = list(pool.map(one, readies))
    by_replica = {r["replica_id"]: g["metrics"]
                  for r, g in zip(readies, got)}
    return {
        "replica_metrics": add_pages(by_replica),
        "door_metrics": procs.scrape(door_port),
        "routez": {"counts": add_counts(
            [g["routez"].get("counts") for g in got])},
        "compilez": {"provenance_mix": add_counts(
            [g["compilez"].get("provenance_mix") for g in got])},
        "by_replica": by_replica,
    }


def ok_by_replica(before: dict, after: dict) -> dict:
    """Good answers each backend gave inside the window, from the
    door's frontdoor_requests_total{outcome="ok",backend=...}."""
    out = {}
    for key, v in after.items():
        name, _, labels = key.partition("{")
        if not name.endswith("frontdoor_requests_total") \
                or 'outcome="ok"' not in labels:
            continue
        rid = labels.partition('backend="')[2].partition('"')[0]
        out[rid] = out.get(rid, 0.0) + v - before.get(key, 0.0)
    return out


def share_min(ok: dict, replica_ids: list) -> float:
    """The least-served replica's share of the window's good reviews
    (0.25 = four replicas served evenly; 0 = one served nothing)."""
    total = sum(ok.values())
    if total <= 0:
        return 0.0
    return min(ok.get(rid, 0.0) for rid in replica_ids) / total


def ejected(fleetz: dict) -> int:
    """Backends the door has out of rotation, or had at any time since
    it started (a readmission says so), from /fleetz."""
    return sum(1 for b in fleetz["backends"]
               if b["ejected"] or b["readmissions"])


def compared(readies: list, ok: dict, fleetz: dict) -> dict:
    """The guarantees a fleet adds to roles/webhook.py compared_of:
    each replica on a chip of its own, every replica serving, none
    ejected."""
    ids = [r["replica_id"] for r in readies]
    return {
        "chips_distinct": {"value": len({r.get("chip") for r in readies
                                         if r.get("chip") is not None}),
                           "at_least": len(readies)},
        "replica_share_min": {"value": round(share_min(ok, ids), 4),
                              "at_least": 0.15},
        "replicas_ejected": {"value": ejected(fleetz), "limit": 0},
    }


def gc_full(pauses_by_replica: list, t_open: float, t_close: float) -> dict:
    """chip.pauses_in over every replica's full collections: `ms` is
    the mean a replica (what one replica's process was held for, as the
    one-replica cells read it), `count` the fleet's."""
    per = [chip.pauses_in(p, t_open, t_close) for p in pauses_by_replica]
    return {"count": sum(p["count"] for p in per),
            "ms": sum(p["ms"] for p in per) / len(per),
            "ms_by_replica": [round(p["ms"], 3) for p in per]}
