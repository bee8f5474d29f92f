"""The mean over a list of per-step readings (dicts) of the sum of
some of their keys: sweep stages from the driver's last_sweep_stats,
the benchmark's own laps."""

from readers.value import lookup


def read(raw: dict, args: dict):
    rows = lookup(raw, args["list"])
    if not rows:
        return None
    vals = [sum(r[k] for k in args["keys"]) for r in rows
            if all(k in r for k in args["keys"])]
    if not vals:
        return None
    return sum(vals) / len(vals) * args.get("scale", 1.0)
