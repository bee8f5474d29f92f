"""From /debug/routez: the share of the window's routing decisions that
went to a tier (`tier`, counts by `tier|reason` grown over the window),
in percent; or a field of the calibration the run served under
(`calibration`)."""


def read(raw: dict, args: dict):
    if "calibration" in args:
        v = (raw.get("calibration") or {}).get(args["calibration"])
        return float(v) if isinstance(v, (int, float)) else None
    if "before" not in raw:
        return None
    b, a = raw["before"]["routez"]["counts"], raw["after"]["routez"]["counts"]
    grown = {k: n - b.get(k, 0) for k, n in a.items()}
    total = sum(grown.values())
    if total <= 0:
        return None
    hit = sum(n for k, n in grown.items()
              if k.split("|")[0] == args["tier"])
    return 100.0 * hit / total
