"""The growth over the window of some series of a scraped /metrics
page, over the growth of others (or over the window's good reviews):
means of histograms (sum over count), time per review.

args: surface ("replica_metrics" | "door_metrics"), num and den: lists
of [name suffix, label substring or ""]; den may be "reviews" or
"seconds"; scale; minus: another such ratio's args, subtracted."""


def growth(raw: dict, surface: str, series: list) -> float:
    before, after = raw["before"][surface], raw["after"][surface]
    total = 0.0
    for suffix, label in series:
        for key, v in after.items():
            name, _, labels = key.partition("{")
            if name.endswith(suffix) and label in labels:
                total += v - before.get(key, 0.0)
    return total


def read(raw: dict, args: dict):
    if "before" not in raw or "after" not in raw:
        return None
    num = growth(raw, args["surface"], args["num"])
    den = args["den"]
    if den == "reviews":
        den = raw["window"]["good"]
    elif den == "seconds":
        den = raw["window"]["window_s"]
    else:
        den = growth(raw, args["surface"], den)
    if not den or (not num and not args.get("zero_ok")):
        return None
    return num / den * args.get("scale", 1.0)
