"""A percentile (nearest rank) of a sorted list of the window's
samples, or with q "rate" their number over the window's seconds."""

from readers.value import lookup


def read(raw: dict, args: dict):
    xs = lookup(raw, args["list"])
    if not xs:
        return None
    if args["q"] == "rate":
        return len(xs) / raw["window"]["window_s"]
    if args["q"] == "mean":
        return sum(xs) / len(xs)
    k = min(len(xs) - 1, max(0, int(round(args["q"] / 100.0 * len(xs))) - 1))
    return xs[k]
