"""A number the harness took itself, by its dotted path in the run's
raw readings, times `scale`."""


def lookup(raw, path: str):
    cur = raw
    for part in path.split("."):
        if not isinstance(cur, dict) or part not in cur:
            return None
        cur = cur[part]
    return cur


def read(raw: dict, args: dict):
    v = lookup(raw, args["path"])
    if not isinstance(v, (int, float)):
        return None
    if "over" in args:
        d = lookup(raw, args["over"])
        if not d:
            return None
        v = v / d
    return v * args.get("scale", 1.0)
