"""The device's idle share of the traced window, in percent: 1 - the
union of the intervals in which an operation ran (mean over the chips
used) over the window's length."""


def read(raw: dict, args: dict):
    tr = raw.get("trace")
    if not tr or not tr.get("window_s") or not tr.get("busy_s"):
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
