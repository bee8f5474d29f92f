"""The replica's host time per review outside the engine, in ms, in a
saturated closed loop: the window's seconds per good review (the
replica is the bottleneck, so that is its wall time per review) less
the engine's dispatch time per review."""

from readers import prom_ratio


def read(raw: dict, args: dict):
    good = raw["window"]["good"]
    dispatch = prom_ratio.read(raw, args["dispatch"])
    if not good or dispatch is None:
        return None
    return raw["window"]["window_s"] / good * 1e3 - dispatch
