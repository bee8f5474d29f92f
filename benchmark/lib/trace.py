"""From a profiler trace (.xplane.pb) to numbers: seconds in which an
operation ran on each device, per-operation sums, and the idle gaps by
what the host was doing.  Checked on benchmark/lib/testdata/small.xplane.pb
(benchmark/tests/test_trace.py)."""

from __future__ import annotations

import bisect
import glob
import os

OPS_LINES = ("XLA Ops",)
MODULE_LINES = ("XLA Modules",)
NAMED_GAPS = 40
MIN_HOST_SPAN_NS = 100_000


def find_xplane(trace_dir: str):
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return paths[-1] if paths else None


def _events(plane, names):
    for line in plane.lines:
        if line.name in names:
            for e in line.events:
                yield (e.name, float(e.start_ns),
                       float(e.start_ns) + float(e.duration_ns))


def union(intervals: list) -> list:
    """Sorted disjoint [start, stop] covering the same time."""
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def _module_of(modules: list, starts: list, t: float) -> str:
    """The program (an event of the modules line, sorted by start) that
    was running at t."""
    k = bisect.bisect_right(starts, t) - 1
    if k >= 0 and modules[k][1] <= t <= modules[k][2]:
        return modules[k][0].split("(")[0]
    return ""


def start_options():
    """jax.profiler options for a trace small enough to reduce: no
    Python call tracing (it writes tens of MB a second)."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    return opts


def reduce_xspace(profile, window_s: float) -> dict:
    """{"busy_s": mean over devices of the union of op intervals,
    "window_s", "devices", "device_ops": [[name, s]...] summed over
    devices, "idle_gaps": [[what, s]...] on the busiest device,
    "op_seconds": {module/op: s}}.  None when no operation ran on any
    device: an idle trace has nothing to read."""
    dev_planes = [p for p in profile.planes
                  if p.name.startswith("/device:TPU:")
                  or p.name.startswith("/device:GPU:")]
    host_events = []
    for p in profile.planes:
        if p.name.startswith("/host:"):
            for line in p.lines:
                for e in line.events:
                    # only a long span can cover half of a long gap
                    if e.duration_ns >= MIN_HOST_SPAN_NS:
                        host_events.append(
                            (e.name, float(e.start_ns),
                             float(e.start_ns) + float(e.duration_ns)))
    busy, op_seconds, best = [], {}, None
    for p in dev_planes:
        ops = list(_events(p, OPS_LINES))
        modules = sorted(_events(p, MODULE_LINES), key=lambda m: m[1])
        starts = [m[1] for m in modules]
        if not ops:
            ops = modules
        if not ops:
            continue
        for name, a, b in ops:
            # the chip's op events carry their whole HLO line as a name:
            # "%fusion.32 = pred[1024,256]{...} fusion(...)"
            name = name.split(" = ")[0].lstrip("%")
            mod = _module_of(modules, starts, a)
            key = f"{mod}/{name}" if mod and mod != name else name
            op_seconds[key] = op_seconds.get(key, 0.0) + (b - a) / 1e9
        u = union([(a, b) for _n, a, b in ops])
        busy.append(sum(b - a for a, b in u) / 1e9)
        if best is None or busy[-1] > best[0]:
            best = (busy[-1], u, modules)
    if not busy:
        return None
    _b, u, modules = best
    # the longest gaps are named one by one; the many short ones between
    # back-to-back operations are one entry
    raw_gaps = sorted(((a1 - b0, b0, a1) for (_a0, b0), (a1, _b1)
                       in zip(u, u[1:])), reverse=True)
    gaps = {}
    for k, (length, b0, a1) in enumerate(raw_gaps):
        what = (_gap_name(host_events, modules, b0, a1) if k < NAMED_GAPS
                else "other_short_gaps")
        gaps[what] = gaps.get(what, 0.0) + length / 1e9
    span = (u[-1][1] - u[0][0]) / 1e9
    if window_s > span:
        gaps["outside_first_and_last_op"] = window_s - span
    top = lambda d: [[k, v] for k, v in sorted(
        d.items(), key=lambda kv: -kv[1])[:10]]
    return {
        "busy_s": sum(busy) / len(busy), "window_s": window_s,
        "devices": len(busy), "device_ops": top(op_seconds),
        "idle_gaps": top(gaps), "op_seconds": op_seconds,
    }


def _gap_name(host_events, modules, start: float, stop: float) -> str:
    """What the host was doing in an idle gap: the host span that covers
    most of it (the benchmark's own TraceAnnotations and jax's runtime
    spans), else the program that ran last before it."""
    best, best_cover = None, 0.0
    for name, a, b in host_events:
        cover = min(b, stop) - max(a, start)
        if cover > best_cover:
            best, best_cover = name, cover
    if best is not None and best_cover >= 0.5 * (stop - start):
        return "host:" + best.split("(")[0][:48]
    last = ""
    for name, a, b in modules:
        if b <= start:
            last = name.split("(")[0]
    return "after_" + (last or "nothing")


def reduce_dir(trace_dir: str, window_s: float) -> dict:
    path = find_xplane(trace_dir)
    if path is None:
        return None
    from jax.profiler import ProfileData  # reads the file; touches no device

    return reduce_xspace(ProfileData.from_file(path), window_s)
