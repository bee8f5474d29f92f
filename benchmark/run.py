#!/usr/bin/env python3
"""One run of one cell of BENCHMARK.json:

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

A fresh process sets the cell up from the seed, warms every shape the
window uses, measures for --seconds, holds what the timed path answered
to the plain reference, and prints one JSON line last on stdout.  With
--trace 0 the line's metrics are the cell's end-to-end metrics, with
--trace 1 its per-layer metrics.  Without a TPU, or without the
repository beside it, it exits non-zero and prints no result.

Everything that belongs to one configuration, one traffic mix, one role
or one metric is a file found by name (benchmark/README.md); this file
knows none of them.  This process never imports jax.
"""

from __future__ import annotations

import argparse
import importlib.util
import json
import os
import shutil
import sys
import time

T_START = time.time()
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)

from lib import procs  # noqa: E402


def load_module(kind: str, name: str):
    path = os.path.join(HERE, kind, name + ".py")
    spec = importlib.util.spec_from_file_location(f"{kind}.{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_cell(workload: str) -> dict:
    """The cell's manifest entries and files, resolved by name."""
    manifest = procs.read_json(os.path.join(ROOT, "BENCHMARK.json"))
    cells = {w["name"]: w for w in manifest["workloads"]}
    if workload not in cells:
        raise SystemExit(f"benchmark: no workload {workload!r} in "
                         f"BENCHMARK.json (has {sorted(cells)})")
    cell = cells[workload]
    cfg_entry = {c["name"]: c for c in manifest["configs"]}[cell["config"]]
    return {
        "manifest": manifest, "cell": cell,
        "config": procs.read_json(os.path.join(ROOT, cfg_entry["file"])),
        "traffic": procs.read_json(os.path.join(
            HERE, "traffic", cell["traffic"] + ".json")),
    }


def cell_metrics(manifest: dict, workload: str, group: str) -> list:
    """The manifest's metrics of `group` that this cell reports."""
    e2e = {m["name"]: m for m in manifest["end_to_end"]}

    def reports(m):
        return "workloads" not in m or workload in m["workloads"]

    if group == "end_to_end":
        return [m for m in manifest["end_to_end"] if reports(m)]
    return [m for m in manifest["per_layer"]
            if reports(m) and reports(e2e[m["moves"]])]


def read_metrics(raw: dict, metrics: list) -> dict:
    """Each metric through its reader (benchmark/metrics/<name>.json
    names it); a reader that finds nothing to read returns None and the
    metric is left out of the line."""
    out = {}
    for m in metrics:
        spec = procs.read_json(os.path.join(
            HERE, "metrics", m["name"] + ".json"))
        value = load_module("readers", spec["reader"]).read(
            raw, spec.get("args") or {})
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def result_line(raw: dict, metrics: dict, trace: bool) -> dict:
    """The contract's line: `compared` (each number beside its limit)
    comes last."""
    compared = raw["compared"]
    correct = all(c["value"] <= c["limit"] if "limit" in c
                  else c["value"] >= c["at_least"]
                  for c in compared.values())
    device = dict(raw["device"])
    line = {"correct": bool(correct), "attempted": int(raw["attempted"]),
            "failed": int(raw["failed"]), "metrics": metrics}
    if trace and raw.get("trace"):
        device["busy_s"] = raw["trace"]["busy_s"]
        device["window_s"] = raw["trace"]["window_s"]
        line["breakdown"] = {k: raw["trace"][k]
                             for k in ("device_ops", "idle_gaps")}
    line["device"] = device
    line["compared"] = compared
    return line


def emit(line: dict, notes: list = ()):
    for n in notes:
        print("benchmark: " + n, file=sys.stderr)
    for name, c in line["compared"].items():
        lim = (f"limit {c['limit']}" if "limit" in c
               else f"at least {c['at_least']}")
        print(f"benchmark: compared {name} = {c['value']} ({lim})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if not os.path.isdir(os.path.join(ROOT, "gatekeeper_tpu")):
        print("benchmark: gatekeeper_tpu/ is not beside benchmark/ — the "
              "benchmark drives the repository and cannot run without it",
              file=sys.stderr)
        return 2
    c = load_cell(args.workload)
    work = os.path.join(ROOT, ".benchmark-work", args.workload)
    shutil.rmtree(work, ignore_errors=True)
    os.makedirs(work)
    pr = procs.Procs()
    ctx = {
        "config": c["config"], "traffic": c["traffic"], "seed": args.seed,
        "seconds": args.seconds, "trace": bool(args.trace),
        "platform": "tpu", "t_start": T_START,
        "work": work, "procs": pr, "timeout_s": 1100.0,
    }
    try:
        raw = load_module("roles", c["config"]["role"]).run(ctx)
    except procs.BenchFailure as e:
        print(f"benchmark: {e.args[0]}", file=sys.stderr)
        return e.args[1] if len(e.args) > 1 and e.args[1] else 1
    finally:
        pr.stop_all()
        shutil.rmtree(os.path.join(work, "snapshot"), ignore_errors=True)
    if (raw["device"]["platform"] != "tpu"
            or raw["device"]["count"] < c["cell"]["chips"]):
        print(f"benchmark: ran on {raw['device']}, the cell needs "
              f"{c['cell']['chips']} TPU chip(s)", file=sys.stderr)
        return 3
    both = {g: read_metrics(raw, cell_metrics(c["manifest"], args.workload, g))
            for g in ("end_to_end", "per_layer")}
    line = result_line(raw, both["per_layer" if args.trace else "end_to_end"],
                       bool(args.trace))
    # for benchmark/repeat.py: both groups of every run, and what it
    # served under (the driver reads only the line below)
    procs.write_json(os.path.join(work, "last_run.json"), {
        "seed": args.seed, "line": line, "metrics": both,
        "timings": raw.get("timings"), "calibration": raw.get("calibration"),
        # the window's small readings (not its samples and scrapes)
        "window": {k: v for k, v in raw["window"].items()
                   if len(json.dumps(v)) < 4000},
        "gauges": raw.get("gauges")})
    emit(line, raw.get("notes", ()))
    assert "jax" not in sys.modules, "the harness parent imported jax"
    return 0


if __name__ == "__main__":
    sys.exit(main())
