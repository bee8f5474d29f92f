#!/usr/bin/env python3
"""The builder's instrument for the bounds: one cell, N times, each in
a fresh process, and the spread over the N.

    python3 benchmark/repeat.py --workload <cell> --runs 6 --seconds 30 [--seed0 N] [--same-seed]

Prints one line per run (end-to-end metrics, the routing calibration it
served under, compiles in the window, how late the generator ran), then
per metric the median, the spread (distance between the first and third
quartile of statistics.quantiles(n=4), as a share of the median) and the
bound the contract's rule gives (five times the spread, never under
1%).  The first run of a checkout compiles: its set-up is shown apart.
The driver never calls this.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
SHOW = ("route_cal_rtt_ms", "route_cal_device_cells_per_ms",
        "compiles_in_window", "gen_late_p99_ms", "batch_size_mean",
        "route_device_share", "replica_gc_full", "sweep_gc_full",
        "dispatch_ms_per_review", "admit_tail")


def spread(xs: list) -> float:
    q = statistics.quantiles(xs, n=4)
    return (q[2] - q[0]) / statistics.median(xs)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--runs", type=int, default=6)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed0", type=int, default=2_500_000_011)
    ap.add_argument("--same-seed", action="store_true",
                    help="every run with seed0 (default: seed0, seed0+1, ...)")
    ap.add_argument("--out", default="",
                    help="also write every run's reading to this JSON file")
    args = ap.parse_args(argv)
    runs = []
    for i in range(args.runs):
        seed = args.seed0 + (0 if args.same_seed else i)
        p = subprocess.run(
            [sys.executable, os.path.join(HERE, "run.py"), "--workload",
             args.workload, "--seed", str(seed), "--seconds",
             str(args.seconds), "--trace", "0"],
            cwd=ROOT, capture_output=True, text=True)
        if p.returncode != 0:
            print(f"run {i} seed {seed}: rc={p.returncode}\n"
                  + p.stderr[-2000:], flush=True)
            continue
        with open(os.path.join(ROOT, ".benchmark-work", args.workload,
                               "last_run.json")) as f:
            last = json.load(f)
        e2e = {k: v["value"] for k, v in last["metrics"]["end_to_end"].items()}
        layer = {k: v["value"] for k, v in
                 last["metrics"]["per_layer"].items() if k.startswith(SHOW)}
        runs.append({"seed": seed, "correct": last["line"]["correct"],
                     "attempted": last["line"]["attempted"],
                     "failed": last["line"]["failed"], "end_to_end": e2e,
                     "per_layer": layer, "timings": last["timings"],
                     "window": last.get("window"),
                     "gauges": last.get("gauges")})
        print(f"run {i} seed {seed} correct={last['line']['correct']} "
              f"attempted={last['line']['attempted']} "
              f"failed={last['line']['failed']} "
              + " ".join(f"{k}={v:.6g}" for k, v in e2e.items()) + " | "
              + " ".join(f"{k}={v:.6g}" for k, v in layer.items())
              + f" | gc_full={(last.get('window') or {}).get('gc_full')}"
              + f" per_s={(last.get('window') or {}).get('per_s')}"
              + f" gen_max_gap_ms={(last.get('window') or {}).get('gen_max_gap_ms')}"
              + f" gauges={json.dumps(last.get('gauges'))[:600]}",
              flush=True)
    if args.out:
        with open(args.out, "w") as f:
            json.dump(runs, f, indent=1)
    if len(runs) < 3:
        print("fewer than three runs: no spread")
        return 1
    for name in runs[0]["end_to_end"]:
        xs = [r["end_to_end"][name] for r in runs]
        note = ""
        if name == "setup_s":
            note = f" (first run {xs[0]:.6g} shown apart)"
            xs = xs[1:]
        if len(xs) < 2:
            continue
        s = spread(xs)
        print(f"{name}: median {statistics.median(xs):.6g} min {min(xs):.6g} "
              f"max {max(xs):.6g} spread {100 * s:.3g}% -> bound by the rule "
              f"{max(0.01, 5 * s):.3g}{note}")
    return 0 if all(r["correct"] for r in runs) else 1


if __name__ == "__main__":
    sys.exit(main())
