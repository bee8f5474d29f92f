#!/usr/bin/env python
"""Placement conformance check (ISSUE 36; wired tier-1 via
tests/test_fleet_chips_tool.py, and the one launcher that runs
fleet/placement.py through the supervisor ON the chips):

A supervised fleet is started on THIS host's chips, one replica a chip.
How many there are is asked of the device files (util/chips.py
host_chips()): the launcher never imports jax, because a parent that
has touched jax holds the chips its children need.  The check asserts:

1. **a chip each** — every replica announces ready holding a chip
   device file of its own (`chip` in the ready line: what it got, not
   what it was asked to take);
2. **a restart returns to its slot's chip** — one replica is SIGKILLed;
   the supervisor respawns it and the new process holds the chip its
   predecessor held, never a neighbour's;
3. **the launcher stayed off jax** — starting the fleet did not bring
   `jax` into this process's modules.

On a host with fewer than two chips there is nothing to place: two CPU
replicas run the same supervised kill and restart with `chips` as the
host says it (0 or 1: placement_env is empty), and their ready lines
carry no `chip`.  The replicas restore no snapshot: an empty replica
opens its backend all the same, which is all a chip check needs.

Run: python tools/check_fleet_chips.py  (exit 0 clean, 1 with findings;
the last line of stdout is the JSON summary).
"""

from __future__ import annotations

import json
import os
import signal
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

RECOVERY_BUDGET_S = 120.0


def run_checks(summary: dict = None) -> list:
    had_jax = "jax" in sys.modules  # a test process has it already
    from gatekeeper_tpu.fleet.supervisor import ReplicaSupervisor
    from gatekeeper_tpu.util.chips import host_chips

    summary = {} if summary is None else summary
    problems: list = []
    chips = host_chips()
    placed = chips > 1
    n = chips if placed else 2
    summary.update(host_chips=chips, replicas=n, placed=placed)
    sup = ReplicaSupervisor(
        env=None if placed else {"JAX_PLATFORMS": "cpu"},
        heartbeat_s=0.25, miss_threshold=2, backoff_base_s=0.1,
        chips=chips,
    )
    try:
        t0 = time.monotonic()
        handles = sup.start(n)
        summary["start_s"] = round(time.monotonic() - t0, 3)
        held = {h.replica_id: h.ready.get("chip") for h in handles}
        summary["chips_held"] = held
        if "jax" in sys.modules and not had_jax:
            problems.append("the launcher imported jax")
        if placed and (None in held.values()
                       or len(set(held.values())) != n):
            problems.append(
                f"{n} replicas on {chips} chips do not hold a chip each: "
                f"{held}")
        if not placed and any(c is not None for c in held.values()):
            problems.append(f"a CPU replica says it holds a chip: {held}")
        if problems:
            return problems

        victim = handles[1]
        rid = victim.replica_id
        os.kill(victim.proc.pid, signal.SIGKILL)
        killed_at = time.monotonic()
        while time.monotonic() < killed_at + RECOVERY_BUDGET_S:
            st = sup.status()[rid]
            if st["state"] == "running" and st["restarts"] >= 1:
                break
            time.sleep(0.1)
        st = sup.status()[rid]
        if st["state"] != "running" or st["restarts"] < 1:
            problems.append(
                f"replica {rid} was not restarted within "
                f"{RECOVERY_BUDGET_S:.0f}s: {st}")
            return problems
        summary["restart_s"] = round(time.monotonic() - killed_at, 3)
        new = next(h for h in sup.handles() if h.replica_id == rid)
        summary["restarted"] = {"replica_id": rid, "index": new.index,
                                "chip": new.ready.get("chip"),
                                "pid_was": victim.proc.pid,
                                "pid": new.proc.pid}
        if new.proc.pid == victim.proc.pid:
            problems.append(f"replica {rid} kept its pid: not restarted")
        if new.index != victim.index:
            problems.append(
                f"replica {rid} restarted at index {new.index}, "
                f"was {victim.index}")
        if new.ready.get("chip") != held[rid]:
            problems.append(
                f"replica {rid} restarted on chip "
                f"{new.ready.get('chip')}, held {held[rid]} before")
        now = {h.replica_id: h.ready.get("chip") for h in sup.handles()}
        if placed and len(set(now.values())) != n:
            problems.append(
                f"after the restart the replicas share a chip: {now}")
    finally:
        sup.stop()
    return problems


def main() -> int:
    summary: dict = {}
    problems = run_checks(summary)
    for p in problems:
        print(f"FAIL: {p}")
    print(json.dumps({"ok": not problems, **summary}))
    return 1 if problems else 0


if __name__ == "__main__":
    sys.exit(main())
