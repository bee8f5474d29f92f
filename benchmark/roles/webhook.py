"""The webhook role: one `--operation webhook` replica that restored the
sealed snapshot an audit-role process wrote, behind one EventFrontDoor
speaking GKW1; the window drives POST /v1/admit on the door's port.

Four processes besides this one, on disjoint CPU sets: the load
generator (plain sockets, nothing of the program), the door, the
replica (holds the chip alone), and before them the audit-role child
that writes the snapshot.  This process hosts no hot loop and never
touches jax.
"""

from __future__ import annotations

import json
import os
import socket
import threading
import time

from lib import chip, corpus, loadgen, procs, reference

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TRACE_MAX_S = 5.0
CPU_WEIGHTS = {"replica": 7, "door": 2, "gen": 2, "harness": 2}


class Lines:
    """The stdout lines of a child, read on a thread, waited for by
    substring."""

    def __init__(self, proc, what: str, log_path: str):
        self.proc, self.what, self.log_path = proc, what, log_path
        self.lines, self.cv = [], threading.Condition()
        threading.Thread(target=self._read, daemon=True).start()

    def _read(self):
        for line in self.proc.stdout:
            with self.cv:
                self.lines.append(line)
                self.cv.notify_all()
        with self.cv:
            self.lines.append(None)
            self.cv.notify_all()

    def wait(self, needle: str, timeout_s: float) -> str:
        deadline = time.monotonic() + timeout_s
        seen = 0
        with self.cv:
            while True:
                for line in self.lines[seen:]:
                    if line is None:
                        raise procs.BenchFailure(
                            f"{self.what} ended (rc={self.proc.poll()}) "
                            f"before {needle!r}; log tail:\n"
                            + procs.log_tail(self.log_path),
                            self.proc.poll())
                    if needle in line:
                        return line
                seen = len(self.lines)
                left = deadline - time.monotonic()
                if left <= 0:
                    raise procs.BenchFailure(
                        f"{self.what}: no {needle!r} in {timeout_s:.0f}s; "
                        "log tail:\n" + procs.log_tail(self.log_path))
                self.cv.wait(left)


def spawn(ctx, what: str, cmd: list, cpus, env=None) -> tuple:
    """A child driven over its stdin and stdout."""
    log_path = os.path.join(ctx["work"], what + ".log")
    proc = ctx["procs"].popen(cmd, log_path, procs.child_env(env),
                              cpus=cpus, pipes=True)
    return proc, Lines(proc, what, log_path)


def tell(proc, line: str):
    proc.stdin.write(line + "\n")
    proc.stdin.flush()


def control(port: int, line: str, timeout_s: float = 120.0) -> dict:
    with socket.create_connection(("127.0.0.1", port),
                                  timeout=timeout_s) as s:
        s.sendall((line + "\n").encode())
        out = json.loads(s.makefile("r").readline())
    if not out.get("ok"):
        raise procs.BenchFailure(f"replica control {line!r}: {out}")
    return out


def gen_spec(ctx) -> dict:
    tr, cfg = ctx["traffic"], ctx["config"]
    per_s = tr.get("rate_per_s") or tr["bodies_per_s"]
    spec = dict(tr)
    spec.update({
        "seed": ctx["seed"], "tag": f"bench-{ctx['seed']}",
        "violating_share": tr.get("violating_share",
                                  cfg["violating_share"]),
        "timeout_s": cfg["timeout_s"],
        "bodies": int(tr["warm_reviews"] + sum(tr.get("warm_bursts", ()))
                      + per_s * (ctx["seconds"] + 3)) + 1024,
        "out": os.path.join(ctx["work"], "gen_result.json"),
    })
    return spec


def surfaces(replica_port: int, metrics_port: int, door_port: int) -> dict:
    return {
        "replica_metrics": procs.scrape(metrics_port),
        "door_metrics": procs.scrape(door_port),
        "routez": procs.get_json(replica_port, "/debug/routez?limit=0"),
        "compilez": procs.get_json(replica_port, "/debug/compilez?limit=0"),
    }


def read_answers(path: str, n: int) -> list:
    out = []
    with open(path, "rb") as f:
        for _ in range(n):
            k = int.from_bytes(f.read(4), "big")
            out.append(f.read(k))
    return out


def compare_window(constraints, bodies, rows, answers, t_open, t_close,
                   timeout_s, closed_loop,
                   families=corpus.FAMILIES) -> dict:
    """Every review of the window held to the plain reference.  The
    window's reviews: those due in it (open loop) or answered in it
    (closed loop).  An answer is judged by what it says, however late
    it came: one that never came, or that says anything but the
    reference's verdict and messages (a shed and a refusal too: the
    configuration fails closed and the traffic stays below what makes
    it shed), is `wrong` or `unanswered` and decides `correct`.  One
    that came after the caller's timeout is `late`: the API server has
    given up on it, so it counts in `failed` and misses every latency,
    but it is not wrong."""
    policies = reference.Policies(constraints, families)
    lat_ms, late_ms, faults = [], [], []
    per_s = [0] * int(t_close - t_open)  # good answers in each second
    attempted = wrong = unanswered = late = 0
    for (i, due, sent, done, status), data in zip(rows, answers):
        if closed_loop:
            if done and not t_open <= done <= t_close:
                continue  # answered before the window or after its close
        elif not t_open <= due < t_close:
            continue
        attempted += 1
        if not done:
            unanswered += 1
            continue
        why = reference.compare_verdict(policies, bodies[i], status, data)
        if why is not None:
            wrong += 1
            if len(faults) < 3:
                faults.append(why)
            continue
        if done - due > timeout_s:
            late += 1
            continue
        lat_ms.append((done - due) * 1e3)
        late_ms.append((sent - due) * 1e3)
        sec = int(done - t_open)
        if 0 <= sec < len(per_s):
            per_s[sec] += 1
    return {"attempted": attempted, "wrong": wrong, "unanswered": unanswered,
            "late": late, "lat_ms": sorted(lat_ms),
            "late_ms": sorted(late_ms), "per_s": per_s, "faults": faults}


def gauges(before: dict, after: dict) -> dict:
    """What the replica's gauges and compile ledger said when the window
    closed (for repeat.py: a run that wandered shows here first)."""
    keep = ("brownout_level", "webhook_batch_target_size",
            "webhook_batch_deadline_ms", "webhook_offered_load_rps",
            "route_decisions_total", "frontdoor_requests_total")
    out = {k: v for surface in ("replica_metrics", "door_metrics")
           for k, v in after[surface].items() if any(s in k for s in keep)}
    mix_b = before["compilez"].get("provenance_mix") or {}
    mix_a = after["compilez"].get("provenance_mix") or {}
    out["compiled_in_window"] = {k: n - mix_b.get(k, 0)
                                 for k, n in mix_a.items()
                                 if n != mix_b.get(k, 0)}
    return out


def compared_of(chk: dict, min_reviews: int) -> dict:
    return {
        "verdicts_wrong": {"value": chk["wrong"], "limit": 0},
        "reviews_unanswered": {"value": chk["unanswered"], "limit": 0},
        "reviews_compared": {"value": chk["attempted"] - chk["unanswered"],
                             "at_least": min_reviews},
    }


def run(ctx: dict) -> dict:
    from roles import audit  # the snapshot comes from the audit role's child

    work, cfg, tr = ctx["work"], ctx["config"], ctx["traffic"]
    cpus = procs.cpu_sets(CPU_WEIGHTS)
    if cpus["harness"]:
        os.sched_setaffinity(0, cpus["harness"])
    t = {}

    # 0. the generator builds its bodies while the cluster comes up
    gspec = gen_spec(ctx)
    gen_spec_path = os.path.join(work, "gen_spec.json")
    procs.write_json(gen_spec_path, gspec)
    gproc, glines = spawn(
        ctx, "loadgen", procs.python(
            os.path.join(HERE, "lib", "loadgen.py"), gen_spec_path),
        cpus["gen"])
    snap_dir = os.path.join(work, "snapshot")
    os.makedirs(snap_dir)

    # 1. the audit-role process writes the sealed snapshot
    t0 = time.monotonic()
    spec = os.path.join(work, "snapshot_spec.json")
    result = os.path.join(work, "snapshot_result.json")
    procs.write_json(spec, {
        "config": cfg, "seed": ctx["seed"], "platform": ctx["platform"],
        "mode": "snapshot", "snapshot_dir": snap_dir, "result": result})
    log = os.path.join(work, "snapshot_child.log")
    proc = ctx["procs"].popen(
        procs.python(audit.__file__, "--child", spec), log,
        procs.child_env(), cpus=cpus["replica"])
    procs.wait_child(proc, "the snapshot-writing audit child", log,
                     ctx["timeout_s"])
    snap = procs.read_json(result)
    t["snapshot_s"] = time.monotonic() - t0

    # 2. the replica restores it and holds the chip alone
    t0 = time.monotonic()
    flags = ["--replica-id", "r0", "--snapshot-dir", snap_dir,
             "--driver", "tpu"]
    rproc, rlines = spawn(
        ctx, "replica", procs.python(
            os.path.join(HERE, "lib", "replica.py"), ctx["platform"], *flags),
        cpus["replica"], env={"GK_REPLICA_LOG_LEVEL": "WARNING"})
    ctl = json.loads(rlines.wait('"control"', 120))
    ready = json.loads(rlines.wait('"ready"', ctx["timeout_s"]))
    t["replica_ready_s"] = time.monotonic() - t0
    if ready.get("restore_outcome") != "restored":
        raise procs.BenchFailure(f"the replica came up cold: {ready}")
    dev = ready.get("device") or {}
    device = {"platform": dev.get("platform"),
              "kind": dev.get("device_kind"), "count": dev.get("count")}

    # 3. the door, a process of its own
    dproc, dlines = spawn(
        ctx, "door", procs.python(
            os.path.join(HERE, "lib", "door.py"), str(ready["wire_port"]),
            str(ready["port"]), "r0"), cpus["door"])
    door_port = json.loads(dlines.wait('"door"', 60))["port"]

    # 4. send nothing until the router has priced its tiers: every run
    # prices its own (three samples), so the reading is kept
    t0 = time.monotonic()
    cal = procs.poll(
        "a routing calibration in /debug/routez", 300,
        lambda: procs.get_json(
            ready["port"], "/debug/routez?limit=0")["calibration"],
        proc=rproc, log_path=os.path.join(work, "replica.log"))
    t["calibration_wait_s"] = time.monotonic() - t0
    glines.wait("built", 300)

    # 5. warm-up: the window's own load, until warm_reviews are answered
    t0 = time.monotonic()
    tell(gproc, f"start {door_port}")
    glines.wait("warmed", 600)
    t["warm_s"] = time.monotonic() - t0
    args = (ready["port"], ready["metrics_port"], door_port)
    before = surfaces(*args)
    setup_s = time.time() - ctx["t_start"]

    # 6. the window.  A traced run traces its last TRACE_MAX_S seconds and
    # stops the profiler once the window has closed and every answer is in:
    # writing the trace out holds the replica for seconds, and with
    # arrivals still coming that made it shed (PERF.md, Findings PR 26)
    tell(gproc, f"open {ctx['seconds']}")
    reduced = None
    if ctx["trace"]:
        time.sleep(max(0.0, ctx["seconds"] - TRACE_MAX_S))
        control(ctl["port"], "trace_start " + os.path.join(work, "trace"))
    glines.wait("closed", ctx["seconds"] + cfg["timeout_s"] + 120)
    if ctx["trace"]:
        reduced = control(ctl["port"], "trace_stop", 300)["trace"]
    after = surfaces(*args)
    peak = control(ctl["port"], "memstats")["memory_peak_bytes"]
    pauses = control(ctl["port"], "gc_full")["pauses"]
    gen = procs.read_json(gspec["out"])
    answers = read_answers(gspec["out"] + ".answers", gen["sent"])

    # 7. free the program's state, then the reference
    for p in (gproc, dproc, rproc):
        procs.Procs.stop(p)
    t0 = time.monotonic()
    _templates, constraints = corpus.make_templates(
        cfg["templates"], corpus.seed32(ctx["seed"], 0))
    bodies = loadgen.build_bodies(gspec)
    chk = compare_window(
        constraints, bodies, gen["rows"], answers, gen["t_open"],
        gen["t_close"], cfg["timeout_s"], tr["kind"] == "closed")
    t["reference_s"] = time.monotonic() - t0
    window_s = gen["t_close"] - gen["t_open"]
    good = len(chk["lat_ms"])
    return {
        "device": dict(device, memory_peak_bytes=peak),
        "setup_s": setup_s, "timings": t, "snapshot": snap,
        "calibration": cal, "ready": ready,
        "window": {"window_s": window_s, "good": good,
                   "lat_ms": chk["lat_ms"], "late_ms": chk["late_ms"],
                   "per_s": chk["per_s"], "late": chk["late"],
                   "gen_max_gap_ms": gen.get("max_gap_ms"),
                   # the replica's clock and the generator's are both
                   # time.monotonic() of one host
                   "gc_full": chip.pauses_in(pauses, gen["t_open"],
                                             gen["t_close"]),
                   "compilez_before": before["compilez"],
                   "compilez_after": after["compilez"]},
        "before": before, "after": after, "trace": reduced,
        "gauges": gauges(before, after),
        "attempted": chk["attempted"],
        "failed": chk["wrong"] + chk["unanswered"] + chk["late"],
        "compared": compared_of(chk, tr.get("min_reviews", 1)),
        "notes": chk["faults"]
        + ([f"{chk['late']} answers came after the caller's timeout"]
           if chk["late"] else [])
        + (["the generator ran out of bodies"]
           if gen["bodies_left"] <= 0 else []),
    }
