"""The webhook Deployment scaled out on one host: `replicas` `--operation
webhook` replicas, each on a chip of its own, all restored from the one
sealed snapshot an audit-role process wrote, behind ONE EventFrontDoor
speaking GKW1 to each; the window drives POST /v1/admit on the door's
port and the roster's `least_inflight` chooses a replica per review.

Generator, snapshot child, replica launcher (lib/replica.py: one control
port a replica), window and comparison are roles/webhook.py's; what is
this role's own:

- **placement** comes from the program: replica i starts under
  `gatekeeper_tpu.fleet.placement.placement_env(i, chips)`.  A program
  without that module cannot give a replica its chip (every replica
  would open all four, and the second to start fails or waits on the
  first), so the run ends there, before any process is started, with
  exit code 4;
- the door is lib/fleet_door.py (several backends, the roster's own
  probe interval);
- the shape ladder: the traffic's `warm_bursts`, each times `replicas`,
  sent through the door until every replica holds an executable for
  every row bucket the bursts reach (how the door deals a burst and how
  a batcher cuts it are their own affair, so a pass may miss one), or a
  pass adds none;
- each replica's /metrics is scraped once after the ladder, before the
  generator's warm-up, so that each stands at brownout level 3 when the
  window opens, as every webhook window of this benchmark does;
- `replica_metrics`, routez and compilez are the replicas' added up
  (lib/fleet.py), `device` says `count` = the replicas' devices and the
  `chips` they hold, a traced run traces every replica and reports the
  busiest chip's;
- `compared` gains what only a fleet can break: `chips_distinct`,
  `replica_share_min`, `replicas_ejected`.

run(ctx) is the parent side (never touches jax); with --control this
file is the comparison's control at the cell's own size:

    python3 benchmark/roles/webhook_fleet.py --control <cell> <seed,...> [reviews]
"""

from __future__ import annotations

import json
import os
import sys
import time
from concurrent.futures import ThreadPoolExecutor

if __name__ == "__main__":  # benchmark/ on the path first
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

from lib import corpus, fleet, loadgen, procs, reference  # noqa: E402
from roles import webhook  # noqa: E402
from roles.webhook_inventory import executables, series  # noqa: E402

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
MAX_LADDER_PASSES = 4
PIN_WAIT_S = 8.0


def placement_of_the_program():
    """The program's own placement_env, or the reason it has none."""
    if procs.ROOT not in sys.path:
        sys.path.insert(0, procs.ROOT)
    try:
        from gatekeeper_tpu.fleet.placement import placement_env
    except ImportError as e:
        raise procs.BenchFailure(
            "the program cannot run this configuration: it has no "
            f"gatekeeper_tpu.fleet.placement ({e}), so nothing can give a "
            "replica a chip of its own: four replicas would open all four "
            "chips each, and the second to start fails or waits on the "
            "first", 4)
    return placement_env


def cpu_split(cfg: dict) -> dict:
    """Disjoint CPU sets: one a replica, the door, the generator, the
    harness, by the configuration's weights."""
    w = cfg["cpu_weights"]
    parts = {f"r{i}": w["replica"] for i in range(cfg["replicas"])}
    parts.update({k: w[k] for k in ("door", "gen", "harness")})
    return procs.cpu_sets(parts)


def warm_ladder(ctx, door_port: int, readies: list, cpus) -> dict:
    """Nothing compiles inside the window: a batch is padded to a power
    of two of rows and each such bucket is an executable of its own, in
    every replica.  Each pass sends every burst of `warm_bursts`, times
    the number of replicas, through the door (the roster deals it out),
    until every replica has gained one executable per bucket since the
    first pass began (a restore loads some ahead of need; which ones is
    the cache's affair) or a pass adds none to any replica, at most
    MAX_LADDER_PASSES times."""
    tr, n = ctx["traffic"], len(readies)
    bursts = [b * n for b in tr["warm_bursts"]]
    want = len({max(8, 1 << (b - 1).bit_length())
                for b in tr["warm_bursts"]})
    base = have = [executables(r["port"]) for r in readies]
    passes, idle = 0, False
    while passes < MAX_LADDER_PASSES and not idle and (
            passes == 0 or min(h - b for h, b in zip(have, base)) < want):
        spec = dict(webhook.gen_spec(ctx), **{
            "kind": "open", "connections": 1, "warm_reviews": 0,
            "warm_bursts": bursts, "bodies": sum(bursts),
            "tag": f"ladder{passes}-{ctx['seed']}",
            "out": os.path.join(ctx["work"], f"ladder_{passes}.json")})
        path = os.path.join(ctx["work"], f"ladder_spec_{passes}.json")
        procs.write_json(path, spec)
        gproc, glines = webhook.spawn(
            ctx, f"ladder{passes}", procs.python(
                os.path.join(HERE, "lib", "loadgen.py"), path), cpus)
        glines.wait("built", 300)
        webhook.tell(gproc, f"start {door_port}")
        glines.wait("warmed", 900)
        procs.Procs.stop(gproc)
        got = [executables(r["port"]) for r in readies]
        idle = sum(got) == sum(have)
        have, passes = got, passes + 1
    return {"ladder_passes": passes, "ladder_executables": have,
            "ladder_executables_restored": base,
            "ladder_executables_wanted": want}


def await_pin(readies: list) -> list:
    """Scrape every replica's /metrics once a second, for at most
    PIN_WAIT_S, until each reads brownout level 3 (a replica evaluates
    its burn alerts when it is scraped and at no other time; after the
    ladder's first-contact reviews the admission-latency alert fires at
    the first scrape and the ladder climbs a rung a second:
    roles/webhook_inventory.py await_pin) -> the levels read last."""
    deadline = time.monotonic() + PIN_WAIT_S
    while True:
        levels = [series(procs.scrape(r["metrics_port"]),
                         "brownout_level", "") for r in readies]
        if min(levels) >= 3 or time.monotonic() >= deadline:
            return levels
        time.sleep(1.0)


def control_all(ctls: list, line: str, timeout_s: float = 300.0) -> list:
    """One control command to every replica at once."""
    with ThreadPoolExecutor(len(ctls)) as pool:
        return list(pool.map(
            lambda c: webhook.control(c["port"], line, timeout_s), ctls))


def bring_up(ctx: dict, t: dict) -> dict:
    """Snapshot child, the replicas on their chips, the door over them,
    every shape in every replica and every replica pinned: all that
    comes before a generator's own warm-up.  Timings go into `t`."""
    from roles import audit  # the snapshot comes from the audit role's child

    placement_env = placement_of_the_program()
    work, cfg = ctx["work"], ctx["config"]
    n = cfg["replicas"]
    chips = n * cfg["chips_per_replica"]
    ids = [f"r{i}" for i in range(n)]
    cpus = cpu_split(cfg)
    if cpus["harness"]:
        os.sched_setaffinity(0, cpus["harness"])
    replica_cpus = None if cpus["r0"] is None else set().union(
        *(cpus[rid] for rid in ids))
    t["cpu_split"] = {k: (sorted(v) if v else None) for k, v in cpus.items()}
    snap_dir = os.path.join(work, "snapshot")
    os.makedirs(snap_dir)

    # 1. the audit-role process writes the sealed snapshot, on chip 0
    # alone (with every chip open it would sweep on the mesh path)
    t0 = time.monotonic()
    spec = os.path.join(work, "snapshot_spec.json")
    result = os.path.join(work, "snapshot_result.json")
    procs.write_json(spec, {
        "config": cfg, "seed": ctx["seed"], "platform": ctx["platform"],
        "mode": "snapshot", "snapshot_dir": snap_dir, "result": result})
    log = os.path.join(work, "snapshot_child.log")
    proc = ctx["procs"].popen(
        procs.python(audit.__file__, "--child", spec), log,
        procs.child_env(placement_env(0, chips)), cpus=replica_cpus)
    procs.wait_child(proc, "the snapshot-writing audit child", log,
                     ctx["timeout_s"])
    snap = procs.read_json(result)
    t["snapshot_s"] = time.monotonic() - t0

    # 2. the replicas restore it, all at once, each on its own chip
    t0 = time.monotonic()
    started = []
    for i, rid in enumerate(ids):
        flags = ["--replica-id", rid, "--snapshot-dir", snap_dir,
                 "--driver", "tpu"]
        started.append(webhook.spawn(
            ctx, f"replica_{rid}", procs.python(
                os.path.join(HERE, "lib", "replica.py"), ctx["platform"],
                *flags), cpus[rid],
            env=dict(placement_env(i, chips),
                     GK_REPLICA_LOG_LEVEL="WARNING")))
    ctls = [json.loads(lines.wait('"control"', 180))
            for _p, lines in started]
    readies = [json.loads(lines.wait('"ready"', ctx["timeout_s"]))
               for _p, lines in started]
    t["replica_ready_s"] = time.monotonic() - t0
    t["replica_ready_each_s"] = [r["ready_s"] for r in readies]
    cold = [r["replica_id"] for r in readies
            if r.get("restore_outcome") != "restored"]
    if cold:
        raise procs.BenchFailure(f"replicas came up cold: {cold}")
    devs = [r.get("device") or {} for r in readies]
    device = {"platform": devs[0].get("platform"),
              "kind": devs[0].get("device_kind"),
              "count": sum(d.get("count", 0) for d in devs),
              "chips": [r.get("chip") for r in readies]}

    # 3. the door over all of them, a process of its own
    dproc, dlines = webhook.spawn(
        ctx, "door", procs.python(
            os.path.join(HERE, "lib", "fleet_door.py"), cfg["balance"],
            *(f"{r['wire_port']}:{r['port']}:{r['replica_id']}"
              for r in readies)), cpus["door"])
    door_port = json.loads(dlines.wait('"door"', 60))["port"]

    # 4. nothing is sent until every router has priced its tiers
    t0 = time.monotonic()
    cal = {}
    for (rproc, _l), r in zip(started, readies):
        cal[r["replica_id"]] = procs.poll(
            "a routing calibration in /debug/routez", 300,
            lambda r=r: procs.get_json(
                r["port"], "/debug/routez?limit=0")["calibration"],
            proc=rproc, log_path=os.path.join(
                work, f"replica_{r['replica_id']}.log"))
    t["calibration_wait_s"] = time.monotonic() - t0

    # 5. every shape in every replica, then each pinned to the device
    t0 = time.monotonic()
    t.update(warm_ladder(ctx, door_port, readies, cpus["gen"]))
    t["ladder_s"] = time.monotonic() - t0
    t["brownout_after_ladder"] = await_pin(readies)
    return {"ids": ids, "cpus": cpus, "snap": snap, "cal": cal,
            "replicas": [p for p, _l in started], "ctls": ctls,
            "readies": readies, "device": device, "door": dproc,
            "door_port": door_port}


def run(ctx: dict) -> dict:
    placement_of_the_program()  # or end here, before any process starts
    work, cfg, tr = ctx["work"], ctx["config"], ctx["traffic"]
    t = {}

    # 0. the generator builds its bodies while the cluster comes up
    gspec = webhook.gen_spec(ctx)
    gen_spec_path = os.path.join(work, "gen_spec.json")
    procs.write_json(gen_spec_path, gspec)
    gproc, glines = webhook.spawn(
        ctx, "loadgen", procs.python(
            os.path.join(HERE, "lib", "loadgen.py"), gen_spec_path),
        cpu_split(cfg)["gen"])

    # 1.-5. snapshot, replicas, door, calibration, ladder, pin
    up = bring_up(ctx, t)
    ids, ctls, readies = up["ids"], up["ctls"], up["readies"]
    door_port = up["door_port"]

    # 6. the generator's own warm-up until warm_reviews are answered
    glines.wait("built", 300)
    t0 = time.monotonic()
    webhook.tell(gproc, f"start {door_port}")
    glines.wait("warmed", 600)
    t["warm_s"] = time.monotonic() - t0
    before = fleet.surfaces(readies, door_port)
    t["brownout_at_open"] = [series(p, "brownout_level", "")
                             for p in before["by_replica"].values()]
    setup_s = time.time() - ctx["t_start"]

    # 7. the window (a traced run traces the last TRACE_MAX_S seconds of
    # every replica and stops the profilers once every answer is in:
    # roles/webhook.py)
    webhook.tell(gproc, f"open {ctx['seconds']}")
    traces = None
    if ctx["trace"]:
        time.sleep(max(0.0, ctx["seconds"] - webhook.TRACE_MAX_S))
        for c, rid in zip(ctls, ids):
            webhook.control(c["port"], "trace_start "
                            + os.path.join(work, f"trace_{rid}"))
    glines.wait("closed", ctx["seconds"] + cfg["timeout_s"] + 120)
    if ctx["trace"]:
        traces = [o["trace"] for o in control_all(ctls, "trace_stop")]
    after = fleet.surfaces(readies, door_port)
    fleetz = procs.get_json(door_port, "/fleetz")
    peak = max(o["memory_peak_bytes"] for o in control_all(ctls, "memstats"))
    pauses = [o["pauses"] for o in control_all(ctls, "gc_full")]
    gen = procs.read_json(gspec["out"])
    answers = webhook.read_answers(gspec["out"] + ".answers", gen["sent"])

    # 8. free the program's state, then the reference
    for p in [gproc, up["door"]] + up["replicas"]:
        procs.Procs.stop(p)
    t0 = time.monotonic()
    _templates, constraints = corpus.make_templates(
        cfg["templates"], corpus.seed32(ctx["seed"], 0))
    bodies = loadgen.build_bodies(gspec)
    chk = webhook.compare_window(
        constraints, bodies, gen["rows"], answers, gen["t_open"],
        gen["t_close"], cfg["timeout_s"], tr["kind"] == "closed")
    t["reference_s"] = time.monotonic() - t0
    ok = fleet.ok_by_replica(before["door_metrics"], after["door_metrics"])
    busiest = None
    if traces and any(traces):
        # the busiest chip's trace is the one reported
        k = max((k for k, tr_ in enumerate(traces) if tr_),
                key=lambda k: traces[k]["busy_s"])
        busiest = traces[k]
        t["trace_busy_s"] = {rid: (tr_ or {}).get("busy_s")
                             for rid, tr_ in zip(ids, traces)}
        t["trace_of"] = ids[k]
    compared = webhook.compared_of(chk, tr.get("min_reviews", 1))
    compared.update(fleet.compared(readies, ok, fleetz))
    return {
        "device": dict(up["device"], memory_peak_bytes=peak),
        "setup_s": setup_s, "timings": t, "snapshot": up["snap"],
        "calibration": up["cal"], "ready": readies,
        "window": {"window_s": gen["t_close"] - gen["t_open"],
                   "good": len(chk["lat_ms"]),
                   "lat_ms": chk["lat_ms"], "late_ms": chk["late_ms"],
                   "per_s": chk["per_s"], "late": chk["late"],
                   "gen_max_gap_ms": gen.get("max_gap_ms"),
                   "replica_ids": ids,
                   "ok_by_replica": ok,
                   # the replicas' clocks and the generator's are all
                   # time.monotonic() of one host
                   "gc_full": fleet.gc_full(pauses, gen["t_open"],
                                            gen["t_close"]),
                   "compilez_before": before["compilez"],
                   "compilez_after": after["compilez"]},
        "before": before, "after": after, "trace": busiest,
        "fleetz": fleetz,
        "gauges": webhook.gauges(before, after),
        "attempted": chk["attempted"],
        "failed": chk["wrong"] + chk["unanswered"] + chk["late"],
        "compared": compared,
        "notes": chk["faults"]
        + ([f"{chk['late']} answers came after the caller's timeout"]
           if chk["late"] else [])
        + (["the generator ran out of bodies"]
           if gen["bodies_left"] <= 0 else []),
    }


# ---------------------------------------------------------------------------
# the comparison's control
# ---------------------------------------------------------------------------


def answer(policies, body: bytes) -> bytes:
    """The reference in a replica's place: the AdmissionReview answer
    to one request under `policies` (control.py webhook_control)."""
    req = json.loads(body)["request"]
    allowed, msgs = policies.verdict(req["object"])
    out = {"uid": req["uid"], "allowed": allowed}
    if not allowed:
        out["status"] = {"code": 403, "message": "\n".join(msgs)}
    return json.dumps({"response": out}).encode()


def control(config: dict, traffic: dict, seed: int, n_reviews: int) -> dict:
    """The plain reference in the fleet's place, reviews dealt to the
    replicas in rotation.  Sound: every replica answers under the policy
    set in force, each reports a chip of its own -> correct.  Each fault
    has to come out as not correct: (a) one replica restored from a
    snapshot one constraint stale (the newest constraint under its
    predecessor's name: control.py's fault, in one replica of four) reads
    verdicts_wrong > 0; (b) two replicas given the same chip read
    chips_distinct under the replicas' number."""
    n = config["replicas"]
    _t, constraints = corpus.make_templates(
        config["templates"], corpus.seed32(seed, 0))
    bodies = loadgen.build_bodies({
        "bodies": n_reviews, "seed": seed, "tag": f"bench-{seed}",
        "violating_share": traffic.get("violating_share",
                                       config["violating_share"])})
    true = reference.Policies(constraints, corpus.FAMILIES)
    stale = reference.Policies(
        constraints[:-1] + [dict(constraints[-1], metadata={
            "name": constraints[-2]["metadata"]["name"]})], corpus.FAMILIES)
    readies = [{"replica_id": f"r{i}", "chip": i} for i in range(n)]
    fleetz = {"backends": [{"ejected": False, "readmissions": 0}] * n}

    def read(stale_replica=None, same_chip=False):
        wrong, ok = 0, {}
        for k, body in enumerate(bodies):
            i = k % n
            pol = stale if i == stale_replica else true
            wrong += reference.compare_verdict(
                true, body, 200, answer(pol, body)) is not None
            ok[f"r{i}"] = ok.get(f"r{i}", 0) + 1
        rs = [dict(r, chip=1) if same_chip and r["chip"] == 2 else r
              for r in readies]
        compared = {
            "verdicts_wrong": {"value": wrong, "limit": 0},
            "reviews_unanswered": {"value": 0, "limit": 0},
            "reviews_compared": {"value": len(bodies),
                                 "at_least": traffic.get("min_reviews", 1)},
            **fleet.compared(rs, ok, fleetz)}
        return {"correct": harness_correct(compared),
                **{k: c["value"] for k, c in compared.items()}}

    return {"sound": read(),
            "faults": {"one_replica_a_constraint_stale": read(n - 1),
                       "two_replicas_one_chip": read(same_chip=True)}}


def harness_correct(compared: dict) -> bool:
    import run as harness

    return harness.result_line(
        {"compared": compared, "attempted": 0, "failed": 0,
         "device": {}}, {}, False)["correct"]


def main(argv) -> int:
    if len(argv) in (3, 4) and argv[0] == "--control":
        import run as harness

        cell = harness.load_cell(argv[1])
        ok = True
        for seed in (int(s) for s in argv[2].split(",")):
            r = control(cell["config"], cell["traffic"], seed,
                        int(argv[3]) if len(argv) == 4 else 20000)
            ok = ok and r["sound"]["correct"] and not any(
                f["correct"] for f in r["faults"].values())
            print(json.dumps({"workload": argv[1], "seed": seed, **r}),
                  flush=True)
        return 0 if ok else 1
    sys.exit("usage: webhook_fleet.py --control <cell> <seed,...> [reviews]")


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
