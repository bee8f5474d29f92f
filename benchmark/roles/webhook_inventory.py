"""The webhook role over a mixed, synced inventory: one `--operation
webhook` replica that restored the sealed snapshot an audit-role process
wrote of the agilebank cluster (lib/agilebank.py: Namespaces, Services
and Pods, all in data.inventory), behind one EventFrontDoor speaking
GKW1; the window drives POST /v1/admit on the door's port with reviews
of three kinds (lib/agilebank_reviews.py), the Service reviews through
the bundle's referential policy.

Processes, CPU sets, door, replica launcher, warm-up and window are
roles/webhook.py's (Lines, spawn, tell, control, surfaces, ...); what
is this role's own is the cluster and its snapshot child, the
generator's bodies (lib/agilebank_loadgen.py), the plain reference
(lib/agilebank_admission_reference.py), a ladder of bursts that meets
every executable three kinds of review can key (warm_shapes), and one
step before any warm-up: a single Service review through the door,
after which the replica's /metrics has to show a referential cell
resolved from the join index.  A program without that binding walks the
whole inventory in the interpreter for every Service review (seconds
each at this size): another system than this cell measures, so the run
ends there.

The window runs with the replica's routing pinned to the device
(brownout level 3), as every webhook window of this benchmark does: the
warm-up's first-contact reviews are slower than the admission-latency
objective, so the first scrape of /metrics after them finds the burn
alert firing, and its five-minute window outlasts the run.  The role
sends no load of its own to bring that about; it scrapes once after
each pass of the ladder, as roles/webhook.py scrapes when its window
opens, and waits for the ladder's three rungs, so the window is one
regime and not two (configs/agilebank4x111k-webhook.json, `assumed`).

run(ctx) is the parent side (never touches jax); this file run as a
script with --child is the snapshot-writing process, and with --control
the comparison's control at the cell's own size:

    python3 benchmark/roles/webhook_inventory.py --control <cell> <seed,...> [reviews]
"""

from __future__ import annotations

import http.client
import json
import os
import sys
import time

if __name__ == "__main__":  # the child: benchmark/ on the path first
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

from lib import (agilebank, agilebank_admission_reference,  # noqa: E402
                 agilebank_reviews, chip, procs)
from roles import webhook  # noqa: E402

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
INDEX_CELLS = ('admission_join_cells_total', 'outcome="index"')
FALLBACK_CELLS = ('admission_join_cells_total', 'outcome="fallback"')
PROBE_TIMEOUT_S = 300.0
MAX_SHAPE_PASSES = 5
PIN_WAIT_S = 8.0


def gen_spec(ctx) -> dict:
    """lib/loadgen.py's spec for the window's generator, with what
    lib/agilebank_reviews.py builds the bodies from beside it."""
    tr, cfg = ctx["traffic"], ctx["config"]
    per_s = tr.get("rate_per_s") or tr["bodies_per_s"]
    spec = {k: v for k, v in tr.items() if not k.startswith("shape_")}
    spec.update({
        "config": cfg, "seed": ctx["seed"], "tag": f"bench-{ctx['seed']}",
        "timeout_s": cfg["timeout_s"],
        "bodies": int(tr["warm_reviews"] + sum(tr.get("warm_bursts", ()))
                      + per_s * (ctx["seconds"] + 3)) + 1024,
        "out": os.path.join(ctx["work"], "gen_result.json"),
    })
    return spec


def await_pin(metrics_port: int) -> float:
    """Scrape the replica's /metrics once a second, for at most
    PIN_WAIT_S, until `brownout_level` reads 3 -> the level read last.
    The replica evaluates its SLO burn alerts when it is scraped and at
    no other time; after a pass of the shape ladder (bursts of up to
    256, executables loaded on first contact) the admission-latency
    alert fires at the first scrape, the brownout ladder climbs a rung
    a second while it stands, and at level 3 every batch is routed to
    the device.  roles/webhook.py's windows meet the same at their
    opening scrape and change regime seconds in (unseen there: with 500
    constraints the router sends every batch to the device anyway);
    with four constraints the router prices the interpreter tier cheaper
    for any batch under ~21 reviews, so here the pin decides the tier,
    and the small shapes can only be met while it holds."""
    level, deadline = 0.0, time.monotonic() + PIN_WAIT_S
    while True:
        level = series(procs.scrape(metrics_port), "brownout_level", "")
        if level >= 3 or time.monotonic() >= deadline:
            return level
        time.sleep(1.0)


def shape_spec(ctx, k: int) -> dict:
    """The spec of one pass of the shape ladder: a generator whose whole
    life is its bursts (shape_bursts once per class of shape_classes,
    pipelined on one connection, each awaited)."""
    tr = ctx["traffic"]
    bursts = list(tr["shape_bursts"]) * len(tr["shape_classes"])
    spec = dict(gen_spec(ctx), **{
        "kind": "open", "connections": 1, "warm_reviews": 0,
        "shape_classes": tr["shape_classes"],
        "shape_bursts": tr["shape_bursts"], "warm_bursts": bursts,
        "bodies": sum(bursts), "tag": f"shapes{k}-{ctx['seed']}",
        "out": os.path.join(ctx["work"], f"shapes_{k}.json")})
    return spec


def executables(replica_port: int) -> int:
    """The review path's mask executables the replica holds, compiled
    or loaded (/debug/compilez)."""
    mix = procs.get_json(replica_port, "/debug/compilez?limit=0").get(
        "provenance_mix") or {}
    return sum(n for k, n in mix.items() if k.startswith("fused-packed"))


def warm_shapes(ctx, door_port: int, ready: dict, cpus) -> dict:
    """Nothing compiles inside the window: a batch is padded to a power
    of two of rows and is as wide as its widest review, and every such
    shape is an executable of its own.  The bursts are sent class by
    class (lib/agilebank_reviews.py burst_ladder) until the replica
    holds one executable per class and row bucket the bursts reach, or
    two passes in a row add none, at most MAX_SHAPE_PASSES times: how
    the batcher cuts a burst is its own affair, so a pass may miss a
    bucket.  Which tier serves a batch is the router's affair: the
    first pass runs unpinned and meets the large buckets only; after
    each pass /metrics is scraped (await_pin), and the passes under the
    pin meet the rest."""
    tr = ctx["traffic"]
    buckets = {max(8, 1 << (n - 1).bit_length()) for n in tr["shape_bursts"]}
    want = len(tr["shape_classes"]) * len(buckets)
    have, passes, idle, level = executables(ready["port"]), 0, 0, 0.0
    while have < want and passes < MAX_SHAPE_PASSES and idle < 2:
        spec = shape_spec(ctx, passes)
        path = os.path.join(ctx["work"], f"shapes_spec_{passes}.json")
        procs.write_json(path, spec)
        gproc, glines = webhook.spawn(
            ctx, f"shapes{passes}", procs.python(
                os.path.join(HERE, "lib", "agilebank_loadgen.py"), path),
            cpus)
        glines.wait("built", 600)
        webhook.tell(gproc, f"start {door_port}")
        glines.wait("warmed", 900)
        procs.Procs.stop(gproc)
        got = executables(ready["port"])
        idle = 0 if got > have else idle + 1
        have, passes = got, passes + 1
        if level < 3:
            level = await_pin(ready["metrics_port"])
            idle = 0 if level >= 3 else idle
    return {"shape_passes": passes, "shape_executables": have,
            "shape_executables_wanted": want, "brownout_level": level}


def series(page: dict, name: str, label: str) -> float:
    """The sum of a scraped /metrics page's series of one name whose
    labels hold `label`."""
    return sum(v for key, v in page.items()
               if key.partition("{")[0].endswith(name)
               and label in key.partition("{")[2])


def probe_request() -> dict:
    """One Service review that needs no cluster to build: CREATE under a
    new name, with a selector nobody holds."""
    obj = agilebank.make_service(0, "team-1", {"app": "capability-probe"})
    obj["metadata"]["name"] = "capability-probe"
    return agilebank_reviews.request(obj, "CREATE", "capability-probe")


def serves_from_the_index(door_port: int, metrics_port: int,
                          post=None) -> str:
    """Send ONE Service review through the door and read the replica's
    /metrics: None where a referential cell was resolved from the join
    index, else why the program cannot serve this configuration."""
    before = procs.scrape(metrics_port)
    status, data = (post or post_review)(
        door_port, agilebank_reviews.body_of(probe_request()))
    after = procs.scrape(metrics_port)
    grown = series(after, *INDEX_CELLS) - series(before, *INDEX_CELLS)
    if grown > 0:
        return None
    return (
        "the program cannot serve this configuration: one Service review "
        f"(HTTP {status}, {data[:80]!r}) left "
        "admission_join_cells_total{outcome=\"index\"} "
        + ("absent" if not any("admission_join_cells_total" in k
                               for k in after) else "where it was")
        + " on the replica's /metrics: its review path does not resolve "
        "K8sUniqueServiceSelector through the join index, so every "
        "Service review walks the 10,000 Services in the interpreter")


def post_review(port: int, body: bytes) -> tuple:
    conn = http.client.HTTPConnection("127.0.0.1", port,
                                      timeout=PROBE_TIMEOUT_S)
    try:
        conn.request("POST", "/v1/admit", body,
                     {"Content-Type": "application/json"})
        resp = conn.getresponse()
        return resp.status, resp.read()
    finally:
        conn.close()


def compare_window(ref, bodies, rows, answers, t_open, t_close,
                   timeout_s, closed_loop) -> dict:
    """roles/webhook.py compare_window with this deployment's plain
    reference: every review of the window (those due in it, open loop;
    those answered in it, closed loop) judged by what its answer says,
    however late it came."""
    lat_ms, late_ms, faults = [], [], []
    per_s = [0] * int(t_close - t_open)
    attempted = wrong = unanswered = late = 0
    for (i, due, sent, done, status), data in zip(rows, answers):
        if closed_loop:
            if done and not t_open <= done <= t_close:
                continue
        elif not t_open <= due < t_close:
            continue
        attempted += 1
        if not done:
            unanswered += 1
            continue
        why = agilebank_admission_reference.compare_verdict(
            ref, bodies[i], status, data)
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


def compared_of(chk: dict, min_reviews: int, fallback_cells: float) -> dict:
    out = webhook.compared_of(chk, min_reviews)
    # a run in which any referential cell fell back to the full inventory
    # measured another system
    out["join_fallback_cells"] = {"value": fallback_cells, "limit": 0}
    return out


def run(ctx: dict) -> dict:
    work, cfg, tr = ctx["work"], ctx["config"], ctx["traffic"]
    cpus = procs.cpu_sets(webhook.CPU_WEIGHTS)
    if cpus["harness"]:
        os.sched_setaffinity(0, cpus["harness"])
    t = {}

    # 0. the generator builds its bodies while the cluster comes up
    gspec = gen_spec(ctx)
    gen_spec_path = os.path.join(work, "gen_spec.json")
    procs.write_json(gen_spec_path, gspec)
    gproc, glines = webhook.spawn(
        ctx, "loadgen", procs.python(
            os.path.join(HERE, "lib", "agilebank_loadgen.py"),
            gen_spec_path), cpus["gen"])
    snap_dir = os.path.join(work, "snapshot")
    os.makedirs(snap_dir)

    # 1. the audit-role process loads the cluster, sweeps once, seals
    t0 = time.monotonic()
    spec = os.path.join(work, "snapshot_spec.json")
    result = os.path.join(work, "snapshot_result.json")
    procs.write_json(spec, {
        "config": cfg, "seed": ctx["seed"], "platform": ctx["platform"],
        "snapshot_dir": snap_dir, "result": result})
    log = os.path.join(work, "snapshot_child.log")
    proc = ctx["procs"].popen(
        procs.python(os.path.abspath(__file__), "--child", spec), log,
        procs.child_env(), cpus=cpus["replica"])
    procs.wait_child(proc, "the snapshot-writing audit child", log,
                     ctx["timeout_s"])
    snap = procs.read_json(result)
    t["snapshot_s"] = time.monotonic() - t0

    # 2. the replica restores it and holds the chip alone
    t0 = time.monotonic()
    flags = ["--replica-id", "r0", "--snapshot-dir", snap_dir,
             "--driver", "tpu"]
    rproc, rlines = webhook.spawn(
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
    dproc, dlines = webhook.spawn(
        ctx, "door", procs.python(
            os.path.join(HERE, "lib", "door.py"), str(ready["wire_port"]),
            str(ready["port"]), "r0"), cpus["door"])
    door_port = json.loads(dlines.wait('"door"', 60))["port"]

    # 4. nothing is sent until the router has priced its tiers
    t0 = time.monotonic()
    cal = procs.poll(
        "a routing calibration in /debug/routez", 300,
        lambda: procs.get_json(
            ready["port"], "/debug/routez?limit=0")["calibration"],
        proc=rproc, log_path=os.path.join(work, "replica.log"))
    t["calibration_wait_s"] = time.monotonic() - t0

    # 5. before any warm-up: can this program serve the configuration?
    t0 = time.monotonic()
    why = serves_from_the_index(door_port, ready["metrics_port"])
    t["probe_s"] = time.monotonic() - t0
    if why is not None:
        raise procs.BenchFailure(why, 4)

    # 6. warm-up: every shape the window can meet (the replica pins its
    # routing at the scrape after the first pass), then the generator's
    # own warm-up until warm_reviews are answered
    t0 = time.monotonic()
    t.update(warm_shapes(ctx, door_port, ready, cpus["gen"]))
    t["shapes_s"] = time.monotonic() - t0
    glines.wait("built", 600)
    t0 = time.monotonic()
    webhook.tell(gproc, f"start {door_port}")
    glines.wait("warmed", 600)
    t["warm_s"] = time.monotonic() - t0
    args = (ready["port"], ready["metrics_port"], door_port)
    before = webhook.surfaces(*args)
    setup_s = time.time() - ctx["t_start"]

    # 7. the window (a traced run traces its last TRACE_MAX_S seconds and
    # stops the profiler once every answer is in: roles/webhook.py)
    webhook.tell(gproc, f"open {ctx['seconds']}")
    reduced = None
    if ctx["trace"]:
        time.sleep(max(0.0, ctx["seconds"] - webhook.TRACE_MAX_S))
        webhook.control(ctl["port"],
                        "trace_start " + os.path.join(work, "trace"))
    glines.wait("closed", ctx["seconds"] + cfg["timeout_s"] + 120)
    if ctx["trace"]:
        reduced = webhook.control(ctl["port"], "trace_stop", 300)["trace"]
    after = webhook.surfaces(*args)
    peak = webhook.control(ctl["port"], "memstats")["memory_peak_bytes"]
    pauses = webhook.control(ctl["port"], "gc_full")["pauses"]
    gen = procs.read_json(gspec["out"])
    answers = webhook.read_answers(gspec["out"] + ".answers", gen["sent"])

    # 8. free the program's state, then the reference
    for p in (gproc, dproc, rproc):
        procs.Procs.stop(p)
    t0 = time.monotonic()
    _t, constraints, objects = agilebank.cluster(cfg, ctx["seed"])
    ref = agilebank_admission_reference.AdmissionReference(
        constraints, objects)
    bodies = agilebank_reviews.build_bodies(gspec)
    chk = compare_window(
        ref, bodies, gen["rows"], answers, gen["t_open"], gen["t_close"],
        cfg["timeout_s"], tr["kind"] == "closed")
    t["reference_s"] = time.monotonic() - t0
    window_s = gen["t_close"] - gen["t_open"]
    fallback = series(after["replica_metrics"], *FALLBACK_CELLS)
    return {
        "device": dict(device, memory_peak_bytes=peak),
        "setup_s": setup_s, "timings": t, "snapshot": snap,
        "calibration": cal, "ready": ready,
        "window": {"window_s": window_s, "good": len(chk["lat_ms"]),
                   "lat_ms": chk["lat_ms"], "late_ms": chk["late_ms"],
                   "per_s": chk["per_s"], "late": chk["late"],
                   "gen_max_gap_ms": gen.get("max_gap_ms"),
                   "gc_full": chip.pauses_in(pauses, gen["t_open"],
                                             gen["t_close"]),
                   "compilez_before": before["compilez"],
                   "compilez_after": after["compilez"]},
        "before": before, "after": after, "trace": reduced,
        "gauges": webhook.gauges(before, after),
        "attempted": chk["attempted"],
        "failed": chk["wrong"] + chk["unanswered"] + chk["late"],
        "compared": compared_of(chk, tr.get("min_reviews", 1), fallback),
        "notes": chk["faults"]
        + ([f"{chk['late']} answers came after the caller's timeout"]
           if chk["late"] else [])
        + (["the generator ran out of bodies"]
           if gen["bodies_left"] <= 0 else []),
    }


# ---------------------------------------------------------------------------
# the comparison's control
# ---------------------------------------------------------------------------

FAULTS = ("index_one_write_stale", "other_service_misnamed")


def answer(ref, req: dict, fault: str = None, stale=None) -> bytes:
    """The reference in the program's place: the AdmissionReview answer
    to one request, sound or with one fault: the index one write stale
    (`stale`: a reference whose inventory still holds a moved Service
    under its old selector) or a colliding Service's message naming the
    wrong Service."""
    allowed, msgs = (stale if fault == "index_one_write_stale"
                     else ref).verdict(req)
    if fault == "other_service_misnamed":
        msgs = [m.replace("service <svc-", "service <svc-9") for m in msgs]
    out = {"uid": req["uid"], "allowed": allowed}
    if not allowed:
        out["status"] = {"code": 403, "message": "\n".join(msgs)}
    return json.dumps({"response": out}).encode()


def control(config: dict, traffic: dict, seed: int, n_reviews: int) -> dict:
    """Sound: the reference's own answers read 0 wrong.  Each fault has
    to come out as not correct: the index one write stale (every Service
    a request collides with moved onto that selector one write ago, and
    the answer still looks for it under its old key, so it misses the
    collision), and the other Service misnamed in every collision's
    message."""
    _t, constraints, objects = agilebank.cluster(config, seed)
    ref = agilebank_admission_reference.AdmissionReference(
        constraints, objects)
    requests = agilebank_reviews.build_requests(
        config, traffic, seed, n_reviews, f"bench-{seed}")
    moved = {other for r in requests for other in ref.colliding(r)}
    stale = agilebank_admission_reference.AdmissionReference(constraints, [])
    stale.services = [
        (ns, name, "old:key" if (ns, name) in moved else flat)
        for ns, name, flat in ref.services]

    def read(fault):
        wrong = sum(
            agilebank_admission_reference.compare_verdict(
                ref, agilebank_reviews.body_of(r), 200,
                answer(ref, r, fault, stale)) is not None
            for r in requests)
        return {"reviews_compared": len(requests), "verdicts_wrong": wrong}

    return {"sound": read(None), "faults": {f: read(f) for f in FAULTS},
            "limit": {"verdicts_wrong": 0}}


# ---------------------------------------------------------------------------
# the child: the audit-role process the replica inherits from
# ---------------------------------------------------------------------------


def child(spec: dict) -> int:
    """Load the cluster through the package boundary, settle the
    vocabulary as a started pod does (roles/audit.py child_snapshot),
    sweep once, write the sealed snapshot."""
    from roles import audit, audit_inventory

    device = chip.device_or_die(spec["platform"])
    cfg = spec["config"]
    t = {}
    t0 = time.monotonic()
    templates, constraints, objects = agilebank.cluster(cfg, spec["seed"])
    t["generate_s"] = time.monotonic() - t0
    t0 = time.monotonic()
    loaded = audit_inventory.load_cluster(templates, constraints, objects)
    if loaded is None:
        print("benchmark: the program has no join plan for "
              "K8sUniqueServiceSelector (join_plan_shapes() is empty): "
              "it cannot run this configuration", file=sys.stderr)
        return 4
    client, driver = loaded
    t["ingest_s"] = time.monotonic() - t0
    from gatekeeper_tpu.snapshot import Snapshotter

    t0 = time.monotonic()
    driver.calibrate_routing()
    client.audit_capped(cfg["violations_limit"])
    audit.settle()
    t["sweep_s"] = time.monotonic() - t0
    t["vocabulary"] = driver.interner.snapshot_size()
    t0 = time.monotonic()
    name = Snapshotter(client, spec["snapshot_dir"],
                       interval_s=0.0).write_once()
    t["snapshot_write_s"] = time.monotonic() - t0
    if not name:
        print("benchmark: the snapshot was not written", file=sys.stderr)
        return 1
    procs.write_json(spec["result"], {"device": device, "timings": t,
                                      "snapshot": name})
    return 0


def main(argv) -> int:
    if len(argv) == 2 and argv[0] == "--child":
        return child(procs.read_json(argv[1]))
    if len(argv) in (3, 4) and argv[0] == "--control":
        import run as harness

        cell = harness.load_cell(argv[1])
        ok = True
        for seed in (int(s) for s in argv[2].split(",")):
            r = control(cell["config"], cell["traffic"], seed,
                        int(argv[3]) if len(argv) == 4 else 20000)
            ok = ok and r["sound"]["verdicts_wrong"] == 0 and all(
                x["verdicts_wrong"] > 0 for x in r["faults"].values())
            print(json.dumps({"workload": argv[1], "seed": seed, **r}),
                  flush=True)
        return 0 if ok else 1
    sys.exit("usage: webhook_inventory.py --child <spec.json> | "
             "--control <cell> <seed,...> [reviews]")


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
