"""The audit role over a mixed, synced inventory: one process holds the
chip and drives Client(driver=TpuDriver()) at the package boundary over
a cluster of several kinds (Namespaces, Services, Pods), every object of
which is replicated into data.inventory — add_data for the interval's
changed objects, then audit_capped(limit), then the background work the
sweep started, joined — one sweep after another.

The loop, the tracer and the comparison's frame are roles/audit.py's
(window, Tracer, settle, sampled, compared_of, sweep_record); what is
this role's own is the cluster (lib/agilebank.py), its steps and its
plain reference (lib/agilebank_reference.py).

run(ctx) is the parent side (never touches jax); this file run as a
script with --child is the process that holds the chip, and with
--control the comparison's control at the cell's own size:

    python3 benchmark/roles/audit_inventory.py --control <cell> <seed,...> [steps]
"""

from __future__ import annotations

import json
import os
import sys
import time

if __name__ == "__main__":  # the child: benchmark/ on the path first
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

from lib import agilebank, agilebank_reference, chip, procs  # noqa: E402
from roles import audit  # noqa: E402

# settle_vocabulary: the steps it takes at a time where it has no
# crossing to aim at, and the most it may use (two doublings past the
# cluster's first 10,800 strings)
SETTLE_BATCH = 8
SETTLE_MAX = 640


def run(ctx: dict) -> dict:
    """Start the chip-holding child, wait, return its raw readings."""
    work = ctx["work"]
    spec = os.path.join(work, "audit_spec.json")
    result = os.path.join(work, "audit_result.json")
    procs.write_json(spec, {
        k: ctx[k] for k in ("config", "traffic", "seed", "seconds", "trace",
                            "platform", "t_start", "work")
    } | {"result": result})
    log = os.path.join(work, "audit_child.log")
    proc = ctx["procs"].popen(
        procs.python(os.path.abspath(__file__), "--child", spec), log,
        procs.child_env())
    procs.wait_child(proc, "the audit child", log, ctx["timeout_s"])
    return procs.read_json(result)


def compare_sweeps(constraints, objects, steps, answers, cap) -> dict:
    """Sweeps held to the plain reference, as roles/audit.py
    compare_sweeps holds its own: the reference applies every step's
    objects in turn, and where `answers` ({step index: answer}, or a
    list with one per step) has the k-th sweep's answer, that has to be
    the audit of the data as it then stands."""
    if not isinstance(answers, dict):
        answers = dict(enumerate(answers))
    ref = agilebank_reference.AuditReference(constraints, objects)
    wrong, first = 0, []
    for k, step in enumerate(steps):
        for _i, obj in step:
            ref.put(obj)
        if k not in answers:
            continue
        faults = ref.compare(answers[k], cap)
        if faults:
            wrong += 1
            if len(first) < 3:
                first.append(f"sweep {k}: {faults[0]}")
    return {"sweeps_compared": len(answers), "sweeps_wrong": wrong,
            "first_faults": first}


UNIQUE_KEY = ("K8sUniqueServiceSelector", "unique-service-selector")
FAULTS = ("other_service_misnamed", "collision_dropped", "total_altered")


def planted(answer: dict, fault: str) -> dict:
    """`answer` with one fault in what it says of the unique-selector
    constraint: the first kept violation names another Service, or is
    missing, or the total is one too high."""
    out = {"totals": dict(answer["totals"]), "kept": list(answer["kept"])}
    at = next(i for i, r in enumerate(out["kept"]) if r[:2] == UNIQUE_KEY)
    if fault == "other_service_misnamed":
        r = out["kept"][at]
        out["kept"][at] = r[:4] + (r[4].replace("<svc-", "<svc-9"),)
    elif fault == "collision_dropped":
        del out["kept"][at]
    else:
        n, how = out["totals"][UNIQUE_KEY]
        out["totals"][UNIQUE_KEY] = (n + 1, how)
    return out


def selector_late(late: dict, now: dict) -> dict:
    """`now`, but for the unique-selector constraint, which is answered
    as `late` answers it."""
    return {"totals": {**now["totals"],
                       UNIQUE_KEY: late["totals"][UNIQUE_KEY]},
            "kept": [r for r in now["kept"] if r[:2] != UNIQUE_KEY]
            + [r for r in late["kept"] if r[:2] == UNIQUE_KEY]}


def control(config: dict, traffic: dict, seed: int, n_steps: int) -> dict:
    """The reference in the program's place and in the program's form
    (AuditReference.answer), sound; with "no stale answer" broken, every
    sweep answered with the audit as it stood one interval earlier, by
    all constraints and by the unique-selector constraint alone; and
    with each of FAULTS planted in every sweep's answer.  All but the
    first have to come out as not correct."""
    cap = config["violations_limit"]
    _t, constraints, objects, steps = agilebank.deployment(
        config, traffic, seed, n_steps)
    ref = agilebank_reference.AuditReference(constraints, objects)
    answers = [ref.answer(cap)]
    for step in steps:
        for _i, obj in step:
            ref.put(obj)
        answers.append(ref.answer(cap))

    def read(given):
        chk = compare_sweeps(constraints, objects, steps, given, cap)
        return {k: chk[k] for k in ("sweeps_compared", "sweeps_wrong")}

    return {"sound": read(answers[1:]), "control": read(answers[:-1]),
            "control_selector_alone": read(
                [selector_late(a, b) for a, b in zip(answers, answers[1:])]),
            "faults": {f: read([planted(a, f) for a in answers[1:]])
                       for f in FAULTS},
            "forms": {f"{k[1]}": how for k, (_n, how)
                      in answers[-1]["totals"].items()},
            "limit": {"sweeps_wrong": 0}}


# ---------------------------------------------------------------------------
# the child
# ---------------------------------------------------------------------------


def load_cluster(templates, constraints, objects):
    """(client, driver): the cluster ingested through the package
    boundary, every object synced; None where the program has no join
    plan for the bundle's referential policy.  Such a program answers
    the same cell from the interpreter tier, O(inventory) per flagged
    Service: another system than this cell measures, and hours at its
    size."""
    from gatekeeper_tpu.client.client import Client
    from gatekeeper_tpu.ops.driver import TpuDriver
    from gatekeeper_tpu.ops.xlacache import enable_caches

    enable_caches()
    driver = TpuDriver()
    client = Client(driver=driver)
    for t in templates:
        client.add_template(t)
    for c in constraints:
        client.add_constraint(c)
    if not driver.join_plan_shapes():
        return None
    for o in objects:
        client.add_data(o)
    return client, driver


def settle_vocabulary(sweep_steps, size, steps: list, reach: int) -> int:
    """Nothing compiles inside the window: every selector a step draws
    is a string the program has not seen, its string tables are as wide
    as the vocabulary's power-of-two bucket, and its executables are
    compiled for the tables' width, the delta path's with the sweep
    that crosses and the full sweep's with the next rebasing one.  So in
    set-up the churn goes on, `sweep_steps(some of steps)` -> their
    last_sweep_stats, until the next power of two is farther from
    `size()` than `reach` more steps can bring it and a full sweep has
    run at this width.  A cluster meets those compiles once per doubling
    of its vocabulary; a window that packs hundreds of audit intervals
    into seconds would weigh them a thousand times their share.
    Returns the steps used."""
    def room(v):
        return (1 << (v - 1).bit_length()) - v

    used, per_step, full_due = 0, 0.0, False
    while used < len(steps):
        v = size()
        far = used and room(v) >= per_step * reach
        if far and not full_due:
            break
        n = min(len(steps) - used, SETTLE_BATCH if far or not used
                else int(room(v) / per_step) + 2)
        stats = sweep_steps(steps[used:used + n])
        used += n
        per_step = (size() - v) / n
        if room(size()) > room(v):          # crossed: a full sweep is due
            full_due = True
        elif any(st.get("full") for st in stats):
            full_due = False
    return used


def child(spec: dict) -> int:
    device = chip.device_or_die(spec["platform"])
    cfg, traffic = spec["config"], spec["traffic"]
    cap = cfg["violations_limit"]
    t = {}
    reach = 8 + int(spec["seconds"] * traffic.get("max_steps_per_s", 4))
    t0 = time.monotonic()
    templates, constraints, objects, steps = agilebank.deployment(
        cfg, traffic, spec["seed"], audit.WARM_STEPS + SETTLE_MAX + reach)
    t["generate_s"] = time.monotonic() - t0
    t0 = time.monotonic()
    loaded = load_cluster(templates, constraints, objects)
    if loaded is None:
        print("benchmark: the program has no join plan for "
              "K8sUniqueServiceSelector (join_plan_shapes() is empty): "
              "it cannot run this configuration", file=sys.stderr)
        return 4
    client, driver = loaded
    t["ingest_s"] = time.monotonic() - t0

    # warm: the first full sweep, the background work it starts, then a
    # few churn steps through the window's own loop (the delta path)
    t0 = time.monotonic()
    client.audit_capped(cap)
    audit.settle()
    t["first_sweep_s"] = time.monotonic() - t0
    warm = audit.window(client, driver, steps[:audit.WARM_STEPS], 1e9, cap)
    answers = {k: audit.sweep_record(*a)
               for k, a in warm.pop("answers").items()}
    t0 = time.monotonic()
    settled = []

    def sweep_steps(some):
        got = audit.window(client, driver, some, 1e9, cap, keep=set())
        at = audit.WARM_STEPS + sum(len(x["sweep_s"]) for x in settled)
        answers.update({at + k: audit.sweep_record(*a)
                        for k, a in got.pop("answers").items()})
        settled.append(got)
        return got["sweep_stats"]

    first = audit.WARM_STEPS + settle_vocabulary(
        sweep_steps, driver.interner.snapshot_size,
        steps[audit.WARM_STEPS:audit.WARM_STEPS + SETTLE_MAX], reach)
    t["settle_s"] = time.monotonic() - t0
    t["settle_steps"] = first - audit.WARM_STEPS
    t["settle_slowest_sweep_s"] = max(
        (x for got in settled for x in got["sweep_s"]), default=0.0)
    t["vocabulary"] = driver.interner.snapshot_size()
    keep = audit.sampled(spec["seed"], reach)
    setup_s = time.time() - spec["t_start"]

    tracer = audit.Tracer(os.path.join(spec["work"], "trace")) \
        if spec["trace"] else None
    w = audit.window(client, driver, steps[first:first + reach],
                     spec["seconds"], cap, tracer=tracer, keep=keep)
    peak = chip.memory_peak_bytes()
    reduced = tracer.reduce() if tracer else None
    n = w["sweeps"]
    answers.update({first + k: audit.sweep_record(*a)
                    for k, a in w.pop("answers").items()})
    del client, driver
    t0 = time.monotonic()
    checks = compare_sweeps(constraints, objects, steps[:first + n],
                            answers, cap)
    t["reference_s"] = time.monotonic() - t0
    procs.write_json(spec["result"], {
        "device": dict(device, memory_peak_bytes=peak),
        "setup_s": setup_s, "timings": t, "window": w, "trace": reduced,
        "attempted": n, "failed": checks["sweeps_wrong"],
        "compared": audit.compared_of(checks, len(answers)),
        "notes": checks["first_faults"], "sizes": cfg,
        "rows_per_step": (traffic["services_per_step"]
                          + traffic["pods_per_step"]),
        "warm_sweep_stats": warm["sweep_stats"],
    })
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
                        int(argv[3]) if len(argv) == 4 else 12)
            ok = ok and r["sound"]["sweeps_wrong"] == 0 and all(
                x["sweeps_wrong"] > 0
                for x in (r["control"], r["control_selector_alone"],
                          *r["faults"].values()))
            print(json.dumps({"workload": argv[1], "seed": seed, **r}),
                  flush=True)
        return 0 if ok else 1
    sys.exit("usage: audit_inventory.py --child <spec.json> | "
             "--control <cell> <seed,...> [steps]")


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
