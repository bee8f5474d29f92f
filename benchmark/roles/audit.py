"""The audit role: one process holds the chip and drives
Client(driver=TpuDriver()) at the package boundary — add_data for the
interval's churned rows, then audit_capped(limit), then the background
work the sweep started, joined — one sweep after another.

run(ctx) is the parent side (never touches jax); this file run as a
script with --child is the process that holds the chip.  The same child
writes the sealed snapshot the webhook role restores (mode "snapshot").
"""

from __future__ import annotations

import os
import sys
import time

if __name__ == "__main__":  # the child: benchmark/ on the path first
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

from lib import chip, corpus, procs, reference  # noqa: E402

WARM_STEPS = 3
TRACE_MAX_S = 6.0


def run(ctx: dict) -> dict:
    """Start the chip-holding child, wait, return its raw readings."""
    work = ctx["work"]
    spec = os.path.join(work, "audit_spec.json")
    result = os.path.join(work, "audit_result.json")
    procs.write_json(spec, {
        k: ctx[k] for k in ("config", "traffic", "seed", "seconds", "trace",
                            "platform", "t_start", "work")
    } | {"mode": "window", "result": result})
    log = os.path.join(work, "audit_child.log")
    proc = ctx["procs"].popen(
        procs.python(os.path.abspath(__file__), "--child", spec), log,
        procs.child_env())
    procs.wait_child(proc, "the audit child", log, ctx["timeout_s"])
    return procs.read_json(result)


# ---------------------------------------------------------------------------
# the child
# ---------------------------------------------------------------------------


def settle():
    """A production sweep is followed by an interval in which its
    background base-mask resolve and delta-executable build land; back
    to back they are joined, and counted into the sweep."""
    from gatekeeper_tpu.ops import deltasweep

    for t in list(deltasweep._BG_THREADS):
        if t.name != "gk-route-cal":
            t.join(timeout=600)


def load_cluster(spec: dict):
    """(client, driver, constraints, pods): the configuration's cluster
    ingested through the package boundary."""
    from gatekeeper_tpu.client.client import Client
    from gatekeeper_tpu.ops.driver import TpuDriver
    from gatekeeper_tpu.ops.xlacache import enable_caches

    enable_caches()
    templates, constraints, pods = corpus.cluster(spec["config"], spec["seed"])
    driver = TpuDriver()
    client = Client(driver=driver)
    for t in templates:
        client.add_template(t)
    for c in constraints:
        client.add_constraint(c)
    for p in pods:
        client.add_data(p)
    return client, driver, constraints, pods


def sweep_record(res, totals) -> dict:
    """One sweep's answer in the form reference.AuditReference.compare
    reads."""
    kept = []
    for r in res.results():
        meta = (r.review.get("object") or {}).get("metadata") or {}
        kept.append((r.constraint.get("kind", ""),
                     (r.constraint.get("metadata") or {}).get("name", ""),
                     meta.get("namespace", ""), meta.get("name", ""), r.msg))
    return {"totals": dict(totals), "kept": kept}


def compare_sweeps(constraints, pods, steps, answers, cap,
                   families=corpus.FAMILIES) -> dict:
    """Sweeps held to the plain reference: the reference applies every
    step's rows in turn, and where `answers` ({step index: answer}, or a
    list with one per step) has the k-th sweep's answer, that has to be
    the audit of the data as it then stands."""
    if not isinstance(answers, dict):
        answers = dict(enumerate(answers))
    ref = reference.AuditReference(
        reference.Policies(constraints, families), pods)
    wrong, first = 0, []
    for k, step in enumerate(steps):
        for _i, pod in step:
            ref.put(pod)
        if k not in answers:
            continue
        faults = ref.compare(answers[k], cap)
        if faults:
            wrong += 1
            if len(first) < 3:
                first.append(f"sweep {k}: {faults[0]}")
    return {"sweeps_compared": len(answers), "sweeps_wrong": wrong,
            "first_faults": first}


def sampled(seed: int, n_steps: int, share: int = 10) -> set:
    """The sweeps whose answers are kept for the comparison: one in
    `share`, drawn from the seed (the window's last sweep is always
    kept).  Keeping every answer alive would grow the heap the
    interpreter's collector walks, and with it the pauses measured."""
    import random

    rng = random.Random(corpus.seed32(seed, 7))
    return set(rng.sample(range(n_steps), max(1, n_steps // share)))


def compared_of(checks: dict, at_least: int) -> dict:
    """Each number compared, beside its limit: exact comparisons, so no
    sweep may differ; and as many sweeps were compared as were kept."""
    return {
        "sweeps_wrong": {"value": checks["sweeps_wrong"], "limit": 0},
        "sweeps_compared": {"value": checks["sweeps_compared"],
                            "at_least": at_least},
    }


def window(client, driver, steps, seconds, cap, tracer=None,
           sweep=None, keep=None) -> dict:
    """Sweep after sweep until `seconds` have passed; the window closes
    with the sweep that was running then.  Answers of the sweeps in
    `keep` (all, if None) and of the last one are kept, by step index."""
    from gatekeeper_tpu.obs import compilestats

    parts = []

    def one(step):
        # the benchmark's own spans, on the profiler's clock: they name
        # the device's idle gaps in a traced run (lib/trace.py)
        from jax.profiler import TraceAnnotation

        a = time.monotonic()
        with TraceAnnotation("bench.ingest"):
            for _i, pod in step:
                client.add_data(pod)
        b = time.monotonic()
        with TraceAnnotation("bench.audit_capped"):
            out = client.audit_capped(cap)
        c = time.monotonic()
        with TraceAnnotation("bench.bg_join"):
            settle()
        parts.append({"ingest_ms": (b - a) * 1e3, "audit_ms": (c - b) * 1e3,
                      "bg_join_ms": (time.monotonic() - c) * 1e3})
        return out

    sweep = sweep or one
    stats, answers, lap = [], {}, []
    pauses = chip.GcPauses()
    out = None
    before = compilestats.get_stats().snapshot(limit=0)
    t0 = time.monotonic()
    if tracer:
        tracer.start()
    for k, step in enumerate(steps):
        t1 = time.monotonic()
        out = sweep(step)
        t2 = time.monotonic()
        if keep is None or k in keep:
            answers[k] = out
        lap.append(t2 - t1)
        stats.append(dict(driver.last_sweep_stats))
        if tracer and tracer.running:
            tracer.sweeps += 1
        if tracer and tracer.running and t2 - t0 >= tracer.max_s:
            tracer.stop()
        if t2 - t0 >= seconds:
            break
    if tracer and tracer.running:
        tracer.stop()
    elapsed = time.monotonic() - t0
    pauses.stop()
    answers[len(lap) - 1] = out
    after = compilestats.get_stats().snapshot(limit=0)
    return {"window_s": elapsed, "sweeps": len(lap), "sweep_s": lap,
            "gc_full": chip.pauses_in(pauses.events, t0, t0 + elapsed),
            "sweep_stats": stats, "sweep_parts": parts, "answers": answers,
            "compilez_before": before, "compilez_after": after}


class Tracer:
    """jax's profiler around the first TRACE_MAX_S of the window."""

    def __init__(self, trace_dir: str, max_s: float = TRACE_MAX_S):
        self.dir, self.max_s = trace_dir, max_s
        self.running = False
        self.window_s = 0.0
        self.sweeps = 0

    def start(self):
        import jax

        from lib import trace

        jax.profiler.start_trace(self.dir,
                                 profiler_options=trace.start_options())
        self.running, self._t0 = True, time.monotonic()

    def stop(self):
        import jax

        # the traced window ends here; writing the trace out takes seconds
        self.window_s = time.monotonic() - self._t0
        jax.profiler.stop_trace()
        self.running = False

    def reduce(self):
        from lib import trace

        out = trace.reduce_dir(self.dir, self.window_s)
        if out is not None:
            out["sweeps"] = self.sweeps
        return out


def child(spec: dict) -> int:
    device = chip.device_or_die(spec["platform"])
    cfg, traffic = spec["config"], spec.get("traffic") or {}
    cap = cfg["violations_limit"]
    t = {}
    t0 = time.monotonic()
    client, driver, constraints, pods = load_cluster(spec)
    t["ingest_s"] = time.monotonic() - t0
    if spec["mode"] == "snapshot":
        return child_snapshot(spec, client, driver, device, t)

    # warm: the first full sweep, the background work it starts, then a
    # few churn steps through the window's own loop (the delta path)
    t0 = time.monotonic()
    client.audit_capped(cap)
    settle()
    t["first_sweep_s"] = time.monotonic() - t0
    max_steps = WARM_STEPS + 8 + int(spec["seconds"] * traffic.get(
        "max_steps_per_s", 4))
    steps = corpus.churn_steps(cfg, traffic, spec["seed"], max_steps)
    warm = window(client, driver, steps[:WARM_STEPS], 1e9, cap)
    keep = sampled(spec["seed"], max_steps - WARM_STEPS)
    setup_s = time.time() - spec["t_start"]

    tracer = Tracer(os.path.join(spec["work"], "trace")) \
        if spec["trace"] else None
    w = window(client, driver, steps[WARM_STEPS:], spec["seconds"], cap,
               tracer=tracer, keep=keep)
    peak = chip.memory_peak_bytes()
    reduced = tracer.reduce() if tracer else None
    n = w["sweeps"]
    answers = {k: sweep_record(*a) for k, a in warm.pop("answers").items()}
    answers.update({WARM_STEPS + k: sweep_record(*a)
                    for k, a in w.pop("answers").items()})
    del client, driver
    t0 = time.monotonic()
    checks = compare_sweeps(constraints, pods, steps[:WARM_STEPS + n],
                            answers, cap)
    t["reference_s"] = time.monotonic() - t0
    procs.write_json(spec["result"], {
        "device": dict(device, memory_peak_bytes=peak),
        "setup_s": setup_s, "timings": t, "window": w, "trace": reduced,
        "attempted": n, "failed": checks["sweeps_wrong"],
        "compared": compared_of(checks, len(answers)),
        "notes": checks["first_faults"], "sizes": cfg,
        "rows_per_step": traffic["rows_per_step"],
        "warm_sweep_stats": warm["sweep_stats"],
    })
    return 0


def child_snapshot(spec, client, driver, device, t) -> int:
    """The audit-role process a webhook replica inherits from: settle
    the vocabulary as a started pod does (its routing calibration
    interns ~1,100 strings, and the vocabulary's bucket keys every
    executable), sweep once, write the sealed snapshot."""
    from gatekeeper_tpu.snapshot import Snapshotter

    t0 = time.monotonic()
    driver.calibrate_routing()
    client.audit_capped(spec["config"]["violations_limit"])
    settle()
    t["sweep_s"] = time.monotonic() - t0
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


if __name__ == "__main__":
    if len(sys.argv) != 3 or sys.argv[1] != "--child":
        sys.exit("usage: audit.py --child <spec.json>")
    sys.exit(child(procs.read_json(sys.argv[2])))
