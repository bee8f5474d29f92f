#!/usr/bin/env python3
"""The comparison's control at a cell's own size: the plain reference
put in the program's place with one stated guarantee broken, which has
to come out as not correct.  The benchmark's own runs never run this;
tests/benchmark/ keeps it at a size a test run can hold.

    python3 benchmark/control.py --workload <cell> --seeds 1,2,3 [--steps 12 | --reviews 20000]

audit role:   "no stale answer" broken — every sweep answered with the
              audit as it stood one interval earlier.
webhook role: "the policy set in force" broken — the newest constraint
              answered under its predecessor's name (a replica that has
              not synced the last constraint edit).
Prints per seed the numbers run.py compares, beside their limits.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import run as harness  # noqa: E402
from lib import corpus, loadgen, reference  # noqa: E402


def reference_answer(ref, cap: int) -> dict:
    """The reference's own capped audit, as a sweep's answer."""
    kept, totals = [], {}
    for ci, ck in enumerate(ref.policies.keys):
        hits = [(k, m) for k, ms in sorted(ref.per_c[ci].items())
                for m in sorted(ms)]
        totals[ck] = (len(hits), "exact")
        kept += [(*ck, k[0], k[1], m) for k, m in hits[:cap]]
    return {"totals": totals, "kept": kept}


def audit_control(cell: dict, seed: int, n_steps: int) -> dict:
    cfg, cap = cell["config"], cell["config"]["violations_limit"]
    _t, constraints, pods = corpus.cluster(cfg, seed)
    steps = corpus.churn_steps(cfg, cell["traffic"], seed, n_steps)
    ref = reference.AuditReference(
        reference.Policies(constraints, corpus.FAMILIES), pods)
    answers = [reference_answer(ref, cap)]
    for step in steps:
        for _i, pod in step:
            ref.put(pod)
        answers.append(reference_answer(ref, cap))
    from roles import audit

    sound = audit.compare_sweeps(constraints, pods, steps, answers[1:], cap)
    stale = audit.compare_sweeps(constraints, pods, steps, answers[:-1], cap)
    return {"sound": {k: sound[k] for k in ("sweeps_compared",
                                            "sweeps_wrong")},
            "control": {k: stale[k] for k in ("sweeps_compared",
                                              "sweeps_wrong")},
            "limit": {"sweeps_wrong": 0}}


def webhook_control(cell: dict, seed: int, n_reviews: int) -> dict:
    cfg, tr = cell["config"], cell["traffic"]
    _t, constraints = corpus.make_templates(
        cfg["templates"], corpus.seed32(seed, 0))
    bodies = loadgen.build_bodies({
        "bodies": n_reviews, "seed": seed, "tag": f"bench-{seed}",
        "violating_share": tr.get("violating_share",
                                  cfg["violating_share"])})
    true = reference.Policies(constraints, corpus.FAMILIES)
    stale = reference.Policies(
        constraints[:-1] + [dict(constraints[-1], metadata={
            "name": constraints[-2]["metadata"]["name"]})], corpus.FAMILIES)

    def answer(pol, body):
        req = json.loads(body)["request"]
        allowed, msgs = pol.verdict(req["object"])
        out = {"uid": req["uid"], "allowed": allowed}
        if not allowed:
            out["status"] = {"code": 403, "message": "\n".join(msgs)}
        return json.dumps({"response": out}).encode()

    res = {}
    for name, pol in (("sound", true), ("control", stale)):
        wrong = sum(reference.compare_verdict(
            true, b, 200, answer(pol, b)) is not None for b in bodies)
        res[name] = {"reviews_compared": len(bodies),
                     "verdicts_wrong": wrong}
    res["limit"] = {"verdicts_wrong": 0}
    return res


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="1,2,3")
    ap.add_argument("--steps", type=int, default=12)
    ap.add_argument("--reviews", type=int, default=20000)
    args = ap.parse_args(argv)
    cell = harness.load_cell(args.workload)
    ok = True
    for seed in (int(s) for s in args.seeds.split(",")):
        if cell["config"]["role"] == "audit":
            r = audit_control(cell, seed, args.steps)
            key = "sweeps_wrong"
        else:
            r = webhook_control(cell, seed, args.reviews)
            key = "verdicts_wrong"
        ok = ok and r["sound"][key] == 0 and r["control"][key] > 0
        print(json.dumps({"workload": args.workload, "seed": seed, **r}),
              flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
