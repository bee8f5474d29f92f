"""The benchmark's data, all of it from --seed.

`make_templates` and `make_pods` are copies of the generators in
gatekeeper_tpu/util/synthetic.py (six policy families, one constraint
per template; Pods of which a stated share trips at least one family),
kept here so that a later change to the program cannot move the
yardstick.  `review_pods` is chip_smoke.py's: unique Pods of one shape
class, because every padded slot width of a review batch keys a
compiled executable (PERF.md, Findings of PR 21).  The rego text is the
policy the system under test is handed; benchmark/lib/reference.py
states the same six policies in plain Python.
"""

from __future__ import annotations

import json
import random

FAMILIES = ["labelreq", "privflag", "hostflags", "portrange",
            "imageprefix", "fieldkeys"]

_REGO = {
    "labelreq": """
package {pkg}

violation[{{"msg": msg, "details": {{"missing": missing}}}}] {{
  have := {{k | input.review.object.metadata.labels[k]}}
  want := {{k | k := input.parameters.required[_]}}
  missing := want - have
  count(missing) > 0
  msg := sprintf("missing required labels: %v", [missing])
}}
""",
    "privflag": """
package {pkg}

workloads[c] {{
  c := input.review.object.spec.containers[_]
}}

workloads[c] {{
  c := input.review.object.spec.initContainers[_]
}}

violation[{{"msg": msg}}] {{
  c := workloads[_]
  c.securityContext.privileged
  msg := sprintf("privileged container forbidden: %v", [c.name])
}}
""",
    "hostflags": """
package {pkg}

uses_host_namespace(o) {{
  o.spec.hostPID
}}

uses_host_namespace(o) {{
  o.spec.hostIPC
}}

violation[{{"msg": msg}}] {{
  uses_host_namespace(input.review.object)
  msg := sprintf("host namespaces forbidden: %v", [input.review.object.metadata.name])
}}
""",
    "portrange": """
package {pkg}

bad_port(o) {{
  p := o.spec.containers[_].ports[_].hostPort
  p < input.parameters.low
}}

bad_port(o) {{
  p := o.spec.containers[_].ports[_].hostPort
  p > input.parameters.high
}}

violation[{{"msg": msg}}] {{
  bad_port(input.review.object)
  msg := sprintf("hostPort outside allowed range [%v, %v]", [input.parameters.low, input.parameters.high])
}}
""",
    "imageprefix": """
package {pkg}

violation[{{"msg": msg}}] {{
  c := input.review.object.spec.containers[_]
  ok := [hit | p = input.parameters.prefixes[_]; hit = startswith(c.image, p)]
  not any(ok)
  msg := sprintf("image %v not from an allowed registry %v", [c.image, input.parameters.prefixes])
}}
""",
    "fieldkeys": """
package {pkg}

allowed(fields) {{
  input.parameters.kinds[_] == "*"
}}

allowed(fields) {{
  allow := {{k | k = input.parameters.kinds[_]}}
  extra := fields - allow
  count(extra) == 0
}}

violation[{{"msg": msg}}] {{
  fields := {{k | input.review.object.spec.volumes[_][k]; k != "name"}}
  not allowed(fields)
  msg := sprintf("volume types %v not allowed", [fields])
}}
""",
}


def _params(family: str, rng: random.Random) -> dict:
    # compliant Pods satisfy every constraint clone: the allowlists always
    # hold the values the good Pods use
    if family == "labelreq":
        return {"required": rng.sample(
            ["owner", "team", "env", "cost", "tier"], 2)}
    if family == "portrange":
        return {"low": rng.choice([1, 80, 100]),
                "high": rng.choice([30000, 60000])}
    if family == "imageprefix":
        return {"prefixes": ["registry.corp/"] + rng.sample(
            ["gcr.io/prod/", "docker.io/library/", "quay.io/app/"], 2)}
    if family == "fieldkeys":
        return {"kinds": ["emptyDir"] + rng.sample(
            ["configMap", "secret", "projected"], 2)}
    return {}


def make_templates(n: int, seed: int):
    """n templates cycling the families (each its own CRD kind) and one
    constraint per template."""
    rng = random.Random(seed)
    templates, constraints = [], []
    for i in range(n):
        family = FAMILIES[i % len(FAMILIES)]
        kind = f"Bench{family.capitalize()}{i}"
        templates.append({
            "apiVersion": "templates.gatekeeper.sh/v1beta1",
            "kind": "ConstraintTemplate",
            "metadata": {"name": kind.lower()},
            "spec": {
                "crd": {"spec": {"names": {"kind": kind}}},
                "targets": [{
                    "target": "admission.k8s.gatekeeper.sh",
                    "rego": _REGO[family].format(pkg=f"bench{family}{i}"),
                }],
            },
        })
        constraints.append({
            "apiVersion": "constraints.gatekeeper.sh/v1beta1",
            "kind": kind,
            "metadata": {"name": f"c-{kind.lower()}"},
            "spec": {
                "match": {"kinds": [{"apiGroups": [""], "kinds": ["Pod"]}]},
                "parameters": _params(family, rng),
            },
        })
    return templates, constraints


def make_pods(n: int, seed: int, violation_rate: float = 0.05) -> list:
    """Pods of which about violation_rate trip at least one family."""
    rng = random.Random(seed)
    pods = []
    for i in range(n):
        bad = rng.random() < violation_rate
        containers = []
        for j in range(rng.randint(1, 3)):
            ctr = {
                "name": f"app-{j}",
                "image": ("evil.io/x:latest" if bad and rng.random() < 0.5
                          else "registry.corp/svc:" + str(rng.randint(1, 40))),
            }
            if bad and rng.random() < 0.3:
                ctr["securityContext"] = {"privileged": True}
            if rng.random() < 0.3:
                ctr["ports"] = [{"hostPort": 31337
                                 if bad and rng.random() < 0.5 else 8080}]
            containers.append(ctr)
        spec = {"containers": containers}
        if bad and rng.random() < 0.2:
            spec["hostPID"] = True
        if rng.random() < 0.3:
            spec["volumes"] = [{
                "name": "v0",
                ("nfs" if bad and rng.random() < 0.4 else "emptyDir"): {}}]
        labels = {"owner": "core", "team": "plat", "env": "prod",
                  "cost": "cc1", "tier": "t1"}
        if bad and rng.random() < 0.4:
            labels.pop(rng.choice(list(labels)))
        pods.append({
            "apiVersion": "v1", "kind": "Pod",
            "metadata": {"name": f"pod-{i}", "namespace": f"ns-{i % 50}",
                         "labels": labels},
            "spec": spec,
        })
    return pods


def seed32(seed: int, salt: int) -> int:
    """A 31-bit stream seed from any --seed (the driver's are large)."""
    return (int(seed) * 1_000_003 + salt * 7919) % (2 ** 31 - 1)


def cluster(config: dict, seed: int):
    """(templates, constraints, pods) of a configuration's cluster."""
    templates, constraints = make_templates(
        config["templates"], seed32(seed, 0))
    pods = make_pods(config["resources"], seed32(seed, 1),
                     config["violating_share"])
    return templates, constraints, pods


def churn_steps(config: dict, traffic: dict, seed: int, steps: int) -> list:
    """steps lists of (row index, replacement Pod): each step replaces
    rows_per_step Pods drawn from the seed by fresh ones of the same
    generator (some flip to violating, some back), under the name and
    namespace of the Pod replaced, so that the store path stays."""
    per = traffic["rows_per_step"]
    rng = random.Random(seed32(seed, 2))
    fresh = make_pods(per * steps, seed32(seed, 3),
                      traffic.get("violating_share",
                                  config["violating_share"]))
    out = []
    for s in range(steps):
        rows = sorted(rng.sample(range(config["resources"]), per))
        step = []
        for k, i in enumerate(rows):
            pod = fresh[s * per + k]
            pod["metadata"]["name"] = f"pod-{i}"
            pod["metadata"]["namespace"] = f"ns-{i % 50}"
            step.append((i, pod))
        out.append(step)
    return out


def _compliant(p: dict) -> bool:
    spec = p["spec"]
    return not spec.get("hostPID") and all(
        "nfs" not in v for v in spec.get("volumes", ())) and all(
        c["image"].startswith("registry.corp/")
        and "securityContext" not in c
        and all(pt.get("hostPort") == 8080 for pt in c.get("ports", ()))
        for c in spec["containers"])


def review_pods(n: int, seed: int, violating_share: float,
                tag: str) -> list:
    """n unique Pods of ONE shape class (3 containers, 5 labels, at most
    one port): any split a batcher makes of them lands on the executable
    compiled for that row bucket.  About violating_share of them are
    denied; which, and in what order, comes from the seed."""
    rng = random.Random(seed32(seed, 4))
    good, bad = [], []
    want_bad = int(n * violating_share) + 8
    batch = 0
    while len(good) < n or len(bad) < want_bad:
        for p in make_pods(4096, seed32(seed, 100 + batch), 0.5):
            ctrs = p["spec"]["containers"]
            if (len(ctrs) == 3 and len(p["metadata"]["labels"]) == 5
                    and sum(len(c.get("ports", ())) for c in ctrs) <= 1):
                (good if _compliant(p) else bad).append(p)
        batch += 1
    out = []
    gi = bi = 0
    for i in range(n):
        if rng.random() < violating_share:
            # the pool of denied shapes is smaller than the demand: reuse
            # a shape under a new name (the name makes the review unique)
            p = json.loads(json.dumps(bad[bi % len(bad)]))
            bi += 1
        else:
            p = json.loads(json.dumps(good[gi % len(good)]))
            gi += 1
        p["metadata"]["name"] = f"{tag}-{i}"
        out.append(p)
    return out


def admission_body(pod: dict, uid: str) -> bytes:
    return json.dumps({"request": {
        "uid": uid,
        "kind": {"group": "", "version": "v1", "kind": "Pod"},
        "name": pod["metadata"]["name"],
        "namespace": pod["metadata"]["namespace"],
        "operation": "CREATE",
        "userInfo": {"username": "benchmark"},
        "object": pod,
    }}).encode()
