"""The agilebank deployment's data, all of it from --seed.

Upstream Gatekeeper's demo/agilebank at v3.1.0-rc.1: four
ConstraintTemplates, four constraints, and sync.yaml (Namespaces, Pods
and Services replicated into data.inventory).  /root/reference is absent
here and on the chip machine, so the Rego below is written from knowledge
of that directory (configs/agilebank4x111k-audit.json lists every such
value under `assumed`).  The Rego text is the policy the system under
test is handed; benchmark/lib/agilebank_reference.py states the same
four policies in plain Python.  Nothing here imports the program.

The cluster is one list of objects, Namespaces first, then Services,
then Pods: an object's index in it is its row in a churn step.
"""

from __future__ import annotations

import random

from .corpus import seed32

_REGO = {
    "K8sAllowedRepos": """
package k8sallowedrepos

violation[{"msg": msg}] {
  container := input.review.object.spec.containers[_]
  satisfied := [good | repo = input.parameters.repos[_] ; good = startswith(container.image, repo)]
  not any(satisfied)
  msg := sprintf("container <%v> has an invalid image repo <%v>, allowed repos are %v", [container.name, container.image, input.parameters.repos])
}
""",
    "K8sContainerLimits": """
package k8scontainerlimits

missing(obj, field) = true {
  not obj[field]
}

missing(obj, field) = true {
  obj[field] == ""
}

canonify_cpu(orig) = new {
  is_number(orig)
  new := orig * 1000
}

canonify_cpu(orig) = new {
  not is_number(orig)
  endswith(orig, "m")
  new := to_number(replace(orig, "m", ""))
}

canonify_cpu(orig) = new {
  not is_number(orig)
  not endswith(orig, "m")
  re_match("^[0-9]+$", orig)
  new := to_number(orig) * 1000
}

# 10 ** 21
mem_multiple("E") = 1000000000000000000000 { true }

# 10 ** 18
mem_multiple("P") = 1000000000000000000 { true }

# 10 ** 15
mem_multiple("T") = 1000000000000000 { true }

# 10 ** 12
mem_multiple("G") = 1000000000000 { true }

# 10 ** 9
mem_multiple("M") = 1000000000 { true }

# 10 ** 6
mem_multiple("k") = 1000000 { true }

# 10 ** 3
mem_multiple("") = 1000 { true }

# Kubernetes accepts millibyte precision when it probably shouldn't.
# https://github.com/kubernetes/kubernetes/issues/28741
# 10 ** 0
mem_multiple("m") = 1 { true }

# 1000 * 2 ** 10
mem_multiple("Ki") = 1024000 { true }

# 1000 * 2 ** 20
mem_multiple("Mi") = 1048576000 { true }

# 1000 * 2 ** 30
mem_multiple("Gi") = 1073741824000 { true }

# 1000 * 2 ** 40
mem_multiple("Ti") = 1099511627776000 { true }

# 1000 * 2 ** 50
mem_multiple("Pi") = 1125899906842624000 { true }

# 1000 * 2 ** 60
mem_multiple("Ei") = 1152921504606846976000 { true }

get_suffix(mem) = suffix {
  not is_string(mem)
  suffix := ""
}

get_suffix(mem) = suffix {
  is_string(mem)
  count(mem) > 0
  suffix := substring(mem, count(mem) - 1, -1)
  mem_multiple(suffix)
}

get_suffix(mem) = suffix {
  is_string(mem)
  count(mem) > 1
  suffix := substring(mem, count(mem) - 2, -1)
  mem_multiple(suffix)
}

get_suffix(mem) = suffix {
  is_string(mem)
  count(mem) > 1
  not mem_multiple(substring(mem, count(mem) - 1, -1))
  not mem_multiple(substring(mem, count(mem) - 2, -1))
  suffix := ""
}

get_suffix(mem) = suffix {
  is_string(mem)
  count(mem) == 1
  not mem_multiple(substring(mem, count(mem) - 1, -1))
  suffix := ""
}

get_suffix(mem) = suffix {
  is_string(mem)
  count(mem) == 0
  suffix := ""
}

canonify_mem(orig) = new {
  is_number(orig)
  new := orig * 1000
}

canonify_mem(orig) = new {
  not is_number(orig)
  suffix := get_suffix(orig)
  raw := replace(orig, suffix, "")
  re_match("^[0-9]+$", raw)
  new := to_number(raw) * mem_multiple(suffix)
}

violation[{"msg": msg}] {
  general_violation[{"msg": msg, "field": "containers"}]
}

violation[{"msg": msg}] {
  general_violation[{"msg": msg, "field": "initContainers"}]
}

general_violation[{"msg": msg, "field": field}] {
  container := input.review.object.spec[field][_]
  cpu_orig := container.resources.limits.cpu
  not canonify_cpu(cpu_orig)
  msg := sprintf("container <%v> cpu limit <%v> could not be parsed", [container.name, cpu_orig])
}

general_violation[{"msg": msg, "field": field}] {
  container := input.review.object.spec[field][_]
  mem_orig := container.resources.limits.memory
  not canonify_mem(mem_orig)
  msg := sprintf("container <%v> memory limit <%v> could not be parsed", [container.name, mem_orig])
}

general_violation[{"msg": msg, "field": field}] {
  container := input.review.object.spec[field][_]
  not container.resources
  msg := sprintf("container <%v> has no resource limits", [container.name])
}

general_violation[{"msg": msg, "field": field}] {
  container := input.review.object.spec[field][_]
  not container.resources.limits
  msg := sprintf("container <%v> has no resource limits", [container.name])
}

general_violation[{"msg": msg, "field": field}] {
  container := input.review.object.spec[field][_]
  missing(container.resources.limits, "cpu")
  msg := sprintf("container <%v> has no cpu limit", [container.name])
}

general_violation[{"msg": msg, "field": field}] {
  container := input.review.object.spec[field][_]
  missing(container.resources.limits, "memory")
  msg := sprintf("container <%v> has no memory limit", [container.name])
}

general_violation[{"msg": msg, "field": field}] {
  container := input.review.object.spec[field][_]
  cpu_orig := container.resources.limits.cpu
  cpu := canonify_cpu(cpu_orig)
  max_cpu_orig := input.parameters.cpu
  max_cpu := canonify_cpu(max_cpu_orig)
  cpu > max_cpu
  msg := sprintf("container <%v> cpu limit <%v> is higher than the maximum allowed of <%v>", [container.name, cpu_orig, max_cpu_orig])
}

general_violation[{"msg": msg, "field": field}] {
  container := input.review.object.spec[field][_]
  mem_orig := container.resources.limits.memory
  mem := canonify_mem(mem_orig)
  max_mem_orig := input.parameters.memory
  max_mem := canonify_mem(max_mem_orig)
  mem > max_mem
  msg := sprintf("container <%v> memory limit <%v> is higher than the maximum allowed of <%v>", [container.name, mem_orig, max_mem_orig])
}
""",
    "K8sRequiredLabels": """
package k8srequiredlabels

get_message(parameters, _default) = msg {
  not parameters.message
  msg := _default
}

get_message(parameters, _default) = msg {
  msg := parameters.message
}

violation[{"msg": msg, "details": {"missing_labels": missing}}] {
  provided := {label | input.review.object.metadata.labels[label]}
  required := {label | label := input.parameters.labels[_].key}
  missing := required - provided
  count(missing) > 0
  def_msg := sprintf("you must provide labels: %v", [missing])
  msg := get_message(input.parameters, def_msg)
}

violation[{"msg": msg}] {
  value := input.review.object.metadata.labels[key]
  expected := input.parameters.labels[_]
  expected.key == key
  # do not match if allowedRegex is not defined, or is an empty string
  expected.allowedRegex != ""
  not re_match(expected.allowedRegex, value)
  def_msg := sprintf("Label <%v: %v> does not satisfy allowed regex: %v", [key, value, expected.allowedRegex])
  msg := get_message(input.parameters, def_msg)
}
""",
    "K8sUniqueServiceSelector": """
package k8suniqueserviceselector

make_apiversion(kind) = apiVersion {
  g := kind.group
  v := kind.version
  g != ""
  apiVersion = sprintf("%v/%v", [g, v])
}

make_apiversion(kind) = apiVersion {
  kind.group == ""
  apiVersion = kind.version
}

identical(obj, review) {
  obj.metadata.namespace == review.namespace
  obj.metadata.name == review.name
  obj.kind == review.kind.kind
  obj.apiVersion == make_apiversion(review.kind)
}

flatten_selector(obj) = flattened {
  selectors := [s | s = concat(":", [key, val]); val = obj.spec.selector[key]]
  flattened := concat(",", sort(selectors))
}

violation[{"msg": msg}] {
  input.review.kind.kind == "Service"
  input.review.kind.version == "v1"
  input.review.kind.group == ""
  input_selector := flatten_selector(input.review.object)
  other := data.inventory.namespace[namespace][_]["Service"][name]
  not identical(other, input.review)
  other_selector := flatten_selector(other)
  input_selector == other_selector
  msg := sprintf("same selector as service <%v> in namespace <%v>", [name, namespace])
}
""",
}

OWNER_MESSAGE = ("All namespaces must have an `owner` label that points to "
                 "your company username")

# constraints/<file>: (kind, metadata.name, match, parameters)
_CONSTRAINTS = [
    ("K8sContainerLimits", "container-must-have-limits",
     {"kinds": [{"apiGroups": [""], "kinds": ["Pod"]}]},
     {"cpu": "200m", "memory": "1Gi"}),
    ("K8sRequiredLabels", "all-must-have-owner",
     {"kinds": [{"apiGroups": [""], "kinds": ["Namespace"]}]},
     {"message": OWNER_MESSAGE,
      "labels": [{"key": "owner",
                  "allowedRegex": "^[a-zA-Z]+.agilebank.demo$"}]}),
    ("K8sAllowedRepos", "prod-repo-is-openpolicyagent",
     {"kinds": [{"apiGroups": [""], "kinds": ["Pod"]}],
      "namespaces": ["production"]},
     {"repos": ["openpolicyagent"]}),
    ("K8sUniqueServiceSelector", "unique-service-selector",
     {"kinds": [{"apiGroups": [""], "kinds": ["Service"]}]},
     None),
]

# sync.yaml: what the audit role replicates into data.inventory
SYNC = {
    "apiVersion": "config.gatekeeper.sh/v1alpha1", "kind": "Config",
    "metadata": {"name": "config", "namespace": "gatekeeper-system"},
    "spec": {"sync": {"syncOnly": [
        {"group": "", "version": "v1", "kind": "Namespace"},
        {"group": "", "version": "v1", "kind": "Pod"},
        {"group": "", "version": "v1", "kind": "Service"}]}},
}


def template(kind: str) -> dict:
    """templates/<kind.lower()>_template.yaml (the limits template's
    file is spelt k8scontainterlimits upstream; its name is not)."""
    return {
        "apiVersion": "templates.gatekeeper.sh/v1beta1",
        "kind": "ConstraintTemplate",
        "metadata": {"name": kind.lower()},
        "spec": {
            "crd": {"spec": {"names": {"kind": kind}}},
            "targets": [{"target": "admission.k8s.gatekeeper.sh",
                         "rego": _REGO[kind]}],
        },
    }


def make_templates():
    """(the four templates, the four constraints)."""
    constraints = []
    for kind, name, match, params in _CONSTRAINTS:
        spec = {"match": match}
        if params is not None:
            spec["parameters"] = params
        constraints.append({
            "apiVersion": "constraints.gatekeeper.sh/v1beta1",
            "kind": kind, "metadata": {"name": name}, "spec": spec})
    return [template(k) for k in sorted(_REGO)], constraints


# ---------------------------------------------------------------------------
# the cluster
# ---------------------------------------------------------------------------

_APPS = [f"{a}{b}" for a in (
    "ledger", "payments", "cards", "loans", "fraud", "kyc", "auth",
    "statements", "fx", "notify", "search", "quotes", "risk", "audit",
    "batch", "gateway", "portal", "vault", "rates", "branch")
    for b in ("", "-api", "-web", "-worker", "-db", "-cache", "-cron",
              "-proxy", "-etl", "-admin", "-edge", "-queue", "-sync",
              "-report", "-test")]                      # 300 names
_TIERS = ["frontend", "backend", "data", "edge", "batch", "internal"]
_VERSIONS = [f"v{i}" for i in range(1, 41)]
_OWNERS = ["alice", "bob", "carol", "dave", "erin", "frank", "grace",
           "heidi", "ivan", "judy", "mallory", "niaj", "olivia", "peggy"]
_IMAGES = ["nginx:1.19", "redis:6", "registry.corp/app:3",
           "gcr.io/prod/svc:12", "busybox", "quay.io/app/job:7"]
_PROD_IMAGES = ["openpolicyagent/opa:0.9.2",
                "openpolicyagent/gatekeeper:v3.1.0-rc.1",
                "openpolicyagent/kube-mgmt:0.11"]
_BAD_PROD_IMAGES = ["nginx:1.19", "docker.io/library/redis:6",
                    "openpolicy/opa:latest"]
_GOOD_LIMITS = [("50m", "128Mi"), ("100m", "256Mi"), ("200m", "512Mi"),
                ("100m", "1Gi"), ("150m", "900M"), ("200m", "1024Mi")]
# what an unlimited or over-limited container holds under `resources`
# (None: the key is absent)
_BAD_RESOURCES = [
    None, {}, {"requests": {"cpu": "10m"}},
    {"limits": {"memory": "256Mi"}}, {"limits": {"cpu": "100m"}},
    {"limits": {"cpu": "500m", "memory": "256Mi"}},
    {"limits": {"cpu": "1", "memory": "512Mi"}},
    {"limits": {"cpu": 2, "memory": "512Mi"}},
    {"limits": {"cpu": "100m", "memory": "2Gi"}},
    {"limits": {"cpu": "100m", "memory": "4G"}},
    {"limits": {"cpu": "0.5", "memory": "512Mi"}},
    {"limits": {"cpu": "100m", "memory": "1.5Gi"}},
    {"limits": {"cpu": "250m", "memory": "8Gi"}},
]


def namespace_name(i: int) -> str:
    return "production" if i == 0 else f"team-{i}"


def make_namespace(i: int, rng: random.Random, bad_share: float) -> dict:
    labels = {"owner": rng.choice(_OWNERS) + ".agilebank.demo"}
    if rng.random() < bad_share:
        if rng.random() < 0.5:
            labels = {"team": "unknown"}
        else:
            labels["owner"] = rng.choice(
                ["user1.agilebank.demo", "alice.example.com", "nobody"])
    return {"apiVersion": "v1", "kind": "Namespace",
            "metadata": {"name": namespace_name(i), "labels": labels}}


def draw_selector(rng: random.Random) -> dict:
    """One selector of the cluster's generator: an `app` of the few
    hundred app names, with a tier and a version beside it, in one to
    three pairs."""
    sel = {"app": rng.choice(_APPS)}
    pairs = rng.randint(1, 3)
    if pairs == 2:
        if rng.random() < 0.5:
            sel["tier"] = rng.choice(_TIERS)
        else:
            sel["version"] = rng.choice(_VERSIONS)
    elif pairs == 3:
        sel["tier"] = rng.choice(_TIERS)
        sel["version"] = rng.choice(_VERSIONS)
    return sel


def make_service(i: int, namespace: str, selector) -> dict:
    spec = {"ports": [{"port": 80, "targetPort": 8080}]}
    if selector is not None:
        spec["selector"] = dict(selector)
    return {"apiVersion": "v1", "kind": "Service",
            "metadata": {"name": f"svc-{i}", "namespace": namespace},
            "spec": spec}


def make_pod(i: int, namespace: str, rng: random.Random, cfg: dict,
             labels: dict) -> dict:
    prod = namespace == "production"
    bad_repo = prod and rng.random() < cfg["prod_other_repo_share"]
    unlimited = rng.random() < cfg["unlimited_share"]
    containers = []
    n = rng.randint(1, 2)
    bad_at = rng.randrange(n)
    for j in range(n):
        if prod:
            image = rng.choice(_BAD_PROD_IMAGES if bad_repo and j == bad_at
                               else _PROD_IMAGES)
        else:
            image = rng.choice(_IMAGES)
        c = {"name": f"c{j}", "image": image}
        if unlimited and j == bad_at:
            res = rng.choice(_BAD_RESOURCES)
            if res is not None:
                c["resources"] = {k: dict(v) for k, v in res.items()}
        else:
            cpu, mem = rng.choice(_GOOD_LIMITS)
            c["resources"] = {"limits": {"cpu": cpu, "memory": mem}}
        containers.append(c)
    return {"apiVersion": "v1", "kind": "Pod",
            "metadata": {"name": f"pod-{i}", "namespace": namespace,
                         "labels": dict(labels)},
            "spec": {"containers": containers}}


# of the Services a step re-points: onto another Service's selector, and
# out of a pair (the rest move to a selector nobody holds)
COLLIDE_SHARE = 0.15
LEAVE_SHARE = 0.15


def flatten(selector) -> str:
    """The join key as the generator tracks it (string pairs only)."""
    return ",".join(sorted(f"{k}:{v}" for k, v in (selector or {}).items()))


class Services:
    """The Services' selectors as they stand, for the generator: who
    holds what.  The Services without a selector are the last by index:
    upstream's flatten_selector gives each the empty key, so each of
    them violates once per other such Service, and the first of them in
    row order would fill the cap alone, with violations no step ever
    changes; behind the Services the traffic re-points, the kept
    violations are theirs."""

    def __init__(self, cfg: dict, rng: random.Random):
        n, n_ns = cfg["services"], cfg["namespaces"]
        self.rng = rng
        self.namespace = [namespace_name(rng.randrange(n_ns))
                          for _ in range(n)]
        self.selector = [None] * n          # None: no selector
        self.key = [None] * n               # its flattened form
        self.holders = {}                   # flattened -> set of indices
        n_bare = int(n * cfg["no_selector_share"])
        n_pair = int(n * cfg["paired_share"]) // 2 * 2
        n_group = int(n * cfg["grouped_share"])
        rest = list(range(n - n_bare))
        rng.shuffle(rest)
        k = 0
        while k < n_pair:                    # two Services, one selector
            self._share(rest[k:k + 2])
            k += 2
        end = n_pair + n_group
        while k < end:                       # groups of 3 to 5
            size = min(rng.randint(3, 5), end - k)
            if size < 3:                     # a remainder joins the last
                size = end - k
            self._share(rest[k:k + size])
            k += size
        for i in rest[k:]:
            self.point(i, self.fresh())
        self.bare = set(range(n - n_bare, n))

    def _share(self, members):
        sel = self.fresh()
        for i in members:
            self.point(i, sel)

    def fresh(self) -> dict:
        """A selector nobody holds, drawn as every other is."""
        while True:
            sel = draw_selector(self.rng)
            if flatten(sel) not in self.holders:
                return sel

    def point(self, i: int, selector: dict):
        old = self.key[i]
        if old is not None:
            self.holders[old].discard(i)
            if not self.holders[old]:
                del self.holders[old]
        self.selector[i] = dict(selector)
        self.key[i] = flatten(selector)
        self.holders.setdefault(self.key[i], set()).add(i)

    def group_of(self, i: int) -> set:
        return self.holders[self.key[i]]

    def paired(self) -> list:
        """The Services that share their selector with exactly one
        other."""
        return sorted(i for g in self.holders.values() if len(g) == 2
                      for i in g)

    def object(self, i: int) -> dict:
        return make_service(i, self.namespace[i], self.selector[i])


def _generate(config: dict, seed: int):
    """(objects, Services state, each Pod's namespace): the cluster as
    it stands before the first step."""
    n_ns, n_svc, n_pod = (config["namespaces"], config["services"],
                          config["pods"])
    rng = random.Random(seed32(seed, 11))
    objects = [make_namespace(i, rng, config["unowned_share"])
               for i in range(n_ns)]
    svcs = Services(config, random.Random(seed32(seed, 12)))
    objects += [svcs.object(i) for i in range(n_svc)]
    rng = random.Random(seed32(seed, 13))
    pod_ns = []
    for i in range(n_pod):
        ns = ("production" if rng.random() < config["production_share"]
              else namespace_name(1 + rng.randrange(n_ns - 1)))
        pod_ns.append(ns)
        sel = svcs.selector[rng.randrange(n_svc)] or {"app": "standalone"}
        objects.append(make_pod(i, ns, rng, config, sel))
    return objects, svcs, pod_ns


def cluster(config: dict, seed: int):
    """(templates, constraints, objects) of the configuration."""
    return deployment(config, {}, seed, 0)[:3]


def churn_steps(config: dict, traffic: dict, seed: int, steps: int) -> list:
    return deployment(config, traffic, seed, steps)[3]


def deployment(config: dict, traffic: dict, seed: int, steps: int):
    """(templates, constraints, objects, steps): the cluster as it
    stands before the first step, and steps lists of (index in
    `objects`, replacement).

    Each step replaces pods_per_step Pods by fresh ones of the same
    generator under the name and namespace of the Pod replaced, and
    re-points services_per_step Services that have a selector: each
    gets another selector under its own name and namespace, so every one
    moves its join key — COLLIDE_SHARE of them onto the selector of a
    Service that was alone with it (both now violate), LEAVE_SHARE out
    of a pair (the Service left behind stops violating), the rest to a
    selector nobody holds.  A Service without a selector has nothing to
    re-point and is left alone."""
    n_ns, n_svc, n_pod = (config["namespaces"], config["services"],
                          config["pods"])
    templates, constraints = make_templates()
    objects, svcs, pod_ns = _generate(config, seed)
    if not steps:
        return templates, constraints, objects, []
    rng = random.Random(seed32(seed, 14))
    per_svc, per_pod = traffic["services_per_step"], traffic["pods_per_step"]
    n_collide = round(per_svc * COLLIDE_SHARE)
    n_leave = round(per_svc * LEAVE_SHARE)
    with_selector = [i for i in range(n_svc) if i not in svcs.bare]
    out = []
    for _s in range(steps):
        step = []
        for i in sorted(rng.sample(range(n_pod), per_pod)):
            sel = svcs.selector[rng.randrange(n_svc)] or {"app": "standalone"}
            step.append((n_ns + n_svc + i,
                         make_pod(i, pod_ns[i], rng, config, sel)))
        # the Services re-pointed hold their selector alone (but for the
        # leavers below), so the colliding population stays level
        chosen = [i for i in rng.sample(with_selector,
                                        min(2 * per_svc, len(with_selector)))
                  if len(svcs.group_of(i)) == 1][:per_svc]
        paired = svcs.paired()
        leavers = rng.sample(paired, min(n_leave, len(paired)))
        # a leaver takes the place of one chosen Service, so the step
        # still re-points services_per_step of them
        movers = leavers + [i for i in chosen
                            if i not in leavers][:per_svc - len(leavers)]
        touched = set(movers)
        for k, i in enumerate(movers):
            if k >= len(leavers) and k < len(leavers) + n_collide:
                target = next((t for t in rng.sample(with_selector,
                                                    min(64, len(with_selector)))
                               if t not in touched
                               and len(svcs.group_of(t)) == 1), None)
                if target is not None:
                    touched.add(target)
                    svcs.point(i, svcs.selector[target])
                    continue
            svcs.point(i, svcs.fresh())
        step += [(n_ns + i, svcs.object(i)) for i in sorted(movers)]
        out.append(step)
    return templates, constraints, objects, out
