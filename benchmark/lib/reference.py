"""The plain reference: the six policies of benchmark/lib/corpus.py
stated in plain Python, and the comparisons that decide `correct`.

It imports nothing of the program and takes nothing the program made:
its inputs are the constraints and Pods the benchmark generated from
the seed, and the answers the timed path returned.  A message is the
text the policy's sprintf produces (sets print as {"a", "b"} sorted,
arrays as ["a", "b"] in order), so answers compare byte for byte.
"""

from __future__ import annotations

import json


def _fmt_set(xs) -> str:
    return "{" + ", ".join(json.dumps(x) for x in sorted(xs)) + "}"


def _fmt_list(xs) -> str:
    return "[" + ", ".join(json.dumps(x) for x in xs) + "]"


def _containers(pod: dict, init: bool) -> list:
    spec = pod.get("spec") or {}
    out = list(spec.get("containers") or ())
    if init:
        out += list(spec.get("initContainers") or ())
    return out


def violations(family: str, params: dict, pod: dict) -> frozenset:
    """The set of messages one policy of `family` with `params` raises
    on one Pod (a Rego violation set: equal messages are one)."""
    msgs = set()
    if family == "labelreq":
        have = set((pod.get("metadata") or {}).get("labels") or {})
        missing = set(params["required"]) - have
        if missing:
            msgs.add("missing required labels: " + _fmt_set(missing))
    elif family == "privflag":
        for c in _containers(pod, init=True):
            if (c.get("securityContext") or {}).get("privileged"):
                msgs.add(f"privileged container forbidden: {c['name']}")
    elif family == "hostflags":
        spec = pod.get("spec") or {}
        if spec.get("hostPID") or spec.get("hostIPC"):
            msgs.add("host namespaces forbidden: "
                     + pod["metadata"]["name"])
    elif family == "portrange":
        low, high = params["low"], params["high"]
        for c in _containers(pod, init=False):
            for pt in c.get("ports") or ():
                hp = pt.get("hostPort")
                if hp is not None and (hp < low or hp > high):
                    msgs.add("hostPort outside allowed range "
                             f"[{low}, {high}]")
    elif family == "imageprefix":
        prefixes = params["prefixes"]
        for c in _containers(pod, init=False):
            if not any(c["image"].startswith(p) for p in prefixes):
                msgs.add(f"image {c['image']} not from an allowed "
                         "registry " + _fmt_list(prefixes))
    elif family == "fieldkeys":
        kinds = params["kinds"]
        fields = set()
        for v in (pod.get("spec") or {}).get("volumes") or ():
            fields.update(k for k in v if k != "name")
        if "*" not in kinds and fields - set(kinds):
            msgs.add(f"volume types {_fmt_set(fields)} not allowed")
    else:
        raise ValueError(f"unknown policy family {family!r}")
    return frozenset(msgs)


class Policies:
    """The constraints of a cluster, grouped by (family, parameters):
    clones of one policy with equal parameters raise equal messages, so
    each distinct policy is evaluated once per Pod."""

    def __init__(self, constraints: list, families: list):
        self.keys = []      # (kind, name) per constraint
        self.group_of = []  # constraint index -> group index
        self.groups = []    # (family, params)
        index = {}
        for i, c in enumerate(constraints):
            family = families[i % len(families)]
            params = c["spec"].get("parameters") or {}
            gk = (family, json.dumps(params, sort_keys=True))
            if gk not in index:
                index[gk] = len(self.groups)
                self.groups.append((family, params))
            self.group_of.append(index[gk])
            self.keys.append((c["kind"], c["metadata"]["name"]))
        self.members = [[] for _ in self.groups]
        for ci, g in enumerate(self.group_of):
            self.members[g].append(ci)

    def evaluate(self, pod: dict) -> list:
        """[(constraint index, frozenset of messages)] for the
        constraints this Pod violates."""
        out = []
        for g, (family, params) in enumerate(self.groups):
            msgs = violations(family, params, pod)
            if msgs:
                out.extend((ci, msgs) for ci in self.members[g])
        return out

    def verdict(self, pod: dict) -> tuple:
        """(allowed, sorted deny messages as the webhook words them)."""
        msgs = sorted(
            f"[denied by {self.keys[ci][1]}] {m}"
            for ci, ms in self.evaluate(pod) for m in ms)
        return (not msgs, msgs)


class AuditReference:
    """The audit of a cluster, kept current as rows are replaced: per
    constraint the violating Pods and their messages."""

    def __init__(self, policies: Policies, pods: list):
        self.policies = policies
        self.by_pod = {}                      # pod key -> [(ci, msgs)]
        self.per_c = [dict() for _ in policies.keys]  # ci -> {key: msgs}
        self.n_viol = [0] * len(policies.keys)        # ci -> violations
        for p in pods:
            self.put(p)

    @staticmethod
    def key(pod: dict) -> tuple:
        return (pod["metadata"]["namespace"], pod["metadata"]["name"])

    def put(self, pod: dict):
        k = self.key(pod)
        for ci, msgs in self.by_pod.pop(k, ()):
            del self.per_c[ci][k]
            self.n_viol[ci] -= len(msgs)
        hits = self.policies.evaluate(pod)
        if hits:
            self.by_pod[k] = hits
            for ci, msgs in hits:
                self.per_c[ci][k] = msgs
                self.n_viol[ci] += len(msgs)

    def compare(self, sweep: dict, cap: int) -> list:
        """Faults of one capped sweep against the audit as it stands.
        sweep = {"totals": {(kind, name): (count, how)},
                 "kept": [(kind, name, namespace, pod name, msg)]}.
        The guarantee (configs/*.json): a total said to be "exact" is
        the number of violations; one said to count "resources" (the
        cap cut rendering short) is no less than the violating Pods and
        comes with at least `cap` kept; every kept violation is one the
        reference raises, none twice; below the cap all are kept."""
        faults = []
        kept = {}
        for kind, name, ns, pod, msg in sweep["kept"]:
            kept.setdefault((kind, name), []).append(((ns, pod), msg))
        totals = sweep["totals"]
        for ci, ckey in enumerate(self.policies.keys):
            ref = self.per_c[ci]
            n_viol = self.n_viol[ci]
            got = kept.get(ckey, [])
            if ckey not in totals:
                faults.append(f"{ckey}: no total")
                continue
            count, how = totals[ckey]
            if len(set(got)) != len(got):
                faults.append(f"{ckey}: a violation kept twice")
            bad = [g for g in got if g[1] not in ref.get(g[0], ())]
            if bad:
                faults.append(f"{ckey}: kept {bad[0]} is not a violation")
            if how == "exact":
                if count != n_viol:
                    faults.append(f"{ckey}: total {count} != {n_viol}")
            elif how == "resources":
                if count < len(ref) or len(got) < cap:
                    faults.append(
                        f"{ckey}: resources total {count} < {len(ref)} "
                        f"or kept {len(got)} < cap")
            else:
                faults.append(f"{ckey}: total said {how!r}")
            if len(got) < min(cap, n_viol):
                faults.append(f"{ckey}: kept {len(got)} of {n_viol}")
            if n_viol <= cap and len(got) != n_viol:
                faults.append(
                    f"{ckey}: kept {len(got)} != all {n_viol} under cap")
        extra = set(kept) - set(self.policies.keys)
        if extra:
            faults.append(f"results for unknown constraints {sorted(extra)}")
        return faults


def response_verdict(status: int, data: bytes) -> tuple:
    """(uid, allowed, code, sorted messages) of one AdmissionReview
    answer as it came off the wire; anything that is not a plain verdict
    (a shed, a 504, a 502, junk) has allowed None."""
    if status != 200:
        return (None, None, status, [repr(data[:120])])
    try:
        out = json.loads(data)["response"]
    except (ValueError, KeyError, TypeError):
        return (None, None, status, [repr(data[:120])])
    st = out.get("status") or {}
    code = st.get("code")
    allowed = out.get("allowed")
    if not isinstance(allowed, bool) or code in (429, 504):
        return (out.get("uid"), None, code, [str(st.get("message"))])
    msgs = [] if allowed else sorted(
        m for m in str(st.get("message", "")).split("\n") if m)
    return (out.get("uid"), allowed, code, msgs)


def compare_verdict(policies: Policies, body: bytes, status: int,
                    data: bytes):
    """None when the answer to the request `body` is the reference's,
    else a short description of the difference."""
    req = json.loads(body)["request"]
    want_allowed, want_msgs = policies.verdict(req["object"])
    uid, allowed, code, msgs = response_verdict(status, data)
    if allowed is None:
        return f"{req['uid']}: no verdict (HTTP {status}, code {code}): {msgs}"
    if uid != req["uid"]:
        return f"{req['uid']}: answered with uid {uid!r}"
    if allowed != want_allowed or msgs != want_msgs:
        return (f"{req['uid']}: got {allowed} {msgs[:2]}, "
                f"reference {want_allowed} {want_msgs[:2]}")
    if not allowed and code != 403:
        return f"{req['uid']}: denied with code {code}"
    return None
