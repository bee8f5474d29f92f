"""The plain reference of the agilebank deployment: the four policies of
demo/agilebank stated in plain Python, an audit kept current as objects
are replaced, and the comparison that decides `correct`.

It imports nothing of the program and takes nothing the program made.
Where the upstream Rego and common sense part ways the Rego decides: a
Service without a selector flattens to the empty string and so collides
with every other such Service; a selector value that is not a string
drops its pair (concat refuses it, which fails that one iteration of the
comprehension) and the rest still flatten; a CPU quantity the regex
refuses ("0.5") "could not be parsed".  A message is the text the
policy's sprintf produces, so answers compare byte for byte.
"""

from __future__ import annotations

import json
import re

_DIGITS = re.compile(r"^[0-9]+$")

_MEM_MULTIPLE = {
    "E": 10 ** 21, "P": 10 ** 18, "T": 10 ** 15, "G": 10 ** 12,
    "M": 10 ** 9, "k": 10 ** 6, "": 10 ** 3, "m": 1,
    "Ki": 1000 * 2 ** 10, "Mi": 1000 * 2 ** 20, "Gi": 1000 * 2 ** 30,
    "Ti": 1000 * 2 ** 40, "Pi": 1000 * 2 ** 50, "Ei": 1000 * 2 ** 60,
}


def _is_number(v) -> bool:
    return isinstance(v, (int, float)) and not isinstance(v, bool)


def _to_number(s: str):
    try:
        return int(s)
    except ValueError:
        try:
            return float(s)
        except ValueError:
            return None


def _v(x) -> str:
    """sprintf's %v."""
    return x if isinstance(x, str) else json.dumps(x)


def canonify_cpu(orig):
    """Millicores, or None where the policy's three clauses all fail."""
    if _is_number(orig):
        return orig * 1000
    if not isinstance(orig, str):
        return None
    if orig.endswith("m"):
        return _to_number(orig.replace("m", ""))
    if _DIGITS.match(orig):
        return int(orig) * 1000
    return None


def _suffix(mem: str) -> str:
    one, two = mem[-1:], mem[-2:]
    if len(mem) > 1 and two in _MEM_MULTIPLE:
        return two
    if one in _MEM_MULTIPLE and mem:
        return one
    return ""


def canonify_mem(orig):
    """Thousandths of a byte, or None where it cannot be parsed."""
    if _is_number(orig):
        return orig * 1000
    if not isinstance(orig, str):
        return None
    suffix = _suffix(orig)
    raw = orig.replace(suffix, "") if suffix else orig
    if not _DIGITS.match(raw):
        return None
    return int(raw) * _MEM_MULTIPLE[suffix]


def _falsy(d, field) -> bool:
    """Rego's `not d[field]`: absent or false."""
    return field not in d or d[field] is False


def container_limits(params: dict, pod: dict) -> set:
    msgs = set()
    max_cpu, max_mem = params.get("cpu"), params.get("memory")
    spec = pod.get("spec") or {}
    for field in ("containers", "initContainers"):
        for c in spec.get(field) or ():
            if "name" not in c:      # sprintf's argument is undefined
                continue
            name = _v(c["name"])
            if _falsy(c, "resources"):
                msgs.add(f"container <{name}> has no resource limits")
                continue
            res = c["resources"]
            if not isinstance(res, dict) or _falsy(res, "limits"):
                msgs.add(f"container <{name}> has no resource limits")
                continue
            limits = res["limits"]
            for key, word, canon, top in (
                    ("cpu", "cpu", canonify_cpu, max_cpu),
                    ("memory", "memory", canonify_mem, max_mem)):
                if _falsy(limits, key) or limits[key] == "":
                    msgs.add(f"container <{name}> has no {word} limit")
                if key not in limits:
                    continue
                orig = limits[key]
                got = canon(orig)
                if got is None:
                    msgs.add(f"container <{name}> {word} limit <{_v(orig)}> "
                             "could not be parsed")
                elif canon(top) is not None and got > canon(top):
                    msgs.add(f"container <{name}> {word} limit <{_v(orig)}> "
                             "is higher than the maximum allowed of "
                             f"<{_v(top)}>")
    return msgs


def required_labels(params: dict, obj: dict) -> set:
    msgs = set()
    labels = (obj.get("metadata") or {}).get("labels") or {}

    def message(default):
        own = params.get("message")
        return default if own is None or own is False else own

    provided = {k for k, v in labels.items() if v is not False}
    missing = {e["key"] for e in params.get("labels") or ()} - provided
    if missing:
        msgs.add(message("you must provide labels: {"
                         + ", ".join(json.dumps(m) for m in sorted(missing))
                         + "}"))
    for key, value in labels.items():
        for e in params.get("labels") or ():
            rx = e.get("allowedRegex")
            if e.get("key") != key or rx is None or rx == "":
                continue
            if not (isinstance(value, str) and re.search(rx, value)):
                msgs.add(message(f"Label <{key}: {_v(value)}> does not "
                                 f"satisfy allowed regex: {rx}"))
    return msgs


def allowed_repos(params: dict, pod: dict) -> set:
    msgs = set()
    repos = params.get("repos") or []
    shown = "[" + ", ".join(json.dumps(r) for r in repos) + "]"
    for c in (pod.get("spec") or {}).get("containers") or ():
        if "name" not in c or "image" not in c:
            continue                 # sprintf's argument is undefined
        image = c["image"]
        if not any(isinstance(image, str) and image.startswith(r)
                   for r in repos):
            msgs.add(f"container <{_v(c['name'])}> has an invalid image "
                     f"repo <{_v(image)}>, allowed repos are {shown}")
    return msgs


def flatten_selector(obj: dict) -> str:
    sel = (obj.get("spec") or {}).get("selector")
    if not isinstance(sel, dict):
        return ""
    return ",".join(sorted(f"{k}:{v}" for k, v in sel.items()
                           if isinstance(v, str)))


def selector_message(other: tuple) -> str:
    return (f"same selector as service <{other[1]}> in namespace "
            f"<{other[0]}>")


ROW_LOCAL = {"K8sContainerLimits": container_limits,
             "K8sRequiredLabels": required_labels,
             "K8sAllowedRepos": allowed_repos}
UNIQUE = "K8sUniqueServiceSelector"


def _kinds(constraint: dict) -> list:
    match = constraint["spec"].get("match") or {}
    return [k for e in match.get("kinds") or () for k in e["kinds"]]


def matches(constraint: dict, obj: dict) -> bool:
    """spec.match as the four constraints use it: kinds (core group)
    and namespaces."""
    match = constraint["spec"].get("match") or {}
    if obj["kind"] not in _kinds(constraint):
        return False
    spaces = match.get("namespaces")
    if spaces and (obj.get("metadata") or {}).get("namespace") not in spaces:
        return False
    return True


def key_of(obj: dict) -> tuple:
    meta = obj["metadata"]
    return (meta.get("namespace", ""), meta["name"])


class AuditReference:
    """The audit of the cluster, kept current under put(): per
    row-local constraint the violating objects and their messages; for
    the unique-selector constraints a dict from flattened selector to
    the Services that hold it, from which a Service's violations are
    one per other holder."""

    def __init__(self, constraints: list, objects: list):
        self.constraints = constraints
        self.keys = [(c["kind"], c["metadata"]["name"]) for c in constraints]
        self.per_c = [dict() for _ in constraints]   # ci -> {key: msgs}
        self.n_viol = [0] * len(constraints)
        self.services = {}                           # key -> Service
        self.holders = {}                            # flattened -> {(ns, name)}
        self.flat = {}                               # (ns, name) -> flattened
        self.rank = {}            # (kind, key) -> place in arrival order
        for o in objects:
            self.put(o)

    def put(self, obj: dict):
        k = key_of(obj)
        self.rank.setdefault((obj["kind"], k), len(self.rank))
        for ci, c in enumerate(self.constraints):
            fn = ROW_LOCAL.get(c["kind"])
            if fn is None or obj["kind"] not in _kinds(c):
                continue     # (namespace, name) is a key within a kind
            old = self.per_c[ci].pop(k, None)
            if old:
                self.n_viol[ci] -= len(old)
            if matches(c, obj):
                msgs = fn(c["spec"].get("parameters") or {}, obj)
                if msgs:
                    self.per_c[ci][k] = frozenset(msgs)
                    self.n_viol[ci] += len(msgs)
        if obj["kind"] == "Service" and k[0]:
            self.services[k] = obj
            old = self.flat.get(k)
            new = flatten_selector(obj)
            if old != new:
                if old is not None:
                    self.holders[old].discard(k)
                    if not self.holders[old]:
                        del self.holders[old]
                self.holders.setdefault(new, set()).add(k)
                self.flat[k] = new

    # ---- the unique-selector policy, from the holders ----------------------

    def selector_others(self, ident: tuple) -> set:
        """The other Services that hold `ident`'s selector."""
        flat = self.flat.get(ident)
        if flat is None:
            return set()
        return self.holders[flat] - {ident}

    def selector_counts(self, constraint: dict) -> tuple:
        """(violations, violating Services) under one constraint."""
        viol = res = 0
        for group in self.holders.values():
            if len(group) < 2:
                continue
            n = sum(matches(constraint, self.services[i]) for i in group)
            viol += n * (len(group) - 1)
            res += n
        return viol, res

    def violations_of(self, ci: int) -> dict:
        """{object key: messages} of one constraint, in full (the
        control and the tests; compare() asks only for what was kept)."""
        c = self.constraints[ci]
        if c["kind"] != UNIQUE:
            return dict(self.per_c[ci])
        out = {}
        for group in self.holders.values():
            if len(group) < 2:
                continue
            for i in group:
                if matches(c, self.services[i]):
                    out[i] = frozenset(selector_message(o)
                                       for o in group - {i})
        return out

    def compare(self, sweep: dict, cap: int) -> list:
        """Faults of one capped sweep against the audit as it stands.
        sweep = {"totals": {(kind, name): (count, how)},
                 "kept": [(kind, name, namespace, object name, msg)]}.
        The guarantees (configs/agilebank4x111k-audit.json): a total
        said to be "exact" is the number of violations; one said to
        count "resources" (the cap cut rendering short) is no less than
        the violating objects (for the unique-selector constraint it is
        their number, or that of the kept violations where that is
        larger: a Service left flagged after its partner left is a stale
        answer) and comes with at least `cap` kept; every kept
        violation is one the reference raises, none twice; below the cap
        all are kept.  cap 0 means no cap."""
        faults = []
        kept = {}
        for kind, name, ns, oname, msg in sweep["kept"]:
            kept.setdefault((kind, name), []).append(((ns, oname), msg))
        totals = sweep["totals"]
        for ci, ckey in enumerate(self.keys):
            c = self.constraints[ci]
            if c["kind"] == UNIQUE:
                n_viol, n_res = self.selector_counts(c)

                def raised(k, msg, c=c):
                    return (k in self.services
                            and matches(c, self.services[k])
                            and msg in {selector_message(o) for o in
                                        self.selector_others(k)})
            else:
                n_viol, n_res = self.n_viol[ci], len(self.per_c[ci])

                def raised(k, msg, ci=ci):
                    return msg in self.per_c[ci].get(k, ())
            got = kept.get(ckey, [])
            if ckey not in totals:
                faults.append(f"{ckey}: no total")
                continue
            count, how = totals[ckey]
            if len(set(got)) != len(got):
                faults.append(f"{ckey}: a violation kept twice")
            bad = [g for g in got if not raised(*g)]
            if bad:
                faults.append(f"{ckey}: kept {bad[0]} is not a violation")
            limit = cap if cap else n_viol + 1
            if how == "exact":
                if count != n_viol:
                    faults.append(f"{ckey}: total {count} != {n_viol}")
            elif how == "resources":
                if count < n_res or len(got) < limit:
                    faults.append(
                        f"{ckey}: resources total {count} < {n_res} "
                        f"or kept {len(got)} < cap")
                elif c["kind"] == UNIQUE and count != max(n_res, len(got)):
                    faults.append(
                        f"{ckey}: resources total {count} != {n_res}")
            else:
                faults.append(f"{ckey}: total said {how!r}")
            if len(got) < min(limit, n_viol):
                faults.append(f"{ckey}: kept {len(got)} of {n_viol}")
            if n_viol <= limit and len(got) != n_viol:
                faults.append(
                    f"{ckey}: kept {len(got)} != all {n_viol} under cap")
        extra = set(kept) - set(self.keys)
        if extra:
            faults.append(f"results for unknown constraints {sorted(extra)}")
        return faults

    def answer(self, cap: int) -> dict:
        """The reference's own capped audit in the form the program
        answers in (the control, and the reference in the program's
        place): under the cap every violation and an exact total; past
        it the violating objects as a "resources" total and the first
        `cap` violations in the objects' arrival order, which is the
        order the program walks its rows in."""
        kept, totals = [], {}
        for ci, ck in enumerate(self.keys):
            kind = _kinds(self.constraints[ci])[0]
            per_obj = sorted(self.violations_of(ci).items(),
                             key=lambda kv: self.rank[(kind, kv[0])])
            hits = [(k, m) for k, ms in per_obj for m in sorted(ms)]
            if cap and len(hits) > cap:
                totals[ck] = (len(per_obj), "resources")
                hits = hits[:cap]
            else:
                totals[ck] = (len(hits), "exact")
            kept += [(*ck, *k, m) for k, m in hits]
        return {"totals": totals, "kept": kept}
