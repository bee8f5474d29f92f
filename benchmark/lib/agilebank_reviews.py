"""The agilebank deployment's admission traffic, all of it from --seed:
review requests of three kinds (Service, Pod, Namespace) against the
cluster of lib/agilebank.py as it stands (nothing is synced while they
are sent, so every request is judged against the same inventory), and
their AdmissionReview bodies.  Nothing here imports the program.

The mix is the traffic file's (traffic/paced-svcapply.json): the shares
of the three kinds, and of the Service reviews the share that keeps its
selector, moves it, or arrives under a new name.  Each kind's count is
exact (round(share x n)); the seed places them.  Every request is
unique in uid; a created object is unique in name.

The Services without a selector are left alone, as svc-keychurn50
leaves them: upstream's flatten_selector gives each the empty key, so a
review of one is denied with one message per other such Service (499
of them at the configuration's size).
"""

from __future__ import annotations

import copy
import json
import random

from . import agilebank
from .corpus import seed32


def request(obj: dict, operation: str, uid: str, old: dict = None) -> dict:
    """One AdmissionRequest of `obj`."""
    meta = obj["metadata"]
    group, _, version = obj["apiVersion"].rpartition("/")
    req = {
        "uid": uid,
        "kind": {"group": group, "version": version, "kind": obj["kind"]},
        "name": meta["name"],
        "namespace": meta.get("namespace", ""),
        "operation": operation,
        "userInfo": {"username": "benchmark"},
        "object": obj,
    }
    if old is not None:
        req["oldObject"] = old
    return req


def body_of(req: dict) -> bytes:
    return json.dumps({"request": req}).encode()


def _counts(n: int, shares: list) -> list:
    """n split by `shares` (they sum to 1): each round(share x n), the
    remainder to the first."""
    out = [round(n * s) for s in shares]
    out[0] += n - sum(out)
    return out


class Mix:
    """The generator of one run's requests."""

    def __init__(self, config: dict, traffic: dict, seed: int, tag: str):
        self.cfg, self.tr, self.tag = config, traffic, tag
        self.objects, self.svcs, _pod_ns = agilebank._generate(config, seed)
        self.rng = random.Random(seed32(seed, 21))
        n_ns, n_svc = config["namespaces"], config["services"]
        self.n_ns, self.n_svc = n_ns, n_svc
        with_sel = [i for i in range(n_svc) if i not in self.svcs.bare]
        self.alone = [i for i in with_sel
                      if len(self.svcs.group_of(i)) == 1]
        self.paired = self.svcs.paired()
        self.with_selector = with_sel

    # ---- the three kinds ---------------------------------------------------

    def _fresh(self) -> dict:
        """A selector nobody holds, drawn as the cluster's own are."""
        while True:
            sel = agilebank.draw_selector(self.rng)
            if agilebank.flatten(sel) not in self.svcs.holders:
                return sel

    def _service(self, i: int) -> dict:
        return self.objects[self.n_ns + i]

    def service(self, how: str, k: int, uid: str) -> dict:
        rng, svcs = self.rng, self.svcs
        if how == "keep":
            obj = self._service(rng.choice(self.with_selector))
            return request(copy.deepcopy(obj), "UPDATE", uid, obj)
        if how in ("move_fresh", "move_onto", "move_out"):
            i = rng.choice(self.paired if how == "move_out" and self.paired
                           else self.alone)
            if how == "move_onto":
                target = rng.choice(self.alone)
                while target == i:
                    target = rng.choice(self.alone)
                sel = svcs.selector[target]
            else:
                sel = self._fresh()
            old = self._service(i)
            return request(
                agilebank.make_service(i, svcs.namespace[i], sel),
                "UPDATE", uid, old)
        sel = (svcs.selector[rng.choice(self.alone)]
               if how == "create_onto" else self._fresh())
        obj = agilebank.make_service(
            0, agilebank.namespace_name(rng.randrange(self.n_ns)), sel)
        obj["metadata"]["name"] = f"{self.tag}-svc-{k}"
        return request(obj, "CREATE", uid)

    def pod(self, k: int, uid: str) -> dict:
        rng, cfg = self.rng, self.cfg
        ns = ("production" if rng.random() < cfg["production_share"]
              else agilebank.namespace_name(1 + rng.randrange(self.n_ns - 1)))
        labels = (self.svcs.selector[rng.randrange(self.n_svc)]
                  or {"app": "standalone"})
        obj = agilebank.make_pod(0, ns, rng, cfg, labels)
        obj["metadata"]["name"] = f"{self.tag}-pod-{k}"
        return request(obj, "CREATE", uid)

    def namespace(self, create: bool, k: int, uid: str) -> dict:
        i = self.rng.randrange(self.n_ns)
        obj = agilebank.make_namespace(
            i, self.rng, self.tr["namespace_unowned_share"])
        if create:
            obj["metadata"]["name"] = f"{self.tag}-ns-{k}"
            return request(obj, "CREATE", uid)
        return request(obj, "UPDATE", uid, self.objects[i])

    # ---- the mix -------------------------------------------------------------

    def plan(self, n: int) -> list:
        """n (kind, how) pairs: exact counts, placed by the seed."""
        tr = self.tr
        n_svc, n_pod, n_nsp = _counts(n, [
            tr["service_share"], tr["pod_share"], tr["namespace_share"]])
        keep, move, create = _counts(n_svc, [
            tr["service_update_keep_share"], tr["service_update_move_share"],
            tr["service_create_share"]])
        m_fresh, m_onto, m_out = _counts(move, [
            1 - tr["move_onto_share"] - tr["move_out_of_pair_share"],
            tr["move_onto_share"], tr["move_out_of_pair_share"]])
        c_fresh, c_onto = _counts(create, [
            1 - tr["create_onto_share"], tr["create_onto_share"]])
        ns_create = n_nsp // 2
        out = ([("Service", "keep")] * keep
               + [("Service", "move_fresh")] * m_fresh
               + [("Service", "move_onto")] * m_onto
               + [("Service", "move_out")] * m_out
               + [("Service", "create_fresh")] * c_fresh
               + [("Service", "create_onto")] * c_onto
               + [("Pod", "create")] * n_pod
               + [("Namespace", "create")] * ns_create
               + [("Namespace", "update")] * (n_nsp - ns_create))
        self.rng.shuffle(out)
        return out

    def requests(self, n: int) -> list:
        out = []
        for k, (kind, how) in enumerate(self.plan(n)):
            uid = f"{self.tag}-u{k}"
            if kind == "Service":
                out.append(self.service(how, k, uid))
            elif kind == "Pod":
                out.append(self.pod(k, uid))
            else:
                out.append(self.namespace(how == "create", k, uid))
        return out


def build_requests(config: dict, traffic: dict, seed: int, n: int,
                   tag: str) -> list:
    return Mix(config, traffic, seed, tag).requests(n)


# ---------------------------------------------------------------------------
# the warm-up's bursts, one ladder per shape class
# ---------------------------------------------------------------------------


def shape_class(req: dict) -> tuple:
    """(labels, containers) of a request's object as the program pads
    them: the label pairs to a power of two, the containers as they
    are, each at least 1.  A batch is as wide as its widest review, and
    every padded width keys a compiled executable of its own (PERF.md,
    PR 21): three kinds of review make six classes where the Pods of
    one shape made one."""
    obj = req["object"]
    labels = len((obj["metadata"].get("labels") or {}))
    width = 1
    while width < labels:
        width *= 2
    return width, max(1, len((obj.get("spec") or {}).get("containers")
                             or ()))


def burst_ladder(pool: list, bursts: list, classes: list, rng) -> list:
    """The bursts' requests, class by class: for each class the whole
    ladder of `bursts`, every request of a burst of exactly that class
    by itself (a Pod with that many labels and containers; for the
    narrowest class any kind), so however the door and the batcher cut
    a burst, every piece is as wide as the class and no wider.  `pool`
    is a run of the mix to draw them from."""
    sized = [(shape_class(r), r) for r in pool]
    out = []
    for wanted in classes:
        exact = [r for got, r in sized if got == tuple(wanted)]
        if not exact:
            raise ValueError(f"no request of shape class {tuple(wanted)} "
                             "in the pool")
        for n in bursts:
            out += [rng.choice(exact) for _ in range(n)]
    return out


def build_all(spec: dict) -> list:
    """Every request of one generator process in the order it sends
    them.  The window's generator: `bodies` requests of the mix (its
    bursts and warm-up are of the mix too).  The shape ladder's (spec
    `shape_classes`): spec `warm_bursts` is `shape_bursts` once per
    class, and the requests are those bursts alone, drawn from a short
    run of the mix (few objects, so few strings new to the program)
    under uids of their own."""
    classes = [tuple(c) for c in spec.get("shape_classes") or ()]
    mix = Mix(spec["config"], spec, spec["seed"], spec["tag"])
    if not classes:
        return mix.requests(spec["bodies"])
    ladder = spec["shape_bursts"]
    if list(spec["warm_bursts"]) != list(ladder) * len(classes):
        raise ValueError("warm_bursts is not shape_bursts once per class "
                         "of shape_classes")
    rng = random.Random(seed32(spec["seed"], 22))
    return [dict(r, uid=f"{spec['tag']}-b{k}") for k, r in enumerate(
        burst_ladder(mix.requests(512), ladder, classes, rng))]


def build_bodies(spec: dict) -> list:
    """The generator process's bodies (lib/loadgen.py's build_bodies for
    this deployment): spec holds `config`, the traffic's keys, `seed`,
    `tag` and `bodies`."""
    return [body_of(r) for r in build_all(spec)]
