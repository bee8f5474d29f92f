"""A referential policy at admission (ISSUE 33): the review path resolves
a join-safe template's cells through the join index (ops/joinreview.py,
JoinState.review_lookup) and not through an interpreter walk of the
inventory.  Held to the interpreter oracle on the FULL inventory,
verdict + code-relevant fields + sorted messages, on a seeded agilebank
cluster (300 Services / 2,000 Pods / 30 Namespaces), in every tier the
review path has (interpreter walk, numpy mask, device mask); after
writes between batches (the index brought current without a sweep); on
a webhook-only client restored from a snapshot; with the fallbacks
counted; and the benchmark's plain admission reference tied to the
oracle on the same reviews."""

import copy
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "benchmark"))

from lib import agilebank, agilebank_admission_reference  # noqa: E402
from lib import agilebank_reviews  # noqa: E402

from gatekeeper_tpu.client.client import Client  # noqa: E402
from gatekeeper_tpu.client.drivers import InterpDriver  # noqa: E402
from gatekeeper_tpu.obs import trace as obstrace  # noqa: E402
from gatekeeper_tpu.ops import joinkernel  # noqa: E402
from gatekeeper_tpu.ops.driver import TpuDriver  # noqa: E402
from gatekeeper_tpu.util.synthetic import (  # noqa: E402
    make_referential_objects,
    make_referential_templates,
)

from tests.test_tracing import _stage_rows  # noqa: E402

SEED = 33
CONFIG = {"pods": 2000, "services": 300, "namespaces": 30,
          "unlimited_share": 0.05, "production_share": 0.1,
          "prod_other_repo_share": 0.03, "unowned_share": 0.02,
          "paired_share": 0.1, "grouped_share": 0.03,
          "no_selector_share": 0.05}
TIERS = ("interp", "np", "device")
CELLS = "admission_join_cells_total"
ROWS = "admission_join_render_rows_total"


def _driver(tier="interp"):
    d = TpuDriver()
    d.mesh_enabled = False
    d._mesh_cache = None
    _route(d, tier)
    return d


def _route(driver, tier):
    """Pin the uncalibrated router to one tier (instance overrides of
    the class priors; no knob of the program)."""
    driver.DEVICE_MIN_CELLS = 0 if tier == "device" else 10 ** 9
    driver.NP_MIN_CELLS = 0 if tier == "np" else 10 ** 9


def _client(driver, templates, constraints, objects):
    c = Client(driver=driver)
    for t in templates:
        c.add_template(t)
    for k in constraints:
        c.add_constraint(k)
    for o in objects:
        c.add_data(o)
    return c


def _sig(responses):
    return sorted((r.constraint["kind"], r.constraint["metadata"]["name"],
                   r.msg, r.enforcement_action)
                  for r in responses.results())


def _cells():
    """{(outcome, rendered): count} of the registry, and the rows."""
    rows = _stage_rows(ROWS)
    return dict(_stage_rows(CELLS)), sum(rows.values()) if rows else 0


def _grew(before, after):
    return {k: after.get(k, 0) - before.get(k, 0)
            for k in set(after) | set(before)
            if after.get(k, 0) != before.get(k, 0)}


class World:
    def __init__(self):
        self.templates, self.constraints, self.objects = agilebank.cluster(
            CONFIG, SEED)
        self.system = _client(_driver(), self.templates, self.constraints,
                              self.objects)
        self.oracle = _client(InterpDriver(), self.templates,
                              self.constraints, self.objects)
        mix = agilebank_reviews.Mix(CONFIG, {}, SEED, "t33")
        self.mix, self.svcs = mix, mix.svcs
        self.n_ns = CONFIG["namespaces"]

    def service(self, i):
        return self.objects[self.n_ns + i]

    def held(self, request, tier):
        """One request through the system in `tier` and the oracle."""
        _route(self.system.driver, tier)
        got = _sig(self.system.review(request))
        want = _sig(self.oracle.review(request))
        assert got == want, (tier, request["name"])
        return got


@pytest.fixture(scope="module")
def world():
    return World()


def _fresh_selector(w):
    return w.mix._fresh()


def _request(w, case):
    """The review request of one named case, and how many
    unique-selector messages it must raise."""
    svcs, req = w.svcs, agilebank_reviews.request
    alone, paired = w.mix.alone, w.mix.paired
    if case == "create_fresh":
        obj = agilebank.make_service(0, "team-3", _fresh_selector(w))
        obj["metadata"]["name"] = "brand-new"
        return req(obj, "CREATE", "u1"), 0
    if case == "create_colliding":
        obj = agilebank.make_service(0, "team-3", svcs.selector[alone[0]])
        obj["metadata"]["name"] = "brand-new"
        return req(obj, "CREATE", "u2"), 1
    if case == "update_keep":
        obj = w.service(alone[1])
        return req(copy.deepcopy(obj), "UPDATE", "u3", obj), 0
    if case == "update_move_onto":
        i = alone[2]
        obj = agilebank.make_service(i, svcs.namespace[i],
                                     svcs.selector[alone[3]])
        return req(obj, "UPDATE", "u4", w.service(i)), 1
    if case == "update_move_out_of_a_pair":
        i = paired[0]
        obj = agilebank.make_service(i, svcs.namespace[i],
                                     _fresh_selector(w))
        return req(obj, "UPDATE", "u5", w.service(i)), 0
    if case == "paired_self_update":
        obj = w.service(paired[1])
        return req(copy.deepcopy(obj), "UPDATE", "u6", obj), 1
    if case == "group_member_update":
        i = next(i for i in w.mix.with_selector
                 if len(svcs.group_of(i)) >= 3)
        obj = w.service(i)
        return (req(copy.deepcopy(obj), "UPDATE", "u7", obj),
                len(svcs.group_of(i)) - 1)
    if case == "no_selector":
        i = sorted(svcs.bare)[0]
        obj = w.service(i)
        return (req(copy.deepcopy(obj), "UPDATE", "u8", obj),
                len(svcs.bare) - 1)
    if case == "no_selector_create":
        obj = agilebank.make_service(0, "team-4", None)
        obj["metadata"]["name"] = "bare-new"
        return req(obj, "CREATE", "u9"), len(svcs.bare)
    if case == "name_of_another_namespace":
        # same name as a Service of another namespace holding the same
        # selector: not the review's own row, so it collides
        i = alone[4]
        other = "team-1" if svcs.namespace[i] != "team-1" else "team-2"
        obj = agilebank.make_service(i, other, svcs.selector[i])
        return req(obj, "CREATE", "u10"), 1
    raise AssertionError(case)


SERVICE_CASES = ("create_fresh", "create_colliding", "update_keep",
                 "update_move_onto", "update_move_out_of_a_pair",
                 "paired_self_update", "group_member_update", "no_selector",
                 "no_selector_create", "name_of_another_namespace")


@pytest.mark.parametrize("tier", TIERS)
@pytest.mark.parametrize("case", SERVICE_CASES)
def test_service_review_matches_the_oracle(world, case, tier, monkeypatch):
    monkeypatch.setenv("GK_JOIN_ASSERT", "1")
    request, n_msgs = _request(world, case)
    before, _ = _cells()
    got = world.held(request, tier)
    unique = [g for g in got if g[0] == "K8sUniqueServiceSelector"]
    assert len(unique) == n_msgs
    assert all(g[2].startswith("same selector as service <") for g in unique)
    # the one referential cell was resolved from the index, never by the
    # full inventory; a cell that cannot raise never met the interpreter
    grew = _grew(before, _cells()[0])
    assert grew == {("index", "yes" if n_msgs else "no"): 1}


@pytest.mark.parametrize("tier", TIERS)
def test_a_mixed_batch_matches_the_oracle(world, tier, monkeypatch):
    """The benchmark's own mix, 120 requests of three kinds in batches
    of 8, the Pods and Namespaces through the same tiers."""
    monkeypatch.setenv("GK_JOIN_ASSERT", "1")
    tr = {"service_share": 0.6, "pod_share": 0.3, "namespace_share": 0.1,
          "service_update_keep_share": 0.5,
          "service_update_move_share": 0.25, "service_create_share": 0.25,
          "move_onto_share": 0.2, "move_out_of_pair_share": 0.2,
          "create_onto_share": 0.3, "namespace_unowned_share": 0.3}
    requests = agilebank_reviews.build_requests(CONFIG, tr, SEED, 120, tier)
    _route(world.system.driver, tier)
    before, _ = _cells()
    denied = 0
    for at in range(0, len(requests), 8):
        batch = requests[at:at + 8]
        got = world.system.review_batch(batch)
        for request, responses in zip(batch, got):
            want = _sig(world.oracle.review(request))
            assert _sig(responses) == want, request["uid"]
            denied += bool(want)
    assert 10 <= denied <= 60
    grew = _grew(before, _cells()[0])
    assert set(grew) <= {("index", "yes"), ("index", "no")}
    assert sum(grew.values()) == 72  # one cell per Service review


def test_the_interpreter_is_handed_the_key_group_not_the_cluster(
        world, monkeypatch):
    """admission_join_render_rows_total: a denied review's render reads
    its key group's provider rows (here: the one other Service), an
    allowed one reads nothing."""
    handed = []
    kind = "K8sUniqueServiceSelector"
    policy = world.system.driver.templates[kind].policy
    real = policy.eval_violations

    def spy(review, params, inventory):
        n = sum(len(by_kind.get("Service", {}))
                for by_ns in inventory.get("namespace", {}).values()
                for by_kind in by_ns.values())
        handed.append(n)
        return real(review, params, inventory)

    monkeypatch.setattr(policy, "eval_violations", spy)
    for tier in TIERS:
        for case, rows in (("create_colliding", 1), ("update_keep", 0),
                           ("paired_self_update", 2),
                           ("create_fresh", 0)):
            del handed[:]
            _, r0 = _cells()
            world.held(_request(world, case)[0], tier)
            _, r1 = _cells()
            assert r1 - r0 == rows, (tier, case)
            assert handed == ([rows] if rows else []), (tier, case)


# ---- upkeep without a sweep --------------------------------------------------


@pytest.mark.parametrize("tier", TIERS)
def test_writes_between_batches_are_seen_without_a_sweep(tier, monkeypatch):
    """add_data / remove_data / a re-point between batches: the next
    batch answers on the inventory as it then stands, the index brought
    current by the review path (no sweep ever runs here)."""
    monkeypatch.setenv("GK_JOIN_ASSERT", "1")
    w = World()
    svcs, alone = w.svcs, w.mix.alone
    a, b = alone[0], alone[1]
    probe = agilebank.make_service(0, "team-5", svcs.selector[a])
    probe["metadata"]["name"] = "probe"
    request = agilebank_reviews.request(probe, "CREATE", "p1")

    def both(fn, obj):
        getattr(w.system, fn)(obj)
        getattr(w.oracle, fn)(obj)

    upkeep0 = dict(_stage_rows("join_index_upkeep_seconds_total"))
    assert len(w.held(request, tier)) == 1          # collides with a
    # a is re-pointed away: nobody holds the selector
    both("add_data", agilebank.make_service(
        a, svcs.namespace[a], w.mix._fresh()))
    assert w.held(request, tier) == []
    # b is re-pointed onto it, and a third Service arrives with it
    both("add_data", agilebank.make_service(
        b, svcs.namespace[b], svcs.selector[a]))
    third = agilebank.make_service(0, "team-6", svcs.selector[a])
    third["metadata"]["name"] = "third"
    both("add_data", third)
    got = w.held(request, tier)
    assert [g[2] for g in got] == sorted(
        f"same selector as service <{n}> in namespace <{ns}>"
        for n, ns in ((f"svc-{b}", svcs.namespace[b]), ("third", "team-6")))
    # b is deleted: only the third is left
    both("remove_data", w.service(b))
    got = w.held(request, tier)
    assert len(got) == 1 and "<third>" in got[0][2]
    # the third is replaced under its name by one without the selector
    both("add_data", dict(third, spec={"ports": [{"port": 1}]}))
    assert w.held(request, tier) == []
    upkeep = _stage_rows("join_index_upkeep_seconds_total")
    assert upkeep.get(("write",), 0) > upkeep0.get(("write",), 0)
    assert upkeep.get(("sweep",), 0) == upkeep0.get(("sweep",), 0)
    assert w.system.driver.last_sweep_stats == {}   # nothing ever swept


def test_a_sweep_after_review_path_upkeep_is_still_exact(monkeypatch):
    """All roles in one process: the review path commits a write to the
    index before the delta sweep does; the sweep takes the readers that
    commit left pending and answers as the oracle does."""
    from gatekeeper_tpu.util.synthetic import audit_result_sig
    from tests.test_tracing import _join_sweep_background as settle

    monkeypatch.setenv("GK_JOIN_ASSERT", "1")
    w = World()
    svcs, alone = w.svcs, w.mix.alone

    def sweep():
        got, totals, _ = w.system.driver.audit_capped(4096)
        want, wtotals, _ = w.oracle.driver.audit_capped(4096)
        assert audit_result_sig(got) == audit_result_sig(want)
        assert totals == wtotals
        return dict(w.system.driver.last_sweep_stats)

    assert sweep()["full"] == 1.0
    settle()
    a, b = alone[0], alone[1]
    moved = agilebank.make_service(b, svcs.namespace[b], svcs.selector[a])
    w.system.add_data(moved)
    w.oracle.add_data(moved)
    # the review path folds the write in first ...
    probe = agilebank_reviews.request(
        copy.deepcopy(w.service(a)), "UPDATE", "p2", w.service(a))
    assert len(w.held(probe, "interp")) == 1
    assert w.system.driver._join_state.pending
    # ... and the delta sweep still re-evaluates a, whose group changed
    stats = sweep()
    assert stats["full"] == 0.0 and stats["join_affected_rows"] >= 1.0
    assert not w.system.driver._join_state.pending


# ---- restore -------------------------------------------------------------------


@pytest.mark.parametrize("basis", ["with_basis", "index_alone", "drifted"])
def test_a_restored_webhook_only_client_serves_from_the_index(
        world, basis, tmp_path, monkeypatch):
    """A replica that never sweeps: the snapshot's join index comes with
    the inventory (with or without the delta basis); drift drops it and
    the restore itself rebuilds it from the restored pack, so no review
    ever does."""
    from gatekeeper_tpu.snapshot import SnapshotLoader, SnapshotWriter

    monkeypatch.setenv("GK_JOIN_ASSERT", "1")
    monkeypatch.setenv("GK_SNAPSHOT_KEY", "t33")
    writer_client = _client(_driver(), world.templates, world.constraints,
                            world.objects)
    writer_client.driver.audit_capped(20)
    SnapshotWriter(str(tmp_path),
                   capture_delta=basis == "with_basis").write(writer_client)
    replica = Client(driver=_driver())
    if basis == "drifted":
        monkeypatch.setattr(joinkernel.JoinState, "restore",
                            classmethod(lambda cls, *a, **k: None))
    upkeep0 = dict(_stage_rows("join_index_upkeep_seconds_total"))
    loader = SnapshotLoader(str(tmp_path))
    assert loader.restore(replica, None, resync=False) == "restored"
    js = replica.driver._join_state
    assert js is not None and js.built
    assert loader.delta_restored == (basis == "with_basis")
    upkeep1 = dict(_stage_rows("join_index_upkeep_seconds_total"))
    rebuilt = upkeep1.get(("write",), 0) > upkeep0.get(("write",), 0)
    assert rebuilt == (basis == "drifted")
    for tier in TIERS:
        _route(replica.driver, tier)
        for case in ("create_colliding", "update_keep",
                     "paired_self_update", "no_selector"):
            request, n_msgs = _request(world, case)
            before, _ = _cells()
            got = _sig(replica.review(request))
            assert got == _sig(world.oracle.review(request)), (tier, case)
            assert _grew(before, _cells()[0]) == {
                ("index", "yes" if n_msgs else "no"): 1}
    assert dict(_stage_rows("join_index_upkeep_seconds_total")) == upkeep1
    assert replica.driver.last_sweep_stats == {}


def test_a_snapshot_with_the_index_inside_its_basis_still_restores(
        world, tmp_path, monkeypatch):
    """Snapshots written before the index had a place of its own kept it
    in the delta payload: the one installer reads it there too."""
    from gatekeeper_tpu.snapshot import SnapshotLoader, SnapshotWriter

    monkeypatch.setenv("GK_SNAPSHOT_KEY", "t33")
    writer_client = _client(_driver(), world.templates, world.constraints,
                            world.objects)
    writer_client.driver.audit_capped(20)
    capture = SnapshotWriter._capture

    def older(self, client):
        state = capture(self, client)
        state["delta"]["join_index"] = state.pop("join_index")
        state["join_index"] = None
        return state

    monkeypatch.setattr(SnapshotWriter, "_capture", older)
    SnapshotWriter(str(tmp_path)).write(writer_client)
    replica = Client(driver=_driver())
    upkeep0 = dict(_stage_rows("join_index_upkeep_seconds_total"))
    loader = SnapshotLoader(str(tmp_path))
    assert loader.restore(replica, None, resync=False) == "restored"
    assert loader.delta_restored is True
    assert replica.driver._join_state.built
    assert dict(_stage_rows("join_index_upkeep_seconds_total")) == upkeep0
    request, _n = _request(world, "create_colliding")
    assert _sig(replica.review(request)) == _sig(
        world.oracle.review(request))


# ---- the index is built where the data arrives ---------------------------------------


def test_a_cold_webhook_only_client_does_not_pack_in_its_first_review(
        world, monkeypatch):
    """No snapshot, no sweep: warm_join_index (what a serving pod runs
    before it reports ready) packs the cluster and builds the index; the
    first Service review after it finds both current."""
    from gatekeeper_tpu.ops.auditpack import AuditPackCache

    monkeypatch.setenv("GK_JOIN_ASSERT", "1")
    cold = _client(_driver(), world.templates, world.constraints,
                   world.objects)
    d = cold.driver
    assert d._audit_pack.rp is None and d._join_state is None
    assert d.warm_join_index() is True
    assert d._audit_pack.rp is not None and d._join_state.built
    synced = []
    real = AuditPackCache.sync
    monkeypatch.setattr(
        AuditPackCache, "sync",
        lambda self, *a, **k: synced.append(1) or real(self, *a, **k))
    upkeep0 = dict(_stage_rows("join_index_upkeep_seconds_total"))
    for case in ("create_colliding", "update_keep"):
        request, n_msgs = _request(world, case)
        before, _ = _cells()
        assert _sig(cold.review(request)) == _sig(
            world.oracle.review(request))
        assert _grew(before, _cells()[0]) == {
            ("index", "yes" if n_msgs else "no"): 1}
    assert not synced
    assert dict(_stage_rows("join_index_upkeep_seconds_total")) == upkeep0


def test_a_join_free_bundle_warms_nothing():
    from gatekeeper_tpu.util.synthetic import make_pods, make_templates

    c = Client(driver=_driver())
    templates, constraints = make_templates(3)
    for t, k in zip(templates, constraints):
        c.add_template(t)
        c.add_constraint(k)
    for p in make_pods(10, seed=33, violation_rate=0.3):
        c.add_data(p)
    assert c.driver.warm_join_index() is False
    assert c.driver._audit_pack.rp is None and c.driver._join_state is None


@pytest.mark.parametrize("driver", ["tpu", "interp"])
def test_a_serving_pod_is_not_ready_before_the_index_is_built(
        world, driver):
    """main.App._admission_ready: the tracker's expectations, then the
    join index, built once on a background thread."""
    import threading
    import time

    from gatekeeper_tpu.main import App

    app = App.__new__(App)
    app._join_warm = None
    app.client = (_client(_driver(), world.templates, world.constraints,
                          world.objects) if driver == "tpu"
                  else world.oracle)

    class Tracker:
        ok = False

        def satisfied(self):
            return self.ok

    app.tracker = Tracker()
    assert app._admission_ready() is False and app._join_warm is None
    app.tracker.ok = True
    gate = threading.Event()
    if driver == "tpu":
        d = app.client.driver
        real = d.warm_join_index
        d.warm_join_index = lambda: gate.wait(10) and real()
        assert app._admission_ready() is False   # the build is running
        assert d._join_state is None
    gate.set()
    deadline = time.monotonic() + 30
    while not app._admission_ready() and time.monotonic() < deadline:
        time.sleep(0.01)
    assert app._admission_ready() is True
    if driver == "tpu":
        assert d._join_state.built


# ---- fallbacks -------------------------------------------------------------------

_UNCLASSIFIED = {
    "apiVersion": "templates.gatekeeper.sh/v1beta1",
    "kind": "ConstraintTemplate",
    "metadata": {"name": "k8ssameportandselector"},
    "spec": {
        "crd": {"spec": {"names": {"kind": "K8sSamePortAndSelector"}}},
        "targets": [{"target": "admission.k8s.gatekeeper.sh", "rego": """
package k8ssameportandselector

violation[{"msg": msg}] {
  other := data.inventory.namespace[ns][_]["Service"][name]
  other.spec.selector.app == input.review.object.spec.selector.app
  other.spec.ports[_].port == input.review.object.spec.ports[_].port
  name != input.review.object.metadata.name
  msg := sprintf("same app and port as <%v/%v>", [ns, name])
}
"""}]},
}


@pytest.mark.parametrize("tier", TIERS)
def test_an_unclassified_inventory_read_falls_back_and_is_exact(
        world, tier, monkeypatch):
    """Two join equalities in one clause: no plan, so the cell gets the
    full inventory and the interpreter, as before, and is counted."""
    monkeypatch.setenv("GK_JOIN_ASSERT", "1")
    constraint = {
        "apiVersion": "constraints.gatekeeper.sh/v1beta1",
        "kind": "K8sSamePortAndSelector", "metadata": {"name": "same"},
        "spec": {"match": {"kinds": [{"apiGroups": [""],
                                      "kinds": ["Service"]}]}}}
    templates = world.templates + [_UNCLASSIFIED]
    constraints = world.constraints + [constraint]
    system = _client(_driver(tier), templates, constraints, world.objects)
    oracle = _client(InterpDriver(), templates, constraints, world.objects)
    d = system.driver
    assert not d._join_safe("K8sSamePortAndSelector")
    assert d.templates["K8sSamePortAndSelector"].policy.uses_inventory
    raised = 0
    for case in ("create_colliding", "update_keep", "create_fresh"):
        request, n_msgs = _request(world, case)
        before, _ = _cells()
        got = _sig(system.review(request))
        assert got == _sig(oracle.review(request))
        raised += sum(g[0] == "K8sSamePortAndSelector" for g in got)
        assert _grew(before, _cells()[0]) == {
            ("fallback", "yes"): 1,
            ("index", "yes" if n_msgs else "no"): 1}
    assert raised >= 1


def _referential_world(tier):
    templates, constraints = make_referential_templates(3)
    objects = make_referential_objects(240, seed=5)
    return (_client(_driver(tier), templates, constraints, objects),
            _client(InterpDriver(), templates, constraints, objects),
            objects)


@pytest.mark.parametrize("tier", TIERS)
def test_a_key_the_normalizer_refuses_falls_back_and_is_exact(
        tier, monkeypatch):
    """UNKNOWN_KEY: a NaN host has no faithful key; the cell falls back
    to the full inventory (counted) and still answers as the oracle."""
    monkeypatch.setenv("GK_JOIN_ASSERT", "1")
    system, oracle, objects = _referential_world(tier)
    ing = copy.deepcopy(next(o for o in objects if o["kind"] == "Ingress"))
    ing["metadata"]["name"] = "nan-host"
    ing["spec"]["rules"] = [{"host": float("nan")},
                            {"host": "app-0.corp.io"}]
    request = agilebank_reviews.request(ing, "CREATE", "n1")
    before, _ = _cells()
    assert _sig(system.review(request)) == _sig(oracle.review(request))
    assert _grew(before, _cells()[0]) == {("fallback", "yes"): 1}


@pytest.mark.parametrize("tier", TIERS)
def test_the_other_plan_families_at_admission(tier, monkeypatch):
    """Slot keys (unique ingress host), existence (required storage
    class) and a count against a parameter (team quota): each object of
    a mixed inventory re-applied under its own name and under a new one,
    every cell from the index, every answer the oracle's."""
    monkeypatch.setenv("GK_JOIN_ASSERT", "1")
    system, oracle, objects = _referential_world(tier)
    before, _ = _cells()
    denied = 0
    for k, obj in enumerate(objects[3:150:3] + objects[4:150:9]
                            + objects[5:150:7]):
        new = copy.deepcopy(obj)
        new["metadata"]["name"] += "-new"
        for request in (
                agilebank_reviews.request(copy.deepcopy(obj), "UPDATE",
                                          f"f{k}", obj),
                agilebank_reviews.request(new, "CREATE", f"g{k}")):
            want = _sig(oracle.review(request))
            assert _sig(system.review(request)) == want, request["name"]
            denied += bool(want)
    assert denied >= 3
    grew = _grew(before, _cells()[0])
    assert grew and set(grew) <= {("index", "yes"), ("index", "no")}


# ---- a join-free bundle pays nothing ---------------------------------------------


@pytest.mark.parametrize("tier", TIERS)
def test_a_join_free_bundle_never_enters_join_lookup(tier):
    from gatekeeper_tpu.util.synthetic import make_pods, make_templates

    c = Client(driver=_driver(tier))
    templates, constraints = make_templates(6)
    for t, k in zip(templates, constraints):
        c.add_template(t)
        c.add_constraint(k)
    pods = make_pods(40, seed=33, violation_rate=0.3)
    for p in pods[:20]:
        c.add_data(p)
    # what earlier tests left unflushed on this thread's clock
    obstrace.stage_clock(obstrace.PATH_BATCH).flush()
    calls0 = dict(_stage_rows("host_stage_calls_total"))
    cells0, _ = _cells()
    upkeep0 = dict(_stage_rows("join_index_upkeep_seconds_total"))
    for at in range(20, 40, 5):
        c.review_batch([
            agilebank_reviews.request(p, "CREATE", f"j{i}")
            for i, p in enumerate(pods[at:at + 5])])
    obstrace.stage_clock(obstrace.PATH_BATCH).flush()
    calls = _stage_rows("host_stage_calls_total")
    assert calls.get(("batch", "render"), 0) > calls0.get(
        ("batch", "render"), 0)
    assert calls.get(("batch", "join_lookup"), 0) == calls0.get(
        ("batch", "join_lookup"), 0)
    assert _cells()[0] == cells0
    assert dict(_stage_rows("join_index_upkeep_seconds_total")) == upkeep0
    assert c.driver._join_state is None


@pytest.mark.parametrize("tier", TIERS)
def test_a_referential_batch_books_the_join_lookup_stage(world, tier):
    _route(world.system.driver, tier)
    obstrace.stage_clock(obstrace.PATH_BATCH).flush()
    calls0 = dict(_stage_rows("host_stage_calls_total"))
    secs0 = dict(_stage_rows("host_stage_seconds_total"))
    world.system.review(_request(world, "create_colliding")[0])
    obstrace.stage_clock(obstrace.PATH_BATCH).flush()
    key = ("batch", "join_lookup")
    assert _stage_rows("host_stage_calls_total")[key] > calls0.get(key, 0)
    assert _stage_rows("host_stage_seconds_total")[key] > secs0.get(key, 0)


# ---- the benchmark's plain reference, tied to the bundle -------------------------


def test_the_plain_admission_reference_agrees_with_the_oracle(world):
    """benchmark/lib/agilebank_admission_reference.py against the
    InterpDriver on the same reviews (as tests/test_agilebank_parity.py
    ties the audit reference): every named case, and the benchmark's
    mix with its collisions turned up."""
    ref = agilebank_admission_reference.AdmissionReference(
        world.constraints, world.objects)
    tr = {"service_share": 0.6, "pod_share": 0.3, "namespace_share": 0.1,
          "service_update_keep_share": 0.5,
          "service_update_move_share": 0.25, "service_create_share": 0.25,
          "move_onto_share": 0.3, "move_out_of_pair_share": 0.2,
          "create_onto_share": 0.3, "namespace_unowned_share": 0.3}
    requests = [_request(world, case)[0] for case in SERVICE_CASES]
    requests += agilebank_reviews.build_requests(CONFIG, tr, SEED, 400, "r")
    denied = 0
    for request in requests:
        allowed, msgs = ref.verdict(request)
        want = sorted(
            f"[denied by {r.constraint['metadata']['name']}] {r.msg}"
            for r in world.oracle.review(request).results())
        assert msgs == want, request["uid"]
        assert allowed == (not want)
        denied += not allowed
    kinds = {r["kind"]["kind"] for r in requests}
    assert kinds == {"Service", "Pod", "Namespace"} and denied >= 40
