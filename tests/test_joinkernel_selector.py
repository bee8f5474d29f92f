"""The upstream K8sUniqueServiceSelector clause on the join tier (ISSUE
29): a key COMPUTED from a map (flatten_selector), an inventory iteration
that BINDS its namespace / name variables, and a message that names the
other row — sound under the identity rule (ops/joinkernel.py
_check_benign_guards) — classified to a dup JoinPlan and held to the
InterpDriver oracle on the corners the upstream Rego decides."""

import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "benchmark"))

from lib import agilebank  # noqa: E402

from gatekeeper_tpu.client.client import Client  # noqa: E402
from gatekeeper_tpu.client.drivers import InterpDriver  # noqa: E402
from gatekeeper_tpu.engine.interp import TemplatePolicy  # noqa: E402
from gatekeeper_tpu.ops import joinkernel as jk  # noqa: E402
from gatekeeper_tpu.ops.driver import TpuDriver  # noqa: E402
from gatekeeper_tpu.ops.vectorizer import vectorize  # noqa: E402
from gatekeeper_tpu.util.synthetic import audit_result_sig  # noqa: E402

CAP = 4096
UPSTREAM = agilebank._REGO["K8sUniqueServiceSelector"]
CLAUSE_MSG = ('msg := sprintf("same selector as service <%v> in namespace '
              '<%v>", [name, namespace])')


def _prog(rego):
    return vectorize(TemplatePolicy.compile(rego))


# ---- classification ----------------------------------------------------------


def test_upstream_clause_classifies_to_a_dup_plan_with_a_computed_key():
    prog = _prog(UPSTREAM)
    assert prog is not None and prog.exact
    (plan,) = prog.join_plans
    assert plan.agg == "dup" and plan.remote_kind == "Service"
    assert plan.remote_scope == "namespace" and not plan.local_slot
    assert plan.local_colkey == plan.remote_colkey
    assert plan.local_colkey == (
        "joinkey", (), ("object", "spec", "selector"), (),
        ("joinpairs", ":", ","))
    # the conditions on the local row alone AND with the aggregate
    assert len(prog.clauses) == 1 and len(prog.clauses[0].conds) == 4


@pytest.mark.parametrize("old, new", [
    # a remote field that is not the provider's identity, in the message
    (CLAUSE_MSG, 'msg := sprintf("same selector as <%v> at %v", '
                 '[name, other.spec.clusterIP])'),
    # the key itself is remote content too
    (CLAUSE_MSG, 'msg := sprintf("same selector <%v> as <%v>", '
                 '[other_selector, name])'),
    # a bound scope variable in a condition correlates with the local row
    (CLAUSE_MSG, "namespace == input.review.object.metadata.namespace\n  "
                 + CLAUSE_MSG),
    # an identity helper that excludes more than the row itself
    ("obj.kind == review.kind.kind", "obj.spec.type == review.kind.kind"),
    # an apiVersion helper that is not the review's own apiVersion
    ('apiVersion = sprintf("%v/%v", [g, v])',
     'apiVersion = sprintf("%v/%v", [v, g])'),
    # a key helper that is not the sorted joined pairs of a map
    ('flattened := concat(",", sort(selectors))',
     'flattened := concat(",", selectors)'),
], ids=["remote_field_in_msg", "remote_key_in_msg", "scope_var_in_cond",
        "identity_too_wide", "apiversion_swapped", "unsorted_key"])
def test_what_the_identity_rule_does_not_cover_stays_interp(old, new):
    assert UPSTREAM.count(old) == 1
    prog = _prog(UPSTREAM.replace(old, new))
    assert prog is not None and not prog.join_plans


def test_identity_named_through_the_object_classifies_too():
    rego = UPSTREAM.replace(
        CLAUSE_MSG, 'msg := sprintf("same selector as service <%v> in '
        'namespace <%v>", [other.metadata.name, other.metadata.namespace])')
    prog = _prog(rego)
    assert prog.exact and len(prog.join_plans) == 1


# ---- the computed key is the interpreter's flatten_selector ------------------


@pytest.mark.parametrize("selector", [
    {"app": "web", "tier": "x"}, {"tier": "x", "app": "web"}, {},
    {"app": "web", "n": 5}, {"n": 5}, {"b": True, "z": None},
    {"a": "b,c:d"}, {"a": "b", "c": "d"}, ["app", "web"], "app:web", 7,
    None, "absent", {"é": "ü", "a": "z"},
], ids=lambda s: repr(s)[:24])
def test_join_pairs_is_the_interpreters_flatten_selector(selector):
    from gatekeeper_tpu.engine.interp import QueryContext
    from gatekeeper_tpu.engine.value import freeze

    spec = {} if selector == "absent" else {"selector": selector}
    policy = TemplatePolicy.compile(UPSTREAM)
    got = QueryContext(policy, freeze({}), None).call_function(
        policy.main, "flatten_selector", (freeze({"spec": spec}),))
    want = jk.join_pairs(spec.get("selector"), ":", ",")
    assert got == want


# ---- oracle parity on the corners --------------------------------------------


def _svc(name, selector="absent", ns="ns-0"):
    spec = {"ports": [{"port": 80}]}
    if selector != "absent":
        spec["selector"] = selector
    return {"apiVersion": "v1", "kind": "Service",
            "metadata": {"name": name, "namespace": ns}, "spec": spec}


def _twins(objects):
    templates, constraints = agilebank.make_templates()
    out = []
    for driver in (TpuDriver(), InterpDriver()):
        c = Client(driver=driver)
        for t in templates:
            if t["spec"]["crd"]["spec"]["names"]["kind"] \
                    == "K8sUniqueServiceSelector":
                c.add_template(t)
        c.add_constraint(constraints[-1])
        for o in objects:
            c.add_data(o)
        out.append(c)
    return out


def _parity(system, oracle):
    res, totals, _ = system.driver.audit_capped(CAP)
    ores, ototals, _ = oracle.driver.audit_capped(CAP)
    assert audit_result_sig(res) == audit_result_sig(ores)
    assert totals == ototals
    assert system.driver.last_sweep_stats["join_plans"] >= 1
    return sorted((r.review["object"]["metadata"]["name"], r.msg)
                  for r in res)


def _settle():
    from gatekeeper_tpu.ops import deltasweep

    for t in list(deltasweep._BG_THREADS):
        if t.name != "gk-route-cal":
            t.join(timeout=120)


@pytest.fixture()
def armed(monkeypatch):
    monkeypatch.setenv("GK_JOIN_ASSERT", "1")


def test_non_string_and_empty_selectors_match_the_oracle(armed):
    objects = [
        _svc("a", {"app": "web"}), _svc("b", {"app": "web", "n": 5}),
        _svc("c", {"n": 5}), _svc("d", {}), _svc("e"),
        _svc("f", {"app": "solo"}), _svc("g", ["app", "web"], ns="ns-1"),
        {"apiVersion": "v1", "kind": "Pod",
         "metadata": {"name": "p", "namespace": "ns-0"},
         "spec": {"containers": []}},
    ]
    got = _parity(*_twins(objects))
    # a pair that concat refuses drops out of the key: a and b collide;
    # c, d, e and g all flatten to the empty string
    assert ("a", "same selector as service <b> in namespace <ns-0>") in got
    assert {n for n, _m in got} == {"a", "b", "c", "d", "e", "g"}
    assert sum(1 for n, _m in got if n == "c") == 3


def test_rename_inside_a_colliding_group_renames_the_readers_message(armed):
    """Delete B, create C on B's selector between two sweeps: C takes
    B's freed pack row, so the key's provider ROW set is what it was.
    The reader A must still be re-rendered: its message names C now."""
    system, oracle = _twins([
        _svc("a", {"app": "web"}), _svc("b", {"app": "web"}),
        _svc("z", {"app": "other"})])
    before = _parity(system, oracle)
    assert ("a", "same selector as service <b> in namespace <ns-0>") \
        in before
    _settle()
    for c in (system, oracle):
        c.remove_data(_svc("b", {"app": "web"}))
        c.add_data(_svc("c", {"app": "web"}))
    after = _parity(system, oracle)
    assert system.driver.last_sweep_stats["full"] == 0.0  # the delta path
    assert after == [
        ("a", "same selector as service <c> in namespace <ns-0>"),
        ("c", "same selector as service <a> in namespace <ns-0>")]


def test_group_of_three_losing_one_member(armed):
    system, oracle = _twins(
        [_svc(n, {"app": "web", "tier": "t"}) for n in "abc"]
        + [_svc("z", {"app": "other"})])
    assert len(_parity(system, oracle)) == 6
    _settle()
    for c in (system, oracle):
        c.add_data(_svc("c", {"app": "web", "tier": "moved"}))
    after = _parity(system, oracle)
    stats = system.driver.last_sweep_stats
    assert stats["full"] == 0.0 and stats["join_affected_rows"] == 2.0
    assert after == [
        ("a", "same selector as service <b> in namespace <ns-0>"),
        ("b", "same selector as service <a> in namespace <ns-0>")]
    # and the last pair dissolves
    _settle()
    for c in (system, oracle):
        c.add_data(_svc("b", {"app": "gone"}))
    assert _parity(system, oracle) == []


def test_full_sweep_diff_sees_a_rename_too(armed):
    """The same rename with the delta path off: JoinState.rebuild's diff
    has to bump the reader whose group kept its rows."""
    system, oracle = _twins([
        _svc("a", {"app": "web"}), _svc("b", {"app": "web"})])
    system.driver.delta_enabled = False
    _parity(system, oracle)
    for c in (system, oracle):
        c.remove_data(_svc("b", {"app": "web"}))
        c.add_data(_svc("c", {"app": "web"}))
    after = _parity(system, oracle)
    assert system.driver.last_sweep_stats["full"] == 1.0
    assert ("a", "same selector as service <c> in namespace <ns-0>") in after


def test_join_index_round_trips_with_its_identities():
    (plan,) = _prog(UPSTREAM).join_plans
    st = jk.JoinState((plan,), 0)
    st.providers[0] = {7: {1, 2}}
    st.readers[0] = {7: {1, 2, 3}}
    st.row_pkeys[0] = {1: (7,), 2: (7,)}
    st.row_rkeys[0] = {1: (7,), 2: (7,), 3: (7,)}
    st.row_ident[0] = {1: ("ns", "a", "ns", "a"), 2: ("ns", "b", "ns", "b")}
    st.built = True
    back = jk.JoinState.restore((plan,), st.persist(), 0)
    assert back is not None and back.row_ident == st.row_ident
    assert back.providers == st.providers
    old = st.persist()
    del old["row_ident"]  # an index from before identities were kept
    assert jk.JoinState.restore((plan,), old, 0) is None


def test_a_miss_past_the_cap_renders_against_the_pruned_inventory(armed):
    """More colliding Services than the sweep keeps candidates for (the
    cap cuts the walk short, so knowledge stays incomplete): a cell that
    misses the render memo still renders against the key groups' pruned
    inventory, not the whole one — one cell against the full tree is
    O(inventory) (ISSUE 29: it made the key-churn cell's sweeps swing
    by seed)."""
    system, oracle = _twins(
        [_svc(f"s{i:03d}", {"app": f"a{i // 2}"}) for i in range(100)])
    driver = system.driver
    cap = 4
    assert driver._audit_topk(cap) < 100
    sizes = []
    real = driver._eval_cell

    def watching(constraint, kind, review, params, inventory, **kw):
        ns = inventory["namespace"] if "namespace" in inventory else {}
        sizes.append(sum(len(names) for gv in ns.values()
                         for kinds in gv.values()
                         for names in kinds.values()))
        return real(constraint, kind, review, params, inventory, **kw)

    driver._eval_cell = watching
    got, totals, _ = driver.audit_capped(cap)
    st = driver._delta_state
    assert int(st.counts[0]) == 100 > len(st.cand[0])  # incomplete
    assert sizes and max(sizes) <= 2 * len(st.cand[0]) < 100
    _settle()
    sizes.clear()
    # the first walked Service's partner leaves: a miss on the delta path
    for c in (system, oracle):
        c.add_data(_svc("s001", {"app": "elsewhere"}))
    got, totals, _ = driver.audit_capped(cap)
    assert driver.last_sweep_stats["full"] == 0.0
    assert sizes and max(sizes) < 100
    ogot, _ototals, _ = oracle.driver.audit_capped(CAP)
    assert set(audit_result_sig(got)) <= set(audit_result_sig(ogot))
    assert len(got) >= cap
