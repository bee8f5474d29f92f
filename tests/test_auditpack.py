"""Incremental audit packing (ops/auditpack.py): the resident columnar
arrays must stay bit-identical to a from-scratch rebuild under any sequence
of store mutations — including the namespace dependency (packed rows bake in
namespaceSelector resolution against the cached Namespace, so a Namespace
change must re-pack its dependents or the device mask under-approximates)."""

import copy

import numpy as np
import pytest

from gatekeeper_tpu.client.client import Client
from gatekeeper_tpu.ops.auditpack import _COL_FILL, _RP_FILL, AuditPackCache
from gatekeeper_tpu.ops.driver import TpuDriver
from gatekeeper_tpu.util.synthetic import make_pods, make_templates


NS_TEMPLATE = {
    "apiVersion": "templates.gatekeeper.sh/v1beta1",
    "kind": "ConstraintTemplate",
    "metadata": {"name": "k8snsselector"},
    "spec": {
        "crd": {"spec": {"names": {"kind": "K8sNsSelector"}}},
        "targets": [{
            "target": "admission.k8s.gatekeeper.sh",
            "rego": """
package k8snsselector

violation[{"msg": msg}] {
  input.review.object.metadata.name
  msg := "selected namespace resource"
}
""",
        }],
    },
}

NS_CONSTRAINT = {
    "apiVersion": "constraints.gatekeeper.sh/v1beta1",
    "kind": "K8sNsSelector",
    "metadata": {"name": "ns-sel"},
    "spec": {
        "match": {
            "kinds": [{"apiGroups": [""], "kinds": ["Pod"]}],
            "namespaceSelector": {"matchLabels": {"team": "audited"}},
        },
    },
}


def _fresh_like(client):
    """A new TpuDriver-backed client rebuilt from the same logical state."""
    c2 = Client(driver=TpuDriver())
    for kind in client.driver.templates:
        c2.driver.put_template(kind, client.driver.templates[kind])
        c2.driver.programs[kind] = client.driver.programs[kind]
    for kind in client.driver.constraints:
        for name, cons in client.driver.constraints[kind].items():
            c2.driver.put_constraint(kind, name, copy.deepcopy(cons))
    from gatekeeper_tpu.engine.value import thaw

    for obj, api, k, n, ns in client.driver.store.iter_objects():
        segs = (
            ("namespace", ns, api, k, n) if ns else ("cluster", api, k, n)
        )
        c2.driver.store.put(segs, thaw(obj))
    return c2


def _audit_keys(client, cap=10_000):
    res, _tot = client.audit_capped(cap)
    return sorted(
        (r.constraint["kind"], r.constraint["metadata"]["name"], r.msg,
         str(r.review.get("object", {}).get("metadata", {}).get("name")))
        for r in res.results()
    )


def _loaded(n_templates=5, n_pods=30):
    templates, constraints = make_templates(n_templates)
    c = Client(driver=TpuDriver())
    for t in templates:
        c.add_template(t)
    for cons in constraints:
        c.add_constraint(cons)
    for p in make_pods(n_pods, seed=3, violation_rate=0.4):
        c.add_data(p)
    return c


def test_incremental_update_matches_rebuild():
    c = _loaded()
    c.audit_capped(100)  # prime the resident pack
    # mutate: one pod flips to privileged
    bad = make_pods(1, seed=99, violation_rate=0.0)[0]
    bad["metadata"]["name"] = "pod-5"
    bad["metadata"]["namespace"] = "ns-5"
    bad["spec"]["containers"][0]["securityContext"] = {"privileged": True}
    c.add_data(bad)
    assert _audit_keys(c) == _audit_keys(_fresh_like(c))


def test_incremental_add_and_delete_matches_rebuild():
    c = _loaded()
    c.audit_capped(100)
    extra = make_pods(3, seed=50, violation_rate=1.0)
    for i, p in enumerate(extra):
        p["metadata"]["name"] = f"extra-{i}"
        c.add_data(p)
    # delete two originals
    c.remove_data({"apiVersion": "v1", "kind": "Pod",
                   "metadata": {"name": "pod-1", "namespace": "ns-1"}})
    c.remove_data({"apiVersion": "v1", "kind": "Pod",
                   "metadata": {"name": "pod-2", "namespace": "ns-2"}})
    keys = _audit_keys(c)
    assert keys == _audit_keys(_fresh_like(c))
    assert not any(k[3] == "pod-1" for k in keys)
    assert any("extra-0" == k[3] for k in keys)


def test_namespace_change_repacks_dependent_rows():
    """Adding/labeling a cached Namespace flips namespaceSelector matching
    for every pod in it; a stale packed row would hide the violations."""
    c = _loaded(n_templates=0, n_pods=0)
    c.add_template(NS_TEMPLATE)
    c.add_constraint(NS_CONSTRAINT)
    pods = make_pods(6, seed=11, violation_rate=0.0)
    for p in pods:
        p["metadata"]["namespace"] = "teamspace"
        c.add_data(p)
    # namespace not cached -> no match (plus autoreject semantics host-side)
    c.audit_capped(100)  # prime
    assert _audit_keys(c) == _audit_keys(_fresh_like(c))
    # now cache the namespace WITH the selected label: all pods must violate
    c.add_data({"apiVersion": "v1", "kind": "Namespace",
                "metadata": {"name": "teamspace",
                             "labels": {"team": "audited"}}})
    keys = _audit_keys(c)
    assert keys == _audit_keys(_fresh_like(c))
    assert len([k for k in keys if k[0] == "K8sNsSelector"]) == 6
    # flip the label off: violations must disappear
    c.add_data({"apiVersion": "v1", "kind": "Namespace",
                "metadata": {"name": "teamspace",
                             "labels": {"team": "other"}}})
    keys = _audit_keys(c)
    assert keys == _audit_keys(_fresh_like(c))
    assert not [k for k in keys if k[0] == "K8sNsSelector"]


def test_wipe_resets_pack():
    c = _loaded()
    c.audit_capped(100)
    c.wipe_data()
    assert _audit_keys(c) == []
    # refill after wipe works
    for p in make_pods(4, seed=60, violation_rate=1.0):
        c.add_data(p)
    assert _audit_keys(c) == _audit_keys(_fresh_like(c))


def test_row_growth_past_capacity():
    c = _loaded(n_templates=3, n_pods=4)
    c.audit_capped(100)
    cap0 = c.driver._audit_pack.capacity
    for p in make_pods(40, seed=70, violation_rate=0.3):
        p["metadata"]["name"] = "grown-" + p["metadata"]["name"]
        c.add_data(p)
    assert _audit_keys(c) == _audit_keys(_fresh_like(c))
    assert c.driver._audit_pack.capacity > cap0


def test_memo_invalidated_on_template_change():
    c = _loaded(n_templates=4, n_pods=20)
    k1 = _audit_keys(c, cap=5)
    assert _audit_keys(c, cap=5) == k1  # memoized second sweep identical
    # removing a constraint changes the constraint side; memo must not leak
    kind = sorted(c.driver.constraints)[0]
    name = sorted(c.driver.constraints[kind])[0]
    c.driver.delete_constraint(kind, name)
    k2 = _audit_keys(c, cap=5)
    assert not [k for k in k2 if k[0] == kind and k[1] == name]


def test_full_audit_uses_resident_pack():
    c = _loaded()
    exact1 = sorted(
        (r.constraint["kind"], r.msg,
         str(r.review.get("object", {}).get("metadata", {}).get("name")))
        for r in c.audit().results()
    )
    # mutate and re-audit through the same resident pack
    p = make_pods(1, seed=80, violation_rate=1.0)[0]
    p["metadata"]["name"] = "late-pod"
    c.add_data(p)
    exact2 = sorted(
        (r.constraint["kind"], r.msg,
         str(r.review.get("object", {}).get("metadata", {}).get("name")))
        for r in c.audit().results()
    )
    fresh = sorted(
        (r.constraint["kind"], r.msg,
         str(r.review.get("object", {}).get("metadata", {}).get("name")))
        for r in _fresh_like(c).audit().results()
    )
    assert exact2 == fresh
    assert exact1 != exact2


def test_flapping_object_stays_incremental():
    """Many change-log entries for few unique paths must take the per-row
    patch path, not the full rebuild (threshold counts unique paths)."""
    from gatekeeper_tpu.client.client import Client
    from gatekeeper_tpu.ops.driver import TpuDriver
    from gatekeeper_tpu.util.synthetic import make_pods, make_templates

    templates, constraints = make_templates(4)
    c = Client(driver=TpuDriver())
    for t, k in zip(templates, constraints):
        c.add_template(t)
        c.add_constraint(k)
    pods = make_pods(60, seed=5)
    for p in pods:
        c.add_data(p)
    c.audit_capped(5)
    ap = c.driver._audit_pack
    gen_before = ap.layout_gen
    flap = dict(pods[0])
    for i in range(2000):  # 2000 entries, 1 unique path
        flap = dict(flap)
        flap["metadata"] = dict(flap["metadata"])
        flap["metadata"]["labels"] = {"rev": str(i % 3)}
        c.driver.store.put(
            ("namespace", flap["metadata"]["namespace"], "v1", "Pod",
             flap["metadata"]["name"]), flap)
    c.audit_capped(5)
    assert ap.layout_gen == gen_before, "flapping forced a full rebuild"


# ---------------------------------------------------------------------------
# One batch per sync, held to a fresh _rebuild of the same store
# ---------------------------------------------------------------------------

TEAM_NS = "team"  # pods 0..7 live here, so a relabel re-packs 8 rows


def _pod(name, ns="ns-x", containers=1, labels=None, tag="1"):
    return {
        "apiVersion": "v1", "kind": "Pod",
        "metadata": {"name": name, "namespace": ns,
                     "labels": {} if labels is None else labels},
        "spec": {"containers": [
            {"name": f"c{j}", "image": f"registry.corp/svc:{tag}"}
            for j in range(containers)
        ]},
    }


def _pod_path(name, ns):
    return ("namespace", ns, "v1", "Pod", name)


def _batch_client():
    """Six template families + the namespaceSelector template, 30 pods
    (8 of them in TEAM_NS), synced once: the resident pack every case
    below mutates."""
    templates, constraints = make_templates(6)
    c = Client(driver=TpuDriver())
    for t in templates + [NS_TEMPLATE]:
        c.add_template(t)
    for cons in constraints + [NS_CONSTRAINT]:
        c.add_constraint(cons)
    for i, p in enumerate(make_pods(30, seed=3, violation_rate=0.4)):
        if i < 8:
            p["metadata"]["namespace"] = TEAM_NS
        c.add_data(p)
    c.audit_capped(20)
    return c


def _padded(arr, row, fill, width):
    out = np.full(width, fill, dtype=arr.dtype)
    out[tuple(slice(0, s) for s in arr.shape[1:])] = arr[row]
    return out


def _assert_matches_rebuild(driver):
    """The resident pack against a fresh cache's _rebuild of the same
    store on the SAME interner (ids compare directly): every rp and cols
    leaf row by row (by path; a wider side must hold the fill value in
    its excess), valid, row_of, ns_rows."""
    ap = driver._audit_pack
    fresh = AuditPackCache()
    fresh.sync(driver, driver._constraint_side()[3])
    assert set(ap.row_of) == set(fresh.row_of)
    live = set(ap.row_of.values())
    assert {int(r) for r in np.flatnonzero(ap.rp["valid"])} == live
    assert all(ap.row_path[r] == p for p, r in ap.row_of.items())

    def by_path(cache):
        return {ns: {cache.row_path[r] for r in rows}
                for ns, rows in cache.ns_rows.items() if rows}
    assert by_path(ap) == by_path(fresh)

    assert set(ap.rp) == set(fresh.rp)
    assert {k: set(v) for k, v in ap.cols.items()} == \
        {k: set(v) for k, v in fresh.cols.items()}
    leaves = [(("rp", k), ap.rp[k], fresh.rp[k], _RP_FILL[k])
              for k in fresh.rp]
    for ckey, fl in fresh.cols.items():
        leaves += [((ckey, leaf), ap.cols[ckey][leaf], arr, _COL_FILL[leaf])
                   for leaf, arr in fl.items()]
    for name, got, want, fill in leaves:
        assert got.dtype == want.dtype, name
        width = tuple(max(a, b) for a, b in
                      zip(got.shape[1:], want.shape[1:]))
        for path, fr in fresh.row_of.items():
            np.testing.assert_array_equal(
                _padded(got, ap.row_of[path], fill, width),
                _padded(want, fr, fill, width), err_msg=f"{name} {path}")
    return fresh


def _widths(cache):
    out = {("rp", k): v.shape[1:] for k, v in cache.rp.items()}
    for ckey, leaves in cache.cols.items():
        out.update({(ckey, k): v.shape[1:] for k, v in leaves.items()})
    return out


# each case: mutate(client) -> (rows the sync must pack, layout bumped?)

def _case_update_only(c):
    for i in range(10, 15):
        c.add_data(_pod(f"pod-{i}", f"ns-{i}", containers=2,
                        labels={"owner": "core"}, tag="77"))
    return 5, False


def _case_add_delete_reuse(c):
    ap = c.driver._audit_pack
    freed = ap.row_of[_pod_path("pod-12", "ns-12")]
    later = ap.row_of[_pod_path("pod-20", "ns-20")]
    c.remove_data(_pod("pod-12", "ns-12"))
    c.add_data(_pod("reuser", "ns-12", containers=2))  # takes the freed row
    c.add_data(_pod("appended", "ns-13"))  # free list empty: a new row
    c.remove_data(_pod("pod-20", "ns-20"))
    return 2, False, lambda: (
        ap.row_of[_pod_path("reuser", "ns-12")] == freed
        and ap.row_of[_pod_path("appended", "ns-13")] == 30
        and ap.free == [later]
    )


def _case_batch_outgrows_width(c):
    wide = {f"k{j}": "v" for j in range(11)}  # 11 label pairs: 8 -> 16
    c.add_data(_pod("pod-10", "ns-10", containers=2))
    c.add_data(_pod("pod-11", "ns-11", containers=5, labels=wide))
    c.add_data(_pod("pod-13", "ns-13", containers=7))  # slots 4 -> 8
    return 3, True


def _case_batch_within_width(c):
    # as wide as the widest resident row (3 containers, 5 labels), no wider
    labels = {f"k{j}": "v" for j in range(5)}
    c.add_data(_pod("pod-10", "ns-10", containers=3, labels=labels))
    c.add_data(_pod("pod-11", "ns-11", containers=3, labels=labels))
    return 2, False


def _case_narrower_replacement(c):
    # find the widest rows and replace them with the narrowest pod there is
    ap = c.driver._audit_pack
    n = 0
    for path, r in list(ap.row_of.items()):
        if len(ap.reviews[r]["object"]["spec"]["containers"]) == 3:
            c.add_data(_pod(path[4], path[1], containers=1, labels={}))
            n += 1
    assert n
    return n, False


def _case_namespace_relabel_with_pod_change(c):
    c.add_data(_pod("pod-3", TEAM_NS, containers=2, tag="99"))
    c.add_data({"apiVersion": "v1", "kind": "Namespace",
                "metadata": {"name": TEAM_NS,
                             "labels": {"team": "audited"}}})
    return 8 + 1, None  # 8 pods (pod-3 once) + the Namespace's own row


def _case_flapping_object(c):
    for i in range(60):
        c.driver.store.put(_pod_path("pod-9", "ns-9"),
                           _pod("pod-9", "ns-9", labels={"rev": str(i % 3)}))
    return 1, False


def _case_one_row(c):
    c.add_data(_pod("pod-25", "ns-25", containers=2, tag="5"))
    return 1, False


def _case_unseen_column_leaf(c):
    # a pack restored without one leaf: the batch that brings it creates
    # it (fill everywhere else), and here the batch is every row
    ap = c.driver._audit_pack
    ckey = next(k for k, v in ap.cols.items() if "mask" in v)
    del ap.cols[ckey]["mask"]
    for path, r in list(ap.row_of.items()):
        obj = copy.deepcopy(ap.reviews[r]["object"])
        obj["metadata"].setdefault("annotations", {})["touched"] = "1"
        c.add_data(obj)
    return 30, True


_CASES = [
    _case_update_only, _case_add_delete_reuse, _case_batch_outgrows_width,
    _case_batch_within_width, _case_narrower_replacement,
    _case_namespace_relabel_with_pod_change, _case_flapping_object,
    _case_one_row, _case_unseen_column_leaf,
]


@pytest.mark.parametrize(
    "case", _CASES, ids=[f.__name__[len("_case_"):] for f in _CASES])
def test_batch_sync_matches_rebuild(case, monkeypatch):
    c = _batch_client()
    ap = c.driver._audit_pack
    calls = []
    orig = AuditPackCache._pack_rows

    def spy(self, driver, rows, col_specs):
        if self is ap:
            calls.append(list(rows))
        return orig(self, driver, rows, col_specs)

    monkeypatch.setattr(AuditPackCache, "_pack_rows", spy)
    layout0, rebuild0, gen0 = ap.layout_gen, ap.rebuild_gen, ap._gen
    n_packed, bumped, *extra = case(c)
    widths0 = _widths(ap)  # after the case's set-up, before the sync
    c.audit_capped(20)  # the sweep's one sync

    assert ap.rebuild_gen == rebuild0, "took the rebuild path"
    assert len(calls) == 1, "one batch per sync"
    rows = calls[0]
    assert len(rows) == n_packed and len(set(rows)) == n_packed
    assert c.driver.last_sweep_stats["pack_rows"] == n_packed
    # a generation of its own for every re-packed row, in batch order
    gens = [ap.row_gen[r] for r in rows]
    assert gens == sorted(set(gens)) and gens[0] > gen0
    if bumped is not None:
        assert (ap.layout_gen != layout0) == bumped
    fresh = _assert_matches_rebuild(c.driver)
    if bumped:
        # grown leaves land on the bucketed width a rebuild would choose
        grown = {k for k, w in _widths(ap).items() if w != widths0.get(k)}
        assert grown
        want = _widths(fresh)
        assert all(_widths(ap)[k] == want[k] for k in grown)
    else:
        assert _widths(ap) == widths0
    for check in extra:
        assert check()
    assert _audit_keys(c) == _audit_keys(_fresh_like(c))


def test_tombstone_lands_before_the_batch_writes_a_reused_row():
    """delete A, add B in one sync: B takes A's row, and the row ends
    valid with B's content (the tombstone's valid=False did not win)."""
    c = _batch_client()
    ap = c.driver._audit_pack
    row = ap.row_of[_pod_path("pod-15", "ns-15")]
    c.remove_data(_pod("pod-15", "ns-15"))
    c.add_data(_pod("phoenix", "ns-15", containers=2))
    c.audit_capped(20)
    assert ap.row_of[_pod_path("phoenix", "ns-15")] == row
    assert ap.rp["valid"][row]
    assert ap.reviews[row]["object"]["metadata"]["name"] == "phoenix"
    assert ap.row_ns[row] == "ns-15" and row in ap.ns_rows["ns-15"]
    _assert_matches_rebuild(c.driver)


@pytest.mark.parametrize("path", ["delta", "full", "clean", "rebuild"])
def test_pack_rows_stat_counts_the_rows_packed(path):
    c = _batch_client()
    d = c.driver
    if path == "clean":
        c.audit_capped(20)
        assert d.last_sweep_stats["pack_rows"] == 0
        return
    if path == "rebuild":
        c.wipe_data()
        for i in range(4):
            c.add_data(_pod(f"w-{i}", "ns-w"))
        c.audit_capped(20)
        assert d.last_sweep_stats["pack_rows"] == 4
        return
    for i in range(10, 17):
        c.add_data(_pod(f"pod-{i}", f"ns-{i}", tag="3"))
    if path == "full":
        d._delta_state = None  # no basis: the sweep takes the full path
    c.audit_capped(20)
    stats = d.last_sweep_stats
    assert stats["pack_rows"] == 7
    assert ("delta_rows" in stats) == (path == "delta")
    c.audit_capped(20)
    assert d.last_sweep_stats["pack_rows"] == 0
