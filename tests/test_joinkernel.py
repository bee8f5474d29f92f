"""Referential policies: the cross-resource join/aggregate kernel
subsystem (ops/joinkernel.py, ISSUE 14).

Covers the interned-key normalization contract (type-strict: int vs str
label values never pool), the device kernels (segment-reduce group-by,
count/sum weights, in-row dedup), clause classification for all three
plan families (+ the shapes that must NOT classify), end-to-end
driver-vs-interpreter-oracle byte parity including the edge cases the
issue names (empty groups, all-one-group, tombstoned rows), the
O(key-group) delta path, join-aware render-cache reuse, the snapshot
round trip of the join index, and the route-ledger attribution."""

import numpy as np
import pytest

from gatekeeper_tpu.engine.interp import TemplatePolicy
from gatekeeper_tpu.ops import joinkernel as jk
from gatekeeper_tpu.ops.driver import TpuDriver
from gatekeeper_tpu.ops.vectorizer import vectorize
from gatekeeper_tpu.util.synthetic import (
    audit_result_sig,
    build_referential_driver,
    build_referential_oracle,
    make_referential_objects,
    make_referential_templates,
)

CAP = 4096  # above every per-constraint count: totals exact everywhere


def _policy(template):
    return TemplatePolicy.compile(
        template["spec"]["targets"][0]["rego"]
    )


def _family_template(family):
    templates, constraints = make_referential_templates(3)
    i = ["uniquehost", "requiredclass", "teamquota"].index(family)
    return templates[i], constraints[i]


# ---------------------------------------------------------------------------
# key normalization
# ---------------------------------------------------------------------------


class TestNormalization:
    def test_type_strict_never_pools(self):
        # the int-vs-str label coercion the satellite pins: distinct
        # values_equal classes -> distinct keys
        assert jk.normalize_join_key(5) != jk.normalize_join_key("5")
        assert jk.normalize_join_key(True) != jk.normalize_join_key(1)
        assert jk.normalize_join_key(False) != jk.normalize_join_key(0)
        assert jk.normalize_join_key(None) != jk.normalize_join_key("")

    def test_numeric_value_classes_pool(self):
        # 5 == 5.0 under the engine's values_equal -> one key
        assert jk.normalize_join_key(5) == jk.normalize_join_key(5.0)
        assert jk.normalize_join_key(2.5) == jk.normalize_join_key(2.5)

    def test_composites_canonical(self):
        a = jk.normalize_join_key({"b": 1, "a": [1, 2]})
        b = jk.normalize_join_key({"a": [1, 2], "b": 1})
        assert a == b and a.startswith("j:")

    def test_nan_is_unnormalizable(self):
        # NaN != NaN under values_equal; a table key would self-match
        assert jk.normalize_join_key(float("nan")) is None
        assert jk.normalize_join_key({"x": float("nan")}) is None


# ---------------------------------------------------------------------------
# device kernels (numpy twin of the traced forms)
# ---------------------------------------------------------------------------


class TestKernels:
    def test_segment_count_group_by(self):
        keys = np.array(
            [7, 3, 7, jk.KEY_INVALID, 3, 7, 9], np.int32
        )
        uk, uc = jk.compact_key_table(
            keys, (keys != jk.KEY_INVALID).astype(np.int32), np
        )
        got = {int(k): int(c) for k, c in zip(uk, uc)
               if k != jk.KEY_INVALID}
        assert got == {3: 2, 7: 3, 9: 1}

    def test_segment_sum_weights(self):
        # the aggregate kernel is weight-generic: counts are weight 1,
        # sums ride arbitrary per-entry weights (sum-by-key)
        keys = np.array([4, 4, 8, jk.KEY_INVALID], np.int32)
        w = np.array([10, 5, 7, 99], np.int32)
        uk, uc = jk.compact_key_table(keys, w, np)
        got = {int(k): int(c) for k, c in zip(uk, uc)
               if k != jk.KEY_INVALID}
        assert got == {4: 15, 8: 7}

    def test_lookup_counts_absent_and_invalid(self):
        uk = np.array([3, 7, jk.KEY_INVALID, jk.KEY_INVALID], np.int32)
        uc = np.array([2, 5, 0, 0], np.int32)
        q = np.array([3, 7, 4, -1, jk.KEY_INVALID], np.int32)
        got = jk.lookup_counts(uk, uc, q, np)
        assert list(got) == [2, 5, 0, 0, 0]

    def test_empty_table(self):
        uk = np.full(8, jk.KEY_INVALID, np.int32)
        uc = np.zeros(8, np.int32)
        assert list(jk.lookup_counts(
            uk, uc, np.array([1, 2], np.int32), np
        )) == [0, 0]

    def test_row_distinct_slot_keys(self):
        # a row providing the same key twice contributes once
        sid = np.array([[5, 5, 9], [9, -1, 9]], np.int32)
        mask = np.array([[True, True, True], [True, False, True]])
        flat = jk.row_distinct_slot_keys(sid, mask & (sid >= 0), np)
        per_row = flat.reshape(2, 3)
        assert sorted(x for x in per_row[0] if x != jk.KEY_INVALID) == [5, 9]
        assert sorted(x for x in per_row[1] if x != jk.KEY_INVALID) == [9]

    def test_jnp_matches_np(self):
        import jax.numpy as jnp

        keys = np.array([2, 9, 2, 2, jk.KEY_INVALID, 9], np.int32)
        w = (keys != jk.KEY_INVALID).astype(np.int32)
        uk_n, uc_n = jk.compact_key_table(keys, w, np)
        uk_j, uc_j = jk.compact_key_table(
            jnp.asarray(keys), jnp.asarray(w), jnp
        )
        assert list(uk_n) == list(np.asarray(uk_j))
        assert list(uc_n) == list(np.asarray(uc_j))


# ---------------------------------------------------------------------------
# classification
# ---------------------------------------------------------------------------


class TestClassification:
    @pytest.mark.parametrize("family,agg", [
        ("uniquehost", "dup"),
        ("requiredclass", "exists"),
        ("teamquota", "count"),
    ])
    def test_families_classify_exact(self, family, agg):
        t, _c = _family_template(family)
        prog = vectorize(_policy(t))
        assert prog is not None and prog.exact
        assert len(prog.join_plans) == 1
        assert prog.join_plans[0].agg == agg

    def test_message_reading_remote_entity_stays_interp(self):
        # a message embedding the OTHER row's fields (beyond its
        # identity: tests/test_joinkernel_selector.py) depends on group
        # content the delta invalidation cannot see -> no plan
        rego = """
package refbad

violation[{"msg": msg}] {
  host := input.review.object.spec.rules[_].host
  other := data.inventory.namespace[_][_]["Ingress"][_]
  otherhost := other.spec.rules[_].host
  host == otherhost
  not identical(other, input.review)
  msg := sprintf("duplicate of %v", [other.metadata.uid])
}

identical(obj, review) {
  obj.metadata.namespace == review.object.metadata.namespace
  obj.metadata.name == review.object.metadata.name
}
"""
        prog = vectorize(TemplatePolicy.compile(rego))
        assert prog is not None
        assert not prog.join_plans
        assert not prog.exact  # generic over-approximation took over

    def test_identity_helper_must_cover_scope_fields(self):
        # name-only identity over a NAMESPACE-scoped iteration would
        # merge objects across namespaces -> no plan
        rego = """
package refbad2

violation[{"msg": msg}] {
  host := input.review.object.spec.rules[_].host
  other := data.inventory.namespace[_][_]["Ingress"][_]
  other.spec.rules[_].host == host
  not identical(other, input.review)
  msg := sprintf("dup %v", [host])
}

identical(obj, review) {
  obj.metadata.name == review.object.metadata.name
}
"""
        prog = vectorize(TemplatePolicy.compile(rego))
        assert prog is not None and not prog.join_plans

    def test_structure_key_distinguishes_plans(self):
        t1, _ = _family_template("uniquehost")
        t3, _ = _family_template("teamquota")
        p1 = vectorize(_policy(t1))
        p3 = vectorize(_policy(t3))
        assert p1.structure_key() != p3.structure_key()
        # clones of one family share a structure (constraint-axis batching)
        templates, _ = make_referential_templates(6)
        pa = vectorize(_policy(templates[0]))
        pb = vectorize(_policy(templates[3]))
        assert pa.structure_key() == pb.structure_key()


# ---------------------------------------------------------------------------
# end-to-end parity + edge cases
# ---------------------------------------------------------------------------


def _parity(client, oracle_client):
    res, totals, _ = client.driver.audit_capped(CAP)
    ores, ototals, _ = oracle_client.driver.audit_capped(CAP)
    assert audit_result_sig(res) == audit_result_sig(ores)
    assert totals == ototals
    return res, totals


def _twin_clients(objs, n_templates=6):
    from gatekeeper_tpu.client.client import Client
    from gatekeeper_tpu.client.drivers import InterpDriver

    templates, constraints = make_referential_templates(n_templates)
    out = []
    for driver in (TpuDriver(), InterpDriver()):
        c = Client(driver=driver)
        for t in templates:
            c.add_template(t)
        for k in constraints:
            c.add_constraint(k)
        for o in objs:
            c.add_data(dict(o))
        out.append(c)
    return out


class TestEndToEndParity:
    def test_synthetic_corpus_byte_parity(self):
        d = build_referential_driver(6, 48)
        o = build_referential_oracle(6, 48)
        res, _ = _parity(d, o)
        assert res  # the corpus violates
        assert d.driver.last_sweep_stats.get("join_plans") == 3.0

    def test_all_one_group(self):
        # every ingress shares ONE host: every row is a duplicate
        objs = [
            {
                "apiVersion": "networking.k8s.io/v1", "kind": "Ingress",
                "metadata": {"name": f"ing-{i}", "namespace": "ns-0"},
                "spec": {"rules": [{"host": "only.corp.io"}]},
            }
            for i in range(7)
        ]
        d, o = _twin_clients(objs, n_templates=3)
        res, totals = _parity(d, o)
        dup_totals = [
            v for (kind, _n), v in totals.items() if "Uniquehost" in kind
        ]
        assert dup_totals and dup_totals[0][0] == 7

    def test_empty_groups(self):
        # no StorageClasses at all: every PVC reference dangles; and a
        # single unique-host ingress: zero duplicates
        objs = [
            {
                "apiVersion": "v1", "kind": "PersistentVolumeClaim",
                "metadata": {"name": f"p-{i}", "namespace": "ns-0"},
                "spec": {"storageClassName": f"cls-{i}"},
            }
            for i in range(4)
        ] + [{
            "apiVersion": "networking.k8s.io/v1", "kind": "Ingress",
            "metadata": {"name": "solo", "namespace": "ns-0"},
            "spec": {"rules": [{"host": "solo.corp.io"}]},
        }]
        d, o = _twin_clients(objs, n_templates=3)
        res, totals = _parity(d, o)
        exists_totals = [
            v for (kind, _n), v in totals.items()
            if "Requiredclass" in kind
        ]
        assert exists_totals and exists_totals[0][0] == 4

    def test_int_vs_str_team_labels_never_pool(self):
        # 3 pods with team 5 (int) and 2 with team "5" (str), limit 2:
        # only the int team exceeds — coercion would flag both
        objs = [
            {
                "apiVersion": "v1", "kind": "Pod",
                "metadata": {"name": f"pi-{i}", "namespace": "ns-0",
                             "labels": {"team": 5}},
                "spec": {},
            }
            for i in range(3)
        ] + [
            {
                "apiVersion": "v1", "kind": "Pod",
                "metadata": {"name": f"ps-{i}", "namespace": "ns-0",
                             "labels": {"team": "5"}},
                "spec": {},
            }
            for i in range(2)
        ]
        from gatekeeper_tpu.client.client import Client

        t, c = _family_template("teamquota")
        c = {**c, "spec": {**c["spec"], "parameters": {"limit": 2}}}
        d = Client(driver=TpuDriver())
        d.add_template(t)
        d.add_constraint(c)
        for obj in objs:
            d.add_data(dict(obj))
        res, totals, _ = d.driver.audit_capped(CAP)
        names = sorted(
            (r.review.get("object") or {})["metadata"]["name"]
            for r in res
        )
        assert names == ["pi-0", "pi-1", "pi-2"]
        assert all("has 3 pods (limit 2)" in r.msg for r in res)

    def test_tombstoned_rows_leave_groups(self):
        d = build_referential_driver(3, 30)
        o = build_referential_oracle(3, 30)
        _parity(d, o)
        # delete every Ingress: duplicate violations must all clear
        for obj in make_referential_objects(30, 1):
            if obj["kind"] == "Ingress":
                d.remove_data(dict(obj))
                o.remove_data(dict(obj))
        res, totals = _parity(d, o)
        assert not any(
            "Uniquehost" in kind for (kind, _n), (n, _how) in
            totals.items() if n
        )


# ---------------------------------------------------------------------------
# delta path: key-group locality
# ---------------------------------------------------------------------------


class TestDeltaPath:
    def _warm(self, n_t=6, n_r=48):
        d = build_referential_driver(n_t, n_r)
        d.driver.audit_capped(CAP)
        return d

    def test_churn_dispatches_only_key_group(self):
        d = self._warm()
        objs = make_referential_objects(48, 1)
        victim = [o for o in objs if o["kind"] == "Ingress"][0]
        old_host = victim["spec"]["rules"][0]["host"]
        victim = {**victim, "spec": {"rules": [{"host": "app-0.corp.io"}]}}
        host_rows = {}
        for o in objs:
            if o["kind"] == "Ingress":
                for r in o["spec"]["rules"]:
                    host_rows.setdefault(r["host"], set()).add(
                        o["metadata"]["name"]
                    )
        expect = (
            host_rows.get(old_host, set())
            | host_rows.get("app-0.corp.io", set())
        ) - {victim["metadata"]["name"]}
        d.add_data(victim)
        d.driver.audit_capped(CAP)
        st = d.driver.last_sweep_stats
        assert st.get("delta_rows") == float(1 + len(expect)), st
        assert st.get("join_affected_rows") == float(len(expect)), st

    def test_churn_parity_vs_oracle(self):
        d = self._warm()
        o = build_referential_oracle(6, 48)
        objs = make_referential_objects(48, 1)
        pod = [x for x in objs if x["kind"] == "Pod"][0]
        pod = {
            **pod,
            "metadata": {**pod["metadata"], "labels": {"team": "beta"}},
        }
        d.add_data(dict(pod))
        o.add_data(dict(pod))
        res, totals, _ = d.driver.audit_capped(CAP)
        assert "delta_rows" in d.driver.last_sweep_stats
        ores, ototals, _ = o.driver.audit_capped(CAP)
        assert audit_result_sig(res) == audit_result_sig(ores)
        assert totals == ototals

    def test_render_cache_reuses_unchanged_referential_results(self):
        """join_safe: a second sweep after unrelated churn re-renders
        only affected cells, not every referential candidate."""
        d = self._warm()
        drv = d.driver
        full_render = drv.last_sweep_stats.get("rendered_cells")
        # churn one PVC (its exists-group only touches itself)
        objs = make_referential_objects(48, 1)
        pvc = [x for x in objs if x["kind"] == "PersistentVolumeClaim"][0]
        pvc = {**pvc, "spec": {"storageClassName": "gold"}}
        d.add_data(pvc)
        drv.audit_capped(CAP)
        st = drv.last_sweep_stats
        assert st.get("rendered_cells", 0) < full_render

    def test_full_sweep_diff_bumps_affected_readers(self):
        """When churn exceeds the delta budget the FULL sweep's join
        index diff must still invalidate affected readers' cached
        renders (no stale quota counts)."""
        d = self._warm(3, 24)
        drv = d.driver
        o = build_referential_oracle(3, 24)
        objs = make_referential_objects(24, 1)
        # churn more rows than DELTA_MAX_ROWS to force the full path
        drv.DELTA_MAX_ROWS = 0
        pod = [x for x in objs if x["kind"] == "Pod"][0]
        pod = {
            **pod,
            "metadata": {**pod["metadata"], "labels": {"team": "alpha"}},
        }
        d.add_data(dict(pod))
        o.add_data(dict(pod))
        res, totals, _ = drv.audit_capped(CAP)
        assert "delta_rows" not in drv.last_sweep_stats
        ores, ototals, _ = o.driver.audit_capped(CAP)
        assert audit_result_sig(res) == audit_result_sig(ores)
        assert totals == ototals


# ---------------------------------------------------------------------------
# observability + divergence assertion
# ---------------------------------------------------------------------------


class TestObservability:
    def test_route_ledger_attributes_join_sweeps(self):
        d = build_referential_driver(3, 24)
        d.driver.audit_capped(CAP)
        snap = d.driver.route_ledger.snapshot()
        assert any(k.endswith("|join_plan") for k in snap["counts"])
        shapes = snap.get("join_plans")
        assert shapes and {s["agg"] for s in shapes} == {
            "dup", "exists", "count"
        }
        assert all(s["groups"] is not None for s in shapes)

    def test_divergence_assertion_raises_when_armed(self, monkeypatch):
        monkeypatch.setenv("GK_JOIN_ASSERT", "1")
        monkeypatch.setenv("GK_BUG_COMPAT", "0")
        with pytest.raises(jk.JoinDivergence):
            jk.note_false_positive("RefX", "c-refx", 3)

    def test_divergence_assertion_disarmed_by_bug_compat(self, monkeypatch):
        monkeypatch.setenv("GK_JOIN_ASSERT", "1")
        monkeypatch.setenv("GK_BUG_COMPAT", "1")
        jk.note_false_positive("RefX", "c-refx", 3)  # counts, no raise

    def test_clean_corpus_sweeps_under_assertion(self, monkeypatch):
        monkeypatch.setenv("GK_JOIN_ASSERT", "1")
        d = build_referential_driver(3, 24)
        o = build_referential_oracle(3, 24)
        _parity(d, o)


# ---------------------------------------------------------------------------
# snapshot round trip of the join index
# ---------------------------------------------------------------------------


class TestSnapshotJoinIndex:
    def _plans(self):
        templates, _ = make_referential_templates(3)
        plans = []
        for t in templates:
            plans.extend(vectorize(_policy(t)).join_plans)
        return tuple(plans)

    def test_persist_restore_unit(self):
        plans = self._plans()
        st = jk.JoinState(plans, rebuild_gen=4)
        st.providers[0] = {11: {0, 2}, 13: {5}}
        st.readers[0] = {11: {0, 2, 9}}
        st.row_pkeys[0] = {0: (11,), 2: (11,), 5: (13,)}
        st.row_rkeys[0] = {0: (11,), 2: (11,), 9: (11,)}
        st.built = True
        data = st.persist()
        back = jk.JoinState.restore(plans, data, rebuild_gen=7)
        assert back is not None and back.built
        assert back.providers[0] == st.providers[0]
        assert back.readers[0] == st.readers[0]
        assert back.row_pkeys[0] == st.row_pkeys[0]
        # drift: a different plan set refuses the restore
        assert jk.JoinState.restore(plans[:1], data, 7) is None

    def test_round_trip_keeps_delta_path(self, tmp_path):
        from gatekeeper_tpu.client.client import Client
        from gatekeeper_tpu.kube.inmem import InMemoryKube
        from gatekeeper_tpu.snapshot import SnapshotLoader, Snapshotter

        kube = InMemoryKube()
        for obj in make_referential_objects(24, 1):
            kube.create(obj)
        templates, constraints = make_referential_templates(3)

        def fresh():
            c = Client(driver=TpuDriver())
            c.driver.set_mesh(False)
            for t in templates:
                c.add_template(t)
            for k in constraints:
                c.add_constraint(k)
            return c

        c1 = fresh()
        for gvk in kube.list_gvks():
            for obj in kube.list(gvk):
                c1.add_data(obj)
        cold_res, cold_tot, _ = c1.driver.audit_capped(CAP)
        snap_dir = str(tmp_path / "snaps")
        snapper = Snapshotter(c1, snap_dir, interval_s=0.0)
        assert snapper.write_once() is not None

        c2 = fresh()
        loader = SnapshotLoader(snap_dir)
        assert loader.restore(c2, kube) == "restored"
        assert loader.delta_restored is True
        js = c2.driver._join_state
        assert js is not None and js.built
        res, tot, _ = c2.driver.audit_capped(CAP)
        # zero churn: the restored basis + join index serve without a
        # full dispatch
        assert c2.driver.last_sweep_stats.get("cached") == 1.0
        assert audit_result_sig(res) == audit_result_sig(cold_res)
        assert tot == cold_tot

    def test_join_index_drift_drops_basis(self, tmp_path, monkeypatch):
        from gatekeeper_tpu.client.client import Client
        from gatekeeper_tpu.kube.inmem import InMemoryKube
        from gatekeeper_tpu.snapshot import SnapshotLoader, Snapshotter

        kube = InMemoryKube()
        for obj in make_referential_objects(18, 1):
            kube.create(obj)
        templates, constraints = make_referential_templates(3)

        def fresh():
            c = Client(driver=TpuDriver())
            c.driver.set_mesh(False)
            for t in templates:
                c.add_template(t)
            for k in constraints:
                c.add_constraint(k)
            return c

        c1 = fresh()
        for gvk in kube.list_gvks():
            for obj in kube.list(gvk):
                c1.add_data(obj)
        cold_res, cold_tot, _ = c1.driver.audit_capped(CAP)
        snap_dir = str(tmp_path / "snaps")
        assert Snapshotter(c1, snap_dir, interval_s=0.0).write_once()

        # simulate a plan-classification drift between writer and reader
        monkeypatch.setattr(
            jk.JoinState, "restore", classmethod(lambda *a, **k: None)
        )
        c2 = fresh()
        loader = SnapshotLoader(snap_dir)
        assert loader.restore(c2, kube) == "restored"  # pack kept
        assert loader.delta_restored is False  # basis dropped
        res, tot, _ = c2.driver.audit_capped(CAP)  # full sweep rebases
        assert audit_result_sig(res) == audit_result_sig(cold_res)
        assert tot == cold_tot


class TestReviewFixes:
    """Regression tests for the PR-review findings."""

    def test_nested_numbers_canonicalize_in_composite_keys(self):
        # values_equal({"a": 5}, {"a": 5.0}) is True: the composite key
        # form must pool them or the aggregate UNDER-approximates
        assert jk.normalize_join_key({"a": 5}) == \
            jk.normalize_join_key({"a": 5.0})
        assert jk.normalize_join_key([1, [2.0]]) == \
            jk.normalize_join_key([1.0, [2]])
        # non-integer floats and type-strictness unaffected
        assert jk.normalize_join_key({"a": 2.5}) != \
            jk.normalize_join_key({"a": 2})
        assert jk.normalize_join_key({"a": True}) != \
            jk.normalize_join_key({"a": 1})

    def test_join_sweep_does_not_flip_the_route_tier(self):
        """An audit-class join dispatch interleaved with review traffic
        must not fabricate route_flip incident events."""
        d = build_referential_driver(3, 24)
        drv = d.driver
        led = drv.route_ledger
        led.record("np", "latency", cells=3, n_reviews=1, lam=None)
        flips_before = led.flips
        drv.audit_capped(CAP)  # records the join_plan entry
        snap = led.snapshot()
        assert any(k == "device|join_plan" for k in snap["counts"])
        assert led.flips == flips_before
        # the next review-tier record does not see a phantom flip either
        led.record("np", "latency", cells=3, n_reviews=1, lam=None)
        assert led.flips == flips_before

    def test_gv_twin_corner_is_not_a_divergence(self, monkeypatch):
        """Two groupVersions of one ingress: the dup plan flags the
        flagged-but-renders-empty cells, but the armed assertion must
        recognize the documented corner instead of raising."""
        monkeypatch.setenv("GK_JOIN_ASSERT", "1")
        from gatekeeper_tpu.client.client import Client

        t, c = _family_template("uniquehost")
        objs = [
            {"apiVersion": "networking.k8s.io/v1", "kind": "Ingress",
             "metadata": {"name": "twin", "namespace": "ns-0"},
             "spec": {"rules": [{"host": "twin.corp.io"}]}},
            {"apiVersion": "networking.k8s.io/v1beta1", "kind": "Ingress",
             "metadata": {"name": "twin", "namespace": "ns-0"},
             "spec": {"rules": [{"host": "twin.corp.io"}]}},
        ]
        cl = Client(driver=TpuDriver())
        cl.add_template(t)
        cl.add_constraint(c)
        for o in objs:
            cl.add_data(dict(o))
        from gatekeeper_tpu.metrics.views import global_registry

        def divergences():
            rows = global_registry().view_rows(
                "join_plan_divergence_total"
            )
            return sum(rows.values()) if rows else 0

        before = divergences()
        res, _totals, _ = cl.driver.audit_capped(CAP)  # must not raise
        # the oracle agrees: identical-by-(ns,name) twins never violate
        assert res == []
        assert divergences() == before  # corner filtered, not counted

    def test_join_plans_gauge_retracts_on_template_removal(self):
        from gatekeeper_tpu.metrics.views import global_registry

        d = build_referential_driver(3, 12)
        drv = d.driver
        drv.audit_capped(CAP)
        rows = global_registry().view_rows("join_plans")
        assert rows and list(rows.values())[-1] == 3.0
        for kind in list(drv.constraints):
            for name in list(drv.constraints[kind]):
                drv.delete_constraint(kind, name)
        for kind in list(drv.templates):
            drv.delete_template(kind)
        drv._ensure_join_state()
        rows = global_registry().view_rows("join_plans")
        assert rows and list(rows.values())[-1] == 0.0
