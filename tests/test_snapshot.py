"""State snapshot & warm resume (gatekeeper_tpu/snapshot/, ISSUE 3).

Covers the round trip (write -> restart -> restore -> first sweep equals
the cold sweep), the delta resync (only churned rows re-pack; deletions
tombstone; additions appear), every validation failure falling back to
the cold path with the outcome metric recorded, retention pruning, and
the malformed-constraint-spec tolerance satellite.
"""

import json
import os
import threading

import pytest

from gatekeeper_tpu.client.client import Client
from gatekeeper_tpu.kube.inmem import InMemoryKube
from gatekeeper_tpu.metrics.views import global_registry
from gatekeeper_tpu.ops.auditpack import AuditPackCache
from gatekeeper_tpu.ops.driver import TpuDriver
from gatekeeper_tpu.snapshot import SnapshotLoader, Snapshotter
from gatekeeper_tpu.snapshot import format as snapfmt

from .test_controllers import CONSTRAINT, TEMPLATE


def ns_obj(name, labeled):
    labels = {"team": name}
    if labeled:
        labels["gatekeeper"] = "yes"
    return {
        "apiVersion": "v1",
        "kind": "Namespace",
        "metadata": {"name": name, "labels": labels},
    }


def build_cluster(n=12, labeled_every=2):
    """InMemoryKube with n Namespaces (RV-stamped), every `labeled_every`-th
    compliant."""
    kube = InMemoryKube()
    for i in range(n):
        kube.create(ns_obj(f"ns-{i:03d}", labeled=i % labeled_every == 0))
    return kube


def fresh_client(mesh_width=None):
    """TPU client pinned to a known sweep sharding: single-device by
    default so the basis round-trip is deterministic; pass mesh_width to
    exercise the sharded sweep (the conftest provisions 8 virtual CPU
    devices).  set_mesh also invalidates every topology-keyed cache, so
    each test starts from a clean placement."""
    client = Client(driver=TpuDriver())
    client.driver.set_mesh(mesh_width is not None, width=mesh_width)
    return client


def make_client(kube):
    client = fresh_client()
    client.add_template(TEMPLATE)
    client.add_constraint(CONSTRAINT)
    for obj in kube.list(("", "v1", "Namespace")):
        client.add_data(obj)
    return client


def make_client_mesh(kube, width):
    client = fresh_client(mesh_width=width)
    client.add_template(TEMPLATE)
    client.add_constraint(CONSTRAINT)
    for obj in kube.list(("", "v1", "Namespace")):
        client.add_data(obj)
    return client


def audit_sig(client):
    res, totals = client.audit_capped(20)
    sig = sorted(
        ((r.resource or {}).get("metadata", {}).get("name", ""), r.msg)
        for r in res.results()
    )
    return sig, totals


def outcome_counts():
    rows = global_registry().view_rows("snapshot_restore_outcome_total")
    return {k[0]: v for k, v in rows.items()}


@pytest.fixture()
def snap_dir(tmp_path):
    return str(tmp_path / "snapshots")


class TestRoundTrip:
    def test_warm_resume_equals_cold_and_skips_repack(self, snap_dir):
        kube = build_cluster(n=12)
        client1 = make_client(kube)
        cold_sig, _ = audit_sig(client1)
        assert cold_sig  # the corpus violates

        snapper = Snapshotter(client1, snap_dir, interval_s=0.0)
        path = snapper.write_once()
        assert path is not None and os.path.isdir(path)
        assert snapfmt.list_snapshots(snap_dir) == [os.path.basename(path)]
        # payload dirs are 0700 (seal trust model)
        assert os.stat(snap_dir).st_mode & 0o777 == 0o700

        # "restart": a fresh client restores and delta-resyncs
        client2 = fresh_client()
        loader = SnapshotLoader(snap_dir)
        packs, rebuilds = _instrument(client2.driver)
        outcome = loader.restore(client2, kube)
        assert outcome == "restored"
        assert loader.stats == {
            "matched": 12, "changed": 0, "added": 0, "deleted": 0,
        }
        assert loader.delta_restored is True
        warm_sig, _ = audit_sig(client2)
        assert warm_sig == cold_sig
        # the whole point: no full rebuild, no per-row re-pack, and with
        # zero churn the restored delta basis serves the sweep without
        # any full [C, R] device dispatch
        assert rebuilds() == 0
        assert packs() == 0
        assert client2.driver.last_sweep_stats.get("cached") == 1.0
        # lazily-adopted leaves still serve every store surface: frozen()
        # freezes them on first call (a later inventory-reading template
        # install), and hashing the result must not raise
        frozen = client2.driver.store.frozen()
        hash(frozen["cluster"]["v1"]["Namespace"]["ns-000"])
        ns = client2.driver.store.cached_namespace("ns-000")
        assert ns is None or isinstance(ns, dict)

    def test_render_cache_of_an_older_shape_restores_as_misses(
            self, snap_dir):
        """A basis whose render_cache holds entries of the shape before
        ISSUE 32 — (signature, results, total), keyed on the cluster-wide
        count and all known candidates — restores; no entry is read as
        the new shape: the first sweep re-renders every constraint,
        answers as the interpreter does, and leaves RenderEntry rows the
        second sweep is served from."""
        from gatekeeper_tpu.ops.deltasweep import RenderEntry

        kube = build_cluster(n=12)
        client1 = make_client(kube)
        cold_sig, cold_totals = audit_sig(client1)
        st = client1.driver._delta_state
        assert st.render_cache
        for ckey, e in list(st.render_cache.items()):
            assert isinstance(e, RenderEntry)
            total = (len(e.results), "exact")
            st.render_cache[ckey] = (
                (e.cap, e.n_cand, e.walked, e.gens), e.results, total)
        assert Snapshotter(client1, snap_dir, interval_s=0.0).write_once()

        client2 = fresh_client()
        loader = SnapshotLoader(snap_dir)
        assert loader.restore(client2, kube) == "restored"
        assert loader.delta_restored is True
        restored = client2.driver._delta_state.render_cache
        assert restored and all(
            type(e) is tuple and len(e) == 3 for e in restored.values())
        warm_sig, warm_totals = audit_sig(client2)
        stats = client2.driver.last_sweep_stats
        assert stats["render_reused"] == 0.0
        assert stats["rendered_cells"] == len(cold_sig) > 0
        oracle = Client()
        oracle.add_template(TEMPLATE)
        oracle.add_constraint(CONSTRAINT)
        for obj in kube.list(("", "v1", "Namespace")):
            oracle.add_data(obj)
        assert (warm_sig, warm_totals) == audit_sig(oracle)
        assert (warm_sig, warm_totals) == (cold_sig, cold_totals)
        cache = client2.driver._delta_state.render_cache
        assert all(isinstance(e, RenderEntry) for e in cache.values())
        assert audit_sig(client2) == (warm_sig, warm_totals)
        assert client2.driver.last_sweep_stats["render_reused"] == len(cache)
        assert client2.driver.last_sweep_stats["rendered_cells"] == 0.0

    def test_delta_basis_dropped_on_mesh_width_drift(self, snap_dir):
        """A basis persisted under one sweep sharding layout must not
        serve a process whose mesh width differs: the restore keeps the
        pack (still 'restored') but drops the basis, and the first sweep
        is a full dispatch that rebases — with identical verdicts."""
        kube = build_cluster(n=12)
        client1 = make_client(kube)
        cold_sig, _ = audit_sig(client1)  # single-device basis (width 1)

        snapper = Snapshotter(client1, snap_dir, interval_s=0.0)
        assert snapper.write_once() is not None

        # same width restores the basis...
        same = fresh_client()
        loader = SnapshotLoader(snap_dir)
        assert loader.restore(same, kube) == "restored"
        assert loader.delta_restored is True

        # ...a width-4 mesh process drops it (width drift) but keeps the
        # restored pack and produces identical verdicts via a full sweep
        drifted = fresh_client(mesh_width=4)
        loader2 = SnapshotLoader(snap_dir)
        assert loader2.restore(drifted, kube) == "restored"
        assert loader2.delta_restored is False
        assert drifted.driver._delta_state is None
        warm_sig, _ = audit_sig(drifted)
        assert warm_sig == cold_sig
        assert drifted.driver.last_sweep_stats.get("cached") != 1.0

    def test_delta_basis_roundtrips_under_same_mesh_width(self, snap_dir):
        """Writer persists the mesh layout: a width-4 process's basis
        restores into another width-4 process and the first sweep serves
        from it (no full dispatch)."""
        kube = build_cluster(n=12)
        client1 = make_client_mesh(kube, width=4)
        cold_sig, _ = audit_sig(client1)

        snapper = Snapshotter(client1, snap_dir, interval_s=0.0)
        assert snapper.write_once() is not None

        client2 = fresh_client(mesh_width=4)
        loader = SnapshotLoader(snap_dir)
        assert loader.restore(client2, kube) == "restored"
        assert loader.delta_restored is True
        warm_sig, _ = audit_sig(client2)
        assert warm_sig == cold_sig
        assert client2.driver.last_sweep_stats.get("cached") == 1.0
        # churn after the restore rides the O(churn) delta path AGAINST
        # the restored (now mesh-committed) base mask — one dirty row
        # dispatched, not a full [C, R] resweep
        flipped = kube.get(("", "v1", "Namespace"), "ns-000")
        flipped["metadata"]["labels"].pop("gatekeeper", None)
        kube.update(flipped)
        client2.add_data(kube.get(("", "v1", "Namespace"), "ns-000"))
        churn_sig, _ = audit_sig(client2)
        assert client2.driver.last_sweep_stats.get("delta_rows") == 1.0
        ref = make_client_mesh(kube, width=4)
        ref_sig, _ = audit_sig(ref)
        assert churn_sig == ref_sig

    def test_delta_resync_packs_only_churn(self, snap_dir):
        kube = build_cluster(n=10)
        client1 = make_client(kube)
        audit_sig(client1)
        assert Snapshotter(client1, snap_dir).write_once() is not None

        # churn while "down": flip one compliant ns to violating, delete
        # one violating ns, add one new violating ns
        gvk = ("", "v1", "Namespace")
        flipped = kube.get(gvk, "ns-000")
        del flipped["metadata"]["labels"]["gatekeeper"]
        kube.update(flipped)
        kube.delete(gvk, "ns-001")
        kube.create(ns_obj("ns-new", labeled=False))

        client2 = fresh_client()
        loader = SnapshotLoader(snap_dir)
        packs, rebuilds = _instrument(client2.driver)
        assert loader.restore(client2, kube) == "restored"
        assert loader.stats == {
            "matched": 8, "changed": 1, "added": 1, "deleted": 1,
        }
        assert loader.delta_restored is True
        warm_sig, _ = audit_sig(client2)
        # the churned rows went through the O(churn) delta dispatch, not
        # a full sweep (changed + added + tombstoned = 3 dirty rows)
        assert client2.driver.last_sweep_stats.get("delta_rows") == 3.0
        # equal to a from-scratch evaluation of the churned cluster
        oracle = make_client(kube)
        cold_sig, _ = audit_sig(oracle)
        assert warm_sig == cold_sig
        names = [n for n, _ in warm_sig]
        assert "ns-000" in names and "ns-new" in names
        assert "ns-001" not in names
        assert rebuilds() == 0
        assert packs() == 2  # the flipped + the added row only

    def test_writer_skips_when_store_ahead_of_pack(self, snap_dir):
        kube = build_cluster(n=4)
        client = make_client(kube)
        audit_sig(client)
        kube.create(ns_obj("ns-late", labeled=False))
        client.add_data(kube.get(("", "v1", "Namespace"), "ns-late"))
        snapper = Snapshotter(client, snap_dir, capture_delta=False)
        assert snapper.write_once() is None
        assert "ahead of pack" in (snapper.last_error or "")
        audit_sig(client)  # sweep re-syncs the pack
        assert snapper.write_once() is not None

    def test_retention_prunes_old_snapshots(self, snap_dir):
        kube = build_cluster(n=3)
        client = make_client(kube)
        audit_sig(client)
        snapper = Snapshotter(client, snap_dir, retain=2,
                              capture_delta=False)
        paths = []
        for _ in range(4):
            snapper._last_write = 0.0  # defeat the cadence for the test
            p = snapper.write_once()
            assert p is not None
            paths.append(os.path.basename(p))
        names = snapfmt.list_snapshots(snap_dir)
        assert len(names) == 2
        assert names[0] == paths[-1]

    def test_restore_spans_visible_in_debug_traces(self, snap_dir):
        from gatekeeper_tpu.obs import trace as obstrace

        kube = build_cluster(n=4)
        client1 = make_client(kube)
        audit_sig(client1)
        snapper = Snapshotter(client1, snap_dir, capture_delta=False)
        assert snapper.write_once() is not None
        client2 = fresh_client()
        assert SnapshotLoader(snap_dir).restore(client2, kube) == "restored"
        traces = json.loads(obstrace.traces_json())["traces"]
        restore = [t for t in traces if t.get("root") == "snapshot.restore"]
        assert restore, "snapshot.restore trace missing from /debug/traces"
        names = {s.get("name") for s in restore[0].get("spans", [])}
        assert {"snapshot.load", "snapshot.install",
                "snapshot.resync"} <= names

    def test_no_snapshot_means_cold_outcome_none(self, snap_dir):
        kube = build_cluster(n=2)
        client = fresh_client()
        before = outcome_counts().get("none", 0)
        assert SnapshotLoader(snap_dir).restore(client, kube) == "none"
        assert outcome_counts().get("none", 0) == before + 1


def _instrument(driver):
    """Counters for per-row re-packs and full rebuilds on a driver's
    audit pack (class-level methods wrapped per-instance)."""
    state = {"packs": 0, "rebuilds": 0}
    ap = driver._audit_pack
    orig_pack = AuditPackCache._pack_rows
    orig_rebuild = AuditPackCache._rebuild

    def pack_rows(self, drv, rows, *a, **k):
        if self is driver._audit_pack:
            state["packs"] += len(rows)
        return orig_pack(self, drv, rows, *a, **k)

    def rebuild(self, *a, **k):
        if self is driver._audit_pack:
            state["rebuilds"] += 1
        return orig_rebuild(self, *a, **k)

    ap.__class__._pack_rows = pack_rows
    ap.__class__._rebuild = rebuild
    return (lambda: state["packs"]), (lambda: state["rebuilds"])


@pytest.fixture(autouse=True)
def _restore_auditpack_methods():
    orig_pack = AuditPackCache._pack_rows
    orig_rebuild = AuditPackCache._rebuild
    yield
    AuditPackCache._pack_rows = orig_pack
    AuditPackCache._rebuild = orig_rebuild


class TestValidationFallback:
    def _snapshot(self, snap_dir, n=6):
        kube = build_cluster(n=n)
        client = make_client(kube)
        sig, _ = audit_sig(client)
        snapper = Snapshotter(client, snap_dir, capture_delta=False)
        assert snapper.write_once() is not None
        return kube, sig

    def _assert_fallback_then_cold_ok(self, snap_dir, kube, cold_sig):
        before = outcome_counts().get("fallback", 0)
        client = fresh_client()
        outcome = SnapshotLoader(snap_dir).restore(client, kube)
        assert outcome == "fallback"
        assert outcome_counts().get("fallback", 0) == before + 1
        # the cold path still serves correct results
        client.add_template(TEMPLATE)
        client.add_constraint(CONSTRAINT)
        for obj in kube.list(("", "v1", "Namespace")):
            client.add_data(obj)
        sig, _ = audit_sig(client)
        assert sig == cold_sig

    def test_corrupt_manifest_falls_back(self, snap_dir):
        kube, sig = self._snapshot(snap_dir)
        snap = os.path.join(snap_dir, snapfmt.list_snapshots(snap_dir)[0])
        mpath = os.path.join(snap, snapfmt.MANIFEST)
        with open(mpath) as f:
            manifest = json.load(f)
        manifest["schema"] = 999  # content change breaks the hmac too
        with open(mpath, "w") as f:
            json.dump(manifest, f)
        self._assert_fallback_then_cold_ok(snap_dir, kube, sig)

    def test_wrong_hmac_falls_back(self, snap_dir):
        kube, sig = self._snapshot(snap_dir)
        snap = os.path.join(snap_dir, snapfmt.list_snapshots(snap_dir)[0])
        mpath = os.path.join(snap, snapfmt.MANIFEST)
        with open(mpath) as f:
            manifest = json.load(f)
        manifest["hmac"] = "0" * 64
        with open(mpath, "w") as f:
            json.dump(manifest, f)
        self._assert_fallback_then_cold_ok(snap_dir, kube, sig)

    def test_truncated_array_falls_back(self, snap_dir):
        kube, sig = self._snapshot(snap_dir)
        snap = os.path.join(snap_dir, snapfmt.list_snapshots(snap_dir)[0])
        apath = os.path.join(snap, snapfmt.ARRAYS)
        blob = open(apath, "rb").read()
        with open(apath, "wb") as f:
            f.write(blob[: len(blob) // 2])
        self._assert_fallback_then_cold_ok(snap_dir, kube, sig)

    def test_tampered_payload_fails_checksum(self, snap_dir):
        kube, sig = self._snapshot(snap_dir)
        snap = os.path.join(snap_dir, snapfmt.list_snapshots(snap_dir)[0])
        ipath = os.path.join(snap, snapfmt.INTERNER)
        strings = json.load(open(ipath))
        with open(ipath, "w") as f:
            json.dump(strings + ["evil"], f)
        self._assert_fallback_then_cold_ok(snap_dir, kube, sig)

    def test_fully_stale_resource_versions_fall_back(self, snap_dir):
        kube, _sig = self._snapshot(snap_dir)
        # every object re-written while down: all recorded RVs stale
        gvk = ("", "v1", "Namespace")
        for obj in kube.list(gvk):
            obj["metadata"]["labels"]["touched"] = "yes"
            kube.update(obj)
        before = outcome_counts().get("fallback", 0)
        client = fresh_client()
        loader = SnapshotLoader(snap_dir)
        outcome = loader.restore(client, kube)
        assert outcome == "fallback"
        assert loader.stats["matched"] == 0
        assert outcome_counts().get("fallback", 0) == before + 1
        # safe degradation: every row re-packs and the sweep is correct
        warm_sig, _ = audit_sig(client)
        oracle = make_client(kube)
        cold_sig, _ = audit_sig(oracle)
        assert warm_sig == cold_sig

    def test_older_snapshot_used_when_newest_corrupt(self, snap_dir):
        kube, sig = self._snapshot(snap_dir)
        client1 = make_client(kube)
        audit_sig(client1)
        snapper = Snapshotter(client1, snap_dir, capture_delta=False)
        snapper._last_write = 0.0
        newest = snapper.write_once()
        assert newest is not None
        # corrupt only the newest; the older one must restore
        with open(os.path.join(newest, snapfmt.ARRAYS), "ab") as f:
            f.write(b"garbage")
        client2 = fresh_client()
        outcome = SnapshotLoader(snap_dir).restore(client2, kube)
        assert outcome == "restored"
        warm_sig, _ = audit_sig(client2)
        assert warm_sig == sig


class TestStoreDeltaSemantics:
    def test_put_dedups_same_resource_version(self):
        client = fresh_client()
        store = client.driver.store
        obj = ns_obj("ns-a", labeled=True)
        obj["metadata"]["resourceVersion"] = "41"
        client.add_data(obj)
        epoch = store.epoch
        client.add_data(json.loads(json.dumps(obj)))  # replayed list entry
        assert store.epoch == epoch  # no change-log spam
        obj2 = json.loads(json.dumps(obj))
        obj2["metadata"]["resourceVersion"] = "42"
        client.add_data(obj2)
        assert store.epoch == epoch + 1

    def test_put_dedups_equal_content_without_rv(self):
        client = fresh_client()
        store = client.driver.store
        obj = ns_obj("ns-b", labeled=False)
        client.add_data(obj)
        epoch = store.epoch
        client.add_data(json.loads(json.dumps(obj)))
        assert store.epoch == epoch
        changed = ns_obj("ns-b", labeled=True)
        client.add_data(changed)
        assert store.epoch == epoch + 1


class TestMalformedConstraintSpec:
    """Satellite: non-dict spec tolerance across review/audit paths
    (mirrors target/match.py _get): one malformed constraint must not
    break every interp-path review."""

    REVIEW = {
        "uid": "u1",
        "kind": {"group": "", "version": "v1", "kind": "Namespace"},
        "name": "ns-x",
        "namespace": "",
        "operation": "CREATE",
        "userInfo": {"username": "t"},
        "object": ns_obj("ns-x", labeled=False),
    }

    @pytest.mark.parametrize("bad_spec", ["junk", ["junk"], 7, None])
    def test_review_survives_malformed_spec(self, bad_spec):
        client = fresh_client()
        client.add_template(TEMPLATE)
        client.add_constraint(CONSTRAINT)
        bad = {
            "apiVersion": "constraints.gatekeeper.sh/v1beta1",
            "kind": "K8sRequiredLabels",
            "metadata": {"name": "malformed"},
            "spec": bad_spec,
        }
        # bypass CRD validation, as a raw store write would
        client.driver.put_constraint("K8sRequiredLabels", "malformed", bad)
        res = client.review(dict(self.REVIEW))
        # the healthy constraint still evaluated and still denies
        names = {
            (r.constraint.get("metadata") or {}).get("name")
            for r in res.results()
        }
        assert "ns-must-have-gk" in names

    @pytest.mark.parametrize("bad_spec", ["junk", ["junk"]])
    def test_audit_survives_malformed_spec(self, bad_spec):
        kube = build_cluster(n=4)
        client = make_client(kube)
        bad = {
            "apiVersion": "constraints.gatekeeper.sh/v1beta1",
            "kind": "K8sRequiredLabels",
            "metadata": {"name": "malformed"},
            "spec": bad_spec,
        }
        client.driver.put_constraint("K8sRequiredLabels", "malformed", bad)
        sig, _ = audit_sig(client)
        assert sig  # healthy constraint still reports violations


class TestWebhookIdempotentStart:
    def test_double_start_does_not_leak_gc_sweeper(self):
        from gatekeeper_tpu.webhook import NamespaceLabelHandler
        from gatekeeper_tpu.webhook.server import WebhookServer

        def handler(_req):  # never invoked
            raise AssertionError

        def sweepers():
            return [
                t for t in threading.enumerate()
                if t.name == "webhook-gc" and t.is_alive()
            ]

        srv = WebhookServer(
            handler, NamespaceLabelHandler([]), port=0,
            certfile=None, keyfile=None,
        )
        baseline = len(sweepers())
        srv.start()
        first_server = srv._server
        try:
            first = [t for t in sweepers()]
            assert len(first) == baseline + 1
            srv.start()  # double start: old sweeper + listener replaced
            assert srv._server is not first_server
            for t in first:
                t.join(timeout=10.0)
            assert len(sweepers()) == baseline + 1
        finally:
            srv.stop()
            for t in sweepers():
                t.join(timeout=10.0)
            assert len(sweepers()) == baseline
