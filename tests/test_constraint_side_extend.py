"""The constraint side under a growing vocabulary (ISSUE 34): a string
new to the program extends the str-pred tables in place and uploads
those tables alone; nothing else of the pack is rebuilt or uploaded.

Exactness is the whole risk: a table column still zero for a string
whose predicate is true loses a violation.  Every case is held to a
driver that re-packs from nothing before every batch and to the
interpreter oracle, on bundles the test carries itself: the agilebank
bundle (benchmark/lib/agilebank.py), the synthetic families (two
`startswith` tables) and a deny-list template whose predicates flag a
violation when TRUE (the direction a stale column would lose)."""

import copy
import os
import random
import sys

import jax
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "benchmark"))

from lib import agilebank, agilebank_reviews  # noqa: E402

from gatekeeper_tpu.client.drivers import InterpDriver  # noqa: E402
from gatekeeper_tpu.obs import compilestats  # noqa: E402
from gatekeeper_tpu.ops import deltasweep  # noqa: E402
from gatekeeper_tpu.util.synthetic import (  # noqa: E402
    audit_result_sig,
    make_pods,
    make_templates,
)

from tests.test_admission_join import _client, _driver, _sig  # noqa: E402
from tests.test_tracing import _stage_rows  # noqa: E402

REFRESH = "constraint_side_refresh_total"
SEED = 34
AGILE = {"pods": 240, "services": 60, "namespaces": 8,
         "unlimited_share": 0.05, "production_share": 0.1,
         "prod_other_repo_share": 0.03, "unowned_share": 0.02,
         "paired_share": 0.1, "grouped_share": 0.03,
         "no_selector_share": 0.05}

DENY_REGO = """
package denylist

violation[{"msg": msg}] {
  c := input.review.object.spec.containers[_]
  startswith(c.image, input.parameters.banned)
  msg := sprintf("image %v is from the banned registry %v", [c.image, input.parameters.banned])
}

violation[{"msg": msg}] {
  v := input.review.object.metadata.labels.owner
  re_match(input.parameters.pattern, v)
  msg := sprintf("owner %v is a service account", [v])
}
"""


def _denylist():
    template = {
        "apiVersion": "templates.gatekeeper.sh/v1beta1",
        "kind": "ConstraintTemplate", "metadata": {"name": "denylist"},
        "spec": {"crd": {"spec": {"names": {"kind": "DenyList"}}},
                 "targets": [{"target": "admission.k8s.gatekeeper.sh",
                              "rego": DENY_REGO}]}}
    constraint = {
        "apiVersion": "constraints.gatekeeper.sh/v1beta1",
        "kind": "DenyList", "metadata": {"name": "deny"},
        "spec": {"match": {"kinds": [{"apiGroups": [""],
                                      "kinds": ["Pod"]}]},
                 "parameters": {"banned": "evil.io/",
                                "pattern": "^sa-[0-9]+$"}}}
    return [template], [constraint], make_pods(40, SEED)


def _bundle(name):
    """(templates, constraints, objects) of one bundle."""
    if name == "agilebank":
        return agilebank.cluster(AGILE, SEED)
    if name == "synthetic":
        templates, constraints = make_templates(12, SEED)
        return templates, constraints, make_pods(100, SEED + 1)
    return _denylist()


def _pod(name, namespace, image, owner="core"):
    # one shape class whatever the strings: one container, five labels
    return {"apiVersion": "v1", "kind": "Pod",
            "metadata": {"name": name, "namespace": namespace,
                         "labels": {"owner": owner, "team": "plat",
                                    "env": "prod", "cost": "cc1",
                                    "tier": "t1"}},
            "spec": {"containers": [{
                "name": "c0", "image": image,
                "resources": {"limits": {"cpu": "100m",
                                         "memory": "256Mi"}}}]}}


def _letters(n):
    return "".join(chr(ord("a") + int(d)) for d in str(n))


def _objects(bundle, b, fresh=True):
    """One batch's objects.  `fresh`: every object carries a string the
    program has not seen — an image under an allowed prefix and one
    under none, a label value the regex clause accepts and one it does
    not, a selector nobody holds; else batch 0's strings under new
    names (names are not interned)."""
    t = b if fresh else 0
    if bundle == "agilebank":
        ns = agilebank.make_namespace(3, random.Random(b), 0.0)
        # a Namespace's name is a string the match kernel reads
        ns["metadata"]["name"] = f"team-new-{t}"
        ns["metadata"]["labels"] = {
            "owner": f"fresh{_letters(t)}.agilebank.demo"}
        bad_ns = copy.deepcopy(ns)
        bad_ns["metadata"]["name"] = f"team-bad-{t}"
        bad_ns["metadata"]["labels"] = {"owner": f"user{t}.example.com"}
        svc = agilebank.make_service(
            0, "team-3", {"app": f"fresh-app-{t}", "tier": "edge"})
        svc["metadata"]["name"] = f"svc-new-{b}"
        twin = copy.deepcopy(svc)
        twin["metadata"]["name"] = f"svc-twin-{b}"
        return [
            _pod(f"ok-{b}", "production", f"openpolicyagent/fresh-{t}:1"),
            _pod(f"bad-{b}", "production", f"docker.io/fresh-{t}:1"),
            ns, bad_ns, svc, twin,
        ]
    return [
        _pod(f"ok-{b}", "ns-1", f"registry.corp/fresh-{t}:1",
             owner=f"owner-{t}"),
        _pod(f"bad-{b}", "ns-1", f"evil.io/fresh-{t}:1",
             owner=f"sa-{t}"),
        _pod(f"meh-{b}", "ns-2", f"elsewhere.io/fresh-{t}:1",
             owner=f"sa-{t}x"),
    ]


def _requests(bundle, b, fresh=True):
    return [agilebank_reviews.request(o, "CREATE", f"u{b}-{i}")
            for i, o in enumerate(_objects(bundle, b, fresh))]


def _device_driver():
    return _driver("device")  # the device tier at every size


def _load(driver, bundle):
    return _client(driver, *_bundle(bundle))


def _masks(client, requests):
    reviews = [client.target.handle_review(r)[1] for r in requests]
    with client.driver._lock:
        ordered, mask, rej = client.driver.compute_masks(reviews)
    n = len(reviews)
    return ([o[:2] for o in ordered], np.asarray(mask)[:, :n],
            np.asarray(rej)[:, :n])


def _drop(driver):
    """The control: nothing of the constraint side survives a batch."""
    driver._cs_cache = None
    driver._cs_device_cache = None


def _refresh():
    rows = _stage_rows(REFRESH) or {}
    return {k[0]: v for k, v in rows.items()}


def _grew(before):
    now = _refresh()
    return {k: now[k] - before.get(k, 0) for k in now
            if now[k] != before.get(k, 0)}


def _table_leaves(placed):
    _cs, gp = placed
    return [mat for _p, _e, tables in gp for mat, _idx in tables.values()]


BUNDLES = ("agilebank", "synthetic", "denylist")


@pytest.mark.parametrize("bundle", BUNDLES)
def test_extended_tables_answer_as_a_repack_and_the_oracle(bundle):
    """(a) batches that each bring fresh strings: masks and responses of
    the extending driver equal the re-packing control's, responses the
    interpreter oracle's."""
    system = _load(_device_driver(), bundle)
    control = _load(_device_driver(), bundle)
    oracle = _load(InterpDriver(), bundle)
    denied = 0
    for b in range(1, 5):
        requests = _requests(bundle, b)
        _drop(control.driver)
        got = [_sig(r) for r in system.review_batch(requests)]
        want = [_sig(r) for r in control.review_batch(requests)]
        truth = [_sig(r) for r in oracle.review_batch(requests)]
        assert got == truth, (bundle, b)
        assert want == truth, (bundle, b)
        denied += sum(1 for s in truth if s)
        _drop(control.driver)
        names, mask, rej = _masks(system, requests)
        cnames, cmask, crej = _masks(control, requests)
        assert names == cnames
        np.testing.assert_array_equal(mask, cmask)
        np.testing.assert_array_equal(rej, crej)
    assert denied >= 4  # the batches do raise violations
    ps = system.driver._cs_cache
    assert ps.tables and ps.table_vocab == \
        system.driver.interner.snapshot_size()
    assert _refresh().get("extend", 0) >= 4


@pytest.mark.parametrize("bundle", BUNDLES)
def test_refresh_counter_says_what_happened(bundle):
    """(b) `extend` once per batch with a new string, nothing on a batch
    without, `repack` on a constraint change and at the bucket's edge;
    the oracle's answers after each."""
    system = _load(_device_driver(), bundle)
    oracle = _load(InterpDriver(), bundle)
    d = system.driver

    def held(requests):
        got = [_sig(r) for r in system.review_batch(requests)]
        assert got == [_sig(r) for r in oracle.review_batch(requests)]

    held(_requests(bundle, 0))  # first contact: packs, compiles
    # ... and, for a referential bundle, builds the join index after its
    # dispatch, which interns the inventory's keys: the next batch's
    # first look at the side extends over those
    held(_requests(bundle, 20))
    for b in (1, 2, 3):
        before = _refresh()
        held(_requests(bundle, b))
        assert _grew(before) == {"extend": 1}, b
    before = _refresh()
    held(_requests(bundle, 9, fresh=False))  # batch 0's strings again
    assert _grew(before) == {}

    _templates, constraints, _objs = _bundle(bundle)
    extra = copy.deepcopy(constraints[-1])
    extra["metadata"]["name"] = "second-of-its-kind"
    for b, change in ((4, lambda c: c.add_constraint(extra)),
                      (5, lambda c: c.remove_constraint(extra))):
        change(system)
        change(oracle)
        before = _refresh()
        # fresh strings, so the request memo answers nothing: the side
        # is packed for the new epoch, then extended over the batch's
        held(_requests(bundle, b))
        assert _grew(before) == {"repack": 1, "extend": 1}

    # the vocabulary past the tables' width: one review whose labels
    # carry more fresh strings than the bucket has room for
    ps = d._cs_cache
    room = ps.width - d.interner.snapshot_size()
    assert 0 < room < 4096
    crowd = _pod("crowd", "production" if bundle == "agilebank" else "ns-1",
                 "evil.io/crowd:1")
    crowd["metadata"]["labels"].update(
        {f"k{i}": f"crowd-{i}" for i in range(room // 2 + 4)})
    before = _refresh()
    held([agilebank_reviews.request(crowd, "CREATE", "crowd")])
    assert _grew(before) == {"repack": 1}
    assert d._cs_cache.width == 2 * ps.width
    before = _refresh()
    held(_requests(bundle, 11))
    assert _grew(before) == {"extend": 1}


@pytest.mark.parametrize("bundle", BUNDLES)
def test_extension_keeps_executable_and_device_leaves(bundle):
    """(c) inside a bucket: `_fused_key` and the compile count stand, the
    device copy's vocabulary-independent leaves are the same objects,
    and only the table leaves were uploaded."""
    system = _load(_device_driver(), bundle)
    d = system.driver
    system.review_batch(_requests(bundle, 0))
    system.review_batch(_requests(bundle, 1))  # every shape class met
    key, gen = d._fused_key, d._fused_gen
    compiles = sum(compilestats.get_stats().provenance_mix().values())
    side, placed = d._cs_cache.side, d._cs_device_cache[1]
    leaves = jax.tree_util.tree_leaves(placed)
    tables = _table_leaves(placed)
    assert tables and len(tables) == len(d._cs_cache.tables)
    tv = d._cs_cache.table_vocab

    system.review_batch(_requests(bundle, 2))

    assert d._cs_cache.table_vocab > tv
    assert d._cs_cache.side is side
    assert d._fused_key is key and d._fused_gen == gen
    assert sum(compilestats.get_stats().provenance_mix().values()) \
        == compiles
    after = jax.tree_util.tree_leaves(d._cs_device_cache[1])
    moved = [i for i, (x, y) in enumerate(zip(leaves, after))
             if x is not y]
    assert len(after) == len(leaves)
    assert len(moved) == len(tables)
    assert all(any(after[i] is t for t in
                   _table_leaves(d._cs_device_cache[1])) for i in moved)
    assert d._cs_uploaded in (0, len(tables))  # the last call of the batch
    # a repeat of known strings uploads nothing
    system.review_batch(_requests(bundle, 12, fresh=False))
    assert d._cs_uploaded == 0
    assert all(x is y for x, y in zip(
        after, jax.tree_util.tree_leaves(d._cs_device_cache[1])))


def _join_bg():
    for t in list(deltasweep._BG_THREADS):
        t.join()


@pytest.mark.parametrize("bundle", ("agilebank", "synthetic"))
def test_delta_sweep_uploads_the_tables_alone(bundle):
    """(d) the audit path: a delta sweep after add_data of objects with
    new strings uploads the str-pred tables and nothing else, and keeps
    what a client packed from nothing keeps."""
    system = _load(_device_driver(), bundle)
    d = system.driver
    system.audit_capped(20)
    _join_bg()
    assert d.last_sweep_stats["full"] == 1.0
    assert d.last_sweep_stats["cs_upload_arrays"] > len(d._cs_cache.tables)
    # first contact with the batches' shape (five labels, a Namespace's
    # own) moves the pack's layout: that sweep is a full one, and it too
    # uploads the tables alone
    added = _objects(bundle, 0)
    for o in added:
        system.add_data(o)
    system.audit_capped(20)
    _join_bg()
    assert d.last_sweep_stats["cs_upload_arrays"] == len(d._cs_cache.tables)
    for b in (1, 2):
        objs = _objects(bundle, b)
        added += objs
        for o in objs:
            system.add_data(o)
        before = _refresh()
        got, totals = system.audit_capped(20)
        _join_bg()
        stats = d.last_sweep_stats
        assert stats["full"] == 0.0, stats
        assert stats["cs_upload_arrays"] == len(d._cs_cache.tables)
        assert _grew(before) == {"extend": 1}

        fresh = _load(_device_driver(), bundle)
        for o in added:
            fresh.add_data(o)
        want, want_totals = fresh.audit_capped(20)
        _join_bg()
        assert fresh.driver.last_sweep_stats["full"] == 1.0
        assert audit_result_sig(got.results()) == \
            audit_result_sig(want.results())
        assert totals == want_totals
    # known strings under new names: the tables already cover them
    for o in _objects(bundle, 13, fresh=False):
        system.add_data(o)
    before = _refresh()
    system.audit_capped(20)
    _join_bg()
    assert d.last_sweep_stats["full"] == 0.0
    assert d.last_sweep_stats["cs_upload_arrays"] == 0.0
    assert _grew(before) == {}


@pytest.mark.parametrize("case", ("epoch_passed", "live_epoch",
                                  "older_tables"))
def test_unlocked_cs_key_never_caches_past_the_live_epoch(case):
    """(e) the async compile thread dispatches unlocked with the cs_key
    it read under the lock: a device copy is never cached under an epoch
    the live one has moved past, and a key with an older table_vocab
    never displaces what a newer copy covers."""
    system = _load(_device_driver(), "denylist")
    d = system.driver
    system.review_batch(_requests("denylist", 0))
    with d._lock:
        _fn, _ordered, _rp, cp, _cols, group_params, _crow = \
            d._device_inputs([system.target.handle_review(r)[1]
                              for r in _requests("denylist", 1)])
        cs_key = d._cs_cache.key()
    assert cs_key == (d._cs_epoch, d._cs_cache.width,
                      d._cs_cache.table_vocab)
    if case == "epoch_passed":
        _t, constraints, _o = _bundle("denylist")
        extra = copy.deepcopy(constraints[0])
        extra["metadata"]["name"] = "another"
        system.add_constraint(extra)
        d._cs_device_cache = None
        placed = d._constraint_device_side(
            cp.arrays, group_params, cs_key, None)
        assert placed is not None
        assert d._cs_device_cache is None
        # the next locked dispatch packs and places for the live epoch
        system.review_batch(_requests("denylist", 2))
        assert d._cs_device_cache[0][0] == d._cs_epoch
    elif case == "live_epoch":
        d._cs_device_cache = None
        placed = d._constraint_device_side(
            cp.arrays, group_params, cs_key, None)
        assert d._cs_device_cache == ((cs_key[0], cs_key[1], 0), placed,
                                      cs_key[2])
    else:
        system.review_batch(_requests("denylist", 3))  # tables move on
        newer = d._cs_device_cache
        assert newer[2] > cs_key[2]
        placed = d._constraint_device_side(
            cp.arrays, group_params, cs_key, None)
        assert placed is newer[1] and d._cs_device_cache is newer
        assert d._cs_uploaded == 0
