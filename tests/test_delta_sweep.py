"""Incremental O(changes) audit sweep (ops/deltasweep.py): steady-state
capped audits evaluate only dirty rows on-device and fold the before/after
candidate columns into host-side counts/candidate state, falling back to a
full sweep only when the known candidate horizon runs out.
"""

import copy

import pytest

from gatekeeper_tpu.client.client import Client
from gatekeeper_tpu.client.drivers import InterpDriver
from gatekeeper_tpu.ops.driver import TpuDriver
from gatekeeper_tpu.util.synthetic import make_pods, make_templates


def _pair(n_templates=8, n_pods=150, violation_rate=0.3, seed=21):
    """(tpu client on single device, interp oracle) on the same workload."""
    out = []
    for driver in (TpuDriver(), InterpDriver()):
        c = Client(driver=driver)
        if isinstance(driver, TpuDriver):
            driver.mesh_enabled = False
            driver._mesh_cache = None
        templates, constraints = make_templates(n_templates)
        for t, k in zip(templates, constraints):
            c.add_template(t)
            c.add_constraint(k)
        for p in make_pods(n_pods, seed=seed, violation_rate=violation_rate):
            c.add_data(p)
        out.append(c)
    return out


def _audit_keys(c):
    return sorted((r.constraint["metadata"]["name"], r.msg)
                  for r in c.audit().results())


def _totals_vs_oracle(totals, oracle_totals):
    for k, (n, how) in totals.items():
        if how == "exact":
            assert n == oracle_totals[k][0], (k, n, oracle_totals[k])


def test_delta_path_used_and_matches_oracle_over_many_mutations():
    ct, ci = _pair()
    ct.audit_capped(5)  # cold full sweep bases the state
    pods = make_pods(150, seed=21, violation_rate=0.3)
    delta_sweeps = 0
    for i in range(6):
        # mix of add / modify / delete per sweep
        newp = make_pods(1, seed=500 + i, violation_rate=1.0)[0]
        newp["metadata"]["name"] = f"delta-add-{i}"
        ct.add_data(newp)
        ci.add_data(dict(newp))
        mod = dict(pods[i])
        mod["metadata"] = dict(mod["metadata"])
        mod["metadata"]["labels"] = {} if i % 2 else {"owner": "x"}
        ct.add_data(mod)
        ci.add_data(dict(mod))
        if i % 3 == 2:
            ct.remove_data(pods[10 + i])
            ci.remove_data(pods[10 + i])
        res_t, tot_t = ct.audit_capped(5)
        res_i, tot_i = ci.audit_capped(5)
        if "delta_rows" in ct.driver.last_sweep_stats:
            delta_sweeps += 1
        # per-constraint rendered counts agree where both are uncapped
        per_t, per_i = {}, {}
        for r in res_t.results():
            per_t[r.constraint["metadata"]["name"]] = per_t.get(
                r.constraint["metadata"]["name"], 0) + 1
        for r in res_i.results():
            per_i[r.constraint["metadata"]["name"]] = per_i.get(
                r.constraint["metadata"]["name"], 0) + 1
        for k, (n, how) in tot_t.items():
            if how == "exact":
                # on failure, capture which sweep path produced the count
                # and the incremental state (rare-flake diagnostics)
                st = ct.driver._delta_state
                assert n == tot_i[k][0], (
                    i, k, n, tot_i[k], ct.driver.last_sweep_stats,
                    None if st is None else {
                        "counts": st.counts.tolist(),
                        "row_cols": sorted(st.row_cols),
                        "store_epoch": st.store_epoch,
                        "cs_epoch": st.cs_epoch,
                    },
                )
        # full uncapped parity (forces a fresh full sweep for audit())
        assert _audit_keys(ct) == _audit_keys(ci), f"sweep {i}"
    assert delta_sweeps >= 4, f"delta path unused ({delta_sweeps} sweeps)"


def test_delta_counts_match_full_recompute():
    ct, _ = _pair(n_templates=6, n_pods=120)
    ct.audit_capped(4)
    for i in range(3):
        p = make_pods(1, seed=900 + i, violation_rate=1.0)[0]
        p["metadata"]["name"] = f"probe-{i}"
        ct.add_data(p)
        ct.audit_capped(4)
    st = ct.driver._delta_state
    delta_counts = st.counts.copy()
    # force a full resweep of the identical store and compare
    ct.driver._delta_state = None
    ct.driver._audit_cache = None
    ct.audit_capped(4)
    full_counts = ct.driver._delta_state.counts
    assert (delta_counts == full_counts).all()


def test_needs_full_sweep_escalation():
    """Exhausting the known horizon after deltas must transparently rebase
    with a full sweep, not miss candidates."""
    ct, ci = _pair(n_templates=1, n_pods=500, violation_rate=0.9)
    drv = ct.driver
    cap = 30  # K = 64 < labelreq candidates (~0.9*0.4*500): finite horizon
    ct.audit_capped(cap)
    st = drv._delta_state
    # make the state stale (delta applied) then chop its known candidates
    p = make_pods(1, seed=777, violation_rate=1.0)[0]
    p["metadata"]["name"] = "stale-maker"
    ct.add_data(p)
    ci.add_data(dict(p))
    ct.audit_capped(cap)
    st = drv._delta_state
    ci_res, ci_tot = ci.audit_capped(cap)
    if all(h is None for h in st.horizon):
        pytest.skip("workload produced complete knowledge; no horizon")
    # artificially shrink a horizon-limited candidate list to force the
    # escalation branch on the next render
    target = next(i for i, h in enumerate(st.horizon) if h is not None)
    st.cand[target] = st.cand[target][:2]
    res, totals = ct.audit_capped(cap)
    _totals_vs_oracle(totals, ci_tot)
    assert drv._delta_state is not st, "state must have been rebased"
    assert _audit_keys(ct) == _audit_keys(ci)


def test_many_dirty_rows_fall_back_to_full_sweep():
    ct, _ = _pair(n_templates=4, n_pods=80)
    ct.audit_capped(5)
    drv = ct.driver
    drv.DELTA_MAX_ROWS = 4
    for i in range(10):  # 10 dirty rows > 4
        p = make_pods(1, seed=1200 + i, violation_rate=0.5)[0]
        p["metadata"]["name"] = f"bulk-{i}"
        ct.add_data(p)
    ct.audit_capped(5)
    assert "delta_rows" not in drv.last_sweep_stats
    # and the state was rebased by the full sweep
    assert drv._delta_state.store_epoch == drv.store.epoch


def test_delta_disabled_env_forces_full_sweeps():
    ct, _ = _pair(n_templates=4, n_pods=60)
    ct.driver.delta_enabled = False
    ct.audit_capped(5)
    p = make_pods(1, seed=1500, violation_rate=1.0)[0]
    p["metadata"]["name"] = "nodelta"
    ct.add_data(p)
    ct.audit_capped(5)
    assert "delta_rows" not in ct.driver.last_sweep_stats


def test_render_cache_respects_cap_changes():
    """Re-auditing an unchanged cluster with a different cap must re-render
    (the per-constraint render cache keys on the cap)."""
    ct, ci = _pair(n_templates=4, n_pods=200, violation_rate=0.9)
    r5, t5 = ct.audit_capped(5)
    r50, t50 = ct.audit_capped(50)
    i5, it5 = ci.audit_capped(5)
    i50, it50 = ci.audit_capped(50)
    per = {}
    for r in r50.results():
        k = r.constraint["metadata"]["name"]
        per[k] = per.get(k, 0) + 1
    per_i = {}
    for r in i50.results():
        k = r.constraint["metadata"]["name"]
        per_i[k] = per_i.get(k, 0) + 1
    assert per == per_i, (per, per_i)
    assert len(r50.results()) > len(r5.results())
    # shrinking the cap must bound results again
    r2, _t2 = ct.audit_capped(2)
    per2 = {}
    for r in r2.results():
        k = r.constraint["metadata"]["name"]
        per2[k] = per2.get(k, 0) + 1
    assert all(v <= 2 + 1 for v in per2.values()), per2


def test_uncapped_audit_incremental_after_churn():
    """audit() (the --audit-exact-totals path) must stay correct and
    incremental under churn: the base mask is fetched once, then changed
    columns are patched host-side."""
    ct, ci = _pair(n_templates=6, n_pods=120)
    ct.audit_capped(5)  # base full sweep
    for i in range(4):
        p = make_pods(1, seed=2500 + i, violation_rate=1.0)[0]
        p["metadata"]["name"] = f"ua-{i}"
        ct.add_data(p)
        ci.add_data(dict(p))
        if i == 2:
            pods = make_pods(120, seed=21, violation_rate=0.3)
            ct.remove_data(pods[7])
            ci.remove_data(pods[7])
        assert _audit_keys(ct) == _audit_keys(ci), f"churn step {i}"
    st = ct.driver._delta_state
    assert st is not None and st.host_mask is not None
    # the host mask equals a fresh full fetch of the same store
    ct.driver._delta_state = None
    ct.driver._audit_cache = None
    _r, _o, fresh = ct.driver._audit_masks()
    assert (st.host_mask == fresh).all()


def test_status_write_back_keeps_the_delta_basis():
    """An all-roles pod's audit writes every constraint's status each
    interval, and the MODIFIED event comes back through the constraint
    controller as add_constraint(same spec, new status/resourceVersion).
    That must not bump the constraint-side epoch (the reference's
    constraintSemanticEquals: spec + labels) — or no sweep of a deployed
    pod is ever a delta sweep; a spec change still invalidates."""
    import copy

    ct, _ci = _pair()
    ct.audit_capped(5)
    driver = ct.driver
    epoch = driver._cs_epoch
    _templates, constraints = make_templates(8)
    written = copy.deepcopy(constraints[0])
    written["metadata"]["resourceVersion"] = "4711"
    written["status"] = {"auditTimestamp": "2026-01-01T00:00:00Z",
                         "totalViolations": 3,
                         "violations": [{"kind": "Pod", "name": "p"}]}
    ct.add_constraint(written)
    assert driver._cs_epoch == epoch

    newp = make_pods(1, seed=900, violation_rate=1.0)[0]
    newp["metadata"]["name"] = "after-status-write"
    ct.add_data(newp)
    ct.audit_capped(5)
    assert driver.last_sweep_stats.get("delta_rows") == 1.0

    relabeled = copy.deepcopy(written)
    relabeled["metadata"]["labels"] = {"tier": "gold"}
    ct.add_constraint(relabeled)
    assert driver._cs_epoch == epoch + 1
    respecced = copy.deepcopy(relabeled)
    respecced["spec"]["enforcementAction"] = "dryrun"
    ct.add_constraint(respecced)
    assert driver._cs_epoch == epoch + 2


# ---------------------------------------------------------------------------
# per-constraint result reuse keyed on the rows the capped walk read
# (driver._render_capped, deltasweep.RenderEntry)
# ---------------------------------------------------------------------------

_ALL_LABELS = {k: "x" for k in ("owner", "team", "env", "cost", "tier")}


def _flip_labels(pod, violating):
    p = copy.deepcopy(pod)
    p["metadata"]["labels"] = {} if violating else dict(_ALL_LABELS)
    return p


def _flip_privileged(pod, violating):
    p = copy.deepcopy(pod)
    for c in p["spec"]["containers"] + p["spec"].get("initContainers", []):
        c["securityContext"] = {"privileged": violating}
    return p


# (constraint key, pod edit that makes a row a candidate or not, how the
# total reads past the cap): a count-exact template and one whose device
# count is resources, not violations
EXACT = (("BenchLabelreq0", "c-benchlabelreq0"), _flip_labels, "exact")
RESOURCES = (("BenchPrivflag1", "c-benchprivflag1"), _flip_privileged,
             "resources")
BOTH = pytest.mark.parametrize(
    "ckey, flip, how_capped", [EXACT, RESOURCES], ids=["exact", "resources"])


def _reuse_pair():
    """The pair, the TPU side's pack and delta state based by one sweep
    (at a cap no test uses: its entries serve nothing)."""
    ct, ci = _pair(n_templates=6, n_pods=200, violation_rate=0.9)
    ct.audit_capped(3)
    return ct, ci


def _put(ct, ci, pod):
    ct.add_data(copy.deepcopy(pod))
    ci.add_data(copy.deepcopy(pod))


def _drop(ct, ci, pod):
    ct.remove_data(copy.deepcopy(pod))
    ci.remove_data(copy.deepcopy(pod))


def _pod_at(ct, row):
    return copy.deepcopy(ct.driver._audit_pack.reviews[row]["object"])


def _last_row(ct):
    reviews = ct.driver._audit_pack.reviews
    return max(r for r, rv in enumerate(reviews) if rv is not None)


def _kept(responses, ckey=None):
    out = {}
    for r in responses.results():
        k = (r.constraint["kind"], r.constraint["metadata"]["name"])
        out.setdefault(k, []).append(r)
    return out if ckey is None else out.get(ckey, [])


def _sig(results):
    return [(r.msg, r.review["name"], r.enforcement_action) for r in results]


def _sweep_checked(ct, ci, cap):
    """One capped sweep of the TPU client, held to the interpreter's
    answer (every kept violation is one of its, in number the cap's or
    all; an exact total is its total) and to a render of the same state
    with the reuse emptied (same violations, same order, same totals).
    -> (responses, totals, last_sweep_stats of the first render)."""
    res, tot = ct.audit_capped(cap)
    stats = dict(ct.driver.last_sweep_stats)
    ires, itot = ci.audit_capped(10 ** 9)
    got, want = _kept(res), _kept(ires)
    assert set(tot) == set(itot)
    for k, (n, how) in tot.items():
        mine, theirs = _sig(got.get(k, [])), _sig(want.get(k, []))
        if how == "exact":
            assert n == itot[k][0], (k, n, itot[k])
        else:
            assert n >= len({name for _m, name, _a in theirs}), k
        assert len(mine) >= min(cap, len(theirs)), (k, mine, theirs)
        for v in mine:
            assert v in theirs, (k, v)
            theirs.remove(v)
    st = ct.driver._delta_state
    served = st.render_cache
    st.render_cache = {}
    res2, tot2 = ct.audit_capped(cap)
    assert ct.driver.last_sweep_stats["render_reused"] == 0.0
    assert tot2 == tot
    assert _sig(res2.results()) == _sig(res.results())
    assert ct.driver._delta_state is st
    st.render_cache = served  # the entries the first render left
    return res, tot, stats


def _entry(ct, ckey):
    return ct.driver._delta_state.render_cache[ckey]


def _total_past_cap(n_cand, kept, how):
    return (max(n_cand, kept) if how == "resources" else n_cand, how)


@BOTH
def test_render_reuse_survives_churn_beyond_the_walked_prefix(
        ckey, flip, how_capped):
    """(a) A far row entering and leaving the constraint's candidates
    moves n_cand and nothing the walk read: the identical Result objects
    are replayed and the total follows the new count."""
    cap = 5
    ct, ci = _reuse_pair()
    far = _pod_at(ct, _last_row(ct))
    _put(ct, ci, flip(far, False))
    res0, tot0, _ = _sweep_checked(ct, ci, cap)
    e0 = _entry(ct, ckey)
    assert e0.capped and len(e0.walked) < 20 and e0.walked[-1] < _last_row(ct)
    assert tot0[ckey] == _total_past_cap(
        e0.n_cand, len(e0.results), how_capped)
    for step, violating in enumerate((True, False, True)):
        _put(ct, ci, flip(far, violating))
        res1, tot1, stats = _sweep_checked(ct, ci, cap)
        assert stats.get("delta_rows") == 1.0, stats
        n_cand = e0.n_cand + (1 if violating else 0)
        assert tot1[ckey] == _total_past_cap(
            n_cand, len(e0.results), how_capped), step
        assert _entry(ct, ckey) is e0  # served, not replaced
        old, new = _kept(res0, ckey), _kept(res1, ckey)
        assert len(old) == len(new) >= cap
        assert all(a is b for a, b in zip(old, new)), step
        assert stats["render_reused"] >= 1.0


@pytest.mark.parametrize("edit", ["replaced", "deleted", "inserted"])
@BOTH
def test_render_reuse_misses_on_churn_inside_the_walked_prefix(
        ckey, flip, how_capped, edit):
    """(b) A walked row re-packed, a walked row deleted, a new candidate
    between walked rows: the entry misses and the constraint re-renders."""
    cap = 5
    ct, ci = _reuse_pair()
    _sweep_checked(ct, ci, cap)
    e0 = _entry(ct, ckey)
    assert e0.capped
    if edit == "replaced":
        pod = _pod_at(ct, e0.walked[2])
        pod["metadata"].setdefault("annotations", {})["touched"] = "1"
        _put(ct, ci, pod)
    elif edit == "deleted":
        _drop(ct, ci, _pod_at(ct, e0.walked[1]))
    else:
        inside = next(r for r in range(e0.walked[0] + 1, e0.walked[-1])
                      if r not in e0.walked)
        _put(ct, ci, flip(_pod_at(ct, inside), True))
    _res, tot, stats = _sweep_checked(ct, ci, cap)
    assert stats.get("delta_rows") == 1.0, stats
    e1 = _entry(ct, ckey)
    assert e1 is not e0
    assert (e1.walked, e1.gens) != (e0.walked, e0.gens)
    assert stats["rendered_cells"] >= len(e1.results) > 0
    assert tot[ckey][1] == how_capped
    # and the entry it left serves the next, quiet sweep
    _res, _tot, stats = _sweep_checked(ct, ci, cap)
    assert _entry(ct, ckey) is e1
    assert stats["rendered_cells"] == 0.0


@pytest.mark.parametrize("churn", ["appended", "elsewhere"])
@BOTH
def test_render_reuse_of_a_walk_that_was_not_capped(
        ckey, flip, how_capped, churn):
    """(c) A walk that consumed every candidate also keys on the count:
    a candidate appended after its last row misses; churn that leaves the
    constraint's candidates alone is served."""
    cap = 10 ** 4
    ct, ci = _reuse_pair()
    far = _pod_at(ct, _last_row(ct))
    _put(ct, ci, flip(far, False))
    res0, tot0, _ = _sweep_checked(ct, ci, cap)
    e0 = _entry(ct, ckey)
    assert not e0.capped and e0.walked[-1] < _last_row(ct)
    assert tot0[ckey] == (len(e0.results), "exact")
    if churn == "appended":
        _put(ct, ci, flip(far, True))
    else:
        far["metadata"].setdefault("annotations", {})["touched"] = "1"
        _put(ct, ci, flip(far, False))
    res1, tot1, stats = _sweep_checked(ct, ci, cap)
    assert stats.get("delta_rows") == 1.0, stats
    e1 = _entry(ct, ckey)
    if churn == "appended":
        assert e1 is not e0
        assert e1.walked == e0.walked + (_last_row(ct),)
        assert tot1[ckey] == (len(e1.results), "exact")
        assert len(e1.results) > len(e0.results)
    else:
        assert e1 is e0 and tot1[ckey] == tot0[ckey]
        assert all(a is b for a, b in
                   zip(_kept(res0, ckey), _kept(res1, ckey)))


@BOTH
def test_render_reuse_across_the_cap(ckey, flip, how_capped):
    """(d) A constraint whose violations exactly fill the cap is not
    capped (no candidate stands behind it); one more candidate caps it,
    and its leaving un-caps it again: an entry that ended capped must not
    be served once nothing stands behind its walked prefix."""
    ct, ci = _reuse_pair()
    far = _pod_at(ct, _last_row(ct))
    _put(ct, ci, flip(far, False))
    _sweep_checked(ct, ci, 10 ** 4)
    cap = len(_entry(ct, ckey).results)  # every violation, and not one more
    _res, tot, _ = _sweep_checked(ct, ci, cap)
    under = _entry(ct, ckey)
    assert not under.capped and tot[ckey] == (cap, "exact")

    _put(ct, ci, flip(far, True))
    _res, tot, stats = _sweep_checked(ct, ci, cap)
    over = _entry(ct, ckey)
    assert over is not under and over.capped
    assert over.walked == under.walked
    assert tot[ckey] == _total_past_cap(over.n_cand, cap, how_capped)

    _put(ct, ci, flip(far, False))
    _res, tot, stats = _sweep_checked(ct, ci, cap)
    back = _entry(ct, ckey)
    assert back is not over and not back.capped
    assert tot[ckey] == (cap, "exact")
    assert _sig(back.results) == _sig(under.results)


@pytest.mark.parametrize("caps", [(5, 7), (7, 5), (5, 50), (5, 5)],
                         ids=["5-7", "7-5", "5-50", "5-5"])
def test_render_reuse_misses_on_a_cap_change(caps):
    """(e) The cap is per call and keys the entry: another cap re-renders
    every constraint (the same cap again is served)."""
    first, second = caps
    ct, ci = _reuse_pair()
    _sweep_checked(ct, ci, first)
    before = dict(ct.driver._delta_state.render_cache)
    _res, _tot, stats = _sweep_checked(ct, ci, second)
    after = ct.driver._delta_state.render_cache
    assert set(after) == set(before)
    if first == second:
        assert stats["render_reused"] == len(after)
        assert stats["rendered_cells"] == 0.0
        assert all(after[k] is before[k] for k in after)
    else:
        assert stats["render_reused"] == 0.0
        assert stats["rendered_cells"] > 0.0
        assert all(e.cap == second for e in after.values())


@pytest.mark.parametrize("churn", ["none", "far", "walked", "deleted"])
def test_render_reuse_counters_read_what_happened(churn):
    """(f) last_sweep_stats["render_reused"] counts the constraints
    served from the reuse, rendered_cells the rows rendered for the rest;
    the audit.render span carries both."""
    from gatekeeper_tpu.obs import trace as obs

    cap = 5
    ct, ci = _reuse_pair()
    _res, _tot, cold = _sweep_checked(ct, ci, cap)
    before = dict(ct.driver._delta_state.render_cache)
    assert cold["render_reused"] == 0.0
    assert cold["rendered_cells"] == sum(
        len(e.walked) for e in before.values())
    probe = before[EXACT[0]]
    if churn == "far":
        _put(ct, ci, _flip_labels(_pod_at(ct, _last_row(ct)), True))
    elif churn == "walked":
        _put(ct, ci, _flip_labels(_pod_at(ct, probe.walked[0]), True))
    elif churn == "deleted":
        _drop(ct, ci, _pod_at(ct, probe.walked[0]))
    obs.get_tracer().clear()
    with obs.root_span("audit.sweep"):
        ct.audit_capped(cap)
    stats = dict(ct.driver.last_sweep_stats)
    after = ct.driver._delta_state.render_cache
    served = [k for k in after if after[k] is before.get(k)]
    reviews = ct.driver._audit_pack.reviews
    rendered = sum(
        sum(1 for r in e.walked if reviews[r] is not None)
        for k, e in after.items() if k not in served)
    assert stats["render_reused"] == len(served)
    assert stats["rendered_cells"] == rendered
    if churn in ("none", "far"):
        assert len(served) == len(after) and rendered == 0
    else:
        assert EXACT[0] not in served and rendered >= cap
        assert len(served) >= 1
    [tr] = obs.get_tracer().traces()
    [span] = [s for s in tr["spans"] if s["name"] == "audit.render"]
    assert span["attrs"]["render_reused"] == len(served)
    assert span["attrs"]["rendered_cells"] == rendered
    _sweep_checked(ct, ci, cap)
