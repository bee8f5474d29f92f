"""Incremental O(changes) audit sweep (ops/deltasweep.py): steady-state
capped audits evaluate only dirty rows on-device and fold the before/after
candidate columns into host-side counts/candidate state, falling back to a
full sweep only when the known candidate horizon runs out.
"""

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
