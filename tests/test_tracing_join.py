"""The audit stage clock's join stages (ISSUE 29): `join_affected` and
`join_commit` are stages of their own on a referential sweep, read apart
from `pack` in last_sweep_stats beside `full` and `render_interp_cells`;
a sweep without a join plan (churn200's path) opens neither."""

import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "benchmark"))

from lib import agilebank  # noqa: E402

from gatekeeper_tpu.client.client import Client  # noqa: E402
from gatekeeper_tpu.ops.driver import TpuDriver  # noqa: E402

from tests.test_tracing import (  # noqa: E402
    _join_sweep_background as _settle,
    _stage_rows,
)

JOIN_KEYS = ("join_affected_ms", "join_commit_ms")


def _driver():
    driver = TpuDriver()
    driver.mesh_enabled = False
    driver._mesh_cache = None
    return driver


def _referential_client():
    templates, constraints = agilebank.make_templates()
    c = Client(driver=_driver())
    for t in templates:
        c.add_template(t)
    for k in constraints:
        c.add_constraint(k)
    for i in range(6):
        c.add_data(agilebank.make_service(i, "ns-0", {"app": f"a{i // 2}"}))
    return c


def _row_local_client():
    from gatekeeper_tpu.util.synthetic import make_pods, make_templates

    c = Client(driver=_driver())
    templates, constraints = make_templates(6)
    for t, k in zip(templates, constraints):
        c.add_template(t)
        c.add_constraint(k)
    for p in make_pods(60, seed=29, violation_rate=0.3):
        c.add_data(p)
    return c, make_pods


@pytest.mark.parametrize("kind", ["full", "delta"])
def test_referential_sweep_reads_its_join_stages_apart_from_pack(kind):
    import time

    c = _referential_client()
    calls0 = _stage_rows("host_stage_calls_total")
    t0 = time.perf_counter()
    c.audit_capped(5)
    wall_ms = (time.perf_counter() - t0) * 1e3
    if kind == "delta":
        _settle()
        c.add_data(agilebank.make_service(0, "ns-0", {"app": "a2"}))
        t0 = time.perf_counter()
        c.audit_capped(5)
        wall_ms = (time.perf_counter() - t0) * 1e3
    stats = c.driver.last_sweep_stats
    assert stats["full"] == (1.0 if kind == "full" else 0.0)
    assert stats["join_plans"] == 1.0
    for key in JOIN_KEYS + ("render_interp_cells", "pack_ms"):
        assert key in stats and stats[key] >= 0.0, key
    assert stats["join_commit_ms"] > 0
    if kind == "delta":
        # svc-0 left svc-1 alone and joined svc-4 and svc-5
        assert stats["join_affected_ms"] > 0
        assert stats["join_affected_rows"] == 3.0
        assert stats["render_interp_cells"] >= 1.0
    else:
        assert stats["join_affected_ms"] == 0.0
    # the named stages still cover the sweep, the join's beside pack's
    named = sum(stats[k] for k in (
        "pack_ms", "slice_ms", "enqueue_ms", "device_wait_ms", "fetch_ms",
        "apply_ms", "render_ms", "cap_ms") + JOIN_KEYS)
    assert wall_ms * 0.8 <= named <= wall_ms * 1.001
    # and both stages reached the sweeping thread's counters
    calls = _stage_rows("host_stage_calls_total")
    secs = _stage_rows("host_stage_seconds_total")
    stages = ("join_commit",) + (("join_affected",) if kind == "delta"
                                 else ())
    for stage in stages:
        assert calls.get(("audit", stage), 0) > calls0.get(
            ("audit", stage), 0), stage
        assert secs.get(("audit", stage), 0) > 0, stage


def test_row_local_sweeps_open_no_join_stage():
    """churn200's path: no join plan, so no join stage, mark or key, and
    `full` says which path the sweep took."""
    c, make_pods = _row_local_client()
    calls0 = _stage_rows("host_stage_calls_total")
    c.audit_capped(5)
    first = dict(c.driver.last_sweep_stats)
    _settle()
    c.add_data(make_pods(1, seed=2901, violation_rate=1.0)[0])
    c.audit_capped(5)
    second = dict(c.driver.last_sweep_stats)
    c.audit_capped(5)
    third = dict(c.driver.last_sweep_stats)
    assert first["full"] == 1.0 and second["full"] == 0.0
    assert third.get("cached") == 1.0 and "full" not in third
    for stats in (first, second):
        assert not set(stats) & {"join_plans", "join_affected_rows",
                                 *JOIN_KEYS}
    calls = _stage_rows("host_stage_calls_total")
    for stage in ("join_affected", "join_commit"):
        assert calls.get(("audit", stage), 0) == calls0.get(
            ("audit", stage), 0)
