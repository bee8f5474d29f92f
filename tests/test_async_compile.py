"""Async template ingestion: XLA recompiles must never block evaluation.

SURVEY §7 hard-part 3 / VERDICT round-1 item 6: a template/constraint
mutation bumps the constraint-side epoch and discards the fused executable;
with GK_ASYNC_COMPILE the re-trace+compile runs in a background thread
(ops/asynccompile.py) while reviews serve from the interpreter oracle, then
the new executable swaps in atomically.  Reference ingestion budget:
pkg/controller/constrainttemplate/stats_reporter.go:33-37 (ms buckets).
"""

from __future__ import annotations

import time

import pytest

from gatekeeper_tpu.client.client import Client
from gatekeeper_tpu.client.drivers import InterpDriver
from gatekeeper_tpu.ops.driver import TpuDriver
from gatekeeper_tpu.util.synthetic import make_pods, make_templates


def _review_req(pod):
    return {
        "uid": "u",
        "kind": {"group": "", "version": "v1", "kind": "Pod"},
        "name": pod["metadata"]["name"],
        "namespace": pod["metadata"]["namespace"],
        "operation": "CREATE",
        "userInfo": {"username": "test"},
        "object": pod,
    }


def _result_keys(results):
    return sorted(
        (r.constraint["kind"], r.constraint["metadata"]["name"], r.msg)
        for r in results
    )


@pytest.fixture
def async_client():
    c = Client(driver=TpuDriver(async_compile=True))
    c.driver.DEVICE_MIN_CELLS = 0  # device path even at tiny sizes
    yield c
    c.driver._compiler.stop()


def test_ingest_storm_never_blocks_on_xla(async_client, monkeypatch):
    """Interleave template ingests with reviews; while the background
    compile is in flight every review must take the interpreter path
    (compute_masks untouched == no eval blocked on XLA)."""
    c = async_client
    driver = c.driver
    templates, constraints = make_templates(24, seed=3)
    pods = make_pods(6, seed=7, violation_rate=1.0)

    device_calls = []
    real_compute = TpuDriver.compute_masks

    def counting_compute(self, reviews):
        device_calls.append(len(reviews))
        return real_compute(self, reviews)

    monkeypatch.setattr(TpuDriver, "compute_masks", counting_compute)

    saw_compiling_review = False
    for t, k in zip(templates, constraints):
        c.add_template(t)
        c.add_constraint(k)
        # a review lands mid-storm; must be served (from the interp path
        # whenever the compiler is still chasing the latest epoch)
        device_calls.clear()
        was_ready = driver._compiler.ready()
        c.review(_review_req(pods[0]))
        if not was_ready and not driver._compiler.ready():
            # the compile was in flight across the whole review: it must
            # not have dispatched to (= blocked on) the device executable
            assert not device_calls, "review blocked on XLA compile"
            saw_compiling_review = True
    assert saw_compiling_review, "storm never overlapped a compile"

    assert driver.wait_ready(timeout=300.0)
    # post-ready reviews use the device path
    device_calls.clear()
    res_dev = c.review(_review_req(pods[1]))
    assert device_calls, "ready driver should dispatch to the device"

    # bit-parity: the interp-served and device-served answers agree with a
    # plain synchronous interpreter client on the same state
    ci = Client(driver=InterpDriver())
    for t in templates:
        ci.add_template(t)
    for k in constraints:
        ci.add_constraint(k)
    res_interp = ci.review(_review_req(pods[1]))
    assert _result_keys(res_dev.results()) == _result_keys(res_interp.results())


def test_storm_coalesces_to_latest_epoch(async_client):
    """500 rapid-fire ingests compile at most a handful of epochs — the
    background loop always chases the LATEST epoch, not every bump."""
    c = async_client
    driver = c.driver
    templates, constraints = make_templates(40, seed=11)
    t0 = time.monotonic()
    for t, k in zip(templates, constraints):
        c.add_template(t)
        c.add_constraint(k)
    ingest_s = time.monotonic() - t0
    assert driver.wait_ready(timeout=300.0)
    assert driver._compiler._ready_epoch == driver._cs_epoch
    # ingest itself must stay cheap (host-side only — vectorize + bump);
    # generous bound to stay robust on loaded CI hosts
    assert ingest_s < 30.0


def test_audit_waits_for_compile_and_matches_sync(async_client):
    """audit()/audit_capped() block on the background compile (throughput
    path) and produce the same answer as a synchronous TpuDriver."""
    c = async_client
    templates, constraints = make_templates(8, seed=5)
    pods = make_pods(32, seed=9, violation_rate=0.5)
    for t, k in zip(templates, constraints):
        c.add_template(t)
        c.add_constraint(k)
    for p in pods:
        c.add_data(p)
    got = _result_keys(c.audit().results())

    cs = Client(driver=TpuDriver(async_compile=False))
    cs.driver.DEVICE_MIN_CELLS = 0
    for t, k in zip(templates, constraints):
        cs.add_template(t)
        cs.add_constraint(k)
    for p in pods:
        cs.add_data(p)
    want = _result_keys(cs.audit().results())
    assert got == want


def test_sync_driver_unaffected():
    """async_compile=False keeps the blocking behavior (no thread)."""
    d = TpuDriver(async_compile=False)
    assert d._compiler is None
    assert d.wait_ready() is True


def test_background_warm_covers_packed_review_fn(async_client):
    """The review path dispatches _packed_variant(fused); the background
    warm must compile THAT executable, or the first real admission review
    pays the synchronous XLA compile the feature exists to prevent."""
    c = async_client
    driver = c.driver
    templates, constraints = make_templates(4, seed=3)
    for t, k in zip(templates, constraints):
        c.add_template(t)
        c.add_constraint(k)
    assert driver.wait_ready(timeout=300.0)
    # one executable, of the probe review's layout
    ((pv, layout),) = driver._fused_packed.values()
    assert pv._tag == "fused-packed" and layout.width > 0
    assert driver._fused_packed_src is driver._fused
