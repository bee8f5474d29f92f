"""The sweeping process's heap discipline (gatekeeper_tpu/util/heap.py),
driven from Client._sweep_done: engaged by the first sweep whose stages
full collections held for heap.ENGAGE_MIN_PAUSE_S, an explicit
collection at the sweep's boundary at most every heap.PERIOD_S,
released by AuditManager.stop.  The gate is monkeypatched (conftest
pins it off for every other test): no test needs a large cluster."""

import gc
import weakref

import pytest

from gatekeeper_tpu.client.client import Client
from gatekeeper_tpu.ops.driver import TpuDriver
from gatekeeper_tpu.util import heap

ROWS = 120
THAWED = [0]   # what a full collection of a thawed heap leaves frozen


@pytest.fixture(autouse=True)
def _collector_as_found(monkeypatch):
    """Every test starts and ends with the collector on and nothing
    frozen, whatever it did in between; and no collection comes by the
    clock unless the test sets the period.  (This CPython's full
    collection parks a few hundred objects of the interpreter's own in
    the permanent generation: THAWED is that count, not 0.)"""
    heap.release()
    gc.unfreeze()
    gc.collect()
    THAWED[0] = gc.get_freeze_count()
    assert gc.isenabled()
    monkeypatch.setattr(heap, "PERIOD_S", 1e9)
    yield
    heap.release()
    gc.enable()
    gc.unfreeze()


def _thawed() -> bool:
    return gc.get_freeze_count() <= THAWED[0]


@pytest.fixture
def gate(monkeypatch):
    """The gate at nothing: the next sweep engages."""
    monkeypatch.setattr(heap, "ENGAGE_MIN_PAUSE_S", 0.0)


def _collecting_render(c, monkeypatch, *generations):
    """The driver's render with collections inside it."""
    real = c.driver._render_capped

    def collecting(*a, **k):
        for g in generations:
            gc.collect(g)
        return real(*a, **k)

    monkeypatch.setattr(c.driver, "_render_capped", collecting)


def _client():
    from gatekeeper_tpu.util.synthetic import make_pods, make_templates

    driver = TpuDriver()
    driver.mesh_enabled = False
    driver._mesh_cache = None
    c = Client(driver=driver)
    templates, constraints = make_templates(6)
    for t, k in zip(templates, constraints):
        c.add_template(t)
        c.add_constraint(k)
    for p in make_pods(ROWS, seed=30, violation_rate=0.3):
        c.add_data(p)
    return c, make_pods


@pytest.fixture(scope="module")
def cluster():
    """One small cluster for the tests that only need sweeps to come:
    (client, churn), where churn() replaces a few rows (so the next
    sweep is a real one) after the last sweep's background work."""
    c, make_pods = _client()
    c.audit_capped(5)
    step = [0]

    def churn(n=3):
        _settle()
        step[0] += 1
        for i, p in enumerate(make_pods(n, seed=3000 + step[0],
                                        violation_rate=0.5)):
            p["metadata"]["name"] = f"t30-{i}"
            c.add_data(p)

    return c, churn


def _settle():
    from gatekeeper_tpu.ops import deltasweep

    for t in list(deltasweep._BG_THREADS):
        if t.name != "gk-route-cal":
            t.join(timeout=120)


def _churn(c, make_pods, step, n=3):
    for i, p in enumerate(make_pods(n, seed=3000 + step,
                                    violation_rate=0.5)):
        p["metadata"]["name"] = f"t30-{step}-{i}"
        c.add_data(p)


def _answer(out):
    responses, totals = out
    return (sorted((r.constraint["metadata"]["name"],
                    r.review["object"]["metadata"]["name"], r.msg)
                   for r in responses.results()),
            dict(totals))


def _audit_stage_rows():
    from gatekeeper_tpu.metrics.views import global_registry

    return {k: v for k, v in global_registry().view_rows(
        "host_stage_seconds_total").items() if k[0] == "audit"}


# ---- the gate ---------------------------------------------------------------


def test_a_sweep_without_a_full_collection_leaves_the_collector_alone(
        cluster, monkeypatch):
    c, churn = cluster
    monkeypatch.setattr(heap, "ENGAGE_MIN_PAUSE_S", 1e-9)
    gc.collect()   # so none comes by itself in the next sweep
    before = _audit_stage_rows().get(("audit", "collect"))
    churn()
    c.audit_capped(5)
    assert c.driver.last_sweep_stats["gc_full_ms"] == 0.0
    assert not heap.engaged()
    assert gc.isenabled()
    assert _thawed()
    assert c.driver.last_sweep_stats["collect_ms"] == 0.0
    # and no `collect` stage was opened on the sweeping thread's clock
    assert _audit_stage_rows().get(("audit", "collect")) == before


def test_the_gate_reads_the_full_collections_inside_the_sweep(
        cluster, monkeypatch):
    c, churn = cluster
    _collecting_render(c, monkeypatch, 2)
    monkeypatch.setattr(heap, "ENGAGE_MIN_PAUSE_S", 3600.0)
    churn()
    c.audit_capped(5)   # a full collection inside it, under the gate
    pause_s = c.driver.last_sweep_stats["gc_full_ms"] / 1e3
    assert pause_s > 0
    assert not heap.engaged() and gc.isenabled()
    monkeypatch.setattr(heap, "ENGAGE_MIN_PAUSE_S", pause_s / 10)
    churn()
    c.audit_capped(5)   # and one over it
    assert heap.engaged() and not gc.isenabled()


def test_the_shipped_gate_and_period():
    """The constants as shipped (conftest pins another gate): a gate no
    full collection of a process that holds jax stays under (the least
    measured was 356 ms), and the webhook's own period."""
    import importlib.util

    spec = importlib.util.find_spec("gatekeeper_tpu.util.heap")
    fresh = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(fresh)
    assert 0 < fresh.ENGAGE_MIN_PAUSE_S <= 0.05
    assert fresh.PERIOD_S == 5.0
    assert not fresh.engaged()


def test_the_interpreter_drivers_sweep_passes_the_same_boundary(gate):
    """Client.audit() on the default driver: the same clock, the same
    boundary, and no last_sweep_stats to write into."""
    from gatekeeper_tpu.util.synthetic import make_pods, make_templates

    c = Client()
    templates, constraints = make_templates(2)
    for t, k in zip(templates, constraints):
        c.add_template(t)
        c.add_constraint(k)
    for p in make_pods(20, seed=30, violation_rate=0.3):
        c.add_data(p)
    assert c.audit().results()
    assert heap.engaged() and not gc.isenabled()
    assert not hasattr(c.driver, "last_sweep_stats")


# ---- engaged ----------------------------------------------------------------


def test_the_first_sweep_at_the_gate_engages(cluster, gate):
    c, churn = cluster
    collections, _frozen = heap.counters()
    churn()
    c.audit_capped(5)
    assert heap.engaged()
    assert not gc.isenabled()
    assert not _thawed()
    ran, frozen = heap.counters()
    assert ran == collections + 1
    # the gauge: what the engagement froze, the whole heap (reference
    # counting has freed some of it since)
    assert frozen > THAWED[0]
    assert abs(gc.get_freeze_count() - frozen) < 0.02 * frozen + THAWED[0]
    # the engagement's full collection ran under the `collect` stage
    stats = c.driver.last_sweep_stats
    assert 0 < stats["gc_full_ms"] <= stats["collect_ms"]


def test_later_sweeps_run_no_automatic_collection(cluster, gate):
    c, churn = cluster
    churn()
    c.audit_capped(5)
    assert heap.engaged()
    for step in range(3):
        # generations 0 and 1 only run automatically (a thread some
        # earlier test left behind may call gc.collect(), generation 2)
        before = [g["collections"] for g in gc.get_stats()[:2]]
        churn()
        c.audit_capped(5)
        assert [g["collections"] for g in gc.get_stats()[:2]] == before
        stats = c.driver.last_sweep_stats
        assert stats["gc_young_ms"] == 0.0
        assert stats["gc_full_ms"] == 0.0
        assert stats["collect_ms"] > 0   # the stage is there, and short


def test_a_collection_comes_at_the_boundary_once_the_period_has_passed(
        cluster, gate, monkeypatch):
    c, churn = cluster
    churn()
    c.audit_capped(5)
    ran = heap.counters()[0]
    churn()
    c.audit_capped(5)   # inside the period: nothing
    assert heap.counters()[0] == ran
    assert c.driver.last_sweep_stats["gc_full_ms"] == 0.0
    monkeypatch.setattr(heap, "PERIOD_S", 0.0)
    churn()
    kept = [[i] for i in range(1000)]   # survivors for it to freeze
    frozen = gc.get_freeze_count()
    c.audit_capped(5)
    assert heap.counters()[0] == ran + 1
    assert gc.get_freeze_count() >= frozen + len(kept)
    stats = c.driver.last_sweep_stats
    assert 0 < stats["gc_full_ms"] <= stats["collect_ms"]
    assert not gc.isenabled()


def test_a_cycle_made_after_engagement_is_reclaimed_by_the_next_collection(
        cluster, gate, monkeypatch):
    c, churn = cluster
    churn()
    c.audit_capped(5)

    class Node:
        pass

    a, b = Node(), Node()
    a.other, b.other = b, a
    gone = weakref.ref(a)
    del a, b
    churn()
    c.audit_capped(5)   # inside the period
    assert gone() is not None
    monkeypatch.setattr(heap, "PERIOD_S", 0.0)
    churn()
    c.audit_capped(5)
    assert gone() is None


def test_sweep_stats_and_counters_carry_the_discipline(
        cluster, gate, monkeypatch):
    from gatekeeper_tpu.metrics.views import global_registry
    from gatekeeper_tpu.obs import trace as obstrace

    monkeypatch.setattr(heap, "PERIOD_S", 0.0)
    c, churn = cluster
    reg = global_registry()
    obstrace.collect_hook()
    pushed = reg.view_rows("heap_collections_total").get((), 0.0)
    before = _audit_stage_rows().get(("audit", "collect"), 0.0)
    for _ in range(2):
        churn()
        c.audit_capped(5)
    stats = c.driver.last_sweep_stats
    for key in ("collect_ms", "gc_young_ms", "gc_full_ms"):
        assert stats[key] >= 0.0, key
    assert stats["collect_ms"] > 0
    assert _audit_stage_rows()[("audit", "collect")] > before
    obstrace.collect_hook()
    assert reg.view_rows("heap_collections_total")[()] == pushed + 2
    assert reg.view_rows("heap_frozen_objects")[()] == heap.counters()[1]
    heap.release()
    obstrace.collect_hook()
    assert reg.view_rows("heap_frozen_objects")[()] == 0.0


def test_young_collections_inside_a_sweep_are_read_apart_from_full_ones(
        cluster, monkeypatch):
    """gc_young_ms: what generations 0 and 1 held the sweeping thread's
    stages; gc_full_ms reads generation 2 alone."""
    c, churn = cluster
    _collecting_render(c, monkeypatch, 0, 1)
    gc.collect()
    churn()
    full = gc.get_stats()[2]["collections"]
    c.audit_capped(5)
    stats = c.driver.last_sweep_stats
    assert stats["gc_young_ms"] > 0
    if gc.get_stats()[2]["collections"] == full:
        assert stats["gc_full_ms"] == 0.0


def test_answers_of_a_churned_sequence_are_the_same_with_the_discipline(
        monkeypatch):
    def run():
        c, make_pods = _client()
        out = [_answer(c.audit_capped(5))]
        for step in range(2):
            _settle()
            _churn(c, make_pods, step)
            out.append(_answer(c.audit_capped(5)))
        return out

    plain = run()
    assert not heap.engaged()
    monkeypatch.setattr(heap, "ENGAGE_MIN_PAUSE_S", 0.0)
    monkeypatch.setattr(heap, "PERIOD_S", 0.0)
    disciplined = run()
    assert heap.engaged()
    assert disciplined == plain
    assert all(kept for kept, _totals in plain)


# ---- release ----------------------------------------------------------------


def test_release_restores_a_collector_that_was_enabled():
    heap.engage()
    assert not gc.isenabled() and not _thawed()
    heap.engage()   # a no-op while engaged
    heap.release()
    assert gc.isenabled() and _thawed()
    assert not heap.engaged()
    assert heap.counters()[1] == 0
    heap.release()  # and so is a second release
    assert gc.isenabled()


def _webhook_start():
    # what WebhookServer.start() does to the collector (webhook/server.py)
    gc.collect()
    gc.freeze()
    gc.disable()


def _webhook_stop():
    # and WebhookServer.stop()
    gc.enable()
    gc.unfreeze()


def test_release_never_enables_what_the_webhook_disabled():
    """An all-roles process: WebhookServer.start() first, and App.stop()
    stops the audit manager before the webhook server."""
    _webhook_start()
    frozen = gc.get_freeze_count()
    heap.engage()
    heap.release()
    assert not gc.isenabled()
    assert frozen > THAWED[0] and not _thawed()
    _webhook_stop()
    assert gc.isenabled() and _thawed()


def test_the_webhook_starting_and_stopping_inside_an_engagement():
    heap.engage()
    _webhook_start()
    _webhook_stop()
    heap.release()
    assert gc.isenabled() and _thawed()


def test_the_discipline_takes_the_collector_over_when_the_webhook_lets_go(
        monkeypatch):
    """WebhookServer.start(), engage, WebhookServer.stop() with sweeps
    still coming: the next boundary turns automatic collection off
    again, and release() then hands back an enabled, thawed collector."""
    monkeypatch.setattr(heap, "PERIOD_S", 0.0)
    _webhook_start()
    heap.engage(0.0)
    _webhook_stop()
    assert gc.isenabled()
    assert heap.after_sweep(1.0)
    assert not gc.isenabled() and not _thawed()
    heap.release()
    assert gc.isenabled() and _thawed()


def test_after_sweep_waits_for_the_period_and_does_nothing_when_released(
        monkeypatch):
    monkeypatch.setattr(heap, "PERIOD_S", 5.0)
    assert not heap.after_sweep(1e9)   # not engaged
    heap.engage(100.0)
    assert not heap.after_sweep(102.5)
    assert heap.after_sweep(105.0)
    assert not heap.after_sweep(107.5)
    heap.release()
    assert not heap.after_sweep(1e9)


def test_audit_manager_stop_releases(cluster, gate):
    from gatekeeper_tpu.audit.manager import AuditManager
    from gatekeeper_tpu.kube.inmem import InMemoryKube

    c, churn = cluster
    mgr = AuditManager(InMemoryKube(), c, from_cache=True)
    churn()
    c.audit_capped(5)
    assert heap.engaged() and not gc.isenabled()
    mgr.stop()
    assert not heap.engaged()
    assert gc.isenabled() and _thawed()
