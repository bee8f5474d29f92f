"""Overload robustness at the webhook layer (ISSUE 12): end-to-end
deadline derivation (configured budget x AdmissionReview timeoutSeconds
x forwarded wire budget — min() semantics pinned), the micro-batcher's
bounded pending queue with dry-run-first shedding, and the explicit
fail-open/closed shed decision.  Front-door-side overload behavior:
tests/test_event_edge.py TestRefusalTaxonomy, tests/test_roster.py;
ladder: tests/test_brownout.py.
"""

import json
import threading
import time
import urllib.request

import pytest

from gatekeeper_tpu import deadline as dl
from gatekeeper_tpu.deadline import OverloadShed
from gatekeeper_tpu.kube.inmem import InMemoryKube
from gatekeeper_tpu.webhook import (
    MicroBatcher,
    ValidationHandler,
    WebhookServer,
)
from gatekeeper_tpu.webhook.policy import (
    FAIL_OPEN_ANNOTATION,
    FAIL_OPEN_SHED,
    SHED_CODE,
    SHED_MESSAGE,
    AdmissionResponse,
)


def _review(name, **extra):
    req = {
        "uid": f"uid-{name}",
        "kind": {"group": "", "version": "v1", "kind": "Namespace"},
        "name": name,
        "namespace": "",
        "operation": "CREATE",
        "userInfo": {"username": "alice"},
        "object": {"apiVersion": "v1", "kind": "Namespace",
                   "metadata": {"name": name, "labels": {}}},
    }
    req.update(extra)
    return req


class _RecordingHandler:
    """Stands in for ValidationHandler: records the deadline budget each
    request carried into handle() — the observable the min() semantics
    are pinned against."""

    def __init__(self):
        self.remaining = []

    def handle(self, req):
        self.remaining.append(dl.remaining())
        return AdmissionResponse(True, "")


def _post(port, payload, headers=None):
    body = json.dumps(payload).encode()
    r = urllib.request.Request(
        f"http://127.0.0.1:{port}/v1/admit", data=body,
        headers={"Content-Type": "application/json", **(headers or {})},
    )
    with urllib.request.urlopen(r, timeout=10) as resp:
        return resp.status, json.loads(resp.read())


class TestBudgetDerivation:
    """The satellite: request.timeoutSeconds enters the budget via
    min(), and the forwarded X-GK-Deadline-Ms wire budget likewise —
    each observed as deadline.remaining() inside handle()."""

    def _serve(self, budget_s=None):
        handler = _RecordingHandler()
        srv = WebhookServer(handler, port=0, deadline_budget_s=budget_s)
        srv.start()
        return srv, handler

    def test_timeout_seconds_smaller_than_configured_wins(self):
        srv, handler = self._serve(budget_s=30.0)
        try:
            _post(srv.port, {"request": _review("a", timeoutSeconds=2)})
            rem = handler.remaining[-1]
            assert rem is not None and 1.5 < rem <= 2.0
        finally:
            srv.stop()

    def test_configured_smaller_than_timeout_seconds_wins(self):
        srv, handler = self._serve(budget_s=0.5)
        try:
            _post(srv.port, {"request": _review("b", timeoutSeconds=10)})
            rem = handler.remaining[-1]
            assert rem is not None and 0.3 < rem <= 0.5
        finally:
            srv.stop()

    def test_timeout_seconds_alone_sets_the_budget(self):
        # a caller-stamped timeoutSeconds budgets the request even with
        # no --admission-deadline-budget-ms configured
        srv, handler = self._serve(budget_s=None)
        try:
            _post(srv.port, {"request": _review("c", timeoutSeconds=3)})
            rem = handler.remaining[-1]
            assert rem is not None and 2.5 < rem <= 3.0
        finally:
            srv.stop()

    def test_wire_header_carries_the_remaining_budget(self):
        srv, handler = self._serve(budget_s=30.0)
        try:
            _post(srv.port, {"request": _review("d")},
                  headers={dl.DEADLINE_HEADER: "250"})
            rem = handler.remaining[-1]
            assert rem is not None and 0.1 < rem <= 0.25
        finally:
            srv.stop()

    def test_min_over_all_three_sources(self):
        srv, handler = self._serve(budget_s=5.0)
        try:
            _post(srv.port,
                  {"request": _review("e", timeoutSeconds=10)},
                  headers={dl.DEADLINE_HEADER: "120"})
            rem = handler.remaining[-1]
            assert rem is not None and rem <= 0.12
        finally:
            srv.stop()

    def test_malformed_header_carries_no_bound(self):
        srv, handler = self._serve(budget_s=None)
        try:
            _post(srv.port, {"request": _review("f")},
                  headers={dl.DEADLINE_HEADER: "whenever"})
            assert handler.remaining[-1] is None
        finally:
            srv.stop()

    def test_no_bound_from_any_source_means_no_deadline(self):
        srv, handler = self._serve(budget_s=None)
        try:
            _post(srv.port, {"request": _review("g")})
            assert handler.remaining[-1] is None
        finally:
            srv.stop()

    def test_non_dict_request_answers_explicit_500(self):
        """A non-object "request" value is a malformed envelope: the
        server must answer the explicit 500 AdmissionReview, never drop
        the connection (regression: the budget-derivation restructure
        briefly let it crash the handler after the parse try)."""
        srv, handler = self._serve(budget_s=None)
        try:
            st, out = _post(srv.port, {"request": "bogus"})
            assert st == 200
            assert out["response"]["allowed"] is False
            assert out["response"]["status"]["code"] == 500
            assert handler.remaining == []  # never reached the handler
        finally:
            srv.stop()


class _GatedClient:
    """review/review_batch park on a gate: the batch loop goes busy and
    the pending queue actually fills (the bound only binds while a
    dispatch is in flight — the loop drains the whole queue otherwise)."""

    def __init__(self):
        self.gate = threading.Event()

    def review(self, obj, tracing=False):
        self.gate.wait(10)
        return ("ok", obj)

    def review_batch(self, objs):
        self.gate.wait(10)
        return [("ok", o) for o in objs]


class TestBatcherBound:
    def _saturate(self, mb, reqs):
        """Spawn one caller per request with a small stagger; returns
        (results, errors) dicts keyed by uid after all joined."""
        out, errs, threads = {}, {}, []

        def call(req):
            try:
                out[req["uid"]] = mb.review(req)
            except Exception as e:
                errs[req["uid"]] = e

        for req in reqs:
            t = threading.Thread(target=call, args=(req,))
            t.start()
            threads.append(t)
            time.sleep(0.03)  # deterministic arrival order
        return out, errs, threads

    def test_queue_full_sheds_and_dryrun_preempted(self):
        client = _GatedClient()
        mb = MicroBatcher(client, adaptive=False, max_pending=2)
        try:
            reqs = [
                {"uid": "inline"},                    # inline, gated
                {"uid": "busy"},                      # dispatched, gated
                {"uid": "dry-old", "dryRun": True},   # queued 1/2
                {"uid": "enf-1"},                     # queued 2/2 (bound)
                {"uid": "dry-new", "dryRun": True},   # sheds itself
                {"uid": "enf-2"},                     # preempts dry-old
            ]
            out, errs, threads = self._saturate(mb, reqs)
            client.gate.set()
            for t in threads:
                t.join(timeout=10)
            assert isinstance(errs.get("dry-new"), OverloadShed)
            assert isinstance(errs.get("dry-old"), OverloadShed)
            assert set(out) == {"inline", "busy", "enf-1", "enf-2"}
            assert mb.sheds == 2
        finally:
            client.gate.set()
            mb.stop()

    def test_enforced_sheds_only_with_no_dryrun_to_preempt(self):
        client = _GatedClient()
        mb = MicroBatcher(client, adaptive=False, max_pending=1)
        try:
            reqs = [
                {"uid": "inline"},   # inline, gated
                {"uid": "busy"},     # dispatched, gated
                {"uid": "enf-1"},    # queued 1/1
                {"uid": "enf-2"},    # enforced at bound, nothing to evict
            ]
            out, errs, threads = self._saturate(mb, reqs)
            client.gate.set()
            for t in threads:
                t.join(timeout=10)
            assert isinstance(errs.get("enf-2"), OverloadShed)
            assert "enf-1" in out
        finally:
            client.gate.set()
            mb.stop()

    def test_shed_total_metric_recorded(self):
        from gatekeeper_tpu.metrics.exporter import render_prometheus

        client = _GatedClient()
        mb = MicroBatcher(client, adaptive=False, max_pending=1)
        try:
            reqs = [
                {"uid": "inline"}, {"uid": "busy"}, {"uid": "q1"},
                {"uid": "drop", "dryRun": True},
            ]
            out, errs, threads = self._saturate(mb, reqs)
            client.gate.set()
            for t in threads:
                t.join(timeout=10)
            assert isinstance(errs.get("drop"), OverloadShed)
            text = render_prometheus()
            assert 'gatekeeper_shed_total{reason="queue_full_dryrun"}' \
                in text
        finally:
            client.gate.set()
            mb.stop()

    def test_unbounded_when_disabled(self):
        client = _GatedClient()
        mb = MicroBatcher(client, adaptive=False, max_pending=0)
        try:
            reqs = [{"uid": f"r{i}"} for i in range(8)]
            out, errs, threads = self._saturate(mb, reqs)
            client.gate.set()
            for t in threads:
                t.join(timeout=10)
            assert not errs and len(out) == 8
        finally:
            client.gate.set()
            mb.stop()


class _SheddingClient:
    def review(self, review, tracing=False):
        raise OverloadShed("full")


class TestShedDecision:
    """The explicit fail-open/closed decision an OverloadShed converts
    to — exact JSON, both policies (mirrors the deadline tests in
    tests/test_webhook.py)."""

    def test_fail_closed_is_a_429_deny(self):
        h = ValidationHandler(_SheddingClient(), kube=InMemoryKube())
        resp = h.handle(_review("shed-closed"))
        out = resp.to_dict(uid="u1")
        assert out == {
            "uid": "u1",
            "allowed": False,
            "status": {"message": SHED_MESSAGE, "code": SHED_CODE},
        }

    def test_fail_open_allows_with_audit_annotation(self):
        h = ValidationHandler(
            _SheddingClient(), kube=InMemoryKube(), fail_open=True
        )
        resp = h.handle(_review("shed-open"))
        out = resp.to_dict(uid="u2")
        assert out["allowed"] is True
        assert out["auditAnnotations"] == {
            FAIL_OPEN_ANNOTATION: FAIL_OPEN_SHED
        }

    def test_shed_is_fast_even_under_load(self):
        """The refusal path must answer in single-digit ms — the whole
        point of shedding (acceptance: shed p99 < 10ms; here a lax 50ms
        bound keeps the assertion robust on a loaded CI box)."""
        h = ValidationHandler(_SheddingClient(), kube=InMemoryKube())
        durs = []
        for i in range(20):
            t0 = time.perf_counter()
            h.handle(_review(f"fast-{i}"))
            durs.append(time.perf_counter() - t0)
        durs.sort()
        assert durs[int(len(durs) * 0.9)] < 0.05


class TestEndToEndShed:
    def test_server_answers_shed_verdict_within_budget(self):
        """A full WebhookServer whose batcher is saturated answers the
        explicit shed AdmissionReview immediately — never queues the
        refusal behind the wedge."""
        client = _GatedClient()
        mb = MicroBatcher(client, adaptive=False, max_pending=1)
        handler = ValidationHandler(mb, kube=InMemoryKube())
        srv = WebhookServer(handler, port=0)
        srv.start()
        occupiers = []
        try:
            # saturate: inline + busy + queue(1)
            for uid in ("inline", "busy", "q1"):
                t = threading.Thread(
                    target=lambda u=uid: _post(
                        srv.port, {"request": _review(u)})
                )
                t.start()
                occupiers.append(t)
                time.sleep(0.05)
            t0 = time.perf_counter()
            st, out = _post(srv.port, {"request": _review("refused")})
            dur = time.perf_counter() - t0
            assert st == 200
            assert out["response"]["allowed"] is False
            assert out["response"]["status"]["code"] == SHED_CODE
            assert out["response"]["status"]["message"] == SHED_MESSAGE
            assert dur < 1.0, f"shed took {dur:.3f}s"
        finally:
            client.gate.set()
            for t in occupiers:
                t.join(timeout=10)
            srv.stop()
            mb.stop()


class TestDryRunClassification:
    def test_low_value_detection(self):
        from gatekeeper_tpu.target.target import AugmentedReview
        from gatekeeper_tpu.webhook.server import _low_value

        assert _low_value({"dryRun": True})
        assert not _low_value({"dryRun": False})
        assert not _low_value({})
        assert _low_value(AugmentedReview(
            admission_request={"dryRun": True}
        ))
        assert not _low_value(AugmentedReview(
            admission_request=_review("x")
        ))
        assert not _low_value(object())
