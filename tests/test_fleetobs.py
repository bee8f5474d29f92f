"""Fleet observability plane (ISSUE 11): classic-format federation
(parse/relabel/merge invariants, stale-marking, bounded scrapes under
the seeded `fleet.scrape_fail` fault), cross-process trace assembly,
and — where spawn is available — a real front-door→replica round trip
proving one trace_id spans both processes."""

import json
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

import pytest

from gatekeeper_tpu import faults
from gatekeeper_tpu.faults import FaultRule
from gatekeeper_tpu.metrics.views import global_registry
from gatekeeper_tpu.obs import fleetobs
from gatekeeper_tpu.obs import trace as obstrace
from gatekeeper_tpu.obs.fleetobs import (
    MetricsFederator,
    TraceCollector,
    label_sample,
    merge_families,
    parse_families,
    render_families,
    split_sample,
)

from .test_snapshot_concurrent import spawn_available


def wait_until(cond, timeout_s=5.0, step_s=0.02):
    end = time.monotonic() + timeout_s
    while time.monotonic() < end:
        if cond():
            return True
        time.sleep(step_s)
    return cond()


# ---- classic-format plumbing ------------------------------------------------


class TestClassicFormat:
    def test_split_sample_handles_braces_in_values(self):
        line = ('gatekeeper_cost_cells{template="K8s{weird}Name"} 5')
        name, labels, value = split_sample(line)
        assert name == "gatekeeper_cost_cells"
        assert labels == 'template="K8s{weird}Name"'
        assert value == "5"

    def test_split_sample_unlabelled(self):
        assert split_sample("gatekeeper_up 1") == \
            ("gatekeeper_up", None, "1")

    def test_label_sample_injects_and_preserves(self):
        assert label_sample("m 1", "r0") == 'm{replica_id="r0"} 1'
        assert label_sample('m{a="b"} 1', "r0") == \
            'm{replica_id="r0",a="b"} 1'
        # replica-stamped series are authoritative: untouched
        stamped = 'm{replica_id="rX",a="b"} 1'
        assert label_sample(stamped, "r0") == stamped

    def test_parse_families_groups_histogram_samples(self):
        text = (
            "# HELP gk_h h\n# TYPE gk_h histogram\n"
            'gk_h_bucket{le="1"} 1\ngk_h_sum 0.5\ngk_h_count 1\n'
            "# HELP gk_g g\n# TYPE gk_g gauge\ngk_g 2\n"
        )
        fams = parse_families(text)
        assert list(fams) == ["gk_h", "gk_g"]
        assert len(fams["gk_h"]["samples"]) == 3

    def test_merge_keeps_one_header_per_family(self):
        body = "# HELP gk_x x\n# TYPE gk_x gauge\ngk_x 1\n"
        out = render_families(merge_families(
            body, [("r0", body), ("r1", body)]
        ))
        assert out.count("# HELP gk_x") == 1
        assert out.count("# TYPE gk_x") == 1
        assert 'gk_x{replica_id="r0"} 1' in out
        assert 'gk_x{replica_id="r1"} 1' in out
        assert "# EOF" not in out


# ---- federation over live (and dead, and wedged) exporters ------------------


class _StubExporter:
    """Minimal /metrics server; delay_s simulates a wedged replica."""

    def __init__(self, body: str, delay_s: float = 0.0):
        outer = self
        self.body = body
        self.delay_s = delay_s

        class H(BaseHTTPRequestHandler):
            protocol_version = "HTTP/1.1"

            def log_message(self, *a):
                pass

            def do_GET(self):
                if outer.delay_s:
                    time.sleep(outer.delay_s)
                data = outer.body.encode()
                self.send_response(200)
                self.send_header("Content-Length", str(len(data)))
                self.end_headers()
                self.wfile.write(data)

        self.server = ThreadingHTTPServer(("127.0.0.1", 0), H)
        self.port = self.server.server_address[1]
        threading.Thread(target=self.server.serve_forever,
                         daemon=True).start()

    def stop(self):
        self.server.shutdown()
        self.server.server_close()


_BODY_A = "# HELP gk_t t\n# TYPE gk_t gauge\ngk_t 7\n"


class TestMetricsFederator:
    def test_scrape_merges_and_marks_health(self):
        a = _StubExporter(_BODY_A)
        try:
            fed = MetricsFederator(lambda: [
                {"replica_id": "r0", "host": "127.0.0.1", "port": a.port},
            ])
            out = fed.render()
            assert 'gk_t{replica_id="r0"} 7' in out
            rows = global_registry().view_rows("fleet_scrape_ok")
            assert rows[("r0",)] == 1.0
            assert 'gatekeeper_fleet_replicas_scraped 1' in out
        finally:
            a.stop()

    def test_dead_replica_serves_stale_marked_not_missing(self):
        a = _StubExporter(_BODY_A)
        fed = MetricsFederator(lambda: [
            {"replica_id": "r0", "host": "127.0.0.1", "port": a.port},
        ])
        assert 'gk_t{replica_id="r0"} 7' in fed.render()
        a.stop()  # replica dies; last-known-good must keep serving
        out = fed.render()
        assert 'gk_t{replica_id="r0"} 7' in out, \
            "stale series vanished instead of being stale-marked"
        rows = global_registry().view_rows("fleet_scrape_ok")
        assert rows[("r0",)] == 0.0
        age = global_registry().view_rows("fleet_scrape_age_seconds")
        assert age[("r0",)] >= 0.0

    def test_wedged_replica_never_blocks_render(self):
        a = _StubExporter(_BODY_A, delay_s=30.0)  # wedged: answers in 30s
        try:
            fed = MetricsFederator(lambda: [
                {"replica_id": "r0", "host": "127.0.0.1",
                 "port": a.port},
            ], timeout_s=0.3)
            t0 = time.monotonic()
            out = fed.render()
            took = time.monotonic() - t0
            assert took < 5.0, f"federated render blocked {took:.1f}s"
            # never scraped: no series, but health says so
            rows = global_registry().view_rows("fleet_scrape_ok")
            assert rows[("r0",)] == 0.0
            assert "fleet_scrape_ok" in out
        finally:
            a.delay_s = 0.0
            a.stop()

    def test_concurrent_render_does_not_stale_mark_healthy_fleet(self):
        """Review regression: two scrapers hitting the federated
        /metrics concurrently — the second render sees the first's
        in-flight scrape and must NOT flip a healthy replica to
        scrape_ok=0 (only a scrape wedged past its budget is stale)."""
        a = _StubExporter(_BODY_A)
        try:
            fed = MetricsFederator(lambda: [
                {"replica_id": "r0", "host": "127.0.0.1",
                 "port": a.port},
            ])
            assert 'gk_t{replica_id="r0"} 7' in fed.render()
            # a RECENT in-flight scrape (a racing render): skip, keep ok
            with fed._mu:
                fed._inflight["r0"] = time.monotonic()
            out = fed.render()
            rows = global_registry().view_rows("fleet_scrape_ok")
            assert rows[("r0",)] == 1.0, \
                "racing render stale-marked a healthy replica"
            assert 'gk_t{replica_id="r0"} 7' in out
            # the SAME in-flight entry aged past the budget: wedged
            with fed._mu:
                fed._inflight["r0"] = (
                    time.monotonic() - fed.timeout_s - 1.0
                )
            fed.render()
            rows = global_registry().view_rows("fleet_scrape_ok")
            assert rows[("r0",)] == 0.0
            with fed._mu:
                fed._inflight.clear()
        finally:
            a.stop()

    def test_fleet_of_wedged_exporters_bounded_by_one_budget(self):
        """Review regression: N wedged exporters must cost ONE scrape
        budget total (shared deadline), not N budgets."""
        stubs = [_StubExporter(_BODY_A, delay_s=30.0) for _ in range(4)]
        try:
            fed = MetricsFederator(lambda: [
                {"replica_id": f"r{i}", "host": "127.0.0.1",
                 "port": s.port}
                for i, s in enumerate(stubs)
            ], timeout_s=0.4)
            t0 = time.monotonic()
            fed.render()
            took = time.monotonic() - t0
            # one budget (0.9s) + slack — NOT 4 x 0.9s
            assert took < 2.5, f"render took {took:.1f}s for 4 wedges"
        finally:
            for s in stubs:
                s.delay_s = 0.0
                s.stop()

    def test_never_scraped_replica_age_grows(self):
        """Review regression: a replica whose exporter never answered
        must show a GROWING fleet_scrape_age_seconds, not 0 forever."""
        dead_port = _StubExporter(_BODY_A)
        dead_port.stop()
        fed = MetricsFederator(lambda: [
            {"replica_id": "rNever", "host": "127.0.0.1",
             "port": dead_port.port},
        ], timeout_s=0.3)
        fed.render()
        time.sleep(0.25)
        fed.render()
        age = global_registry().view_rows("fleet_scrape_age_seconds")
        assert age[("rNever",)] >= 0.2, age[("rNever",)]

    def test_immortal_inflight_scrape_is_evicted_and_rescraped(self):
        """Review regression: a scrape thread that never terminates (a
        drip-feeding exporter defeats the socket timeout) must not
        block that replica's scrapes forever — past the eviction cap
        the registration is replaced and a healthy replica recovers to
        scrape_ok=1."""
        a = _StubExporter(_BODY_A)
        try:
            fed = MetricsFederator(lambda: [
                {"replica_id": "r0", "host": "127.0.0.1",
                 "port": a.port},
            ], timeout_s=0.3)
            # an immortal scrape registration from the distant past
            with fed._mu:
                fed._inflight["r0"] = time.monotonic() - 3600.0
            out = fed.render()
            rows = global_registry().view_rows("fleet_scrape_ok")
            assert rows[("r0",)] == 1.0, \
                "evicted in-flight entry still blocks re-scrape"
            assert 'gk_t{replica_id="r0"} 7' in out
        finally:
            a.stop()

    def test_evicted_scrapes_late_write_is_discarded(self):
        """Review regression: a scrape evicted past the cap that later
        completes must NOT overwrite the successor's fresher state —
        its body predates the successor's scrape (counters would appear
        to regress, stale data marked freshest)."""
        a = _StubExporter(_BODY_A)
        try:
            fed = MetricsFederator(lambda: [
                {"replica_id": "r0", "host": "127.0.0.1",
                 "port": a.port},
            ])
            assert 'gk_t{replica_id="r0"} 7' in fed.render()  # fresh
            with fed._mu:
                st = fed._state["r0"]
                fresh_at = st.last_ok_at
                # the successor owns the registration now
                fed._inflight["r0"] = time.monotonic()
            a.body = _BODY_A.replace(" 7", " 99")
            # the EVICTED thread's late completion: stale token
            fed._scrape_one(
                {"replica_id": "r0", "host": "127.0.0.1",
                 "port": a.port},
                token=fresh_at - 100.0,
            )
            with fed._mu:
                assert "gk_t 7" in fed._state["r0"].body, \
                    "evicted scrape overwrote the successor's state"
                # and it must not have evicted the successor's entry
                assert "r0" in fed._inflight
                fed._inflight.clear()
        finally:
            a.stop()

    def test_departed_replica_health_keeps_updating(self):
        """Review regression: a replica that LEAVES the targets roster
        (quarantine, scale-down) must not freeze its health gauges at
        the last value — ok flips to 0 and age keeps growing; its
        cached series leave the merged body."""
        a = _StubExporter(_BODY_A)
        roster = [{"replica_id": "r0", "host": "127.0.0.1",
                   "port": a.port}]
        try:
            fed = MetricsFederator(lambda: list(roster))
            assert 'gk_t{replica_id="r0"} 7' in fed.render()
            assert global_registry().view_rows(
                "fleet_scrape_ok")[("r0",)] == 1.0
            roster.clear()  # quarantined / scaled down
            time.sleep(0.05)
            out = fed.render()
            rows = global_registry().view_rows("fleet_scrape_ok")
            assert rows[("r0",)] == 0.0, \
                "departed replica's scrape_ok froze at 1"
            age1 = global_registry().view_rows(
                "fleet_scrape_age_seconds")[("r0",)]
            assert age1 > 0.0
            assert 'gk_t{replica_id="r0"}' not in out, \
                "departed replica's series still federated"
            time.sleep(0.1)
            fed.render()
            age2 = global_registry().view_rows(
                "fleet_scrape_age_seconds")[("r0",)]
            assert age2 > age1, "departed replica's age froze"
        finally:
            a.stop()

    def test_rollup_sums_request_count(self):
        body = (
            "# HELP gatekeeper_request_count c\n"
            "# TYPE gatekeeper_request_count counter\n"
            'gatekeeper_request_count{admission_status="allow"} 5\n'
            'gatekeeper_request_count{admission_status="deny"} 2\n'
        )
        a, b = _StubExporter(body), _StubExporter(body)
        try:
            fed = MetricsFederator(lambda: [
                {"replica_id": "r0", "host": "127.0.0.1", "port": a.port},
                {"replica_id": "r1", "host": "127.0.0.1", "port": b.port},
            ])
            out = fed.render()
            assert "gatekeeper_fleet_admission_requests 14" in out
        finally:
            a.stop()
            b.stop()


@pytest.mark.chaos
class TestScrapeFailChaos:
    def test_seeded_scrape_fail_degrades_to_stale(self):
        """An error-mode fleet.scrape_fail makes the scrape fail while
        the replica itself is healthy: the federated view must degrade
        to the stale-marked cache, never error and never block."""
        a = _StubExporter(_BODY_A)
        try:
            fed = MetricsFederator(lambda: [
                {"replica_id": "r0", "host": "127.0.0.1",
                 "port": a.port},
            ])
            assert 'gk_t{replica_id="r0"} 7' in fed.render()  # warm cache
            plane = faults.install(seed=7)
            plane.add(faults.SCRAPE_FAIL,
                      FaultRule(mode="error", count=2))
            try:
                out = fed.render()
                assert 'gk_t{replica_id="r0"} 7' in out
                rows = global_registry().view_rows("fleet_scrape_ok")
                assert rows[("r0",)] == 0.0
            finally:
                faults.uninstall()
            # fault exhausted: the next pass recovers to fresh
            fed.render()
            rows = global_registry().view_rows("fleet_scrape_ok")
            assert rows[("r0",)] == 1.0
        finally:
            a.stop()


# ---- cross-process trace assembly ------------------------------------------


class _StubTraces:
    """Replica /debug/traces stub serving canned trace JSON."""

    def __init__(self, traces):
        outer = self
        self.traces = traces

        class H(BaseHTTPRequestHandler):
            protocol_version = "HTTP/1.1"

            def log_message(self, *a):
                pass

            def do_GET(self):
                data = json.dumps({"traces": outer.traces}).encode()
                self.send_response(200)
                self.send_header("Content-Length", str(len(data)))
                self.end_headers()
                self.wfile.write(data)

        self.server = ThreadingHTTPServer(("127.0.0.1", 0), H)
        self.port = self.server.server_address[1]
        threading.Thread(target=self.server.serve_forever,
                         daemon=True).start()

    def stop(self):
        self.server.shutdown()
        self.server.server_close()


def _make_wire_trace() -> str:
    """One completed front-door-shaped trace in the global tracer;
    returns its trace_id."""
    with obstrace.root_span("wire", path="/v1/admit") as sp:
        with obstrace.span("wire.replica_wait", stage="replica_wait"):
            pass
        tid = sp.trace.trace_id
    return tid


class TestTraceCollector:
    def test_joins_frontdoor_and_replica_spans_by_trace_id(self):
        tid = _make_wire_trace()
        replica_trace = {
            "trace_id": tid,
            "root": "admission",
            "duration_ms": 3.0,
            "spans": [
                {"name": "webhook.queue_wait", "trace_id": tid,
                 "duration_ms": 1.0, "attrs": {"stage": "queue_wait"}},
                {"name": "tpu.dispatch", "trace_id": tid,
                 "duration_ms": 2.0, "attrs": {"stage": "dispatch"}},
            ],
        }
        stub = _StubTraces([replica_trace])
        try:
            col = TraceCollector(lambda: [
                {"replica_id": "r1", "host": "127.0.0.1",
                 "port": stub.port},
            ])
            out = col.assemble()
            entry = next(t for t in out["traces"]
                         if t["trace_id"] == tid)
            assert entry["processes"] == ["frontdoor", "r1"]
            procs = {s.get("process") for s in entry["spans"]}
            assert procs == {"frontdoor", "r1"}
            # one view: wire AND device stages in the same breakdown
            assert "replica_wait" in entry["stage_breakdown"]
            assert "dispatch" in entry["stage_breakdown"]
            assert "dispatch" not in entry["wire_stage_breakdown"]
            assert out["failed_replicas"] == []
        finally:
            stub.stop()

    def test_wedged_fleet_trace_fetch_bounded_by_one_budget(self):
        """Review regression: N wedged replicas must cost ONE fetch
        budget on /debug/fleet-traces (concurrent fetches, shared
        deadline), not N sequential timeouts — wedged fleets are
        exactly when operators query traces."""
        stubs = [_StubExporter(_BODY_A, delay_s=30.0) for _ in range(4)]
        try:
            col = TraceCollector(lambda: [
                {"replica_id": f"r{i}", "host": "127.0.0.1",
                 "port": s.port}
                for i, s in enumerate(stubs)
            ], timeout_s=0.4)
            t0 = time.monotonic()
            out = col.assemble()
            took = time.monotonic() - t0
            assert took < 2.5, f"assemble took {took:.1f}s for 4 wedges"
            assert sorted(out["failed_replicas"]) == \
                ["r0", "r1", "r2", "r3"]
        finally:
            for s in stubs:
                s.delay_s = 0.0
                s.stop()

    def test_unreachable_replica_reported_not_fatal(self):
        tid = _make_wire_trace()
        stub = _StubTraces([])
        stub.stop()  # nothing listening
        col = TraceCollector(lambda: [
            {"replica_id": "r9", "host": "127.0.0.1",
             "port": stub.port},
        ], timeout_s=0.3)
        out = col.assemble()
        assert "r9" in out["failed_replicas"]
        assert any(t["trace_id"] == tid for t in out["traces"])

    def test_min_ms_filters_on_wire_duration(self):
        _make_wire_trace()
        col = TraceCollector(lambda: [])
        out = col.assemble(min_ms=10_000.0)
        assert out["traces"] == []

    def test_install_serves_fleet_traces_route(self):
        from gatekeeper_tpu.obs.debug import get_router

        tid = _make_wire_trace()
        col = TraceCollector(lambda: []).install()
        assert col is not None
        code, ctype, body = get_router().handle("/debug/fleet-traces")
        assert code == 200
        payload = json.loads(body)
        assert any(t["trace_id"] == tid for t in payload["traces"])
        code, _ct, body = get_router().handle(
            "/debug/fleet-traces", "min_ms=abc"
        )
        assert code == 400 and b"min_ms" in body


# ---- the real thing: one trace across two processes -------------------------


@spawn_available
class TestCrossProcessPropagation:
    def test_one_trace_id_spans_door_and_replica(self, tmp_path):
        """Front-door→replica round trip: the wire trace id propagates
        into the replica's admission trace, and /debug/fleet-traces
        serves the joined view with both sides' stage spans drawn from
        the documented stable sets (docs/tracing.md)."""
        import http.client

        from gatekeeper_tpu.fleet import EventFrontDoor
        from gatekeeper_tpu.fleet.replica import spawn_replica
        from gatekeeper_tpu.fleet.wireproto import WIRE_STAGES
        # stage spans exist only on a head-sampled request
        obstrace.configure(buffer_size=256, sample_rate=1.0)

        # the default tpu driver (on the CPU backend): the interp driver
        # emits no stage spans, and this test's whole point is stage
        # spans on BOTH sides of the hop
        handle = spawn_replica(
            "rT", env={"JAX_PLATFORMS": "cpu"}, timeout_s=240.0,
        )
        door = None
        try:
            door = EventFrontDoor([handle.wire_backend()],
                                  probe_interval_s=3600.0).start()
            col = TraceCollector(lambda: [
                {"replica_id": handle.replica_id, "host": handle.host,
                 "port": handle.port},
            ])
            body = json.dumps({"request": {
                "uid": "xproc-1",
                "kind": {"group": "", "version": "v1",
                         "kind": "Namespace"},
                "name": "xproc", "namespace": "",
                "operation": "CREATE",
                "userInfo": {"username": "t"},
                "object": {"apiVersion": "v1", "kind": "Namespace",
                           "metadata": {"name": "xproc",
                                        "labels": {}}},
            }}).encode()
            conn = http.client.HTTPConnection(
                "127.0.0.1", door.port, timeout=60)
            conn.request("POST", "/v1/admit", body=body,
                         headers={"Content-Type": "application/json"})
            resp = conn.getresponse()
            hd = dict(resp.getheaders())
            assert resp.status == 200 and b"response" in resp.read()
            conn.close()
            tid = hd["X-GK-Trace-Id"]
            assert hd["X-GK-Replica"] == "rT"

            def joined():
                out = col.assemble()
                for t in out["traces"]:
                    if t["trace_id"] == tid and \
                            len(t["processes"]) > 1:
                        return t
                return None

            entry = None

            def have():
                nonlocal entry
                entry = joined()
                return entry is not None

            assert wait_until(have, 10.0), \
                "replica half never joined the wire trace"
            # both sides' stage spans present under ONE trace_id
            wire_stages = {
                (s.get("attrs") or {}).get("stage")
                for s in entry["spans"]
                if s.get("process") == "frontdoor"
            } - {None}
            replica_stages = {
                (s.get("attrs") or {}).get("stage")
                for s in entry["spans"]
                if s.get("process") == "rT"
            } - {None}
            assert wire_stages and wire_stages <= set(WIRE_STAGES)
            # replica stages come from the documented admission set
            documented = {"queue_wait", "cache_lookup", "pack",
                          "compile", "dispatch", "fetch", "render"}
            assert replica_stages and replica_stages <= documented
            # every span is the request's own, except what the
            # replica grafts in from the ONE batch its review rode in
            # (the batcher's trace keeps its own id: it is shared by
            # the batch's members; the wire lane always batches)
            grafted = [s for s in entry["spans"]
                       if s.get("trace_id") not in (None, tid)]
            assert len({s["trace_id"] for s in grafted}) <= 1
            assert all(s["process"] == "rT" for s in grafted)
            # the command-pipe mirror of /debug/traces (the saturated-
            # or draining-listener fallback documented in
            # docs/tracing.md) serves the same ring
            reply = handle.command({"cmd": "traces", "limit": 64})
            assert reply["event"] == "traces"
            assert any(t["trace_id"] == tid
                       for t in reply["traces"]), \
                "pipe traces command did not serve the joined trace"
            # malformed params degrade to defaults, never kill the loop
            reply = handle.command({"cmd": "traces", "limit": "zzz",
                                    "min_ms": []})
            assert reply["event"] == "traces"
            assert handle.command({"cmd": "ping"})["event"] == "pong"
        finally:
            if door is not None:
                door.stop()
            handle.stop()
