"""Exemplars + exposition formats (ISSUE 5): bounded per-bucket exemplar
capture, byte-checked OpenMetrics and classic renderings, HTTP content
negotiation, the exporter's debug surface, idempotent start, and the
port-in-use contract."""

import json
import urllib.error
import urllib.request

import pytest

from gatekeeper_tpu.metrics.exporter import (
    CONTENT_TYPE_OPENMETRICS,
    CONTENT_TYPE_TEXT,
    MetricsExporter,
    render_openmetrics,
    render_prometheus,
)
from gatekeeper_tpu.metrics.views import (
    AGG_COUNT,
    AGG_DISTRIBUTION,
    AGG_LAST_VALUE,
    Measure,
    Registry,
    View,
)

TRACE_ID = "ab" * 16


def small_registry() -> Registry:
    reg = Registry()
    m_count = Measure("demo_total", "demo counter")
    m_gauge = Measure("demo_gauge", "demo gauge")
    m_hist = Measure("demo_seconds", "demo histogram", "s")
    reg.register(
        View("demo_total", m_count, AGG_COUNT, tag_keys=("outcome",)),
        View("demo_gauge", m_gauge, AGG_LAST_VALUE),
        View("demo_seconds", m_hist, AGG_DISTRIBUTION,
             buckets=(0.01, 0.1)),
    )
    reg.record(m_count, 1.0, {"outcome": "hit"}, count=3)
    reg.record(m_gauge, 2.5)
    reg.record(m_hist, 0.05, exemplar_trace_id=TRACE_ID)
    reg.record(m_hist, 0.5, exemplar_trace_id=TRACE_ID)
    # pin the (wall-anchored) exemplar timestamps so the rendering is
    # byte-checkable
    dist = reg._views["demo_seconds"].rows[()]
    dist.exemplars = {
        i: type(ex)(value=ex.value, trace_id=ex.trace_id, ts=1700000000.0)
        for i, ex in dist.exemplars.items()
    }
    return reg


def test_exemplar_capture_is_bounded_per_bucket():
    reg = small_registry()
    m_hist = Measure("demo_seconds", "demo histogram", "s")
    for _ in range(50):  # hammer one bucket: newest exemplar wins
        reg.record(m_hist, 0.02, exemplar_trace_id="cd" * 16)
    dist = reg._views["demo_seconds"].rows[()]
    assert set(dist.exemplars) == {1, 2}  # never more than one per bucket
    assert dist.exemplars[1].trace_id == "cd" * 16
    # records without an active trace attach nothing
    reg.record(m_hist, 0.02)
    assert dist.exemplars[1].trace_id == "cd" * 16


def test_openmetrics_rendering_byte_exact():
    expected = (
        "# HELP gatekeeper_demo_gauge demo gauge\n"
        "# TYPE gatekeeper_demo_gauge gauge\n"
        "gatekeeper_demo_gauge 2.5\n"
        "# HELP gatekeeper_demo_seconds demo histogram\n"
        "# TYPE gatekeeper_demo_seconds histogram\n"
        'gatekeeper_demo_seconds_bucket{le="0.01"} 0\n'
        'gatekeeper_demo_seconds_bucket{le="0.1"} 1 '
        f'# {{trace_id="{TRACE_ID}"}} 0.05 1700000000.000\n'
        'gatekeeper_demo_seconds_bucket{le="+Inf"} 2 '
        f'# {{trace_id="{TRACE_ID}"}} 0.5 1700000000.000\n'
        "gatekeeper_demo_seconds_sum 0.55\n"
        "gatekeeper_demo_seconds_count 2\n"
        "# HELP gatekeeper_demo demo counter\n"
        "# TYPE gatekeeper_demo counter\n"
        'gatekeeper_demo_total{outcome="hit"} 3\n'
        "# EOF\n"
    )
    assert render_openmetrics(small_registry()) == expected


def test_classic_rendering_byte_exact_no_exemplars():
    expected = (
        "# HELP gatekeeper_demo_gauge demo gauge\n"
        "# TYPE gatekeeper_demo_gauge gauge\n"
        "gatekeeper_demo_gauge 2.5\n"
        "# HELP gatekeeper_demo_seconds demo histogram\n"
        "# TYPE gatekeeper_demo_seconds histogram\n"
        'gatekeeper_demo_seconds_bucket{le="0.01"} 0\n'
        'gatekeeper_demo_seconds_bucket{le="0.1"} 1\n'
        'gatekeeper_demo_seconds_bucket{le="+Inf"} 2\n'
        "gatekeeper_demo_seconds_sum 0.55\n"
        "gatekeeper_demo_seconds_count 2\n"
        "# HELP gatekeeper_demo_total demo counter\n"
        "# TYPE gatekeeper_demo_total counter\n"
        'gatekeeper_demo_total{outcome="hit"} 3\n'
    )
    assert render_prometheus(small_registry()) == expected


def test_stage_records_capture_trace_exemplars():
    """record_stage inside an active span attaches the span's trace id;
    outside one it attaches nothing."""
    from gatekeeper_tpu.metrics import catalog
    from gatekeeper_tpu.obs import trace as obstrace

    reg = catalog.register_catalog(Registry())
    import gatekeeper_tpu.metrics.catalog as cat

    old_ready, old_global = cat._GLOBAL_READY, None
    # route the module-global recorder at our registry for the test
    import gatekeeper_tpu.metrics.views as views_mod

    old_global = views_mod._global
    views_mod._global = reg
    cat._GLOBAL_READY = False
    try:
        with obstrace.root_span("t") as sp:
            cat.record_stage(catalog.DISPATCH_M, 0.001,
                             {"path": "review", "tier": "tpu"})
            tid = sp.trace.trace_id
        rows = reg.view_rows("tpu_dispatch_seconds")
        dist = rows[("review", "tpu")]
        assert len(dist.exemplars) == 1
        ex = next(iter(dist.exemplars.values()))
        assert ex.trace_id == tid and ex.value == pytest.approx(0.001)
        cat.record_stage(catalog.DISPATCH_M, 0.001,
                         {"path": "review", "tier": "tpu"})
        assert len(dist.exemplars) == 1  # no trace, no new exemplar...
    finally:
        views_mod._global = old_global
        cat._GLOBAL_READY = old_ready


def content_type_of(url, accept=None):
    req = urllib.request.Request(url)
    if accept:
        req.add_header("Accept", accept)
    with urllib.request.urlopen(req, timeout=10) as resp:
        return resp.headers.get("Content-Type"), resp.read().decode()


class TestExporterServer:
    def test_content_negotiation_and_debug_surface(self):
        exp = MetricsExporter(port=0, registry=small_registry())
        exp.start()
        try:
            base = f"http://127.0.0.1:{exp.port}"
            ctype, body = content_type_of(f"{base}/metrics")
            assert ctype == CONTENT_TYPE_TEXT
            assert "# EOF" not in body and " # {" not in body
            ctype, body = content_type_of(
                f"{base}/metrics", accept=CONTENT_TYPE_OPENMETRICS
            )
            assert ctype == CONTENT_TYPE_OPENMETRICS
            assert body.endswith("# EOF\n")
            assert f'# {{trace_id="{TRACE_ID}"}}' in body
            # audit-only deployments get the debug surface from this
            # listener: traces, costs, slo
            for path in ("/debug/traces", "/debug/costs", "/debug/slo"):
                with urllib.request.urlopen(base + path, timeout=10) as r:
                    assert r.status == 200
                    json.loads(r.read())
            # hardened params: JSON 400, never a 500 traceback
            with pytest.raises(urllib.error.HTTPError) as ei:
                urllib.request.urlopen(
                    base + "/debug/costs?top=banana", timeout=10
                )
            assert ei.value.code == 400
            assert json.loads(ei.value.read())["error"] == (
                "top must be numeric"
            )
        finally:
            exp.stop()

    def test_collect_hooks_refresh_before_scrape(self):
        calls = []
        reg = small_registry()
        exp = MetricsExporter(
            port=0, registry=reg, collect_hooks=[lambda r: calls.append(r)]
        )
        exp.start()
        try:
            content_type_of(f"http://127.0.0.1:{exp.port}/metrics")
            assert calls == [reg]
        finally:
            exp.stop()

    def test_start_is_idempotent(self):
        exp = MetricsExporter(port=0, registry=small_registry())
        exp.start()
        first_port = exp.port
        try:
            # double start replaces the listener instead of leaking it;
            # the replacement binds and serves
            exp.port = 0
            exp.start()
            assert exp.port != 0
            ctype, _ = content_type_of(f"http://127.0.0.1:{exp.port}/metrics")
            assert ctype == CONTENT_TYPE_TEXT
            # the first port was released by the replacement
            exp2 = MetricsExporter(
                port=first_port, registry=small_registry(),
                host="127.0.0.1",
            )
            exp2.start()
            exp2.stop()
        finally:
            exp.stop()

    def test_port_in_use_is_a_clear_error(self):
        exp = MetricsExporter(port=0, registry=small_registry(),
                              host="127.0.0.1")
        exp.start()
        try:
            clash = MetricsExporter(
                port=exp.port, registry=small_registry(), host="127.0.0.1"
            )
            with pytest.raises(RuntimeError) as ei:
                clash.start()
            msg = str(ei.value)
            assert str(exp.port) in msg
            assert "--prometheus-port" in msg
        finally:
            exp.stop()
