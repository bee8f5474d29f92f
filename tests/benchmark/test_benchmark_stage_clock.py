"""The per-layer metrics that read the program's stage clock (ISSUE 27),
rehearsed on the CPU twin: the audit cell's come out of
last_sweep_stats in the per-layer line, the webhook cells' resolve from
the replica's scraped /metrics page, and where the program has no such
series (the parent commit) every one of them is left out, not raised."""

import os

import pytest

from test_benchmark_rehearsal import (  # noqa: F401  (child_env: fixture)
    AUDIT_CELL,
    AUDIT_TRAFFIC,
    BENCH,
    REPO,
    SAT_CELL,
    child_env,
    drive,
    harness,
    line_of,
    procs,
    tiny,
)

PACED_CELL = "paced-unique.synth500x100k-webhook"
AUDIT_NEW = {"sweep_slice_ms", "sweep_enqueue_ms", "sweep_device_wait_ms",
             "sweep_fetch_ms", "sweep_apply_ms", "sweep_gc_pause_ms",
             "sweep_stage_sum_ms"}
SAT_NEW = {"replica_cpu_ms_per_review.sat", "wire_codec_ms_per_review.sat",
           "admit_prepare_ms_per_review.sat",
           "admit_finalize_ms_per_review.sat",
           "batch_pack_ms_per_review.sat", "batch_render_ms_per_review.sat",
           "batch_account_ms_per_review.sat",
           "host_stage_busy_ms_per_review.sat", "dispatch_enqueue_ms.sat",
           "dispatch_device_wait_ms.sat", "dispatch_fetch_ms.sat",
           "replica_gc_pause_ms_per_s.sat", "wire_queued_ms.sat",
           "replica_gc_pause_mean_ms.sat"}
PACED_NEW = {"dispatch_enqueue_ms.paced", "dispatch_device_wait_ms.paced",
             "dispatch_fetch_ms.paced", "replica_gc_pause_ms_per_s.paced",
             "wire_queued_ms.paced", "replica_gc_pause_mean_ms.paced"}


def manifest():
    return procs.read_json(os.path.join(REPO, "BENCHMARK.json"))


def test_new_metrics_are_data_over_the_readers_that_exist():
    per_layer = {m["name"]: m for m in manifest()["per_layer"]}
    for names, cell in ((AUDIT_NEW, AUDIT_CELL), (SAT_NEW, SAT_CELL),
                        (PACED_NEW, PACED_CELL)):
        for name in names:
            assert name in per_layer, name
            spec = procs.read_json(os.path.join(BENCH, "metrics",
                                                name + ".json"))
            assert spec["reader"] in ("prom_ratio", "mean_of", "value"), name
            assert cell in per_layer[name]["workloads"], name


def test_audit_stage_metrics_are_in_the_per_layer_line(child_env, tmp_path,
                                                       capsys):
    raw, _ctx = drive("audit", tiny("synth500x100k-audit"), AUDIT_TRAFFIC,
                      tmp_path)
    layers = line_of(raw, AUDIT_CELL, "per_layer", capsys)["metrics"]
    assert AUDIT_NEW <= set(layers)
    assert all(layers[n]["value"] is not None for n in AUDIT_NEW)
    assert layers["sweep_fetch_ms"]["value"] > 0
    assert layers["sweep_slice_ms"]["value"] > 0
    # the accepted metric reads the interval it always read: device_ms +
    # fetch_ms = slice + enqueue + device_wait + fetch
    parts = sum(layers[n]["value"] for n in (
        "sweep_slice_ms", "sweep_enqueue_ms", "sweep_device_wait_ms",
        "sweep_fetch_ms"))
    assert layers["sweep_dispatch_ms"]["value"] == pytest.approx(
        parts, rel=1e-6)
    # the stages with a name cover the sweep: what the harness's own
    # laps call a sweep, less what is the harness's own
    w = raw["window"]
    sweep_ms = 1e3 * w["window_s"] / w["sweeps"]
    assert 0.8 * sweep_ms <= layers["sweep_stage_sum_ms"]["value"] \
        <= 1.001 * sweep_ms
    # the program's own ingest stage beside the benchmark's lap of it
    ingest = sum(s["ingest_ms"] for s in w["sweep_stats"]) / w["sweeps"]
    assert ingest == pytest.approx(layers["sweep_ingest_ms"]["value"],
                                   rel=0.2)


def test_webhook_stage_metrics_resolve_from_the_scraped_page(
        child_env, tmp_path):
    cfg = tiny("synth500x100k-webhook")
    traffic = procs.read_json(os.path.join(BENCH, "traffic",
                                           "sat-unique.json"))
    traffic.update(connections=2, inflight_per_connection=8,
                   warm_reviews=200, bodies_per_s=1500, min_reviews=50)
    raw, _ctx = drive("webhook", cfg, traffic, tmp_path)
    page = raw["after"]["replica_metrics"]
    # the device tier's three stages exist only where the router sent a
    # batch to the device (on a loaded CPU it may price numpy cheaper)
    device = {"enqueue", "device_wait", "fetch"}
    on_device = any("tier=\"device\"" in k and v > 0 for k, v in page.items()
                    if k.startswith("gatekeeper_route_decisions_total"))
    for path, stages in (
            ("wire", ("read", "decode", "prepare", "wait", "finalize",
                      "encode", "write", "queued")),
            ("batch", ("wait", "collect", "route", "pack", "enqueue",
                       "device_wait", "fetch", "render", "account",
                       "release"))):
        for stage in stages:
            if stage in device and not on_device:
                continue
            key = ("gatekeeper_host_stage_seconds_total"
                   f'{{path="{path}",stage="{stage}"}}')
            assert page.get(key, 0) > 0, key
    assert page["gatekeeper_process_cpu_seconds_total"] > 0
    m = manifest()
    absent = {f"replica_gc_pause_{q}{x}" for q in ("ms_per_s", "mean_ms")
              for x in (".sat", ".paced")}
    if not on_device:
        absent |= {f"dispatch_{s}_ms{x}" for s in device
                   for x in (".sat", ".paced")}
    sat = harness.read_metrics(
        raw, harness.cell_metrics(m, SAT_CELL, "per_layer"))
    assert SAT_NEW - absent <= set(sat)
    # the same page through the paced cell's metric files
    paced = harness.read_metrics(
        raw, [x for x in harness.cell_metrics(m, PACED_CELL, "per_layer")
              if x["name"] in PACED_NEW])
    assert PACED_NEW - absent <= set(paced)
    # a two-second window need not hold one of the replica's five-second
    # collections; the series is on the page all the same
    assert any(k.startswith("gatekeeper_gc_pause_seconds_total")
               for k in page)
    # the busy stages hold the pack stage and more
    busy = sat["host_stage_busy_ms_per_review.sat"]["value"]
    assert busy > sat["batch_pack_ms_per_review.sat"]["value"] > 0


def test_a_program_without_the_series_leaves_every_new_metric_out():
    """The parent commit: no stage series on the page, no new key in
    last_sweep_stats.  Nothing is raised and nothing is printed as 0
    (sweep_fetch_ms excepted: the parent reported a constant 0.0)."""
    m = manifest()
    old_page = {"gatekeeper_tpu_dispatch_seconds_sum": 1.0}
    raw = {"before": {"replica_metrics": {}, "door_metrics": {}},
           "after": {"replica_metrics": old_page, "door_metrics": {}},
           "window": {"good": 100, "window_s": 2.0, "sweeps": 2,
                      "sweep_stats": [{"pack_ms": 1.0, "device_ms": 2.0,
                                       "fetch_ms": 0.0, "render_ms": 3.0}]}}
    for cell, new in ((SAT_CELL, SAT_NEW), (PACED_CELL, PACED_NEW)):
        got = harness.read_metrics(
            raw, [x for x in harness.cell_metrics(m, cell, "per_layer")
                  if x["name"] in new])
        assert got == {}
    got = harness.read_metrics(
        raw, [x for x in harness.cell_metrics(m, AUDIT_CELL, "per_layer")
              if x["name"] in AUDIT_NEW])
    assert set(got) == {"sweep_fetch_ms"}


def test_mean_full_collection_is_pause_seconds_over_collections():
    """gc_collections_total's reader: two full collections that held
    the replica 0.3 s between the scrapes read 150 ms each, in both
    webhook cells."""
    m = manifest()
    pre = "gatekeeper_gc_"
    before = {pre + 'pause_seconds_total{generation="2"}': 1.0,
              pre + 'collections_total{generation="2"}': 7.0,
              pre + 'collections_total{generation="0"}': 3.0}
    after = {pre + 'pause_seconds_total{generation="2"}': 1.3,
             pre + 'collections_total{generation="2"}': 9.0,
             pre + 'collections_total{generation="0"}': 50.0}
    raw = {"before": {"replica_metrics": before, "door_metrics": {}},
           "after": {"replica_metrics": after, "door_metrics": {}},
           "window": {"good": 100, "window_s": 2.0}}
    for cell, name in ((SAT_CELL, "replica_gc_pause_mean_ms.sat"),
                       (PACED_CELL, "replica_gc_pause_mean_ms.paced")):
        got = harness.read_metrics(
            raw, [x for x in harness.cell_metrics(m, cell, "per_layer")
                  if x["name"] == name])
        assert got[name]["value"] == pytest.approx(150.0)
        assert got[name]["unit"] == "ms"
