"""The two metrics of ISSUE 34 (the constraint side extended in place
when the vocabulary grows): their data files load, `sweep_cs_upload_arrays`
names the three audit cells, both resolve from recorded readings (and
are left out where the program has no such series or key: the parent
commit), and a rehearsal of each cell on the CPU at a tiny size reads
what the issue predicts: the webhook window brings the side current by
extension alone, and a delta sweep uploads no more constraint-side
arrays than there are str-pred tables.

`cs_extend_share.svcapply` has its data file and no entry in
BENCHMARK.json: test_benchmark_admission_inventory.py pins the number of
per-layer metrics paced-svcapply reports, and a PR that claims a gain
edits no file the benchmark has.  Its reader is held here against the
file, so a `benchmark` PR adds the entry and nothing else."""

import os

import pytest

from test_benchmark_rehearsal import (  # noqa: F401  (child_env: fixture)
    BENCH,
    REPO,
    child_env,
    drive,
    harness,
    line_of,
    procs,
)

import test_benchmark_admission_inventory as admission
import test_benchmark_agilebank as inventory

SVCAPPLY = "paced-svcapply.agilebank4x111k-webhook"
AUDIT_CELLS = ["churn200.synth500x100k-audit",
               "svc-keychurn50.agilebank4x111k-audit",
               "churn2000.synth500x100k-audit"]
SHARE, ARRAYS = "cs_extend_share.svcapply", "sweep_cs_upload_arrays"
SERIES = "gatekeeper_constraint_side_refresh_total"
# K8sAllowedRepos' two startswith nodes: the agilebank bundle's tables
AGILEBANK_TABLES = 2


# what BENCHMARK.json's entry will hold (a `benchmark` PR's to add)
SHARE_METRIC = {"name": SHARE, "unit": "%", "better": "higher",
                "source": "program_counter", "layer": "engine tiers",
                "moves": "admit_p50_ms", "workloads": [SVCAPPLY]}

_metrics = inventory._metrics


def test_the_data_files_load_and_name_their_cells():
    m = procs.read_json(os.path.join(REPO, "BENCHMARK.json"))
    for name, reader in ((SHARE, "prom_ratio"), (ARRAYS, "mean_of")):
        data = procs.read_json(os.path.join(BENCH, "metrics",
                                            name + ".json"))
        assert data["reader"] == reader and "ISSUE 34" in data["doc"]
        harness.load_module("readers", reader)
    entry = next(x for x in m["per_layer"] if x["name"] == ARRAYS)
    assert entry == m["per_layer"][-1]  # appended, nothing before it moved
    assert entry["workloads"] == AUDIT_CELLS
    assert (entry["layer"], entry["moves"], entry["source"]) == (
        "audit sweep", "audit_sweep_s", "program_counter")
    for cell in AUDIT_CELLS:
        assert _metrics(cell, {ARRAYS})
    assert not [x for x in m["per_layer"] if x["name"] == SHARE]
    layers = {x["layer"] for x in m["per_layer"]}
    assert SHARE_METRIC["layer"] in layers


def _page(extend, repack):
    page = {"gatekeeper_request_count": 1.0}
    if extend is not None:
        page[SERIES + '{outcome="extend"}'] = extend
    if repack is not None:
        page[SERIES + '{outcome="repack"}'] = repack
    return {"replica_metrics": page}


@pytest.mark.parametrize("before,after,want", [
    ((10.0, 3.0), (410.0, 3.0), 100.0),   # extensions alone
    ((10.0, 3.0), (409.0, 4.0), 99.75),   # one bucket crossing
    ((None, 3.0), (None, 3.0), None),     # nothing brought current
    ((None, None), (None, None), None),   # the parent: no such series
])
def test_extend_share_resolves_from_recorded_pages(before, after, want):
    raw = {"before": _page(*before), "after": _page(*after),
           "window": {"good": 4000, "window_s": 30.0}}
    got = harness.read_metrics(raw, [SHARE_METRIC])
    if want is None:
        assert got == {}
    else:
        assert got == {SHARE: {"value": pytest.approx(want), "unit": "%"}}


def test_upload_arrays_resolves_from_recorded_sweep_stats():
    stats = [{"full": 0.0, "cs_upload_arrays": 2.0},
             {"full": 0.0, "cs_upload_arrays": 0.0},
             {"full": 1.0, "cs_upload_arrays": 43.0}]
    for cell in AUDIT_CELLS:
        got = harness.read_metrics({"window": {"sweep_stats": stats}},
                                   _metrics(cell, {ARRAYS}))
        assert got == {ARRAYS: {"value": 15.0, "unit": "arrays"}}
        # the parent commit: no such key, so no such metric
        assert harness.read_metrics(
            {"window": {"sweep_stats": [{"full": 0.0, "slice_ms": 8.3}]}},
            _metrics(cell, {ARRAYS})) == {}


def test_a_svc_keychurn_rehearsal_uploads_the_tables_alone(
        child_env, tmp_path, capsys):
    raw, _ctx = drive("audit_inventory", inventory.tiny(),
                      inventory.TRAFFIC, tmp_path)
    stats = raw["window"]["sweep_stats"]
    assert stats and all("cs_upload_arrays" in s for s in stats)
    # every sweep of the window took the delta path and uploaded at most
    # the tables; the re-pointed selectors bring new strings, so some
    # sweep did upload them
    assert all(s["full"] == 0.0 for s in stats)
    assert all(s["cs_upload_arrays"] in (0.0, AGILEBANK_TABLES)
               for s in stats)
    assert any(s["cs_upload_arrays"] == AGILEBANK_TABLES for s in stats)
    layers = line_of(raw, inventory.CELL, "per_layer", capsys)["metrics"]
    assert 0 < layers[ARRAYS]["value"] <= AGILEBANK_TABLES
    line = line_of(raw, inventory.CELL, "end_to_end", capsys)
    assert line["correct"] is True and line["failed"] == 0


def test_a_paced_svcapply_rehearsal_extends_and_never_repacks(
        child_env, tmp_path, capsys, monkeypatch):
    from roles import webhook_inventory as role

    # at this size the router prices a paced batch to the interpreter
    # tier, where no constraint side is packed: the window has to run
    # pinned to the "device", as the cell's does.  Bursts of 32 are sent
    # there by the router itself, load their executables on first
    # contact and so fire the latency alert at the ladder's scrape
    monkeypatch.setattr(role, "PIN_WAIT_S", 15.0)
    tr = admission.traffic()
    tr.update(shape_bursts=[1, 4, 12, 32], warm_bursts=[1, 4, 12, 32])
    raw, _ctx = drive("webhook_inventory", admission.tiny(), tr, tmp_path)
    assert raw["timings"]["brownout_level"] == 3.0, raw["timings"]
    line = line_of(raw, SVCAPPLY, "end_to_end", capsys)
    assert line["correct"] is True and line["failed"] == 0, (
        line["compared"], raw["notes"], raw["timings"])
    layers = line_of(raw, SVCAPPLY, "per_layer", capsys)["metrics"]
    assert layers["route_device_share.paced"]["value"] == 100.0
    share = harness.read_metrics(raw, [SHARE_METRIC])[SHARE]
    assert share["value"] >= 99.0
    grew = {k: v - raw["before"]["replica_metrics"].get(k, 0.0)
            for k, v in raw["after"]["replica_metrics"].items()
            if SERIES in k}
    # a quarter of the mix's Service reviews bring a new selector
    assert grew[SERIES + '{outcome="extend"}'] >= 5
    assert grew.get(SERIES + '{outcome="repack"}', 0.0) == 0.0
