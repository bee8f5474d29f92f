"""sweep_pack_rows (ISSUE 28): the rows a sweep's pack sync re-packed,
read from a recorded last_sweep_stats by a reader that was already
there.  A program without the key (the parent commit) leaves the metric
out of the line."""

import os

import pytest

from test_benchmark_rehearsal import AUDIT_CELL, BENCH, REPO, harness, procs

NAME = "sweep_pack_rows"


def _metric():
    m = procs.read_json(os.path.join(REPO, "BENCHMARK.json"))
    return [x for x in harness.cell_metrics(m, AUDIT_CELL, "per_layer")
            if x["name"] == NAME]


def _raw(sweep_stats):
    return {"window": {"window_s": 2.0, "sweeps": len(sweep_stats),
                       "sweep_stats": sweep_stats}}


def test_sweep_pack_rows_is_data_over_mean_of():
    (entry,) = _metric()
    assert entry["workloads"] == [AUDIT_CELL]
    assert entry["moves"] == "audit_sweep_s" and entry["unit"] == "rows"
    spec = procs.read_json(os.path.join(BENCH, "metrics", NAME + ".json"))
    assert spec["reader"] == "mean_of"
    assert spec["args"] == {"list": "window.sweep_stats",
                            "keys": ["pack_rows"]}


@pytest.mark.parametrize("stats, want", [
    # delta sweeps, a step whose sample repeated a row, a rebasing full sweep
    ([{"pack_ms": 6.0, "pack_rows": 200.0},
      {"pack_ms": 6.1, "pack_rows": 199.0},
      {"pack_ms": 9.0, "pack_rows": 201.0}], 200.0),
    # clean sweeps count as 0 rows, not as missing
    ([{"pack_ms": 0.1, "pack_rows": 0.0},
      {"pack_ms": 5.0, "pack_rows": 100.0}], 50.0),
    # the parent commit: no such key, so no such metric
    ([{"pack_ms": 66.0, "render_ms": 70.0}], None),
], ids=["churn", "clean", "parent"])
def test_sweep_pack_rows_resolves_from_recorded_sweep_stats(stats, want):
    got = harness.read_metrics(_raw(stats), _metric())
    if want is None:
        assert got == {}
        return
    assert got[NAME]["value"] == pytest.approx(want)
    assert got[NAME]["unit"] == "rows"
