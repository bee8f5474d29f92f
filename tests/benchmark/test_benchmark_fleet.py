"""The fleet cell (ISSUE 36): `paced-unique1000.synth500x100k-fleet4`
resolves to its files and reports what the issue lists; the summed
scrape adds series by series; `share_min` on a made-up page; the role
rehearsed at a tiny size on the CPU (two replicas behind one door; every
CPU process holds no chip, so `chips_distinct` is the one number
that reads not correct there, and the test waives it and nothing else);
the controls read not correct; and over a program without
`fleet/placement.py` the role ends at once, non-zero, with a message."""

import json
import os
import subprocess
import sys

import pytest

from test_benchmark_rehearsal import (  # noqa: F401  (child_env: fixture)
    BENCH,
    KEYS,
    REPO,
    child_env,
    drive,
    harness,
    line_of,
    procs,
    tiny,
)

import test_benchmark_admission_inventory as admission  # noqa: E402
import test_benchmark_cs_extend as cs_extend  # noqa: E402
from lib import fleet  # noqa: E402
from readers import share_min  # noqa: E402

CELL = "paced-unique1000.synth500x100k-fleet4"
PACED = "paced-unique.synth500x100k-webhook"
NEW = {"door_choose_us.fleet4", "door_tie_share.fleet4",
       "replica_share_min.fleet4", "dispatch_ms_per_review.fleet4",
       "replica_gc_full_ms_per_s.fleet4",
       # the splits that say where a replica's time goes, as
       # paced-unique reads them of its one replica
       "dispatch_enqueue_ms.fleet4", "dispatch_device_wait_ms.fleet4",
       "dispatch_fetch_ms.fleet4", "wire_queued_ms.fleet4",
       "replica_gc_pause_ms_per_s.fleet4", "replica_gc_pause_mean_ms.fleet4"}
SHARED = {"gen_late_p99_ms.paced", "door_overhead_ms.paced",
          "replica_wait_ms.paced", "wire_chunk_records_mean.paced",
          "batch_size_mean.paced", "batch_queue_ms.paced",
          "route_device_share.paced", "compiles_in_window.paced",
          "device_idle_share.paced", "admit_tail_p95_ms",
          "admit_tail_p99_ms"}


def manifest():
    return procs.read_json(os.path.join(REPO, "BENCHMARK.json"))


def test_the_cell_resolves_to_its_files_and_says_what_it_is():
    c = harness.load_cell(CELL)
    assert c["cell"]["chips"] == 4
    cfg, tr = c["config"], c["traffic"]
    assert cfg["role"] == "webhook_fleet" and cfg["reduced"] == []
    assert (cfg["replicas"], cfg["chips_per_replica"]) == (4, 1)
    assert cfg["balance"] == "least_inflight"
    one = procs.read_json(os.path.join(
        BENCH, "configs", "synth500x100k-webhook.json"))
    for k in ("templates", "resources", "violating_share",
              "violations_limit", "max_inflight", "timeout_s", "fail_open",
              "webhook_max_pending"):
        assert cfg[k] == one[k], k  # the same cluster, the same caller
    assert set(one["guarantees"]) < set(cfg["guarantees"])
    assert set(one) <= set(cfg)
    paced = procs.read_json(os.path.join(BENCH, "traffic",
                                         "paced-unique.json"))
    assert tr["kind"] == "open" and tr["connections"] == 64
    assert tr["rate_per_s"] == cfg["replicas"] * paced["rate_per_s"]
    assert tr["warm_bursts"] == paced["warm_bursts"]
    assert (tr["warm_reviews"], tr["min_reviews"]) == (10000, 4000)
    four = [w for w in manifest()["workloads"] if w["chips"] == 4]
    assert [w["name"] for w in four] == [CELL]


def test_it_reports_setup_and_p50_and_the_per_layer_set_the_issue_lists():
    m = manifest()
    e2e = {x["name"] for x in harness.cell_metrics(m, CELL, "end_to_end")}
    assert e2e == {"setup_s", "admit_p50_ms"}
    layers = {x["name"]: x for x in harness.cell_metrics(m, CELL, "per_layer")}
    assert set(layers) == NEW | SHARED
    for name in NEW:
        assert layers[name]["workloads"] == [CELL]
        assert layers[name]["moves"] == "admit_p50_ms"
    for name in SHARED:
        assert PACED in layers[name]["workloads"]
    # new entries stand at the end of their lists
    assert m["configs"][-1]["name"] == "synth500x100k-fleet4"
    assert m["workloads"][-1]["name"] == CELL
    assert {x["name"] for x in m["per_layer"][-len(NEW):]} == NEW
    assert next(x for x in m["end_to_end"]
                if x["name"] == "admit_p50_ms")["workloads"][-1] == CELL


def _less_this_pr(m):
    """BENCHMARK.json as it was before PR 36 appended to it."""
    m = json.loads(json.dumps(m))
    m["configs"] = [c for c in m["configs"]
                    if c["name"] != "synth500x100k-fleet4"]
    m["workloads"] = [w for w in m["workloads"] if w["name"] != CELL]
    m["per_layer"] = [x for x in m["per_layer"] if x["name"] not in NEW]
    for x in m["end_to_end"] + m["per_layer"]:
        if CELL in x.get("workloads", ()):
            x["workloads"].remove(CELL)
    return m


@pytest.mark.parametrize("pinned", [
    admission.test_the_new_cell_in_the_manifest,
    cs_extend.test_the_data_files_load_and_name_their_cells,
], ids=lambda f: f.__name__)
def test_what_was_there_stands_where_it_stood(pinned, monkeypatch):
    """Two accepted tests pin `[-1]` of the manifest's lists, and the
    contract has a PR append at the end, so both read red from their
    first assert on (PERF.md section 7, 6i: a `benchmark` issue's).
    Their bodies, whole, against the manifest less what this PR
    appended: everything they hold still holds, and what is new came
    after all of it."""
    m, read = manifest(), procs.read_json

    def read_json(path):
        got = read(path)
        return _less_this_pr(got) if path.endswith("BENCHMARK.json") else got

    old = _less_this_pr(m)
    for k in ("configs", "workloads", "per_layer"):
        assert [x["name"] for x in m[k][:len(old[k])]] == [
            x["name"] for x in old[k]]
    monkeypatch.setattr(procs, "read_json", read_json)
    pinned()


def test_the_summed_scrape_adds_series_by_series():
    a = {'webhook_batch_size_sum{replica_id="r0"}': 10.0,
         "process_cpu_seconds_total": 1.5, "tpu_dispatch_seconds_sum": 2.0,
         "brownout_level": 3.0, 'cal_rtt_ms{tier="device"}': 4.0,
         'replica_up{replica_id="r0"}': 1.0}
    b = {'webhook_batch_size_sum{replica_id="r1"}': 30.0,
         "process_cpu_seconds_total": 2.5, "only_b_total": 1.0,
         "brownout_level": 3.0, 'cal_rtt_ms{tier="device"}': 5.0,
         'replica_up{replica_id="r1"}': 1.0}
    assert fleet.add_pages({"r0": a, "r1": b}) == {
        'webhook_batch_size_sum{replica_id="r0"}': 10.0,
        'webhook_batch_size_sum{replica_id="r1"}': 30.0,
        "process_cpu_seconds_total": 4.0, "tpu_dispatch_seconds_sum": 2.0,
        "only_b_total": 1.0,
        # a gauge is kept once a replica, never added up
        'brownout_level{replica_id="r0"}': 3.0,
        'brownout_level{replica_id="r1"}': 3.0,
        'cal_rtt_ms{replica_id="r0",tier="device"}': 4.0,
        'cal_rtt_ms{replica_id="r1",tier="device"}': 5.0,
        'replica_up{replica_id="r0"}': 1.0,
        'replica_up{replica_id="r1"}': 1.0}
    assert fleet.add_counts([{"device|pinned": 3}, None,
                             {"device|pinned": 4, "np|load": 1}]) == {
        "device|pinned": 7, "np|load": 1}
    # a reader that sums a name's series reads the fleet-wide mean
    raw = {"before": {"replica_metrics": fleet.add_pages({
               "r0": {'s_sum{replica_id="r0"}': 1.0, 's_count{replica_id="r0"}': 1.0},
               "r1": {'s_sum{replica_id="r1"}': 0.0, 's_count{replica_id="r1"}': 0.0}})},
           "after": {"replica_metrics": fleet.add_pages({
               "r0": {'s_sum{replica_id="r0"}': 5.0, 's_count{replica_id="r0"}': 2.0},
               "r1": {'s_sum{replica_id="r1"}': 8.0, 's_count{replica_id="r1"}': 3.0}})},
           "window": {}}
    from readers import prom_ratio

    assert prom_ratio.read(raw, {"surface": "replica_metrics",
                                 "num": [["s_sum", ""]],
                                 "den": [["s_count", ""]]}) == 12.0 / 4.0
    g = fleet.gc_full([[(10.5, 0.2)], [], [(11.0, 0.1), (99.0, 0.3)], []],
                      10.0, 40.0)
    assert g["count"] == 2 and g["ms"] == pytest.approx(300.0 / 4)


def page(**ok):
    out = {f'gatekeeper_frontdoor_requests_total{{outcome="ok",backend="{r}"}}': v
           for r, v in ok.items()}
    out['gatekeeper_frontdoor_requests_total{outcome="shed",backend=""}'] = 9.0
    return out


def test_share_min_on_a_made_up_page():
    raw = {"before": {"door_metrics": page(r0=100, r1=100, r2=100, r3=0)},
           "after": {"door_metrics": page(r0=350, r1=300, r2=400, r3=150)},
           "window": {"replica_ids": ["r0", "r1", "r2", "r3"]}}
    assert share_min.read(raw, {}) == pytest.approx(100.0 * 150 / 900)
    # a replica the door never chose has no series: it reads 0, not the
    # least of those that have one
    raw["after"]["door_metrics"] = page(r0=400, r1=400, r2=400)
    assert share_min.read(raw, {}) == 0.0
    # nothing to read (the parent's tree, an idle window): left out
    raw["after"]["door_metrics"] = raw["before"]["door_metrics"]
    assert share_min.read(raw, {}) is None
    assert share_min.read({}, {}) is None
    ok = fleet.ok_by_replica(page(r0=1, r1=2), page(r0=11, r1=32))
    assert ok == {"r0": 10.0, "r1": 30.0}
    assert fleet.share_min(ok, ["r0", "r1"]) == 0.25
    assert fleet.share_min({}, ["r0"]) == 0.0


def test_what_a_fleet_adds_to_compared():
    readies = [{"replica_id": f"r{i}", "chip": i} for i in range(4)]
    ok = {"r0": 250, "r1": 260, "r2": 240, "r3": 250}
    fleetz = {"backends": [{"ejected": False, "readmissions": 0}] * 4}

    def correct(compared):
        return harness.result_line(
            {"compared": compared, "attempted": 0, "failed": 0,
             "device": {}}, {}, False)["correct"]

    sound = fleet.compared(readies, ok, fleetz)
    assert correct(sound)
    assert sound["chips_distinct"] == {"value": 4, "at_least": 4}
    assert sound["replica_share_min"]["value"] == 0.24
    shared = [dict(r, chip=1) if r["chip"] == 2 else r for r in readies]
    assert not correct(fleet.compared(shared, ok, fleetz))
    starved = dict(ok, r3=10)
    assert not correct(fleet.compared(readies, starved, fleetz))
    for b in ({"ejected": True, "readmissions": 0},
              {"ejected": False, "readmissions": 1}):
        assert not correct(fleet.compared(
            readies, ok, {"backends": [b] + fleetz["backends"][1:]}))
    # a replica that does not say which chip it holds holds none
    mute = [{"replica_id": "r0"}] + readies[1:]
    assert fleet.compared(mute, ok, fleetz)["chips_distinct"]["value"] == 3


def test_the_controls_read_not_correct():
    """The reference in the fleet's place: sound is correct; one replica
    of four one constraint stale reads verdicts_wrong > 0; two replicas
    on one chip read chips_distinct < 4."""
    role = harness.load_module("roles", "webhook_fleet")
    c = harness.load_cell(CELL)
    cfg = dict(c["config"], templates=12)
    r = role.control(cfg, c["traffic"], 2_900_000_017, 4000)
    assert r["sound"]["correct"] is True
    assert r["sound"]["verdicts_wrong"] == 0
    assert r["sound"]["chips_distinct"] == 4
    stale = r["faults"]["one_replica_a_constraint_stale"]
    assert stale["correct"] is False and stale["verdicts_wrong"] > 0
    assert stale["chips_distinct"] == 4  # by this limit alone
    one_chip = r["faults"]["two_replicas_one_chip"]
    assert one_chip["correct"] is False and one_chip["chips_distinct"] == 3
    assert one_chip["verdicts_wrong"] == 0


def test_over_a_program_without_placement_the_role_ends_at_once(tmp_path):
    """The benchmark's files laid over the parent's tree: run.py ends
    non-zero, with a message, before it starts any process."""
    tree = tmp_path / "parent"
    (tree / "gatekeeper_tpu" / "fleet").mkdir(parents=True)
    for pkg in ("gatekeeper_tpu", "gatekeeper_tpu/fleet"):
        (tree / pkg / "__init__.py").write_text("")
    import shutil

    shutil.copytree(BENCH, tree / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tree)
    p = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", CELL, "--seed",
         "3000000019", "--seconds", "30"], cwd=tree, capture_output=True,
        text=True, timeout=60)
    assert p.returncode == 4, p.stderr[-800:]
    assert p.stdout.strip() == ""
    assert "no gatekeeper_tpu.fleet.placement" in p.stderr
    assert not [f for f in os.listdir(tree / ".benchmark-work" / CELL)]


def test_fleet_cell_rehearsed_on_the_cpu(child_env, tmp_path, capsys):
    cfg = tiny("synth500x100k-fleet4")
    cfg["replicas"] = 2
    traffic = procs.read_json(os.path.join(BENCH, "traffic",
                                           "paced-unique1000.json"))
    traffic.update(connections=4, rate_per_s=150, warm_reviews=150,
                   min_reviews=100, warm_bursts=[1, 12])
    raw, ctx = drive("webhook_fleet", cfg, traffic, tmp_path)
    line = line_of(raw, CELL, "end_to_end", capsys)
    assert set(line) == KEYS
    assert set(line["metrics"]) == {"setup_s", "admit_p50_ms"}
    failing = {k for k, c in line["compared"].items()
               if not (c["value"] <= c["limit"] if "limit" in c
                       else c["value"] >= c["at_least"])}
    # no CPU process holds a chip: waived here, and only this
    assert failing == {"chips_distinct"}
    assert line["compared"]["chips_distinct"] == {"value": 0, "at_least": 2}
    assert line["failed"] == 0 and line["attempted"] >= 100
    assert line["compared"]["verdicts_wrong"]["value"] == 0
    assert line["compared"]["reviews_unanswered"]["value"] == 0
    assert line["compared"]["replicas_ejected"]["value"] == 0
    assert line["compared"]["replica_share_min"]["value"] >= 0.15
    assert line["device"]["count"] == 2 and line["device"]["chips"] == [None, None]
    assert [r["restore_outcome"] for r in raw["ready"]] == ["restored"] * 2
    # each replica was started under the program's placement
    t = raw["timings"]
    assert set(t["cpu_split"]) == {"r0", "r1", "door", "gen", "harness"}
    assert min(t["ladder_executables"]) >= t["ladder_executables_wanted"]
    layers = line_of(raw, CELL, "per_layer", capsys)["metrics"]
    # a window this short may hold no full collection of a replica's:
    # the two readers of the program's gc hook then have nothing to read
    gc_hook = {"replica_gc_pause_ms_per_s.fleet4",
               "replica_gc_pause_mean_ms.fleet4"}
    for name in (NEW - gc_hook) | {
            "door_overhead_ms.paced", "replica_wait_ms.paced",
            "batch_size_mean.paced", "route_device_share.paced",
            "compiles_in_window.paced", "gen_late_p99_ms.paced",
            "admit_tail_p95_ms", "admit_tail_p99_ms"}:
        assert name in layers, name
    assert "device_idle_share.paced" not in layers  # nothing to read here
    assert 15.0 <= layers["replica_share_min.fleet4"]["value"] <= 50.0
    assert 0.0 <= layers["door_tie_share.fleet4"]["value"] <= 100.0
    assert layers["door_choose_us.fleet4"]["value"] > 0
    # every choice of the window counted: least + tie = the reviews the
    # door sent on and got answered (no retry in a sound run)
    d0, d1 = raw["before"]["door_metrics"], raw["after"]["door_metrics"]
    choices = sum(v - d0.get(k, 0.0) for k, v in d1.items()
                  if "frontdoor_choice_total" in k)
    ok = sum(raw["window"]["ok_by_replica"].values())
    # a review in flight when the window's first scrape was taken was
    # chosen for before it and answered after it
    assert 0 < ok - 16 <= choices <= ok
    # a gauge of the summed page stands once a replica, never added up
    levels = {k: v for k, v in raw["after"]["replica_metrics"].items()
              if "brownout_level" in k}
    assert len(levels) == 2 and set(levels.values()) == {3.0}, levels
    assert 1 <= t["ladder_passes"] <= 4
