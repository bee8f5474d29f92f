"""The thirteen `.paced` metrics that read the program's `review` path
(ISSUE 38; benchmark/REVIEWPATH.md): every data file loads and names
its reader; on a made-up pair of scrapes the nine stage groups sum to
`review_service_net_ms.paced` and `review_service_ms +
door_replica_transit_ms = replica_wait_ms`; `prom_ratio_diff` where
there is nothing to read; which cells report them; the fleet role's
summed scrape; the paced cell rehearsed on the CPU; and the three
accepted tests that pin the end of `per_layer` or a cell's count, run
whole against the manifest less this PR's thirteen entries."""

import json
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
    tiny,
)

import test_benchmark_admission_inventory as admission  # noqa: E402
import test_benchmark_cs_extend as cs_extend  # noqa: E402
import test_benchmark_fleet as fleet_cell  # noqa: E402
from lib import fleet  # noqa: E402
from readers import prom_ratio, prom_ratio_diff  # noqa: E402

PACED = ["paced-unique.synth500x100k-webhook",
         "paced-svcapply.agilebank4x111k-webhook",
         "paced-unique1000.synth500x100k-fleet4"]
GROUPS = {  # metric -> (layer, the stages it sums)
    "review_wire_in_ms.paced": ("wire", ("frame", "queued", "decode",
                                         "prepare")),
    "review_batch_queue_ms.paced": ("batcher", ("batch_queue",)),
    "review_batch_pre_ms.paced": ("engine tiers", ("batch_pre",)),
    "review_dispatch_ms.paced": ("engine tiers", ("dispatch",)),
    "review_render_ms.paced": ("engine tiers", ("render",)),
    "review_batch_post_ms.paced": ("batcher", ("batch_post",)),
    "review_wake_ms.paced": ("batcher", ("wake",)),
    "review_wire_out_ms.paced": ("wire", ("finalize", "encode")),
    "review_send_ms.paced": ("wire", ("handoff", "write")),
}
WHOLE = {"review_service_ms.paced": "replica process",
         "review_service_net_ms.paced": "replica process",
         "review_gc_ms.paced": "replica process",
         "door_replica_transit_ms.paced": "door"}
NEW = set(GROUPS) | set(WHOLE)
STAGES = [s for _layer, stages in GROUPS.values() for s in stages]
PRE = "gatekeeper_host_stage_"


def manifest():
    return procs.read_json(os.path.join(REPO, "BENCHMARK.json"))


def _entries():
    return [x for x in manifest()["per_layer"] if x["name"] in NEW]


def test_the_data_files_load_and_name_their_readers():
    assert len(NEW) == 13
    for name in sorted(NEW):
        spec = procs.read_json(os.path.join(BENCH, "metrics", name + ".json"))
        assert set(spec) == {"reader", "args", "doc"}, name
        assert spec["reader"] in ("prom_ratio", "prom_ratio_diff"), name
        assert "ISSUE 38" in spec["doc"], name
        harness.load_module("readers", spec["reader"])
        args = spec["args"]
        sides = [args["a"], args["b"]] if "a" in args else [args]
        for side in sides:
            assert side["scale"] == 1000.0
        if name != "door_replica_transit_ms.paced":
            for side in sides:
                assert side["surface"] == "replica_metrics"
                assert side["den"] == [[
                    "host_stage_calls_total",
                    'path="review",stage="write"']], name
    # the stages the groups read are the program's, each read once
    from gatekeeper_tpu.obs import trace

    assert STAGES == list(trace.REVIEW_STAGES)
    for name, (_layer, stages) in GROUPS.items():
        args = procs.read_json(os.path.join(
            BENCH, "metrics", name + ".json"))["args"]
        for side, series in (("a", "host_stage_seconds_total"),
                             ("b", "host_stage_gc_seconds_total")):
            assert args[side]["num"] == [
                [series, f'path="review",stage="{s}"'] for s in stages]
    # and the text beside them names every stage and every metric
    doc = open(os.path.join(BENCH, "REVIEWPATH.md")).read()
    for word in list(NEW) + STAGES:
        assert f"`{word}`" in doc, word


def test_the_entries_stand_at_the_end_and_say_what_the_issue_lists():
    m = manifest()
    tail = m["per_layer"][-13:]
    assert {x["name"] for x in tail} == NEW
    for x in tail:
        assert x == {
            "name": x["name"], "unit": "ms", "better": "lower",
            "source": "program_span", "moves": "admit_p50_ms",
            "layer": (GROUPS.get(x["name"]) or [WHOLE.get(x["name"])])[0],
            "workloads": PACED}
    layers = {x["layer"] for x in m["per_layer"][:-13]}
    assert {x["layer"] for x in tail} <= layers   # no layer is new


def test_the_three_paced_cells_report_all_thirteen_and_no_other_does():
    m = manifest()
    for w in m["workloads"]:
        got = {x["name"] for x in harness.cell_metrics(
            m, w["name"], "per_layer")} & NEW
        assert got == (NEW if w["name"] in PACED else set()), w["name"]


def _scrapes(reviews=400.0, gc=None, door_wait_s=3.6):
    """A made-up pair of scrapes: `reviews` booked in the window, each
    stage k holding 0.1 * (k + 1) s over all of them, some held by the
    collector; the door waited `door_wait_s` for its replica in all."""
    gc = gc or {}
    before = {PRE + 'calls_total{path="review",stage="write"}': 100.0,
              PRE + 'seconds_total{path="wire",stage="write"}': 5.0}
    after = {PRE + 'seconds_total{path="wire",stage="write"}': 9.0}
    for k, stage in enumerate(STAGES):
        lab = f'{{path="review",stage="{stage}"}}'
        before[PRE + "seconds_total" + lab] = 1.0
        after[PRE + "seconds_total" + lab] = 1.0 + 0.1 * (k + 1)
        after[PRE + "calls_total" + lab] = 100.0 + reviews
        if stage in gc:
            after[PRE + "gc_seconds_total" + lab] = gc[stage]
    door = 'gatekeeper_frontdoor_stage_seconds_%s{stage="replica_wait"}'
    return {"before": {"replica_metrics": before,
                       "door_metrics": {door % "sum": 1.0,
                                        door % "count": 50.0}},
            "after": {"replica_metrics": after,
                      "door_metrics": {door % "sum": 1.0 + door_wait_s,
                                       door % "count": 50.0 + reviews}},
            "window": {"good": reviews, "window_s": 2.0}}


def _read(raw, names=None):
    got = harness.read_metrics(
        raw, [x for x in _entries() if names is None or x["name"] in names])
    return {k: v["value"] for k, v in got.items()}


def test_on_a_made_up_pair_of_scrapes_the_identities_hold():
    raw = _scrapes(gc={"render": 0.2, "wake": 0.04})
    got = _read(raw)
    assert set(got) == NEW
    assert got["review_service_ms.paced"] == pytest.approx(10.5 / 400 * 1e3)
    assert got["review_gc_ms.paced"] == pytest.approx(0.24 / 400 * 1e3)
    assert got["review_service_net_ms.paced"] == pytest.approx(
        got["review_service_ms.paced"] - got["review_gc_ms.paced"])
    assert sum(got[n] for n in GROUPS) == pytest.approx(
        got["review_service_net_ms.paced"], abs=1e-9)
    assert got["review_render_ms.paced"] == pytest.approx(
        (0.8 - 0.2) / 400 * 1e3)      # net of the collector
    assert got["review_wire_in_ms.paced"] == pytest.approx(
        (0.1 + 0.2 + 0.3 + 0.4) / 400 * 1e3)
    wait = harness.read_metrics(raw, [
        x for x in manifest()["per_layer"]
        if x["name"] == "replica_wait_ms.paced"])
    assert got["review_service_ms.paced"] + got[
        "door_replica_transit_ms.paced"] == pytest.approx(
            wait["replica_wait_ms.paced"]["value"])
    assert wait["replica_wait_ms.paced"]["value"] == pytest.approx(9.0)
    # no collection met a review: the collector's part reads 0, it is
    # not left out, and net = gross
    calm = _read(_scrapes())
    assert calm["review_gc_ms.paced"] == 0.0
    assert calm["review_service_net_ms.paced"] == pytest.approx(
        calm["review_service_ms.paced"])


def test_a_stage_that_never_happened_reads_zero_and_the_sum_stands():
    """The interpreter tier: no `dispatch` series at all."""
    raw = _scrapes()
    for page in (raw["before"]["replica_metrics"],
                 raw["after"]["replica_metrics"]):
        for key in [k for k in page if 'stage="dispatch"' in k]:
            del page[key]
    got = _read(raw)
    assert got["review_dispatch_ms.paced"] == 0.0
    assert sum(got[n] for n in GROUPS) == pytest.approx(
        got["review_service_net_ms.paced"], abs=1e-9)


@pytest.mark.parametrize("case", ["the_parent", "no_growth", "no_scrapes"])
def test_where_there_is_nothing_to_read_every_one_is_left_out(case):
    """The parent commit (no such series), a window in which no review
    was booked, a role that scraped nothing: left out, never raised."""
    raw = _scrapes()
    if case == "the_parent":
        for side in ("before", "after"):
            raw[side]["replica_metrics"] = {
                k: v for k, v in raw[side]["replica_metrics"].items()
                if 'path="review"' not in k}
    elif case == "no_growth":
        raw["after"]["replica_metrics"] = dict(
            raw["before"]["replica_metrics"])
    else:
        raw = {"window": raw["window"]}
    assert _read(raw) == {}


def test_prom_ratio_diff_on_none_and_on_zero_growth():
    raw = _scrapes(gc={"wake": 0.04})
    den = [["host_stage_calls_total", 'path="review",stage="write"']]

    def side(series, stage, **kw):
        return dict({"surface": "replica_metrics", "den": den,
                     "num": [[series, f'stage="{stage}"']]}, **kw)

    a = side("host_stage_seconds_total", "wake")
    b = side("host_stage_gc_seconds_total", "wake")
    assert prom_ratio_diff.read(raw, {"a": a, "b": b}) == pytest.approx(
        (1.0 - 0.04) / 400)
    # b grew by nothing: it subtracts 0 and is not None
    quiet = side("host_stage_gc_seconds_total", "frame")
    assert prom_ratio.read(raw, quiet) is None
    assert prom_ratio_diff.read(raw, {"a": a, "b": quiet}) == \
        pytest.approx(1.0 / 400)
    # a has nothing to read: None, whatever b says
    gone = side("host_stage_seconds_total", "no-such-stage")
    assert prom_ratio_diff.read(raw, {"a": gone, "b": b}) is None
    # b's denominator did not grow (a program without the path): None
    other = dict(b, den=[["host_stage_calls_total", 'stage="nowhere"']])
    assert prom_ratio_diff.read(raw, {"a": a, "b": other}) is None
    assert prom_ratio_diff.read({}, {"a": a, "b": b}) is None


def test_the_fleets_summed_scrape_adds_the_review_series():
    one = _scrapes(reviews=300.0, gc={"render": 0.3})
    two = _scrapes(reviews=100.0)
    raw = {side: {
        "replica_metrics": fleet.add_pages({
            "r0": one[side]["replica_metrics"],
            "r1": two[side]["replica_metrics"]}),
        "door_metrics": one[side]["door_metrics"]}
        for side in ("before", "after")}
    raw["window"] = one["window"]
    key = PRE + 'calls_total{path="review",stage="write"}'
    assert raw["before"]["replica_metrics"][key] == 200.0
    assert raw["after"]["replica_metrics"][key] == 600.0
    got = _read(raw)
    assert set(got) == NEW
    # both replicas' seconds over both replicas' reviews
    assert got["review_service_ms.paced"] == pytest.approx(
        2 * 10.5 / 400 * 1e3)
    assert got["review_gc_ms.paced"] == pytest.approx(0.3 / 400 * 1e3)
    assert sum(got[n] for n in GROUPS) == pytest.approx(
        got["review_service_net_ms.paced"], abs=1e-9)


def _less_this_pr(m):
    """BENCHMARK.json as it was before PR 38 appended to it."""
    m = json.loads(json.dumps(m))
    m["per_layer"] = [x for x in m["per_layer"] if x["name"] not in NEW]
    return m


@pytest.mark.parametrize("pinned", [
    lambda mp: fleet_cell.
    test_it_reports_setup_and_p50_and_the_per_layer_set_the_issue_lists(),
    lambda mp: fleet_cell.test_what_was_there_stands_where_it_stood(
        admission.test_the_new_cell_in_the_manifest, mp),
    lambda mp: fleet_cell.test_what_was_there_stands_where_it_stood(
        cs_extend.test_the_data_files_load_and_name_their_cells, mp),
], ids=["the_fleet_cells_per_layer_set", "the_new_cell_in_the_manifest",
        "the_data_files_load_and_name_their_cells"])
def test_what_was_there_stands_where_it_stood(pinned, monkeypatch):
    """Three accepted tests pin the end of `per_layer` or a cell's count
    of metrics, and the contract has a PR append at the end, so they
    read red once anything is appended (PERF.md section 7, 6i: a
    `benchmark` issue's).  Their bodies, whole, against the manifest
    less what this PR appended: everything they hold still holds, and
    what is new came after all of it."""
    m, read = manifest(), procs.read_json

    def read_json(path):
        got = read(path)
        return _less_this_pr(got) if path.endswith("BENCHMARK.json") else got

    old = _less_this_pr(m)
    assert m["per_layer"][:len(old["per_layer"])] == old["per_layer"]
    assert len(m["per_layer"]) == len(old["per_layer"]) + 13
    for k in ("command", "paths", "run_seconds", "configs", "workloads",
              "end_to_end"):
        assert m[k] == old[k]
    monkeypatch.setattr(procs, "read_json", read_json)
    pinned(monkeypatch)


def test_paced_cell_rehearsed_on_the_cpu_prints_all_thirteen(
        child_env, tmp_path, capsys):
    traffic = procs.read_json(os.path.join(BENCH, "traffic",
                                           "paced-unique.json"))
    # a rate one CPU replica of this sandbox sustains beside its
    # neighbours (at 150/s one run in three shed in a stall)
    traffic.update(connections=4, rate_per_s=60, warm_reviews=100,
                   min_reviews=50, warm_bursts=[1, 12])
    raw, _ctx = drive("webhook", tiny("synth500x100k-webhook"), traffic,
                      tmp_path)
    line = line_of(raw, PACED[0], "end_to_end", capsys)
    assert line["correct"] is True and line["failed"] == 0
    layers = line_of(raw, PACED[0], "per_layer", capsys)["metrics"]
    got = {n: layers[n]["value"] for n in NEW if n in layers}
    assert set(got) == NEW
    assert all(layers[n]["unit"] == "ms" for n in NEW)
    # the identities, on the printed numbers
    assert sum(got[n] for n in GROUPS) == pytest.approx(
        got["review_service_net_ms.paced"], abs=0.01)
    assert got["review_service_net_ms.paced"] + got["review_gc_ms.paced"] \
        == pytest.approx(got["review_service_ms.paced"], abs=1e-6)
    assert got["review_service_ms.paced"] + got[
        "door_replica_transit_ms.paced"] == pytest.approx(
            layers["replica_wait_ms.paced"]["value"], abs=1e-6)
    # (no order is held between the two on a window this short: the
    # door's histogram is recorded at once and the review clock flushes
    # with the loop's tick, so a stalled review of the warm-up can land
    # on this side of the first scrape)
    assert got["review_service_ms.paced"] > 0
    for name in ("review_wire_in_ms.paced", "review_batch_queue_ms.paced",
                 "review_render_ms.paced", "review_wake_ms.paced",
                 "review_wire_out_ms.paced", "review_send_ms.paced"):
        assert got[name] > 0, name
    # the reviews booked between the two scrapes beside the same page's
    # own count of verdicts: the same reviews, but for those in flight at
    # either scrape and those the review clock had not yet flushed (it
    # flushes with the loop's tick, at most every 0.25 s, and on a
    # loaded CPU later), so in a window of two seconds only roughly.
    # tests/test_review_path.py holds the count, and `batch_queue` to the
    # queue histogram's interval, exactly, where nothing lags.  (The
    # window's `good` is fewer than either: the first scrape is taken,
    # over several surfaces, before the generator opens its window, and
    # the open loop keeps offering meanwhile.)
    b0, b1 = (raw[s]["replica_metrics"] for s in ("before", "after"))
    key = PRE + 'calls_total{path="review",stage="write"}'
    booked = b1[key] - b0.get(key, 0.0)
    answered = sum(v - b0.get(k, 0.0) for k, v in b1.items()
                   if k.startswith("gatekeeper_request_count"))
    assert 0.5 * answered <= booked <= answered + 16
