"""The agilebank deployment's webhook role (ISSUE 33) rehearsed on the
CPU at a tiny size: roles/webhook_inventory.py end to end through the
line printer with both metric groups in the line; the comparison's
control (sound 0, each planted fault wrong); the same window's answers
broken where they are produced; the capability exit (a program without
the join binding ends the run before any warm-up); the traffic file's
mix; and the new cell's manifest entries."""

import json
import os

import pytest

from test_benchmark_rehearsal import (  # noqa: F401  (child_env: fixture)
    BENCH,
    KEYS,
    REPO,
    SEED,
    child_env,
    drive,
    harness,
    line_of,
    procs,
)

from lib import (agilebank, agilebank_admission_reference,  # noqa: E402
                 agilebank_reviews)

CELL = "paced-svcapply.agilebank4x111k-webhook"
JOIN_METRICS = {"join_lookup_ms_per_review.svcapply",
                "join_index_share.svcapply",
                "join_render_rows_per_cell.svcapply",
                "batch_render_ms_per_review.svcapply",
                "dispatch_ms_per_review.svcapply"}
# at this size every paced batch is priced to the interpreter tier, so
# tpu_dispatch_seconds may not grow in a window
READ_HERE = JOIN_METRICS - {"dispatch_ms_per_review.svcapply"}
PINNED = {"dispatch_enqueue_ms.paced", "dispatch_device_wait_ms.paced",
          "dispatch_fetch_ms.paced", "replica_gc_pause_ms_per_s.paced",
          "replica_gc_pause_mean_ms.paced", "wire_queued_ms.paced"}
# the nineteen per-layer metrics this cell reported when it was added
REPORTED = JOIN_METRICS | {
    "admit_tail_p95_ms", "admit_tail_p99_ms", "gen_late_p99_ms.paced",
    "door_overhead_ms.paced", "replica_wait_ms.paced",
    "wire_chunk_records_mean.paced", "batch_size_mean.paced",
    "batch_queue_ms.paced", "route_device_share.paced",
    "route_cal_rtt_ms.paced", "route_cal_device_cells_per_ms.paced",
    "compiles_in_window.paced", "device_idle_share.paced",
    "replica_gc_full_ms_per_s.paced"}


def tiny() -> dict:
    cfg = procs.read_json(os.path.join(
        BENCH, "configs", "agilebank4x111k-webhook.json"))
    cfg.update(pods=300, services=80, namespaces=10, violations_limit=5,
               paired_share=0.2, grouped_share=0.1, unlimited_share=0.2,
               prod_other_repo_share=0.3, unowned_share=0.3,
               # a batch the router sends to the "device" in the window
               # may meet a shape for the first time, and on a CPU busy
               # with the other test workers that compile takes longer
               # than the deployment's 3 s: not what is rehearsed here
               timeout_s=60)
    return cfg


def traffic() -> dict:
    tr = procs.read_json(os.path.join(BENCH, "traffic",
                                      "paced-svcapply.json"))
    tr.update(rate_per_s=120, connections=4, warm_reviews=150,
              warm_bursts=[1, 4, 12],
              shape_bursts=[1, 4, 12],
              min_reviews=50, move_onto_share=0.3, create_onto_share=0.3)
    return tr


def test_role_end_to_end_and_broken(child_env, tmp_path, capsys,
                                    monkeypatch):
    from roles import webhook, webhook_inventory as role

    # at this size a pass of the ladder may leave the burn alert quiet
    monkeypatch.setattr(role, "PIN_WAIT_S", 2.0)
    cfg, tr = tiny(), traffic()
    raw, ctx = drive("webhook_inventory", cfg, tr, tmp_path)
    line = line_of(raw, CELL, "end_to_end", capsys)
    assert set(line) == KEYS
    assert line["correct"] is True and line["failed"] == 0, (
        line["compared"], raw["notes"], raw["timings"])
    assert line["attempted"] >= 100
    assert {"setup_s", "admit_p50_ms"} <= set(line["metrics"])
    assert set(line["compared"]) == {
        "verdicts_wrong", "reviews_unanswered", "reviews_compared",
        "join_fallback_cells"}
    assert line["compared"]["join_fallback_cells"] == {"value": 0,
                                                       "limit": 0}
    assert line["device"]["platform"] == "cpu"  # run.py would refuse it
    layers = line_of(raw, CELL, "per_layer", capsys)["metrics"]
    assert READ_HERE <= set(layers)
    assert layers["join_index_share.svcapply"]["value"] == 100.0
    # a key group at this size: a handful of rows, never the 80 Services
    assert 1.0 <= layers["join_render_rows_per_cell.svcapply"]["value"] <= 8
    assert layers["join_lookup_ms_per_review.svcapply"]["value"] > 0
    for name in ("route_cal_rtt_ms.paced", "batch_size_mean.paced",
                 "compiles_in_window.paced", "gen_late_p99_ms.paced",
                 "admit_tail_p95_ms"):
        assert name in layers, name
    assert "device_idle_share.paced" not in layers  # nothing to read here
    assert raw["timings"]["probe_s"] > 0
    assert 1 <= raw["timings"]["shape_passes"] <= 5
    assert 0 <= raw["timings"]["brownout_level"] <= 3
    # the role sends nothing but the ladder, the probe and the generator
    assert not [f for f in os.listdir(ctx["work"]) if "settle" in f]

    # the same window's answers, broken where they are produced
    gen = procs.read_json(os.path.join(ctx["work"], "gen_result.json"))
    answers = webhook.read_answers(
        os.path.join(ctx["work"], "gen_result.json.answers"), gen["sent"])
    spec = procs.read_json(os.path.join(ctx["work"], "gen_spec.json"))
    bodies = agilebank_reviews.build_bodies(spec)
    _t, constraints, objects = agilebank.cluster(cfg, SEED)
    ref = agilebank_admission_reference.AdmissionReference(
        constraints, objects)

    def verdict(rows, answers, ref=ref, fallback=0.0):
        chk = role.compare_window(
            ref, bodies, rows, answers, gen["t_open"], gen["t_close"],
            cfg["timeout_s"], False)
        r = {"compared": role.compared_of(chk, 50, fallback),
             "attempted": chk["attempted"],
             "failed": chk["wrong"] + chk["unanswered"] + chk["late"],
             "device": raw["device"]}
        return harness.result_line(r, {}, False)

    assert verdict(gen["rows"], answers)["correct"] is True
    in_window = [k for k, r in enumerate(gen["rows"])
                 if gen["t_open"] <= r[1] < gen["t_close"]]
    kinds = {json.loads(bodies[k])["request"]["kind"]["kind"]
             for k in in_window}
    assert kinds == {"Service", "Pod", "Namespace"}
    collision = next(k for k in in_window
                     if b"same selector as service" in answers[k])
    # a deny turned into an allow
    flipped = list(answers)
    flipped[collision] = json.dumps({"response": {
        "uid": json.loads(answers[collision])["response"]["uid"],
        "allowed": True}}).encode()
    assert verdict(gen["rows"], flipped)["correct"] is False
    # the colliding Service's message names another Service
    misnamed = list(answers)
    misnamed[collision] = answers[collision].replace(
        b"service <svc-", b"service <svc-9", 1)
    assert verdict(gen["rows"], misnamed)["correct"] is False
    # half of the window sent and never answered
    rows = [list(r) for r in gen["rows"]]
    for k in in_window[::2]:
        rows[k][3] = 0.0
    line = verdict(rows, answers)
    assert line["correct"] is False
    assert (line["compared"]["reviews_unanswered"]["value"]
            >= len(in_window) // 2)
    # one referential cell that fell back to the full inventory
    line = verdict(gen["rows"], answers, fallback=1.0)
    assert line["correct"] is False
    # the inventory one write stale: the program held to a reference
    # that has already seen the colliding Service leave
    gone = json.loads(answers[collision])["response"]["status"]["message"]
    name = gone.split("service <", 1)[1].split(">", 1)[0]
    later = agilebank_admission_reference.AdmissionReference(
        constraints, [o for o in objects
                      if o["metadata"]["name"] != name])
    assert verdict(gen["rows"], answers, ref=later)["correct"] is False


def test_control_reads_sound_and_each_planted_fault_wrong():
    from roles import webhook_inventory as role

    for seed in (1, SEED):
        r = role.control(tiny(), traffic(), seed, 600)
        assert r["sound"] == {"reviews_compared": 600, "verdicts_wrong": 0}
        assert set(r["faults"]) == set(role.FAULTS)
        for fault, read in r["faults"].items():
            assert read["verdicts_wrong"] >= 10, fault
        assert r["limit"] == {"verdicts_wrong": 0}


@pytest.mark.parametrize("page", ["absent", "unmoved"])
def test_a_program_without_the_binding_ends_the_run_before_warm_up(page):
    """The capability exit, against a stub of the replica's surfaces: a
    /metrics page without admission_join_cells_total (the parent's), or
    with the counter where it was, is a reason to stop; one on which
    outcome="index" grew is not."""
    from roles import webhook_inventory as role

    sent = []
    pages = {
        "absent": [{"gatekeeper_request_count": 1.0}] * 2,
        "unmoved": [{'gatekeeper_admission_join_cells_total{outcome='
                     '"fallback",rendered="yes"}': 3.0}] * 2,
        "grown": [{}, {'gatekeeper_admission_join_cells_total{outcome='
                       '"index",rendered="no"}': 1.0}],
    }

    def check(which):
        scrapes = iter(pages[which])
        real, procs.scrape = procs.scrape, lambda port: next(scrapes)
        try:
            return role.serves_from_the_index(
                1, 2, post=lambda port, body: (
                    sent.append(json.loads(body)) or (200, b"{}")))
        finally:
            procs.scrape = real

    why = check(page)
    assert "cannot serve this configuration" in why
    assert ("absent" in why) == (page == "absent")
    assert check("grown") is None
    req = sent[0]["request"]
    assert req["kind"]["kind"] == "Service" and req["operation"] == "CREATE"
    # run() turns the reason into a non-zero exit of run.py, with no line
    failure = procs.BenchFailure(why, 4)
    assert failure.args[1] == 4


def test_the_traffic_file_holds_the_mix_and_the_generator_follows_it():
    tr = procs.read_json(os.path.join(BENCH, "traffic",
                                      "paced-svcapply.json"))
    assert tr["kind"] == "open" and tr["connections"] == 16
    assert tr["rate_per_s"] % 10 == 0 and tr["warm_reviews"] == 2500
    paced = procs.read_json(os.path.join(BENCH, "traffic",
                                         "paced-unique.json"))
    assert tr["warm_bursts"] == paced["warm_bursts"]
    # the role's shape ladder: one class per padded width of the three
    # kinds (every padded width keys an executable of its own), and a
    # burst for every row bucket the batcher can cut
    assert tr["shape_classes"] == [[1, 1], [2, 1], [4, 1],
                                   [1, 2], [2, 2], [4, 2]]
    assert {max(8, 1 << (n - 1).bit_length())
            for n in tr["shape_bursts"]} == {8, 16, 32, 64, 128, 256}
    # no load but the ladder and paced-unique's warm-up: the parameters
    # are the generator's, the mix's and the ladder's
    assert not [k for k in tr if k.startswith("settle")]
    assert tr["min_reviews"] == 1000
    assert (tr["service_share"], tr["pod_share"],
            tr["namespace_share"]) == (0.6, 0.3, 0.1)
    assert (tr["service_update_keep_share"],
            tr["service_update_move_share"],
            tr["service_create_share"]) == (0.5, 0.25, 0.25)
    assert (tr["move_onto_share"], tr["move_out_of_pair_share"],
            tr["create_onto_share"]) == (0.1, 0.1, 0.1)
    assert tr["namespace_unowned_share"] == 0.02
    cfg = tiny()
    a = agilebank_reviews.build_requests(cfg, tr, 5, 1000, "t")
    assert a == agilebank_reviews.build_requests(cfg, tr, 5, 1000, "t")
    assert a != agilebank_reviews.build_requests(cfg, tr, 6, 1000, "t")
    assert len({r["uid"] for r in a}) == 1000
    by = {}
    for r in a:
        by.setdefault((r["kind"]["kind"], r["operation"]), []).append(r)
    n = {k: len(v) for k, v in by.items()}
    assert n[("Service", "UPDATE")] == 450 and n[("Service", "CREATE")] == 150
    assert n[("Pod", "CREATE")] == 300
    assert n[("Namespace", "CREATE")] == n[("Namespace", "UPDATE")] == 50
    assert all("oldObject" in r for r in by[("Service", "UPDATE")])
    # no review of a Service without a selector
    assert all(r["object"]["spec"].get("selector")
               for k, v in by.items() if k[0] == "Service" for r in v)
    # the window's generator sends the mix; a pass of the shape ladder
    # sends its bursts alone, class by class, every request as wide as
    # its class by itself (however a burst is cut), from few objects
    base = dict(tr, config=cfg, seed=5, tag="t", bodies=1000)
    bodies = agilebank_reviews.build_bodies(
        {k: v for k, v in base.items() if not k.startswith("shape_")})
    assert [json.loads(b)["request"] for b in bodies] == a
    bursts = tr["shape_bursts"] * len(tr["shape_classes"])
    sent = [json.loads(b)["request"] for b in agilebank_reviews.build_bodies(
        dict(base, warm_bursts=bursts, bodies=sum(bursts)))]
    assert len(sent) == sum(bursts) == len({r["uid"] for r in sent})
    assert len({r["name"] for r in sent}) <= 512
    at = 0
    for k, n in enumerate(bursts):
        widths = {agilebank_reviews.shape_class(r)
                  for r in sent[at:at + n]}
        at += n
        assert widths == {tuple(
            tr["shape_classes"][k // len(tr["shape_bursts"])])}


def test_the_new_cell_in_the_manifest():
    manifest = procs.read_json(os.path.join(REPO, "BENCHMARK.json"))
    cell = {w["name"]: w for w in manifest["workloads"]}[CELL]
    assert cell == {
        "name": CELL, "config": "agilebank4x111k-webhook",
        "traffic": "paced-svcapply", "chips": 1, "why": cell["why"]}
    entry = {c["name"]: c for c in manifest["configs"]}[
        "agilebank4x111k-webhook"]
    cfg = procs.read_json(os.path.join(REPO, entry["file"]))
    assert entry["name"] == cfg["name"] == "agilebank4x111k-webhook"
    assert entry["source"] == cfg["source"] and len(cfg["source"]) <= 200
    assert entry["reduced"] == cfg["reduced"] == []
    assert cfg["role"] == "webhook_inventory"
    audit = procs.read_json(os.path.join(
        BENCH, "configs", "agilebank4x111k-audit.json"))
    for key in ("pods", "services", "namespaces", "paired_share",
                "grouped_share", "no_selector_share", "unlimited_share",
                "production_share", "prod_other_repo_share",
                "unowned_share"):
        assert cfg[key] == audit[key], key
    assert cfg["source"] != audit["source"]
    assert (cfg["timeout_s"], cfg["max_inflight"], cfg["fail_open"],
            cfg["replicas"]) == (3, 200, False, 1)
    assert tiny()["timeout_s"] == 60
    e2e = {m["name"] for m in harness.cell_metrics(manifest, CELL,
                                                   "end_to_end")}
    assert {"setup_s", "admit_p50_ms"} <= e2e
    layers = {m["name"]: m for m in harness.cell_metrics(
        manifest, CELL, "per_layer")}
    assert REPORTED <= set(layers)
    for name in JOIN_METRICS:
        assert CELL in layers[name]["workloads"]
        assert layers[name]["moves"] == "admit_p50_ms"
    # paced-unique still reports the six it reported before this cell
    per_layer = {m["name"]: m for m in manifest["per_layer"]}
    for name in PINNED:
        assert ("paced-unique.synth500x100k-webhook"
                in per_layer[name]["workloads"]), name
