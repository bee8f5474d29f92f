"""The agilebank deployment's role (ISSUE 29) rehearsed on the CPU at a
tiny size: roles/audit_inventory.py end to end through the line printer,
its stale-reference control reading not correct, the reference in the
program's place (and in the program's form) reading correct, planted
faults reading not correct, the new metrics resolving from recorded
readings (and left out where the program has no such key), and the two
new cells' manifest entries."""

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

from lib import agilebank, agilebank_reference  # noqa: E402

CELL = "svc-keychurn50.agilebank4x111k-audit"
CHURN2000 = "churn2000.synth500x100k-audit"
TRAFFIC = {"services_per_step": 8, "pods_per_step": 12,
           "max_steps_per_s": 20}
UNIQUE = ("K8sUniqueServiceSelector", "unique-service-selector")
JOIN_METRICS = {"sweep_join_affected_ms", "sweep_join_commit_ms",
                "sweep_join_affected_rows", "sweep_join_plans",
                "sweep_full_share", "sweep_render_interp_cells"}


def tiny() -> dict:
    cfg = procs.read_json(os.path.join(
        BENCH, "configs", "agilebank4x111k-audit.json"))
    cfg.update(pods=300, services=60, namespaces=10, violations_limit=5,
               paired_share=0.2, grouped_share=0.1, unlimited_share=0.2,
               prod_other_repo_share=0.3, unowned_share=0.3)
    return cfg


def test_role_end_to_end(child_env, tmp_path, capsys):
    raw, _ctx = drive("audit_inventory", tiny(), TRAFFIC, tmp_path)
    line = line_of(raw, CELL, "end_to_end", capsys)
    assert set(line) == KEYS
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 2
    assert set(line["metrics"]) == {"setup_s", "audit_sweep_s"}
    assert line["device"]["platform"] == "cpu"  # run.py would refuse it
    layers = line_of(raw, CELL, "per_layer", capsys)["metrics"]
    assert JOIN_METRICS <= set(layers)
    # the unique-selector policy is served by a join plan in every sweep,
    # through the delta path
    stats = raw["window"]["sweep_stats"]
    assert all(s.get("join_plans") == 1.0 for s in stats)
    assert layers["sweep_join_plans"]["value"] == 1.0
    assert layers["sweep_full_share"]["value"] == 0.0
    assert all(s["full"] == 0.0 and s["delta_rows"] >= 16 for s in stats)
    assert layers["compiles_in_window.audit"]["value"] == 0
    # nothing to read on a CPU: no device share is ever printed as 0
    assert "device_idle_share.audit" not in layers


@pytest.fixture(scope="module")
def world():
    from roles import audit_inventory as role

    cfg = tiny()
    _t, constraints, objects, steps = agilebank.deployment(
        cfg, TRAFFIC, SEED, 6)
    ref = agilebank_reference.AuditReference(constraints, objects)
    sound = []
    for step in steps:
        for _i, obj in step:
            ref.put(obj)
        sound.append(ref.answer(cfg["violations_limit"]))
    return role, cfg, constraints, objects, steps, sound


def verdict(world, answers) -> dict:
    role, cfg, constraints, objects, steps, _sound = world
    from roles import audit

    chk = role.compare_sweeps(constraints, objects, steps, answers,
                              cfg["violations_limit"])
    raw = {"compared": audit.compared_of(chk, len(steps)),
           "attempted": len(steps), "failed": chk["sweeps_wrong"],
           "device": {"platform": "cpu", "kind": "cpu", "count": 1,
                      "memory_peak_bytes": 0}}
    return harness.result_line(raw, {}, False)


def test_reference_in_the_programs_place_is_correct(world):
    line = verdict(world, world[5])
    assert line["correct"] is True
    assert line["compared"]["sweeps_wrong"]["value"] == 0


def test_control_a_stale_answer_is_not_correct(world):
    sound = world[5]
    line = verdict(world, [sound[0]] + sound[:-1])
    assert line["correct"] is False
    assert line["compared"]["sweeps_wrong"]["value"] >= len(sound) - 2


def test_the_references_answer_has_the_programs_form(world):
    """Past the cap: the violating objects as a "resources" total and
    the first `cap` violations in arrival order, as the program walks
    its rows; the Services without a selector come last, so what is
    kept is the re-pointed Services'."""
    _role, cfg, constraints, objects, steps, sound = world
    cap = cfg["violations_limit"]
    ref = agilebank_reference.AuditReference(constraints, objects)
    ci = ref.keys.index(UNIQUE)
    n_viol, n_res = ref.selector_counts(constraints[ci])
    assert n_viol > cap
    first = ref.answer(cap)
    assert first["totals"][UNIQUE] == (n_res, "resources")
    kept = [r for r in first["kept"] if r[:2] == UNIQUE]
    assert len(kept) == cap
    rows = [ref.rank[("Service", r[2:4])] for r in kept]
    assert rows == sorted(rows)
    flagged = sorted(ref.rank[("Service", k)]
                     for k in ref.violations_of(ci))
    assert set(rows) <= set(flagged[:cap])
    assert all(ref.flat[r[2:4]] != "" for r in kept)
    assert first == dict(first, **ref.answer(cap))        # no state kept
    assert ref.answer(0)["totals"][UNIQUE] == (n_viol, "exact")
    assert any(how == "resources" for a in sound
               for _n, how in a["totals"].values())


def test_control_a_stale_selector_answer_alone_is_not_correct(world):
    """Every other constraint answered soundly, the unique-selector
    constraint one interval late: the comparison sees the join's
    answers, not only the row-local ones beside them."""
    role, sound = world[0], world[5]
    stale = [role.selector_late(late, now)
             for late, now in zip([sound[0]] + sound[:-1], sound)]
    assert all(s["totals"][k] == n["totals"][k]
               for s, n in zip(stale, sound) for k in n["totals"]
               if k != UNIQUE)
    line = verdict(world, stale)
    assert line["correct"] is False
    assert line["compared"]["sweeps_wrong"]["value"] >= 1


def test_control_entry_point_at_a_small_size(world):
    role, cfg = world[0], world[1]
    r = role.control(cfg, TRAFFIC, SEED, 24)
    assert r["sound"]["sweeps_wrong"] == 0
    assert r["control"]["sweeps_wrong"] > 0
    assert r["control_selector_alone"]["sweeps_wrong"] > 0
    assert set(r["faults"]) == set(role.FAULTS)
    assert all(f["sweeps_wrong"] == 24 for f in r["faults"].values())
    assert r["forms"]["unique-service-selector"] == "resources"


@pytest.mark.parametrize("fault", [
    "other_service_misnamed", "collision_dropped", "total_altered"])
def test_broken_selector_answers_are_not_correct(world, fault):
    role, sound = world[0], world[5]
    assert sound[-1]["totals"][UNIQUE][1] == "resources"
    answers = sound[:-1] + [role.planted(sound[-1], fault)]
    assert verdict(world, answers)["correct"] is False


def test_a_program_without_a_join_plan_cannot_run_the_cell(world):
    """What makes the parent commit fail cleanly: no join plan for the
    bundle's referential policy, so nothing is ingested."""
    role, _cfg, constraints = world[:3]
    templates, _c = agilebank.make_templates()
    local = [c for c in constraints if c["kind"] != UNIQUE[0]]
    assert role.load_cluster(
        [t for t in templates
         if t["metadata"]["name"] != UNIQUE[0].lower()], local, []) is None
    assert role.load_cluster(templates, constraints, []) is not None


@pytest.mark.parametrize("v0, growth, crossings", [
    (10779, 40, 1),      # the cell's own: one doubling in reach
    (16500, 36, 0),      # the next power of two is out of reach
    (20000, 0, 0),       # a vocabulary that stands still
    (600, 8, 3),
])
def test_set_up_churns_past_the_vocabularys_next_doubling(
        v0, growth, crossings):
    """settle_vocabulary on a made-up program: it stops once no power of
    two lies within the window's reach, and not before a full sweep has
    run at the width it stops at."""
    from roles import audit_inventory as role

    reach, seen = 368, []

    def sweep_steps(some):
        out = []
        for _ in some:
            seen.append(v0 + growth * (len(seen) + 1))
            out.append({"full": float(len(seen) % 43 == 0)})
        return out

    def size():
        return seen[-1] if seen else v0

    def width(v):
        return 1 << (v - 1).bit_length()

    used = role.settle_vocabulary(sweep_steps, size, list(range(640)),
                                  reach)
    assert used == len(seen) >= role.SETTLE_BATCH
    assert width(size() + growth * reach) == width(size())
    widths = sorted({width(v) for v in [v0] + seen})
    assert len(widths) == 1 + crossings
    at_last = [i for i, v in enumerate(seen) if width(v) == widths[-1]]
    if crossings:
        assert any((i + 1) % 43 == 0 for i in at_last)
        assert used < at_last[0] + 43 + 2 * role.SETTLE_BATCH


def _metrics(cell, names):
    m = procs.read_json(os.path.join(REPO, "BENCHMARK.json"))
    return [x for x in harness.cell_metrics(m, cell, "per_layer")
            if x["name"] in names]


def test_join_metrics_resolve_from_recorded_sweep_stats():
    stats = [{"full": 0.0, "join_plans": 1.0, "join_affected_rows": 14.0,
              "join_affected_ms": 1.5, "join_commit_ms": 4.0,
              "render_interp_cells": 30.0},
             {"full": 1.0, "join_plans": 1.0, "join_affected_ms": 0.0,
              "join_commit_ms": 200.0, "render_interp_cells": 400.0}]
    got = harness.read_metrics(
        {"window": {"sweep_stats": stats}}, _metrics(CELL, JOIN_METRICS))
    assert got["sweep_full_share"] == {"value": 50.0, "unit": "%"}
    assert got["sweep_join_plans"]["value"] == 1.0
    assert got["sweep_join_commit_ms"]["value"] == pytest.approx(102.0)
    assert got["sweep_join_affected_rows"]["value"] == 14.0
    assert got["sweep_render_interp_cells"]["value"] == 215.0
    # the parent commit: no such keys, so no such metrics
    assert harness.read_metrics(
        {"window": {"sweep_stats": [{"pack_ms": 4.0, "render_ms": 70.0}]}},
        _metrics(CELL, JOIN_METRICS - {"sweep_render_interp_cells"})) == {}


def test_new_cells_in_the_manifest():
    m = procs.read_json(os.path.join(REPO, "BENCHMARK.json"))
    cells = {w["name"]: w for w in m["workloads"]}
    assert cells[CELL]["chips"] == 1 and cells[CHURN2000]["chips"] == 1
    assert cells[CHURN2000]["config"] == "synth500x100k-audit"
    tr = procs.read_json(os.path.join(BENCH, "traffic", "churn2000.json"))
    base = procs.read_json(os.path.join(BENCH, "traffic", "churn200.json"))
    assert {**base, "name": "churn2000", "rows_per_step": 2000,
            "max_steps_per_s": 4} == tr
    for cell in (CELL, CHURN2000):
        e2e = {x["name"] for x in harness.cell_metrics(m, cell, "end_to_end")}
        assert e2e == {"setup_s", "audit_sweep_s"}
        layers = {x["name"] for x in
                  harness.cell_metrics(m, cell, "per_layer")}
        assert {"sweep_pack_ms", "sweep_render_ms", "sweep_full_share",
                "device_idle_share.audit"} <= layers
        assert "delta_sweep_hbm_roofline" not in layers
    cfg = procs.read_json(os.path.join(
        BENCH, "configs", "agilebank4x111k-audit.json"))
    assert (cfg["pods"], cfg["services"], cfg["namespaces"]) == (
        100000, 10000, 1000)
    assert cfg["reduced"] == [] and len(cfg["guarantees"]) == 4


def test_the_reference_and_generator_import_nothing_of_the_program():
    for name in ("agilebank.py", "agilebank_reference.py"):
        src = open(os.path.join(BENCH, "lib", name)).read()
        assert "import gatekeeper_tpu" not in src
        assert "from gatekeeper_tpu" not in src
