"""The benchmark rehearsed on the CPU at a tiny size: each role's
set-up, window and comparison through the line printer (run.py itself
has no way around its no-chip error, so the roles are called with the
platform they find here); the comparison's control, which has to come
out as not correct; and the timed path broken underneath, once per
fault a cell can have."""

import json
import os
import sys
import time

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(REPO, "benchmark")
sys.path.insert(0, BENCH)

import control  # noqa: E402
import run as harness  # noqa: E402
from lib import corpus, loadgen, procs, reference  # noqa: E402

SEED = 2_900_000_017  # more than 32 signed bits hold
KEYS = {"correct", "attempted", "failed", "metrics", "device", "compared"}


def tiny(config_name: str) -> dict:
    cfg = procs.read_json(os.path.join(BENCH, "configs", config_name + ".json"))
    cfg.update(templates=12, resources=300, violating_share=0.2,
               violations_limit=5)
    return cfg


@pytest.fixture()
def child_env(tmp_path, monkeypatch):
    """One CPU device, as one chip (conftest's eight would put every
    child on the mesh path), and a compile cache of this test's own."""
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    monkeypatch.setenv("XLA_FLAGS", " ".join(
        f for f in os.environ.get("XLA_FLAGS", "").split()
        if "xla_force_host_platform_device_count" not in f))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "xla"))


def drive(role: str, cfg: dict, traffic: dict, tmp_path, trace=False):
    work = str(tmp_path / "work")
    os.makedirs(work)
    pr = procs.Procs()
    ctx = {"config": cfg, "traffic": traffic, "seed": SEED, "seconds": 2.0,
           "trace": trace, "platform": "cpu",
           "t_start": time.time(), "work": work, "procs": pr,
           "timeout_s": 300.0}
    try:
        return harness.load_module("roles", role).run(ctx), ctx
    finally:
        pr.stop_all()


def line_of(raw: dict, cell: str, group: str, capsys) -> dict:
    """Through the metric readers and the line printer; the last line of
    stdout, parsed."""
    manifest = procs.read_json(os.path.join(REPO, "BENCHMARK.json"))
    metrics = harness.read_metrics(
        raw, harness.cell_metrics(manifest, cell, group))
    harness.emit(harness.result_line(raw, metrics, group == "per_layer"))
    out = capsys.readouterr()
    line = json.loads(out.out.strip().splitlines()[-1])
    assert list(line)[-1] == "compared"
    for name in line["compared"]:
        assert f"compared {name} = " in out.err
    return line


# ---------------------------------------------------------------------------
# the audit role
# ---------------------------------------------------------------------------

AUDIT_CELL = "churn200.synth500x100k-audit"
AUDIT_TRAFFIC = {"rows_per_step": 20, "max_steps_per_s": 20}


def test_audit_cell_end_to_end(child_env, tmp_path, capsys):
    raw, _ctx = drive("audit", tiny("synth500x100k-audit"), AUDIT_TRAFFIC,
                      tmp_path)
    line = line_of(raw, AUDIT_CELL, "end_to_end", capsys)
    assert set(line) == KEYS
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 2
    assert set(line["metrics"]) == {"setup_s", "audit_sweep_s"}
    assert all(m["value"] > 0 for m in line["metrics"].values())
    assert line["device"]["platform"] == "cpu"  # run.py would refuse it
    layers = line_of(raw, AUDIT_CELL, "per_layer", capsys)["metrics"]
    assert layers["compiles_in_window.audit"]["value"] == 0
    assert {"sweep_pack_ms", "sweep_dispatch_ms", "sweep_render_ms",
            "sweep_ingest_ms"} <= set(layers)
    # nothing to read on a CPU: no device share is ever printed as 0
    assert "delta_sweep_hbm_roofline" not in layers
    assert "device_idle_share.audit" not in layers
    # every sweep of the window took the delta path (a replacement equal
    # to the Pod it replaces is no change: 19 now and then at this size)
    assert all(15 <= s.get("delta_rows", 0) <= 20
               for s in raw["window"]["sweep_stats"])


@pytest.fixture(scope="module")
def audit_world():
    """A tiny cluster, its churn steps and the reference's sound
    answers to them (the reference in the program's place)."""
    from roles import audit

    cfg = tiny("synth500x100k-audit")
    _t, constraints, pods = corpus.cluster(cfg, SEED)
    steps = corpus.churn_steps(cfg, AUDIT_TRAFFIC, SEED, 6)
    ref = reference.AuditReference(
        reference.Policies(constraints, corpus.FAMILIES), pods)

    sound = []
    for step in steps:
        for _i, pod in step:
            ref.put(pod)
        sound.append(control.reference_answer(ref, cfg["violations_limit"]))
    return audit, cfg, constraints, pods, steps, sound


def audit_verdict(world, answers) -> dict:
    audit, cfg, constraints, pods, steps, _sound = world
    chk = audit.compare_sweeps(constraints, pods, steps, answers,
                               cfg["violations_limit"])
    raw = {"compared": audit.compared_of(chk, len(steps)),
           "attempted": len(steps), "failed": chk["sweeps_wrong"],
           "device": {"platform": "cpu", "kind": "cpu", "count": 1,
                      "memory_peak_bytes": 0}}
    return harness.result_line(raw, {}, False)


def test_audit_reference_in_the_programs_place_is_correct(audit_world):
    line = audit_verdict(audit_world, audit_world[5])
    assert line["correct"] is True
    assert line["compared"]["sweeps_wrong"]["value"] == 0


def test_audit_control_a_stale_answer_is_not_correct(audit_world):
    """The control: the reference one interval behind (the guarantee
    'no stale answer' broken) in the program's place."""
    sound = audit_world[5]
    line = audit_verdict(audit_world, [sound[0]] + sound[:-1])
    assert line["correct"] is False
    assert line["compared"]["sweeps_wrong"]["value"] >= len(sound) - 2


@pytest.mark.parametrize("fault", [
    "state_unchanged", "half_the_rows_left_out", "answer_altered",
    "total_altered", "a_violation_dropped"])
def test_audit_broken_timed_path_is_not_correct(audit_world, fault):
    audit, cfg, constraints, pods, steps, sound = audit_world
    answers = [dict(a, kept=list(a["kept"]), totals=dict(a["totals"]))
               for a in sound]
    if fault == "state_unchanged":
        # a sweep that returns its state as it was: every answer the first
        answers = [answers[0]] * len(answers)
    elif fault == "half_the_rows_left_out":
        # the reference fed half of each step's rows, in the program's place
        ref = reference.AuditReference(
            reference.Policies(constraints, corpus.FAMILIES), pods)
        answers = []
        for step in steps:
            for _i, pod in step[::2]:
                ref.put(pod)
            answers.append(control.reference_answer(
                ref, cfg["violations_limit"]))
    elif fault == "answer_altered":
        k = answers[-1]["kept"]
        k[0] = k[0][:4] + (k[0][4] + "!",)
    elif fault == "total_altered":
        key = next(k for k, v in answers[2]["totals"].items() if v[0])
        n, how = answers[2]["totals"][key]
        answers[2]["totals"][key] = (n + 1, how)
    elif fault == "a_violation_dropped":
        key = next(k for k, v in answers[1]["totals"].items() if v[0])
        answers[1]["kept"] = [r for r in answers[1]["kept"]
                              if (r[0], r[1]) != key]
    line = audit_verdict(audit_world, answers)
    assert line["correct"] is False
    assert line["failed"] >= 1


def test_audit_window_drives_a_broken_sweep_to_not_correct(audit_world):
    """The rest of a run over a timed path broken underneath: the
    window loop itself, with a sweep that skips the ingest."""
    audit, cfg, constraints, pods, steps, sound = audit_world

    class Driver:
        last_sweep_stats = {}

    calls = []

    def broken(step):  # leaves its state unchanged: answers the first
        calls.append(step)
        return sound[0]

    w = audit.window(None, Driver(), steps, 0.0, cfg["violations_limit"],
                     sweep=broken)
    assert w["sweeps"] == 1 and len(calls) == 1  # closed by the clock
    w = audit.window(None, Driver(), steps, 1e9, cfg["violations_limit"],
                     sweep=broken)
    assert w["sweeps"] == len(steps) and len(w["answers"]) == len(steps)
    line = audit_verdict(audit_world, w["answers"])
    assert line["correct"] is False
    # with a sample kept, the window's last sweep is always among it
    w = audit.window(None, Driver(), steps, 1e9, cfg["violations_limit"],
                     sweep=broken, keep={1})
    assert sorted(w["answers"]) == [1, len(steps) - 1]
    assert audit.sampled(SEED, 60) == audit.sampled(SEED, 60)
    assert len(audit.sampled(SEED, 60)) == 6


# ---------------------------------------------------------------------------
# the webhook role
# ---------------------------------------------------------------------------

SAT_CELL = "sat-unique.synth500x100k-webhook"


def test_webhook_cell_end_to_end_and_broken(child_env, tmp_path, capsys):
    from roles import webhook

    cfg = tiny("synth500x100k-webhook")
    traffic = procs.read_json(os.path.join(BENCH, "traffic",
                                           "sat-unique.json"))
    traffic.update(connections=2, inflight_per_connection=8,
                   warm_reviews=200, bodies_per_s=1500, min_reviews=50)
    raw, ctx = drive("webhook", cfg, traffic, tmp_path)
    line = line_of(raw, SAT_CELL, "end_to_end", capsys)
    assert set(line) == KEYS
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 50
    assert set(line["metrics"]) == {"setup_s", "admit_reviews_per_s"}
    layers = line_of(raw, SAT_CELL, "per_layer", capsys)["metrics"]
    for name in ("route_cal_rtt_ms.sat", "route_device_share.sat",
                 "compiles_in_window.sat", "batch_size_mean.sat",
                 "dispatch_ms_per_review.sat"):
        assert name in layers, name
    assert "device_idle_share.sat" not in layers
    # the first warm-up review went out only after a calibration was read
    assert raw["calibration"]["rtt_ms"] > 0

    # the same window's answers, broken where they are produced
    gen = procs.read_json(os.path.join(ctx["work"], "gen_result.json"))
    answers = webhook.read_answers(
        os.path.join(ctx["work"], "gen_result.json.answers"), gen["sent"])
    spec = procs.read_json(os.path.join(ctx["work"], "gen_spec.json"))
    bodies = loadgen.build_bodies(spec)
    _t, constraints = corpus.make_templates(
        cfg["templates"], corpus.seed32(SEED, 0))

    def verdict(rows, answers, constraints=constraints):
        chk = webhook.compare_window(
            constraints, bodies, rows, answers, gen["t_open"],
            gen["t_close"], cfg["timeout_s"], True)
        r = {"compared": webhook.compared_of(chk, 50),
             "attempted": chk["attempted"],
             "failed": chk["wrong"] + chk["unanswered"] + chk["late"],
             "device": raw["device"]}
        return harness.result_line(r, {}, False)

    assert verdict(gen["rows"], answers)["correct"] is True
    in_window = [k for k, r in enumerate(gen["rows"])
                 if gen["t_open"] <= r[3] <= gen["t_close"]]
    denied = next(k for k in in_window if b'"allowed": false' in answers[k]
                  or b'"allowed":false' in answers[k])
    # an answer altered: a deny turned into an allow
    flipped = list(answers)
    flipped[denied] = json.dumps({"response": {
        "uid": json.loads(answers[denied])["response"]["uid"],
        "allowed": True}}).encode()
    assert verdict(gen["rows"], flipped)["correct"] is False
    # one byte of a message altered
    garbled = list(answers)
    garbled[denied] = answers[denied].replace(b"denied by", b"denied bY", 1)
    assert verdict(gen["rows"], garbled)["correct"] is False
    # half of the window left out: sent, never answered
    rows = [list(r) for r in gen["rows"]]
    for k in in_window[::2]:
        rows[k][3] = 0.0
    line = verdict(rows, answers)
    assert line["correct"] is False
    assert line["compared"]["reviews_unanswered"]["value"] >= len(in_window) // 2
    # an answer that comes late is late, not wrong: it fails, and
    # `correct` stands
    rows = [list(r) for r in gen["rows"]]
    rows[denied][1] -= 10.0  # due ten seconds before it was answered
    rows[denied][2] -= 10.0
    line = verdict(rows, answers)
    assert line["correct"] is True and line["failed"] == 1
    # the control: the reference with a stale policy set (the newest
    # constraint not synced yet) in the program's place — here read from
    # the other side: the program held to a reference that has one more
    assert verdict(gen["rows"], answers,
                   constraints[:-1] + [dict(
                       constraints[-1], metadata={"name": "c-not-synced"})]
                   )["correct"] is False


def test_generator_offers_every_seed_the_same_work_in_another_order():
    spec = {"rate_per_s": 500.0, "seed": 1}
    a = loadgen.arrivals(spec, 400)
    b = loadgen.arrivals(dict(spec, seed=2), 400)
    gaps = lambda xs: sorted(round(y - x, 9) for x, y in zip([0.0] + xs, xs))
    assert a != b and gaps(a) == gaps(b)
    s = {"bodies": 300, "seed": 5, "tag": "t", "violating_share": 0.1}
    bodies = loadgen.build_bodies(s)
    assert len(set(bodies)) == 300
    pol = reference.Policies(corpus.make_templates(12, 3)[1], corpus.FAMILIES)
    denied = sum(not pol.verdict(json.loads(b)["request"]["object"])[0]
                 for b in bodies)
    assert denied == 30
    assert loadgen.build_bodies(s) == bodies
