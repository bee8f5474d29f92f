"""Tier-1 twin of chip_smoke.py: the same script, tiny, on the CPU — all
three phases and every check but the platform one — plus the refusals
that make it a chip check: at the default size it must not continue
without a TPU, and it is nothing without the repository beside it."""

import json
import os
import shutil
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")


def _env(tmp_path):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    # one CPU device, as one chip: conftest's 8 virtual devices would put
    # every child on the mesh path
    env["XLA_FLAGS"] = " ".join(
        f for f in env.get("XLA_FLAGS", "").split()
        if "xla_force_host_platform_device_count" not in f)
    # the three phases share one compile cache; this run's is its own, so
    # the test never reads or fills <checkout>/.xla-cache
    env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "xla-cache")
    return env


def _run(args, tmp_path, cwd=REPO, script=SMOKE, timeout=600):
    return subprocess.run(
        [sys.executable, script, *args], cwd=cwd, env=_env(tmp_path),
        capture_output=True, text=True, timeout=timeout,
    )


def test_tiny_smoke_runs_every_phase_on_the_cpu(tmp_path):
    out_dir = tmp_path / "out"
    proc = _run([
        "--platform", "cpu", "--templates", "6", "--resources", "96",
        "--churn", "5", "--subset", "24", "--reviews", "2", "--burst", "8",
        "--phase-timeout", "240", "--out", str(out_dir),
    ], tmp_path)
    assert proc.returncode == 0, proc.stderr[-4000:]
    lines = proc.stdout.strip().splitlines()
    # the result line: these keys and no others, the last thing on stdout
    assert json.loads(lines[-1]) == {"ok": True, "device": {
        "platform": "cpu", "kind": "cpu", "count": 1}}
    assert proc.stdout.endswith(lines[-1] + "\n")
    # the door's warnings went to the output directory, not to stderr
    assert "slow trace" not in proc.stderr
    last = json.loads(lines[-2])  # the summary, the line before
    assert last["ok"] is True and last["claim"] is None
    assert last["device"] == json.loads(lines[-1])["device"]
    assert last["mesh_width"] == 1 and last["failed_checks"] == []
    p, a, b = (last["phases"][k] for k in "PAB")
    # P: a device full sweep, then a device delta sweep of exactly the churn
    assert p["sweep_full"]["rows"] == 96 and "device_ms" in p["sweep_full"]
    assert p["sweep_delta"]["delta_rows"] == 5
    assert p["mask_parity"]["mismatches"] == 0
    assert all(r["tier"] == "device" for r in p["routes"])
    # A: the deployed pod swept the churn by delta, and reused P's compiles
    assert sum(s["delta_rows"] for s in a["sweeps_delta"]) == 5
    assert a["cache"]["aot_loads"] + a["cache"]["xla_hits"] > 0
    # B: restored, served through the door, a device-tier batch, nothing cold
    assert b["restore_outcome"] == "restored"
    assert b["device_decisions"] and b["cold_tags"] == []
    assert b["device_tier"] in ("router", "forced (GK_DEVICE_MIN_CELLS=0)")
    # the bulky state is gone from the output directory; the record stays
    assert not (out_dir / "snapshot").exists()
    assert json.loads((out_dir / "summary.json").read_text())["ok"] is True


def test_default_size_refuses_to_run_without_a_tpu(tmp_path):
    """`python chip_smoke.py` where jax finds only the CPU: non-zero, a
    reason on stderr, and NO result line — it never falls back."""
    proc = _run(["--out", str(tmp_path / "out")], tmp_path, timeout=300)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "refusing to run" in proc.stderr and "'cpu'" in proc.stderr
    # and the CPU cannot be asked for at that size
    proc = _run(["--platform", "cpu", "--out", str(tmp_path / "out")],
                tmp_path, timeout=60)
    assert proc.returncode == 2 and proc.stdout.strip() == ""
    assert "on a TPU or not at all" in proc.stderr


def test_alone_in_a_directory_it_fails(tmp_path):
    lone = tmp_path / "lone"
    lone.mkdir()
    shutil.copy(SMOKE, lone / "chip_smoke.py")
    proc = _run([], tmp_path, cwd=str(lone),
                script=str(lone / "chip_smoke.py"), timeout=60)
    assert proc.returncode != 0 and proc.stdout.strip() == ""
    assert "gatekeeper_tpu" in proc.stderr
