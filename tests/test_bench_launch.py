"""bench.py's launch code (ISSUE 21): the default mode is a jax-free
parent running one child per config, a failed config fails the run, and
a device without published peaks is an error, not a default."""

import json

import pytest

import bench


def test_unknown_device_kind_is_an_error():
    with pytest.raises(RuntimeError, match="no published peaks"):
        bench.device_peaks("cpu")
    v5e = bench.device_peaks("TPU v5 lite")
    assert (v5e["hbm_gbps"], v5e["bf16_tflops"], v5e["hbm_gb"]) == (
        819.0, 197.0, 16.0)
    assert v5e["source"]


def test_all_mode_runs_children_and_fails_when_one_failed(
        monkeypatch, capsys):
    ran = []

    def child(name):
        ran.append(name)
        if name == "psp":
            return None  # that config's process exited non-zero
        out = {"value": 1.0, "curve_p50_ms": 1.0, "platform": "tpu",
               "device_kind": "TPU v5 lite", "device_count": 1}
        if name in bench._CPU_PINNED:
            out.update(platform="cpu", device_kind="cpu")
        return out

    def no_jax_here():
        raise AssertionError("the all-mode parent must stay off jax")

    monkeypatch.setenv("BENCH_CONFIG", "all")
    monkeypatch.setattr(bench, "_run_config_child", child)
    monkeypatch.setattr(bench, "device_stamp", lambda dev=None: no_jax_here())
    monkeypatch.setattr(bench, "run_config", lambda name: no_jax_here())
    with pytest.raises(SystemExit) as exc:
        bench.main()
    assert exc.value.code == 1
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out["failed_configs"] == ["psp"] and out["psp_audit_s"] is None
    assert ran == ["synthetic"] + [n for n, _k in bench._FOLDED]
    assert out["platform"] == "tpu"  # the synthetic child's own stamp
    assert out["devices"]["fleet"]["platform"] == "cpu"
    assert out["devices"]["latency"]["platform"] == "tpu"


def test_child_work_configs_take_their_stamp_from_the_child():
    # restart / warm_resume measure in chip-holding children: the parent
    # never initialises a backend, the stamp is TpuDriver.device_info()'s
    assert bench._CHILD_WORK == {"restart", "warm_resume"}
    assert bench.device_stamp({
        "platform": "tpu", "device_kind": "TPU v5 lite", "count": 1,
    }) == {"platform": "tpu", "device_kind": "TPU v5 lite",
           "device_count": 1}
