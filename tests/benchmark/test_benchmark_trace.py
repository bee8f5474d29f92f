"""The reduction from a profiler trace to numbers, on the small
recorded trace kept beside it (benchmark/lib/testdata/small.xplane.txt
says what is in it), and the byte count behind the delta sweep's
roofline share against a hand sum."""

import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, os.path.join(REPO, "benchmark"))

from lib import peaks, trace  # noqa: E402

TESTDATA = os.path.join(REPO, "benchmark", "lib", "testdata")


@pytest.fixture(scope="module")
def reduced():
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(os.path.join(TESTDATA, "small.xplane.pb"))
    trace.MIN_HOST_SPAN_NS = 2000  # the hand-made trace is in microseconds
    try:
        return trace.reduce_xspace(pd, window_s=30000e-9)
    finally:
        trace.MIN_HOST_SPAN_NS = 100_000


def test_union_merges_overlaps():
    assert trace.union([(5, 6), (1, 3), (2, 4)]) == [[1, 4], [5, 6]]


def test_busy_union_and_idle_share(reduced):
    assert reduced["devices"] == 1
    assert reduced["busy_s"] == pytest.approx(4500e-9)
    assert reduced["window_s"] == pytest.approx(30000e-9)
    idle = 1 - reduced["busy_s"] / reduced["window_s"]
    assert idle == pytest.approx(0.85)


def test_per_op_sums_carry_their_module(reduced):
    ops = dict(reduced["device_ops"])
    assert ops["jit_delta/a"] == pytest.approx(3000e-9)
    assert ops["jit_delta/b"] == pytest.approx(1500e-9)
    assert ops["jit_fused/c"] == pytest.approx(500e-9)
    assert reduced["device_ops"][0][0] == "jit_delta/a"  # longest first


def test_gaps_are_named_by_what_the_host_was_doing(reduced):
    gaps = dict(reduced["idle_gaps"])
    assert gaps["host:render"] == pytest.approx(6000e-9)
    assert gaps["after_jit_delta"] == pytest.approx(9000e-9)
    # the window outside the first and last op: 30000 - (20500 - 1000)
    assert gaps["outside_first_and_last_op"] == pytest.approx(10500e-9)
    assert sum(gaps.values()) + reduced["busy_s"] == pytest.approx(30000e-9)


def test_a_trace_with_no_device_op_reads_nothing():
    from jax.profiler import ProfileData

    empty = ProfileData.from_serialized_xspace(
        ProfileData.text_proto_to_serialized_xspace(
            'planes { id: 1 name: "/host:CPU" }'))
    assert trace.reduce_xspace(empty, 1.0) is None


def test_delta_sweep_bytes_against_a_hand_sum():
    sizes = {"templates": 500, "packed_row_bytes": 256,
             "constraint_param_bytes": 64, "violations_limit": 20}
    # 200 rows in: 51,200; parameters: 32,000; mask cells out: 100,000;
    # counts + 20 kept indices per constraint, int32: 42,000
    assert peaks.delta_sweep_bytes(sizes, 200) == 51200 + 32000 + 100000 + 42000


def test_an_unknown_device_has_no_peaks():
    assert peaks.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks.peaks("cpu")
