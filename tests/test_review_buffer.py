"""The review side of a dispatch as one buffer (ISSUE 37,
ops/reviewbuf.py): the layout round-trips every dtype the review side
holds bit for bit; `compute_masks` through the buffer equals the plain
fused function called on the unpacked `(rv, cs, cols, gp)`, array for
array, for both bundles, every review kind and row buckets 8 and 64; a
dispatch hands the call one host array; and after the async warm-up and
one pass of a shape ladder a first real review of each kind compiles
nothing."""

import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "benchmark"))

from lib import agilebank, agilebank_reviews, corpus  # noqa: E402

from gatekeeper_tpu.obs import compilestats  # noqa: E402
from gatekeeper_tpu.ops import aotcache, reviewbuf  # noqa: E402
from gatekeeper_tpu.ops.driver import TpuDriver  # noqa: E402

from tests.test_admission_join import _client, _route  # noqa: E402
from tests.test_tracing import _stage_rows  # noqa: E402

SEED = 37
UPLOADS = "tpu_dispatch_upload_arrays_total"
ROWS = 8

# ---- the layout ------------------------------------------------------------

LEAVES = {
    "int32": np.array([[-4, 2 ** 31 - 1], [-(2 ** 31), 7]] * 4, np.int32),
    "bool": np.array([True, False] * 4),
    "int8": np.array([[-128, 127, 0]] * ROWS, np.int8),
    # 0.1 and 16777217 are not float32 values: the host rounds them as
    # the jit boundary does; a negative, a subnormal-free tiny, a zero
    "float64": np.array([[0.1, -16777217.0, 1e-30, 0.0]] * ROWS),
    "float32": np.array([1.5, -2.25] * 4, np.float32),
    "pairs": np.arange(ROWS * 3 * 2, dtype=np.int32).reshape(ROWS, 3, 2) - 9,
}


@pytest.mark.parametrize("name", sorted(LEAVES))
def test_layout_round_trips_a_leaf_bit_for_bit(name):
    """Packed on the host and unpacked in the trace, a leaf is what the
    jit boundary would have made of it as an argument of its own."""
    x = LEAVES[name]
    tree = ({"valid": np.ones(ROWS, bool), "x": x}, {})
    layout = reviewbuf.Layout(tree, ROWS)
    leaves = jax.tree_util.tree_leaves(tree)
    buf, extras = layout.pack(leaves, ROWS)
    assert buf.dtype == np.int32 and buf.shape == (ROWS, layout.width)
    assert extras == ()
    assert layout.width == 1 + int(np.prod(x.shape[1:], dtype=int))
    got = jax.jit(layout.unpack)(buf, extras)[0]["x"]
    want = jnp.asarray(x)  # as an argument: float64 arrives as float32
    assert got.dtype == want.dtype and got.shape == want.shape
    np.testing.assert_array_equal(np.asarray(got).view(np.uint8),
                                  np.asarray(want).view(np.uint8))


def test_layout_keeps_a_leaf_without_the_row_axis_as_an_argument():
    """A leaf that does not lead with the row count, or whose dtype no
    column carries, stays an argument of its own, in the tree's order."""
    table = np.arange(5, dtype=np.int32)
    wide = np.arange(ROWS, dtype=np.int64)  # int32 at the jit boundary
    half = np.ones(ROWS, np.float16)
    tree = ({"valid": np.ones(ROWS, bool), "table": table},
            {"wide": wide, "half": half})
    layout = reviewbuf.Layout(tree, ROWS)
    leaves = jax.tree_util.tree_leaves(tree)
    buf, extras = layout.pack(leaves, ROWS)
    assert [e.dtype for e in extras] == [table.dtype, half.dtype]
    assert layout.width == 2  # valid, and wide as the int32 it becomes
    rv, cols = jax.jit(layout.unpack)(buf, extras)
    np.testing.assert_array_equal(rv["table"], table)
    np.testing.assert_array_equal(cols["wide"], wide)
    assert cols["wide"].dtype == jnp.int32
    np.testing.assert_array_equal(cols["half"], half)
    # the signature an executable's name is derived from is plain data,
    # the same text in every process
    def plain(x):
        return all(map(plain, x)) if isinstance(x, tuple) else \
            x is None or type(x) in (str, int)
    assert plain(layout.sig)
    assert repr(layout.sig) == repr(reviewbuf.Layout(tree, ROWS).sig)


def test_layouts_that_differ_in_one_width_do_not_share_a_signature():
    def of(w):
        return reviewbuf.Layout(
            ({"valid": np.ones(ROWS, bool)},
             {"c": np.zeros((ROWS, w), np.int32)}), ROWS)
    assert of(2).sig != of(4).sig and of(2).sig == of(2).sig


# ---- parity: the buffer against the plain fused function -------------------

AGILEBANK = {"pods": 300, "services": 80, "namespaces": 10,
             "unlimited_share": 0.05, "production_share": 0.1,
             "prod_other_repo_share": 0.03, "unowned_share": 0.02,
             "paired_share": 0.1, "grouped_share": 0.03,
             "no_selector_share": 0.05}
TRAFFIC = json.load(open(os.path.join(REPO, "benchmark", "traffic",
                                      "paced-svcapply.json")))


def _device_driver(**kw):
    d = TpuDriver(**kw)
    d.mesh_enabled = False
    d._mesh_cache = None
    _route(d, "device")
    return d


def _agilebank_requests(n=400):
    return agilebank_reviews.Mix(AGILEBANK, TRAFFIC, SEED, "t37").requests(n)


def _synth_requests(n=80):
    return [json.loads(corpus.admission_body(p, f"u{i}"))["request"]
            for i, p in enumerate(corpus.review_pods(n, SEED, 0.3, "t37"))]


class Bundles:
    def __init__(self):
        t, k, o = agilebank.cluster(AGILEBANK, SEED)
        self.agilebank = _client(_device_driver(), t, k, o)
        t, k, pods = corpus.cluster(
            {"templates": 12, "resources": 60, "violating_share": 0.3}, SEED)
        self.synth = _client(_device_driver(), t, k, pods)
        by_kind = {}
        for r in _agilebank_requests():
            by_kind.setdefault(r["kind"]["kind"], []).append(r)
        self.requests = {("agilebank", k): v for k, v in by_kind.items()}
        self.requests[("synth", "Pod")] = _synth_requests()

    def reviews(self, bundle, kind, n):
        d = getattr(self, bundle).driver
        reqs = self.requests[(bundle, kind)]
        assert len(reqs) >= n, (bundle, kind, len(reqs))
        return d, [d.target.handle_review(r)[1] for r in reqs[:n]]


@pytest.fixture(scope="module")
def bundles():
    return Bundles()


@pytest.mark.parametrize("rows,n", [(8, 5), (64, 40)])
@pytest.mark.parametrize("bundle,kind", [
    ("synth", "Pod"), ("agilebank", "Service"), ("agilebank", "Pod"),
    ("agilebank", "Namespace")])
def test_compute_masks_equals_the_plain_fused_function(bundles, bundle, kind,
                                                       rows, n):
    d, reviews = bundles.reviews(bundle, kind, n)
    before = dict(_stage_rows(UPLOADS))
    ordered, mask, autoreject = d.compute_masks(reviews)
    grew = {k: v - before.get(k, 0)
            for k, v in _stage_rows(UPLOADS).items()}
    # one host array handed to the call, where rv and cols held dozens
    assert grew.get(("review",)) == 1
    with d._lock:
        fn, ordered2, rp, cp, cols, gp, crow = d._device_inputs(reviews)
    assert len(rp.arrays["valid"]) == rows
    assert len(jax.tree_util.tree_leaves((rp.arrays, cols))) >= 33
    want_mask, want_rej = jax.jit(fn.__wrapped__)(rp.arrays, cp.arrays,
                                                  cols, gp)
    assert [(k, name) for k, name, _c in ordered] == \
        [(k, name) for k, name, _c in ordered2]
    np.testing.assert_array_equal(
        mask, np.asarray(want_mask)[crow][:, :rows])
    np.testing.assert_array_equal(
        autoreject, np.asarray(want_rej)[crow][:, :rows])
    assert mask.dtype == bool and mask.shape == (len(ordered), rows)
    if bundle == "synth":
        assert mask.any()  # the parity is not one of two empty masks


def test_one_executable_wrapper_a_layout_and_none_on_a_repeat(bundles):
    """A layout is fixed by the column specs and the padded widths: the
    same kind of review finds the wrapper it made, another width makes
    another, and a new fused function drops them all."""
    d, reviews = bundles.reviews("agilebank", "Service", 3)
    d.compute_masks(reviews)
    held = dict(d._fused_packed)
    d.compute_masks(reviews[:2])
    assert d._fused_packed == held
    assert d._fused_packed_src is d._fused
    widths = {layout.width for _pv, layout in held.values()}
    _d, pods = bundles.reviews("agilebank", "Pod", 3)
    d.compute_masks(pods)
    assert len(d._fused_packed) >= len(held)
    assert widths <= {layout.width
                      for _pv, layout in d._fused_packed.values()}


# ---- the warm-up compiles what serving calls --------------------------------


def _compiles():
    return sum(compilestats.get_stats().provenance_mix().values())


def test_after_warm_up_and_one_ladder_pass_a_first_review_compiles_nothing(
        tmp_path, monkeypatch):
    """compiles_in_window's tier-1 twin: asynccompile's probe and a shape
    ladder reach the executables through the packing serving goes
    through, so a first real review of each kind finds its executable."""
    monkeypatch.setattr(aotcache, "_dir", None)
    assert aotcache.enable(str(tmp_path))
    d = _device_driver(async_compile=True)
    try:
        t, k, o = agilebank.cluster(AGILEBANK, SEED)
        c = _client(d, t, k, o)
        assert d.wait_ready(timeout=600.0)
        warmed = _compiles()
        assert warmed >= 1  # the probe's executable, through aot_jit
        by_class = {}
        for r in _agilebank_requests():
            by_class.setdefault(
                (r["kind"]["kind"], agilebank_reviews.shape_class(r)),
                []).append(r)
        assert {k for k, _s in by_class} == {"Service", "Pod", "Namespace"}
        # the ladder: every class at the two small row buckets
        usable = {cls: rs for cls, rs in by_class.items() if len(rs) >= 16}
        assert {k for k, _s in usable} == {"Service", "Pod", "Namespace"}
        for rs in usable.values():
            c.review_batch(rs[:1])
            c.review_batch(rs[1:13])
        laddered = _compiles()
        assert laddered > warmed
        hashed = dict(_stage_rows("aot_executable_lookups_total"))
        for rs in usable.values():
            c.review_batch(rs[13:14])
            c.review_batch(rs[14:16] + rs[:9])
        assert _compiles() == laddered
        now = _stage_rows("aot_executable_lookups_total")
        assert now[("hashed",)] == hashed[("hashed",)]
        assert now[("memo",)] > hashed.get(("memo",), 0)
    finally:
        d._compiler.stop()
        monkeypatch.setattr(aotcache, "_dir", None)
