"""A review dispatch's buffer written straight from the reviews
(ops/reviewbuf.py Writer, the native `review_scratch` / `review_write`):
byte for byte the buffer the per-field arrays make through `Layout.pack`,
with the interner left holding the same ids in the same order, for
hand-made column specs of every kind over awkward inputs and for both
bundles' own specs; `compute_masks` through it equals the plain fused
function; `tpu_review_pack_total{path}` says which path packed a
dispatch, and both paths reach the same executable and layout."""

import copy
import os
import random
import sys

import jax
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "benchmark"))

import gatekeeper_tpu.native as native_mod  # noqa: E402
from gatekeeper_tpu.ops import reviewbuf  # noqa: E402
from gatekeeper_tpu.ops.columns import (  # noqa: E402
    ColumnSpec,
    extract_columns,
    parse_path,
)
from gatekeeper_tpu.ops.interning import Interner  # noqa: E402
from gatekeeper_tpu.ops.pack import pack_reviews  # noqa: E402

from tests.test_native import cached_namespace, rand_review  # noqa: E402
from tests.test_review_buffer import Bundles  # noqa: E402
from tests.test_tracing import _stage_rows  # noqa: E402

native = native_mod.load()
pytestmark = pytest.mark.skipif(native is None, reason="native unavailable")

PACKS = "tpu_review_pack_total"


def _copy(interner):
    c = Interner()
    c._ids = dict(interner._ids)
    c._strings = list(interner._strings)
    return c


def _both_paths(reviews, specs, interner, cached_ns):
    """-> (arrays path's buffer, direct buffer, arrays interner, direct
    interner, arrays widths, direct widths); each path on its own copy of
    `interner`, the plan derived from the arrays' layout as the driver
    derives it."""
    ia, ib = _copy(interner), _copy(interner)
    rp = pack_reviews(reviews, ia, cached_ns)
    rows = len(rp.arrays["valid"])
    cols = extract_columns(reviews, specs, ia, rows)
    tree = (rp.arrays, cols)
    leaves = jax.tree_util.tree_leaves(tree)
    layout = reviewbuf.Layout(tree, rows)
    want, extras = layout.pack(leaves, rows)
    assert extras == ()
    writer = reviewbuf.Writer(native, specs)
    plan = writer.plan(layout, leaves, rp.arrays, cols)
    assert plan is not None and plan.shape == (len(leaves), 3)
    scratch, widths = writer.extract(reviews, ib, cached_ns, rows)
    got = writer.write(scratch, plan, rows, layout.width)
    return (want, got, ia, ib, reviewbuf.widths_of(rp.arrays, cols, specs),
            widths)


def _assert_same(want, got, ia, ib, wwidths, gwidths):
    assert gwidths == wwidths
    assert got.dtype == np.int32 and got.shape == want.shape
    np.testing.assert_array_equal(got.view(np.uint8), want.view(np.uint8))
    assert ib._strings == ia._strings and ib._ids == ia._ids


# ---- hand-made specs over awkward inputs ------------------------------------

SPECS = [
    ColumnSpec("scalar", (), parse_path("object.metadata.name")),
    ColumnSpec("slot", (parse_path("object.spec.containers[]"),
                        parse_path("object.spec.initContainers[]")),
               parse_path("image")),
    ColumnSpec("scalar", (), parse_path("object.spec.replicas")),
    ColumnSpec("keyset", (parse_path("object.metadata"),),
               parse_path("labels"), exclude=("skip",)),
    ColumnSpec("joinkey", (), parse_path("object.spec.selector"),
               form=("joinpairs", "=", ",")),
    # the same group as the first slot spec: one width, aligned slots
    ColumnSpec("slot", (parse_path("object.spec.containers[]"),
                        parse_path("object.spec.initContainers[]")),
               parse_path("resources.limits.cpu")),
    ColumnSpec("joinkey", (parse_path("object.spec.containers[]"),),
               parse_path("name")),
    ColumnSpec("slot", (parse_path("object.spec.volumes[]"),), ()),
    ColumnSpec("joinkey", (), parse_path("object.metadata.name")),
    ColumnSpec("keyset", (parse_path("object.spec.containers[]"),), ()),
]

# values the float32 cast rounds, overflows or keeps exactly
NUMBERS = [0.1, 16777217, -16777217.0, 1e40, -1e40, 10 ** 400, -0.0, 0, 1.5,
           2 ** 31, True, None, "7", 3.4028235677973366e38]


def _awkward(rng, i):
    review = rand_review(rng, i)
    obj = review.setdefault("object", {})
    if not isinstance(obj, dict):
        return review
    spec = {}
    k = rng.randint(0, 4)
    spec["containers"] = [
        {"name": rng.choice([f"c{i}-{j}", "app", 7, None]),
         "image": rng.choice([f"img-{i}-{j}", "nginx", None, 3, {"x": 1}]),
         "resources": {"limits": {"cpu": rng.choice(NUMBERS)}},
         "x": rng.choice([True, False, "y"])}
        for j in range(k)] if rng.random() < 0.9 else "not-a-list"
    if rng.random() < 0.4:
        spec["initContainers"] = [{"image": f"init-{i}"}]
    if rng.random() < 0.5:
        spec["volumes"] = [rng.choice(NUMBERS) for _ in range(rng.randint(0,
                                                                         3))]
    spec["replicas"] = rng.choice(NUMBERS)
    if rng.random() < 0.7:
        spec["selector"] = rng.choice([
            {"app": f"a{i % 3}", "tier": "web"}, {"n": 1}, "flat", {}])
    obj["spec"] = spec
    md = obj.get("metadata")
    if isinstance(md, dict) and isinstance(md.get("labels"), dict) \
            and rng.random() < 0.3:
        md["labels"]["skip"] = "me"
    return review


# 1e40 overflows float32 in Layout.pack as at the jit boundary, and says so
@pytest.mark.filterwarnings("ignore:overflow encountered in cast")
@pytest.mark.parametrize("n", [1, 5, 8, 40, 64])
@pytest.mark.parametrize("seed", [41, 4141])
def test_hand_made_specs_write_the_arrays_buffer_byte_for_byte(seed, n):
    """Every column kind, missing and non-string fields, numbers float32
    rounds, strings new to the interner mid-batch; one review in a bucket
    of 8 (seven pad rows) up to 64 rows."""
    rng = random.Random(seed)
    reviews = [_awkward(rng, i) for i in range(n)]
    interner = Interner()
    for s in ("nginx", "app", "s:app"):  # some strings known beforehand
        interner.intern(s)
    got = _both_paths(reviews, SPECS, interner, cached_namespace)
    _assert_same(*got)
    assert len(got[3]) > len(interner)  # the batch interned new strings


def test_a_batch_without_columns_and_one_without_reviews():
    """No column specs at all: the review's own fields alone; and no
    reviews: eight pad rows."""
    rng = random.Random(7)
    reviews = [rand_review(rng, i) for i in range(3)]
    _assert_same(*_both_paths(reviews, [], Interner(), cached_namespace))
    _assert_same(*_both_paths([], SPECS, Interner(), cached_namespace))


def test_a_write_plan_refuses_a_row_wider_than_its_columns():
    """The plan of one width tuple laid over a batch of wider data raises
    instead of writing past its columns."""
    narrow = [{"object": {"metadata": {"labels": {"a": "b"}}}}]
    wide = [{"object": {"metadata": {"labels": {"a": "b", "c": "d"}}}}]
    it = Interner()
    rp = pack_reviews(narrow, it, cached_namespace)
    tree = (rp.arrays, {})
    leaves = jax.tree_util.tree_leaves(tree)
    layout = reviewbuf.Layout(tree, 8)
    writer = reviewbuf.Writer(native, [])
    plan = writer.plan(layout, leaves, rp.arrays, {})
    scratch, widths = writer.extract(wide, it, cached_namespace, 8)
    assert widths[0] == 2 and reviewbuf.widths_of(rp.arrays, {}, [])[0] == 1
    with pytest.raises(ValueError):
        writer.write(scratch, plan, 8, layout.width)


# ---- the bundles' own specs, through the driver -----------------------------

KINDS = [("synth", "Pod"), ("agilebank", "Service"), ("agilebank", "Pod"),
         ("agilebank", "Namespace")]


@pytest.fixture(scope="module")
def bundles():
    return Bundles()


def _fresh(reviews, tag):
    """The reviews with strings no interner holds yet, in the middle of
    the batch."""
    out = copy.deepcopy(reviews)
    for i, r in enumerate(out[len(out) // 3:], start=len(out) // 3):
        md = r.get("object", {}).get("metadata")
        if isinstance(md, dict):
            md.setdefault("labels", {})[f"{tag}-k{i}"] = f"{tag}-v{i}"
    return out


def _packs():
    return dict(_stage_rows(PACKS))


def _grew(before):
    return {k: v - before.get(k, 0) for k, v in _packs().items()
            if v != before.get(k, 0)}


@pytest.mark.parametrize("rows,n", [(8, 1), (8, 5), (64, 40)])
@pytest.mark.parametrize("bundle,kind", KINDS)
def test_bundle_specs_write_the_arrays_buffer_and_the_same_masks(
        bundles, bundle, kind, rows, n):
    d, reviews = bundles.reviews(bundle, kind, n)
    reviews = _fresh(reviews, f"{bundle}{kind}{n}")
    with d._lock:
        specs = d._fused_fn()[1][3]
        got = _both_paths(reviews, specs, d.interner,
                          d.store.cached_namespace)
    _assert_same(*got)
    assert got[0].shape[0] == rows
    # through the driver: the second dispatch of a layout is the writer's,
    # and its masks are the plain fused function's
    d.compute_masks(reviews)
    before = _packs()
    ordered, mask, autoreject = d.compute_masks(reviews)
    assert _grew(before) == {("direct",): 1}
    with d._lock:
        fn, _o, rp, cp, cols, gp, crow = d._device_inputs(reviews)
    want_mask, want_rej = jax.jit(fn.__wrapped__)(rp.arrays, cp.arrays,
                                                  cols, gp)
    np.testing.assert_array_equal(mask, np.asarray(want_mask)[crow][:, :rows])
    np.testing.assert_array_equal(autoreject,
                                  np.asarray(want_rej)[crow][:, :rows])


def test_a_new_width_tuple_packs_arrays_once_then_writes_direct(bundles):
    """The first dispatch of a width tuple counts `arrays` and leaves its
    plan; the next counts `direct`; both reach the one executable and
    layout `_packed_variant` holds for it."""
    d, pods = bundles.reviews("agilebank", "Pod", 5)
    wider = copy.deepcopy(pods[:2])
    md = wider[0]["object"]["metadata"]
    md["labels"] = {f"w{j}": f"x{j}" for j in range(11)}  # 16 label slots
    with d._lock:
        specs = d._fused_fn()[1][3]
        _s, widths = reviewbuf.Writer(native, specs).extract(
            wider, _copy(d.interner), d.store.cached_namespace, 8)
        assert widths[0] == 16 and widths not in d._fused_plans
        before = _packs()
        pv, *_rest = d._packed_inputs(wider)
        assert _grew(before) == {("arrays",): 1}
        _writer, ppv, layout, _plan = d._fused_plans[widths]
        assert ppv is pv
        assert (pv, layout) in d._fused_packed.values()
        before = _packs()
        pv2, _o, buf, extras, *_r = d._packed_inputs(wider)
        assert _grew(before) == {("direct",): 1}
        assert pv2 is pv and extras == () and buf.shape == (8, layout.width)
        # the same buffer the arrays make, and the same signature
        fn, _side, _specs, rp, cols = d._review_arrays(wider)
        tree = (rp.arrays, cols)
        arrays_layout = reviewbuf.Layout(tree, 8)
        assert arrays_layout.sig == layout.sig
        want, _e = arrays_layout.pack(jax.tree_util.tree_leaves(tree), 8)
        np.testing.assert_array_equal(buf, want)


def test_without_the_native_extension_every_dispatch_packs_arrays(
        bundles, monkeypatch):
    """GK_NATIVE=0's path: no writer, the arrays serve, the same masks."""
    d, reviews = bundles.reviews("synth", "Pod", 5)
    d.compute_masks(reviews)
    want = d.compute_masks(reviews)
    monkeypatch.setattr(native_mod, "load", lambda: None)
    d._writer = None
    try:
        before = _packs()
        got = d.compute_masks(reviews)
        got2 = d.compute_masks(reviews)
        assert _grew(before) == {("arrays",): 2}
    finally:
        monkeypatch.undo()
        d._writer = None
    for masks in (got, got2):
        for w, g in zip(want[1:], masks[1:]):
            np.testing.assert_array_equal(w, g)
