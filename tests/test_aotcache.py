"""Serialized-executable (AOT) cache: ops/aotcache.py.

The warm-restart artifact (SURVEY §5.4): a restarted process must load
compiled executables from disk without re-tracing, never reuse an
executable across kernel-source changes, and degrade to plain jit on
any cache pathology.
"""

import numpy as np
import pytest

from gatekeeper_tpu.ops import aotcache


@pytest.fixture
def cache_dir(tmp_path, monkeypatch):
    # isolate the module state: enabled dir + memoized fingerprint
    monkeypatch.setattr(aotcache, "_dir", None)
    assert aotcache.enable(str(tmp_path))
    yield str(tmp_path)
    monkeypatch.setattr(aotcache, "_dir", None)


def _fn(x, y):
    return (x * 2 + y).sum()


class TestRoundTrip:
    def test_save_then_fresh_instance_loads(self, cache_dir):
        import os

        x = np.arange(8, dtype=np.float32)
        y = np.ones(8, dtype=np.float32)
        a = aotcache.aot_jit(_fn, "t-roundtrip", sig="s1")
        out1 = float(a(x, y))
        assert any(f.endswith(".aot") for f in os.listdir(cache_dir))
        # a fresh instance (fresh process analogue) must LOAD, not compile
        b = aotcache.aot_jit(_fn, "t-roundtrip", sig="s1")
        key = b._key((x, y))
        assert aotcache.load(key) is not None
        out2 = float(b(x, y))
        assert out1 == out2

    def test_multiple_layouts_memoized(self, cache_dir):
        a = aotcache.aot_jit(_fn, "t-layouts", sig="s1")
        x8 = np.arange(8, dtype=np.float32)
        x16 = np.arange(16, dtype=np.float32)
        a(x8, x8)
        a(x16, x16)
        a(x8, x8)  # back to the first layout: no thrash
        assert len(a._compiled) == 2

    def test_disabled_falls_back_to_jit(self, monkeypatch):
        monkeypatch.setattr(aotcache, "_dir", None)
        a = aotcache.aot_jit(_fn, "t-disabled", sig=None)
        x = np.ones(4, dtype=np.float32)
        assert float(a(x, x)) == float(_fn(x, x))
        assert not a._compiled


class TestInvalidation:
    def test_sig_change_changes_key(self, cache_dir):
        x = np.ones(4, dtype=np.float32)
        a = aotcache.aot_jit(_fn, "t-sig", sig="v1")
        b = aotcache.aot_jit(_fn, "t-sig", sig="v2")
        assert a._key((x, x)) != b._key((x, x))

    def test_layout_change_changes_key(self, cache_dir):
        a = aotcache.aot_jit(_fn, "t-shape", sig="s")
        x4 = np.ones(4, dtype=np.float32)
        x8 = np.ones(8, dtype=np.float32)
        assert a._key((x4, x4)) != a._key((x8, x8))

    def test_code_fingerprint_in_key(self, cache_dir, monkeypatch):
        from gatekeeper_tpu.util import seal

        x = np.ones(4, dtype=np.float32)
        a = aotcache.aot_jit(_fn, "t-code", sig="s")
        k1 = a._key((x, x))
        # the fingerprint is shared with the snapshot seal (util/seal.py)
        monkeypatch.setattr(seal, "_code_fp", "different-build")
        b = aotcache.aot_jit(_fn, "t-code", sig="s")
        assert b._key((x, x)) != k1

    def test_unreadable_entry_is_miss(self, cache_dir):
        import os

        x = np.ones(4, dtype=np.float32)
        a = aotcache.aot_jit(_fn, "t-corrupt", sig="s")
        a(x, x)
        (entry,) = [f for f in os.listdir(cache_dir) if f.endswith(".aot")]
        with open(os.path.join(cache_dir, entry), "wb") as f:
            f.write(b"not a pickle")
        fresh = aotcache.aot_jit(_fn, "t-corrupt", sig="s")
        assert float(fresh(x, x)) == float(_fn(x, x))  # recompiles fine


class TestBadEntryBlacklist:
    def test_rejecting_executable_blacklisted_and_dropped(self, cache_dir):
        import os

        x = np.ones(4, dtype=np.float32)
        a = aotcache.aot_jit(_fn, "t-bad", sig="s")
        a(x, x)
        key = a._key((x, x))

        class Rejecting:
            calls = 0

            def __call__(self, *args):
                Rejecting.calls += 1
                raise RuntimeError("layout drift")

        a._compiled[key] = Rejecting()
        out = a(x, x)  # falls back to jit
        assert float(out) == float(_fn(x, x))
        assert key in a._bad
        assert not os.path.exists(os.path.join(cache_dir, key + ".aot"))
        # subsequent calls never touch the bad entry again
        a(x, x)
        assert Rejecting.calls == 1


# ---- a key it already knows (ISSUE 37) -------------------------------------

LOOKUPS = "aot_executable_lookups_total"


def _lookups():
    from gatekeeper_tpu.metrics.views import global_registry

    rows = global_registry().view_rows(LOOKUPS)
    return rows.get(("memo",), 0), rows.get(("hashed",), 0)


def _tree():
    """One small tree with a dict, a tuple, an empty tuple and the
    dtypes the review path hands over."""
    return (np.zeros((8, 5), np.int32), (),
            {("scalar", ()): np.ones(8, bool),
             ("slot", ("object", "spec")): {"num": np.zeros((8, 2)),
                                            "tcode": np.zeros((8, 2), np.int8)}},
            [np.float32(1.5)])


def _parent_key(a, args) -> str:
    """aot_jit._key as the parent commit computed it on every call: one
    formatted string a leaf, fed to the instance's SHA-256."""
    import jax

    h = a._prefix.copy()
    h.update(jax.default_backend().encode())
    leaves, treedef = jax.tree_util.tree_flatten(args)
    h.update(str(treedef).encode())
    for x in leaves:
        h.update(f"{tuple(x.shape)}:{x.dtype}".encode())
    return f"{a._tag}-{h.hexdigest()[:32]}"


class TestLayoutMemo:
    def test_on_disk_name_is_the_parents(self, cache_dir, monkeypatch):
        """The name a new layout is hashed to is the one the parent's
        _key gave the same arguments: entries on disk are found again
        (but for the code fingerprint, which any edit moves)."""
        import jax

        from gatekeeper_tpu.util import seal

        monkeypatch.setattr(seal, "_code_fp", "pinned-build")
        monkeypatch.setattr(jax, "__version__", "0.0.0-pinned")
        a = aotcache.aot_jit(lambda *t: t[0].sum(), "t-pin", sig=("s", 1))
        args = _tree()
        _layout, key = a._lookup(args)
        assert key == a._key(args) == _parent_key(a, args)
        # read off the parent commit's aot_jit._key with the same two
        # pins, in this container (jax's str(treedef) is part of it)
        assert key == PINNED

    def test_second_call_does_not_reach_hashlib(self, cache_dir,
                                                monkeypatch):
        a = aotcache.aot_jit(_fn, "t-memo", sig="s")
        x = np.arange(8, dtype=np.float32)
        memo0, hashed0 = _lookups()
        out1 = float(a(x, x))
        assert _lookups() == (memo0, hashed0 + 1)

        def no_hash(*_a, **_k):
            raise AssertionError("a layout met before was hashed again")

        monkeypatch.setattr(a, "_key", no_hash)
        monkeypatch.setattr(aotcache, "_leaf_sig", no_hash)
        assert float(a(x + 0, x)) == out1  # other arrays, the same layout
        assert float(a(x, x)) == out1
        assert _lookups() == (memo0 + 2, hashed0 + 1)
        assert len(a._keys) == len(a._compiled) == 1

    @pytest.mark.parametrize("other", ("dtype", "treedef", "shape"))
    def test_layouts_apart_get_executables_apart(self, cache_dir, other):
        """Two argument trees that differ only in one leaf's dtype, only
        in their structure, or only in one shape never share a memo
        entry, a name or an executable."""
        a = aotcache.aot_jit(lambda t: sum(v.sum() for v in
                                           jax_leaves(t)), "t-apart", sig="s")
        base = {"a": np.ones(4, np.int32), "b": np.ones(4, np.int32)}
        twin = {
            "dtype": {"a": np.ones(4, np.int32), "b": np.ones(4, np.int8)},
            "treedef": {"a": np.ones(4, np.int32), "c": np.ones(4, np.int32)},
            "shape": {"a": np.ones(4, np.int32), "b": np.ones(8, np.int32)},
        }[other]
        assert int(a(base)) == 8
        assert int(a(twin)) == (12 if other == "shape" else 8)
        (k1, k2) = a._keys.values()
        assert k1 != k2 and len(a._compiled) == 2
        assert a._compiled[k1] is not a._compiled[k2]
        assert {k1, k2} == {a._key((base,)), a._key((twin,))}

    def test_blacklisting_drops_the_memo_entry(self, cache_dir):
        """A rejected executable leaves neither its memo entry nor
        itself behind; the next call names the key again, finds it
        blacklisted and goes to jit."""
        x = np.ones(4, dtype=np.float32)
        a = aotcache.aot_jit(_fn, "t-bad-memo", sig="s")
        a(x, x)
        (layout, key), = a._keys.items()

        class Rejecting:
            def __call__(self, *args):
                raise RuntimeError("layout drift")

        a._compiled[key] = Rejecting()
        assert float(a(x, x)) == float(_fn(x, x))
        assert layout not in a._keys and key not in a._compiled
        assert key in a._bad
        _memo0, hashed0 = _lookups()
        assert float(a(x, x)) == float(_fn(x, x))
        assert a._keys == {layout: key} and key not in a._compiled
        assert _lookups()[1] == hashed0 + 1


def jax_leaves(tree):
    import jax

    return jax.tree_util.tree_leaves(tree)


PINNED = "t-pin-b0f79373b60aa2376fead9ccefae1c6c"
