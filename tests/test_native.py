"""Native runtime (C++ arena/store/prefetcher) + the cached FeatureSet."""

import numpy as np
import pytest

from analytics_zoo_tpu import native

pytestmark = pytest.mark.skipif(
    not native.available(), reason="native toolchain unavailable")


def test_arena_alloc_and_accounting():
    a = native.NativeArena(1 << 20)
    assert a.capacity == 1 << 20
    assert a.used == 0
    s = native.NativeSampleStore(a)
    s.put(np.arange(10, dtype=np.float32))
    assert a.used >= 40
    a2 = a.used
    s.put(np.arange(10, dtype=np.float32))
    assert a.used > a2
    s.close()
    a.close()


def test_arena_full_raises():
    a = native.NativeArena(256)
    s = native.NativeSampleStore(a)
    with pytest.raises(MemoryError):
        for _ in range(10):
            s.put(np.zeros(64, np.uint8))
    s.close()
    a.close()


def test_store_roundtrip_file_backed(tmp_path):
    a = native.NativeArena(1 << 20, str(tmp_path / "pmem.bin"))
    s = native.NativeSampleStore(a)
    rng = np.random.default_rng(0)
    recs = [rng.normal(size=17).astype(np.float32) for _ in range(5)]
    ids = [s.put(r) for r in recs]
    assert ids == [0, 1, 2, 3, 4]
    for r, i in zip(recs, ids):
        got = np.frombuffer(s.get(i), np.float32)
        np.testing.assert_array_equal(got, r)
    assert (tmp_path / "pmem.bin").exists()
    s.close()
    a.close()


def test_prefetcher_batches_in_order():
    a = native.NativeArena(1 << 22)
    s = native.NativeSampleStore(a)
    n = 37
    for i in range(n):
        rec = np.concatenate([
            np.full(8, i, np.float32).view(np.uint8).ravel(),
            np.asarray([i], np.int32).view(np.uint8).ravel()])
        s.put(rec)
    pf = native.NativePrefetcher(s, [(8,), ()], [np.float32, np.int32],
                                 batch_size=10, n_slots=2, n_threads=3)
    order = np.arange(n, dtype=np.uint64)
    got_labels = []
    for xb, yb in pf.epoch(order):
        assert xb.shape == (10, 8) and yb.shape == (10,)
        np.testing.assert_array_equal(xb[:, 0].astype(np.int32), yb)
        got_labels.extend(yb.tolist())
    # 4 batches of 10 with wrap-padding: 37 real + 3 wrapped from the front
    assert len(got_labels) == 40
    assert got_labels[:37] == list(range(37))
    assert got_labels[37:] == [0, 1, 2]
    # second epoch with a different order works (ring reset)
    rev = order[::-1].copy()
    first = next(iter(pf.epoch(rev)))
    np.testing.assert_array_equal(first[1][:5], [36, 35, 34, 33, 32])
    pf.close()
    s.close()
    a.close()


def test_prefetcher_abandoned_epoch_restarts_clean():
    a = native.NativeArena(1 << 22)
    s = native.NativeSampleStore(a)
    for i in range(32):
        s.put(np.asarray([i], np.int64))
    pf = native.NativePrefetcher(s, [()], [np.int64], batch_size=4,
                                 n_slots=2, n_threads=2)
    order = np.arange(32, dtype=np.uint64)
    it = pf.epoch(order)
    next(it)  # consume one batch, abandon the rest mid-flight
    del it
    vals = [int(b[0][0]) for b in pf.epoch(order, drop_remainder=True)]
    assert vals == [0, 4, 8, 12, 16, 20, 24, 28]
    pf.close()
    s.close()
    a.close()


def test_native_cached_feature_set_matches_array_set():
    from analytics_zoo_tpu.data.feature_set import ArrayFeatureSet
    from analytics_zoo_tpu.data.pmem import cached_feature_set

    rng = np.random.default_rng(1)
    x = rng.normal(size=(23, 5)).astype(np.float32)
    y = rng.integers(0, 3, size=23).astype(np.int32)
    fs = cached_feature_set(x, y, memory_type="DRAM")
    ref = ArrayFeatureSet(x, y)
    for (xa, ya), (xb, yb) in zip(fs.batches(8, shuffle=True, seed=7),
                                  ref.batches(8, shuffle=True, seed=7)):
        np.testing.assert_array_equal(np.asarray(xa), xb)
        np.testing.assert_array_equal(np.asarray(ya), yb)
    # eval path (take) agrees as well
    xa, ya = fs.take(np.array([3, 1, 4]))
    xb, yb = ref.take(np.array([3, 1, 4]))
    np.testing.assert_array_equal(xa, xb)
    np.testing.assert_array_equal(ya, yb)
    if hasattr(fs, "close"):
        fs.close()


def test_cached_feature_set_pmem_file(tmp_path):
    from analytics_zoo_tpu.data.pmem import NativeCachedFeatureSet

    x = np.arange(60, dtype=np.float32).reshape(20, 3)
    fs = NativeCachedFeatureSet(x, None, memory_type="PMEM",
                                path=str(tmp_path / "cache.bin"))
    xs, ys = fs.take(np.arange(20))
    np.testing.assert_array_equal(xs, x)
    assert ys is None
    assert (tmp_path / "cache.bin").exists()
    fs.close()


def test_multi_component_feature_set():
    from analytics_zoo_tpu.data.pmem import NativeCachedFeatureSet

    rng = np.random.default_rng(2)
    x1 = rng.normal(size=(12, 4)).astype(np.float32)
    x2 = rng.integers(0, 9, size=(12, 2)).astype(np.int32)
    y = rng.normal(size=(12, 1)).astype(np.float32)
    fs = NativeCachedFeatureSet([x1, x2], y)
    (g1, g2), gy = fs.take(np.arange(12))
    np.testing.assert_array_equal(g1, x1)
    np.testing.assert_array_equal(g2, x2)
    np.testing.assert_array_equal(gy, y)
    for (bx1, bx2), by in fs.batches(6, shuffle=False):
        assert bx1.shape == (6, 4) and bx2.shape == (6, 2) and by.shape == (6, 1)
    fs.close()


# ---------------------------------------------------------------------------
# batches lent from the ring (ISSUE 27)
# ---------------------------------------------------------------------------


def _lending_set(multi=False, n_slots=2):
    from analytics_zoo_tpu.data.pmem import NativeCachedFeatureSet

    rng = np.random.default_rng(5)
    x = rng.normal(size=(23, 5)).astype(np.float32)
    y = rng.integers(0, 3, size=23).astype(np.int32)
    if multi:
        x = [x, rng.integers(0, 9, size=(23, 2)).astype(np.int32)]
    return NativeCachedFeatureSet(x, y, n_slots=n_slots)


def _addresses(batch):
    import jax

    return [a.ctypes.data for a in jax.tree_util.tree_leaves(batch[:2])]


@pytest.mark.parametrize("multi", [False, True], ids=["one_x", "two_x"])
def test_borrowed_batches_are_the_train_batches_without_the_copy(multi):
    import jax

    fs = _lending_set(multi)
    for seed in (0, 7):
        own = list(fs.train_batches(8, shuffle=True, seed=seed))
        seen = []
        for b, lent in enumerate(fs.train_batches(8, shuffle=True, seed=seed,
                                                  borrowed=True)):
            # same batches, same wrap-padded tail, same mask, same order
            jax.tree_util.tree_map(np.testing.assert_array_equal, lent, own[b])
            assert (jax.tree_util.tree_structure(lent)
                    == jax.tree_util.tree_structure(own[b]))
            assert not any(a.flags.owndata
                           for a in jax.tree_util.tree_leaves(lent[:2]))
            seen.append(_addresses(lent))
        assert len(seen) == len(own) == 3
        assert own[-1][2].tolist() == [1.0] * 7 + [0.0]
        # views into the ring's two slots, handed round; the copies are not
        assert seen[0] == seen[2] != seen[1]
        assert len({tuple(_addresses(o)) for o in own}) == 3
    fs.close()


def test_a_borrowed_batch_is_overwritten_after_the_next_step_a_copy_is_not():
    fs = _lending_set(n_slots=2)
    own = [x for x, _, _ in fs.train_batches(8, shuffle=False)]
    it = fs.train_batches(8, shuffle=False, borrowed=True)
    first = next(it)[0]
    np.testing.assert_array_equal(first, own[0])
    next(it)
    third = next(it)[0]             # batch 2 lands in batch 0's slot
    assert third.ctypes.data == first.ctypes.data
    np.testing.assert_array_equal(first, own[2])
    fs.close()


def test_borrowed_batches_skip_and_window_like_the_copies():
    """Neither form takes ``start_step`` or ``window``: the estimator's helpers
    fall back for both alike (skipped batches are assembled and dropped,
    the window is sliced after the take), and give the same rows."""
    import jax

    from analytics_zoo_tpu.engine.estimator import _skip_steps, _windowed_iter

    fs = _lending_set()

    def batches(lend_kw, skip, window):
        out = []
        it = _skip_steps(
            lambda **skip_kw: _windowed_iter(
                lambda **kw: fs.train_batches(8, shuffle=True, seed=3,
                                              **lend_kw, **skip_kw, **kw),
                window), skip)
        for item in it:
            out.append(jax.tree_util.tree_map(np.array, item))
        return out

    for skip, window in ((0, None), (2, None), (1, (2, 6)), (3, None)):
        own = batches({}, skip, window)
        lent = batches({"borrowed": True}, skip, window)
        assert len(own) == len(lent) == 3 - skip
        jax.tree_util.tree_map(np.testing.assert_array_equal, lent, own)
        if window:
            assert all(b[0].shape == (4, 5) for b in lent)
    fs.close()
