"""Native runtime ring tests.

Covers the C++ components (sherman_tpu/native): skiplist (the reference's
one host-only unit test, test/skiplist_test.cpp), IndexCache semantics
(IndexCache.h: add / lookup / invalidate / eviction / stats), the local
ticket-lock hand-over protocol (Tree.cpp:1124-1173), the zipf sampler, and
the latency histogram (benchmark.cpp:207-249 cal_latency role).
"""

import os
import threading

import numpy as np
import pytest

from sherman_tpu import native

pytestmark = pytest.mark.skipif(
    not native.available(), reason=f"native lib: {native.load_error()}")


# -- skiplist (skiplist_test.cpp parity) -------------------------------------

def test_skiplist_insert_seek():
    sl = native.SkipList(100_000)
    rng = np.random.default_rng(0)
    keys = rng.choice(1 << 40, size=10_000, replace=False).astype(np.uint64)
    for k in keys:
        sl.insert(int(k), int(k) * 3)
    assert len(sl) == keys.size
    skeys = np.sort(keys)
    # exact seeks
    for k in skeys[::97]:
        got = sl.seek_ge(int(k))
        assert got == (int(k), int(k) * 3)
    # between-key seeks land on the successor
    for i in range(0, len(skeys) - 1, 131):
        probe = int(skeys[i]) + 1
        if probe == int(skeys[i + 1]):
            continue
        assert sl.seek_ge(probe) == (int(skeys[i + 1]), int(skeys[i + 1]) * 3)
    assert sl.seek_ge(int(skeys[-1]) + 1) is None


def test_skiplist_overwrite():
    sl = native.SkipList(16)
    assert sl.insert(7, 1) == 0
    assert sl.insert(7, 2) == 1  # updated in place
    assert len(sl) == 1
    assert sl.seek_ge(0) == (7, 2)


def test_skiplist_concurrent_insert():
    sl = native.SkipList(200_000)
    n_threads, per = 8, 5_000

    def worker(tid):
        for i in range(per):
            k = tid * per + i
            sl.insert(k, k + 1)

    ts = [threading.Thread(target=worker, args=(t,)) for t in range(n_threads)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert len(sl) == n_threads * per
    for k in range(0, n_threads * per, 977):
        assert sl.seek_ge(k) == (k, k + 1)


# -- index cache -------------------------------------------------------------

def test_cache_add_lookup_invalidate():
    c = native.IndexCache(1024)
    c.add(0, 100, 11)
    c.add(100, 200, 22)
    c.add(200, 300, 33)
    assert c.lookup(0) == 11
    assert c.lookup(99) == 11
    assert c.lookup(100) == 22
    assert c.lookup(299) == 33
    assert c.lookup(300) == 0  # uncovered
    assert c.invalidate(150)
    assert c.lookup(150) == 0
    assert c.lookup(250) == 33  # neighbors unaffected
    s = c.stats()
    assert s["invalidates"] == 1 and s["hits"] == 5 and s["misses"] == 2


def test_cache_refresh_same_range():
    c = native.IndexCache(64)
    c.add(10, 20, 1)
    c.add(10, 20, 2)  # refresh ptr in place
    assert c.lookup(15) == 2
    assert c.stats()["used_slots"] == 1


def test_cache_split_narrowing():
    """A leaf split narrows the covering range: new entries for both halves
    shadow the old one (the new `to`=split bound wins by skiplist order; the
    right half overwrites the stale full-range mapping's bound)."""
    c = native.IndexCache(64)
    c.add(0, 1000, 7)          # original leaf
    c.add(0, 500, 7)           # left half after split
    c.add(500, 1000, 8)        # right half (overwrites to=1000 mapping)
    assert c.lookup(250) == 7
    assert c.lookup(750) == 8


def test_cache_eviction_under_pressure():
    c = native.IndexCache(128)
    # heat up half the entries so eviction prefers the cold ones
    for i in range(128):
        c.add(i * 10, i * 10 + 10, i + 1)
    for _ in range(50):
        for i in range(0, 64):
            c.lookup(i * 10)
    # overflow: adds beyond capacity force 2-random eviction + delay-free
    import time
    added = 0
    for i in range(128, 256):
        r = c.add(i * 10, i * 10 + 10, i + 1)
        if r == -1:  # all victims still inside the 30 µs delay window
            time.sleep(0.0001)
            r = c.add(i * 10, i * 10 + 10, i + 1)
        added += (r >= 0)
    s = c.stats()
    assert s["evictions"] > 0
    assert added > 64  # the cache keeps absorbing under pressure
    # hot half should have mostly survived
    hot_alive = sum(c.lookup(i * 10) != 0 for i in range(64))
    cold_alive = sum(c.lookup(i * 10) != 0 for i in range(64, 128))
    assert hot_alive > cold_alive


def test_cache_lookup_many():
    c = native.IndexCache(64)
    c.add_many([0, 100], [100, 200], [5, 6])
    out = c.lookup_many(np.array([0, 50, 150, 999], np.uint64))
    np.testing.assert_array_equal(out, [5, 5, 6, 0])


# -- local ticket locks ------------------------------------------------------

def test_lock_handover_protocol():
    lt = native.LocalLockTable(8)
    # uncontended: no handover either way
    assert lt.acquire(3) is False
    assert lt.release(3) is False

    # contended: the releaser passes the global lock to the waiter
    got_handover = []

    def waiter():
        got_handover.append(lt.acquire(3))
        lt.release(3)

    t = threading.Thread(target=waiter)
    assert lt.acquire(3) is False
    t.start()
    import time
    time.sleep(0.05)  # let the waiter join the queue
    handed = lt.release(3)
    t.join()
    assert handed is True
    assert got_handover == [True]


def test_lock_handover_bounded():
    """The hand-over train is bounded by kMaxHandOver=8 (Common.h:101):
    with a continuous queue, release() must eventually return False."""
    lt = native.LocalLockTable(1)
    results = []
    n = 12

    def worker():
        lt.acquire(0)
        results.append(lt.release(0))

    # keep the queue non-empty: stagger starts before releases begin
    ts = [threading.Thread(target=worker) for _ in range(n)]
    lt.acquire(0)
    for t in ts:
        t.start()
    import time
    time.sleep(0.1)
    results.append(lt.release(0))
    for t in ts:
        t.join()
    # the true last release (empty queue) returns False and the train
    # bound forces at least one mid-train False past 8 hand-overs — but
    # append order can RACE release order between two workers (A hands
    # to B, B releases+appends False before A appends True), so assert
    # the COUNT of Falses, not a list position
    assert sum(r is False for r in results) >= 2


@pytest.mark.slow
def test_lock_mutual_exclusion():
    lt = native.LocalLockTable(1)
    counter = {"v": 0}

    def worker():
        for _ in range(2000):
            lt.acquire(0)
            counter["v"] += 1
            lt.release(0)

    ts = [threading.Thread(target=worker) for _ in range(4)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert counter["v"] == 8000


# -- zipf --------------------------------------------------------------------

def test_zipf_skew_and_range():
    z = native.ZipfGen(1_000_000, 0.99, seed=7)
    s = z.sample(200_000)
    assert s.min() >= 0 and s.max() < 1_000_000
    # theta=0.99 -> top-10 ranks draw a large constant share
    share = (s < 10).mean()
    assert 0.10 < share < 0.35
    # uniform degenerate case
    u = native.ZipfGen(1_000_000, 0.0, seed=7)
    su = u.sample(200_000)
    assert (su < 10).mean() < 0.001
    assert su.max() < 1_000_000


def test_zipf_python_wrapper_prefers_native():
    from sherman_tpu.workload.zipf import ZipfGen
    z = ZipfGen(1000, 0.99, seed=3)
    assert z._native is not None
    s = z.sample(1000)
    assert s.dtype == np.int64 and s.min() >= 0 and s.max() < 1000


# -- histogram ---------------------------------------------------------------

def test_histogram_percentiles():
    h = native.LatencyHistogram()
    # 1..100 µs uniformly -> p50 ~ 50 µs, p99 ~ 99 µs
    h.record_many_ns(np.arange(1_000, 100_001, 1_000, dtype=np.uint64)
                     .repeat(10))
    p = h.percentiles_us()
    assert abs(p["p50"] - 50) < 2
    assert abs(p["p99"] - 99) < 2
    assert p["p999"] <= 101
    assert h.count == 1000
    h.reset()
    assert h.count == 0


def test_histogram_batch_record():
    h = native.LatencyHistogram()
    h.record_batch(5_000, 100)  # 100 ops completed together at 5 µs
    assert h.count == 100
    assert abs(h.percentiles_us([0.5])["p50"] - 5.0) < 0.2


def test_wrlock_writer_preference_and_counts():
    import threading
    import time

    from sherman_tpu import native

    if not native.available():
        import pytest
        pytest.skip(native.load_error())
    rw = native.WRLock()
    # readers share: a second rlock must not block under a held rlock
    rw.rlock()
    done = []
    t2 = threading.Thread(target=lambda: (rw.rlock(), done.append(1),
                                          rw.runlock()))
    t2.start()
    t2.join(timeout=5)
    assert done, "second reader blocked under a held read lock"
    rw.runlock()
    # writer excludes readers
    rw.wlock()
    seen = []

    def reader():
        rw.rlock()
        seen.append(time.monotonic())
        rw.runlock()

    t = threading.Thread(target=reader)
    t.start()
    time.sleep(0.05)
    assert not seen  # blocked while the writer holds it
    t0 = time.monotonic()
    rw.wunlock()
    t.join(timeout=5)
    assert seen and seen[0] >= t0


# -- BatchPrep (the fused serving-loop prep pipeline, src/prep.cc) ------------

def _rebuild_keys(buf, n):
    return ((buf.khi[:n].view(np.uint32).astype(np.uint64) << np.uint64(32))
            | buf.klo[:n].view(np.uint32).astype(np.uint64))


def test_prep_keys_matches_numpy_unique():
    rng = np.random.default_rng(5)
    keys = rng.integers(1, 5000, 100_000, dtype=np.uint64)
    table = rng.integers(1, 1 << 20, 1 << 14, dtype=np.int64).astype(np.int32)
    shift = 50
    prep = native.BatchPrep(batch=100_000, capacity=8192)
    buf = prep.run_keys(keys, prep.buffers(), table, shift=shift,
                        default_start=3)
    n = buf.n_uniq
    uk = _rebuild_keys(buf, n)
    ref = np.unique(keys)
    assert n == ref.size
    np.testing.assert_array_equal(np.sort(uk), ref)  # same unique SET
    # inverse fans every client op back to its own key
    np.testing.assert_array_equal(uk[buf.inv], keys)
    # active exactly covers the unique prefix
    assert buf.active[:n].all() and not buf.active[n:].any()
    # router probe matches the host_start formula (min(key>>shift, nb-1))
    b = np.minimum(uk >> np.uint64(shift), np.uint64(table.size - 1))
    np.testing.assert_array_equal(buf.start[:n], table[b.astype(np.int64)])
    # pad rows carry the default start seed
    assert (buf.start[n:] == 3).all()


def test_prep_overflow_raises():
    prep = native.BatchPrep(batch=1000, capacity=8)
    keys = np.arange(1, 1001, dtype=np.uint64)  # 1000 uniques > 8
    with pytest.raises(native.PrepOverflow):
        prep.run_keys(keys, prep.buffers(), None)


def test_prep_epoch_isolation_across_batches():
    """Batch k's dedup state must not leak into batch k+1 (epoch tags)."""
    prep = native.BatchPrep(batch=1000, capacity=1000)
    buf = prep.buffers()
    a = np.arange(1, 501, dtype=np.uint64).repeat(2)
    prep.run_keys(a, buf, None)
    assert buf.n_uniq == 500
    # same keys again: they must count as fresh uniques, not stale dups
    prep.run_keys(a, buf, None)
    assert buf.n_uniq == 500
    np.testing.assert_array_equal(np.sort(_rebuild_keys(buf, 500)),
                                  np.arange(1, 501, dtype=np.uint64))


def test_prep_zipf_synthetic_mode():
    """Synthetic rank->key mode: keys come from mix64(rank ^ salt); the
    recorded client keys must dedup consistently and land inside the
    synthetic keyspace."""
    n_keys, batch, salt = 1 << 20, 65_536, 0x5E17_AB1E_5A17
    keyspace, rank_to_key = native.synthetic_keyspace(n_keys, salt)
    prep = native.BatchPrep(batch=batch, capacity=batch, n_keys=n_keys,
                            theta=0.99, seed=7, salt=salt)
    buf = prep.buffers(with_keys=True)
    prep.run_zipf(None, buf, None, want_keys=True)
    n = buf.n_uniq
    assert 0 < n < batch  # zipf 0.99 must combine substantially
    uk = _rebuild_keys(buf, n)
    np.testing.assert_array_equal(uk[buf.inv], buf.keys)
    # every sampled key is a member of the synthetic keyspace
    assert np.isin(buf.keys[:1000], keyspace).all()
    # hot head: rank 0's key must dominate any cold key's count
    head_key = rank_to_key[0]
    assert (buf.keys == head_key).sum() > batch // 100


def test_prep_zipf_keyspace_gather_mode():
    """Explicit-keyspace mode gathers keys[rank] with internal lookahead."""
    n_keys, batch = 1 << 18, 32_768
    rng = np.random.default_rng(2)
    keyspace = np.sort(rng.choice(1 << 40, n_keys, replace=False)
                       .astype(np.uint64))
    prep = native.BatchPrep(batch=batch, capacity=batch, n_keys=n_keys,
                            theta=0.99, seed=7)
    buf = prep.buffers(with_keys=True)
    prep.run_zipf(keyspace, buf, None, want_keys=True)
    assert np.isin(buf.keys, keyspace).all()
    uk = _rebuild_keys(buf, buf.n_uniq)
    np.testing.assert_array_equal(uk[buf.inv], buf.keys)


def test_prep_zipf_distribution_matches_exact_sampler():
    """The AVX-512 fast-pow sampler must track the exact inverse-CDF:
    compare head-rank shares against ZipfGen (std::pow) on 200k draws."""
    n_keys, batch, salt = 1 << 22, 200_000, 0x5E17_AB1E_5A17
    prep = native.BatchPrep(batch=batch, capacity=batch, n_keys=n_keys,
                            theta=0.99, seed=11, salt=salt)
    buf = prep.buffers(with_keys=True)
    prep.run_zipf(None, buf, None, want_keys=True)
    lut_n = 1 << 12
    r2k = native.mix64(np.arange(lut_n, dtype=np.uint64) ^ np.uint64(salt))
    exact = native.ZipfGen(n_keys, 0.99, seed=23).sample(batch)
    for rank in (0, 1, 10):
        fast_share = (buf.keys == r2k[rank]).mean()
        exact_share = (exact == rank).mean()
        assert abs(fast_share - exact_share) < 0.004, (
            rank, fast_share, exact_share)
    # share of the hot head (top 4096 ranks) within 2% absolute
    fast_head = np.isin(buf.keys, r2k).mean()
    exact_head = (exact < lut_n).mean()
    assert abs(fast_head - exact_head) < 0.02, (fast_head, exact_head)


def test_library_keyed_by_source_content(tmp_path, monkeypatch):
    """The library that loads is named by a hash of src/* and the
    compile command: touching a file changes nothing, editing one
    names a new library (a stale .so can never load for new sources)."""
    import shutil
    src = tmp_path / "src"
    shutil.copytree(native._SRC, src)
    monkeypatch.setattr(native, "_SRC", str(src))
    p1 = native._lib_path()
    cc = sorted(src.glob("*.cc"))[0]
    os.utime(cc, (1, 1))
    assert native._lib_path() == p1
    with open(cc, "a") as f:
        f.write("\n// edited\n")
    p2 = native._lib_path()
    assert p2 != p1
    monkeypatch.setattr(native, "_CMD", native._CMD + ("-g",))
    assert native._lib_path() != p2
