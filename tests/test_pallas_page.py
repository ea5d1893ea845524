"""Pallas page-engine kernels (ops/pallas_page) vs the XLA primitives:
bit-identical on ANY inputs, interpreter mode on the CPU mesh — the
transport_pallas coverage recipe applied to the HBM<->VMEM data plane.
Their compiles for a v5e live in tests/test_tpu_compile.py.

The fuzz deliberately feeds GARBAGE pools (uniform random words): the
parity contract is bitwise equality of the kernel and its ``*_xla`` twin
on arbitrary bytes, not just legal trees — the descent kernel's child
pick must take the same edge one-hot, wrap the same masked sums, and
zero the same not-ok rows as the XLA composition, or a straggler row
could diverge silently under corruption.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sherman_tpu import config as C
from sherman_tpu import obs
from sherman_tpu.config import DSMConfig, TreeConfig
from sherman_tpu.ops import bits, layout
from sherman_tpu.ops import pallas_page as PP

pytestmark = pytest.mark.skipif(not PP.available(),
                                reason="pallas unavailable")


def _rand_words(rng, shape):
    return rng.integers(-2**31, 2**31, shape, dtype=np.int64).astype(np.int32)


def _mixed_addrs(rng, B, P):
    """Addresses spanning every validity class: in-range pages, pages
    past the pool, nonzero node bits, full-garbage words."""
    addr = _rand_words(rng, B)
    k = B // 3
    addr[:k] = rng.integers(0, P, k).astype(np.int32)
    addr[k:2 * k] = rng.integers(0, 2 * P, k).astype(np.int32)
    return addr


# ---------------------------------------------------------------------------
# Kernel 1: fused descent round.
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("seed,B,P,stop_level", [
    (0, 256, 64, 0),
    (1, 777, 32, 0),     # straggler shape: pads to 1024
    (2, 8, 16, 0),       # tiny batch, pads to one BLOCK
    (3, 512, 64, 1),     # parent-maintenance descent target
])
def test_descent_round_fuzz_bit_identity(seed, B, P, stop_level):
    rng = np.random.default_rng(seed)
    pool = _rand_words(rng, (P, C.PAGE_WORDS))
    addr = _mixed_addrs(rng, B, P)
    khi = _rand_words(rng, B)
    klo = _rand_words(rng, B)
    active = rng.integers(0, 2, B).astype(bool)

    got = jax.jit(lambda *a: PP.descent_round(*a, stop_level=stop_level))(
        pool, addr, khi, klo, active)
    want = jax.jit(
        lambda *a: PP.descent_round_xla(*a, stop_level=stop_level))(
        pool, addr, khi, klo, active)
    for g, w, name in zip(got, want, ("nxt", "is_leaf", "chase", "ok",
                                      "found", "vhi", "vlo")):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w),
                                      err_msg=name)


def test_descent_round_on_real_pages():
    """Legal pages (not garbage): a two-level tree fragment — the round
    must pick the right child on the internal page, find keys on the
    leaf, flag the sibling chase past the fence."""
    P = 8
    pool = np.zeros((P, C.PAGE_WORDS), np.int32)
    # page 1: internal level-1, children 2 (keys < 100) and 3 (>= 100)
    pg = layout.np_empty_page(1, 0, C.KEY_POS_INF, leftmost=2)
    layout.np_internal_set_entry(pg, 0, 100, 3)
    pg[C.W_NKEYS] = 1
    pool[1] = pg
    # page 2: leaf [0, 100) holding keys 7 and 50, B-link sibling -> 3
    pg = layout.np_empty_page(0, 0, 100, sibling=3)
    layout.np_leaf_set_entry(pg, 0, 7, 70)
    layout.np_leaf_set_entry(pg, 4, 50, 500)
    pool[2] = pg
    # page 3: leaf [100, inf) holding key 200
    pg = layout.np_empty_page(0, 100, C.KEY_POS_INF)
    layout.np_leaf_set_entry(pg, 1, 200, 2000)
    pool[3] = pg

    keys = np.array([7, 50, 99, 200], np.uint64)
    khi, klo = bits.keys_to_pairs(keys)
    act = np.ones(4, bool)

    # round at the internal page routes every key to its child
    addr = np.full(4, 1, np.int32)
    nxt, is_leaf, chase, ok, *_ = jax.jit(PP.descent_round)(
        pool, addr, khi, klo, act)
    assert ok.all() and not np.asarray(is_leaf).any()
    np.testing.assert_array_equal(np.asarray(nxt), [2, 2, 2, 3])

    # round at leaf 2: in-fence keys resolve, 200 chases the sibling
    addr = np.full(4, 2, np.int32)
    nxt, is_leaf, chase, ok, found, vhi, vlo = jax.jit(PP.descent_round)(
        pool, addr, khi, klo, act)
    np.testing.assert_array_equal(np.asarray(is_leaf), [1, 1, 1, 0])
    np.testing.assert_array_equal(np.asarray(chase), [0, 0, 0, 1])
    assert int(np.asarray(nxt)[3]) == 3
    np.testing.assert_array_equal(np.asarray(found), [1, 1, 0, 0])
    got = bits.pairs_to_keys(np.asarray(vhi), np.asarray(vlo))
    np.testing.assert_array_equal(got[:2], [70, 500])


# ---------------------------------------------------------------------------
# Kernel 3: snapshot gather.
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("seed,M,P", [(0, 256, 32), (1, 300, 64),
                                      (2, 16, 16)])
def test_gather_pages_fuzz_bit_identity(seed, M, P):
    rng = np.random.default_rng(seed)
    pool = _rand_words(rng, (P, C.PAGE_WORDS))
    rows = _mixed_addrs(rng, M, P)
    got = jax.jit(PP.gather_pages)(pool, rows)
    want = jax.jit(PP.gather_pages_xla)(pool, rows)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_read_pages_local_matches_dsm_contract():
    """read_pages_local == the single-node read_pages_spmd branch
    (zeroed not-ok rows, ok = active & in-range)."""
    rng = np.random.default_rng(7)
    P, B = 32, 200
    pool = _rand_words(rng, (P, C.PAGE_WORDS))
    addrs = _mixed_addrs(rng, B, P)
    active = rng.integers(0, 2, B).astype(bool)
    pages, ok = jax.jit(PP.read_pages_local)(pool, addrs, active)
    page = np.asarray(bits.addr_page(addrs))
    ok_w = active & (page >= 0) & (page < P)
    want = np.where(ok_w[:, None], pool[np.clip(page, 0, P - 1)], 0)
    np.testing.assert_array_equal(np.asarray(ok), ok_w)
    np.testing.assert_array_equal(np.asarray(pages), want)


# ---------------------------------------------------------------------------
# Kernel 2: multi-lane write-back.
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("seed,M,P,lanes", [
    (0, 256, 64, (C.L_VER_W, C.L_VHI_W, C.L_VLO_W)),             # update
    (1, 300, 32, (C.L_VER_W, C.L_KHI_W, C.L_KLO_W,
                  C.L_VHI_W, C.L_VLO_W)),                        # insert
    (2, 64, 16, (C.L_VER_W,)),                                   # delete
])
def test_writeback_fuzz_bit_identity(seed, M, P, lanes):
    rng = np.random.default_rng(seed)
    L = len(lanes)
    pool = _rand_words(rng, (P, C.PAGE_WORDS))
    # applied rows carry unique (page, slot) and in-range slots — the
    # apply kernels' contract (found/ranked slots are always in-page)
    page = rng.integers(0, P, M).astype(np.int32)
    slot = rng.integers(0, C.LEAF_CAP, M).astype(np.int32)
    applied = rng.integers(0, 2, M).astype(bool)
    seen = set()
    for i in range(M):
        if applied[i]:
            if (int(page[i]), int(slot[i])) in seen:
                applied[i] = False
            else:
                seen.add((int(page[i]), int(slot[i])))
    ent = _rand_words(rng, (M, L))
    got = jax.jit(lambda *a: PP.writeback(*a, field_w=lanes))(
        pool, page, slot, applied, ent)
    want = jax.jit(lambda *a: PP.writeback_xla(*a, field_w=lanes))(
        pool, page, slot, applied, ent)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    # the pass really wrote something (fuzz sanity, not a tautology)
    assert applied.any() and not np.array_equal(np.asarray(got), pool)


def test_writeback_idempotent_duplicates():
    """Delete-style duplicates (same target, same value) are legal and
    land the value once — the delete kernel's no-dedup contract."""
    P, M = 16, 256
    pool = np.ones((P, C.PAGE_WORDS), np.int32)
    page = np.full(M, 3, np.int32)
    slot = np.full(M, 5, np.int32)
    applied = np.ones(M, bool)
    ent = np.zeros((M, 1), np.int32)
    out = np.asarray(jax.jit(
        lambda *a: PP.writeback(*a, field_w=(C.L_VER_W,)))(
        pool, page, slot, applied, ent))
    want = pool.copy()
    want[3, C.L_VER_W + 5] = 0
    np.testing.assert_array_equal(out, want)


# ---------------------------------------------------------------------------
# Knob plumbing + obs receipts.
# ---------------------------------------------------------------------------

def test_gather_impl_knob_validated():
    with pytest.raises(AssertionError):
        DSMConfig(gather_impl="bogus")


def test_use_pallas_unavailable_names_the_knob(monkeypatch):
    monkeypatch.setattr(PP, "HAVE_PALLAS", False)
    cfg = DSMConfig(gather_impl="pallas")
    with pytest.raises(PP.PallasUnavailableError) as ei:
        PP.use_pallas(cfg)
    msg = str(ei.value)
    assert "gather_impl" in msg and "xla" in msg
    assert PP.use_pallas(DSMConfig()) is False  # default never raises


def test_kernels_obs_counters_count_traces():
    before = obs.snapshot()
    jax.jit(PP.gather_pages)(np.zeros((16, C.PAGE_WORDS), np.int32),
                             np.zeros(8, np.int32))
    after = obs.snapshot()
    assert (after.get("kernels.snapshot_gathers_traced", 0)
            > before.get("kernels.snapshot_gathers_traced", 0))
    assert (after.get("kernels.snapshot_rows_per_gather", 0)
            >= before.get("kernels.snapshot_rows_per_gather", 0) + 8)


# ---------------------------------------------------------------------------
# Engine-level CI pin: both impls produce bit-identical pools/results.
# ---------------------------------------------------------------------------

def _build_engine(impl, n_nodes=1):
    from sherman_tpu.cluster import Cluster
    from sherman_tpu.models import batched
    from sherman_tpu.models.btree import Tree
    cfg = DSMConfig(machine_nr=n_nodes, pages_per_node=512 // n_nodes,
                    locks_per_node=256, step_capacity=256,
                    chunk_pages=32, gather_impl=impl)
    cluster = Cluster(cfg)
    tree = Tree(cluster)
    eng = batched.BatchedEngine(tree, batch_per_node=256 // n_nodes,
                                tcfg=TreeConfig(sibling_chase_budget=2))
    return tree, eng


def test_engine_pool_bit_identity_xla_vs_pallas(eight_devices):
    """The CI pin the knob rests on: the same workload (bulk load,
    splits, updates, deletes, mixed) leaves BIT-IDENTICAL pools and
    results under both gather impls."""
    from sherman_tpu.models import batched
    rng = np.random.default_rng(11)
    keys = np.unique(rng.integers(1, 1 << 62, 700, dtype=np.uint64))[:600]
    vals = keys ^ np.uint64(0xBEEF)
    pools, results = {}, {}
    for impl in ("xla", "pallas"):
        tree, eng = _build_engine(impl)
        batched.bulk_load(tree, keys[:400], vals[:400])
        eng.attach_router()
        st = eng.insert(keys[400:], vals[400:])     # forces device splits
        assert st["applied"] == 200
        v, f = eng.search(keys)
        ov, of, ost = eng.mixed(keys[:128], vals[:128] ^ np.uint64(3),
                                np.arange(128) % 2 == 0)
        d = eng.delete(keys[:40])
        pools[impl] = np.asarray(tree.dsm.pool)
        results[impl] = (v, f, ov, of, ost, d)
    np.testing.assert_array_equal(pools["xla"], pools["pallas"])
    for a, b in zip(results["xla"], results["pallas"]):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert results["xla"][1].all()


@pytest.mark.slow
def test_engine_pool_bit_identity_multinode(eight_devices):
    """Same pin over the 4-node mesh (owner-side pallas gathers under
    the routed exchanges)."""
    from sherman_tpu.models import batched
    rng = np.random.default_rng(13)
    keys = np.unique(rng.integers(1, 1 << 62, 700, dtype=np.uint64))[:600]
    vals = keys ^ np.uint64(0x5A)
    pools = {}
    for impl in ("xla", "pallas"):
        tree, eng = _build_engine(impl, n_nodes=4)
        batched.bulk_load(tree, keys[:500], vals[:500])
        eng.attach_router()
        eng.insert(keys[500:], vals[500:])
        v, f = eng.search(keys)
        assert f.all() and (v == vals).all()
        pools[impl] = np.asarray(tree.dsm.pool)
    np.testing.assert_array_equal(pools["xla"], pools["pallas"])


def test_interpret_mode_never_on_a_tpu(monkeypatch):
    """Interpreter off the chip only: on a TPU backend the default is
    compiled, and an explicit interpret=True is refused, not obeyed."""
    monkeypatch.setattr(PP.jax, "default_backend", lambda: "tpu")
    assert PP.interpret_mode() is False
    assert PP.interpret_mode(False) is False
    with pytest.raises(ValueError):
        PP.interpret_mode(True)
    monkeypatch.setattr(PP.jax, "default_backend", lambda: "cpu")
    assert PP.interpret_mode() is True
