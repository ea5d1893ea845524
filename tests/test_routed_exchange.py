"""The routed read exchange on four nodes (4 of the suite's 8 CPU
devices): round 1's destination buckets are sized from the rows the call
carries (``D.spread_capacity``), a row whose bucket is full is answered
by the straggler loop and counted in ``dsm.xchg_overflow_rows``, every
answer matches the benchmark's plain reference, and the staged read
serve holds no buffer of N x ``step_capacity`` pages."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import reference, traffic
from sherman_tpu.ops import bits
from sherman_tpu.parallel import dsm as D

N = 4
SALT = 0x5E17_AB1E_5A17
XOR = 0xDEADBEEF
N_KEYS = 200_000
STEP_CAP = 131_072     # the engine's step capacity (batch per node)
R = 8192               # unique rows per node a call carries
ACTIVE = 4000          # of which active
CLIENTS = 8192         # client slots per node


def _engine():
    from sherman_tpu.cluster import build_engine, pages_for_keys
    from sherman_tpu.models import batched
    pages = pages_for_keys(N_KEYS, 0.75)
    _, tree, eng = build_engine(N, pages // N, STEP_CAP,
                                chunk_pages=pages // N // 4)
    keys = np.sort(traffic.rank_keys(np.arange(N_KEYS), SALT))
    batched.bulk_load(tree, keys, keys ^ np.uint64(XOR), fill=0.75)
    eng.attach_router()
    return eng


def _requests(eng, seed):
    """Per node: ACTIVE distinct keys (a tenth not loaded) in R unique
    rows, and CLIENTS clients each reading one of them (GLOBAL index)."""
    rng = np.random.default_rng(seed)
    keys = np.zeros((N, R), np.uint64)
    for n in range(N):
        ranks = rng.choice(N_KEYS + N_KEYS // 10, ACTIVE, replace=False)
        keys[n, :ACTIVE] = traffic.rank_keys(ranks, SALT)
        keys[n, ACTIVE:] = keys[n, 0]
    active = np.zeros((N, R), bool)
    active[:, :ACTIVE] = True
    inv = (rng.integers(0, ACTIVE, (N, CLIENTS))
           + (np.arange(N) * R)[:, None]).astype(np.int32)
    khi, klo = bits.keys_to_pairs(keys.reshape(-1))
    start = eng.router.host_start(khi, klo)
    return keys.reshape(-1), khi, klo, active.reshape(-1), start, \
        inv.reshape(-1)


def _serve(eng, seed):
    """One call of the staged read serve (node-local fan-out) ->
    (client keys, values, found, unique rows done, counter deltas)."""
    keys, khi, klo, active, start, inv = _requests(eng, seed)
    fn = eng._get_search_fanout(eng._iters(), local=True)
    before = eng.dsm.counter_snapshot()
    counters, done, found, vhi, vlo = fn(
        eng.dsm.pool, eng.dsm.counters, eng._shard(khi), eng._shard(klo),
        np.int32(eng.tree._root_addr), eng._shard(active),
        eng._shard(start), eng._shard(inv))
    eng.dsm.counters = counters
    after = eng.dsm.counter_snapshot()
    delta = {k: after[k] - before[k] for k in after}
    vals = bits.pairs_to_keys(np.asarray(vhi), np.asarray(vlo))
    return (keys[inv], vals, np.asarray(found), np.asarray(done)[active],
            delta, active, start)


def _bucket_counts(active, start):
    """[node, destination] active rows of round 1."""
    dest = np.asarray(bits.addr_node(jnp.asarray(start)))
    a, d = active.reshape(N, R), dest.reshape(N, R)
    return np.array([[int((a[n] & (d[n] == m)).sum()) for m in range(N)]
                     for n in range(N)])


@pytest.fixture(scope="module")
def engine(eight_devices):
    return _engine()


def test_spread_capacity_rule():
    # the cell: 1,572,864 unique rows a node over four nodes, engine
    # step capacity 4,194,304 -> 2 % over 393,216, rounded up to 8,192,
    # plus 16,384
    assert D.spread_capacity(1_572_864, 4, 4_194_304) == 417_792
    assert D.spread_capacity(8192, 4, STEP_CAP) == 8192       # <= rows
    assert D.spread_capacity(1_572_864, 4, 300_000) == 300_000  # <= cap
    assert D.spread_capacity(65_536, 4, STEP_CAP) == 40_960


def _check_answers(cli_keys, vals, found):
    want_v, want_f = reference.lookup(cli_keys, n_keys=N_KEYS, salt=SALT,
                                      value_xor=XOR)
    assert want_f.any() and not want_f.all()
    assert reference.count_wrong(cli_keys, vals, found, want_v,
                                 want_f) == 0


def test_routed_read_four_nodes_matches_reference(engine):
    cli_keys, vals, found, done, delta, active, start = _serve(engine, 1)
    assert done.all()
    _check_answers(cli_keys, vals, found)
    counts = _bucket_counts(active, start)
    assert counts.max() <= D.spread_capacity(R, N, STEP_CAP)
    assert delta["xchg_overflow_rows"] == 0
    # round 1's rows on another node, plus any straggler's
    remote1 = int(counts.sum() - np.trace(counts))
    assert remote1 > 0
    assert remote1 <= delta["xchg_remote_rows"] <= delta["read_ops"]


def test_full_round1_buckets_ride_the_straggler_loop(eight_devices,
                                                     monkeypatch):
    """Round 1's capacity forced under the largest bucket: the rows
    that find it full are answered by the loop, and counted exactly."""
    eng = _engine()    # a fresh program, traced under the patch
    _, _, _, active, start, _ = _requests(eng, 2)
    counts = _bucket_counts(active, start)
    cap = int(counts.max()) - 64
    monkeypatch.setattr(D, "spread_capacity", lambda rows, n, c: cap)
    cli_keys, vals, found, done, delta, *_ = _serve(eng, 2)
    over = int(np.maximum(counts - cap, 0).sum())
    assert over > 0
    assert done.all()
    _check_answers(cli_keys, vals, found)
    assert delta["xchg_overflow_rows"] == over


def test_staged_read_serve_sizes_round1_from_rows(engine):
    """The staged read serve (the cell's program) lowered at N = 4: no
    [N x step_capacity, 256] page buffer, and no all-to-all carries
    more than N x the derived capacity of its unique rows."""
    from sherman_tpu.workload.device_prep import make_staged_step
    dev_b = 65_536
    step, _ = make_staged_step(
        engine, n_keys=N_KEYS, theta=0.99, salt=SALT, batch=STEP_CAP,
        dev_b=dev_b, log2_bins=16, fusion="aligned")
    cap = D.spread_capacity(dev_b, N, STEP_CAP)
    assert cap == 40_960 < dev_b
    shard = engine.dsm.shard
    S = jax.ShapeDtypeStruct
    txt = step.jserve.lower(
        engine.dsm.pool, engine.dsm.counters,
        S((N * dev_b,), jnp.int32, sharding=shard),
        S((N * dev_b,), jnp.int32, sharding=shard),
        np.int32(engine.tree._root_addr),
        S((N * dev_b,), jnp.bool_, sharding=shard),
        S((N * dev_b,), jnp.int32, sharding=shard),
        S((N * STEP_CAP,), jnp.int32, sharding=shard)).as_text()
    assert f"tensor<{N * STEP_CAP}x256xi32>" not in txt
    assert f"tensor<{N * cap}x256xi32>" in txt
    rows = [int(m) for m in re.findall(
        r"all_to_all.*?: \(tensor<(\d+)x", txt)]
    assert rows and max(rows) == N * cap
    # the node-local fan-out: no answer-table all-gather
    assert "all_gather" not in txt


# sha256 of each one-node serve program's lowered text (20,000 keys,
# 2,048 rows, the mixed step at 1,024 + 1,024 rows) with the counters'
# length written as ``C``: the text these programs had before the
# exchange's two counter slots were added.  A change to a one-node serve
# program updates its digest and says why.
ONE_NODE_HLO = {
    "read": "3dee6af113b8acc24433c60256a1e3ca27b334ffeef095bbaececfd8e67ce764",
    "packed": "8502566d580a09b2e0494e4fbe966f02e33e7fdd45944b8ea5bbaba097122d51",
    "mixed": "61ec12f123d302f6a29bf4b3c782cb43019784e20425000c6bec6eaa008471ca",
}


@pytest.fixture(scope="module")
def one_node_texts(eight_devices):
    """Lowered text of the one-node read, packed read and mixed serve
    programs (the mixed step run once to record its arguments)."""
    from sherman_tpu.cluster import Cluster
    from sherman_tpu.config import DSMConfig
    from sherman_tpu.models import batched
    from sherman_tpu.models.btree import Tree
    from sherman_tpu.workload.device_prep import make_staged_mixed_step

    n_keys, b = 20_000, 2048
    cfg = DSMConfig(machine_nr=1, pages_per_node=2048, locks_per_node=512,
                    step_capacity=b, chunk_pages=32)
    tree = Tree(Cluster(cfg))
    eng = batched.BatchedEngine(tree, batch_per_node=b)
    keys = bits.mix64_np(np.arange(n_keys, dtype=np.uint64)
                         ^ np.uint64(SALT))
    order = np.argsort(keys)
    batched.bulk_load(tree, keys[order], (keys ^ np.uint64(XOR))[order],
                      fill=0.8)
    eng.attach_router()
    S, shard, it = jax.ShapeDtypeStruct, eng.dsm.shard, eng._iters()
    v = lambda dt=jnp.int32: S((b,), dt, sharding=shard)  # noqa: E731
    dsm = eng.dsm
    texts = {
        "read": eng._get_search_fanout(it).lower(
            dsm.pool, dsm.counters, v(), v(), np.int32(0), v(jnp.bool_),
            v(), v()).as_text(),
        "packed": eng._get_search_fanout_packed(it).lower(
            dsm.pool, dsm.counters, S((b, 5), jnp.int32, sharding=shard),
            np.int32(0)).as_text(),
    }
    step, (new_carry, tb, rt, rk) = make_staged_mixed_step(
        eng, n_keys=n_keys, theta=0.99, salt=SALT, batch=b,
        read_ratio=0.5, dev_rb=1024, dev_wb=1024, log2_bins=16,
        fusion="pipelined")
    prog, fn, seen = step.jserve, step.jserve._fn, []

    class Rec:
        def _cache_size(self):
            return fn._cache_size()

        def __call__(self, *args):
            seen.append(jax.tree.map(
                lambda x: S(x.shape, x.dtype, sharding=x.sharding), args))
            return fn(*args)

    prog._fn = Rec()
    try:
        pool, counters, carry = step(dsm.pool, dsm.locks, dsm.counters, tb,
                                     rt, rk, new_carry())
        jax.block_until_ready(step.drain(carry))
        dsm.pool, dsm.counters = pool, counters
    finally:
        prog._fn = fn
    texts["mixed"] = fn.lower(*seen[0]).as_text()
    return texts


@pytest.mark.parametrize("program", sorted(ONE_NODE_HLO))
def test_one_node_serve_programs_differ_only_in_counters(program,
                                                         one_node_texts):
    import hashlib
    txt = one_node_texts[program]
    assert f"tensor<{D.N_COUNTERS}xui32>" in txt
    norm = txt.replace(f"tensor<{D.N_COUNTERS}xui32>", "tensor<Cxui32>")
    assert hashlib.sha256(norm.encode()).hexdigest() == ONE_NODE_HLO[program]


def test_dsm_counters_outlive_the_dsm(eight_devices):
    """The registry's ``dsm.*`` entries still read the last counters
    after the DSM is gone, as the benchmark's readers read them once the
    loop has let its cluster go."""
    import gc
    import weakref

    from sherman_tpu import obs
    from sherman_tpu.config import DSMConfig

    dsm = D.DSM(DSMConfig(machine_nr=N, pages_per_node=64,
                          locks_per_node=256, step_capacity=64))
    dsm.read_pages([bits.make_addr(n, 1) for n in range(N)])
    want = dsm.counter_snapshot()
    assert want["read_ops"] == N
    ref = weakref.ref(dsm)
    del dsm
    gc.collect()
    assert ref() is None
    snap = obs.snapshot()
    assert {k: snap[f"dsm.{k}"] for k in want} == want
