"""Serving front door (sherman_tpu/serve.py) fast tier.

The PR 13 contract set: adaptive width controller (frontier pick,
queue-aware breach handling), the shared admission pacer, ingress-step
correctness (request combining + cache merge, bit-identical to the
engine paths), fair-share admission under a greedy tenant, typed
overload/degraded rejects, write-shed brownout with reads still
serving, the journaled-ack crash drill (RPO 0 against the acked-op
ledger, acks/fsync > 1 under concurrent writers), the sealed
zero-retrace pin for the serving loop (aligned + pipelined, cache on
and off), and the perfgate serve-mode comparability rules.
"""

import contextlib
import os
import sys
import threading
import time

import numpy as np
import pytest

from sherman_tpu.cluster import Cluster
from sherman_tpu.config import DSMConfig, TreeConfig
from sherman_tpu.errors import ConfigError, KeyRangeError, StateError
from sherman_tpu.models import batched
from sherman_tpu.models.batched import DegradedError
from sherman_tpu.models.btree import Tree
from sherman_tpu.serve import (ServeConfig, ServeFuture,
                               ServeOverloadError, ShermanServer,
                               WidthController)
from sherman_tpu.utils import journal as J
from sherman_tpu.workload.device_prep import make_ingress_step

sys.path.insert(0, os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "tools"))


def make(n=3000, B=256, pages=2048, cap=1024, step=3, nodes=1):
    cfg = DSMConfig(machine_nr=nodes, pages_per_node=pages,
                    locks_per_node=512, step_capacity=cap,
                    chunk_pages=32)
    cluster = Cluster(cfg)
    tree = Tree(cluster)
    keys = np.arange(100, 100 + n * step, step, dtype=np.uint64)
    vals = keys * np.uint64(7)
    batched.bulk_load(tree, keys, vals)
    eng = batched.BatchedEngine(tree, batch_per_node=B,
                                tcfg=TreeConfig(sibling_chase_budget=2))
    eng.attach_router()
    return tree, eng, keys, vals


def targets(ms=10_000.0):
    return {c: ms for c in ("read", "scan", "insert", "delete")}


@contextlib.contextmanager
def serving(eng, keys, vals, *, widths=(128, 512), journal=None,
            calibrate=True, auditor=None, **cfgkw):
    cfg = ServeConfig(widths=widths,
                      p99_targets_ms=cfgkw.pop("p99_targets_ms",
                                               targets()),
                      **cfgkw)
    srv = ShermanServer(eng, cfg, journal=journal, auditor=auditor)
    try:
        if calibrate:
            srv.start(calib_keys=keys,
                      calib_writes=(keys[:64], vals[:64]),
                      calib_delete_keys=np.asarray([5], np.uint64))
        else:
            srv.start()
        yield srv
    finally:
        srv.stop()


# -- width controller (pure units) --------------------------------------------

def test_controller_pick_frontier():
    c = WidthController((128, 512, 2048), target_p99_ms=10.0,
                        model_mult=2.0)
    c.seed(128, 1.0)    # est p99 2 ms
    c.seed(512, 3.0)    # est 6 ms
    c.seed(2048, 9.0)   # est 18 ms — infeasible
    # deep backlog: largest FEASIBLE rung, not the largest rung
    assert c.pick(10**9) == 512
    # shallow backlog: don't overshoot — smallest feasible that covers
    assert c.pick(100) == 128
    # nothing feasible: narrowest rung (lowest latency)
    c2 = WidthController((128, 512), target_p99_ms=0.5)
    c2.seed(128, 1.0)
    c2.seed(512, 2.0)
    assert c2.pick(10**9) == 128
    # unmeasured ladder: narrowest rung
    c3 = WidthController((128, 512), target_p99_ms=10.0)
    assert c3.pick(10**9) == 128


def test_controller_breach_queue_attribution():
    c = WidthController((128, 512, 2048), target_p99_ms=10.0,
                        model_mult=2.0, hold_steps=4)
    for w in (128, 512, 2048):
        c.seed(w, 1.0)
    assert c.pick(10**9) == 2048
    # queue-dominated breach must NOT downshift (narrower width would
    # deepen the queue that caused it)
    c.note_window_p99(100.0, queue_dominated=True)
    assert c.downshifts == 0 and c.pick(10**9) == 2048
    # service-dominated breach steps the cap down one rung and holds
    c.note_window_p99(100.0, queue_dominated=False)
    assert c.downshifts == 1
    assert c.pick(10**9) == 512
    # hold expires through update()s, cap probes back up one rung
    for _ in range(5):
        c.update(512, 1.0)
    assert c.pick(10**9) == 2048
    assert c.settled_width() in (512, 2048)
    snap = c.snapshot()
    assert snap["downshifts"] == 1 and snap["target_p99_ms"] == 10.0


def test_controller_ewma_update():
    c = WidthController((128,), target_p99_ms=10.0, ewma=0.5)
    c.seed(128, 2.0)
    c.update(128, 4.0)
    assert c.wall_ms[128] == pytest.approx(3.0)


# -- shared admission pacer ---------------------------------------------------

def test_admission_pacer_receipt():
    from common import AdmissionPacer
    p = AdmissionPacer(0.002, spin_ms=0.5)
    p.start(lead_periods=1)
    for i in range(20):
        err = p.wait_turn(i)
        assert err >= 0
    r = p.jitter_receipt()
    assert r["pacing"] == "sleep+spin"
    assert r["adm_jitter_p99_ms"] >= r["adm_jitter_p50_ms"] >= 0
    # spin budget duty-cycle bound: never more than half the period
    assert p.spin_ns <= 0.5 * p.period_ns
    # merge: errors accumulate
    p2 = AdmissionPacer(0.002)
    p2.start()
    p2.wait_turn(0)
    n0 = len(p.errors_ns)
    p.merge_errors(p2)
    assert len(p.errors_ns) == n0 + 1


def test_pacer_absorb_stall_is_capped():
    from common import AdmissionPacer
    p = AdmissionPacer(0.001)
    p.start(lead_periods=0)
    base0 = p._t_base
    time.sleep(0.02)  # fall far behind
    p.absorb_stall(1, cap_ns=2_000_000)  # forgive at most 2 ms
    assert 0 < p._t_base - base0 <= 2_000_000


def test_latency_bench_shares_pacer():
    # the extraction satellite: latency_bench must import the SHARED
    # pacer, not carry its own copy of the spin loop
    import pathlib
    src = (pathlib.Path(__file__).parent.parent / "tools"
           / "latency_bench.py").read_text()
    assert "AdmissionPacer" in src
    assert "while True:\n                now = time.perf_counter_ns()" \
        not in src


# -- ingress step -------------------------------------------------------------

def test_ingress_step_combines_and_answers(eight_devices):
    tree, eng, keys, vals = make()
    step = make_ingress_step(eng, width=256)
    rng = np.random.default_rng(3)
    # duplicates share one descent row; every client row still answers
    kreq = keys[rng.integers(0, keys.size, 200)]
    got, found = step(kreq)
    assert found.all()
    np.testing.assert_array_equal(got, kreq * np.uint64(7))
    # missing keys report found=False
    miss = np.asarray([7, 11], np.uint64)  # absent (keys start at 100)
    got, found = step(np.concatenate([kreq[:10], miss]))
    assert found[:10].all() and not found[10:].any()
    # split dispatch/complete round trip
    h = step.dispatch(kreq[:50])
    got, found = step.complete(h)
    assert found.all() and got.shape == (50,)


def test_ingress_step_cache_bit_identical(eight_devices):
    tree, eng, keys, vals = make()
    kreq = np.concatenate([keys[:100], keys[:100], keys[500:600]])
    base = make_ingress_step(eng, width=512)(kreq)
    lc = eng.attach_leaf_cache(slots=1024)
    lc.fill(keys[:200])
    cached = make_ingress_step(eng, width=512, leaf_cache=lc)(kreq)
    np.testing.assert_array_equal(base[0], cached[0])
    np.testing.assert_array_equal(base[1], cached[1])
    assert lc.hits > 0
    eng.detach_leaf_cache()


def test_ingress_matches_engine_search_combined(eight_devices):
    """The ingress step and BatchedEngine.search_combined implement
    one combine/probe/fan-out/rescue/merge protocol at two width
    regimes — this pin is what keeps the two copies from diverging
    (see the make_ingress_step docstring note)."""
    tree, eng, keys, vals = make()
    rng = np.random.default_rng(9)
    kreq = np.concatenate([keys[rng.integers(0, keys.size, 300)],
                           np.asarray([7, 11], np.uint64)])  # + misses
    for cached in (False, True):
        if cached:
            lc = eng.attach_leaf_cache(slots=1024)
            lc.fill(keys[:200])
        step = make_ingress_step(eng, width=512,
                                 leaf_cache=eng.leaf_cache)
        got_i, found_i = step(kreq)
        got_e, found_e = eng.search_combined(kreq)
        np.testing.assert_array_equal(found_i, found_e)
        np.testing.assert_array_equal(got_i[found_i], got_e[found_e])
        if cached:
            eng.detach_leaf_cache()


def test_ingress_step_validates_width(eight_devices):
    tree, eng, keys, vals = make()
    with pytest.raises(ConfigError):
        make_ingress_step(eng, width=0)
    eng2 = batched.BatchedEngine(tree, batch_per_node=64)
    with pytest.raises(ConfigError):
        make_ingress_step(eng2, width=128)  # no router attached


def unpacked_ingress(eng, width, keys, leaf_cache=None):
    """The ingress step at an unpacked boundary, the reference the
    packed step must match bit for bit: five puts and the root scalar
    into ``_get_search_fanout``, four arrays back, the same combine,
    cache merge and straggler rescue."""
    from sherman_tpu.ops import bits
    n = keys.shape[0]
    uk, inv = np.unique(keys, return_inverse=True)
    U = uk.shape[0]
    khi = np.zeros(width, np.int32)
    klo = np.zeros(width, np.int32)
    khi[:U], klo[:U] = bits.keys_to_pairs(uk)
    active = np.zeros(width, bool)
    active[:U] = True
    inv_p = np.zeros(width, np.int32)
    inv_p[:n] = inv
    chit = None
    if leaf_cache is not None:
        chit, cvhi, cvlo = leaf_cache.probe(khi, klo, active)
        active &= ~chit
    start = eng.router.host_start(khi, klo)
    fn = eng._get_search_fanout(eng._iters())
    eng.dsm.counters, done, found, vhi, vlo = fn(
        eng.dsm.pool, eng.dsm.counters, eng._shard(khi), eng._shard(klo),
        np.int32(eng.tree._root_addr), eng._shard(active),
        eng._shard(start), eng._shard(inv_p))
    done, found, vhi, vlo = eng._unshard(done, found, vhi, vlo)
    done_u = done[:U] if chit is None else done[:U] | chit[:U]
    if not done_u.all():
        v, f = eng.search(uk)
        return v[inv], f[inv]
    vals = bits.pairs_to_keys(vhi[:n], vlo[:n])
    fnd = np.array(found[:n])
    if chit is not None:
        ch = chit[:U][inv]
        fnd[ch] = True
        vals[ch] = bits.pairs_to_keys(cvhi[:U], cvlo[:U])[inv][ch]
    return vals, fnd


def ingress_batches(keys, width):
    """One key, a partial batch and a full one; the wider two with
    duplicates, two absent keys, and cached keys (``keys[:200]``)."""
    rng = np.random.default_rng(width)
    dup = np.concatenate([keys[rng.integers(0, 200, width // 4)],
                          keys[rng.integers(0, keys.size, width)]])
    miss = np.asarray([7, 11], np.uint64)  # absent (keys start at 100)
    return {"one": keys[-1:],
            "partial": np.concatenate([dup[:width // 3], miss]),
            "full": np.concatenate([miss, dup[:width - 2]])}


@pytest.mark.parametrize("cached", (False, True), ids=("nocache", "cache"))
@pytest.mark.parametrize("width", (256, 512))
def test_packed_ingress_bit_identical(eight_devices, width, cached):
    """The packed ingress answers every batch — one key, partial, full
    width; duplicates, misses, and with stale router seeds a forced
    straggler rescue — exactly as the unpacked serve does, and as
    ``search_combined`` does, with the leaf cache off and on."""
    from sherman_tpu import obs
    tree, eng, keys, vals = make()
    lc = None
    if cached:
        lc = eng.attach_leaf_cache(slots=1024)
        lc.fill(keys[:200])
    step = make_ingress_step(eng, width=width, leaf_cache=lc)
    rescues = obs.counter("serve.rescues")
    table = eng.router.table_np.copy()
    try:
        for stale in (False, True):
            if stale:
                eng.router.table_np[:] = table[0]  # every seed far left
            r0 = rescues.value
            for name, kreq in ingress_batches(keys, width).items():
                got, found = step(kreq)
                want = unpacked_ingress(eng, width, kreq, lc)
                np.testing.assert_array_equal(got, want[0], err_msg=name)
                np.testing.assert_array_equal(found, want[1], err_msg=name)
                got_e, found_e = eng.search_combined(kreq)
                np.testing.assert_array_equal(found, found_e, err_msg=name)
                np.testing.assert_array_equal(got[found], got_e[found_e])
                absent = np.isin(kreq, np.asarray([7, 11], np.uint64))
                assert (found == ~absent).all(), name
            assert rescues.value - r0 == (3 if stale else 0)
    finally:
        eng.router.table_np[:] = table
    if cached:
        assert lc.hits > 0
        eng.detach_leaf_cache()


def test_packed_ingress_one_put_no_implicit_transfer(eight_devices,
                                                     monkeypatch):
    """``dispatch`` moves its batch in one explicit put and nothing
    implicitly (it runs under ``transfer_guard("disallow")``); the root
    it hands the serve is device-resident, put again, explicitly, only
    when the tree's root moves.  Each step counts one put and one get."""
    import jax
    from sherman_tpu import obs
    from sherman_tpu.workload import device_prep
    tree, eng, keys, vals = make()
    step = make_ingress_step(eng, width=256)
    kreq = keys[:100]
    step(kreq)                     # compiles outside the guard
    puts, roots = [], []
    shard, rep_put = eng._shard, device_prep._rep_put
    monkeypatch.setattr(eng, "_shard",
                        lambda x: puts.append(x.shape) or shard(x))
    monkeypatch.setattr(device_prep, "_rep_put",
                        lambda dsm, x: roots.append(int(x))
                        or rep_put(dsm, x))
    names = ("serve.h2d_puts", "serve.d2h_gets", "serve.answer_copy_ready")
    c0 = {k: obs.counter(k).value for k in names}
    for moved in (False, False, True):
        if moved:
            monkeypatch.setattr(tree, "_root_addr", tree._root_addr + 1)
        with jax.transfer_guard("disallow"):
            h = step.dispatch(kreq)
        got, found = step.complete(h)
        assert found.all()
        np.testing.assert_array_equal(got, kreq * np.uint64(7))
    assert puts == [(256, 5)] * 3
    assert roots == [tree._root_addr]      # the moved root, once
    d = {k: obs.counter(k).value - c0[k] for k in names}
    assert d["serve.h2d_puts"] == d["serve.d2h_gets"] == 3
    assert 0 <= d["serve.answer_copy_ready"] <= 3
    h = step.dispatch(kreq)
    step.drain(h)
    assert obs.counter("serve.d2h_gets").value - c0["serve.d2h_gets"] == 4


def test_packed_ingress_four_nodes(eight_devices):
    """On four nodes the packed [W, 5] batch and the [W, 4] answer
    table shard on the batch axis as the separate arrays did, and the
    answers equal the unpacked serve's and ``search_combined``'s."""
    from jax.sharding import PartitionSpec
    from sherman_tpu.parallel.mesh import AXIS
    tree, eng, keys, vals = make(B=64, pages=1024, nodes=4)
    width = 256
    step = make_ingress_step(eng, width=width)
    kreq = ingress_batches(keys, width)["full"]
    puts, shard = [], eng._shard
    eng._shard = lambda x: puts.append(shard(x)) or puts[-1]
    try:
        h = step.dispatch(kreq)
    finally:
        del eng._shard
    (packed,) = puts
    blocks = [(i * 64, (i + 1) * 64) for i in range(4)]
    for arr, cols in ((packed, 5), (h[4], 4)):     # the batch, the answers
        assert arr.shape == (width, cols)
        assert arr.sharding.spec == PartitionSpec(AXIS)
        assert sorted((s.index[0].start, s.index[0].stop)
                      for s in arr.addressable_shards) == blocks
    got, found = step.complete(h)
    want = unpacked_ingress(eng, width, kreq)
    np.testing.assert_array_equal(got, want[0])
    np.testing.assert_array_equal(found, want[1])
    got_e, found_e = eng.search_combined(kreq)
    np.testing.assert_array_equal(found, found_e)
    np.testing.assert_array_equal(got[found], got_e[found_e])
    assert found.sum() == width - 2


# -- serving basics -----------------------------------------------------------

def test_serve_reads_writes_scans(eight_devices):
    tree, eng, keys, vals = make()
    with serving(eng, keys, vals) as srv:
        rng = np.random.default_rng(0)
        futs = []
        for i in range(12):
            kreq = keys[rng.integers(0, keys.size, 100)]
            futs.append((srv.submit("read", kreq,
                                    tenant=f"t{i % 3}"), kreq))
        for f, kreq in futs:
            got, found = f.result(timeout=60)
            assert found.all()
            np.testing.assert_array_equal(got, kreq * np.uint64(7))
        # write then read-your-write (sequenced through the ack)
        ok = srv.submit("insert", keys[:8],
                        keys[:8] ^ np.uint64(0xAB)).result(timeout=60)
        assert ok.all()
        got, found = srv.submit("read", keys[:8]).result(timeout=60)
        assert found.all()
        np.testing.assert_array_equal(got, keys[:8] ^ np.uint64(0xAB))
        # delete
        fnd = srv.submit("delete", keys[:4]).result(timeout=60)
        assert fnd.all()
        got, found = srv.submit("read", keys[:4]).result(timeout=60)
        assert not found.any()
        # scan
        res = srv.submit("scan", ranges=[(int(keys[10]),
                                          int(keys[20]))]
                         ).result(timeout=60)
        assert len(res) == 1 and len(res[0][0]) == 10  # [lo, hi)
        # telemetry: the serve. collector carries the window
        from sherman_tpu import obs
        snap = obs.snapshot()
        assert "serve.read.p99_ms" in snap
        assert snap["serve.served_ops"] > 0
    # submit after stop is a typed StateError
    with pytest.raises(StateError):
        srv.submit("read", keys[:4])


def test_serve_request_records_and_dispatcher_spans(eight_devices, tmp_path):
    """Every answered read records its step, dispatch and answer times;
    ``serve.queue_wait_ms`` and ``serve.service_ms`` take one value per
    answered read request.  The dispatcher's spans are hot: untraced, an
    idle or a serving dispatcher records none; under a profiler trace
    they carry step ids, and an idle stretch is one ``serve.idle`` span
    however many wake-ups it takes."""
    import jax
    from sherman_tpu import obs
    tree, eng, keys, vals = make()
    hq = obs.histogram("serve.queue_wait_ms")
    hs = obs.histogram("serve.service_ms")
    tr = obs.get_tracer()

    def serve_counts():
        return {k: v["n"] for k, v in tr.summary().items()
                if k.startswith("serve.")}

    with serving(eng, keys, vals) as srv:
        untraced = serve_counts()
        srv.submit("read", keys[:8]).result(timeout=60)
        time.sleep(0.05)                     # ~25 idle wake-ups
        assert serve_counts() == untraced
        n0q, n0s = hq.count, hs.count
        before = tr.summary()
        with jax.profiler.trace(str(tmp_path)):
            rng = np.random.default_rng(5)
            futs = [srv.submit("read", keys[rng.integers(0, keys.size, 50)],
                               tenant=f"t{i % 2}") for i in range(20)]
            for f in futs:
                assert f.result(timeout=60)[1].all()
            time.sleep(0.2)                  # ~100 idle wake-ups
        assert srv._thread.name == "sherman-serve-dispatch"
    assert hq.count - n0q == hs.count - n0s == len(futs)
    for f in futs:
        assert isinstance(f.step, int) and f.step >= 0
        assert f.t_submit <= f.t_dispatch <= f.t_answer
    after = tr.summary()
    steps = {f.step for f in futs}

    def grew(name):
        return after.get(name, {"n": 0})["n"] - \
            before.get(name, {"n": 0})["n"]

    for name in ("serve.take", "serve.prep", "serve.prep.combine",
                 "serve.prep.router", "serve.prep.h2d", "serve.launch",
                 "serve.complete", "serve.materialize", "serve.answer"):
        assert grew(name) >= len(steps), name
    assert grew("serve.complete") == len(steps)
    assert 1 <= grew("serve.idle") <= 2 * len(steps) + 2
    # a step's spans share the step id its requests recorded
    evs = tr.chrome_trace()["traceEvents"]
    prep_steps = {e["args"]["step"] for e in evs
                  if e["name"] == "serve.prep"}
    assert steps <= prep_steps
    prep = [e for e in evs if e["name"] == "serve.prep"
            and e["args"]["step"] == futs[0].step][0]
    assert prep["args"]["width"] in (128, 512)
    assert prep["args"]["requests"] >= 1 and prep["args"]["keys"] >= 50


def test_stale_router_step_counts_a_rescue(eight_devices):
    """Router seeds far left of a key's leaf overrun the descent's
    sibling-chase budget: the step's stragglers go through the engine's
    root descent, counted in ``serve.rescues`` / ``serve.rescued_keys``
    (registry counters, so in ``obs.snapshot()``), and every answer is
    still right."""
    from sherman_tpu import obs
    tree, eng, keys, vals = make()
    step = make_ingress_step(eng, width=256)
    kreq = keys[-200:]
    c, ck = obs.counter("serve.rescues"), obs.counter("serve.rescued_keys")
    r0, k0 = c.value, ck.value
    got, found = step(kreq)
    assert c.value == r0      # fresh seeds: no rescue
    router = eng.router
    table = router.table_np.copy()
    router.table_np[:] = table[0]     # every seed at the first leaf
    try:
        got, found = step(kreq)
    finally:
        router.table_np[:] = table
    assert found.all()
    np.testing.assert_array_equal(got, kreq * np.uint64(7))
    assert c.value == r0 + 1
    assert ck.value == k0 + 200
    snap = obs.snapshot()
    assert snap["serve.rescues"] == c.value
    assert snap["serve.rescued_keys"] == ck.value


def test_serve_validates_requests(eight_devices):
    tree, eng, keys, vals = make()
    with serving(eng, keys, vals) as srv:
        with pytest.raises(ConfigError):
            srv.submit("bogus", keys[:4])
        with pytest.raises(KeyRangeError):
            srv.submit("read", np.asarray([0], np.uint64))
        with pytest.raises(ConfigError):
            srv.submit("read", np.zeros(0, np.uint64))
        with pytest.raises(ConfigError):
            srv.submit("read", keys[: 513])  # wider than the ladder
        with pytest.raises(ConfigError):
            srv.submit("scan")


# -- admission: fair share, overload, brownout --------------------------------

def admission_only(eng, **cfgkw):
    """Server with admission OPEN but no dispatcher thread — the
    deterministic shape for queue-policy tests (nothing drains)."""
    cfg = ServeConfig(widths=cfgkw.pop("widths", (128, 512)),
                      p99_targets_ms=targets(), **cfgkw)
    srv = ShermanServer(eng, cfg)
    srv._running = True
    return srv


def test_fair_share_admission_deterministic(eight_devices):
    tree, eng, keys, vals = make()
    srv = admission_only(eng, max_queue_ops=1000)
    # A alone: capped at HALF the queue (a lone flooder must leave a
    # newcomer's share free), so 5 x 100 admit and the 6th rejects
    for _ in range(5):
        srv.submit("read", keys[:100], tenant="A")
    with pytest.raises(ServeOverloadError):
        srv.submit("read", keys[:100], tenant="A")
    # B arrives into its own untouched share
    for _ in range(4):
        srv.submit("read", keys[:100], tenant="B")
    # A stays typed-rejected at its share; B keeps admitting
    with pytest.raises(ServeOverloadError):
        srv.submit("read", keys[:100], tenant="A")
    srv.submit("read", keys[:100], tenant="B")
    st = srv.stats()["tenants"]
    assert st["A"]["rejected_overload"] == 2
    assert st["B"]["rejected_overload"] == 0
    assert st["A"]["queued_ops"] == st["B"]["queued_ops"] == 500
    # total cap is absolute regardless of tenant count
    with pytest.raises(ServeOverloadError):
        srv.submit("read", keys[:500], tenant="C")
    srv._running = False
    srv._fail_queued(StateError("test done"))


def test_brownout_sheds_writes_first(eight_devices):
    tree, eng, keys, vals = make()
    srv = admission_only(eng, max_queue_ops=1000, brownout_hi=0.5,
                         brownout_lo=0.2)
    # fill past the hi mark with reads from two tenants (each within
    # its fair share)
    for t in ("A", "B"):
        for _ in range(3):
            srv.submit("read", keys[:100], tenant=t)
    assert srv._brownout
    # writes shed typed; reads still admitted up to the full cap
    with pytest.raises(ServeOverloadError):
        srv.submit("insert", keys[:10], vals[:10], tenant="C")
    srv.submit("read", keys[:100], tenant="A")
    # drain below lo via the dispatcher's own take path -> brownout
    # exits, writes admit again
    while srv._queued_ops > 100:
        got = srv._take(("read",), 200)
        for r in got:
            r.fut._fail(StateError("drained by test"))
    assert not srv._brownout
    srv.submit("insert", keys[:10], vals[:10], tenant="C")
    srv._running = False
    srv._fail_queued(StateError("test done"))


def test_degraded_sheds_queued_writes_keeps_reads(eight_devices):
    tree, eng, keys, vals = make()
    srv = admission_only(eng)
    wfut = srv.submit("insert", keys[:10], vals[:10], tenant="A")
    rfut = srv.submit("read", keys[:10], tenant="A")
    eng.enter_degraded("test damage")
    # the dispatcher's transition hook fails queued writes typed
    srv._check_degraded_transition()
    with pytest.raises(DegradedError):
        wfut.result(timeout=5)
    assert not rfut.done()  # reads stay queued, not shed
    # new writes reject at the door; reads keep admitting
    with pytest.raises(DegradedError):
        srv.submit("delete", keys[:5], tenant="A")
    srv.submit("read", keys[:5], tenant="A")
    assert srv.stats()["rejects"]["degraded"] >= 2
    eng.exit_degraded()
    srv._running = False
    srv._fail_queued(StateError("test done"))


def test_degraded_live_reads_still_serve(eight_devices):
    tree, eng, keys, vals = make()
    with serving(eng, keys, vals) as srv:
        eng.enter_degraded("live test damage")
        with pytest.raises(DegradedError):
            srv.submit("insert", keys[:4], vals[:4])
        got, found = srv.submit("read", keys[:20]).result(timeout=60)
        assert found.all()
        np.testing.assert_array_equal(got, keys[:20] * np.uint64(7))
        eng.exit_degraded()


def test_greedy_tenant_capped_live(eight_devices):
    tree, eng, keys, vals = make()
    with serving(eng, keys, vals, max_queue_ops=2048) as srv:
        stop = threading.Event()
        greedy_rejects = [0]

        def greedy():
            futs = []
            while not stop.is_set():
                try:
                    futs.append(srv.submit("read", keys[:256],
                                           tenant="greedy"))
                except ServeOverloadError:
                    greedy_rejects[0] += 1
                while len(futs) > 32:
                    futs.pop(0).result(timeout=60)
            for f in futs:
                f.result(timeout=60)

        th = threading.Thread(target=greedy, daemon=True)
        th.start()
        # the polite tenant sees zero rejects while greedy floods
        for _ in range(30):
            got, found = srv.submit("read", keys[:64],
                                    tenant="polite").result(timeout=60)
            assert found.all()
            time.sleep(0.002)
        stop.set()
        th.join(timeout=60)
        st = srv.stats()["tenants"]
        assert greedy_rejects[0] > 0
        assert st["polite"]["rejected_overload"] == 0
        assert st["polite"]["served_ops"] == 30 * 64


# -- client contract: exactly-once, deadlines, weighted shares (PR 15) --------

def test_exactly_once_retry_reacks_never_reapplies(eight_devices):
    """The lost-update kill: a retried rid re-acks the ORIGINAL result
    from the dedup window; a newer write between the original and the
    retry survives (the retry does NOT re-apply)."""
    tree, eng, keys, vals = make()
    with serving(eng, keys, vals) as srv:
        k8 = keys[:8]
        v1 = k8 ^ np.uint64(0xA1)
        ok1 = srv.submit("insert", k8, v1, rid=77,
                         tenant="t").result(timeout=60)
        assert ok1.all()
        v2 = k8 ^ np.uint64(0xB2)
        srv.submit("insert", k8, v2, rid=78,
                   tenant="t").result(timeout=60)
        fut = srv.submit("insert", k8, v1, rid=77, tenant="t")
        okr = fut.result(timeout=60)
        assert fut.deduped and np.array_equal(okr, ok1)
        got, found = srv.submit("read", k8).result(timeout=60)
        assert found.all()
        np.testing.assert_array_equal(got, v2)  # v1 NOT re-applied
        # delete results cache too
        fnd = srv.submit("delete", k8[:2], rid=79,
                         tenant="t").result(timeout=60)
        f2 = srv.submit("delete", k8[:2], rid=79, tenant="t")
        assert f2.deduped and np.array_equal(f2.result(timeout=60),
                                             fnd)
        st = srv.stats()["contract"]
        assert st["dedup_hits"] == 2 and st["duplicate_applies"] == 0
        assert st["cached_rids"] == 3 and st["pending_rids"] == 0
        # per-tenant isolation: another tenant's same rid is fresh
        f3 = srv.submit("insert", k8, v1, rid=77, tenant="other")
        assert not f3.deduped
        f3.result(timeout=60)
        # ... and restore for later tests' probes
        srv.submit("insert", k8, v2, rid=80,
                   tenant="t").result(timeout=60)


def test_dedup_window_is_bounded_and_evicts_oldest(eight_devices):
    tree, eng, keys, vals = make()
    with serving(eng, keys, vals, dedup_window=2) as srv:
        for rid in (1, 2, 3):
            srv.submit("insert", keys[:2], vals[:2], rid=rid,
                       tenant="t").result(timeout=60)
        # rid 1 evicted: a retry re-applies (idempotent same payload)
        f = srv.submit("insert", keys[:2], vals[:2], rid=1,
                       tenant="t")
        f.result(timeout=60)
        assert not f.deduped
        f3 = srv.submit("insert", keys[:2], vals[:2], rid=3,
                        tenant="t")
        f3.result(timeout=60)
        assert f3.deduped


def test_dedup_inflight_retry_joins_same_future(eight_devices):
    tree, eng, keys, vals = make()
    srv = admission_only(eng)
    f1 = srv.submit("insert", keys[:4], vals[:4], rid=5, tenant="t")
    f2 = srv.submit("insert", keys[:4], vals[:4], rid=5, tenant="t")
    assert f1 is f2  # one apply, one ack, shared
    assert srv.stats()["contract"]["pending_rids"] == 1
    srv._running = False
    srv._fail_queued(StateError("test done"))
    assert srv.stats()["contract"]["pending_rids"] == 0


def test_seed_dedup_adopts_and_rejournals(eight_devices, tmp_path):
    from sherman_tpu.serve import READ_CLASSES  # noqa: F401
    tree, eng, keys, vals = make()
    jpath = str(tmp_path / "seed-j.bin")
    journal = J.Journal(jpath, sync=True)
    window = {("t", 42): (J.J_UPSERT, np.asarray([True, False]))}
    with serving(eng, keys, vals, journal=journal) as srv:
        assert srv.seed_dedup(window) == 1
        f = srv.submit("insert", keys[:2], vals[:2], rid=42,
                       tenant="t")
        ok = f.result(timeout=60)
        assert f.deduped and list(ok) == [True, False]
    # the adopted window was re-journaled: a SECOND recovery would
    # still see it
    acks = [a for kind, _k, aux in J.read_records(jpath)
            if kind == J.J_ACK for a in aux]
    assert any(rid == 42 and tenant == "t" for rid, tenant, _o, _ok
               in acks)
    journal.close()


def test_ack_records_reach_journal_before_ack(eight_devices, tmp_path):
    tree, eng, keys, vals = make()
    jpath = str(tmp_path / "ack-rec.bin")
    journal = J.Journal(jpath, sync=True, group_commit_ms=1.0)
    with serving(eng, keys, vals, journal=journal) as srv:
        srv.submit("insert", keys[:16], vals[:16], rid=9,
                   tenant="w").result(timeout=60)
        # the moment result() returned, the J_ACK record is parseable
        recs = J.read_records(jpath, with_rids=True)
        acks = [aux for kind, _k, aux, _r in recs if kind == J.J_ACK]
        assert acks and acks[0][0][0] == 9
        assert acks[0][0][1] == "w"
        assert acks[0][0][3].all() and acks[0][0][3].size == 16
    journal.close()


def test_deadline_shed_typed_before_dispatch(eight_devices):
    from sherman_tpu.serve import DeadlineExceededError
    tree, eng, keys, vals = make()
    srv = admission_only(eng)
    fut = srv.submit("read", keys[:8], deadline_ms=0.01, tenant="t")
    rid_fut = srv.submit("insert", keys[:4], vals[:4], rid=3,
                         deadline_ms=0.01, tenant="t")
    time.sleep(0.01)
    assert srv._take(("read",), 512) == []  # shed, not served
    assert srv._take(("insert", "delete"), 512) == []
    with pytest.raises(DeadlineExceededError):
        fut.result(timeout=1)
    with pytest.raises(DeadlineExceededError):
        rid_fut.result(timeout=1)
    assert srv.deadline_shed == 2
    # the shed write's rid is free again (pending cleared)
    assert srv.stats()["contract"]["pending_rids"] == 0
    # an unexpired request is NOT shed
    f2 = srv.submit("read", keys[:8], deadline_ms=60_000.0,
                    tenant="t")
    assert len(srv._take(("read",), 512)) == 1
    f2._fail(StateError("test done"))
    with pytest.raises(ConfigError):
        srv.submit("read", keys[:8], deadline_ms=-1.0)
    srv._running = False
    srv._fail_queued(StateError("test done"))


def test_deadline_live_served_or_typed(eight_devices):
    from sherman_tpu.serve import DeadlineExceededError
    from sherman_tpu.errors import ShermanError
    tree, eng, keys, vals = make()
    with serving(eng, keys, vals) as srv:
        outcomes = {"served": 0, "shed": 0}
        for i in range(20):
            try:
                got, found = srv.submit(
                    "read", keys[i::307],
                    deadline_ms=0.02 if i % 2 else 5000.0
                ).result(timeout=30)
                outcomes["served"] += 1
                assert found.all()
            except DeadlineExceededError:
                outcomes["shed"] += 1
            except ShermanError:
                raise
        assert outcomes["served"] >= 10  # generous budgets all served


def test_weighted_fair_share_admission_2to1(eight_devices):
    """The ROADMAP weighted-shares item: a 2:1 weight split holds
    2/3 vs 1/3 of the queue under contention; the lone-flooder
    reserve still holds."""
    tree, eng, keys, vals = make()
    srv = admission_only(eng, max_queue_ops=900,
                         tenant_weights={"gold": 2.0, "free": 1.0})
    # lone gold flooder: reserve = w_gold + max_other(1.0) = 3 ->
    # share = 900 * 2/3 = 600
    for _ in range(6):
        srv.submit("read", keys[:100], tenant="gold")
    with pytest.raises(ServeOverloadError):
        srv.submit("read", keys[:100], tenant="gold")
    # free arrives into its 1/3 = 300
    for _ in range(3):
        srv.submit("read", keys[:100], tenant="free")
    with pytest.raises(ServeOverloadError):
        srv.submit("read", keys[:100], tenant="free")
    st = srv.stats()["tenants"]
    assert st["gold"]["queued_ops"] == 600
    assert st["free"]["queued_ops"] == 300
    assert st["gold"]["weight"] == 2.0
    srv._running = False
    srv._fail_queued(StateError("test done"))


def test_weighted_env_parsing(monkeypatch):
    monkeypatch.setenv("SHERMAN_SERVE_WEIGHTS", "gold:2,free:0.5")
    monkeypatch.setenv("SHERMAN_SERVE_DEDUP", "128")
    cfg = ServeConfig.from_env()
    assert cfg.tenant_weights == {"gold": 2.0, "free": 0.5}
    assert cfg.dedup_window == 128
    monkeypatch.setenv("SHERMAN_SERVE_WEIGHTS", "gold:-1")
    with pytest.raises(ConfigError):
        ServeConfig.from_env()
    monkeypatch.setenv("SHERMAN_SERVE_WEIGHTS", "nonsense")
    with pytest.raises(ConfigError):
        ServeConfig.from_env()


def test_retry_policy_and_client(eight_devices):
    from sherman_tpu.serve import RetryPolicy, RetryingClient
    import random as _random
    pol = RetryPolicy(base_backoff_ms=2.0, backoff_cap_ms=10.0)
    rng = _random.Random(0)
    for attempt in range(8):
        b = pol.backoff_s(attempt, rng)
        assert 0.0 <= b <= 0.010 + 1e-9  # capped
    tree, eng, keys, vals = make()
    with serving(eng, keys, vals) as srv:
        cl = RetryingClient(srv, tenant="c", seed=3)
        got, found = cl.read(keys[:32])
        assert found.all()
        np.testing.assert_array_equal(got, keys[:32] * np.uint64(7))
        # writes auto-assign UNIQUE rids; an explicit rid is a retry
        ok = cl.insert(keys[:4], keys[:4] ^ np.uint64(1))
        assert ok.all()
        rid = cl._rid
        ok2 = cl.insert(keys[:4], keys[:4] ^ np.uint64(1), rid=rid)
        assert ok2.all() and srv.dedup_hits >= 1  # re-acked
        assert cl.next_rid() != rid
        fnd = cl.delete(np.asarray([5], np.uint64))
        assert not fnd.any()  # absent key


def test_drain_serves_admitted_and_fsyncs(eight_devices, tmp_path):
    tree, eng, keys, vals = make()
    jpath = str(tmp_path / "drain-j.bin")
    journal = J.Journal(jpath, sync=True, group_commit_ms=1.0)
    cfg = ServeConfig(widths=(128, 512), p99_targets_ms=targets(),
                      write_linger_ms=50.0)  # linger: writes pend
    srv = ShermanServer(eng, cfg, journal=journal)
    srv.start(calib_keys=keys, calib_writes=(keys[:64], vals[:64]))
    futs = [srv.submit("read", keys[:64])]
    futs.append(srv.submit("insert", keys[:8],
                           keys[:8] ^ np.uint64(0xD1), rid=1))
    fsyncs0 = journal.fsyncs
    srv.drain()
    for f in futs:
        f.result(timeout=1)  # everything admitted was SERVED
    assert journal.fsyncs > fsyncs0  # the epilogue fsync landed
    with pytest.raises(StateError):
        srv.submit("read", keys[:4])
    journal.close()


# -- journal record format v2 (request ids + ack records) ---------------------

def test_journal_v2_rid_round_trip(tmp_path):
    jp = str(tmp_path / "v2.bin")
    j = J.Journal(jp, sync=True)
    assert j.format == 2
    j.append(J.J_UPSERT, np.asarray([1, 2], np.uint64),
             np.asarray([3, 4], np.uint64), rid=0xABCD)
    j.append(J.J_DELETE, np.asarray([9], np.uint64))
    j.append_acks([(7, "tenant-x", J.J_UPSERT,
                    np.asarray([True, False, True])),
                   (8, "y", J.J_DELETE, np.asarray([True] * 9))])
    j.close()
    recs = J.read_records(jp, with_rids=True)
    assert recs[0][3] == 0xABCD and recs[1][3] is None
    kind, keys_, acks, _ = recs[2]
    assert kind == J.J_ACK and len(acks) == 2 and keys_ is None
    rid, tenant, op, ok = acks[0]
    assert (rid, tenant, op) == (7, "tenant-x", J.J_UPSERT)
    assert list(ok) == [True, False, True]
    assert list(acks[1][3]) == [True] * 9
    # default 3-tuple shape unchanged for old callers
    assert len(J.read_records(jp)[0]) == 3


def test_journal_v1_backcompat_missing_field(tmp_path):
    """The missing-field round trip: an old (v1) journal replays
    cleanly with rid=None everywhere — dedup disabled for the
    segment — and appends to it stay v1 (no mixed-format file)."""
    import struct
    import zlib
    jp = str(tmp_path / "v1.bin")
    with open(jp, "wb") as f:
        f.write(J.MAGIC_V1)
        pay = struct.pack("<BxxxI", J.J_UPSERT, 2) \
            + np.asarray([9, 10], np.uint64).tobytes() \
            + np.asarray([11, 12], np.uint64).tobytes()
        f.write(struct.pack("<II", len(pay), zlib.crc32(pay)) + pay)
    recs = J.read_records(jp, with_rids=True)
    assert recs[0][3] is None
    np.testing.assert_array_equal(recs[0][1],
                                  np.asarray([9, 10], np.uint64))
    j = J.Journal(jp, sync=True)
    assert j.format == 1
    j.append(J.J_UPSERT, np.asarray([13], np.uint64),
             np.asarray([14], np.uint64), rid=99)  # rid dropped
    assert j.append_acks([(1, "t", J.J_UPSERT,
                           np.asarray([True]))]) == 0  # refused
    j.close()
    recs = J.read_records(jp, with_rids=True)
    assert len(recs) == 2 and recs[1][3] is None


def test_journal_replay_collects_acks(eight_devices, tmp_path):
    tree, eng, keys, vals = make()
    jp = str(tmp_path / "rp.bin")
    j = J.Journal(jp, sync=True)
    j.append(J.J_UPSERT, keys[:4], keys[:4] ^ np.uint64(0xE1))
    j.append_acks([(5, "t", J.J_UPSERT, np.asarray([True] * 4))])
    j.close()
    sink: list = []
    stats = J.replay(jp, eng, ack_sink=sink)
    assert stats["acks"] == 1 and stats["upserts"] == 1
    assert sink[0][0] == 5 and sink[0][1] == "t"
    got, found = eng.search(keys[:4])
    assert found.all()
    np.testing.assert_array_equal(got, keys[:4] ^ np.uint64(0xE1))
    # restore for later tests sharing the session-scoped mesh
    eng.insert(keys[:4], vals[:4])


# -- sealed zero-retrace serving loop -----------------------------------------

@pytest.mark.parametrize("fusion", ["aligned", "pipelined"])
@pytest.mark.parametrize("cache", [False, True])
def test_sealed_serving_loop_zero_retrace(eight_devices, fusion, cache):
    """The PR 8 contract on the front door — now with the FULL client
    contract plane armed (PR 15): exactly-once dedup, deadlines, and
    the sampling auditor are pure host-side machinery, so the sealed
    loop must stay zero-retrace with all three on."""
    from sherman_tpu import audit as A
    tree, eng, keys, vals = make()
    if cache:
        lc = eng.attach_leaf_cache(slots=1024, admit_every=4)
    aud = A.Auditor(sample_mod=4, interval_s=0.05)
    try:
        with serving(eng, keys, vals, fusion=fusion,
                     max_queue_ops=16384, auditor=aud) as srv:
            assert srv._sealed
            rng = np.random.default_rng(1)
            futs = []
            for i in range(24):
                # zipf-ish hot head so the sketch admits real keys
                idx = rng.integers(0, 50 if i % 2 else keys.size, 120)
                kreq = keys[idx]
                futs.append((srv.submit(
                    "read", kreq,
                    deadline_ms=60_000.0 if i % 3 else None), kreq))
            for f, kreq in futs:
                got, found = f.result(timeout=60)
                assert found.all()
                np.testing.assert_array_equal(got, kreq * np.uint64(7))
            # writes + deletes + scans inside the sealed window too —
            # rid-carrying (dedup window + J_ACK path) and retried
            srv.submit("insert", keys[:8], keys[:8] ^ np.uint64(2),
                       rid=501).result(timeout=60)
            f = srv.submit("insert", keys[:8], keys[:8] ^ np.uint64(2),
                           rid=501)
            assert f.result(timeout=60).all() and f.deduped
            srv.submit("delete", np.asarray([5], np.uint64),
                       rid=502).result(timeout=60)
            srv.submit("scan", ranges=[(int(keys[0]), int(keys[9]))]
                       ).result(timeout=60)
            assert srv.retraces == 0, \
                "compile inside the sealed serving loop"
            if cache:
                cs = srv.stats()["cache"]
                assert cs["sketch"]["observed_batches"] > 0
        assert aud.violations == 0
        assert aud.rec.events > 0  # the auditor really watched
    finally:
        if cache:
            eng.detach_leaf_cache()


def test_serve_cache_sketch_admission_hits(eight_devices):
    """The PR 10 REMAINING item: the front door's read classes feed
    the decayed top-K sketch from REAL request streams, and after
    admission the hot keys serve from the cache."""
    tree, eng, keys, vals = make()
    lc = eng.attach_leaf_cache(slots=1024, admit_every=2)
    try:
        with serving(eng, keys, vals) as srv:
            hot = keys[:64]
            for _ in range(8):
                got, found = srv.submit(
                    "read", np.tile(hot, 3)).result(timeout=60)
                assert found.all()
            assert lc.sketch_stats()["observed_batches"] >= 8
            assert lc.fills > 0, "sketch admission never fired"
            assert lc.hits > 0, "admitted hot keys never hit"
            got, found = srv.submit("read", hot).result(timeout=60)
            np.testing.assert_array_equal(got, hot * np.uint64(7))
    finally:
        eng.detach_leaf_cache()


# -- journaled acks + crash drill ---------------------------------------------

def test_journaled_ack_crash_drill_rpo0(eight_devices, tmp_path):
    tree, eng, keys, vals = make()
    jpath = str(tmp_path / "serve-journal.bin")
    journal = J.Journal(jpath, sync=True, group_commit_ms=2.0)
    acked: dict[int, int] = {}
    with serving(eng, keys, vals, journal=journal,
                 write_linger_ms=20.0) as srv:
        jstats0 = journal.stats()
        # several concurrent writers on DISJOINT slices; the long
        # linger coalesces their requests into shared batch records
        def writer(w):
            my = keys[w * 500:(w + 1) * 500]
            for gen in range(1, 4):
                kreq = my[:128]
                vreq = kreq ^ np.uint64(0xBEEF) ^ np.uint64(gen)
                fut = srv.submit("insert", kreq, vreq,
                                 tenant=f"w{w}")
                ok = fut.result(timeout=60)
                # only OK rows are owed durability (a lock-timeout row
                # is typed-rejected and never journaled)
                for k, v, o in zip(kreq.tolist(), vreq.tolist(),
                                   ok.tolist()):
                    if o:
                        acked[k] = v

        ths = [threading.Thread(target=writer, args=(w,))
               for w in range(4)]
        for t in ths:
            t.start()
        for t in ths:
            t.join(timeout=120)
        jstats = journal.stats()
        # acks/fsync > 1 under concurrent writers: the batch record
        # covers every client write it coalesced
        fsyncs = jstats["fsyncs"] - jstats0["fsyncs"]
        assert fsyncs > 0
        assert srv.acked_writes / fsyncs > 1.0, (srv.acked_writes,
                                                 fsyncs)
        srv.kill()  # crash: no drain, journal left unclosed
    # RECOVERY: rebuild the base image, replay the journal, audit every
    # acked write — RPO must be 0
    cfg2 = DSMConfig(machine_nr=1, pages_per_node=2048,
                     locks_per_node=512, step_capacity=1024,
                     chunk_pages=32)
    tree2 = Tree(Cluster(cfg2))
    batched.bulk_load(tree2, keys, vals)
    eng2 = batched.BatchedEngine(tree2, batch_per_node=256)
    eng2.attach_router()
    stats = J.replay(jpath, eng2)
    assert stats["records"] > 0
    ak = np.fromiter(acked.keys(), np.uint64, len(acked))
    av = np.fromiter(acked.values(), np.uint64, len(acked))
    got, found = eng2.search(ak)
    rpo = int(np.sum(~(found & (got == av))))
    assert rpo == 0, f"{rpo} acked writes lost"


def test_write_ack_implies_durable_record(eight_devices, tmp_path):
    """No ack before a covering fsync — the record for an acked write
    is already parseable from the journal file the moment result()
    returns, with the journal still open (no close-time flush
    involved)."""
    tree, eng, keys, vals = make()
    jpath = str(tmp_path / "ack-journal.bin")
    journal = J.Journal(jpath, sync=True, group_commit_ms=1.0)
    with serving(eng, keys, vals, journal=journal) as srv:
        kreq = keys[:32]
        vreq = kreq ^ np.uint64(0xACED)
        srv.submit("insert", kreq, vreq).result(timeout=60)
        recs = J.read_records(jpath)
        rows = {int(k): int(v) for kind, ks, vs in recs if vs is not None
                for k, v in zip(ks, vs)}
        assert all(rows.get(int(k)) == int(v)
                   for k, v in zip(kreq, vreq))
    journal.close()


# -- perfgate serve-mode rules ------------------------------------------------

def _serve_receipt(keys=200_000, p99=8.0, ops=500_000, target=10.0):
    return {
        "schema_version": 3, "metric": "serve_bench", "keys": keys,
        "serve_ops_s": ops, "serve_read_p99_ms": p99,
        "serve": {"p99_targets_ms": {"read": target}},
    }


def test_perfgate_serve_never_gates_closed_loop():
    import perfgate
    closed = {"keys": 200_000, "batch": 4096, "value": 1_000_000,
              "sustained_ops_s": 2_000_000,
              "sus_dev_ms_per_step": 10.0, "_round": 5}
    cand = _serve_receipt()
    res = perfgate.gate(cand, [closed])
    # no comparable metric at all: the gate refuses to vouch (exit-2
    # shape), it does NOT compare open-loop ops to closed-loop ops
    assert not res["ok"] and "error" in res
    # and symmetrically a closed-loop candidate skips serve rounds
    sr = dict(_serve_receipt(), _round=12)
    res2 = perfgate.gate(dict(closed, _round=None), [sr])
    assert "skipped" in res2["metrics"]["sustained_ops_s"]


def test_perfgate_serve_gates_within_serve_rounds():
    import perfgate
    base = dict(_serve_receipt(), _round=12)
    good = _serve_receipt(p99=8.4, ops=510_000)
    res = perfgate.gate(good, [base])
    assert res["ok"], res
    # p99 regression beyond the margin goes red
    bad = _serve_receipt(p99=20.0)
    res = perfgate.gate(bad, [base])
    assert not res["ok"]
    assert not res["metrics"]["serve_read_p99_ms"]["ok"]
    # a re-aimed target is a config change, not a regression
    retargeted = _serve_receipt(p99=20.0, target=25.0)
    res = perfgate.gate(retargeted, [base])
    assert "skipped" in res["metrics"]["serve_read_p99_ms"]


def test_perfgate_contract_receipts_hard_pins():
    """The retrace-red pattern for the contract drill: robustness
    receipts are never throughput-gated, but duplicate_acks > 0 /
    lost_acks > 0 / linearizable == false in a committed receipt is a
    hard red (and a green-pinned receipt PASSES on its pins alone —
    no exit-2 for carrying no comparable throughput metric)."""
    import perfgate
    closed = {"keys": 200_000, "batch": 4096, "value": 1_000_000,
              "sustained_ops_s": 2_000_000,
              "sus_dev_ms_per_step": 10.0, "_round": 5}
    good = {"metric": "contract_drill", "duplicate_acks": 0,
            "lost_acks": 0, "rpo_ops": 0, "linearizable": True}
    res = perfgate.gate(good, [closed])
    assert res["ok"] and "error" not in res, res
    assert res["metrics"]["contract.duplicate_acks"]["ok"]
    assert res["metrics"]["contract.linearizable"]["ok"]
    for bad in ({"duplicate_acks": 1}, {"lost_acks": 3},
                {"linearizable": False}):
        res = perfgate.gate(dict(good, **bad), [closed])
        assert not res["ok"], bad
    # contract pins never rescue a CLOSED-LOOP receipt that merely
    # carries the fields: a bench row still gates on throughput
    cand = dict(closed, _round=None, sustained_ops_s=1_000_000,
                duplicate_acks=0, linearizable=True)
    res = perfgate.gate(cand, [closed])
    assert not res["ok"]  # the -50% sustained loss still fails


# -- journal instance stats ---------------------------------------------------

def test_journal_instance_stats(tmp_path):
    jp = str(tmp_path / "j.bin")
    j = J.Journal(jp, sync=True)
    assert j.stats() == {"appends": 0, "rows": 0, "fsyncs": 0,
                         "appends_per_fsync": None}
    j.append(J.J_UPSERT, np.asarray([1, 2], np.uint64),
             np.asarray([3, 4], np.uint64))
    j.append(J.J_DELETE, np.asarray([1], np.uint64))
    s = j.stats()
    assert s["appends"] == 2 and s["rows"] == 3 and s["fsyncs"] == 2
    assert s["appends_per_fsync"] == 1.0
    j.close()


# -- config parsing -----------------------------------------------------------

def test_serve_config_env_parsing(monkeypatch):
    monkeypatch.setenv("SHERMAN_SERVE_WIDTHS", "256,64,1024")
    monkeypatch.setenv("SHERMAN_SERVE_P99_MS", "read:5,insert:200")
    monkeypatch.setenv("SHERMAN_SERVE_QUEUE_OPS", "9999")
    cfg = ServeConfig.from_env()
    assert cfg.widths == (64, 256, 1024)
    assert cfg.p99_targets_ms["read"] == 5.0
    assert cfg.p99_targets_ms["insert"] == 200.0
    assert cfg.p99_targets_ms["delete"] == 50.0  # default fill-in
    assert cfg.max_queue_ops == 9999
    monkeypatch.setenv("SHERMAN_SERVE_WIDTHS", "banana")
    with pytest.raises(ConfigError):
        ServeConfig.from_env()
    monkeypatch.setenv("SHERMAN_SERVE_WIDTHS", "256")
    monkeypatch.setenv("SHERMAN_SERVE_P99_MS", "bogus:5")
    with pytest.raises(ConfigError):
        ServeConfig.from_env()


def test_serve_future_contract():
    f = ServeFuture("read", "t", 4)
    assert not f.done()
    with pytest.raises(StateError):
        f.result(timeout=0.01)
    f._set(("x", "y"))
    assert f.done() and f.result() == ("x", "y")
    f2 = ServeFuture("insert", "t", 1)
    f2._fail(ServeOverloadError("nope"))
    with pytest.raises(ServeOverloadError):
        f2.result()


# -- quorum acks (PR 18) ------------------------------------------------------

def _quorum_rig(tmp_path, tag, n=1200):
    """A serve engine whose writes journal through a RecoveryPlane —
    the chain a ReplicaGroup's followers feed on (quorum acks resolve
    against follower watermarks over THIS journal)."""
    from sherman_tpu.recovery import RecoveryPlane
    tree, eng, keys, vals = make(n=n, pages=1024, B=128, cap=512)
    plane = RecoveryPlane(tree.cluster, tree, eng,
                          str(tmp_path / tag))
    plane.checkpoint_base()
    return tree, eng, keys, vals, plane


def test_quorum_config_validation(eight_devices, tmp_path):
    """The quorum knobs refuse bad values typed, and ack_quorum > 1
    without an attached group is a start()-time ConfigError — acking
    K copies without K-1 followers would be a lie."""
    with pytest.raises(ConfigError):
        ServeConfig(widths=(128,), p99_targets_ms=targets(),
                    ack_quorum=0)
    with pytest.raises(ConfigError):
        ServeConfig(widths=(128,), p99_targets_ms=targets(),
                    quorum_timeout_ms=0.0)
    tree, eng, keys, vals = make(n=900, pages=1024, B=128, cap=512)
    cfg = ServeConfig(widths=(128,), p99_targets_ms=targets(),
                      ack_quorum=2)
    srv = ShermanServer(eng, cfg)
    with pytest.raises(ConfigError):
        srv.start()


def test_quorum_off_bit_identity(eight_devices, tmp_path):
    """ack_quorum=1 (the shipped default) with a group attached takes
    the exact write path of a build without the quorum gate: the
    quorum wait is never entered and the pool is bit-identical."""
    from sherman_tpu.replica import ReplicaGroup
    pools = []
    for tag, attach in (("bi-off", False), ("bi-on", True)):
        tree, eng, keys, vals, plane = _quorum_rig(tmp_path, tag)
        cfg = ServeConfig(widths=(128,), p99_targets_ms=targets(),
                          write_linger_ms=0.0)
        assert cfg.ack_quorum == 1  # SHERMAN_ACK_QUORUM default
        srv = ShermanServer(eng, cfg)
        group = None
        if attach:
            group = ReplicaGroup(plane, 1)
            srv.attach_replica_group(group)
        srv.start()
        try:
            kreq = keys[:256]
            vreq = kreq ^ np.uint64(0xC0DE)
            srv.submit("insert", kreq, vreq).result(timeout=60)
            srv.submit("delete", keys[300:316]).result(timeout=60)
            srv.drain()
            assert srv.quorum_acks == 0  # the gate never ran
        finally:
            srv.stop()
        pools.append(np.asarray(tree.cluster.dsm.pool))
        if group is not None:
            group.close()
        plane.close()
    assert pools[0].shape == pools[1].shape
    assert bool(np.all(pools[0] == pools[1])), \
        "quorum-off write path diverged from the no-group build"


def test_quorum_gate_end_to_end(eight_devices, tmp_path):
    """ack_quorum=2 through the front door: acks resolve only after a
    follower's durable watermark covers them (counters in stats()),
    a full ship partition expires the bounded wait TYPED, and the
    same-rid retry after the heal re-acks through the dedup window
    (exactly-once across quorum retries)."""
    from sherman_tpu.chaos import ReplChaos
    from sherman_tpu.replica import QuorumTimeoutError, ReplicaGroup
    tree, eng, keys, vals, plane = _quorum_rig(tmp_path, "gate")
    group = ReplicaGroup(plane, 1)
    chaos = ReplChaos([], seed=0)
    group.attach_chaos(chaos)
    cfg = ServeConfig(widths=(128,), p99_targets_ms=targets(),
                      write_linger_ms=0.0, ack_quorum=2,
                      quorum_timeout_ms=400.0)
    srv = ShermanServer(eng, cfg)
    srv.attach_replica_group(group)
    srv.start()
    try:
        kreq = keys[:48]
        vreq = kreq ^ np.uint64(0xACDC)
        ok = srv.submit("insert", kreq, vreq, tenant="q") \
                .result(timeout=60)
        assert int(np.sum(ok)) > 0
        assert srv.quorum_acks >= 1
        q = srv.stats()["quorum"]
        assert q["ack_quorum"] == 2 and q["acks"] >= 1 \
            and q["timeouts"] == 0
        # the resolved ack's frontier is durably covered downstream
        tok = group.quorum_token()
        assert group.followers[0].tailer.covers(*tok)
        # full ship partition: the bounded wait expires typed
        chaos.hold("ship")
        rid = (0x77 << 32) | 3
        k2 = keys[64:80]
        v2 = k2 ^ np.uint64(0xD1CE)
        t0 = time.perf_counter()
        with pytest.raises(Exception) as ei:
            srv.submit("insert", k2, v2, tenant="q",
                       rid=rid).result(timeout=30)
        tip, typed = ei.value, False
        while tip is not None:
            if isinstance(tip, QuorumTimeoutError):
                typed = True
                break
            tip = tip.__cause__
        assert typed, f"untyped quorum expiry: {ei.value!r}"
        assert time.perf_counter() - t0 < 5.0, "wait was not bounded"
        assert srv.quorum_timeouts >= 1
        # heal -> the SAME rid re-acks the original result (dedup),
        # never a second apply; the re-ack honors the quorum promise
        chaos.heal()
        fut = srv.submit("insert", k2, v2, tenant="q", rid=rid)
        ok2 = fut.result(timeout=60)
        assert fut.deduped, "quorum retry re-applied, not re-acked"
        assert np.asarray(ok2).shape == k2.shape
        assert srv.duplicate_applies == 0
        srv.drain()
    finally:
        srv.stop()
    group.close()
    plane.close()
