"""Data-plane chaos + recovery: fault injection, lock-lease recovery,
online scrubbing, degraded-mode serving (the robustness PR's fast tier).

Control-plane failures (peer death, stalls, preemption) are
tests/test_failure.py; these drills cover the DATA plane: a wedged lock
word, torn version words, dropped CAS winners, stale reads — and the
detection/recovery machinery each must trip (lease revocation, the
bounded lock retry's typed timeout, scrub violation counters +
quarantine, read-only degraded mode with the checkpoint-restore exit).
"""

import numpy as np
import pytest

from sherman_tpu import chaos as CH
from sherman_tpu import obs
from sherman_tpu.cluster import Cluster
from sherman_tpu.config import DSMConfig, TreeConfig
from sherman_tpu.models import batched
from sherman_tpu.models.btree import Tree
from sherman_tpu.models.scrub import Scrubber
from sherman_tpu.models.validate import (SCRUB_BITS, check_structure_device,
                                         scrub_pass)
from sherman_tpu.ops import bits
from sherman_tpu.parallel import dsm as D


@pytest.fixture()
def small_cluster(eight_devices):
    cfg = DSMConfig(machine_nr=4, pages_per_node=1024, locks_per_node=256,
                    step_capacity=256, chunk_pages=32)
    cluster = Cluster(cfg)
    tree = Tree(cluster)
    eng = batched.BatchedEngine(
        tree, batch_per_node=128,
        tcfg=TreeConfig(lock_retry_rounds=2))
    keys = np.arange(1, 1501, dtype=np.uint64) * np.uint64(17)
    batched.bulk_load(tree, keys, keys ^ np.uint64(0xBEEF))
    eng.attach_router()
    return cluster, tree, eng, keys


def _victim(tree, keys):
    addr = int(tree._descend(int(keys[keys.size // 2]))[0])
    return addr, tree._lock_word_addr(addr)


def _fire(dsm, plan):
    """Install a plan and run one no-op host step so step-0 faults land."""
    dsm.install_chaos(plan)
    dsm.read_word(0, 0)
    dsm.install_chaos(None)
    assert plan.exhausted


# -- FaultPlan mechanics ------------------------------------------------------

def test_fault_plan_parse_and_random_determinism():
    p = CH.FaultPlan.parse(
        '[{"kind": "wedge_lock", "step": 2, "addr": 5}]')
    assert p.faults[0].kind == "wedge_lock" and p.faults[0].step == 2
    a = CH.FaultPlan.random(9, n_faults=4)
    b = CH.FaultPlan.random(9, n_faults=4)
    assert [(f.kind, f.step, f.slot) for f in a.faults] \
        == [(f.kind, f.step, f.slot) for f in b.faults]
    with pytest.raises(ValueError):
        CH.FaultPlan.parse("bogus")
    with pytest.raises(ValueError):
        CH.Fault(kind="nope")


def test_chaos_env_spec_installs_on_dsm(eight_devices, monkeypatch):
    monkeypatch.setenv("SHERMAN_CHAOS", "random:3:2")
    cfg = DSMConfig(machine_nr=2, pages_per_node=64, locks_per_node=32,
                    step_capacity=32, chunk_pages=8)
    from sherman_tpu.parallel.dsm import DSM
    dsm = DSM(cfg)
    assert dsm.chaos is not None and len(dsm.chaos.faults) == 2


def test_chaos_undo_restores_words(small_cluster):
    cluster, tree, eng, keys = small_cluster
    victim, la = _victim(tree, keys)
    before = np.asarray(cluster.dsm.pool).copy()
    plan = CH.FaultPlan([
        CH.Fault(kind="torn_page", step=0, addr=victim),
        CH.Fault(kind="flip_entry_ver", step=0, addr=victim, slot=3),
        CH.Fault(kind="wedge_lock", step=0, addr=la),
    ])
    _fire(cluster.dsm, plan)
    assert scrub_pass(tree)["violations"] == 1
    assert plan.undo(cluster.dsm) == 3
    np.testing.assert_array_equal(np.asarray(cluster.dsm.pool), before)
    assert int(cluster.dsm.read_word(la, 0, space=D.SPACE_LOCK)) == 0
    assert scrub_pass(tree)["violations"] == 0


def test_drop_cas_loses_honestly(small_cluster):
    cluster, tree, eng, keys = small_cluster
    la = bits.make_addr(1, 7)
    plan = CH.FaultPlan([CH.Fault(kind="drop_cas", step=0)])
    cluster.dsm.install_chaos(plan)
    old, won = cluster.dsm.cas(la, 0, 0, tree.ctx.lease,
                               space=D.SPACE_LOCK)
    cluster.dsm.install_chaos(None)
    assert not won  # the dropped winner sees an honest loss...
    assert int(cluster.dsm.read_word(la, 0, space=D.SPACE_LOCK)) == 0
    _, won = cluster.dsm.cas(la, 0, 0, tree.ctx.lease, space=D.SPACE_LOCK)
    assert won      # ...and the plain retry wins
    cluster.dsm.write_word(la, 0, 0, space=D.SPACE_LOCK)


def test_stale_read_serves_old_snapshot(small_cluster):
    cluster, tree, eng, keys = small_cluster
    addr, _ = _victim(tree, keys)
    fresh = np.asarray(cluster.dsm.read_page(addr))
    plan = CH.FaultPlan([CH.Fault(kind="stale_read", step=2)])
    cluster.dsm.install_chaos(plan)
    cluster.dsm.read_word(0, 0)  # step 0 arms the snapshot
    # mutate the page through a host write, then read under the fault
    cluster.dsm.write_words(addr, C_W := 200, np.array([1234], np.int32))
    got = cluster.dsm.read_page(addr)
    cluster.dsm.install_chaos(None)
    np.testing.assert_array_equal(got, fresh)  # stale: pre-write content
    assert int(cluster.dsm.read_page(addr)[C_W]) == 1234  # live again


# -- lock-lease recovery ------------------------------------------------------

def test_host_lock_revokes_dead_lease(small_cluster):
    cluster, tree, eng, keys = small_cluster
    victim, la = _victim(tree, keys)
    _fire(cluster.dsm, CH.FaultPlan(
        [CH.Fault(kind="wedge_lock", step=0, addr=la)]))
    snap = obs.snapshot()
    held = tree._lock(victim)  # spins, probes the lease table, revokes
    tree._unlock(held)
    d = obs.delta(snap, obs.snapshot())
    assert d.get("lease.revoked", 0) >= 1
    assert int(cluster.dsm.read_word(la, 0, space=D.SPACE_LOCK)) == 0


def test_expired_epoch_is_revocable(small_cluster):
    """A REGISTERED client whose lease the control plane expired
    (epoch bump) is dead for data-plane purposes: its lock is revoked
    exactly like an unregistered owner's."""
    cluster, tree, eng, keys = small_cluster
    victim, la = _victim(tree, keys)
    zombie = cluster.register_client()
    cluster.dsm.write_word(la, 0, zombie.lease, space=D.SPACE_LOCK)
    cluster.expire_client(zombie.tag)  # control plane declares it dead
    held = tree._lock(victim)
    tree._unlock(held)
    assert int(cluster.dsm.read_word(la, 0, space=D.SPACE_LOCK)) == 0


def test_sweep_dead_processes_expires_tags(small_cluster):
    """The collective maintenance pass: clients of a process the
    coordination service no longer lists as live get their lease
    epochs bumped (single-process: only process 0 is live)."""
    cluster, tree, eng, keys = small_cluster
    ghost = cluster.register_client()
    assert cluster.lease_is_live(ghost.tag, ghost.epoch)
    expired = cluster.sweep_dead_processes({1: [ghost.tag]})
    assert expired == [ghost.tag]
    assert not cluster.lease_is_live(ghost.tag, ghost.epoch)
    # process 0 is live: its tags survive a sweep untouched
    assert cluster.sweep_dead_processes({0: [tree.ctx.tag]}) == []
    assert cluster.lease_is_live(tree.ctx.tag, tree.ctx.epoch)


def test_deadlock_reporter_names_live_holder(small_cluster):
    """The LOCK_SPIN_LIMIT reporter path, made reachable: injectable
    threshold + a LIVE holder (never revoked), diagnostic names the
    lock word, holder tag and liveness."""
    cluster, tree, eng, keys = small_cluster
    victim, la = _victim(tree, keys)
    holder = cluster.register_client()
    cluster.dsm.write_word(la, 0, holder.lease, space=D.SPACE_LOCK)
    tree.lock_spin_limit = 6
    with pytest.raises(RuntimeError) as ei:
        tree._lock(victim)
    msg = str(ei.value)
    assert f"{la:#x}" in msg and f"holder tag {holder.tag}" in msg
    assert "live lease" in msg
    # the lock word was NOT touched: live leases are never revoked
    assert int(cluster.dsm.read_word(la, 0, space=D.SPACE_LOCK)) \
        == holder.lease
    cluster.dsm.write_word(la, 0, 0, space=D.SPACE_LOCK)


def test_engine_bounded_retry_revokes_dead_lease(small_cluster):
    cluster, tree, eng, keys = small_cluster
    victim, la = _victim(tree, keys)
    _fire(cluster.dsm, CH.FaultPlan(
        [CH.Fault(kind="wedge_lock", step=0, addr=la)]))
    snap = obs.snapshot()
    band = keys[keys.size // 2: keys.size // 2 + 6]
    st = eng.insert(band, band)
    d = obs.delta(snap, obs.snapshot())
    assert d.get("lease.revoked", 0) >= 1
    assert st["lock_timeouts"] == 0
    assert st["applied"] + st["superseded"] + st["host_path"] == band.size
    v, f = eng.search(band)
    assert f.all()


def test_engine_lock_timeout_is_typed_not_silent(small_cluster):
    """A LIVE holder that never releases: the device insert loop must
    reject the blocked ops with ST_LOCK_TIMEOUT after its bounded
    budget — typed per-op status, not a silently burned insert_rounds
    budget or a hang."""
    cluster, tree, eng, keys = small_cluster
    victim, la = _victim(tree, keys)
    holder = cluster.register_client()
    cluster.dsm.write_word(la, 0, holder.lease, space=D.SPACE_LOCK)
    band = keys[keys.size // 2: keys.size // 2 + 4]
    snap = obs.snapshot()
    st = eng.insert(band, band)
    assert st["lock_timeouts"] == band.size, st
    assert sorted(st["lock_timeout_keys"]) == sorted(int(k) for k in band)
    assert obs.delta(snap, obs.snapshot()).get(
        "engine.lock_timeouts", 0) == band.size
    # mixed() carries the typed status through its write-retry path
    vals = band ^ np.uint64(1)
    is_read = np.zeros(band.size, bool)
    _, _, status = eng.mixed(band, vals, is_read)
    assert (status == batched.ST_LOCK_TIMEOUT).all()
    cluster.dsm.write_word(la, 0, 0, space=D.SPACE_LOCK)
    st = eng.insert(band, band)  # released: the same ops now land
    assert st["applied"] + st["superseded"] == band.size


# -- online scrubbing + degraded mode ----------------------------------------

def test_scrub_detects_and_quarantines_torn_versions(small_cluster):
    cluster, tree, eng, keys = small_cluster
    victim, la = _victim(tree, keys)
    scr = Scrubber(eng, interval=1)
    assert scr.scrub()["violations"] == 0
    _fire(cluster.dsm, CH.FaultPlan([
        CH.Fault(kind="torn_page", step=0, addr=victim),
        CH.Fault(kind="flip_entry_ver", step=0, addr=victim, slot=1),
    ]))
    snap = obs.snapshot()
    res = scr.scrub()
    assert res["violations"] == 1
    assert res["classes"]["bad_version"] == 1
    assert res["classes"]["torn_slot"] == 1
    assert res["quarantined"] >= 1
    d = obs.delta(snap, obs.snapshot())
    assert d.get("scrub.violations", 0) == 1
    assert d.get("scrub.pages_checked", 0) > 0
    # quarantine = the page's lock word held under the scrubber's LIVE
    # lease: writers are fenced (typed timeout), never revoked
    assert int(cluster.dsm.read_word(la, 0, space=D.SPACE_LOCK)) \
        == scr.ctx.lease
    # torn page versions are structural -> degraded read-only
    assert eng.degraded
    with pytest.raises(batched.DegradedError):
        eng.insert(keys[:2], keys[:2])
    with pytest.raises(batched.DegradedError):
        eng.delete(keys[:2])
    with pytest.raises(batched.DegradedError):
        eng.mixed(keys[:2], keys[:2], np.array([True, False]))
    assert obs.snapshot().get("engine.degraded") == 1.0
    # searches keep serving (reads of other pages unaffected)
    v, f = eng.search(keys[:64])
    assert f.all()
    # all-read mixed batches are allowed too
    ov, fnd, _ = eng.mixed(keys[:4], keys[:4], np.ones(4, bool))
    assert fnd.all()


def test_entry_level_violation_quarantines_without_degrading(
        small_cluster):
    """A torn SLOT (entry-level) is contained by quarantine: the page
    is fenced from writers, the engine keeps accepting writes
    elsewhere."""
    cluster, tree, eng, keys = small_cluster
    victim, la = _victim(tree, keys)
    _fire(cluster.dsm, CH.FaultPlan(
        [CH.Fault(kind="flip_entry_ver", step=0, addr=victim, slot=0)]))
    scr = Scrubber(eng, interval=1)
    res = scr.scrub()
    assert res["violations"] == 1
    assert res["classes"]["torn_slot"] == 1
    assert res["classes"]["bad_version"] == 0
    assert not eng.degraded
    # writes away from the quarantined page still land
    other = keys[:8]
    st = eng.insert(other, other)
    assert st["applied"] + st["superseded"] == other.size


def test_degraded_recovery_via_checkpoint_restore(small_cluster,
                                                  tmp_path):
    """The documented degraded-mode exit: restore the pre-fault
    checkpoint, re-validate green, writes accepted again."""
    import os

    from sherman_tpu.utils import checkpoint as CK
    cluster, tree, eng, keys = small_cluster
    p = os.path.join(tmp_path, "pre_fault.npz")
    CK.checkpoint(cluster, p)
    victim, _ = _victim(tree, keys)
    _fire(cluster.dsm, CH.FaultPlan(
        [CH.Fault(kind="torn_page", step=0, addr=victim)]))
    scr = Scrubber(eng, interval=1)
    assert scr.scrub()["degraded"]
    with pytest.raises(RuntimeError):
        check_structure_device(tree)  # the full validator agrees
    cluster2 = CK.restore(p)
    tree2 = Tree(cluster2)
    eng2 = batched.BatchedEngine(tree2, batch_per_node=128)
    eng2.attach_router()
    assert not eng2.degraded
    info = check_structure_device(tree2)
    assert info["keys"] == keys.size
    v, f = eng2.search(keys)
    assert f.all()
    np.testing.assert_array_equal(v, keys ^ np.uint64(0xBEEF))
    st = eng2.insert(keys[:8], keys[:8])
    assert st["applied"] + st["superseded"] == 8


def test_scrubber_tick_interval(small_cluster):
    cluster, tree, eng, keys = small_cluster
    scr = Scrubber(eng, interval=3, quarantine=False)
    assert scr.tick() is None and scr.tick() is None
    assert scr.tick() is not None  # every 3rd tick scrubs


def test_validator_flags_torn_slot(small_cluster):
    """The full validator gained the torn-pair invariant (fver != rver
    is unreachable by legal writes)."""
    cluster, tree, eng, keys = small_cluster
    victim, _ = _victim(tree, keys)
    check_structure_device(tree)
    _fire(cluster.dsm, CH.FaultPlan(
        [CH.Fault(kind="flip_entry_ver", step=0, addr=victim, slot=2)]))
    with pytest.raises(RuntimeError, match="bad_torn_slot"):
        check_structure_device(tree)


# -- Replication fault layer (PR 18) ------------------------------------------

def test_repl_fault_grammar_and_split():
    """``repl_*`` kinds ride the same FaultPlan grammar but are split
    into the replication layer, never the DSM hook."""
    p = CH.FaultPlan.parse(
        '[{"kind": "repl_drop", "poll": 2, "span": 3},'
        ' {"kind": "wedge_lock", "step": 1, "addr": 5},'
        ' {"kind": "repl_partition", "poll": 4, "scope": "lease"}]')
    assert len(p.faults) == 1 and len(p.repl_faults) == 2
    layer = p.repl_layer()
    assert layer is not None and layer is p.repl_layer()  # cached
    assert any("repl_drop" in d["kind"] for d in p.describe())
    # a plan with no repl faults has no layer
    assert CH.FaultPlan([{"kind": "wedge_lock", "step": 0,
                          "addr": 1}]).repl_layer() is None
    # validation is typed at construction
    from sherman_tpu.errors import ConfigError
    with pytest.raises(ConfigError):
        CH.ReplFault(kind="repl_nope")
    with pytest.raises(ConfigError):
        CH.ReplFault(kind="repl_drop", span=0)
    with pytest.raises(ConfigError):
        CH.ReplFault(kind="repl_partition", scope="wat")
    with pytest.raises(ConfigError):
        CH.ReplChaos([]).hold("sideways")


def test_repl_chaos_directives_deterministic():
    """Same (plan, seed) -> the same directive sequence and the same
    byte perturbations; the storm constructor is seed-stable too."""
    def run(layer):
        seq = []
        for _ in range(30):
            seq.append(layer.on_poll(0))
        return seq

    mk = lambda: CH.ReplChaos([
        CH.ReplFault(kind="repl_drop", poll=1, span=2),
        CH.ReplFault(kind="repl_delay", poll=4, span=1, follower=1),
        CH.ReplFault(kind="repl_reorder", poll=6, span=2),
        CH.ReplFault(kind="repl_slow", poll=9, span=1, ms=3.0),
    ], seed=5)
    a, b = mk(), mk()
    assert run(a) == run(b)
    blob = bytes(range(200)) * 2
    assert a.view(blob) == b.view(blob) != blob
    # follower filter: a follower-1 delay never freezes follower 0
    c = mk()
    d0 = [c.on_poll(0) for _ in range(6)]
    assert not any(d and d["freeze"] for d in d0)
    s1 = CH.ReplChaos.storm(7, n_faults=6).describe()
    s2 = CH.ReplChaos.storm(7, n_faults=6).describe()
    assert s1 == s2 and len(s1) == 6
    assert all(f["scope"] == "ship" for f in s1)  # no lease noise


def test_repl_chaos_hold_heal_and_lease_freeze():
    """Manual holds: a ship hold partitions every poll; a lease hold
    freezes the primary's lease view at first observation until the
    heal restores the live table."""
    layer = CH.ReplChaos([], seed=0)
    assert layer.on_poll(0) is None          # zero-cost common case
    layer.hold("ship")
    d = layer.on_poll(0)
    assert d and d["partition"]
    assert not layer.exhausted
    layer.heal()
    assert layer.on_poll(0) is None and layer.exhausted
    # lease scope: frozen at the FIRST view under the cut
    layer.hold("lease")
    assert layer.on_poll(1) is None          # ship side unaffected
    live = {7: 1}
    frozen = layer.lease_view(live)
    assert frozen == {7: 1}
    live[7] = 2                              # the epoch bump
    assert layer.lease_view(live) == {7: 1}  # still the old world
    layer.heal()
    assert layer.lease_view(live) == {7: 2}  # live again


@pytest.mark.parametrize("block_rows", [1000, 3000])
def test_validator_blocks_pad_a_ragged_pool(small_cluster, monkeypatch,
                                            block_rows):
    """A pool whose row count is no multiple of the block is zero-padded
    to whole blocks, never cut into tiny ones: same stats, and a torn
    page is found at the same address."""
    from sherman_tpu.models import validate as V
    cluster, tree, eng, keys = small_cluster

    victim, _ = _victim(tree, keys)

    def both():
        stats = check_structure_device(tree)
        _fire(cluster.dsm, CH.FaultPlan(
            [CH.Fault(kind="flip_entry_ver", step=0, addr=victim, slot=2)]))
        return stats, scrub_pass(tree)["bad"]

    want_stats = check_structure_device(tree)
    monkeypatch.setattr(V, "_BLOCK_ROWS", block_rows)
    for k in (V._validate_kernel, V._scrub_kernel):
        k.clear_cache()
    try:
        got_stats, got_bad = both()
    finally:
        monkeypatch.undo()
        for k in (V._validate_kernel, V._scrub_kernel):
            k.clear_cache()
    assert got_stats == want_stats
    assert got_bad == scrub_pass(tree)["bad"] and got_bad
    assert got_bad[0][0] == victim
