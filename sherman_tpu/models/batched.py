"""Batched device-side tree operations — the TPU-native hot path.

Where the reference hides per-op RDMA latency with 8 coroutines per thread
(``Tree.cpp:1059-1122``) and doorbell-coalesced verb chains
(``Operation.cpp:351-481``), the TPU build amortizes everything by *batching*:
one jitted SPMD step carries thousands of keys per node through a full
descent (one gathered page read per level, ``Tree.cpp:429-458`` hot loop) and,
for inserts, applies every non-split write in a single owner-side scatter.

Consistency model (stronger than the reference, by construction):

- A step's reads all see ONE snapshot of the pool (the functional array the
  step was called with), so torn pages cannot occur *within* a step — the
  front/rear version protocol (``Tree.h:199-210``) remains on the pages for
  cross-driver/host interleavings and protocol parity.
- All writes of a step become visible atomically at the step boundary; this
  IS the write+unlock doorbell guarantee (``Operation.cpp:351-380``).
- Intra-batch conflicts are linearized deterministically by stable request
  order (a serial order exists: the (source, slot) order), which replaces the reference's
  hierarchical local-lock hand-over (``Tree.cpp:1124-1173``): requests to the
  same leaf are *combined* in one step instead of queueing on a ticket lock.

Slow paths (leaf full -> split, locked page, routing overflow) fail fast with
a per-key status and are retried through the host ``Tree`` path, mirroring
how the reference falls out of its fast path into lock-and-split code
(``Tree.cpp:922-963``).
"""

from __future__ import annotations

import dataclasses
import functools
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from sherman_tpu import config as C
from sherman_tpu import obs
from sherman_tpu.errors import (ConfigError, KeyRangeError, ProtocolError,
                                ShermanError, StateError)
from sherman_tpu.obs import device as DEV
from sherman_tpu.obs import recorder as FR
from sherman_tpu.obs import slo as SLO
from sherman_tpu.config import DSMConfig, TreeConfig
from sherman_tpu.models.btree import META_ADDR
from sherman_tpu.ops import bits, layout, pallas_page
from sherman_tpu.parallel import dsm as D
from sherman_tpu.parallel import transport
from sherman_tpu.parallel.mesh import AXIS
from sherman_tpu.utils import journal as J

# Per-key insert status codes (reply of one insert step).
ST_INVALID = 0      # inactive slot (padding)
ST_APPLIED = 1      # written in this step
ST_SUPERSEDED = 2   # an earlier-ordered same-key request won AND applied
                    # (final: the winner's write is a legal concurrent
                    # overwrite of this one; losers of a non-applying
                    # winner get ST_RETRY instead)
ST_FULL = 3         # leaf full -> host split path
ST_LOCKED = 4       # page lock held (host split in flight) -> retry
ST_RETRY = 5        # routing overflow / descent incomplete -> retry
ST_BAD = 6          # failed sanity checks (not a level-0 page / fence)
ST_NOT_FOUND = 7    # delete: key absent (final)
ST_LOCK_TIMEOUT = 8  # host-side terminal: the key's page lock was STILL
                     # held by a LIVE lease when the insert round budget
                     # ran out — the op is REJECTED with this typed
                     # status instead of spinning unboundedly in the
                     # host fallback (dead leases are revoked by the
                     # in-loop probes every tcfg.lock_retry_rounds
                     # blocked rounds; see _recover_wedged_locks)

_PW = C.PAGE_WORDS


class DegradedError(ShermanError, RuntimeError):
    """Typed write rejection: the engine is in read-only degraded mode.

    Raised by every mutating engine entry point after unrecoverable
    data-plane damage (scrub-detected corruption that quarantine could
    not contain, or a failed lock revocation).  Searches keep being
    served; the documented exit is ``utils.checkpoint.restore`` into a
    fresh cluster (see README "Robustness")."""

    def __init__(self, reason: str):
        super().__init__(
            "engine degraded (read-only): write rejected — " + reason
            + "; recover via utils.checkpoint.restore")
        self.reason = reason


# degraded-mode gauge + lock-timeout counter (data-plane failure story)
_OBS_DEGRADED = obs.gauge("engine.degraded")
_OBS_LOCK_TIMEOUTS = obs.counter("engine.lock_timeouts")


def _slo_observe(op_class: str, ops: int, t0: float | None) -> None:
    """Attribute one host-path batch wall to its SLO op class (the
    amortized per-op latency model: a client op's completion latency IS
    its batch's wall).  ``t0`` None = a retry/chunk frame whose parent
    (or whose own chunks) already account the ops."""
    if t0 is not None and ops:
        SLO.observe(op_class, int(ops), time.perf_counter() - t0)


# ---------------------------------------------------------------------------
# Descent: batch of keys walks root -> leaf, one gathered read per level.
# ---------------------------------------------------------------------------

@jax.named_scope("descend")
def descend_spmd(pool, counters, khi, klo, root, active, *, cfg: DSMConfig,
                 iters: int, axis_name: str = AXIS, start=None,
                 stop_level: int = 0):
    """Walk each active key from ``root`` to its ``stop_level`` page
    (default: the leaf, level 0, in fence).  ``stop_level=1`` is the
    parent-maintenance descent (internal_page_store's target,
    Tree.cpp:980-987).

    Runs inside shard_map; khi/klo are this node's [B] key shard.  ``iters``
    is a static trip count (tree height + sibling-chase budget).  ``start``
    optionally seeds per-key start addresses (the index-cache fast path);
    keys then only need the sibling-chase/leaf hops from there.

    Returns (counters, addr [B], page [B, PW], done [B]).  done=False keys
    exhausted the budget (capacity overflow or deep chase): retry.

    Perf note: the loop carries ONLY (addr, done) — the leaf page is
    re-gathered once after the loop.  Carrying the [B, PAGE_WORDS] page
    through the loop costs a full-batch select per iteration, which
    dominates step time at large B.
    """
    B = khi.shape[0]
    if start is None:
        start = jnp.broadcast_to(jnp.asarray(root, jnp.int32), (B,))
    addr = start
    done = ~active
    # Single-node + gather_impl="pallas": the level's gather + in-page
    # pick run FUSED in one kernel (the page is searched in VMEM while
    # the next rows stream in; no [B, PAGE_WORDS] intermediate lands in
    # HBM between them).  Multi-node descents keep the XLA elementwise
    # pick after the exchange; their owner-side page reads still go
    # through the pallas snapshot kernel inside read_pages_spmd.
    fused = cfg.machine_nr == 1 and pallas_page.use_pallas(cfg)

    def advance(addr, done, nreads):
        # exact read accounting (DSM.cpp:17-21 counter semantics): one
        # read op per page actually fetched — the rows still descending
        nreads = nreads + jnp.sum((~done).astype(jnp.uint32))
        if fused:
            nxt, at_leaf, _, ok, _, _, _ = pallas_page.descent_round(
                pool, addr, khi, klo, ~done, stop_level=stop_level)
        else:
            pages, ok = D.read_pages_spmd(pool, addr, cfg=cfg,
                                          axis_name=axis_name,
                                          active=~done)
            lvl = layout.h_level(pages)
            chase = layout.needs_sibling_chase(pages, khi, klo)
            at_leaf = (lvl == stop_level) & ~chase
            nxt = jnp.where(chase, layout.h_sibling(pages),
                            layout.internal_pick_child(pages, khi, klo))
        step_ok = ok & ~done
        new_addr = jnp.where(step_ok & ~at_leaf, nxt, addr)
        new_done = done | (step_ok & at_leaf)
        return new_addr, new_done, nreads

    nreads = jnp.uint32(0)
    if cfg.machine_nr == 1:
        # Dynamic early exit: no collectives in the body, so a data-dependent
        # while_loop is legal; a fresh index-cache start exits after ~1 hop.
        def cond(st):
            it, _, done, _ = st
            return (it < iters) & jnp.any(~done)

        def bodyw(st):
            it, addr, done, nreads = st
            addr, done, nreads = advance(addr, done, nreads)
            return it + 1, addr, done, nreads

        _, addr, done, nreads = lax.while_loop(
            cond, bodyw, (0, addr, done, nreads))
    else:
        # SPMD: every node must run the SAME trip count (the body carries
        # all_to_all exchanges) — but it need not be the static budget:
        # a psum of the pending count is identical on every node, so a
        # while_loop on it exits uniformly as soon as the whole mesh is
        # done (with router seeds that is typically round 1-2, not the
        # full height+chase budget).  Rows already done post inactive
        # requests — not counted as reads.
        def pend_of(done):
            return lax.psum(jnp.sum((~done).astype(jnp.int32)), axis_name)

        def cond(st):
            it, _, _, _, pend = st
            return (it < iters) & (pend > 0)

        def body(st):
            it, addr, done, nreads, _ = st
            addr, done, nreads = advance(addr, done, nreads)
            return it + 1, addr, done, nreads, pend_of(done)

        _, addr, done, nreads, _ = lax.while_loop(
            cond, body, (0, addr, done, nreads, pend_of(done)))

    # one final gather yields the leaf pages for the done keys
    page, ok_f = D.read_pages_spmd(pool, addr, cfg=cfg, axis_name=axis_name,
                                   active=done & active)
    nreads = nreads + jnp.sum((done & active).astype(jnp.uint32))
    done = done & active & ok_f
    counters = counters.at[D.CNT_READ_OPS].add(nreads)
    counters = counters.at[D.CNT_READ_PAGES].add(nreads)
    return counters, addr, page, done


def search_routed_spmd(pool, counters, khi, klo, root, active, start, *,
                       cfg: DSMConfig, iters: int,
                       axis_name: str = AXIS):
    """Cache-hit search: one full-batch leaf read, then a COMPACTED
    straggler loop (any mesh size).

    ``start`` is the per-key seed address from the host index-cache probe
    (router.host_start): with a warm cache ~90%+ of keys finish in round 1
    (their seed IS their leaf).  The stragglers (bucket-boundary sibling
    chases, stale entries) are compacted into a small fixed buffer so later
    rounds gather S rows instead of B — full-batch rounds are what make a
    naive descent loop pay the whole batch's bandwidth per level.

    Perf notes (measured on v5e): the page gather is per-row latency-bound
    (~20-25 ns/row regardless of row width), so the step does exactly ONE
    full-batch gather.  Round 1 is leaf-only — seeds always satisfy
    ``page.lowest <= key`` (router invariant: buckets are only ever
    remapped to right-siblings whose ``lowest`` is the split key, so a
    seed can never land right of the key's leaf), and non-leaf seeds
    (cold router) fall into the compacted loop, which runs the full
    descent logic on S rows only.
    """
    counters, done, addr, found, vhi, vlo = _routed_resolve(
        pool, counters, khi, klo, active, start, iters=iters, cfg=cfg,
        axis_name=axis_name)
    return counters, done, found, vhi, vlo


@jax.named_scope("descend")
def _routed_resolve(pool, counters, khi, klo, active, start, *, iters: int,
                    cfg: DSMConfig, axis_name: str = AXIS):
    """Walk every active key from its cache seed to its leaf.

    Shared core of the routed search and mixed steps: round 1 + compacted
    straggler loop as described in :func:`search_routed_spmd`.  Returns
    (counters, done, addr, found, vhi, vlo): ``addr`` is the key's leaf
    page (for owner-side applies), found/vhi/vlo its lookup result.

    The stragglers are compacted ONCE after round 1 and the loop runs
    entirely in the compacted [S] space (the set only shrinks — a row
    that resolved in round 1 never becomes a straggler later), with a
    single scatter of results back to [B] after the loop.  The previous
    shape re-compacted and scattered [B]-wide EVERY round, which
    measured ~41 ms of the 68 ms step at 2 M rows — 60% of the read
    path spent resolving ~3% of rows.  Rows beyond the S-slot buffer
    (cold-router floods) stay not-done; callers retry them through the
    full-descent path, same contract as the round budget.

    Multi-node meshes run the SAME shape per node shard: pages come
    through the bucket-routed read exchange (``D.read_pages_spmd``) —
    round 1 with buckets sized from the B rows it carries (leaf seeds
    spread over the nodes, ``D.spread_capacity``), the straggler loop at
    an S-capacity exchange so straggler cost scales with miss count, not
    batch width (the reference's cache-hit path is O(1) reads per op at
    any cluster size, ``IndexCache.h:134-184``) — and the loop exits on
    a psum'd pending count so every node leaves together.  A round-1 row
    whose bucket was full stays not-done and rides the loop; the
    ``CNT_XCHG_OVERFLOW`` slot counts those rows, ``CNT_XCHG_REMOTE``
    the round-1 and loop rows whose page lives on another node.
    """
    B = khi.shape[0]
    P = pool.shape[0]
    N = cfg.machine_nr
    S = max(min(1024, B), B // 16)
    max_rounds = iters * 4
    # gather_impl="pallas" on one node: each round is ONE fused kernel
    # (page stream + in-VMEM search, ops/pallas_page.descent_round) —
    # bit-identical outputs to the gather + elementwise composition.
    fused = N == 1 and pallas_page.use_pallas(cfg)

    if N == 1:
        def read(addrs, act, loop: bool):
            page = bits.addr_page(addrs)
            ok = act & (page >= 0) & (page < P)
            return pool[jnp.clip(page, 0, P - 1)], ok
    else:
        loop_cfg = dataclasses.replace(cfg, step_capacity=S)

        def read(addrs, act, loop: bool):
            return D.read_pages_spmd(
                pool, addrs, cfg=loop_cfg if loop else cfg,
                axis_name=axis_name, active=act, spread=not loop)

        me = lax.axis_index(axis_name)

        def remote(addrs, act):
            return jnp.sum((act & (bits.addr_node(addrs) != me))
                           .astype(jnp.uint32))

    def advance(pg, ok, kh, kl):
        lvl = layout.h_level(pg)
        chase = layout.needs_sibling_chase(pg, kh, kl)
        at_leaf = ok & (lvl == 0) & ~chase
        nxt = jnp.where(chase, layout.h_sibling(pg),
                        layout.internal_pick_child(pg, kh, kl))
        f, vh, vl, _ = layout.leaf_find_key(pg, kh, kl)
        return at_leaf, nxt, f, vh, vl

    # round 1: full batch from the cache-seeded start; leaf-only logic
    # (no internal_pick_child on the full batch — stragglers descend in
    # the compacted loop below)
    if fused:
        # when chase is set the kernel's next address IS the sibling
        nxt1, leaf1, chase, ok, f, vh, vl = pallas_page.descent_round(
            pool, start, khi, klo, active)
        at_leaf = ok & leaf1
        sib1 = nxt1
    else:
        pg, ok = read(start, active, False)
        # NO optimization_barrier here: materializing the [B, PW] round-1
        # gather costs ~+10 ms at 2 M rows vs letting XLA fuse it into the
        # chase/level/find consumers (measured; the opposite tradeoff from
        # the apply path's snapshot)
        chase = layout.needs_sibling_chase(pg, khi, klo)
        at_leaf = ok & (layout.h_level(pg) == 0) & ~chase
        f, vh, vl, _ = layout.leaf_find_key(pg, khi, klo)
        sib1 = layout.h_sibling(pg)
    if N > 1:
        # a seed is a page address, so an active row the exchange did
        # not serve is one whose bucket was full
        counters = counters.at[D.CNT_XCHG_OVERFLOW].add(
            jnp.sum((active & ~ok).astype(jnp.uint32)))
        counters = counters.at[D.CNT_XCHG_REMOTE].add(remote(start, active))
    hit = active & at_leaf
    done = ~active | at_leaf
    found = hit & f
    vhi = jnp.where(found, vh, 0)
    vlo = jnp.where(found, vl, 0)
    addr = jnp.where(ok & chase, sib1, start)

    # one-time compaction; fill rows (sidx == B) start done
    sidx = jnp.nonzero(~done, size=S, fill_value=B)[0].astype(jnp.int32)
    valid = sidx < B
    ci = jnp.clip(sidx, 0, B - 1)
    s_kh, s_kl = khi[ci], klo[ci]
    s_addr = addr[ci]
    s_done = ~valid
    s_f = jnp.zeros(S, bool)
    s_vh = jnp.zeros(S, jnp.int32)
    s_vl = jnp.zeros(S, jnp.int32)

    if N == 1:
        def pend_of(s_done):
            return jnp.sum((~s_done).astype(jnp.int32))
    else:
        # uniform exit: every node sees the same cluster-wide pending
        # count (the loop body carries all_to_all exchanges)
        def pend_of(s_done):
            return lax.psum(jnp.sum((~s_done).astype(jnp.int32)), axis_name)

    def cond(st):
        it, pend = st[0], st[-1]
        return (it < max_rounds) & (pend > 0)

    def body(st):
        it, s_done, s_addr, s_f, s_vh, s_vl, loop_reads, *xr, _ = st
        loop_reads = loop_reads + jnp.sum((~s_done).astype(jnp.uint32))
        if N > 1:
            xr = [xr[0] + remote(s_addr, ~s_done)]
        if fused:
            nxt, leafb, _, ok, f, vh, vl = pallas_page.descent_round(
                pool, s_addr, s_kh, s_kl, ~s_done)
            at_leaf = ok & leafb
        else:
            pg, ok = read(s_addr, ~s_done, True)
            ok = ok & ~s_done
            at_leaf, nxt, f, vh, vl = advance(pg, ok, s_kh, s_kl)
        fin = ok & at_leaf
        s_f = jnp.where(fin, f, s_f)
        s_vh = jnp.where(fin & f, vh, s_vh)
        s_vl = jnp.where(fin & f, vl, s_vl)
        s_done = s_done | fin
        s_addr = jnp.where(ok & ~at_leaf, nxt, s_addr)
        return (it + 1, s_done, s_addr, s_f, s_vh, s_vl, loop_reads,
                *xr, pend_of(s_done))

    # the loop's remote-row count rides the carry on multi-node meshes
    xr0 = (jnp.uint32(0),) if N > 1 else ()
    (_, s_done, s_addr, s_f, s_vh, s_vl, loop_reads, *xr,
     _) = lax.while_loop(
        cond, body,
        (1, s_done, s_addr, s_f, s_vh, s_vl, jnp.uint32(0), *xr0,
         pend_of(s_done)))
    if N > 1:
        counters = counters.at[D.CNT_XCHG_REMOTE].add(xr[0])

    # single scatter of the compacted results back to [B]
    res = valid & s_done
    tgt = jnp.where(res, sidx, B)
    done = done.at[tgt].set(True, mode="drop")
    found = found.at[tgt].set(s_f, mode="drop")
    vhi = vhi.at[tgt].set(jnp.where(s_f, s_vh, 0), mode="drop")
    vlo = vlo.at[tgt].set(jnp.where(s_f, s_vl, 0), mode="drop")
    addr = addr.at[tgt].set(s_addr, mode="drop")

    # round-1 gather (one page per active key) + every straggler-loop row
    n_reads = jnp.sum(active.astype(jnp.uint32)) + loop_reads
    counters = counters.at[D.CNT_READ_OPS].add(n_reads)
    counters = counters.at[D.CNT_READ_PAGES].add(n_reads)
    done = done & active
    return counters, done, addr, found & done, vhi, vlo


def search_spmd(pool, counters, khi, klo, root, active, start=None, *,
                cfg: DSMConfig, iters: int,
                axis_name: str = AXIS):
    """Batched ``Tree::search`` (Tree.cpp:405-458): pure one-sided reads.

    With ``start`` (host index-cache seeds), descent starts at the seeded
    page — normally the leaf itself (cache-hit path, Tree.cpp:415-427).
    Returns (done, found, vhi, vlo) per key.
    """
    counters, _, page, done = descend_spmd(
        pool, counters, khi, klo, root, active, cfg=cfg, iters=iters,
        axis_name=axis_name, start=start)
    found, vhi, vlo, _ = layout.leaf_find_key(page, khi, klo)
    return counters, done, found & done, vhi, vlo


# ---------------------------------------------------------------------------
# Owner-side leaf apply: the write fast path.
# ---------------------------------------------------------------------------

def leaf_apply_spmd(pool, locks, counters, inc, fresh=None, *,
                    cfg: DSMConfig, update_only: bool = False,
                    combine: bool = False):
    """Apply routed insert requests to this node's leaf pages.

    inc: dict of [M] arrays — active, addr (leaf), khi, klo, vhi, vlo.
    fresh: optional [F] int32 pre-allocated LOCAL page addrs (0 = no
    grant) enabling device-side leaf splits.
    Returns (pool, counters, status [M]) — plus a split log dict when
    ``fresh`` is given.

    ``update_only`` (static) compiles the steady-state fast kernel:
    requests whose key is NOT already present report ST_FULL (escalate
    to the general kernel with grants) instead of inserting, which drops
    the insert-rank/split machinery and shrinks the write-back to the 3
    words an update actually changes (packed version pair, vhi, vlo) —
    the update-heavy YCSB shape runs ~20% faster.

    Mirrors ``leaf_page_store`` (Tree.cpp:828-921): in-place update of an
    existing key, or insert into a free slot, with the single-entry
    write-back (only the touched 5-word entry is written).  Same-key
    requests are deduped (stable request order: lowest (source, slot)
    wins) — the intra-step linearization that replaces local-lock
    hand-over.

    ``combine`` (static) is HOCL-style write combining (the reference's
    local-lock-table handover, Tree.cpp:218-239): the lock verdict is
    consulted ONCE per page group (the sort's outer key is the page)
    and handed to every row of the group, instead of one lock-word
    gather per row.  Bit-identical by construction — all rows of a page
    hash to ONE lock word (``bits.lock_index`` is per-addr), so the
    per-row verdicts inside a group were always uniform; the only
    observable deltas are the lock-consult count and the
    ``CNT_COMBINE_*`` counter slots.  Deletes
    (:func:`leaf_delete_apply_spmd`) stay uncombined: their per-row
    verdict feeds a row-compacted CAS path with no group structure to
    ride.

    Splits (Tree.cpp:922-963, TPU-shaped): the first overflowing insert
    winner of a page (its in-page rank equals the page's free-slot count)
    becomes the page's *splitter* and is granted a fresh page; the owner
    sorts the LEAF_CAP slots + pending entry, writes the upper half to the
    fresh right sibling and rewrites the left page with fences/sibling
    updated — the B-link makes the split correct before any parent knows
    (the log lets the host insert parent entries lazily, which is why
    splits don't need the recursive ascent on-device).  Every other write
    to a splitting page retries next step: the split rewrites the whole
    page from the pre-step snapshot, so co-applying would be lost.
    """
    M = inc["addr"].shape[0]
    P = pool.shape[0]
    L = locks.shape[0]
    act = inc["active"]
    khi, klo = inc["khi"], inc["klo"]
    page_idx = bits.addr_page(inc["addr"])
    safe_page = jnp.clip(page_idx, 0, P - 1)
    # ONE materialized snapshot gather: pg feeds many consumers (fences,
    # liveness, find, versions); the barrier stops XLA rematerializing
    # the gather into consumer fusions (net-neutral at the 131 K-page
    # scale, insurance at larger pools where a duplicated gather costs
    # the full per-row latency again).  Reusing the descent's round-1
    # pages here instead was measured SLOWER (+24 ms at 2 M rows — the
    # materialized [B, PW] hint buffer costs more than the re-gather).
    # gather_impl="pallas": the explicit-DMA snapshot kernel's output IS
    # the materialized buffer — no barrier needed.
    with jax.named_scope("snapshot"):
        use_pk = pallas_page.use_pallas(cfg)
        if use_pk:
            pg = pallas_page.gather_pages(pool, safe_page)  # [M, PW]
        else:
            pg = lax.optimization_barrier(pool[safe_page])  # [M, PW]

    with jax.named_scope("lock"):
        lock_idx = bits.lock_index(inc["addr"], cfg.locks_per_node)
        if not combine:
            locked = locks[jnp.clip(lock_idx, 0, L - 1)] != 0

    with jax.named_scope("apply"):
        sane = act & (page_idx >= 0) & (page_idx < P) \
            & (layout.h_level(pg) == 0) & layout.in_fence(pg, khi, klo) \
            & layout.page_consistent(pg)
        # combined mode defers the lock verdict to the per-group consult
        # below (sane rows enter the sort; their page-group head decides)
        ok_req = sane if combine else (sane & ~locked)

        found, _, _, fslot = layout.leaf_find_key(pg, khi, klo)
        if update_only:
            assert fresh is None, "update_only excludes the split path"
            freec = jnp.zeros(M, jnp.int32)  # unused: no insert ranking
        else:
            free = ~layout.leaf_slot_used(pg)                  # [M, CAP]
            cumfree = jnp.cumsum(free.astype(jnp.int32), axis=-1)
            freec = cumfree[:, -1]                         # page free slots

        # --- dedupe + insert-rank in ONE sorted pass -----------------------
        # A single multi-operand lax.sort (stable) groups requests by
        # (page, key) and carries the original index / found / free-count
        # along — measured 4x cheaper than lexsort + per-array
        # permutation gathers, and it subsumes the old second sort for
        # insert ranks: the sort's outer key IS the page, so a segmented
        # count over the sorted order ranks each fresh-insert winner
        # within its page (the scan-based segment base replaces an
        # O(B log B) searchsorted).
        # Dedup winner = first row of its group = lowest original index.
        # A superseded loser is final ONLY when its winner applied (the
        # winner's write is then a legal concurrent overwrite of the
        # loser's value); a loser whose winner went to the split path
        # (ST_FULL) must retry — the acked write would otherwise be
        # observably absent.
        idx0 = jnp.arange(M, dtype=jnp.int32)
        pk = jnp.where(ok_req, page_idx, P)
        if combine:
            # -- HOCL-style handover: one lock consult per page group -----
            # The sort already groups rows by page; carry the lock index
            # along, consult the lock word only at each group's head, and
            # hand the verdict down the group with a position-encoded
            # running max (same encoding as the dedup-winner broadcast
            # below).  Locked groups' rows fall out of ``sok`` exactly as
            # the per-row gather would have dropped them — same page ⇒
            # same lock word ⇒ uniform verdict — so everything downstream
            # (dedup, ranks, splits, write-back, statuses) is unchanged.
            sp, skhi, sklo, sidx, sfound, sfreec, slidx = lax.sort(
                (pk, bits._ux(khi), bits._ux(klo), idx0, found, freec,
                 lock_idx), num_keys=3)
            sok_all = sp < P
            page_head_all = jnp.concatenate(
                [sok_all[:1], (sp[1:] != sp[:-1]) & sok_all[1:]])
            with jax.named_scope("lock"):
                head_lw = locks[jnp.where(page_head_all,
                                          jnp.clip(slidx, 0, L - 1), 0)]
            head_locked = page_head_all & (head_lw != 0)
            encL = lax.associative_scan(
                jnp.maximum,
                jnp.where(page_head_all,
                          idx0 * 2 + head_locked.astype(jnp.int32), -1))
            locked_s = sok_all & ((encL & 1) == 1)
            sok = sok_all & ~locked_s
            u32c = lambda m: jnp.sum(m.astype(jnp.uint32))
            counters = counters.at[D.CNT_COMBINE_GROUPS].add(
                u32c(page_head_all))
            counters = counters.at[D.CNT_COMBINE_SAVED].add(
                u32c(sok_all) - u32c(page_head_all))
        else:
            sp, skhi, sklo, sidx, sfound, sfreec = lax.sort(
                (pk, bits._ux(khi), bits._ux(klo), idx0, found, freec),
                num_keys=3)
            sok = sp < P
        same_prev = jnp.concatenate([
            jnp.zeros(1, bool),
            (sp[1:] == sp[:-1]) & (skhi[1:] == skhi[:-1])
            & (sklo[1:] == sklo[:-1])
            & sok[1:],
        ])
        winner_s = sok & ~same_prev
        ESCALATE = M + M  # update_only's not-found code, above any rank/split
        if update_only:
            # winners apply iff their key exists; not-found winners escalate
            applied_s = winner_s & sfound
            ins_code_s = jnp.full(M, ESCALATE, jnp.int32)
        else:
            need_ins_s = winner_s & ~sfound
            # rank among the page's fresh inserts: cum at row minus cum at the
            # page segment's head (cum_excl is nondecreasing, so a running max
            # over head-masked values yields the latest head's base)
            page_head = jnp.concatenate([jnp.ones(1, bool), sp[1:] != sp[:-1]])
            cum = jnp.cumsum(need_ins_s.astype(jnp.int32))
            cum_excl = cum - need_ins_s
            base = lax.associative_scan(
                jnp.maximum, jnp.where(page_head, cum_excl, -1))
            rank_s = cum_excl - base
            # a winner applies if it updates, or its insert rank fits the
            # page's free slots
            applied_s = winner_s & (sfound | (rank_s < sfreec))
            ins_code_s = rank_s
        # propagate the head's verdict to its losers with a position-encoded
        # running max (groups are contiguous, heads are winners)
        enc = lax.associative_scan(
            jnp.maximum,
            jnp.where(winner_s, idx0 * 2 + applied_s.astype(jnp.int32), -1))
        grp_winner_applied = (enc & 1) == 1
        # sorted-space verdicts: -4 loser whose winner did not apply (retry),
        # -3 dropped, -2 superseded-final, -1 winner-found (update),
        # 0 <= r < SPLIT_CODE winner insert rank, SPLIT_CODE + f granted
        # splitter using fresh slot f, ESCALATE update_only's key-absent.
        # Ranks are strictly below M (at most M requests per page), so M is a
        # safe static boundary for any batch geometry.
        SPLIT_CODE = M
        code_s = jnp.where(
            ~sok, -3,
            jnp.where(~winner_s, jnp.where(grp_winner_applied, -2, -4),
                      jnp.where(sfound, -1, ins_code_s)))
        if fresh is not None:
            F = fresh.shape[0]
            # the page's FIRST overflowing insert (rank == free count) splits
            splitter_s = need_ins_s & (rank_s == sfreec)
            sf_idx = jnp.cumsum(splitter_s.astype(jnp.int32)) - 1
            grant = fresh[jnp.clip(sf_idx, 0, F - 1)]
            granted_s = splitter_s & (sf_idx < F) & (grant != 0)
            code_s = jnp.where(granted_s, SPLIT_CODE + sf_idx, code_s)
        # un-sort via a 2-operand key-value sort (sidx is a permutation of
        # [0, M)): ~1 ms at 2 M rows on v5e vs ~15 ms for the equivalent
        # full-width scatter
        if combine:
            # carry the group verdict back to row space for the status line
            _, code, locked_i = lax.sort(
                (sidx, code_s, locked_s.astype(jnp.int32)), num_keys=1)
            locked = locked_i != 0
        else:
            _, code = lax.sort((sidx, code_s), num_keys=1)
        winner_upd = code == -1
        superseded = code == -2
        loser_retry = code == -4

        if update_only:
            splitter = jnp.zeros(M, bool)
            suppressed = jnp.zeros(M, bool)
            full = code == ESCALATE      # ST_FULL -> caller escalates to the
            applied = winner_upd         # general kernel (grants + inserts)
            slot = fslot
        else:
            splitter = (code >= SPLIT_CODE) & (code < ESCALATE)
            winner_ins = (code >= 0) & ~splitter
            rank = jnp.where(winner_ins, code, 0)
            have_slot = freec >= (rank + 1)

            if fresh is not None:
                has_split = jnp.zeros(P + 1, bool).at[
                    jnp.where(splitter, safe_page, P)].set(True, mode="drop")
                page_splitting = has_split[safe_page]
            else:
                page_splitting = jnp.zeros(M, bool)

            # On a splitting page, updates and fitting inserts (rank < free
            # count) STILL apply — the split consumes the post-apply page, so
            # nothing is lost and the page splits exactly full.  Only inserts
            # ranked past the free slots retry (they land in the halves next
            # round).  Without this, an append-shaped workload funnels into
            # the rightmost leaf at ONE key per step.
            suppressed = winner_ins & page_splitting & ~have_slot
            full = winner_ins & ~have_slot & ~page_splitting
            applied = winner_upd | (winner_ins & have_slot)

            target = (rank + 1)[:, None]
            islot = jnp.argmax(cumfree >= target, axis=-1)
            slot = jnp.where(found, fslot, islot)

        # --- single-entry write-back scatter -------------------------------
        # one-hot extract of the slot's old packed version pair
        # (take_along_axis is slow on TPU)
        ver_blk = pg[:, C.L_VER_W:C.L_VER_W + C.LEAF_CAP]
        slot_oh = jnp.arange(C.LEAF_CAP)[None, :] == slot[:, None]
        old_fv = (jnp.sum(jnp.where(slot_oh, ver_blk, 0), axis=-1)
                  >> 16) & C.ENTRY_VER_MASK
        new_ver = (old_fv + 1) & C.ENTRY_VER_MASK
        new_ver = jnp.where(new_ver == 0, 1, new_ver)
        new_pair = layout.ver_pack(new_ver)

    with jax.named_scope("writeback"):
        # ONE fused scatter pass of exactly the entry words that change — the
        # reference single-entry write-back (Tree.cpp:914-921) writes the
        # LeafEntry only: page front/rear versions move on STRUCTURAL
        # rewrites (splits, internal rebuilds), not per-entry updates, and
        # the entry's own fver/rver pair carries the write's visibility.
        # Scatter cost is ~13.5 ms per word lane at 2 M rows on v5e, so lane
        # count is the write path's #1 knob: the 16/16-packed version pair
        # makes updates touch 3 words (version pair + value); inserts also
        # write the 2 key words.
        if update_only:
            ent = jnp.stack([new_pair, inc["vhi"], inc["vlo"]],
                            axis=-1)                           # [M, 3]
            lanes = (C.L_VER_W, C.L_VHI_W, C.L_VLO_W)
        else:
            ent = jnp.stack([new_pair, khi, klo, inc["vhi"], inc["vlo"]],
                            axis=-1)                           # [M, 5]
            lanes = (C.L_VER_W, C.L_KHI_W, C.L_KLO_W, C.L_VHI_W, C.L_VLO_W)
        if use_pk:
            # all lanes ride ONE kernel pass (per-row doorbell batch of
            # single-word DMAs) instead of one full-batch scatter per lane
            pool = pallas_page.writeback(pool, safe_page, slot, applied,
                                         ent, lanes)
        else:
            # the twin the parity fuzz pins IS the served path
            pool = pallas_page.writeback_xla(pool, safe_page, slot, applied,
                                             ent, lanes)

        # --- device-side splits (consume the POST-apply page) --------------
        if fresh is not None:
            pool, counters, log = _leaf_split_apply(
                pool, counters, inc, splitter, code - SPLIT_CODE, fresh,
                safe_page, cfg=cfg)

    # --- status ------------------------------------------------------------
    status = jnp.full(M, ST_INVALID, jnp.int32)
    status = jnp.where(act, ST_BAD, status)
    status = jnp.where(act & sane & locked, ST_LOCKED, status)
    status = jnp.where(loser_retry | suppressed, ST_RETRY, status)
    status = jnp.where(superseded, ST_SUPERSEDED, status)
    status = jnp.where(full, ST_FULL, status)
    status = jnp.where(applied | splitter, ST_APPLIED, status)

    u32 = lambda m: jnp.sum(m.astype(jnp.uint32))
    counters = counters.at[D.CNT_WRITE_OPS].add(u32(applied))
    counters = counters.at[D.CNT_WRITE_WORDS].add(
        u32(applied) * jnp.uint32(3 if update_only
                                  else C.LEAF_ENTRY_WORDS))
    if fresh is not None:
        return pool, counters, status, log
    return pool, counters, status


def _leaf_pages(blk_khi, blk_klo, blk_vhi, blk_vlo, blk_live, ver, low_hi,
                low_lo, high_hi, high_lo, sibling):
    """Assemble [R] whole leaf pages from [R, LEAF_CAP] field blocks +
    [R] header words — the ONE place that knows the leaf wire layout as
    full pages (shared by the device split kernel and the bulk-load
    builder).  Dead slots are zeroed; fver/rver carry the liveness."""
    R = blk_khi.shape[0]
    CAP = C.LEAF_CAP
    page = jnp.zeros((R, _PW), jnp.int32)
    page = page.at[:, C.W_FRONT_VER].set(ver)
    page = page.at[:, C.W_REAR_VER].set(ver)
    page = page.at[:, C.W_SIBLING].set(sibling)
    page = page.at[:, C.W_LOW_HI].set(low_hi)
    page = page.at[:, C.W_LOW_LO].set(low_lo)
    page = page.at[:, C.W_HIGH_HI].set(high_hi)
    page = page.at[:, C.W_HIGH_LO].set(high_lo)
    lv = blk_live.astype(jnp.int32) * jnp.int32(layout.ver_pack(1))
    page = page.at[:, C.L_VER_W:C.L_VER_W + CAP].set(lv)
    z = lambda b: jnp.where(blk_live, b, 0)
    page = page.at[:, C.L_KHI_W:C.L_KHI_W + CAP].set(z(blk_khi))
    page = page.at[:, C.L_KLO_W:C.L_KLO_W + CAP].set(z(blk_klo))
    page = page.at[:, C.L_VHI_W:C.L_VHI_W + CAP].set(z(blk_vhi))
    page = page.at[:, C.L_VLO_W:C.L_VLO_W + CAP].set(z(blk_vlo))
    return page


def _leaf_split_apply(pool, counters, inc, splitter, fidx, fresh,
                      safe_page, *, cfg: DSMConfig):
    """Execute granted leaf splits in a compacted [F] buffer.

    splitter/fidx select granted rows and their fresh-page slots.  Reads
    the POST-apply page from ``pool`` (this step's fitting inserts and
    updates already landed, so the page splits exactly full and nothing
    co-applied is lost), builds both halves as whole pages (a split is a
    full-page rewrite in the reference too, Tree.cpp:922-963), and
    returns a log for lazy parent insertion + index-cache refresh.
    """
    M = splitter.shape[0]
    P = pool.shape[0]
    F = fresh.shape[0]
    CAP = C.LEAF_CAP

    sidx2 = jnp.nonzero(splitter, size=F, fill_value=M)[0].astype(jnp.int32)
    valid = sidx2 < M
    ci = jnp.clip(sidx2, 0, M - 1)
    left_row = safe_page[ci]
    spg = pool[left_row]                           # [F, PW] POST-apply
    pkhi, pklo = inc["khi"][ci], inc["klo"][ci]
    pvhi, pvlo = inc["vhi"][ci], inc["vlo"][ci]
    new_addr = fresh[jnp.clip(fidx[ci], 0, F - 1)]
    right_row = jnp.clip(bits.addr_page(new_addr), 0, P - 1)
    valid = valid & (new_addr != 0)

    # sort the LEAF_CAP slots + pending entry by key; dead slots sort last
    sv = layout.leaf_slots_view(spg)
    live = jnp.concatenate(
        [layout.leaf_slot_used(spg), jnp.ones((F, 1), bool)], axis=1)
    cat = lambda blk, pend: jnp.concatenate([blk, pend[:, None]], axis=1)
    k_hi, k_lo = cat(sv["khi"], pkhi), cat(sv["klo"], pklo)
    v_hi, v_lo = cat(sv["vhi"], pvhi), cat(sv["vlo"], pvlo)
    inf = jnp.int32(0x7FFFFFFF)
    gkh_key = jnp.where(live, bits._ux(k_hi), inf)
    gkl_key = jnp.where(live, bits._ux(k_lo), inf)
    # dead slots sort last, so sorted column j is live iff j < n
    _, _, gkh, gkl, gvh, gvl = lax.sort(
        (gkh_key, gkl_key, k_hi, k_lo, v_hi, v_lo), num_keys=2,
        dimension=1)                               # [F, CAP+1] each

    n = jnp.sum(live, axis=1).astype(jnp.int32)    # live incl pending
    m = n // 2                                     # left keeps m entries
    cols = jnp.arange(CAP + 1, dtype=jnp.int32)[None, :]
    # split key = first right entry (one-hot: column == m)
    at_m = cols == m[:, None]
    skhi = jnp.sum(jnp.where(at_m, gkh, 0), axis=1)
    sklo = jnp.sum(jnp.where(at_m, gkl, 0), axis=1)

    colsC = jnp.arange(CAP, dtype=jnp.int32)[None, :]
    l_live = colsC < m[:, None]
    ridx = jnp.clip(m[:, None] + colsC, 0, CAP)
    r_live = colsC < (n - m)[:, None]
    take = lambda a: jnp.take_along_axis(a, ridx, axis=1)

    old_ver = spg[:, C.W_FRONT_VER]
    bumped = (old_ver + 1) & 0x7FFFFFFF
    lver = jnp.where(bumped == 0, 1, bumped)
    old_hhi, old_hlo = spg[:, C.W_HIGH_HI], spg[:, C.W_HIGH_LO]
    left = _leaf_pages(gkh[:, :CAP], gkl[:, :CAP], gvh[:, :CAP],
                       gvl[:, :CAP], l_live, lver, spg[:, C.W_LOW_HI],
                       spg[:, C.W_LOW_LO], skhi, sklo, new_addr)
    right = _leaf_pages(take(gkh), take(gkl), take(gvh), take(gvl), r_live,
                        jnp.ones(F, jnp.int32), skhi, sklo, old_hhi,
                        old_hlo, spg[:, C.W_SIBLING])

    # right page first in program order is irrelevant — both land at the
    # step boundary (the atomic-split guarantee, stronger than the
    # reference's ordered sibling-then-page writes)
    pool = pool.at[jnp.where(valid, right_row, P)].set(right, mode="drop")
    pool = pool.at[jnp.where(valid, left_row, P)].set(left, mode="drop")

    u32 = lambda x: jnp.sum(x.astype(jnp.uint32))
    counters = counters.at[D.CNT_WRITE_OPS].add(u32(valid) * jnp.uint32(2))
    counters = counters.at[D.CNT_WRITE_WORDS].add(
        u32(valid) * jnp.uint32(2 * _PW))

    log = {"valid": valid, "skhi": skhi, "sklo": sklo,
           "new_addr": jnp.where(valid, new_addr, 0),
           "old_hhi": old_hhi, "old_hlo": old_hlo}
    return pool, counters, log


def _resolve_leaves(pool, counters, khi, klo, root, active, start, *,
                    cfg: DSMConfig, iters: int, axis_name: str):
    """Walk every active key to its leaf, picking the best descent:
    cache-seeded compacted loop when seeds exist (any mesh size),
    generic full-batch descent otherwise.  -> (counters, done, addr,
    found, vhi, vlo); callers that only need addresses let XLA drop the
    lookup outputs.
    """
    if start is not None:
        return _routed_resolve(pool, counters, khi, klo, active, start,
                               iters=iters, cfg=cfg, axis_name=axis_name)
    counters, addr, page, done = descend_spmd(
        pool, counters, khi, klo, root, active, cfg=cfg, iters=iters,
        axis_name=axis_name, start=start)
    f, vh, vl, _ = layout.leaf_find_key(page, khi, klo)
    found = f & done
    return (counters, done, addr, found,
            jnp.where(found, vh, 0), jnp.where(found, vl, 0))


def _mark_dirty_pages(dirty, page_idx, active):
    """OR ``active`` rows' (owner-local) target pages into the dirty
    shard — the delta-checkpoint feed.  Marks the pages the apply MAY
    write (lock-blocked / deduped rows over-mark: a spare delta row,
    never a missed one)."""
    P = dirty.shape[0]
    rows = jnp.where(active & (page_idx >= 0) & (page_idx < P),
                     page_idx, P)
    return dirty.at[rows].set(True, mode="drop")


def _route_and_apply(pool, locks, counters, dirty, apply_fn, addr, eligible,
                     fields, *, cfg: DSMConfig, axis_name: str):
    """Ship ``eligible`` requests to their owner nodes and apply.

    Shared tail of the insert/delete/mixed steps: single-node applies
    directly; multi-node bucketizes by owner, all_to_all-exchanges the
    request fields, applies on the owner, and routes statuses back.
    ``fields`` are the per-request arrays ``apply_fn`` expects beyond
    active/addr.  Returns (pool, counters, dirty, status_raw [B], extra)
    where status_raw is the apply status for eligible routed rows and
    ST_RETRY for rows that missed the bucket capacity (full RDMA send
    queue moral equivalent) — callers mask inactive rows to ST_INVALID;
    ``dirty`` is the per-node dirty-page mask with this step's write
    targets marked (delta-checkpoint feed; ``None`` = untracked, passed
    through).  ``extra`` is the apply_fn's optional 4th output (e.g. the
    split log), which stays owner-node-local (no reply routing).
    """
    N, cap = cfg.machine_nr, cfg.step_capacity
    if N == 1:
        inc = {"active": eligible, "addr": addr, **fields}
        if dirty is not None:
            dirty = _mark_dirty_pages(dirty, bits.addr_page(addr), eligible)
        out = apply_fn(pool, locks, counters, inc, cfg=cfg)
        pool, counters, st = out[:3]
        extra = out[3] if len(out) > 3 else None
        return (pool, counters, dirty,
                jnp.where(eligible, st, ST_RETRY), extra)

    dest = bits.addr_node(addr)
    bucket_idx, routed = transport.bucketize(dest, eligible, N, cap)
    out_fields = {"active": eligible & routed, "addr": addr, **fields}
    out = {k: transport.scatter_to_buckets(v, bucket_idx, N * cap)
           for k, v in out_fields.items()}
    inc = transport.exchange(out, axis_name, impl=cfg.exchange_impl)
    if dirty is not None:
        dirty = _mark_dirty_pages(dirty, bits.addr_page(inc["addr"]),
                                  inc["active"])
    aout = apply_fn(pool, locks, counters, inc, cfg=cfg)
    pool, counters, st = aout[:3]
    extra = aout[3] if len(aout) > 3 else None
    rep = transport.exchange({"st": st}, axis_name,
                             impl=cfg.exchange_impl)
    safe_b = jnp.where(routed, bucket_idx, 0)
    return (pool, counters, dirty,
            jnp.where(eligible & routed, rep["st"][safe_b], ST_RETRY),
            extra)


def insert_step_spmd(pool, locks, counters, khi, klo, vhi, vlo, root,
                     active, start=None, fresh=None, *, cfg: DSMConfig,
                     iters: int, axis_name: str = AXIS,
                     update_only: bool = False, combine: bool = False,
                     dirty=None):
    """One batched insert step: descend + route to owners + leaf apply.

    With ``fresh`` (per-node pre-allocated pages), full leaves split
    owner-side and a split log is returned for lazy parent insertion.
    ``update_only`` compiles the steady-state kernel (see
    :func:`leaf_apply_spmd`).  Returns (pool, counters, status [B]) per
    this node's key shard — plus the log when ``fresh`` is given.

    ``dirty`` (keyword-only): the node's dirty-page mask shard; when
    given, target leaves and granted split pages mark it and it rides
    the return tuple after ``counters`` (the delta-checkpoint feed —
    the ENGINE passes it; raw harness compositions that leave it None
    are outside the durability contract).
    """
    # NOTE: threading the descent's round-1 pages into the apply (to skip
    # its snapshot gather) was measured SLOWER (+24 ms at 2 M rows):
    # materializing the [B, PW] round-1 pages costs more than the
    # duplicate gather, which XLA fuses into the apply's consumers.
    counters, done, addr, _, _, _ = _resolve_leaves(
        pool, counters, khi, klo, root, active, start, cfg=cfg,
        iters=iters, axis_name=axis_name)
    apply_fn = functools.partial(leaf_apply_spmd, fresh=fresh,
                                 update_only=update_only, combine=combine)
    if fresh is not None and dirty is not None:
        # granted split pages are written owner-side this step; marking
        # every OFFERED grant over-marks unconsumed ones (spare delta
        # rows, never a miss)
        dirty = _mark_dirty_pages(dirty, bits.addr_page(fresh), fresh != 0)
    pool, counters, dirty, status, log = _route_and_apply(
        pool, locks, counters, dirty, apply_fn, addr, done,
        {"khi": khi, "klo": klo, "vhi": vhi, "vlo": vlo},
        cfg=cfg, axis_name=axis_name)
    status = jnp.where(active, status, ST_INVALID)
    state = (pool, counters) if dirty is None else (pool, counters, dirty)
    if fresh is not None:
        return (*state, status, log)
    return (*state, status)


# ---------------------------------------------------------------------------
# Batched delete: descend + routed owner-side slot clear.
# ---------------------------------------------------------------------------

def leaf_delete_apply_spmd(pool, locks, counters, inc, *, cfg: DSMConfig):
    """Clear routed delete requests on this node's leaf pages.

    Mirrors ``Tree::del``'s leaf step (btree.py delete / reference
    ``Tree.cpp`` del path): zero the slot's fver/rver pair — the two-level
    version liveness rule makes the slot free.  Clearing is idempotent, so
    same-key duplicates need no dedup (they scatter identical zeros).
    Returns (pool, counters, status [M]).
    """
    M = inc["addr"].shape[0]
    P = pool.shape[0]
    L = locks.shape[0]
    act = inc["active"]
    khi, klo = inc["khi"], inc["klo"]
    page_idx = bits.addr_page(inc["addr"])
    safe_page = jnp.clip(page_idx, 0, P - 1)
    use_pk = pallas_page.use_pallas(cfg)
    if use_pk:
        pg = pallas_page.gather_pages(pool, safe_page)  # one gather
    else:
        pg = lax.optimization_barrier(pool[safe_page])  # one gather, many uses

    lock_idx = bits.lock_index(inc["addr"], cfg.locks_per_node)
    locked = locks[jnp.clip(lock_idx, 0, L - 1)] != 0

    sane = act & (page_idx >= 0) & (page_idx < P) \
        & (layout.h_level(pg) == 0) & layout.in_fence(pg, khi, klo) \
        & layout.page_consistent(pg)
    ok_req = sane & ~locked

    found, _, _, slot = layout.leaf_find_key(pg, khi, klo)
    applied = ok_req & found
    safe_slot = jnp.clip(slot, 0, C.LEAF_CAP - 1)

    # ONE scatter: zero the slot's packed version word — the slot becomes
    # free.  Like the insert write-back, page front/rear versions move
    # only on structural rewrites (reference parity: Tree::del writes the
    # entry, not the page header).
    wb = pallas_page.writeback if use_pk else pallas_page.writeback_xla
    pool = wb(pool, safe_page, safe_slot, applied,
              jnp.zeros((M, 1), jnp.int32), (C.L_VER_W,))

    status = jnp.full(M, ST_INVALID, jnp.int32)
    status = jnp.where(act, ST_BAD, status)
    status = jnp.where(act & sane & locked, ST_LOCKED, status)
    status = jnp.where(ok_req & ~found, ST_NOT_FOUND, status)
    status = jnp.where(applied, ST_APPLIED, status)

    u32 = lambda m: jnp.sum(m.astype(jnp.uint32))
    counters = counters.at[D.CNT_WRITE_OPS].add(u32(applied))
    # the slot's packed version word
    counters = counters.at[D.CNT_WRITE_WORDS].add(u32(applied))
    return pool, counters, status


def delete_step_spmd(pool, locks, counters, khi, klo, root, active,
                     start=None, *, cfg: DSMConfig, iters: int,
                     axis_name: str = AXIS, dirty=None):
    """One batched delete step: descend + route to owners + slot clear.

    Returns (pool, counters, status [B]) per this node's key shard —
    with ``dirty`` threaded after ``counters`` when given (see
    :func:`insert_step_spmd`).
    """
    counters, done, addr, _, _, _ = _resolve_leaves(
        pool, counters, khi, klo, root, active, start, cfg=cfg, iters=iters,
        axis_name=axis_name)
    pool, counters, dirty, status, _ = _route_and_apply(
        pool, locks, counters, dirty, leaf_delete_apply_spmd, addr, done,
        {"khi": khi, "klo": klo}, cfg=cfg, axis_name=axis_name)
    status = jnp.where(active, status, ST_INVALID)
    if dirty is None:
        return pool, counters, status
    return pool, counters, dirty, status


# ---------------------------------------------------------------------------
# Mixed step: searches and upserts share one descent (YCSB-A/B shape).
# ---------------------------------------------------------------------------

def mixed_step_spmd(pool, locks, counters, khi, klo, vhi, vlo, root,
                    active_r, active_w, start=None, *, cfg: DSMConfig,
                    iters: int, axis_name: str = AXIS,
                    write_lo: int | None = None,
                    update_only: bool = False, combine: bool = False,
                    dirty=None):
    """One fused step of searches (``active_r``) and upserts (``active_w``).

    The reference interleaves reads and writes per thread from one open
    loop (``benchmark.cpp:159-188``); the batched equivalent runs both
    workload classes through a SINGLE descent per step — a read costs the
    same whether its neighbor is a write.  Consistency: reads that resolve
    in this step see the pre-step pool snapshot, and writes apply at the
    step boundary — the serial order is (resolved reads) < (writes).
    Reads that overrun the descent budget (done_r False) are NOT part of
    this step's linearization: the caller retries them in a later step,
    where they may legally observe this step's writes (the same outcome
    as a reference thread whose read lost the race to a concurrent
    writer).

    Returns (pool, counters, status [B], done_r [B], found [B], vhi [B],
    vlo [B]); status is ST_* for write keys, done_r/found/v* cover
    reads.  With ``dirty`` given it rides after ``counters``, write
    targets marked (see :func:`insert_step_spmd`).

    ``write_lo`` (static): when the caller lays each node's shard out as
    ``[reads | writes]`` with writes in ``[write_lo:]``, the apply runs on
    that half-width slice only — the apply path (page snapshot gather,
    dedup sort, write-back scatter) costs per ROW regardless of activity,
    so applying over the full batch pays ~2x for a 50/50 mix.
    """
    active = active_r | active_w
    counters, done, addr, found, rvh, rvl = _resolve_leaves(
        pool, counters, khi, klo, root, active, start, cfg=cfg, iters=iters,
        axis_name=axis_name)

    done_r = done & active_r
    found = found & done_r
    rvh = jnp.where(found, rvh, 0)
    rvl = jnp.where(found, rvl, 0)

    if write_lo is None:
        w = slice(None)
        pad = 0
    else:
        w = slice(write_lo, None)
        pad = write_lo
    pool, counters, dirty, st_w, _ = _route_and_apply(
        pool, locks, counters, dirty,
        functools.partial(leaf_apply_spmd, update_only=update_only,
                          combine=combine),
        addr[w], (done & active_w)[w],
        {"khi": khi[w], "klo": klo[w], "vhi": vhi[w], "vlo": vlo[w]},
        cfg=cfg, axis_name=axis_name)
    if pad:
        st_w = jnp.concatenate(
            [jnp.full(pad, ST_INVALID, jnp.int32), st_w])
    status = jnp.where(active_w, st_w, ST_INVALID)
    if dirty is None:
        return pool, counters, status, done_r, found, rvh, rvl
    return pool, counters, dirty, status, done_r, found, rvh, rvl


# ---------------------------------------------------------------------------
# Host-facing engine: jit/shard_map wrappers + retry loop.
# ---------------------------------------------------------------------------

def _assert_replicated(multihost: bool, arrays, what: str) -> None:
    """Multihost divergence guard: all processes must drive identical
    request streams — mirrored allocators and collective step sequences
    depend on it.  Cheap digest allgather; raises loudly on skew."""
    if not multihost:
        return
    import zlib

    from jax.experimental import multihost_utils as mhu
    dig = 0
    for a in arrays:
        dig = zlib.crc32(np.ascontiguousarray(a).tobytes(), dig)
    digs = np.asarray(mhu.process_allgather(
        np.asarray([dig], np.uint32))).ravel()
    if not (digs == np.uint32(dig)).all():
        raise ProtocolError(
            f"multihost {what} diverged across processes: every process "
            "must drive identical request streams (replicated-driver SPMD)")


class BatchedEngine:
    """Compiled batched ops over a :class:`~sherman_tpu.models.btree.Tree`.

    The engine is the analogue of ``run_coroutine`` (Tree.cpp:1059-1122) ×
    doorbell batching: a fixed per-node batch shape keeps one compiled
    program per tree height.
    """

    def __init__(self, tree, batch_per_node: int = 1024,
                 tcfg: TreeConfig | None = None,
                 split_slots: int | None = None,
                 write_combine: bool | None = None):
        self.tree = tree
        self.dsm = tree.dsm
        self.cfg = tree.cfg
        self.tcfg = tcfg if tcfg is not None else TreeConfig()
        self.B = batch_per_node
        # HOCL-style write combining (leaf_apply_spmd's ``combine``
        # static): one lock consult per same-leaf write group.  None
        # (default) reads the SHERMAN_WRITE_COMBINE knob; explicit
        # True/False pins it for A/B drivers and tests.  Static per
        # engine — it selects which program the jit caches compile.
        self._write_combine = (C.write_combine() if write_combine is None
                               else bool(write_combine))
        # device-split grant slots per node per insert round; unused grants
        # are cached host-side and re-offered (free() is a no-op, so
        # abandoning them would leak pages every round).  The default
        # suits steady-state workloads; split-storm drivers (fresh-key
        # bulk insertion into a near-full tree) raise it so one round can
        # split tens of thousands of leaves (tools/insert_bench.py).
        self.split_slots = (min(256, batch_per_node) if split_slots is None
                            else min(split_slots, batch_per_node))
        # Mid-chunk parent-flush trigger: flush when the pending backlog
        # reaches this many entries (insert() always flushes at the end
        # regardless).  1 = every round (default, tightest chains); a
        # split-storm driver raises it to ~split_slots — the router's
        # note_split keeps descents short between flushes, and each flush
        # pass costs several host round trips.
        self.parent_flush_threshold = 1
        self._fresh_cache: dict[int, list[int]] = {}
        self._pending_parents: list[tuple[int, int]] = []
        # empty-leaf reclamation bookkeeping (reclaim_empty_leaves).
        # "parked" holds retired pages still referenced as some parent's
        # LEFTMOST child — they stay retired forever (self-healing via
        # their back-sibling) rather than risking a dangling reference
        # into a reused page; bounded at ~1/INTERNAL_CAP of reclaimable
        # leaves.
        self._reclaim_state: dict = {"round": 0, "quarantine": [],
                                     "pending_parent": [], "parked": set()}
        # reclaim mutates engine-local reclaim state and the allocator
        # free pools across many steps; it is a maintenance pass, not a
        # concurrent op — overlapping calls are a caller bug
        self._reclaim_mutex = threading.Lock()
        self._parent_descend_cache: dict = {}
        self.router = None
        # Optional hot-key tier (models/leaf_cache.py, attached by
        # attach_leaf_cache / the SHERMAN_LEAF_CACHE knob): a versioned
        # compute-side leaf/value cache probed in front of the descent
        # by search/search_combined/mixed; write entry points invalidate
        # it, degraded entry flushes it.  None (default) costs one
        # `is None` test per read batch.
        self.leaf_cache = None
        # Optional out-of-line value heap (models/value_heap.py,
        # attached by attach_value_heap): leaf values become versioned
        # slab handles resolved in the fused fan-out; journal replay
        # discovers it here.  None (default) = inline 64-bit values,
        # bit-identical to pre-heap builds.
        self.value_heap = None
        # Optional write-ahead op journal (utils/journal.py, attached by
        # the recovery plane): every engine write op appends ONE batch
        # record of its APPLIED rows before returning — the record is
        # durable before the caller sees the ack, so recovery = restore
        # chain + replay journal loses zero acknowledged ops (RPO 0).
        # None (default) costs one `is None` test per op.  Single-writer
        # contract: record order must match apply order, so journaled
        # engines are driven from one thread (the drill/serving shape).
        self.journal = None
        # Graceful degradation (data-plane failure story): once flipped,
        # every mutating entry point raises DegradedError (typed write
        # rejection) while searches keep serving; exit = checkpoint
        # restore into a fresh engine.  A fresh engine is healthy by
        # construction, so the gauge resets here.
        self._degraded_reason: str | None = None
        _OBS_DEGRADED.set(0)
        self._search_cache: dict = {}
        self._insert_cache: dict = {}
        self._delete_cache: dict = {}
        self._mixed_cache: dict = {}
        spec = jax.sharding.PartitionSpec(AXIS)
        self._spec = spec
        self._rep = jax.sharding.PartitionSpec()
        # Multihost = replicated-driver SPMD: every process must call the
        # engine with IDENTICAL request streams (multi-controller JAX runs
        # the same host program everywhere; host-API ops execute once via
        # cluster.host_dsm, and the device batch shards over the
        # process-spanning mesh).  _check_replicated enforces it.
        self._mh = self.dsm.multihost
        # Compiled-step launches mutate the same donated pool/locks/
        # counters handles as the host-API steps, so concurrent host
        # threads (Tree clients taking locks/splitting — the reference's
        # 26-thread axis, benchmark.cpp:285-287) would race the engine on
        # the handle swap: an engine step built from a pre-host-step pool
        # handle writes back a result that LOSES the host step wholesale.
        # Sharing the DSM's step mutex for the read-handles -> launch ->
        # write-handles window makes every step atomic at the handle
        # level; cross-step consistency is then the lock/version
        # protocol's job, exactly as in the reference.  Launch-only:
        # dispatch is async, so the mutex is held microseconds and never
        # across a host DSM op (threading.Lock is not reentrant).
        self._step_mutex = self.dsm._step_mutex
        # Write-combining observability: the device kernels accumulate
        # group/saved counts in the DSM counter slots (no per-step host
        # sync); this pull-time collector names them the combine.* way
        # the receipts and dashboards expect.  Registered only when the
        # knob is on, so combine-off scrapes are bit-identical to a
        # build without the subsystem.  Weakly bound like the dsm
        # collector.
        self._combine_steps = 0
        self._combine_rows = 0
        if self._write_combine:
            import weakref
            _dref = weakref.ref(self.dsm)
            _eref = weakref.ref(self)

            def _combine_collect():
                d = _dref()
                e = _eref()
                if d is None or e is None:
                    return {}
                snap = d.counter_snapshot()
                groups = snap["combine_groups"]
                saved = snap["combine_locks_saved"]
                return {"groups": groups, "locks_saved": saved,
                        "ops_combined": saved,
                        "steps": float(e._combine_steps),
                        "rows": float(e._combine_rows)}
            obs.register_collector("combine", _combine_collect)

    # -- degraded mode (read-only serving after unrecoverable damage) --------

    @property
    def degraded(self) -> bool:
        return self._degraded_reason is not None

    @property
    def degraded_reason(self) -> str | None:
        return self._degraded_reason

    def enter_degraded(self, reason: str) -> None:
        """Flip to read-only degraded serving: searches continue, writes
        raise :class:`DegradedError`.  Idempotent (the first reason
        wins — it names the root cause)."""
        if self._degraded_reason is None:
            self._degraded_reason = reason
            _OBS_DEGRADED.set(1)
            obs.counter("engine.degraded_entries").inc()
            # the hot-key tier must not serve answers certified against
            # a pool the engine no longer trusts — flush wholesale (the
            # cache is volatile by contract; see leaf_cache.py)
            if self.leaf_cache is not None:
                self.leaf_cache.flush()
            # black box: the transition is a flight event, and entering
            # degraded auto-dumps the bundle (env-gated, debounced) so
            # the postmortem starts from the moment the engine gave up
            FR.record_event("engine.degraded_enter", reason=reason)
            FR.auto_dump("degraded_entry")

    def _note_combine_step(self, rows: int) -> None:
        """Per-batch write-combining accounting (plain integer adds —
        SL006-registered: this runs inside the write wall).  The
        group/saved counts themselves accumulate in the DSM counter
        slots on device; this only tracks how many batches/rows went
        through the combined kernel."""
        self._combine_steps += 1
        self._combine_rows += rows

    def exit_degraded(self) -> None:
        """Clear degraded mode — only after the damage is actually gone
        (state restored or repaired and re-validated); the chaos drill
        is the reference sequence."""
        self._degraded_reason = None
        _OBS_DEGRADED.set(0)
        FR.record_event("engine.degraded_exit")

    def _require_writable(self) -> None:
        if self._degraded_reason is not None:
            FR.record_event("engine.typed_error", error="DegradedError",
                            reason=self._degraded_reason)
            FR.auto_dump("typed_error")
            raise DegradedError(self._degraded_reason)

    def attach_journal(self, journal) -> None:
        """Attach (or detach, with ``None``) the write-ahead op journal;
        see the ``journal`` attribute's contract in ``__init__``."""
        self.journal = journal

    def _journal_applied(self, kind: int, keys, values=None) -> None:
        if self.journal is None or keys.size == 0:
            return
        self.journal.append(kind, keys, values)

    def _iters(self) -> int:
        # STATIC descent budget: max height + chase slack.  Deliberately
        # NOT tied to the live root level — that would change the compiled
        # program shape on every root growth, and every recompile costs
        # seconds to minutes.  Single-node loops exit
        # early dynamically (while_loop), so the slack is free there; the
        # multi-node fori pays it only on CPU test meshes.
        return self.tcfg.max_level + self.tcfg.sibling_chase_budget

    def attach_router(self, log2_buckets: int | None = None,
                      scan: bool = True):
        """Create + seed the device index cache (see router.py).  Uses the
        bulk-load leaf directory when available; otherwise (a restored or
        host-built tree) enumerates the live leaves in one device step
        (``validate.leaf_directory``) so the router is warm AND correctly
        sized from the first batch.  ``scan=False`` forces the cold
        root-seeded table (refined only by split notifications).

        COLLECTIVE in multihost deployments when ``scan=True`` and no
        bulk-load directory exists: the leaf scan does a
        ``process_allgather``, so EVERY process must call attach_router
        with the same arguments at the same point (calling it on a subset,
        or conditionally, deadlocks).  ``scan=False`` is process-local and
        safe to call unilaterally."""
        from sherman_tpu.models.router import LeafRouter, default_log2_buckets
        leaf_dir = getattr(self.tree, "_bulk_leaf_dir", None)
        if leaf_dir is None and scan:
            from sherman_tpu.models.validate import leaf_directory
            leaf_dir = leaf_directory(self.tree)
        if log2_buckets is None:
            n_leaves = len(leaf_dir[0]) if leaf_dir else 1024
            log2_buckets = default_log2_buckets(n_leaves)
        r = LeafRouter(self.tree, log2_buckets)
        if leaf_dir is not None and len(leaf_dir[0]):
            r.seed_from_leaves(*leaf_dir)
        self.router = r
        return r

    def attach_leaf_cache(self, slots: int | None = None,
                          admit_every: int = 0):
        """Create + attach the hot-key tier (models/leaf_cache.py): a
        versioned compute-side leaf/value cache probed in front of the
        descent by every read entry point.  ``slots`` defaults to the
        ``SHERMAN_LEAF_CACHE`` knob (``config.leaf_cache_slots``;
        65536 when the knob only says "on"); ``admit_every`` > 0 arms
        frequency-based auto-admission every that-many observed read
        batches (0 = manual ``fill`` — the staged bench drivers prefill
        the analytically known hot set instead)."""
        from sherman_tpu.models.leaf_cache import LeafCache
        self.leaf_cache = LeafCache(self, slots=slots,
                                    admit_every=admit_every)
        return self.leaf_cache

    def attach_value_heap(self, **kw):
        """Create + attach the out-of-line value heap
        (models/value_heap.py) over this engine's DSM heap region
        (``DSMConfig.heap_pages_per_node`` / ``SHERMAN_VALUE_HEAP``):
        leaf values become versioned slab handles and
        ``put``/``get``/``remove``/``scan`` on the returned
        :class:`~sherman_tpu.models.value_heap.ValueHeap` serve
        variable-length payloads."""
        from sherman_tpu.models.value_heap import ValueHeap
        return ValueHeap(self, **kw)

    def detach_leaf_cache(self) -> None:
        """Drop the hot-key tier (reads go back to full descents).
        The ``cache.`` collector unregisters with it — a scrape must
        not keep publishing stats for a tier that no longer probes."""
        if self.leaf_cache is not None:
            obs.get_registry().unregister_collector("cache")
        self.leaf_cache = None

    def _get_search(self, iters: int, with_start: bool):
        key = (iters, with_start)
        fn = self._search_cache.get(key)
        if fn is None:
            spec, rep = self._spec, self._rep
            in_specs = [spec, spec, spec, spec, rep, spec]
            if with_start:
                in_specs.append(spec)
            if with_start:
                kernel = functools.partial(search_routed_spmd, cfg=self.cfg,
                                           iters=iters)
            else:
                kernel = functools.partial(search_spmd, cfg=self.cfg,
                                           iters=iters)
            sm = jax.shard_map(
                kernel,
                mesh=self.dsm.mesh,
                in_specs=tuple(in_specs),
                out_specs=(spec, spec, spec, spec, spec),
                check_vma=False)
            # compile-ledger wrap (obs/device.py): the WRAPPER is what
            # the cache holds, so program-identity pins keep holding
            fn = DEV.wrap_program("engine.search",
                                  jax.jit(sm, donate_argnums=C.donate_argnums(1)))
            self._search_cache[key] = fn
        return fn

    def _get_insert(self, iters: int, with_start: bool,
                    with_fresh: bool = True, update_only: bool = False):
        """Insert step.  ``with_fresh`` (static) enables the device-split
        path: a per-node fresh page array goes in and the split log comes
        out.  Rounds that offer NO grants (round 0's optimistic pass, the
        steady-state update benchmark) compile the leaner variant — the
        splitter ranking, split-page detection and split-apply machinery
        drop out of the program entirely (~30 ms/step at 2 M rows).
        ``update_only`` additionally compiles the 3-word write-back
        steady-state kernel (absent keys escalate, see leaf_apply_spmd).
        The engine's ``_write_combine`` (SHERMAN_WRITE_COMBINE) selects
        the HOCL-style group-lock-consult variant — part of the cache
        key so A/B drivers flipping it per engine never collide."""
        assert not (update_only and with_fresh)
        combine = self._write_combine
        key = (iters, with_start, with_fresh, update_only, combine)
        fn = self._insert_cache.get(key)
        if fn is None:
            spec, rep = self._spec, self._rep
            in_specs = [spec, spec, spec, spec, spec, spec, spec, spec,
                        rep, spec]
            if with_start:
                in_specs.append(spec)
            if with_fresh:
                in_specs.append(spec)  # fresh pages [N*F]
            log_spec = {k: spec for k in ("valid", "skhi", "sklo",
                                          "new_addr", "old_hhi",
                                          "old_hlo")}

            def kernel(pool, locks, counters, dirty, khi, klo, vhi, vlo,
                       root, active, *rest):
                start = rest[0] if with_start else None
                fresh = rest[-1] if with_fresh else None
                return insert_step_spmd(
                    pool, locks, counters, khi, klo, vhi, vlo,
                    root, active, start, fresh, cfg=self.cfg, iters=iters,
                    update_only=update_only, combine=combine, dirty=dirty)

            sm = jax.shard_map(
                kernel,
                mesh=self.dsm.mesh,
                in_specs=tuple(in_specs),
                out_specs=((spec, spec, spec, spec, log_spec) if with_fresh
                           else (spec, spec, spec, spec)),
                check_vma=False)
            fn = DEV.wrap_program(
                "engine.insert",
                jax.jit(sm, donate_argnums=C.donate_argnums(0, 2, 3)))
            self._insert_cache[key] = fn
        return fn

    def _get_delete(self, iters: int, with_start: bool):
        key = (iters, with_start)
        fn = self._delete_cache.get(key)
        if fn is None:
            spec, rep = self._spec, self._rep
            in_specs = [spec, spec, spec, spec, spec, spec, rep, spec]
            if with_start:
                in_specs.append(spec)

            def kernel(pool, locks, counters, dirty, khi, klo, root,
                       active, *rest):
                start = rest[0] if with_start else None
                return delete_step_spmd(
                    pool, locks, counters, khi, klo, root, active, start,
                    cfg=self.cfg, iters=iters, dirty=dirty)

            sm = jax.shard_map(
                kernel,
                mesh=self.dsm.mesh,
                in_specs=tuple(in_specs),
                out_specs=(spec, spec, spec, spec),
                check_vma=False)
            fn = DEV.wrap_program(
                "engine.delete",
                jax.jit(sm, donate_argnums=C.donate_argnums(0, 2, 3)))
            self._delete_cache[key] = fn
        return fn

    def _get_mixed(self, iters: int, with_start: bool,
                   write_lo: int | None = None,
                   update_only: bool = False):
        """``write_lo`` (static, per-node offset): callers that lay each
        node's shard out as [reads | writes] get the half-width apply
        (see mixed_step_spmd).  ``update_only``: the 4-word steady-state
        apply (absent keys escalate with ST_FULL).  ``_write_combine``
        selects the group-lock-consult apply, like ``_get_insert``."""
        combine = self._write_combine
        key = (iters, with_start, write_lo, update_only, combine)
        fn = self._mixed_cache.get(key)
        if fn is None:
            spec, rep = self._spec, self._rep
            in_specs = [spec, spec, spec, spec, spec, spec, spec, spec,
                        rep, spec, spec]
            if with_start:
                in_specs.append(spec)

            def kernel(pool, locks, counters, dirty, khi, klo, vhi, vlo,
                       root, active_r, active_w, *rest):
                start = rest[0] if with_start else None
                return mixed_step_spmd(
                    pool, locks, counters, khi, klo, vhi, vlo, root,
                    active_r, active_w, start, cfg=self.cfg, iters=iters,
                    write_lo=write_lo, update_only=update_only,
                    combine=combine, dirty=dirty)

            sm = jax.shard_map(
                kernel,
                mesh=self.dsm.mesh,
                in_specs=tuple(in_specs),
                out_specs=(spec, spec, spec, spec, spec, spec, spec, spec),
                check_vma=False)
            fn = DEV.wrap_program(
                "engine.mixed",
                jax.jit(sm, donate_argnums=C.donate_argnums(0, 2, 3)))
            self._mixed_cache[key] = fn
        return fn

    def mixed(self, keys, values, is_read):
        """One fused step of reads and upserts over one key batch.

        keys u64 [n], values u64 [n] (ignored where is_read), is_read
        bool [n].  Returns (out_values u64 [n], found bool [n] — read
        rows only, status int32 [n] — write rows only).  Writes that
        miss the fast path (ST_FULL / ST_RETRY / ST_LOCKED — splits in
        flight, chase-budget overruns on stale seeds) retry through
        :meth:`insert`, which owns the split/host fallbacks; their
        status is rewritten to the retry outcome.  Reads that overran
        the descent budget retry inline as a LATER step — per the
        mixed_step_spmd linearization rule they may observe this step's
        writes.  (The bench drivers bypass this wrapper and treat
        fast-path misses as open-loop misses.)
        """
        t_slo = time.perf_counter()
        keys = np.asarray(keys, np.uint64)
        if keys.size and (keys.min() < C.KEY_MIN or keys.max() > C.KEY_MAX):
            raise KeyRangeError("keys outside [KEY_MIN, KEY_MAX]")
        values = np.asarray(values, np.uint64)
        is_read = np.asarray(is_read, bool)
        if not bool(np.asarray(is_read).all()):
            self._require_writable()  # degraded mode: reads-only batches
        self._check_replicated(keys, values, is_read)
        n = keys.shape[0]
        total = self.cfg.machine_nr * self.B
        assert n <= total, "chunk the batch to machine_nr * B"
        khi, klo = bits.keys_to_pairs(keys)
        vhi, vlo = bits.keys_to_pairs(values)
        (khi, _), (klo, _) = self._pad(khi), self._pad(klo)
        (vhi, _), (vlo, _) = self._pad(vhi), self._pad(vlo)
        ar, _ = self._pad(is_read)   # pad rows are neither read nor write
        aw, _ = self._pad(~is_read)
        # hot-key tier: probe the READ rows only — hits see the same
        # pre-step snapshot the fused descent's reads see (the probe
        # runs before the step's writes apply), so the mixed
        # linearization (resolved reads < writes) is unchanged
        cache = self.leaf_cache
        c_hit = c_vhi = c_vlo = None
        if cache is not None and bool(is_read.any()):
            c_hit, c_vhi, c_vlo = cache.probe(khi, klo, ar)
            ar = ar & ~c_hit
        use_router = self.router is not None
        fn = self._get_mixed(self._iters(), use_router)
        if self._write_combine:
            self._note_combine_step(int(np.count_nonzero(~is_read)))
        # batch prep (router probe, host->device transfers) OUTSIDE the
        # step mutex — only the handle read -> launch -> handle write is
        # locked (see __init__); holding it across prep would stall
        # concurrent host clients for the whole transfer
        args = [self._shard(khi), self._shard(klo),
                self._shard(vhi), self._shard(vlo),
                np.int32(self.tree._root_addr),
                self._shard(ar), self._shard(aw)]
        if use_router:
            args.append(self._shard(self.router.host_start(khi, klo)))
        with obs.span("engine.mixed.descend_lock_apply", n=int(n)):
            with self._step_mutex:
                (self.dsm.pool, self.dsm.counters, self.dsm.dirty, status,
                 done_r, found, rvh, rvl) = fn(
                    self.dsm.pool, self.dsm.locks, self.dsm.counters,
                    self.dsm.dirty, *args)
            status, done_r, found, rvh, rvl = self._unshard(
                status, done_r, found, rvh, rvl)
        status = np.array(status[:n])  # writable: retry outcomes land here
        done_r = done_r[:n]
        found = np.array(found[:n])
        out_vals = np.array(bits.pairs_to_keys(rvh[:n], rvl[:n]))
        if c_hit is not None and c_hit[:n].any():
            # merge cache-served reads (probe active mask was the read
            # rows, so hits are read rows by construction)
            hits = c_hit[:n]
            done_r = np.array(done_r)
            done_r[hits] = True
            found[hits] = True
            out_vals[hits] = bits.pairs_to_keys(
                c_vhi[:n], c_vlo[:n])[hits]
        # journal the fast-path applied writes BEFORE the retry branch:
        # retried rows apply in later steps through insert() (which
        # journals its own record), so appending here keeps record order
        # == apply order even for same-key duplicates across the classes
        fast_app = ~is_read & (status == ST_APPLIED)
        self._journal_applied(J.J_UPSERT, keys[fast_app], values[fast_app])
        if cache is not None and bool((~is_read).any()):
            # write-path invalidation hook: these keys' entry versions
            # bump this step (conservative over the full write class — a
            # spare invalidation, never a missed one; retried writes go
            # through insert(), which invalidates its own keys)
            cache.invalidate_keys(keys[~is_read])
        miss_r = is_read & ~done_r
        if miss_r.any():
            v2, f2 = self.search(keys[miss_r])
            out_vals[miss_r], found[miss_r] = v2, f2
        miss_w = ~is_read & np.isin(status, (ST_FULL, ST_RETRY, ST_LOCKED))
        if miss_w.any():
            st = self.insert(keys[miss_w], values[miss_w])
            # The rewrite below depends on insert()'s postcondition: every
            # request ends APPLIED, SUPERSEDED by a same-batch duplicate,
            # applied through the host path, or REJECTED with the typed
            # ST_LOCK_TIMEOUT outcome (lock held by a live lease past the
            # bounded retry budget).  Assert it so a future relaxation of
            # that guarantee cannot silently turn these synthesized
            # statuses into lies.
            resolved = (st["applied"] + st["superseded"] + st["host_path"]
                        + st["lock_timeouts"])
            assert resolved == int(miss_w.sum()), (
                f"insert() postcondition broken: {st} resolved != "
                f"{int(miss_w.sum())} retried writes")
            # per-request outcomes match the fast path's dedup semantics:
            # the first-ordered request of a key applies, later duplicates
            # are superseded by it (insert linearizes them the same way);
            # lock-timeout keys carry the typed rejection through
            idx_w = np.nonzero(miss_w)[0]
            first = np.zeros(idx_w.shape[0], bool)
            first[np.unique(keys[idx_w], return_index=True)[1]] = True
            status[idx_w[first]] = ST_APPLIED
            status[idx_w[~first]] = ST_SUPERSEDED
            if st["lock_timeouts"]:
                to = np.isin(keys[idx_w],
                             np.asarray(st["lock_timeout_keys"], np.uint64))
                status[idx_w[to]] = ST_LOCK_TIMEOUT
        # the whole fused batch (incl. any retry sub-batches, which also
        # report under their own classes) is the mixed class's wall
        _slo_observe("mixed", n, t_slo)
        return out_vals, found, status

    # -- helpers -------------------------------------------------------------

    def _shard(self, x):
        """Global-shape host array -> node-sharded device array.  In
        multihost mode ``x`` is the full (replicated) batch; each process
        contributes its local node block."""
        if not self._mh:
            return jax.device_put(x, self.dsm.shard)
        from jax.experimental import multihost_utils as mhu
        per = x.shape[0] // self.cfg.machine_nr
        lo = self.dsm.local_nodes[0] * per
        hi = (self.dsm.local_nodes[-1] + 1) * per
        return mhu.host_local_array_to_global_array(
            np.ascontiguousarray(x[lo:hi]), self.dsm.mesh,
            jax.sharding.PartitionSpec(AXIS))

    def _unshard(self, *ys):
        """Node-sharded device arrays -> full host arrays on every process
        (multihost: local block + ONE tiled allgather for all arrays;
        block order asserted ascending by ReplicatedDSM).  Returns a
        single array for one input, else a tuple."""
        if not self._mh:
            out = tuple(np.asarray(y) for y in ys)
            return out[0] if len(ys) == 1 else out
        from jax.experimental import multihost_utils as mhu
        spec = jax.sharding.PartitionSpec(AXIS)
        locals_ = tuple(np.asarray(mhu.global_array_to_host_local_array(
            y, self.dsm.mesh, spec)) for y in ys)
        g = mhu.process_allgather(locals_, tiled=True)
        out = tuple(np.asarray(x) for x in g)
        return out[0] if len(ys) == 1 else out

    def _check_replicated(self, *arrays) -> None:
        _assert_replicated(self._mh, arrays, "engine drivers")

    def _pad(self, arr: np.ndarray, fill=0) -> tuple[np.ndarray, int]:
        total = self.cfg.machine_nr * self.B
        n = arr.shape[0]
        assert n <= total
        if n == total:
            return arr, n
        pad = np.full((total - n,) + arr.shape[1:], fill, arr.dtype)
        return np.concatenate([arr, pad]), n

    # -- public ops ----------------------------------------------------------

    def search(self, keys, _depth: int = 0,
               _checked: bool = False) -> tuple[np.ndarray, np.ndarray]:
        """Batched lookup.  keys: uint64 array [n] (n <= N*B per call is
        chunked automatically).  Returns (values uint64 [n], found bool [n]).
        """
        keys = np.asarray(keys, np.uint64)
        if keys.size and (keys.min() < C.KEY_MIN or keys.max() > C.KEY_MAX):
            raise KeyRangeError("keys outside [KEY_MIN, KEY_MAX]")
        if _depth == 0 and not _checked:
            self._check_replicated(keys)
        n = keys.shape[0]
        total = self.cfg.machine_nr * self.B
        if n > total:
            # chunks were digest-checked as one array; each still routes
            # like a fresh call (_depth=0)
            parts = [self.search(keys[i:i + total], _checked=True)
                     for i in range(0, n, total)]
            return (np.concatenate([p[0] for p in parts]),
                    np.concatenate([p[1] for p in parts]))

        # SLO accounting: one batch wall per top-level call (chunks and
        # straggler retries fold into their parent's wall; _depth > 0
        # frames never observe)
        t_slo = time.perf_counter() if _depth == 0 else None
        khi, klo = bits.keys_to_pairs(keys)
        (khi, _), (klo, _) = self._pad(khi), self._pad(klo)
        active, _ = self._pad(np.ones(n, bool))
        # hot-key tier: probe the leaf/value cache in front of the
        # descent — hits are pool-validated (bit-identical to a
        # descent, see leaf_cache.py) and drop out of the device batch,
        # so the existing search program serves the RESIDUAL active set
        cache = self.leaf_cache if _depth == 0 and n else None
        c_hit = c_vhi = c_vlo = None
        if cache is not None:
            cache.observe(keys)
            c_hit, c_vhi, c_vlo = cache.probe(khi, klo, active)
            active = active & ~c_hit
        # retries (depth > 0) bypass the index cache and descend from root
        use_router = self.router is not None and _depth == 0
        fn = self._get_search(self._iters(), use_router)
        args = [self._shard(khi), self._shard(klo),
                np.int32(self.tree._root_addr), self._shard(active)]
        if use_router:
            args.append(self._shard(self.router.host_start(khi, klo)))
        # span covers launch -> materialized replies (dispatch is async;
        # _unshard's host materialization is the real step drain)
        with obs.span("engine.search.descend", n=int(n)):
            with self._step_mutex:  # launch-only (prep above)
                self.dsm.counters, done, found, vhi, vlo = fn(
                    self.dsm.pool, self.dsm.counters, *args)
            done, found, vhi, vlo = self._unshard(done, found, vhi, vlo)
        done = done[:n]
        if c_hit is not None and c_hit[:n].any():
            # merge the cache hits back into the batch's answers (their
            # device rows were inactive — the residual descent never
            # touched them)
            hits = c_hit[:n]
            done = np.array(done)
            done[hits] = True
            found, vhi, vlo = (np.array(found), np.array(vhi),
                               np.array(vlo))
            found[:n][hits] = True  # found/v* keep the padded width
            vhi[:n][hits] = c_vhi[:n][hits]
            vlo[:n][hits] = c_vlo[:n][hits]
        if not done.all():
            assert _depth < 8, "search stragglers not converging"
            # stale cache / height growth / capacity overflow: refresh root,
            # full descent for the stragglers
            self.tree._refresh_root()
            vals = np.array(bits.pairs_to_keys(vhi[:n], vlo[:n]))
            fnd = np.array(found[:n])
            miss = ~done
            v2, f2 = self.search(keys[miss], _depth=_depth + 1)
            vals[miss], fnd[miss] = v2, f2
            _slo_observe("read", n, t_slo)
            return vals, fnd
        _slo_observe("read", n, t_slo)
        return bits.pairs_to_keys(vhi[:n], vlo[:n]), found[:n]

    def _get_search_fanout(self, iters: int, *, local: bool = False):
        """Search over the unique-key set + packed IN-STEP fan-out of
        every client request's answer.

        TPU gathers are per-row latency-bound regardless of width, so the
        three answer lanes (found, vhi, vlo) pack into ONE [U, 4] table
        and fan out to the [B_client] request slots with a single
        take_along_axis — the client-ops throughput of a combined batch
        is then fully earned on device (nothing deferred to the host).
        Multi-node: the fan-out runs AFTER the reply exchange — each node
        all-gathers the [U, 4] answer table once, then its client slots
        take locally (``inv`` holds GLOBAL unique indices).  ``local``:
        every client's unique row lies on the client's own node (the
        device-staged loops combine per node), so a multi-node fan-out
        takes from the node's own [U_loc, 4] table at ``inv - node *
        U_loc``, with no all-gather; on one node it is the same program.
        jit re-specializes per (unique-width, client-width) shape pair.
        """
        local = local and self.cfg.machine_nr > 1
        key = ("fanout_local" if local else "fanout", iters)
        fn = self._search_cache.get(key)
        if fn is None:
            spec, rep = self._spec, self._rep
            body = self._search_fanout_body(iters, done_lane=False,
                                            local=local)

            def kernel(pool, counters, khi, klo, root, active, start, inv):
                counters, done, out = body(pool, counters, khi, klo, root,
                                           active, start, inv)
                return (counters, done, out[:, 0].astype(bool),
                        out[:, 1], out[:, 2])

            sm = jax.shard_map(
                kernel, mesh=self.dsm.mesh,
                in_specs=(spec, spec, spec, spec, rep, spec, spec, spec),
                out_specs=(spec, spec, spec, spec, spec), check_vma=False)
            fn = DEV.wrap_program(
                "engine.search_fanout_local" if local
                else "engine.search_fanout",
                jax.jit(sm, donate_argnums=C.donate_argnums(1)))
            self._search_cache[key] = fn
        return fn

    def _search_fanout_body(self, iters: int, *, done_lane: bool,
                            local: bool = False):
        """The routed descent plus the in-step fan-out shared by both
        fan-out entries: ``(counters, done, out)`` with ``out`` the
        [B_client, 4] answer table (found, vhi, vlo, lane 3).  Lane 3 is
        zero, or with ``done_lane`` the unique row's ``done`` flag, so a
        packed caller reads every answer from the one table.  ``local``:
        see :meth:`_get_search_fanout`."""
        N = self.cfg.machine_nr

        def body(pool, counters, khi, klo, root, active, start, inv):
            counters, done, found, vhi, vlo = search_routed_spmd(
                pool, counters, khi, klo, root, active, start,
                cfg=self.cfg, iters=iters)
            with jax.named_scope("fanout"):
                ans = jnp.stack([found.astype(jnp.int32), vhi, vlo,
                                 done.astype(jnp.int32) if done_lane
                                 else jnp.zeros_like(vhi)],
                                axis=-1)                        # [U_loc, 4]
                if N > 1 and local:
                    inv = inv - lax.axis_index(AXIS) * ans.shape[0]
                elif N > 1:
                    ans = transport.gather_rows(ans, AXIS)      # [U, 4]
                safe = jnp.clip(inv, 0, ans.shape[0] - 1)
                out = jnp.take_along_axis(ans, safe[:, None], axis=0)
            return counters, done, out

        return body

    def _get_search_fanout_packed(self, iters: int):
        """The fan-out search at a packed host boundary (the serving
        front door's ingress step): one ``packed`` [B_client, 5] int32
        input of (khi, klo, active, start, inv) columns and one
        [B_client, 4] answer table out (found, vhi, vlo, done), so a
        step moves its batch in one host->device put and its answers in
        one device->host copy.  Same kernel body as
        :meth:`_get_search_fanout`; the traced function keeps the name
        ``kernel`` so the profiler names both modules ``jit_kernel``."""
        fn = self._search_cache.get(("fanout_packed", iters))
        if fn is None:
            spec, rep = self._spec, self._rep
            body = self._search_fanout_body(iters, done_lane=True)

            def kernel(pool, counters, packed, root):
                khi, klo, active, start, inv = (packed[:, i]
                                                for i in range(5))
                counters, _, out = body(pool, counters, khi, klo, root,
                                        active != 0, start, inv)
                return counters, out

            sm = jax.shard_map(
                kernel, mesh=self.dsm.mesh,
                in_specs=(spec, spec, spec, rep),
                out_specs=(spec, spec), check_vma=False)
            fn = DEV.wrap_program(
                "engine.search_fanout_packed",
                jax.jit(sm, donate_argnums=C.donate_argnums(1)))
            self._search_cache[("fanout_packed", iters)] = fn
        return fn

    def search_combined(self, keys) -> tuple[np.ndarray, np.ndarray]:
        """Batched lookup with request combining: duplicate keys share one
        descent + page fetch; every request still gets its answer.

        The read-side symmetric of the insert step's same-key dedup (its
        intra-step linearization — see :func:`leaf_apply_spmd`): the
        device batch is the unique-key set.  With the router attached,
        the per-request answer fan-out runs ON DEVICE inside the same
        step (:meth:`_get_search_fanout`) on any mesh size — multi-node
        fans out after the reply exchange via an answer-table all-gather;
        without a router it is a host vectorized gather.  Semantically
        identical to :meth:`search` (combined duplicates read the same
        snapshot, a legal concurrent schedule); ~2-10x fewer device rows
        on zipf-skewed batches.  Returns (values uint64 [n], found [n]).
        """
        keys = np.asarray(keys, np.uint64)
        with obs.span("engine.search.combine", n=int(keys.size)):
            uk, inv = np.unique(keys, return_inverse=True)
        use_device = (self.router is not None
                      and 0 < uk.size <= self.B * self.cfg.machine_nr)
        if not use_device:
            # host fan-out: search() attributes the unique-set batch
            vals, found = self.search(uk)
            return vals[inv], found[inv]
        t_slo = time.perf_counter()
        if keys.size and (keys.min() < C.KEY_MIN or keys.max() > C.KEY_MAX):
            raise KeyRangeError("keys outside [KEY_MIN, KEY_MAX]")
        self._check_replicated(keys)
        khi, klo = bits.keys_to_pairs(uk)
        (khi, _), (klo, _) = self._pad(khi), self._pad(klo)
        active, _ = self._pad(np.ones(uk.size, bool))
        # hot-key tier: probe the unique set — cache hits leave the
        # device batch (smaller residual descent); their answers merge
        # back per CLIENT row below via the same inverse map the
        # fan-out uses.  The admission sketch sees the raw (duplicated)
        # key stream: frequency ranking needs the multiplicities.
        cache = self.leaf_cache if uk.size else None
        c_hit = c_vhi = c_vlo = None
        if cache is not None:
            cache.observe(keys)
            c_hit, c_vhi, c_vlo = cache.probe(khi, klo, active)
            active = active & ~c_hit
        # bucket the CLIENT width so varying request counts reuse one
        # compiled program per quantum (unique width is already fixed at
        # N*B); pad rows fan out slot 0 and are sliced off below.  The
        # quantum is a machine_nr multiple so the client array shards
        # evenly over the node mesh.
        n = keys.size
        quantum = 8192 * self.cfg.machine_nr
        n_pad = -(-n // quantum) * quantum
        inv_p = np.zeros(n_pad, np.int32)
        inv_p[:n] = inv.astype(np.int32)
        fn = self._get_search_fanout(self._iters())
        args = [self._shard(khi), self._shard(klo),
                np.int32(self.tree._root_addr), self._shard(active),
                self._shard(self.router.host_start(khi, klo)),
                self._shard(inv_p)]
        with obs.span("engine.search.descend", n=int(uk.size),
                      fanout=int(n)):
            with self._step_mutex:  # launch-only (prep above)
                self.dsm.counters, done, found, vhi, vlo = fn(
                    self.dsm.pool, self.dsm.counters, *args)
            done, found, vhi, vlo = self._unshard(done, found, vhi, vlo)
        hit_u = c_hit[:uk.size] if c_hit is not None else None
        done_u = np.asarray(done[:uk.size]) if hit_u is None \
            else (np.asarray(done[:uk.size]) | hit_u)
        if not bool(done_u.all()):
            # straggler rescue (stale seeds / growth): host fan-out path
            # (search() attributes the rescue batch to the read class)
            vals, fnd = self.search(uk)
            return vals[inv], fnd[inv]
        if hit_u is not None and hit_u.any():
            # cache hits' device fan-out rows carried an inactive unique
            # row — overwrite them client-side through the inverse map
            chit = hit_u[inv]
            found, vhi, vlo = (np.array(found), np.array(vhi),
                               np.array(vlo))
            found[:n][chit] = True
            vhi[:n][chit] = c_vhi[:uk.size][inv][chit]
            vlo[:n][chit] = c_vlo[:uk.size][inv][chit]
        _slo_observe("read", n, t_slo)
        return (bits.pairs_to_keys(vhi[:n], vlo[:n]), found[:n])

    def insert(self, keys, values, max_rounds: int | None = None) -> dict:
        """Batched upsert with host fallback for splits.

        Returns stats {applied, superseded, host_path, rounds, st_locked,
        lock_timeouts, lock_timeout_keys}: every request ends APPLIED,
        SUPERSEDED, applied through the host path, or — when its page
        lock stayed held by a live lease past the bounded retry budget —
        REJECTED with the typed ST_LOCK_TIMEOUT outcome (counted in
        lock_timeouts, keys listed in lock_timeout_keys).
        """
        self._require_writable()
        t_slo = time.perf_counter()
        if max_rounds is None:
            max_rounds = self.tcfg.insert_rounds
        keys = np.asarray(keys, np.uint64)
        if keys.size and (keys.min() < C.KEY_MIN or keys.max() > C.KEY_MAX):
            raise KeyRangeError("keys outside [KEY_MIN, KEY_MAX]")
        values = np.asarray(values, np.uint64)
        self._check_replicated(keys, values)
        n = keys.shape[0]
        total = self.cfg.machine_nr * self.B
        stats = {"applied": 0, "superseded": 0, "host_path": 0, "rounds": 0,
                 "st_locked": 0, "lock_timeouts": 0, "lock_timeout_keys": []}
        applied_rows = np.zeros(n, bool)
        for i in range(0, n, total):
            applied_rows[i:i + total] = self._insert_chunk(
                keys[i:i + total], values[i:i + total], max_rounds, stats)
        self.flush_parents()
        # ONE journal batch record of the rows that actually landed
        # (superseded duplicates carry the winner's value — excluded;
        # lock-timeout rejections never applied — excluded), durable
        # before the caller sees the stats ack
        self._journal_applied(J.J_UPSERT, keys[applied_rows],
                              values[applied_rows])
        if self.leaf_cache is not None and n:
            # write-path invalidation hook (entry versions bumped);
            # whole batch, conservatively — superseded duplicates share
            # their winner's key, rejected rows invalidate spare
            self.leaf_cache.invalidate_keys(keys)
        # the wall includes flush_parents + the durable journal append —
        # insert's ack latency, which is what an SLO target governs
        _slo_observe("insert", n, t_slo)
        return stats

    def _get_parent_descend(self, iters: int, stop_level: int = 1):
        key = (iters, stop_level)
        fn = self._parent_descend_cache.get(key)
        if fn is None:
            spec, rep = self._spec, self._rep
            sm = jax.shard_map(
                functools.partial(descend_spmd, cfg=self.cfg, iters=iters,
                                  stop_level=stop_level),
                mesh=self.dsm.mesh,
                in_specs=(spec, spec, spec, spec, rep, spec),
                out_specs=(spec, spec, spec, spec),
                check_vma=False)
            fn = DEV.wrap_program(
                "engine.parent_descend",
                jax.jit(sm, donate_argnums=C.donate_argnums(1)))
            self._parent_descend_cache[key] = fn
        return fn

    def _descend_to_level(self, keys: np.ndarray, level: int = 1):
        """Batched root -> level-``level`` descent.  -> (addrs [n],
        done [n])."""
        n = keys.shape[0]
        total = self.cfg.machine_nr * self.B
        if n > total:
            parts = [self._descend_to_level(keys[i:i + total], level)
                     for i in range(0, n, total)]
            return (np.concatenate([p[0] for p in parts]),
                    np.concatenate([p[1] for p in parts]))
        khi, klo = bits.keys_to_pairs(keys)
        (khi, _), (klo, _) = self._pad(khi), self._pad(klo)
        active, _ = self._pad(np.ones(n, bool))
        fn = self._get_parent_descend(self._iters(), level)
        args = [self._shard(khi), self._shard(klo),
                np.int32(self.tree._root_addr), self._shard(active)]
        with self._step_mutex:  # launch-only (prep above)
            self.dsm.counters, addr, _, done = fn(
                self.dsm.pool, self.dsm.counters, *args)
        addr, done = self._unshard(addr, done)
        return addr[:n], done[:n]

    def flush_parents(self) -> int:
        """Insert deferred parent entries for device-side splits — the
        internal_page_store ascent (Tree.cpp:980-987), BATCHED at every
        level: per pending level, one device descent to that level, one
        step that lock+reads every touched internal page (coalesced
        cas_read rows), a host-side sorted merge — overflowing pages
        split IN the batch (both halves coalesce into the write step;
        the promoted middle entries become next attempt's pending set,
        one level up) — and one step writing every rebuilt page together
        with all unlocks.  Root growth is the only per-key host-path
        remnant (once per tree level, not per entry).  Searches are
        correct without any of this — the B-link covers the new pages —
        it only trims sibling chases.  Returns the entries flushed."""
        import collections
        import os
        import time as _t
        dbg = os.environ.get("SHERMAN_DEBUG_INSERT")

        # atomic drain: swap the list out FIRST — building pend from the
        # live list and then reassigning [] would silently drop an entry
        # a concurrent writer appends between the two statements (reclaim
        # calls this from a maintenance thread)
        raw, self._pending_parents = self._pending_parents, []
        total = len(raw)
        if not total:
            return 0
        with obs.span("engine.insert.flush_parents", n=total):
            return self._flush_parents_drained(raw, total, dbg)

    def _flush_parents_drained(self, raw, total, dbg) -> int:
        import collections
        import time as _t

        # legacy 2-tuples target level 1
        pend = [t if len(t) == 3 else (t[0], t[1], 1) for t in raw]
        tree, dsm = self.tree, self.dsm
        for _attempt in range(12):
            if not pend:
                break
            if dbg:
                print(f"[flush] attempt {_attempt} pend={len(pend)} "
                      f"t={_t.time():.1f}", flush=True)
            tree._refresh_root()
            # entries above the current root grow the tree on the host
            # path (rare: once per new level)
            grow = [t for t in pend if t[2] > tree._root_level]
            pend = [t for t in pend if t[2] <= tree._root_level]
            for k, c, lv in grow:
                tree._insert_parent(int(k), int(c), int(lv), {})
            if not pend:
                continue

            next_pend = []
            for lv in sorted({t[2] for t in pend}):
                at_lv = [t for t in pend if t[2] == lv]
                keysu = np.array([k for k, _, _ in at_lv], np.uint64)
                t_d0 = _t.time()
                addrs, done = self._descend_to_level(keysu, lv)
                t_d1 = _t.time()

                # lock + read every unique target page in ONE step; two
                # pages hashing to one lock word -> second CAS loses ->
                # next attempt
                uaddr = [int(a) for a in np.unique(addrs[done])]
                rows = []
                for a in uaddr:
                    la = tree._lock_word_addr(a)
                    rows.append({"op": D.OP_CAS, "addr": la, "woff": 0,
                                 "arg0": 0, "arg1": tree.ctx.lease,
                                 "space": D.SPACE_LOCK})
                    rows.append({"op": D.OP_READ, "addr": a})
                rep = dsm._batch(rows)
                t_l1 = _t.time()
                pages, unlock_rows = {}, []
                for i, a in enumerate(uaddr):
                    if bool(rep.ok[2 * i]):
                        pages[a] = np.array(rep.data[2 * i + 1])
                        unlock_rows.append(tree._unlock_row(
                            tree._lock_word_addr(a)))

                group = collections.defaultdict(list)
                for (k, c, _), a, d in zip(at_lv, addrs, done):
                    if d and int(a) in pages:
                        group[int(a)].append((int(k), int(c)))
                    else:
                        next_pend.append((k, c, lv))

                write_rows, host_fb = [], []
                n_split = 0
                for a, ents_new in group.items():
                    pg = pages[a]
                    lo, hi = layout.np_lowest(pg), layout.np_highest(pg)
                    stay = [(k, c) for k, c in ents_new if lo <= k < hi]
                    next_pend += [(k, c, lv) for k, c in ents_new
                                  if not (lo <= k < hi)]  # fence moved
                    if not stay:
                        continue
                    ents = sorted(set(layout.np_internal_entries(pg)
                                      + stay))
                    if len(ents) <= C.INTERNAL_CAP:
                        newpg = layout.np_internal_rebuild(pg, ents, lv)
                        write_rows.append({"op": D.OP_WRITE, "addr": a,
                                           "woff": 0, "nw": C.PAGE_WORDS,
                                           "payload": newpg})
                        continue
                    if len(ents) > 2 * C.INTERNAL_CAP:
                        host_fb += stay  # needs >1 split (rare)
                        continue
                    # BATCHED internal split: the page is already locked,
                    # so split it HERE and coalesce both halves into the
                    # same write step (the old per-key fallback paid
                    # host round trips per entry under a split storm —
                    # 398 fallbacks on one 131k-op chunk).  Mirrors Tree._insert_parent_inner
                    # (internal_page_store's split, Tree.cpp:980-987);
                    # the promoted middle entry joins next attempt's
                    # pending set one level up, flushed through this same
                    # batched path.
                    try:
                        sib_addr = tree.ctx.alloc.alloc()
                    except MemoryError:
                        host_fb += stay
                        continue
                    m = len(ents) // 2
                    up_key, up_child = ents[m]
                    old_high = layout.np_highest(pg)
                    old_sib = int(pg[C.W_SIBLING])
                    ver = ((int(pg[C.W_FRONT_VER]) + 1) & 0x7FFFFFFF) or 1
                    right = layout.np_empty_page(lv, up_key, old_high,
                                                 sibling=old_sib,
                                                 leftmost=up_child)
                    for i, (k2, c2) in enumerate(ents[m + 1:]):
                        layout.np_internal_set_entry(right, i, k2, c2)
                    right[C.W_NKEYS] = len(ents) - m - 1
                    left = layout.np_empty_page(
                        lv, lo, up_key, sibling=sib_addr,
                        leftmost=int(pg[C.W_LEFTMOST]), version=ver)
                    for i, (k2, c2) in enumerate(ents[:m]):
                        layout.np_internal_set_entry(left, i, k2, c2)
                    left[C.W_NKEYS] = m
                    write_rows.append({"op": D.OP_WRITE, "addr": sib_addr,
                                       "woff": 0, "nw": C.PAGE_WORDS,
                                       "payload": right})
                    write_rows.append({"op": D.OP_WRITE, "addr": a,
                                       "woff": 0, "nw": C.PAGE_WORDS,
                                       "payload": left})
                    next_pend.append((up_key, sib_addr, lv + 1))
                    n_split += 1
                t_m1 = _t.time()
                if write_rows or unlock_rows:
                    dsm.write_rows(write_rows + unlock_rows)
                if dbg:
                    print(f"[flush] lv={lv} wrote={len(write_rows)} "
                          f"splits={n_split} host_fb={len(host_fb)} "
                          f"descend={t_d1 - t_d0:.1f}s "
                          f"lock={t_l1 - t_d1:.1f}s merge={t_m1 - t_l1:.1f}s "
                          f"write={_t.time() - t_m1:.1f}s", flush=True)
                for k, c in host_fb:
                    tree._insert_parent(k, c, lv, {})
            pend = next_pend
        if dbg and pend:
            print(f"[flush] per-key fallback for {len(pend)}", flush=True)
        for k, c, lv in pend:
            tree._insert_parent(int(k), int(c), int(lv), {})
        return total

    def _fill_fresh(self, grant: bool) -> np.ndarray:
        """Per-node fresh-page grants for the next insert round ([N*F],
        0 = no grant).  Grants are node-local pages (a split's right
        sibling is written by the page's owner).  Unconsumed grants stay
        in the host cache for the next round."""
        N, F = self.cfg.machine_nr, self.split_slots
        arr = np.zeros(N * F, np.int32)
        if not grant:
            return arr
        for nd in range(N):
            lst = self._fresh_cache.setdefault(nd, [])
            while len(lst) < F:
                try:
                    lst.append(self.tree.ctx.alloc.alloc(node=nd))
                except (KeyError, MemoryError):
                    break  # node not local / partition exhausted
            arr[nd * F:nd * F + len(lst[:F])] = lst[:F]
        return arr

    def _drain_split_log(self, log, stats) -> None:
        """Apply a round's split log: reclaim unconsumed grants, refresh
        the index cache, and lazily insert the parent entries (the B-link
        already makes the split pages reachable — Tree.cpp:116-124's
        broadcast role, deferred)."""
        valid, new_addr, skhi, sklo, ohhi, ohlo = self._unshard(
            log["valid"], log["new_addr"], log["skhi"], log["sklo"],
            log["old_hhi"], log["old_hlo"])
        if not valid.any():
            return
        new_addr = new_addr[valid]
        sk = bits.pairs_to_keys(skhi[valid], sklo[valid])
        oh = bits.pairs_to_keys(ohhi[valid], ohlo[valid])
        consumed = set(int(a) for a in new_addr)
        for nd, lst in self._fresh_cache.items():
            self._fresh_cache[nd] = [a for a in lst if a not in consumed]
        stats["device_splits"] = stats.get("device_splits", 0) + len(sk)
        if self.router is not None:
            # one vectorized table update for the whole split log (the
            # per-split path costs seconds at storm volume)
            self.router.note_splits_batch(sk, new_addr, oh)
        for i in range(len(sk)):
            # parent entries are deferred (flush_parents): the B-link
            # keeps the tree correct meanwhile, and retries reach the new
            # pages through the refreshed router seeds
            self._pending_parents.append((int(sk[i]), int(new_addr[i])))

    def _insert_chunk(self, keys, values, max_rounds, stats):
        """-> applied [n] bool: rows whose OWN value landed in the pool
        (device fast path or host fallback) — the journal's record set.
        Superseded duplicates and lock-timeout rejections stay False."""
        import os
        import time as _t
        dbg = os.environ.get("SHERMAN_DEBUG_INSERT")
        n = keys.shape[0]
        applied_rows = np.zeros(n, bool)
        pending = np.ones(n, bool)
        # consecutive rounds each row spent blocked on a HELD page lock
        # (bounded lock retry: see the ST_LOCKED handling below)
        locked_rounds = np.zeros(n, np.int32)
        fresh_np = self._fill_fresh(False)  # round 0: optimistic, no splits
        # Progress-adaptive rounds: append-shaped workloads drain the
        # rightmost leaf at ~(free slots + 1) keys per round (the same
        # serialization the reference pays on the last leaf's lock), so a
        # fixed budget would spill long appends to the host path.  Keep
        # going while rounds make progress; stop after 2 stalled rounds.
        round_i, stalled = 0, 0
        router_usable = self.router is not None
        while round_i < max_rounds or (stalled < 2
                                       and round_i < max_rounds * 16):
            round_i += 1
            if dbg:
                print(f"[ins] round {round_i} pending={pending.sum()} "
                      f"t={_t.time():.1f}", flush=True)
            if not pending.any():
                return applied_rows
            n_before = int(pending.sum())
            stats["rounds"] += 1
            idx = np.nonzero(pending)[0]
            khi, klo = bits.keys_to_pairs(keys[idx])
            vhi, vlo = bits.keys_to_pairs(values[idx])
            (khi, _), (klo, _) = self._pad(khi), self._pad(klo)
            (vhi, _), (vlo, _) = self._pad(vhi), self._pad(vlo)
            active, _ = self._pad(np.ones(idx.shape[0], bool))
            # The router is CORRECT on every round (seeds never land right
            # of a key's leaf; note_split keeps it current), and retries
            # then land directly on freshly split leaves.  But seeds that
            # land far left of a key's leaf (a cold unseeded table deep in
            # a tall tree, or a coarse span right after _grow_span) can
            # cost sibling chases beyond the descent budget, and such
            # keys would retry FOREVER: once a round makes no progress,
            # LATCH off the router for the rest of the chunk and use root
            # descents (fence-guided, height-bounded) like search's
            # straggler retry.  (Sub-2^32 keyspaces used to be the main
            # trigger; they now bucket at full resolution — the latch
            # remains the generic no-progress backstop.  It also avoids
            # oscillating: resetting on progress would re-enable the same
            # seeds every other round.)  First fallback round pays a
            # one-time compile of the no-seed insert kernel; cached after.
            if stalled > 0:
                router_usable = False
            use_router = router_usable
            # the compiled program SHAPE must agree across processes:
            # fresh_np holds only this process's local-node grants, so a
            # per-process any() could diverge (one host exhausted, another
            # granted) and mismatched SPMD programs deadlock the mesh —
            # multihost always keeps the fixed with-fresh shape
            with_fresh = self._mh or bool(fresh_np.any())
            fn = self._get_insert(self._iters(), use_router, with_fresh)
            if self._write_combine:
                self._note_combine_step(int(np.count_nonzero(active)))
            args = [self._shard(khi), self._shard(klo),
                    self._shard(vhi), self._shard(vlo),
                    np.int32(self.tree._root_addr), self._shard(active)]
            if use_router:
                args.append(self._shard(self.router.host_start(khi, klo)))
            if with_fresh:
                args.append(self._shard(fresh_np))
            # one fused device round: descend + lock + leaf apply (+
            # splits); the span drains at the status materialization
            with obs.span("engine.insert.descend_lock_apply",
                          n=int(idx.shape[0]), round=round_i):
                with self._step_mutex:  # launch-only (prep above)
                    if with_fresh:
                        (self.dsm.pool, self.dsm.counters, self.dsm.dirty,
                         status, log) = fn(
                            self.dsm.pool, self.dsm.locks,
                            self.dsm.counters, self.dsm.dirty, *args)
                    else:
                        (self.dsm.pool, self.dsm.counters, self.dsm.dirty,
                         status) = fn(
                            self.dsm.pool, self.dsm.locks,
                            self.dsm.counters, self.dsm.dirty, *args)
                        log = None
                status = self._unshard(status)[:idx.shape[0]]
            if dbg:
                import collections as _c
                print(f"[ins] status {dict(_c.Counter(status.tolist()))} "
                      f"t={_t.time():.1f}", flush=True)
            # host-held page locks surface as ST_LOCKED retries (the
            # protocol linchpin under concurrent host writers); count them
            # so drivers/tests can assert the interleaving really happened
            stats["st_locked"] += int((status == ST_LOCKED).sum())
            if log is not None:
                with obs.span("engine.insert.split_drain"):
                    self._drain_split_log(log, stats)
            if len(self._pending_parents) >= self.parent_flush_threshold:
                # flush between rounds: parents keep descent paths short —
                # deferring across many split rounds can grow a B-link
                # chain past the static descent budget, spilling the batch
                # tail to the per-key host path.  (With a router attached,
                # note_split already retargets the affected buckets, so
                # storm drivers raise the threshold and flush per chunk.)
                self.flush_parents()

            stats["applied"] += int((status == ST_APPLIED).sum())
            stats["superseded"] += int((status == ST_SUPERSEDED).sum())
            applied_rows[idx[status == ST_APPLIED]] = True
            done = (status == ST_APPLIED) | (status == ST_SUPERSEDED)
            pending[idx[done]] = False

            # Bounded lock retry with backoff (data-plane failure story):
            # a row blocked on a HELD page lock for lock_retry_rounds
            # consecutive rounds triggers a lease probe — a DEAD holder
            # (client died mid-critical-section) is revoked and the row
            # retries fresh; a LIVE holder is normal contention and
            # keeps retrying (with host-side backoff) through the round
            # budget.  Rows still lock-blocked when the budget runs out
            # get the typed ST_LOCK_TIMEOUT rejection below instead of
            # the host path's unbounded spin.
            lr = status == ST_LOCKED
            locked_rounds[idx[lr]] += 1
            locked_rounds[idx[~lr]] = 0
            probe = np.zeros(n, bool)
            probe[idx] = lr & (locked_rounds[idx]
                               % self.tcfg.lock_retry_rounds == 0)
            if probe.any():
                live = self._recover_wedged_locks(keys[probe])
                # reset ONLY rows whose lock was dead (now revoked) or
                # already freed — a live-blocked row must keep its
                # counter so budget exhaustion still rejects it typed
                rows_p = np.nonzero(probe)[0]
                locked_rounds[rows_p[~live]] = 0
            if lr.any():
                # brief host-side backoff before re-spinning on held
                # locks (doubles per consecutive blocked round, capped)
                _t.sleep(min(2e-4 * (1 << min(int(locked_rounds.max()),
                                              6)), 2e-2))

            # ST_FULL keys retry with fresh-page grants: the next round
            # splits their leaves on-device.  ST_BAD shouldn't happen but
            # is retried via host for robustness.
            bad = status == ST_BAD
            for j in idx[bad]:
                self.tree.insert(int(keys[j]), int(values[j]))
                stats["host_path"] += 1
                applied_rows[j] = True
                pending[j] = False
            if bad.any():
                self.tree._refresh_root()
            # grant fresh pages whenever anything retries: suppressed
            # writers on a splitting page report ST_RETRY, and their next
            # round may need to split again — granting only on ST_FULL
            # would split every OTHER round
            fresh_np = self._fill_fresh(
                bool(((status == ST_FULL) | (status == ST_RETRY)).any()))
            stalled = stalled + 1 if int(pending.sum()) == n_before else 0
        # Round budget exhausted.  Rows that ended it still blocked on a
        # page lock held by a LIVE lease get the typed ST_LOCK_TIMEOUT
        # rejection: handing them to the host path would trade a bounded
        # budget for an unbounded spin on a holder that never drained
        # (dead leases were revoked by the probes above, and one final
        # probe here catches a holder that died after the last round).
        still = np.nonzero(pending)[0]
        blocked = still[locked_rounds[still] > 0]
        if blocked.size:
            live_mask = self._recover_wedged_locks(keys[blocked])
            to = blocked[live_mask]
            if to.size:
                stats["lock_timeouts"] += int(to.size)
                stats["lock_timeout_keys"] += [int(k) for k in keys[to]]
                pending[to] = False
                _OBS_LOCK_TIMEOUTS.inc(int(to.size))
        # anything still pending after max_rounds: host path
        for j in np.nonzero(pending)[0]:
            self.tree.insert(int(keys[j]), int(values[j]))
            stats["host_path"] += 1
            applied_rows[j] = True
        return applied_rows

    def _recover_wedged_locks(self, keys: np.ndarray) -> np.ndarray:
        """Lock-lease recovery for keys blocked on held page locks:
        resolve each key's leaf with one device descent, read the
        leaves' global lock words in one step, and revoke every holder
        whose lease is DEAD — delegated per word to
        ``Tree._try_revoke_lease``, the single revocation policy (lease
        decode, epoch-table liveness, masked CAS, lease.* counters).
        -> live_mask [bool, aligned with keys]: True where the lock is
        held by a LIVE lease (legit contention or a stuck-but-alive
        peer — never revoked here).  Rides ``host_dsm``, so it is
        collective-safe: in multihost mode every process calls with the
        identical replicated key set and the revocation executes once
        cluster-wide."""
        tree = self.tree
        keys = np.asarray(keys, np.uint64)
        addrs, done = self._descend_to_level(keys, 0)
        la_by_key = np.array(
            [tree._lock_word_addr(int(a)) if d else -1
             for a, d in zip(addrs, done)], np.int64)
        las = sorted({int(la) for la in la_by_key if la != -1})
        if not las:
            return np.zeros(keys.shape[0], bool)
        rep = self.dsm._batch(
            [{"op": D.OP_READ_WORD, "addr": la, "woff": 0,
              "space": D.SPACE_LOCK} for la in las])
        live_las = {la for la, w in zip(las, rep.old)
                    if int(w) != 0
                    and not tree._try_revoke_lease(la, int(w))}
        return np.array([int(la) in live_las for la in la_by_key])

    def reclaim_empty_leaves(self, quarantine_rounds: int = 2) -> dict:
        """Unlink EMPTY leaves from the B-link chain and recycle their
        pages — beyond-reference: ``free()`` is a no-op in the reference
        (``DSM.h:226``, ``LocalAllocator.h:45-47``), so delete/churn
        workloads leak the pool dry.  Single-process meshes only (a local
        maintenance pass; multihost reclamation would need a replicated
        drive and is out of scope).

        Protocol, per (left, empty) adjacent leaf pair:

        MULTIHOST: a replicated COLLECTIVE — every process must call it
        at the same point with the same ``quarantine_rounds`` (digest-
        checked).  The pass then runs the PARITY #7 pattern implicitly:
        the plan is deterministic host code over mirrored state (the
        chain scan and every lock/verify/write step ride the leader-
        posted ReplicatedDSM; the allocator free pools are mirrored
        directories), so all processes compute and apply the identical
        plan in lock-step.  Calling it on a subset of processes
        deadlocks the collective steps — same contract as flush_parents.

        1. one jitted pool scan finds candidates (``leaf_chain_info``):
           an ACTIVE leaf with zero live slots whose chain predecessor
           exists (the leftmost leaf is never reclaimed — bounded waste,
           it is the chain's sentinel);
        2. lock left+empty (global CAS words; a shared hash word locks
           once), re-verify under the locks (left.sibling == empty, still
           empty, fences abut), then ONE atomic step rewrites left's
           header (sibling/highest bypass the empty leaf, front/rear
           version bump — a structural rewrite) and RETIRES the empty
           leaf: ``highest := 0`` refuses reads and writes structurally
           (every fence check fails), and ``sibling := left`` sends stale
           readers BACK to the absorbing leaf, which now owns the range;
        3. the retired leaf's parent entry is removed (lock + rebuild,
           the flush_parents merge protocol) — required before reuse: a
           stale parent entry must keep resolving to the RETIRED page
           (which self-heals via its back-sibling), never to a reused
           one.  A retired page referenced as a parent's LEFTMOST child
           is PARKED instead (retired forever, never freed — repointing
           the leftmost would dangle once its target is itself reused;
           bounded at ~1/INTERNAL_CAP of reclaimable leaves).  Cleanup
           failures stay pending and retry on the next call; retired
           strays found by the scan (e.g. in-flight state lost at a
           checkpoint/restore boundary) re-enter this path, so reclaim
           is crash-recoverable;
        4. quarantine: cleaned pages return to their node's allocator
           free pool only after ``quarantine_rounds`` further calls — the
           grace period for concurrent host clients still holding
           pre-unlink addresses (steps are serialized, so in-flight
           device work cannot straddle the boundary; the window is host
           threads mid-descent).

        Returns {"unlinked", "freed", "quarantined", "candidates"}.
        """
        self._require_writable()  # reclaim rewrites pages: not degraded
        # replicated-collective contract (multihost): identical call
        # sites + identical args on every process, pinned by the same
        # digest check the other engine drivers use.  The engine-local
        # reclaim round counter rides the digest so a process that
        # skipped an earlier reclaim call fails loudly here instead of
        # desyncing the mirrored allocator pools; the deferred-parent
        # count rides it too so a process whose writer thread raced an
        # entry in fails HERE, not by desyncing the flush_parents
        # collective the drain below would run on a subset of processes.
        self._check_replicated(np.array(
            [quarantine_rounds, self._reclaim_state["round"],
             len(self._pending_parents)], np.uint64))
        if not self._reclaim_mutex.acquire(blocking=False):
            raise StateError(
                "reclaim_empty_leaves is not reentrant: another reclaim "
                "pass is already running on this engine")
        try:
            return self._reclaim_empty_leaves_locked(quarantine_rounds)
        finally:
            self._reclaim_mutex.release()

    def _reclaim_empty_leaves_locked(self, quarantine_rounds: int) -> dict:
        from sherman_tpu.models.validate import leaf_chain_info
        tree, dsm = self.tree, self.dsm
        # Drain deferred parent entries BEFORE scanning: a pending
        # (k -> c) entry not yet flushed leaves leaf c with no parent
        # entry to find, so parent removal would quarantine it while the
        # deferred flush still owes a parent entry pointing at it — the
        # flush would then alias a freed/reused page.
        if self._pending_parents:
            self.flush_parents()
        st = self._reclaim_state
        st["round"] += 1
        stats = {"unlinked": 0, "freed": 0, "candidates": 0,
                 "quarantined": len(st["quarantine"]),
                 "parked": len(st["parked"])}

        # Snapshot the released-page state BEFORE the scan: a page freed
        # at snapshot time is either still free at scan time (snapshot
        # covers it) or was popped and rewritten by a writer (the scan
        # then no longer sees it as retired).  Snapshotting AFTER the
        # scan would leave a window where a writer pops a scanned-
        # retired page out of the pool and the sweep double-frees it.
        released = set()
        for nd, d in self.tree.ctx.alloc._by_node.items():
            for p in d.allocator.free_pages_list:
                released.add((nd << C.ADDR_PAGE_BITS) | p)
        for lst in self._fresh_cache.values():
            for a in lst:
                released.add(int(a) & 0xFFFFFFFF)
        # the chain scan launches on the CURRENT pool handle: hold the
        # step mutex so a concurrent host writer's donated-buffer swap
        # cannot invalidate the handle between read and launch (the scan
        # materializes inside, so the mutex spans one kernel execution —
        # acceptable for a maintenance pass)
        with self._step_mutex:
            (addrs, lows, highs, sibs, n_live,
             retired_addrs, retired_lows) = leaf_chain_info(tree)
        tree._refresh_root()
        quarantined = {a for _, a in st["quarantine"]}
        # sweep retired strays: pages unlinked by a PREVIOUS incarnation
        # (in-flight quarantine/cleanup state is engine-local and not
        # checkpointed) re-enter the parent-cleanup -> quarantine path
        # here, so a restored cluster's reclaim calls recover them.
        # `known` MUST also cover pages already RELEASED — the pre-scan
        # `released` snapshot of the allocator free pools and cached
        # split grants — because a freed page still LOOKS retired until
        # its next write; sweeping one would double-free it into the
        # pool (the same page granted twice = silent aliasing).
        known = (quarantined | st["parked"] | released
                 | {e for e, _, _ in st["pending_parent"]})
        for ra, rl in zip(retired_addrs.tolist(), retired_lows.tolist()):
            if ra not in known:
                st["pending_parent"].append((int(ra), int(rl), 0))
        # adjacent pairs with chain continuity; greedy-alternate so a
        # pair's left member is never itself unlinked this round.  Pages
        # still owed a deferred parent entry (appended after the flush
        # above, e.g. by a concurrent writer's split log) are excluded:
        # their parent entry does not exist yet, so parent removal would
        # wrongly conclude they are unreferenced.
        pend_children = {int(t[1]) & 0xFFFFFFFF
                         for t in self._pending_parents}
        pairs = []
        taken = set()
        for i in range(1, addrs.size):
            L, E = int(addrs[i - 1]), int(addrs[i])
            if (n_live[i] == 0 and sibs[i - 1] == E and E not in taken
                    and L not in taken and E not in quarantined
                    and (E & 0xFFFFFFFF) not in pend_children
                    and E != tree._root_addr):
                pairs.append((L, E, int(lows[i]), int(highs[i])))
                taken.add(E)
                taken.add(L)
        stats["candidates"] = len(pairs)

        # Two host steps for ALL pairs (the flush_parents coalescing
        # pattern — no per-pair round trips): one step CAS-locks every pair's word(s) and
        # reads both pages; one step writes every verified unlink plus
        # every unlock.  Pairs sharing a lock word with an earlier pair
        # are deferred to the next call (CAS outcomes would be ambiguous
        # across pairs).
        seen_words: set = set()
        plan = []
        for L, E, e_low, e_high in pairs:
            la, ea = tree._lock_word_addr(L), tree._lock_word_addr(E)
            words = (la,) if la == ea else (la, ea)
            if any(w in seen_words for w in words):
                continue
            seen_words.update(words)
            plan.append((L, E, e_low, e_high, words))
        rows = []
        base = {}
        for L, E, e_low, e_high, words in plan:
            base[E] = len(rows)
            for w in words:
                rows.append({"op": D.OP_CAS, "addr": w, "woff": 0,
                             "arg0": 0, "arg1": tree.ctx.lease,
                             "space": D.SPACE_LOCK})
            rows.append({"op": D.OP_READ, "addr": L})
            rows.append({"op": D.OP_READ, "addr": E})
        rep = dsm._batch(rows) if rows else None
        w1 = lambda a, w, v: {"op": D.OP_WRITE, "addr": a, "woff": w,
                              "nw": 1, "payload": np.array([v], np.int32)}
        out_rows = []
        mapping: dict[int, int] = {}
        for L, E, e_low, e_high, words in plan:
            i0 = base[E]
            got = [bool(rep.ok[i0 + j]) for j in range(len(words))]
            held = [w for w, g in zip(words, got) if g]
            if not all(got):
                out_rows += [tree._unlock_row(w) for w in held]
                continue
            lpg = np.array(rep.data[i0 + len(words)])
            epg = np.array(rep.data[i0 + len(words) + 1])
            ok = (int(lpg[C.W_SIBLING]) & 0xFFFFFFFF) == (E & 0xFFFFFFFF) \
                and layout.np_highest(lpg) == e_low \
                and layout.np_lowest(epg) == e_low \
                and layout.np_highest(epg) == e_high \
                and not layout.np_leaf_entries(epg)
            if not ok:
                out_rows += [tree._unlock_row(w) for w in held]
                continue
            ver = ((int(lpg[C.W_FRONT_VER]) + 1) & 0x7FFFFFFF) or 1
            hh, hl = bits.key_to_pair(e_high)
            out_rows += [
                # left absorbs the range: highest/sibling bypass E
                w1(L, C.W_HIGH_HI, hh), w1(L, C.W_HIGH_LO, hl),
                w1(L, C.W_SIBLING, int(epg[C.W_SIBLING])),
                w1(L, C.W_FRONT_VER, ver), w1(L, C.W_REAR_VER, ver),
                # E retires: highest=0 refuses every fence check; sibling
                # points BACK at the absorber so stale readers self-heal
                w1(E, C.W_HIGH_HI, 0), w1(E, C.W_HIGH_LO, 0),
                w1(E, C.W_SIBLING, np.int32(np.uint32(L & 0xFFFFFFFF)
                                            .view(np.int32))),
            ] + [tree._unlock_row(w) for w in held]
            st["pending_parent"].append((E, e_low, L))
            mapping[E] = L
            stats["unlinked"] += 1
            if tree.index_cache is not None:
                tree.index_cache.invalidate(e_low)
        if out_rows:
            dsm._batch(out_rows)
        if mapping and self.router is not None:
            self.router.remap_addrs(mapping)
        if mapping and self.leaf_cache is not None:
            # reclaim rewrites the absorber's header and retires the
            # empty page for eventual reuse: drop every cached entry on
            # either side of each unlinked pair (the retired page holds
            # no live keys, but a later reuse must never meet a stale
            # cached position)
            self.leaf_cache.invalidate_pages(
                list(mapping.keys()) + list(mapping.values()))

        # parent-entry removal for unlinked pages (flush-style); only
        # cleaned pages advance to quarantine
        if st["pending_parent"]:
            st["pending_parent"] = self._remove_parent_entries(
                st["pending_parent"], st)

        # release quarantine
        ready = [(r, a) for r, a in st["quarantine"]
                 if st["round"] - r >= quarantine_rounds]
        st["quarantine"] = [(r, a) for r, a in st["quarantine"]
                            if st["round"] - r < quarantine_rounds]
        by_node: dict[int, list[int]] = {}
        for _, a in ready:
            by_node.setdefault(bits.addr_node(a), []).append(
                bits.addr_page(a))
        for nd, pgs in by_node.items():
            d = self.tree.ctx.alloc._by_node.get(nd)
            if d is None:
                # non-local node: keep quarantined rather than leak
                st["quarantine"].extend((st["round"], bits.make_addr(nd, p))
                                        for p in pgs)
                continue
            d.allocator.reclaim(pgs)
            stats["freed"] += len(pgs)
        stats["quarantined"] = len(st["quarantine"])
        stats["parked"] = len(st["parked"])
        return stats

    def _remove_parent_entries(self, pend, st) -> list:
        """Remove retired pages' parent entries (lock + rebuild, the
        flush_parents merge protocol).  Cleaned pages enter quarantine;
        failures stay pending for the next reclaim call."""
        tree, dsm = self.tree, self.dsm
        tree._refresh_root()
        if tree._root_level < 1:
            # root is a leaf: no parents exist; straight to quarantine
            for e, _k, _l in pend:
                st["quarantine"].append((st["round"], e))
            return []
        keysu = np.array([k for _, k, _ in pend], np.uint64)
        # descend by the retired page's OLD low fence: its parent entry
        # (if any) lives on that path's level-1 page
        paddrs, done = self._descend_to_level(keysu, 1)
        group: dict[int, list[tuple[int, int, int]]] = {}
        nxt: list = []
        for (e, k, ab), a, d_ok in zip(pend, paddrs, done):
            if d_ok:
                group.setdefault(int(a), []).append((e, k, ab))
            else:
                nxt.append((e, k, ab))
        # TWO host steps for ALL parents (the unlink stage's coalescing
        # pattern): one step CAS-locks + reads every grouped parent, one
        # step writes every rebuilt page together with all unlocks
        # (a churn pass touches ~10^3 parents; per-parent round trips
        # would scale with them).  Parents sharing a lock word with an earlier parent
        # defer to the next call (CAS outcomes across same-word rows in
        # one step would be ambiguous).
        seen_words: set = set()
        plan = []
        for pa, items in group.items():
            la = tree._lock_word_addr(pa)
            if la in seen_words:
                nxt.extend(items)
                continue
            seen_words.add(la)
            plan.append((pa, la, items))
        rows = []
        for pa, la, _items in plan:
            rows.append({"op": D.OP_CAS, "addr": la, "woff": 0, "arg0": 0,
                         "arg1": tree.ctx.lease, "space": D.SPACE_LOCK})
            rows.append({"op": D.OP_READ, "addr": pa})
        rep = dsm._batch(rows) if rows else None
        out_rows = []
        decisions = []
        for i, (pa, la, items) in enumerate(plan):
            if not bool(rep.ok[2 * i]):
                nxt.extend(items)
                continue
            pg = np.array(rep.data[2 * i + 1])
            if int(pg[C.W_LEVEL]) != 1:
                # fence moved / wrong page: retry next round
                out_rows.append(tree._unlock_row(la))
                nxt.extend(items)
                continue
            # fence re-check UNDER the lock (the same guard flush_parents
            # applies at its merge step): a concurrent split of this
            # parent between the descent and the CAS moves entries >= the
            # split key to the right sibling.  An item whose key the
            # locked page no longer covers may have its entry alive over
            # there — concluding "entry absent, page unreferenced" from
            # THIS page would quarantine and reuse a page a live parent
            # entry still resolves to.  Uncovered items retry next round.
            lo, hi = layout.np_lowest(pg), layout.np_highest(pg)
            covered = [t for t in items if lo <= t[1] < hi]
            nxt.extend(t for t in items if not (lo <= t[1] < hi))
            if not covered:
                out_rows.append(tree._unlock_row(la))
                continue
            items = covered
            drop = {e & 0xFFFFFFFF for e, _, _ in items}
            ents = [(k, c) for k, c in layout.np_internal_entries(pg)
                    if (c & 0xFFFFFFFF) not in drop]
            kept = {c & 0xFFFFFFFF for _, c in ents}
            newpg = layout.np_internal_rebuild(pg, ents, 1)
            lm = int(pg[C.W_LEFTMOST]) & 0xFFFFFFFF
            out_rows.append({"op": D.OP_WRITE, "addr": pa, "woff": 0,
                             "nw": C.PAGE_WORDS, "payload": newpg})
            out_rows.append(tree._unlock_row(la))
            decisions.append((items, kept, lm))
        # quarantine/park decisions apply ONLY after the write batch
        # lands: if it raises, st is untouched and the caller's
        # pending_parent assignment never happens, so every item stays
        # pending and retries — a failed batch must never quarantine
        # (-> later free + reuse) a page whose parent entry survived
        # on-device.
        if out_rows:
            dsm._batch(out_rows)
        for items, kept, lm in decisions:
            for e, k, ab in items:
                eu = e & 0xFFFFFFFF
                if eu == lm:
                    # this parent's LEFTMOST child: the pointer cannot be
                    # dropped (the page has no left entry) and repointing
                    # it at the absorber would dangle once the absorber
                    # is itself reclaimed and reused.  PARK the page: it
                    # stays retired forever (reads/writes refuse via the
                    # zero fence; stale descents self-heal through its
                    # back-sibling) and is never freed.
                    st["parked"].add(e)
                elif eu in kept:  # entry elsewhere: retry
                    nxt.append((e, k, ab))
                else:
                    st["quarantine"].append((st["round"], e))
        return nxt

    def range_query(self, lo: int, hi: int) -> tuple[np.ndarray, np.ndarray]:
        """All (k, v) with lo <= k < hi, sorted.  See
        :meth:`range_query_many`."""
        return self.range_query_many([(lo, hi)])[0]

    def range_query_many(self, ranges) -> list[tuple[np.ndarray, np.ndarray]]:
        """Batched scans: ONE device gather prefetches the candidate
        leaves of EVERY range, then each range walks its chain over the
        shared prefetch.  The multi-scan analogue of the reference's
        kParaFetch window (Tree.cpp:501-522): where it pipelines 32
        fetches within one scan, the batched server amortizes the whole
        scan SET into one step.  ranges: iterable of (lo, hi); returns
        [(keys, vals)] per range, each sorted by key."""
        ranges = [(int(lo), int(hi)) for lo, hi in ranges]
        # replication guard: the chain walk issues a data-dependent number
        # of collective host reads — divergent bounds would desync them
        self._check_replicated(
            np.asarray([b for r in ranges for b in r], np.uint64))
        t_slo = time.perf_counter()
        out = range_query_many(self, ranges)
        # scans: one op per range (row counts vary per range; the SLO
        # unit is the client request, as for every other class)
        _slo_observe("scan", len(ranges), t_slo)
        return out

    def delete(self, keys, max_rounds: int | None = None) -> np.ndarray:
        """Batched delete (``Tree::del`` parity).  Returns found bool [n]
        (True where the key existed and was removed)."""
        self._require_writable()
        t_slo = time.perf_counter()
        if max_rounds is None:
            max_rounds = self.tcfg.insert_rounds
        keys = np.asarray(keys, np.uint64)
        if keys.size and (keys.min() < C.KEY_MIN or keys.max() > C.KEY_MAX):
            raise KeyRangeError("keys outside [KEY_MIN, KEY_MAX]")
        self._check_replicated(keys)
        n = keys.shape[0]
        total = self.cfg.machine_nr * self.B
        out = np.zeros(n, bool)
        for i in range(0, n, total):
            out[i:i + total] = self._delete_chunk(keys[i:i + total],
                                                  max_rounds)
        # journal the deletes that actually cleared a slot (not-found
        # rows are no-ops; replaying them would also be, but keeping the
        # record set == applied set keeps replay accounting exact)
        self._journal_applied(J.J_DELETE, keys[out])
        if self.leaf_cache is not None and n:
            self.leaf_cache.invalidate_keys(keys)
        _slo_observe("delete", n, t_slo)
        return out

    def _delete_chunk(self, keys, max_rounds) -> np.ndarray:
        n = keys.shape[0]
        found_out = np.zeros(n, bool)
        pending = np.ones(n, bool)
        for round_i in range(max_rounds):
            if not pending.any():
                return found_out
            idx = np.nonzero(pending)[0]
            khi, klo = bits.keys_to_pairs(keys[idx])
            (khi, _), (klo, _) = self._pad(khi), self._pad(klo)
            active, _ = self._pad(np.ones(idx.shape[0], bool))
            use_router = self.router is not None and round_i == 0
            fn = self._get_delete(self._iters(), use_router)
            args = [self._shard(khi), self._shard(klo),
                    np.int32(self.tree._root_addr), self._shard(active)]
            if use_router:
                args.append(self._shard(self.router.host_start(khi, klo)))
            with obs.span("engine.delete.descend_lock_apply",
                          n=int(idx.shape[0])):
                with self._step_mutex:  # launch-only (prep above)
                    (self.dsm.pool, self.dsm.counters, self.dsm.dirty,
                     status) = fn(
                        self.dsm.pool, self.dsm.locks, self.dsm.counters,
                        self.dsm.dirty, *args)
                status = self._unshard(status)[:idx.shape[0]]

            found_out[idx[status == ST_APPLIED]] = True
            done = (status == ST_APPLIED) | (status == ST_NOT_FOUND)
            pending[idx[done]] = False
            bad = status == ST_BAD
            for j in idx[bad]:
                found_out[j] = self.tree.delete(int(keys[j]))
                pending[j] = False
            if bad.any():
                self.tree._refresh_root()
        for j in np.nonzero(pending)[0]:
            found_out[j] = self.tree.delete(int(keys[j]))
        return found_out


# ---------------------------------------------------------------------------
# Range query: cache-seeded batched leaf fetch (Tree.cpp:461-522).
# ---------------------------------------------------------------------------

def _addr_rows(addrs: np.ndarray, pages_per_node: int) -> np.ndarray:
    """Packed addrs -> global pool row indices (host)."""
    a = np.asarray(addrs).astype(np.uint32).astype(np.uint64)
    return ((a >> C.ADDR_PAGE_BITS) * np.uint64(pages_per_node)
            + (a & np.uint64(C.ADDR_PAGE_MASK))).astype(np.int64)


@jax.jit
def _gather_rows(pool, rows):
    return pool[rows]


def range_query_many(eng: "BatchedEngine", ranges
                     ) -> list[tuple[np.ndarray, np.ndarray]]:
    """Batched scans: all (k, v) with lo <= k < hi per range, sorted.

    TPU-native shape of the reference's pipelined scan
    (``Tree.cpp:461-522``): the index cache (router table) yields the
    candidate leaf set of EVERY range in O(1); ONE device gather fetches
    the union of candidate pages (beating the reference's 32-deep fetch
    window, and amortizing the host<->device round trip over the whole
    scan set); each range then walks its B-link chain over the shared
    prefetch and only touches the DSM again for chain gaps (stale
    cache), mirroring the re-descend fallback.
    """
    tree = eng.tree
    cfg = eng.cfg
    # materialize + coerce: callers may pass generators or numpy scalars
    ranges = [(int(lo), int(hi)) for lo, hi in ranges]
    for lo, hi in ranges:
        assert C.KEY_MIN <= lo and hi <= C.KEY_POS_INF and lo < hi

    # -- candidate prefetch from the router table (union of all ranges) ----
    fetched: dict[int, np.ndarray] = {}
    if eng.router is not None and ranges:
        r = eng.router
        cand_parts = []
        with r._read_locked():
            for lo, hi in ranges:
                # clamp BOTH ends into the table: out-of-span ranges
                # (common with narrow-keyspace seeds) start from the last
                # bucket's seed instead of silently skipping the prefetch
                b_lo = min(r.nb - 1, lo >> r.shift)
                b_hi = min(r.nb - 1, max(0, (hi - 1) >> r.shift))
                cand_parts.append(r.table_np[b_lo:b_hi + 1])
        cand = np.unique(np.concatenate(cand_parts))
        if cand.size:
            if eng._mh:
                # replicated host reads (chunked collective steps)
                pages = tree.dsm.read_pages([int(a) for a in cand])
            else:
                rows = _addr_rows(cand, cfg.pages_per_node)
                with eng._step_mutex:  # pool handle read vs donating steps
                    got = _gather_rows(eng.dsm.pool, jnp.asarray(rows))
                pages = np.asarray(got)
            for a, p in zip(cand.tolist(), pages):
                if int(p[C.W_LEVEL]) == 0:   # stale entries may be internal
                    fetched[int(a) & 0xFFFFFFFF] = p

    # pages fetched during chain walks (router misses) join `extras` so
    # later ranges starting inside them skip the re-descend
    extras: dict[int, np.ndarray] = {}

    def get_page(addr: int) -> np.ndarray:
        p = fetched.get(addr & 0xFFFFFFFF)
        if p is None:
            p = tree.dsm.read_page(addr)
            fetched[addr & 0xFFFFFFFF] = p
            extras[addr & 0xFFFFFFFF] = p
        return p

    # sorted (lowest -> addr) index over the prefetch: start-leaf lookup
    # per range is a binary search, not a scan of every fetched page
    if fetched:
        f_addrs = np.fromiter(fetched.keys(), np.int64, len(fetched))
        f_lows = np.array([layout.np_lowest(fetched[int(a)])
                           for a in f_addrs], np.uint64)
        f_highs = np.array([layout.np_highest(fetched[int(a)])
                            for a in f_addrs], np.uint64)
        f_order = np.argsort(f_lows)
        f_addrs, f_lows, f_highs = (f_addrs[f_order], f_lows[f_order],
                                    f_highs[f_order])
    else:
        f_addrs = np.zeros(0, np.int64)
        f_lows = f_highs = np.zeros(0, np.uint64)

    out: list[tuple[np.ndarray, np.ndarray]] = []
    for lo, hi in ranges:
        # -- find the first leaf containing lo ------------------------------
        start = None
        i = int(np.searchsorted(f_lows, np.uint64(lo), side="right")) - 1
        if i >= 0 and lo < int(f_highs[i]):
            start = int(f_addrs[i])
        if start is None:
            for a, p in extras.items():   # walk-fetched pages, few
                if layout.np_lowest(p) <= lo < layout.np_highest(p):
                    start = a
                    break
        if start is None:
            start, _, _ = tree._descend(lo, 0)

        # -- walk the chain -------------------------------------------------
        addr = start
        chain_pages = []
        hops = 0
        while True:
            pg = get_page(addr)
            chain_pages.append(pg)
            if layout.np_highest(pg) >= hi:
                break
            sib = int(pg[C.W_SIBLING])
            if bits.addr_is_null(sib):
                break
            addr = sib
            hops += 1
            assert hops < cfg.machine_nr * cfg.pages_per_node, \
                "chain runaway"
        pages = np.stack(chain_pages)
        keys, vals, live = layout.np_leaf_entries_batch(pages)
        m = live & (keys >= np.uint64(lo)) & (keys < np.uint64(hi))
        out_k, out_v = keys[m], vals[m]
        order = np.argsort(out_k)
        out.append((out_k[order], out_v[order]))
    return out


# ---------------------------------------------------------------------------
# Bulk load: bottom-up tree construction (benchmark warmup path).
# ---------------------------------------------------------------------------

def _install_pages_impl(pool, rows, pages):
    return pool.at[rows].set(pages)


@functools.lru_cache(maxsize=None)
def _install_pages_jit():
    # jitted lazily so the donation decision (backend-gated — see
    # config.donate_argnums) never initializes the backend at import
    return jax.jit(_install_pages_impl,
                   donate_argnums=C.donate_argnums(0))


def _install_pages(pool, rows, pages):
    return _install_pages_jit()(pool, rows, pages)


@functools.lru_cache(maxsize=None)
def _build_install_leaves_jit():
    return jax.jit(_build_install_leaves_impl,
                   donate_argnums=C.donate_argnums(0),
                   static_argnames=("per_leaf",))


def _build_install_leaves(pool, rows, khi, klo, vhi, vlo, live,
                          lhi, llo, hhi, hlo, sib, *, per_leaf: int):
    return _build_install_leaves_jit()(
        pool, rows, khi, klo, vhi, vlo, live, lhi, llo, hhi, hlo, sib,
        per_leaf=per_leaf)


def _build_install_leaves_impl(pool, rows, khi, klo, vhi, vlo, live,
                               lhi, llo, hhi, hlo, sib, *, per_leaf: int):
    """Build all leaf pages ON DEVICE and scatter them into the pool.

    The leaf level is ~97% of a bulk load's bytes; building it device-side
    ships 4 words per entry (khi/klo/vhi/vlo) instead of whole 256-word
    pages — ~2.7x less host->device traffic — and the build itself is
    reshape/pad/concat work the VPU does in milliseconds.  Entries are
    packed sequentially ``per_leaf`` per page (sorted bulk keys), so the
    [L, CAP] field blocks are plain reshapes of the flat word arrays —
    no scatter until the final page install.

    rows: [L] pool row of each leaf; khi..vlo: [L*per_leaf] padded flat
    entry words; live: [L*per_leaf] int32 slot liveness; lhi..sib: [L]
    header words.
    """
    L = rows.shape[0]
    pad_cols = ((0, 0), (0, C.LEAF_CAP - per_leaf))

    def blk(x):
        return jnp.pad(x.reshape(L, per_leaf), pad_cols)

    page = _leaf_pages(blk(khi), blk(klo), blk(vhi), blk(vlo),
                       blk(live).astype(bool), jnp.ones(L, jnp.int32),
                       lhi, llo, hhi, hlo, sib)
    return pool.at[rows].set(page)

def bulk_load(tree, keys, values, fill: float | None = None) -> dict:
    """Build the tree bottom-up from unique sorted keys and install it.

    The host builds every page vectorized in numpy and writes the whole pool
    once — the analogue of the benchmark's warmup phase
    (``test/benchmark.cpp:114-120``) at TPU speed.  Returns stats.
    """
    cfg = tree.cfg
    if fill is None:
        fill = TreeConfig().bulk_fill
    # replicated-driver invariant: every process must bulk-load the
    # identical data (mirrored allocators depend on it)
    _assert_replicated(tree.dsm.multihost,
                       (np.asarray(keys, np.uint64),
                        np.asarray(values, np.uint64)), "bulk_load")
    # Guard: bulk load replaces the whole tree, so refuse to drop existing
    # data — the current tree must be an empty root leaf.
    tree._refresh_root()
    old_root = tree._root_addr
    old_pg = tree.dsm.read_page(old_root)
    if tree._root_level != 0 or layout.np_leaf_entries(old_pg):
        raise ConfigError("bulk_load requires an empty tree")

    keys = np.asarray(keys, np.uint64)
    values = np.asarray(values, np.uint64)
    order = np.argsort(keys, kind="stable")
    keys, values = keys[order], values[order]
    assert (np.diff(keys) > 0).all(), "bulk_load requires unique keys"
    n = keys.shape[0]

    per_leaf = max(1, min(C.LEAF_CAP, int(C.LEAF_CAP * fill)))
    n_leaves = max(1, -(-n // per_leaf))

    # multi-controller jit needs explicit (replicated) global arrays for
    # the non-sharded operands; single-process passes host arrays through
    if tree.dsm.multihost:
        rep_shard = jax.sharding.NamedSharding(
            tree.dsm.mesh, jax.sharding.PartitionSpec())
        mk = lambda x: jax.make_array_from_callback(
            x.shape, rep_shard, lambda idx: x[idx])
    else:
        mk = jnp.asarray

    # --- leaf level: built ON DEVICE (_build_install_leaves) ----------------
    alloc = tree.ctx.alloc
    leaf_addrs = alloc.alloc_many(n_leaves)
    total = n_leaves * per_leaf
    khi, klo = bits.keys_to_pairs(keys)
    vhi, vlo = bits.keys_to_pairs(values)
    pad = total - n
    flat = lambda x: mk(np.pad(x, (0, pad)))
    live = np.zeros(total, np.int32)
    live[:n] = 1

    # fences: lowest = first key of leaf (leaf 0: -inf); highest = next
    # leaf's first key (last: +inf); sibling links left->right
    first_keys = keys[::per_leaf][:n_leaves]
    lows = np.empty(n_leaves, np.uint64)
    lows[0] = C.KEY_NEG_INF
    lows[1:] = first_keys[1:]
    highs = np.empty(n_leaves, np.uint64)
    highs[:-1] = first_keys[1:]
    highs[-1] = C.KEY_POS_INF
    lhi, llo = bits.keys_to_pairs(lows)
    hhi, hlo = bits.keys_to_pairs(highs)
    sib = np.zeros(n_leaves, np.int32)
    sib[:-1] = leaf_addrs[1:].astype(np.int32)
    leaf_rows = _addr_rows(leaf_addrs, cfg.pages_per_node)
    tree.dsm.pool = _build_install_leaves(
        tree.dsm.pool, mk(leaf_rows), flat(khi), flat(klo), flat(vhi),
        flat(vlo), mk(live), mk(lhi), mk(llo), mk(hhi), mk(hlo), mk(sib),
        per_leaf=per_leaf)
    # direct installs bypass the step path: mark for delta checkpoints
    tree.dsm.mark_dirty_rows(leaf_rows)

    all_pages = []
    all_addrs = []
    stats = {"leaves": n_leaves, "internal": 0, "levels": 1}

    # --- internal levels ----------------------------------------------------
    level = 0
    child_addrs = leaf_addrs
    child_lows = lows
    while len(child_addrs) > 1:
        level += 1
        # children per internal page (incl leftmost): same fill slack as
        # leaves — packing internal pages to capacity would force an
        # internal split on the FIRST post-bulk leaf split under them
        fan = max(2, int(C.INTERNAL_CAP * fill))
        m = len(child_addrs)
        n_pages = -(-m // fan)
        addrs = alloc.alloc_many(n_pages)
        ipages = np.zeros((n_pages, _PW), np.int32)
        ipages[:, C.W_FRONT_VER] = 1
        ipages[:, C.W_REAR_VER] = 1
        ipages[:, C.W_LEVEL] = level

        pg_of = np.arange(m) // fan
        pos = np.arange(m) % fan
        # first child of each page -> leftmost; rest -> entries keyed by
        # the child's lowest fence
        is_first = pos == 0
        ipages[pg_of[is_first], C.W_LEFTMOST] = \
            child_addrs[is_first].astype(np.int32)
        ent = pos - 1
        ei = ~is_first
        eslot = ent[ei]
        ckhi, cklo = bits.keys_to_pairs(child_lows[ei])
        ipages[pg_of[ei], C.I_KHI_W + eslot] = ckhi
        ipages[pg_of[ei], C.I_KLO_W + eslot] = cklo
        ipages[pg_of[ei], C.I_PTR_W + eslot] = child_addrs[ei].astype(np.int32)
        counts = np.bincount(pg_of, minlength=n_pages) - 1
        ipages[:, C.W_NKEYS] = counts.astype(np.int32)

        pfirst = child_lows[::fan][:n_pages]
        plows = np.empty(n_pages, np.uint64)
        plows[0] = C.KEY_NEG_INF
        plows[1:] = pfirst[1:]
        phighs = np.empty(n_pages, np.uint64)
        phighs[:-1] = pfirst[1:]
        phighs[-1] = C.KEY_POS_INF
        lhi, llo = bits.keys_to_pairs(plows)
        hhi, hlo = bits.keys_to_pairs(phighs)
        ipages[:, C.W_LOW_HI], ipages[:, C.W_LOW_LO] = lhi, llo
        ipages[:, C.W_HIGH_HI], ipages[:, C.W_HIGH_LO] = hhi, hlo
        ipages[:-1, C.W_SIBLING] = addrs[1:].astype(np.int32)

        all_pages.append(ipages)
        all_addrs.append(addrs)
        stats["internal"] += n_pages
        stats["levels"] += 1
        child_addrs, child_lows = addrs, plows

    root_addr = int(child_addrs[0])
    root_level = level

    # --- install internal levels (the ~3% the host still builds) -----------
    if all_addrs:
        flat_addrs = np.concatenate(all_addrs)
        flat_pages = np.concatenate(all_pages, axis=0)
        rows = _addr_rows(flat_addrs, cfg.pages_per_node)
        tree.dsm.pool = _install_pages(tree.dsm.pool, mk(rows),
                                       mk(flat_pages))
        tree.dsm.mark_dirty_rows(rows)

    # Install root (bulk load is cluster-quiescent) and POISON the old root:
    # clients holding a stale root handle recover through the B-link chase
    # (btree.py's correctness invariant), so the old root must chase into the
    # new tree — set its highest fence to -inf (every key overshoots) and its
    # sibling to the new root.
    old_poison = old_pg.copy()
    old_poison[C.W_HIGH_HI] = 0
    old_poison[C.W_HIGH_LO] = 0
    old_poison[C.W_SIBLING] = root_addr
    tree.dsm.write_rows([
        {"op": D.OP_WRITE, "addr": old_root, "woff": 0,
         "nw": C.PAGE_WORDS, "payload": old_poison},
        {"op": D.OP_WRITE_WORD, "addr": META_ADDR,
         "woff": C.META_ROOT_ADDR_W, "arg1": root_addr},
    ])
    tree.cluster.broadcast_new_root(root_addr, root_level)
    tree._root_addr, tree._root_level = root_addr, root_level
    stats["root_level"] = root_level

    # leaf directory for index-cache seeding (router.seed_from_leaves)
    tree._bulk_leaf_dir = (leaf_addrs.copy(), lows.copy())
    if tree.router is not None:
        tree.router.seed_from_leaves(leaf_addrs, lows)
    return stats
