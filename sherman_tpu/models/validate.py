"""Device-side batched structure validation — the whole tree in one step.

The reference's structural sanity tool is a host walk
(``print_and_check_tree``, Tree.cpp:151-203) reading one page per round
trip; our host twin (``Tree.check_structure``) shares that shape —
O(pages) device steps, fine for unit fixtures but unusable at benchmark
scale (tens of minutes for 10^4 pages on the CPU mesh, unthinkable at
10^8).  This module validates the WHOLE tree in O(1) jitted device
steps: every invariant is a vectorized predicate over the full pool plus
a handful of single-word gathers.

Checks (a superset of the host walk's):

1. version pairs consistent (front == rear) on every live page.
2. fences strictly ordered (lowest < highest) on every active page.
3. every live leaf slot's key inside the page's [lowest, highest) fence.
4. internal entries strictly ascending (sorted-page invariant).
5. per-link B-link continuity for EVERY page with a sibling: sibling is
   live, same level, and sibling.lowest == my highest (no fence gaps).
6. leaf-chain global shape WITHOUT walking it: exactly one head
   (in-degree 0, lowest == NEG_INF), exactly one tail (sibling == NULL,
   highest == POS_INF), in-degree <= 1 everywhere.  Together with 2.
   and 5. this PROVES one gap-free chain covering the keyspace: fences
   strictly increase along links (so no disjoint cycle can hide — its
   fences would have to wrap), every leaf has out-degree <= 1, and
   exactly one head/tail exist — the same conclusion the host walk
   reaches by O(leaves) round trips.
7. parent/child coherence (beyond the host walk): every valid internal
   entry's child is live with level == parent-1 and lowest == the entry
   key; the leftmost child's lowest == the page's own lowest.

Retired pages are excluded: bulk_load poisons the replaced root
(highest := NEG_INF, sibling := the new root) so stale handles chase
into the new tree — ``highest == NEG_INF`` cannot occur on a reachable
page, so it doubles as the retirement marker.

Usable at any scale, including the real-chip benchmark tree
(``SHERMAN_BENCH_VALIDATE=1`` in bench.py) and the multihost mesh (the
jit auto-partitions the sharded pool; every process calls collectively).
"""

from __future__ import annotations

import functools

import jax
import numpy as np

from sherman_tpu import config as C

from sherman_tpu.errors import TreeCorruptError
from sherman_tpu.ops import bits, layout

_STATS = ("keys", "leaves", "internal_pages", "retired", "bad_version",
          "bad_fence", "bad_leaf_slot", "bad_torn_slot",
          "bad_internal_order", "bad_sibling", "heads", "bad_head",
          "tails", "bad_tail", "multi_indegree", "bad_leftmost",
          "bad_child")


# Rows per lax.map block of the whole-pool kernels: the per-row masks
# are built block by block so no [rows, CAP] temporary (GBs at the
# 100 M-key pool on a 16 GB chip) ever exists whole.
_BLOCK_ROWS = 1 << 16


def _blocks(pool, rows: int):
    """-> (pool viewed [n_blocks, B, PAGE_WORDS], B): blocks of
    ``_BLOCK_ROWS`` rows (one block below that), the pool zero-padded to
    a whole number of blocks (no copy when ``rows`` divides evenly, as
    every power-of-two pool does)."""
    import jax.numpy as jnp
    B = min(rows, _BLOCK_ROWS)
    pad = -rows % B
    if pad:
        pool = jnp.pad(pool, ((0, pad), (0, 0)))
    return pool.reshape(-1, B, C.PAGE_WORDS), B


def _rows_of(addr, P: int, N: int):
    """addr -> (pool row, in range) — BOTH fields bounds-checked: a page
    >= P would alias into the next node's row range and validate an
    unrelated page."""
    import jax.numpy as jnp
    u = addr.astype(jnp.uint32)
    node = (u >> C.ADDR_PAGE_BITS).astype(jnp.int32)
    page = (u & C.ADDR_PAGE_MASK).astype(jnp.int32)
    ok = (addr != 0) & (node < N) & (page < P)
    return jnp.clip(node * P + page, 0, N * P - 1), ok


def _row_block(pg, ridx, next_by_node, P: int):
    """The per-page LOCAL predicates of one block of rows ``pg`` [B,
    PAGE_WORDS] (global row numbers ``ridx``): every output is [B]."""
    import jax.numpy as jnp

    allocated = (ridx % P >= 1) & (ridx % P < next_by_node[ridx // P])

    def col(w):
        return pg[:, w]

    fv = col(C.W_FRONT_VER)
    live = allocated & (fv != 0)
    hi_hi, hi_lo = col(C.W_HIGH_HI), col(C.W_HIGH_LO)
    lo_hi, lo_lo = col(C.W_LOW_HI), col(C.W_LOW_LO)
    retired = live & (hi_hi == 0) & (hi_lo == 0)
    act = live & ~retired
    lvl = col(C.W_LEVEL)
    leaf = act & (lvl == 0)
    internal = act & (lvl > 0)
    # every active page's fences must be strictly ordered.  Beyond local
    # sanity this closes the chain proof: with lowest < highest on every
    # page and sibling.lowest == highest per link, fences strictly
    # increase along a chain, so a disjoint leaf CYCLE (whose members
    # would all have in-degree 1 — invisible to the head/tail counts)
    # cannot exist
    bad_fence = act & ~bits.key_lt(lo_hi, lo_lo, hi_hi, hi_lo)

    # leaf slots: liveness, fence containment, and the TORN pair class.
    # ver_pack writes both halves of the packed fver/rver pair equal in
    # one atomic step, so fver != rver is unreachable by legal writes —
    # any occurrence is corruption (the failure class CONFIG_ENABLE_CRC
    # guards in the reference; here the scrubber's bread and butter).
    LC = C.LEAF_CAP
    sfv, srv = layout.ver_unpack(pg[:, C.L_VER_W:C.L_VER_W + LC])
    skh = pg[:, C.L_KHI_W:C.L_KHI_W + LC]
    skl = pg[:, C.L_KLO_W:C.L_KLO_W + LC]
    s_live = (sfv == srv) & (sfv != 0)
    in_f = (bits.key_le(lo_hi[:, None], lo_lo[:, None], skh, skl)
            & bits.key_lt(skh, skl, hi_hi[:, None], hi_lo[:, None]))
    leaf_slots = leaf[:, None] & s_live

    # internal entries strictly ascending
    IC = C.INTERNAL_CAP
    ikh = pg[:, C.I_KHI_W:C.I_KHI_W + IC]
    ikl = pg[:, C.I_KLO_W:C.I_KLO_W + IC]
    nk = col(C.W_NKEYS)
    pos = jnp.arange(IC, dtype=jnp.int32)
    asc = bits.key_lt(ikh[:, :-1], ikl[:, :-1], ikh[:, 1:], ikl[:, 1:])
    pair_valid = internal[:, None] & (pos[None, 1:] < nk[:, None])

    return dict(act=act, retired=retired, leaf=leaf, internal=internal,
                lvl=lvl, sib=col(C.W_SIBLING), lm=col(C.W_LEFTMOST),
                nk=nk, lo_hi=lo_hi, lo_lo=lo_lo, hi_hi=hi_hi, hi_lo=hi_lo,
                bad_ver=act & (fv != col(C.W_REAR_VER)),
                bad_fence=bad_fence,
                n_slots=leaf_slots.sum(axis=-1),
                bad_slot_rows=(leaf_slots & ~in_f).sum(axis=-1),
                torn_slot_rows=(leaf[:, None] & (sfv != srv)).sum(axis=-1),
                bad_order_rows=(pair_valid & ~asc).sum(axis=-1))


def _local_invariants(pool, next_by_node, P: int, N: int) -> dict:
    """Per-page LOCAL invariant predicates over the whole pool — the
    shared core of the full validator below and the online scrubber's
    per-row fault masks (``_scrub_kernel``).  Every mask is [rows],
    built block by block (``_row_block``); trace-time only.
    """
    import jax.numpy as jnp
    from jax import lax

    rows = N * P
    blocks, B = _blocks(pool, rows)
    base = jnp.arange(blocks.shape[0], dtype=jnp.int32) * B
    m = lax.map(lambda a: _row_block(
        a[0], a[1] + jnp.arange(B, dtype=jnp.int32), next_by_node, P),
        (blocks, base))
    m = {k: v.reshape(-1)[:rows] for k, v in m.items()}

    # B-link continuity per link
    act, lvl, sib = m["act"], m["lvl"], m["sib"]
    srow, s_in_range = _rows_of(sib, P, N)
    has_sib = act & (sib != 0)
    m["bad_sib"] = has_sib & (
        ~s_in_range | ~act[srow] | (lvl[srow] != lvl)
        | (m["lo_hi"][srow] != m["hi_hi"])
        | (m["lo_lo"][srow] != m["hi_lo"]))
    m.update(rows=rows, srow=srow, has_sib=has_sib)
    return m


@functools.partial(jax.jit, static_argnames=("P", "N"))
def _validate_kernel(pool, next_by_node, freed, P: int, N: int):
    import jax.numpy as jnp
    from jax import lax

    m = _local_invariants(pool, next_by_node, P, N)
    rows = m["rows"]
    act, retired = m["act"], m["retired"]
    leaf, internal, lvl = m["leaf"], m["internal"], m["lvl"]
    lo_hi, lo_lo = m["lo_hi"], m["lo_lo"]
    hi_hi, hi_lo = m["hi_hi"], m["hi_lo"]
    bad_ver, bad_fence, bad_sib = m["bad_ver"], m["bad_fence"], m["bad_sib"]
    srow, has_sib, sib = m["srow"], m["has_sib"], m["sib"]

    # -- 5. leaf-chain shape via in-degrees ----------------------------------
    link_src = leaf & has_sib
    indeg = jnp.zeros(rows, jnp.int32).at[
        jnp.where(link_src, srow, rows)].add(1, mode="drop")
    heads = leaf & (indeg == 0)
    bad_head = heads & ~((lo_hi == 0) & (lo_lo == 0))
    tails = leaf & (sib == 0)
    inf_hi, inf_lo = bits.key_to_pair(C.KEY_POS_INF)
    bad_tail = tails & ~((hi_hi == inf_hi) & (hi_lo == inf_lo))
    multi_in = leaf & (indeg > 1)

    # -- 6. parent/child coherence -------------------------------------------
    IC = C.INTERNAL_CAP
    lm = m["lm"]
    lmrow, lm_ok = _rows_of(lm, P, N)
    # a PARKED page — retired (zero high fence) but still this parent's
    # leftmost child — is legal: reclaim cannot drop a leftmost pointer
    # (batched.py _remove_parent_entries), so the page stays retired
    # forever and descents through it self-heal via its back-sibling.
    # Level and lowest must still match; only the liveness clause is
    # relaxed.  A page in the allocator FREE POOL is excluded from the
    # accepted retired set: its stale contents still look retired with
    # the old level/lowest until reuse rewrites them, so without the
    # mask a dangling parent entry to a freed page — the exact
    # corruption quarantine exists to prevent — would pass until reuse.
    ref_ok = retired & ~freed
    live_or_parked = act | ref_ok
    bad_lm = internal & (
        (lm == 0) | ~lm_ok | ~live_or_parked[lmrow]
        | (lvl[lmrow] != lvl - 1)
        | (lo_hi[lmrow] != lo_hi) | (lo_lo[lmrow] != lo_lo))

    # a RETIRED child with matching level+lowest is in-flight reclaim
    # state (unlinked, parent-entry removal pending retry — the
    # pending_parent set; a restored cluster's reclaim sweeps it), not
    # corruption.  A freed-and-REUSED page cannot hide here: reuse
    # rewrites the fences, so the lowest-key clause flags the entry —
    # and a freed-NOT-YET-reused page is caught by the freed mask
    # (ref_ok above), closing the window between free and reuse.
    blocks, B = _blocks(pool, rows)
    pos = jnp.arange(IC, dtype=jnp.int32)

    def child_block(a):
        pg, internal_b, lvl_b, nk_b = a
        crow, c_ok = _rows_of(pg[:, C.I_PTR_W:C.I_PTR_W + IC], P, N)
        e_valid = internal_b[:, None] & (pos[None, :] < nk_b[:, None])
        return (e_valid & (
            ~c_ok | ~live_or_parked[crow]
            | (lvl[crow] != (lvl_b - 1)[:, None])
            | (lo_hi[crow] != pg[:, C.I_KHI_W:C.I_KHI_W + IC])
            | (lo_lo[crow] != pg[:, C.I_KLO_W:C.I_KLO_W + IC]))).sum()

    per_block = lambda x: jnp.pad(
        x, (0, blocks.shape[0] * B - rows)).reshape(-1, B)
    bad_child = lax.map(child_block, (blocks, per_block(internal),
                                      per_block(lvl),
                                      per_block(m["nk"]))).sum()

    # int32 counts are ample (< 2^31 pages/keys per cluster by
    # construction; jax x64 is disabled anyway)
    return jnp.stack([
        m["n_slots"].sum().astype(jnp.int32),
        leaf.sum(), internal.sum(), retired.sum(), bad_ver.sum(),
        bad_fence.sum(), m["bad_slot_rows"].sum().astype(jnp.int32),
        m["torn_slot_rows"].sum().astype(jnp.int32),
        m["bad_order_rows"].sum().astype(jnp.int32),
        bad_sib.sum(), heads.sum(), bad_head.sum(),
        tails.sum(), bad_tail.sum(), multi_in.sum(), bad_lm.sum(),
        bad_child.astype(jnp.int32)])


# ---------------------------------------------------------------------------
# Online scrubbing: the per-page fault-mask view of the local invariants.
# ---------------------------------------------------------------------------

# violation classes, one bit each, in the per-page mask _scrub_kernel
# emits.  STRUCTURAL classes mean the page cannot be trusted as a unit
# (the scrubber degrades the engine); entry-level classes (torn /
# out-of-fence slots) are contained by quarantining the page.
SCRUB_BITS = {
    "bad_version": 1,
    "bad_fence": 2,
    "bad_leaf_slot": 4,
    "torn_slot": 8,
    "bad_internal_order": 16,
    "bad_sibling": 32,
}
SCRUB_STRUCTURAL = (SCRUB_BITS["bad_version"] | SCRUB_BITS["bad_fence"]
                    | SCRUB_BITS["bad_internal_order"]
                    | SCRUB_BITS["bad_sibling"])


@functools.partial(jax.jit, static_argnames=("P", "N"))
def _scrub_kernel(pool, next_by_node, P: int, N: int):
    """Per-page violation bitmask over the live pool — the SAME local
    predicates as the full validator (``_local_invariants``), reduced
    per row instead of globally, so the scrubber can QUARANTINE the
    specific violating pages.  One jitted step at any scale."""
    import jax.numpy as jnp

    m = _local_invariants(pool, next_by_node, P, N)
    z = jnp.int32(0)
    mask = (
        jnp.where(m["bad_ver"], jnp.int32(SCRUB_BITS["bad_version"]), z)
        | jnp.where(m["bad_fence"], jnp.int32(SCRUB_BITS["bad_fence"]), z)
        | jnp.where(m["bad_slot_rows"] > 0,
                    jnp.int32(SCRUB_BITS["bad_leaf_slot"]), z)
        | jnp.where(m["torn_slot_rows"] > 0,
                    jnp.int32(SCRUB_BITS["torn_slot"]), z)
        | jnp.where(m["bad_order_rows"] > 0,
                    jnp.int32(SCRUB_BITS["bad_internal_order"]), z)
        | jnp.where(m["bad_sib"], jnp.int32(SCRUB_BITS["bad_sibling"]), z))
    return mask, m["act"].sum()


def scrub_pass(tree) -> dict:
    """One online-scrub pass over the live pool: -> {"pages_checked",
    "violations", "bad": [(addr, mask), ...], "classes": {name: pages}}.
    Collective in multihost deployments (the jit partitions the sharded
    pool; every process calls together and computes the same result)."""
    import jax.numpy as jnp

    cfg = tree.dsm.cfg
    P = cfg.pages_per_node
    nxt = np.ones(cfg.machine_nr, np.int64)
    for d in tree.cluster.directories:
        nxt[d.node_id] = d.allocator.pages_used
    mask, checked = _scrub_kernel(tree.dsm.pool,
                                  jnp.asarray(nxt, jnp.int32),
                                  P=P, N=cfg.machine_nr)
    if tree.dsm.multihost:
        from jax.experimental import multihost_utils as mhu
        shards = sorted(mask.addressable_shards,
                        key=lambda s: s.index[0].start or 0)
        local = np.concatenate([np.asarray(s.data) for s in shards])
        mask = np.asarray(mhu.process_allgather(local, tiled=True))
        checked = int(np.asarray(checked))
    else:
        mask = np.asarray(mask)
        checked = int(checked)
    rows = np.nonzero(mask)[0]
    bad = [(bits.make_addr(int(r) // P, int(r) % P), int(mask[r]))
           for r in rows]
    classes = {name: int(sum(1 for _, mk in bad if mk & bit))
               for name, bit in SCRUB_BITS.items()}
    return {"pages_checked": checked, "violations": len(bad),
            "bad": bad, "classes": classes}


@functools.partial(jax.jit, static_argnames=("P", "N"))
def _leaf_scan_kernel(pool, next_by_node, P: int, N: int):
    import jax.numpy as jnp

    ridx = jnp.arange(N * P, dtype=jnp.int32)
    pg_i = ridx % P
    allocated = (pg_i >= 1) & (pg_i < next_by_node[ridx // P])
    fv = pool[:, C.W_FRONT_VER]
    hi_hi, hi_lo = pool[:, C.W_HIGH_HI], pool[:, C.W_HIGH_LO]
    act = allocated & (fv != 0) & ~((hi_hi == 0) & (hi_lo == 0))
    leaf = act & (pool[:, C.W_LEVEL] == 0)
    return leaf, pool[:, C.W_LOW_HI], pool[:, C.W_LOW_LO]


def leaf_directory(tree) -> tuple[np.ndarray, np.ndarray]:
    """Enumerate every live leaf in ONE device step: -> (addrs int64,
    lows uint64), sorted by key — the exact shape of the bulk-load leaf
    directory (``tree._bulk_leaf_dir``), computed for ANY tree.

    This is what makes a RESTORED (or host-built) tree's router warm
    from step one: without it, ``attach_router`` on a tree that never
    bulk-loaded starts cold at the root with a table sized for nothing,
    and the first steps funnel the whole batch through the straggler
    loop.  Collective in multihost deployments (every process calls;
    the assembled directory is identical everywhere).
    """
    cfg = tree.dsm.cfg
    nxt = np.ones(cfg.machine_nr, np.int64)
    for d in tree.cluster.directories:
        nxt[d.node_id] = d.allocator.pages_used
    import jax.numpy as jnp
    out = _leaf_scan_kernel(tree.dsm.pool, jnp.asarray(nxt, jnp.int32),
                            P=cfg.pages_per_node, N=cfg.machine_nr)
    if tree.dsm.multihost:
        from jax.experimental import multihost_utils as mhu
        blocks = []
        for x in out:
            shards = sorted(x.addressable_shards,
                            key=lambda s: s.index[0].start or 0)
            blocks.append(np.concatenate([np.asarray(s.data)
                                          for s in shards]))
        leaf, lh, ll = (np.asarray(g) for g in
                        mhu.process_allgather(tuple(blocks), tiled=True))
    else:
        leaf, lh, ll = (np.asarray(x) for x in out)
    rows = np.nonzero(leaf)[0]
    P = cfg.pages_per_node
    addrs = ((rows // P).astype(np.int64) << C.ADDR_PAGE_BITS) | (rows % P)
    lows = bits.pairs_to_keys(lh[rows], ll[rows])
    order = np.argsort(lows)
    return addrs[order], lows[order]


@functools.partial(jax.jit, static_argnames=("P", "N"))
def _leaf_chain_kernel(pool, next_by_node, P: int, N: int):
    import jax.numpy as jnp

    ridx = jnp.arange(N * P, dtype=jnp.int32)
    pg_i = ridx % P
    allocated = (pg_i >= 1) & (pg_i < next_by_node[ridx // P])
    fv = pool[:, C.W_FRONT_VER]
    hi_hi, hi_lo = pool[:, C.W_HIGH_HI], pool[:, C.W_HIGH_LO]
    retired = allocated & (fv != 0) & (hi_hi == 0) & (hi_lo == 0)
    act = allocated & (fv != 0) & ~retired
    leaf = act & (pool[:, C.W_LEVEL] == 0)
    n_live = jnp.sum(layout.leaf_slot_used(pool), axis=-1)
    return (leaf, pool[:, C.W_LOW_HI], pool[:, C.W_LOW_LO], hi_hi, hi_lo,
            pool[:, C.W_SIBLING], n_live.astype(jnp.int32),
            retired & (pool[:, C.W_LEVEL] == 0))


def leaf_chain_info(tree):
    """One jitted scan over the pool: every ACTIVE leaf's (addr, low,
    high, sibling, n_live), sorted by low, plus the RETIRED leaves'
    (addr, low) — the reclaim scanner's view of the B-link chain.  On
    process-spanning meshes the scan is a COLLECTIVE (every process
    calls it; the global view is allgathered so each computes the same
    reclaim plan).  Retired = unlinked by a previous reclaim
    (highest == 0) but not yet released; surfacing them lets a restored
    cluster's reclaim pass recover pages that were mid-quarantine at
    checkpoint time."""
    import jax.numpy as jnp

    cfg = tree.dsm.cfg
    nxt = np.ones(cfg.machine_nr, np.int64)
    for d in tree.cluster.directories:
        nxt[d.node_id] = d.allocator.pages_used
    out = _leaf_chain_kernel(
        tree.dsm.pool, jnp.asarray(nxt, jnp.int32),
        P=cfg.pages_per_node, N=cfg.machine_nr)
    if tree.dsm.multihost:
        # process-spanning pool: materialize local shards, allgather the
        # global view (every process computes the identical reclaim plan
        # from it — the replicated-collective contract)
        from jax.experimental import multihost_utils as mhu
        blocks = []
        for x in out:
            shards = sorted(x.addressable_shards,
                            key=lambda s: s.index[0].start or 0)
            blocks.append(np.concatenate([np.asarray(s.data)
                                          for s in shards]))
        leaf, lh, ll, hh, hl, sib, nl, ret = (
            np.asarray(g) for g in
            mhu.process_allgather(tuple(blocks), tiled=True))
    else:
        leaf, lh, ll, hh, hl, sib, nl, ret = (np.asarray(x) for x in out)
    rows = np.nonzero(leaf)[0]
    P = cfg.pages_per_node
    addrs = ((rows // P).astype(np.int64) << C.ADDR_PAGE_BITS) | (rows % P)
    lows = bits.pairs_to_keys(lh[rows], ll[rows])
    highs = bits.pairs_to_keys(hh[rows], hl[rows])
    order = np.argsort(lows)
    rrows = np.nonzero(ret)[0]
    raddrs = ((rrows // P).astype(np.int64) << C.ADDR_PAGE_BITS) \
        | (rrows % P)
    rlows = bits.pairs_to_keys(lh[rrows], ll[rrows])
    return (addrs[order], lows[order], highs[order],
            sib[rows][order].astype(np.int64) & 0xFFFFFFFF,
            nl[rows][order], raddrs, rlows)


def check_structure_device(tree) -> dict:
    """Validate the whole tree on device.  -> stats dict (keys, leaves,
    internal_pages, levels, retired); raises RuntimeError listing every
    violated invariant.  Collective in multihost deployments (every
    process calls; the jit partitions the sharded pool)."""
    import jax.numpy as jnp

    tree._refresh_root()
    cfg = tree.dsm.cfg
    P = cfg.pages_per_node
    nxt = np.ones(cfg.machine_nr, np.int64)
    # pages in the allocator free pools: retired pages a parent entry
    # must NOT reference anymore (see the ref_ok comment in the kernel).
    # Directories are mirrored in every process (replicated-driver
    # model), so the mask is globally consistent on multihost meshes.
    freed = np.zeros(cfg.machine_nr * P, bool)
    for d in tree.cluster.directories:
        nxt[d.node_id] = d.allocator.pages_used
        fp = d.allocator.free_pages_list
        if fp:
            freed[d.node_id * P + np.asarray(fp, np.int64)] = True
    out = np.asarray(_validate_kernel(
        tree.dsm.pool, jnp.asarray(nxt, jnp.int32), jnp.asarray(freed),
        P=P, N=cfg.machine_nr))
    s = dict(zip(_STATS, out.tolist()))
    problems = [f"{k}={s[k]}" for k in (
        "bad_version", "bad_fence", "bad_leaf_slot", "bad_torn_slot",
        "bad_internal_order", "bad_sibling", "bad_head", "bad_tail",
        "multi_indegree", "bad_leftmost", "bad_child") if s[k]]
    if s["heads"] != 1:
        problems.append(f"heads={s['heads']} (want exactly 1)")
    if s["tails"] != 1:
        problems.append(f"tails={s['tails']} (want exactly 1)")
    if problems:
        raise TreeCorruptError("tree structure invalid: " + ", ".join(problems))
    return {"keys": s["keys"], "leaves": s["leaves"],
            "internal_pages": s["internal_pages"],
            "levels": tree._root_level + 1, "retired": s["retired"]}
