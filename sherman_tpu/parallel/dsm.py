"""DSM — the one-sided remote-memory runtime, TPU-native.

This is the analogue of the reference's ``DSM`` class (``include/DSM.h``,
``src/DSM.cpp``): a cluster-wide word/page-addressable memory pool with
one-sided READ / WRITE / CAS / FAA, plus the separate small lock-word space
standing in for NIC on-chip device memory (the ``_dm`` op variants,
``DSM.cpp:395-523``).

Design (TPU-first, not a port):

- The pool is one global jax array ``[machine_nr * pages_per_node, 256]``
  int32, sharded over the 1-D ``node`` mesh axis — each chip's HBM shard is
  that node's DSM partition (reference: hugepage pool per node, DSM.cpp:40).
- One *step* executes a whole batch of requests from every node as one SPMD
  program: bucket-route requests by owner (``transport.py``), owners apply
  them to their local shard, replies route back.  A step is the unit of
  visibility: reads snapshot the pre-step pool; conflicting atomics within a
  step are linearized deterministically (CAS: at most one winner per word per
  step; FAA: serial prefix semantics).  Cross-step concurrency is governed by
  the same lock/version protocol as the reference.
- Async latency hiding (coroutines yielding per verb, reference
  ``Tree.cpp:1059-1122``; doorbell batching, ``Operation.cpp:351-481``) is
  subsumed by batching: dependent op pairs (write+unlock, cas+read) are
  simply issued in consecutive steps or fused into one step where ordering
  permits (writes in a step become visible together, which IS the
  write+unlock coalescing guarantee).

Apply-order within a step: READ (snapshot) < CAS < FAA < WRITE_WORD < WRITE.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from sherman_tpu import config as CFG
from sherman_tpu import obs
from sherman_tpu.config import DSMConfig, PAGE_WORDS
from sherman_tpu.errors import ConfigError, ProtocolError
from sherman_tpu.ops import bits
from sherman_tpu.parallel import transport
from sherman_tpu.parallel.mesh import AXIS, make_mesh, node_sharding

# Request opcodes (cf. verb set in Rdma.h:89-143).
OP_NOP = 0
OP_READ = 1        # read one page; reply in data[:, :256]
OP_WRITE = 2       # write nw words starting at woff of page addr (payload)
OP_WRITE_WORD = 3  # write single word arg1 at (addr, woff) / lock word
OP_CAS = 4         # compare-and-swap word: expected=arg0, desired=arg1
OP_FAA = 5         # fetch-and-add word: delta=arg0
OP_READ_WORD = 6   # read single word; reply in old
OP_MASKED_CAS = 7  # CAS under bitmask arg2 (ibv_exp masked CAS,
                   #   Operation.cpp:253-283): compare/swap only mask bits
OP_MASKED_FAA = 8  # fetch-add within the field arg2 (boundary FAA,
                   #   Operation.cpp:316-348): delta=arg0 pre-shifted to the
                   #   field; carries never leave the field.  One winner per
                   #   word per step (losers retry with ok=0)

# Address spaces: pool pages vs the lock table ("on-chip device memory",
# reference DirectoryConnection.cpp:24-30, DSM::fill_keys_dest DSM.cpp:169).
SPACE_POOL = 0
SPACE_LOCK = 1

REQ_FIELDS = ("op", "addr", "woff", "nw", "space", "arg0", "arg1",
              "arg2")

# Counter slots (reference op counters, DSM.cpp:17-21).
CNT_READ_OPS = 0
CNT_READ_PAGES = 1
CNT_WRITE_OPS = 2
CNT_WRITE_WORDS = 3
CNT_CAS_OPS = 4
CNT_FAA_OPS = 5
CNT_WW_OPS = 6
# Write-combining accounting (PR 17): fed by the leaf-apply kernels when
# config.write_combine() is on — per-batch page-group head count and the
# lock consults the HOCL-style handover saved (rows that rode a group
# head's verdict instead of gathering their own lock word).  Device-side
# slots so the hot path never syncs; the ``combine.*`` obs collector
# materializes them at PULL time like every other collector.
CNT_COMBINE_GROUPS = 7
CNT_COMBINE_SAVED = 8
# Routed read exchange (multi-node only, fed by the routed descent):
# rows whose page lives on another node, and round-1 rows whose
# destination bucket was full (answered by the straggler loop instead).
CNT_XCHG_REMOTE = 9
CNT_XCHG_OVERFLOW = 10
N_COUNTERS = 11

# Host-side step counter (device op counts ride the sharded counters
# array and surface via the registry's "dsm" collector; this one counts
# host-API step LAUNCHES — the control-plane round-trip rate).
_OBS_HOST_STEPS = obs.counter("dsm.host_steps")


def empty_requests(n: int) -> dict[str, np.ndarray]:
    """Host-side all-NOP request batch of n slots."""
    reqs = {f: np.zeros(n, np.int32) for f in REQ_FIELDS}
    reqs["payload"] = np.zeros((n, PAGE_WORDS), np.int32)
    return reqs


# ---------------------------------------------------------------------------
# Owner-side apply (runs on each node's local shard).
# ---------------------------------------------------------------------------

def _word_apply(flat, m_cas, m_faa, m_ww, m_rw, widx, arg0, arg1,
                m_mcas=None, m_mfaa=None, arg2=None):
    """Linearized word ops on a flat word array.

    Returns (new_flat, old[M], ok[M]) where old is: pre-step value for
    CAS/READ_WORD/masked ops; serial pre-value for FAA.  ok is the winner
    flag for CAS-like ops (True for everything else).

    Masked ops fold into the CAS machinery by rewriting expected/desired
    against the pre-step value: masked CAS compares and swaps only the
    ``arg2`` bits; masked FAA always matches and adds ``arg0`` inside the
    ``arg2`` field, dropping carries that leave it — at most one masked
    FAA per word lands per step (the NIC serializes; here losers retry).
    """
    M = widx.shape[0]
    W = flat.shape[0]
    if m_mcas is None:
        m_mcas = jnp.zeros(M, bool)
    if m_mfaa is None:
        m_mfaa = jnp.zeros(M, bool)
    if arg2 is None:
        arg2 = jnp.zeros(M, jnp.int32)
    prio = jnp.arange(M, dtype=jnp.int32)
    any_word = m_cas | m_faa | m_ww | m_rw | m_mcas | m_mfaa
    gidx = jnp.where(any_word, widx, 0)
    gidx = jnp.clip(gidx, 0, W - 1)
    cur = flat[gidx]

    # CAS-like: at most one winner per word per step — the lowest-priority
    # request whose expected value matches (linearization point = step start).
    m_caslike = m_cas | m_mcas | m_mfaa
    exp_eff = jnp.where(m_mcas, (cur & ~arg2) | (arg0 & arg2),
                        jnp.where(m_mfaa, cur, arg0))
    des_eff = jnp.where(
        m_mcas, (cur & ~arg2) | (arg1 & arg2),
        jnp.where(m_mfaa, (cur & ~arg2) | (((cur & arg2) + arg0) & arg2),
                  arg1))
    eligible = m_caslike & (cur == exp_eff)
    key_w = jnp.where(m_caslike, widx, W)
    perm = jnp.lexsort((prio, ~eligible, key_w))
    sw = key_w[perm]
    head = jnp.concatenate([jnp.ones(1, bool), sw[1:] != sw[:-1]])
    winner_s = head & eligible[perm] & (sw < W)
    winner = jnp.zeros(M, bool).at[perm].set(winner_s)
    flat = flat.at[jnp.where(winner, widx, W)].set(des_eff, mode="drop")

    # FAA: all succeed; each sees the serial prefix value (post-CAS state).
    cur2 = flat[gidx]
    key_f = jnp.where(m_faa, widx, W)
    permf = jnp.lexsort((prio, key_f))
    sf = key_f[permf]
    d = jnp.where(m_faa, arg0, 0)[permf]
    csum = jnp.cumsum(d)
    excl = csum - d
    startsf = jnp.searchsorted(sf, sf, side="left")
    in_seg_excl = excl - excl[startsf]
    old_faa_s = cur2[permf] + in_seg_excl
    old_faa = jnp.zeros(M, flat.dtype).at[permf].set(old_faa_s)
    flat = flat.at[jnp.where(m_faa, widx, W)].add(arg0, mode="drop")

    # WRITE_WORD: plain store, wins over same-step CAS/FAA results.
    flat = flat.at[jnp.where(m_ww, widx, W)].set(arg1, mode="drop")

    old = jnp.where(m_faa, old_faa, cur)
    ok = jnp.where(m_caslike, winner, True)
    return flat, old, ok


def _apply(pool, locks, counters, req):
    """Apply incoming requests [M] to this node's shard."""
    P, PW = pool.shape
    page = bits.addr_page(req["addr"])
    op = req["op"]
    m_pool = req["space"] == SPACE_POOL
    m_lock = req["space"] == SPACE_LOCK

    # In-shard bounds checks: the page field must index a real pool page (or
    # a real lock word for the lock space), word ops must stay inside their
    # page, and multi-word writes must not spill into the next page.
    # Out-of-range or unroutable (op, space) requests fail with ok=0 rather
    # than silently clamping or corrupting neighbors.
    woff, nw = req["woff"], req["nw"]
    page_ok = jnp.where(m_lock, page < locks.shape[0], page < P) & (page >= 0)
    word_ok = m_lock | ((woff >= 0) & (woff < PW))
    write_ok = (woff >= 0) & (nw >= 0) & (woff + nw <= PW)
    wordspace = m_pool | m_lock

    is_read = (op == OP_READ) & m_pool & page_ok
    m_cas = (op == OP_CAS) & wordspace & page_ok & word_ok
    m_faa = (op == OP_FAA) & wordspace & page_ok & word_ok
    m_ww = (op == OP_WRITE_WORD) & wordspace & page_ok & word_ok
    m_rw = (op == OP_READ_WORD) & wordspace & page_ok & word_ok
    m_mcas = (op == OP_MASKED_CAS) & wordspace & page_ok & word_ok
    m_mfaa = (op == OP_MASKED_FAA) & wordspace & page_ok & word_ok
    is_write = (op == OP_WRITE) & m_pool & page_ok & write_ok

    # READ: snapshot gather of whole pages before any mutation.
    rpage = pool[jnp.clip(page, 0, P - 1)]
    data = jnp.where(is_read[:, None], rpage, 0)

    # Word-granular ops on the pool space...
    flatpool = pool.reshape(-1)
    widx_pool = page * PW + woff
    flatpool, old_p, ok_p = _word_apply(
        flatpool, m_cas & m_pool, m_faa & m_pool, m_ww & m_pool, m_rw & m_pool,
        widx_pool, req["arg0"], req["arg1"],
        m_mcas & m_pool, m_mfaa & m_pool, req["arg2"])
    # ...and on the lock space (lock index rides the addr page field).
    locks, old_l, ok_l = _word_apply(
        locks, m_cas & m_lock, m_faa & m_lock, m_ww & m_lock, m_rw & m_lock,
        page, req["arg0"], req["arg1"],
        m_mcas & m_lock, m_mfaa & m_lock, req["arg2"])

    # Page WRITE: word-masked scatter (single-entry write-back support —
    # the reference's write-amplification optimization, Tree.cpp:914-921).
    cols = jnp.arange(PW, dtype=jnp.int32)
    idx = widx_pool[:, None] + cols[None, :]
    wmask = is_write[:, None] & (cols[None, :] < nw[:, None])
    idx = jnp.where(wmask, idx, P * PW)
    flatpool = flatpool.at[idx.reshape(-1)].set(
        req["payload"].reshape(-1), mode="drop")
    pool = flatpool.reshape(P, PW)

    handled = (is_read | is_write | m_cas | m_faa | m_ww | m_rw
               | m_mcas | m_mfaa)
    old = jnp.where(m_lock, old_l, old_p)
    ok = jnp.where(m_lock, ok_l, ok_p) & handled

    u32 = lambda m: jnp.sum(m.astype(jnp.uint32))
    counters = counters.at[CNT_READ_OPS].add(u32(is_read))
    counters = counters.at[CNT_READ_PAGES].add(u32(is_read))
    counters = counters.at[CNT_WRITE_OPS].add(u32(is_write))
    counters = counters.at[CNT_WRITE_WORDS].add(
        jnp.sum(jnp.where(is_write, req["nw"], 0)).astype(jnp.uint32))
    counters = counters.at[CNT_CAS_OPS].add(u32(m_cas | m_mcas))
    counters = counters.at[CNT_FAA_OPS].add(u32(m_faa | m_mfaa))
    counters = counters.at[CNT_WW_OPS].add(u32(m_ww))
    return pool, locks, counters, data, old, ok


# ---------------------------------------------------------------------------
# The SPMD step (composable inside shard_map).
# ---------------------------------------------------------------------------

def dsm_step_spmd(pool, locks, counters, reqs, *, cfg: DSMConfig,
                  axis_name: str = AXIS):
    """One DSM step on per-node shards; call inside shard_map.

    reqs: dict of [R] arrays (+ payload [R, 256]).
    Returns (pool, locks, counters, replies) with replies =
    {"data": [R,256], "old": [R], "ok": [R] bool}.
    """
    N, C = cfg.machine_nr, cfg.step_capacity
    xch = functools.partial(transport.exchange, axis_name=axis_name,
                            impl=cfg.exchange_impl)
    active = reqs["op"] != OP_NOP
    dest = bits.addr_node(reqs["addr"])
    bucket_idx, routed = transport.bucketize(dest, active, N, C)

    out = {k: transport.scatter_to_buckets(v, bucket_idx, N * C)
           for k, v in reqs.items()}
    inc = xch(out)

    pool, locks, counters, data, old, ok = _apply(pool, locks, counters, inc)

    rep = xch({"data": data, "old": old, "ok": ok})
    safe_b = jnp.where(routed, bucket_idx, 0)
    replies = {
        "data": jnp.where((active & routed)[:, None], rep["data"][safe_b], 0),
        "old": jnp.where(active & routed, rep["old"][safe_b], 0),
        "ok": jnp.where(active, routed & rep["ok"][safe_b], True),
    }
    return pool, locks, counters, replies


def spread_capacity(rows: int, n_nodes: int, cap: int) -> int:
    """Rows per destination bucket of a read exchange whose ``rows``
    spread evenly over ``n_nodes``: 2 % over an even share, rounded up
    to 8,192, plus 16,384 rows (bench.py's headroom rule, as the
    benchmark sizes the unique-row cap), at most ``rows`` and ``cap``."""
    share = -(-int(rows / n_nodes * 1.02) // 8192) * 8192 + 16_384
    return min(rows, cap, share)


def read_pages_spmd(pool, addrs, *, cfg: DSMConfig, axis_name: str = AXIS,
                    active=None, spread: bool = False):
    """Lightweight read-only exchange: fetch pages for a batch of addrs.

    The hot-loop primitive for batched tree descent — avoids shipping write
    payloads: requests are 1 word each; only replies carry pages.
    Returns (pages [R, 256], ok [R]).

    Multi-node, each destination bucket holds ``cfg.step_capacity`` rows,
    or with ``spread`` (the rows' pages lie spread over the nodes, as
    leaf seeds do) :func:`spread_capacity` of the call's own R rows, so
    the owner's gather and the reply are ~R pages, not N x capacity.  A
    row whose bucket is full comes back not-ok, for the caller to retry.

    ``cfg.gather_impl`` selects the page-fetch engine: "xla" (default)
    is the native gather; "pallas" routes the owner-side page reads
    through the explicit-DMA snapshot kernel
    (:mod:`sherman_tpu.ops.pallas_page`) — bit-identical results, same
    op accounting (counters are per ROW, not per impl).
    """
    from sherman_tpu.ops import pallas_page
    N, C = cfg.machine_nr, cfg.step_capacity
    P = pool.shape[0]
    if active is None:
        active = jnp.ones(addrs.shape, bool)
    if N == 1:
        if pallas_page.use_pallas(cfg):
            return pallas_page.read_pages_local(pool, addrs, active)
        # Single-node fast path: no routing, direct local gather.
        page = bits.addr_page(addrs)
        ok = active & (page >= 0) & (page < P)
        pages = pool[jnp.clip(page, 0, P - 1)]
        return jnp.where(ok[:, None], pages, 0), ok
    if spread:
        C = spread_capacity(addrs.shape[0], N, C)
    xch = functools.partial(transport.exchange, axis_name=axis_name,
                            impl=cfg.exchange_impl)
    with jax.named_scope("exchange"):
        dest = bits.addr_node(addrs)
        bucket_idx, routed = transport.bucketize(dest, active, N, C)
        out = transport.scatter_to_buckets(bits.addr_page(addrs),
                                           bucket_idx, N * C)
        inc = xch(out)
    with jax.named_scope("owner_gather"):
        if pallas_page.use_pallas(cfg):
            data = pallas_page.gather_pages(pool, inc)
        else:
            data = pool[jnp.clip(inc, 0, P - 1)]
    with jax.named_scope("exchange"):
        rep = xch({"data": data, "okb": (inc >= 0) & (inc < P)})
        safe_b = jnp.where(routed, bucket_idx, 0)
        served = active & routed & rep["okb"][safe_b]
        pages = jnp.where(served[:, None], rep["data"][safe_b], 0)
    return pages, served


# ---------------------------------------------------------------------------
# Host-facing runtime.
# ---------------------------------------------------------------------------

@dataclass
class Replies:
    data: np.ndarray
    old: np.ndarray
    ok: np.ndarray


class _HostOps:
    """Host convenience API over :meth:`_batch` (one small step per call).

    Shared by :class:`DSM` (single-process / raw per-process multihost
    mode) and :class:`ReplicatedDSM` (replicated-driver multihost mode);
    subclasses provide ``_batch``.
    """

    def _batch(self, rows: list[dict]) -> Replies:  # pragma: no cover
        raise NotImplementedError

    @staticmethod
    def _require_ok(ok, what: str) -> None:
        """Host-API ops must not fail silently: a refused row (bad
        address, routing overflow) indicates a protocol bug or an
        undersized step, and a bare assert would be stripped under
        python -O — masking lost writes as success."""
        if not bool(np.all(ok)):
            raise ProtocolError(f"host DSM op failed: {what}")

    def read_page(self, addr: int) -> np.ndarray:
        r = self._batch([{"op": OP_READ, "addr": addr}])
        self._require_ok(r.ok[0], "read_page (bad address?)")
        return r.data[0]

    def read_pages(self, addrs) -> np.ndarray:
        rows = [{"op": OP_READ, "addr": int(a)} for a in addrs]
        r = self._batch(rows)
        self._require_ok(r.ok, "read_pages overflow: raise step_capacity")
        return r.data

    def write_page(self, addr: int, words: np.ndarray):
        r = self._batch([{"op": OP_WRITE, "addr": addr, "woff": 0,
                          "nw": PAGE_WORDS, "payload": words}])
        self._require_ok(r.ok[0], "write_page (bad address?)")

    def write_words(self, addr: int, woff: int, words: np.ndarray):
        words = np.asarray(words, np.int32)
        r = self._batch([{"op": OP_WRITE, "addr": addr, "woff": woff,
                          "nw": words.shape[0], "payload": words}])
        self._require_ok(r.ok[0], "write_words (bad address/range?)")

    def write_rows(self, rows: list[dict]):
        """Batched writes in ONE step — the write_batch/doorbell analogue
        (Operation.cpp:351-380): all writes in a step become visible
        atomically at the step boundary."""
        r = self._batch(rows)
        self._require_ok(r.ok, "write_rows (bad address or overflow)")

    def cas(self, addr: int, woff: int, expected: int, desired: int,
            space: int = SPACE_POOL) -> tuple[int, bool]:
        r = self._batch([{"op": OP_CAS, "addr": addr, "woff": woff,
                          "arg0": expected, "arg1": desired, "space": space}])
        return int(r.old[0]), bool(r.ok[0])

    def faa(self, addr: int, woff: int, delta: int,
            space: int = SPACE_POOL) -> int:
        r = self._batch([{"op": OP_FAA, "addr": addr, "woff": woff,
                          "arg0": delta, "space": space}])
        self._require_ok(r.ok[0], "faa (bad address?)")
        return int(r.old[0])

    def read_word(self, addr: int, woff: int, space: int = SPACE_POOL) -> int:
        r = self._batch([{"op": OP_READ_WORD, "addr": addr, "woff": woff,
                          "space": space}])
        self._require_ok(r.ok[0], "read_word (bad address?)")
        return int(r.old[0])

    def write_word(self, addr: int, woff: int, value: int,
                   space: int = SPACE_POOL):
        r = self._batch([{"op": OP_WRITE_WORD, "addr": addr, "woff": woff,
                          "arg1": value, "space": space}])
        self._require_ok(r.ok[0], "write_word (bad address?)")

    def masked_cas(self, addr: int, woff: int, expected: int, desired: int,
                   mask: int, space: int = SPACE_POOL) -> tuple[int, bool]:
        """CAS only the ``mask`` bits (ibv_exp masked CAS parity,
        Operation.cpp:253-283): other bits are untouched and ignored in
        the comparison.  -> (old_word, won)."""
        r = self._batch([{"op": OP_MASKED_CAS, "addr": addr, "woff": woff,
                          "arg0": expected, "arg1": desired, "arg2": mask,
                          "space": space}])
        return int(r.old[0]), bool(r.ok[0])

    def masked_faa(self, addr: int, woff: int, delta: int, mask: int,
                   space: int = SPACE_POOL) -> tuple[int, bool]:
        """Fetch-and-add within the ``mask`` field (boundary FAA parity,
        Operation.cpp:316-348): ``delta`` must be pre-shifted into the
        field; carries never cross out of it.  One per word lands per
        step; a lost race returns won=False to retry.
        -> (old_word, won)."""
        r = self._batch([{"op": OP_MASKED_FAA, "addr": addr, "woff": woff,
                          "arg0": delta, "arg2": mask, "space": space}])
        return int(r.old[0]), bool(r.ok[0])

    # -- coalesced dependent-op chains (doorbell parity) ----------------------
    # One step = one "doorbell": its ops land atomically at the step
    # boundary, which is the guarantee the reference builds from chained
    # WRs + fences (Operation.cpp:351-481).

    def cas_read(self, cas_addr: int, woff: int, expected: int, desired: int,
                 read_addr: int, cas_space: int = SPACE_LOCK
                 ) -> tuple[int, bool, np.ndarray]:
        """CAS a word and read a page in ONE step (rdmaCasRead,
        Operation.cpp:382-414) — the lock-acquire + page-fetch fusion.

        The read returns the pre-step page snapshot.  That is exactly the
        fenced post-CAS read when the CAS wins a *lock*: the previous
        holder's page write and its unlock land in one earlier step, so
        any snapshot taken at or after the unlock already contains the
        protected write.  -> (old_word, cas_won, page).
        """
        r = self._batch([
            {"op": OP_CAS, "addr": cas_addr, "woff": woff,
             "arg0": expected, "arg1": desired, "space": cas_space},
            {"op": OP_READ, "addr": read_addr},
        ])
        self._require_ok(r.ok[1], "cas_read: bad page address")
        return int(r.old[0]), bool(r.ok[0]), r.data[1]

    def write_cas(self, waddr: int, woff: int, payload: np.ndarray,
                  cas_addr: int, cas_woff: int, expected: int, desired: int,
                  cas_space: int = SPACE_LOCK) -> bool:
        """Write words and CAS a word in ONE step (rdmaWriteCas,
        Operation.cpp:449-481).  The CAS linearizes on the pre-step value;
        both effects land together.  -> cas_won."""
        payload = np.asarray(payload, np.int32)
        r = self._batch([
            {"op": OP_WRITE, "addr": waddr, "woff": woff,
             "nw": payload.shape[0], "payload": payload},
            {"op": OP_CAS, "addr": cas_addr, "woff": cas_woff,
             "arg0": expected, "arg1": desired, "space": cas_space},
        ])
        self._require_ok(r.ok[0], "write_cas: bad write address")
        return bool(r.ok[1])

    def write_faa(self, waddr: int, woff: int, payload: np.ndarray,
                  faa_addr: int, faa_woff: int, delta: int,
                  faa_space: int = SPACE_POOL) -> int:
        """Write words and fetch-and-add a word in ONE step (rdmaWriteFaa,
        Operation.cpp:416-447).  -> the FAA's serial pre-value."""
        payload = np.asarray(payload, np.int32)
        r = self._batch([
            {"op": OP_WRITE, "addr": waddr, "woff": woff,
             "nw": payload.shape[0], "payload": payload},
            {"op": OP_FAA, "addr": faa_addr, "woff": faa_woff,
             "arg0": delta, "space": faa_space},
        ])
        self._require_ok(r.ok[0] and r.ok[1], "write_faa: bad address")
        return int(r.old[1])


class DSM(_HostOps):
    """Host handle to the cluster: owns the sharded pool/locks/counters and a
    jitted step.  The analogue of ``DSM::getInstance`` (DSM.cpp:23-35).

    Single-process SPMD: one Python process drives all nodes (the mesh).
    Multi-host meshes use the same code path via jax.distributed — the mesh
    simply spans processes.
    """

    def __init__(self, cfg: DSMConfig, mesh: jax.sharding.Mesh | None = None):
        self.cfg = cfg
        self.mesh = mesh if mesh is not None else make_mesh(cfg.machine_nr)
        if self.mesh.devices.size != cfg.machine_nr:
            raise ConfigError("mesh size must equal cfg.machine_nr")
        self.shard = node_sharding(self.mesh)
        N, P, L = cfg.machine_nr, cfg.pages_per_node, cfg.locks_per_node

        # Multi-host: the mesh spans processes.  Host-API calls are then
        # COLLECTIVES — every process must issue the same sequence of
        # steps, each contributing requests from its own (contiguous)
        # block of nodes and receiving its own replies (multi-controller
        # SPMD, the jax.distributed execution model).
        me = jax.process_index()
        flat = list(self.mesh.devices.flat)
        self.multihost = any(d.process_index != me for d in flat)
        local_idx = [i for i, d in enumerate(flat) if d.process_index == me]
        assert local_idx, "mesh has no process-local devices"
        lo, hi = local_idx[0], local_idx[-1] + 1
        assert local_idx == list(range(lo, hi)), (
            "process-local devices must be contiguous in the mesh")
        self.local_nodes = range(lo, hi)

        def _zeros(shape, dtype):
            if not self.multihost:
                return jax.device_put(jnp.zeros(shape, dtype), self.shard)
            return jax.make_array_from_callback(
                shape, self.shard,
                lambda idx: np.zeros(self.shard.shard_shape(shape), dtype))

        self.pool = _zeros((N * P, PAGE_WORDS), jnp.int32)
        self.locks = _zeros((N * L,), jnp.int32)
        self._counter_box = {}
        self.counters = _zeros((N * N_COUNTERS,), jnp.uint32)
        # Out-of-line VALUE HEAP — the second DSM region (see
        # DSMConfig.heap_pages_per_node; models/value_heap.py owns the
        # slab/handle protocol on top).  Sharded over nodes like the
        # pool; None when disabled, so a heap-off build carries no
        # extra device state and stays bit-identical to pre-heap
        # builds.  Single-process only for now (like delta checkpoints
        # and the recovery plane — the heap's allocator/journal
        # integration assumes one driver).
        self.heap = None
        self._heap_dirty_host: set[int] = set()
        self._heap_write = None
        if cfg.heap_pages_per_node > 0:
            # multihost allocation rides the same make_array_from_
            # callback path as the pool (PR 19): ownership is row-
            # range-based — each process's allocator hands out slabs
            # from its OWN nodes' heap rows only (global-row handles
            # stay valid everywhere; only allocation is local), so no
            # cross-host allocator coordination exists to get wrong.
            self.heap = _zeros((N * cfg.heap_pages_per_node, PAGE_WORDS),
                               jnp.int32)
        # Dirty-page tracking (the recovery plane's delta-checkpoint
        # feed, utils/checkpoint.checkpoint_delta): pages written since
        # the last checkpoint artifact.  Two tiers, united at save time:
        # - ``dirty``: a pool-sharded device mask the engine's compiled
        #   write programs OR into owner-side (leaf applies, splits,
        #   deletes — their target pages never surface host-side);
        # - ``_dirty_host``: a host set of global pool rows, marked at
        #   the DSM.step boundary from the (host-visible) request batch
        #   — one address-set union per control-plane step — plus
        #   explicit marks for direct installs (bulk_load).
        # Chaos corruption pokes bypass both on purpose: injected damage
        # is not a legal write and must NOT leak into delta artifacts.
        self.dirty = _zeros((N * P,), jnp.bool_)
        self._dirty_host: set[int] = set()
        # Dirty SINKS (the online migrator's feed, sherman_tpu/migrate.py):
        # checkpoint saves consume-and-clear the dirty tracking, which
        # would silently hide post-copy writes from any second consumer.
        # A registered sink is handed the rows about to be cleared, so a
        # concurrent consumer (the migration re-copy queue) never loses
        # dirt to a checkpoint racing its polls.  Empty list = zero cost.
        self._dirty_sinks: list = []

        spec = jax.sharding.PartitionSpec(AXIS)
        in_specs = (spec, spec, spec,
                    {k: spec for k in (*REQ_FIELDS, "payload")})
        out_specs = (spec, spec, spec, {k: spec for k in ("data", "old", "ok")})
        # The host control-plane step uses its own small routing capacity —
        # see DSMConfig.host_step_capacity.
        import dataclasses as _dc
        self._host_cfg = _dc.replace(
            cfg, step_capacity=min(cfg.step_capacity,
                                   cfg.host_step_capacity))
        step = jax.shard_map(
            functools.partial(dsm_step_spmd, cfg=self._host_cfg),
            mesh=self.mesh, in_specs=in_specs, out_specs=out_specs,
            check_vma=False)
        self._step = jax.jit(step, donate_argnums=CFG.donate_argnums(0, 1, 2))
        # Per-step request slots available to the *host* API; device kernels
        # compose dsm_step_spmd directly and have their own batches.
        self.host_slots = len(self.local_nodes) * self._host_cfg.step_capacity
        # Host-API steps mutate self.pool/locks/counters with donated
        # buffers; serialize them so multithreaded clients (the local
        # lock tier's use case) can't interleave inside a step.
        import threading
        self._step_mutex = threading.Lock()

        # Chaos injection hook (sherman_tpu/chaos.py): a FaultPlan fired
        # at the host-step boundary.  None (the default) costs one `is
        # None` test per host step — engine/staged programs are
        # untouched, so receipts with chaos off are bit-identical to a
        # build without the subsystem.  Env-drivable: SHERMAN_CHAOS
        # installs a plan on every DSM at construction.
        import os as _os
        self.chaos = None
        if _os.environ.get("SHERMAN_CHAOS"):
            from sherman_tpu.chaos import FaultPlan
            self.chaos = FaultPlan.from_env()

        # Observability: expose the device op/byte counters as a pull
        # collector on the process-wide registry — snapshots then carry
        # ``dsm.read_ops`` etc. without any per-op host cost (the
        # counters accumulate on device; reading them is the same
        # materialization counter_snapshot always did).  Bound to the
        # counters alone: a dead DSM does not pin its pool, and its last
        # counters (N x N_COUNTERS words) stay readable, so a caller that
        # has let its cluster go still reads the totals of its run.
        box, mh = self._counter_box, self.multihost
        obs.register_collector(
            "dsm", lambda: _counter_totals(box["array"], mh))
        import weakref
        ref = weakref.ref(self)
        # HBM accountant (obs/device.py): the DSM's device-resident
        # arrays ARE the pool-side HBM footprint — register them as
        # weakref-bound byte sources so ``device.hbm_*`` gauges and the
        # peak watermark track the live buffers (a dead DSM reports 0
        # and drops out; the step-donated handles are re-read per
        # snapshot, so rotation through donation is invisible here).
        acct = obs.get_accountant()
        for _src in ("pool", "locks", "counters", "dirty"):
            acct.register(_src, (lambda r=ref, n=_src: (
                getattr(r(), n).nbytes if r() is not None else 0)))
        if self.heap is not None:
            acct.register("heap", (lambda r=ref: (
                r().heap.nbytes
                if r() is not None and r().heap is not None else 0)))

    # -- raw step ------------------------------------------------------------

    def step(self, reqs: dict[str, np.ndarray]) -> Replies:
        """Run one DSM step.

        Single-process: ``reqs`` are global request arrays [N*R]; replies
        cover all slots.  Multi-host: a COLLECTIVE — every process calls
        with its own host-local arrays [len(local_nodes)*R] and receives
        replies for its slots only.

        Thread-safe: one step at a time (the state arrays are donated).
        """
        _OBS_HOST_STEPS.inc()
        self._mark_dirty_from_reqs(reqs)
        with self._step_mutex:
            if self.chaos is None:
                return self._step_locked(reqs)
            # Fault injection at the step boundary (the single chaos
            # hook): due faults corrupt pool/lock words or rewrite this
            # step's requests before it runs; stale_read faults
            # post-process its replies.  Runs under the step mutex, so
            # the corruption + step land as one atomic handle swap.
            reqs0 = reqs
            reqs, post = self.chaos.on_step(self, reqs)
            rep = self._step_locked(reqs)
            return self.chaos.on_replies(self, reqs0, rep) if post else rep

    def _step_locked(self, reqs: dict[str, np.ndarray]) -> Replies:
        if self.multihost:
            from jax.experimental import multihost_utils as mhu
            reqs = {k: mhu.host_local_array_to_global_array(
                        np.asarray(v), self.mesh,
                        jax.sharding.PartitionSpec(AXIS))
                    for k, v in reqs.items()}
        else:
            reqs = {k: jax.device_put(jnp.asarray(v), self.shard)
                    for k, v in reqs.items()}
        self.pool, self.locks, self.counters, rep = self._step(
            self.pool, self.locks, self.counters, reqs)
        if self.multihost:
            from jax.experimental import multihost_utils as mhu
            spec = jax.sharding.PartitionSpec(AXIS)
            rep = {k: mhu.global_array_to_host_local_array(v, self.mesh, spec)
                   for k, v in rep.items()}
        return Replies(data=np.asarray(rep["data"]), old=np.asarray(rep["old"]),
                       ok=np.asarray(rep["ok"]))

    def install_chaos(self, plan) -> None:
        """Install (or clear, with ``None``) a chaos
        :class:`~sherman_tpu.chaos.FaultPlan`; its step indices count
        host steps from the moment of installation."""
        self.chaos = plan

    # -- dirty-page tracking (delta-checkpoint feed) -------------------------

    _POOL_WRITE_OPS = (OP_WRITE, OP_WRITE_WORD, OP_CAS, OP_FAA,
                       OP_MASKED_CAS, OP_MASKED_FAA)

    def local_row_range(self) -> tuple[int, int]:
        """``[lo, hi)`` global pool rows owned by THIS process — the
        row-range ownership basis of the multihost service plane
        (PR 19).  Single-process: the whole pool.  Global-row
        addressing means a reshard never rewrites a handle; ownership
        is just which process's dirty tracking / delta artifacts a row
        lands in."""
        P = self.cfg.pages_per_node
        return (self.local_nodes.start * P, self.local_nodes.stop * P)

    def _mark_dirty_from_reqs(self, reqs) -> None:
        """One address-set union per host step: every pool-space request
        that CAN mutate its page marks that page dirty (CAS losers
        over-mark — a harmless extra delta row, never a missed one).
        Pure numpy (no device trip); out-of-range addresses are the
        requests _apply refuses with ok=0 — skipped here too.
        Multihost: only LOCALLY-OWNED rows are tracked (row-range
        ownership, PR 19) — a remote-node write is the remote process's
        to track, from its own copy of the same collective step."""
        op = np.asarray(reqs["op"]).ravel()
        wr = np.isin(op, self._POOL_WRITE_OPS) \
            & (np.asarray(reqs["space"]).ravel() == SPACE_POOL)
        if not wr.any():
            return
        a = np.asarray(reqs["addr"]).ravel()[wr].astype(np.int64) \
            & 0xFFFFFFFF
        node = a >> CFG.ADDR_PAGE_BITS
        page = a & CFG.ADDR_PAGE_MASK
        ok = (node < self.cfg.machine_nr) & (page < self.cfg.pages_per_node)
        rows = node[ok] * self.cfg.pages_per_node + page[ok]
        if self.multihost:
            lo, hi = self.local_row_range()
            rows = rows[(rows >= lo) & (rows < hi)]
        self._dirty_host.update(int(r) for r in np.unique(rows))

    def mark_dirty_rows(self, rows) -> None:
        """Explicitly mark global pool rows dirty (direct pool installs
        — bulk_load — whose writes bypass the step/request path).
        Multihost: rows outside this process's ownership range are
        dropped (the owner marks them from its own call)."""
        rows = np.asarray(rows, np.int64).ravel()
        if self.multihost:
            lo, hi = self.local_row_range()
            rows = rows[(rows >= lo) & (rows < hi)]
        self._dirty_host.update(int(r) for r in rows)

    def dirty_rows(self) -> np.ndarray:
        """Sorted global pool rows written since the last clear: the
        device mask (engine write programs) united with the host set
        (DSM.step boundary + direct installs).  Multihost: THIS
        process's owned rows only — the device mask is read from the
        addressable shards (collective-free; each shard's mesh
        position gives its global row offset), and the host set was
        ownership-filtered at mark time.  The union of every host's
        return IS the cluster's dirty set, disjoint by construction —
        the per-host delta artifacts the union recovery replays."""
        if self.multihost:
            P = self.cfg.pages_per_node
            parts = [self._dirty_host]
            for s in self.dirty.addressable_shards:
                off = s.index[0].start or 0
                loc = np.nonzero(np.asarray(s.data))[0]
                parts.append(set((loc + off).tolist()))
            allr = set().union(*parts)
            return np.array(sorted(allr), np.int64)
        dev = np.nonzero(np.asarray(self.dirty))[0].astype(np.int64)
        if not self._dirty_host:
            return dev
        host = np.fromiter(self._dirty_host, np.int64,
                           len(self._dirty_host))
        return np.union1d(dev, host)

    def read_rows_local(self, rows, region: str = "pool") -> np.ndarray:
        """Gather pool/heap rows host-side from this process's
        ADDRESSABLE shards only — the collective-free gather the
        per-host delta save needs on a process-spanning mesh (a global
        fancy-index there would be a cross-host collective).  ``rows``
        must lie in :meth:`local_row_range` (scaled to the heap's rows
        for ``region="heap"``); out-of-range rows raise."""
        import jax.numpy as _jnp
        arr = self.heap if region == "heap" else self.pool
        if arr is None:
            raise ConfigError("no value heap configured")
        rows = np.asarray(rows, np.int64).ravel()
        if rows.size == 0:
            return np.zeros((0, arr.shape[1]), np.int32)
        if not self.multihost:
            return np.asarray(arr[_jnp.asarray(rows)])
        out = np.zeros((rows.size, arr.shape[1]), np.int32)
        seen = np.zeros(rows.size, bool)
        for s in arr.addressable_shards:
            off = s.index[0].start or 0
            n = s.data.shape[0]
            sel = (rows >= off) & (rows < off + n)
            if sel.any():
                out[sel] = np.asarray(s.data)[rows[sel] - off]
                seen |= sel
        if not seen.all():
            raise ConfigError(
                f"read_rows_local: {int((~seen).sum())} row(s) outside "
                "this process's addressable shards — gather them on "
                "their owner host")
        return out

    # -- value-heap region (the second DSM region) ---------------------------
    # Word-cell writes + page reads over ``self.heap``.  The slab/handle
    # protocol (size classes, versions, freelists) lives in
    # models/value_heap.py; these are the raw region ops, kept on the
    # DSM so dirty tracking and checkpoints see ONE owner for both
    # regions.  Single-process only (enforced at construction).

    def _require_heap(self) -> None:
        if self.heap is None:
            raise ConfigError(
                "no value heap configured: set "
                "DSMConfig.heap_pages_per_node > 0 (SHERMAN_VALUE_HEAP)")

    def heap_write_cells(self, rows, woffs, vals) -> None:
        """Scatter int32 words into heap pages in ONE device step:
        ``heap[rows[i], woffs[i]] = vals[i]``.  Row/word arrays are
        padded to a power-of-two quantum so the compiled scatter count
        stays bounded (pad cells target row H with ``mode="drop"``).
        Marks the touched heap rows dirty (delta-checkpoint feed)."""
        self._require_heap()
        rows = np.asarray(rows, np.int64)
        woffs = np.asarray(woffs, np.int32)
        vals = np.asarray(vals, np.int32)
        if rows.size == 0:
            return
        H = self.heap.shape[0]
        n = max(256, 1 << int(np.ceil(np.log2(rows.size))))
        pr = np.full(n, H, np.int32)   # out-of-range: dropped
        pw = np.zeros(n, np.int32)
        pv = np.zeros(n, np.int32)
        pr[: rows.size] = rows.astype(np.int32)
        pw[: rows.size] = woffs
        pv[: rows.size] = vals
        with self._step_mutex:
            self.heap = self._heap_write_jit()(
                self.heap, jnp.asarray(pr), jnp.asarray(pw),
                jnp.asarray(pv))
        self._heap_dirty_host.update(int(r) for r in np.unique(rows))

    def _heap_write_jit(self):
        if self._heap_write is None:
            self._heap_write = jax.jit(
                lambda h, r, w, v: h.at[r, w].set(v, mode="drop"),
                donate_argnums=CFG.donate_argnums(0))
        return self._heap_write

    def heap_read_rows(self, rows) -> np.ndarray:
        """Gather heap pages by global heap row (host convenience — the
        reference resolver / scrub path; the hot read path gathers on
        device inside the fused fan-out).  Takes the step mutex: the
        heap handle is DONATED by heap_write_cells, so an unguarded
        read racing a writer thread can hit a deleted buffer."""
        self._require_heap()
        rows = np.asarray(rows, np.int64)
        if rows.size == 0:
            return np.zeros((0, PAGE_WORDS), np.int32)
        with self._step_mutex:
            return np.asarray(self.heap[jnp.asarray(rows)])

    def heap_snapshot(self) -> np.ndarray:
        """Materialize the whole heap region (mutex-guarded handle
        read — see :meth:`heap_read_rows`)."""
        self._require_heap()
        with self._step_mutex:
            return np.asarray(self.heap)

    def mark_heap_dirty_rows(self, rows) -> None:
        """Explicitly mark global heap rows dirty (restore/replay paths
        whose writes bypass heap_write_cells)."""
        self._heap_dirty_host.update(int(r) for r in np.asarray(rows).ravel())

    def heap_dirty_rows(self) -> np.ndarray:
        """Sorted global heap rows written since the last clear."""
        if not self._heap_dirty_host:
            return np.zeros(0, np.int64)
        return np.sort(np.fromiter(self._heap_dirty_host, np.int64,
                                   len(self._heap_dirty_host)))

    def add_dirty_sink(self, fn) -> None:
        """Register a callable handed the dirty rows at every
        :meth:`clear_dirty` (BEFORE the reset) — the second-consumer
        contract for the dirty tracking (see ``_dirty_sinks``).
        Multihost: the sink sees this process's OWNED rows only
        (:meth:`dirty_rows`' row-range contract)."""
        self._dirty_sinks.append(fn)

    def remove_dirty_sink(self, fn) -> None:
        if fn in self._dirty_sinks:
            self._dirty_sinks.remove(fn)

    def clear_dirty(self) -> None:
        """Reset both dirty tiers (a checkpoint artifact captured them).
        Registered dirty sinks see the rows first — a clear must not
        hide writes from a concurrent consumer (migration re-copy)."""
        if self._dirty_sinks:
            rows = self.dirty_rows()
            if rows.size:
                for fn in list(self._dirty_sinks):
                    fn(rows)
        N, P = self.cfg.machine_nr, self.cfg.pages_per_node
        if not self.multihost:
            self.dirty = jax.device_put(jnp.zeros(N * P, jnp.bool_),
                                        self.shard)
        else:
            self.dirty = jax.make_array_from_callback(
                (N * P,), self.shard,
                lambda idx: np.zeros(self.shard.shard_shape((N * P,)),
                                     bool))
        self._dirty_host.clear()
        self._heap_dirty_host.clear()

    # -- host convenience ops (control plane / slow paths / tests) -----------
    # Each builds a small batch and steps once; requests are spread over
    # source nodes round-robin so per-(src,dst) capacity is not the limit.

    def _batch(self, rows: list[dict]) -> Replies:
        # Cap one host step at host_step_capacity TOTAL rows so that no
        # destination bucket can overflow regardless of the rows' targets.
        # Multi-host: rows ride THIS process's node block only (each
        # process contributes its own rows to the collective step).
        cap = self._host_cfg.step_capacity
        n_src = len(self.local_nodes)
        n = n_src * cap
        if len(rows) > cap:
            if self.multihost:
                # Refuse to split silently: each chunk is one COLLECTIVE
                # step, and a data-dependent chunk count would desync the
                # processes' step sequences (a silent cluster deadlock).
                # Callers chunk identically on every host instead.
                raise ConfigError(
                    f"multi-host host-API batch of {len(rows)} rows "
                    f"exceeds host_step_capacity={cap}: chunk the call "
                    "identically on every process (each chunk is one "
                    "collective step)")
            out = [self._batch(rows[i:i + cap])
                   for i in range(0, len(rows), cap)]
            return Replies(
                data=np.concatenate([r.data for r in out]),
                old=np.concatenate([r.old for r in out]),
                ok=np.concatenate([r.ok for r in out]))
        reqs = empty_requests(n)
        R = cap
        slots = []
        # round-robin rows over local source nodes: slot = s*R + idx
        per_src = [0] * n_src
        for i, row in enumerate(rows):
            src = i % n_src
            slot = src * R + per_src[src]
            per_src[src] += 1
            slots.append(slot)
            for k, v in row.items():
                if k == "payload":
                    v = np.asarray(v, np.int32)
                    reqs["payload"][slot, :v.shape[0]] = v
                else:
                    # accept full uint32 bit patterns (e.g. high-bit masks
                    # like 0xFFFF0000): wrap to the int32 representation —
                    # NumPy 2 raises OverflowError on a raw assignment
                    reqs[k][slot] = np.uint32(
                        int(v) & 0xFFFFFFFF).astype(np.int32)
        rep = self.step(reqs)
        sl = np.array(slots, np.int64)
        return Replies(data=rep.data[sl], old=rep.old[sl], ok=rep.ok[sl])

    # -- observability (write_test.cpp:72-76 parity) -------------------------

    @property
    def counters(self):
        """The device op counters, [N * N_COUNTERS] uint32 sharded over
        nodes (engine steps donate and replace them)."""
        return self._counter_box["array"]

    @counters.setter
    def counters(self, value):
        self._counter_box["array"] = value

    def counter_snapshot(self) -> dict[str, int]:
        """Op counters summed over this process's nodes (single-process:
        the whole cluster).  Multi-host drivers aggregate across hosts
        with ``keeper.sum`` — the reference's pattern exactly
        (``dsm->sum``, test/benchmark.cpp:336-346)."""
        return _counter_totals(self.counters, self.multihost)


def _counter_totals(counters, multihost: bool) -> dict[str, int]:
    """:meth:`DSM.counter_snapshot` of a counters array."""
    if multihost:
        c = np.concatenate([np.asarray(s.data)
                            for s in counters.addressable_shards])
    else:
        c = np.asarray(counters)
    c = c.reshape(-1, N_COUNTERS)
    tot = c.sum(axis=0, dtype=np.uint64)
    return {
        "read_ops": int(tot[CNT_READ_OPS]),
        "read_bytes": int(tot[CNT_READ_PAGES]) * CFG.PAGE_BYTES,
        "write_ops": int(tot[CNT_WRITE_OPS]),
        "write_bytes": int(tot[CNT_WRITE_WORDS]) * 4,
        "cas_ops": int(tot[CNT_CAS_OPS]),
        "faa_ops": int(tot[CNT_FAA_OPS]),
        "write_word_ops": int(tot[CNT_WW_OPS]),
        "combine_groups": int(tot[CNT_COMBINE_GROUPS]),
        "combine_locks_saved": int(tot[CNT_COMBINE_SAVED]),
        "xchg_remote_rows": int(tot[CNT_XCHG_REMOTE]),
        "xchg_overflow_rows": int(tot[CNT_XCHG_OVERFLOW]),
    }


class ReplicatedDSM(_HostOps):
    """Replicated-driver host API over a process-spanning DSM.

    Multi-controller JAX runs the SAME host program on every process, so
    a host-API op (lock CAS, page read/write, coalesced chains) is
    requested by every process but must execute on the cluster exactly
    ONCE.  This wrapper is that contract: every process calls every
    method with identical arguments (replicated control flow — the
    engine enforces it with input digests); process 0 posts the real
    request rows while the others contribute empty collective steps, and
    the replies are broadcast so each process returns identical results.
    The role parallels the reference's UD-RPC control plane
    (``Directory.cpp:60-92``): one requester executes, everyone learns
    the outcome (here: synchronously, via the broadcast).

    Batches of any length are chunked to ``host_step_capacity`` rows per
    step; the chunk count derives from the (replicated) row list, so the
    processes' collective step sequences can never desync — the hazard
    :meth:`DSM._batch` refuses to risk in raw per-process mode.

    Device state (pool/locks/counters) is shared with the wrapped DSM;
    the batched engine keeps driving the raw arrays directly.
    """

    def __init__(self, dsm: DSM):
        from jax.experimental import multihost_utils as mhu
        assert dsm.multihost, "ReplicatedDSM wraps a process-spanning DSM"
        self._dsm = dsm
        self._leader = jax.process_index() == 0
        # tiled reassembly in engine._unshard requires process-local node
        # blocks ordered by process index; verify once per cluster
        firsts = np.asarray(mhu.process_allgather(
            np.asarray([dsm.local_nodes[0]], np.int32))).ravel()
        assert (np.diff(firsts) > 0).all(), (
            "mesh node blocks must ascend with process index")

    # -- shared-state passthrough (the engine mutates pool/counters) ---------

    pool = property(lambda s: s._dsm.pool,
                    lambda s, v: setattr(s._dsm, "pool", v))
    locks = property(lambda s: s._dsm.locks,
                     lambda s, v: setattr(s._dsm, "locks", v))
    counters = property(lambda s: s._dsm.counters,
                        lambda s, v: setattr(s._dsm, "counters", v))
    dirty = property(lambda s: s._dsm.dirty,
                     lambda s, v: setattr(s._dsm, "dirty", v))
    cfg = property(lambda s: s._dsm.cfg)
    mesh = property(lambda s: s._dsm.mesh)
    shard = property(lambda s: s._dsm.shard)
    multihost = property(lambda s: s._dsm.multihost)
    local_nodes = property(lambda s: s._dsm.local_nodes)
    host_slots = property(lambda s: s._dsm.host_slots)
    _host_cfg = property(lambda s: s._dsm._host_cfg)
    _step_mutex = property(lambda s: s._dsm._step_mutex)

    def counter_snapshot(self) -> dict[str, int]:
        return self._dsm.counter_snapshot()

    def mark_dirty_rows(self, rows) -> None:
        self._dsm.mark_dirty_rows(rows)

    def clear_dirty(self) -> None:
        self._dsm.clear_dirty()

    def _batch(self, rows: list[dict]) -> Replies:
        from jax.experimental import multihost_utils as mhu
        if not rows:
            self._dsm._batch([])  # still one collective step
            return Replies(data=np.zeros((0, PAGE_WORDS), np.int32),
                           old=np.zeros(0, np.int32), ok=np.zeros(0, bool))
        cap = self._dsm._host_cfg.step_capacity
        parts = []
        for i in range(0, len(rows), cap):
            chunk = rows[i:i + cap]
            if self._leader:
                parts.append(self._dsm._batch(chunk))
            else:
                self._dsm._batch([])
                parts.append(Replies(
                    data=np.zeros((len(chunk), PAGE_WORDS), np.int32),
                    old=np.zeros(len(chunk), np.int32),
                    ok=np.zeros(len(chunk), bool)))
        rep = Replies(data=np.concatenate([p.data for p in parts]),
                      old=np.concatenate([p.old for p in parts]),
                      ok=np.concatenate([p.ok for p in parts]))
        # one-to-all broadcast of the leader's replies (non-leaders pass
        # shape/dtype placeholders — rows are replicated so shapes agree)
        g = mhu.broadcast_one_to_all((rep.data, rep.old, rep.ok))
        return Replies(data=np.asarray(g[0]), old=np.asarray(g[1]),
                       ok=np.asarray(g[2]))
