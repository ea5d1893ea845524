"""Cluster — the top-level runtime handle (``DSM::getInstance`` analogue).

Bundles the sharded-memory transport (:class:`~sherman_tpu.parallel.dsm.DSM`),
the bootstrap Keeper, and one Directory per node, and hands out per-client
contexts the way ``DSM::registerThread`` does (``DSM.cpp:68-92``).

Construction order mirrors the reference init path (SURVEY.md §3.1):
pool allocation -> fabric (the mesh itself) -> keeper enter -> directories
-> cluster barrier.
"""

from __future__ import annotations

from dataclasses import dataclass

import jax

from sherman_tpu.config import DSMConfig
from sherman_tpu.errors import MultiprocessUnsupportedError
from sherman_tpu.parallel.alloc import Directory, LocalAllocator
from sherman_tpu.parallel.bootstrap import Keeper
from sherman_tpu.parallel.dsm import DSM, ReplicatedDSM


@dataclass
class ClientContext:
    """Per-client state (the registerThread product): a client id and a
    private page allocator with per-node chunk leases."""

    client_id: int
    alloc: LocalAllocator
    # lease epoch under which this client's lock acquisitions are valid
    # (bumped by Cluster.expire_client when the control plane declares
    # the client dead; a lock word holding the old epoch is revocable)
    epoch: int = 1

    @property
    def tag(self) -> int:
        """Lock-holder tag; must be nonzero (thread_tag, DSM.cpp:76)."""
        return self.client_id + 1

    @property
    def lease(self) -> int:
        """The lock word this client writes when acquiring a global
        lock: {epoch:15, owner:16} (see ops.bits lease helpers)."""
        from sherman_tpu.ops import bits
        return bits.lease_word(self.tag, self.epoch)


class Cluster:
    def __init__(self, cfg: DSMConfig, mesh: jax.sharding.Mesh | None = None,
                 keeper: Keeper | None = None):
        self.cfg = cfg
        self.dsm = DSM(cfg, mesh)
        self.keeper = keeper if keeper is not None else Keeper(cfg.machine_nr)
        # A process-spanning mesh REQUIRES the multihost keeper: with the
        # in-process keeper every host would take the single-process
        # branch and serve ALL nodes' directories, so two hosts hand out
        # the same chunks (silent corruption).  (The converse — a
        # DistributedKeeper on a 1-process deployment — is fine: it is
        # just a 1-host cluster.)
        assert not (self.dsm.multihost and not self.keeper.is_multihost), (
            "mesh spans processes but the keeper is single-process: pass "
            "bootstrap.init_multihost()'s keeper to Cluster on every host")
        if self.keeper.is_multihost:
            # Replicated-driver SPMD (see dsm.ReplicatedDSM): every host
            # process enters the cluster once and then mirrors ALL nodes'
            # directories.  Identical replicated control flow keeps the
            # mirrors in lock-step, which is what lets any client lease
            # chunks on ANY node — DSM::alloc's round-robin over every
            # directory (DSM.h:200-221) — without a cross-host RPC.
            # Divergent per-process request streams would desync the
            # mirrors (and the collective step sequences); the batched
            # engine guards that with input-digest checks.
            self.keeper.server_enter()
            self.node_ids = list(range(cfg.machine_nr))
        else:
            # single-process SPMD: this process plays every symmetric
            # CN+MN node
            self.node_ids = [self.keeper.server_enter()
                             for _ in range(cfg.machine_nr)]
        self.directories = [Directory(n, cfg) for n in self.node_ids]
        # host_dsm is the handle Tree/engine host paths use: raw DSM in
        # single-process mode; the leader-posted replicated wrapper when
        # the mesh spans processes (each host-API op must execute once
        # cluster-wide even though every process requests it)
        self.host_dsm = (ReplicatedDSM(self.dsm) if self.dsm.multihost
                         else self.dsm)
        # Hierarchical lock, local tier (Sherman technique #1,
        # Tree.cpp:1124-1173): one process-wide native ticket-lock table
        # indexed like the global lock space; Tree clients of this
        # process queue here first and hand the GLOBAL lock down the
        # ticket train (bounded by kMaxHandOverTime=8), paying one
        # remote CAS + one remote unlock per train instead of per op.
        # Disabled on process-spanning meshes: hand-over decisions are
        # per-process thread-timing-dependent, and ReplicatedDSM requires
        # every process to issue the IDENTICAL collective step sequence.
        from sherman_tpu import native
        self.local_locks = (
            native.LocalLockTable(cfg.machine_nr * cfg.locks_per_node)
            if not self.dsm.multihost and native.available() else None)
        self._next_client = 0
        # Lock-lease epoch table: tag -> current lease epoch of every
        # registered client.  The data-plane liveness oracle for lock
        # revocation (Tree._try_revoke_lease): a lock word whose
        # (owner, epoch) is absent or stale here belongs to a dead
        # client and may be revoked.  Mirrored across processes by the
        # replicated-registration contract (identical register_client
        # streams), exactly like the directories above.
        self.lease_epochs: dict[int, int] = {}
        self.keeper.barrier("DSM-init")

    def register_client(self, replicated: bool | None = None
                        ) -> ClientContext:
        """Per-client context (``DSM::registerThread``).

        Multi-host: allocation state is MIRRORED on every process
        (replicated-driver SPMD), so a registered client may only
        allocate from replicated control flow — identical calls on every
        process (the Tree/BatchedEngine path, which digest-checks its
        inputs).  Divergent per-process allocation would advance the
        mirrors differently and hand out colliding pages.  To make that
        contract structural rather than documentation, registering a
        client on a multi-host cluster requires ``replicated=True`` as
        an explicit acknowledgment; raw per-process drivers
        (``cluster.dsm``) get a loud error here instead of silent
        corruption later.
        """
        if self.dsm.multihost and replicated is not True:
            raise MultiprocessUnsupportedError(
                "multi-host clients allocate from MIRRORED directories: "
                "pass register_client(replicated=True) to acknowledge "
                "that this client runs identical (replicated) control "
                "flow on every process; raw per-process drivers must "
                "not allocate")
        cid = self._next_client
        self._next_client += 1
        ctx = ClientContext(client_id=cid,
                            alloc=LocalAllocator(self.directories))
        self.lease_epochs[ctx.tag] = ctx.epoch
        return ctx

    # -- lock-lease liveness (data-plane failure story) ----------------------
    # The control plane (utils/failure.py) detects peer DEATH and stalls;
    # these methods are the data plane's matching oracle: whether a lock
    # word's holder is still entitled to it.  The spin paths consult ONLY
    # the host-local epoch table (a dict lookup — no collective, no extra
    # DSM op); ``sweep_dead_processes`` is the periodic maintenance pass
    # that folds coordination-service liveness into the table.

    def lease_is_live(self, owner_tag: int, epoch: int) -> bool:
        """True iff a lock word's (owner, epoch) names a live lease:
        the tag is registered here and the epoch matches its current
        lease generation.  An unregistered tag (a client of a previous
        incarnation, or junk from corruption) is dead; a registered tag
        at a stale epoch was expired by the control plane."""
        return self.lease_epochs.get(int(owner_tag)) == int(epoch)

    def expire_client(self, owner_tag: int) -> None:
        """Declare a client's current lease dead: bump its epoch so any
        lock word it still holds fails ``lease_is_live`` and becomes
        revocable.  Called by control-plane death handling (and tests);
        on multi-host meshes every process must call identically (the
        table is mirrored, like the directories)."""
        t = int(owner_tag)
        self.lease_epochs[t] = self.lease_epochs.get(t, 0) + 1

    def sweep_dead_processes(self, tags_by_process: dict[int, list[int]]
                             ) -> list[int]:
        """COLLECTIVE maintenance pass: consult the coordination
        service's liveness roll call (``failure.live_processes`` — every
        live process must call this together) and expire every client
        tag owned by a process that is no longer live.  ``tags_by_
        process`` maps process index -> the tags that process's
        non-replicated drivers registered (replicated clients exist on
        every process and die only with the whole cluster).  Returns the
        expired tags.  Single-process clusters trivially expire nothing.
        """
        from sherman_tpu.utils import failure
        live = set(failure.live_processes(
            self.keeper.machine_nr if self.keeper.is_multihost else 1))
        expired = []
        for proc, tags in tags_by_process.items():
            if int(proc) in live:
                continue
            for t in tags:
                self.expire_client(t)
                expired.append(int(t))
        return expired

    # NEW_ROOT broadcast (Tree.cpp:116-124): update the local directories'
    # hints.  The hint is advisory acceleration only — the authoritative
    # root is the meta-page word every client reads (Tree._refresh_root),
    # so other hosts' hints converge lazily rather than via cross-host RPC.
    def broadcast_new_root(self, addr: int, level: int) -> None:
        for d in self.directories:
            d.new_root(addr, level)


# ---------------------------------------------------------------------------
# The one sizing + construction rule of a bulk-loaded deployment: bench.py,
# chip_smoke.py and the tools/ drivers (through tools/common) all call it.
# ---------------------------------------------------------------------------

def pages_for_keys(n_keys: int, fill: float = 0.75,
                   min_pages: int = 1 << 14) -> int:
    """Pool pages for ``n_keys`` bulk-loaded at ``fill``: the leaves plus
    10 % internal overhead plus one chunk of slack, rounded up to a power
    of two (2^22 at the 100 M-key north star)."""
    from sherman_tpu.config import LEAF_CAP
    per_leaf = max(1, int(LEAF_CAP * fill))
    est = int(n_keys / per_leaf * 1.10) + 8192
    return max(min_pages, 1 << (est - 1).bit_length())


def build_engine(n_nodes: int, pages_per_node: int, batch_per_node: int,
                 locks_per_node: int = 65_536, chunk_pages: int = 4096,
                 exchange_impl: str = "xla", gather_impl: str = "xla"):
    """-> (cluster, tree, engine): a Cluster, its Tree and a BatchedEngine
    of ``batch_per_node`` rows.  Sibling-chase budget 1: a bulk-loaded
    tree under read traffic needs height + 1 rounds of descent only."""
    from sherman_tpu.config import TreeConfig
    from sherman_tpu.models import batched
    from sherman_tpu.models.btree import Tree

    cfg = DSMConfig(machine_nr=n_nodes, pages_per_node=pages_per_node,
                    locks_per_node=locks_per_node,
                    step_capacity=batch_per_node, chunk_pages=chunk_pages,
                    exchange_impl=exchange_impl, gather_impl=gather_impl)
    cluster = Cluster(cfg)
    tree = Tree(cluster)
    eng = batched.BatchedEngine(tree, batch_per_node=batch_per_node,
                                tcfg=TreeConfig(sibling_chase_budget=1))
    return cluster, tree, eng
