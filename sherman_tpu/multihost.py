"""Multihost service plane — per-host chain ownership, key routing,
and a cross-host front door (PR 19).

Sherman is a symmetric cluster bootstrapped by an all-pairs metadata
plane (survey L2/L3): every host serves clients against the shared
pool.  The reproduction sharded the POOL from PR 1, but the SERVICE —
the front door, the journal, the checkpoint chain — stayed
single-process.  This module is the service half:

- **ownership**: the key space is partitioned over hosts by a
  deterministic mix hash (:class:`HostRouter`).  Each host owns ONE
  journal stream and one chain namespace in the shared recovery
  directory (``base-h<i>.npz`` / ``delta-h<i>-<cid>-k.npz`` /
  ``journal-h<i>-<cid>-k.wal`` — ``sherman_tpu/recovery.py``), so N
  hosts fsync/rotate/sweep fully independently: ack bandwidth
  multiplies by host count instead of serializing on one stream.
- **front door**: per-host ingress dispatchers (one
  :class:`~sherman_tpu.serve.ShermanServer` per host, each with its
  own ``WidthController``) behind ONE logical
  :class:`MultihostService`: a submit splits by owner host, each
  sub-batch rides the owner's sealed programs, and the write ack gates
  on the OWNER's journal only.  :func:`merge_host_stats` folds the
  per-host receipts into one logical SLO plane (summed throughput
  counters, worst-host tail percentiles — on a real pod the same
  reduction is one psum over the per-host receipt vector).
- **recovery**: ``RecoveryPlane.recover_union`` — the union of
  per-host chains, each restored + replayed independently; a torn tail
  on one host never blocks another's replay, and cross-host replay
  order is immaterial because no two hosts' journals ever carry the
  same key (the router is the partition proof).
- **replication seam**: a follower on host B ships host A's chain by
  pointing the PR 16 tailer at A's namespace
  (``JournalTailer(dir, cid, host_id=A)``) — same shared
  ``apply_records`` core, now cross-host.

**Scope honesty.**  The plane is exercised via EMULATION: N
host contexts (N single-process clusters = N chain namespaces + one
routing table) in one process.  Every file-format, routing, recovery
and replication path is the real code; the transport (one mesh
spanning processes) is not — true 2-process drills stay gated behind
:func:`multihost_capable` (the conftest probe) and real-pod captures are queued in
BENCHMARKS.md.  ``SHERMAN_HOSTS=1`` (the shipped default) constructs
no plane at all: artifact names, journal bytes and receipts are
bit-identical to a build without this module.
"""

from __future__ import annotations

import threading

import numpy as np

from sherman_tpu import config as C
from sherman_tpu import obs
from sherman_tpu.errors import ConfigError, StateError

_OBS_SPLITS = obs.counter("multihost.split_submits")
_OBS_ROUTED = obs.counter("multihost.routed_ops")
_OBS_SCANS = obs.counter("multihost.fanout_scans")
_OBS_ADOPTIONS = obs.counter("multihost.adoptions")


class HostDownError(StateError):
    """The owner host of (part of) this request is unreachable —
    crashed or frozen at the dispatch seam.  Typed so clients retry by
    rid once an adopter serves the namespace (exactly-once re-acks),
    instead of stranding a half-submitted merged future."""

#: cached :func:`multihost_capable` probe result —
#: ``[(ok: bool, reason: str)]`` once probed, shared with conftest
_MULTIHOST_PROBE: list = []

_PROBE_WORKER = r'''
import os, sys
pid = int(sys.argv[1]); port = sys.argv[2]
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
os.environ["JAX_PLATFORMS"] = "cpu"
import jax
jax.config.update("jax_platforms", "cpu")
jax.distributed.initialize(f"localhost:{port}", 2, pid)
import numpy as np
from jax.experimental import multihost_utils
out = multihost_utils.process_allgather(np.asarray([pid], np.int32))
assert sorted(np.asarray(out).ravel().tolist()) == [0, 1]
print("PROBE-OK", flush=True)
'''


def multihost_capable() -> tuple[bool, str]:
    """(capable, reason) — can THIS jaxlib run CPU multiprocess
    collectives?  Probed once per process (two tiny subprocesses run a
    cross-process allgather with a deadline), subprocess-isolated so
    the probe can neither poison nor be poisoned by this process's jax
    runtime.  Gates the true 2-process drills
    (``tests/test_multihost.py``)."""
    if _MULTIHOST_PROBE:
        return _MULTIHOST_PROBE[0]
    import os
    import socket
    import subprocess
    import sys as _sys
    import tempfile
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with tempfile.TemporaryDirectory() as d:
        worker = os.path.join(d, "probe.py")
        with open(worker, "w") as f:
            f.write(_PROBE_WORKER)
        with socket.socket() as s:
            s.bind(("localhost", 0))
            port = str(s.getsockname()[1])
        env = dict(os.environ)
        env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")
        env.pop("XLA_FLAGS", None)
        procs = [subprocess.Popen(
            [_sys.executable, worker, str(pid), port],
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, env=env,
            text=True) for pid in range(2)]
        outs = []
        try:
            for p in procs:
                out, _ = p.communicate(timeout=120)
                outs.append(out)
        except subprocess.TimeoutExpired:
            for p in procs:
                p.kill()
            _MULTIHOST_PROBE.append(
                (False, "probe timed out (collective hung)"))
            return _MULTIHOST_PROBE[0]
        if all(p.returncode == 0 and "PROBE-OK" in o
               for p, o in zip(procs, outs)):
            _MULTIHOST_PROBE.append((True, ""))
        else:
            tail = next((o for p, o in zip(procs, outs)
                         if p.returncode != 0), outs[0])[-600:]
            _MULTIHOST_PROBE.append(
                (False, "this jaxlib cannot run CPU multiprocess "
                 "collectives: " + tail.strip().replace("\n", " | ")))
    return _MULTIHOST_PROBE[0]


# ---------------------------------------------------------------------------
# Key -> owner-host routing
# ---------------------------------------------------------------------------

class HostRouter:
    """Deterministic key -> owner-host partition (the service plane's
    ownership function).  A splitmix64-style finalizer over the raw
    key, mod host count: stateless, identical on every host and every
    retry (exactly-once composes — a retried rid re-splits into the
    SAME per-host sub-batches), and independent of the tree's node
    routing (pool placement and service ownership are different
    axes: any host can read any page; only the owner journals the
    write).

    **Adoption overlay** (PR 20): :meth:`owner` is namespace IDENTITY
    and never changes — a dead host's keys still belong to ITS chain
    namespace.  The overlay answers a different question — which
    host's PROCESS currently serves that namespace
    (:meth:`route`): after host-loss failover, ``overlay[dead] =
    adopter``.  The map itself is durably journaled by the failover
    plane (``hostlease.OwnershipLog``); this is the in-memory routing
    view the service publishes.
    """

    __slots__ = ("hosts", "overlay")

    def __init__(self, hosts: int):
        if int(hosts) < 1:
            raise ConfigError(f"HostRouter wants hosts >= 1 (got {hosts})")
        self.hosts = int(hosts)
        #: namespace -> serving host (absent = serves itself)
        self.overlay: dict[int, int] = {}

    def route(self, host: int) -> int:
        """Which host's process serves ``host``'s namespace right now
        (identity until an adoption installs an overlay entry)."""
        return self.overlay.get(int(host), int(host))

    def adopt(self, dead: int, adopter: int) -> None:
        """Install one adoption: ``dead``'s namespace is now served by
        ``adopter``'s process.  Ownership (:meth:`owner`) is
        unchanged — the adopted front door runs over the DEAD
        namespace's recovered engine, not the adopter's own."""
        dead, adopter = int(dead), int(adopter)
        if not (0 <= dead < self.hosts and 0 <= adopter < self.hosts):
            raise ConfigError(
                f"adopt({dead} -> {adopter}): hosts outside "
                f"[0, {self.hosts})")
        if dead == adopter:
            raise ConfigError(f"host {dead} cannot adopt itself")
        self.overlay[dead] = adopter

    def handback(self, dead: int) -> None:
        """Drop one adoption overlay entry: ``dead``'s namespace
        serves itself again.  The routing half of the explicit
        hand-back (``hostlease.HostFailover.handback``) — the caller
        re-registers the returning host and rebuilds its door before
        traffic routes back."""
        self.overlay.pop(int(dead), None)

    def owner(self, keys) -> np.ndarray:
        """Owner host per key -> int32 [n] in [0, hosts)."""
        k = np.ascontiguousarray(keys, np.uint64)
        if self.hosts == 1:
            return np.zeros(k.shape, np.int32)
        # splitmix64 finalizer: unsigned wraparound is the algorithm
        x = k.copy()
        x ^= x >> np.uint64(30)
        x *= np.uint64(0xBF58476D1CE4E5B9)
        x ^= x >> np.uint64(27)
        x *= np.uint64(0x94D049BB133111EB)
        x ^= x >> np.uint64(31)
        return (x % np.uint64(self.hosts)).astype(np.int32)

    def split(self, keys, values=None):
        """Partition one request by owner -> list of
        ``(host, idx, keys_h, values_h)`` with ``idx`` the positions
        of ``keys_h`` in the original batch (the merge permutation).
        Hosts with no keys in the batch are absent."""
        k = np.ascontiguousarray(keys, np.uint64)
        own = self.owner(k)
        v = None if values is None \
            else np.ascontiguousarray(values, np.uint64)
        out = []
        for h in range(self.hosts):
            idx = np.nonzero(own == h)[0]
            if idx.size:
                out.append((h, idx, k[idx],
                            None if v is None else v[idx]))
        return out


# ---------------------------------------------------------------------------
# Emulated host context
# ---------------------------------------------------------------------------

class HostContext:
    """One host's slice of the plane: its cluster/tree/engine triple,
    its recovery plane (chain namespace ``-h<host_id>-``), and its
    front-door server.  On a real pod each process holds exactly one
    of these (``SHERMAN_HOST_ID``); the CPU emulation constructs all N
    in one process — same objects, same files, in-process transport."""

    __slots__ = ("host_id", "cluster", "tree", "eng", "plane", "server")

    def __init__(self, host_id: int, cluster=None, tree=None, eng=None,
                 plane=None, server=None):
        self.host_id = int(host_id)
        self.cluster = cluster
        self.tree = tree
        self.eng = eng
        self.plane = plane
        self.server = server


# ---------------------------------------------------------------------------
# Cross-host front door
# ---------------------------------------------------------------------------

class _MergedFuture:
    """Future over one split submit: resolves when every owner host's
    sub-future has, reassembling per-host results into the original
    batch order.  Duck-types the :class:`~sherman_tpu.serve.ServeFuture`
    surface the clients use (``result`` / ``done`` / ``deduped``)."""

    __slots__ = ("op", "tenant", "n_ops", "rid", "parts", "_lock")

    def __init__(self, op: str, tenant: str, n_ops: int, rid,
                 parts: list):
        self.op = op
        self.tenant = tenant
        self.n_ops = int(n_ops)
        self.rid = rid
        #: [(host, idx, sub_future)] — idx maps sub-results home
        self.parts = parts
        self._lock = threading.Lock()

    def done(self) -> bool:
        return all(f.done() for _h, _i, f in self.parts)

    @property
    def deduped(self) -> bool:
        """True when EVERY owner host re-acked from its exactly-once
        window — the split retry's analog of the single-door flag (the
        router is deterministic, so a retried rid reaches the same
        owners and each dedups independently)."""
        return all(f.deduped for _h, _i, f in self.parts)

    def result(self, timeout: float | None = None):
        subs = [(idx, f.result(timeout)) for _h, idx, f in self.parts]
        if self.op == "read":
            vals = np.zeros(self.n_ops, np.uint64)
            found = np.zeros(self.n_ops, bool)
            for idx, (v, fnd) in subs:
                vals[idx] = np.asarray(v, np.uint64)
                found[idx] = np.asarray(fnd, bool)
            return vals, found
        # insert -> ok per key; delete -> found per key
        ok = np.zeros(self.n_ops, bool)
        for idx, r in subs:
            ok[idx] = np.asarray(r, bool)
        return ok


class _MergedScan:
    """Future over one fan-out scan: every host runs the SAME range
    set over its own shard (a hash partition scatters any range's keys
    across all hosts), and each range's per-host results concatenate
    and re-sort by key — ``range_query_many``'s per-range order,
    restored plane-wide.  Duck-types the ``ServeFuture`` surface."""

    __slots__ = ("tenant", "n_ranges", "parts")

    def __init__(self, tenant: str, n_ranges: int, parts: list):
        self.tenant = tenant
        self.n_ranges = int(n_ranges)
        #: [(host, sub_future)] — every host contributes to every range
        self.parts = parts

    def done(self) -> bool:
        return all(f.done() for _h, f in self.parts)

    @property
    def deduped(self) -> bool:
        return False            # scans never ride the write contract

    def result(self, timeout: float | None = None):
        per_host = [f.result(timeout) for _h, f in self.parts]
        out = []
        for r in range(self.n_ranges):
            ks = np.concatenate([np.asarray(ph[r][0], np.uint64)
                                 for ph in per_host])
            vs = np.concatenate([np.asarray(ph[r][1], np.uint64)
                                 for ph in per_host])
            order = np.argsort(ks, kind="stable")
            out.append((ks[order], vs[order]))
        return out


class MultihostService:
    """One logical front door over N per-host servers.

    Reads and writes split by owner host
    (:meth:`HostRouter.split`); each sub-batch is admitted by the
    owner's own ``WidthController``/tenant gates and — for writes —
    acked only after the OWNER's journal fsync covers it.  The merged
    future resolves in the original batch order.  Scans FAN OUT: a
    hash partition scatters every range's keys across all hosts, so
    each host runs the whole range set over its shard and the merged
    future re-sorts each range plane-wide (YCSB-E runs through the
    merged door).  The one typed refusal left is a scan carrying a
    resume ``cursor``: a cursor token is positional within ONE host's
    range walk and does not compose over a hash partition.

    The service itself holds NO pool state — it is a routing table
    plus futures glue, exactly the piece a real pod runs on every
    ingress host.
    """

    def __init__(self, servers, router: HostRouter | None = None,
                 planes=None):
        if not servers:
            raise ConfigError("MultihostService wants >= 1 server")
        self.servers = list(servers)
        self.hosts = len(self.servers)
        self.router = router or HostRouter(self.hosts)
        if self.router.hosts != self.hosts:
            raise ConfigError(
                f"router spans {self.router.hosts} hosts but "
                f"{self.hosts} servers were given")
        #: per-host recovery planes (host order) when the caller wants
        #: frontier tokens through the service handle; optional — the
        #: front door itself never touches the chain
        self.planes = list(planes) if planes is not None else None
        self._chaos = None      # HostChaos at the dispatch seam
        self.adoptions = 0

    def attach_chaos(self, host_chaos) -> None:
        """Install a ``chaos.HostChaos`` layer at the dispatch seam:
        every sub-batch's serving host is checked before routing —
        crashed/frozen hosts refuse typed (:class:`HostDownError`)."""
        self._chaos = host_chaos

    def _check_dispatch(self, owners) -> None:
        """Ask the chaos layer about EVERY serving host of this
        request BEFORE submitting any part — a typed refusal must not
        strand sub-futures already admitted on live hosts.  The
        dispatch clock ticks ONCE per service dispatch (refused or
        not), never once per host probed, so scheduled fault windows
        elapse independently of a request's fan-out."""
        if self._chaos is None:
            return
        try:
            for h in owners:
                serving = self.router.route(h)
                d = self._chaos.on_dispatch(serving)
                if d is not None and d.get("down"):
                    raise HostDownError(
                        f"host {serving} (serving namespace {h}) is "
                        f"unreachable ({d.get('state')}); retry by rid "
                        "once the namespace is adopted")
        finally:
            self._chaos.tick()

    def submit(self, op: str, keys=None, values=None, *,
               tenant: str = "default", ranges=None, cursor=None,
               rid=None, deadline_ms: float | None = None):
        """Split-admit one request across owner hosts -> a merged
        future (original batch order).  Single-host planes delegate
        straight through — zero added surface at hosts=1."""
        if cursor is not None:
            raise ConfigError(
                "scan cursors do not resume over a hash-partitioned "
                "host plane (a resume token is positional within one "
                "host's range walk); re-submit the full ranges, or "
                "resume on a single-host front door")
        if self.hosts == 1:
            return self.servers[0].submit(
                op, keys, values, tenant=tenant, ranges=ranges,
                rid=rid, deadline_ms=deadline_ms)
        if op == "scan":
            if not ranges:
                raise ConfigError("scan submit needs ranges")
            self._check_dispatch(range(self.hosts))
            _OBS_SCANS.inc()
            parts = [(h, self.servers[h].submit(
                "scan", tenant=tenant, ranges=ranges,
                deadline_ms=deadline_ms)) for h in range(self.hosts)]
            return _MergedScan(tenant, len(ranges), parts)
        keys = np.ascontiguousarray(keys, np.uint64)
        parts_in = self.router.split(keys, values)
        self._check_dispatch([h for h, _i, _k, _v in parts_in])
        _OBS_SPLITS.inc()
        _OBS_ROUTED.inc(int(keys.size))
        parts = []
        for h, idx, k_h, v_h in parts_in:
            f = self.servers[h].submit(
                op, k_h, v_h, tenant=tenant, rid=rid,
                deadline_ms=deadline_ms)
            parts.append((h, idx, f))
        return _MergedFuture(op, tenant, int(keys.size), rid, parts)

    def adopt(self, dead: int, server, *, plane=None,
              adopter: int | None = None) -> None:
        """Swap ``dead``'s front door for the ADOPTED one (a fresh
        server over the dead namespace's recovered engine, run by the
        adopter's process) and install the router overlay.  Called by
        ``hostlease.HostFailover.adopt`` after the done frame is
        durable — the service's in-memory view follows the journaled
        ownership map, never leads it."""
        dead = int(dead)
        if not (0 <= dead < self.hosts):
            raise ConfigError(f"adopt: host {dead} outside "
                              f"[0, {self.hosts})")
        self.servers[dead] = server
        if self.planes is not None and plane is not None:
            self.planes[dead] = plane
        if adopter is not None:
            self.router.adopt(dead, adopter)
        self.adoptions += 1
        _OBS_ADOPTIONS.inc()

    def journal_frontiers(self) -> list[tuple[str, int]]:
        """Per-host durable journal frontier tokens, host order —
        the union coverage token (a follower set covering every
        entry holds everything any host acked)."""
        if self.planes is None:
            raise StateError(
                "MultihostService was built without planes= — frontier "
                "tokens live on the per-host RecoveryPlanes")
        return [p.journal_frontier() for p in self.planes]

    def stats(self) -> dict:
        """One logical SLO plane over the per-host receipts
        (:func:`merge_host_stats`).  Adoption state rides along only
        once an adoption happened — an unfailed plane's receipt is
        byte-identical to the pre-failover build's."""
        out = merge_host_stats([s.stats() for s in self.servers])
        if self.adoptions:
            out["adoptions"] = self.adoptions
            out["overlay"] = {str(d): a for d, a
                              in sorted(self.router.overlay.items())}
        return out


def merge_host_stats(per_host: list[dict]) -> dict:
    """Fold per-host ``ShermanServer.stats()`` receipts into ONE
    logical SLO plane: throughput counters SUM (the plane serves the
    union of the hosts' traffic), tail percentiles take the WORST host
    (a plane's p99 promise is broken if any host's is), journal
    coalescing re-derives from the summed acks/fsyncs.  On a real pod
    this exact reduction is one psum over the per-host receipt vector
    — emulation computes it host-side, which is bit-identical for the
    integer counters by commutativity."""
    if not per_host:
        raise ConfigError("merge_host_stats wants >= 1 stats dict")
    merged = {
        "hosts": len(per_host),
        "admitted_ops": sum(s.get("admitted_ops", 0) for s in per_host),
        "served_ops": sum(s.get("served_ops", 0) for s in per_host),
        "acked_writes": sum(s.get("acked_writes", 0) for s in per_host),
        "rejects": {
            "overload": sum(s.get("rejects", {}).get("overload", 0)
                            for s in per_host),
            "degraded": sum(s.get("rejects", {}).get("degraded", 0)
                            for s in per_host),
        },
        "dispatch_errors": sum(s.get("dispatch_errors", 0)
                               for s in per_host),
        "retraces": sum(s.get("retraces", 0) for s in per_host),
        "widths": [(s.get("controller") or {}).get(
            "settled_width", (s.get("controller") or {}).get("cap_width"))
            for s in per_host],
    }
    # worst-host tail per op class over the hosts that observed it
    window: dict = {}
    for s in per_host:
        for cls, w in (s.get("window") or {}).items():
            cur = window.setdefault(cls, {
                "ops_s": 0.0, "p50_ms": 0.0, "p99_ms": 0.0,
                "window_ops": 0, "ops_total": 0})
            cur["ops_s"] += float(w.get("ops_s", 0.0))
            cur["p50_ms"] = max(cur["p50_ms"],
                                float(w.get("p50_ms", 0.0)))
            cur["p99_ms"] = max(cur["p99_ms"],
                                float(w.get("p99_ms", 0.0)))
            cur["window_ops"] += int(w.get("window_ops", 0))
            cur["ops_total"] += int(w.get("ops_total", 0))
    merged["window"] = window
    # exactly-once window, summed (disjoint by construction: one rid's
    # entries live only on its sub-batches' owner hosts)
    merged["contract"] = {
        k: sum((s.get("contract") or {}).get(k, 0) for s in per_host)
        for k in ("dedup_hits", "deadline_shed", "duplicate_applies",
                  "cached_rids", "pending_rids")}
    fsyncs = sum((s.get("journal") or {}).get("fsyncs", 0)
                 for s in per_host)
    appends = sum((s.get("journal") or {}).get("appends", 0)
                  for s in per_host)
    if fsyncs:
        merged["journal"] = {
            "fsyncs": fsyncs, "appends": appends,
            "acks_per_fsync": round(
                merged["acked_writes"] / fsyncs, 3),
        }
    return merged


# ---------------------------------------------------------------------------
# Knob-gated construction
# ---------------------------------------------------------------------------

def plane_from_env() -> tuple[int, int]:
    """(hosts, host_id) from the knobs — the shipped default (1, 0)
    constructs NO plane (legacy names, one front door); callers pass
    the pair straight into ``RecoveryPlane(..., hosts=, host_id=)``."""
    return C.hosts(), C.host_id()
