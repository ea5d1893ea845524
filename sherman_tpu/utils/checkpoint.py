"""Checkpoint / resume of the whole DSM cluster state.

The reference has NO durability story (SURVEY.md §5: "Checkpoint /
resume. Absent.") — a crashed cluster loses the index.  This module goes
beyond parity: one call snapshots everything a cluster needs to come
back — the sharded pool (which contains every page AND the root-pointer
meta words), the lock table, op counters, and each directory's allocator
bump state — into a single ``.npz``; ``restore`` rebuilds a live Cluster
on any mesh of the same ``machine_nr``.  Multi-host clusters checkpoint
collectively: one shard file per host plus the (mirrored, identical)
manifest written by every host, restored onto the same nodes-per-host
partition.

Client-side chunk leases (LocalAllocator tails) are deliberately NOT
saved: clients re-register after restore and lease fresh chunks.  The
abandoned tails are unreachable pages — the same class of leak as the
reference's no-op ``free`` (DSM.h:226), bounded by one chunk per client.

Locks are saved as-is; a checkpoint taken mid-operation may hold locks
whose owners are gone, so ``restore(clear_locks=True)`` (default) zeroes
the table — valid because restore is a cluster-wide restart: no client
of the old incarnation survives.
"""

from __future__ import annotations

import glob
import json
import os
import zlib

import numpy as np

from sherman_tpu import config as _C
from sherman_tpu import obs
from sherman_tpu.config import DSMConfig
from sherman_tpu.errors import (CheckpointFormatError, ConfigError,
                                ShermanError)

_CFG_FIELDS = ("machine_nr", "pages_per_node", "locks_per_node",
               "step_capacity", "host_step_capacity", "chunk_pages",
               "exchange_impl", "gather_impl", "heap_pages_per_node")

# fsync indirection for tests (patching os.fsync itself would also
# intercept interpreter/numpy internals)
_fsync = os.fsync

_OBS_FULL_SAVES = obs.counter("ckpt.full_saves")
_OBS_DELTA_SAVES = obs.counter("ckpt.delta_saves")
_OBS_DELTA_PAGES = obs.counter("ckpt.delta_pages")
_OBS_DELTA_BYTES = obs.counter("ckpt.delta_bytes")
_OBS_ORPHANS = obs.counter("ckpt.orphans_swept")


class CheckpointCorruptError(ShermanError, RuntimeError):
    """A checkpoint artifact failed its content CRC / framing / chain
    pairing — corruption is detected at restore time, never served."""

# Page-layout fingerprint stamped into every checkpoint: the pool is raw
# words, so restoring across a layout change (e.g. round 4's packed
# 16/16 entry version pair, 41 -> 49 leaf slots) would silently
# misinterpret every page.  Missing tag = pre-stamp checkpoint, also
# rejected.
LAYOUT_TAG = (f"pw{_C.PAGE_WORDS}"
              f"+leaf{_C.LEAF_ENTRY_WORDS}x{_C.LEAF_CAP}"
              f"+int{_C.INTERNAL_ENTRY_WORDS}x{_C.INTERNAL_CAP}")


def cfg_to_json(cfg) -> bytes:
    d = {f: getattr(cfg, f) for f in _CFG_FIELDS}
    d["_layout"] = LAYOUT_TAG
    return json.dumps(d).encode()


def cfg_from_json(raw) -> DSMConfig:
    """Saved cfg JSON -> DSMConfig, under the _CFG_FIELDS forward-compat
    contract: fields ABSENT from the JSON (a checkpoint written before
    the field existed, e.g. pre-``gather_impl``) take the DSMConfig
    default — never a KeyError; fields this build does NOT know (a
    checkpoint written by a newer build) refuse loudly — silently
    dropping a semantic knob could reinterpret the pool."""
    import dataclasses
    d = json.loads(bytes(raw).decode())
    tag = d.pop("_layout", None)
    if tag != LAYOUT_TAG:
        raise CheckpointFormatError(
            f"checkpoint page layout {tag or 'unstamped'!r} does not match "
            f"this build's {LAYOUT_TAG!r}; re-create the checkpoint (raw "
            "page words cannot be reinterpreted across layouts)")
    known = {f.name for f in dataclasses.fields(DSMConfig)}
    unknown = sorted(set(d) - known)
    if unknown:
        raise CheckpointFormatError(
            f"checkpoint cfg carries unknown fields {unknown} (written "
            "by a newer build?); refusing to drop config knobs silently")
    return DSMConfig(**d)


def _local_block(arr) -> np.ndarray:
    """This host's contiguous block of a node-sharded array, shards
    ordered by their global row offset."""
    shards = sorted(arr.addressable_shards,
                    key=lambda s: s.index[0].start or 0)
    return np.concatenate([np.asarray(s.data) for s in shards])


def checkpoint(cluster, path: str):
    """Write the cluster's full state to ``path`` (.npz).

    Multi-host clusters write one shard file per host
    (``<path>.host<k>.npz`` with that process's node block) and EVERY
    process writes the (identical, mirrored) manifest at ``<path>`` —
    each host's own disk gets both files, no shared filesystem needed;
    every process must call (collective — barrier at the end).  All
    files are written atomically (tmp + replace) and carry a shared
    epoch, so a crash mid-checkpoint leaves the PREVIOUS checkpoint
    intact and restore rejects mixed-epoch shard/manifest pairs.
    Restore requires the same machine_nr AND the same nodes-per-host
    partition.
    """
    if not path.endswith(".npz"):
        path += ".npz"  # np.savez appends it silently; keep restore in sync
    if cluster.keeper.is_multihost:
        from sherman_tpu.utils import failure

        # a peer dying mid-protocol would hang every other host inside
        # the broadcast/allgather/barrier below; the env-gated watchdog
        # (SHERMAN_COLLECTIVE_TIMEOUT_S) turns that into a fail-fast
        # exit so the launcher can restart from the previous checkpoint
        with failure.Watchdog.maybe(
                what="collective checkpoint save",
                diagnostics=lambda: cluster.dsm.counter_snapshot()):
            _checkpoint_multihost(cluster, path)
        return None
    dsm = cluster.dsm
    man = _manifest(cluster)
    # Epoch on single-host full checkpoints too: the (nonce, seq, crc)
    # triple is what delta artifacts chain their parent_epoch to.
    seq = cluster.keeper.mem_fetch_and_add("checkpoint_epoch")
    epoch = make_epoch(man, seq)
    arrays = dict(
        pool=np.asarray(dsm.pool),
        locks=np.asarray(dsm.locks),
        counters=np.asarray(dsm.counters),
        epoch=epoch,
        **man,
    )
    # value-heap region (optional — heap-off checkpoints are unchanged)
    if dsm.heap is not None:
        arrays["heap"] = dsm.heap_snapshot()
    arrays["integrity"] = _integrity(arrays)
    _savez_atomic(path, 0, **arrays)
    _OBS_FULL_SAVES.inc()
    obs.record_event("checkpoint.save", path=path, seq=int(seq))
    # A full save captures everything: dirty tracking restarts here.
    dsm.clear_dirty()
    return epoch


def _checkpoint_multihost(cluster, path: str) -> None:
    import jax
    dsm = cluster.dsm
    me = jax.process_index()
    # Epoch pairing shard <-> manifest AND checkpoint <-> checkpoint:
    # (nonce, seq, digest).  The nonce is random on process 0 and
    # broadcast, making every checkpoint invocation globally unique —
    # a per-process counter alone resets across restarts and the
    # manifest digest alone is unchanged by update-in-place traffic,
    # so (seq, dig) could collide across distinct checkpoints.
    # int32 throughout: restore allgathers the epoch, and jax (x64
    # disabled) canonicalizes int64 -> int32, which would wrap an
    # unsigned crc and break the cross-host equality check.
    from jax.experimental import multihost_utils as mhu
    seq = cluster.keeper.mem_fetch_and_add("checkpoint_epoch")
    man = _manifest(cluster)
    nonce = np.frombuffer(os.urandom(4), np.int32).copy()
    nonce = np.asarray(mhu.broadcast_one_to_all(nonce))
    epoch = make_epoch(man, seq, nonce=int(nonce[0]))
    # Save-time epoch agreement, BEFORE any file write: seq is a
    # process-local counter and dig hashes the (supposedly mirrored)
    # manifest — if the replicated-driver invariant was ever violated,
    # hosts would diverge here, every os.replace would still succeed,
    # and the previous good checkpoint would be overwritten by a set
    # restore rejects as mixed-epoch (losing BOTH).  Abort loudly with
    # the prior files untouched instead.
    all_ep = np.asarray(mhu.process_allgather(epoch))
    if not (all_ep == all_ep[0]).all():
        raise CheckpointFormatError(
            "checkpoint aborted before writing: hosts disagree on the "
            f"checkpoint epoch {all_ep.tolist()} (divergent checkpoint "
            "counts or manifests — the replicated-driver invariant is "
            "broken); the previous checkpoint is left intact")
    shard_arrays = dict(
        pool=_local_block(dsm.pool),
        locks=_local_block(dsm.locks),
        counters=_local_block(dsm.counters),
        nodes=np.asarray(list(dsm.local_nodes), np.int64),
        epoch=epoch,
    )
    shard_arrays["integrity"] = _integrity(shard_arrays)
    _savez_atomic(f"{path}.host{me}.npz", me, **shard_arrays)
    man_arrays = dict(
        multihost=np.asarray([jax.process_count()], np.int64),
        epoch=epoch, **man)
    man_arrays["integrity"] = _integrity(man_arrays)
    _savez_atomic(path, me, **man_arrays)
    _OBS_FULL_SAVES.inc()
    cluster.keeper.barrier("checkpoint")


def make_epoch(man: dict, seq: int, nonce: int | None = None) -> np.ndarray:
    """The (nonce, seq, manifest-crc) epoch triple pairing shard files
    with their manifest — ONE construction shared by the collective
    checkpoint save and the offline resharder (utils/reshard.py), so
    emitted checkpoints always satisfy restore's pairing rules.  int32
    throughout: restore allgathers the epoch under jax's x64-disabled
    canonicalization (see the save path's comment)."""
    import zlib
    dig = zlib.crc32(b"".join(np.ascontiguousarray(v).tobytes()
                              for v in man.values()))
    if nonce is None:
        nonce = int(np.frombuffer(os.urandom(4), np.int32)[0])
    return np.asarray([nonce, seq, np.uint32(dig).view(np.int32)], np.int32)


def _sweep_tmp_orphans(path: str) -> int:
    """Remove ``<path>.tmp*.npz`` leftovers from a writer that crashed
    mid-:func:`_savez_atomic` (before its os.replace).  Returns the
    count removed.  Safe by construction: a live writer's tmp file only
    exists inside its own _savez_atomic call, which sweeps BEFORE
    creating it; concurrent writers to one path are already excluded by
    the single-saver contract."""
    n = 0
    for orphan in glob.glob(glob.escape(path) + ".tmp*.npz"):
        try:
            os.unlink(orphan)
            n += 1
        except OSError:
            pass  # raced with another sweeper: gone either way
    if n:
        _OBS_ORPHANS.inc(n)
    return n


def _savez_atomic(path: str, tag: int, **arrays) -> None:
    """np.savez_compressed via tmp + fsync + atomic replace + directory
    fsync: a crash mid-write never clobbers an existing checkpoint file,
    and a completed save survives power loss (the data AND the rename
    are both on disk before return).  Stale tmp orphans from a previous
    crash are swept first."""
    _sweep_tmp_orphans(path)
    tmp = f"{path}.tmp{tag}.npz"
    with open(tmp, "wb") as f:
        np.savez_compressed(f, **arrays)
        f.flush()
        _fsync(f.fileno())
    os.replace(tmp, path)
    dfd = os.open(os.path.dirname(os.path.abspath(path)), os.O_RDONLY)
    try:
        _fsync(dfd)
    finally:
        os.close(dfd)


def _integrity(arrays: dict) -> np.ndarray:
    """Per-array content CRCs, stored alongside the arrays so restore
    detects corruption instead of serving it (npz member checksums
    cover the compressed stream; this covers the decoded content, one
    named CRC per array — a typed CheckpointCorruptError names the
    damaged array)."""
    crcs = {k: int(np.uint32(zlib.crc32(
        np.ascontiguousarray(v).tobytes())))
        for k, v in arrays.items()}
    return np.frombuffer(json.dumps(crcs).encode(), np.uint8).copy()


def _verify_integrity(arrays: dict, path: str) -> None:
    """Check every loaded array against the artifact's stored CRC map
    (legacy artifacts without one pass — integrity is opt-out only by
    age).  Raises :class:`CheckpointCorruptError` naming the array."""
    blob = arrays.get("integrity")
    if blob is None:
        return
    try:
        crcs = json.loads(bytes(np.asarray(blob)).decode())
    except Exception as e:
        raise CheckpointCorruptError(
            f"{path}: unreadable integrity map ({e})") from e
    for k, v in arrays.items():
        if k == "integrity" or k not in crcs:
            continue
        got = int(np.uint32(zlib.crc32(np.ascontiguousarray(v).tobytes())))
        if got != int(crcs[k]):
            raise CheckpointCorruptError(
                f"{path}: array {k!r} failed its content CRC "
                f"({got:#x} != stored {int(crcs[k]):#x}) — the artifact "
                "is corrupt; restore from another chain link")


def _load_arrays(path: str, keys=None) -> dict:
    """np.load + materialize (+ CRC verify) with typed failure: any
    unreadable/torn/corrupt artifact surfaces as
    :class:`CheckpointCorruptError`, never a stack of zipfile/zlib
    internals half-way through a restore."""
    try:
        with np.load(path) as z:
            names = z.files if keys is None else \
                [k for k in z.files if k in set(keys) | {"integrity"}]
            out = {k: np.asarray(z[k]) for k in names}
    except CheckpointCorruptError:
        raise
    except Exception as e:
        raise CheckpointCorruptError(
            f"{path}: unreadable checkpoint artifact "
            f"({type(e).__name__}: {e})") from e
    _verify_integrity(out, path)
    return out


# The manifest schema (one source of truth: _manifest() must emit exactly
# these keys; _restore_multihost materializes exactly these + extras).
_MANIFEST_FIELDS = ("cfg", "dir_nodes", "dir_next", "dir_root", "dir_free")


def _manifest(cluster) -> dict:
    """Config + directory/allocator state — the part of a checkpoint that
    is host-independent (mirrored on every process in multi-host).
    ``dir_free`` carries each allocator's reclaimed-page pool as packed
    addresses (reclaim_empty_leaves output): those pages sit below the
    bump high-water mark with nonzero versions, so without this field a
    restore would permanently re-leak everything reclamation freed."""
    from sherman_tpu.ops import bits as _bits
    free = []
    for d in cluster.directories:
        free += [_bits.make_addr(d.node_id, p) & 0xFFFFFFFF
                 for p in d.allocator.free_pages_list]
    out = dict(
        cfg=np.frombuffer(cfg_to_json(cluster.cfg), np.uint8),
        dir_nodes=np.asarray([d.node_id for d in cluster.directories],
                             np.int64),
        dir_next=np.asarray(
            [d.allocator._next for d in cluster.directories], np.int64),
        dir_root=np.asarray(
            [[d.root_ptr, d.root_level] for d in cluster.directories],
            np.int64),
        dir_free=np.asarray(sorted(free), np.int64),
    )
    assert set(out) == set(_MANIFEST_FIELDS)
    return out


def restore(path: str, mesh=None, keeper=None, clear_locks: bool = True):
    """Rebuild a live Cluster from a checkpoint.  -> Cluster."""
    import jax

    from sherman_tpu.cluster import Cluster

    if not path.endswith(".npz") and not os.path.exists(path):
        path += ".npz"
    if keeper is not None and keeper.is_multihost:
        from sherman_tpu.utils import failure
        with failure.Watchdog.maybe(what="collective checkpoint restore"):
            return _restore_multihost(path, mesh, keeper, clear_locks)
    z = _load_arrays(path)
    if "delta" in z:
        raise CheckpointCorruptError(
            f"{path} is a DELTA artifact: restore its chain with "
            "restore_chain(base, deltas) — a delta alone holds only the "
            "pages written since its parent")
    cfg = cfg_from_json(z["cfg"])
    saved_mh = int(z["multihost"][0]) if "multihost" in z else 0
    if saved_mh != 0:  # durability check: must survive python -O
        raise CheckpointFormatError(
            "multi-host checkpoint needs a multi-host cluster (pass "
            "init_multihost()'s keeper on every host)")
    cluster = Cluster(cfg, mesh=mesh, keeper=keeper)
    dsm = cluster.dsm
    dsm.pool = jax.device_put(z["pool"], dsm.shard)
    locks = z["locks"]
    if clear_locks:
        locks = np.zeros_like(locks)
    dsm.locks = jax.device_put(locks, dsm.shard)
    dsm.counters = jax.device_put(z["counters"], dsm.shard)
    if dsm.heap is not None:
        if "heap" not in z:
            raise CheckpointFormatError(
                f"{path}: cfg configures a value heap "
                f"({cfg.heap_pages_per_node} pages/node) but the "
                "artifact carries no heap array")
        dsm.heap = jax.device_put(z["heap"], dsm.shard)
    _restore_directories(cluster, z)
    # flight event: a restore is the recovery step every drill's black
    # box must show after the degraded transition
    obs.record_event("checkpoint.restore", path=path)
    return cluster


def _restore_directories(cluster, man) -> None:
    """SET the directory/allocator state to the manifest's (replace, not
    merge: the free pool is cleared first, so chain restores can apply
    successive manifests without double-reclaiming pages)."""
    from sherman_tpu.ops import bits as _bits
    by_node = {int(n): i for i, n in enumerate(man["dir_nodes"])}
    free_by_node: dict[int, list[int]] = {}
    if "dir_free" in man:
        for a in np.asarray(man["dir_free"]).tolist():
            free_by_node.setdefault(_bits.addr_node(int(a)), []).append(
                _bits.addr_page(int(a)))
    for d in cluster.directories:
        i = by_node.get(d.node_id)
        if i is None:
            continue  # node had no directory in the saved cluster
        d.allocator._next = int(man["dir_next"][i])
        d.root_ptr = int(man["dir_root"][i][0])
        d.root_level = int(man["dir_root"][i][1])
        d.allocator._free.clear()
        if free_by_node.get(d.node_id):
            d.allocator.reclaim(free_by_node[d.node_id])


def _restore_multihost(path: str, mesh, keeper, clear_locks: bool):
    """Multi-host restore, COLLECTIVE-FIRST: every host resolves ALL its
    fallible local work (file loads, epoch pairing) into a status vector,
    every host allgathers it unconditionally, and only then asserts — a
    host-local failure before the collective would leave the other hosts
    hanging in it (or in the Cluster constructor's own collectives)
    instead of erroring cleanly everywhere."""
    import jax
    from jax.experimental import multihost_utils as mhu
    from jax.sharding import PartitionSpec

    from sherman_tpu.cluster import Cluster
    from sherman_tpu.parallel.mesh import AXIS

    me = jax.process_index()
    EW = 3  # epoch words; sentinel -1s for legacy/odd shapes
    man = shard = None
    err = ""
    # materialize only the manifest keys (the _manifest schema + the
    # multihost extras): a mistakenly-pointed-at single-host checkpoint
    # carries the full pool in its manifest file, and eagerly
    # decompressing gigabytes just to fail the host-count check below
    # would be wasteful
    man_keys = set(_MANIFEST_FIELDS) | {"multihost", "epoch"}
    try:
        # typed + CRC-verified loads (corruption surfaces here and rides
        # the status gather like any other host-local load failure)
        man = _load_arrays(path, keys=man_keys)
        shard = _load_arrays(f"{path}.host{me}.npz")
    except Exception as e:  # missing/torn/corrupt file: report via gather
        err = f"{type(e).__name__}: {e}"
    loads_ok = int(man is not None and shard is not None and "cfg" in man)
    pair_ok, saved_mh = 1, -1
    ep = np.full(EW, -1, np.int32)
    if loads_ok:
        saved_mh = int(man["multihost"][0]) if "multihost" in man else 0
        if ("epoch" in shard) != ("epoch" in man):
            pair_ok = 0  # mixed legacy/tagged files = torn pair
        elif "epoch" in shard:
            he = np.asarray(shard["epoch"]).ravel()
            ze = np.asarray(man["epoch"]).ravel()
            if he.shape != ze.shape or not (he == ze).all():
                pair_ok = 0
            else:
                ep[: min(EW, he.size)] = he[:EW].astype(np.int32)
    status = np.concatenate(
        [np.asarray([loads_ok, pair_ok, saved_mh], np.int32), ep])
    all_st = np.asarray(mhu.process_allgather(status))
    # durability-critical validation: explicit raises (a bare assert is
    # stripped under python -O and would silently restore torn state)
    if not (all_st[:, 0] == 1).all():
        raise CheckpointFormatError("a host failed to load its checkpoint files "
                           f"({err or 'other host'})")
    if not (all_st[:, 1] == 1).all():
        raise CheckpointFormatError(
            "a host holds a torn checkpoint (shard/manifest from different "
            "checkpoints or mixed legacy/tagged files)")
    if not (all_st[:, 2] == jax.process_count()).all():
        raise CheckpointFormatError(
            f"checkpoint host count {sorted(set(all_st[:, 2].tolist()))} != "
            f"{jax.process_count()} restoring processes")
    if not (all_st[:, 3:] == all_st[0, 3:]).all():
        raise CheckpointFormatError(
            "hosts hold checkpoints from different epochs (crashed "
            "mid-checkpoint?): refusing to mix")

    # all hosts validated: collectives are now safe
    cfg = cfg_from_json(man["cfg"])
    cluster = Cluster(cfg, mesh=mesh, keeper=keeper)
    dsm = cluster.dsm
    nodes_ok = int(list(shard["nodes"]) == list(dsm.local_nodes))
    all_nodes = np.asarray(mhu.process_allgather(
        np.asarray([nodes_ok], np.int32)))
    if not (all_nodes == 1).all():
        raise CheckpointFormatError("per-host node blocks changed since the "
                           "checkpoint")
    spec = PartitionSpec(AXIS)
    glob = lambda x: mhu.host_local_array_to_global_array(x, dsm.mesh, spec)
    dsm.pool = glob(shard["pool"])
    locks = shard["locks"]
    if clear_locks:
        locks = np.zeros_like(locks)
    dsm.locks = glob(locks)
    dsm.counters = glob(shard["counters"])
    _restore_directories(cluster, man)
    return cluster


# ---------------------------------------------------------------------------
# Incremental (delta) checkpoints — the recovery plane's cheap-frequent
# half (utils/journal.py is the replayable-log half; sherman_tpu/recovery.py
# coordinates both).  A delta saves only the pages written since the
# previous chain link (the DSM's dirty tracking: device-marked by the
# engine's write programs, host-marked at the DSM.step boundary), plus
# the full (tiny) locks/counters/manifest state, chained by the same
# (nonce, seq, crc) epoch machinery the multihost save uses: each delta
# records its parent's epoch, and restore refuses out-of-order or
# mixed-chain links.  Multihost meshes save per-host row-range deltas
# (PR 19) — each process's chain covers the rows it owns.
# ---------------------------------------------------------------------------

def checkpoint_delta(cluster, path: str, parent_epoch) -> dict:
    """Save a delta artifact chained onto ``parent_epoch`` (the epoch
    returned by the previous :func:`checkpoint` / :func:`checkpoint_delta`
    of this chain).  Clears the DSM's dirty tracking on success.
    Returns {"pages", "bytes", "epoch"}.

    Multihost meshes (PR 19): each process saves a delta of its OWN
    row range only — ``dirty_rows()`` is ownership-filtered and the
    page gather reads this process's addressable shards
    (``read_rows_local``, collective-free), so N hosts write N
    disjoint delta streams concurrently.  Restore is per-host too:
    each host's chain replays onto the rows it owns
    (``RecoveryPlane.recover_union``'s contract)."""
    if not path.endswith(".npz"):
        path += ".npz"
    if parent_epoch is None:
        raise ConfigError(
            "checkpoint_delta needs the parent artifact's epoch "
            "(returned by checkpoint()/checkpoint_delta())")
    import jax.numpy as jnp
    dsm = cluster.dsm
    rows = dsm.dirty_rows()
    man = _manifest(cluster)
    seq = cluster.keeper.mem_fetch_and_add("checkpoint_epoch")
    epoch = make_epoch(man, seq)
    # gather the dirty pages DEVICE-side: the d2h transfer is then
    # O(dirty pages) like the artifact, not O(pool) — at the 100 M-key
    # config a full-pool materialization would cost the whole 4.3 GB
    # device-to-host transfer per "cheap frequent delta".  Multihost: the
    # owned-shard gather (a global fancy-index would be a cross-host
    # collective inside a per-host save).
    if dsm.multihost:
        pages = dsm.read_rows_local(rows)
    else:
        pages = (np.asarray(dsm.pool[jnp.asarray(rows)]) if rows.size
                 else np.zeros((0, _C.PAGE_WORDS), np.int32))
    if dsm.multihost:
        # this process's lock/counter shards only (the full arrays
        # are not addressable here; the owner rows are what this
        # host's chain replays onto anyway)
        locks = np.concatenate([np.asarray(s.data) for s in
                                dsm.locks.addressable_shards])
        counters = np.concatenate([np.asarray(s.data) for s in
                                   dsm.counters.addressable_shards])
    else:
        locks = np.asarray(dsm.locks)
        counters = np.asarray(dsm.counters)
    arrays = dict(
        delta=np.asarray([1], np.int64),
        parent_epoch=np.asarray(parent_epoch, np.int32).ravel(),
        epoch=epoch,
        delta_rows=rows.astype(np.int64),
        delta_pages=pages,
        locks=locks,
        counters=counters,
        **man,
    )
    # value-heap dirty rows ride the same link (optional arrays —
    # heap-off deltas are byte-compatible with pre-heap builds)
    if dsm.heap is not None:
        hrows = dsm.heap_dirty_rows()
        arrays["heap_rows"] = hrows.astype(np.int64)
        if dsm.multihost:
            arrays["heap_pages"] = dsm.read_rows_local(hrows, "heap")
        else:
            arrays["heap_pages"] = (
                np.asarray(dsm.heap[jnp.asarray(hrows)]) if hrows.size
                else np.zeros((0, _C.PAGE_WORDS), np.int32))
    arrays["integrity"] = _integrity(arrays)
    _savez_atomic(path, 0, **arrays)
    dsm.clear_dirty()
    _OBS_DELTA_SAVES.inc()
    _OBS_DELTA_PAGES.inc(int(rows.size))
    size = os.path.getsize(path)
    _OBS_DELTA_BYTES.inc(size)
    return {"pages": int(rows.size), "bytes": int(size), "epoch": epoch}


def _check_delta_link(z: dict, path: str, base_cfg_raw: bytes,
                      prev_epoch, n_rows_max: int) -> None:
    """Chain-pairing + sanity rules for one delta artifact."""
    if "delta" not in z:
        raise CheckpointCorruptError(
            f"{path}: not a delta artifact (chain links after the base "
            "must be checkpoint_delta outputs)")
    if bytes(np.asarray(z["cfg"])) != base_cfg_raw:
        raise CheckpointCorruptError(
            f"{path}: delta cfg does not match the chain's base cfg — "
            "links from different clusters cannot be mixed")
    pe = np.asarray(z["parent_epoch"]).ravel()
    prev = np.asarray(prev_epoch).ravel()
    if pe.shape != prev.shape or not (pe == prev).all():
        raise CheckpointCorruptError(
            f"{path}: parent epoch {pe.tolist()} does not pair with the "
            f"previous chain link's epoch {prev.tolist()} (wrong order, "
            "a skipped link, or artifacts from different chains)")
    rows = np.asarray(z["delta_rows"])
    pages = np.asarray(z["delta_pages"])
    if rows.ndim != 1 or pages.shape != (rows.size, _C.PAGE_WORDS):
        raise CheckpointCorruptError(
            f"{path}: delta rows/pages shape mismatch "
            f"({rows.shape} vs {pages.shape})")
    if rows.size and (rows.min() < 0 or rows.max() >= n_rows_max):
        raise CheckpointCorruptError(
            f"{path}: delta rows outside the pool "
            f"[0, {n_rows_max}) — corrupt row index")


def restore_chain(base_path: str, delta_paths, mesh=None,
                  clear_locks: bool = True):
    """Rebuild a live Cluster from ``base`` + ordered delta artifacts.

    Every artifact is CRC-verified and the (nonce, seq, crc) epoch chain
    is checked link by link — a corrupted, reordered or foreign link
    raises :class:`CheckpointCorruptError` instead of materializing a
    silently wrong pool.  The LAST link's locks/counters/allocator
    manifest win (each link carries the full small state).
    -> Cluster."""
    import jax
    import jax.numpy as jnp

    cluster = restore(base_path, mesh=mesh, clear_locks=clear_locks)
    if not delta_paths:
        return cluster
    dsm = cluster.dsm
    base = _load_arrays(base_path, keys=("cfg", "epoch"))
    if "epoch" not in base:
        raise CheckpointCorruptError(
            f"{base_path}: base carries no epoch (pre-chain legacy "
            "checkpoint) — take a fresh base to start a delta chain")
    base_cfg_raw = bytes(np.asarray(base["cfg"]))
    prev_epoch = np.asarray(base["epoch"])
    n_rows = dsm.pool.shape[0]
    for path in delta_paths:
        z = _load_arrays(path)
        _check_delta_link(z, path, base_cfg_raw, prev_epoch, n_rows)
        rows = np.asarray(z["delta_rows"], np.int64)
        if rows.size:
            dsm.pool = jax.device_put(
                dsm.pool.at[jnp.asarray(rows)].set(
                    jnp.asarray(z["delta_pages"])), dsm.shard)
        if dsm.heap is not None and "heap_rows" in z:
            hrows = np.asarray(z["heap_rows"], np.int64)
            if hrows.size:
                hpages = np.asarray(z["heap_pages"])
                if hpages.shape != (hrows.size, _C.PAGE_WORDS) \
                        or hrows.min() < 0 \
                        or hrows.max() >= dsm.heap.shape[0]:
                    raise CheckpointCorruptError(
                        f"{path}: heap delta rows/pages shape mismatch "
                        "or rows outside the heap region")
                dsm.heap = jax.device_put(
                    dsm.heap.at[jnp.asarray(hrows)].set(
                        jnp.asarray(hpages)), dsm.shard)
        locks = np.asarray(z["locks"])
        if clear_locks:
            locks = np.zeros_like(locks)
        dsm.locks = jax.device_put(locks, dsm.shard)
        dsm.counters = jax.device_put(np.asarray(z["counters"]), dsm.shard)
        _restore_directories(cluster, z)
        prev_epoch = np.asarray(z["epoch"])
    # restored state predates the crash-lost dirty tracking: callers
    # start a fresh chain (RecoveryPlane re-bases after replay)
    dsm.clear_dirty()
    return cluster


def read_chain_rows(base_path: str, delta_paths, rows) -> np.ndarray:
    """Reconstruct the CONTENT of specific pool rows as of the chain's
    tip, without materializing a cluster: the latest link containing a
    row wins, the base covers everything else.  The targeted-repair
    primitive (sherman_tpu/recovery.py): recovery cost scales with the
    damage, not the pool.  -> pages [len(rows), PAGE_WORDS] int32."""
    rows = np.asarray(rows, np.int64)
    base = _load_arrays(base_path)
    if "delta" in base:
        raise CheckpointCorruptError(
            f"{base_path}: chain base must be a full checkpoint")
    pool = np.asarray(base["pool"])
    if rows.size and (rows.min() < 0 or rows.max() >= pool.shape[0]):
        raise CheckpointCorruptError(
            f"repair rows outside the pool [0, {pool.shape[0]})")
    out = pool[rows].copy()
    base_cfg_raw = bytes(np.asarray(base["cfg"]))
    prev_epoch = np.asarray(base["epoch"]) if "epoch" in base else None
    for path in delta_paths:
        z = _load_arrays(path, keys=("delta", "cfg", "epoch",
                                     "parent_epoch", "delta_rows",
                                     "delta_pages"))
        if prev_epoch is None:
            raise CheckpointCorruptError(
                f"{base_path}: base carries no epoch to chain from")
        _check_delta_link(z, path, base_cfg_raw, prev_epoch,
                          pool.shape[0])
        drows = np.asarray(z["delta_rows"], np.int64)
        dpages = np.asarray(z["delta_pages"])
        pos = {int(r): i for i, r in enumerate(drows)}
        for i, r in enumerate(rows.tolist()):
            j = pos.get(int(r))
            if j is not None:
                out[i] = dpages[j]
        prev_epoch = np.asarray(z["epoch"])
    return out
