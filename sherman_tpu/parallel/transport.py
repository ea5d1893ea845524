"""Request routing over the ICI mesh: the RDMA-fabric analogue.

Where the reference posts verbs on per-destination RC queue pairs
(``ThreadConnection.cpp:21-27``, ``src/rdma/Operation.cpp``), we route a
fixed-capacity batch of requests per step with one ``all_to_all`` exchange:
each node scatters its requests into per-destination buckets of capacity
``C``; one tiled all_to_all delivers every bucket to its owner; replies ride
the reverse exchange.  A request's slot is its rank among the requests bound
for the same node, in request order, found by a linear scan and no search
(:func:`bucketize`): a running count per node on small meshes, a stable sort
and a running max over its run heads on large ones.  Requests beyond a
bucket's capacity are dropped with ``ok=0`` and retried by the caller — the
moral equivalent of a full RDMA send queue.

All helpers run *inside* ``shard_map`` on per-node shards.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from sherman_tpu import obs

# Collective-issue accounting.  ``exchange`` executes INSIDE compiled
# SPMD programs, so a per-execution host counter is impossible without
# round-tripping device state; what IS observable host-side is each
# exchange issued during program tracing.  The counters therefore mean:
# one inc per collective issued per program BUILD (recompiles included),
# with ``bytes`` the per-node payload that collective moves on every
# execution of that program.  Executed-op truth stays with the DSM's
# device counters ("dsm.*" in the registry snapshot).
_OBS_XCH_ISSUES = obs.counter("transport.exchange_issues_traced")
_OBS_XCH_BYTES = obs.counter("transport.exchange_bytes_per_step")
_OBS_XCH_PALLAS = obs.counter("transport.pallas_exchange_issues_traced")
_OBS_AG_ISSUES = obs.counter("transport.allgather_issues_traced")
_OBS_AG_BYTES = obs.counter("transport.allgather_bytes_per_step")


def _tree_nbytes(tree) -> int:
    return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(tree))


#: Up to this many nodes a request's rank is a running count over an
#: [n_nodes, R] one-hot, O(n_nodes * R) with no sort; past it, a stable
#: sort and one running max, O(R log R) whatever the node count.
_ONE_HOT_NODES = 8


def bucketize(dest, active, n_nodes: int, capacity: int):
    """Assign each request a slot in its destination bucket.

    A request's slot is its rank among the active requests bound for
    the same node, in request order.  Up to :data:`_ONE_HOT_NODES`
    nodes the rank is the running count of the request's node over an
    ``[n_nodes, R]`` one-hot (``cumsum`` along R, the minor axis), with
    no sort.  Past that the requests are stably sorted by destination,
    a request's rank is its sorted position minus where its run of equal
    destinations starts, and one running max over the run heads
    (``lax.cummax``) finds every run start.  Both are linear scans, not
    a search, and give the same slots.  Requests ranked at or past
    ``capacity`` are not routed.

    Args:
      dest: [R] int32 destination node per request.
      active: [R] bool; inactive requests are never routed.
      n_nodes, capacity: static bucket geometry.

    Returns:
      (bucket_idx[R] int32 in [0, n_nodes*capacity) or -1,
       routed[R] bool).
    """
    R = dest.shape[0]
    with jax.named_scope("bucketize"):
        d = jnp.where(active, dest, n_nodes).astype(jnp.int32)
        if n_nodes <= _ONE_HOT_NODES:
            hot = d[None, :] == jnp.arange(n_nodes, dtype=jnp.int32)[:, None]
            count = jnp.cumsum(hot, axis=1, dtype=jnp.int32)
            rank = jnp.sum(jnp.where(hot, count, 0), axis=0) - 1
            ok = (d < n_nodes) & (rank < capacity)
            bucket_idx = jnp.where(ok, d * capacity + rank, -1)
        else:
            pos = jnp.arange(R, dtype=jnp.int32)
            sd, perm = jax.lax.sort((d, pos), num_keys=1, is_stable=True)
            head = (pos == 0) | (sd != jnp.roll(sd, 1))
            rank = pos - jax.lax.cummax(jnp.where(head, pos, 0))
            ok = (sd < n_nodes) & (rank < capacity)
            bidx = jnp.where(ok, sd * capacity + rank, -1)
            bucket_idx = jnp.zeros(R, jnp.int32).at[perm].set(bidx)
    return bucket_idx, bucket_idx >= 0


def scatter_to_buckets(field, bucket_idx, n_slots: int):
    """Place request fields [R, ...] into bucket slots [n_slots, ...]."""
    safe = jnp.where(bucket_idx >= 0, bucket_idx, n_slots)
    out = jnp.zeros((n_slots,) + field.shape[1:], field.dtype)
    return out.at[safe].set(field, mode="drop")


def gather_rows(x, axis_name: str):
    """Tiled ``all_gather`` of ``x`` along dim 0 — the reply-side
    answer-table broadcast shared by every fan-out kernel (the engine's
    combined-search fan-out and the device-staged serve/mixed serve):
    each node contributes its local row block, every node receives the
    full table, and client slots gather from GLOBAL row indices.

    One helper so collective PLACEMENT is a single code site: the
    all-gather always runs AFTER the descent/stack (on the packed [U, 4]
    answer lanes, never on the raw descent outputs — 4 int32 words/row
    is the minimal reply payload) and before the per-client take.
    Traced-issue accounting follows :func:`exchange`'s convention: one
    inc per collective per program BUILD, bytes = the per-step GLOBAL
    payload every node receives."""
    n = jax.lax.axis_size(axis_name)
    _OBS_AG_ISSUES.inc()
    _OBS_AG_BYTES.inc(int(x.size) * x.dtype.itemsize * int(n))
    return jax.lax.all_gather(x, axis_name, axis=0, tiled=True)


def exchange(tree, axis_name: str, *, impl: str = "xla"):
    """Tiled all_to_all of every array in the pytree along dim 0.

    impl="xla" (default): one XLA all_to_all per array — compiler-
    scheduled over ICI.  impl="pallas": the whole pytree packed into one
    buffer of explicit per-peer one-sided remote-DMA writes
    (:mod:`transport_pallas`) — the literal RDMA-verbs analogue;
    interpreter-mode on CPU meshes.
    """
    if impl == "pallas":
        from sherman_tpu.parallel import transport_pallas
        _OBS_XCH_PALLAS.inc()
        _OBS_XCH_BYTES.inc(_tree_nbytes(tree))
        return transport_pallas.exchange(
            tree, axis_name, jax.lax.axis_size(axis_name))
    _OBS_XCH_ISSUES.inc(len(jax.tree.leaves(tree)))
    _OBS_XCH_BYTES.inc(_tree_nbytes(tree))
    return jax.tree.map(
        lambda x: jax.lax.all_to_all(x, axis_name, 0, 0, tiled=True), tree
    )
