"""Pallas ICI remote-DMA exchange — the explicit RDMA-verbs data plane.

The default transport (:mod:`sherman_tpu.parallel.transport`) routes request
buckets with one XLA ``all_to_all`` — idiomatic, compiler-scheduled.  This
module is the hand-rolled equivalent the reference's verb layer maps to most
literally (``src/rdma/Operation.cpp``): each node posts ONE one-sided remote
write per peer (``pltpu.make_async_remote_copy`` over ICI), with DMA
semaphores as the completion queue.  Per step and per peer:

- bucket ``p`` of the local request array is pushed straight into bucket
  ``my_id`` of peer ``p``'s incoming array (a one-sided RDMA WRITE with
  rkey/addr replaced by the SPMD-symmetric ref + row slice);
- all N-1 pushes start before any wait (the doorbell batch: full bisection
  bandwidth, no serialization on a ring);
- ``descriptor.wait()`` drains send + receive semaphores (CQ polling,
  ``pollWithCQ`` role, Operation.cpp:3-43).

Parity/selection: ``DSMConfig.exchange_impl = "xla" | "pallas"`` switches
the DSM step's exchanges.  The Pallas path is validated in interpreter mode
on the virtual CPU mesh (tests); the XLA path remains the default.  The
pre-post cluster barrier (``use_barrier``) cannot run in the interpreter
(it cannot lower ``get_barrier_semaphore`` and runs devices sequentially);
the compiled form, barrier included, is compiled for a described 4-chip
v5e in ``tests/test_tpu_compile.py``,
and run on four chips by ``chip_smoke.py --chips 4``.

Layout contract (same as ``transport.exchange`` with tiled all_to_all):
arrays are ``[N * C, ...]`` per node — row block ``d*C:(d+1)*C`` is the
bucket for/from peer ``d``.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

try:  # pallas is TPU-oriented; CPU uses interpreter mode
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    HAVE_PALLAS = True
except Exception:  # pragma: no cover
    HAVE_PALLAS = False

from sherman_tpu import obs
from sherman_tpu.ops.pallas_page import PallasUnavailableError, interpret_mode
from sherman_tpu.errors import ShermanError


class ExchangeLaneError(ShermanError, TypeError):
    """Typed, actionable: a request field cannot ride the packed 32-bit
    exchange buffer.  Names the knob whose default path has no such
    constraint."""

    def __init__(self, dtype):
        super().__init__(
            f"pallas exchange carries 32-bit lanes; got {dtype} — widen "
            "the field to a 32-bit dtype (bools and any 4-byte dtype "
            "travel bit-exactly) or set DSMConfig.exchange_impl=\"xla\" "
            "(the default all_to_all transport, which has no lane-width "
            "constraint)")
        self.dtype = dtype


# Traced-issue accounting (see transport.py for the trace-time
# semantics): per kernel BUILD, the number of one-sided remote writes
# it posts per execution and the packed payload bytes it moves.
_OBS_REMOTE_WRITES = obs.counter("transport.pallas_remote_writes_traced")
_OBS_PACKED_BYTES = obs.counter("transport.pallas_packed_bytes_per_step")

def _collective_id(n_nodes: int, rows: int, width: int) -> int:
    """Barrier-semaphore key, distinct per program shape family.

    Two pallas programs sharing a collective_id share a barrier
    semaphore and could cross-credit if the runtime ever overlapped
    them; deriving the id from (n_nodes, rows_per_peer, width) gives
    each compiled exchange shape its own semaphore.  A hash collision
    degrades to the shared-semaphore case, which is still safe under
    the TPU runtime's in-launch-order execution of collectives — the
    same contract a single fixed id relied on for ALL families."""
    return 11 + (n_nodes * 7919 + rows * 131 + width) % 4093


def _exchange_kernel(x_ref, out_ref, send_sem, recv_sem, *, n_nodes: int,
                     axis_name: str, use_barrier: bool):
    """All-to-all of per-peer blocks via N-1 one-sided remote writes.
    ``x_ref``/``out_ref`` are [N, K, 128]: block ``d`` is the payload
    for/from peer ``d``, whole (8, 128) tiles, so every DMA is
    tile-aligned."""
    my = jax.lax.axis_index(axis_name)

    # Cluster barrier BEFORE posting any one-sided write: without it a
    # fast device can race ahead into the NEXT exchange kernel and its
    # remote writes could credit a slow peer's still-pending recv
    # semaphores from THIS kernel (scratch semaphore slots are reused
    # across calls).  Keyed by compiler_params.collective_id.  The
    # interpreter runs devices sequentially (no such race) and cannot
    # lower get_barrier_semaphore, so compiled runs only.
    if use_barrier:
        bar = pltpu.get_barrier_semaphore()
        for k in range(1, n_nodes):
            pltpu.semaphore_signal(
                bar, inc=1, device_id=jax.lax.rem(my + k, n_nodes),
                device_id_type=pltpu.DeviceIdType.LOGICAL)
        pltpu.semaphore_wait(bar, n_nodes - 1)

    # local bucket: plain local DMA (no network)
    local = pltpu.make_async_copy(x_ref.at[my], out_ref.at[my],
                                  send_sem.at[0])
    local.start()

    # post every remote write first (doorbell batch), then wait all.
    # step-indexed semaphore slots keep sender/receiver symmetric: my
    # step-k push signals the receiver's recv_sem[k], and the step-k
    # push ARRIVING here (from (my - k) % N) signals mine.
    rdmas = []
    for k in range(1, n_nodes):
        peer = jax.lax.rem(my + k, n_nodes)
        rdma = pltpu.make_async_remote_copy(
            src_ref=x_ref.at[peer],
            dst_ref=out_ref.at[my],
            send_sem=send_sem.at[k],
            recv_sem=recv_sem.at[k],
            device_id=peer,
            device_id_type=pltpu.DeviceIdType.LOGICAL,
        )
        rdma.start()
        rdmas.append(rdma)

    local.wait()
    for rdma in rdmas:
        rdma.wait()


def exchange_pallas(x, axis_name: str, n_nodes: int, *,
                    interpret: bool | None = None):
    """Pallas remote-DMA all_to_all of one [N*C, W] int32 array.

    Call inside shard_map on per-node shards.  Equivalent to
    ``lax.all_to_all(x, axis_name, 0, 0, tiled=True)``.  Each peer's
    C*W words travel as a lane-dense [K, 128] block (zero-padded to
    whole tiles), whatever W is.
    """
    if not HAVE_PALLAS:
        raise PallasUnavailableError("DSMConfig.exchange_impl")
    interpret = interpret_mode(interpret)
    rows = x.shape[0]
    assert rows % n_nodes == 0
    C = rows // n_nodes
    words = C * math.prod(x.shape[1:])
    tile = 8 * 128
    padded = -(-words // tile) * tile
    _OBS_REMOTE_WRITES.inc(n_nodes - 1)
    _OBS_PACKED_BYTES.inc(x.size * x.dtype.itemsize)
    blocks = jnp.pad(x.reshape(n_nodes, words),
                     ((0, 0), (0, padded - words)))
    blocks = blocks.reshape(n_nodes, padded // 128, 128)
    kernel = functools.partial(
        _exchange_kernel, n_nodes=n_nodes, axis_name=axis_name,
        use_barrier=not interpret)
    out = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct(blocks.shape, x.dtype),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        scratch_shapes=[pltpu.SemaphoreType.DMA((n_nodes,)),
                        pltpu.SemaphoreType.DMA((n_nodes,))],
        compiler_params=pltpu.CompilerParams(
            collective_id=_collective_id(
                n_nodes, C, math.prod(x.shape[1:]))),
        interpret=interpret,
    )(blocks)
    return out.reshape(n_nodes, padded)[:, :words].reshape(x.shape)


def exchange(tree, axis_name: str, n_nodes: int, *,
             interpret: bool | None = None):
    """Drop-in for ``transport.exchange``: the whole pytree is packed into
    ONE [N*C, sum(W)] int32 buffer and rides one kernel — one barrier and
    N-1 posted writes per step, however many request fields there are.

    Bools widen to int32; other 32-bit dtypes travel BIT-EXACTLY via
    bitcast (a value cast would corrupt floats); anything else is
    rejected rather than silently truncated.
    """
    leaves, treedef = jax.tree.flatten(tree)
    assert leaves, "empty exchange"
    rows = leaves[0].shape[0]

    def to_i32(x):
        dt = x.dtype
        if dt == jnp.bool_:
            x2 = x.astype(jnp.int32)
        elif dt == jnp.int32:
            x2 = x
        elif x.dtype.itemsize == 4:
            x2 = jax.lax.bitcast_convert_type(x, jnp.int32)
        else:
            raise ExchangeLaneError(dt)
        assert x2.shape[0] == rows, "exchange arrays must share dim 0"
        return x2.reshape(rows, -1)

    cols = [to_i32(x) for x in leaves]
    widths = [c.shape[1] for c in cols]
    packed = cols[0] if len(cols) == 1 else jnp.concatenate(cols, axis=1)
    out = exchange_pallas(packed, axis_name, n_nodes, interpret=interpret)

    outs = []
    off = 0
    for x, w in zip(leaves, widths):
        piece = out[:, off:off + w].reshape(x.shape)
        off += w
        if x.dtype == jnp.bool_:
            piece = piece.astype(jnp.bool_)
        elif x.dtype != jnp.int32:
            piece = jax.lax.bitcast_convert_type(piece, x.dtype)
        outs.append(piece)
    return jax.tree.unflatten(treedef, outs)
