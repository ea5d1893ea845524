"""Pallas page-engine kernels: the explicit-DMA data plane for the pool.

The TPU twins of the reference's one-sided READ descent loop
(``Tree.cpp:429-458``) and single-entry write-back (``Tree.cpp:914-921``),
the HBM<->VMEM complement of :mod:`~sherman_tpu.parallel.transport_pallas`'s
inter-chip lane:

1. :func:`descent_round` — the FUSED descent round: each row's page is
   streamed HBM->VMEM with double-buffered ``make_async_copy`` chunks
   (the next chunk's DMAs fly while the previous chunk's in-page
   search/child-pick runs on the VPU), and only the next-level address +
   leaf verdicts leave the kernel — no ``[B, PAGE_WORDS]`` intermediate
   is materialized in HBM between the gather and the pick.
2. :func:`writeback` — the multi-lane write-back: all 3-5 word lanes of
   an applied entry land in ONE read-modify-write of the page's tile.
3. :func:`gather_pages` — the snapshot gather for the apply path's
   one-page-many-consumers read (``leaf_apply_spmd``'s page snapshot),
   page DMAs with an ``N_INFLIGHT``-deep ring.

Tile rule (Mosaic on v5e): a DMA slice of the ``(P, 256)`` int32 pool
in HBM must cover whole ``(8, 128)`` tiles, so a page travels as its
aligned 8-page GROUP (8 KB) and the kernel picks the page's row out of
VMEM; the write-back rewrites the whole group.  That is 8x the bytes of
a one-page copy, which is why ``"xla"`` stays the default.  Per-row
scalars (DMA targets) ride 1-D SMEM blocks of ``BLOCK`` rows (XLA tiles
an int32 vector by 1024); per-row vectors ride lane-dense
``(rows // 128, 128)`` blocks, one 128-row chunk per sublane row.  The
pool needs a multiple of ``GROUP`` pages.

Selection: ``DSMConfig.gather_impl = "xla" | "pallas"`` (mirroring
``exchange_impl``); wrappers raise :class:`PallasUnavailableError` naming
the knob when the toolchain is absent.

Parity contract: every kernel is BIT-IDENTICAL to its ``*_xla`` twin
(which mirrors the inline code in ``models/batched.py`` /
``parallel/dsm.py``) on ANY inputs — including garbage pages — pinned
by the interpreter-mode fuzz in ``tests/test_pallas_page.py``.  The one
exception is :func:`writeback`: rows with ``applied`` must carry
in-range (page, word) targets, which the apply kernels guarantee by
construction (clipped pages, found/ranked slots).

Kernels run in INTERPRETER mode off-TPU (the CPU test mesh) and never
on a TPU; ``tests/test_tpu_compile.py`` compiles them for a described
v5e at real widths.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from sherman_tpu.errors import ConfigError, ShermanError
from sherman_tpu import config as C
from sherman_tpu import obs
from sherman_tpu.ops import bits, layout

try:  # pallas is TPU-oriented; CPU uses interpreter mode
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    HAVE_PALLAS = True
except Exception:  # pragma: no cover
    HAVE_PALLAS = False

_PW = C.PAGE_WORDS

# Rows per grid program: the SMEM scalar blocks must match XLA's T(1024)
# tiling of a 1-D int32 array.
BLOCK = 1024
# Rows per descent chunk: one lane-dense row of the per-row I/O blocks.
# The next chunk's page DMAs are posted before this chunk is searched.
CHUNK = 128
# Pages per HBM DMA: one (8, 128)-tile row group of the pool.
GROUP = 8
# In-flight page-group DMAs of the snapshot gather ring.
N_INFLIGHT = 16

# Traced-issue accounting (transport.py convention: one inc per program
# BUILD; per-execution truth stays with the dsm.* device counters).
_OBS_DESCENT = obs.counter("kernels.descent_rounds_traced")
_OBS_DESCENT_ROWS = obs.counter("kernels.descent_rows_per_round")
_OBS_SNAP = obs.counter("kernels.snapshot_gathers_traced")
_OBS_SNAP_ROWS = obs.counter("kernels.snapshot_rows_per_gather")
_OBS_WB = obs.counter("kernels.writeback_passes_traced")
_OBS_WB_ROWS = obs.counter("kernels.writeback_rows_per_pass")
_OBS_WB_LANES = obs.counter("kernels.writeback_lanes_traced")


class PallasUnavailableError(ShermanError, RuntimeError):
    """Typed, actionable: the Pallas/Mosaic toolchain is missing but a
    config knob selected it.  Names the knob to flip back."""

    def __init__(self, knob: str):
        super().__init__(
            f"Pallas/Mosaic toolchain unavailable but {knob}=\"pallas\" "
            f"was requested: set {knob}=\"xla\" (the default, "
            "compiler-scheduled path) or install a jaxlib with Pallas "
            "TPU support")
        self.knob = knob


def available() -> bool:
    return HAVE_PALLAS


def use_pallas(cfg) -> bool:
    """True iff ``cfg.gather_impl == "pallas"``; raises the typed error
    (naming the knob) when that was requested without the toolchain."""
    if cfg.gather_impl != "pallas":
        return False
    if not HAVE_PALLAS:
        raise PallasUnavailableError("DSMConfig.gather_impl")
    return True


def interpret_mode(interpret: bool | None = None) -> bool:
    """Trace-time interpreter rule shared with ``transport.exchange``:
    interpreter everywhere but a TPU backend, and never on one — an
    explicit ``interpret=True`` there is refused, not obeyed."""
    on_tpu = jax.default_backend() == "tpu"
    if interpret is None:
        return not on_tpu
    if interpret and on_tpu:
        raise ConfigError("Pallas interpret mode requested on a TPU "
                         "backend: the kernels run compiled there")
    return interpret


def _pad_to_block(n: int) -> int:
    return -(-max(n, 1) // BLOCK) * BLOCK


def _pad1(x, n_pad, fill=0):
    n = x.shape[0]
    if n == n_pad:
        return x
    return jnp.concatenate(
        [x, jnp.full((n_pad - n,) + x.shape[1:], fill, x.dtype)])


def _check_pool(P: int) -> None:
    if P % GROUP:
        raise ConfigError(f"pallas page kernels need a pool of a multiple "
                         f"of {GROUP} pages, got {P}")


def _group_copy(pool_ref, pg, dst, sem):
    """DMA page ``pg``'s aligned 8-page group (whole HBM tiles) into
    ``dst`` [GROUP, PAGE_WORDS]."""
    g = pl.multiple_of(jnp.bitwise_and(pg, -GROUP), GROUP)
    return pltpu.make_async_copy(pool_ref.at[pl.ds(g, GROUP)], dst, sem)


def _row_to_col(row):
    """[1, CHUNK] lane row -> [CHUNK, 1] column (one XLU transpose)."""
    return jnp.broadcast_to(row, (CHUNK, CHUNK)).T[:, :1]


def _col_to_row(col):
    """[CHUNK, 1] column -> [1, CHUNK] lane row."""
    return jnp.broadcast_to(col, (CHUNK, CHUNK)).T[:1, :]


# ---------------------------------------------------------------------------
# In-kernel page search primitives — bit-exact twins of ops/layout.py on
# [R, PAGE_WORDS] pages with [R, 1] key columns (no captured constants).
# ---------------------------------------------------------------------------

def _w(pg, w):
    return pg[:, w:w + 1]


def _any_last(mask):
    return jnp.max(mask.astype(jnp.int32), axis=-1, keepdims=True) > 0


def _masked_sum(vals, mask):
    """XLA's wrapping int32 masked sum, keepdims."""
    return jnp.sum(jnp.where(mask, vals, 0), axis=-1, keepdims=True)


def _pick_child_k(pg, kh, kl):
    """``layout.internal_pick_child`` twin.  The le_next shift reads the
    entry blocks offset by one word (static slice) instead of
    concatenating, masked so column CAP-1 is always False — identical to
    the zero-padded shift on ALL inputs, garbage pages included."""
    ICAP = C.INTERNAL_CAP
    ekhi = pg[:, C.I_KHI_W:C.I_KHI_W + ICAP]
    eklo = pg[:, C.I_KLO_W:C.I_KLO_W + ICAP]
    n = _w(pg, C.W_NKEYS)
    iota = lax.broadcasted_iota(jnp.int32, ekhi.shape, 1)
    le = bits.key_le(ekhi, eklo, kh, kl) & (iota < n)
    ekhi1 = pg[:, C.I_KHI_W + 1:C.I_KHI_W + 1 + ICAP]
    eklo1 = pg[:, C.I_KLO_W + 1:C.I_KLO_W + 1 + ICAP]
    le_next = (bits.key_le(ekhi1, eklo1, kh, kl)
               & ((iota + 1) < n) & (iota < ICAP - 1))
    child = _masked_sum(pg[:, C.I_PTR_W:C.I_PTR_W + ICAP], le & ~le_next)
    return jnp.where(_any_last(le), child, _w(pg, C.W_LEFTMOST))


def _leaf_find_k(pg, kh, kl):
    """``layout.leaf_find_key`` twin (found, vhi, vlo)."""
    LCAP = C.LEAF_CAP
    fv, rv = layout.ver_unpack(pg[:, C.L_VER_W:C.L_VER_W + LCAP])
    used = (fv == rv) & (fv != 0)
    ekhi = pg[:, C.L_KHI_W:C.L_KHI_W + LCAP]
    eklo = pg[:, C.L_KLO_W:C.L_KLO_W + LCAP]
    hit = used & bits.key_eq(ekhi, eklo, kh, kl)
    return (_any_last(hit),
            _masked_sum(pg[:, C.L_VHI_W:C.L_VHI_W + LCAP], hit),
            _masked_sum(pg[:, C.L_VLO_W:C.L_VLO_W + LCAP], hit))


def _round_compute(pg, kh, kl, ok, stop_level: int):
    """One row-chunk's in-VMEM search: level/chase/child-pick/leaf-find
    on [R, PAGE_WORDS] pages, zeroed where not ok (the read_pages
    contract).  All operands and results are [R, 1] columns."""
    pg = jnp.where(ok, pg, 0)
    chase = ~bits.key_lt(kh, kl, _w(pg, C.W_HIGH_HI), _w(pg, C.W_HIGH_LO))
    is_leaf = (_w(pg, C.W_LEVEL) == stop_level) & ~chase
    nxt = jnp.where(chase, _w(pg, C.W_SIBLING), _pick_child_k(pg, kh, kl))
    f, vh, vl = _leaf_find_k(pg, kh, kl)
    return nxt, is_leaf, chase, f, vh, vl


# ---------------------------------------------------------------------------
# Kernel 1: fused descent round.
# ---------------------------------------------------------------------------

def _descent_kernel(addr_s, addr_ref, khi_ref, klo_ref, act_ref, pool_ref,
                    nxt_ref, leaf_ref, chase_ref, ok_ref, f_ref, vh_ref,
                    vl_ref, buf, pages, sems, *, n_pages: int,
                    stop_level: int):
    n_chunks = BLOCK // CHUNK

    def page_no(i):  # clipped exactly as the XLA gather clips
        return jnp.clip(addr_s[i] & C.ADDR_PAGE_MASK, 0, n_pages - 1)

    def chunk_dma(c, slot, start):
        # CHUNK group copies posted back-to-back on one semaphore; the
        # waits drain it copy by copy (all copies are the same size)
        def row(r, _):
            cp = _group_copy(pool_ref, page_no(c * CHUNK + r),
                             buf.at[slot, r], sems.at[slot])
            (cp.start if start else cp.wait)()
            return 0
        lax.fori_loop(0, CHUNK, row, 0)

    chunk_dma(0, 0, True)

    def body(c, _):
        slot = lax.rem(c, 2)

        @pl.when(c + 1 < n_chunks)
        def _():  # stream the NEXT chunk while this one is searched
            chunk_dma(c + 1, 1 - slot, True)

        chunk_dma(c, slot, False)

        def pick(r, _):  # the row's page out of its group
            sub = jnp.bitwise_and(page_no(c * CHUNK + r), GROUP - 1)
            pages[pl.ds(r, 1), :] = buf[slot, r, pl.ds(sub, 1), :]
            return 0
        lax.fori_loop(0, CHUNK, pick, 0)

        def col(ref):
            return _row_to_col(ref[pl.ds(c, 1), :])

        page_idx = col(addr_ref) & C.ADDR_PAGE_MASK
        ok = (col(act_ref) != 0) & (page_idx >= 0) & (page_idx < n_pages)
        outs = (ok,) + _round_compute(pages[...], col(khi_ref),
                                      col(klo_ref), ok, stop_level)
        for ref, v in zip((ok_ref, nxt_ref, leaf_ref, chase_ref, f_ref,
                           vh_ref, vl_ref), outs):
            ref[pl.ds(c, 1), :] = _col_to_row(v.astype(jnp.int32))
        return 0

    lax.fori_loop(0, n_chunks, body, 0)


def descent_round(pool, addr, khi, klo, active, *, stop_level: int = 0,
                  interpret: bool | None = None):
    """One fused descent round over ``[B]`` rows.

    For each active row: stream its page HBM->VMEM (double-buffered
    CHUNK tiles), search it in VMEM, and emit ``(nxt, is_leaf, chase,
    ok, found, vhi, vlo)`` — next-level address, (level == stop_level
    and in fence), sibling-chase flag, page-read validity, and the leaf
    lookup verdicts.  Bit-identical to :func:`descent_round_xla` (the
    gather + ``ops/layout`` composition the XLA path runs) on any
    inputs.  Bool outputs return as bool arrays.
    """
    if not HAVE_PALLAS:
        raise PallasUnavailableError("DSMConfig.gather_impl")
    B = addr.shape[0]
    P = pool.shape[0]
    _check_pool(P)
    Bp = _pad_to_block(B)
    addr_p = _pad1(jnp.asarray(addr, jnp.int32), Bp)
    lanes = lambda x: _pad1(jnp.asarray(x, jnp.int32), Bp).reshape(-1, CHUNK)
    _OBS_DESCENT.inc()
    _OBS_DESCENT_ROWS.inc(B)

    vspec = lambda: pl.BlockSpec((BLOCK // CHUNK, CHUNK), lambda i: (i, 0))
    kern = functools.partial(_descent_kernel, n_pages=P,
                             stop_level=stop_level)
    sh = jax.ShapeDtypeStruct((Bp // CHUNK, CHUNK), jnp.int32)
    outs = pl.pallas_call(
        kern, out_shape=(sh,) * 7, grid=(Bp // BLOCK,),
        in_specs=[pl.BlockSpec((BLOCK,), lambda i: (i,),
                               memory_space=pltpu.SMEM),
                  vspec(), vspec(), vspec(), vspec(),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=tuple(vspec() for _ in range(7)),
        scratch_shapes=[pltpu.VMEM((2, CHUNK, GROUP, _PW), jnp.int32),
                        pltpu.VMEM((CHUNK, _PW), jnp.int32),
                        pltpu.SemaphoreType.DMA((2,))],
        interpret=interpret_mode(interpret),
    )(addr_p, lanes(addr), lanes(khi), lanes(klo), lanes(active), pool)
    nxt, is_leaf, chase, ok, f, vh, vl = (o.reshape(-1)[:B] for o in outs)
    return (nxt, is_leaf != 0, chase != 0, ok != 0, f != 0, vh, vl)


def descent_round_xla(pool, addr, khi, klo, active, *, stop_level: int = 0):
    """Reference twin: the exact gather + layout composition the XLA
    descent paths run (``read_pages_spmd`` N==1 + ``advance``), with the
    same output tuple as :func:`descent_round`."""
    P = pool.shape[0]
    page = bits.addr_page(addr)
    ok = active & (page >= 0) & (page < P)
    pg = jnp.where(ok[:, None], pool[jnp.clip(page, 0, P - 1)], 0)
    lvl = layout.h_level(pg)
    chase = layout.needs_sibling_chase(pg, khi, klo)
    is_leaf = (lvl == stop_level) & ~chase
    nxt = jnp.where(chase, layout.h_sibling(pg),
                    layout.internal_pick_child(pg, khi, klo))
    f, vh, vl, _ = layout.leaf_find_key(pg, khi, klo)
    return nxt, is_leaf, chase, ok, f, vh, vl


# ---------------------------------------------------------------------------
# Kernel 2: multi-lane write-back.
# ---------------------------------------------------------------------------

def _writeback_kernel(page_s, slot_s, app_s, *refs, n_pages: int,
                      field_w: tuple[int, ...]):
    L = len(field_w)
    # one SMEM block per lane; the pool input is aliased to out_ref
    # (read-modify-write in place)
    ent_s, (_, out_ref, tile, sem) = refs[:L], refs[L:]
    isub = lax.broadcasted_iota(jnp.int32, (GROUP, _PW), 0)
    iword = lax.broadcasted_iota(jnp.int32, (GROUP, _PW), 1)

    def row(r, _):
        @pl.when(app_s[r] != 0)
        def _():
            # one read-modify-write of the page's 8-page group per row,
            # all lanes at once; rows run in order, so two rows that
            # share a group never race
            pg = jnp.clip(page_s[r], 0, n_pages - 1)
            rd = _group_copy(out_ref, pg, tile, sem.at[0])
            rd.start()
            rd.wait()
            hit_row = isub == jnp.bitwise_and(pg, GROUP - 1)
            t = tile[...]
            for l in range(L):
                t = jnp.where(hit_row & (iword == field_w[l] + slot_s[r]),
                              ent_s[l][r], t)
            tile[...] = t
            g = pl.multiple_of(jnp.bitwise_and(pg, -GROUP), GROUP)
            wr = pltpu.make_async_copy(tile, out_ref.at[pl.ds(g, GROUP)],
                                       sem.at[0])
            wr.start()
            wr.wait()
        return 0

    lax.fori_loop(0, BLOCK, row, 0)


def writeback(pool, page, slot, applied, ent, field_w: tuple[int, ...],
              interpret: bool | None = None):
    """Multi-lane entry write-back: for each row with ``applied``, write
    ``ent[r, l]`` to ``pool[page[r], field_w[l] + slot[r]]`` — all lanes
    in ONE read-modify-write of the page's group (vs one full-batch XLA
    scatter per lane).  In-place on ``pool`` (input/output aliased).

    Contract: ``page`` pre-clipped to the pool (the apply kernels pass
    ``safe_page``) and applied rows carry in-page ``field_w[l] + slot``
    word targets — guaranteed by the apply kernels' found/ranked slots.
    Matches :func:`writeback_xla` under that contract; rows without
    ``applied`` are dropped exactly like the XLA path's out-of-range
    scatter indices.
    """
    if not HAVE_PALLAS:
        raise PallasUnavailableError("DSMConfig.gather_impl")
    M = page.shape[0]
    P = pool.shape[0]
    _check_pool(P)
    L = len(field_w)
    assert ent.shape == (M, L)
    Mp = _pad_to_block(M)
    page_p = _pad1(jnp.asarray(page, jnp.int32), Mp)
    slot_p = _pad1(jnp.asarray(slot, jnp.int32), Mp)
    app_p = _pad1(applied.astype(jnp.int32), Mp)
    ent_p = _pad1(jnp.asarray(ent, jnp.int32), Mp)
    _OBS_WB.inc()
    _OBS_WB_ROWS.inc(M)
    _OBS_WB_LANES.inc(L)

    sspec = lambda: pl.BlockSpec((BLOCK,), lambda i: (i,),
                                 memory_space=pltpu.SMEM)
    kern = functools.partial(_writeback_kernel, n_pages=P,
                             field_w=tuple(int(w) for w in field_w))
    return pl.pallas_call(
        kern, out_shape=jax.ShapeDtypeStruct((P, _PW), pool.dtype),
        grid=(Mp // BLOCK,),
        in_specs=[sspec() for _ in range(3 + L)]
        + [pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        scratch_shapes=[pltpu.VMEM((GROUP, _PW), jnp.int32),
                        pltpu.SemaphoreType.DMA((1,))],
        input_output_aliases={3 + L: 0},  # pool
        interpret=interpret_mode(interpret),
    )(page_p, slot_p, app_p, *(ent_p[:, l] for l in range(L)), pool)


def writeback_xla(pool, page, slot, applied, ent, field_w: tuple[int, ...]):
    """Reference twin: the per-lane flat scatter the XLA apply path runs
    (``leaf_apply_spmd`` / ``leaf_delete_apply_spmd`` write-back)."""
    P = pool.shape[0]
    fw = jnp.asarray(list(field_w), jnp.int32)
    idx = (page * _PW)[:, None] + fw[None, :] + slot[:, None]
    idx = jnp.where(applied[:, None], idx, P * _PW)
    flat = pool.reshape(-1)
    flat = flat.at[idx.reshape(-1)].set(ent.reshape(-1), mode="drop")
    return flat.reshape(P, _PW)


# ---------------------------------------------------------------------------
# Kernel 3: snapshot gather (one page, many consumers).
# ---------------------------------------------------------------------------

def _gather_kernel(rows_s, pool_ref, out_ref, buf, sems, *, n_pages: int):
    def page_no(j):
        return jnp.clip(rows_s[j], 0, n_pages - 1)

    def group_dma(j):
        k = lax.rem(j, N_INFLIGHT)
        return _group_copy(pool_ref, page_no(j), buf.at[k], sems.at[k])

    for j in range(N_INFLIGHT):  # fill the ring
        group_dma(j).start()

    def body(j, _):
        group_dma(j).wait()
        sub = jnp.bitwise_and(page_no(j), GROUP - 1)
        out_ref[pl.ds(j, 1), :] = buf[lax.rem(j, N_INFLIGHT),
                                      pl.ds(sub, 1), :]

        @pl.when(j + N_INFLIGHT < BLOCK)
        def _():  # the slot is consumed: refill it
            group_dma(j + N_INFLIGHT).start()
        return 0

    lax.fori_loop(0, BLOCK, body, 0)


def gather_pages(pool, rows, interpret: bool | None = None):
    """``pool[jnp.clip(rows, 0, P - 1)]`` as an N_INFLIGHT-deep ring of
    page-group DMAs — the apply path's materialized page snapshot (its
    output IS the snapshot buffer, so no ``optimization_barrier`` is
    needed to stop XLA re-fusing the gather into consumers)."""
    if not HAVE_PALLAS:
        raise PallasUnavailableError("DSMConfig.gather_impl")
    M = rows.shape[0]
    P = pool.shape[0]
    _check_pool(P)
    Mp = _pad_to_block(M)
    rows_p = _pad1(jnp.asarray(rows, jnp.int32), Mp)
    _OBS_SNAP.inc()
    _OBS_SNAP_ROWS.inc(M)

    kern = functools.partial(_gather_kernel, n_pages=P)
    out = pl.pallas_call(
        kern, out_shape=jax.ShapeDtypeStruct((Mp, _PW), pool.dtype),
        grid=(Mp // BLOCK,),
        in_specs=[pl.BlockSpec((BLOCK,), lambda i: (i,),
                               memory_space=pltpu.SMEM),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((BLOCK, _PW), lambda i: (i, 0)),
        scratch_shapes=[pltpu.VMEM((N_INFLIGHT, GROUP, _PW), jnp.int32),
                        pltpu.SemaphoreType.DMA((N_INFLIGHT,))],
        interpret=interpret_mode(interpret),
    )(rows_p, pool)
    return out[:M]


def gather_pages_xla(pool, rows):
    """Reference twin of :func:`gather_pages`."""
    P = pool.shape[0]
    return pool[jnp.clip(rows, 0, P - 1)]


def read_pages_local(pool, addrs, active):
    """The single-node ``read_pages_spmd`` contract over the pallas
    gather: (pages zeroed where not ok, ok)."""
    P = pool.shape[0]
    page = bits.addr_page(addrs)
    ok = active & (page >= 0) & (page < P)
    pages = gather_pages(pool, page)
    return jnp.where(ok[:, None], pages, 0), ok
