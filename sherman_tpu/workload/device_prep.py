"""Device-resident batch staging: the benchmark loop's entire client
side — zipf rank sampling, the synthetic rank->key map, request
combining (sort-based unique + inverse), and the index-cache probe —
as ONE jitted TPU computation fused with the serving step, so a
sustained loop ships NOTHING per step (the step counter threads through
device-resident carry; the host only dispatches).

Reference parity: the reference benchmark's client threads generate
their zipf key and issue it inline in the open loop
(``test/benchmark.cpp:159-188``) — nothing hoisted.  Here the TPU is
client and server fused, so generation runs on device inside the timed
step.  Fidelity:

- The rank distribution inverts the SAME Gray/Jain CDF the native
  sampler uses (``native/src/prep.cc``), via a host-precomputed
  quantile table: ``table[i]`` = inverse CDF at quantile ``i / 2^LB``
  (float64-exact head + Euler-Maclaurin tail, vectorized bisection).
  On device a sample is a 2-word counter-based PRNG draw: word 0 picks
  the quantile bin (the CDF is exact at bin edges — hot ranks span
  many whole bins, so the head is EXACT), word 1 lerps within the bin
  (piecewise-uniform; bins are <= ~2^14 ranks wide even in the deepest
  tail, where the zipf density is locally flat, so the within-bin
  approximation is statistically invisible).  The f32 lerp is exact to
  <1 rank for bin widths < 2^24 (asserted at table build).
- The rank->key map is bit-for-bit the native one:
  ``mix64(rank ^ salt)`` on (hi, lo) uint32 pairs
  (:func:`sherman_tpu.ops.bits.mix64_pair`), so device-generated
  batches hit exactly the keys the bulk load wrote.
- Dedup is a device ``lax.sort`` by key + segment scan; the unique set
  is compacted by a SECOND stable sort on the first-occurrence flag
  (sorts measure ~6 ms at 4 M rows on chip, while the scatter-based
  compaction they replace measured ~24 ms per scatter — random
  HBM writes are the expensive primitive, sorts are not).  The unique
  rows come out KEY-SORTED, which after a sequential bulk load is also
  page-address-sorted: the round-1 leaf gather gets the start-sorted
  locality win (measured ~27% on host-staged batches) for free.
- The step SERVES CLIENTS IN SORTED ORDER: the client view of the
  batch is the key-sorted permutation of the generated ops (client
  order carries no meaning — the reference's client threads are
  unordered).  That makes the per-request answer fan-out a MONOTONE
  gather (``ans[seg]``, seg nondecreasing) instead of a random one,
  and drops the inverse-permutation scatter entirely.  Every client
  op's answer is still materialized in HBM inside the step and
  VERIFIED on device: the carry accumulates the exact count of client
  ops whose returned value matched ``key ^ check_xor`` — the
  honest-accounting receipts ride inside the timed loop.

Program structure (the round-6 "staged-step anatomy" work): the step's
compiled-program split is a first-class knob (``fusion=`` /
``SHERMAN_STAGED_FUSION``, see :func:`make_staged_step`).  The default
``aligned`` form dispatches ``prep -> serve -> verify`` where the serve
IS the engine's host-staged combined-search fan-out program — the same
compiled executable the throughput phase runs — so no input-layout,
donation, or shard_map-fusion difference can exist between the staged
serve and the host-staged serve by construction.  Every form exposes
``step.programs`` and ``step.phase_profile`` (chained-delta per-phase
wall costs) so benchmarks publish per-phase timings instead of
re-profiling.
"""

from __future__ import annotations

import numpy as np

from sherman_tpu import config as C
from sherman_tpu.errors import ConfigError
from sherman_tpu.obs import device as DEV
from sherman_tpu.ops import bits


def zipf_table(n: int, theta: float, log2_bins: int = 20) -> np.ndarray:
    """Inverse-CDF quantile table for Zipf(theta) ranks over [0, n):
    int32 [2^log2_bins + 1], ``table[i]`` = smallest 0-based rank r with
    CDF(r) >= i / 2^log2_bins (``table[-1]`` = n - 1).

    theta == 0 degenerates to the uniform ramp.  Head ranks are exact
    (float64 cumsum of the harmonic series up to 2^22); tail CDF values
    use the Euler-Maclaurin continuation (error << one quantile), and
    the inversion is a vectorized bisection."""
    assert 0.0 <= theta < 1.0 and n >= 1
    nb = 1 << log2_bins
    if theta == 0.0:
        t = np.floor(np.arange(nb + 1, dtype=np.float64) * n / nb)
        table = np.minimum(t, n - 1).astype(np.int32)
    else:
        M = min(n, 1 << 22)
        f = np.arange(1, M + 1, dtype=np.float64) ** -theta
        Hhead = np.cumsum(f)
        om = 1.0 - theta

        def H(r):
            """Harmonic partial sum H(r) = sum_{k=1..r} k^-theta for
            real r >= M (Euler-Maclaurin; exact head)."""
            r = np.asarray(r, np.float64)
            integral = (r ** om - float(M) ** om) / om
            half = 0.5 * (r ** -theta - float(M) ** -theta)
            d112 = (theta / 12.0) * (r ** (-theta - 1.0)
                                     - float(M) ** (-theta - 1.0))
            return Hhead[-1] + integral + half - d112

        Hn = Hhead[-1] if n <= M else float(H(float(n)))
        q = np.arange(nb + 1, dtype=np.float64) / nb * Hn
        table = np.searchsorted(Hhead, q, side="left").astype(np.int64)
        tail = q > Hhead[-1]
        if tail.any():
            qt = q[tail]
            lo = np.full(qt.shape, float(M))
            hi = np.full(qt.shape, float(n))
            for _ in range(48):
                mid = 0.5 * (lo + hi)
                ge = H(mid) >= qt
                hi = np.where(ge, mid, hi)
                lo = np.where(ge, lo, mid)
            table[tail] = np.ceil(hi).astype(np.int64) - 1
        table = np.minimum(np.maximum(table, 0), n - 1).astype(np.int32)
    assert (np.diff(table) >= 0).all()
    assert int(np.diff(table.astype(np.int64)).max(initial=0)) < (1 << 24), \
        "quantile bin wider than the 24-bit lerp resolution; raise log2_bins"
    return table


def zipf_analytic_consts(n: int, theta: float, head: int = 64) -> dict:
    """Host-side float64 constants for the ANALYTIC device inverse CDF
    (:func:`_gen_ranks_analytic`): exact partial sums H(1..head) for the
    head, and the Euler-Maclaurin continuation constants for the tail.

    Same approximation class as the quantile table (exact head, E-M
    tail, ~single-rank precision where the density is steep and a flat
    local density where it is not) — but evaluated in VPU registers
    instead of a [2^20, 2] HBM gather, which is the dominant prep cost
    on chip (~15 ns/row)."""
    assert 0.0 < theta < 1.0 and n > head
    f = np.arange(1, head + 1, dtype=np.float64) ** -theta
    Hh = np.cumsum(f)
    om = 1.0 - theta
    M = float(head)

    def H(r):
        """E-M continuation of the harmonic partial sum for r >= head."""
        r = np.asarray(r, np.float64)
        return (Hh[-1] + (r ** om - M ** om) / om
                + 0.5 * (r ** -theta - M ** -theta)
                - (theta / 12.0) * (r ** (-theta - 1.0)
                                    - M ** (-theta - 1.0)))

    return {
        "head_sums": Hh, "om": om, "theta": theta, "M": M,
        "Hn": float(H(float(n))),
        # tail-init constant: r0 = (om*(x - B0))^(1/om) drops the small
        # E-M terms; Newton below restores them
        "B0": float(Hh[-1] - (M ** om) / om),
    }


def _gen_ranks_analytic(consts: dict, w, *, n_keys: int):
    """Zipf ranks via the analytic inverse CDF — NO table gather.

    u from 24 fresh PRNG bits -> x = u * H(n); head ranks (< head) by
    64 unrolled register compares against the exact partial sums (CDF-
    exact, like the table's head); tail by inverting the Euler-Maclaurin
    continuation: closed-form init + two Newton steps in f32
    (H'(r) = r^-theta).  f32 rank jitter in the deep tail (~1e4 ranks
    at r ~ 1e8) sits inside the quantile table's own bin width there
    (up to 2^24 ranks), so the two samplers share an approximation
    class; `tests/test_device_prep.py` pins both against the exact CDF.
    """
    import jax.numpy as jnp

    Hh = consts["head_sums"]
    om = jnp.float32(consts["om"])
    theta = jnp.float32(consts["theta"])
    Mf = jnp.float32(consts["M"])
    u = (w[0] >> 8).astype(jnp.float32) * jnp.float32(2.0 ** -24)
    x = u * jnp.float32(consts["Hn"])
    # head: rank = #(partial sums < x), CDF-exact for ranks < head
    rank_head = jnp.zeros(x.shape, jnp.int32)
    for h in Hh:
        rank_head = rank_head + (x > jnp.float32(h)).astype(jnp.int32)
    HhM = jnp.float32(Hh[-1])
    c_half = jnp.float32(0.5 * consts["M"] ** -consts["theta"])
    c_d12 = jnp.float32((consts["theta"] / 12.0)
                        * consts["M"] ** (-consts["theta"] - 1.0))
    Mom = jnp.float32(consts["M"] ** consts["om"])
    B0 = jnp.float32(consts["B0"])

    def invert(xt):
        """Solve H(r) = xt for r >= head: closed-form init (small E-M
        terms dropped) + two Newton steps (H'(r) = r^-theta)."""
        r = jnp.exp(jnp.log(om * (xt - B0)) / om)
        for _ in range(2):
            r = jnp.maximum(r, Mf)
            rmt = jnp.exp(-theta * jnp.log(r))         # r^-theta
            Hr = (HhM + (r * rmt - Mom) / om + 0.5 * rmt - c_half
                  - (theta / jnp.float32(12.0)) * (rmt / r) + c_d12)
            r = r - (Hr - xt) / rmt
        return jnp.maximum(r, Mf)

    # tail: u has 24 bits, so ~4 M draws collide heavily on quantile
    # cells (2^24 cells); recover the lost entropy EXACTLY like the
    # quantile table does — invert at BOTH edges of the 2^-24-wide cell
    # and lerp on w[1] (a virtual [2^24]-bin table, piecewise-linear in
    # the locally flat tail)
    du = jnp.float32(2.0 ** -24) * jnp.float32(consts["Hn"])
    xt = jnp.maximum(x, HhM)
    r_lo = invert(xt)
    r_hi = invert(xt + du)
    v = (w[1] >> 8).astype(jnp.float32) * jnp.float32(2.0 ** -24)
    rank_tail = (r_lo + (r_hi - r_lo) * v).astype(jnp.int32)
    rank = jnp.where(rank_head < jnp.int32(len(Hh)), rank_head, rank_tail)
    return jnp.clip(rank, 0, n_keys - 1)


def _gen_ranks(tpair, w, *, log2_bins: int, n_keys: int):
    """Zipf ranks from two uint32 PRNG words per sample: bin from the
    top ``log2_bins`` bits (CDF-exact edges), f32 lerp within the bin on
    24 fresh bits.  ``tpair`` is the [nb, 2] edge-pair table — one
    random gather per sample, not two (random HBM access is the
    dominant prep cost on chip — ~15 ns/row)."""
    import jax.numpy as jnp

    bin_ = (w[0] >> (32 - log2_bins)).astype(jnp.int32)
    t2 = tpair[bin_]                     # [batch, 2]
    lo_r, hi_r = t2[:, 0], t2[:, 1]
    frac = (w[1] >> 8).astype(jnp.float32) * jnp.float32(2.0 ** -24)
    rank = lo_r + ((hi_r - lo_r).astype(jnp.float32)
                   * frac).astype(jnp.int32)
    return jnp.clip(rank, 0, n_keys - 1)


def _keys_of_ranks(rank, salt_hi, salt_lo):
    """The synthetic rank->key map, bit-for-bit the native one:
    ``mix64(rank ^ salt)`` on (hi, lo) uint32 pairs.  Ranks < 2^31, so
    the high word of ``rank ^ salt`` is salt's high word."""
    import jax.numpy as jnp
    from jax import lax

    xlo = lax.bitcast_convert_type(rank, jnp.uint32) ^ salt_lo
    xhi = jnp.full(rank.shape, salt_hi, jnp.uint32)
    return bits.mix64_pair(xhi, xlo)


def _sort_combine(khi, klo, cap):
    """Sort-based request combining: clients served in key-sorted order
    (no index payload, no inverse-permutation scatter).  Returns the
    sorted client keys, the unique rows compacted to ``cap``, the
    client->row segment map, and the unique count.

    The unique set is compacted with a flag-sort: plain 3-key sort, NOT
    ``is_stable=True`` — the composite (flag, khi, klo) is already a
    total order on the rows that matter (first rows have distinct
    keys), and the stable-sort path measured ~12x slower on chip.
    Sorts are ~4x cheaper than the equivalent scatters on chip."""
    import jax.numpy as jnp
    from jax import lax

    skhi, sklo = lax.sort((khi, klo), num_keys=2)
    first = jnp.concatenate([
        jnp.ones((1,), jnp.uint32),
        ((skhi[1:] != skhi[:-1])
         | (sklo[1:] != sklo[:-1])).astype(jnp.uint32)])
    seg = (jnp.cumsum(first) - 1).astype(jnp.int32)
    n_uniq = seg[-1] + 1
    _, ckhi, cklo = lax.sort((jnp.uint32(1) - first, skhi, sklo),
                             num_keys=3)
    return skhi, sklo, ckhi[:cap], cklo[:cap], seg, n_uniq


def _router_probe(rtable, ukhi, uklo, shift, nb):
    """Index-cache probe: bucket = min(key >> shift, nb - 1), one
    gather from the router table."""
    import jax.numpy as jnp

    bhi, blo = bits.u64_shr(ukhi, uklo, shift)
    bucket = jnp.where(bhi != 0, jnp.uint32(nb - 1),
                       jnp.minimum(blo, jnp.uint32(nb - 1)))
    return rtable[bucket.astype(jnp.int32)]


def _rep_put(dsm, x):
    """Host value -> device-resident REPLICATED array, multihost-aware:
    single-process meshes use a plain ``device_put``; process-spanning
    meshes build the global replicated array from every process's
    identical local copy (the engine's ``_shard`` idiom with an empty
    partition spec)."""
    import jax

    x = np.asarray(x)
    if getattr(dsm, "multihost", False):
        from jax.experimental import multihost_utils as mhu
        return mhu.host_local_array_to_global_array(
            x, dsm.mesh, jax.sharding.PartitionSpec())
    return jax.device_put(x)


def _stage_inputs(dsm, router, n_keys: int, theta: float, log2_bins: int,
                  seed: int, sampler: str = "table"):
    """Stage the step's device-resident inputs once, before any timed
    region: the [nb, 2] zipf edge-pair table (a tiny dummy when the
    analytic sampler needs no table), the router table, and the PRNG
    key.  All replicated (multihost-aware via :func:`_rep_put`)."""
    import jax

    if sampler == "analytic":
        table = np.zeros((1, 2), np.int32)
    else:
        t = zipf_table(n_keys, theta, log2_bins)
        table = np.stack([t[:-1], t[1:]], axis=1)
    with router._read_locked():
        rtable = np.array(router.table_np)
    rkey = np.asarray(jax.random.PRNGKey(seed))
    return (_rep_put(dsm, table), _rep_put(dsm, rtable),
            _rep_put(dsm, rkey))


def _delta_ms(loop, reps: int) -> float:
    """Chained-delta phase timing: run ``loop(K)`` and ``loop(2K)``
    (each a chain of data-dependent dispatches ending in a drain) and
    return ``(t_2K - t_K) / K`` in ms — the methodology of
    tools/profile_insert.py, which cancels the per-call dispatch + sync
    overhead exactly."""
    import time

    loop(1)  # warm: compile + program load stay out of the delta
    t0 = time.perf_counter()
    loop(reps)
    t1 = time.perf_counter() - t0
    t0 = time.perf_counter()
    loop(2 * reps)
    t2 = time.perf_counter() - t0
    return max(0.0, (t2 - t1) / reps * 1e3)


def overlap_receipt(prep_ms: float, serve_ms: float, verify_ms: float,
                    wall_ms: float) -> dict:
    """The round-8 OVERLAP RECEIPT, computed in exactly one place (the
    read-only and mixed pipelined phase profiles and the
    profile_staged2 mode table all publish it): ``bubble_ms`` = wall −
    serve (the work NOT hidden behind the serve bound) and
    ``overlap_efficiency`` = 1 − wall/(prep+serve+verify) (0 = fully
    serial dispatch, (prep+verify)/sum = perfect hiding)."""
    serial = prep_ms + serve_ms + verify_ms
    return {
        "wall_ms": wall_ms,
        "bubble_ms": max(0.0, wall_ms - serve_ms),
        "overlap_efficiency": (1.0 - wall_ms / serial
                               if serial > 0 else 0.0),
    }


def record_phase_obs(prefix: str, phases: dict) -> None:
    """Route one phase/overlap dict into obs — the SINGLE copy of the
    routing every publisher (bench read-only + mixed, profile_staged2)
    shares: ``overlap_efficiency`` is a ratio and lands in a gauge;
    every wall cost lands in a ``<prefix>.<name>_ms`` histogram
    (``wall_ms``/``bubble_ms`` already carry the unit)."""
    from sherman_tpu import obs

    for name, v in phases.items():
        if name == "overlap_efficiency":
            obs.gauge(f"{prefix}.overlap_efficiency").set(v)
        else:
            h = name if name.endswith("_ms") else f"{name}_ms"
            obs.histogram(f"{prefix}.{h}").record(v)


def _two_deep_slot(jverify):
    """The pipelined modes' pending-slot protocol, in ONE copy shared
    by the read-only and mixed steps (the slot tuple contents and the
    verify program differ; the stateful contract must not): ``fold``
    folds a pending batch's verify inputs (if any) into the receipts,
    ``put`` parks batch k's, ``drain`` flushes the slot so the carry
    is bit-identical to the sequential mode's, ``reset`` clears it
    without folding (``new_carry()`` — a fresh receipts stream must
    not fold a stale batch left by an undrained previous run)."""
    pend = {"slot": None}

    def fold(rcarry):
        if pend["slot"] is not None:
            rcarry = jverify(rcarry, *pend["slot"])
        return rcarry

    def put(*slot):
        pend["slot"] = slot

    def drain(carry):
        step_idx, *rcarry = carry
        rcarry = tuple(rcarry)
        if pend["slot"] is not None:
            rcarry = jverify(rcarry, *pend["slot"])
            pend["slot"] = None
        return (step_idx,) + rcarry

    def reset():
        pend["slot"] = None

    return fold, put, drain, reset


def _rank_sampler(sampler: str, n_keys: int, theta: float,
                  log2_bins: int):
    """-> (rank(tpair, w), effective_name) for the chosen sampler.
    ``analytic`` (no HBM table gather) requires 0 < theta < 1 AND a
    keyspace larger than its exact head; BOTH out-of-range cases fall
    back to the quantile table (uniformly — never a crash on one and a
    silent fallback on the other), and the effective name is returned
    so drivers can log which sampler actually ran."""
    if sampler == "analytic" and 0.0 < theta < 1.0 and n_keys > 64:
        zc = zipf_analytic_consts(n_keys, theta)
        return (lambda tpair, w: _gen_ranks_analytic(zc, w,
                                                     n_keys=n_keys),
                "analytic")
    return (lambda tpair, w: _gen_ranks(tpair, w, log2_bins=log2_bins,
                                        n_keys=n_keys), "table")


def make_staged_step(eng, *, n_keys: int, theta: float, salt: int,
                     batch: int, dev_b: int, log2_bins: int = 20,
                     check_xor: int = 0xDEADBEEF, seed: int = 11,
                     staged=None, sampler: str = "table",
                     fusion: str | None = None, leaf_cache=None,
                     dev_b_resid: int | None = None):
    """Build the device-staged serving step for ``eng`` (a
    :class:`~sherman_tpu.models.batched.BatchedEngine` with an attached
    router).

    Returns ``(step, state)`` where ``state = (new_carry, table_d,
    rtable_d, rkey_d)``: ``new_carry()`` makes a fresh device-resident
    carry, the rest are device-resident inputs staged once, before any
    timed region.  Then

        ``counters, carry = step(pool, counters, table_d, rtable_d,
                                 rkey_d, carry)``

    runs ONE step: generate ``batch`` zipf client keys per node from the
    carry's step counter, combine to <= ``dev_b`` unique rows, probe the
    router, descend, fan out every answer in-step, and fold the
    verification receipts into the carry.  Carry fields (all replicated
    int32/uint32 scalars):

        (step_idx, ok, n_correct, sum_nuniq, max_nuniq)

    ``ok`` goes 0 if any step's unique count overflowed ``dev_b`` (its
    rows would be dropped, so the step's receipts are void);
    ``n_correct`` counts client ops whose value matched
    ``key ^ check_xor`` — after S steps it must equal
    ``S * batch * machine_nr``.  ``sum_nuniq`` accumulates per-node
    unique counts (psum across nodes) for combine-ratio reporting.

    ``fusion`` picks the compiled-program structure (default
    :func:`sherman_tpu.config.staged_fusion`, overridable via the
    ``SHERMAN_STAGED_FUSION`` env var):

    - ``"aligned"`` (default): THREE chained programs ``prep -> serve
      -> verify`` where the serve IS the engine's combined-search
      fan-out program (``BatchedEngine._get_search_fanout``) — the
      byte-identical compiled executable the host-staged throughput
      phase runs.  This forces the staged serve's input layouts,
      donation and HLO to match the host-staged case by construction,
      eliminating the cross-program layout / shard_map-fusion suspects
      of BENCHMARKS.md round-5 "known headroom"; the receipts
      arithmetic moves to its own elementwise ``verify`` program.
    - ``"pipelined"``: the SAME three compiled programs as ``aligned``
      (the serve is the same ``_get_search_fanout`` program OBJECT, so
      the CI program-identity pin extends to this mode), dispatched as
      a TWO-DEEP software pipeline: call k first folds batch k-1's
      already-materialized serve outputs through ``verify`` (consuming
      the pending slot), then dispatches ``prep`` for batch k into the
      slot the verify just released, then the serve — so while the
      device serves batch k-1, the host has already queued batch k's
      prep and batch k-2's verify, and a backend that overlaps
      independent programs hides the prep + verify walls behind the
      serve.  Double-buffered: at most TWO batches' staging arrays are
      alive (the in-flight prep outputs and the pending verify
      inputs); no extra pool or batch copies are materialized, and
      donation stays exactly the serve program's own
      (:func:`sherman_tpu.config.donate_argnums`-gated).  Receipts lag
      one batch in the returned carry; ``step.drain(carry)`` flushes
      the pending verify, after which the carry is BIT-IDENTICAL to S
      ``aligned`` steps' (same programs, same fold order).  A fresh
      ``new_carry()`` also resets the pipeline (a fresh receipts
      stream must not fold a stale pending batch).  CONTRACT: the
      pending slot lives on the STEP object, so one pipelined step
      drives ONE carry stream at a time — interleaving two carries
      through the same step folds one stream's pending batch into the
      other's receipts; build a second step (``staged=`` reuses the
      resident tables) for a second stream.
    - ``"chained"``: the round-5 two-program form (``prep -> serve``
      with fan-out + verification fused into the serve program), kept
      for continuity and A/B measurement against ``aligned``.
    - ``"fused"``: ONE jitted program.  On TPU, XLA compiles the prep
      pipeline fused into the serve's straggler while-loop ~50-100x
      slower than the sum of its parts (measured 6.8-10.3 s fused vs
      56 + 63 ms split on chip; ``optimization_barrier`` does not fix
      it), so this form exists for CPU-mesh regression tests — a single
      program PROVES no host round trip can hide between generation and
      serve — and for re-testing the pathology on new toolchains.

    ``leaf_cache`` (optional; aligned/pipelined only): an attached
    :class:`~sherman_tpu.models.leaf_cache.LeafCache` — a fourth
    compiled program ``cache_probe`` (fixed table shapes, so the sealed
    loop stays zero-retrace) runs between prep and serve: pool-validated
    hot-key hits leave the unique batch, the probe COMPACTS the misses
    into a ``dev_b_resid``-wide residual (descent cost is per ROW of
    the compiled shape, so deactivating rows saves nothing — shrinking
    the shape is the whole win), the serve descends only that residual,
    and the verify program merges the cache answers back per client row
    before the receipts arithmetic — so the drained receipts are
    BIT-IDENTICAL to the uncached loop's, with two extra carry scalars
    appended: ``sum_hits`` (client ops served from cache — the measured
    hit ratio's numerator) and ``sum_hits_uniq`` (unique rows removed
    from the serve — the residual-batch receipt).  ``dev_b_resid``
    (default ``dev_b`` — no shrink) caps the per-node residual; a step
    whose misses overflow it voids the phase through the ``ok``
    receipt, the SAME contract as the ``dev_b`` unique cap (drivers
    size it from a warmup step's measured residual, the mixed loop's
    cap-tightening dance).  The cache's device tables are staged ONCE
    (read-only sealed window: in-window stale entries just keep
    missing, validation stays authoritative); ``step.phase_labels``
    and the compile ledger carry the ``cache_probe`` label so the
    probe's cost is attributable.

    In every mode the dispatched programs are chained back-to-back with
    no host work or transfer between them (the multi-program forms pass
    device-resident arrays only).  ``counters`` is donated; the rcarry
    scalars are deliberately NOT donated — callers block their dispatch
    window on ``carry[1]`` (the LAST program's output; see bench.py
    ``run_windowed``), which must stay a live buffer after the next
    step consumes it.  Step attributes: ``step.fusion``,
    ``step.sampler``, ``step.programs`` (name -> jitted program in
    dispatch order), ``step.n_programs``, ``step.phase_profile``
    (chained-delta per-phase wall costs; in ``pipelined`` mode the
    dict also carries the OVERLAP RECEIPT — ``wall_ms`` the drained
    pipelined wall per step, ``bubble_ms`` = wall − serve, the host
    work not hidden behind the serve bound, and
    ``overlap_efficiency`` = 1 − wall/(prep + serve + verify), 0 =
    fully serial), ``step.drain`` (flush the pending verify; identity
    for non-pipelined modes), ``step.pipeline_depth`` (2 for
    ``pipelined``, else 1), plus per-mode handles (``step.jprep`` /
    ``step.jserve`` / ``step.jverify`` / ``step.jfused``)."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    from sherman_tpu.models.batched import AXIS, search_routed_spmd
    from sherman_tpu.parallel import transport

    fusion = fusion or C.staged_fusion()
    if fusion not in ("aligned", "pipelined", "chained", "fused"):
        raise ConfigError(
            f"fusion={fusion!r}: want aligned|pipelined|chained|fused")
    use_cache = leaf_cache is not None
    if use_cache and fusion not in ("aligned", "pipelined"):
        raise ConfigError(
            f"leaf_cache requires fusion aligned|pipelined (got "
            f"{fusion!r}): the probe is its own chained program")
    router = eng.router
    assert router is not None, "attach_router() first"
    cfg = eng.cfg
    dsm = eng.dsm
    N = cfg.machine_nr
    iters = eng._iters()
    spec, rep = eng._spec, eng._rep
    shift, nb = int(router.shift), int(router.nb)
    LB = int(log2_bins)
    gen_ranks, sampler = _rank_sampler(sampler, n_keys, theta, LB)
    root = np.int32(eng.tree._root_addr)
    salt_hi = np.uint32((salt >> 32) & 0xFFFFFFFF)
    salt_lo = np.uint32(salt & 0xFFFFFFFF)
    cx_hi = np.uint32((check_xor >> 32) & 0xFFFFFFFF)
    cx_lo = np.uint32(check_xor & 0xFFFFFFFF)
    i32 = lambda x: lax.bitcast_convert_type(x, jnp.int32)

    assert batch >= dev_b, "dev_b is the unique-set cap; cannot exceed batch"

    def prep_core(tpair, rtable, rkey, step_idx):
        # per-node, per-step independent stream (counter-based PRNG):
        # fold the step counter and the node index into the key
        with jax.named_scope("sample"):
            node = lax.axis_index(AXIS) if N > 1 else jnp.uint32(0)
            k = jax.random.fold_in(rkey, step_idx * np.uint32(N)
                                   + node.astype(jnp.uint32))
            w = jax.random.bits(k, (2, batch), dtype=jnp.uint32)
            rank = gen_ranks(tpair, w)
            khi_u, klo_u = _keys_of_ranks(rank, salt_hi, salt_lo)
        # sort-based unique (request combining): clients are served in
        # key-sorted order (see module docstring), so no index payload
        # and no inverse-permutation scatter are needed
        with jax.named_scope("combine"):
            skhi, sklo, ukhi, uklo, seg, n_uniq = _sort_combine(
                khi_u, klo_u, dev_b)
            active = lax.iota(jnp.int32, dev_b) < n_uniq
        with jax.named_scope("router_probe"):
            start = _router_probe(rtable, ukhi, uklo, shift, nb)
        return skhi, sklo, ukhi, uklo, start, active, seg, n_uniq

    def serve_fanout(pool, counters, ukhi, uklo, start, active, seg):
        """chained/fused serve body: routed descent + the monotone
        per-client answer fan-out (seg is NONDECREASING, so the gather
        is sequential in HBM, unlike an inverse-permuted one).  Each
        node combines its own clients, so ``seg`` indexes the node's own
        [dev_b, 4] answer table on any mesh size."""
        counters, done, found, vhi, vlo = search_routed_spmd(
            pool, counters, i32(ukhi), i32(uklo), root, active, start,
            cfg=cfg, iters=iters)
        with jax.named_scope("fanout"):
            ans = jnp.stack([found.astype(jnp.int32), vhi, vlo,
                             jnp.zeros_like(vhi)], axis=-1)  # [U_loc, 4]
            safe = jnp.clip(seg, 0, ans.shape[0] - 1)
            out = jnp.take_along_axis(ans, safe[:, None], axis=0)
        return counters, out[:, 0] != 0, out[:, 1], out[:, 2]

    def verify_core(rcarry, skhi, sklo, found, vhi, vlo, n_uniq):
        """Receipts: every (sorted-order) client answer must equal its
        key ^ check_xor; the scalar carries psum across the mesh."""
        ok, n_correct, sum_nu, max_nu = rcarry
        exp_hi = i32(skhi ^ cx_hi)
        exp_lo = i32(sklo ^ cx_lo)
        corr = found & (vhi == exp_hi) & (vlo == exp_lo)
        inc_corr = jnp.sum(corr.astype(jnp.int32))
        step_ok = (n_uniq <= dev_b).astype(jnp.int32)
        if N > 1:
            inc_corr = lax.psum(inc_corr, AXIS)
            sum_inc = lax.psum(n_uniq, AXIS)
            max_inc = lax.pmax(n_uniq, AXIS)
            step_ok = lax.pmin(step_ok, AXIS)
        else:
            sum_inc, max_inc = n_uniq, n_uniq
        return (jnp.minimum(ok, step_ok), n_correct + inc_corr,
                sum_nu + sum_inc, jnp.maximum(max_nu, max_inc))

    mesh = dsm.mesh
    root_rep = None
    _pipe_reset = None  # pipelined mode installs its slot reset here

    if fusion in ("aligned", "pipelined"):
        def prep(tpair, rtable, rkey, step_idx):
            skhi, sklo, ukhi, uklo, start, active, seg, n_uniq = \
                prep_core(tpair, rtable, rkey, step_idx)
            if N > 1:
                # the engine fan-out kernel takes GLOBAL unique indices
                node = lax.axis_index(AXIS)
                seg = seg + node.astype(jnp.int32) * dev_b
            # keys bitcast to int32 IN PREP: the serve consumes exactly
            # the dtypes/layouts the host-staged path ships
            return (step_idx + np.uint32(1), skhi, sklo, i32(ukhi),
                    i32(uklo), start, active, seg, n_uniq[None])

        # compile-ledger wraps (obs/device.py): the staged programs are
        # the serve path's white-box unit of account — a post-seal
        # compile on ANY of them is the silent-retrace hazard
        jprep = DEV.wrap_program("staged.prep", jax.jit(jax.shard_map(
            prep, mesh=mesh, in_specs=(rep, rep, rep, rep),
            out_specs=(rep,) + (spec,) * 8, check_vma=False)))
        # the serve is the ENGINE's host-staged program object: same jit
        # cache entry, same donation, same HLO as the throughput phase
        # (already ledger-wrapped at the engine cache site — wrap() is
        # idempotent, so the identity pin keeps holding).  Multi-node,
        # its node-local variant: a client's unique row is on its own
        # node, so no answer-table all-gather (``inv`` stays GLOBAL)
        jserve = eng._get_search_fanout(iters, local=True)

        jcache = cache_tables = None
        R_resid = int(dev_b_resid) if dev_b_resid else dev_b
        if use_cache:
            from sherman_tpu.models.leaf_cache import probe_rows
            assert 0 < R_resid <= dev_b, \
                "dev_b_resid caps the residual within the unique cap"
            cache_tables = leaf_cache.device_tables()

            def cache_probe(pool, tkhi, tklo, tvhi, tvlo, tver, taddr,
                            tslot, khi, klo, active, start, inv):
                tbl = {"khi": tkhi, "klo": tklo, "vhi": tvhi,
                       "vlo": tvlo, "ver": tver, "addr": taddr,
                       "slot": tslot}
                hit, cvhi, cvlo, _, _ = probe_rows(
                    pool, tbl, khi, klo, active, cfg=cfg)
                # read-only sealed window: no device-side slot
                # invalidation here (the table arrays are staged
                # constants) — a stale entry keeps missing and the pool
                # validation stays the authoritative guard
                resid = active & ~hit
                n_resid = jnp.sum(resid.astype(jnp.int32))
                # compact the misses to the [R_resid] residual the
                # serve actually descends; overflowing rows drop and
                # VOID the step via the ok receipt (n_resid check in
                # verify), never silently mis-serve
                sidx = jnp.nonzero(resid, size=R_resid,
                                   fill_value=dev_b)[0].astype(jnp.int32)
                valid = sidx < dev_b
                ci = jnp.clip(sidx, 0, dev_b - 1)
                # remap client fan-out indices onto the residual rows;
                # hit clients land on row 0 (their garbage fan-out is
                # overwritten by the verify merge)
                remap = jnp.zeros(dev_b + 1, jnp.int32).at[
                    jnp.where(valid, sidx, dev_b)].set(
                    jnp.arange(R_resid, dtype=jnp.int32), mode="drop")
                if N > 1:
                    node = lax.axis_index(AXIS).astype(jnp.int32)
                    loc = jnp.clip(inv - node * dev_b, 0, dev_b)
                    inv_r = remap[loc] + node * R_resid
                else:
                    inv_r = remap[jnp.clip(inv, 0, dev_b)]
                return (hit, cvhi, cvlo, khi[ci], klo[ci], start[ci],
                        valid, inv_r, n_resid[None])

            jcache = DEV.wrap_program(
                "staged.cache_probe", jax.jit(jax.shard_map(
                    cache_probe, mesh=mesh,
                    in_specs=(spec,) + (rep,) * 7 + (spec,) * 5,
                    out_specs=(spec,) * 9, check_vma=False)))

        if not use_cache:
            def verify(rcarry, skhi, sklo, found, vhi, vlo, n_uniq_a):
                return verify_core(rcarry, skhi, sklo, found, vhi, vlo,
                                   n_uniq_a[0])

            jverify = DEV.wrap_program(
                "staged.verify", jax.jit(jax.shard_map(
                    verify, mesh=mesh,
                    in_specs=((rep,) * 4, spec, spec, spec, spec, spec,
                              spec),
                    out_specs=(rep,) * 4, check_vma=False)))
        else:
            def verify(rcarry, skhi, sklo, found, vhi, vlo, n_uniq_a,
                       seg, hit, cvhi, cvlo, n_resid_a):
                """Cache-aware receipts: merge the cache answers back
                per client row (the hit rows' serve outputs fanned out
                residual row 0), then run the SAME receipts arithmetic
                — plus the two hit accumulators and the residual-
                overflow void (the dev_b_resid twin of the unique cap's
                ok receipt)."""
                (ok, n_correct, sum_nu, max_nu, hits_c,
                 hits_u) = rcarry
                ctab = jnp.stack([hit.astype(jnp.int32), cvhi, cvlo,
                                  jnp.zeros_like(cvhi)], axis=-1)
                if N > 1:
                    ctab = transport.gather_rows(ctab, AXIS)
                safe = jnp.clip(seg, 0, ctab.shape[0] - 1)
                cout = jnp.take_along_axis(ctab, safe[:, None], axis=0)
                chit = cout[:, 0] != 0
                inc_hc = jnp.sum(chit.astype(jnp.int32))
                inc_hu = jnp.sum(hit.astype(jnp.int32))
                rok = (n_resid_a[0] <= R_resid).astype(jnp.int32)
                if N > 1:
                    inc_hc = lax.psum(inc_hc, AXIS)
                    inc_hu = lax.psum(inc_hu, AXIS)
                    rok = lax.pmin(rok, AXIS)
                base = verify_core(
                    (ok, n_correct, sum_nu, max_nu), skhi, sklo,
                    found | chit, jnp.where(chit, cout[:, 1], vhi),
                    jnp.where(chit, cout[:, 2], vlo), n_uniq_a[0])
                return ((jnp.minimum(base[0], rok),) + base[1:]
                        + (hits_c + inc_hc, hits_u + inc_hu))

            jverify = DEV.wrap_program(
                "staged.verify", jax.jit(jax.shard_map(
                    verify, mesh=mesh,
                    in_specs=((rep,) * 6,) + (spec,) * 11,
                    out_specs=(rep,) * 6, check_vma=False)))
        root_rep = _rep_put(dsm, root)

        if fusion == "aligned":
            def step(pool, counters, tpair, rtable, rkey, carry):
                step_idx, *rcarry = carry
                (step_idx, skhi, sklo, khi, klo, start, active, inv,
                 nu) = jprep(tpair, rtable, rkey, step_idx)
                if use_cache:
                    # hot-key probe: validated hits leave the batch and
                    # the misses compact into the [dev_b_resid]
                    # residual the serve descends
                    (hit, cvhi, cvlo, khi, klo, start, active, inv_s,
                     nr) = jcache(pool, *cache_tables, khi, klo,
                                  active, start, inv)
                else:
                    inv_s = inv
                counters, done, found, vhi, vlo = jserve(
                    pool, counters, khi, klo, root_rep, active, start,
                    inv_s)
                if use_cache:
                    rcarry = jverify(tuple(rcarry), skhi, sklo, found,
                                     vhi, vlo, nu, inv, hit, cvhi,
                                     cvlo, nr)
                else:
                    rcarry = jverify(tuple(rcarry), skhi, sklo, found,
                                     vhi, vlo, nu)
                return counters, (step_idx,) + tuple(rcarry)
        else:  # pipelined: two-deep software pipeline, same 3 programs
            # the pending slot (:func:`_two_deep_slot`): batch k-1's
            # verify inputs — device handles only, the serve outputs
            # are already materializing when the slot is consumed.
            # After S steps + drain the carry is bit-identical to S
            # aligned steps'.
            _fold, _put, _drain, _pipe_reset = _two_deep_slot(jverify)

            def step(pool, counters, tpair, rtable, rkey, carry):
                step_idx, *rcarry = carry
                # 1. consume batch k-1: fold its answers into the
                #    receipts — off the serve(k-1) -> serve(k) path
                rcarry = _fold(tuple(rcarry))
                # 2. prep batch k into the slot verify just released
                #    (independent of the in-flight serve: a backend
                #    that overlaps programs runs it behind the serve)
                (step_idx, skhi, sklo, khi, klo, start, active, inv,
                 nu) = jprep(tpair, rtable, rkey, step_idx)
                if use_cache:
                    (hit, cvhi, cvlo, khi, klo, start, active, inv_s,
                     nr) = jcache(pool, *cache_tables, khi, klo,
                                  active, start, inv)
                else:
                    inv_s = inv
                # 3. serve batch k — the SAME compiled program object
                #    aligned (and the host-staged phase) dispatches
                counters, done, found, vhi, vlo = jserve(
                    pool, counters, khi, klo, root_rep, active, start,
                    inv_s)
                if use_cache:
                    _put(skhi, sklo, found, vhi, vlo, nu, inv, hit,
                         cvhi, cvlo, nr)
                else:
                    _put(skhi, sklo, found, vhi, vlo, nu)
                return counters, (step_idx,) + rcarry

            step.drain = _drain

        step.jprep, step.jserve, step.jverify = jprep, jserve, jverify
        programs = {"prep": jprep, "serve_fanout": jserve,
                    "verify": jverify}
        if use_cache:
            step.jcache = jcache
            # dispatch order: prep -> cache_probe -> serve -> verify
            programs = {"prep": jprep, "cache_probe": jcache,
                        "serve_fanout": jserve, "verify": jverify}

    elif fusion == "chained":
        def prep(tpair, rtable, rkey, step_idx):
            skhi, sklo, ukhi, uklo, start, active, seg, n_uniq = \
                prep_core(tpair, rtable, rkey, step_idx)
            # n_uniq ships as [1] so it shards per node like the rest
            return (step_idx + np.uint32(1), skhi, sklo, ukhi, uklo,
                    start, active, seg, n_uniq[None])

        jprep = DEV.wrap_program("staged.prep", jax.jit(jax.shard_map(
            prep, mesh=mesh, in_specs=(rep, rep, rep, rep),
            out_specs=(rep,) + (spec,) * 8, check_vma=False)))

        def serve(pool, counters, rcarry, skhi, sklo, ukhi, uklo, start,
                  active, seg, n_uniq_a):
            counters, found, vhi, vlo = serve_fanout(
                pool, counters, ukhi, uklo, start, active, seg)
            rcarry = verify_core(rcarry, skhi, sklo, found, vhi, vlo,
                                 n_uniq_a[0])
            return counters, rcarry

        serve_sm = jax.shard_map(
            serve, mesh=mesh,
            in_specs=(spec, spec, (rep,) * 4) + (spec,) * 8,
            out_specs=(spec, (rep,) * 4), check_vma=False)
        # donate counters only: the prep intermediates' shapes cannot
        # alias any serve output (donating them just warns every
        # compile), and donating 4 replicated scalars saves nothing
        jserve = DEV.wrap_program(
            "staged.serve_fanout_verify",
            jax.jit(serve_sm, donate_argnums=C.donate_argnums(1)))

        def step(pool, counters, tpair, rtable, rkey, carry):
            step_idx, *rcarry = carry
            step_idx, *arrs = jprep(tpair, rtable, rkey, step_idx)
            counters, rcarry = jserve(pool, counters, tuple(rcarry),
                                      *arrs)
            return counters, (step_idx,) + tuple(rcarry)

        step.jprep, step.jserve = jprep, jserve
        programs = {"prep": jprep, "serve_fanout_verify": jserve}

    else:  # fused: one program, CPU regression / toolchain re-tests
        def fused(pool, counters, rcarry, tpair, rtable, rkey, step_idx):
            skhi, sklo, ukhi, uklo, start, active, seg, n_uniq = \
                prep_core(tpair, rtable, rkey, step_idx)
            counters, found, vhi, vlo = serve_fanout(
                pool, counters, ukhi, uklo, start, active, seg)
            rcarry = verify_core(rcarry, skhi, sklo, found, vhi, vlo,
                                 n_uniq)
            return step_idx + np.uint32(1), counters, rcarry

        fused_sm = jax.shard_map(
            fused, mesh=mesh,
            in_specs=(spec, spec, (rep,) * 4, rep, rep, rep, rep),
            out_specs=(rep, spec, (rep,) * 4), check_vma=False)
        jfused = DEV.wrap_program(
            "staged.fused_step",
            jax.jit(fused_sm, donate_argnums=C.donate_argnums(1)))

        def step(pool, counters, tpair, rtable, rkey, carry):
            step_idx, *rcarry = carry
            step_idx, counters, rcarry = jfused(
                pool, counters, tuple(rcarry), tpair, rtable, rkey,
                step_idx)
            return counters, (step_idx,) + tuple(rcarry)

        step.jfused = jfused
        programs = {"fused_step": jfused}

    step.fusion, step.sampler = fusion, sampler
    step.programs, step.n_programs = programs, len(programs)
    step.cache = use_cache
    step.cache_slots = leaf_cache.slots if use_cache else None
    step.dev_b_resid = R_resid if use_cache else None
    step.pipeline_depth = 2 if fusion == "pipelined" else 1
    if not hasattr(step, "drain"):
        step.drain = lambda carry: carry  # nothing pending off-pipeline
    # phase -> compile-ledger label, the join key the roofline receipts
    # use (obs/device.rooflines: phase_profile walls x cost_analysis
    # floors).  Overlap-receipt keys (wall_ms/bubble_ms/...) are
    # deliberately absent — they are not programs.
    step.phase_labels = {name: prog.label
                         for name, prog in programs.items()}

    # SLO accounting hook (obs/slo.py): the staged loop is an open read
    # loop of `batch` client ops per step; the driver attributes a whole
    # DRAINED window at once (per-batch wall = elapsed / n_steps — the
    # amortized per-op latency model), so the per-step dispatch path
    # carries ZERO extra obs work.
    step.slo_class = "read"

    def record_slo(n_steps: int, elapsed_s: float) -> None:
        from sherman_tpu.obs import slo as _slo
        _slo.observe("read", n_steps * batch, elapsed_s, batches=n_steps)

    step.record_slo = record_slo

    def new_carry():
        """Fresh device-resident carry.  Also resets the pipelined
        mode's pending slot: a fresh receipts stream must not fold a
        stale batch left by an undrained previous run.  With the leaf
        cache on, two hit accumulators (sum_hits, sum_hits_uniq) ride
        at the END so every base field keeps its index."""
        if _pipe_reset is not None:
            _pipe_reset()
        vals = [np.uint32(0), np.int32(1), np.int32(0), np.int32(0),
                np.int32(0)]
        if use_cache:
            vals += [np.int32(0), np.int32(0)]
        return tuple(_rep_put(dsm, v) for v in vals)

    def phase_profile(pool, counters, tpair, rtable, rkey, reps: int = 4):
        """Per-phase wall-cost attribution of the staged step: each
        dispatched program runs K and 2K CHAINED repetitions (data-
        dependent carries) and costs ``(t_2K - t_K)/K``
        (:func:`_delta_ms` — cancels per-call dispatch/sync overhead).
        Read-only: safe to run mid-benchmark.  NOTE the per-phase sum
        can exceed the pipelined ms/step — the pipelined loop overlaps
        prep with serve; attribution measures each program standalone.
        Returns ``({phase: ms}, counters)`` with the threaded counters
        handle (the serve donates its input counters)."""
        box = {"c": counters}
        out = {}
        if fusion == "fused":
            rc0 = new_carry()

            def floop(k):
                si, rc = rc0[0], tuple(rc0[1:])
                for _ in range(k):
                    si, box["c"], rc = jfused(pool, box["c"], rc, tpair,
                                              rtable, rkey, si)
                jax.block_until_ready(rc)

            out["fused_step"] = _delta_ms(floop, reps)
            return out, box["c"]

        def prep_loop(k):
            si, o = new_carry()[0], None
            for _ in range(k):
                o = jprep(tpair, rtable, rkey, si)
                si = o[0]
            jax.block_until_ready(o)

        out["prep"] = _delta_ms(prep_loop, reps)
        arrs = jprep(tpair, rtable, rkey, new_carry()[0])[1:]
        jax.block_until_ready(arrs)
        if fusion in ("aligned", "pipelined"):
            skhi, sklo, khi, klo, start, active, inv, nu = arrs
            inv_s = inv
            hit = cvhi = cvlo = nr = None
            if use_cache:
                def cache_loop(k):
                    o = None
                    for _ in range(k):
                        o = jcache(pool, *cache_tables, khi, klo,
                                   active, start, inv)
                    jax.block_until_ready(o)

                out["cache_probe"] = _delta_ms(cache_loop, reps)
                # the serve measures the COMPACTED residual — the
                # width the live cache-on loop actually descends
                (hit, cvhi, cvlo, khi, klo, start, active, inv_s,
                 nr) = jcache(pool, *cache_tables, khi, klo, active,
                              start, inv)

            def serve_loop(k):
                o = None
                for _ in range(k):
                    box["c"], done, f, vh, vl = jserve(
                        pool, box["c"], khi, klo, root_rep, active,
                        start, inv_s)
                    o = f
                jax.block_until_ready(o)

            out["serve_fanout"] = _delta_ms(serve_loop, reps)
            box["c"], done, f, vh, vl = jserve(
                pool, box["c"], khi, klo, root_rep, active, start,
                inv_s)

            def verify_loop(k):
                rc = tuple(new_carry()[1:])
                for _ in range(k):
                    if use_cache:
                        rc = jverify(rc, skhi, sklo, f, vh, vl, nu,
                                     inv, hit, cvhi, cvlo, nr)
                    else:
                        rc = jverify(rc, skhi, sklo, f, vh, vl, nu)
                jax.block_until_ready(rc)

            out["verify"] = _delta_ms(verify_loop, reps)
            if fusion == "pipelined":
                # OVERLAP RECEIPT (:func:`overlap_receipt`): the
                # drained pipelined wall per step (same chained-delta
                # method) against the serial sum of the standalone
                # phase walls just measured.  The cache probe sits on
                # the prep side of the serve bound (it must finish
                # before the serve's active mask exists), so its wall
                # folds into the prep term.
                def pipe_loop(k):
                    c = new_carry()
                    for _ in range(k):
                        box["c"], c = step(pool, box["c"], tpair,
                                           rtable, rkey, c)
                    c = step.drain(c)
                    jax.block_until_ready(c)

                # warm both carry variants (fresh new_carry() inputs
                # vs threaded program outputs are distinct jit cache
                # entries) so no trace lands inside the delta
                pipe_loop(2)
                out.update(overlap_receipt(
                    out["prep"] + out.get("cache_probe", 0.0),
                    out["serve_fanout"], out["verify"],
                    _delta_ms(pipe_loop, reps)))
        else:  # chained

            def sv_loop(k):
                rc = tuple(new_carry()[1:])
                for _ in range(k):
                    box["c"], rc = jserve(pool, box["c"], rc, *arrs)
                jax.block_until_ready(rc)

            out["serve_fanout_verify"] = _delta_ms(sv_loop, reps)
        return out, box["c"]

    step.phase_profile = phase_profile

    table_d, rtable_d, rkey_d = staged or _stage_inputs(
        dsm, router, n_keys, theta, LB, seed, sampler)
    return step, (new_carry, table_d, rtable_d, rkey_d)


def make_staged_mixed_step(eng, *, n_keys: int, theta: float, salt: int,
                           batch: int, read_ratio: float, dev_rb: int,
                           dev_wb: int, log2_bins: int = 20,
                           check_xor: int = 0xDEADBEEF, seed: int = 13,
                           staged=None, sampler: str = "table",
                           fusion: str | None = None):
    """Device-staged sustained MIXED loop (YCSB-A/B shape): the same
    nothing-shipped open loop as :func:`make_staged_step`, but each step
    carries both point lookups and in-place updates through ONE fused
    ``mixed_step_spmd`` descent (reads see the pre-step snapshot, writes
    apply at the step boundary — reference parity:
    ``test/benchmark.cpp:159-188`` with ``kReadRatio < 100``).

    Client layout per node per step: ``R = round(batch * read_ratio)``
    read clients then ``batch - R`` write clients (roles fixed by slot;
    keys are iid zipf draws, so a fixed per-step count is the
    hypergeometric twin of the reference's per-op biased coin — same
    marginal mix, no dynamic shapes).  Each class is combined
    independently by the sort/flag-sort pipeline and served from the
    ``[reads | writes]`` row block the engine's half-width apply expects
    (``mixed_step_spmd`` ``write_lo``).

    Write values ENCODE THE WRITING STEP: ``v = key ^ check_xor ^
    (step + 1)`` (uint64, step in the low word).  Combining stays sound
    — a step's duplicate writes carry identical values, so supersede
    returns the value every duplicate wrote — and read verification
    becomes a linearization check, on device, inside the timed loop: a
    read's value must decode to a step STRICTLY BEFORE its own
    (``decoded <= step`` with writers stamping ``step + 1``), i.e.
    reads must observe the pre-step snapshot, never their own step's
    writes.  Bulk-loaded values decode to 0 and pass.

    Write receipts: every unique write row must come back ``ST_APPLIED``
    (update-only over live keys; on multi-node meshes a cross-node
    same-key duplicate may be ``ST_SUPERSEDED`` by the identical-value
    winner — also a success), and every write client's fanned-out
    status is checked in-step.

    Carry fields (replicated scalars):

        (step_idx, ok, n_correct_reads, n_ok_writes, sum_nuniq,
         max_nuniq_r, max_nuniq_w, serve_step_idx)

    ``serve_step_idx`` is the serve program's OWN step counter (prep's
    is already bumped when serve runs, so the linearization check keeps
    a separate one).  After S steps ``n_correct_reads ==
    S * R * machine_nr`` and ``n_ok_writes == S * (batch - R) *
    machine_nr`` or the phase is void.

    ``fusion`` picks the program structure, mirroring
    :func:`make_staged_step`'s knob on the mixed loop's two credible
    forms (default: ``pipelined`` iff ``SHERMAN_STAGED_FUSION`` says
    so, else ``chained`` — the mixed loop has no separate "aligned"
    comparator, its chained serve IS the canonical fused
    ``mixed_step_spmd`` program):

    - ``"chained"`` (default): prep -> serve, receipts folded inside
      the serve program (the round-5 form).
    - ``"pipelined"``: prep -> serve -> verify as a TWO-DEEP software
      pipeline — the receipts arithmetic moves to its own program fed
      from a pending slot one batch behind, exactly like the read-only
      pipelined mode, and the write batch k's journal-relevant apply
      still happens in serve order (the pipeline reorders only the
      RECEIPTS fold, never the pool writes).  Same arithmetic, same
      fold order: after ``step.drain`` the carry is bit-identical to
      ``chained``'s.

    The hot-key leaf cache deliberately stays OUT of this loop: its
    write half re-stamps the hot keys every step, so cached entries
    would invalidate as fast as they fill (the read-only staged loop
    and the engine's host ``mixed`` entry point are the cache's
    consumers; a mixed-loop A/B belongs behind its own receipt if the
    read ratio ever skews high enough to pay)."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    from sherman_tpu.models.batched import (
        AXIS, ST_APPLIED, ST_SUPERSEDED, mixed_step_spmd)
    from sherman_tpu.parallel import transport

    router = eng.router
    assert router is not None, "attach_router() first"
    cfg = eng.cfg
    dsm = eng.dsm
    N = cfg.machine_nr
    iters = eng._iters()
    spec, rep = eng._spec, eng._rep
    shift, nb = int(router.shift), int(router.nb)
    LB = int(log2_bins)
    gen_ranks, sampler = _rank_sampler(sampler, n_keys, theta, LB)
    root = np.int32(eng.tree._root_addr)
    salt_hi = np.uint32((salt >> 32) & 0xFFFFFFFF)
    salt_lo = np.uint32(salt & 0xFFFFFFFF)
    cx_hi = np.uint32((check_xor >> 32) & 0xFFFFFFFF)
    cx_lo = np.uint32(check_xor & 0xFFFFFFFF)
    i32 = lambda x: lax.bitcast_convert_type(x, jnp.int32)
    u32 = lambda x: lax.bitcast_convert_type(x, jnp.uint32)

    R = int(round(batch * read_ratio))
    Wc = batch - R
    assert 0 < R <= batch and Wc > 0, "mixed loop needs both classes"
    assert R >= dev_rb and Wc >= dev_wb, "dev caps cannot exceed class sizes"

    def prep(tpair, rtable, rkey, step_idx):
        with jax.named_scope("sample"):
            node = lax.axis_index(AXIS) if N > 1 else jnp.uint32(0)
            k = jax.random.fold_in(rkey, step_idx * np.uint32(N)
                                   + node.astype(jnp.uint32))
            w = jax.random.bits(k, (2, batch), dtype=jnp.uint32)
            rank = gen_ranks(tpair, w)
            khi_u, klo_u = _keys_of_ranks(rank, salt_hi, salt_lo)
        # slots [0, R) are read clients, [R, batch) write clients; each
        # class combines independently (same pipeline as the read-only
        # staged step)
        with jax.named_scope("combine"):
            rskhi, rsklo, rukhi, ruklo, rseg, r_nu = _sort_combine(
                khi_u[:R], klo_u[:R], dev_rb)
            wskhi, wsklo, wukhi, wuklo, wseg, w_nu = _sort_combine(
                khi_u[R:], klo_u[R:], dev_wb)
            # the [reads | writes] row block mixed_step_spmd serves
            akhi = jnp.concatenate([rukhi, wukhi])
            aklo = jnp.concatenate([ruklo, wuklo])
            act_r = jnp.concatenate([
                lax.iota(jnp.int32, dev_rb) < r_nu,
                jnp.zeros((dev_wb,), bool)])
            act_w = jnp.concatenate([
                jnp.zeros((dev_rb,), bool),
                lax.iota(jnp.int32, dev_wb) < w_nu])
            # write value = key ^ check_xor ^ (step + 1): identical
            # across a step's duplicates (combining sound),
            # step-decodable for the read-side linearization check
            stamp = step_idx + np.uint32(1)
            vhi = jnp.concatenate([jnp.zeros((dev_rb,), jnp.uint32),
                                   wukhi ^ cx_hi])
            vlo = jnp.concatenate([jnp.zeros((dev_rb,), jnp.uint32),
                                   wuklo ^ cx_lo ^ stamp])
        with jax.named_scope("router_probe"):
            start = _router_probe(rtable, akhi, aklo, shift, nb)
        return (step_idx + np.uint32(1), akhi, aklo, vhi, vlo, act_r,
                act_w, start, rskhi, rsklo, rseg, r_nu[None],
                wseg, w_nu[None])

    def serve_fanout_core(pool, locks, counters, akhi, aklo, vhi, vlo,
                          act_r, act_w, start, rseg, wseg):
        """The mixed serve minus receipts: fused descent/apply + the
        monotone per-client fan-out of read answers and write statuses
        (GLOBAL indices on multi-node meshes).  Shared verbatim by the
        chained and pipelined forms so their pools and receipts cannot
        diverge."""
        pool, counters, status, done_r, found, rvh, rvl = mixed_step_spmd(
            pool, locks, counters, i32(akhi), i32(aklo), i32(vhi),
            i32(vlo), root, act_r, act_w, start, cfg=cfg, iters=iters,
            write_lo=dev_rb, update_only=True)
        with jax.named_scope("fanout"):
            ans = jnp.stack([found.astype(jnp.int32), rvh, rvl,
                             jnp.zeros_like(rvh)], axis=-1)[:dev_rb]
            stat_w = status[dev_rb:]
            if N > 1:
                node = lax.axis_index(AXIS)
                ans = transport.gather_rows(ans, AXIS)
                stat_w = transport.gather_rows(stat_w, AXIS)
                rseg = rseg + node.astype(jnp.int32) * dev_rb
                wseg = wseg + node.astype(jnp.int32) * dev_wb
            out = jnp.take_along_axis(
                ans, jnp.clip(rseg, 0, ans.shape[0] - 1)[:, None], axis=0)
            st_cli = jnp.take_along_axis(
                stat_w, jnp.clip(wseg, 0, stat_w.shape[0] - 1), axis=0)
        return pool, counters, out, st_cli

    def verify_mixed_core(rcarry, rskhi, rsklo, out, st_cli, r_nu, w_nu):
        """Receipts: the on-device linearization check (a read's value
        must decode to a strictly earlier step — writers stamp step+1,
        bulk decodes to 0) + the write-status audit."""
        ok, n_corr_r, n_ok_w, sum_nu, max_nu_r, max_nu_w, sidx = rcarry
        dec_hi = u32(out[:, 1]) ^ rskhi ^ cx_hi
        dec_lo = u32(out[:, 2]) ^ rsklo ^ cx_lo
        corr_r = ((out[:, 0] != 0) & (dec_hi == 0) & (dec_lo <= sidx))
        ok_w = ((st_cli == ST_APPLIED)
                | ((st_cli == ST_SUPERSEDED) if N > 1
                   else jnp.zeros_like(st_cli, bool)))
        inc_r = jnp.sum(corr_r.astype(jnp.int32))
        inc_w = jnp.sum(ok_w.astype(jnp.int32))
        step_ok = ((r_nu <= dev_rb) & (w_nu <= dev_wb)).astype(jnp.int32)
        if N > 1:
            inc_r = lax.psum(inc_r, AXIS)
            inc_w = lax.psum(inc_w, AXIS)
            sum_inc = lax.psum(r_nu + w_nu, AXIS)
            max_r = lax.pmax(r_nu, AXIS)
            max_w = lax.pmax(w_nu, AXIS)
            step_ok = lax.pmin(step_ok, AXIS)
        else:
            sum_inc, max_r, max_w = r_nu + w_nu, r_nu, w_nu
        return (jnp.minimum(ok, step_ok), n_corr_r + inc_r,
                n_ok_w + inc_w, sum_nu + sum_inc,
                jnp.maximum(max_nu_r, max_r),
                jnp.maximum(max_nu_w, max_w),
                sidx + jnp.uint32(1))

    fusion = fusion or ("pipelined" if C.staged_fusion() == "pipelined"
                        else "chained")
    if fusion not in ("chained", "pipelined"):
        raise ConfigError(f"mixed fusion={fusion!r}: want "
                         "chained|pipelined")
    mesh = dsm.mesh
    _pipe_reset = None
    prep_sm = jax.shard_map(
        prep, mesh=mesh, in_specs=(rep, rep, rep, rep),
        out_specs=(rep,) + (spec,) * 13, check_vma=False)
    jprep = DEV.wrap_program("staged_mixed.prep", jax.jit(prep_sm))

    if fusion == "chained":
        def serve(pool, locks, counters, rcarry, akhi, aklo, vhi, vlo,
                  act_r, act_w, start, rskhi, rsklo, rseg, r_nu_a, wseg,
                  w_nu_a):
            pool, counters, out, st_cli = serve_fanout_core(
                pool, locks, counters, akhi, aklo, vhi, vlo, act_r,
                act_w, start, rseg, wseg)
            rcarry = verify_mixed_core(rcarry, rskhi, rsklo, out,
                                       st_cli, r_nu_a[0], w_nu_a[0])
            return pool, counters, rcarry

        serve_sm = jax.shard_map(
            serve, mesh=mesh,
            in_specs=(spec, spec, spec, (rep,) * 7) + (spec,) * 13,
            out_specs=(spec, spec, (rep,) * 7), check_vma=False)
        # pool + counters donated; rcarry is NOT (callers block the
        # dispatch window on carry[1] — see the read-only step's note)
        jserve = DEV.wrap_program(
            "staged_mixed.serve_fanout_verify",
            jax.jit(serve_sm, donate_argnums=C.donate_argnums(0, 2)))

        def step(pool, locks, counters, tpair, rtable, rkey, carry):
            step_idx, *rcarry = carry
            step_idx, *arrs = jprep(tpair, rtable, rkey, step_idx)
            pool, counters, rcarry = jserve(pool, locks, counters,
                                            tuple(rcarry), *arrs)
            return pool, counters, (step_idx,) + tuple(rcarry)

        step.jprep, step.jserve = jprep, jserve
        step.programs = {"prep": jprep, "serve_fanout_verify": jserve}
    else:  # pipelined: receipts fold one batch behind the serve
        def serve_p(pool, locks, counters, akhi, aklo, vhi, vlo, act_r,
                    act_w, start, rseg, wseg):
            return serve_fanout_core(pool, locks, counters, akhi, aklo,
                                     vhi, vlo, act_r, act_w, start,
                                     rseg, wseg)

        serve_sm = jax.shard_map(
            serve_p, mesh=mesh, in_specs=(spec,) * 12,
            out_specs=(spec,) * 4, check_vma=False)
        jserve = DEV.wrap_program(
            "staged_mixed.serve_fanout",
            jax.jit(serve_sm, donate_argnums=C.donate_argnums(0, 2)))

        def verify_p(rcarry, rskhi, rsklo, out, st_cli, r_nu_a, w_nu_a):
            return verify_mixed_core(rcarry, rskhi, rsklo, out, st_cli,
                                     r_nu_a[0], w_nu_a[0])

        verify_sm = jax.shard_map(
            verify_p, mesh=mesh,
            in_specs=((rep,) * 7,) + (spec,) * 6,
            out_specs=(rep,) * 7, check_vma=False)
        jverify = DEV.wrap_program("staged_mixed.verify",
                                   jax.jit(verify_sm))
        _fold, _put, _drain, _pipe_reset = _two_deep_slot(jverify)

        def step(pool, locks, counters, tpair, rtable, rkey, carry):
            step_idx, *rcarry = carry
            # consume batch k-1's fanned-out answers/statuses; the
            # POOL writes of batch k-1 already landed in serve order —
            # the pipeline reorders only the receipts fold
            rcarry = _fold(tuple(rcarry))
            (step_idx, akhi, aklo, vhi, vlo, act_r, act_w, start,
             rskhi, rsklo, rseg, r_nu_a, wseg, w_nu_a) = jprep(
                tpair, rtable, rkey, step_idx)
            pool, counters, out, st_cli = jserve(
                pool, locks, counters, akhi, aklo, vhi, vlo, act_r,
                act_w, start, rseg, wseg)
            _put(rskhi, rsklo, out, st_cli, r_nu_a, w_nu_a)
            return pool, counters, (step_idx,) + rcarry

        step.drain = _drain
        step.jprep, step.jserve, step.jverify = jprep, jserve, jverify
        step.programs = {"prep": jprep, "serve_fanout": jserve,
                         "verify": jverify}

    step.sampler = sampler
    step.fusion = fusion
    step.n_programs = len(step.programs)
    step.pipeline_depth = 2 if fusion == "pipelined" else 1
    if not hasattr(step, "drain"):
        step.drain = lambda carry: carry
    # roofline join key (see the read-only factory's phase_labels note)
    step.phase_labels = {name: prog.label
                         for name, prog in step.programs.items()}

    # SLO hook (see make_staged_step): the fused read/write batch is the
    # mixed class's wall, attributed per drained window by the driver
    step.slo_class = "mixed"

    def record_slo(n_steps: int, elapsed_s: float) -> None:
        from sherman_tpu.obs import slo as _slo
        _slo.observe("mixed", n_steps * batch, elapsed_s, batches=n_steps)

    step.record_slo = record_slo

    def new_carry():
        """(step_idx, ok, n_correct_reads, n_ok_writes, sum_nuniq,
        max_nuniq_r, max_nuniq_w, serve_step_idx) — serve keeps its own
        step counter (last slot) so its linearization check cannot read
        prep's already-bumped one."""
        if _pipe_reset is not None:
            _pipe_reset()
        return tuple(_rep_put(dsm, v)
                     for v in (np.uint32(0), np.int32(1), np.int32(0),
                               np.int32(0), np.int32(0), np.int32(0),
                               np.int32(0), np.uint32(0)))

    def phase_profile(pool, locks, counters, tpair, rtable, rkey,
                      reps: int = 4):
        """Per-phase attribution of the mixed step (same chained-delta
        methodology as the read-only step's).  NOT read-only: the serve
        chain re-applies ONE prep's write batch each repetition (same
        keys, same stamped values — idempotent tree content, but the
        profiled steps' stamps land in the pool), so run it only AFTER
        the receipt-checked windows.  Returns ``({phase: ms}, pool,
        counters)``."""
        box = {"p": pool, "c": counters}

        def prep_loop(k):
            si, o = new_carry()[0], None
            for _ in range(k):
                o = jprep(tpair, rtable, rkey, si)
                si = o[0]
            jax.block_until_ready(o)

        out = {"prep": _delta_ms(prep_loop, reps)}
        arrs = jprep(tpair, rtable, rkey, new_carry()[0])[1:]
        jax.block_until_ready(arrs)

        if fusion == "chained":
            def sv_loop(k):
                rc = tuple(new_carry()[1:])
                for _ in range(k):
                    box["p"], box["c"], rc = jserve(box["p"], locks,
                                                    box["c"], rc, *arrs)
                jax.block_until_ready(rc)

            out["serve_fanout_verify"] = _delta_ms(sv_loop, reps)
            return out, box["p"], box["c"]

        # pipelined: attribute the split serve and verify programs,
        # then the drained pipelined wall (the overlap receipt — see
        # the read-only step's phase_profile)
        (akhi, aklo, vhi, vlo, act_r, act_w, start, rskhi, rsklo,
         rseg, r_nu_a, wseg, w_nu_a) = arrs

        def serve_loop(k):
            o = None
            for _ in range(k):
                box["p"], box["c"], o, st = jserve(
                    box["p"], locks, box["c"], akhi, aklo, vhi, vlo,
                    act_r, act_w, start, rseg, wseg)
            jax.block_until_ready(o)

        out["serve_fanout"] = _delta_ms(serve_loop, reps)
        box["p"], box["c"], o, st = jserve(
            box["p"], locks, box["c"], akhi, aklo, vhi, vlo, act_r,
            act_w, start, rseg, wseg)

        def verify_loop(k):
            rc = tuple(new_carry()[1:])
            for _ in range(k):
                rc = jverify(rc, rskhi, rsklo, o, st, r_nu_a, w_nu_a)
            jax.block_until_ready(rc)

        out["verify"] = _delta_ms(verify_loop, reps)

        def pipe_loop(k):
            c = new_carry()
            for _ in range(k):
                box["p"], box["c"], c = step(box["p"], locks, box["c"],
                                             tpair, rtable, rkey, c)
            c = step.drain(c)
            jax.block_until_ready(c)

        # warm both carry variants (see the read-only overlap receipt)
        pipe_loop(2)
        out.update(overlap_receipt(out["prep"], out["serve_fanout"],
                                   out["verify"],
                                   _delta_ms(pipe_loop, reps)))
        return out, box["p"], box["c"]

    step.phase_profile = phase_profile

    table_d, rtable_d, rkey_d = staged or _stage_inputs(
        dsm, router, n_keys, theta, LB, seed, sampler)
    return step, (new_carry, table_d, rtable_d, rkey_d)


def make_device_prep(eng, *, width: int):
    """Fused DEVICE request-plane prep for the ingress step (PR 17's
    ``config.prep_impl = "device"``): one compiled program per ladder
    width that performs on device exactly what the host path's
    ``np.unique`` + ``LeafRouter.host_start`` + zero-padding do —
    duplicate-key combining, dedup, key sort, router probe — emitting
    the staged fan-out inputs ``(khi, klo, active, start, inv)``
    BIT-IDENTICALLY (the CI pin in tests/test_prep.py), plus the
    unique count as a replicated device scalar.

    Anatomy (the same two-sort discipline as :func:`_sort_combine`,
    generalized to partial batches): a 5-operand ``lax.sort`` orders
    the raw (hi, lo) pairs unsigned and carries the original index; a
    segment scan numbers the unique groups (this IS the host ``inv`` —
    ``np.unique``'s inverse is the rank of each key in sorted unique
    order); a flag-sort compacts the first-occurrence rows (already
    key-sorted, so the unique set matches ``np.unique``'s order); and
    the router probe reuses the HOST table uploaded as a replicated
    device array with the shift as TRACED data
    (:func:`sherman_tpu.ops.bits.u64_shr_dyn`) — a span grow updates a
    scalar input instead of retracing the sealed program.

    Padding contract: rows past ``n`` carry the KEY_POS_INF sentinel
    pair ``(-1, -1)`` — excluded from the valid key range
    (config.KEY_MAX < KEY_POS_INF), it can never collide with a client
    key, sorts strictly last, and therefore forms exactly ONE trailing
    unique group iff ``n < width`` — subtracting it yields the host
    ``U``.  Masked unique rows and the inverse map then zero exactly
    like the host path's padding.

    Returns ``(prep_fn, upload)``: ``prep_fn(khi_raw, klo_raw, n,
    rtable, shift) -> (khi, klo, active, start, inv, n_uniq)`` with the
    five arrays node-sharded for the fan-out and ``n_uniq`` replicated;
    ``upload(x)`` places host values as replicated device arrays
    (multihost-aware)."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    dsm = eng.dsm
    rep_sharding = jax.sharding.NamedSharding(
        dsm.mesh, jax.sharding.PartitionSpec())

    def prep_core(khi_raw, klo_raw, n, rtable, shift):
        idx0 = jnp.arange(width, dtype=jnp.int32)
        # sort by unsigned 64-bit key, carrying the raw pair + index
        _, _, skhi, sklo, sidx = lax.sort(
            (bits._ux(khi_raw), bits._ux(klo_raw), khi_raw, klo_raw,
             idx0), num_keys=2)
        first = jnp.concatenate([
            jnp.ones((1,), bool),
            (skhi[1:] != skhi[:-1]) | (sklo[1:] != sklo[:-1])])
        seg = (jnp.cumsum(first.astype(jnp.int32)) - 1)
        # the sentinel contributes one trailing group iff padding exists
        n_uniq = seg[-1] + 1 - (n < width).astype(jnp.int32)
        # compact first-occurrence rows (key-sorted, = np.unique order);
        # the sentinel group's head lands at position n_uniq and is
        # masked to zero with the rest of the tail, like the host pad
        flag = (~first).astype(jnp.int32)
        _, _, _, ckhi, cklo = lax.sort(
            (flag, bits._ux(skhi), bits._ux(sklo), skhi, sklo),
            num_keys=3)
        active = idx0 < n_uniq
        ukhi = jnp.where(active, ckhi, 0)
        uklo = jnp.where(active, cklo, 0)
        # router probe, dynamic shift (host_start twin: key 0 -> bucket
        # 0 -> table[0] covers the masked tail exactly like the host)
        nb = rtable.shape[0]
        bhi, blo = bits.u64_shr_dyn(
            lax.bitcast_convert_type(ukhi, jnp.uint32),
            lax.bitcast_convert_type(uklo, jnp.uint32), shift)
        bucket = jnp.where(bhi != 0, jnp.uint32(nb - 1),
                           jnp.minimum(blo, jnp.uint32(nb - 1)))
        start = rtable[bucket.astype(jnp.int32)]
        # un-sort the segment map: inv[i] = unique rank of client row i
        _, inv = lax.sort((sidx, seg), num_keys=1)
        inv = jnp.where(idx0 < n, inv, 0)
        return ukhi, uklo, active, start, inv, n_uniq

    prep_fn = DEV.wrap_program(
        "serve.device_prep",
        jax.jit(prep_core,
                out_shardings=(dsm.shard, dsm.shard, dsm.shard,
                               dsm.shard, dsm.shard, rep_sharding)))
    return prep_fn, (lambda x: _rep_put(dsm, x))


def make_ingress_step(eng, *, width: int, leaf_cache=None,
                      prep_impl: str | None = None):
    """External-driver hook on the staged serving substrate — the
    serving front door's read path (:mod:`sherman_tpu.serve`).

    The staged factories above generate their client batches ON DEVICE
    (the bench's synthetic zipf open loop); a front door serves batches
    that arrive from OUTSIDE.  This factory is the host-fed twin: client
    key batches of ONE fixed compiled ``width`` are combined, probed and
    dispatched through the same serve kernel body the staged loops and
    the host-staged throughput phase run, entered at a packed host
    boundary (``BatchedEngine._get_search_fanout_packed``, ledger label
    ``engine.search_fanout_packed``): the step's batch goes up as one
    [width, 5] int32 put, and its answers come back as one [width, 4]
    table whose host copy starts at launch.  The per-request answer
    fan-out runs on device via the unique-inverse map, exactly like
    ``search_combined`` but at the CALLER's width instead of the
    engine's fixed ``machine_nr * B``.  Fixed width is the whole
    point: the adaptive batcher picks a step width from a pre-warmed
    ladder, and every ladder rung is one compiled shape — the sealed
    serving loop stays zero-retrace by construction.

    Split dispatch/complete protocol (the two-deep pipeline's raw
    material — the front door keeps ONE batch in flight and overlaps
    batch k's host prep + dispatch with batch k-1's device serve, the
    ``fusion="pipelined"`` discipline applied to external traffic)::

        handle = step.dispatch(keys)        # launch only, keys u64 [n]
        vals, found = step.complete(handle) # blocks, materializes

    ``dispatch`` contract (it is a registered SL001 hot function — no
    host syncs of device data inside): ``keys`` MUST already be a
    uint64 ndarray with ``0 < n <= width`` and every key in
    ``[KEY_MIN, KEY_MAX]`` (the front door validates at admission);
    duplicate keys share one descent row (request combining — the
    unique set is key-sorted, the round-1 locality win).  With
    ``leaf_cache`` attached the unique batch is probed first
    (pool-validated hits leave the active set and merge back per client
    row in ``complete`` — bit-identical to the uncached path, the
    engine read paths' own contract) and the raw client stream feeds
    the admission sketch (``observe``), so sketch-driven admission
    learns from REAL request streams.

    Straggler contract: rows whose descent overran the budget (stale
    router seeds after splits/growth) are rescued in ``complete`` via
    the engine's root-descent ``search`` — warm it before sealing.

    NOTE this factory and ``BatchedEngine.search_combined`` implement
    the same combine/probe/fan-out/rescue/merge protocol at different
    width regimes (the engine's fixed ``machine_nr * B`` + client
    quantum vs the caller's ladder rung); the bit-identity pin in
    ``tests/test_serve.py`` (ingress vs ``search_combined`` on the
    same batch) is the guard that keeps the two copies from
    diverging.
    """
    router = eng.router
    if router is None:
        raise ConfigError("make_ingress_step: attach_router() first — "
                          "the front door serves router-seeded descents")
    if width <= 0 or width % eng.cfg.machine_nr != 0:
        raise ConfigError(
            f"ingress width {width} must be a positive multiple of "
            f"machine_nr={eng.cfg.machine_nr} (the batch shards over "
            "the node mesh)")
    if prep_impl is None:
        prep_impl = C.prep_impl()
    if prep_impl not in ("host", "device"):
        raise ConfigError(
            f"make_ingress_step: prep_impl={prep_impl!r}: want "
            "host|device")
    if prep_impl == "device" and leaf_cache is not None:
        # documented fallback (config.prep_impl): the cache probe is
        # host-in/host-out (it syncs its hit count), so device prep
        # composed with it would reintroduce the per-batch host
        # round-trip the knob exists to remove
        prep_impl = "host"
    iters = eng._iters()
    fn = eng._get_search_fanout_packed(iters)
    # the serve's root argument is device-resident: put here, and again
    # only when the tree's root moves — never a host value per call
    _root = {"addr": None, "dev": None}

    def _root_dev():
        addr = eng.tree._root_addr
        if addr != _root["addr"]:
            _root["dev"] = _rep_put(eng.dsm, np.int32(addr))
            _root["addr"] = addr
        return _root["dev"]

    _root_dev()
    # prep-phase attribution (PR 17): per-dispatch host wall of the
    # request plane, split host-vs-device — histogram and counter
    # handles created here so dispatch (SL001-hot) only records plain
    # numbers.  The spans below run on the caller's (the front door's
    # dispatcher) thread; ``step`` is the caller's step id.
    import time as _time
    from sherman_tpu import obs as _obs
    _h_prep = _obs.histogram(
        "prep.device_dispatch_ms" if prep_impl == "device"
        else "prep.host_ms")
    _obs.gauge("prep.impl_device").set(
        1.0 if prep_impl == "device" else 0.0)
    _c_rescues = _obs.counter("serve.rescues")
    _c_rescued_keys = _obs.counter("serve.rescued_keys")
    # the packed boundary at work: transfers per ingress step (one put,
    # one get), and steps whose device work was done when completion
    # began (so the answer copy started at launch had it to copy)
    _c_puts = _obs.counter("serve.h2d_puts")
    _c_gets = _obs.counter("serve.d2h_gets")
    _c_ready = _obs.counter("serve.answer_copy_ready")

    def dispatch(keys, step: int = -1):
        t0p = _time.perf_counter()
        n = keys.shape[0]
        with _obs.span("serve.prep.combine", hot=True, step=step):
            uk, inv = np.unique(keys, return_inverse=True)
            U = uk.shape[0]
            # a fresh buffer a step: the put may read it after dispatch
            # returns (an asynchronous or zero-copy transfer)
            packed = np.zeros((width, 5), np.int32)
            khi, klo, active, start, inv_p = packed.T   # column views
            khi[:U], klo[:U] = bits.keys_to_pairs(uk)
            active[:U] = 1
            inv_p[:n] = inv
        chit = cvhi = cvlo = None
        if leaf_cache is not None:
            # admission sketch sees the RAW (duplicated) client stream —
            # frequency ranking needs the multiplicities — then the
            # probe drops pool-validated hits out of the device batch
            with _obs.span("serve.prep.cache", hot=True, step=step):
                leaf_cache.observe(keys)
                chit, cvhi, cvlo = leaf_cache.probe(khi, klo, active != 0)
                active[chit] = 0
        with _obs.span("serve.prep.router", hot=True, step=step):
            start[:] = router.host_start(khi, klo)
        with _obs.span("serve.prep.h2d", hot=True, step=step):
            packed = eng._shard(packed)
            _c_puts.inc()
        # launch-only, the engine step contract; the answers' host copy
        # starts here and runs while the next step is prepared
        with _obs.span("serve.launch", hot=True, step=step):
            with eng._step_mutex:
                eng.dsm.counters, ans = fn(eng.dsm.pool, eng.dsm.counters,
                                           packed, _root_dev())
            ans.copy_to_host_async()
        _h_prep.record((_time.perf_counter() - t0p) * 1e3)
        return n, U, uk, inv, ans, chit, cvhi, cvlo, step

    def rescue(uk, step):
        """Straggler rescue (stale seeds / height growth): the engine's
        root-descent path answers the whole unique set ``uk`` (search()
        owns retries + SLO attribution)."""
        with _obs.span("serve.rescue", step=step, keys=uk.shape[0]):
            _c_rescues.inc()
            _c_rescued_keys.inc(uk.shape[0])
            return eng.search(uk)

    def complete(handle):
        n, U, uk, inv, ans, chit, cvhi, cvlo, k = handle
        with _obs.span("serve.materialize", hot=True, step=k):
            _c_ready.inc(int(ans.is_ready()))
            ans = eng._unshard(ans)
            _c_gets.inc()
            # lane 3 is each client row's unique row's done flag, and
            # every unique row has a client row: all done iff all rows
            done = ans[:n, 3] != 0
            ch = None if chit is None else chit[:U][inv]  # client rows
            if ch is not None:
                done = done | ch
            stragglers = not bool(done.all())
            if not stragglers:
                vals = bits.pairs_to_keys(ans[:n, 1], ans[:n, 2])
                fnd = ans[:n, 0] != 0
                if ch is not None and ch.any():
                    # cache hits' device rows were inactive — overwrite
                    # their client rows through the same inverse map the
                    # fan-out used
                    fnd[ch] = True
                    vals[ch] = bits.pairs_to_keys(
                        cvhi[:U], cvlo[:U])[inv][ch]
        if stragglers:
            vals_u, found_u = rescue(uk, k)
            return vals_u[inv], found_u[inv]
        return vals, fnd

    def step(keys):
        """Synchronous convenience: dispatch + complete in one call
        (closed-loop drivers and tests; the front door pipelines the
        two halves itself)."""
        return complete(dispatch(keys))

    def drain(handle):
        """Teardown-path completion (the front door's kill/drain hook):
        materialize the handle's device work and discard it WITHOUT
        the straggler rescue — a draining or crashing server must not
        launch fresh root descents (``eng.search`` compiles programs,
        takes the step mutex, and can raise through a degraded
        engine).  The in-flight step's first serve output (the answer
        table; the device twin's done flags) is blocked on, which the
        whole serve finishes with; nothing is returned — the caller has
        already failed or resolved the slot's futures."""
        eng._unshard(handle[4])
        _c_gets.inc()

    programs = {"serve_fanout": fn}
    if prep_impl == "device":
        import jax

        # the device-resident prep outputs feed the unpacked entry
        ufn = eng._get_search_fanout(iters)
        prep_fn, _upload = make_device_prep(eng, width=width)
        programs = {"serve_fanout": ufn, "device_prep": prep_fn}
        # router-table snapshot versioned by the split/grow counters:
        # plain Python ints, so staleness detection costs two compares
        # per dispatch and the re-upload happens only when the table
        # actually moved (splits_noted / span_grows bump)
        _rt = {"ver": None, "rtable": None, "shift": None}

        def _router_state():
            ver = (router.splits_noted, router.span_grows)
            if _rt["ver"] != ver:
                with router._read_locked():
                    table = np.array(router.table_np)
                    shift = np.uint32(router.shift)
                    ver = (router.splits_noted, router.span_grows)
                _rt["rtable"] = _upload(table)
                _rt["shift"] = _upload(shift)
                _rt["ver"] = ver
            return _rt["rtable"], _rt["shift"]

        def dispatch_device(keys, step: int = -1):
            """Device-prep twin of ``dispatch`` (same SL001 hot-path
            contract: launch-only, no host syncs of device data): the
            host's only per-batch work is the pair split + sentinel
            pad + three scalar/array uploads — combining, dedup, sort
            and the router probe all run in the sealed ``prep_fn``
            program, whose outputs feed the serve fan-out without
            touching the host."""
            t0p = _time.perf_counter()
            n = keys.shape[0]
            with _obs.span("serve.prep.combine", hot=True, step=step):
                kh, kl = bits.keys_to_pairs(keys)
                khi_raw = np.full(width, -1, np.int32)  # KEY_POS_INF pair
                klo_raw = np.full(width, -1, np.int32)
                khi_raw[:n] = kh
                klo_raw[:n] = kl
            with _obs.span("serve.prep.router", hot=True, step=step):
                rtable, shift = _router_state()
            with _obs.span("serve.prep.h2d", hot=True, step=step):
                args = (jax.device_put(khi_raw), jax.device_put(klo_raw),
                        jax.device_put(np.int32(n)))
            with _obs.span("serve.launch", hot=True, step=step):
                khi, klo, active, start, inv_p, n_uniq = prep_fn(
                    *args, rtable, shift)
                # launch-only, the engine step contract
                with eng._step_mutex:
                    eng.dsm.counters, done, found, vhi, vlo = ufn(
                        eng.dsm.pool, eng.dsm.counters, khi, klo,
                        _root_dev(), active, start, inv_p)
            _h_prep.record((_time.perf_counter() - t0p) * 1e3)
            return (n, n_uniq, (khi, klo), inv_p, done, found, vhi, vlo,
                    None, None, None, step)

        def complete_device(handle):
            """Completion half (materializes by design): the unique
            count syncs here, and the straggler rescue lazily
            materializes the unique set + inverse map only when a
            descent actually overran."""
            n, n_uniq, ukpair, inv_p, done, found, vhi, vlo, *_, k = handle
            with _obs.span("serve.materialize", hot=True, step=k):
                done, found, vhi, vlo = eng._unshard(done, found, vhi,
                                                     vlo)
                U = int(np.asarray(n_uniq))
                stragglers = not bool(np.asarray(done[:U]).all())
                if stragglers:
                    ukhi, uklo = eng._unshard(*ukpair)
                    uk = bits.pairs_to_keys(ukhi[:U], uklo[:U])
                    inv = np.asarray(eng._unshard(inv_p))[:n]
                else:
                    vals = np.array(bits.pairs_to_keys(vhi[:n], vlo[:n]))
                    fnd = np.array(found[:n])
            if stragglers:
                vals_u, found_u = rescue(uk, k)
                return vals_u[inv], found_u[inv]
            return vals, fnd

        dispatch, complete = dispatch_device, complete_device

    def prep_profile(keys, reps: int = 8) -> dict:
        """Chained-delta wall of the request-plane prep ALONE for this
        step's impl — the host-vs-device A/B's per-phase number
        (tools/profile_prep.py publishes it; record_phase_obs routes it
        into the ``prep.*`` histograms).  Host mode times the actual
        ``np.unique`` + router-probe + pad sequence; device mode chains
        ``prep_fn`` dispatches and blocks once at the end, so the
        per-dispatch overhead cancels exactly like every other
        chained-delta phase receipt."""
        keys = np.asarray(keys, np.uint64)
        n = keys.shape[0]
        if prep_impl == "device":
            import jax

            kh, kl = bits.keys_to_pairs(keys)
            khi_raw = np.full(width, -1, np.int32)
            klo_raw = np.full(width, -1, np.int32)
            khi_raw[:n] = kh
            klo_raw[:n] = kl
            rtable, shift = _router_state()
            dk, dl = jax.device_put(khi_raw), jax.device_put(klo_raw)
            dn = jax.device_put(np.int32(n))

            def loop(k):
                out = None
                for _ in range(k):
                    out = prep_fn(dk, dl, dn, rtable, shift)
                np.asarray(out[-1])  # drain
            return {"prep_device_ms": _delta_ms(loop, reps)}

        def loop(k):
            for _ in range(k):
                uk, inv = np.unique(keys, return_inverse=True)
                U = uk.shape[0]
                kh, kl = bits.keys_to_pairs(uk)
                khi = np.zeros(width, kh.dtype)
                klo = np.zeros(width, kl.dtype)
                khi[:U] = kh
                klo[:U] = kl
                router.host_start(khi, klo)
        return {"prep_host_ms": _delta_ms(loop, reps)}

    step.dispatch = dispatch
    step.complete = complete
    step.drain = drain
    step.width = width
    step.cache = leaf_cache is not None
    step.prep_impl = prep_impl
    step.prep_profile = prep_profile
    step.programs = programs
    step.phase_labels = {name: prog.label for name, prog in programs.items()}
    return step
