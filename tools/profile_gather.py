#!/usr/bin/env python
"""Page-engine kernel profiler — the pallas-vs-xla gather/scatter A/B.

Times the three ops/pallas_page.py kernels against their XLA twins at a
configurable row count, side by side, with the chained-delta method from
``tools/profile_insert.py`` (each phase runs K and 2K times chained
inside one jitted fori_loop with data-dependent carries; cost =
(t_2K - t_K)/K, which cancels the per-call sync exactly):

- ``descent_round``   one fused gather+pick round (the routed-search
                      descent floor: 54.7-55.4 ms at 2 M rows on the
                      XLA path, BENCHMARKS.md phase table)
- ``snapshot_gather`` the apply path's page snapshot (~28 ms XLA)
- ``writeback_3w/5w`` the update/insert write-back (XLA: ~13.5 ms per
                      word lane)

Emits a table on stderr, ONE JSON line on stdout
({phase: {xla_ms, pallas_ms, ratio}}), and records each timing as a
``kernels.{phase}_{impl}_ms`` obs histogram so bench artifacts can carry
the same receipts (`bench.py` embeds them via ``kernel_phase_ms``).

On non-TPU backends the pallas kernels run in INTERPRETER mode — orders
of magnitude slower, useful only as a mechanics smoke (CI runs it at
tiny --rows); the chip capture is the number that decides the
``gather_impl`` knob.  See BENCHMARKS.md "Chip-session queue".

Usage:  python tools/profile_gather.py [--rows N] [--keys N] [--k K]
                                       [--impls xla,pallas]
"""

from __future__ import annotations

import argparse
import functools
import json
import sys
import time

import numpy as np

from common import build_cluster, pages_for_keys


# pages of the pool copy the write-back phases scatter into: a
# whole-pool copy does not fit beside the 4.3 GB pool of the 100 M-key
# config on a 16 GB chip
WB_PAGES = 1 << 18


def phase_table(pool, addr, khi, klo, *, k: int = 4,
                impls=("xla", "pallas"), rows: int | None = None) -> dict:
    """Chained-delta ms per phase per impl on live arrays.

    ``pool`` [P, PAGE_WORDS]; ``addr`` packed page addresses [M] (the
    descent seeds AND the gather/scatter row source); khi/klo [M] key
    words.  Returns {phase: {impl: ms}} and records the matching
    ``kernels.*_ms`` obs histograms.  The write-back phases scatter
    random entries into a COPY of the pool's first ``WB_PAGES`` pages
    — the caller's pool handle is never mutated.
    """
    import jax
    import jax.numpy as jnp
    from jax import lax

    from sherman_tpu import config as C
    from sherman_tpu import obs
    from sherman_tpu.ops import bits
    from sherman_tpu.ops import pallas_page as PP

    M = addr.shape[0] if rows is None else rows
    addr = jnp.asarray(addr[:M])
    khi, klo = jnp.asarray(khi[:M]), jnp.asarray(klo[:M])
    P = pool.shape[0]
    pages = bits.addr_page(addr)
    act = jnp.ones(M, bool)
    rng = np.random.default_rng(3)
    slots = jnp.asarray(rng.integers(0, C.LEAF_CAP, M).astype(np.int32))
    res: dict = {}

    def drain(x):
        np.asarray(jnp.ravel(jax.tree_util.tree_leaves(x)[0])[0])

    def chain_cost(phase, impl, mk_loop, *args):
        spans = {}
        for reps in (k, 2 * k):
            fn = jax.jit(functools.partial(mk_loop, reps=reps))
            out = fn(*args)
            drain(out)
            best = []
            for _ in range(3):
                t0 = time.perf_counter()
                out = fn(*args)
                drain(out)
                best.append(time.perf_counter() - t0)
            spans[reps] = min(best)
        ms = (spans[2 * k] - spans[k]) / k * 1e3
        res.setdefault(phase, {})[impl] = ms
        obs.histogram(f"kernels.{phase}_{impl}_ms").record(ms)
        print(f"{phase:20s} {impl:7s} {ms:9.2f} ms", file=sys.stderr,
              flush=True)

    # --- fused descent round (gather + in-page pick) -----------------------
    def mk_descent(impl):
        fn = (PP.descent_round if impl == "pallas"
              else PP.descent_round_xla)

        def loop(pool, addr, reps):
            def body(i, st):
                a, acc = st
                nxt, is_leaf, chase, ok, f, vh, vl = fn(
                    pool, a, khi, klo, act)
                # data-dependent carry: the next round starts where this
                # one routed (wrapped into the pool so rows stay valid)
                a2 = jnp.where(ok & ~is_leaf, nxt, a)
                a2 = bits.addr_page(a2 + i) % P
                return a2, acc + jnp.sum(vh ^ vl)
            _, acc = lax.fori_loop(0, reps, body, (addr, jnp.int32(0)))
            return acc
        return loop

    # --- snapshot gather ----------------------------------------------------
    def mk_gather(impl):
        fn = PP.gather_pages if impl == "pallas" else PP.gather_pages_xla

        def loop(pool, rows, reps):
            def body(i, st):
                acc, r = st
                pg = fn(pool, (r + i) % P)
                return acc + pg[:, 0], r
            acc, _ = lax.fori_loop(0, reps, body,
                                   (jnp.zeros(M, jnp.int32), rows))
            return acc
        return loop

    # --- multi-lane write-back ---------------------------------------------
    def mk_writeback(impl, lanes):
        ent0 = jnp.asarray(
            rng.integers(1, 1 << 30, (M, len(lanes))).astype(np.int32))
        fn = PP.writeback if impl == "pallas" else PP.writeback_xla

        def loop(pool, rows, reps):
            def body(i, pl_):
                return fn(pl_, rows, slots, act, ent0 ^ i,
                          field_w=lanes)
            return lax.fori_loop(0, reps, body, pool)
        return loop

    upd = (C.L_VER_W, C.L_VHI_W, C.L_VLO_W)
    ins = (C.L_VER_W, C.L_KHI_W, C.L_KLO_W, C.L_VHI_W, C.L_VLO_W)
    safe_rows = jnp.clip(pages, 0, P - 1)
    wb_pool = pool[:min(P, WB_PAGES)]
    wb_rows = safe_rows % wb_pool.shape[0]
    for impl in impls:
        chain_cost("descent_round", impl, mk_descent(impl), pool, addr)
        chain_cost("snapshot_gather", impl, mk_gather(impl), pool,
                   safe_rows)
        chain_cost("writeback_3w", impl, mk_writeback(impl, upd), wb_pool,
                   wb_rows)
        chain_cost("writeback_5w", impl, mk_writeback(impl, ins), wb_pool,
                   wb_rows)
    for phase, by_impl in res.items():
        if "xla" in by_impl and "pallas" in by_impl and by_impl["xla"]:
            by_impl["ratio"] = by_impl["pallas"] / by_impl["xla"]
    return res


def main(argv=None) -> dict:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--rows", type=int, default=2_097_152)
    p.add_argument("--keys", type=int, default=2_000_000)
    p.add_argument("--k", type=int, default=4)
    p.add_argument("--impls", default="xla,pallas")
    a = p.parse_args(argv)

    import jax

    from sherman_tpu.models import batched
    from sherman_tpu.ops import bits

    impls = tuple(s for s in a.impls.split(",") if s)
    cluster, tree, eng = build_cluster(1, pages_for_keys(a.keys), a.rows)
    rng = np.random.default_rng(7)
    keys = np.unique(rng.integers(1, 1 << 63, int(a.keys * 1.05),
                                  dtype=np.uint64))[:a.keys]
    batched.bulk_load(tree, keys, keys)
    router = eng.attach_router()
    dsm = tree.dsm
    backend = jax.default_backend()
    print(f"# rows={a.rows} keys={a.keys} pages={dsm.pool.shape[0]} "
          f"K={a.k} backend={backend}"
          + (" (pallas INTERPRETED — mechanics only)"
             if backend != "tpu" else ""), file=sys.stderr)

    bk = keys[rng.integers(0, a.keys, a.rows)]
    khi, klo = bits.keys_to_pairs(bk)
    start = router.host_start(khi, klo)
    d = lambda x: jax.device_put(x, dsm.shard)
    res = phase_table(dsm.pool, d(start), d(khi), d(klo), k=a.k,
                      impls=impls)
    out = {
        "metric": "pallas_vs_xla_page_kernels",
        "rows": a.rows,
        "keys": a.keys,
        "backend": backend,
        "pallas_interpreted": backend != "tpu",
        "phases": {ph: {k2: round(v, 3) for k2, v in by.items()}
                   for ph, by in res.items()},
    }
    print(json.dumps(out))
    return out


if __name__ == "__main__":
    main()
