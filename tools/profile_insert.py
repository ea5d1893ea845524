#!/usr/bin/env python
"""Insert-step phase profiler — where does the write path's time go?

The insert step (``batched.insert_step_spmd``) is: routed descent ->
page-snapshot gather -> multi-operand dedup sort -> rank/verdict scans ->
one-hot fver extract -> fused write-back scatter.  This driver measures
the FULL step and each phase in isolation at a configurable row count,
so the published per-phase breakdown (BENCHMARKS.md) is reproducible.

Methodology: a per-call sync adds its own cost to per-call timings of
ms-scale phases.  Each phase
is therefore run K and 2K times CHAINED inside one jitted fori_loop
(data-dependent carries so XLA cannot elide the repeats), and the cost
is the difference quotient (t_2K - t_K) / K — the sync overhead cancels
exactly.

Usage:  python tools/profile_insert.py [--rows N] [--keys N] [--k K]
"""

from __future__ import annotations

import argparse
import sys
import time

import numpy as np

from common import build_cluster, pages_for_keys


def main(argv=None) -> dict:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--rows", type=int, default=2_097_152)
    p.add_argument("--keys", type=int, default=2_000_000)
    p.add_argument("--k", type=int, default=8)
    a = p.parse_args(argv)

    import jax
    import jax.numpy as jnp
    from jax import lax

    from sherman_tpu import config as C
    from sherman_tpu.models import batched
    from sherman_tpu.ops import bits

    M, K = a.rows, a.k
    cluster, tree, eng = build_cluster(1, pages_for_keys(a.keys), M)
    rng = np.random.default_rng(7)
    keys = np.unique(rng.integers(1, 1 << 63, int(a.keys * 1.05),
                                  dtype=np.uint64))[:a.keys]
    batched.bulk_load(tree, keys, keys)
    router = eng.attach_router()
    dsm = tree.dsm
    P = dsm.pool.shape[0]
    print(f"# rows={M} keys={a.keys} pages={P} K={K}", file=sys.stderr)

    bk = keys[rng.integers(0, a.keys, M)]
    khi, klo = bits.keys_to_pairs(bk)
    shard = dsm.shard
    d = lambda x: jax.device_put(x, shard)
    khi_d, klo_d = d(khi), d(klo)
    vhi_d, vlo_d = d(khi ^ np.int32(0xBEE)), d(klo)
    act_d = d(np.ones(M, bool))
    start = router.host_start(khi, klo)
    start_d = d(start)
    root = np.int32(tree._root_addr)
    rows_np = np.asarray(bits.addr_page(start)).astype(np.int32)
    rows_d = d(rows_np)
    res = {}

    def drain(x):
        np.asarray(jnp.ravel(jax.tree_util.tree_leaves(x)[0])[0])

    def chain_cost(name, mk_loop, *args):
        """(t_2K - t_K)/K of a jitted fori_loop phase chain."""
        import functools
        spans = {}
        for reps in (K, 2 * K):
            fn = jax.jit(functools.partial(mk_loop, reps=reps),
                         static_argnames=())
            out = fn(*args)
            drain(out)
            best = []
            for _ in range(3):
                t0 = time.perf_counter()
                out = fn(*args)
                drain(out)
                best.append(time.perf_counter() - t0)
            spans[reps] = min(best)
        ms = (spans[2 * K] - spans[K]) / K * 1e3
        res[name] = ms
        print(f"{name:32s} {ms:9.2f} ms", flush=True)

    # --- full insert step + search floor, chained inside ONE jit -----------
    # (an in-jit fori_loop sidesteps the per-call sync)
    iters = eng._iters()

    def mk_insert_loop(update_only):
        def insert_loop(pool, counters, reps):
            def body(i, st):
                pool, counters, acc = st
                pool, counters, status = batched.insert_step_spmd(
                    pool, dsm.locks, counters, khi_d, klo_d,
                    vhi_d ^ i, vlo_d, root, act_d, start_d, None,
                    cfg=eng.cfg, iters=iters, update_only=update_only)
                return pool, counters, acc + jnp.sum(status)
            _, _, acc = lax.fori_loop(0, reps, body,
                                      (pool, counters, jnp.int32(0)))
            return acc
        return insert_loop

    # one real engine step first: correctness spot check
    ifn = eng._get_insert(iters, True, with_fresh=False, update_only=True)
    dsm.pool, dsm.counters, dsm.dirty, st = ifn(
        dsm.pool, dsm.locks, dsm.counters, dsm.dirty, khi_d, klo_d,
        vhi_d, vlo_d, root, act_d, start_d)
    ok = np.isin(np.asarray(st), (batched.ST_APPLIED, batched.ST_SUPERSEDED))
    assert ok.all(), f"profile batch: {np.unique(np.asarray(st))}"
    chain_cost("insert_step_update_only", mk_insert_loop(True),
               dsm.pool, dsm.counters)
    chain_cost("insert_step_general", mk_insert_loop(False),
               dsm.pool, dsm.counters)

    def search_loop(pool, counters, reps):
        # roll the (key, seed) pairs per iteration so the read-only body
        # is not loop-invariant (XLA would hoist it and time nothing);
        # rolling keeps every key/seed pair intact — identical work
        def body(i, st):
            counters, acc = st
            counters, done, f, vh, vl = batched.search_routed_spmd(
                pool, counters, jnp.roll(khi_d, i), jnp.roll(klo_d, i),
                root, act_d, jnp.roll(start_d, i),
                cfg=eng.cfg, iters=iters)
            return counters, acc + jnp.sum(f)
        _, acc = lax.fori_loop(0, reps, body, (counters, jnp.int32(0)))
        return acc

    chain_cost("search_step_same_width", search_loop, dsm.pool,
               dsm.counters)

    # --- isolated phases (chained in-jit) ----------------------------------
    def gather_loop(pool, rows, reps):
        def body(i, st):
            acc, r = st
            pg = pool[(r + i) % P]
            return acc + pg[:, 0], r
        acc, _ = lax.fori_loop(0, reps, body,
                               (jnp.zeros(M, jnp.int32), rows))
        return acc

    chain_cost("page_snapshot_gather", gather_loop, dsm.pool, rows_d)

    def sort6_loop(pk, kh, kl, reps):
        idx0 = jnp.arange(M, dtype=jnp.int32)
        f0 = jnp.zeros(M, bool)
        fc0 = jnp.full(M, 5, jnp.int32)

        def body(i, st):
            pk, kh, kl = st
            sp, skh, skl, _, _, _ = lax.sort(
                (pk ^ i, kh, kl, idx0, f0, fc0), num_keys=3)
            return sp, skh, skl
        return lax.fori_loop(0, reps, body, (pk, kh, kl))

    chain_cost("dedup_sort_6op", sort6_loop, rows_d, khi_d, klo_d)

    def scans_loop(win, reps):
        idx0 = jnp.arange(M, dtype=jnp.int32)

        def body(i, st):
            w, acc = st
            head = jnp.concatenate([jnp.ones(1, bool), w[1:] != w[:-1]])
            cum = jnp.cumsum(head.astype(jnp.int32))
            base = lax.associative_scan(
                jnp.maximum, jnp.where(head, cum - 1, -1))
            enc = lax.associative_scan(
                jnp.maximum, jnp.where(head, idx0 * 2 + 1, -1))
            return w + 1, acc + base + enc
        _, acc = lax.fori_loop(0, reps, body,
                               (win, jnp.zeros(M, jnp.int32)))
        return acc

    chain_cost("verdict_scans_x3", scans_loop, rows_d)

    def onehot_loop(pool, rows, slot, reps):
        def body(i, acc):
            pg = pool[(rows + i) % P]
            blk = pg[:, C.L_VER_W:C.L_VER_W + C.LEAF_CAP]
            oh = jnp.arange(C.LEAF_CAP)[None, :] == slot[:, None]
            return acc + jnp.sum(jnp.where(oh, blk, 0), axis=-1)
        return lax.fori_loop(0, reps, body, jnp.zeros(M, jnp.int32))

    slot_d = d(rng.integers(0, C.LEAF_CAP, M).astype(np.int32))
    chain_cost("gather_plus_onehot_ver", onehot_loop, dsm.pool, rows_d,
               slot_d)

    field_w = np.array([C.L_VER_W, C.L_KHI_W, C.L_KLO_W, C.L_VHI_W,
                        C.L_VLO_W, C.W_FRONT_VER, C.W_REAR_VER,
                        C.W_NKEYS], np.int32)

    def scatter_loop_w(width):
        idx = d((rows_np[:, None] * C.PAGE_WORDS
                 + field_w[None, :width]).astype(np.int32))
        ent = d(rng.integers(1, 1 << 30, (M, width)).astype(np.int32))

        def loop(pool, idx, ent, reps):
            def body(i, pl):
                flat = pl.reshape(-1)
                flat = flat.at[idx.reshape(-1)].set(
                    (ent ^ i).reshape(-1), mode="drop")
                return flat.reshape(P, C.PAGE_WORDS)
            return lax.fori_loop(0, reps, body, pool)
        return loop, idx, ent

    for width in (8, 6, 4):
        loop, idx, ent = scatter_loop_w(width)
        chain_cost(f"writeback_scatter_{width}w", loop, dsm.pool, idx, ent)

    for k, v in sorted(res.items(), key=lambda kv: -kv[1]):
        print(f"# {k:32s} {v:9.2f} ms", file=sys.stderr)
    return res


if __name__ == "__main__":
    main()
