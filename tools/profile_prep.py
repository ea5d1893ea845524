#!/usr/bin/env python
"""Price the request-plane prep: host vs device A/B + chip stage deltas.

Two modes:

* default (``main()``): the PR 17 host-vs-device A/B.  Builds a small
  engine, constructs the SHIPPED ingress step twice (``prep_impl=host``
  and ``prep_impl=device``), and prices the prep phase of each with the
  same chained-delta discipline every phase receipt uses
  (``step.prep_profile``).  Also runs a duplicate-leaf write batch
  through the write-combining kernel and publishes the measured combine
  ratio (``combine.locks_saved / lock-acquisitions-uncombined``).  The
  last stdout line is the JSON receipt BENCHMARKS rounds consume;
  ``main()`` returns the same dict (the test_tools driver contract).

* ``--stages`` (or ``MODE=stages``): the round-5 cumulative cut-down
  profiler of the device-staged PREP pipeline (PRNG -> +zipf gather ->
  +mix64 -> +pair sort -> +flag compact -> +router probe); successive
  deltas price every phase on the real chip.

Env: KEYS (default 20_000), W (ingress width, default 1024), K (reps,
default 8), DUP (combine-batch duplication factor, default 8).  Stage
mode keeps its own knobs (KEYS, B, DEVB, K, LB, RT).
"""

import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def stage_deltas():
    """Cumulative cut-down stage profiler (chip mode; prints a table)."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    from sherman_tpu.utils.compile_cache import setup_compile_cache
    setup_compile_cache()

    from sherman_tpu.ops import bits
    from sherman_tpu.workload.device_prep import (
        _gen_ranks, _keys_of_ranks, _router_probe, _sort_combine,
        zipf_table)

    n_keys = int(os.environ.get("KEYS", 10_000_000))
    batch = int(os.environ.get("B", 4_194_304))
    K = int(os.environ.get("K", 16))
    theta = 0.99
    salt = 0x5E17_AB1E_5A17
    LB = int(os.environ.get("LB", 20))
    dev_b = int(os.environ.get("DEVB", 1_114_112))
    salt_hi = np.uint32((salt >> 32) & 0xFFFFFFFF)
    salt_lo = np.uint32(salt & 0xFFFFFFFF)

    t = zipf_table(n_keys, theta, LB)
    tpair = jax.device_put(np.stack([t[:-1], t[1:]], axis=1))
    # stand-in router table (the probe is one gather from an int32 table
    # of this size; content does not affect its cost)
    rt_size = int(os.environ.get("RT", 1 << 24))
    rtable = jax.device_put(np.zeros(rt_size, np.int32))
    rkey = jax.device_put(jax.random.PRNGKey(11))

    # cumulative stages call the SHIPPED device_prep helpers — a change
    # to the production pipeline is automatically what gets priced here
    def stage_prng(rk, si):
        k = jax.random.fold_in(rk, si)
        return jax.random.bits(k, (2, batch), dtype=jnp.uint32)

    def stage_rank(rk, si):
        return _gen_ranks(tpair, stage_prng(rk, si), log2_bins=LB,
                          n_keys=n_keys)

    def stage_mix(rk, si):
        return _keys_of_ranks(stage_rank(rk, si), salt_hi, salt_lo)

    def stage_sort(rk, si):
        khi, klo = stage_mix(rk, si)
        return lax.sort((khi, klo), num_keys=2)

    def stage_compact(rk, si):
        khi, klo = stage_mix(rk, si)
        skhi, sklo, ukhi, uklo, seg, n_uniq = _sort_combine(
            khi, klo, dev_b)
        return ukhi, uklo, seg

    def stage_full(rk, si):
        ukhi, uklo, seg = stage_compact(rk, si)
        return _router_probe(rtable, ukhi, uklo, 20, rt_size), seg

    # --- rank-sort alternative: 1-op sort + 2-op flag sort, mix64 and
    # probe on the unique set only; clients served in rank-sorted order
    def stage_ranksort_full(rk, si):
        rank = stage_rank(rk, si)
        srank = lax.sort(rank)
        first = jnp.concatenate([
            jnp.ones((1,), jnp.int32),
            (srank[1:] != srank[:-1]).astype(jnp.int32)])
        seg = (jnp.cumsum(first) - 1).astype(jnp.int32)
        _, crank = lax.sort((jnp.int32(1) - first, srank), num_keys=2)
        ur = crank[:dev_b]
        xlo = lax.bitcast_convert_type(ur, jnp.uint32) ^ salt_lo
        xhi = jnp.full((dev_b,), salt_hi, jnp.uint32)
        ukhi, uklo = bits.mix64_pair(xhi, xlo)
        bhi, blo = bits.u64_shr(ukhi, uklo, 20)
        bucket = jnp.where(bhi != 0, jnp.uint32(rt_size - 1),
                           jnp.minimum(blo, jnp.uint32(rt_size - 1)))
        # client keys for the verification compare: monotone gather from
        # the unique rows
        ckh = jnp.take_along_axis(ukhi, jnp.clip(seg, 0, dev_b - 1), 0)
        ckl = jnp.take_along_axis(uklo, jnp.clip(seg, 0, dev_b - 1), 0)
        return rtable[bucket.astype(jnp.int32)], seg, ckh, ckl

    stages = [
        ("prng(2xB)", stage_prng),
        ("+zipf gather", stage_rank),
        ("+mix64", stage_mix),
        ("+pair sort", stage_sort),
        ("+flag compact", stage_compact),
        ("+router probe", stage_full),
        ("ranksort FULL", stage_ranksort_full),
    ]
    prev = 0.0
    for name, fn in stages:
        j = jax.jit(fn)
        out = j(rkey, np.uint32(0))
        jax.block_until_ready(out)
        t0 = time.time()
        for i in range(K):
            out = j(rkey, np.uint32(i))
        jax.block_until_ready(out)
        ms = (time.time() - t0) / K * 1e3
        print(f"{name:16s} {ms:8.1f} ms  (delta {ms - prev:+7.1f})",
              flush=True)
        prev = ms


def _make_engine(n, *, write_combine=False):
    from sherman_tpu.cluster import Cluster
    from sherman_tpu.config import DSMConfig, TreeConfig
    from sherman_tpu.models import batched
    from sherman_tpu.models.btree import Tree

    cfg = DSMConfig(machine_nr=1,
                    pages_per_node=max(2048, n // 8),
                    locks_per_node=512, step_capacity=1024,
                    chunk_pages=32)
    tree = Tree(Cluster(cfg))
    keys = np.arange(100, 100 + n * 3, 3, dtype=np.uint64)
    vals = keys * np.uint64(7)
    batched.bulk_load(tree, keys, vals)
    eng = batched.BatchedEngine(
        tree, batch_per_node=256,
        tcfg=TreeConfig(sibling_chase_budget=2),
        write_combine=write_combine)
    eng.attach_router()
    return eng, keys, vals


def main():
    if "--stages" in sys.argv[1:] or os.environ.get("MODE") == "stages":
        stage_deltas()
        return None

    from sherman_tpu.workload.device_prep import make_ingress_step

    n = int(os.environ.get("KEYS", 20_000))
    width = int(os.environ.get("W", 1024))
    reps = int(os.environ.get("K", 8))
    dup = int(os.environ.get("DUP", 8))

    eng, keys, vals = _make_engine(n)
    rng = np.random.default_rng(17)
    batch = rng.choice(keys, size=width, replace=True).astype(np.uint64)

    # -- host-vs-device prep A/B: same batch, same chained-delta timer,
    # the only variable is where combine/sort/route ran
    impls = {}
    for impl in ("host", "device"):
        step = make_ingress_step(eng, width=width, prep_impl=impl)
        prof = step.prep_profile(batch, reps=reps)
        (key, ms), = prof.items()
        # end-to-end ingress step (prep + fused fan-out serve), chained
        t0 = time.perf_counter()
        for _ in range(2):
            step(batch)
        t_warm = time.perf_counter()
        for _ in range(reps):
            step(batch)
        step_ms = (time.perf_counter() - t_warm) / reps * 1e3
        del t0
        impls[impl] = {"prep_ms": round(ms, 4),
                       "step_ms": round(step_ms, 4),
                       "phase_key": key}
        print(f"prep[{impl:6s}]  prep {ms:8.3f} ms   "
              f"full step {step_ms:8.3f} ms", flush=True)

    # -- write-combining ratio on a duplicate-leaf write batch: DUP
    # writers per key land on the same leaf page, so the combined
    # kernel takes one lock per group instead of one per row
    ceng, ckeys, _ = _make_engine(max(2048, n // 4), write_combine=True)
    wk = np.repeat(ckeys[: max(1, 512 // dup)], dup)[:512].astype(np.uint64)
    ceng.insert(wk, wk * np.uint64(3))
    snap = ceng.dsm.counter_snapshot()
    groups = int(snap["combine_groups"])
    saved = int(snap["combine_locks_saved"])
    ratio = saved / (groups + saved) if (groups + saved) else 0.0
    combine = {"groups": groups, "locks_saved": saved,
               "ops_combined": saved,
               "ratio": round(ratio, 4)}
    print(f"combine      groups {groups}  locks_saved {saved}  "
          f"ratio {ratio:.3f}", flush=True)

    out = {
        "metric": "prep_ab",
        "keys": n,
        "width": width,
        "reps": reps,
        "impls": impls,
        "speedup_prep": round(
            impls["host"]["prep_ms"] / impls["device"]["prep_ms"], 3)
        if impls["device"]["prep_ms"] else None,
        "combine": combine,
    }
    print(json.dumps(out), flush=True)
    return out


if __name__ == "__main__":
    main()
