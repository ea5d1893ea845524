#!/usr/bin/env python
"""Drifting-keyspace churn + reclamation benchmark (real chip).

The workload empty-leaf reclamation exists for: a sliding key window —
each iteration inserts a fresh window of keys at the right edge and
deletes the oldest window at the left — on a BOUNDED pool.  The
reference leaks the pool dry here (``free()`` is a no-op,
``DSM.h:226``); sherman_tpu's reclaim pass (unlink + parent cleanup +
quarantine + free, ``BatchedEngine.reclaim_empty_leaves``) runs INSIDE
the timed loop and must keep occupancy FLAT.

Prints per-iteration pool telemetry and ONE final JSON line:
churn ops/s (inserts + deletes, reclaim passes included in the wall
clock), reclaim pass cost, pool occupancy first/last/max, parked-page
count, and end-of-run integrity (live window searched, structure
checked).

Control: ``--no-reclaim`` runs the same loop without reclaim passes —
on the default sizing the pool exhausts within a few iterations
(MemoryError), which is the reference's fate on this workload.

Steady state needs DENSITY-MATCHED warm data: churn-inserted leaves
hold ~LEAF_CAP/2 keys (append-split density), so bulk-load at
``--fill 0.5`` or warm leaves (denser) retire SLOWER than inserts
create new ones and live pages grow structurally — ~window/7 pages per
iteration at the default fill 0.75 — until the delete window reaches
the churned region, regardless of reclaim.

Run (real chip):  python tools/churn_bench.py --keys 10000000
                      --window 524288 --reclaim-every 1 --fill 0.5 \\
                      --minutes 32
CPU smoke:        SHERMAN_PLATFORM=cpu python tools/churn_bench.py \\
                      --keys 60000 --window 4000 --iters 8 --chunk 8192
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from common import setup_platform  # noqa: E402


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--keys", type=int, default=10_000_000,
                    help="live keys at any moment (the sliding window "
                         "set's size)")
    ap.add_argument("--window", type=int, default=524_288,
                    help="keys inserted + deleted per iteration")
    ap.add_argument("--iters", type=int, default=55)
    ap.add_argument("--chunk", type=int, default=131_072,
                    help="engine call width.  Fresh-window inserts all "
                         "land on the current RIGHTMOST leaf (appending "
                         "churn), so each chunk needs a full split "
                         "cascade: ~log2(chunk/LEAF_CAP) doubling "
                         "rounds.  Size chunks so that cascade fits the "
                         "round budget (--max-rounds) with margin — a "
                         "chunk that exhausts its rounds spills the "
                         "tail to the per-key host path")
    ap.add_argument("--max-rounds", type=int, default=24,
                    help="insert round budget per chunk (the appending "
                         "cascade needs ~log2(chunk/49) split rounds "
                         "plus retry slack; the engine default 16 is "
                         "sized for scattered inserts)")
    ap.add_argument("--reclaim-every", type=int, default=2,
                    help="reclaim pass cadence (iterations)")
    ap.add_argument("--fill", type=float, default=0.75)
    ap.add_argument("--slack", type=float, default=0.55,
                    help="pool slack over the warm tree, in units of "
                         "window-leaf footprints: sized so the loop "
                         "EXHAUSTS without reclaim but runs flat with "
                         "it (unlink + quarantine hold "
                         "~3*reclaim_every+2 windows in flight — see "
                         "the sizing comment in main)")
    ap.add_argument("--streams", type=int, default=0,
                    help="append streams (0 = auto: window/128, capped "
                         "4096).  The churn keyspace is a multi-stream "
                         "time series: key = (stream << 44) | seq, so a "
                         "window's inserts append at --streams points "
                         "of the tree instead of one.  A SINGLE append "
                         "point is pathological for a batched engine: "
                         "every key targets the one rightmost leaf, "
                         "which absorbs ~LEAF_CAP/2 winners per round "
                         "and splits again — ~25 keys/round measured "
                         "on chip, i.e. linear rounds in window size "
                         "(the split does not bisect PENDING keys: "
                         "they are all above the split key).  Deletes "
                         "still retire whole leaves per stream, which "
                         "is what reclaim needs")
    ap.add_argument("--no-reclaim", action="store_true",
                    help="control: reference behavior (pool leaks)")
    ap.add_argument("--minutes", type=float, default=0.0,
                    help="if > 0, keep iterating until this much wall "
                         "time has passed (overrides --iters)")
    args = ap.parse_args(argv)

    jax = setup_platform(1)
    from sherman_tpu.utils.compile_cache import setup_compile_cache
    setup_compile_cache()

    from sherman_tpu.cluster import Cluster
    from sherman_tpu.config import LEAF_CAP, DSMConfig
    from sherman_tpu.models import batched
    from sherman_tpu.models.btree import Tree

    S = args.streams or max(16, min(4096, args.window // 128))

    def key_of(i):
        """Multi-stream time-series keyspace (see --streams)."""
        i = np.asarray(i, np.uint64)
        return ((i % np.uint64(S)) << np.uint64(44)) \
            | ((i // np.uint64(S)) + np.uint64(1))

    vals_of = lambda k: k ^ np.uint64(0xBEEF)

    # pool sizing: warm leaves + internals + a bounded number of
    # window-leaf footprints.  In-flight retired pages before the first
    # release: a deleted window is UNLINKED one reclaim pass after its
    # delete (the chain scan sees it empty then), then sits quarantined
    # for ~2 passes (engine default) — with passes every
    # ``reclaim_every`` iters that is ~(3 * reclaim_every + 1) windows
    # of lag, +1 window for the alternate-pair drain (a pass unlinks at
    # most every other member of an empty run).
    per_leaf = max(1, int(LEAF_CAP * args.fill))
    warm_pages = int(args.keys / per_leaf * 1.06) + 2048
    win_pages = int(args.window / (LEAF_CAP // 2))
    slack_pages = int(win_pages * (3 * args.reclaim_every + 2)
                      * (1.0 + args.slack))
    pages = warm_pages + slack_pages
    # locks_per_node sized for the reclaim batches: a pass's candidate
    # set (10^4-10^5 pairs under churn backlog) CAS-locks pages through
    # the hashed lock table, and pairs hashing onto an already-taken
    # word defer to the next pass — at 65,536 words the birthday
    # collisions capped unlinks ~15% under the retire rate and the pool
    # leaked ~3K pages/iter until exhaustion.  1M words (4 MB) keeps
    # the deferral rate negligible.
    cfg = DSMConfig(machine_nr=1, pages_per_node=pages,
                    locks_per_node=1 << 20, step_capacity=args.chunk,
                    chunk_pages=1024, host_step_capacity=8192)
    cluster = Cluster(cfg)
    tree = Tree(cluster)
    eng = batched.BatchedEngine(tree, batch_per_node=args.chunk,
                                split_slots=min(131_072, win_pages * 2))
    eng.parent_flush_threshold = eng.split_slots

    rng = np.random.default_rng(23)
    warm = np.sort(key_of(np.arange(args.keys, dtype=np.uint64)))
    t0 = time.time()
    batched.bulk_load(tree, warm, vals_of(warm), fill=args.fill)
    router = eng.attach_router()
    print(f"# warm load {time.time() - t0:.1f}s pool={pages} pages "
          f"(warm ~{warm_pages}, slack {slack_pages}) streams={S} "
          f"router_lb={router.lb}", file=sys.stderr)

    def pool_live():
        used = free = 0
        for d in cluster.directories:
            used += d.allocator.pages_used
            free += d.allocator.pages_free
        return used - free, free

    # compile warmup outside the timed loop: one small insert (split
    # kernels), one small delete, one reclaim pass
    w = min(16_384, args.window)
    wf = key_of(np.arange(args.keys, args.keys + w, dtype=np.uint64))
    eng.insert(wf, vals_of(wf))
    eng.delete(wf)
    if not args.no_reclaim:
        eng.reclaim_empty_leaves()

    lo, hi = 0, args.keys
    live0, _ = pool_live()
    occ = [live0]
    parked_hist = [len(eng._reclaim_state["parked"])]
    reclaim_ms = []
    reclaim_stats = {"unlinked": 0, "freed": 0}
    n_ops = 0
    t_start = time.time()
    it = 0
    while True:
        if args.minutes > 0:
            if time.time() - t_start > args.minutes * 60:
                break
        elif it >= args.iters:
            break
        fresh = key_of(np.arange(hi, hi + args.window, dtype=np.uint64))
        for i in range(0, fresh.size, args.chunk):
            # ascending chunks; shuffle WITHIN a chunk (arrival order
            # uncorrelated with key order, as in the storm driver) but
            # keep chunks ordered so each cascade builds on the last
            ck = fresh[i: i + args.chunk].copy()
            rng.shuffle(ck)
            t_c = time.time()
            st_i = eng.insert(ck, vals_of(ck), max_rounds=args.max_rounds)
            print(f"#     ins chunk {i // args.chunk} "
                  f"{time.time() - t_c:.1f}s rounds={st_i['rounds']} "
                  f"host={st_i['host_path']}", file=sys.stderr, flush=True)
            if st_i["host_path"] > args.chunk // 100:
                print(f"# WARN iter {it}: {st_i['host_path']} keys "
                      f"spilled to the host path (cascade exceeded "
                      f"--max-rounds?)", file=sys.stderr)
        dead = key_of(np.arange(lo, lo + args.window, dtype=np.uint64))
        for i in range(0, dead.size, args.chunk):
            t_c = time.time()
            eng.delete(dead[i: i + args.chunk])
            print(f"#     del chunk {i // args.chunk} "
                  f"{time.time() - t_c:.1f}s", file=sys.stderr, flush=True)
        n_ops += fresh.size + dead.size
        lo += args.window
        hi += args.window
        if not args.no_reclaim and it % args.reclaim_every == \
                args.reclaim_every - 1:
            t1 = time.time()
            st = eng.reclaim_empty_leaves()
            reclaim_ms.append((time.time() - t1) * 1e3)
            reclaim_stats["unlinked"] += st["unlinked"]
            reclaim_stats["freed"] += st["freed"]
            print(f"#     reclaim {reclaim_ms[-1] / 1e3:.1f}s "
                  f"unlinked={st['unlinked']} freed={st['freed']} "
                  f"quarantined={st['quarantined']} "
                  f"candidates={st['candidates']}",
                  file=sys.stderr, flush=True)
        live, free = pool_live()
        occ.append(live)
        parked_hist.append(len(eng._reclaim_state["parked"]))
        it += 1
        dt = time.time() - t_start
        print(f"#   iter {it}: {n_ops / dt / 1e3:.1f} K ops/s cum, "
              f"pool live {live} (free {free}), "
              f"parked {parked_hist[-1]}, "
              f"reclaimed {reclaim_stats['freed']}", file=sys.stderr)
    elapsed = time.time() - t_start

    # integrity: current window fully live, dead band gone, structure ok
    print(f"# verify: probing live window + structure", file=sys.stderr,
          flush=True)
    t_v = time.time()
    live_keys = key_of(np.arange(lo, hi, dtype=np.uint64))
    probe = live_keys[:: max(1, live_keys.size // 50_000)]
    got, found = eng.search(probe)
    assert found.all(), f"churn lost {int((~found).sum())} live keys"
    np.testing.assert_array_equal(got, vals_of(probe))
    old_probe = key_of(np.arange(max(0, lo - args.window), lo,
                                 dtype=np.uint64))[:10_000]
    _, f2 = eng.search(old_probe)
    assert not f2.any(), "deleted window still resolves"
    # whole-pool structure check on DEVICE (models/validate.py): one
    # jitted step instead of a host walk over every page
    from sherman_tpu.models.validate import check_structure_device
    info = check_structure_device(tree)
    # exact count: the validator's device-side key total must equal the
    # live window EXACTLY — catches any lost or duplicated key the
    # sampled probes above could miss, at zero extra device cost
    assert info["keys"] == hi - lo, \
        f"device key count {info['keys']} != live window {hi - lo}"
    print(f"# verify done in {time.time() - t_v:.1f}s: {info}",
          file=sys.stderr, flush=True)

    out = {
        "metric": "churn_reclaim",
        "value": round(n_ops / elapsed),
        "unit": "ops/s",
        "churn_ops_s": round(n_ops / elapsed),
        "iters": it,
        "elapsed_s": round(elapsed, 1),
        "window": args.window,
        "keys_live": args.keys,
        "pool_pages": pages,
        "pool_live_first": occ[1] if len(occ) > 1 else occ[0],
        "pool_live_last": occ[-1],
        "pool_live_max": max(occ),
        # flat = the steady-state band is bounded: growth since the
        # first full unlink->quarantine->release cycle stays within the
        # in-flight window footprint (see the slack sizing comment)
        # plus chunk-lease granularity (the allocator bumps whole
        # chunk_pages leases, so occupancy moves in those steps).  The
        # baseline clamps to the run's midpoint so short runs (CI
        # smoke) still compare two distinct samples instead of
        # degenerating to occ[-1] - occ[-1].
        "pool_flat": bool(
            occ[-1] - occ[max(1, min(len(occ) - 1,
                                     3 * args.reclaim_every + 1,
                                     (len(occ) - 1) // 2))]
            <= (3 * args.reclaim_every + 2) * win_pages
            + 2 * cfg.chunk_pages),
        "parked_final": parked_hist[-1],
        "reclaim_passes": len(reclaim_ms),
        "reclaim_ms_mean": round(float(np.mean(reclaim_ms)), 1)
        if reclaim_ms else None,
        "reclaim_ms_max": round(float(np.max(reclaim_ms)), 1)
        if reclaim_ms else None,
        "unlinked": reclaim_stats["unlinked"],
        "freed": reclaim_stats["freed"],
        "tree_keys": info["keys"],
        "no_reclaim": args.no_reclaim,
    }
    print(json.dumps(out))


if __name__ == "__main__":
    main()
