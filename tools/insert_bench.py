#!/usr/bin/env python
"""Fresh-insert split-storm benchmark — BASELINE config 3 at scale.

The reference's config 3 is "insert-only: bulk-load, leaf-split heavy"
(``test/benchmark.cpp`` with kReadRatio=0; split machinery
``src/Tree.cpp:922-963``, parent ascent ``:980-987``).  The existing
``tools/benchmark.py 1 0 ...`` row measures the update-heavy steady state
(writes over the warm set); THIS driver measures sustained NEW-key
insertion: an 80-90%-full tree absorbs a stream of fresh keys with
device-side leaf splits, ``flush_parents`` and router ``note_split`` all
inside the timed loop.

    python tools/insert_bench.py [--keys 10000000] [--fresh 3000000]
        [--chunk 1048576] [--fill 0.9] [--split-slots 16384] [--nodes 1]

Key layout: warm and fresh keys come from one synthetic keyspace
(``mix64(rank ^ salt)``, native.synthetic_keyspace) so fresh keys
interleave UNIFORMLY across the warm tree — every leaf sees inserts and
the storm splits leaves everywhere, not just an append tail (appending
past the max key would serialize on the rightmost leaf, the same
last-leaf lock serialization the reference pays for appends).

Prints per-chunk progress and ONE summary JSON line:
    fresh_insert_ops_s, splits_s, device_splits, host_path (must be ~0
    at steady state), rounds_per_chunk, parent_flushes.
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


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--keys", type=int, default=10_000_000,
                    help="warm tree size (bulk-loaded)")
    ap.add_argument("--fresh", type=int, default=3_000_000,
                    help="fresh keys inserted during the timed storm")
    ap.add_argument("--chunk", type=int, default=1_048_576,
                    help="fresh keys per engine insert call")
    ap.add_argument("--fill", type=float, default=0.9,
                    help="bulk-load leaf fill (higher = more splits)")
    ap.add_argument("--split-slots", type=int, default=16_384,
                    help="fresh-page grant slots per node per round")
    ap.add_argument("--nodes", type=int, default=1)
    ap.add_argument("--verify", action="store_true",
                    help="post-storm: search every fresh key + device "
                         "structure validation")
    args = ap.parse_args()

    jax = setup_platform(args.nodes)
    from sherman_tpu.utils.compile_cache import setup_compile_cache
    setup_compile_cache()

    from sherman_tpu import native
    from sherman_tpu.cluster import Cluster
    from sherman_tpu.config import LEAF_CAP, DSMConfig
    from sherman_tpu.models import batched
    from sherman_tpu.models.btree import Tree

    total_keys = args.keys + args.fresh
    if native.available():
        salt = 0x5E17_AB1E_5A17
        while True:
            try:
                _, rank_to_key = native.synthetic_keyspace(total_keys, salt)
                break
            except ValueError:
                salt += 1
    else:
        rng0 = np.random.default_rng(7)
        rank_to_key = np.unique(rng0.integers(
            1, (1 << 63), int(total_keys * 1.05),
            dtype=np.uint64))[:total_keys]
        rng0.shuffle(rank_to_key)
    warm = np.sort(rank_to_key[: args.keys])
    fresh = rank_to_key[args.keys:]
    rng = np.random.default_rng(13)
    rng.shuffle(fresh)  # arrival order uncorrelated with key order
    vals_of = lambda k: k ^ np.uint64(0xBEEF)

    # pool: warm leaves at --fill + post-storm growth + internals + slack
    per_leaf = max(1, int(LEAF_CAP * args.fill))
    est = int(total_keys / per_leaf * 1.35) + 8192
    pages = 1 << max(14, (est - 1).bit_length())
    # host_step_capacity: flush_parents posts ~2 rows per touched parent
    # page; a split storm touches thousands per round, and the default 64
    # rows/step would serialize the flush into dozens of host round
    # trips per round
    cfg = DSMConfig(machine_nr=args.nodes, pages_per_node=pages,
                    locks_per_node=65_536, step_capacity=args.chunk,
                    chunk_pages=4096, host_step_capacity=8192)
    cluster = Cluster(cfg)
    tree = Tree(cluster)
    eng = batched.BatchedEngine(tree, batch_per_node=args.chunk,
                                split_slots=args.split_slots)
    # flush parent entries once per chunk, not per round: the router's
    # note_split keeps mid-chunk descents short, and each flush pass is
    # several host round trips
    eng.parent_flush_threshold = eng.split_slots
    t0 = time.time()
    stats0 = batched.bulk_load(tree, warm, vals_of(warm), fill=args.fill)
    router = eng.attach_router()
    print(f"# warm load {time.time() - t0:.1f}s {stats0} "
          f"router_lb={router.lb} split_slots={eng.split_slots}",
          file=sys.stderr)

    # compile warmup OUTSIDE the timed window: one small chunk exercises
    # the no-grant round-0 kernel, the with-grant split kernel and the
    # flush_parents machinery (first compiles cost seconds each; the
    # storm then measures execution)
    w = max(4096, args.chunk // 64)
    t0 = time.time()
    ws = eng.insert(fresh[:w], vals_of(fresh[:w]))
    print(f"# compile-warm chunk ({w} keys) {time.time() - t0:.1f}s {ws}",
          file=sys.stderr)

    # ---- the storm: everything inside the timed loop ----
    agg = {"applied": 0, "superseded": 0, "host_path": 0, "rounds": 0,
           "st_locked": 0, "device_splits": 0}
    splits_before = 0
    chunks = 0
    t0 = time.time()
    for i in range(w, fresh.size, args.chunk):
        ck = fresh[i: i + args.chunk]
        st = eng.insert(ck, vals_of(ck))
        for k in agg:
            agg[k] += st.get(k, 0)
        chunks += 1
        dt = time.time() - t0
        done_n = i + ck.size - w
        print(f"#   chunk {chunks}: +{ck.size} keys, "
              f"splits {agg['device_splits']}, rounds {st['rounds']}, "
              f"host_path {agg['host_path']}, "
              f"{done_n / dt / 1e6:.2f} M ops/s cum", file=sys.stderr)
    elapsed = time.time() - t0
    n_storm = fresh.size - w

    ops_s = n_storm / elapsed
    splits_s = (agg["device_splits"] - splits_before) / elapsed
    out = {
        "metric": "fresh_insert_split_storm",
        "value": round(ops_s),
        "unit": "ops/s",
        "keys_warm": args.keys,
        "keys_fresh": n_storm,
        "fill": args.fill,
        "elapsed_s": round(elapsed, 2),
        "fresh_insert_ops_s": round(ops_s),
        "device_splits": agg["device_splits"],
        "splits_s": round(splits_s),
        "host_path": agg["host_path"],
        "st_locked": agg["st_locked"],
        "rounds_per_chunk": round(agg["rounds"] / max(1, chunks), 2),
        "router_splits_noted": router.splits_noted,
        "chunk": args.chunk,
        "split_slots": eng.split_slots,
        "nodes": args.nodes,
    }

    if args.verify:
        t0 = time.time()
        got, found = eng.search(fresh)
        assert found.all(), f"storm lost {int((~found).sum())} fresh keys"
        np.testing.assert_array_equal(got, vals_of(fresh))
        sample = warm[:: max(1, warm.size // 1_000_000)]
        got, found = eng.search(sample)
        assert found.all(), "storm lost warm keys"
        np.testing.assert_array_equal(got, vals_of(sample))
        from sherman_tpu.models.validate import check_structure_device
        info = check_structure_device(tree)
        assert info["keys"] == total_keys, info
        out["verified"] = True
        print(f"# verify {time.time() - t0:.1f}s: every fresh+sampled-warm "
              f"key present, structure valid ({info['keys']} keys)",
              file=sys.stderr)

    print(f"# storm: {n_storm} fresh keys in {elapsed:.1f}s -> "
          f"{ops_s / 1e6:.2f} M inserts/s, {agg['device_splits']} device "
          f"splits ({splits_s:.0f}/s), host_path {agg['host_path']}, "
          f"{tree.dsm.counter_snapshot()}", file=sys.stderr)
    print(json.dumps(out))


if __name__ == "__main__":
    main()
