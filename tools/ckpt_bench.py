#!/usr/bin/env python
"""Checkpoint/restore wall times at benchmark scale (real chip).

Builds the north-star tree (default 100 M synthetic keys, the bench.py
config), then times one full cycle: ``checkpoint(cluster, path)`` ->
``restore(path)`` -> post-restore verification (a key sample searched
through a fresh engine + the device structure validator).  With
``--delta-ops N`` (default on) it also measures the INCREMENTAL side:
N engine upserts after the base, one ``checkpoint_delta`` (only the
dirty pages), and a chain restore — the delta-vs-full A/B the recovery
plane's "cheap frequent deltas" claim rests on.  Prints a side-by-side
table on stderr and ONE JSON line (receipt) with all wall times/sizes.

The reference has no durability story at any scale (SURVEY.md §5); this
pins the cost of ours at the full benchmark config, where the pool is
multi-GB — a full checkpoint is one d2h of the sharded pool + tiny
metadata, a delta only the written pages.  The JSON publishes byte
sizes so any host can be priced from its own link rate.

It also prices the journal's **group-commit A/B** (round-8): per-op
fsync vs ``Journal(sync=True, group_commit_ms=...)`` under a
multi-writer append load shaped like the recovery drill's batch
records — acks/s, mean/p99 ack latency, the added ack latency vs the
per-op baseline, and the measured acks-per-fsync coalescing ratio
(asserted >= 2x at ``group_commit_ms=2`` — the receipt the pipelined
write path's "writes ride the group commit" claim rests on; RPO 0
itself is pinned by the recovery drill, which runs with the knob on).

Run (real chip):  python tools/ckpt_bench.py --keys 100000000
CPU smoke:        SHERMAN_PLATFORM=cpu python tools/ckpt_bench.py \\
                      --keys 50000 --sample 5000 --delta-ops 4000
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from common import setup_platform  # noqa: E402


def journal_group_commit_ab(threads: int = 4, appends: int = 24,
                            rows: int = 256,
                            modes=(0.0, 0.5, 2.0)) -> dict:
    """The group-commit A/B: ``threads`` concurrent writers each
    appending ``appends`` drill-shaped batch records (``rows`` u64
    key/value pairs — the recovery drill's record scale) through one
    Journal per mode.  Every append blocks until its record is covered
    by an fsync (RPO 0 in every mode); the A/B prices what that ack
    costs: per-op fsync re-serializes the writers on the fsync
    latency, group commit coalesces a window of acks into one fsync.
    Returns {mode_label: {acks_per_s, ack_mean_ms, ack_p99_ms,
    added_ack_ms, fsyncs, acks_per_fsync}}."""
    import shutil
    import tempfile
    import threading

    from sherman_tpu import obs
    from sherman_tpu.utils import journal as J

    td = tempfile.mkdtemp(prefix="sherman_jab_")
    rng = np.random.default_rng(17)
    # one key/value block per (thread, append): identical across modes
    # so the three files carry the same bytes
    blocks = rng.integers(1, 1 << 60, (threads, appends, rows),
                          dtype=np.uint64)
    results: dict = {}
    try:
        for gc in modes:
            label = "per_op" if gc == 0 else f"gc_{gc:g}ms"
            path = os.path.join(td, f"{label}.wal")
            snap0 = obs.snapshot()
            j = J.Journal(path, sync=True, group_commit_ms=gc)
            lat: list = []
            lock = threading.Lock()

            def writer(t):
                mine = []
                for i in range(appends):
                    ks = blocks[t, i]
                    t0 = time.perf_counter()
                    j.append(J.J_UPSERT, ks, ks ^ np.uint64(0x5EED))
                    mine.append(time.perf_counter() - t0)
                with lock:
                    lat.extend(mine)

            ths = [threading.Thread(target=writer, args=(t,))
                   for t in range(threads)]
            t0 = time.perf_counter()
            for t in ths:
                t.start()
            for t in ths:
                t.join()
            elapsed = time.perf_counter() - t0
            j.close()
            d = obs.delta(snap0, obs.snapshot())
            n = threads * appends
            assert len(J.read_records(path)) == n, \
                "group-commit A/B lost records"
            fsyncs = int(d.get("journal.fsyncs", 0))
            lat.sort()
            results[label] = {
                "group_commit_ms": gc,
                "acks": n,
                "acks_per_s": round(n / elapsed, 1),
                "ack_mean_ms": round(1e3 * sum(lat) / len(lat), 3),
                "ack_p99_ms": round(
                    1e3 * lat[int(0.99 * (len(lat) - 1))], 3),
                "fsyncs": fsyncs,
                "acks_per_fsync": round(n / max(1, fsyncs), 2),
            }
            os.unlink(path)
    finally:
        # a failed mode leaves its .wal behind: remove the whole
        # tempdir, contents and all
        shutil.rmtree(td, ignore_errors=True)
    base = results.get("per_op", {}).get("ack_mean_ms", 0.0)
    for r in results.values():
        # the group-commit tradeoff, made explicit: acks coalesce at
        # the cost of up to group_commit_ms of added ack latency
        r["added_ack_ms"] = round(r["ack_mean_ms"] - base, 3)
    return results


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--keys", type=int, default=100_000_000)
    ap.add_argument("--sample", type=int, default=200_000,
                    help="post-restore verification sample size")
    ap.add_argument("--dir", default=None,
                    help="where to write the .npz (default: a tempdir; "
                         "the 100 M-key pool is ~4.3 GB on disk)")
    ap.add_argument("--validate", action="store_true",
                    help="run the whole-pool device validator on the "
                         "restored tree too (adds its own wall time)")
    ap.add_argument("--delta-ops", type=int, default=None,
                    help="engine upserts between base and delta "
                         "checkpoint (default keys/100 capped at 1 M; "
                         "0 disables the delta A/B)")
    ap.add_argument("--journal-ab-threads", type=int, default=4,
                    help="concurrent writers in the journal "
                         "group-commit A/B (0 disables it)")
    ap.add_argument("--journal-ab-appends", type=int, default=24,
                    help="records per writer in the group-commit A/B")
    args = ap.parse_args(argv)
    if args.delta_ops is None:
        args.delta_ops = min(max(args.keys // 100, 1000), 1_000_000)

    jax = setup_platform(1)
    from sherman_tpu.utils.compile_cache import setup_compile_cache
    setup_compile_cache()

    from sherman_tpu import native
    from sherman_tpu.cluster import Cluster
    from sherman_tpu.config import LEAF_CAP, DSMConfig
    from sherman_tpu.models import batched
    from sherman_tpu.models.btree import Tree
    from sherman_tpu.utils import checkpoint as CK

    fill = 0.75
    per_leaf = max(1, int(LEAF_CAP * fill))
    est_pages = int(args.keys / per_leaf * 1.10) + 8192
    pages = 1 << max(14, (est_pages - 1).bit_length())
    cfg = DSMConfig(machine_nr=1, pages_per_node=pages,
                    locks_per_node=65_536, step_capacity=65_536,
                    chunk_pages=4096)
    cluster = Cluster(cfg)
    tree = Tree(cluster)

    salt = 0x5E17_AB1E_5A17
    if native.available():
        keys, _ = native.synthetic_keyspace(args.keys, salt)
    else:
        rng = np.random.default_rng(7)
        keys = np.unique(rng.integers(1, 1 << 63, int(args.keys * 1.05),
                                      dtype=np.uint64))[: args.keys]
    vals = keys ^ np.uint64(0xDEADBEEF)
    t0 = time.time()
    batched.bulk_load(tree, keys, vals, fill=fill)
    build_s = time.time() - t0
    print(f"# bulk_load {build_s:.1f}s ({args.keys} keys, pool {pages} "
          f"pages)", file=sys.stderr, flush=True)

    td = args.dir or tempfile.mkdtemp(prefix="sherman_ckpt_")
    path = os.path.join(td, "bench.npz")
    dpath = os.path.join(td, "bench.delta1.npz")
    delta = None
    try:
        t0 = time.time()
        epoch = CK.checkpoint(cluster, path)
        ckpt_s = time.time() - t0
        size = os.path.getsize(path)
        print(f"# checkpoint {ckpt_s:.1f}s ({size / 1e9:.2f} GB)",
              file=sys.stderr, flush=True)

        # delta A/B: N engine upserts dirty a bounded page set; the
        # delta saves ONLY those pages — the "cheap frequent deltas"
        # half of the recovery plane, priced at this scale
        dkeys = None
        if args.delta_ops:
            # traffic-engine batch sized to the op count: the CPU smoke
            # then compiles a small insert program, the chip run a real
            # one
            eng0 = batched.BatchedEngine(
                tree, batch_per_node=min(65_536,
                                         max(1024, args.delta_ops)))
            eng0.attach_router()
            # a CLUSTERED working set (contiguous key range): the delta
            # then covers the touched leaves, not every leaf — a uniform
            # spray of N ops over N*40 keys would dirty the whole tree
            # and measure nothing but a full save with extra steps
            dkeys = keys[: min(args.delta_ops, args.keys)]
            t0 = time.time()
            st = eng0.insert(dkeys, dkeys ^ np.uint64(0x5EED))
            traffic_s = time.time() - t0
            assert st["lock_timeouts"] == 0
            t0 = time.time()
            dinfo = CK.checkpoint_delta(cluster, dpath,
                                        parent_epoch=epoch)
            delta = {"ops": int(dkeys.size),
                     "traffic_s": round(traffic_s, 1),
                     "pages": dinfo["pages"],
                     "npz_bytes": dinfo["bytes"],
                     "checkpoint_s": round(time.time() - t0, 2)}
            print(f"# delta checkpoint {delta['checkpoint_s']}s "
                  f"({delta['pages']} pages, "
                  f"{delta['npz_bytes'] / 1e6:.1f} MB)",
                  file=sys.stderr, flush=True)

        # release the ORIGINAL pool before restoring: at the 100 M-key
        # config two resident pools (4.3 GB each) plus the validator's
        # intermediates exhaust a 16 GB chip
        mesh = cluster.dsm.mesh
        cluster.dsm.pool.delete()
        del tree
        t0 = time.time()
        c2 = CK.restore_chain(path, [dpath] if delta else [], mesh=mesh)
        restore_s = time.time() - t0
        print(f"# restore {restore_s:.1f}s"
              + (" (chain: base + 1 delta)" if delta else ""),
              file=sys.stderr, flush=True)

        t2 = Tree(c2)
        e2 = batched.BatchedEngine(t2, batch_per_node=65_536)
        e2.attach_router()
        t0 = time.time()
        idx = np.linspace(0, args.keys - 1,
                          min(args.sample, args.keys)).astype(np.int64)
        probe = keys[idx]
        got, found = e2.search(probe)
        assert found.all(), f"restore lost {int((~found).sum())} keys"
        if dkeys is not None:
            # delta-written values win where the probe overlaps them
            upd = np.isin(probe, dkeys)
            np.testing.assert_array_equal(
                got[upd], probe[upd] ^ np.uint64(0x5EED))
            np.testing.assert_array_equal(
                got[~upd], probe[~upd] ^ np.uint64(0xDEADBEEF))
            gd, fd = e2.search(dkeys)
            assert fd.all()
            np.testing.assert_array_equal(gd, dkeys ^ np.uint64(0x5EED))
        else:
            np.testing.assert_array_equal(got,
                                          probe ^ np.uint64(0xDEADBEEF))
        verify_s = time.time() - t0
        validate_s = None
        if args.validate:
            from sherman_tpu.models.validate import check_structure_device
            t0 = time.time()
            info = check_structure_device(t2)
            validate_s = time.time() - t0
            assert info["keys"] == args.keys
    finally:
        if args.dir is None:
            for f in (path, dpath):
                try:
                    os.unlink(f)
                except OSError:
                    pass
            try:
                os.rmdir(td)
            except OSError:
                pass

    if delta:
        print("# {:>10s} {:>12s} {:>12s}".format("", "full", "delta"),
              file=sys.stderr)
        print("# {:>10s} {:>12.2f} {:>12.2f}".format(
            "save (s)", ckpt_s, delta["checkpoint_s"]), file=sys.stderr)
        print("# {:>10s} {:>12.3f} {:>12.3f}".format(
            "size (GB)", size / 1e9, delta["npz_bytes"] / 1e9),
            file=sys.stderr, flush=True)

    jab = None
    if args.journal_ab_threads > 0:
        jab = journal_group_commit_ab(threads=args.journal_ab_threads,
                                      appends=args.journal_ab_appends)
        print("# journal group-commit A/B ({} writers x {} records):"
              .format(args.journal_ab_threads, args.journal_ab_appends),
              file=sys.stderr)
        print("# {:>10s} {:>9s} {:>12s} {:>11s} {:>12s} {:>14s}".format(
            "mode", "acks/s", "ack_mean_ms", "ack_p99_ms",
            "added_ack_ms", "acks_per_fsync"), file=sys.stderr)
        for label, r in jab.items():
            print("# {:>10s} {:>9.0f} {:>12.3f} {:>11.3f} {:>12.3f} "
                  "{:>14.2f}".format(label, r["acks_per_s"],
                                     r["ack_mean_ms"], r["ack_p99_ms"],
                                     r["added_ack_ms"],
                                     r["acks_per_fsync"]),
                  file=sys.stderr, flush=True)
        g2 = jab.get("gc_2ms")
        if g2 is not None and args.journal_ab_threads >= 2:
            # the round-8 acceptance pin: bounded-delay group commit
            # must actually coalesce under a multi-writer load
            assert g2["acks_per_fsync"] >= 2.0, \
                f"group commit failed to coalesce: {g2}"

    print(json.dumps({
        "metric": "checkpoint_restore_at_scale",
        "value": round(ckpt_s + restore_s, 1),
        "unit": "s",
        "keys": args.keys,
        "pool_pages": pages,
        "npz_bytes": size,
        "bulk_load_s": round(build_s, 1),
        "checkpoint_s": round(ckpt_s, 1),
        "restore_s": round(restore_s, 1),
        "verify_sample": int(probe.shape[0]),
        "verify_s": round(verify_s, 1),
        "validate_s": round(validate_s, 1) if validate_s else None,
        "delta": delta,
        # per-op-fsync vs bounded-delay group commit (acks/s, ack
        # latency, coalescing ratio); RPO 0 in every mode — the drill
        # pins it with the knob ON
        "journal_group_commit": jab,
    }))


if __name__ == "__main__":
    main()
