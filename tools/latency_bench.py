#!/usr/bin/env python
"""Width x depth latency frontier — the coroutine-depth analogue.

The reference hides per-op latency with 8-deep coroutine clients
(``Tree.cpp:1059-1122``): narrow per-op work, many in flight.  The
batched engine's analogue is NARROW STEPS, many in flight via JAX async
dispatch: a width-W routed-search step costs span(W) on chip, the host
keeps the dispatch queue non-empty, and in the step-span latency model an
op's completion latency is (batch-formation wait <= span) + (its step's
span) — p50 ~= 1.5 x span for an open loop admitting a batch every span.

This driver measures, per width:

- ``pipe_ms``    — pipelined ms/step (dispatch N, drain once): the
                   throughput-side truth, any queue depth.
- ``span_ms``    — per-step span from 64 block-amortized samples
                   (--kblk steps per sync), minus the CALIBRATED share of
                   one blocking sync; both raw and adjusted are printed.

Percentiles (round 7+) come from ``obs/slo.py`` trackers — the same
log-bucketed streaming estimator the SLO plane publishes — instead of
ad-hoc numpy arrays, so the latency-bracket chip re-capture and the
serving-side SLO window report through ONE code path (rank-interpolated
within <= 12.5% buckets; each row also gains p999 fields and an ``slo``
sub-dict with the tracker's own window view).
- ``ops_s``      — width / pipe_ms.
- ``p50_model``  — 1.5 x span (formation wait + service); the measured
                   span is the same quantity bench.py's p50 reports at
                   wide widths, where the sync share is negligible.
- ``p50_measured_raw`` / ``p50_measured`` — a MEASURED open-loop
                   async-dispatch client (wall-clock-paced admissions at
                   utilization ``--rho``, sampled completion drains)
                   brackets the true per-op latency: raw timestamps are
                   an upper bound (the observing drain adds <= 1 sync),
                   the calibrated-sync-subtracted values a lower bound.

Admissions are paced by the shared ``perf_counter_ns`` SLEEP+SPIN
hybrid (round 6; one copy in ``tools/common.py`` —
:class:`common.AdmissionPacer` — shared with ``tools/serve_bench.py``):
coarse sleep until ``--spin-ms`` before each deadline, then a spin
bounded at half the batch period — ms-granularity ``time.sleep`` could
not pace sub-ms periods, which is what kept the 16 K row below the
round-5 admission floor.  Every row publishes its per-admission pacing
error (``adm_jitter_p50/p99_ms``) and an ``adm_feasible`` verdict, so a
width whose jitter rivals its period is rejected by measurement, not by
prose.

Run: python tools/latency_bench.py [--keys 10000000]
         [--widths 16384,32768,65536,262144] [--blocks 64] [--kblk 32]
         [--spin-ms 2.0]
Prints ONE JSON line with the frontier.
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

from common import AdmissionPacer, setup_platform  # noqa: E402


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--keys", type=int, default=10_000_000)
    ap.add_argument("--widths", type=str, default="16384,32768,65536,262144")
    ap.add_argument("--blocks", type=int, default=64,
                    help="latency block samples per width; also the "
                         "open-loop sample-count target (values below 8 "
                         "are honored as given — expect coarse "
                         "percentiles)")
    ap.add_argument("--kblk", type=int, default=32,
                    help="steps per latency block (one sync each)")
    ap.add_argument("--theta", type=float, default=0.99)
    ap.add_argument("--rho", type=float, default=0.85,
                    help="open-loop admission utilization (offered rate "
                         "/ service rate).  1.0 is marginally stable — "
                         "any stall grows the queue without bound")
    ap.add_argument("--spin-ms", type=float, default=2.0,
                    help="spin-wait window before each admission "
                         "deadline: the pacer sleeps until this close "
                         "to the deadline, then spins on "
                         "perf_counter_ns.  Bounded duty cycle: the "
                         "spin budget is additionally capped at half "
                         "the batch period, so pacing can never eat a "
                         "full core.  Per-admission error is published "
                         "(adm_jitter_*) as each row's feasibility "
                         "receipt")
    args = ap.parse_args()
    if args.blocks < 1:
        ap.error("--blocks must be >= 1 (percentiles need samples)")
    widths = [int(w) for w in args.widths.split(",")]

    jax = setup_platform(1)
    from sherman_tpu.utils.compile_cache import setup_compile_cache
    setup_compile_cache()
    import jax.numpy as jnp

    from sherman_tpu import native
    from sherman_tpu.cluster import Cluster
    from sherman_tpu.config import LEAF_CAP, DSMConfig, TreeConfig
    from sherman_tpu.models import batched
    from sherman_tpu.models.btree import Tree
    from sherman_tpu.obs import slo as SLO
    from sherman_tpu.ops import bits

    n_keys = args.keys
    assert native.available(), "latency bench needs the native lib"
    salt = 0x5E17_AB1E_5A17
    while True:
        try:
            keys, rank_to_key = native.synthetic_keyspace(n_keys, salt)
            break
        except ValueError:
            salt += 1
    fill = 0.75
    est = int(n_keys / int(LEAF_CAP * fill) * 1.10) + 8192
    pages = 1 << max(14, (est - 1).bit_length())
    Bmax = max(widths)
    cfg = DSMConfig(machine_nr=1, pages_per_node=pages,
                    locks_per_node=65_536, step_capacity=Bmax,
                    chunk_pages=4096)
    cluster = Cluster(cfg)
    tree = Tree(cluster)
    t0 = time.time()
    batched.bulk_load(tree, keys, keys ^ np.uint64(0xD00D), fill=fill)
    print(f"# bulk load {time.time() - t0:.1f}s", file=sys.stderr)

    # calibrate the cost of one blocking sync: block_until_ready on an
    # already-materialized tiny array + a tiny jitted step, repeated
    one = jax.device_put(np.zeros(8, np.int32))
    tiny = jax.jit(lambda x: x + 1)
    tiny(one)
    rtts = []
    for _ in range(12):
        y = tiny(one)
        t1 = time.time()
        jax.block_until_ready(y)
        np.asarray(y[0])
        rtts.append(time.time() - t1)
    sync_ms = float(np.median(rtts)) * 1e3
    print(f"# calibrated per-sync cost {sync_ms:.3f} ms", file=sys.stderr)

    zg = native.ZipfGen(n_keys, args.theta, seed=29)
    rows = []
    for W in widths:
        eng = batched.BatchedEngine(tree, batch_per_node=W,
                                    tcfg=TreeConfig(sibling_chase_budget=1))
        router = eng.attach_router()
        fn = eng._get_search(eng._iters(), True)
        shard = tree.dsm.shard
        root = np.int32(tree._root_addr)
        pool, counters = tree.dsm.pool, tree.dsm.counters
        # pre-staged batches (latency mode serves pre-formed batches; the
        # sustained-prep story lives in bench.py)
        n_b = 32
        batches = []
        for i in range(n_b):
            k = rank_to_key[zg.sample(W)]
            khi, klo = bits.keys_to_pairs(k)
            st = router.host_start(khi, klo)
            batches.append((jax.device_put(khi, shard),
                            jax.device_put(klo, shard),
                            jax.device_put(st, shard)))
        act = jax.device_put(np.ones(W, bool), shard)

        def step(i, counters):
            b = batches[i % n_b]
            return fn(pool, counters, b[0], b[1], root, act, b[2])

        counters, done, found, vhi, vlo = step(0, counters)
        jax.block_until_ready(found)
        assert bool(np.asarray(found).all())
        for i in range(4):
            counters, done, found, vhi, vlo = step(i, counters)
        jax.block_until_ready(found)

        # pipelined throughput: N steps, one drain
        N = max(64, min(512, int(4e6 * 64 / W)))
        t1 = time.time()
        for i in range(N):
            counters, done, found, vhi, vlo = step(i, counters)
        jax.block_until_ready(found)
        pipe_ms = (time.time() - t1) / N * 1e3

        # block-amortized spans -> the SLO plane's own streaming
        # tracker (one estimator for the latency bench AND the serving
        # window; W ops per step at the per-step span is the same
        # amortized-wall attribution bench.py's slo section uses)
        span_t = SLO.SloTracker(window_s=3600.0)
        for b in range(args.blocks):
            t1 = time.time()
            for i in range(args.kblk):
                counters, done, found, vhi, vlo = step(i, counters)
            jax.block_until_ready(found)
            span_t.observe("read", W * args.kblk, time.time() - t1,
                           batches=args.kblk)
        span_w = span_t.window()["read"]
        raw50 = span_w["p50_ms"]
        raw99 = span_w["p99_ms"]
        adj = sync_ms / args.kblk
        span50 = max(pipe_ms, raw50 - adj)
        span99 = max(pipe_ms, raw99 - adj)
        ops_s = W / (pipe_ms / 1e3)

        # MEASURED open loop (the async-dispatch client the 1.5x-span
        # MODEL predicts; benchmark.cpp:159-188,207-249 parity).  Ops
        # arrive on a WALL-CLOCK schedule — batch i's ops arrive
        # uniformly over [t0+(i-1)*T, t0+i*T), T = pipe_ms (admission at
        # the service rate) — and batches dispatch when due, never
        # self-clocked.  A SAMPLE of batches gets a completion
        # timestamp: a blocking drain costs ~sync_ms of host time, so
        # timestamping every batch would throttle
        # admission; every STRIDE-th batch keeps the drain duty cycle
        # under ~50% and the in-between batches pipeline freely (the
        # emergent dispatch queue IS the client's depth).
        #
        # Admission runs at utilization RHO < 1 (batch period T =
        # pipe_ms / rho): an open loop offered EXACTLY the service rate
        # is marginally stable — any stall grows the queue without
        # bound and the measurement diverges.  The
        # reference's own open loop is self-limiting the same way: its
        # clients cap in-flight ops at coroutine depth.
        #
        # A sampled batch's completion timestamp brackets the true
        # latency between two published numbers:
        #   raw      = t_complete - mean_arrival      (upper bound: the
        #              observing drain adds up to one sync)
        #   adjusted = raw - sync_ms, clamped >= 0    (lower bound: the
        #              calibrated MEDIAN RTT may exceed this sample's
        #              actual RTT, so the subtraction can overshoot)
        # Both ends are published per width.
        rho = args.rho
        T = pipe_ms / 1e3 / rho
        stride = max(1, int(np.ceil((sync_ms / 1e3) / T / 0.5)))
        # --blocks is the sample-count target here too, bounded by a
        # ~2000-dispatch budget per width (long strides on high-RTT
        # hosts would otherwise turn many samples into minutes).  An
        # explicit --blocks below 8 is honored as given (quick smoke
        # runs; the old 8-sample floor silently overrode it) — the
        # dispatch-budget bound is >= 16, so any --blocks <= 16 passes
        # through unchanged.
        n_samp = min(args.blocks, max(16, 2000 // stride))
        n_ol = n_samp * stride
        # open-loop samples stream into slo.LatencyTracker pairs (raw /
        # sync-adjusted) — the bracket's two ends through the same
        # estimator the SLO plane publishes
        ol_raw_t = SLO.LatencyTracker()
        ol_adj_t = SLO.LatencyTracker()
        # Admission pacing: the SHARED perf_counter_ns sleep+spin pacer
        # (common.AdmissionPacer — one copy for this driver and
        # serve_bench; the rationale and the jitter-receipt contract
        # live on the class).  Deadline i = t_base + i*T; per-admission
        # error is recorded and PUBLISHED (adm_jitter_p50/p99_ms) as
        # the row's admission-feasibility receipt.
        pacer = AdmissionPacer(T, spin_ms=args.spin_ms)
        T_ns = pacer.period_ns
        sync_ns = int(sync_ms * 1e6)
        pacer.start()
        for i in range(n_ol):
            pacer.wait_turn(i)
            counters, done, found, vhi, vlo = step(i, counters)
            if i % stride == stride - 1:
                jax.block_until_ready(found)
                t_c = time.perf_counter_ns()
                # arrivals are uniform over batch i's admission window,
                # so the sample's reference point is the MEAN arrival
                mean_arrival = pacer.due_ns(i) - T_ns // 2
                raw_ms = (t_c - mean_arrival) / 1e6
                ol_raw_t.record(raw_ms / 1e3)
                ol_adj_t.record(max(0.0, raw_ms - sync_ms) / 1e3)
                # RE-ANCHOR the admission schedule by the OBSERVER's
                # stall only (~sync_ms): without it, admissions accrue
                # against the drain-stalled clock and every later
                # sample measures accumulated observation backlog
                # (+~sync_ms per sample), not service latency.  Capped
                # at sync_ms so GENUINE service backlog — the device
                # falling behind the offered rate — still accumulates
                # across strides exactly as in a true open loop
                # (uncapped re-anchoring would reintroduce coordinated
                # omission).  AdmissionPacer.absorb_stall is this exact
                # rule.
                pacer.absorb_stall(i + 1, sync_ns)
        adm = pacer.jitter_receipt()
        adm_p50 = adm["adm_jitter_p50_ms"]
        adm_p99 = adm["adm_jitter_p99_ms"]
        # feasibility: admissions held the offered schedule if the p99
        # pacing error is small against the batch period
        adm_ok = adm["adm_feasible"]
        spin_ns = pacer.spin_ns
        # each sample is a batch-MEAN op latency; op arrivals are
        # uniform over a T-wide window, so op-level tails spread
        # +-T/2 around the batch mean.  p50 is unaffected (symmetric);
        # p99 adds ~0.48*T (the 98th pct of U[-T/2, T/2]) — published
        # op-level, not batch-level.
        p50_raw_m = ol_raw_t.percentile_ms(50)
        p99_raw_m = ol_raw_t.percentile_ms(99) + 0.48 * T * 1e3
        p50_meas = ol_adj_t.percentile_ms(50)
        p99_meas = ol_adj_t.percentile_ms(99) + 0.48 * T * 1e3
        n_lat = ol_raw_t.count
        row = {
            "width": W,
            "pipe_ms": round(pipe_ms, 2),
            "span_p50_raw_ms": round(raw50, 2),
            "span_p50_ms": round(span50, 2),
            "span_p99_ms": round(span99, 2),
            "ops_s": round(ops_s),
            "p50_model_ms": round(1.5 * span50, 2),
            # measured open-loop bracket (see comment above): raw =
            # upper bound incl. <= 1 sync, plain = sync-adjusted lower
            # bound
            "p50_measured_raw_ms": round(p50_raw_m, 2),
            "p99_measured_raw_ms": round(p99_raw_m, 2),
            "p50_measured_ms": round(p50_meas, 2),
            "p99_measured_ms": round(p99_meas, 2),
            # SLO-plane extras: the tracker resolves p999 for free, and
            # the span tracker's window is published whole so this row
            # and bench.py's "slo" section are the same estimator
            "span_p999_ms": round(span_t.window()["read"]["p999_ms"], 2),
            "p999_measured_raw_ms": round(
                ol_raw_t.percentile_ms(99.9) + 0.48 * T * 1e3, 2),
            "slo": {k: round(float(v), 3)
                    for k, v in span_w.items()},
            "percentile_source": "obs.slo.LatencyTracker",
            "ol_samples": n_lat,
            "ol_stride": stride,
            "ol_rho": rho,
            "sync_share_ms": round(adj, 2),
            # admission-pacing receipts (perf_counter_ns spin-wait):
            # dispatch-vs-schedule error percentiles and the spin
            # budget actually used.  adm_feasible=false flags a row
            # whose pacing error rivals its batch period — its
            # measured bracket reflects admission backlog, not
            # service latency, and must be read accordingly.
            "adm_jitter_p50_ms": round(adm_p50, 3),
            "adm_jitter_p99_ms": round(adm_p99, 3),
            "adm_spin_budget_ms": round(spin_ns / 1e6, 3),
            "adm_feasible": bool(adm_ok),
            "pacing": "sleep+spin",
        }
        rows.append(row)
        print(f"# W={W:>7}: pipe {pipe_ms:6.2f} ms/step -> "
              f"{ops_s / 1e6:5.1f} M ops/s; span p50 {span50:5.2f} ms "
              f"(raw {raw50:5.2f} - sync/blk {adj:4.2f}), p99 "
              f"{span99:5.2f}; open-loop p50 model {1.5 * span50:5.2f} ms "
              f"vs MEASURED [{p50_meas:5.2f}, {p50_raw_m:6.2f}] ms "
              f"(p99 [{p99_meas:5.2f}, {p99_raw_m:6.2f}], "
              f"{n_lat} samples, stride {stride}, rho {rho}; "
              f"adm jitter p50 {adm_p50:.3f} / p99 {adm_p99:.3f} ms, "
              f"spin {spin_ns / 1e6:.2f} ms, "
              f"{'feasible' if adm_ok else 'NOT FEASIBLE'})",
              file=sys.stderr)
        tree.dsm.counters = counters

    best = [r for r in rows if r["ops_s"] >= 10_000_000]
    best = min(best, key=lambda r: r["p50_model_ms"]) if best else None
    # model honesty: does the model's p50 land inside the measured
    # [adjusted, raw] bracket per width?
    in_bracket = [r["p50_measured_ms"] <= r["p50_model_ms"]
                  <= r["p50_measured_raw_ms"] for r in rows]
    out = {
        "metric": "latency_frontier",
        "sync_ms": round(sync_ms, 1),
        "rows": rows,
        "best_10M": best,
        # per-width: model p50 inside the measured [adjusted, raw]
        # bracket (lower bound subtracts the calibrated sync cost,
        # upper includes <= 1 sync; see the open-loop comment)
        "model_p50_in_measured_bracket": in_bracket,
        "keys": n_keys,
    }
    print(json.dumps(out))


if __name__ == "__main__":
    main()
