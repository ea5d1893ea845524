#!/usr/bin/env python
"""Open-loop serving-front-door bench (``bench.py --serve``).

Where ``bench.py`` is a CLOSED loop that owns the machine, this driver
is the front door's proof of service: paced multi-tenant clients submit
independent requests through :class:`sherman_tpu.serve.ShermanServer`,
and the receipt shows the SLO-adaptive width controller settling on a
step width whose MEASURED end-to-end p99 meets the configured target
while throughput stays within 1.3x of the best fixed-width closed-loop
number at that width (measured in-process by the calibration sweep —
same tree, same programs, same host).

Methodology:

- admissions are paced by the shared ``perf_counter_ns`` sleep+spin
  pacer (``tools/common.py`` :class:`~common.AdmissionPacer`, one copy
  with ``latency_bench``); every paced tenant's jitter lands in ONE
  merged ``adm_*`` receipt with the ``adm_feasible`` verdict — a run
  whose pacing error rivals its request period was not actually offered
  at the stated rate, and says so in the JSON;
- the offered rate is ``rho x`` the calibrated closed-loop throughput
  of the width the controller would pick under saturation (open loops
  offered exactly the service rate are marginally stable — the
  latency_bench lesson);
- an optional GREEDY tenant submits unpaced bursts beside the polite
  tenants: its typed :class:`~sherman_tpu.serve.ServeOverloadError`
  rejects and the per-tenant served shares are the fair-share receipt;
- the serving loop runs SEALED (warmup compiles every ladder rung);
  ``retraces`` in the receipt must be 0 — the PR 8 contract applied to
  a real request path;
- writes are journaled by construction (ack gate = fsync): the
  ``journal`` block carries this run's acks-per-fsync coalescing.

``--crash-drill`` instead runs the durability drill: concurrent writer
tenants stream value re-stamps through the front door while a
client-side ledger records every ACKED (key, value); the server is
KILLED mid-traffic (journal left unclosed, exactly what a crash leaves
behind), the base image is rebuilt, the journal replays, and the
receipt pins ``rpo_ops == 0`` — no acked write lost — plus
``acks_per_fsync > 1`` under concurrent writers with group commit on.

Run::

    python tools/serve_bench.py [--keys 200000] [--secs 6]
        [--widths 1024,4096,16384] [--p99-ms 0 (auto)] [--tenants 3]
        [--req-ops 512] [--rho 0.8] [--write-frac 0.1] [--no-greedy]
        [--cache] [--crash-drill]

Prints ONE JSON line (``metric: serve_bench`` / ``serve_crash_drill``).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import threading
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from common import AdmissionPacer, pages_for_keys, setup_platform  # noqa: E402

STAMP0 = 0xD00D          # bulk-load value stamp (key ^ STAMP0)
STAMP1 = 0x5EED_0001     # open-loop write re-stamp


def build_engine(n_keys: int, widths, cache: bool):
    """Cluster + bulk-loaded tree + engine (+ router, + optional
    sketch-admission leaf cache) — the drivers' shared prologue."""
    from sherman_tpu.cluster import Cluster
    from sherman_tpu.config import DSMConfig, TreeConfig
    from sherman_tpu.models import batched
    from sherman_tpu.models.btree import Tree
    from sherman_tpu import native

    if native.available():
        salt = 0x5E17_AB1E_5A17
        while True:
            try:
                keys, rank_to_key = native.synthetic_keyspace(n_keys, salt)
                break
            except ValueError:
                salt += 1
    else:
        rng = np.random.default_rng(7)
        keys = np.unique(rng.integers(
            1, (1 << 63), int(n_keys * 1.05), dtype=np.uint64))[:n_keys]
        rank_to_key = np.sort(keys)
    vals = keys ^ np.uint64(STAMP0)
    cfg = DSMConfig(machine_nr=1, pages_per_node=pages_for_keys(n_keys),
                    locks_per_node=65_536, step_capacity=max(widths),
                    chunk_pages=1024)
    cluster = Cluster(cfg)
    tree = Tree(cluster)
    batched.bulk_load(tree, keys, vals)
    # engine batch width bounds the WRITE path's padded step (the
    # ingress read path does its own per-rung padding and never uses
    # it): a write flush stalls the single dispatcher for one engine
    # op, so its width is a read-p99 tax — keep it at the mid rung,
    # not the widest
    eng_b = min(4096, max(widths))
    eng = batched.BatchedEngine(tree, batch_per_node=eng_b,
                                tcfg=TreeConfig(sibling_chase_budget=1))
    eng.attach_router()
    if cache:
        # sketch-driven admission from REAL request streams: the front
        # door's read path feeds the decayed top-K sketch, and every
        # admit_every observed batches the hottest keys are re-admitted
        eng.attach_leaf_cache(slots=4096, admit_every=16)
    return cluster, tree, eng, keys, rank_to_key


def make_sampler(n_keys: int, theta: float, rank_to_key, seed: int):
    from sherman_tpu import native
    if native.available() and theta > 0:
        zg = native.ZipfGen(n_keys, theta, seed=seed)
        return lambda n: rank_to_key[zg.sample(n)]
    from sherman_tpu.workload.zipf import ZipfGen, uniform_ranks
    rng = np.random.default_rng(seed)
    if theta > 0:
        zg = ZipfGen(n_keys, theta, seed=seed)
        return lambda n: rank_to_key[zg.sample(n)]
    return lambda n: rank_to_key[uniform_ranks(n_keys, n, rng)]


def run_serve(a) -> dict:
    from sherman_tpu import obs
    from sherman_tpu.errors import ShermanError
    from sherman_tpu.models.batched import DegradedError
    from sherman_tpu.serve import (ServeConfig, ServeOverloadError,
                                   ShermanServer)
    from sherman_tpu.utils.journal import Journal

    widths = tuple(int(w) for w in a.widths.split(","))
    t0 = time.time()
    cluster, tree, eng, keys, rank_to_key = build_engine(
        a.keys, widths, a.cache)
    print(f"# build + bulk load {time.time() - t0:.1f}s "
          f"(keys={a.keys}, cache={'on' if a.cache else 'off'})",
          file=sys.stderr)

    jdir = a.journal_dir or tempfile.mkdtemp(prefix="serve-journal-")
    jpath = os.path.join(jdir, "serve-journal.bin")
    journal = Journal(jpath, sync=True, group_commit_ms=a.group_commit_ms)
    # provisional huge target: calibration first, then re-aim (auto
    # mode picks the target FROM the measured frontier below)
    cfg = ServeConfig(widths=widths,
                      p99_targets_ms={c: (a.p99_ms or 1e9)
                                      for c in ("read", "scan",
                                                "insert", "delete")},
                      fusion=a.fusion,
                      group_commit_ms=a.group_commit_ms,
                      # one engine chunk per write flush: a wider
                      # flush is a longer dispatcher stall every read
                      # behind it pays
                      write_width=2048,
                      # end-to-end p99 model on a GIL'd CPU host:
                      # formation wait (~wall/rho) + the in-flight
                      # pipeline slot (~wall) + service (~wall) +
                      # scheduling jitter — ~3.5x the step wall, vs
                      # the library's 2x default for a co-located
                      # accelerator host
                      model_mult=3.5)
    srv = ShermanServer(eng, cfg, journal=journal)
    calib_n = min(a.keys, 4096)
    absent = np.asarray([int(keys.max()) - 1], np.uint64)
    absent = absent[~np.isin(absent, keys)]
    calib = srv.start(
        calib_keys=keys[:: max(1, a.keys // 65536)],
        calib_writes=(keys[:calib_n], keys[:calib_n] ^ np.uint64(STAMP0)),
        calib_delete_keys=absent if absent.size else None)
    for w, c in sorted(calib.items()):
        print(f"# calib W={w:>7}: {c['wall_ms']:8.2f} ms/step closed "
              f"-> {c['ops_s'] / 1e6:6.2f} M ops/s", file=sys.stderr)

    # aim the controller: explicit --p99-ms, or AUTO = a target sitting
    # between the second-widest and widest rungs' modeled p99 so the
    # adaptive pick has a real ceiling to respect (the widest rung is
    # deliberately infeasible when walls grow with width).  The 2.5x
    # slack over the idle-calibration model absorbs the wall inflation
    # a CPU mesh pays once client threads share the cores with the
    # "device" (~2x measured) — without it the mid rung sits exactly
    # on the feasibility boundary and the pick flaps.
    if a.p99_ms:
        target = float(a.p99_ms)
    else:
        w_mid = widths[-2] if len(widths) > 1 else widths[-1]
        target = cfg.model_mult * calib[w_mid]["wall_ms"] * 2.5
    srv.retarget("read", target)
    n_paced = max(1, a.tenants)

    jstats0 = journal.stats()  # calibration's appends/fsyncs excluded
    stats_lock = threading.Lock()
    cstats = {"rejects": 0, "degraded_rejects": 0, "bad_values": 0,
              "reqs": 0, "write_reqs": 0, "inflight_failures": 0}
    pacers: list[AdmissionPacer] = []
    ok_vals = (np.uint64(STAMP0), np.uint64(STAMP1))

    def check_reads(keys_req, vals_out, found):
        # every loaded key must be found, valued with either stamp
        # (writes re-stamp concurrently)
        x = vals_out ^ keys_req
        return int(np.sum(~(found & ((x == ok_vals[0])
                                     | (x == ok_vals[1])))))

    def client(tenant: str, seed: int, stop: threading.Event,
               period: float, write_frac: float):
        # requests are PRE-GENERATED (the bench's pre-staged-batches
        # idiom) and results audited AFTER the phase: on a CPU mesh the
        # clients share cores with the "device", so per-request numpy
        # work inside the paced loop would throttle the very server
        # being measured
        sample = make_sampler(a.keys, a.theta, rank_to_key, seed)
        reqpool = [np.ascontiguousarray(sample(a.req_ops), np.uint64)
                   for _ in range(128)]
        wmask = np.random.default_rng(seed).random(4096) < write_frac
        pacer = AdmissionPacer(period, spin_ms=a.spin_ms)
        with stats_lock:
            pacers.append(pacer)
        futs = []    # (future, request keys) in flight
        results = []  # (request keys, result) for the post-phase audit
        local = {"rejects": 0, "deg": 0, "bad": 0, "reqs": 0,
                 "writes": 0, "seen": 0, "failed": 0}

        def drain(f, kreq):
            try:
                res = f.result(timeout=60)
            except (ServeOverloadError, DegradedError):
                local["rejects"] += 1
                return
            except ShermanError:
                # in-flight failure (dispatch error, result timeout):
                # counted, never a silent thread death that drops this
                # tenant's stats from the receipt
                local["failed"] += 1
                return
            if f.op == "read":
                # sample 1-in-4 AT APPEND time: retaining every result
                # for a post-phase audit would hold GBs at the chip
                # parameters (65536-op requests x 30 s)
                local["seen"] += 1
                if local["seen"] % 4 == 0:
                    results.append((kreq, res))

        pacer.start()
        i = 0
        while not stop.is_set():
            pacer.wait_turn(i)
            kreq = reqpool[i & 127]
            write = bool(wmask[i & 4095])
            i += 1
            try:
                if write:
                    fut = srv.submit("insert", kreq,
                                     kreq ^ np.uint64(STAMP1),
                                     tenant=tenant)
                    local["writes"] += 1
                else:
                    fut = srv.submit("read", kreq, tenant=tenant)
                futs.append((fut, kreq))
                local["reqs"] += 1
            except ServeOverloadError:
                local["rejects"] += 1
            except DegradedError:
                local["deg"] += 1
            # reap completed futures without blocking the pacer; only
            # block (bounded in-flight) when the backlog runs away
            while futs and futs[0][0].done():
                drain(*futs.pop(0))
            while len(futs) > 256:
                drain(*futs.pop(0))
        for f, kreq in futs:
            drain(f, kreq)
        # value audit of the sampled results, off the timed phase
        for kreq, (vals_out, found) in results:
            local["bad"] += check_reads(kreq, vals_out, found)
        with stats_lock:
            cstats["rejects"] += local["rejects"]
            cstats["degraded_rejects"] += local["deg"]
            cstats["bad_values"] += local["bad"]
            cstats["reqs"] += local["reqs"]
            cstats["write_reqs"] += local["writes"]
            cstats["inflight_failures"] += local["failed"]

    def greedy(tenant: str, seed: int, stop: threading.Event):
        """Unpaced burst tenant: the fair-share test's adversary —
        admission must cap it at its share with typed rejects while
        the polite tenants keep admitting into theirs."""
        sample = make_sampler(a.keys, a.theta, rank_to_key, seed)
        reqpool = [np.ascontiguousarray(sample(a.req_ops), np.uint64)
                   for _ in range(32)]
        futs = []
        i = 0
        while not stop.is_set():
            i += 1
            try:
                futs.append(srv.submit("read", reqpool[i & 31],
                                       tenant=tenant))
            except ServeOverloadError:
                time.sleep(0.002)
            while len(futs) > 64:
                try:
                    futs.pop(0).result(timeout=60)
                except ShermanError:
                    pass
        for f in futs:
            try:
                f.result(timeout=60)
            except ShermanError:
                pass

    # -- PHASE 0 (capacity probe): ONE unpaced loader saturates the
    # front door at the controller's settled width — the measured
    # OPEN-loop capacity.  The within-1.3x pin compares THIS number to
    # the same width's closed-loop calibration: the front door's whole
    # machinery (admission, coalescing, futures, tracker) may cost at
    # most 30% of the closed loop it wraps.
    served0 = srv.served_ops
    picks0 = dict(srv.controller.picks)
    stop0 = threading.Event()
    ld = threading.Thread(target=greedy, args=("loader", 555, stop0),
                          daemon=True)
    t1 = time.perf_counter()
    ld.start()
    time.sleep(min(2.5, a.secs / 2))
    stop0.set()
    ld.join(timeout=120)
    cap_elapsed = time.perf_counter() - t1
    cap_ops_s = (srv.served_ops - served0) / cap_elapsed
    cap_picks = {w: srv.controller.picks[w] - picks0.get(w, 0)
                 for w in srv.controller.picks}
    settled = max(cap_picks.items(), key=lambda kv: kv[1])[0]
    closed_at_settled = calib[settled]["ops_s"]
    ratio = closed_at_settled / cap_ops_s if cap_ops_s else None
    print(f"# capacity: {cap_ops_s / 1e6:.2f} M ops/s open-loop "
          f"saturated at settled W={settled} (closed "
          f"{closed_at_settled / 1e6:.2f} M -> ratio {ratio:.2f})",
          file=sys.stderr)

    # -- PHASE A (SLO): paced tenants at a SUSTAINABLE offered rate —
    # the p99-vs-target receipt.  The anchor is rho x the MID rung's
    # closed rate, not the saturated capacity: step fill (and with it
    # the front door's effective service rate) is a function of queue
    # depth, so "60% of saturated capacity" is NOT automatically
    # stable at shallow queues — the paced regime serves narrower
    # steps than the flooded one.  The adversarial flooder is
    # deliberately ABSENT here: a tenant that saturates the admission
    # queue by design makes every request's latency the queue-cap
    # drain time, which measures the cap, not the width.
    w_mid = widths[-2] if len(widths) > 1 else widths[-1]
    offered_ops_s = a.rho * calib[w_mid]["ops_s"]
    req_rate = offered_ops_s / a.req_ops
    period_s = n_paced / req_rate
    print(f"# target p99 {target:.2f} ms; offering "
          f"{offered_ops_s / 1e6:.2f} M ops/s ({req_rate:.0f} req/s x "
          f"{a.req_ops} ops, rho {a.rho}, {n_paced} paced tenants)",
          file=sys.stderr)
    srv.tracker.reset()
    served0 = srv.served_ops
    stopA = threading.Event()
    threads = [threading.Thread(
        target=client,
        args=(f"tenant{k}", 100 + k, stopA, period_s, a.write_frac),
        daemon=True) for k in range(n_paced)]
    t1 = time.perf_counter()
    for t in threads:
        t.start()
    time.sleep(a.secs)
    stopA.set()
    for t in threads:
        t.join(timeout=120)
    elapsed = time.perf_counter() - t1
    window = srv.tracker.window()
    served_ops = srv.served_ops - served0
    width_picks = dict(srv.controller.picks)
    downshifts = srv.controller.downshifts
    slo_picks = {w: width_picks[w] - cap_picks.get(w, 0)
                 - picks0.get(w, 0) for w in width_picks}
    slo_settled = max(slo_picks.items(), key=lambda kv: kv[1])[0] \
        if any(slo_picks.values()) else settled

    # -- PHASE B (fairness): an unpaced greedy flooder beside a polite
    # paced tenant — the fair-share shares + typed-reject receipts
    fairness = None
    if a.greedy:
        stopB = threading.Event()
        tb = [threading.Thread(target=greedy, args=("greedy", 999, stopB),
                               daemon=True),
              threading.Thread(target=client,
                               args=("polite", 777, stopB,
                                     period_s * 2, 0.0),
                               daemon=True)]
        for t in tb:
            t.start()
        time.sleep(min(3.0, a.secs / 2))
        stopB.set()
        for t in tb:
            t.join(timeout=120)
        tstats = srv.stats()["tenants"]
        phase_b = {name: tstats[name] for name in ("greedy", "polite")
                   if name in tstats}
        b_served = max(1, sum(t["served_ops"] for t in phase_b.values()))
        for t in phase_b.values():
            t["share"] = round(t["served_ops"] / b_served, 4)
        fairness = {
            "tenants": phase_b,
            "greedy_rejects": phase_b.get("greedy", {}).get(
                "rejected_overload", 0),
            "polite_rejects": phase_b.get("polite", {}).get(
                "rejected_overload", 0),
        }

    sstats = srv.stats()
    retraces = srv.retraces
    srv.stop()
    journal.close()
    serve_ops_s = served_ops / elapsed
    read_w = window.get("read") or {}
    ins_w = window.get("insert") or {}
    p99_read = read_w.get("p99_ms")
    adm = pacers[0] if pacers else AdmissionPacer(period_s)
    for p in pacers[1:]:
        adm.merge_errors(p)
    adm_receipt = adm.jitter_receipt()
    obs_slo = obs.slo_window()

    out = {
        "schema_version": 3,
        "metric": "serve_bench",
        "keys": a.keys,
        "theta": a.theta,
        "nodes": 1,
        "secs": round(elapsed, 2),
        "serve_ops_s": round(serve_ops_s),
        "serve_read_p99_ms": round(p99_read, 3) if p99_read else None,
        "serve_write_p99_ms": round(ins_w["p99_ms"], 3)
        if ins_w.get("p99_ms") else None,
        "serve": {
            "p99_targets_ms": {"read": round(target, 3)},
            "p99_target_met": bool(p99_read is not None
                                   and p99_read <= target),
            "widths": list(widths),
            # width the saturated capacity phase settled on (the
            # throughput pin's width) and the SLO phase's own settle —
            # step fill follows queue depth, so they may differ
            "settled_width": settled,
            "slo_settled_width": slo_settled,
            "width_picks": width_picks,
            "slo_picks": slo_picks,
            "downshifts": downshifts,
            "fusion": a.fusion,
            "offered_ops_s": round(offered_ops_s),
            "rho": a.rho,
            "req_ops": a.req_ops,
            "requests": cstats["reqs"],
            "write_requests": cstats["write_reqs"],
            "closed_loop": {str(w): round(c["ops_s"])
                            for w, c in calib.items()},
            # capacity pin: SATURATED open-loop throughput at the
            # settled width vs the same width's closed-loop number
            "capacity_ops_s": round(cap_ops_s),
            "capacity_picks": cap_picks,
            "closed_ops_s_at_settled": round(closed_at_settled),
            "ratio_vs_closed": round(ratio, 3) if ratio else None,
            "within_1_3x": bool(ratio is not None and ratio <= 1.3),
            "tenants": {n: t for n, t in sstats["tenants"].items()
                        if n.startswith("tenant")},
            "fairness": fairness,
            "rejects": sstats["rejects"],
            "client_rejects": cstats["rejects"],
            "inflight_failures": cstats["inflight_failures"],
            "bad_values": cstats["bad_values"],
            "window": {cls: {k: round(float(v), 3)
                             for k, v in st.items()}
                       for cls, st in window.items()},
            "slo_window": {cls: {k: round(float(v), 3)
                                 for k, v in st.items()}
                           for cls, st in obs_slo.items()},
            "sealed": sstats["sealed"],
            "retraces": retraces,
            # traffic-phase journal coalescing (calibration excluded):
            # acked write REQUESTS per real fsync
            "journal": {
                "appends": sstats["journal"]["appends"]
                - jstats0["appends"],
                "fsyncs": sstats["journal"]["fsyncs"]
                - jstats0["fsyncs"],
                "acked_write_requests": cstats["write_reqs"],
                "acks_per_fsync": round(
                    cstats["write_reqs"]
                    / (sstats["journal"]["fsyncs"] - jstats0["fsyncs"]),
                    2)
                if sstats["journal"]["fsyncs"] > jstats0["fsyncs"]
                else None,
            } if sstats.get("journal") else None,
            "cache": sstats.get("cache"),
            **adm_receipt,
        },
    }
    ok = (retraces == 0 and cstats["bad_values"] == 0
          and out["serve"]["p99_target_met"]
          and out["serve"]["within_1_3x"])
    if fairness is not None:
        ok = ok and fairness["greedy_rejects"] > 0 \
            and fairness["polite_rejects"] == 0
    out["ok"] = bool(ok)
    print(f"# serve: {served_ops} ops in {elapsed:.2f}s -> "
          f"{serve_ops_s / 1e6:.2f} M ops/s open-loop; read p99 "
          f"{p99_read if p99_read else float('nan'):.2f} ms vs target "
          f"{target:.2f} ({'MET' if out['serve']['p99_target_met'] else 'MISSED'}); "
          f"settled W={settled} (closed {closed_at_settled / 1e6:.2f} M, "
          f"ratio {ratio:.2f}); retraces {retraces}; "
          f"rejects {sstats['rejects']}; "
          f"adm p99 {adm_receipt['adm_jitter_p99_ms']:.3f} ms "
          f"({'feasible' if adm_receipt['adm_feasible'] else 'NOT FEASIBLE'})",
          file=sys.stderr)
    return out


def run_crash_drill(a) -> dict:
    """Journaled-ack durability drill — see the module docstring."""
    from sherman_tpu.models import batched
    from sherman_tpu.models.btree import Tree
    from sherman_tpu.cluster import Cluster
    from sherman_tpu.config import DSMConfig, TreeConfig
    from sherman_tpu.serve import ServeConfig, ShermanServer
    from sherman_tpu.utils import journal as J
    from sherman_tpu.errors import StateError

    widths = tuple(int(w) for w in a.widths.split(","))
    cluster, tree, eng, keys, rank_to_key = build_engine(
        a.keys, widths, False)
    jdir = a.journal_dir or tempfile.mkdtemp(prefix="serve-crash-")
    jpath = os.path.join(jdir, "serve-journal.bin")
    journal = J.Journal(jpath, sync=True,
                        group_commit_ms=a.group_commit_ms)
    cfg = ServeConfig(widths=widths,
                      p99_targets_ms={c: 1e9 for c in
                                      ("read", "scan", "insert",
                                       "delete")},
                      group_commit_ms=a.group_commit_ms,
                      write_linger_ms=0.5)
    srv = ShermanServer(eng, cfg, journal=journal)
    srv.start(calib_keys=keys[:4096],
              calib_writes=(keys[:512], keys[:512] ^ np.uint64(STAMP0)))
    jstats0 = journal.stats()  # calibration fsyncs excluded (run_serve
    # does the same): the acks/fsync pin must count traffic only

    n_writers = 4
    per = a.keys // (n_writers + 1)
    acked: list[dict] = [dict() for _ in range(n_writers)]
    stop = threading.Event()

    def writer(w: int):
        # DISJOINT key slice per writer: per-key FIFO within one tenant
        # makes "last acked value" well-defined for the RPO audit
        my = keys[w * per:(w + 1) * per]
        rng = np.random.default_rng(w)
        gen = 0
        while not stop.is_set():
            gen += 1
            idx = rng.integers(0, my.size, 128)
            kreq = np.unique(my[idx])
            vreq = kreq ^ np.uint64(STAMP1) ^ np.uint64(gen)
            try:
                fut = srv.submit("insert", kreq, vreq,
                                 tenant=f"writer{w}")
                ok = fut.result(timeout=30)
            except StateError:
                return  # the crash: in-flight op never acked, not owed
            except Exception:
                continue
            # the ack gate passed: the OK rows are DURABLE by contract
            # (a lock-timeout row is typed-rejected, never journaled —
            # the ledger must not hold the engine to a write it
            # refused)
            for k, v, o in zip(kreq.tolist(), vreq.tolist(),
                               ok.tolist()):
                if o:
                    acked[w][k] = v

    threads = [threading.Thread(target=writer, args=(w,), daemon=True)
               for w in range(n_writers)]
    for t in threads:
        t.start()
    time.sleep(a.secs)
    # CRASH: kill mid-traffic — no drain, journal left unclosed
    srv.kill()
    stop.set()
    for t in threads:
        t.join(timeout=60)
    jstats = journal.stats()
    n_acked = sum(len(d) for d in acked)
    acked_reqs = srv.acked_writes
    fsyncs = jstats["fsyncs"] - jstats0["fsyncs"]
    acks_per_fsync = acked_reqs / fsyncs if fsyncs else None

    # RECOVERY: rebuild the base image (the bulk-loaded state the
    # journal's records apply onto), replay, audit every acked write
    cfg2 = DSMConfig(machine_nr=1,
                     pages_per_node=pages_for_keys(a.keys),
                     locks_per_node=65_536, step_capacity=max(widths),
                     chunk_pages=1024)
    tree2 = Tree(Cluster(cfg2))
    batched.bulk_load(tree2, keys, keys ^ np.uint64(STAMP0))
    eng2 = batched.BatchedEngine(tree2, batch_per_node=max(widths),
                                 tcfg=TreeConfig(sibling_chase_budget=1))
    eng2.attach_router()
    replay_stats = J.replay(jpath, eng2)
    missing = 0
    for d in acked:
        if not d:
            continue
        ak = np.fromiter(d.keys(), np.uint64, len(d))
        av = np.fromiter(d.values(), np.uint64, len(d))
        got, found = eng2.search(ak)
        missing += int(np.sum(~(found & (got == av))))
    out = {
        "schema_version": 3,
        "metric": "serve_crash_drill",
        "keys": a.keys,
        "acked_write_requests": acked_reqs,
        "acked_rows": n_acked,
        "rpo_ops": missing,
        "group_commit_ms": a.group_commit_ms,
        "journal": jstats,
        "acks_per_fsync": round(acks_per_fsync, 2)
        if acks_per_fsync else None,
        "replay": replay_stats,
        "ok": bool(missing == 0 and n_acked > 0
                   and (acks_per_fsync or 0) > 1),
    }
    print(f"# crash drill: {acked_reqs} acked write reqs ({n_acked} "
          f"rows) across {n_writers} concurrent writers; "
          f"{fsyncs} fsyncs -> {acks_per_fsync:.1f} "
          f"acks/fsync; replayed {replay_stats['records']} records; "
          f"RPO {missing} ops", file=sys.stderr)
    return out


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(
        description="open-loop serving front-door bench / crash drill")
    ap.add_argument("--keys", type=int, default=200_000)
    ap.add_argument("--theta", type=float, default=0.99)
    ap.add_argument("--widths", type=str, default="1024,4096,16384")
    ap.add_argument("--p99-ms", type=float, default=0.0,
                    help="read p99 target in ms (0 = auto from the "
                         "calibrated frontier)")
    ap.add_argument("--secs", type=float, default=6.0)
    ap.add_argument("--tenants", type=int, default=3)
    ap.add_argument("--req-ops", type=int, default=1024,
                    help="ops per client request (one RPC's batch)")
    ap.add_argument("--rho", type=float, default=0.6,
                    help="SLO-phase offered fraction of the MID "
                         "rung's closed-loop calibration rate (the "
                         "sustainable paced anchor).  The "
                         "throughput-vs-closed pin is the capacity "
                         "phase's; this phase must be genuinely "
                         "stable for its p99 to measure the width, "
                         "not a standing queue")
    ap.add_argument("--write-frac", type=float, default=0.0,
                    help="write fraction of SLO-phase requests "
                         "(default 0: the SLO phase measures the "
                         "headline read class, YCSB-C).  Every write "
                         "flush blocks the single dispatcher for one "
                         "engine op (~the insert wall — the journaled "
                         "single-writer contract), so any nonzero "
                         "fraction taxes the read p99 by that stall; "
                         "the write path's own receipts are the crash "
                         "drill's (rpo_ops, acks/fsync)")
    ap.add_argument("--spin-ms", type=float, default=2.0)
    ap.add_argument("--fusion", choices=("aligned", "pipelined"),
                    default="pipelined")
    ap.add_argument("--no-greedy", dest="greedy", action="store_false",
                    help="drop the unpaced burst tenant")
    ap.add_argument("--cache", action="store_true",
                    help="attach the hot-key leaf cache with "
                         "sketch-driven admission (admit_every=16)")
    ap.add_argument("--group-commit-ms", type=float, default=2.0)
    ap.add_argument("--journal-dir", type=str, default=None)
    ap.add_argument("--crash-drill", action="store_true")
    a = ap.parse_args(argv)

    jax = setup_platform(1)
    from sherman_tpu.utils.compile_cache import setup_compile_cache
    setup_compile_cache()

    out = run_crash_drill(a) if a.crash_drill else run_serve(a)
    print(json.dumps(out))


if __name__ == "__main__":
    main()
