#!/usr/bin/env python
"""Staged-step anatomy: decompose the device-staged step and put it
side by side with the host-staged serve it must match.

Round-5 left a measured-but-unexplained 2x ("known headroom" in
BENCHMARKS.md): the staged step ran ~124 ms/step while the identical
routed serve fed host-staged inputs measured 72-84 ms.  This driver is
the attribution tool for that gap:

- builds the staged step in any fusion mode (``FUSION`` env:
  aligned | pipelined | chained | fused — see ``config.staged_fusion``),
- times the FULL pipelined step (bounded dispatch window, the honest
  loop shape bench.py runs),
- attributes per-phase costs with the chained-delta method
  (``step.phase_profile``: K and 2K data-dependent repetitions per
  program, cost = (t_2K - t_K)/K — cancels the per-call sync, see
  tools/profile_insert.py),
- runs the HOST-STAGED comparator: the engine's combined-search
  fan-out program on one pre-staged batch of the same width — in
  ``aligned`` mode this is the SAME compiled program object the staged
  serve dispatches, so staged-vs-host serve cost is an apples-to-apples
  diff by construction,
- records every region as an obs span / histogram and prints the
  side-by-side prep-vs-serve table plus ONE JSON line,
- runs the MODE WALL table (round-8): aligned vs ``pipelined`` (the
  two-deep software pipeline — verify k-1 / prep k+1 dispatched behind
  serve k) through the same bounded-window loop, each with its
  ``bubble_ms`` (wall − serve: the work not hidden behind the serve
  bound) and ``overlap_efficiency`` (1 − wall/(prep+serve+verify))
  against ONE shared phase attribution — the JSON ``modes`` block is
  the CPU receipt for BENCHMARKS' Round-8 and the input to the queued
  pipelined-vs-aligned chip A/B.

Env knobs: KEYS (10 M), B (4 M), DEVB, K (delta reps, 8), FUSION,
SAMPLER (analytic), W (dispatch window, 8), STEPS (pipelined steps, 24),
MODES (mode-wall table, default "aligned,pipelined"; "" disables; a
"+cache" suffix — e.g. "aligned+cache" — runs that mode with the
hot-key leaf cache's probe program chained in and the residual serve
width sized from a 2-step warmup's measured misses (RESID env
overrides), attributed with its own cache_probe/residual-serve phase
walls so the probe cost AND the serve shrink are priced next to the
uncached modes).
"""

import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _host_staged_batch(native, router, n_keys, batch, dev_b, theta, salt):
    """One host-staged batch (khi, klo, start, active, inv) — the
    throughput-phase prep: native BatchPrep when available, else the
    numpy unique+inverse fallback (CPU smoke runs)."""
    from sherman_tpu.ops import bits

    if native.available():
        prep_h = native.BatchPrep(batch, dev_b, n_keys, theta, seed=11,
                                  salt=salt)
        buf = prep_h.buffers()
        b = prep_h.run_zipf(None, buf, router.table_np, router.shift)
        return b.khi, b.klo, b.start, b.active.view(bool), b.inv
    from sherman_tpu.workload.zipf import ZipfGen, uniform_ranks
    if theta > 0:
        ranks = ZipfGen(n_keys, theta, seed=11).sample(batch)
    else:
        ranks = uniform_ranks(n_keys, batch, np.random.default_rng(11))
    keys = bits.mix64_np(ranks.astype(np.uint64) ^ np.uint64(salt))
    uk, inv = np.unique(keys, return_inverse=True)
    assert uk.size <= dev_b, (uk.size, dev_b)
    pad = (0, dev_b - uk.size)
    khi, klo = bits.keys_to_pairs(np.pad(uk, pad))
    act = np.zeros(dev_b, bool)
    act[:uk.size] = True
    start = np.pad(router.host_start(*bits.keys_to_pairs(uk)), pad)
    return khi, klo, start, act, inv.astype(np.int32)


def main():
    import jax

    from sherman_tpu.utils.compile_cache import setup_compile_cache
    setup_compile_cache()

    from sherman_tpu import native, obs
    from sherman_tpu import config as C
    from sherman_tpu.cluster import Cluster
    from sherman_tpu.config import DSMConfig, LEAF_CAP, TreeConfig
    from sherman_tpu.models import batched
    from sherman_tpu.models.btree import Tree
    from sherman_tpu.ops import bits
    from sherman_tpu.workload import device_prep

    n_keys = int(os.environ.get("KEYS", 10_000_000))
    batch = int(os.environ.get("B", 4_194_304))
    theta = float(os.environ.get("THETA", 0.99))
    fusion = os.environ.get("FUSION") or C.staged_fusion()
    sampler = os.environ.get("SAMPLER", "analytic")
    K = int(os.environ.get("K", 8))
    W = int(os.environ.get("W", 8))
    n_steps = int(os.environ.get("STEPS", 24))
    salt = 0x5E17_AB1E_5A17
    fill = 0.75
    per_leaf = max(1, int(LEAF_CAP * fill))
    est_pages = int(n_keys / per_leaf * 1.10) + 8192
    pages = 1 << max(14, (est_pages - 1).bit_length())
    cfg = DSMConfig(machine_nr=1, pages_per_node=pages,
                    locks_per_node=65_536, step_capacity=batch,
                    chunk_pages=4096)
    cluster = Cluster(cfg)
    tree = Tree(cluster)
    eng = batched.BatchedEngine(tree, batch_per_node=batch,
                                tcfg=TreeConfig(sibling_chase_budget=1))
    if native.available():
        keys, _ = native.synthetic_keyspace(n_keys, salt)
    else:
        ranks = np.arange(n_keys, dtype=np.uint64)
        keys = np.sort(bits.mix64_np(ranks ^ np.uint64(salt)))
    t0 = time.time()
    with obs.span("profile.bulk_load", keys=n_keys):
        batched.bulk_load(tree, keys, keys ^ np.uint64(0xDEADBEEF),
                          fill=fill)
    eng.attach_router()
    print(f"# bulk_load {time.time() - t0:.1f}s", file=sys.stderr)

    dev_b = int(os.environ.get("DEVB", min(batch, 1_097_728 + 16384)))
    step, (new_carry, table_d, rtable_d, rkey_d) = \
        device_prep.make_staged_step(eng, n_keys=n_keys, theta=theta,
                                     salt=salt, batch=batch, dev_b=dev_b,
                                     sampler=sampler, fusion=fusion)
    dsm = eng.dsm
    pool, counters = dsm.pool, dsm.counters

    # A. full staged step, pipelined with the bounded dispatch window
    # bench.py uses (PJRT allocates output buffers at enqueue; block on
    # the LAST program's carry from W steps back)
    from collections import deque

    def windowed_wall(stp, nc, box, span_name):
        """Bounded-window wall per step of one staged-step build,
        chained-delta timed (STEPS and 2*STEPS windowed dispatches,
        cost = (t_2K - t_K)/K — same methodology as the phases, so
        the loop-invocation constant [first-dispatch program load,
        carry staging] cancels and wall-vs-phase comparisons are
        apples to apples).  Receipts verified on every invocation
        (drained — the pipelined mode's receipts lag a batch until
        ``stp.drain``)."""
        state = {}

        def loop(k):
            carry = nc()
            pend = deque()
            for _ in range(k):
                box["c"], carry = stp(pool, box["c"], table_d,
                                      rtable_d, rkey_d, carry)
                pend.append(carry[1])
                if len(pend) > W:
                    jax.block_until_ready(pend.popleft())
            carry = stp.drain(carry)
            jax.block_until_ready(carry)
            assert int(np.asarray(carry[1])) == 1, \
                "windowed loop: unique overflow"
            assert int(np.asarray(carry[2])) == k * batch, \
                "windowed loop: receipts failed"
            state["steps"] = k

        # warm BOTH carry variants before the delta: step 1 consumes a
        # fresh new_carry() (host-put shardings), step 2+ the threaded
        # program outputs — two jit cache entries, and the second's
        # trace must not land inside the first timed invocation
        loop(2)
        with obs.span(span_name, steps=n_steps, fusion=stp.fusion):
            wall = device_prep._delta_ms(loop, n_steps)
        assert state["steps"] == 2 * n_steps  # every batch verified
        return wall

    cbox = {"c": counters}
    full_ms = windowed_wall(step, new_carry, cbox,
                            "profile.full_step_pipelined")
    counters = cbox["c"]
    obs.histogram("staged.full_step_ms").record(full_ms)
    print(f"{'full_step':20s} {full_ms:9.1f} ms/step (windowed W={W}, "
          f"chained-delta, receipts verified)", file=sys.stderr)

    # B. per-phase attribution (chained-delta; obs histograms under
    # staged.<phase>_ms)
    with obs.span("profile.phase_attribution", reps=K, fusion=fusion):
        phase_ms, counters = step.phase_profile(pool, counters, table_d,
                                                rtable_d, rkey_d, reps=K)
    device_prep.record_phase_obs("staged", phase_ms)
    for name, ms in phase_ms.items():
        if name == "overlap_efficiency":  # a ratio, not a wall
            print(f"{name:20s} {ms:9.2f}", file=sys.stderr)
        else:
            print(f"{name:20s} {ms:9.1f} ms", file=sys.stderr)

    # C. host-staged serve comparator: the engine fan-out program on one
    # pre-staged batch of the same width.  In 'aligned' mode this is the
    # same compiled program object as the staged serve.
    hkhi, hklo, hstart, hact, hinv = _host_staged_batch(
        native, eng.router, n_keys, batch, dev_b, theta, salt)
    shard = dsm.shard
    d = (jax.device_put(hkhi, shard), jax.device_put(hklo, shard),
         jax.device_put(hstart, shard), jax.device_put(hact, shard),
         jax.device_put(hinv, shard))
    fn = eng._get_search_fanout(eng._iters())
    root = np.int32(tree._root_addr)
    box = {"c": counters}

    def serve_host_loop(k):
        out = None
        for _ in range(k):
            box["c"], done, found, vhi, vlo = fn(
                pool, box["c"], d[0], d[1], root, d[3], d[2], d[4])
            out = found
        jax.block_until_ready(out)

    with obs.span("profile.serve_host_staged", reps=K):
        serve_host_ms = device_prep._delta_ms(serve_host_loop, K)
    counters = box["c"]
    obs.histogram("staged.serve_host_staged_ms").record(serve_host_ms)
    dsm.counters = counters

    # side-by-side: what the staged loop pays vs the host-staged serve.
    # Only the serve-bearing phase is comparable: aligned's serve_fanout
    # (the SAME compiled program as the comparator) and chained's
    # serve_fanout_verify (serve + ~elementwise verify).  A fused run
    # has no separable serve — its ratio would fold prep+verify in and
    # read as a phantom serve regression, so it is not published.
    staged_serve = phase_ms.get("serve_fanout",
                                phase_ms.get("serve_fanout_verify"))
    print("#\n# side-by-side (ms): staged step vs host-staged serve",
          file=sys.stderr)
    print(f"# {'phase':22s} {'staged':>9s} {'host-staged':>12s}",
          file=sys.stderr)
    print(f"# {'prep':22s} {phase_ms.get('prep', float('nan')):9.1f} "
          f"{'(host prep untimed)':>12s}", file=sys.stderr)
    if staged_serve is not None:
        print(f"# {'serve(+fanout)':22s} {staged_serve:9.1f} "
              f"{serve_host_ms:12.1f}", file=sys.stderr)
    else:
        print(f"# {'fused prep+serve+verify':22s} "
              f"{phase_ms['fused_step']:9.1f} {serve_host_ms:12.1f}",
              file=sys.stderr)
    if "verify" in phase_ms:
        print(f"# {'verify':22s} {phase_ms['verify']:9.1f} "
              f"{'—':>12s}", file=sys.stderr)
    print(f"# {'full step (pipelined)':22s} {full_ms:9.1f} "
          f"{'—':>12s}", file=sys.stderr)
    gap = (staged_serve / serve_host_ms
           if staged_serve is not None and serve_host_ms else None)
    if gap is not None:
        same = (" (aligned dispatches the SAME program: any residual is"
                " input production, not program shape)"
                if fusion == "aligned" else
                " (chained serve also folds the ~elementwise verify)")
        print(f"# staged-serve / host-staged-serve = {gap:.2f}x{same}",
              file=sys.stderr)
    else:
        print("# no serve-only ratio for fused runs (one program; "
              "prep+verify inseparable)", file=sys.stderr)

    # D. mode wall table (round-8): aligned vs the two-deep pipelined
    # form through the SAME bounded-window loop.  The three compiled
    # programs are SHARED between the modes by construction (pipelined
    # reuses the aligned serve object), so ONE phase attribution prices
    # both: bubble_ms = wall - serve (work not hidden behind the serve
    # bound), overlap_efficiency = 1 - wall/(prep+serve+verify).
    modes_env = os.environ.get("MODES", "aligned,pipelined")
    modes = {}
    if modes_env.strip():
        want = [m.strip() for m in modes_env.split(",") if m.strip()]
        # "+cache" suffix (e.g. "aligned+cache"): the same fusion mode
        # with the hot-key leaf cache's probe program chained in, so
        # the probe's cost is attributable per phase next to the
        # uncached walls.  The cache is built once, prefilled with the
        # analytically hottest ranks (the zipf sampler's own ranking).
        lc_box = {"lc": None}

        def _leaf_cache():
            if lc_box["lc"] is None:
                lc = eng.attach_leaf_cache()
                lc.fill(bits.mix64_np(
                    np.arange(min(lc.capacity, n_keys),
                              dtype=np.uint64) ^ np.uint64(salt)))
                lc_box["lc"] = lc
            return lc_box["lc"]

        by_mode = {}
        for spec_m in want:
            base_m, _, suffix = spec_m.partition("+")
            if suffix not in ("", "cache"):
                raise SystemExit(f"MODES entry {spec_m!r}: want "
                                 "<fusion> or <fusion>+cache")
            cache_on = suffix == "cache"
            resid = None
            if cache_on:
                # size the residual serve width from a 2-step warmup of
                # a full-width sizing build (bench.py's cap-tightening
                # dance — the serve must SHRINK for the hits to pay;
                # RESID env overrides).  Overflow voids via the ok
                # receipt, which windowed_wall asserts on.
                resid_env = os.environ.get("RESID")
                if resid_env:
                    resid = int(resid_env)
                else:
                    sz, (nc_sz, *_r) = device_prep.make_staged_step(
                        eng, n_keys=n_keys, theta=theta, salt=salt,
                        batch=batch, dev_b=dev_b, sampler=sampler,
                        fusion=base_m,
                        staged=(table_d, rtable_d, rkey_d),
                        leaf_cache=_leaf_cache())
                    c_sz = nc_sz()
                    cbox = {"c": counters}
                    for _ in range(2):
                        cbox["c"], c_sz = sz(pool, cbox["c"], table_d,
                                             rtable_d, rkey_d, c_sz)
                    c_sz = sz.drain(c_sz)
                    jax.block_until_ready(c_sz)
                    counters = cbox["c"]
                    miss = (int(np.asarray(c_sz[3]))
                            - int(np.asarray(c_sz[6]))) // 2
                    # quantum scales down with dev_b so smoke-scale
                    # runs still show a real shrink (bench.py's 8192
                    # matters only at its multi-M widths)
                    q = min(8192, max(256, dev_b // 8))
                    resid = min(dev_b,
                                -(-int(max(1, miss) * 1.05) // q) * q)
                    print(f"# {spec_m}: residual serve width {resid} "
                          f"of {dev_b} ({miss} measured misses/step)",
                          file=sys.stderr)
            if base_m == fusion and not cache_on:
                by_mode[spec_m] = (step, new_carry)
            else:
                s2, (nc2, *_r) = device_prep.make_staged_step(
                    eng, n_keys=n_keys, theta=theta, salt=salt,
                    batch=batch, dev_b=dev_b, sampler=sampler,
                    fusion=base_m, staged=(table_d, rtable_d, rkey_d),
                    leaf_cache=_leaf_cache() if cache_on else None,
                    dev_b_resid=resid)
                by_mode[spec_m] = (s2, nc2)
        if {"prep", "serve_fanout", "verify"} <= set(phase_ms):
            attr = phase_ms
        else:  # anatomy ran chained/fused: attribute the shared
            #    3-program form once for the table
            s_al, nc_al = by_mode.get("aligned", (None, None))
            if s_al is None:
                s_al, (nc_al, *_r) = device_prep.make_staged_step(
                    eng, n_keys=n_keys, theta=theta, salt=salt,
                    batch=batch, dev_b=dev_b, sampler=sampler,
                    fusion="aligned", staged=(table_d, rtable_d,
                                              rkey_d))
            with obs.span("profile.mode_attribution", reps=K):
                attr, counters = s_al.phase_profile(
                    pool, counters, table_d, rtable_d, rkey_d, reps=K)
        serial = attr["prep"] + attr["serve_fanout"] + attr["verify"]
        print(f"#\n# mode walls (W={W}, {n_steps} steps; serial sum "
              f"{serial:.1f} ms = prep {attr['prep']:.1f} + serve "
              f"{attr['serve_fanout']:.1f} + verify "
              f"{attr['verify']:.1f})", file=sys.stderr)
        print(f"# {'mode':16s} {'wall_ms':>9s} {'bubble_ms':>10s} "
              f"{'overlap_eff':>12s}", file=sys.stderr)
        attr_cache = None  # one shared attribution per cache-ness
        for mode in want:
            s2, nc2 = by_mode[mode]
            cache_on = bool(getattr(s2, "cache", False))
            if cache_on and attr_cache is None:
                # cache modes get their OWN attribution: the serve
                # phase measures the RESIDUAL batch and cache_probe is
                # a fourth program
                with obs.span("profile.mode_attribution_cache", reps=K):
                    attr_cache, counters = s2.phase_profile(
                        pool, counters, table_d, rtable_d, rkey_d,
                        reps=K)
            a = attr_cache if cache_on else attr
            cbox = {"c": counters}
            wall = (full_ms if mode == fusion else windowed_wall(
                s2, nc2, cbox, f"profile.mode_wall_{mode}"))
            counters = cbox["c"]
            rec = device_prep.overlap_receipt(
                a["prep"] + a.get("cache_probe", 0.0),
                a["serve_fanout"], a["verify"], wall)
            row = {"wall_ms": round(rec["wall_ms"], 2),
                   "bubble_ms": round(rec["bubble_ms"], 2),
                   "overlap_efficiency":
                   round(rec["overlap_efficiency"], 3)}
            if cache_on:
                row["cache_probe_ms"] = round(
                    a.get("cache_probe", 0.0), 2)
                row["serve_fanout_ms"] = round(a["serve_fanout"], 2)
            modes[mode] = row
            obs.histogram(f"staged.{mode}_wall_ms").record(wall)
            print(f"# {mode:16s} {row['wall_ms']:9.1f} "
                  f"{row['bubble_ms']:10.1f} "
                  f"{row['overlap_efficiency']:12.3f}", file=sys.stderr)
    dsm.counters = counters

    out = {
        "metric": "staged_step_anatomy",
        "fusion": fusion,
        "sampler": step.sampler,
        "n_programs": step.n_programs,
        "full_step_ms": round(full_ms, 2),
        "phase_ms": {k: round(v, 2) for k, v in phase_ms.items()},
        "serve_host_staged_ms": round(serve_host_ms, 2),
        # serve-vs-serve only (aligned/chained); null on fused runs —
        # there is no separable staged serve to compare
        "staged_vs_host_serve_ratio": round(gap, 3)
        if gap is not None else None,
        # per-mode bounded-window walls + overlap receipts (round-8):
        # {mode: {wall_ms, bubble_ms, overlap_efficiency}} — the
        # pipelined-vs-aligned side of the queued chip A/B
        "modes": modes or None,
        "pipeline_depth": step.pipeline_depth,
        "keys": n_keys, "batch": batch, "dev_b": dev_b,
        "window": W, "delta_reps": K,
        # per-phase obs spans/histograms of this run (staged.* keys)
        "obs": obs.obs_section(),
    }
    print(json.dumps(out))
    return out


if __name__ == "__main__":
    main()
