#!/usr/bin/env python
"""White-box device-telemetry report: compile ledger + roofline receipts.

The SLO plane answers "how fast was it"; this driver answers "how close
to the machine was it, and did anything recompile behind our back".
Two modes:

- ``--receipt BENCH.json``: render the ``device`` section of a schema-3
  bench receipt (bare or driver-wrapped ``{"parsed": {...}}``) as the
  side-by-side tables — the chip workflow: run ``bench.py`` on the TPU,
  commit the JSON, read the receipts anywhere without a device.
- live (default): build a small tree, run the staged read-only loop
  under a SEALED compile ledger — the zero-retrace steady-state pin:
  warmup covers both carry variants, so ANY compile inside the sealed
  window is a silent retrace and the report raises — then attribute
  per-phase walls (chained-delta, ``step.phase_profile``) and join them
  with each compiled program's ``cost_analysis()`` byte/flop floor into
  roofline receipts (:func:`sherman_tpu.obs.device.rooflines`).

Env knobs (live mode): KEYS (20 K), B (8192), DEVB (B), K (delta reps,
2), STEPS (sealed steps, 8), FUSION (config.staged_fusion), SAMPLER
(analytic), THETA (0.99).  ``SHERMAN_PEAK_GBPS``/``SHERMAN_PEAK_TFLOPS``
set the roofs on devices the peak table does not know (absolute
achieved rates print otherwise — fractions are never invented).
``SHERMAN_BENCH_DEVICE_MEMORY=0`` skips per-program memory_analysis.
``SHERMAN_LEAF_CACHE`` runs the sealed loop with the hot-key tier's
``cache_probe`` program chained in (prefilled with the hottest ranks)
— the zero-retrace pin then covers the cache-on serving loop.

Output (the profile_gather/profile_staged2 conventions): the ledger
table (program, compiles, compile ms, retraces), the roofline table
(phase, program, wall ms, GB/s, GF/s, fraction-of-peak, bound), the
memory gauges, and ONE JSON line ``{"metric": "device_report", ...}``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _f(v, w=8, p=2):
    """Right-aligned number or an em-dash for absent values."""
    return f"{v:{w}.{p}f}" if isinstance(v, (int, float)) else f"{'—':>{w}s}"


def print_tables(dev: dict, file=sys.stderr) -> None:
    """The side-by-side tables of one ``device`` section (bench JSON
    schema 3 or the live report's identical shape)."""
    led = dev.get("ledger") or {}
    print(f"# compile ledger ({dev.get('compile_source', '?')}): "
          f"{led.get('programs', 0)} programs, "
          f"{led.get('compiles', 0)} compiles, "
          f"{led.get('compile_ms_total', 0)} ms total, "
          f"{led.get('retraces', 0)} steady-state retraces over "
          f"{led.get('sealed_windows', 0)} sealed windows", file=file)
    print(f"# {'program':34s} {'compiles':>8s} {'compile ms':>11s} "
          f"{'retraces':>8s}", file=file)
    for e in led.get("entries", ()):
        print(f"# {e['label']:34s} {e['compiles']:>8d} "
              f"{e['compile_ms']:>11.1f} {e['retraces']:>8d}", file=file)
    peaks = dev.get("peaks") or {}
    for group, phases in (dev.get("rooflines") or {}).items():
        print(f"#\n# roofline receipts [{group}] "
              f"(peaks: {peaks.get('source', '?')})", file=file)
        print(f"# {'phase':22s} {'program':30s} {'wall ms':>8s} "
              f"{'GB/s':>8s} {'GF/s':>8s} {'B-frac':>8s} {'F-frac':>8s} "
              f"{'bound':>6s}", file=file)
        for ph, rec in phases.items():
            if not rec.get("available"):
                print(f"# {ph:22s} {rec.get('program', '?'):30s} "
                      f"{_f(rec.get('wall_ms'))} unavailable: "
                      f"{rec.get('reason', '?')}", file=file)
                continue
            if rec.get("wall_below_resolution"):
                # a sub-resolution wall makes the achieved rates noise
                # (532 TB/s "bandwidth" on a 0.00 ms wall) — the JSON
                # keeps them; the human table must not present them
                print(f"# {ph:22s} {rec.get('program', '?'):30s} "
                      f"{_f(rec.get('wall_ms'))} {'<res':>8s} {'<res':>8s} "
                      f"{'—':>8s} {'—':>8s} {'—':>6s}", file=file)
                continue
            print(f"# {ph:22s} {rec.get('program', '?'):30s} "
                  f"{_f(rec.get('wall_ms'))} "
                  f"{_f(rec.get('achieved_gbytes_s'))} "
                  f"{_f(rec.get('achieved_gflops_s'))} "
                  f"{_f(rec.get('achieved_bytes_frac'), p=4)} "
                  f"{_f(rec.get('achieved_flops_frac'), p=4)} "
                  f"{rec.get('bound', '—'):>6s}", file=file)
    mem = dev.get("memory") or {}
    if mem:
        print("#\n# memory gauges: "
              + ", ".join(f"{k} {v}" for k, v in sorted(mem.items())),
              file=file)


def _receipt_report(path: str) -> dict:
    with open(path) as f:
        doc = json.load(f)
    if isinstance(doc, dict) and isinstance(doc.get("parsed"), dict):
        doc = doc["parsed"]
    dev = doc.get("device") if isinstance(doc, dict) else None
    if not isinstance(dev, dict):
        out = {"metric": "device_report", "source": path,
               "error": "no device section (schema_version < 3 or "
                        "SHERMAN_DEVICE_OBS=0 run)"}
        print(json.dumps(out))
        return out
    print_tables(dev)
    out = {"metric": "device_report", "source": path,
           "schema_version": doc.get("schema_version"),
           "retraces": (dev.get("ledger") or {}).get("retraces"),
           "device": dev}
    print(json.dumps(out))
    return out


def _live_report() -> dict:
    import jax

    from sherman_tpu.utils.compile_cache import setup_compile_cache
    setup_compile_cache()

    import common
    from sherman_tpu import native, obs
    from sherman_tpu import config as C
    from sherman_tpu.config import LEAF_CAP
    from sherman_tpu.models import batched
    from sherman_tpu.obs import device as DEV
    from sherman_tpu.ops import bits
    from sherman_tpu.workload import device_prep

    n_keys = int(os.environ.get("KEYS", 20_000))
    batch = int(os.environ.get("B", 8192))
    dev_b = int(os.environ.get("DEVB", batch))
    theta = float(os.environ.get("THETA", 0.99))
    K = int(os.environ.get("K", 2))
    S = int(os.environ.get("STEPS", 8))
    fusion = os.environ.get("FUSION") or C.staged_fusion()
    sampler = os.environ.get("SAMPLER", "analytic")
    salt = 0x5E17_AB1E_5A17
    per_leaf = max(1, int(LEAF_CAP * 0.75))
    est_pages = int(n_keys / per_leaf * 1.10) + 2048
    pages = 1 << max(12, (est_pages - 1).bit_length())

    # a fresh ledger for THIS report: the process may have compiled
    # under other labels before (pytest smoke); the programs built
    # below are new jit objects, so their compiles land cleanly
    ledger = DEV.get_ledger()
    ledger.reset()

    _, tree, eng = common.build_cluster(1, pages, batch)
    if native.available():
        keys, _ = native.synthetic_keyspace(n_keys, salt)
    else:
        ranks = np.arange(n_keys, dtype=np.uint64)
        keys = np.sort(bits.mix64_np(ranks ^ np.uint64(salt)))
    t0 = time.time()
    with obs.span("device_report.bulk_load", keys=n_keys):
        batched.bulk_load(tree, keys, keys ^ np.uint64(0xDEADBEEF),
                          fill=0.75)
    eng.attach_router()
    print(f"# bulk_load {time.time() - t0:.1f}s", file=sys.stderr)

    # hot-key tier (SHERMAN_LEAF_CACHE): run the sealed loop with the
    # cache_probe program chained in — the zero-retrace pin then covers
    # the cache-on serving loop (fixed table shapes by construction)
    lc = None
    if C.leaf_cache_slots():
        lc = eng.attach_leaf_cache()
        hot = bits.mix64_np(
            np.arange(min(lc.capacity, n_keys), dtype=np.uint64)
            ^ np.uint64(salt))
        filled = lc.fill(hot)
        print(f"# leaf cache: {lc.slots} slots, prefilled "
              f"{filled['placed']} hottest ranks", file=sys.stderr)
    step, (new_carry, tb, rt, rk) = device_prep.make_staged_step(
        eng, n_keys=n_keys, theta=theta, salt=salt, batch=batch,
        dev_b=dev_b, sampler=sampler, fusion=fusion, leaf_cache=lc)
    dsm = eng.dsm
    pool, counters = dsm.pool, dsm.counters

    # warmup: BOTH carry variants (fresh new_carry() host shardings and
    # the threaded program outputs are distinct jit entries), so the
    # sealed window below must observe zero compiles
    carry = new_carry()
    counters, carry = step(pool, counters, tb, rt, rk, carry)
    counters, carry = step(pool, counters, tb, rt, rk, carry)
    carry = step.drain(carry)
    jax.block_until_ready(carry)

    # sealed steady-state loop — the zero-retrace pin
    with ledger.sealed_scope():
        t0 = time.perf_counter()
        for _ in range(S):
            counters, carry = step(pool, counters, tb, rt, rk, carry)
        carry = step.drain(carry)
        jax.block_until_ready(carry)
        wall = time.perf_counter() - t0
    assert int(np.asarray(carry[1])) == 1, "unique overflow"
    assert int(np.asarray(carry[2])) == (S + 2) * batch, \
        "staged receipts failed"
    retraces = ledger.retraces
    cache_hit_ratio = None
    if lc is not None:
        hits = int(np.asarray(carry[5]))
        cache_hit_ratio = hits / ((S + 2) * batch)
        print(f"# leaf cache: {hits} client hits "
              f"(ratio {cache_hit_ratio:.4f})", file=sys.stderr)
        assert hits > 0, "cache-on sealed loop served zero hits"
    print(f"# sealed loop: {S} steps in {wall:.3f}s "
          f"({wall / S * 1e3:.2f} ms/step), {retraces} retraces",
          file=sys.stderr)

    with obs.span("device_report.phase_attribution", reps=K):
        phase_ms, counters = step.phase_profile(pool, counters, tb, rt,
                                                rk, reps=K)
    device_prep.record_phase_obs("staged", phase_ms)
    dsm.counters = counters

    peaks = DEV.device_peaks()
    want_mem = os.environ.get("SHERMAN_BENCH_DEVICE_MEMORY", "1") != "0"
    roofs = DEV.rooflines(phase_ms, step.phase_labels, memory=want_mem,
                          peaks=peaks, ledger=ledger)
    dev = {
        "compile_source": ledger.attach(),
        "ledger": ledger.summary(),
        "peaks": peaks,
        "rooflines": {"staged": roofs},
        "memory": DEV.get_accountant().gauges(),
    }
    print_tables(dev)
    out = {"metric": "device_report", "fusion": step.fusion,
           "keys": n_keys, "batch": batch, "steps": S,
           "wall_ms_per_step": round(wall / S * 1e3, 3),
           "retraces": retraces,
           "cache": ({"slots": lc.slots,
                      "hit_ratio": round(cache_hit_ratio, 4)}
                     if lc is not None else None),
           "device": dev}
    print(json.dumps(out))
    # the pin itself: a live report with a steady-state retrace is a
    # broken serving loop, not a report
    assert retraces == 0, \
        f"{retraces} steady-state retraces in the sealed loop (see " \
        "the compile ledger table above)"
    return out


def main(argv=None) -> dict:
    ap = argparse.ArgumentParser(
        description="white-box device report: compile ledger + rooflines")
    ap.add_argument("--receipt", default=None,
                    help="render a schema-3 bench JSON's device section "
                         "instead of running the live sealed loop")
    a = ap.parse_args(argv)
    if a.receipt:
        return _receipt_report(a.receipt)
    return _live_report()


if __name__ == "__main__":
    main()
