#!/usr/bin/env python
"""Chip smoke: drive sherman_tpu's main path once on a real TPU and check
every answer against a plain numpy model of the same data.

One process, the library's own entry points, the deployment bench.py
sizes (BASELINE.md north star): 100 M synthetic keys (bench's mix64 key
map), zipf 0.99 point operations, one node per chip.  Phases, in the
order they run (each prints one line: PASS/FAIL, its wall time — a smoke
timing, never a benchmark figure — and what it checked):

  device   jax.devices(): platform must be "tpu" (else exit non-zero)
  load     Cluster + Tree + BatchedEngine exactly as bench.py:run builds
           them; bulk-load the keys, values made from --seed
  search   one search_combined batch of bench's width (4,194,304 zipf
           client keys), every answer vs np.searchsorted on the model
  staged   a few steps of the device-staged loop bench's sustained phase
           builds (make_staged_step); on-device verified count must be
           steps x batch.  Runs before `mutate`: its on-device check
           needs the loaded values untouched
  mutate   fresh inserts + updates, one 50/50 mixed batch, deletes,
           range_query_many — each vs the model — then the device
           structure validator.  Writes go through a second engine on
           the same tree, 65,536 rows wide: the bench-width engine's
           4 M-row apply would hold a 4 GB page snapshot beside the
           4.3 GB pool
  serve    reads, inserts, deletes and scans from two tenants through
           the served front door (ShermanServer start -> submit ->
           stop(drain=True)); every future resolves and matches
  kernels  the Pallas page kernels on the loaded pool, bit-identical to
           their "xla" twins
  engines  the first tree released, a gather_impl="pallas" engine loaded
           with the same keys: one bench-width search_combined and one
           mixed batch (reads, inserts, updates) vs the model; its pool
           bit-identical to an "xla" twin's after the same batch

``--chips 4`` runs only the sharded path instead: a machine_nr=4 cluster
over four TPUs holding the same keys (per-node page occupancy printed),
routed insert/search/mixed batches vs the model, and the same again with
exchange_impl="pallas", pools bit-identical to "xla".

The last stdout line is ``{"ok": true, "device": {...}}`` — printed only
when every phase passed.  Any failure exits non-zero with no such line.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
import traceback

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))

THETA = 0.99
FILL = 0.75  # bench.py's bulk fill
SALT = 0x5E17_AB1E_5A17  # bench.py's synthetic key map salt


class SmokeError(RuntimeError):
    """A smoke check failed: the named phase saw a wrong answer."""


def check(cond, what: str) -> None:
    if not cond:
        raise SmokeError(what)


def report(name: str, t0: float, checked: str) -> None:
    print(f"[chip_smoke] {name}: PASS in {time.time() - t0:.2f} s wall "
          f"(smoke timing, not a benchmark figure); checked: {checked}",
          flush=True)


# ---------------------------------------------------------------------------
# The plain reference: live keys sorted, values beside them.
# ---------------------------------------------------------------------------

class Reference:
    def __init__(self, keys, vals):
        order = np.argsort(keys, kind="stable")
        self.keys = np.asarray(keys, np.uint64)[order]
        self.vals = np.asarray(vals, np.uint64)[order]

    def __len__(self) -> int:
        return self.keys.shape[0]

    def _find(self, q):
        q = np.asarray(q, np.uint64)
        i = np.searchsorted(self.keys, q)
        ic = np.minimum(i, len(self) - 1)
        return ic, (i < len(self)) & (self.keys[ic] == q)

    def contains(self, q) -> np.ndarray:
        return self._find(q)[1]

    def lookup(self, q):
        ic, found = self._find(q)
        return np.where(found, self.vals[ic], np.uint64(0)), found

    def upsert(self, k, v) -> None:
        k = np.asarray(k, np.uint64)
        v = np.asarray(v, np.uint64)
        ic, found = self._find(k)
        self.vals[ic[found]] = v[found]
        new = ~found
        if new.any():
            nk, nv = k[new], v[new]
            order = np.argsort(nk)
            nk, nv = nk[order], nv[order]
            at = np.searchsorted(self.keys, nk)
            self.keys = np.insert(self.keys, at, nk)
            self.vals = np.insert(self.vals, at, nv)

    def delete(self, k) -> None:
        ic, found = self._find(k)
        keep = np.ones(len(self), bool)
        keep[ic[found]] = False
        self.keys, self.vals = self.keys[keep], self.vals[keep]

    def range(self, lo: int, hi: int):
        a, b = np.searchsorted(self.keys, np.uint64(lo)), \
            np.searchsorted(self.keys, np.uint64(hi))
        return self.keys[a:b], self.vals[a:b]


def check_lookup(ref: Reference, q, vals, found, what: str) -> None:
    want_v, want_f = ref.lookup(q)
    check(np.array_equal(np.asarray(found, bool), want_f),
          f"{what}: {int((np.asarray(found) != want_f).sum())} found flags "
          "differ from the model")
    check(np.array_equal(np.asarray(vals, np.uint64)[want_f], want_v[want_f]),
          f"{what}: values differ from the model")


def fresh_keys(ref: Reference, rng, n: int) -> np.ndarray:
    """``n`` keys absent from the model."""
    from sherman_tpu import config as C
    out = np.empty(0, np.uint64)
    while out.shape[0] < n:
        cand = rng.integers(C.KEY_MIN, C.KEY_MAX, 2 * n, dtype=np.uint64)
        cand = np.setdiff1d(np.unique(cand), out)
        out = np.concatenate([out, cand[~ref.contains(cand)]])
    return rng.permutation(out[:n])


def sample_keys(ref: Reference, rng, n: int, exclude=None) -> np.ndarray:
    """``n`` distinct live keys (not in ``exclude``)."""
    out = np.empty(0, np.uint64)
    while out.shape[0] < n:
        cand = ref.keys[rng.integers(0, len(ref), 2 * n)]
        if exclude is not None:
            cand = cand[~np.isin(cand, exclude)]
        out = np.union1d(out, cand)
    return rng.permutation(out)[:n]


def new_values(rng, n: int) -> np.ndarray:
    return rng.integers(1, 1 << 63, n, dtype=np.uint64)


# ---------------------------------------------------------------------------
# Phases.
# ---------------------------------------------------------------------------

def phase_device(chips: int = 1):
    """-> (platform, kind, count); SmokeError unless ``chips`` TPUs."""
    import jax
    devs = jax.devices()
    d = devs[0]
    print(f"[chip_smoke] devices: {devs}", flush=True)
    check(d.platform == "tpu",
          f"platform is {d.platform!r}, not 'tpu': this smoke runs only "
          "on the chip")
    check(len(devs) >= chips, f"need {chips} TPUs, found {len(devs)}")
    return d.platform, d.device_kind, len(devs)


def phase_load(n_keys: int, batch: int, seed: int, *, nodes: int = 1,
               exchange_impl: str = "xla", gather_impl: str = "xla",
               keyspace=None) -> dict:
    """Build the cluster bench.py:run builds, through the same library
    rule (``nodes`` > 1: the same pool split over the nodes), and
    bulk-load ``n_keys`` keys.  ``keyspace`` reuses another load's
    (keys, rank_to_key)."""
    from sherman_tpu import native
    from sherman_tpu.cluster import build_engine, pages_for_keys
    from sherman_tpu.config import LEAF_CAP
    from sherman_tpu.models import batched

    check(native.available(), "native library unavailable (g++ build)")
    pages = pages_for_keys(n_keys, FILL)
    cluster, tree, eng = build_engine(
        nodes, pages // nodes, batch,
        chunk_pages=min(4096, pages // nodes // 4),
        exchange_impl=exchange_impl, gather_impl=gather_impl)
    cfg = cluster.cfg
    per_leaf = max(1, int(LEAF_CAP * FILL))
    salt = SALT
    if keyspace is None:
        while True:
            try:
                keyspace = native.synthetic_keyspace(n_keys, salt)
                break
            except ValueError:
                salt += 1
    keys, rank_to_key = keyspace
    xor = int(np.random.default_rng(seed).integers(1, 1 << 63))
    vals = keys ^ np.uint64(xor)
    stats = batched.bulk_load(tree, keys, vals, fill=FILL)
    eng.attach_router()
    occupancy = [d.allocator.pages_used for d in cluster.directories]
    check(stats["leaves"] * per_leaf >= n_keys, f"bulk load short: {stats}")
    check(all(p > 0 for p in occupancy),
          f"a node holds no pages: {occupancy}")
    return {"cluster": cluster, "tree": tree, "eng": eng, "cfg": cfg,
            "n_keys": n_keys, "batch": batch, "seed": seed, "salt": salt,
            "xor": xor, "keyspace": keyspace, "rank_to_key": rank_to_key,
            "ref": Reference(keys, vals), "stats": stats,
            "occupancy": occupancy}


def zipf_client_keys(ctx: dict, n: int, seed: int) -> np.ndarray:
    from sherman_tpu.workload.zipf import ZipfGen
    ranks = ZipfGen(ctx["n_keys"], THETA, seed=seed).sample(n)
    return ctx["rank_to_key"][ranks]


def phase_search(ctx: dict) -> str:
    q = zipf_client_keys(ctx, ctx["batch"], ctx["seed"] + 11)
    vals, found = ctx["eng"].search_combined(q)
    check_lookup(ctx["ref"], q, vals, found, "search_combined")
    check(bool(np.asarray(found).all()), "a loaded key was not found")
    n_u = np.unique(q).shape[0]
    ctx["n_uniq"] = n_u
    return (f"{q.shape[0]} zipf-{THETA} client keys ({n_u} unique) all "
            "found, values == np.searchsorted model")


def phase_staged(ctx: dict, steps: int = 4) -> str:
    import jax

    from sherman_tpu.workload.device_prep import make_staged_step
    eng, tree, batch = ctx["eng"], ctx["tree"], ctx["batch"]
    # bench.py's unique-row capacity: the measured unique count, 8192-
    # rounded with 2% headroom, plus its 16 K slack for the device PRNG
    dev_b = -(-int(ctx.get("n_uniq", batch) * 1.02) // 8192) * 8192
    dev_b = min(batch, dev_b + 16384)
    step_fn, (new_carry, table_d, rtable_d, rkey_d) = make_staged_step(
        eng, n_keys=ctx["n_keys"], theta=THETA, salt=ctx["salt"],
        batch=batch, dev_b=dev_b, sampler="analytic",
        check_xor=ctx["xor"])
    pool, counters = tree.dsm.pool, tree.dsm.counters
    carry = new_carry()
    for _ in range(steps):
        counters, carry = step_fn(pool, counters, table_d, rtable_d,
                                  rkey_d, carry)
    carry = step_fn.drain(carry)
    jax.block_until_ready(carry)
    # the step donated the counters buffer: hand the live handle back
    tree.dsm.counters = counters
    ok, correct = int(np.asarray(carry[1])), int(np.asarray(carry[2]))
    want = steps * batch * ctx["cfg"].machine_nr
    check(ok == 1, "staged loop: unique-row overflow")
    check(correct == want,
          f"staged loop verified {correct} of {want} ops on device")
    return (f"{steps} staged steps x {batch} ops: {correct} answers "
            f"verified on device (fusion {step_fn.fusion}, sampler "
            f"{step_fn.sampler})")


def writer_engine(ctx: dict, width: int) -> None:
    """Swap in a second BatchedEngine on the same tree, ``width`` rows
    per node, for the write phases (its router is now the tree's)."""
    from sherman_tpu.models import batched
    eng = batched.BatchedEngine(ctx["tree"], batch_per_node=width)
    eng.attach_router()
    ctx["eng"] = eng


def phase_mutate(ctx: dict, ops: int = 65_536) -> str:
    from sherman_tpu.models.validate import check_structure_device
    eng, ref = ctx["eng"], ctx["ref"]
    rng = np.random.default_rng(ctx["seed"] + 21)

    # fresh inserts + updates of live keys, one insert call
    fk, fv = fresh_keys(ref, rng, ops), new_values(rng, ops)
    uk, uv = sample_keys(ref, rng, ops), new_values(rng, ops)
    stats = eng.insert(np.concatenate([fk, uk]), np.concatenate([fv, uv]))
    check(stats["lock_timeouts"] == 0, f"insert lock timeouts: {stats}")
    ref.upsert(fk, fv)
    ref.upsert(uk, uv)
    q = np.concatenate([fk, uk])
    check_lookup(ref, q, *eng.search(q), "search after insert/update")

    # one mixed 50/50 batch: reads see the pre-step snapshot, so the
    # read and write key sets are disjoint
    half = ops // 2
    rk = sample_keys(ref, rng, half)
    wk = sample_keys(ref, rng, half, exclude=rk)
    wv = new_values(rng, half)
    keys = np.concatenate([rk, wk])
    is_read = np.arange(2 * half) < half
    perm = rng.permutation(2 * half)
    keys, is_read = keys[perm], is_read[perm]
    vals = np.concatenate([np.zeros(half, np.uint64), wv])[perm]
    out_v, found, _status = eng.mixed(keys, vals, is_read)
    check_lookup(ref, keys[is_read], np.asarray(out_v)[is_read],
                 np.asarray(found)[is_read], "mixed reads")
    ref.upsert(wk, wv)
    check_lookup(ref, wk, *eng.search(wk), "search after mixed writes")

    # deletes: half just-inserted keys, a quarter loaded keys, a
    # quarter absent
    q4 = ops // 4
    dk = np.concatenate([fk[:2 * q4], sample_keys(ref, rng, q4,
                                                  exclude=fk),
                         fresh_keys(ref, rng, q4)])
    want = ref.contains(dk)
    got = eng.delete(dk)
    check(np.array_equal(np.asarray(got, bool), want),
          "delete found flags differ from the model")
    ref.delete(dk)
    _, f = eng.search(dk)
    check(not np.asarray(f).any(), "a deleted key is still found")

    # batched scans over a few ranges of ~span live keys
    n_ranges, span = 4, 256
    starts = rng.integers(0, len(ref) - span - 1, n_ranges)
    ranges = [(int(ref.keys[i]), int(ref.keys[i + span])) for i in starts]
    for (lo, hi), (k, v) in zip(ranges, eng.range_query_many(ranges)):
        wk_, wv_ = ref.range(lo, hi)
        check(np.array_equal(np.asarray(k, np.uint64), wk_)
              and np.array_equal(np.asarray(v, np.uint64), wv_),
              f"range_query [{lo}, {hi}) differs from the model")

    info = check_structure_device(ctx["tree"])
    check(info["keys"] == len(ref),
          f"validator counts {info['keys']} keys, model {len(ref)}")
    return (f"{ops} inserts + {ops} updates, mixed {half}R/{half}W, "
            f"{dk.shape[0]} deletes, {n_ranges} ranges == model; "
            f"structure valid ({info['keys']} keys, {info['leaves']} "
            "leaves)")


def phase_serve(ctx: dict, widths=(4096, 16384), ops: int = 2048) -> str:
    from sherman_tpu.serve import OP_CLASSES, ServeConfig, ShermanServer
    eng, ref = ctx["eng"], ctx["ref"]
    rng = np.random.default_rng(ctx["seed"] + 31)
    cfg = ServeConfig(widths=tuple(widths),
                      p99_targets_ms={c: 600_000.0 for c in OP_CLASSES})
    srv = ShermanServer(eng, cfg)
    calib = sample_keys(ref, rng, 2 * max(widths))
    calib_v, _ = ref.lookup(calib[:64])
    srv.start(calib_keys=calib, calib_writes=(calib[:64], calib_v),
              calib_delete_keys=fresh_keys(ref, rng, 64))
    n_req = 0
    try:
        for tenant in ("alpha", "beta"):
            q = np.concatenate([sample_keys(ref, rng, ops),
                                fresh_keys(ref, rng, 16)])
            v, f = srv.submit("read", q, tenant=tenant).result(600)
            check_lookup(ref, q, v, f, f"served read ({tenant})")
            ik, iv = fresh_keys(ref, rng, ops), new_values(rng, ops)
            ok = srv.submit("insert", ik, iv, tenant=tenant).result(600)
            check(bool(np.asarray(ok).all()),
                  f"served insert ({tenant}) not acked")
            ref.upsert(ik, iv)
            v, f = srv.submit("read", ik, tenant=tenant).result(600)
            check_lookup(ref, ik, v, f, f"served read-back ({tenant})")
            dk = ik[:ops // 2]
            f = srv.submit("delete", dk, tenant=tenant).result(600)
            check(bool(np.asarray(f).all()),
                  f"served delete ({tenant}) missed keys")
            ref.delete(dk)
            i = int(rng.integers(0, len(ref) - 300))
            rg = [(int(ref.keys[i]), int(ref.keys[i + 256]))]
            (k, v), = srv.submit("scan", ranges=rg,
                                 tenant=tenant).result(600)
            wk, wv = ref.range(*rg[0])
            check(np.array_equal(np.asarray(k, np.uint64), wk)
                  and np.array_equal(np.asarray(v, np.uint64), wv),
                  f"served scan ({tenant}) differs from the model")
            n_req += 5
    finally:
        srv.stop(drain=True)
    return (f"{n_req} requests (read/insert/read-back/delete/scan x 2 "
            "tenants) resolved, answers == model; drained")


def phase_kernels(ctx: dict, rows: int = 262_144) -> str:
    import jax
    import jax.numpy as jnp

    from sherman_tpu import config as C
    from sherman_tpu.ops import bits
    from sherman_tpu.ops import pallas_page as PP
    tree = ctx["tree"]
    pool = tree.dsm.pool
    P = pool.shape[0]
    used = ctx["tree"].cluster.directories[0].allocator.pages_used
    rng = np.random.default_rng(ctx["seed"] + 41)
    addr = rng.integers(0, used, rows).astype(np.int32)
    addr[: rows // 16] = rng.integers(P, 2 * P, rows // 16)  # off-pool
    khi, klo = bits.keys_to_pairs(sample_keys(ctx["ref"], rng, rows))
    active = rng.random(rows) < 0.9

    def same(a, b, what):
        for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
            check(bool(jnp.array_equal(x, y)), f"{what}: pallas != xla")

    got = jax.jit(PP.descent_round)(pool, addr, khi, klo, active)
    want = jax.jit(PP.descent_round_xla)(pool, addr, khi, klo, active)
    same(got, want, "descent_round")
    n_leaf = int(jnp.sum(want[1]))
    pages = np.clip(addr, 0, P - 1)
    same(jax.jit(PP.gather_pages)(pool, pages),
         jax.jit(PP.gather_pages_xla)(pool, pages), "gather_pages")

    # write-back into a copy of the pool's first 2^18 pages (the pool
    # itself stays untouched); distinct pages per row
    wbp = min(1 << 18, P)
    sub = pool[:wbp]
    m = min(rows, wbp)
    page = rng.permutation(wbp)[:m].astype(np.int32)
    slot = rng.integers(0, C.LEAF_CAP, m).astype(np.int32)
    applied = rng.random(m) < 0.75
    lanes = (C.L_VER_W, C.L_KHI_W, C.L_KLO_W, C.L_VHI_W, C.L_VLO_W)
    ent = rng.integers(-2**31, 2**31, (m, len(lanes))).astype(np.int32)
    wb = lambda f: jax.jit(lambda p, *a: f(p, *a, field_w=lanes))(
        sub, page, slot, applied, ent)
    same(wb(PP.writeback), wb(PP.writeback_xla), "writeback")
    return (f"descent_round ({rows} rows, {n_leaf} leaf hits), "
            f"gather_pages ({rows} rows) on the {P}-page pool and "
            f"writeback ({m} rows x {len(lanes)} lanes) on a {wbp}-page "
            "copy: bit-identical to xla")


def release(ctx: dict) -> None:
    """Free a loaded tree's pool on the device now (4.3 GB at 100 M
    keys): two such pools fit beside each other on a v5e, three do not."""
    ctx["tree"].dsm.pool.delete()
    ctx.clear()


def phase_engines(n_keys: int, batch: int, seed: int, keyspace,
                  ops: int = 65_536) -> str:
    """The engine paths DSMConfig(gather_impl="pallas") selects — the
    fused descent, the snapshot gather and write-back of the apply —
    through the engine's own entry points: one bench-width
    search_combined, then one mixed batch (reads, fresh inserts,
    updates) on it and on an "xla" twin loaded with the same keys.
    Answers == model, the twin's read-back == model, pools equal."""
    import jax.numpy as jnp
    pk = phase_load(n_keys, batch, seed, gather_impl="pallas",
                    keyspace=keyspace)
    q = zipf_client_keys(pk, batch, seed + 61)
    vals, found = pk["eng"].search_combined(q)
    check_lookup(pk["ref"], q, vals, found, "pallas search_combined")
    check(bool(np.asarray(found).all()), "a loaded key was not found")
    xk = phase_load(n_keys, batch, seed, keyspace=keyspace)
    ref, rng, q4 = pk["ref"], np.random.default_rng(seed + 63), ops // 4
    rk = sample_keys(ref, rng, 2 * q4)
    wk = np.concatenate([fresh_keys(ref, rng, q4),
                         sample_keys(ref, rng, q4, exclude=rk)])
    wv = new_values(rng, 2 * q4)
    keys = np.concatenate([rk, wk])
    vals = np.concatenate([np.zeros(2 * q4, np.uint64), wv])
    is_read = np.arange(4 * q4) < 2 * q4
    for ctx in (pk, xk):
        impl = ctx["cfg"].gather_impl
        writer_engine(ctx, min(batch, ops))
        out_v, found, _ = ctx["eng"].mixed(keys, vals, is_read)
        check_lookup(ctx["ref"], rk, np.asarray(out_v)[:2 * q4],
                     np.asarray(found)[:2 * q4], f"mixed reads ({impl})")
        ctx["ref"].upsert(wk, wv)
    check_lookup(xk["ref"], wk, *xk["eng"].search(wk), "read-back (xla)")
    check(bool(jnp.array_equal(pk["tree"].dsm.pool, xk["tree"].dsm.pool)),
          "pools differ between gather_impl pallas and xla")
    return (f"gather_impl=pallas engine: search_combined of {batch} zipf "
            f"keys == model; mixed {2 * q4}R/{q4} inserts/{q4} updates "
            f"== model; {pk['tree'].dsm.pool.shape[0]}-page pool "
            "bit-identical to the xla engine's after the same batches")


def phase_routed(ctx: dict, ops: int = 65_536) -> str:
    """The sharded path: routed insert + search batches and one mixed
    batch, each vs the model."""
    eng, ref = ctx["eng"], ctx["ref"]
    rng = np.random.default_rng(ctx["seed"] + 51)
    fk, fv = fresh_keys(ref, rng, ops), new_values(rng, ops)
    uk, uv = sample_keys(ref, rng, ops), new_values(rng, ops)
    stats = eng.insert(np.concatenate([fk, uk]), np.concatenate([fv, uv]))
    check(stats["lock_timeouts"] == 0, f"insert lock timeouts: {stats}")
    ref.upsert(fk, fv)
    ref.upsert(uk, uv)
    q = np.concatenate([fk, uk, zipf_client_keys(ctx, ops, ctx["seed"])])
    check_lookup(ref, q, *eng.search(q), "routed search")
    vals, found = eng.search_combined(q)
    check_lookup(ref, q, vals, found, "routed search_combined")
    half = ops // 2
    rk = sample_keys(ref, rng, half)
    wk = sample_keys(ref, rng, half, exclude=rk)
    wv = new_values(rng, half)
    keys = np.concatenate([rk, wk])
    is_read = np.arange(2 * half) < half
    out_v, found, _ = eng.mixed(keys, np.concatenate(
        [np.zeros(half, np.uint64), wv]), is_read)
    check_lookup(ref, rk, np.asarray(out_v)[:half],
                 np.asarray(found)[:half], "routed mixed reads")
    ref.upsert(wk, wv)
    check_lookup(ref, wk, *eng.search(wk), "search after routed mixed")
    return (f"{2 * ops} routed inserts/updates, {q.shape[0]} routed "
            f"lookups (plain + combined), mixed {half}R/{half}W == model")


def phase_pools_equal(a: dict, b: dict) -> str:
    import jax.numpy as jnp
    pa, pb = a["tree"].dsm.pool, b["tree"].dsm.pool
    check(pa.shape == pb.shape, "pool shapes differ")
    check(bool(jnp.array_equal(pa, pb)),
          "pools differ between exchange_impl xla and pallas")
    return f"{pa.shape[0]}-page pools bit-identical across exchange_impl"


# ---------------------------------------------------------------------------
# Driver.
# ---------------------------------------------------------------------------

def run_phase(name: str, fn, *args, **kw):
    t0 = time.time()
    try:
        out = fn(*args, **kw)
    except Exception:
        print(f"[chip_smoke] {name}: FAIL after {time.time() - t0:.2f} s",
              flush=True)
        raise
    return out, t0


def one_chip(a) -> None:
    ctx, t0 = run_phase("load", phase_load, a.keys, a.batch, a.seed)
    report("load", t0, f"{a.keys} keys bulk-loaded ({ctx['stats']}, "
           f"{ctx['occupancy'][0]} pages used)")
    for name, fn in (("search", phase_search), ("staged", phase_staged),
                     ("mutate", phase_mutate), ("serve", phase_serve),
                     ("kernels", phase_kernels)):
        if name == "mutate":
            writer_engine(ctx, min(a.batch, 65_536))
        what, t0 = run_phase(name, fn, ctx)
        report(name, t0, what)
    keyspace = ctx["keyspace"]
    release(ctx)
    what, t0 = run_phase("engines", phase_engines, a.keys, a.batch,
                         a.seed, keyspace)
    report("engines", t0, what)


def four_chips(a) -> None:
    ctxs = {}
    keyspace = None
    for impl in ("xla", "pallas"):
        ctx, t0 = run_phase(f"load[{impl}]", phase_load, a.keys, a.batch,
                            a.seed, nodes=4, exchange_impl=impl,
                            keyspace=keyspace)
        keyspace = ctx["keyspace"]
        report(f"load[{impl}]", t0,
               f"{a.keys} keys over 4 nodes, pages per node "
               f"{ctx['occupancy']}")
        what, t0 = run_phase(f"routed[{impl}]", phase_routed, ctx)
        report(f"routed[{impl}]", t0, what)
        ctxs[impl] = ctx
    what, t0 = run_phase("pools", phase_pools_equal, ctxs["xla"],
                         ctxs["pallas"])
    report("pools", t0, what)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--keys", type=int, default=100_000_000)
    ap.add_argument("--batch", type=int, default=None,
                    help="client ops per step per node (default: bench's "
                         "4,194,304 on one chip, 65,536 per node on four)")
    ap.add_argument("--seed", type=int, default=0)
    a = ap.parse_args(argv)
    if a.batch is None:
        a.batch = 4_194_304 if a.chips == 1 else 65_536
    try:
        sys.path.insert(0, ROOT)
        from sherman_tpu.utils.compile_cache import setup_compile_cache
        setup_compile_cache()
        t0 = time.time()
        platform, kind, count = phase_device(a.chips)
        report("device", t0, f"platform {platform}, kind {kind!r}, "
               f"{count} device(s)")
        (one_chip if a.chips == 1 else four_chips)(a)
    except Exception:
        traceback.print_exc()
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": kind, "count": count}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
