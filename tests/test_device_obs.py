"""White-box device plane: compile ledger, seal/retrace, rooflines,
memory accountant, and the obs-on/off cost pin.

Fast tier.  Ledger tests use PRIVATE CompileLedger instances (wrapper
cache-size detection needs no monitoring listener), so the process-wide
ledger's listener — attached once, unremovable — cannot cross-pollute
counts; the retrace-event test checks the shared flight-recorder ring
by kind, which other tests do not emit."""

import json
import os
import time

import numpy as np
import pytest

from sherman_tpu import obs
from sherman_tpu.obs import device as dev
from sherman_tpu.obs import recorder as recorder_mod


# -- compile ledger: wrap, seal, retrace --------------------------------------

def test_wrapper_records_compiles_with_signature():
    import jax

    led = dev.CompileLedger()
    f = led.wrap("t.double", jax.jit(lambda x: x * 2))
    out = f(np.arange(8, dtype=np.int32))
    np.testing.assert_array_equal(np.asarray(out),
                                  np.arange(8, dtype=np.int32) * 2)
    (e,) = (x for x in led.entries() if x["label"] == "t.double")
    assert e["compiles"] == 1
    assert list(e["signatures"]) == ["int32[8]"]
    # same shape again: cache hit, no new compile
    f(np.arange(8, dtype=np.int32))
    (e,) = (x for x in led.entries() if x["label"] == "t.double")
    assert e["compiles"] == 1
    # new shape: a second compile, second signature
    f(np.arange(16, dtype=np.int32))
    (e,) = (x for x in led.entries() if x["label"] == "t.double")
    assert e["compiles"] == 2 and "int32[16]" in e["signatures"]


def test_wrap_idempotent_and_transparent():
    import jax

    led = dev.CompileLedger()
    base = jax.jit(lambda x: x + 1)
    w = led.wrap("t.inc", base)
    assert led.wrap("relabel", w) is w  # no history-splitting rewrap
    assert w.unwrapped is base
    assert w.label == "t.inc"
    # attribute delegation: the jit surface stays reachable
    assert callable(w.lower)


def test_seal_retrace_semantics_and_recorder_event():
    """The tentpole pin: post-seal same shapes trip NOTHING; a post-seal
    new shape increments retraces AND lands a compile.retrace flight
    event naming the program."""
    import jax

    led = dev.CompileLedger()
    f = led.wrap("t.sealed", jax.jit(lambda x: x - 1))
    f(np.arange(8, dtype=np.int32))        # warmup compile, pre-seal
    assert led.retraces == 0
    with led.sealed_scope():
        assert led.sealed
        f(np.arange(8, dtype=np.int32))    # warmed shape: no retrace
        assert led.retraces == 0
        f(np.arange(32, dtype=np.int32))   # NEW shape inside the seal
    assert not led.sealed
    assert led.retraces == 1
    (e,) = (x for x in led.entries() if x["label"] == "t.sealed")
    assert e["retraces"] == 1 and e["compiles"] == 2
    evs = [e for e in recorder_mod.get_recorder().events()
           if e["kind"] == "compile.retrace"
           and e.get("fields", {}).get("program") == "t.sealed"]
    assert evs, "retrace must land a compile.retrace flight event"
    assert evs[-1]["fields"]["signature"] == "int32[32]"
    # post-unseal compiles are ordinary again
    f(np.arange(64, dtype=np.int32))
    assert led.retraces == 1


def test_compile_recorded_when_dispatch_raises():
    # a retraced program whose execution then fails is exactly the
    # postmortem the ledger exists for: detection runs in the finally
    class FakeJit:
        def __init__(self):
            self.n = 0

        def _cache_size(self):
            return self.n

        def __call__(self, *a, **k):
            self.n += 1  # "compiled", then the execution dies
            raise RuntimeError("boom")

    led = dev.CompileLedger()
    f = led.wrap("t.raise", FakeJit())
    with pytest.raises(RuntimeError):
        f(np.arange(4, dtype=np.int32))
    (e,) = (x for x in led.entries() if x["label"] == "t.raise")
    assert e["compiles"] == 1
    with led.sealed_scope():
        with pytest.raises(RuntimeError):
            f(np.arange(4, dtype=np.int32))
    assert led.retraces == 1


def test_seal_nests_and_summary_shape():
    led = dev.CompileLedger()
    with led.sealed_scope():
        with led.sealed_scope():
            assert led.sealed
        assert led.sealed  # outer scope still open
    assert not led.sealed
    s = led.summary()
    assert {"programs", "compiles", "compile_ms_total", "retraces",
            "sealed_windows", "entries"} <= set(s)
    assert s["sealed_windows"] == 2


def test_suppress_scope_hides_analysis_compiles():
    import jax

    led = dev.CompileLedger()
    f = led.wrap("t.quiet", jax.jit(lambda x: x * 3))
    with led.sealed_scope():
        with led.suppress():
            f(np.arange(8, dtype=np.int32))  # instrument's own compile
    assert led.retraces == 0
    assert all(e["label"] != "t.quiet" for e in led.entries())


def test_kill_switch_forwards_untracked(monkeypatch):
    import jax

    monkeypatch.setenv(dev.DEVICE_OBS_ENV, "0")
    assert not dev.enabled()
    led = dev.CompileLedger()
    f = led.wrap("t.dark", jax.jit(lambda x: x + 7))
    with led.sealed_scope():
        out = f(np.arange(8, dtype=np.int32))
    np.testing.assert_array_equal(np.asarray(out),
                                  np.arange(8, dtype=np.int32) + 7)
    assert led.retraces == 0 and led.entries() == []


def test_default_ledger_registers_device_collector():
    dev.get_ledger()
    snap = obs.snapshot()
    assert "device.programs" in snap and "device.retraces" in snap
    assert "device.hbm_total_bytes" in snap


# -- cost / memory analysis ----------------------------------------------------

class _TpuLikeLowered:
    """A lowering that, like a TPU's, answers cost analysis with None
    and leaves it to the compiled executable."""

    def __init__(self, low):
        self._low = low

    def cost_analysis(self):
        return None

    def compile(self):
        return self._low.compile()


@pytest.mark.parametrize("tpu_like", [False, True])
def test_program_cost_and_memory_on_cpu(tpu_like):
    import types

    import jax

    jf = jax.jit(lambda x: (x.astype(np.float32) * 2.0).sum())
    f = types.SimpleNamespace(
        lower=lambda *a: _TpuLikeLowered(jf.lower(*a))) if tpu_like else jf
    x = np.arange(1024, dtype=np.int32)
    c = dev.program_cost(f, x)
    assert c["available"] and c["flops"] > 0 and c["bytes"] > 0
    m = dev.program_memory(f, x)
    assert m["available"] and m["argument_bytes"] >= x.nbytes


def test_cost_memory_graceful_degradation():
    # no .lower on the callable: typed unavailable, never a raise
    c = dev.program_cost(lambda x: x, np.arange(4))
    assert c == {"available": False, "reason": c["reason"]}
    assert "AttributeError" in c["reason"]
    m = dev.program_memory(lambda x: x, np.arange(4))
    assert not m["available"] and "reason" in m


def test_ledger_analyze_from_captured_avals():
    import jax

    led = dev.CompileLedger()
    f = led.wrap("t.cost", jax.jit(lambda x: x * 2 + 1))
    f(np.arange(256, dtype=np.int32))
    ana = led.analyze("t.cost", memory=True)
    assert ana["available"] and ana["flops"] > 0
    assert ana["memory"]["available"]
    # analysis must not count as a compile (suppressed AOT path)
    (e,) = (x for x in led.entries() if x["label"] == "t.cost")
    assert e["compiles"] == 1
    # unknown label: typed unavailable
    assert not led.analyze("t.never")["available"]


# -- rooflines ----------------------------------------------------------------

def test_roofline_fractions_with_env_peaks(monkeypatch):
    monkeypatch.setenv("SHERMAN_PEAK_GBPS", "100")     # 100 GB/s roof
    monkeypatch.setenv("SHERMAN_PEAK_TFLOPS", "0.001")  # 1 GF/s roof
    peaks = dev.device_peaks()
    assert peaks["source"] == "env"
    cost = {"available": True, "flops": 1e6, "bytes": 1e9}
    r = dev.roofline(cost, 100.0, peaks)  # 100 ms wall
    assert r["available"]
    assert r["achieved_gbytes_s"] == pytest.approx(10.0)
    # 10 GB/s over a 100 GB/s roof
    assert r["achieved_bytes_frac"] == pytest.approx(0.1)
    # 10 MF/s over a 1 GF/s roof
    assert r["achieved_flops_frac"] == pytest.approx(0.01)
    assert r["bound"] == "bytes"


def test_device_peaks_malformed_env_falls_back(monkeypatch):
    # a typo'd override (chip-queue instructions hand-set these) must
    # not raise at end-of-run receipt build — each field falls back
    # like an unset one, with the bad value flagged in source
    monkeypatch.setenv("SHERMAN_PEAK_GBPS", "819GB")
    peaks = dev.device_peaks()
    assert "bad-env:SHERMAN_PEAK_GBPS" in peaks["source"]
    # this CPU backend has no table entry: peaks stay None, no crash
    assert peaks["bytes_per_s"] is None or peaks["bytes_per_s"] > 0


def test_device_peaks_env_fields_resolve_independently(monkeypatch):
    # one malformed field must not discard the other valid override
    monkeypatch.setenv("SHERMAN_PEAK_GBPS", "819GB")
    monkeypatch.setenv("SHERMAN_PEAK_TFLOPS", "197")
    peaks = dev.device_peaks()
    assert peaks["flops_per_s"] == pytest.approx(197e12)
    assert "bad-env:SHERMAN_PEAK_GBPS" in peaks["source"]
    assert "env" in peaks["source"].split(";")


@pytest.mark.parametrize("kind,gbps", [("TPU v5 lite", 819), ("TPU v6 lite", 1640),
                                       ("TPU v4", 1228)])
def test_device_peaks_tpu_table_ignores_env(monkeypatch, kind, gbps):
    # on a TPU the table is the only source: an env override never
    # stands in for a known part's published peak
    monkeypatch.setenv("SHERMAN_PEAK_GBPS", "1")
    peaks = dev.device_peaks("tpu", kind)
    assert peaks["bytes_per_s"] == pytest.approx(gbps * 1e9)
    assert peaks["source"] == f"device_kind:{kind.lower()}"


def test_device_peaks_unknown_tpu_kind_raises(monkeypatch):
    monkeypatch.setenv("SHERMAN_PEAK_GBPS", "100")
    monkeypatch.setenv("SHERMAN_PEAK_TFLOPS", "100")
    with pytest.raises(dev.UnknownDeviceKindError) as ei:
        dev.device_peaks("tpu", "TPU v99 prototype")
    assert "_KNOWN_PEAKS" in str(ei.value)


def test_roofline_unknown_backend_omits_fractions():
    cost = {"available": True, "flops": 1e6, "bytes": 1e9}
    r = dev.roofline(cost, 10.0,
                     {"bytes_per_s": None, "flops_per_s": None})
    assert r["available"] and "achieved_gbytes_s" in r
    assert "achieved_bytes_frac" not in r and "bound" not in r


def test_roofline_below_resolution_flags_and_omits_fracs():
    cost = {"available": True, "flops": 1e3, "bytes": 1e3}
    r = dev.roofline(cost, 0.0001,
                     {"bytes_per_s": 1e9, "flops_per_s": 1e9})
    assert r["wall_below_resolution"]
    assert "achieved_bytes_frac" not in r


def test_roofline_unavailable_cost_passthrough():
    r = dev.roofline({"available": False, "reason": "nope"}, 5.0)
    assert not r["available"] and r["reason"] == "nope"
    assert r["wall_ms"] == 5.0


def test_rooflines_joins_phase_walls_skipping_unlabeled():
    import jax

    led = dev.CompileLedger()
    f = led.wrap("t.phase", jax.jit(lambda x: x * 2))
    f(np.arange(64, dtype=np.int32))
    phase_ms = {"serve": 3.0, "wall_ms": 9.9, "overlap_efficiency": 0.4}
    labels = {"serve": "t.phase"}  # overlap-receipt keys: no label
    out = dev.rooflines(phase_ms, labels, ledger=led,
                        peaks={"bytes_per_s": 1e9, "flops_per_s": 1e9})
    assert set(out) == {"serve"}
    assert out["serve"]["program"] == "t.phase"
    assert out["serve"]["available"]


# -- memory accountant --------------------------------------------------------

def test_accountant_gauges_watermark_and_dead_source():
    acct = dev.MemoryAccountant()
    live = {"n": 1000}
    acct.register("pool", lambda: live["n"])
    acct.register("journal", lambda: 77, kind="host")
    g = acct.gauges()
    assert g["hbm_pool_bytes"] == 1000 and g["host_journal_bytes"] == 77
    assert g["hbm_total_bytes"] == 1000  # host sources don't sum as hbm
    assert g["hbm_peak_bytes"] == 1000
    live["n"] = 5000
    assert acct.gauges()["hbm_peak_bytes"] == 5000
    live["n"] = 10  # shrink: watermark holds
    g = acct.gauges()
    assert g["hbm_total_bytes"] == 10 and g["hbm_peak_bytes"] == 5000

    def boom():
        raise RuntimeError("donated mid-step")

    acct.register("pool", boom)  # re-register replaces
    assert acct.gauges()["hbm_pool_bytes"] == 0  # raises -> 0, no crash


def test_dsm_registers_hbm_sources(eight_devices):
    """Building a DSM must surface its pool bytes through the device
    collector (the weakref-bound accountant sources in parallel/dsm)."""
    from sherman_tpu.cluster import Cluster
    from sherman_tpu.config import DSMConfig

    cl = Cluster(DSMConfig(machine_nr=1, pages_per_node=256,
                           locks_per_node=64, step_capacity=64,
                           chunk_pages=16))
    snap = obs.snapshot()
    assert snap["device.hbm_pool_bytes"] == cl.dsm.pool.nbytes
    assert snap["device.hbm_total_bytes"] >= cl.dsm.pool.nbytes


# -- the device-obs cost pin (< 2% staged-step wall) --------------------------

def test_staged_step_device_obs_cost_under_two_percent(eight_devices,
                                                       monkeypatch):
    """Device-obs on/off staged wall delta pinned < 2% (mirrors
    test_slo's pin): per dispatch the wrapper pays one env check, a
    thread-local push/pop and a jit-cache-size read — nothing that can
    show up against a compiled step.  Same shapes as test_slo's pin so
    the jit cache is shared."""
    from sherman_tpu.cluster import Cluster
    from sherman_tpu.config import DSMConfig
    from sherman_tpu.models import batched
    from sherman_tpu.models.btree import Tree
    from sherman_tpu.ops import bits
    from sherman_tpu.workload.device_prep import make_staged_step
    import jax

    salt = 0x5E17_AB1E_5A17
    n_keys, batch, S = 20_000, 2048, 20
    cfg = DSMConfig(machine_nr=1, pages_per_node=2048, locks_per_node=512,
                    step_capacity=batch, chunk_pages=32)
    tree = Tree(Cluster(cfg))
    eng = batched.BatchedEngine(tree, batch_per_node=batch)
    ranks = np.arange(n_keys, dtype=np.uint64)
    keys = bits.mix64_np(ranks ^ np.uint64(salt))
    order = np.argsort(keys)
    batched.bulk_load(tree, keys[order],
                      (keys ^ np.uint64(0xDEADBEEF))[order], fill=0.8)
    eng.attach_router()
    step, (new_carry, tb, rt, rk) = make_staged_step(
        eng, n_keys=n_keys, theta=0.99, salt=salt, batch=batch,
        dev_b=batch, log2_bins=16, fusion="aligned")

    def wall(observe: bool) -> float:
        monkeypatch.setenv(dev.DEVICE_OBS_ENV, "1" if observe else "0")
        carry = new_carry()
        counters = eng.dsm.counters
        t0 = time.perf_counter()
        for _ in range(S):
            counters, carry = step(eng.dsm.pool, counters, tb, rt, rk,
                                   carry)
        carry = step.drain(carry)
        jax.block_until_ready(carry)
        dt = time.perf_counter() - t0
        eng.dsm.counters = counters
        return dt

    wall(True)  # warm: compiles + first-dispatch cost stay out
    # min-of-N interleaved pairs; whole-A/B retry on a noise spike (the
    # same measured-retry shape test_slo's pin uses — a busy CI host
    # must not fail a claim about wrapper cost)
    for attempt in range(3):
        on, off = [], []
        for _ in range(3):
            on.append(wall(True))
            off.append(wall(False))
        w_on, w_off = min(on), min(off)
        if w_on <= w_off * 1.02:
            break
    assert w_on <= w_off * 1.02, \
        f"device-obs cost {(w_on / w_off - 1) * 100:.2f}% > 2% " \
        f"(on {w_on * 1e3:.1f} ms vs off {w_off * 1e3:.1f} ms)"


# -- staged factories expose the roofline join keys ---------------------------

def test_staged_phase_labels_cover_programs(eight_devices):
    """step.phase_labels must name a ledger label for every program in
    dispatch order (the bench roofline join contract) — reuses the cost
    pin's compiled shapes."""
    from sherman_tpu.cluster import Cluster
    from sherman_tpu.config import DSMConfig
    from sherman_tpu.models import batched
    from sherman_tpu.models.btree import Tree
    from sherman_tpu.ops import bits
    from sherman_tpu.workload.device_prep import make_staged_step

    salt = 0x5E17_AB1E_5A17
    n_keys, batch = 20_000, 2048
    cfg = DSMConfig(machine_nr=1, pages_per_node=2048, locks_per_node=512,
                    step_capacity=batch, chunk_pages=32)
    tree = Tree(Cluster(cfg))
    eng = batched.BatchedEngine(tree, batch_per_node=batch)
    ranks = np.arange(n_keys, dtype=np.uint64)
    keys = bits.mix64_np(ranks ^ np.uint64(salt))
    order = np.argsort(keys)
    batched.bulk_load(tree, keys[order],
                      (keys ^ np.uint64(0xDEADBEEF))[order], fill=0.8)
    eng.attach_router()
    step, _ = make_staged_step(
        eng, n_keys=n_keys, theta=0.99, salt=salt, batch=batch,
        dev_b=batch, log2_bins=16, fusion="aligned")
    assert set(step.phase_labels) == set(step.programs)
    assert step.phase_labels["serve_fanout"] == "engine.search_fanout"
    assert step.phase_labels["prep"] == "staged.prep"
    # every wrapped program keeps its identity through the wrapper
    assert step.programs["serve_fanout"] is eng._get_search_fanout(
        eng._iters())
    # the front door's packed entry: a program of its own in the ledger,
    # whose HLO module carries the serve's name (``jit_kernel``), the
    # name the benchmark finds the serve's device time by
    import jax
    serve = eng._get_search_fanout(eng._iters())
    packed = eng._get_search_fanout_packed(eng._iters())
    assert packed is not serve
    assert packed is eng._get_search_fanout_packed(eng._iters())
    assert packed.label == "engine.search_fanout_packed"
    module = "jit_" + serve.unwrapped.__name__
    assert module == "jit_kernel"
    arg = jax.ShapeDtypeStruct((batch, 5), np.int32,
                               sharding=eng.dsm.shard)
    text = packed.lower(eng.dsm.pool, eng.dsm.counters, arg,
                        np.int32(0)).as_text()
    assert text.startswith(f"module @{module} ")
