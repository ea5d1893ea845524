"""SLO telemetry plane: per-op-class trackers, flight recorder,
Prometheus exposition, perf gate, and the obs-cost pin.

The fast tier of the observability PR: everything here is either pure
host code (trackers, recorder, exposition, perfgate) or reuses compiled
step shapes other fast-tier tests already pay for (the engine-wiring
and flight-drill tests mirror test_recovery/test_device_prep configs so
the jit cache is shared)."""

import json
import os
import threading
import time
import urllib.request

import numpy as np
import pytest

from sherman_tpu import obs
from sherman_tpu.obs import export as obs_export
from sherman_tpu.obs import recorder as FR
from sherman_tpu.obs import slo as SLO


# -- LatencyTracker -----------------------------------------------------------

def test_latency_tracker_percentiles_close_to_exact():
    t = SLO.LatencyTracker()
    rng = np.random.default_rng(3)
    vals = rng.lognormal(mean=np.log(5e-3), sigma=0.7, size=20_000)
    for v in vals:
        t.record(float(v))
    for q in (50, 99, 99.9):
        est = t.percentile_ms(q)
        true = float(np.percentile(vals, q)) * 1e3
        # 8 sub-buckets per octave bound the bucket error at 12.5%;
        # rank interpolation lands well inside it
        assert abs(est / true - 1) < 0.125, (q, est, true)
    snap = t.snapshot()
    assert snap["count"] == 20_000
    assert snap["min_ms"] <= snap["p50_ms"] <= snap["p99_ms"] \
        <= snap["p999_ms"] <= snap["max_ms"]


def test_latency_tracker_weighted_and_merge():
    a, b = SLO.LatencyTracker(), SLO.LatencyTracker()
    a.record(0.010, n=90)   # 90 ops saw a 10 ms batch wall
    b.record(0.100, n=10)   # 10 ops saw a 100 ms wall
    a.merge(b)
    assert a.count == 100
    assert abs(a.percentile_ms(50) / 10 - 1) < 0.15
    assert a.percentile_ms(99) > 80
    # clamped into [min, max]: the bucket upper bound cannot overshoot
    assert a.percentile_ms(100) <= 100.0 + 1e-9
    assert a.percentile_ms(0.1) >= 10.0 - 1e-9


def test_latency_tracker_bucket_roundtrip():
    # every bucket's bounds invert its index (the exposition relies on
    # monotone bucket edges)
    for v in (0, 1, 7, 8, 9, 255, 1 << 20, (1 << 40) + 12345):
        idx = SLO.LatencyTracker._bucket(v)
        lo, hi = SLO.LatencyTracker._bucket_bounds(idx)
        assert lo <= v < hi, (v, idx, lo, hi)


# -- WindowedRate -------------------------------------------------------------

def test_windowed_rate_slides_and_expires():
    r = SLO.WindowedRate(window_s=10.0, granules=10)
    for s in range(5):
        r.add(100, now=100.0 + s)
    # 500 ops over a 5 s partial window
    assert abs(r.rate(now=105.0) - 100.0) < 25
    assert r.total(now=105.0) == 500
    # ... fully expired once the window slides past them
    assert r.total(now=120.0) == 0
    r.add(50, now=120.5)
    assert r.total(now=121.0) == 50


def test_windowed_rate_sub_granule_burst_not_diluted():
    # A long-window tracker (latency_bench uses window_s=3600 so its
    # percentile generations never rotate mid-run) queried after a
    # burst much shorter than one granule must divide by the REAL
    # elapsed span, not the 180 s granule width — else the published
    # ops_s is under-reported ~granule/elapsed-fold.
    r = SLO.WindowedRate(window_s=3600.0, granules=20)
    for s in range(6):
        r.add(1_000_000, now=1000.0 + s)
    assert abs(r.rate(now=1005.0) / 1.2e6 - 1) < 0.05
    # degenerate zero-elapsed query stays finite
    r2 = SLO.WindowedRate(window_s=3600.0, granules=20)
    r2.add(100, now=50.0)
    assert 0 < r2.rate(now=50.0) < float("inf")


# -- SloTracker ---------------------------------------------------------------

def test_slo_tracker_batch_attribution_and_window():
    st = SLO.SloTracker(window_s=10.0, clock=lambda: 0.0)
    # 4 batches of 1000 ops at a 20 ms wall each, observed as a window
    st.observe("read", 4000, 0.080, batches=4, now=1.0)
    st.observe("insert", 100, 0.050, batches=1, now=1.5)
    w = st.window(now=2.0)
    assert set(w) == {"read", "insert"}
    # amortized per-op latency = the per-batch wall
    assert abs(w["read"]["p50_ms"] / 20 - 1) < 0.15
    assert abs(w["insert"]["p50_ms"] / 50 - 1) < 0.15
    assert w["read"]["window_ops"] == 4000
    assert w["read"]["ops_total"] == 4000
    assert w["read"]["batches_total"] == 4
    assert w["read"]["ops_s"] > 0
    for k in ("p50_ms", "p99_ms", "p999_ms"):
        assert k in w["read"]


def test_slo_tracker_two_generation_rotation():
    now = [0.0]
    st = SLO.SloTracker(window_s=1.0, clock=lambda: now[0])
    st.observe("read", 100, 0.010, now=0.5)
    # rotate once: the sample survives in the previous generation
    st.observe("read", 100, 0.010, now=1.6)
    assert st.window(now=1.7)["read"]["window_ops"] == 200
    # rotate twice more with nothing new: the old samples age out
    assert st.window(now=2.8)["read"]["window_ops"] == 100
    assert st.window(now=4.5)["read"]["window_ops"] == 0


def test_slo_rotation_single_swap_under_race():
    # Two contenders both past the due-check must rotate ONCE: a double
    # swap would shunt the just-filled tracker through prev and publish
    # a near-empty window.  Park both behind the tracker lock so they
    # attempt the swap back-to-back (the worst interleave of an
    # observe() racing a scrape-thread window() at the boundary).
    st = SLO.SloTracker(window_s=1.0, clock=lambda: 0.0)
    st.observe("read", 100, 0.010, now=0.5)
    cs = st._classes["read"]
    filled = cs.cur
    st._lock.acquire()
    ts = [threading.Thread(target=cs.rotate_if_due,
                           args=(1.0, 2.0, st._lock)) for _ in range(2)]
    for t in ts:
        t.start()
    time.sleep(0.05)  # both pass the outer due-check and park
    st._lock.release()
    for t in ts:
        t.join()
    assert cs.prev is filled, "second contender re-rotated the window"
    assert cs.cur.count == 0
    assert st.window(now=2.1)["read"]["window_ops"] == 100


def test_default_tracker_registers_slo_collector():
    SLO.get_slo().reset()
    obs.observe("read", 1000, 0.005)
    snap = obs.snapshot()
    assert snap["slo.read.ops_total"] >= 1000
    assert snap["slo.read.p50_ms"] > 0


def test_slo_env_kill_switch(monkeypatch):
    SLO.get_slo().reset()
    monkeypatch.setenv("SHERMAN_SLO", "0")
    obs.observe("read", 1000, 0.005)
    obs.observe_op("read", 0.005)
    assert "read" not in SLO.slo_window()
    monkeypatch.setenv("SHERMAN_SLO", "1")
    obs.observe("read", 10, 0.005)
    assert SLO.slo_window()["read"]["ops_total"] == 10
    SLO.get_slo().reset()


# -- engine wiring ------------------------------------------------------------

def test_engine_ops_attributed_to_classes(eight_devices):
    """search/insert/delete/mixed/scan walls land in their SLO classes
    (the per-op-class accounting the front door consumes)."""
    from sherman_tpu.cluster import Cluster
    from sherman_tpu.config import DSMConfig
    from sherman_tpu.models import batched
    from sherman_tpu.models.btree import Tree

    SLO.get_slo().reset()
    cfg = DSMConfig(machine_nr=2, pages_per_node=256, locks_per_node=128,
                    step_capacity=256)
    tree = Tree(Cluster(cfg))
    eng = batched.BatchedEngine(tree, batch_per_node=64)
    keys = np.arange(1, 65, dtype=np.uint64)
    eng.insert(keys, keys + 1)
    eng.search(keys)
    eng.mixed(keys[:16], keys[:16], np.arange(16) % 2 == 0)
    eng.range_query(1, 10)
    eng.delete(keys[:8])
    w = SLO.slo_window()
    assert w["insert"]["ops_total"] >= 64
    assert w["read"]["ops_total"] >= 64
    assert w["mixed"]["ops_total"] == 16
    assert w["scan"]["ops_total"] == 1
    assert w["delete"]["ops_total"] == 8
    for cls in ("read", "insert", "delete", "mixed", "scan"):
        assert w[cls]["p99_ms"] > 0
    SLO.get_slo().reset()


# -- flight recorder ----------------------------------------------------------

def test_flight_recorder_ring_bounds_and_order():
    r = FR.FlightRecorder(capacity=4)
    for i in range(10):
        r.record("e", i=i)
    evs = r.events()
    assert len(evs) == 4
    assert [e["fields"]["i"] for e in evs] == [6, 7, 8, 9]
    assert evs[0]["seq"] < evs[-1]["seq"]  # global order survives eviction
    assert r.dropped == 6


def test_flight_recorder_dump_bundle(tmp_path):
    r = FR.FlightRecorder()
    r.record("chaos.inject", fault="torn_page")
    r.record("engine.degraded_enter", reason="test")
    path = r.dump("unit", str(tmp_path))
    with open(path) as f:
        doc = json.load(f)
    od = doc["otherData"]
    assert od["reason"] == "unit"
    kinds = [e["kind"] for e in od["flight_events"]]
    assert kinds == ["chaos.inject", "engine.degraded_enter"]
    assert "metrics" in od and "traceEvents" in doc
    jl = path.replace(".json", ".events.jsonl")
    lines = [json.loads(ln) for ln in open(jl)]
    assert [ln["kind"] for ln in lines] == kinds


def test_flight_recorder_auto_dump_env_gated_and_debounced(
        tmp_path, monkeypatch):
    r = FR.FlightRecorder(min_dump_interval_s=60.0)
    r.record("x")
    monkeypatch.delenv(FR.BLACKBOX_ENV, raising=False)
    assert r.auto_dump("nope") is None  # env unset: never writes
    monkeypatch.setenv(FR.BLACKBOX_ENV, str(tmp_path))
    p1 = r.auto_dump("first")
    assert p1 and os.path.exists(p1)
    assert r.auto_dump("debounced") is None     # inside the window
    p3 = r.auto_dump("forced", force=True)      # watchdog path
    assert p3 and p3 != p1


def test_span_closes_feed_the_recorder():
    rec = FR.get_recorder()
    rec.clear()
    with obs.span("slo_test_phase"):
        pass
    evs = [e for e in rec.events() if e["kind"] == "span"
           and e["fields"]["name"] == "slo_test_phase"]
    assert len(evs) == 1
    assert evs[0]["fields"]["dur_ms"] >= 0


def test_degraded_transition_is_a_flight_event(eight_devices, tmp_path,
                                               monkeypatch):
    """Degraded entry records the transition, auto-dumps the bundle
    (env-gated), and the typed raise records its own event."""
    from sherman_tpu.cluster import Cluster
    from sherman_tpu.config import DSMConfig
    from sherman_tpu.models import batched
    from sherman_tpu.models.btree import Tree

    monkeypatch.setenv(FR.BLACKBOX_ENV, str(tmp_path / "bb"))
    rec = FR.get_recorder()
    rec.clear()
    cfg = DSMConfig(machine_nr=2, pages_per_node=64, locks_per_node=32,
                    step_capacity=32)
    eng = batched.BatchedEngine(Tree(Cluster(cfg)), batch_per_node=16)
    eng.enter_degraded("unit damage")
    with pytest.raises(batched.DegradedError):
        eng.insert(np.asarray([5], np.uint64), np.asarray([6], np.uint64))
    eng.exit_degraded()
    kinds = [e["kind"] for e in rec.events()]
    i_enter = kinds.index("engine.degraded_enter")
    i_typed = kinds.index("engine.typed_error")
    i_exit = kinds.index("engine.degraded_exit")
    assert i_enter < i_typed < i_exit
    dumps = [f for f in os.listdir(tmp_path / "bb")
             if f.endswith(".json") and not f.endswith(".events.jsonl")]
    assert dumps, "degraded entry did not auto-dump the bundle"


# -- the black-box drill (inject -> degrade -> repair, in order) --------------

def test_flight_drill_inject_degrade_repair_in_order(eight_devices,
                                                     tmp_path):
    """The acceptance drill: corruption -> scrub degrade -> targeted
    repair, and the black box shows the injected fault, the degraded
    transition and the repair events IN ORDER.  Mirrors
    test_recovery.test_targeted_repair_exits_degraded's shapes so the
    compiled steps come from the shared jit cache."""
    from sherman_tpu import chaos as CH
    from sherman_tpu.cluster import Cluster
    from sherman_tpu.config import DSMConfig, TreeConfig
    from sherman_tpu.models import batched
    from sherman_tpu.models.btree import Tree
    from sherman_tpu.models.scrub import Scrubber
    from sherman_tpu.recovery import RecoveryPlane

    cfg = DSMConfig(machine_nr=4, pages_per_node=1024, locks_per_node=256,
                    step_capacity=256, chunk_pages=64)
    cluster = Cluster(cfg)
    tree = Tree(cluster)
    eng = batched.BatchedEngine(
        tree, batch_per_node=128,
        tcfg=TreeConfig(sibling_chase_budget=1, lock_retry_rounds=2))
    rng = np.random.default_rng(5)
    keys = np.unique(rng.integers(1, 1 << 56, 880,
                                  dtype=np.uint64))[:800]
    batched.bulk_load(tree, keys, keys ^ np.uint64(0xABCD))
    eng.attach_router()
    plane = RecoveryPlane(cluster, tree, eng, str(tmp_path / "r"))
    plane.checkpoint_base()

    rec = FR.get_recorder()
    rec.clear()
    victim = int(tree._descend(int(keys[400]))[0])
    plan = CH.FaultPlan([
        CH.Fault(kind="torn_page", step=0, addr=victim),
        CH.Fault(kind="flip_entry_ver", step=0, addr=victim, slot=1),
    ])
    cluster.dsm.install_chaos(plan)
    cluster.dsm.read_word(0, 0)
    cluster.dsm.install_chaos(None)
    scr = Scrubber(eng, interval=1)
    res = scr.scrub()
    assert res["violations"] >= 1 and eng.degraded
    rep = plane.targeted_repair(scr)
    assert rep["pages"] >= 1 and not eng.degraded
    plane.close()

    dump = rec.dump("flight_drill", str(tmp_path / "bb"))
    with open(dump) as f:
        evs = json.load(f)["otherData"]["flight_events"]
    seq = {k: next((e["seq"] for e in evs if e["kind"] == k), None)
           for k in ("chaos.inject", "scrub.violation",
                     "engine.degraded_enter",
                     "recovery.targeted_repair_begin",
                     "engine.degraded_exit", "recovery.targeted_repair")}
    assert None not in seq.values(), seq
    assert seq["chaos.inject"] < seq["scrub.violation"] \
        < seq["engine.degraded_enter"] \
        < seq["recovery.targeted_repair_begin"] \
        < seq["engine.degraded_exit"] \
        < seq["recovery.targeted_repair"], seq
    injected = [e for e in evs if e["kind"] == "chaos.inject"]
    assert {e["fields"]["fault"] for e in injected} \
        == {"torn_page", "flip_entry_ver"}


# -- Prometheus exposition ----------------------------------------------------

def test_prometheus_text_format():
    reg = obs.MetricsRegistry()
    reg.counter("a.ops").inc(3)
    reg.gauge("b.depth").set(1.5)
    h = reg.histogram("c.lat_ms")
    for v in (1, 2, 50):
        h.record(v)
    reg.register_collector("dsm", lambda: {"read_ops": 7})
    text = obs_export.prometheus_text(reg)
    lines = text.strip().splitlines()
    assert "# TYPE sherman_a_ops_total counter" in lines
    assert "sherman_a_ops_total 3" in lines
    assert "sherman_b_depth 1.5" in lines
    assert "# TYPE sherman_c_lat_ms summary" in lines
    assert "sherman_c_lat_ms_count 3" in lines
    assert "sherman_dsm_read_ops 7" in lines
    for ln in lines:
        if ln.startswith("#"):
            continue
        name, val = ln.rsplit(" ", 1)
        float(val)  # every sample parses as a number
        assert " " not in name.split("{")[0]
        assert "." not in name.split("{")[0]  # dots sanitized


def test_write_prometheus_atomic(tmp_path):
    reg = obs.MetricsRegistry()
    reg.counter("x").inc()
    p = str(tmp_path / "metrics.prom")
    obs_export.write_prometheus(p, reg)
    assert "sherman_x_total 1" in open(p).read()
    assert not [f for f in os.listdir(tmp_path) if ".tmp" in f]


def test_periodic_exporter_prom_mode(tmp_path):
    reg = obs.MetricsRegistry()
    reg.counter("ticks").inc(2)
    p = str(tmp_path / "m.prom")
    ex = obs_export.PeriodicExporter(p, interval_s=30.0, reg=reg,
                                     fmt="prom").start()
    ex.stop()  # the final write covers the no-tick-elapsed case
    assert "sherman_ticks_total 2" in open(p).read()


def test_metrics_http_endpoint():
    reg = obs.MetricsRegistry()
    reg.counter("served").inc(5)
    with obs_export.MetricsServer(port=0, reg=reg) as srv:
        body = urllib.request.urlopen(
            f"http://127.0.0.1:{srv.port}/metrics", timeout=10
        ).read().decode()
        assert "sherman_served_total 5" in body
        with pytest.raises(urllib.error.HTTPError):
            urllib.request.urlopen(
                f"http://127.0.0.1:{srv.port}/nope", timeout=10)


def test_maybe_serve_http_env_gate(monkeypatch):
    monkeypatch.delenv(obs_export.METRICS_PORT_ENV, raising=False)
    assert obs_export.maybe_serve_http() is None
    monkeypatch.setenv(obs_export.METRICS_PORT_ENV, "0")
    assert obs_export.maybe_serve_http() is None
    monkeypatch.setenv(obs_export.METRICS_PORT_ENV, "bogus")
    with pytest.raises(ValueError):
        obs_export.maybe_serve_http()


# -- perfgate -----------------------------------------------------------------

def _repo_root() -> str:
    return os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# synthetic, schema-identical closed-loop receipts (r02-r05): the gate's
# own pins, independent of any measured record
_DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                     "perfgate")
_R05 = os.path.join(_DATA, "BENCH_r05.json")


def _perfgate():
    import importlib
    import sys
    sys.path.insert(0, os.path.join(_repo_root(), "tools"))
    return importlib.import_module("perfgate")


def test_perfgate_passes_its_own_r05():
    pg = _perfgate()
    rc = pg.main(["--receipt", _R05, "--repo", _DATA])
    assert rc == 0


def test_perfgate_flags_synthetic_regression(tmp_path, capsys):
    pg = _perfgate()
    cand = pg.load_receipt(_R05)
    cand.pop("_round", None)  # a fresh receipt gates on the full history
    for k in ("value", "sustained_ops_s", "sus_mixed_ops_s"):
        cand[k] = round(cand[k] * 0.8)  # the -20% acceptance case
    p = str(tmp_path / "degraded.json")
    json.dump(cand, open(p, "w"))
    assert pg.main(["--receipt", p, "--repo", _DATA]) == 1
    res = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert not res["ok"]
    assert not res["metrics"]["sustained_ops_s"]["ok"]
    assert res["metrics"]["sustained_ops_s"]["baseline_round"] == 5


def test_perfgate_noise_sized_wiggle_passes(tmp_path):
    # a calibrated-spread wiggle (33.8 vs 32.2 = ~5%) must NOT trip
    # the gate: same-build noise is not a regression
    pg = _perfgate()
    cand = pg.load_receipt(_R05)
    cand.pop("_round", None)
    for k in ("value", "sustained_ops_s", "sus_mixed_ops_s"):
        cand[k] = round(cand[k] * (32.2 / 33.8))
    p = str(tmp_path / "wiggle.json")
    json.dump(cand, open(p, "w"))
    assert pg.main(["--receipt", p, "--repo", _DATA]) == 0


def test_perfgate_incomparable_receipt_exits_2(tmp_path):
    pg = _perfgate()
    p = str(tmp_path / "other.json")
    json.dump({"value": 1, "keys": 42, "batch": 7, "p99_ms": 1.0}, open(p, "w"))
    assert pg.main(["--receipt", p, "--repo", _DATA]) == 2


def test_perfgate_value_config_change_is_incomparable(tmp_path,
                                                      capsys):
    """Value-config comparability rule (PR 14): a receipt whose
    config.value_bytes/value_dist/value_heap differ from a round's
    never gates against it in EITHER direction — a heap-on capture
    with halved throughput SKIPS, and an inline capture keeps gating
    against the inline trajectory (missing fields = the pre-heap
    8-byte fixed inline fact)."""
    pg = _perfgate()
    cand = pg.load_receipt(_R05)
    cand.pop("_round", None)
    cand.setdefault("config", {})
    cand["config"].update({"value_bytes": 252, "value_dist": "fixed",
                           "value_heap": True})
    for k in ("value", "sustained_ops_s", "sus_mixed_ops_s"):
        cand[k] = round(cand[k] * 0.5)
    p = str(tmp_path / "heapcfg.json")
    json.dump(cand, open(p, "w"))
    assert pg.main(["--receipt", p, "--repo", _DATA]) == 2  # nothing comparable at all
    # direction 2: the same halved numbers back at the inline config
    # gate red against the inline trajectory
    cand["config"].update({"value_bytes": 8, "value_heap": False})
    json.dump(cand, open(p, "w"))
    assert pg.main(["--receipt", p, "--repo", _DATA]) == 1
    # explicit inline fields match the field-less history exactly
    cand2 = pg.load_receipt(_R05)
    cand2.pop("_round", None)
    cand2.setdefault("config", {})
    cand2["config"].update({"value_bytes": 8, "value_dist": "fixed",
                            "value_heap": False})
    json.dump(cand2, open(p, "w"))
    assert pg.main(["--receipt", p, "--repo", _DATA]) == 0


def test_perfgate_node_count_change_is_incomparable(tmp_path, capsys):
    """Elastic-reshard comparability rule: a receipt captured at a
    different node count never gates against the fixed-shape
    trajectory — even a halved sustained number SKIPS (the per-node
    workload changed wholesale).  A missing ``nodes`` field means the
    pre-field machine_nr=1 bench, so 1-node receipts keep gating."""
    pg = _perfgate()
    cand = pg.load_receipt(_R05)
    cand.pop("_round", None)
    cand["nodes"] = 6  # a post-reshard capture at the grown shape
    for k in ("value", "sustained_ops_s", "sus_mixed_ops_s"):
        cand[k] = round(cand[k] * 0.5)
    p = str(tmp_path / "resharded.json")
    json.dump(cand, open(p, "w"))
    assert pg.main(["--receipt", p, "--repo", _DATA]) == 2  # nothing comparable at all
    # same numbers at the trajectory's own shape: a real regression
    cand["nodes"] = 1
    json.dump(cand, open(p, "w"))
    assert pg.main(["--receipt", p, "--repo", _DATA]) == 1
    # and a reshard-drill receipt is not a bench receipt: exits 2
    drill = {"metric": "reshard_drill", "ok": True, "lost_acks": 0,
             "rpo_ops": 0, "nodes": 4, "target_nodes": 6}
    json.dump(drill, open(p, "w"))
    assert pg.main(["--receipt", p, "--repo", _DATA]) == 2


def test_perfgate_cache_on_never_gates_against_cache_off(tmp_path,
                                                         capsys):
    """Round-10 comparability rule: the hot-key `cache` block is
    config metadata — a cache-ON receipt's sustained_ops_s (most ops
    never descend) must SKIP, not gate, against the cache-off
    trajectory, even when the number would otherwise read as a
    regression; the symmetric throughput metrics still gate."""
    pg = _perfgate()
    cand = pg.load_receipt(_R05)
    cand.pop("_round", None)
    cand["cache"] = {"enabled": True, "slots": 65536,
                     "hit_ratio": 0.79, "hit_ratio_pred": 0.79}
    cand["sustained_ops_s"] = round(cand["sustained_ops_s"] * 0.5)
    p = str(tmp_path / "cache_on.json")
    json.dump(cand, open(p, "w"))
    assert pg.main(["--receipt", p, "--repo", _DATA]) == 0  # halved sustained: skipped
    res = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert "skipped" in res["metrics"]["sustained_ops_s"]
    # and the rule is symmetric config-matching, not a blanket skip:
    # with the cache OFF the same number is a real regression
    cand.pop("cache")
    json.dump(cand, open(p, "w"))
    assert pg.main(["--receipt", p, "--repo", _DATA]) == 1


def test_perfgate_red_on_steady_state_retraces(tmp_path, capsys):
    """Schema-3 device gate: a receipt whose compile ledger counted a
    retrace inside a sealed window fails HARD (no noise margin) even
    with every throughput metric at baseline."""
    pg = _perfgate()
    cand = pg.load_receipt(_R05)
    cand.pop("_round", None)
    cand["device"] = {"ledger": {"retraces": 1}}
    p = str(tmp_path / "retrace.json")
    json.dump(cand, open(p, "w"))
    assert pg.main(["--receipt", p, "--repo", _DATA]) == 1
    res = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert not res["metrics"]["device.retraces"]["ok"]
    # zero retraces: the same receipt passes
    cand["device"] = {"ledger": {"retraces": 0}}
    json.dump(cand, open(p, "w"))
    assert pg.main(["--receipt", p, "--repo", _DATA]) == 0


def test_perfgate_device_bytes_frac_drop_flagged_and_skips_old_rounds():
    pg = _perfgate()

    def mk(rnd, frac):
        r = {"keys": 1000, "batch": 64, "value": 100,
             "device": {"ledger": {"retraces": 0},
                        "rooflines": {"staged": {"serve_fanout": {
                            "available": True,
                            "achieved_bytes_frac": frac}}}}}
        if rnd is not None:
            r["_round"] = rnd
        return r

    hist = [mk(8, 0.60), mk(9, 0.62)]
    res = pg.gate(mk(None, 0.40), hist)  # a real fraction collapse
    m = res["metrics"]["device.staged.serve_fanout.bytes_frac"]
    assert not res["ok"] and not m["ok"] and m["baseline_round"] == 9
    # noise-sized wiggle passes (same margin rule as the walls)
    assert pg.gate(mk(None, 0.59), hist)["ok"]
    # schema-1/2 history: the device comparison SKIPS, never crashes,
    # and the receipt still gates green on the throughput metrics
    old = [{"_round": 5, "keys": 1000, "batch": 64, "value": 100}]
    res3 = pg.gate(mk(None, 0.5), old)
    assert res3["ok"]
    assert "skipped" in \
        res3["metrics"]["device.staged.serve_fanout.bytes_frac"]


def test_perfgate_vanished_device_fraction_is_red():
    """A fraction a committed round published that the candidate
    DROPPED is the limit of "silently sinking" — red when the candidate
    still publishes other fractions, skipped when it publishes none
    (unknown-peak backend: a platform difference, not a regression)."""
    pg = _perfgate()

    def mk(rnd, fracs):
        r = {"keys": 1000, "batch": 64, "value": 100,
             "device": {"ledger": {"retraces": 0},
                        "rooflines": {"staged": {
                            ph: {"available": True,
                                 "achieved_bytes_frac": f}
                            for ph, f in fracs.items()}}}}
        if rnd is not None:
            r["_round"] = rnd
        return r

    hist = [mk(8, {"serve_fanout": 0.60, "prep": 0.30})]
    # candidate keeps prep but drops serve_fanout: hard red
    res = pg.gate(mk(None, {"prep": 0.31}), hist)
    m = res["metrics"]["device.staged.serve_fanout.bytes_frac"]
    assert not res["ok"] and not m["ok"]
    assert m["candidate"] is None and m["baseline"] == 0.60
    assert "absent" in m["error"]
    # candidate publishes NO fractions at all: skip, receipt stays green
    res2 = pg.gate(mk(None, {}), hist)
    assert res2["ok"]
    assert "skipped" in \
        res2["metrics"]["device.staged.serve_fanout.bytes_frac"]


# -- the obs-cost pin (< 2% staged-step wall) ---------------------------------

def test_staged_step_obs_cost_under_two_percent(eight_devices,
                                                monkeypatch):
    """Obs-on vs obs-off staged-step wall delta pinned < 2%: the staged
    dispatch path carries zero per-step obs work (attribution happens
    once per drained window), so the A/B must be noise-flat.  Uses
    test_device_prep's exact shapes (shared jit cache); min-of-N walls
    defeat scheduler spikes."""
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
        monkeypatch.setenv("SHERMAN_SLO", "1" if observe else "0")
        carry = new_carry()
        counters = eng.dsm.counters
        t0 = time.perf_counter()
        for _ in range(S):
            counters, carry = step(eng.dsm.pool, counters, tb, rt, rk,
                                   carry)
        carry = step.drain(carry)
        jax.block_until_ready(carry)
        # the one obs call a window pays rides INSIDE the timed wall
        # (disabled mode pays the env-check branch and nothing else)
        step.record_slo(S, time.perf_counter() - t0)
        dt = time.perf_counter() - t0
        eng.dsm.counters = counters
        return dt

    wall(True)  # warm: compiles + first-dispatch cost stay out
    # The loops are identical code either way (attribution is per
    # window, not per step), so min-of-N over interleaved pairs should
    # be flat; retry the whole A/B on a noise spike so a busy CI host
    # cannot fail a claim about OBS cost.
    for attempt in range(3):
        on, off = [], []
        for _ in range(3):
            on.append(wall(True))
            off.append(wall(False))
        w_on, w_off = min(on), min(off)
        if w_on <= w_off * 1.02:
            break
    assert w_on <= w_off * 1.02, \
        f"obs-on staged wall {w_on * 1e3:.1f} ms vs obs-off " \
        f"{w_off * 1e3:.1f} ms: > 2% delta across {attempt + 1} A/Bs"
    # the deterministic half of the pin: the obs work a window adds
    # (one observe() + the window math) costs well under 2% of the
    # cheapest measured wall
    n_obs = 200
    t0 = time.perf_counter()
    for _ in range(n_obs):
        SLO.observe("read", S * batch, w_off, batches=S)
    obs_cost = (time.perf_counter() - t0) / n_obs
    assert obs_cost < 0.02 * w_off, \
        f"one SLO window observation costs {obs_cost * 1e6:.0f} us vs " \
        f"wall {w_off * 1e3:.1f} ms"
    # and the observed windows actually landed
    assert SLO.slo_window()["read"]["ops_total"] >= S * batch
    SLO.get_slo().reset()
