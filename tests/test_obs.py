"""sherman_tpu.obs — registry, spans, export, and layer wiring."""

import json
import threading

import numpy as np
import pytest

from sherman_tpu import obs
from sherman_tpu.obs.registry import MetricsRegistry, delta
from sherman_tpu.obs.spans import SpanTracer


# -- registry ----------------------------------------------------------------

def test_counter_gauge_histogram_snapshot():
    reg = MetricsRegistry()
    c = reg.counter("c")
    c.inc()
    c.inc(4)
    reg.gauge("g").set(2.5)
    h = reg.histogram("h")
    for v in (1, 2, 3, 1000):
        h.record(v)
    snap = reg.snapshot()
    assert snap["c"] == 5
    assert snap["g"] == 2.5
    assert snap["h"]["count"] == 4
    assert snap["h"]["sum"] == 1006
    assert snap["h"]["min"] == 1 and snap["h"]["max"] == 1000
    # percentile is bucket-resolved: p50 within 2x of the true median
    assert 1 <= snap["h"]["p50"] <= 4
    assert snap["h"]["p99"] >= 511


def test_metric_get_or_create_idempotent_and_typed():
    reg = MetricsRegistry()
    assert reg.counter("x") is reg.counter("x")
    with pytest.raises(TypeError):
        reg.gauge("x")


def test_snapshot_delta_semantics():
    reg = MetricsRegistry()
    c = reg.counter("ops")
    c.inc(10)
    before = reg.snapshot()
    c.inc(7)
    reg.counter("born_inside").inc(3)  # metric created inside the region
    after = reg.snapshot()
    d = delta(before, after)
    assert d["ops"] == 7
    assert d["born_inside"] == 3


def test_reset_zeroes_in_place_keeping_bindings():
    # instrumentation sites bind Counter objects at import; reset must
    # zero them in place, not orphan them from future snapshots
    reg = MetricsRegistry()
    c = reg.counter("bound")
    g = reg.gauge("g")
    h = reg.histogram("h")
    c.inc(5)
    g.set(3.0)
    h.record(10)
    reg.register_collector("src", lambda: {"a": 1})
    reg.reset()
    assert reg.snapshot()["bound"] == 0
    assert reg.snapshot()["h"]["count"] == 0
    c.inc(2)  # the pre-reset object still feeds snapshots
    assert reg.counter("bound") is c
    assert reg.snapshot()["bound"] == 2
    assert reg.snapshot()["src.a"] == 1  # collectors survive too


def test_collector_merge_and_error_isolation():
    reg = MetricsRegistry()
    reg.register_collector("src", lambda: {"a": 1, "b": 2})
    reg.register_collector("bad", lambda: 1 / 0)
    snap = reg.snapshot()
    assert snap["src.a"] == 1 and snap["src.b"] == 2
    assert any("bad" in e for e in snap["_collector_errors"])
    reg.unregister_collector("src")
    assert "src.a" not in reg.snapshot()


def test_snapshot_vs_increment_fuzz_undercounts_never_crashes():
    """The documented lock-free-hot-path contract, pinned by storm:
    concurrent inc/record during snapshot()/delta() may UNDERCOUNT
    (increments are not atomic RMWs) but must never raise, corrupt a
    histogram's invariants, or over-count."""
    reg = MetricsRegistry()
    c = reg.counter("storm.ops")
    h = reg.histogram("storm.lat")
    g = reg.gauge("storm.depth")
    N_THREADS, N_INCS = 4, 5_000
    stop = threading.Event()
    errors: list = []

    def incer():
        try:
            for i in range(N_INCS):
                c.inc()
                h.record(i % 1000)
                g.set(i)
        except Exception as e:  # pragma: no cover - the failure mode
            errors.append(e)

    def snapper():
        try:
            while not stop.is_set():
                snap = reg.snapshot()
                assert 0 <= snap["storm.ops"] <= N_THREADS * N_INCS
                hs = snap["storm.lat"]
                assert 0 <= hs["count"] <= N_THREADS * N_INCS
                delta(snap, reg.snapshot())
        except Exception as e:  # pragma: no cover
            errors.append(e)

    ts = [threading.Thread(target=incer) for _ in range(N_THREADS)]
    ss = [threading.Thread(target=snapper) for _ in range(2)]
    for t in ss + ts:
        t.start()
    for t in ts:
        t.join()
    stop.set()
    for t in ss:
        t.join()
    assert not errors, errors
    final = reg.snapshot()
    # everything joined: the final snapshot is exact (undercount can
    # only happen to a reader racing a writer, never after quiescence
    # on CPython's per-op atomic int adds)
    assert 0 < final["storm.ops"] <= N_THREADS * N_INCS
    assert final["storm.lat"]["count"] == sum(h.buckets)


def test_collector_raises_mid_storm_isolated():
    """A collector that raises INTERMITTENTLY (the donated-buffer-
    mid-step shape) is recorded under _collector_errors on its bad
    snapshots and contributes normally on its good ones — the other
    metrics never disappear either way."""
    reg = MetricsRegistry()
    reg.counter("solid").inc(3)
    calls = [0]

    def flaky():
        calls[0] += 1
        if calls[0] % 2:
            raise RuntimeError("donated buffer mid-step")
        return {"ok": 1}

    reg.register_collector("flaky", flaky)
    bad = reg.snapshot()
    good = reg.snapshot()
    assert bad["solid"] == good["solid"] == 3
    assert any("flaky" in e for e in bad["_collector_errors"])
    assert good["flaky.ok"] == 1 and "_collector_errors" not in good
    # delta() skips the underscore bookkeeping keys entirely
    assert "_collector_errors" not in delta(bad, good)


# -- spans -------------------------------------------------------------------

def test_nested_spans_and_summary():
    tr = SpanTracer()
    with tr.span("outer"):
        with tr.span("inner"):
            pass
        with tr.span("inner"):
            pass
    s = tr.summary()
    assert s["outer"]["n"] == 1
    assert s["inner"]["n"] == 2
    # nesting recorded: inner events carry depth 1 under outer
    depths = {e[0]: e[4] for e in tr._events}
    assert depths["outer"] == 0 and depths["inner"] == 1


def test_chrome_trace_roundtrips_through_json(tmp_path):
    tr = SpanTracer()
    with tr.span("phase_a", step=3):
        with tr.span("phase_b"):
            pass
    path = tr.export_chrome(str(tmp_path / "trace.json"))
    with open(path) as f:
        doc = json.load(f)
    evs = doc["traceEvents"]
    assert isinstance(evs, list) and len(evs) == 2
    by_name = {e["name"]: e for e in evs}
    for e in evs:
        assert e["ph"] == "X"
        assert e["dur"] >= 0 and e["ts"] >= 0
    assert by_name["phase_a"]["args"] == {"step": 3}
    # b nests inside a on the timeline
    a, b = by_name["phase_a"], by_name["phase_b"]
    assert a["ts"] <= b["ts"]
    assert b["ts"] + b["dur"] <= a["ts"] + a["dur"] + 1e-3


def test_chrome_trace_event_schema_perfetto_loadable(tmp_path):
    """Validate the emitted trace-event JSON against the Chrome
    trace-event spec's required fields/types so
    bench_logs/trace_last.json stays loadable in Perfetto: complete
    ("X") events with numeric microsecond ts/dur, integer pid/tid, and
    child events properly NESTED inside their parents' [ts, ts+dur]
    intervals (the X-event encoding of B/E nesting)."""
    tr = SpanTracer()
    with tr.span("root", step=1):
        with tr.span("child_a"):
            with tr.span("grandchild"):
                pass
        with tr.span("child_b"):
            pass
    tr.record("after_the_fact", 0.001)
    path = tr.export_chrome(str(tmp_path / "trace.json"))
    with open(path) as f:
        doc = json.load(f)
    assert isinstance(doc["traceEvents"], list)
    assert doc["displayTimeUnit"] in ("ms", "ns")
    by_name = {}
    for e in doc["traceEvents"]:
        # required fields of an "X" (complete) event, with their types
        assert e["ph"] == "X", e
        assert isinstance(e["name"], str) and e["name"]
        assert isinstance(e["pid"], int)
        assert isinstance(e["tid"], int)
        assert isinstance(e["ts"], (int, float)) and e["ts"] >= 0
        assert isinstance(e["dur"], (int, float)) and e["dur"] >= 0
        assert isinstance(e.get("cat", ""), str)
        if "args" in e:
            assert isinstance(e["args"], dict)
        by_name[e["name"]] = e

    def contains(outer, inner, tol_us=1e-3):
        return (outer["ts"] <= inner["ts"] + tol_us
                and inner["ts"] + inner["dur"]
                <= outer["ts"] + outer["dur"] + tol_us)

    root = by_name["root"]
    assert contains(root, by_name["child_a"])
    assert contains(root, by_name["child_b"])
    assert contains(by_name["child_a"], by_name["grandchild"])
    # siblings on one thread never interleave
    a, b = by_name["child_a"], by_name["child_b"]
    assert a["ts"] + a["dur"] <= b["ts"] + 1e-3
    assert root["args"] == {"step": 1}
    # the whole document survives a strict JSON round trip (Perfetto's
    # parser rejects NaN/Inf, which json.dumps would emit unquoted)
    json.loads(json.dumps(doc, allow_nan=False))


def test_span_recording_thread_safe():
    tr = SpanTracer()

    def worker():
        for _ in range(200):
            with tr.span("w"):
                pass

    ts = [threading.Thread(target=worker) for _ in range(4)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert tr.summary()["w"]["n"] == 800
    assert len(tr.chrome_trace()["traceEvents"]) == 800


def test_event_cap_keeps_aggregates():
    tr = SpanTracer(max_events=3)
    for _ in range(10):
        with tr.span("s"):
            pass
    assert tr.summary()["s"]["n"] == 10  # aggregate sees everything
    assert len(tr.chrome_trace()["traceEvents"]) == 3
    assert tr.dropped == 7


def test_span_ring_keeps_the_newest_events():
    tr = SpanTracer(max_events=4)
    for i in range(10):
        with tr.span("s", i=i):
            pass
    evs = tr.chrome_trace()["traceEvents"]
    assert [e["args"]["i"] for e in evs] == [6, 7, 8, 9]
    assert tr.chrome_trace()["otherData"]["dropped_events"] == 6
    tr.reset()
    assert tr.dropped == 0 and tr.chrome_trace()["traceEvents"] == []


def test_span_ring_accounts_every_span_under_thread_churn():
    """More recording threads than cores, a short switch interval and a
    small ring: every span is either in the ring or counted dropped,
    and the aggregate sees all of them."""
    import os
    import sys
    tr = SpanTracer(max_events=64)
    n_threads = 2 * (os.cpu_count() or 4)
    per = 300

    def worker():
        for _ in range(per):
            with tr.span("w"):
                pass

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        ts = [threading.Thread(target=worker) for _ in range(n_threads)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(60)
    finally:
        sys.setswitchinterval(old)
    assert not any(t.is_alive() for t in ts)
    total = n_threads * per
    assert tr.summary()["w"]["n"] == total
    assert len(tr.chrome_trace()["traceEvents"]) == 64
    assert tr.dropped == total - 64


def test_span_lands_on_the_profiler_host_plane(tmp_path):
    """``obs.span`` opens a TraceAnnotation: under a profiler trace the
    span is an event of the same name, with its arguments as stats, on
    the host plane's line of the thread that ran it."""
    import glob

    import jax
    import jax.numpy as jnp
    from jax.profiler import ProfileData

    tr = SpanTracer()
    with jax.profiler.trace(str(tmp_path)):
        with tr.span("test.outer", step=7):
            with tr.span("test.inner"):
                jax.block_until_ready(jnp.arange(8) * 2)
    path = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    prof = ProfileData.from_file(path[0])
    found = {}
    for plane in prof.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("test."):
                    found[ev.name] = (line.name, ev.start_ns,
                                      ev.duration_ns, dict(ev.stats))
    assert set(found) == {"test.outer", "test.inner"}
    outer, inner = found["test.outer"], found["test.inner"]
    assert outer[3] == {"step": 7}
    assert outer[0] == inner[0]                     # one thread's line
    assert outer[1] <= inner[1] and \
        inner[1] + inner[2] <= outer[1] + outer[2]  # nested in time
    # the span's own record is unchanged by the trace
    assert tr.summary()["test.outer"]["n"] == 1


# -- export ------------------------------------------------------------------

def test_dump_and_jsonl(tmp_path):
    reg = MetricsRegistry()
    reg.counter("n").inc(2)
    tr = SpanTracer()
    with tr.span("p"):
        pass
    path = obs.dump(str(tmp_path / "obs.json"), reg, tr,
                    extra={"run": "test"})
    with open(path) as f:
        doc = json.load(f)
    assert doc["traceEvents"][0]["name"] == "p"
    assert doc["otherData"]["metrics"]["n"] == 2
    assert doc["otherData"]["run"] == "test"
    jl = str(tmp_path / "obs.jsonl")
    obs.write_snapshot_jsonl(jl, reg)
    obs.write_snapshot_jsonl(jl, reg)
    lines = [json.loads(ln) for ln in open(jl)]
    assert len(lines) == 2 and lines[0]["metrics"]["n"] == 2


# -- layer wiring ------------------------------------------------------------

def test_dsm_counters_visible_through_registry(eight_devices):
    from sherman_tpu.config import DSMConfig
    from sherman_tpu.ops import bits
    from sherman_tpu.parallel.dsm import DSM

    cfg = DSMConfig(machine_nr=2, pages_per_node=64, locks_per_node=64,
                    step_capacity=16)
    dsm = DSM(cfg)
    before = obs.snapshot()
    a = bits.make_addr(1, 3)
    dsm.write_page(a, np.arange(256, dtype=np.int32))
    pg = dsm.read_page(a)
    assert pg[7] == 7
    d = delta(before, obs.snapshot())
    assert d["dsm.read_ops"] == 1
    assert d["dsm.write_ops"] == 1
    assert d["dsm.read_bytes"] == 1024
    assert d["dsm.host_steps"] == 2
    # the registry view and the legacy attribute API agree
    snap = obs.snapshot()
    for k, v in dsm.counter_snapshot().items():
        assert snap[f"dsm.{k}"] == v


def test_btree_cache_counters(eight_devices):
    from sherman_tpu import native
    if not native.available():
        pytest.skip("native lib unavailable")
    from sherman_tpu.cluster import Cluster
    from sherman_tpu.config import DSMConfig
    from sherman_tpu.models.btree import Tree

    cfg = DSMConfig(machine_nr=2, pages_per_node=128, locks_per_node=64,
                    step_capacity=64)
    tree = Tree(Cluster(cfg))
    tree.enable_index_cache(64)
    for k in range(1, 6):
        tree.insert(k, k + 100)
    before = obs.snapshot()
    tree.search(3)  # miss (nothing cached at leaf level yet) or hit
    tree.search(3)
    d = delta(before, obs.snapshot())
    assert d.get("btree.cache_hits", 0) + d.get("btree.cache_misses", 0) == 2


def test_engine_phases_recorded_as_spans(eight_devices):
    from sherman_tpu.cluster import Cluster
    from sherman_tpu.config import DSMConfig
    from sherman_tpu.models import batched
    from sherman_tpu.models.btree import Tree

    cfg = DSMConfig(machine_nr=2, pages_per_node=256, locks_per_node=128,
                    step_capacity=256)
    tree = Tree(Cluster(cfg))
    eng = batched.BatchedEngine(tree, batch_per_node=64)
    keys = np.arange(1, 65, dtype=np.uint64)
    before = obs.get_tracer().summary()
    eng.insert(keys, keys + 1)
    vals, found = eng.search(keys)
    assert found.all() and (vals == keys + 1).all()
    after = obs.get_tracer().summary()

    def n(summ, name):
        return summ.get(name, {}).get("n", 0)

    assert n(after, "engine.insert.descend_lock_apply") > n(
        before, "engine.insert.descend_lock_apply")
    assert n(after, "engine.search.descend") > n(
        before, "engine.search.descend")


def test_metrics_server_scrapes_slo_and_device_planes():
    """End-to-end scrape over a real socket: GET /metrics on an
    ephemeral port against the DEFAULT registry must expose the slo.
    and device. pull collectors as parseable Prometheus gauges — the
    deployment shape (node scraping the serving process), not the
    renderer in isolation."""
    import urllib.request
    from sherman_tpu.obs import device as dev
    from sherman_tpu.obs import export as obs_export

    dev.get_ledger()                  # device. collector registered
    obs.observe("read", 100, 0.010)   # slo.read window carries data
    with obs_export.MetricsServer(port=0) as srv:
        body = urllib.request.urlopen(
            f"http://127.0.0.1:{srv.port}/metrics", timeout=10
        ).read().decode()
    # parse the text exposition: unlabeled lines are "<name> <number>"
    metrics = {}
    for line in body.splitlines():
        if not line or line.startswith("#") or "{" in line:
            continue
        name, val = line.rsplit(" ", 1)
        metrics[name] = float(val)  # malformed value -> test fails
    assert metrics["sherman_device_programs"] >= 0
    assert metrics["sherman_device_retraces"] >= 0
    assert "sherman_device_hbm_total_bytes" in metrics
    assert metrics["sherman_slo_read_ops_total"] >= 100
    assert "sherman_slo_read_p99_ms" in metrics
