"""The scope and program-span reduction (benchmarks/scopes.py): the
protobuf metadata reader on a v5e trace, attribution on synthetic
profiles, and gap naming by the program's own spans, and the front door's
readers in a traced served rehearsal."""

import os

import pytest

from benchmarks import scopes, trace
from bench_rehearsal import run_tiny  # noqa: F401 — the fixture

DATA = os.path.join(os.path.dirname(__file__), "data")
STAGED_READ = os.path.join(DATA, "staged_read_v5e.xplane.pb")


class Ev:
    def __init__(self, name, start, dur):
        self.name, self.start_ns, self.duration_ns = name, start, dur


class Line:
    def __init__(self, name, events):
        self.name, self.events = name, events


class Plane:
    def __init__(self, name, lines):
        self.name, self.lines = name, lines


class Profile:
    def __init__(self, planes):
        self.planes = planes


def _recorded():
    from jax.profiler import ProfileData
    return ProfileData.from_file(STAGED_READ)


def test_metadata_reader_maps_recorded_ops_to_their_paths():
    """Every name-stack path of the recorded staged read trace sits under its
    module's ``jit(<fn>)``, keyed by the id in the module's event name;
    the paths cover nearly all of the device's op time."""
    paths = scopes.op_paths(STAGED_READ)
    prof = _recorded()
    dev = [p for p in prof.planes if trace.DEVICE_PLANE.match(p.name)][0]
    lines = {line.name: line for line in dev.lines}
    ids = {}
    for ev in lines[trace.MODULES_LINE].events:
        name = trace.module_name(ev.name)
        ids[int(ev.name[len(name) + 1:-1])] = name[len("jit_"):]
    assert len(paths) > 90
    for (pid, _op), path in paths.items():
        # the compiler names some ops itself (``reduce_window_sum``)
        assert "/" not in path or path.startswith(f"jit({ids[pid]})/"), \
            path
    assert paths[(next(k for k, v in ids.items() if v == "kernel"),
                  next(o for p, o in paths if "fusion.67 " in o))] \
        == "jit(kernel)/while/body/gather"
    total = covered = 0.0
    for ev in lines[trace.OPS_LINE].events:
        total += ev.duration_ns
        covered += ev.duration_ns * any(o == ev.name for _p, o in paths)
    assert covered / total > 0.95


def test_recorded_trace_keeps_every_key_of_the_reduction():
    """On a trace with no program span and no scope, the added keys read
    ``other`` alone and every key the reduction printed before reads
    the same."""
    prof = _recorded()
    base = trace.reduce_profile(prof)
    t = scopes.reduce_profile(prof, scopes.op_paths(STAGED_READ))
    assert set(t) == set(base) | {"scopes", "program_spans"}
    for k in base:
        assert t[k] == base[k], k
    assert t["program_spans"] == {}
    for mod, sc in t["scopes"].items():
        assert set(sc) == {"other"}
        assert sc["other"] == pytest.approx(t["modules"][mod]["s"])


def _scoped_profile():
    host = Plane("/host:CPU", [
        Line("python3", [Ev("bench.window", 0, 2000),
                         Ev("bench.wait", 1000, 900)]),
        Line("sherman-serve-dispatch", [
            Ev("serve.complete", 1100, 500),
            Ev("serve.materialize", 1150, 300),
            Ev("serve.idle", 1700, 200)])])
    dev = Plane("/device:TPU:0", [
        Line("XLA Modules", [Ev("jit_kernel(7)", 100, 800)]),
        Line("XLA Ops", [
            Ev("%fusion.1 = gather", 100, 100),    # descend
            Ev("%while.2 = while", 200, 300),      # wraps the next two
            Ev("%fusion.3 = body", 210, 100),      # descend
            Ev("%fusion.4 = body", 320, 150),      # unscoped
            Ev("%copy.5 = copy", 520, 50),         # no path: the next op's
            Ev("%fusion.6 = scatter", 580, 200),   # writeback
        ])])
    paths = {(7, "%fusion.1 = gather"): "jit(kernel)/descend/gather",
             (7, "%while.2 = while"): "jit(kernel)/descend/while",
             (7, "%fusion.3 = body"): "jit(kernel)/descend/while/body/x",
             (7, "%fusion.4 = body"): "jit(kernel)/while/body/add",
             (7, "%fusion.6 = scatter"): "jit(kernel)/writeback/scatter"}
    return Profile([host, dev]), paths


def test_scopes_attribute_ops_and_sum_to_the_module():
    prof, paths = _scoped_profile()
    t = scopes.reduce_profile(prof, paths)
    sc = t["scopes"]["jit_kernel"]
    assert sc["descend"] == pytest.approx(200e-9)       # not the while
    assert sc["writeback"] == pytest.approx(250e-9)     # copy + scatter
    assert sum(sc.values()) == pytest.approx(t["modules"]["jit_kernel"]["s"])
    assert sc["other"] == pytest.approx(350e-9)


def test_gaps_are_named_by_program_spans_before_harness_spans():
    prof, paths = _scoped_profile()
    t = scopes.reduce_profile(prof, paths)
    base = trace.reduce_profile(prof)
    assert [d for _n, d in t["idle_gaps"]] == \
        [d for _n, d in base["idle_gaps"]]
    names = dict((round(d * 1e9), n) for n, d in t["idle_gaps"])
    # [780, 2000): midpoint 1390 under serve.materialize inside
    # serve.complete inside bench.wait -> the innermost program span
    assert names[1220] == "serve.materialize"
    assert dict((round(d * 1e9), n) for n, d in base["idle_gaps"]
                )[1220] == "wait"
    # [0, 100): no span covers it
    assert names[100] == "none"
    assert t["program_spans"] == {
        "serve.complete": {"s": 500e-9, "n": 1},
        "serve.materialize": {"s": 300e-9, "n": 1},
        "serve.idle": {"s": 200e-9, "n": 1}}


def test_scope_reader_needs_the_module_and_scope():
    prof, paths = _scoped_profile()
    run = {"trace": scopes.reduce_profile(prof, paths),
           "modules": {"serve": "jit_kernel", "prep": "jit_prep"}}
    assert scopes.scope_ms_per_execution(run, "serve", "descend") == \
        pytest.approx(200e-6)
    assert scopes.scope_ms_per_execution(run, "serve", "fanout") is None
    assert scopes.scope_ms_per_execution(run, "prep", "descend") is None
    assert scopes.scope_ms_per_execution(
        {"trace": trace.reduce_profile(prof), "modules": run["modules"]},
        "serve", "descend") is None


@pytest.mark.parametrize("path,scope", [
    ("jit(kernel)/descend/while/body/gather", "descend"),
    ("jit(serve_p)/apply/lock/gather", "lock"),
    ("jit(serve_p)/writeback/reshape", "writeback"),
    ("jit(prep)/router_probe", "router_probe"),
    ("jit(kernel)/while/body/gather", "other"),
    ("jit(kernel)/descend_spmd/gather", "other"),
])
def test_scope_of_takes_the_innermost_listed_scope(path, scope):
    assert scopes.scope_of(path) == scope


def _reduce(name):
    from jax.profiler import ProfileData
    path = os.path.join(DATA, name)
    return scopes.reduce_profile(ProfileData.from_file(path),
                                 scopes.op_paths(path))


# device ms a step per (module, scope) of two ``--trace 1`` windows of
# 1 s, measured on one v5e chip with this program's scopes: the
# staged read (17 steps) and the staged mixed loop (14 steps), with the
# host-metadata plane stripped (the reduction reads the same without it)
RECORDED = {
    "staged_read_scopes_v5e.xplane.pb": {
        "jit_kernel": {"descend": 52.03, "fanout": 18.35},
        "jit_prep": {"router_probe": 28.26, "combine": 20.19,
                     "sample": 3.45}},
    "staged_mixed_scopes_v5e.xplane.pb": {
        "jit_serve_p": {"descend": 58.99, "writeback": 62.16,
                        "fanout": 24.24, "apply": 14.85,
                        "snapshot": 10.86, "lock": 6.26},
        "jit_prep": {"router_probe": 28.15, "combine": 18.05,
                     "sample": 3.45}},
}


@pytest.mark.parametrize("name", sorted(RECORDED))
def test_recorded_v5e_scopes_account_for_each_program(name):
    """Each scope's device ms a step is pinned to 1 %; a module's scopes
    with ``other`` sum to its time, and ``other`` is under 1 % of it."""
    t = _reduce(name)
    for mod, want in RECORDED[name].items():
        m, sc = t["modules"][mod], t["scopes"][mod]
        assert set(sc) == set(want) | {"other"}
        for scope, ms in want.items():
            assert 1e3 * sc[scope] / m["n"] == pytest.approx(ms, rel=0.01)
        assert sum(sc.values()) == pytest.approx(m["s"])
        assert 0 <= sc["other"] < 0.01 * m["s"]


def test_recorded_mixed_writeback_holds_the_whole_pool_traffic():
    """The mixed serve's whole-pool write-back — the scatter into the
    flattened pool, the relayout copy before it (no op_name: it counts
    toward the scatter it feeds) and the reshape back — is ``writeback``;
    the staged prep's router gather from ``rtable`` is ``router_probe``."""
    from jax.profiler import ProfileData
    path = os.path.join(DATA, "staged_mixed_scopes_v5e.xplane.pb")
    paths = scopes.op_paths(path)
    ops, mods = scopes._devices(list(ProfileData.from_file(path).planes))[0]
    ops = sorted(ops, key=lambda o: o[1])
    starts = [o[1] for o in ops]
    seen = {}
    for mod in mods:
        for name, d, scope in scopes.attribute(mod, ops, starts, paths):
            text = trace.op_name(name)
            if " = s32[1073741824] fusion(" in text:
                seen["scatter"] = scope
            elif " = s32[524288,2,8,128] copy(" in text:
                seen["copy"] = scope
            elif " = s32[4194304,256] reshape(s32[1073741824]" in text:
                seen["reshape"] = scope
            elif "%rtable" in text:
                seen["rtable"] = scope
    assert seen == {"scatter": "writeback", "copy": "writeback",
                    "reshape": "writeback", "rtable": "router_probe"}


def _ring(monkeypatch, spans, max_events=1 << 18):
    """Make a private span ring holding ``spans`` ((name, start_s,
    dur_s), seconds from the tracer's epoch) the program's tracer."""
    from sherman_tpu import obs
    from sherman_tpu.obs.spans import SpanTracer
    tr = SpanTracer(max_events=max_events)
    for name, s, d in spans:
        tr._record(name, tr._t0 + s, tr._t0 + s + d, 0, None)
    monkeypatch.setattr(obs, "get_tracer", lambda: tr)


def _served_ring(idle_before_s):
    """A dispatcher that idles ``idle_before_s`` before a 1 s window of
    100 steps (take 0.1, prep 4, complete 3, idle 0.2 ms each), then
    idles into the drain."""
    w0 = 10.0
    spans = []
    if idle_before_s:      # a take that found nothing, then the idle
        spans += [("serve.take", w0 - idle_before_s - 0.0002, 0.0001),
                  ("serve.idle", w0 - idle_before_s, idle_before_s)]
    for k in range(100):
        t = w0 + 0.01 * k
        spans += [("serve.take", t, 0.0001),
                  ("serve.prep", t + 0.0001, 0.004),
                  ("serve.complete", t + 0.0041, 0.003),
                  ("serve.idle", t + 0.0071, 0.0002)]
    spans.append(("serve.idle", w0 + 0.9975, 2.0))
    return spans


@pytest.mark.parametrize("idle_before_s", [0.0, 0.5, 30.0])
def test_served_readers_ignore_the_dispatcher_before_the_window(
        monkeypatch, idle_before_s):
    """However long the dispatcher idled before the window, the idle
    share and the completion time read the window alone: it runs
    ``window_s`` from the first ``serve.prep`` and clips the idle that
    runs past its end."""
    from benchmarks import harness
    _ring(monkeypatch, _served_ring(idle_before_s))
    run = {"window_s": 1.0}
    idle = harness.load_reader("ingress_dispatcher_idle_share")(run)
    # 100 in-window idles of 0.2 ms + the drain's idle up to the end
    # (first prep at 10.0001 s, window end 11.0001 s)
    assert idle == pytest.approx(100 * (100 * 0.0002 + 0.0026), rel=1e-4)
    comp = harness.load_reader("ingress_complete_host_ms")(run)
    assert comp == pytest.approx(3.0, rel=1e-4)


def test_served_readers_need_the_dispatchers_spans(monkeypatch):
    """A program without the front door's spans (or a ring that dropped
    events) gives the served span readers nothing to read."""
    from benchmarks import harness
    names = ("ingress_dispatcher_idle_share", "ingress_complete_host_ms")
    _ring(monkeypatch, [("bench.other", 0.0, 1.0)])
    for name in names:
        assert harness.load_reader(name)({"window_s": 1.0}) is None
    _ring(monkeypatch, _served_ring(0.0), max_events=64)
    for name in names:
        assert harness.load_reader(name)({"window_s": 1.0}) is None


def test_traced_served_run_on_cpu_reads_the_front_door_metrics(run_tiny):
    """The served cell's traced run reads the front door's own spans and
    counters (the device-trace metrics stay away on the CPU): the
    dispatcher's idle share of the window, its mean step completion and
    the requests' mean queue wait."""
    r = run_tiny("ycsb-c.zipf99.served", trace=True)
    assert r["correct"]
    m = {k: v["value"] for k, v in r["metrics"].items()}
    assert 0.0 <= m["ingress_dispatcher_idle_share"] < 100.0
    assert m["ingress_complete_host_ms"] > 0.0
    assert m["ingress_queue_wait_ms"] >= 0.0
    assert "device_idle_share.served" not in m
