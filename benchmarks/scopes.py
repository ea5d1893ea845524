"""The program's named scopes and spans in a profiler trace.

:mod:`benchmarks.trace` times whole programs and names the device's
idle gaps by the harness's own ``bench.*`` spans.  This module adds what
the program itself marks:

- ``scopes``: device op time per ``(module, scope)``.  The program wraps
  the phases of its serve and prep programs in ``jax.named_scope``
  (:data:`SCOPES`); XLA keeps the scope path as each op's ``op_name``,
  which the profiler stores as the ``tf_op`` stat of the op's event
  metadata on the device plane.  ``jax.profiler.ProfileData`` does not
  expose metadata stats, so :func:`op_paths` reads them from the
  ``.xplane.pb`` with a minimal protobuf wire-format reader (no
  TensorFlow).  An op counts toward the innermost listed scope of its
  path; an op with no name-stack path (a relayout copy or an async
  slice the compiler inserted) toward the next op of its execution that
  has one; a ``while`` counts through its body's ops.  ``other`` is the rest of
  the module's time (unscoped ops and the gaps between ops), so a
  module's scopes sum to its time.
- ``program_spans``: ``{name: {"s", "n"}}`` of the program's own host
  spans (``obs.span``, which open a ``TraceAnnotation`` of the same
  name; names under :data:`PROGRAM_SPANS`) inside the window.
- ``idle_gaps``: the first chip's longest idle gaps, as
  :mod:`benchmarks.trace` finds them, named by the innermost program
  span covering the gap's midpoint, else by the harness span as before.

:func:`served_window` reads the served window's spans from the
program's own span ring instead, for the served readers while the
harness does not hand the trace's ``program_spans`` over.

:func:`reduce_profile` returns :func:`benchmarks.trace.reduce_profile`'s
dict with these three keys added or replaced; every other key reads the
same.  Times are seconds, averaged over the device planes.
"""

from __future__ import annotations

import bisect
import re

from benchmarks import trace

#: the scope names the program's serve and prep programs use
#: (models/batched.py, workload/device_prep.py)
SCOPES = ("descend", "fanout", "snapshot", "lock", "apply", "writeback",
          "sample", "combine", "router_probe")
OTHER = "other"
#: name prefixes of the program's own spans (obs.span)
PROGRAM_SPANS = ("serve.", "engine.")
_PROGRAM_ID = re.compile(r"\((\d+)\)$")


# ---------------------------------------------------------------------------
# Protobuf wire format: just enough of XSpace to read event metadata.
#   XSpace.planes = 1; XPlane.name = 2, .event_metadata = 4 (map),
#   .stat_metadata = 5 (map); map entry key = 1, value = 2;
#   XEventMetadata.name = 2, .stats = 5; XStatMetadata.name = 2;
#   XStat.metadata_id = 1, uint64 = 3, int64 = 4, str = 5, ref = 7.
# ---------------------------------------------------------------------------

def _varint(b, i: int):
    r = shift = 0
    while True:
        c = b[i]
        i += 1
        r |= (c & 0x7F) << shift
        if c < 0x80:
            return r, i
        shift += 7


def _fields(b, lo: int, hi: int):
    """(field number, wire type, value) of one message in ``b[lo:hi]``;
    a length-delimited value is its (start, end)."""
    i = lo
    while i < hi:
        key, i = _varint(b, i)
        f, wt = key >> 3, key & 7
        if wt == 0:
            v, i = _varint(b, i)
        elif wt == 1:
            v, i = None, i + 8
        elif wt == 2:
            n, i = _varint(b, i)
            v, i = (i, i + n), i + n
        elif wt == 5:
            v, i = None, i + 4
        else:
            raise ValueError(f"wire type {wt} at byte {i}")
        yield f, wt, v


def _map_entry(b, span):
    key = val = None
    for f, _wt, v in _fields(b, *span):
        if f == 1:
            key = v
        elif f == 2:
            val = v
    return key, val


def _text(b, span) -> str:
    return bytes(b[span[0]:span[1]]).decode("utf-8", "replace")


def op_paths(path: str) -> dict:
    """``{(program id, op event name): op_name path}`` of every device
    plane's op metadata in the ``.xplane.pb`` at ``path`` (the ``tf_op``
    stat without its ``:type`` suffix)."""
    with open(path, "rb") as f:
        b = f.read()
    out = {}
    for f, _wt, plane in _fields(b, 0, len(b)):
        if f != 1:
            continue
        name, events, stat_names = None, [], {}
        for pf, _pwt, pv in _fields(b, *plane):
            if pf == 2:
                name = _text(b, pv)
                if not trace.DEVICE_PLANE.match(name):
                    break
            elif pf == 4:
                events.append(pv)
            elif pf == 5:
                k, v = _map_entry(b, pv)
                for sf, _swt, sv in _fields(b, *v):
                    if sf == 2:
                        stat_names[k] = _text(b, sv)
        if name is None or not trace.DEVICE_PLANE.match(name):
            continue
        ids = {n: k for k, n in stat_names.items()}
        tf_op, prog = ids.get("tf_op"), ids.get("program_id")
        for entry in events:
            _k, meta = _map_entry(b, entry)
            ev_name = op = pid = None
            for ef, _ewt, ev in _fields(b, *meta):
                if ef == 2:
                    ev_name = _text(b, ev)
                elif ef == 5:
                    sid = sval = None
                    for sf, swt, sv in _fields(b, *ev):
                        if sf == 1:
                            sid = sv
                        elif sf in (3, 4):
                            sval = sv
                        elif sf == 5:
                            sval = _text(b, sv)
                        elif sf == 7:
                            sval = stat_names.get(sv)
                    if sid == tf_op and isinstance(sval, str):
                        op = sval
                    elif sid == prog and isinstance(sval, int):
                        pid = sval
            if ev_name is not None and op is not None:
                out[(pid, ev_name)] = op.rsplit(":", 1)[0] \
                    if ":" in op else op
    return out


def scope_of(op_path: str) -> str:
    """The innermost listed scope of an ``op_name`` path, else
    ``other``: ``jit(kernel)/descend/while/body/gather`` -> ``descend``."""
    for part in reversed(op_path.split("/")):
        if part in SCOPES:
            return part
    return OTHER


# ---------------------------------------------------------------------------
# The reduction.
# ---------------------------------------------------------------------------

def _devices(planes):
    out = []
    for plane in planes:
        if not trace.DEVICE_PLANE.match(plane.name):
            continue
        lines = {line.name: line for line in plane.lines}
        ops = [(ev.name, s, e) for ev, s, e in
               trace._events(lines[trace.OPS_LINE])] \
            if trace.OPS_LINE in lines else []
        mods = [(ev.name, s, e) for ev, s, e in
                trace._events(lines[trace.MODULES_LINE])] \
            if trace.MODULES_LINE in lines else []
        if ops or mods:
            out.append((ops, mods))
    return out


def _window(spans, devices):
    """The window's bounds, as :func:`benchmarks.trace.reduce_profile`
    sets them."""
    starts = [s for o, m in devices for _, s, _e in (o or m)]
    ends = [e for o, m in devices for _, _s, e in (o or m)]
    lo, hi = min(starts), max(ends)
    windows = [(s, e) for s, e, n in spans if n == trace.WINDOW_SPAN]
    if windows:
        ws, we = windows[-1]
        if ws < hi and we > lo:
            lo, hi = ws, we
    return lo, hi


def _program_spans(planes):
    """All program host spans as (start, end, name)."""
    out = []
    for plane in planes:
        if trace.DEVICE_PLANE.match(plane.name):
            continue
        for line in plane.lines:
            for ev, s, e in trace._events(line):
                if ev.name.startswith(PROGRAM_SPANS):
                    out.append((s, e, ev.name))
    return out


def _innermost(spans, mid):
    best = None
    for a, b, name in spans:
        if a <= mid <= b and (best is None or b - a < best[1] - best[0]):
            best = (a, b, name)
    return best


def name_gap(program, bench, s, e) -> str:
    """The innermost program span covering the gap's midpoint, else the
    innermost harness span (without its ``bench.`` prefix), else
    ``none``."""
    mid = 0.5 * (s + e)
    best = _innermost(program, mid)
    if best is not None:
        return best[2]
    best = _innermost([x for x in bench if x[2] != trace.WINDOW_SPAN], mid)
    return best[2][len(trace.SPAN_PREFIX):] if best else "none"


def _gaps(devices, lo, hi):
    """The first chip's idle gaps inside [lo, hi) as (dur, start, end),
    longest first (the intervals :func:`benchmarks.trace.reduce_profile`
    finds)."""
    ops, mods = devices[0]
    merged = trace._union(trace._clip([(s, e) for _, s, e in (ops or mods)],
                                      lo, hi))
    gaps, edge = [], lo
    for s, e in merged:
        if s > edge:
            gaps.append((s - edge, edge, s))
        edge = e
    if hi > edge:
        gaps.append((hi - edge, edge, hi))
    gaps.sort(reverse=True)
    return gaps


def attribute(module_event, ops, starts, paths) -> list:
    """``[(op name, seconds, scope)]`` of one module execution:
    ``module_event`` its (name, start, end) on the ``XLA Modules`` line,
    ``ops`` the device's (name, start, end) op events sorted by start,
    ``starts`` their starts.
    A control-flow op (``while``) whose event spans its body's ops is
    left out: the body counts.  An op with no name-stack path (a
    relayout copy or async slice the compiler inserted with no op_name,
    a sum it named itself) counts toward the next op that has one: the
    op it feeds."""
    mname, ms, me = module_event
    m = _PROGRAM_ID.search(mname)
    pid = int(m.group(1)) if m else None
    leaves = []
    j = bisect.bisect_left(starts, ms)
    while j < len(ops) and ops[j][1] <= me:
        name, s, e = ops[j]
        j += 1
        if j < len(ops) and ops[j][1] < e:
            continue
        path = paths.get((pid, name)) or ""
        leaves.append([name, (min(e, me) - s) / 1e9,
                       scope_of(path) if "/" in path else None])
    nxt = OTHER
    for leaf in reversed(leaves):
        nxt = leaf[2] = leaf[2] if leaf[2] is not None else nxt
    return [tuple(leaf) for leaf in leaves]


def _scopes(devices, lo, hi, paths):
    """{module: {scope: seconds}} over the executions whose midpoint lies
    in [lo, hi), ``other`` the rest of each module's time."""
    out: dict = {}
    for ops, mods in devices:
        ops = sorted(ops, key=lambda o: o[1])
        starts = [o[1] for o in ops]
        for mod in mods:
            _n, ms, me = mod
            if not lo <= 0.5 * (ms + me) < hi:
                continue
            sc = out.setdefault(trace.module_name(mod[0]), {})
            scoped = 0.0
            for _name, d, scope in attribute(mod, ops, starts, paths):
                if scope != OTHER:
                    sc[scope] = sc.get(scope, 0.0) + d
                    scoped += d
            sc[OTHER] = sc.get(OTHER, 0.0) + (me - ms) / 1e9 - scoped
    k = len(devices)
    return {mod: {s: v / k for s, v in sc.items()}
            for mod, sc in out.items()}


def reduce_profile(profile, paths: dict) -> dict | None:
    """:func:`benchmarks.trace.reduce_profile` plus ``scopes``,
    ``program_spans`` and ``idle_gaps`` named by program spans.
    ``paths`` is :func:`op_paths` of the same trace."""
    t = trace.reduce_profile(profile)
    if t is None:
        return None
    planes = list(profile.planes)
    devices = _devices(planes)
    bench = trace._host_spans(planes)
    lo, hi = _window(bench, devices)
    program = _program_spans(planes)
    spans: dict = {}
    for s, e, name in program:
        if lo <= 0.5 * (s + e) < hi:
            m = spans.setdefault(name, {"s": 0.0, "n": 0})
            m["s"] += (e - s) / 1e9
            m["n"] += 1
    t["scopes"] = _scopes(devices, lo, hi, paths)
    t["program_spans"] = spans
    t["idle_gaps"] = [[name_gap(program, bench, s, e), d / 1e9]
                      for d, s, e in _gaps(devices, lo, hi)[:trace.TOP_N]]
    return t


def reduce_trace_dir(trace_dir: str) -> dict | None:
    path = trace.find_xplane(trace_dir)
    if path is None:
        return None
    from jax.profiler import ProfileData
    return reduce_profile(ProfileData.from_file(path), op_paths(path))


def scope_ms_per_execution(run: dict, role: str, scope: str
                           ) -> float | None:
    """Device ms per execution of the role's program under ``scope``."""
    m = trace.program(run, role)
    t = run.get("trace") or {}
    name = run.get("modules", {}).get(role)
    sc = t.get("scopes", {}).get(name, {})
    if m is None or scope not in sc:
        return None
    return 1e3 * sc[scope] / m["n"]



def served_window(run: dict):
    """The served window on the program's own span ring
    (``obs.get_tracer()``; the front door's hot spans reach it only
    while a profiler trace is active): ``(t0, t1, spans)``, the window's
    bounds in the ring's microseconds and the ``(name, start, end)`` of
    every span that overlaps it.  The window is ``run["window_s"]`` from
    the dispatcher's first ``serve.prep``: no read is submitted before
    the window opens, so whatever the dispatcher did before it (idled,
    calibrated) counts nothing.  None with no ``serve.prep`` to anchor
    on, or when the ring dropped events (its first ``serve.prep`` may
    then not be the window's)."""
    from sherman_tpu import obs
    window_s = run.get("window_s")
    doc = obs.get_tracer().chrome_trace()
    if doc["otherData"].get("dropped_events") or not window_s:
        return None
    evs = doc["traceEvents"]
    t0 = min((e["ts"] for e in evs if e["name"] == "serve.prep"),
             default=None)
    if t0 is None:
        return None
    t1 = t0 + window_s * 1e6
    return t0, t1, [(e["name"], e["ts"], e["ts"] + e["dur"])
                    for e in evs if e["ts"] < t1 and e["ts"] + e["dur"] > t0]
