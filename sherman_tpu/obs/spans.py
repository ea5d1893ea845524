"""Span tracing: nested wall-clock spans on the device trace's clock.

:class:`SpanTracer` is the program's one span API.  Each span:

- while a profiler trace is active, opens a
  ``jax.profiler.TraceAnnotation`` of the same name and arguments, so
  it lands on the trace's host plane, on the line of the thread that
  ran it, beside the device's op and module events (untraced, the span
  costs one TraceMe check instead);
- adds to a per-name aggregate (count, total seconds) that keeps the
  whole history;
- appends one event to a bounded ring of the recent past, exported as
  Chrome-trace-event JSON (``"X"`` complete events with microsecond
  timestamps, loadable in ``chrome://tracing`` and Perfetto) — the
  flight recorder's black-box bundle carries it;
- tells the ``on_close`` subscriber (the flight recorder's feed).

A span opened with ``hot=True`` belongs to a loop that runs hundreds of
times a second (the front door's per-step ``serve.*`` spans).  It
reaches the aggregates and the ring only while a profiler trace is
active, and never ``on_close``: untraced it records nothing, and a busy
or idle server evicts nothing from the ring.

Recording is thread-safe: each thread counts its own nesting depth
(``threading.local``), completed spans append under one lock.

:func:`device_trace` (the XLA/TPU profiler capture) also lives here.
"""

from __future__ import annotations

import contextlib
import json
import os
import threading
from collections import defaultdict, deque
from time import perf_counter

from jax.profiler import TraceAnnotation

#: ``True`` while a profiler trace records (one static TraceMe check)
_trace_active = TraceAnnotation.is_enabled

__all__ = ["SpanTracer", "device_trace", "get_tracer", "span"]


class _Depth(threading.local):
    depth = 0   # open spans of this thread


class _Span:
    """One span (the context manager :meth:`SpanTracer.span` returns).
    The front door opens ~10 a step, so opening and closing one keeps
    to a few calls."""

    __slots__ = ("_tr", "_name", "_args", "_hot", "_ann", "_t0")

    def __init__(self, tr: "SpanTracer", name: str, hot: bool, args: dict):
        self._tr = tr
        self._name = name
        self._hot = hot
        self._args = args

    def __enter__(self) -> "SpanTracer":
        if _trace_active():
            ann = self._ann = TraceAnnotation(self._name, **self._args)
            ann.__enter__()
        else:
            self._ann = None    # an inactive TraceMe records nothing
            if self._hot:
                self._t0 = None     # no record at exit
                return self._tr
        self._tr._tls.depth += 1
        self._t0 = perf_counter()
        return self._tr

    def __exit__(self, *exc) -> bool:
        if self._t0 is None:
            return False
        t1 = perf_counter()
        if self._ann is not None:
            self._ann.__exit__(*exc)
        tr = self._tr
        tls = tr._tls
        tls.depth -= 1
        tr._record(self._name, self._t0, t1, tls.depth, self._args or None,
                   notify=not self._hot)
        return False


class SpanTracer:
    """Nested spans, thread-safe, on the profiler's clock.

    Each completed span records (name, start_s, dur_s, tid, depth,
    args); nesting depth is counted per thread, so concurrent threads
    (the front door's dispatcher and its clients) never corrupt each
    other's spans.  The event list is a ring of the newest
    ``max_events`` spans; ``dropped`` counts the older ones it
    overwrote.  The aggregates behind :meth:`summary` see every span.
    """

    def __init__(self, max_events: int = 1 << 18):
        self._lock = threading.Lock()
        self._events: deque = deque(maxlen=max_events)
        self._agg = defaultdict(lambda: [0, 0.0])  # name -> [n, total_s]
        self._tls = _Depth()
        self._t0 = perf_counter()
        self.max_events = max_events
        self.dropped = 0
        # optional span-close subscriber (name, dur_s, depth) — the
        # flight recorder's feed; called OUTSIDE the lock
        self.on_close = None

    def span(self, name: str, *, hot: bool = False, **args) -> _Span:
        """``with tracer.span("serve.prep", step=k): ...``; ``hot=True``
        for a span of a loop hundreds of times a second (recorded only
        while a profiler trace is active, never sent to ``on_close``)."""
        return _Span(self, name, hot, args)

    def record(self, name: str, seconds: float) -> None:
        """After-the-fact record: the span ends now and lasted
        ``seconds`` (it reaches the aggregates and the ring, not a
        profiler trace)."""
        t1 = perf_counter()
        self._record(name, t1 - float(seconds), t1, self._tls.depth, None)

    def _record(self, name, t0, t1, depth, args, notify=True) -> None:
        tid = threading.get_ident()
        # an after-the-fact record() may claim a start BEFORE the
        # tracer's epoch; clip the exported event to the trace window
        # (negative ts breaks the Chrome trace-event contract) while
        # the aggregate keeps the true duration
        e0 = t0 if t0 > self._t0 else self._t0
        with self._lock:
            a = self._agg[name]
            a[0] += 1
            a[1] += t1 - t0
            if len(self._events) == self.max_events:
                self.dropped += 1
            self._events.append((name, e0 - self._t0, t1 - e0, tid,
                                 depth, args))
        cb = self.on_close
        if notify and cb is not None:
            cb(name, t1 - t0, depth)

    # -- views ---------------------------------------------------------------

    def summary(self) -> dict[str, dict[str, float]]:
        """Per-name aggregate over the whole history: ``{name: {"n",
        "total_s", "mean_ms"}}``."""
        with self._lock:
            return {name: {"n": n, "total_s": tot,
                           "mean_ms": tot / n * 1e3}
                    for name, (n, tot) in self._agg.items()}

    def report(self) -> str:
        lines = []
        for name, s in sorted(self.summary().items(),
                              key=lambda kv: -kv[1]["total_s"]):
            lines.append(f"{name:24s} n={s['n']:<6d} "
                         f"total={s['total_s']:8.3f}s "
                         f"mean={s['mean_ms']:8.3f}ms")
        return "\n".join(lines)

    def chrome_trace(self) -> dict:
        """Chrome-trace-event JSON object of the ring, oldest first:
        ``{"traceEvents": [...]}``.

        Complete ("X") events with microsecond timestamps; one pid
        (this process), tids preserved so multi-threaded drivers render
        as parallel tracks.  Load in chrome://tracing or Perfetto.
        """
        pid = os.getpid()
        with self._lock:
            events = list(self._events)
        trace_events = [
            {"name": name, "ph": "X", "pid": pid, "tid": tid,
             "ts": round(start * 1e6, 3), "dur": round(dur * 1e6, 3),
             "cat": "sherman_tpu",
             **({"args": args} if args else {})}
            for name, start, dur, tid, _depth, args in events
        ]
        meta = {"dropped_events": self.dropped} if self.dropped else {}
        return {"traceEvents": trace_events, "displayTimeUnit": "ms",
                "otherData": {"tracer": "sherman_tpu.obs", **meta}}

    def export_chrome(self, path: str) -> str:
        """Write :meth:`chrome_trace` to ``path``; returns the path."""
        d = os.path.dirname(os.path.abspath(path))
        os.makedirs(d, exist_ok=True)
        with open(path, "w") as f:
            json.dump(self.chrome_trace(), f)
        return path

    def reset(self) -> None:
        with self._lock:
            self._events.clear()
            self._agg.clear()
            self.dropped = 0
            self._t0 = perf_counter()


@contextlib.contextmanager
def device_trace(log_dir: str):
    """Capture an XLA device trace for the enclosed block.

    View with TensorBoard's profile plugin or Perfetto.  No-op overhead
    outside the block; inside, the runtime records kernel/DMA timelines
    and every :func:`span` the process opens.
    """
    import jax
    with jax.profiler.trace(log_dir):
        yield


# -- process-wide default tracer ---------------------------------------------

_TRACER = SpanTracer()


def get_tracer() -> SpanTracer:
    return _TRACER


#: ``obs.span(name, **args)``: a span on the default tracer — the one
#: instrumentation sites use
span = _TRACER.span
