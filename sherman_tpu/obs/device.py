"""White-box device telemetry: compile ledger, HBM accounting, rooflines.

The SLO plane (:mod:`sherman_tpu.obs.slo`) measures the system from the
OUTSIDE — per-class walls and windowed rates — but attributes nothing to
the compiled programs that produce those walls.  Sherman's performance
argument is that every op is a fixed number of one-sided reads/writes
against known page layouts (PAPER.md §4-5), so each serve program has a
*computable* byte/flop floor; this module publishes it, plus the two
device-side hazards no black-box gauge can see:

- **Compile ledger** (:class:`CompileLedger`): every jit compilation is
  recorded as a structured entry ``{program label, abstract-shape
  signature, compile ms, count}``.  Compiles are observed two ways at
  once: a ``jax.monitoring`` duration listener (the
  ``backend_compile`` events)
  attributes compile *walls* to the program whose dispatch triggered
  them, and a per-program wrapper (:meth:`CompileLedger.wrap`, applied
  at the engine/staged jit-cache sites) detects the compile itself via
  the jit cache-size delta — the fallback that keeps detection working
  on toolchains where the event names are absent.  The **steady-state
  retrace detector**: after :meth:`CompileLedger.seal` (bench.py's
  ``run_windowed`` seals around every timed device-step window), ANY
  new compilation increments ``device.retraces``, emits a
  ``compile.retrace`` flight-recorder event, and auto-dumps the black
  box (env-gated + debounced, the degraded-entry contract) — the
  classic silent-retrace serving hazard becomes a red CI pin instead
  of a mystery p99 cliff.
- **HBM / live-buffer accountant** (:class:`MemoryAccountant`):
  weakref-bound byte sources (the DSM registers its pool/locks/
  counters, the journal and recovery plane their on-disk artifacts)
  published as ``device.hbm_*`` / ``device.host_*`` gauges with a peak
  watermark, plus per-program :func:`program_memory` —
  ``compiled.memory_analysis()`` through the AOT path, gracefully
  degrading to a typed ``{"available": False, "reason": ...}`` where
  the backend cannot answer.
- **Roofline receipts**: :func:`program_cost` (flops/bytes from
  ``lowered.cost_analysis()`` — no second backend compile) joined with
  a measured phase wall by :func:`roofline` into
  ``achieved_bytes_frac`` / ``achieved_flops_frac`` against the
  device's peak HBM bandwidth and peak flops
  (:func:`device_peaks`: known TPU generations by ``device_kind``; a
  TPU kind missing from the table is an error.  Off the chip,
  ``SHERMAN_PEAK_GBPS`` / ``SHERMAN_PEAK_TFLOPS`` may stand in for test
  rigs, and otherwise the fractions are left out rather than invented).

Process-wide default: :func:`get_ledger` / :func:`get_accountant`
register the ``device.`` pull collector on first access, so every
registry snapshot (and the Prometheus exposition) carries flat
``device.<stat>`` keys.  ``SHERMAN_DEVICE_OBS=0`` is the kill switch —
checked per dispatch, so the obs-on/off A/B needs no rebuild (the
wrapper then forwards straight to the program; the ledger goes dark).

Analysis compiles are **suppressed**: :func:`program_cost` /
:func:`program_memory` re-lower (and for memory, re-compile) through
the AOT path, which fires the same monitoring events as a real compile
— the suppression scope keeps the white-box instrument from reading
its own probe as a steady-state retrace.
"""

from __future__ import annotations

import os
import threading
import time

from sherman_tpu.errors import ShermanError
from sherman_tpu.obs import recorder as _recorder
from sherman_tpu.obs import registry as _registry

__all__ = [
    "DEVICE_OBS_ENV", "CompileLedger", "LedgeredProgram",
    "MemoryAccountant", "program_cost", "program_memory", "roofline",
    "rooflines", "device_peaks", "get_ledger", "get_accountant",
    "wrap_program", "enabled",
]

DEVICE_OBS_ENV = "SHERMAN_DEVICE_OBS"

# the jax.monitoring event that marks a real backend compile on this
# toolchain (/jax/core/compile/backend_compile_duration); tracing and
# MLIR-lowering events deliberately do NOT count — only the executable
# build is the retrace hazard's cost
_COMPILE_EVENT_TOKEN = "backend_compile"

# label charged for compiles the listener sees OUTSIDE any wrapped
# program's dispatch (host-API one-offs, third-party jits)
UNATTRIBUTED = "<unattributed>"


def enabled() -> bool:
    """The kill switch, checked per dispatch (one dict lookup) so the
    obs-on/off A/B toggles at runtime without rebuilding programs."""
    return os.environ.get(DEVICE_OBS_ENV, "1") != "0"


def _signature(args, kwargs=None) -> str:
    """Abstract-shape signature of a call: dtype[shape] per array leaf,
    the repr of everything else.  Computed only when a compile was
    detected — never on the per-dispatch hot path."""
    import jax

    parts = []
    leaves = jax.tree_util.tree_leaves((args, kwargs or {}))
    for a in leaves:
        shape = getattr(a, "shape", None)
        dtype = getattr(a, "dtype", None)
        if shape is not None and dtype is not None:
            parts.append(f"{jax.numpy.dtype(dtype).name}"
                         f"[{','.join(str(d) for d in shape)}]")
        else:
            parts.append(repr(a))
    return ",".join(parts)


def _abstractify(args):
    """Args -> ShapeDtypeStruct pytree for AOT re-lowering (analysis
    must not pin device buffers); non-array leaves pass through."""
    import jax

    def one(a):
        shape = getattr(a, "shape", None)
        dtype = getattr(a, "dtype", None)
        if shape is not None and dtype is not None:
            return jax.ShapeDtypeStruct(tuple(shape), dtype)
        return a

    return jax.tree_util.tree_map(one, args)


class _ProgramEntry:
    """One (label)'s ledger row: compile count/walls, the signatures
    that compiled, and the retraces charged to it post-seal."""

    __slots__ = ("label", "compiles", "compile_ms", "retraces",
                 "signatures", "avals", "fn_ref", "last_compile_t")

    def __init__(self, label: str):
        self.label = label
        self.compiles = 0
        self.compile_ms = 0.0
        self.retraces = 0
        self.signatures: dict[str, int] = {}   # sig -> compile count
        self.avals = None          # arg avals of the LAST compile
        self.fn_ref = None         # weakref to the jitted program
        self.last_compile_t = 0.0

    def snapshot(self) -> dict:
        return {
            "label": self.label,
            "compiles": self.compiles,
            "compile_ms": round(self.compile_ms, 3),
            "retraces": self.retraces,
            "signatures": dict(self.signatures),
        }


class LedgeredProgram:
    """Transparent wrapper around one jitted program: forwards every
    call (attributes, hashes and donation untouched — ``__getattr__``
    delegates), detects compiles via the jit cache-size delta, and
    reports them to the ledger with this program's label.  Cache the
    WRAPPER at the jit-cache site so program-identity pins
    (``step.jserve is eng._get_search_fanout(...)``) keep holding."""

    __slots__ = ("_fn", "label", "_ledger", "__weakref__")

    def __init__(self, ledger: "CompileLedger", label: str, fn):
        self._fn = fn
        self.label = label
        self._ledger = ledger

    @property
    def unwrapped(self):
        return self._fn

    def _cache_size(self):
        f = getattr(self._fn, "_cache_size", None)
        if f is None:
            return None
        try:
            return f()
        except Exception:
            return None

    def __call__(self, *args, **kwargs):
        led = self._ledger
        if not enabled():
            return self._fn(*args, **kwargs)
        n0 = self._cache_size()
        tok = led._enter(self.label)
        try:
            return self._fn(*args, **kwargs)
        finally:
            # detection runs even when the dispatch raises — a retraced
            # program that then fails is exactly the postmortem the
            # ledger exists for, and the monitoring events were already
            # credited to this frame
            ms, events = led._exit(tok)
            n1 = self._cache_size()
            # primary detection: the jit cache grew; fallback (no
            # _cache_size on this toolchain): a backend-compile event
            # landed inside this dispatch
            if (n1 is not None and n0 is not None and n1 > n0) \
                    or (n1 is None and events > 0):
                led._record_compile(self.label, ms, args, kwargs,
                                    self._fn)

    def __getattr__(self, name):
        return getattr(self._fn, name)

    def __repr__(self):
        return f"LedgeredProgram({self.label!r}, {self._fn!r})"


class CompileLedger:
    """Structured record of every observed jit compilation, with the
    post-``seal()`` steady-state retrace detector (module docstring).

    Thread model: entries mutate under one lock (compiles are rare);
    the per-dispatch cost when nothing compiles is a thread-local
    push/pop and one ``_cache_size()`` call.  The monitoring listener
    is process-wide and registered once (jax offers no unregister that
    spares other listeners), so :meth:`reset` zeroes state in place.
    """

    def __init__(self):
        self._lock = threading.Lock()
        self._entries: dict[str, _ProgramEntry] = {}
        self._tls = threading.local()
        self._sealed = 0          # nesting depth of seal() scopes
        self.retraces = 0
        self.seals = 0
        self._attached = False
        self._listener_live = [False]  # probed: events actually arrive

    # -- dispatch context (wrapper + listener attribution) -------------------

    def _enter(self, label: str):
        st = self._tls
        stack = getattr(st, "stack", None)
        if stack is None:
            stack = st.stack = []
        frame = {"label": label, "ms": 0.0, "events": 0}
        stack.append(frame)
        return frame

    def _exit(self, frame) -> tuple[float, int]:
        st = self._tls
        stack = getattr(st, "stack", ())
        if stack and stack[-1] is frame:
            stack.pop()
        return frame["ms"], frame["events"]

    def _suppressed(self) -> bool:
        return getattr(self._tls, "suppress", 0) > 0

    class _Suppress:
        def __init__(self, ledger):
            self._l = ledger

        def __enter__(self):
            tls = self._l._tls
            tls.suppress = getattr(tls, "suppress", 0) + 1

        def __exit__(self, *exc):
            self._l._tls.suppress -= 1

    def suppress(self):
        """Scope in which compiles are the instrument's own (AOT
        analysis) and must not be recorded — least of all as
        retraces."""
        return self._Suppress(self)

    # -- jax.monitoring listener ---------------------------------------------

    def attach(self) -> str:
        """Register the duration listener once; returns the active
        compile-detection source.  First registration reports
        ``"monitoring"`` optimistically; later calls report it only
        once a backend-compile event has ACTUALLY arrived — on a
        toolchain where jax.monitoring imports but the event name
        changed, the end-of-run ``compile_source`` honestly reads
        ``"wrapper"`` (cache-size detection, walls unattributed)
        instead of claiming attribution that never happened."""
        with self._lock:
            if self._attached:
                return "monitoring" if self._listener_live[0] else "wrapper"
            try:
                from jax import monitoring
                monitoring.register_event_duration_secs_listener(
                    self._on_duration)
                self._attached = True
                return "monitoring"
            except Exception:
                self._attached = True
                return "wrapper"

    def _on_duration(self, name: str, dur_s: float, **kw) -> None:
        if _COMPILE_EVENT_TOKEN not in name:
            return
        # the liveness probe: this toolchain's event names match
        self._listener_live[0] = True
        if not enabled():
            return
        if self._suppressed():
            return
        ms = dur_s * 1e3
        stack = getattr(self._tls, "stack", ())
        if stack:
            # inside a wrapped dispatch: the wrapper will record the
            # compile (with signature) when the call returns
            stack[-1]["ms"] += ms
            stack[-1]["events"] += 1
            return
        # outside any wrapped program: record here so NOTHING compiles
        # invisibly — the post-seal case is exactly the silent retrace
        self._record_compile(UNATTRIBUTED, ms, None, None, None)

    # -- recording -----------------------------------------------------------

    def _record_compile(self, label: str, ms: float, args, kwargs,
                        fn) -> None:
        if self._suppressed():
            return
        sig = _signature(args, kwargs) if args is not None else "?"
        with self._lock:
            e = self._entries.get(label)
            if e is None:
                e = self._entries[label] = _ProgramEntry(label)
            e.compiles += 1
            e.compile_ms += ms
            e.signatures[sig] = e.signatures.get(sig, 0) + 1
            e.last_compile_t = time.monotonic()
            if args is not None:
                try:
                    e.avals = (_abstractify(args),
                               _abstractify(kwargs or {}))
                except Exception:
                    e.avals = None
            if fn is not None:
                import weakref
                try:
                    e.fn_ref = weakref.ref(fn)
                except TypeError:
                    e.fn_ref = None
            tripped = self._sealed > 0
            if tripped:
                e.retraces += 1
                self.retraces += 1
        if tripped:
            # the serving hazard: a compile inside a sealed steady-state
            # window.  Flight event + env-gated debounced black-box dump
            # (the degraded-entry contract) — postmortems start from the
            # program and shape that retraced.
            _recorder.record_event("compile.retrace", program=label,
                                   signature=sig,
                                   compile_ms=round(ms, 3))
            _recorder.auto_dump("compile_retrace")

    # -- seal / steady state --------------------------------------------------

    def seal(self) -> None:
        """Enter steady state: warmup/drain is done, every program this
        loop dispatches has compiled — from here until :meth:`unseal`,
        ANY observed compilation is a retrace.  Nests (scopes stack)."""
        with self._lock:
            self._sealed += 1
            self.seals += 1

    def unseal(self) -> None:
        with self._lock:
            if self._sealed > 0:
                self._sealed -= 1

    @property
    def sealed(self) -> bool:
        return self._sealed > 0

    class _Sealed:
        def __init__(self, ledger):
            self._l = ledger

        def __enter__(self):
            self._l.seal()
            return self._l

        def __exit__(self, *exc):
            self._l.unseal()

    def sealed_scope(self):
        """``with ledger.sealed_scope(): <timed loop>`` — the bench
        run_windowed shape."""
        return self._Sealed(self)

    # -- wrapping -------------------------------------------------------------

    def wrap(self, label: str, fn):
        """Wrap a jitted program for the ledger.  Idempotent on an
        already-wrapped program (re-labeling would split its history)."""
        if isinstance(fn, LedgeredProgram):
            return fn
        return LedgeredProgram(self, label, fn)

    # -- views ----------------------------------------------------------------

    def entries(self) -> list[dict]:
        with self._lock:
            return [e.snapshot() for e in self._entries.values()]

    def entry(self, label: str) -> _ProgramEntry | None:
        with self._lock:
            return self._entries.get(label)

    def summary(self) -> dict:
        """The bench-JSON ledger block: totals + per-program entries."""
        with self._lock:
            entries = [e.snapshot() for e in self._entries.values()]
        return {
            "programs": len(entries),
            "compiles": sum(e["compiles"] for e in entries),
            "compile_ms_total": round(
                sum(e["compile_ms"] for e in entries), 3),
            "retraces": self.retraces,
            "sealed_windows": self.seals,
            "entries": sorted(entries, key=lambda e: -e["compile_ms"]),
        }

    def collect(self) -> dict:
        """Flat numbers for the ``device.`` pull collector."""
        with self._lock:
            n = len(self._entries)
            compiles = sum(e.compiles for e in self._entries.values())
            ms = sum(e.compile_ms for e in self._entries.values())
        return {
            "programs": n,
            "compiles": compiles,
            "compile_ms_total": round(ms, 3),
            "retraces": self.retraces,
            "sealed": int(self._sealed > 0),
        }

    def analyze(self, label: str, *, memory: bool = False) -> dict:
        """Cost (and optionally memory) analysis of a ledgered program
        from its captured compile-time avals — no arg plumbing at the
        call site.  Typed-unavailable when the program never compiled
        under the ledger or the backend cannot answer."""
        e = self.entry(label)
        if e is None:
            return {"available": False,
                    "reason": f"no ledger entry for {label!r}"}
        fn = e.fn_ref() if e.fn_ref is not None else None
        if fn is None or e.avals is None:
            return {"available": False,
                    "reason": f"{label!r}: program or avals not captured"}
        args, kwargs = e.avals
        out = program_cost(fn, *args, _ledger=self, **kwargs)
        if memory:
            out["memory"] = program_memory(fn, *args, _ledger=self,
                                           **kwargs)
        return out

    def reset(self) -> None:
        """Zero in place (test isolation); the process-wide listener
        registration and any wrapped programs stay live."""
        with self._lock:
            self._entries.clear()
            self.retraces = 0
            self.seals = 0
            self._sealed = 0


# -- per-program analysis (AOT path, suppressed) ------------------------------

def _unwrap(fn):
    return fn.unwrapped if isinstance(fn, LedgeredProgram) else fn


def program_cost(fn, *args, _ledger=None, **kwargs) -> dict:
    """flops/bytes of one program via ``lowered.cost_analysis()`` (no
    second backend compile) — or, on a TPU, where the lowered form
    answers None, the compiled executable's (the persistent compile
    cache absorbs that compile).  Graceful: any failure returns the typed
    ``{"available": False, "reason": ...}`` instead of raising — the
    receipts column reads "unavailable", the run does not die."""
    led = _ledger or get_ledger()
    try:
        with led.suppress():
            low = _unwrap(fn).lower(*args, **kwargs)
            ca = low.cost_analysis()
            if ca is None:
                ca = low.compile().cost_analysis()
        if isinstance(ca, (list, tuple)):   # per-partition form
            ca = ca[0] if ca else {}
        flops = float(ca.get("flops", 0.0) or 0.0)
        bytes_ = float(ca.get("bytes accessed", 0.0) or 0.0)
        return {"available": True, "flops": flops, "bytes": bytes_}
    except Exception as e:
        return {"available": False,
                "reason": f"{type(e).__name__}: {e}"}


def program_memory(fn, *args, _ledger=None, **kwargs) -> dict:
    """``compiled.memory_analysis()`` through the AOT path (this DOES
    pay a backend compile — the persistent compilation cache absorbs it
    on repeat runs).  Graceful typed-unavailable on backends that
    cannot answer."""
    led = _ledger or get_ledger()
    try:
        with led.suppress():
            m = _unwrap(fn).lower(*args, **kwargs).compile() \
                           .memory_analysis()
        out = {"available": True}
        for k in ("generated_code_size_in_bytes",
                  "argument_size_in_bytes", "output_size_in_bytes",
                  "alias_size_in_bytes", "temp_size_in_bytes"):
            v = getattr(m, k, None)
            if v is not None:
                out[k.replace("_size_in_bytes", "_bytes")] = int(v)
        return out
    except Exception as e:
        return {"available": False,
                "reason": f"{type(e).__name__}: {e}"}


# -- rooflines ----------------------------------------------------------------

# peak (HBM bytes/s, flops/s) by TPU device_kind substring — the roofline
# ceilings fractions are computed against.  Sources: published TPU specs
# (bf16 peak flops; HBM BW) — v5e: Google Cloud documentation "TPU v5e"
# (197 TFLOP/s bf16, 819 GB/s HBM).
_KNOWN_PEAKS = (
    ("v5p", 2765e9, 459e12),
    ("v5 lite", 819e9, 197e12),  # libtpu reports v5e as "TPU v5 lite"
    ("v5e", 819e9, 197e12),
    ("v6 lite", 1640e9, 918e12),  # ... and v6e/Trillium as "TPU v6 lite"
    ("v6e", 1640e9, 918e12),
    ("v4", 1228e9, 275e12),
    ("v3", 900e9, 123e12),
    ("v2", 700e9, 45e12),
)


class UnknownDeviceKindError(ShermanError, LookupError):
    """A TPU whose ``device_kind`` has no row in the peak table: a
    roofline fraction against a guessed peak would be a made-up number,
    so the table must learn the part first."""

    def __init__(self, kind: str):
        super().__init__(
            f"no peak HBM bandwidth / flops known for TPU device_kind "
            f"{kind!r}: add it to obs/device.py _KNOWN_PEAKS with its "
            "published source")
        self.kind = kind


def device_peaks(platform: str | None = None,
                 kind: str | None = None) -> dict:
    """{"bytes_per_s", "flops_per_s", "source"} for device 0 (or the
    given ``platform``/``kind``).  On a TPU the peaks come from the
    table only and an unknown kind raises :class:`UnknownDeviceKindError`.
    Off the chip (the CPU test mesh) there is no table: the
    ``SHERMAN_PEAK_GBPS`` / ``SHERMAN_PEAK_TFLOPS`` overrides stand in
    for test rigs, a malformed one is flagged in ``source``, and unset
    peaks stay None so fractions are omitted, never invented."""
    if platform is None or kind is None:
        import jax
        dev = jax.devices()[0]
        platform = dev.platform if platform is None else platform
        kind = dev.device_kind if kind is None else kind
    kind = kind.lower()
    if platform == "tpu":
        for token, tbw, tfl in _KNOWN_PEAKS:
            if token in kind:
                return {"bytes_per_s": tbw, "flops_per_s": tfl,
                        "source": f"device_kind:{kind}"}
        raise UnknownDeviceKindError(kind)
    notes = []

    def _env(var: str, scale: float):
        raw = os.environ.get(var)
        if not raw:
            return None
        try:
            return float(raw) * scale
        except ValueError:
            notes.append(f"bad-env:{var}")
            return None

    bw = _env("SHERMAN_PEAK_GBPS", 1e9)
    fl = _env("SHERMAN_PEAK_TFLOPS", 1e12)
    if bw is not None or fl is not None:
        notes.append("env")
    if bw is None or fl is None:
        notes.append(f"unknown:{kind or 'no-device'}")
    return {"bytes_per_s": bw, "flops_per_s": fl,
            "source": ";".join(notes)}


def roofline(cost: dict, wall_ms: float, peaks: dict | None = None) -> dict:
    """Join one program's flop/byte floor with its measured wall:
    achieved rates always, achieved FRACTIONS only when the device's
    peaks are known (``achieved_bytes_frac`` = achieved bytes/s over
    peak HBM bandwidth — Sherman's serve phases should live near the
    bytes roof, which is the whole paper's §4-5 claim made auditable).
    Typed-unavailable cost dicts pass through with the wall attached."""
    out = {"wall_ms": round(float(wall_ms), 3)}
    if not cost.get("available"):
        out["available"] = False
        out["reason"] = cost.get("reason", "cost analysis unavailable")
        return out
    wall_s = max(float(wall_ms), 1e-6) / 1e3
    flops, bytes_ = cost["flops"], cost["bytes"]
    out.update({
        "available": True,
        "flops": flops,
        "bytes": bytes_,
        "achieved_gbytes_s": round(bytes_ / wall_s / 1e9, 3),
        "achieved_gflops_s": round(flops / wall_s / 1e9, 3),
    })
    # a wall under the chained-delta resolution (~50 us) makes the
    # achieved rates measurement noise — keep them (flagged) but never
    # publish FRACTIONS from them: a noise-phase frac would whipsaw the
    # perfgate bytes-frac comparison round to round
    if float(wall_ms) < 0.05:
        out["wall_below_resolution"] = True
        return out
    peaks = peaks or device_peaks()
    pb, pf = peaks.get("bytes_per_s"), peaks.get("flops_per_s")
    if pb:
        out["achieved_bytes_frac"] = round(bytes_ / wall_s / pb, 4)
    if pf:
        out["achieved_flops_frac"] = round(flops / wall_s / pf, 4)
    if pb and pf:
        # which roof binds this program (its arithmetic intensity vs
        # the machine balance point)
        t_bytes = bytes_ / pb
        t_flops = flops / pf
        out["bound"] = "bytes" if t_bytes >= t_flops else "flops"
    return out


def rooflines(phase_ms: dict, phase_labels: dict, *,
              memory: bool = False, peaks: dict | None = None,
              ledger: "CompileLedger | None" = None) -> dict:
    """Per-phase roofline receipts: join a ``phase_profile``-shaped
    ``{phase: wall_ms}`` dict with the ledger entries named by
    ``phase_labels`` (``step.phase_labels`` on the staged factories).
    Phases without a label (the pipelined overlap-receipt keys) are
    skipped; unanalyzable programs carry the typed unavailable."""
    led = ledger or get_ledger()
    peaks = peaks or device_peaks()
    out = {}
    for phase, ms in phase_ms.items():
        label = phase_labels.get(phase)
        if label is None or not isinstance(ms, (int, float)):
            continue
        ana = led.analyze(label, memory=memory)
        rec = roofline(ana, ms, peaks)
        rec["program"] = label
        if memory and "memory" in ana:
            rec["memory"] = ana["memory"]
        out[phase] = rec
    return out


# -- memory accountant --------------------------------------------------------

class MemoryAccountant:
    """Named live-byte sources with a peak watermark.

    Sources are weakref-bound at the call sites (a dead DSM's pool must
    drop out, not pin device arrays); a source that raises reports 0
    for that snapshot (donated buffer mid-step — the registry
    collector-error contract).  ``kind`` splits the exposition:
    ``hbm`` sources are device-resident buffers (pool/locks/counters),
    ``host`` sources are host-side artifacts (journal, checkpoints).
    The watermark tracks the max TOTAL hbm bytes any snapshot saw."""

    def __init__(self):
        self._lock = threading.Lock()
        self._sources: dict[str, tuple[str, object]] = {}
        self.hbm_peak_bytes = 0

    def register(self, name: str, fn, *, kind: str = "hbm") -> None:
        """``fn() -> bytes``; re-registering a name replaces it (a
        rotated journal segment supersedes its ancestor)."""
        assert kind in ("hbm", "host"), kind
        with self._lock:
            self._sources[name] = (kind, fn)

    def unregister(self, name: str) -> None:
        with self._lock:
            self._sources.pop(name, None)

    def gauges(self) -> dict:
        """Flat ``{hbm_<name>_bytes, host_<name>_bytes, ...,
        hbm_total_bytes, hbm_peak_bytes}``; updates the watermark."""
        with self._lock:
            sources = list(self._sources.items())
        out: dict = {}
        hbm_total = 0
        for name, (kind, fn) in sources:
            try:
                v = int(fn())
            except Exception:
                v = 0
            out[f"{kind}_{name}_bytes"] = v
            if kind == "hbm":
                hbm_total += v
        out["hbm_total_bytes"] = hbm_total
        with self._lock:
            if hbm_total > self.hbm_peak_bytes:
                self.hbm_peak_bytes = hbm_total
            out["hbm_peak_bytes"] = self.hbm_peak_bytes
        return out

    def reset(self) -> None:
        with self._lock:
            self._sources.clear()
            self.hbm_peak_bytes = 0


# -- process-wide defaults ----------------------------------------------------

_LEDGER = CompileLedger()
_ACCOUNTANT = MemoryAccountant()
_REGISTERED = [False]


def _collect() -> dict:
    if not enabled():
        return {"enabled": 0}
    out = _LEDGER.collect()
    out.update(_ACCOUNTANT.gauges())
    out["enabled"] = 1
    return out


def _register() -> None:
    if not _REGISTERED[0]:
        _registry.register_collector("device", _collect)
        _REGISTERED[0] = True


def get_ledger() -> CompileLedger:
    """The default ledger, listener attached and registered as (half
    of) the ``device.`` pull collector on first access."""
    _register()
    if enabled():
        _LEDGER.attach()
    return _LEDGER


def get_accountant() -> MemoryAccountant:
    _register()
    return _ACCOUNTANT


def wrap_program(label: str, fn):
    """Module-level convenience for the jit-cache sites:
    ``fn = device.wrap_program("engine.search", jax.jit(...))``."""
    return get_ledger().wrap(label, fn)
