"""Shared plumbing for the CLI drivers (the ``test/*.cpp`` role).

Every reference test binary begins with ``DSM::getInstance`` +
``registerThread`` + ``new Tree`` (e.g. ``test/benchmark.cpp:253-266``);
this module is that prologue: platform selection, cluster construction,
and tree/engine setup from CLI-ish knobs.
"""

from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def setup_platform(n_nodes: int):
    """Pick the backend: the real devices by default (the chips, one node
    each).  Only an explicit CPU request — ``SHERMAN_PLATFORM=cpu`` or
    ``JAX_PLATFORMS=cpu``, as the tests and CI lanes make — selects an
    n-node virtual CPU mesh (the in-process multi-node backend, SURVEY.md
    §4's fake-transport lesson).  Fewer devices than nodes is an error,
    never a fall back to the CPU.  Must run before the first jax device
    query — a devices() call initializes the backend and freezes
    XLA_FLAGS."""
    platform = (os.environ.get("SHERMAN_PLATFORM")
                or os.environ.get("JAX_PLATFORMS", ""))
    if platform == "cpu":
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + f" --xla_force_host_platform_device_count={n_nodes}"
            ).strip()
    import jax
    if platform:
        jax.config.update("jax_platforms", platform)
    devs = jax.devices()
    if len(devs) < n_nodes:
        raise RuntimeError(
            f"{n_nodes} nodes need {n_nodes} devices, but "
            f"{devs[0].platform} has {len(devs)}: run on a host with "
            f"{n_nodes} chips, or ask for the CPU mesh explicitly "
            "(SHERMAN_PLATFORM=cpu)")
    return jax


def build_cluster(*args, **kw):
    """-> (cluster, tree, engine): the library's one construction rule
    (imported late: the platform is picked first)."""
    from sherman_tpu.cluster import build_engine
    return build_engine(*args, **kw)


def pages_for_keys(n_keys: int, fill: float = 0.75) -> int:
    """The library's sizing rule with the drivers' 2^12-page floor (their
    CPU-mesh runs are small)."""
    from sherman_tpu.cluster import pages_for_keys as pages
    return pages(n_keys, fill, min_pages=1 << 12)


class AdmissionPacer:
    """The round-6 ``perf_counter_ns`` SLEEP+SPIN admission pacer, in ONE
    copy shared by ``tools/latency_bench.py`` and ``tools/serve_bench.py``
    (the open-loop drivers' wall-clock schedule).

    ms-granularity ``time.sleep`` cannot pace sub-ms periods — the
    round-5 16 K latency row sat below the host's ADMISSION floor purely
    because sleep() quantizes at ~1-16 ms.  The hybrid sleeps until
    ``spin_ns`` before each deadline, then spins on the ns clock.  The
    spin budget is capped at HALF the period, so pacing can never eat a
    whole core busy-waiting.

    Every admission's pacing error (dispatch time − due time) is
    recorded; :meth:`jitter_receipt` publishes the p50/p99 percentiles
    plus an ``adm_feasible`` verdict (p99 error small against the
    period) — a row/phase whose jitter rivals its period was NOT paced
    at the offered rate, and the receipt says so instead of a prose
    rejection note.

    Usage::

        pacer = AdmissionPacer(period_s, spin_ms=2.0)
        pacer.start()                 # schedule anchored 2 periods out
        for i in range(n):
            pacer.wait_turn(i)        # blocks until deadline i
            ... dispatch ...
            pacer.absorb_stall(i + 1, cap_ns)  # optional: re-anchor
                                      # after an OBSERVER stall
                                      # (ns-capped — see the
                                      # coordinated-omission note)

    Thread contract: one pacer paces ONE admission stream (per-thread
    instances for multi-tenant drivers); ``jitter_receipt`` may merge
    several pacers' errors via ``merge_errors``.
    """

    def __init__(self, period_s: float, spin_ms: float = 2.0):
        import time
        assert period_s > 0
        self._clock = time.perf_counter_ns
        self._sleep = time.sleep
        self.period_ns = int(period_s * 1e9)
        # duty-cycle bound: never spin more than half the period
        self.spin_ns = int(min(spin_ms * 1e6, 0.5 * self.period_ns))
        self.errors_ns: list[int] = []
        self._t_base: int | None = None

    def start(self, lead_periods: int = 2) -> None:
        """Anchor the schedule ``lead_periods`` periods from now (slack
        for the first dispatch's setup)."""
        self._t_base = self._clock() + lead_periods * self.period_ns

    def due_ns(self, i: int) -> int:
        assert self._t_base is not None, "call start() first"
        return self._t_base + i * self.period_ns

    def wait_turn(self, i: int) -> int:
        """Block (sleep, then spin) until deadline ``i``; returns and
        records the pacing error in ns (>= 0: late dispatch)."""
        due = self.due_ns(i)
        now = self._clock()
        if now < due - self.spin_ns:
            self._sleep((due - self.spin_ns - now) / 1e9)
        while True:
            now = self._clock()
            if now >= due:
                break
        err = now - due
        self.errors_ns.append(err)
        return err

    def absorb_stall(self, next_i: int, cap_ns: int) -> None:
        """Re-anchor the schedule by at most ``cap_ns`` after an
        OBSERVER stall (a blocking drain on the measurement path).
        Uncapped re-anchoring would reintroduce coordinated omission —
        genuine service backlog must keep accumulating; only the
        observation cost is forgiven (latency_bench caps at the
        calibrated sync RTT)."""
        lag = self._clock() - self.due_ns(next_i)
        if lag > 0:
            self._t_base += min(lag, cap_ns)

    def merge_errors(self, other: "AdmissionPacer") -> None:
        self.errors_ns.extend(other.errors_ns)

    def jitter_receipt(self, feasible_frac: float = 0.25) -> dict:
        """{adm_jitter_p50_ms, adm_jitter_p99_ms, adm_spin_budget_ms,
        adm_feasible, pacing} — each open-loop row/phase's
        admission-feasibility receipt."""
        import numpy as np
        errs = self.errors_ns or [0]
        p50 = float(np.percentile(errs, 50)) / 1e6
        p99 = float(np.percentile(errs, 99)) / 1e6
        return {
            "adm_jitter_p50_ms": round(p50, 3),
            "adm_jitter_p99_ms": round(p99, 3),
            "adm_spin_budget_ms": round(self.spin_ns / 1e6, 3),
            "adm_feasible": bool(
                p99 < feasible_frac * self.period_ns / 1e6),
            "pacing": "sleep+spin",
        }
