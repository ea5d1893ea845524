"""Unit tests for sherman_tpu.utils (Timer.h / Debug.h parity)."""

import io
import time

from sherman_tpu.utils import Timer, spin_sleep_ns
from sherman_tpu.utils import debug


def test_timer_measures_elapsed():
    t = Timer()
    t.begin()
    time.sleep(0.01)
    ns = t.end()
    assert 5e6 < ns < 5e8


def test_timer_amortizes_over_loop():
    t = Timer()
    t.begin()
    time.sleep(0.01)
    total = t.end(1)
    per_loop = t.end(10)
    assert per_loop < total  # amortized over 10 loops


def test_timer_end_print_units(capsys):
    t = Timer()
    t.begin()
    t.end_print(label="x")
    assert "x: " in capsys.readouterr().out


def test_spin_sleep():
    t0 = time.perf_counter_ns()
    spin_sleep_ns(2_000_000)
    assert time.perf_counter_ns() - t0 >= 2_000_000


def test_debug_levels(monkeypatch, capsys):
    debug.set_level("info")
    debug.notify_info("hello %d", 7)
    debug.debug_item("hidden")
    out = capsys.readouterr().out
    assert "hello 7" in out
    assert "hidden" not in out
    debug.set_level("debug")
    debug.debug_item("visible")
    assert "visible" in capsys.readouterr().out
    debug.set_level("info")


def test_debug_error_to_stderr(capsys):
    debug.notify_error("boom %s", "x")
    assert "boom x" in capsys.readouterr().err


def test_device_trace_writes_profile(tmp_path):
    import jax
    import jax.numpy as jnp

    from sherman_tpu.obs import device_trace
    with device_trace(str(tmp_path)):
        jax.block_until_ready(jnp.arange(8) * 2)
    import os
    entries = [os.path.join(r, f) for r, _, fs in os.walk(tmp_path)
               for f in fs]
    assert entries  # some trace artifact was written
