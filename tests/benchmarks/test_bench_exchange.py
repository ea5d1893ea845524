"""The routed exchange's readers on synthetic run records: the exchange's
device time, its share of the ICI roof and the round-1 overflow share,
each None where the run has no trace or the program no counter."""

import pytest

from benchmarks import exchange_roofline, harness
from sherman_tpu import obs


def _run(trace=True, kind="TPU v5 lite"):
    """Four chips, 1.5 M unique rows a chip, 20 serve executions in
    2 s, 0.5 s of collectives."""
    return {
        "chips": 4, "unique_rows_per_step": 4 * 1.5e6,
        "device": {"kind": kind},
        "modules": {"serve": "jit_kernel", "prep": "jit_prep"},
        "trace": {"modules": {"jit_kernel": {"s": 2.0, "n": 20},
                              "jit_prep": {"s": 1.0, "n": 20}},
                  "collective_s": 0.5, "busy_s": 2.9, "window_s": 3.0}
        if trace else None,
    }


@pytest.fixture
def counters(monkeypatch):
    """``counters(**dsm)`` makes the registry's snapshot hold those
    ``dsm.*`` entries alone."""
    def put(**dsm):
        snap = {f"dsm.{k}": v for k, v in dsm.items()}
        monkeypatch.setattr(obs, "snapshot", lambda: snap)
    return put


def test_exchange_device_ms_per_step():
    read = harness.load_reader("exchange_device_ms_per_step")
    assert read(_run()) == pytest.approx(1e3 * 0.5 / 20)
    assert read(_run(trace=False)) is None
    run = _run()
    run["modules"]["serve"] = "jit_absent"
    assert read(run) is None


def test_exchange_ici_roofline(counters):
    read = harness.load_reader("exchange_ici_roofline")
    counters(read_ops=1000, xchg_remote_rows=750)
    # 1.5 M rows a chip x 3/4 remote x 17 B, in 25 ms, at 200 GB/s
    want = 100 * 1.5e6 * 0.75 * 17 / 0.025 / 200e9
    assert read(_run()) == pytest.approx(want)
    assert 0 < want < 100
    assert read(_run(trace=False)) is None
    with pytest.raises(exchange_roofline.UnknownDeviceError):
        read(_run(kind="cpu"))
    counters(read_ops=1000)              # a program without the slot
    assert read(_run()) is None
    counters(read_ops=1000, xchg_remote_rows=0)   # one node
    assert read(_run()) is None


def test_exchange_overflow_share(counters):
    read = harness.load_reader("exchange_overflow_share")
    counters(read_ops=1000, xchg_overflow_rows=10)
    assert read(_run()) == pytest.approx(1.0)
    counters(read_ops=1000, xchg_overflow_rows=0)
    assert read(_run()) == 0.0
    counters(read_ops=1000)
    assert read(_run()) is None
    counters(read_ops=0, xchg_overflow_rows=0)
    assert read(_run()) is None


def test_routed_read_bytes_and_peak():
    assert exchange_roofline.routed_read_bytes(1) == 8 + 9
    assert exchange_roofline.ici_bytes_s("TPU v5 lite") == 200e9
    assert exchange_roofline.share(200e9, 1.0, "TPU v5e") == \
        pytest.approx(100.0)
