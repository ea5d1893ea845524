"""Share of the chip's ICI roof the routed read exchange reaches: the
least bytes a chip moves a step for its remote rows
(benchmarks/exchange_roofline.py) over the exchange's device time per
serve execution (``exchange_device_ms_per_step``, profiler trace).
Remote rows a step: the run's unique rows per chip times the share of
descent reads whose page lives on another node (the program's
``dsm.xchg_remote_rows`` over ``dsm.read_ops``, registry).  None where
the program keeps no such counter."""

from benchmarks import exchange_roofline
from benchmarks.trace import program


def read(run):
    from sherman_tpu import obs
    m = program(run, "serve")
    if m is None or "unique_rows_per_step" not in run:
        return None
    snap = obs.snapshot()
    remote, reads = snap.get("dsm.xchg_remote_rows"), snap.get(
        "dsm.read_ops")
    xchg_s = run["trace"]["collective_s"] / m["n"]
    if not remote or not reads or xchg_s <= 0:
        return None
    rows = run["unique_rows_per_step"] / run["chips"] * remote / reads
    return exchange_roofline.share(exchange_roofline.routed_read_bytes(rows),
                                   xchg_s, run["device"]["kind"])
