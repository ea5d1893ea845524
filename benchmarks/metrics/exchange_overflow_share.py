"""Percent of descent reads that found their round-1 exchange bucket
full and were answered by the straggler loop instead (program counter):
the program's ``dsm.xchg_overflow_rows`` over ``dsm.read_ops``, from
the registry, over the run (warm-up and window).  None where the
program keeps no such counter or counted no read."""


def read(run):
    from sherman_tpu import obs
    snap = obs.snapshot()
    over, reads = snap.get("dsm.xchg_overflow_rows"), snap.get(
        "dsm.read_ops")
    if over is None or not reads:
        return None
    return 100.0 * over / reads
