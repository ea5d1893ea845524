"""Device time of the collectives per execution of the serve program,
in ms (profiler trace): the trace's ``collective_s`` (all-to-all,
all-gather, all-reduce ops, averaged over the chips) over the serve's
executions in the window.  In the multi-node read cell these are the
routed page exchange's all-to-alls, the descent's pending-count
all-reduce and the verify program's receipt all-reduces."""

from benchmarks.trace import program


def read(run):
    m = program(run, "serve")
    if m is None:
        return None
    return 1e3 * run["trace"]["collective_s"] / m["n"]
