"""Mean host time of the front door's completion of one read step, in
ms (program span): the dispatcher's ``serve.complete`` spans that lie
inside the served window (:func:`benchmarks.scopes.served_window`; a
step cut by the window's end is left out), each covering the wait for
the device and materialize, straggler rescue, and the futures'
answers."""


def read(run):
    from benchmarks import scopes
    w = scopes.served_window(run)
    if w is None:
        return None
    t0, t1, spans = w
    durs = [e - s for name, s, e in spans
            if name == "serve.complete" and t0 <= s and e <= t1]
    if not durs:
        return None
    return sum(durs) / len(durs) / 1e3
