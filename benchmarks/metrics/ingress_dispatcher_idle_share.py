"""Percent of the served window in which the front door's dispatcher
waited with nothing due (program span): ``serve.idle`` span time inside
the window over the window (:func:`benchmarks.scopes.served_window`,
anchored on the dispatcher's first ``serve.prep``).  High: the
closed-loop clients set the pace; low: the server does."""


def read(run):
    from benchmarks import scopes
    w = scopes.served_window(run)
    if w is None:
        return None
    t0, t1, spans = w
    idle = sum(min(e, t1) - max(s, t0) for name, s, e in spans
               if name == "serve.idle")
    return 100.0 * idle / (t1 - t0)
