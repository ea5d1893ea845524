"""Mean wait of a served read request between its submit and the
dispatch of the step it rode in, in ms (program counter): the
program's ``serve.queue_wait_ms`` histogram, one value per answered
read request.  The loop hands over ``prep.host_ms`` alone by window, so
the reader takes the program's registry, which covers the run, not the
window: the window's requests and the few (at most one a client)
answered in the drain after it."""


def read(run):
    h = run.get("counters", {}).get("serve.queue_wait_ms")
    if h is None:
        from sherman_tpu import obs
        h = obs.histogram("serve.queue_wait_ms").snapshot()
    if not h or h["count"] <= 0:
        return None
    return h["sum"] / h["count"]
