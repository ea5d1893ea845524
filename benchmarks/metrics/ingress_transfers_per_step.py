"""Host<->device transfers of the front door's ingress per dispatched
step (program counter): the program's ``serve.h2d_puts`` plus
``serve.d2h_gets`` over its ``prep.host_ms`` dispatches.  The loop hands
over ``prep.host_ms`` alone by window, so the reader takes the
program's registry, which covers the run (calibration, window and
drain).  None where the program keeps no such counters."""


def read(run):
    from sherman_tpu import obs
    moved = (obs.counter("serve.h2d_puts").value
             + obs.counter("serve.d2h_gets").value)
    steps = obs.histogram("prep.host_ms").count
    if moved <= 0 or steps <= 0:
        return None
    return moved / steps
