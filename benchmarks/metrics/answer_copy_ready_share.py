"""Percent of the front door's completed ingress steps whose device
work was done when their completion began, so that the answer copy
started at launch had its data (program counter): the program's
``serve.answer_copy_ready`` over its ``serve.d2h_gets``, over the run
(calibration, window and drain).  None where the program keeps no such
counters."""


def read(run):
    from sherman_tpu import obs
    gets = obs.counter("serve.d2h_gets").value
    if gets <= 0:
        return None
    return 100.0 * obs.counter("serve.answer_copy_ready").value / gets
