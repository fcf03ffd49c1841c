"""``queue_wait_ms.*``: the mean wait of a request from its arrival to the
start of the launch that answered it, over the requests of the window's
launches, in ms: the ``wait_us_sum`` and ``live`` arguments of the
program's ``huge2.launch`` spans (``serving/control_plane.py``)."""
from bench import program_trace


def read(run):
    pt = program_trace.load(run)
    if pt is None:
        return None
    return program_trace.queue_wait_ms(pt, *run.trace_window)
