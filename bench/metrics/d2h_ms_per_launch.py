"""``d2h_ms_per_launch.*``: copying a launch's output to the host and
slicing off the padded rows, the program's ``huge2.launch.d2h`` spans per
launch of the window, in ms."""
from bench import program_trace


def read(run):
    pt = program_trace.load(run)
    if pt is None:
        return None
    return program_trace.per_launch_ms(pt, ["huge2.launch.d2h"],
                                       *run.trace_window)
