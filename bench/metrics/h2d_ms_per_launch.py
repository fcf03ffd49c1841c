"""``h2d_ms_per_launch.*``: the host's side of a launch's input, stacking
and padding the payloads and handing the batch to the device, the
program's ``huge2.launch.stack`` and ``huge2.launch.h2d`` spans per
launch of the window, in ms."""
from bench import program_trace


def read(run):
    pt = program_trace.load(run)
    if pt is None:
        return None
    return program_trace.per_launch_ms(
        pt, ["huge2.launch.stack", "huge2.launch.h2d"], *run.trace_window)
