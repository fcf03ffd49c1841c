"""``schedule_ms_per_launch.*``: the control plane's scheduling of a
launch (EDF pick across models, class pick, bucket cover, taking and
shedding requests), the program's ``huge2.schedule`` spans per launch of
the window, in ms."""
from bench import program_trace


def read(run):
    pt = program_trace.load(run)
    if pt is None:
        return None
    return program_trace.per_launch_ms(pt, ["huge2.schedule"],
                                       *run.trace_window)
