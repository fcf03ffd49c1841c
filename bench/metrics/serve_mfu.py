"""``serve_mfu``: the model operations of the images answered in the
traced window (counted from the geometry, ``convcount``, whatever
implements them) over the window's seconds times the chip's bf16 peak,
in %."""


def read(run):
    if run.trace is None or run.peaks is None:
        return None
    lo, hi = run.trace_window
    seconds = (hi - lo) / 1e9
    return 100.0 * run.completed * run.model_flops / (
        seconds * run.peaks["bf16_flops"])
