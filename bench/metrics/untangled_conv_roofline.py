"""``untangled_conv_roofline``: the Pallas kernels of
``kernels/untangled_conv`` against their roofline, in %.

For every launch in the window, the sites the plan routes to ``pallas``
at the launched bucket (``route_for_batch(b).path``) are taken with their
operations and least bytes (``convcount``, padded rows included, since
the kernel computes them); each contributes the larger of its compute and
memory bounds at the chip's peaks.  Their sum is divided by the summed
device time of the Pallas kernel events in the trace.  Nothing is
returned when no site routes to Pallas or no kernel event was traced."""
from bench import convcount, trace


def read(run):
    if run.trace is None or run.peaks is None:
        return None
    lo, hi = run.trace_window
    t_kernel = trace.kernel_ns(run.trace, lo, hi) / 1e9
    bound = sum(convcount.roofline_s(site, b, run.peaks)[0]
                for b, _ in run.launches for site in run.pallas_sites(b))
    if not bound or not t_kernel:
        return None
    return 100.0 * bound / t_kernel
