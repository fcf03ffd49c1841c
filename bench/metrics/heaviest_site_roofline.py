"""``heaviest_site_roofline``: the conv site with the most Pallas kernel
time in the window against its roofline, in %.

Kernel events are known by their ``pallas_call`` name and joined to the
configuration's sites (``sites(model)`` of the adapter) by the site's
``jax.named_scope`` in their op-name metadata (``program_trace``).  The
site's roofline time (``convcount.roofline_s``) at each launched bucket
that routes it to ``pallas``, summed over the window's launches, is
divided by its kernel time.  Nothing is returned when no kernel event
names a site: the whole-model share is ``untangled_conv_roofline``."""
from bench import convcount, program_trace


def read(run):
    pt = program_trace.load(run)
    if pt is None or run.peaks is None:
        return None
    lo, hi = run.trace_window
    names = [s.name for s in run.cell.adapter.sites(run.cell.config["model"])]
    by_site = program_trace.kernel_ns_by_site(pt, names, lo, hi)
    by_site.pop(None, None)
    if not by_site:
        return None
    site = max(by_site, key=by_site.get)
    bound = sum(convcount.roofline_s(s, b, run.peaks)[0]
                for b, _ in run.launches for s in run.pallas_sites(b)
                if s.name == site)
    return 100.0 * bound / (by_site[site] / 1e9) if bound else None
