"""``host_ms_per_launch.*``: per control-plane pump that launched, its
host span minus the device-busy time inside it, averaged over those
pumps, in ms: the host's own cost of one launch (scheduling, stacking,
host-to-device and device-to-host copies, slicing)."""
from bench import trace


def read(run):
    if run.trace is None or not run.trace.ops:
        return None
    pumps = run.trace.spans.get("bench.pump", [])
    if not pumps:
        return None
    dev = min(run.trace.ops)
    merged = trace.merge((s, e) for _, s, e, _ in run.trace.ops[dev])
    host = [(e - s) - trace.covered(merged, s, e) for s, e in pumps]
    return sum(host) / len(host) / 1e6
