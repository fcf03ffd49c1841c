"""From the profiler's trace to intervals, and from intervals to shares.

``read_xplane`` takes the ``.xplane.pb`` that ``jax.profiler`` writes and
keeps two things: the operations that ran on each TPU device (name,
start, end, and whether it is a Pallas kernel) and the benchmark's own
host spans (``TraceAnnotation`` names starting ``bench.``).  Everything
after that works on plain tuples, so the reduction can be checked on a
small recorded trace (``Trace.to_json`` / ``Trace.from_json``).
"""
from __future__ import annotations

import dataclasses
import gzip
import json
import pathlib

OPS_LINE = "XLA Ops"
DEVICE_PREFIX = "/device:TPU:"
HOST_PREFIX = "bench."


@dataclasses.dataclass
class Trace:
    # device index -> [(name, start_ns, end_ns, is_kernel)]
    ops: dict[int, list[tuple[str, float, float, bool]]]
    # span name -> [(start_ns, end_ns)]
    spans: dict[str, list[tuple[float, float]]]

    def to_json(self, path: pathlib.Path) -> None:
        with gzip.open(path, "wt") as f:
            json.dump({"ops": {str(k): v for k, v in self.ops.items()},
                       "spans": self.spans}, f)

    @classmethod
    def from_json(cls, path: pathlib.Path) -> "Trace":
        with gzip.open(path, "rt") as f:
            raw = json.load(f)
        return cls(ops={int(k): [tuple(e) for e in v]
                        for k, v in raw["ops"].items()},
                   spans={k: [tuple(s) for s in v]
                          for k, v in raw["spans"].items()})


def _stat_text(event) -> str:
    parts = []
    for k, v in event.stats:
        parts.append(f"{k}={v}")
    return " ".join(parts)


def is_pallas(name: str, stats: str) -> bool:
    """A Mosaic kernel shows as an HLO custom call whose target is
    ``tpu_custom_call``; the served programs hold no other custom call,
    so an op named or categorised as a custom call counts too."""
    return ("tpu_custom_call" in stats or "tpu_custom_call" in name
            or name.startswith("custom-call")
            or "hlo_category=custom-call" in stats)


def read_xplane(path: pathlib.Path) -> Trace:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(path))
    ops: dict[int, list] = {}
    spans: dict[str, list] = {}
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            dev = int(plane.name[len(DEVICE_PREFIX):].split()[0])
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                ops.setdefault(dev, []).extend(
                    (e.name, e.start_ns, e.end_ns,
                     is_pallas(e.name, _stat_text(e)))
                    for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(HOST_PREFIX):
                        spans.setdefault(e.name, []).append(
                            (e.start_ns, e.end_ns))
    for v in ops.values():
        v.sort(key=lambda e: e[1])
    return Trace(ops=ops, spans=spans)


def merge(intervals) -> list[tuple[float, float]]:
    """Union of ``(start, end)`` intervals as sorted disjoint intervals."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def covered(merged, lo: float, hi: float) -> float:
    """Length of ``[lo, hi]`` covered by merged disjoint intervals."""
    total = 0.0
    for s, e in merged:
        if e <= lo:
            continue
        if s >= hi:
            break
        total += min(e, hi) - max(s, lo)
    return total


def gaps(merged, lo: float, hi: float) -> list[tuple[float, float]]:
    """The stretches of ``[lo, hi]`` that no merged interval covers."""
    out, t = [], lo
    for s, e in merged:
        if e <= lo:
            continue
        if s >= hi:
            break
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if t < hi:
        out.append((t, hi))
    return out


def window(trace: Trace) -> tuple[float, float]:
    """The measured window: the one ``bench.window`` span."""
    (w,) = trace.spans["bench.window"]
    return w


def busy(trace: Trace, lo: float, hi: float) -> float:
    """Device-busy ns in ``[lo, hi]``, averaged over the devices traced."""
    if not trace.ops:
        return 0.0
    return sum(covered(merge((s, e) for _, s, e, _ in v), lo, hi)
               for v in trace.ops.values()) / len(trace.ops)


def kernel_ns(trace: Trace, lo: float, hi: float) -> float:
    """Summed device time of the Pallas kernel events inside ``[lo, hi]``
    (summed over devices)."""
    return sum(min(e, hi) - max(s, lo)
               for v in trace.ops.values() for _, s, e, k in v
               if k and e > lo and s < hi)


def top_ops(trace: Trace, lo: float, hi: float, k: int = 10):
    """The ``k`` device operations with the most summed time in the
    window, as ``[name, seconds]``."""
    acc: dict[str, float] = {}
    for v in trace.ops.values():
        for name, s, e, _ in v:
            if e > lo and s < hi:
                acc[name] = acc.get(name, 0.0) + (min(e, hi) - max(s, lo))
    top = sorted(acc.items(), key=lambda kv: -kv[1])[:k]
    return [[n, t / 1e9] for n, t in top]


def idle_by_label(trace: Trace, lo: float, hi: float, k: int = 10):
    """Device-idle time in the window, summed by what the host was doing,
    as ``[label, seconds]`` (device 0): ``launch`` (inside the backend's
    launch: stack, pad, host-to-device, dispatch, device-to-host, slice),
    ``pump`` (the control plane's scheduling outside a launch),
    ``submit`` (admission of new requests) or ``loadgen`` (none of them:
    the generator waiting for the next arrival)."""
    dev = min(trace.ops) if trace.ops else None
    merged = merge((s, e) for _, s, e, _ in trace.ops.get(dev, ()))
    spans = {n: merge(v) for n, v in trace.spans.items()}
    acc = {"launch": 0.0, "pump": 0.0, "submit": 0.0, "loadgen": 0.0}
    for g0, g1 in gaps(merged, lo, hi):
        n_launch = covered(spans.get("bench.launch", ()), g0, g1)
        n_pump = covered(spans.get("bench.pump", ()), g0, g1)  # holds launch
        n_submit = covered(spans.get("bench.submit", ()), g0, g1)
        acc["launch"] += n_launch
        acc["pump"] += max(0.0, n_pump - n_launch)
        acc["submit"] += n_submit
        acc["loadgen"] += max(0.0, (g1 - g0) - max(n_pump, n_launch)
                              - n_submit)
    top = sorted(((n, t) for n, t in acc.items() if t > 0),
                 key=lambda kv: -kv[1])[:k]
    return [[n, t / 1e9] for n, t in top]
