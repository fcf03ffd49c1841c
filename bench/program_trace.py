"""What the program itself records in a traced run's profile.

``bench/trace.py`` reduces the profile to device intervals and the
benchmark's own ``bench.*`` spans.  This module re-reads the same
``.xplane.pb`` for what the serving path writes there:

- the host spans named ``huge2.*`` (``jax.profiler.TraceAnnotation`` in
  ``serving/control_plane.py`` and ``serving/image_batcher.py``) with
  their keyword arguments, which the profiler keeps as event stats;
- each device operation's scope: the op-name metadata of its HLO
  instruction (``jit(batched)/dc0/untangled_deconv/pallas_call:``), whose
  ``jax.named_scope`` component names the conv site.  A Pallas kernel is
  known by its ``pallas_call`` name, which names the HLO instruction
  (``%untangled_deconv.4 = f32[64,8,8,512]... custom-call(...)``);
- the program executions (the ``XLA Modules`` line), which bound how far
  the device's timeline is shifted against the host's (``clock_offset``).

A traced run leaves its profile under ``<root>/.bench_out/trace`` until
the next (``harness.run_cell``); ``load`` finds it there and returns
nothing unless its ``bench.window`` is the run's.  Everything after
reading works on plain tuples, so the reduction can be checked on a small
recorded trace (``ProgramTrace.to_json`` / ``from_json``).

    python3 -m bench.program_trace PROFILE.xplane.pb [--config FILE]

prints, for one traced run, the per-launch split of the host steps with
the device's idle time inside each, and the kernel time by site.
"""
from __future__ import annotations

import dataclasses
import functools
import gzip
import json
import pathlib
import re

from bench import trace as tracemod

PREFIX = "huge2."
LAUNCH = "huge2.launch"
# the launch's host steps in order, each a span inside ``huge2.launch``
STEPS = ("huge2.launch.stack", "huge2.launch.h2d", "huge2.launch.dispatch",
         "huge2.launch.wait", "huge2.launch.d2h")
MODULES_LINE = "XLA Modules"
# the stat of a device op's event metadata that holds its op-name metadata
SCOPE_STAT = "tf_op"
# the ``pallas_call`` names of kernels/untangled_conv.py as they name an
# XLA op: the HLO instruction, the kernel's name with a numeric suffix
KERNEL = re.compile(r"%?untangled_(?:conv|deconv)(?:_tiled)?(?:\.\d+)?\b")

Span = tuple[float, float, dict]          # start_ns, end_ns, args
Op = tuple[str, float, float, str]        # name, start_ns, end_ns, scope


@dataclasses.dataclass
class ProgramTrace:
    window: tuple[float, float]           # the bench.window event
    spans: dict[str, list[Span]]          # huge2.* span name -> spans
    ops: dict[int, list[Op]]              # device index -> its ops
    modules: dict[int, list[tuple[float, float]]]  # device -> executions

    def to_json(self, path: pathlib.Path) -> None:
        with gzip.open(path, "wt") as f:
            json.dump({"window": self.window, "spans": self.spans,
                       "ops": {str(k): v for k, v in self.ops.items()},
                       "modules": {str(k): v
                                   for k, v in self.modules.items()}}, f)

    @classmethod
    def from_json(cls, path: pathlib.Path) -> "ProgramTrace":
        with gzip.open(path, "rt") as f:
            raw = json.load(f)
        return cls(window=tuple(raw["window"]),
                   spans={k: [tuple(s) for s in v]
                          for k, v in raw["spans"].items()},
                   ops={int(k): [tuple(o) for o in v]
                        for k, v in raw["ops"].items()},
                   modules={int(k): [tuple(m) for m in v]
                            for k, v in raw["modules"].items()})


# -- the profile ---------------------------------------------------------------

def _varint(buf: bytes, i: int) -> tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        out |= (b & 0x7F) << shift
        i += 1
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf: bytes, lo: int, hi: int):
    """``(field number, value)`` of the protobuf message ``buf[lo:hi]``: an
    int for a varint, ``(start, end)`` of the bytes of a length-delimited
    field, ``None`` for a fixed-width one."""
    i = lo
    while i < hi:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            v, i = (i, i + n), i + n
        elif wire in (1, 5):
            v, i = None, i + (8 if wire == 1 else 4)
        else:
            raise ValueError(f"protobuf wire type {wire} at byte {i}")
        yield key >> 3, v


def _map_entry(buf: bytes, span) -> tuple[int, tuple[int, int]]:
    key, value = 0, (0, 0)
    for f, v in _fields(buf, *span):
        if f == 1:
            key = v
        elif f == 2:
            value = v
    return key, value


def _text(buf: bytes, span) -> str:
    return buf[span[0]:span[1]].decode("utf-8", "replace")


def op_scopes(path: pathlib.Path) -> dict[str, str]:
    """XLA op name -> its op-name metadata (stat ``tf_op``), over the device
    planes of the profile at ``path``.

    The profiler keeps this stat in each device plane's event metadata,
    which ``jax.profiler.ProfileData`` does not expose, so the few fields
    needed are decoded from the ``XSpace`` protobuf here (field numbers of
    ``xplane.proto``): ``XSpace.planes`` 1; ``XPlane.name`` 2,
    ``.event_metadata`` 4 and ``.stat_metadata`` 5 (maps: key 1, value 2);
    ``XEventMetadata.name`` 2, ``.stats`` 5; ``XStatMetadata.name`` 2;
    ``XStat.metadata_id`` 1, ``.str_value`` 5, ``.ref_value`` 7 (the id of
    a stat metadata whose name is the string)."""
    buf = pathlib.Path(path).read_bytes()
    out: dict[str, str] = {}
    for num, plane in _fields(buf, 0, len(buf)):
        if num != 1:
            continue
        name, events, stat_meta = "", [], []
        for f, v in _fields(buf, *plane):
            if f == 2:
                name = _text(buf, v)
            elif f == 4:
                events.append(v)
            elif f == 5:
                stat_meta.append(v)
        if not name.startswith(tracemod.DEVICE_PREFIX):
            continue
        stat_names = {}
        for entry in stat_meta:
            sid, meta = _map_entry(buf, entry)
            stat_names[sid] = next((_text(buf, v) for f, v in
                                    _fields(buf, *meta) if f == 2), "")
        scope_ids = {k for k, n in stat_names.items() if n == SCOPE_STAT}
        for entry in events:
            ename, scope = "", ""
            for f, v in _fields(buf, *_map_entry(buf, entry)[1]):
                if f == 2:
                    ename = _text(buf, v)
                elif f == 5:
                    stat = dict(_fields(buf, *v))
                    if stat.get(1) in scope_ids:
                        scope = (_text(buf, stat[5]) if 5 in stat
                                 else stat_names.get(stat.get(7), ""))
            if scope:
                out[ename] = scope
    return out


def read_xplane(path: pathlib.Path) -> ProgramTrace:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(path))
    scopes = op_scopes(path)
    window, spans, ops, modules = None, {}, {}, {}
    for plane in pd.planes:
        if plane.name.startswith(tracemod.DEVICE_PREFIX):
            dev = int(plane.name[len(tracemod.DEVICE_PREFIX):].split()[0])
            for line in plane.lines:
                if line.name == tracemod.OPS_LINE:
                    ops.setdefault(dev, []).extend(
                        (e.name, e.start_ns, e.end_ns, scopes.get(e.name, ""))
                        for e in line.events)
                elif line.name == MODULES_LINE:
                    modules.setdefault(dev, []).extend(
                        (e.start_ns, e.end_ns) for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(PREFIX):
                        spans.setdefault(e.name, []).append(
                            (e.start_ns, e.end_ns, dict(e.stats)))
                    elif e.name == "bench.window":
                        window = (e.start_ns, e.end_ns)
    for v in ops.values():
        v.sort(key=lambda o: o[1])
    for v in modules.values():
        v.sort()
    return ProgramTrace(window=window, spans=spans, ops=ops, modules=modules)


@functools.lru_cache(maxsize=1)
def _read_cached(path: str, mtime_ns: int, size: int) -> ProgramTrace:
    return read_xplane(pathlib.Path(path))


def load(run):
    """The program's trace of the traced run ``run``, or ``None`` when the
    run was not traced, left no profile, or the profile under
    ``<root>/.bench_out/trace`` (the root holds the cell's ``bench``
    directory) is another run's."""
    if run.trace_window is None:
        return None
    trace_dir = run.cell.bench_dir.parent / ".bench_out" / "trace"
    path = next(trace_dir.rglob("*.xplane.pb"), None)
    if path is None:
        return None
    st = path.stat()
    pt = _read_cached(str(path), st.st_mtime_ns, st.st_size)
    if pt.window is None or tuple(pt.window) != tuple(run.trace_window):
        return None
    return pt


# -- readings --------------------------------------------------------------------

def launches(pt: ProgramTrace, lo: float, hi: float) -> list[Span]:
    """The ``huge2.launch`` spans that start inside ``[lo, hi)``."""
    return [sp for sp in pt.spans.get(LAUNCH, ()) if lo <= sp[0] < hi]


def per_launch_ms(pt: ProgramTrace, names, lo: float, hi: float):
    """Over the launches that start in the window, the summed duration of
    the spans ``names`` carrying each launch's ``seq``, per launch, in ms;
    ``None`` without a launch."""
    seqs = {a["seq"] for _, _, a in launches(pt, lo, hi)}
    if not seqs:
        return None
    total = sum(e - s for n in names for s, e, a in pt.spans.get(n, ())
                if a.get("seq") in seqs)
    return total / len(seqs) / 1e6


def queue_wait_ms(pt: ProgramTrace, lo: float, hi: float):
    """Mean wait of a request from its arrival to its launch, over the
    requests of the launches that start in the window, in ms."""
    ls = launches(pt, lo, hi)
    live = sum(a["live"] for _, _, a in ls)
    if not live:
        return None
    return sum(a["wait_us_sum"] for _, _, a in ls) / live / 1e3


def is_kernel(name: str) -> bool:
    return KERNEL.match(name) is not None


def kernel_ns_by_site(pt: ProgramTrace, sites, lo: float, hi: float):
    """Device time of the Pallas kernel events inside ``[lo, hi]``, summed
    over devices, by the one site of ``sites`` that their scope names;
    key ``None`` for an event whose scope names none or several."""
    sites, acc = set(sites), {}
    for v in pt.ops.values():
        for name, s, e, scope in v:
            if e <= lo or s >= hi or not is_kernel(name):
                continue
            hit = [c for c in scope.split("/") if c in sites]
            key = hit[0] if len(hit) == 1 else None
            acc[key] = acc.get(key, 0.0) + min(e, hi) - max(s, lo)
    return acc


def clock_offset(pt: ProgramTrace):
    """``(least, most)`` ns by which the first device's events must be
    moved later for every program execution to lie between the start of
    its launch's dispatch and the end of its wait, executions and launches
    paired in order; ``None`` when their counts differ.  The profiler puts
    device events on the host's clock itself; a positive ``least`` is how
    far that placement runs early."""
    mods = pt.modules.get(min(pt.modules), []) if pt.modules else []
    disp = sorted(pt.spans.get("huge2.launch.dispatch", ()))
    wait = sorted(pt.spans.get("huge2.launch.wait", ()))
    if not mods or not len(mods) == len(disp) == len(wait):
        return None
    return (max(d[0] - m[0] for d, m in zip(disp, mods)),
            min(w[1] - m[1] for w, m in zip(wait, mods)))


def split(pt: ProgramTrace, lo: float, hi: float, shift: float = 0.0):
    """The host steps of the window's launches: per step, ms per launch and
    the part of it in which the first device was idle; the share of
    device-busy time inside launch spans; the share of launch time the
    steps cover.  ``shift`` ns moves the device's events later first."""
    ls = launches(pt, lo, hi)
    seqs = {a["seq"] for _, _, a in ls}
    n = max(1, len(ls))
    dev = min(pt.ops) if pt.ops else None
    busy = tracemod.merge((s + shift, e + shift)
                          for _, s, e, _ in pt.ops.get(dev, ()))
    out = {"launches": len(ls), "steps": {}}
    for name in ("huge2.schedule",) + STEPS:
        sp = [(s, e) for s, e, a in pt.spans.get(name, ())
              if a.get("seq") in seqs]
        total = sum(e - s for s, e in sp)
        idle = total - sum(tracemod.covered(busy, s, e) for s, e in sp)
        out["steps"][name] = {"ms": total / n / 1e6, "idle_ms": idle / n / 1e6}
    t_launch = sum(e - s for s, e, _ in ls)
    t_steps = sum(out["steps"][k]["ms"] for k in STEPS) * n * 1e6
    dev_busy = tracemod.covered(busy, lo, hi)
    inside = sum(tracemod.covered(busy, s, e)
                 for s, e in tracemod.merge((s, e) for s, e, _ in ls))
    out["launch_ms"] = t_launch / n / 1e6
    out["steps_cover_launch"] = t_steps / t_launch if t_launch else None
    out["busy_inside_launch"] = inside / dev_busy if dev_busy else None
    return out


def main(argv=None) -> int:
    import argparse
    from bench import convcount, harness, peaks
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("xplane")
    ap.add_argument("--config", default="bench/configs/dcgan-lsun64.json")
    ap.add_argument("--device-kind", default="TPU v5 lite")
    args = ap.parse_args(argv)
    pt = read_xplane(pathlib.Path(args.xplane))
    lo, hi = pt.window
    conf = json.loads(pathlib.Path(args.config).read_text())
    ad = harness.load_module(harness.BENCH_DIR / "models"
                             / f"{conf['kind']}.py", "bench_model")
    sites = {s.name: s for s in ad.sites(conf["model"])}
    pk = peaks.peaks_for(args.device_kind)
    buckets = [a["bucket"] for _, _, a in launches(pt, lo, hi)]
    by_site = kernel_ns_by_site(pt, sites, lo, hi)
    offset = clock_offset(pt)
    out = {"window_s": (hi - lo) / 1e9,
           "queue_wait_ms": queue_wait_ms(pt, lo, hi),
           "clock_offset_us": offset and [o / 1e3 for o in offset],
           "split": split(pt, lo, hi),
           "split_shifted": offset and split(pt, lo, hi, max(0, offset[0])),
           "kernel_ms_unattributed": by_site.pop(None, 0.0) / 1e6,
           "sites": {name: {
               "ms_per_launch": ns / max(1, len(buckets)) / 1e6,
               "roofline_pct": 100.0 * sum(
                   convcount.roofline_s(sites[name], b, pk)[0]
                   for b in buckets) / (ns / 1e9)}
               for name, ns in sorted(by_site.items())}}
    print(json.dumps(out, indent=1))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
