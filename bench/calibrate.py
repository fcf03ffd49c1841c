#!/usr/bin/env python3
"""Readings the limits, the bounds and the rate of a cell are set from,
many runs in one process on the chip (the benchmark's own runs never run
this).

    python3 bench/calibrate.py --workload dcgan-bulk --seeds 1,2,3 \\
        --seconds 3 [--variant program|int8|bf16-ref] [--out FILE]
    python3 bench/calibrate.py --workload dcgan-trickle --rates 2000,4000 \\
        --max-wait-ms 2 --buckets all --seconds 5 --seeds 7
    python3 bench/calibrate.py --workload dcgan-trickle --seeds 9 \\
        --seconds 1 --dump-trace DIR

``--variant``: ``program`` (the timed path as configured), ``int8`` (the
program's own int8-weight path: the lower-precision control), ``bf16-ref``
(the plain reference computed in bfloat16, served in the program's
place).  ``--rates`` sweeps the open-loop rate of the cell's mix (one line
per rate, with the requests still queued at the close).  ``--dump-trace``
keeps the traced run's trace and writes into DIR the profiler's
``.xplane.pb``, a summary of its planes and the reduced trace
(``Trace.to_json``).

Each run appends one JSON line to ``--out``: the seed, the variant, the
result line and the numbers compared.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import pathlib
import shutil
import sys
import time

T0 = time.perf_counter()
ROOT = pathlib.Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]
os.environ.setdefault("TPU_LOG_DIR", str(ROOT / ".bench_out" / "tpu_logs"))


def bf16_ref(ad, model):
    import jax.numpy as jnp
    return lambda serve, w: (lambda x: ad.reference(w, x, model,
                                                    dtype=jnp.bfloat16))


def dump_trace(run, out: pathlib.Path) -> None:
    from jax.profiler import ProfileData
    out.mkdir(parents=True, exist_ok=True)
    run.trace.to_json(out / f"{run.cell.name}.trace.json.gz")
    xp = next((ROOT / ".bench_out" / "trace").rglob("*.xplane.pb"))
    lines = [f"xplane {xp.stat().st_size} bytes"]
    for plane in ProfileData.from_file(str(xp)).planes:
        lines.append(f"PLANE {plane.name}")
        for line in plane.lines:
            evs = list(line.events)
            lines.append(f"  LINE {line.name!r} events={len(evs)}")
            seen = set()
            for e in evs:
                if e.name in seen or len(seen) >= 25:
                    continue
                seen.add(e.name)
                stats = [(k, str(v)[:160]) for k, v in e.stats]
                lines.append(f"    {e.name!r} dur={e.duration_ns} {stats}")
    (out / f"{run.cell.name}.planes.txt").write_text("\n".join(lines))
    shutil.copy(xp, out / f"{run.cell.name}.xplane.pb")


def main() -> int:
    from bench import harness
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--variant", default="program",
                    choices=("program", "int8", "bf16-ref"))
    ap.add_argument("--rates", default="")
    ap.add_argument("--max-wait-ms", type=float, default=None)
    ap.add_argument("--buckets", default=None)
    ap.add_argument("--trace", type=int, default=0)
    ap.add_argument("--dump-trace", default="")
    ap.add_argument("--out", default="chiprun_out/calibrate.jsonl")
    args = ap.parse_args()

    benchmark = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell = harness.find_cell(benchmark, args.workload, ROOT)
    if args.max_wait_ms is not None:
        cell.traffic = dataclasses.replace(cell.traffic,
                                           max_wait_ms=args.max_wait_ms)
    if args.buckets:
        from repro.core.plan import BATCH_BUCKETS
        cell.traffic = dataclasses.replace(
            cell.traffic, buckets=tuple(BATCH_BUCKETS)
            if args.buckets == "all" else
            tuple(int(b) for b in args.buckets.split(",")))
    over, swap = None, None
    if args.variant == "int8":
        over = {"wdtype": "int8"}
    elif args.variant == "bf16-ref":
        swap = bf16_ref(cell.adapter, cell.config["model"])
    out = ROOT / args.out
    out.parent.mkdir(parents=True, exist_ok=True)
    rates = [float(r) for r in args.rates.split(",") if r]
    seeds = [int(s) for s in args.seeds.split(",")]
    jobs = ([(s, r) for r in rates for s in seeds] if rates
            else [(s, None) for s in seeds])
    for n, (seed, rate) in enumerate(jobs):
        if rate is not None:
            cell.traffic = dataclasses.replace(cell.traffic, rate_rps=rate)
        t = time.perf_counter()
        res, run = harness.run_cell(
            cell, seed, args.seconds, bool(args.trace or args.dump_trace),
            root=ROOT, t_start=T0 if n == 0 else t,
            program_over=over, swap=swap)
        if args.dump_trace:
            dump_trace(run, ROOT / args.dump_trace)
        from bench import check
        refs = harness.reference_outputs(
            cell.adapter, run.weights, run.sample_xs, cell.config["model"],
            cell.config["check"].get("ref_block", 16),
            precision="highest")
        at_highest = check.numbers(run.sample_outs, refs, 0)
        line = {"workload": cell.name, "seed": seed, "variant": args.variant,
                "rate_rps": rate, "seconds": args.seconds,
                "pending_at_close": run.pending_at_close,
                "completed": run.completed, "window_s": run.window_s,
                "latency_p50_ms": _pct(run.latencies_s, 50),
                "latency_p99_ms": _pct(run.latencies_s, 99),
                "launch_hist": _hist(run.launches),
                "numbers": run.checked, "numbers_highest": at_highest,
                "result": res}
        with out.open("a") as f:
            f.write(json.dumps(line) + "\n")
        print(json.dumps({k: line[k] for k in (
            "seed", "variant", "rate_rps", "pending_at_close", "numbers",
            "numbers_highest")}
            | {"correct": res["correct"], "metrics": res["metrics"]}),
            flush=True)
    return 0


def _pct(values, p):
    import numpy as np
    return float(np.percentile(np.asarray(values) * 1e3, p)) if values else None


def _hist(launches):
    h = {}
    for b, _ in launches:
        h[b] = h.get(b, 0) + 1
    return h


if __name__ == "__main__":
    sys.exit(main())
