"""Percentile arithmetic of the benchmark.

``latency_stats`` is a copy of ``repro.serving.metrics.latency_stats``
(numpy's linear-interpolation percentiles over completed requests), kept
here so that a change to the program cannot change how the benchmark
reads it.
"""
from __future__ import annotations

import numpy as np

PERCENTILES = (50, 95, 99)


def latency_stats(latencies_s, *, window_s: float | None = None) -> dict:
    """``completed``, ``mean_ms``, ``p50_ms``/``p95_ms``/``p99_ms`` of
    per-request latencies in seconds; with ``window_s``, also
    ``throughput_rps`` = completed / window."""
    lat = np.asarray([float(v) for v in latencies_s], np.float64)
    out = {"completed": int(lat.size)}
    if lat.size:
        out["mean_ms"] = float(lat.mean() * 1e3)
        for p in PERCENTILES:
            out[f"p{p}_ms"] = float(np.percentile(lat, p) * 1e3)
    else:
        out["mean_ms"] = 0.0
        out.update({f"p{p}_ms": 0.0 for p in PERCENTILES})
    if window_s is not None:
        out["throughput_rps"] = (lat.size / window_s) if window_s > 0 else 0.0
    return out

