"""The one traffic generator: a mix is a JSON file of parameters under
``bench/traffic/``, read here.  Keys:

- ``loop``: ``"closed"`` (clients that each wait for their answer before
  sending the next request) or ``"open"`` (arrivals on a schedule,
  whatever the server does).
- ``buckets``: the batch buckets the served model registers and warms:
  a list of sizes, ``"all"`` (every plan bucket) or ``"largest"``.
- ``max_wait_ms``: the batcher's coalescing wait.
- closed: ``outstanding_buckets``, the requests kept in flight as a
  multiple of the largest registered bucket.
- open: ``rate_rps``, the Poisson rate; optional ``burst``
  (``{"every_s": s, "size": n}``: ``n`` extra requests arrive together
  every ``s`` seconds); ``drain_s``, how long after the window the
  requests still queued may take before they count as failed.

Every seed gets the same set of gaps (the quantiles of the exponential
distribution at ``rate_rps``), in an order drawn from the seed, so two
seeds offer the same work and differ only in its order.
"""
from __future__ import annotations

import dataclasses
import json
import pathlib
from typing import Optional, Sequence

import numpy as np


@dataclasses.dataclass(frozen=True)
class Traffic:
    name: str
    loop: str
    buckets: tuple[int, ...]
    max_wait_ms: float
    outstanding_buckets: int = 0
    rate_rps: float = 0.0
    burst: Optional[dict] = None
    drain_s: float = 2.0

    @property
    def outstanding(self) -> int:
        return self.outstanding_buckets * self.buckets[-1]


def load_traffic(path: pathlib.Path, plan_buckets: Sequence[int]) -> Traffic:
    """Parse one mix file; ``plan_buckets`` resolves ``"all"`` and
    ``"largest"`` against the engine's bucket set."""
    raw = json.loads(pathlib.Path(path).read_text())
    b = raw.get("buckets", "all")
    if b == "all":
        buckets = tuple(plan_buckets)
    elif b == "largest":
        buckets = (max(plan_buckets),)
    else:
        buckets = tuple(sorted(int(v) for v in b))
    loop = raw["loop"]
    if loop not in ("closed", "open"):
        raise ValueError(f"{path}: loop must be 'closed' or 'open'")
    t = Traffic(name=pathlib.Path(path).stem, loop=loop, buckets=buckets,
                max_wait_ms=float(raw.get("max_wait_ms", 2.0)),
                outstanding_buckets=int(raw.get("outstanding_buckets", 0)),
                rate_rps=float(raw.get("rate_rps", 0.0)),
                burst=raw.get("burst"),
                drain_s=float(raw.get("drain_s", 2.0)))
    if loop == "closed" and t.outstanding < 1:
        raise ValueError(f"{path}: a closed loop needs outstanding_buckets")
    if loop == "open" and t.rate_rps <= 0:
        raise ValueError(f"{path}: an open loop needs rate_rps > 0")
    return t


def open_schedule(t: Traffic, seconds: float, seed: int) -> np.ndarray:
    """Arrival offsets (s, ascending) of an open-loop window."""
    rng = np.random.default_rng(seed)
    n = int(round(t.rate_rps * seconds))
    q = (np.arange(n) + 0.5) / n
    gaps = -np.log1p(-q) / t.rate_rps
    rng.shuffle(gaps)
    arrivals = np.cumsum(gaps)
    if t.burst:
        every, size = float(t.burst["every_s"]), int(t.burst["size"])
        at = np.arange(every, seconds, every)
        arrivals = np.concatenate([arrivals, np.repeat(at, size)])
    arrivals.sort(kind="stable")
    return arrivals


def payload_indices(n: int, pool: int, seed: int) -> np.ndarray:
    """Which pool payload each of ``n`` requests carries."""
    return np.random.default_rng([seed, 1]).integers(0, pool, n)
