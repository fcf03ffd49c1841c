"""The comparison that decides ``correct``.

The timed path's answers are compared with the plain float32 reference
of the model adapter, request by request: for each sampled request, the
largest absolute difference over its output, as a share of the largest
magnitude of the reference's output (``max|y - ref| / max|ref|``).  The
number held against the limit is the widest such gap over the sample
(``rel_err_max``).  Beside it, every answer must have come
(``unanswered``) and be finite (``nonfinite``), each with the limit 0.
"""
from __future__ import annotations

import numpy as np


class Reservoir:
    """A uniform sample of ``k`` answers out of a stream of unknown
    length, drawn from the seed (algorithm R): the same seed and the same
    completion order keep the same requests."""

    def __init__(self, k: int, seed: int):
        self.k = k
        self.rng = np.random.default_rng([seed, 2])
        self.seen = 0
        self.items: list[tuple[int, np.ndarray]] = []

    def offer(self, rid: int, out: np.ndarray) -> None:
        if self.seen < self.k:
            self.items.append((rid, np.array(out)))
        else:
            j = int(self.rng.integers(0, self.seen + 1))
            if j < self.k:
                self.items[j] = (rid, np.array(out))
        self.seen += 1


def rel_errors(outs: np.ndarray, refs: np.ndarray) -> np.ndarray:
    """Per-request ``max|y - ref| / max|ref|`` over a batch."""
    y = outs.reshape(len(outs), -1).astype(np.float64)
    r = refs.reshape(len(refs), -1).astype(np.float64)
    return np.max(np.abs(y - r), axis=1) / np.max(np.abs(r), axis=1)


def numbers(outs: np.ndarray, refs: np.ndarray, unanswered: int) -> dict:
    """Every number the check compares, by name: the widest per-request
    gap (``rel_err_max``) and the counts of missing and non-finite
    answers."""
    finite = np.isfinite(outs.reshape(len(outs), -1)).all(axis=1)
    clean = np.where(np.isfinite(outs), outs, 0.0)
    e = rel_errors(clean, refs)
    return {"rel_err_max": float(e.max()) if len(e) else float("inf"),
            "unanswered": int(unanswered),
            "nonfinite": int((~finite).sum())}


def verdict(nums: dict, limits: dict) -> tuple[bool, dict]:
    """``correct`` and the ``{name: {"value", "limit"}}`` that shows why:
    each number with a limit (``unanswered`` and ``nonfinite`` have the
    limit 0) must not exceed it."""
    lim = {"unanswered": 0, "nonfinite": 0, **limits}
    shown = {k: {"value": nums[k], "limit": v} for k, v in lim.items()}
    ok = all(nums[k] <= v for k, v in lim.items())
    return ok, shown
