"""The check that decides ``correct``, driven through whole runs on the
CPU with the look for a chip skipped: a sound run passes, and a run whose
timed path is broken underneath fails."""
import time

import numpy as np
import pytest

from benchkit import ROOT, bench_with, tiny_gan

from bench import check, harness


def _run(tmp_path, workload, confs, swap=None, seconds=0.5, trace=False,
         over=None, seed=2**31 + 11):
    bench = bench_with(tmp_path, confs)
    cell = harness.find_cell(bench, workload, ROOT)
    out, _ = harness.run_cell(cell, seed, seconds, trace, root=tmp_path,
                              t_start=time.perf_counter(), chip=False,
                              swap=swap, program_over=over,
                              log=lambda *a: None)
    return out


def _altered(serve, w):
    """One answer of every launch altered where it is produced."""
    return lambda x: serve(x).at[0].add(1.0)


def _rows_swapped(serve, w):
    """Answers handed to the wrong requests."""
    return lambda x: serve(x)[::-1]


def _nonfinite(serve, w):
    return lambda x: serve(x).at[0].set(np.nan)


CELLS = [("dcgan-bulk", "dcgan-lsun64", tiny_gan),
         ("dcgan-trickle", "dcgan-lsun64", tiny_gan)]


@pytest.mark.parametrize("workload,config,make", CELLS)
def test_sound_run_is_correct(tmp_path, workload, config, make):
    out = _run(tmp_path, workload, {config: make()})
    assert out["correct"], out["checked"]
    assert out["failed"] == 0 and out["attempted"] > 0
    assert list(out)[-1] == "checked"
    assert set(out["checked"]) >= {"rel_err_max", "unanswered", "nonfinite"}


# a row swap needs two live rows in a launch: the trickle cell's launches
# are mostly single requests, so it is tried on the bulk cells
FAULTS = [(cell, fault) for cell in CELLS
          for fault in (_altered, _rows_swapped, _nonfinite)
          if not (fault is _rows_swapped and cell[0] == "dcgan-trickle")]


@pytest.mark.parametrize("cell,fault", FAULTS,
                         ids=[f"{c[0]}-{f.__name__}" for c, f in FAULTS])
def test_broken_path_is_not_correct(tmp_path, cell, fault):
    workload, config, make = cell
    out = _run(tmp_path, workload, {config: make()}, swap=fault)
    assert not out["correct"], out["checked"]


def test_verdict_and_reservoir():
    ok, shown = check.verdict({"rel_err_max": 0.01, "unanswered": 0,
                               "nonfinite": 0},
                              {"rel_err_max": 0.02})
    assert ok and set(shown) == {"rel_err_max", "unanswered", "nonfinite"}
    ok, _ = check.verdict({"rel_err_max": 0.01, "unanswered": 1,
                           "nonfinite": 0}, {"rel_err_max": 0.02})
    assert not ok
    a, b = check.Reservoir(5, 3), check.Reservoir(5, 3)
    for i in range(100):
        a.offer(i, np.full(2, i))
        b.offer(i, np.full(2, i))
    assert [r for r, _ in a.items] == [r for r, _ in b.items]
    assert len(a.items) == 5 and a.seen == 100
