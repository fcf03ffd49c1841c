"""The lower-precision control comes out not correct: the program's own
int8-weight path (``wdtype="int8"``), run through a whole cell on the CPU
at published widths, against the configuration's committed limit.

DCGAN runs at its full Table-1 widths in the open-loop cell.  On the CPU
the program's own f32 path reads about 1e-6, so this test shows the
limit's upper side only; its lower side is the program's reading on the
chip (PERF.md)."""
import json
import time

import pytest

from benchkit import ROOT, bench_with

from bench import harness


def _dcgan():
    conf = json.loads((ROOT / "bench/configs/dcgan-lsun64.json").read_text())
    conf["payload_pool"] = 64
    conf["check"]["sample"] = 64
    return conf


@pytest.mark.parametrize("workload,config,make,seconds", [
    ("dcgan-trickle", "dcgan-lsun64", _dcgan, 0.3)])
def test_int8_control_is_not_correct(tmp_path, workload, config, make,
                                     seconds):
    cell = harness.find_cell(bench_with(tmp_path, {config: make()}),
                             workload, ROOT)
    out, _ = harness.run_cell(cell, 2**31 + 5, seconds, False,
                              root=tmp_path, t_start=time.perf_counter(),
                              chip=False, program_over={"wdtype": "int8"},
                              log=lambda *a: None)
    got = out["checked"]["rel_err_max"]
    assert not out["correct"] and got["value"] > got["limit"], got
