#!/usr/bin/env python3
"""Run one benchmark cell once, on the chip this process finds.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout (where ``BENCHMARK.json`` is).  The last
line of standard output is the result as one JSON object; the numbers
that decide ``correct`` are also the last lines of standard error.  On a
platform other than a TPU, on fewer chips than the cell asks for, or on a
chip missing from ``bench/peaks.py``, it exits non-zero and prints no
result.
"""
import time

T_START = time.perf_counter()

import os  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]
# the TPU runtime's logs stay inside the checkout
os.environ.setdefault("TPU_LOG_DIR", str(ROOT / ".bench_out" / "tpu_logs"))

if __name__ == "__main__":
    from bench.harness import main
    sys.exit(main(t_start=T_START))
