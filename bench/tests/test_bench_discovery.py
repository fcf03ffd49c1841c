"""A later change adds a configuration, a traffic mix and a per-layer
metric as new files and entries alone: the harness finds each by name."""
import json
import shutil
import time

from benchkit import ROOT, tiny_gan

from bench import harness

NEW_METRIC = '''"""``launches.count``: launches in the window (a test metric)."""


def read(run):
    return len(run.launches) or None
'''


def test_new_files_are_found_by_name(tmp_path):
    bench_dir = tmp_path / "bench"
    shutil.copytree(ROOT / "bench", bench_dir,
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    (bench_dir / "configs" / "tiny-new.json").write_text(
        json.dumps(tiny_gan(layers=((4, 16, 8, 4, 2), (8, 8, 3, 4, 2)))))
    (bench_dir / "traffic" / "burst-new.json").write_text(json.dumps(
        {"loop": "open", "rate_rps": 300, "buckets": "all",
         "max_wait_ms": 1.0, "burst": {"every_s": 0.25, "size": 20}}))
    (bench_dir / "metrics" / "launches.py").write_text(NEW_METRIC)

    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "tiny-new", "source": "a test",
                             "file": "bench/configs/tiny-new.json",
                             "reduced": [], "why": "a test"})
    bench["workloads"].append({"name": "tiny-new.burst", "config": "tiny-new",
                               "traffic": "burst-new", "chips": 1,
                               "why": "a test"})
    bench["end_to_end"][-1].setdefault("workloads", []).append(
        "tiny-new.burst")
    bench["per_layer"].append({"name": "launches.count", "unit": "launches",
                               "better": "lower", "source": "program_counter",
                               "layer": "serving/image_batcher",
                               "moves": "latency_p95_ms",
                               "workloads": ["tiny-new.burst"]})

    cell = harness.find_cell(bench, "tiny-new.burst", tmp_path,
                             bench_dir=bench_dir)
    assert cell.traffic.burst == {"every_s": 0.25, "size": 20}
    assert [m["name"] for m in cell.per_layer] == ["launches.count"]
    assert [m["name"] for m in cell.end_to_end] == ["setup_s",
                                                    "latency_p95_ms"]
    for trace in (False, True):
        out, _ = harness.run_cell(cell, 5, 0.5, trace, root=tmp_path,
                                  t_start=time.perf_counter(), chip=False,
                                  log=lambda *a: None)
        assert out["correct"], out["checked"]
        want = {"launches.count"} if trace else {"setup_s",
                                                 "latency_p95_ms"}
        assert set(out["metrics"]) == want
        assert out["attempted"] == 150 + 20
