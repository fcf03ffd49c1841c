"""Small configurations and a BENCHMARK.json of their own for the tests
that drive whole runs on the CPU (the look for a chip skipped)."""
import json
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))


def tiny_gan(layers=((4, 32, 16, 5, 2), (8, 16, 3, 5, 2)), z_dim=8):
    conf = json.loads((ROOT / "bench/configs/dcgan-lsun64.json").read_text())
    conf["model"]["layers"] = [list(l) for l in layers]
    conf["model"]["z_dim"] = z_dim
    conf["payload_pool"] = 64
    conf["check"]["sample"] = 10**6       # every answer
    return conf


def bench_with(tmp_path, confs: dict) -> dict:
    """The repo's BENCHMARK.json with each named configuration's file
    replaced by the given contents, written under ``tmp_path``."""
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    for c in bench["configs"]:
        if c["name"] in confs:
            p = tmp_path / f"{c['name']}.json"
            p.write_text(json.dumps(confs[c["name"]]))
            c["file"] = str(p)
    return bench
