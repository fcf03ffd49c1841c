"""The traffic mixes: schedules and payload choices follow the seed."""
import json
import pathlib
import sys

import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import loadgen  # noqa: E402

BUCKETS = (1, 4, 16, 64)


def _mix(tmp_path, **raw):
    p = tmp_path / "mix.json"
    p.write_text(json.dumps(raw))
    return loadgen.load_traffic(p, BUCKETS)


def test_open_schedule_is_deterministic(tmp_path):
    t = _mix(tmp_path, loop="open", rate_rps=200.0)
    a = loadgen.open_schedule(t, 20.0, 2**31 + 7)
    b = loadgen.open_schedule(t, 20.0, 2**31 + 7)
    np.testing.assert_array_equal(a, b)
    assert len(a) == 4000
    assert np.all(np.diff(a) >= 0)


def test_seeds_reorder_the_same_work(tmp_path):
    t = _mix(tmp_path, loop="open", rate_rps=500.0)
    a = loadgen.open_schedule(t, 4.0, 1)
    b = loadgen.open_schedule(t, 4.0, 2)
    assert not np.array_equal(a, b)
    np.testing.assert_allclose(np.sort(np.diff(a, prepend=0.0)),
                               np.sort(np.diff(b, prepend=0.0)))
    # the whole schedule spans the window
    assert a[-1] == pytest.approx(b[-1])
    assert 3.9 < a[-1] < 4.0


def test_bursts_add_requests(tmp_path):
    t = _mix(tmp_path, loop="open", rate_rps=100.0,
             burst={"every_s": 1.0, "size": 8})
    a = loadgen.open_schedule(t, 3.0, 5)
    assert len(a) == 300 + 2 * 8
    assert np.sum(a == 1.0) >= 8


def test_payload_indices_follow_the_seed():
    a = loadgen.payload_indices(100, 1024, 3)
    np.testing.assert_array_equal(a, loadgen.payload_indices(100, 1024, 3))
    assert not np.array_equal(a, loadgen.payload_indices(100, 1024, 4))
    assert a.min() >= 0 and a.max() < 1024


def test_bucket_sets(tmp_path):
    assert _mix(tmp_path, loop="closed", buckets="largest",
                outstanding_buckets=2).buckets == (64,)
    closed = _mix(tmp_path, loop="closed", outstanding_buckets=2)
    assert closed.buckets == BUCKETS and closed.outstanding == 128
    assert _mix(tmp_path, loop="open", rate_rps=1,
                buckets=[4, 1]).buckets == (1, 4)
    with pytest.raises(ValueError):
        _mix(tmp_path, loop="closed")
    with pytest.raises(ValueError):
        _mix(tmp_path, loop="open")


@pytest.mark.parametrize("name", sorted(
    p.stem for p in (ROOT / "bench" / "traffic").glob("*.json")))
def test_committed_mixes_parse(name):
    t = loadgen.load_traffic(ROOT / "bench" / "traffic" / f"{name}.json",
                             BUCKETS)
    assert t.buckets and set(t.buckets) <= set(BUCKETS)
