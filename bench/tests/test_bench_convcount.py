"""Operation and byte counts of conv sites against the paper's Table 1
and against a brute count over small geometries."""
import pathlib
import sys

import pytest

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[2]))

from bench import convcount  # noqa: E402
from bench.peaks import PEAKS  # noqa: E402

# Table 1 of the paper: the zero-free multiply-adds of the DCGAN generator
# layers, H*W*K*K*C*N (benchmarks/table1_layers.py, "huge_MACs")
TABLE1 = [((4, 1024, 512, 5, 2), 209_715_200),
          ((8, 512, 256, 5, 2), 209_715_200),
          ((16, 256, 128, 5, 2), 209_715_200),
          ((32, 128, 3, 5, 2), 9_830_400)]


@pytest.mark.parametrize("layer,want", TABLE1)
def test_table1_macs(layer, want):
    hw, c, n, k, s = layer
    site = convcount.Site("dc", "transposed", hw, c, n, k, s)
    assert convcount.macs(site) == want
    assert convcount.flops(site, batch=64) == 2 * 64 * want
    assert site.out_hw == hw * s


def test_table1_macs_match_the_repo_count():
    root = pathlib.Path(__file__).resolve().parents[2]
    sys.path.insert(0, str(root))
    from benchmarks.table1_layers import layer_macs
    from repro.models.gan import DCGAN_LAYERS
    for l, (_, want) in zip(DCGAN_LAYERS, TABLE1):
        assert layer_macs(l)[1] == want


def _brute_transposed(h, k, s, pl):
    """Per axis: the (output, tap) pairs of the lhs-dilated correlation
    whose input falls on a real sample, not an inserted zero."""
    return sum(1 for o in range(s * h) for t in range(k)
               if (o + t - pl) % s == 0)


def _brute_single(h, k, s, d):
    """Per axis: outputs of a 'SAME'-padded correlation times its taps."""
    half = d * (k - 1) // 2
    outs = len(range(0, h + 2 * half - d * (k - 1), s))
    return outs, outs * k


@pytest.mark.parametrize("h,k,s", [(3, 5, 2), (4, 4, 2), (5, 3, 3),
                                   (2, 1, 1), (6, 5, 2)])
def test_transposed_brute(h, k, s):
    pl = max(0, (k - s + 1) // 2)
    per_axis = _brute_transposed(h, k, s, pl)
    site = convcount.Site("t", "transposed", h, 3, 2, k, s)
    assert convcount.macs(site) == per_axis ** 2 * 3 * 2


@pytest.mark.parametrize("kind,h,k,s,d", [
    ("conv", 7, 3, 1, 1), ("conv", 8, 3, 2, 1), ("conv", 9, 3, 2, 1),
    ("dilated", 10, 3, 1, 2), ("dilated", 12, 3, 1, 4), ("conv", 5, 1, 1, 1)])
def test_single_brute(kind, h, k, s, d):
    outs, per_axis = _brute_single(h, k, s, d)
    site = convcount.Site("c", kind, h, 4, 5, k, s, d)
    assert site.out_hw == outs
    assert convcount.macs(site, batch=3) == 3 * per_axis ** 2 * 4 * 5


def test_bytes_and_roofline_bound():
    site = convcount.Site("dc0", "transposed", 4, 1024, 512, 5, 2)
    b = 64
    want = (b * 16 * 1024 + 25 * 1024 * 512 + b * 64 * 512) * 4
    assert convcount.bytes_moved(site, b) == want
    peaks = PEAKS["TPU v5 lite"]
    t, bound = convcount.roofline_s(site, b, peaks)
    assert bound == "compute"
    assert t == pytest.approx(convcount.flops(site, b) / 197e12)
    t1, bound1 = convcount.roofline_s(site, 1, peaks)
    assert bound1 == "memory"
    assert t1 == pytest.approx(convcount.bytes_moved(site, 1) / 819e9)


def test_unknown_chip_is_an_error():
    from bench.peaks import peaks_for
    with pytest.raises(KeyError):
        peaks_for("TPU v9 imaginary")
