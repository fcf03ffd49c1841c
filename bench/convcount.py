"""Operations and bytes of one conv site, from its geometry alone.

The counts are the conventional ones, the same the paper's Table 1 and the
models' documentation use:

- ``conv`` / ``dilated``: every output pixel takes every tap,
  ``OH·OW·R·S·C·N`` multiply-adds (taps that land in the zero border
  count; dilation changes where a tap reads, never how many there are);
- ``transposed``: the zero-free count, every *input* pixel meets every
  tap once, ``H·W·R·S·C·N`` multiply-adds (the inserted zeros of the
  naive engine are never counted).

One multiply-add is two operations.  Bytes are the least traffic a call
needs: the input plane read once, the weights read once, the output
written once.  A kernel that reads more moves more than this, so a
roofline share built on these bytes can only read low, never above 100%.
"""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Site:
    """One conv site: ``kind`` is 'conv' | 'dilated' | 'transposed';
    ``in_hw``/``out_hw`` are square extents; ``stride``/``dilation`` are
    scalars (the benchmark's models are square)."""

    name: str
    kind: str
    in_hw: int
    in_c: int
    out_c: int
    kernel: int
    stride: int = 1
    dilation: int = 1
    out_hw: int = 0

    def __post_init__(self):
        if self.kind not in ("conv", "dilated", "transposed"):
            raise ValueError(f"unknown site kind {self.kind!r}")
        if not self.out_hw:
            out = (self.in_hw * self.stride if self.kind == "transposed"
                   else -(-self.in_hw // self.stride))
            object.__setattr__(self, "out_hw", out)


def macs(site: Site, batch: int = 1) -> int:
    """Multiply-adds of one call on ``batch`` images."""
    taps = site.kernel * site.kernel * site.in_c * site.out_c
    pixels = site.in_hw ** 2 if site.kind == "transposed" else site.out_hw ** 2
    return batch * pixels * taps


def flops(site: Site, batch: int = 1) -> int:
    return 2 * macs(site, batch)


def bytes_moved(site: Site, batch: int = 1, act_bytes: int = 4,
                weight_bytes: int = 4) -> int:
    """Least HBM traffic of one call: input read, weights read, output
    written, each once."""
    x = batch * site.in_hw ** 2 * site.in_c * act_bytes
    w = site.kernel ** 2 * site.in_c * site.out_c * weight_bytes
    y = batch * site.out_hw ** 2 * site.out_c * act_bytes
    return x + w + y


def roofline_s(site: Site, batch: int, peaks: dict, act_bytes: int = 4,
               weight_bytes: int = 4) -> tuple[float, str]:
    """Least time of one call on a chip with ``peaks`` and which bound
    sets it ('compute' | 'memory').  Products run on the MXU as bf16
    passes, so the bf16 peak is the compute bound."""
    t_c = flops(site, batch) / peaks["bf16_flops"]
    t_m = bytes_moved(site, batch, act_bytes, weight_bytes) / peaks[
        "hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")
