"""Plan/executor engine: every HUGE² conv is *planned once* at model-load,
and every conv — transposed, strided, or dilated — *executes as one launch*.

The paper's central claim is that transposed / strided / dilated convolutions
should be decomposed **offline** and executed as zero-free GEMMs with maximal
data reuse.  This module is that offline step made explicit:

- ``ConvSpec``   — a hashable description of one convolution site (op kind,
  spatial/channel shapes, strides, padding, dilation, dtype, backend policy).
- ``plan_conv``  — compiles a spec into a ``ConvPlan`` exactly once (keyed
  LRU cache); everything the old engine recomputed inside every jitted call
  is captured here: per-phase ``PhasePlan1D`` geometry, the *whole-conv*
  execution path (one fused Pallas launch / one wide XLA GEMM / per-tap
  GEMM fallback, with VMEM tile sizes chosen at plan time), and the mirrored
  backward schedules.  ``ConvSpec`` carries no batch — instead every plan
  sizes one ``Route`` per batch bucket (``BATCH_BUCKETS`` = 1/4/16/64)
  against the plane-bytes/VMEM caps at build time, and the executors look
  the route up with ``ConvPlan.route_for_batch(B)``; serving pads request
  batches to the nearest bucket so each bucket jits exactly once.
- ``ConvPlan.pack``    — flattens the HWIO kernel into the **superpacked**
  weight layout, one tap-major buffer per site.  For the transposed kind:
  all phase sub-kernels concatenated, ``(Σ_q T_h·T_w·C, N)``, with phase row
  offsets as plan-time constants (``PhaseExec.tap_off``).  For the
  single-correlation kinds ('conv' / 'dilated'): the same tap-major layout
  with one phase, ``(R·S·C, N)`` — tap ``t = m·S + n`` owns rows
  ``[t·C, (t+1)·C)``, and dilation never appears in the layout (a dilated
  kernel packs identically to a dense one — the *geometry* moves into the
  plan, not the weights).  Done once at model load; the superpack *is* the
  model's parameter from then on.
- ``ConvPlan.apply``   — executes the planned convolution on the superpack.

All three kinds execute through the same single-correlation machinery: pad
the input **once**, keep that plane resident, and run shift-and-add tap
GEMMs against superpack rows at plan-time offsets.

Transposed execution (EcoFlow-style fusion of all s_h·s_w phases over one
residency of the input):

* ``pallas``      — one multi-phase Pallas kernel: the globally padded plane
  resident in VMEM once, a static unrolled loop over every phase's taps
  accumulating into per-phase f32 scratch, and a flush that writes the
  *interleaved* output block directly with strided in-kernel stores.  When
  the whole plane does not fit VMEM, the same launch runs the **spatially
  tiled** grid instead (``Route.sp_tiles``): halo'd output tiles with
  double-buffered input DMA — the 'pallas' verdict is a *tile*-fits check,
  so plane size never forces a site off the Pallas route (the XLA
  fallbacks below remain for non-uniform-phase transposed shapes, and
  for the pathological case of a minimum halo tile over the budget).
* ``fused_tap``   — one wide XLA GEMM: all tap-shifted views of the resident
  plane stacked against the superpack reshaped ``(ΣT, C, N)``, per-phase
  tap-segment sums, one reshape-interleave.  Exact FLOPs; wins when the
  plane is small relative to the phase output (DCGAN head layers).
* ``fused_plane`` — one wide XLA GEMM of the whole padded plane against the
  superpack viewed as ``(C, ΣT·N)``; every tap's contribution for every
  position comes out of the single GEMM, then shifted slice-accumulate and
  one reshape-interleave.  Slight FLOP overhead ``Hg·Wg·ΣT / Σ u·v·T``;
  wins when that ratio is small (deep layers, big planes).
* ``taps``        — general fallback (non-uniform phase extents with a large
  plane ratio): still a *single* global pad — per-phase GEMMs read the one
  resident plane through plan-time offsets — but phases are separate GEMMs
  and the output goes through ``interleave_phases``.

Single-correlation execution ('conv' / 'dilated', §3.2.2 — the dilated
kernel is never zero-inserted; taps read the raw plane at ``m·d_h`` /
``n·d_w`` offsets):

* ``pallas``      — ONE launch of the superpack Pallas kernel: the padded
  plane resident in VMEM, a static unrolled tap loop accumulating into f32
  scratch, tiles picked at plan time from the dilation-aware working set
  (the plane grows by the dilated tap reach ``(R-1)·d_h``; the superpack
  tile does not — taps are R·S rows regardless of dilation).  Big planes
  run the spatially tiled grid (``Route.sp_tiles``, halo'd output tiles +
  double-buffered input DMA) under the same single launch.
* ``fused_tap``   — ONE wide XLA GEMM: the R·S tap-shifted (strided,
  dilated) views of the resident plane concatenated along channels against
  the full ``(R·S·C, N)`` superpack.  Exact FLOPs (the buffer is built from
  the raw input — im2col's *layout*, but zero-free and load-time planned).
* ``taps``        — fallback when the tap-stacked buffer would out-grow the
  edge memory budget: per-tap shift-and-add GEMMs reading superpack rows
  ``[t·C, (t+1)·C)`` over the same single resident plane.

``apply`` is a ``jax.custom_vjp`` for **every** kind, running directly on
the superpacked layout:

* dx of a transposed conv — the §3.2.3 *strided-conv* form: per-tap GEMMs
  of the padded derivative maps against ``(C, N)`` panels fetched straight
  out of the superpack at plan-time row offsets (no kernel reassembly).
* dK of a transposed conv — the §3.2.3 *dilated-kernel* form, emitted
  directly in superpack order.
* dx of a strided/dilated conv — the mirrored *transposed-tap* form: one
  GEMM of dy against the superpack viewed ``(ΣT, C, N)``, then per-tap
  strided/dilated shift-and-add into the padded input plane (the exact
  transpose of the forward tap reads; no flipped kernel is ever assembled).
* dK of a strided/dilated conv — tap views of the resident input plane
  contracted with dy in one GEMM, emitted directly in superpack row order.

No other module slices kernels at execution time; ``repro.core.engine`` and
``repro.kernels.ops`` are thin dispatchers over this cache.
"""
from __future__ import annotations

import dataclasses
import functools
import time
import warnings
from typing import Sequence

import jax
import jax.numpy as jnp

from repro.core import decompose as dec
from repro.core.untangle import pad_or_crop

Pair = tuple[int, int]

# the Pallas tile searches fit the layout-exact working set
# (``kernels.untangled_conv.vmem_bytes_estimate_*``) under this budget:
# 8 MiB below the scoped-VMEM limit every kernel compiles with
# (``VMEM_LIMIT_BYTES``), headroom for Mosaic's internal scratch
_VMEM_BUDGET = 24 * 1024 * 1024

# plan-time fuse heuristic for the per-phase fallback and plain convs:
# concatenate tap views + one wide GEMM when the GEMM has too few rows to
# amortize per-tap dispatch (paper Fig. 7 DC1).
_FUSE_MAX_ROWS = 128

# batch buckets every plan sizes a route for at build time.  Serving pads
# each request batch up to the nearest bucket (``serving/image_batcher``),
# so the executor jits exactly once per bucket and ``route_for_batch`` is a
# plan-time table lookup — no byte-cap arithmetic happens at trace time.
BATCH_BUCKETS = (1, 4, 16, 64)

# whole-conv XLA path heuristic: the plane GEMM computes
# Hg*Wg*ΣT*C*N MACs where Σ u·v·T_q*C*N would be exact; take the plane
# GEMM when the overhead ratio is below this, else the exact tap-stacked
# GEMM (uniform phases) or the per-phase fallback.
_PLANE_RATIO_MAX = 1.6
# cap the (B=1) f32 plane-GEMM intermediate (Hg*Wg*ΣT*N) — beyond this the
# im2col-like blowup stops being an edge-memory win.
_PLANE_BYTES_MAX = 64 * 1024 * 1024

# plane-parallel verdict floor: a spec that *requests* device tiling
# (``ConvSpec.spatial != (1, 1)``) still routes single-device at buckets
# whose resident input+output planes stay under this — splitting a small
# plane buys halo traffic without relieving any memory pressure.
_SPATIAL_MIN_BYTES = 4 * 1024 * 1024


def norm_padding(padding, k_hw) -> tuple[Pair, Pair]:
    """Normalize 'SAME'/'VALID'/int-pair/nested paddings to ((lo,hi),(lo,hi))."""
    if isinstance(padding, str):
        r, s = k_hw
        if padding.upper() == "SAME":
            return ((r // 2, (r - 1) // 2), (s // 2, (s - 1) // 2))
        if padding.upper() == "VALID":
            return ((0, 0), (0, 0))
        raise ValueError(padding)
    (a, b) = padding
    if isinstance(a, int):
        return ((a, a), (b, b))
    return (tuple(a), tuple(b))


def pick_vmem_tiles(hp, wp, c, n, r, s, oh, ow, itemsize, witemsize=None):
    """(C_t, N_t) of the whole-plane single-correlation kernel — one lane
    tile per channel dim (``lane_tile``) — or None when its working set
    does not fit the VMEM budget.  ``witemsize`` is the *weight* itemsize
    when it differs from the activation's (int8 superpacks: 1 byte/elem +
    the f32 scale column)."""
    from repro.kernels.untangled_conv import (lane_tile,
                                              vmem_bytes_estimate_superpack)
    c_t, n_t = lane_tile(c), lane_tile(n)
    if vmem_bytes_estimate_superpack(hp, wp, c_t, r * s, n_t, oh, ow,
                                     itemsize,
                                     witemsize=witemsize) <= _VMEM_BUDGET:
        return c_t, n_t
    return None


def pick_fused_tiles(hg, wg, c, n, total_taps, sum_uv, oh, ow, tap_rows,
                     itemsize, witemsize=None, batch=1):
    """(C_t, N_t, B_t) for the multi-phase fused kernel, or None: the
    working set is the whole global plane + the superpack tile + per-phase
    f32 scratch + the full interleaved output block + the largest phase's
    ``tap_rows``-row tap GEMM, every term but the superpack tile times the
    ``B_t`` images of a grid step.  ``B_t`` is the largest divisor of the
    bucket ``batch`` whose working set fits the budget — the most images
    that share one fetch of each superpack tile; None when one image does
    not fit."""
    from repro.kernels.untangled_conv import (lane_tile,
                                              vmem_bytes_estimate_fused)
    c_t, n_t = lane_tile(c), lane_tile(n)
    for b_t in range(batch, 0, -1):
        if batch % b_t == 0 and vmem_bytes_estimate_fused(
                hg, wg, c_t, total_taps, n_t, sum_uv, oh, ow, tap_rows,
                itemsize, witemsize=witemsize, b_tile=b_t) <= _VMEM_BUDGET:
            return c_t, n_t, b_t
    return None


def _spatial_cands(extent: int) -> tuple[int, ...]:
    """Output-tile size candidates along one dim, descending, clipped."""
    return tuple(dict.fromkeys(min(t, extent) for t in (128, 64, 32, 16, 8)))


def pick_tiled_single(c, n, r, s, oh, ow, strides, dilation, itemsize,
                      witemsize=None):
    """(C_t, N_t, (T_oh, T_ow)) for the spatially tiled single-correlation
    kernel, or None: ``C_t`` is a whole lane tile (the DMA'd halo slice
    needs a lane-dense channel dim, so narrower planes are zero-padded up
    to it), ``N_t`` a lane tile, and the output tile the largest whose
    double-buffered working set (``vmem_bytes_estimate_tiled``) fits the
    budget."""
    from repro.kernels.untangled_conv import (LANES, halo_extent, lane_tile,
                                              vmem_bytes_estimate_tiled)
    (sh, sw), (dh, dw) = strides, dilation
    c_t, n_t = LANES, lane_tile(n)
    for toh in _spatial_cands(oh):
        for tow in _spatial_cands(ow):
            tin_h = halo_extent(toh, r, sh, dh)
            tin_w = halo_extent(tow, s, sw, dw)
            if vmem_bytes_estimate_tiled(
                    tin_h, tin_w, c_t, r * s, n_t, (toh, tow), toh * tow,
                    toh * tow, itemsize,
                    witemsize=witemsize) <= _VMEM_BUDGET:
                return c_t, n_t, (toh, tow)
    return None


def pick_tiled_transposed(c, n, total_taps, phases, strides, itemsize,
                          witemsize=None):
    """(C_t, N_t, (T_u, T_v)) for the spatially tiled multi-phase deconv
    kernel, or None.  Tile sizes are in *phase-output* coordinates (the
    interleaved output tile is (T_u·s_h, T_v·s_w)); the halo covers the
    phase tap-origin span, so it is phase-aware by construction.  Channel
    tiles as in ``pick_tiled_single``; the largest spatial tile that fits.
    Only uniform-phase plans call this (checked by the route builder)."""
    from repro.kernels.untangled_conv import (LANES, deconv_tap_span,
                                              lane_tile,
                                              vmem_bytes_estimate_tiled)
    uu, vv = phases[0].out_hw
    (sh, sw) = strides
    ((mh, xh_max), (mw, xw_max)) = deconv_tap_span(phases)
    c_t, n_t = LANES, lane_tile(n)
    for tu in _spatial_cands(uu):
        for tv in _spatial_cands(vv):
            tin_h = xh_max - mh + tu
            tin_w = xw_max - mw + tv
            if vmem_bytes_estimate_tiled(
                    tin_h, tin_w, c_t, total_taps, n_t, (tu * sh, tv * sw),
                    len(phases) * tu * tv, tu * tv, itemsize,
                    witemsize=witemsize) <= _VMEM_BUDGET:
                return c_t, n_t, (tu, tv)
    return None


# ---------------------------------------------------------------------------
# spec
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class ConvSpec:
    """Hashable description of one convolution site — the plan-cache key."""

    kind: str                     # 'transposed' | 'conv' | 'dilated'
    in_hw: Pair                   # input spatial (H, W)
    in_c: int
    out_c: int
    kernel_hw: Pair               # (R, S)
    strides: Pair = (1, 1)
    padding: tuple[Pair, Pair] = ((0, 0), (0, 0))
    dilation: Pair = (1, 1)
    dtype: str = "float32"
    backend: str = "auto"         # 'auto' | 'xla' | 'pallas'
    # requested device tiling (D_h, D_w) of the plane over a spatial mesh
    # (``core.spatial``).  Part of the cache key: a tiled site plans its
    # own routes (``Route.dev_tiles``).  (1, 1) = single-device, always.
    spatial: Pair = (1, 1)
    # weight *storage* dtype: 'float32' (dense superpack) or 'int8' (the
    # quantized superpack — ``pack`` emits a ``QuantizedSuperpack`` with
    # per-tap-row f32 scales, routes account 1 byte/weight-elem).
    # Activations and accumulation stay ``dtype``/f32 regardless.
    wdtype: str = "float32"


_WDTYPES = ("float32", "int8")


def conv_spec(kind: str, x_shape: Sequence[int], kernel_shape: Sequence[int],
              *, strides=(1, 1), padding=((0, 0), (0, 0)), dilation=(1, 1),
              dtype=None, backend: str = "auto",
              spatial: Pair = (1, 1), wdtype: str = "float32") -> ConvSpec:
    """Build a normalized (cache-canonical) spec from array shapes."""
    r, s, c, n = kernel_shape
    if x_shape[-1] != c:
        raise ValueError(f"channel mismatch {x_shape[-1]} vs {c}")
    return ConvSpec(
        kind=kind, in_hw=(int(x_shape[-3]), int(x_shape[-2])),
        in_c=int(c), out_c=int(n), kernel_hw=(int(r), int(s)),
        strides=tuple(int(v) for v in strides),
        padding=norm_padding(padding, (r, s)),
        dilation=tuple(int(v) for v in dilation),
        dtype=str(jnp.dtype(dtype)) if dtype is not None else "float32",
        backend=backend, spatial=tuple(int(v) for v in spatial),
        wdtype=str(wdtype))


def _weight_itemsize(spec: ConvSpec) -> int:
    """Per-element byte cost of the *stored* weights for VMEM/route
    accounting — 1 for the int8 superpack (scale rows are charged
    separately by the estimators), the activation itemsize otherwise."""
    return 1 if spec.wdtype == "int8" else jnp.dtype(spec.dtype).itemsize


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(eq=False)
class QuantizedSuperpack:
    """The int8 superpack: the tap-major weight buffer quantized per row.

    ``q`` is the ``(rows, N)`` int8 buffer in the exact row order the f32
    superpack uses (transposed: phase-concatenated taps; conv/dilated: tap
    ``t = m·S + n`` owns rows ``[t·C, (t+1)·C)``); ``scale`` is the f32
    ``(rows, 1)`` per-tap-row scale column riding with it — appended to the
    layout, so slicing rows of ``q`` and ``scale`` together yields a
    dequantizable panel at any plan-time offset.  Scales come from
    ``runtime.compress.quantize_int8_rows`` (symmetric, max/127, floored),
    which bounds the per-element weight error by ``0.5 · scale[row]``.

    Registered as a pytree so it rides through jit / custom_vjp / serving
    param trees like any other leaf pair."""

    q: jax.Array                  # (rows, N) int8
    scale: jax.Array              # (rows, 1) f32

    def tree_flatten(self):
        return (self.q, self.scale), None

    @classmethod
    def tree_unflatten(cls, aux, leaves):
        return cls(*leaves)

    @property
    def shape(self):
        return self.q.shape

    def dequant(self) -> jax.Array:
        """The f32 superpack view — a row-broadcast multiply that XLA fuses
        into the consuming GEMM (the dequant-on-the-fly read)."""
        from repro.runtime.compress import dequantize_int8
        return dequantize_int8(self.q, self.scale)

    def nbytes(self) -> int:
        """Stored bytes: 1/elem for ``q`` plus the f32 scale rows."""
        return int(self.q.size) + 4 * int(self.scale.size)


# ---------------------------------------------------------------------------
# per-phase execution record
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class PhaseExec:
    """Plan-time geometry record for one output phase (or the whole conv).

    Offsets are superpack / fused-kernel coordinates, fixed at plan time:
    ``tap_off`` rows (in taps) into the superpacked weight buffer,
    ``acc_off`` rows (in output pixels) into the fused kernel's accumulator,
    ``xoff`` the phase's tap origin inside the globally padded plane.
    """

    key: str                      # legacy per-phase pytree key (checkpoints)
    q: Pair                       # (q_h, q_w) output phase
    rho: Pair                     # first kernel tap per dim
    taps: Pair                    # (T_h, T_w) sub-kernel extent
    pad: tuple[Pair, Pair]        # input pad/crop for this phase's stride-1 conv
    out_hw: Pair                  # (U, V) phase output extent
    tap_off: int = 0              # taps preceding this phase in the superpack
    acc_off: int = 0              # U·V rows preceding this phase in scratch
    xoff: Pair = (0, 0)           # tap origin in the globally padded plane


def _choose_path(backend: str, hp: int, wp: int, c: int, n: int,
                 taps: Pair, out_hw: Pair, itemsize: int) -> tuple[str, Pair | None]:
    """Per-phase path choice — kept as the measured baseline policy for
    ``apply_per_phase`` (the pre-fusion transposed executor)."""
    th, tw = taps
    u, v = out_hw
    if th == 0 or tw == 0 or u == 0 or v == 0:
        return "zeros", None
    if backend == "pallas" or (backend == "auto"
                               and jax.default_backend() == "tpu"):
        tiles = pick_vmem_tiles(hp, wp, c, n, th, tw, u, v, itemsize)
        if tiles is not None:
            return "pallas", tiles
    if u * v <= _FUSE_MAX_ROWS and th * tw > 2:
        return "fused", None
    return "taps", None


@dataclasses.dataclass(frozen=True)
class Route:
    """One batch bucket's execution decision, fixed at plan time.

    ``batch`` is the bucket the byte caps were evaluated at; ``path`` /
    ``tiles`` are the whole-conv forward route for that bucket, and
    ``fused_bwd`` says whether the single-correlation backward may
    materialize its ``(B, OH, OW, ΣT, ·)`` f32 buffers (one wide dy GEMM +
    one stacked dK GEMM) or must fall back to per-tap GEMMs.

    ``b_tile`` is the images per grid step of the whole-plane fused
    transposed kernel (``B_t``: each superpack tile is fetched once per
    ``B_t`` images); 1 on every other route, which is the per-image grid.

    ``sp_tiles`` is the spatial output-tile shape when the 'pallas' route is
    the *tiled* kernel — ``(T_oh, T_ow)`` output pixels for the single-
    correlation kinds, ``(T_u, T_v)`` phase-output pixels for the transposed
    kind (the interleaved tile is ``(T_u·s_h, T_v·s_w)``).  ``None`` means
    whole-plane VMEM residency (the small-plane fast path).

    ``dev_tiles`` is the *device*-tiling verdict, sitting one level above
    ``sp_tiles``: ``(D_h, D_w)`` devices the plane shards over when the spec
    requests spatial tiling, the geometry admits one-hop halo exchange, and
    this bucket's working set clears ``_SPATIAL_MIN_BYTES``
    (``core.spatial``).  ``path``/``tiles`` remain the *single-device*
    verdict — each shard (and any mesh-less fallback) executes through
    them unchanged."""

    batch: int
    # 'pallas'|'fused_plane'|'fused_tap'|'taps'|'pixel_shuffle', plus
    # (transposed, autotune-only) 'per_phase' — the PR-1 per-phase executor
    # promoted to a first-class route so the tuner can rank it (the
    # heuristic never emits it; BENCH_fig7 shows it winning on some hosts,
    # e.g. DC2).  'pixel_shuffle' is the transposed sub-pixel rewrite:
    # eligible specs (every phase shares (U,V)==(H,W), tap extent and pad)
    # run as ONE dense stride-1 conv against the (Q,T,C,N) superpack view
    # followed by depth-to-space — the tap buffer is Q× smaller than
    # 'fused_tap''s (T views instead of ΣT=Q·T).
    path: str
    tiles: Pair | None            # (C_t, N_t) when path == 'pallas'
    fused_bwd: bool = True
    sp_tiles: Pair | None = None  # spatial tile when 'pallas' is tiled
    dev_tiles: Pair | None = None  # (D_h, D_w) plane-parallel verdict
    b_tile: int = 1               # images per step of the fused deconv


def _dev_verdict(spec: ConvSpec, out_hw: Pair, itemsize: int,
                 batch: int) -> Pair | None:
    """The per-bucket device-tiling verdict: the spec must request tiling,
    the geometry must admit one-hop halo exchange (``spatial.spatial_plan``
    — pure arithmetic, identical on every host), and the bucket's resident
    planes must outgrow the single-device floor."""
    if spec.spatial == (1, 1):
        return None
    from repro.core import spatial
    sp = spatial.spatial_plan(spec)
    if sp is None:
        return None
    if spatial.plane_parallel_bytes(spec, out_hw, batch,
                                    itemsize) <= _SPATIAL_MIN_BYTES:
        return None
    return spec.spatial


def _single_route(spec: ConvSpec, hp: int, wp: int, out_hw: Pair,
                  itemsize: int, batch: int) -> Route:
    """Single-correlation bucket route + the device-tiling verdict."""
    route = _single_route_1dev(spec, hp, wp, out_hw, itemsize, batch)
    dev = _dev_verdict(spec, out_hw, itemsize, batch)
    return dataclasses.replace(route, dev_tiles=dev) if dev else route


def _single_route_1dev(spec: ConvSpec, hp: int, wp: int, out_hw: Pair,
                       itemsize: int, batch: int) -> Route:
    """Whole-conv route for the single-correlation kinds ('conv'/'dilated')
    at one batch bucket: one Pallas launch / one wide GEMM / per-tap
    fallback.

    The same plane-ratio heuristic as the transposed path, extended with
    the dilation-aware VMEM working set: ``hp``/``wp`` are padded-plane
    dims that already carry the dilated tap reach ``(R-1)·d``, while the
    superpack tile stays R·S rows regardless of dilation — a dilated
    kernel costs plane residency, never weight bytes.  The tap-stacked
    GEMM buffer carries R·S copies of the output extent (exact FLOPs,
    im2col-sized layout) and grows linearly in the bucket, so big buckets
    route to 'taps' where small ones fuse."""
    r, s = spec.kernel_hw
    c = spec.in_c
    oh, ow = out_hw
    # tap-stack blowup vs the resident plane: B*oh*ow*R*S rows of C against
    # B*hp*wp plane rows; cap the materialized f32 buffer.  The backward's
    # dy-GEMM / stacked-dK buffers are the same size, so one cap governs
    # both directions of the bucket.
    fused_ok = 4 * batch * oh * ow * r * s * c <= _PLANE_BYTES_MAX
    pallas = pallas_single_routes(spec, hp, wp, out_hw, itemsize, batch,
                                  fused_ok)
    if pallas:
        return pallas[0]
    if fused_ok:
        return Route(batch, "fused_tap", None, fused_bwd=True)
    return Route(batch, "taps", None, fused_bwd=False)


def want_pallas(spec: ConvSpec) -> bool:
    """Does the spec's backend policy ask for the Pallas kernels?"""
    return spec.backend == "pallas" or (
        spec.backend == "auto" and jax.default_backend() == "tpu")


# specs whose Pallas request already fell through to an XLA route (warned
# once per process, like ``spatial._INFEASIBLE_WARNED``)
_OFF_PALLAS_WARNED: set = set()


def _warn_off_pallas(spec: ConvSpec, batch: int, why: str) -> None:
    """A spec that asks for Pallas but gets no compilable Pallas route at
    some bucket runs an XLA route there — say so, once per spec."""
    if spec in _OFF_PALLAS_WARNED:
        return
    _OFF_PALLAS_WARNED.add(spec)
    warnings.warn(
        f"plan_conv: {spec.kind} site {spec.in_hw}x{spec.in_c}->"
        f"{spec.out_c} k={spec.kernel_hw} s={spec.strides} asks for the "
        f"Pallas kernels but bucket B={batch} runs an XLA route ({why})",
        RuntimeWarning, stacklevel=4)


def pallas_single_routes(spec: ConvSpec, hp: int, wp: int, out_hw: Pair,
                         itemsize: int, batch: int,
                         fused_bwd: bool) -> list[Route]:
    """Every Pallas route of a single-correlation bucket, preferred first:
    whole-plane residency when its tiles fit (no halo waste), then the
    spatially tiled kernel — plane size alone never pushes a site off the
    Pallas route.  Empty unless the spec asks for Pallas (warned when
    nothing fits)."""
    if not want_pallas(spec):
        return []
    r, s = spec.kernel_hw
    c, n = spec.in_c, spec.out_c
    oh, ow = out_hw
    witemsize = _weight_itemsize(spec)
    routes = []
    tiles = pick_vmem_tiles(hp, wp, c, n, r, s, oh, ow, itemsize,
                            witemsize=witemsize)
    if tiles is not None:
        routes.append(Route(batch, "pallas", tiles, fused_bwd=fused_bwd))
    dil = spec.dilation if spec.kind == "dilated" else (1, 1)
    tiled = pick_tiled_single(c, n, r, s, oh, ow, spec.strides, dil,
                              itemsize, witemsize=witemsize)
    if tiled is not None:
        c_t, n_t, sp = tiled
        routes.append(Route(batch, "pallas", (c_t, n_t), fused_bwd=fused_bwd,
                            sp_tiles=sp))
    if not routes:
        _warn_off_pallas(spec, batch, "no halo tile fits the VMEM budget")
    return routes


def _pixel_shuffle_geom(spec: ConvSpec, phases) -> tuple[Pair, tuple[Pair, Pair]] | None:
    """The sub-pixel rewrite's shared stride-1 footprint, or ``None``.

    A transposed spec is eligible when every phase shares the *same*
    stride-1 correlation: output extent ``(U, V) == (H, W)`` (so the
    interleave is an exact ×s_h×s_w depth-to-space), tap extent ``(T_h,
    T_w)`` and input pad.  Then the Q = s_h·s_w per-phase sub-kernels are
    one dense ``(T_h, T_w, C, Q·N)`` kernel and the whole conv is a single
    stride-1 correlation + depth-to-space — zero inserted zeros, exact
    FLOPs.  ``deconv_padding`` sites with ``k % s == 0`` (cGAN/VAE-decoder
    k=4 s=2) qualify; k=5 s=2 (DCGAN) does not (phase tap counts 3 vs 2) —
    exactly the geometry-dependent transposed-vs-sub-pixel tradeoff of
    arXiv:2107.07647."""
    if not phases:
        return None
    first = phases[0]
    th, tw = first.taps
    if th == 0 or tw == 0:
        return None
    if first.out_hw != spec.in_hw:
        return None
    for ex in phases[1:]:
        if (ex.taps != first.taps or ex.pad != first.pad
                or ex.out_hw != first.out_hw):
            return None
    return first.taps, first.pad


def _pixel_shuffle_route(spec: ConvSpec, phases, batch: int) -> Route | None:
    """The 'pixel_shuffle' verdict at one bucket: the spec must admit the
    rewrite and the bucket's tap-stacked GEMM buffer (T views of the input
    plane, f32) must clear the plane-bytes cap."""
    geom = _pixel_shuffle_geom(spec, phases)
    if geom is None:
        return None
    (th, tw), _ = geom
    h, w = spec.in_hw
    if 4 * batch * th * tw * h * w * spec.in_c > _PLANE_BYTES_MAX:
        return None
    return Route(batch, "pixel_shuffle", None)


def _transposed_route(spec: ConvSpec, hg: int, wg: int, out_hw: Pair,
                      total_taps: int, sum_uv: int, sum_uvt: int,
                      uniform: bool, phases, itemsize: int,
                      batch: int) -> Route:
    """Transposed bucket route + the device-tiling verdict."""
    route = _transposed_route_1dev(spec, hg, wg, out_hw, total_taps, sum_uv,
                                   sum_uvt, uniform, phases, itemsize, batch)
    dev = _dev_verdict(spec, out_hw, itemsize, batch)
    return dataclasses.replace(route, dev_tiles=dev) if dev else route


def _transposed_route_1dev(spec: ConvSpec, hg: int, wg: int, out_hw: Pair,
                           total_taps: int, sum_uv: int, sum_uvt: int,
                           uniform: bool, phases, itemsize: int,
                           batch: int) -> Route:
    """Whole-conv route for the transposed kind at one batch bucket: one
    launch / one wide GEMM, the plane-GEMM intermediate capped at the
    bucket's size."""
    n = spec.out_c
    if total_taps == 0:
        # every phase is empty; executor emits zeros
        return Route(batch, "taps", None)
    pallas = pallas_transposed_routes(spec, hg, wg, out_hw, total_taps,
                                      sum_uv, uniform, phases, itemsize,
                                      batch)
    if pallas:
        return pallas[0]
    # sub-pixel rewrite ahead of the fused routes: exact FLOPs like
    # fused_tap but a Q×-smaller GEMM buffer, and no plane-GEMM blowup
    ps = _pixel_shuffle_route(spec, phases, batch)
    if ps is not None:
        return ps
    plane_ratio = hg * wg * total_taps / max(1, sum_uvt)
    plane_bytes = 4 * batch * hg * wg * total_taps * n
    if plane_ratio <= _PLANE_RATIO_MAX and plane_bytes <= _PLANE_BYTES_MAX:
        return Route(batch, "fused_plane", None)
    if uniform:
        return Route(batch, "fused_tap", None)
    return Route(batch, "taps", None)


def pallas_transposed_routes(spec: ConvSpec, hg: int, wg: int, out_hw: Pair,
                             total_taps: int, sum_uv: int, uniform: bool,
                             phases, itemsize: int,
                             batch: int) -> list[Route]:
    """Every Pallas route of a transposed bucket, preferred first: the
    whole-plane fused kernel when it fits, then the spatially tiled one
    (uniform phases only — equivalently out % stride == 0 — so the
    interleaved output tiles block cleanly).  Empty unless the spec asks
    for Pallas (warned when nothing fits)."""
    if not want_pallas(spec):
        return []
    c, n = spec.in_c, spec.out_c
    oh, ow = out_hw
    witemsize = _weight_itemsize(spec)
    tap_rows = max(ex.out_hw[0] * ex.out_hw[1] for ex in phases)
    routes = []
    tiles = pick_fused_tiles(hg, wg, c, n, total_taps, sum_uv, oh, ow,
                             tap_rows, itemsize, witemsize=witemsize,
                             batch=batch)
    if tiles is not None:
        c_t, n_t, b_t = tiles
        routes.append(Route(batch, "pallas", (c_t, n_t), b_tile=b_t))
    tileable = (uniform and oh % spec.strides[0] == 0
                and ow % spec.strides[1] == 0)
    if tileable:
        tiled = pick_tiled_transposed(c, n, total_taps, phases, spec.strides,
                                      itemsize, witemsize=witemsize)
        if tiled is not None:
            c_t, n_t, sp = tiled
            routes.append(Route(batch, "pallas", (c_t, n_t), sp_tiles=sp))
    if not routes:
        _warn_off_pallas(spec, batch, "the whole plane does not fit the VMEM "
                         "budget" + ("" if tileable else " and non-uniform "
                                     "phases cannot be spatially tiled"))
    return routes


def _route_exact(plan: "ConvPlan", batch: int) -> Route:
    """Re-run the plan-time route choice for an exact (bucket-less) batch —
    the geometry is rebuilt from the plan's own constants."""
    spec = plan.spec
    itemsize = jnp.dtype(spec.dtype).itemsize
    h, w = spec.in_hw
    if spec.kind == "transposed":
        (glh, ghh), (glw, ghw) = plan.gpad
        sum_uvt = sum(ex.out_hw[0] * ex.out_hw[1] * ex.taps[0] * ex.taps[1]
                      for ex in plan.phases)
        return _transposed_route(
            spec, h + glh + ghh, w + glw + ghw, plan.out_hw, plan.total_taps,
            plan.sum_uv, sum_uvt, plan.uniform, plan.phases, itemsize, batch)
    (ph, pw) = spec.padding
    return _single_route(spec, h + ph[0] + ph[1], w + pw[0] + pw[1],
                         plan.out_hw, itemsize, batch)


# ---------------------------------------------------------------------------
# plan
# ---------------------------------------------------------------------------

@dataclasses.dataclass(eq=False)
class ConvPlan:
    """Compiled execution plan.  Identity-hashable (plans are cache singletons),
    so it can ride through ``jax.custom_vjp`` as a static argument."""

    spec: ConvSpec
    out_hw: Pair
    phases: tuple[PhaseExec, ...]          # len 1 for 'conv'/'dilated'
    gpad: tuple[Pair, Pair] | None         # transposed: single global input pad
    total_taps: int                        # Σ_q T_h·T_w (superpack rows / C)
    sum_uv: int                            # Σ_q U·V (fused accumulator rows)
    uniform: bool                          # all phases share (U, V)
    bwd_pad: tuple[Pair, Pair] | None      # transposed: dy padding for dx/dK
    # (m, n, superpack row) tap schedule.  transposed: dx rows of the
    # flipped/swapped read.  conv/dilated: the forward row order m·S+n,
    # walked by both the taps-fallback forward and the backward.
    dx_taps: tuple[tuple, ...] | None
    # per-bucket routes, ascending by Route.batch (one per BATCH_BUCKETS)
    routes: tuple[Route, ...] = ()
    build_ms: float = 0.0
    # True when the routes came from measurement (autotune), not heuristics
    tuned: bool = False
    # memo for batches beyond the largest bucket (plans are cache
    # singletons, so this fills at most once per distinct oversize batch)
    _xl_routes: dict = dataclasses.field(default_factory=dict, repr=False)

    @property
    def path(self) -> str:
        """The B=1 bucket's path (introspection / the benches' headline)."""
        return self.routes[0].path

    @property
    def tiles(self) -> Pair | None:
        """(C_t, N_t) when the B=1 route is 'pallas'."""
        return self.routes[0].tiles

    def route_for_batch(self, batch: int) -> Route:
        """The execution route sized for ``batch``: the smallest plan-time
        bucket that fits it (callers pad up to ``Route.batch``).  A batch
        beyond the largest bucket gets an exactly-sized route, built once
        and memoized — still plan-level arithmetic, never a traced branch."""
        for r in self.routes:
            if batch <= r.batch:
                return r
        if batch not in self._xl_routes:
            self._xl_routes[batch] = _route_exact(self, batch)
        return self._xl_routes[batch]

    def with_routes(self, routes: tuple[Route, ...],
                    tuned: bool = True) -> "ConvPlan":
        """A sibling plan sharing every piece of compiled geometry but with
        a replaced per-bucket route table (how the autotuner installs
        measured winners, and how tests force a route).  The copy is its
        own identity (fresh jit/vjp cache key) with an empty oversize-batch
        memo."""
        return ConvPlan(
            spec=self.spec, out_hw=self.out_hw, phases=self.phases,
            gpad=self.gpad, total_taps=self.total_taps, sum_uv=self.sum_uv,
            uniform=self.uniform, bwd_pad=self.bwd_pad, dx_taps=self.dx_taps,
            routes=tuple(routes), build_ms=self.build_ms, tuned=tuned)

    # -- weight layout -----------------------------------------------------
    def pack(self, kernel: jax.Array):
        """Kernel (R,S,C,N) -> the superpacked GEMM-ready weight buffer.

        'transposed': ``(Σ_q T_h·T_w·C, N)`` — all phase sub-kernels
        flattened tap-major and concatenated in phase order (row offsets
        are plan-time constants).  'conv'/'dilated': the single-phase
        tap-major flatten ``(R·S·C, N)`` — tap ``t = m·S + n`` owns rows
        ``[t·C, (t+1)·C)``; dilation changes the *plan geometry*, never the
        packed layout, so a dilated kernel packs bit-identically to a dense
        one.

        ``wdtype='int8'`` specs emit a ``QuantizedSuperpack`` instead: the
        same tap-major rows quantized per row (``runtime.compress
        .quantize_int8_rows``) with the f32 scale column appended — the
        quantize-at-pack half of the checkpoint round-trip."""
        if self.spec.kind != "transposed":
            r, s = self.spec.kernel_hw
            packed = kernel.reshape(r * s * self.spec.in_c, self.spec.out_c)
            return self._maybe_quantize(packed)
        subs = dec.decompose_kernel(kernel, self.spec.strides,
                                    self.spec.padding)
        c, n = self.spec.in_c, self.spec.out_c
        segs = []
        for ex in self.phases:
            th, tw = ex.taps
            if th * tw == 0:
                continue
            segs.append(subs[ex.q].reshape(th * tw * c, n))
        if not segs:
            packed = jnp.zeros((0, n), kernel.dtype)
        else:
            packed = jnp.concatenate(segs, axis=0)
        return self._maybe_quantize(packed)

    def _maybe_quantize(self, packed):
        """f32 superpack -> ``QuantizedSuperpack`` when the spec stores int8
        weights (idempotent: already-quantized buffers pass through)."""
        if self.spec.wdtype != "int8" or isinstance(packed,
                                                    QuantizedSuperpack):
            return packed
        from repro.runtime.compress import quantize_int8_rows
        q, scale = quantize_int8_rows(packed.astype(jnp.float32))
        return QuantizedSuperpack(q, scale)

    def as_superpack(self, packed):
        """Adapt legacy weight layouts onto the superpack; superpack arrays
        pass through unchanged.  Transposed: per-phase dicts ({'q0x1': buf}
        or {(0,1): buf}) from pre-superpack checkpoints.  'conv'/'dilated':
        full (R,S,C,N) HWIO kernels from pre-superpack params (the flatten
        is free — same memory order).  ``wdtype='int8'`` specs quantize any
        float layout they adapt, so f32 checkpoints load straight into a
        quantized plan; a ``QuantizedSuperpack`` passes through unchanged."""
        if isinstance(packed, QuantizedSuperpack):
            return packed
        if not isinstance(packed, dict):
            if self.spec.kind != "transposed" and getattr(
                    packed, "ndim", 2) == 4:
                return self.pack(packed)
            return self._maybe_quantize(packed)
        segs = []
        for ex in self.phases:
            if ex.taps[0] * ex.taps[1] == 0:
                continue
            sub = packed[ex.key] if ex.key in packed else packed[ex.q]
            segs.append(sub.reshape(-1, self.spec.out_c))
        if not segs:
            return self._maybe_quantize(
                jnp.zeros((0, self.spec.out_c), self.spec.dtype))
        return self._maybe_quantize(jnp.concatenate(segs, axis=0))

    def unpack(self, packed):
        """Packed weights -> full (R,S,C,N) kernel (offline use only).
        Accepts the superpack, a full HWIO kernel, or (transposed) a legacy
        per-phase dict; round-trips ``pack`` exactly, so checkpoints survive
        the layout migration.  A ``QuantizedSuperpack`` dequantizes first
        (``runtime.compress.dequantize_int8``), so an int8 checkpoint
        round-trips to HWIO within one quantization step per element."""
        packed = self.as_superpack(packed)
        if isinstance(packed, QuantizedSuperpack):
            packed = packed.dequant()
        if self.spec.kind != "transposed":
            r, s = self.spec.kernel_hw
            return packed.reshape(r, s, self.spec.in_c, self.spec.out_c)
        r, s = self.spec.kernel_hw
        c, n = self.spec.in_c, self.spec.out_c
        (sh, sw) = self.spec.strides
        kernel = jnp.zeros((r, s, c, n), packed.dtype)
        for ex in self.phases:
            th, tw = ex.taps
            if th * tw == 0:
                continue
            sub = jax.lax.slice(packed, [ex.tap_off * c, 0],
                                [(ex.tap_off + th * tw) * c, n])
            kernel = kernel.at[ex.rho[0]::sh, ex.rho[1]::sw].set(
                sub.reshape(th, tw, c, n))
        return kernel

    # -- execution ---------------------------------------------------------
    def apply(self, x: jax.Array, packed) -> jax.Array:
        """Planned execution on packed weights (differentiable)."""
        if (tuple(x.shape[-3:-1]) != self.spec.in_hw
                or x.shape[-1] != self.spec.in_c):
            raise ValueError(
                f"input {x.shape[-3:]} does not match plan spec "
                f"{self.spec.in_hw + (self.spec.in_c,)} — plans bake geometry "
                f"at build time; plan_conv a spec for this shape")
        if self.spec.spatial != (1, 1):
            # plane-parallel dispatch sits *above* the custom VJP: jax
            # differentiates through the shard_map (the shard-local plan's
            # own VJP runs per device), so the backward is plane-parallel
            # too.  Returns None without a matching bound mesh — the
            # route's single-device path/tiles fields take over below.
            from repro.core import spatial
            y = spatial.try_spatial(self, x, packed)
            if y is not None:
                return y
        if self.spec.kind == "transposed":
            return _planned_transposed(self, x, self.as_superpack(packed))
        return _planned_single(self, x, self.as_superpack(packed))

    __call__ = apply

    def apply_kernel(self, x: jax.Array, kernel: jax.Array) -> jax.Array:
        """Compatibility path: pack per call, then execute.  Under jit this
        re-slices the kernel every invocation — serve from ``pack`` instead."""
        return self.apply(x, self.pack(kernel))

    def apply_per_phase(self, x: jax.Array, packed) -> jax.Array:
        """The pre-fusion per-phase executor (one pad + GEMM chain per phase,
        stack/transpose interleave).  Kept as the measurement baseline for
        the fused single-launch path and as a parity oracle in tests; not
        differentiable through the custom VJP."""
        if self.spec.kind != "transposed":
            return self.apply(x, packed)
        return _transposed_per_phase(self, x, self.as_superpack(packed))


def plan_conv(spec: ConvSpec, autotune=None) -> ConvPlan:
    """Compile ``spec`` into a ``ConvPlan`` (LRU-cached; one build per live
    site).  ``autotune`` is an optional ``repro.core.autotune
    .AutotunePolicy``: when set, the heuristic per-bucket routes are
    replaced by measured winners — cached per-host results when available,
    live microbenchmarks on a cache miss under ``mode='measure'`` — with
    heuristic routes as the universal fallback (cold cache, unmeasurable
    candidates, unreadable cache file)."""
    plan = _plan_conv_heuristic(spec)
    if autotune is None or getattr(autotune, "mode", "off") == "off":
        return plan
    from repro.core.autotune import autotune_plan
    return autotune_plan(plan, autotune)


@functools.lru_cache(maxsize=4096)
def _plan_conv_heuristic(spec: ConvSpec) -> ConvPlan:
    """The heuristic compile: geometry + analytic per-bucket routes (the
    bound only matters for workloads cycling through thousands of distinct
    shapes, which evict oldest-first rather than grow unbounded)."""
    t0 = time.perf_counter()
    if spec.wdtype not in _WDTYPES:
        raise ValueError(f"unsupported wdtype {spec.wdtype!r} "
                         f"(supported: {_WDTYPES})")
    itemsize = jnp.dtype(spec.dtype).itemsize
    h, w = spec.in_hw
    r, s = spec.kernel_hw
    c, n = spec.in_c, spec.out_c
    (sh, sw) = spec.strides
    (ph, pw) = spec.padding

    if spec.kind == "transposed":
        if spec.dilation != (1, 1):
            raise ValueError("transposed plans do not support rhs dilation")
        plans_h = dec.plan_phases_1d(h, r, sh, ph)
        plans_w = dec.plan_phases_1d(w, s, sw, pw)
        oh = dec.transposed_out_size(h, r, sh, ph)
        ow = dec.transposed_out_size(w, s, sw, pw)
        # single global pad: one residency of the input serves every phase
        # (phase tap origins become plan-time offsets into the padded plane)
        gl_h = max(0, max(p.pad[0] for p in plans_h))
        gh_h = max(0, max(p.pad[1] for p in plans_h))
        gl_w = max(0, max(p.pad[0] for p in plans_w))
        gh_w = max(0, max(p.pad[1] for p in plans_w))
        gpad = ((gl_h, gh_h), (gl_w, gh_w))
        hg, wg = h + gl_h + gh_h, w + gl_w + gh_w
        phases = []
        tap_off = acc_off = sum_uvt = 0
        for p_h in plans_h:
            for p_w in plans_w:
                taps = (p_h.taps, p_w.taps)
                out_hw = (p_h.out_size, p_w.out_size)
                phases.append(PhaseExec(
                    key=f"q{p_h.phase}x{p_w.phase}", q=(p_h.phase, p_w.phase),
                    rho=(p_h.rho, p_w.rho), taps=taps,
                    pad=(p_h.pad, p_w.pad), out_hw=out_hw,
                    tap_off=tap_off, acc_off=acc_off,
                    xoff=(gl_h - p_h.pad[0], gl_w - p_w.pad[0])))
                tap_off += taps[0] * taps[1]
                acc_off += out_hw[0] * out_hw[1]
                sum_uvt += out_hw[0] * out_hw[1] * taps[0] * taps[1]
        total_taps, sum_uv = tap_off, acc_off
        uniform = len({ex.out_hw for ex in phases}) == 1
        routes = tuple(_transposed_route(
            spec, hg, wg, (oh, ow), total_taps, sum_uv, sum_uvt, uniform,
            tuple(phases), itemsize, bb) for bb in BATCH_BUCKETS)
        # dx schedule (strided-conv form): tap (m, n) of the flipped/swapped
        # kernel reads full-kernel tap (r-1-m, s-1-n), which lives in phase
        # ((pl-r') % s) at superpack row tap_off + r'//s (tap units).
        by_q = {ex.q: ex for ex in phases}
        dx_taps = []
        for m in range(r):
            for nn in range(s):
                rp, sp = r - 1 - m, s - 1 - nn
                qh, qw = (ph[0] - rp) % sh, (pw[0] - sp) % sw
                ex = by_q[(qh, qw)]
                row = ex.tap_off + (rp // sh) * ex.taps[1] + (sp // sw)
                dx_taps.append((m, nn, row))
        bwd_pad = ((r - 1 - ph[0], r - 1 - ph[1]),
                   (s - 1 - pw[0], s - 1 - pw[1]))
        plan = ConvPlan(spec=spec, out_hw=(oh, ow), phases=tuple(phases),
                        gpad=gpad, total_taps=total_taps, sum_uv=sum_uv,
                        uniform=uniform, bwd_pad=bwd_pad,
                        dx_taps=tuple(dx_taps), routes=routes)

    elif spec.kind in ("conv", "dilated"):
        (dh, dw) = spec.dilation if spec.kind == "dilated" else (1, 1)
        hp, wp = h + ph[0] + ph[1], w + pw[0] + pw[1]
        oh = dec.single_out_size(h, r, sh, dh, ph)
        ow = dec.single_out_size(w, s, sw, dw, pw)
        if oh <= 0 or ow <= 0:
            raise ValueError(f"non-positive output {oh}x{ow}")
        routes = tuple(_single_route(spec, hp, wp, (oh, ow), itemsize, bb)
                       for bb in BATCH_BUCKETS)
        ex = PhaseExec(key="k", q=(0, 0), rho=(0, 0), taps=(r, s),
                       pad=spec.padding, out_hw=(oh, ow))
        # superpack row of tap (m, n) is m*S + n — recorded like the
        # transposed dx schedule so the backward never re-derives layout.
        taps_sched = tuple((m, nn, m * s + nn)
                           for m in range(r) for nn in range(s))
        plan = ConvPlan(spec=spec, out_hw=(oh, ow), phases=(ex,),
                        gpad=None, total_taps=r * s, sum_uv=oh * ow,
                        uniform=True, bwd_pad=None, dx_taps=taps_sched,
                        routes=routes)
    else:
        raise ValueError(f"unknown conv kind {spec.kind!r}")

    plan.build_ms = (time.perf_counter() - t0) * 1e3
    return plan


def plan_cache_info():
    return _plan_conv_heuristic.cache_info()


def plan_cache_clear():
    _plan_conv_heuristic.cache_clear()
    # tuned plans / loaded route caches index into the heuristic plans;
    # drop them together so patched-constant contexts rebuild both sides
    import sys
    autotune = sys.modules.get("repro.core.autotune")
    if autotune is not None:
        autotune.reset()
    spatial = sys.modules.get("repro.core.spatial")
    if spatial is not None:
        spatial.reset()


# ---------------------------------------------------------------------------
# executors (all geometry is plan-time constant)
# ---------------------------------------------------------------------------

def _deq(packed):
    """The f32 superpack view of either layout: identity on dense buffers,
    the dequant-on-the-fly broadcast multiply on a ``QuantizedSuperpack``
    (one ``convert_element_type`` + one ``mul`` ahead of the consuming
    GEMM — every fused route keeps its single dot_general)."""
    if isinstance(packed, QuantizedSuperpack):
        return packed.dequant()
    return packed


def _weight_cotangent(packed, dk):
    """The backward's cotangent for the packed operand.  Dense superpacks
    take the f32 dK directly.  Quantized superpacks chain through
    ``w = q · scale``: the int8 codes are non-differentiable (float0 —
    there is nothing to train there), the scale column gets the exact
    ``dscale[row] = Σ_n dK[row, n] · q[row, n]``."""
    if not isinstance(packed, QuantizedSuperpack):
        return dk.astype(packed.dtype)
    import numpy as np
    dscale = jnp.sum(dk.astype(jnp.float32) * packed.q.astype(jnp.float32),
                     axis=-1, keepdims=True).astype(packed.scale.dtype)
    dq = np.zeros(packed.q.shape, jax.dtypes.float0)
    return QuantizedSuperpack(dq, dscale)


def _exec_phase(xp: jax.Array, sub4: jax.Array, path: str, tiles: Pair | None,
                taps: Pair, out_hw: Pair, strides: Pair, dilation: Pair,
                out_dtype, interpret=None) -> jax.Array:
    """One planned stride/dilation correlation of pre-padded ``xp`` with the
    4-D sub-kernel, along the path chosen at plan time."""
    th, tw = taps
    u, v = out_hw
    (sh, sw), (dh, dw) = strides, dilation
    cc = xp.shape[-1]

    def tap_view(m, nn):
        return jax.lax.slice(
            xp, [0] * (xp.ndim - 3) + [m * dh, nn * dw, 0],
            list(xp.shape[:-3]) + [m * dh + (u - 1) * sh + 1,
                                   nn * dw + (v - 1) * sw + 1, cc],
            [1] * (xp.ndim - 3) + [sh, sw, 1])

    if path == "pallas":
        from repro.kernels.untangled_conv import untangled_conv2d_pallas
        lead = xp.shape[:-3]
        xp4 = xp.reshape((-1,) + xp.shape[-3:])
        y = untangled_conv2d_pallas(xp4, sub4, strides=strides,
                                    rhs_dilation=dilation,
                                    c_tile=tiles[0], n_tile=tiles[1],
                                    out_dtype=out_dtype, interpret=interpret)
        return y.reshape(lead + y.shape[1:])
    if path == "fused":
        buf = jnp.concatenate([tap_view(m, nn) for m in range(th)
                               for nn in range(tw)], axis=-1)
        w2 = sub4.reshape(th * tw * cc, sub4.shape[-1])
        y = jax.lax.dot_general(buf, w2, (((buf.ndim - 1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)
        return y.astype(out_dtype)
    acc = None
    for m in range(th):
        for nn in range(tw):
            xs = tap_view(m, nn)
            t = jax.lax.dot_general(
                xs, sub4[m, nn], (((xs.ndim - 1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            acc = t if acc is None else acc + t
    return acc.astype(out_dtype)


# -- transposed: fused single-launch executors ------------------------------

def _global_plane(plan: ConvPlan, x4: jax.Array,
                  batch: int | None = None) -> jax.Array:
    """``x4`` padded to the global plane, and with zero images up to
    ``batch`` when that is given."""
    (glh, ghh), (glw, ghw) = plan.gpad
    bpad = 0 if batch is None else batch - x4.shape[0]
    if bpad or glh or ghh or glw or ghw:
        return jnp.pad(x4, ((0, bpad), (glh, ghh), (glw, ghw), (0, 0)))
    return x4


def _phase_tap_view(xg: jax.Array, ex: PhaseExec, ti: int, tj: int):
    u, v = ex.out_hw
    return jax.lax.slice(
        xg, [0, ex.xoff[0] + ti, ex.xoff[1] + tj, 0],
        [xg.shape[0], ex.xoff[0] + ti + u, ex.xoff[1] + tj + v, xg.shape[3]])


def _fused_tap_fwd(plan: ConvPlan, xg: jax.Array, packed: jax.Array):
    """One wide GEMM, exact FLOPs: every tap view of every phase stacked
    against the superpack (ΣT, C, N), then per-phase tap-segment sums."""
    spec = plan.spec
    c, n = spec.in_c, spec.out_c
    b = xg.shape[0]
    views = []
    for ex in plan.phases:
        th, tw = ex.taps
        for t in range(th * tw):
            views.append(_phase_tap_view(xg, ex, *divmod(t, tw)))
    buf = jnp.stack(views, axis=0)                     # (ΣT, B, U, V, C)
    w3 = packed.reshape(plan.total_taps, c, n)
    yt = jax.lax.dot_general(buf, w3, (((4,), (1,)), ((0,), (0,))),
                             preferred_element_type=jnp.float32)
    outs = []
    for ex in plan.phases:
        th, tw = ex.taps
        u, v = ex.out_hw
        if th * tw == 0:
            outs.append(jnp.zeros((b, u, v, n), jnp.float32))
            continue
        outs.append(yt[ex.tap_off:ex.tap_off + th * tw].sum(axis=0))
    return outs


def _fused_plane_fwd(plan: ConvPlan, xg: jax.Array, packed: jax.Array):
    """One wide GEMM of the whole resident plane against the superpack viewed
    (C, ΣT·N); per-phase shifted slice-accumulate reads the tap planes."""
    spec = plan.spec
    c, n = spec.in_c, spec.out_c
    b, hg, wg, _ = xg.shape
    w2 = packed.reshape(plan.total_taps, c, n).transpose(1, 0, 2) \
        .reshape(c, plan.total_taps * n)
    yf = jax.lax.dot_general(xg.reshape(b * hg * wg, c), w2,
                             (((1,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)
    yf = yf.reshape(b, hg, wg, plan.total_taps, n)
    outs = []
    for ex in plan.phases:
        th, tw = ex.taps
        u, v = ex.out_hw
        if th * tw == 0 or u == 0 or v == 0:
            outs.append(jnp.zeros((b, u, v, n), jnp.float32))
            continue
        acc = None
        for t in range(th * tw):
            ti, tj = divmod(t, tw)
            sl = jax.lax.slice(
                yf, [0, ex.xoff[0] + ti, ex.xoff[1] + tj, ex.tap_off + t, 0],
                [b, ex.xoff[0] + ti + u, ex.xoff[1] + tj + v,
                 ex.tap_off + t + 1, n])[..., 0, :]
            acc = sl if acc is None else acc + sl
        outs.append(acc)
    return outs


def _pixel_shuffle_fwd(plan: ConvPlan, x4: jax.Array, packed: jax.Array):
    """Sub-pixel route: the eligible transposed conv as ONE dense stride-1
    correlation + depth-to-space.

    Eligibility (``_pixel_shuffle_geom``) guarantees every phase shares the
    same pad, tap extent and ``(U, V) == (H, W)`` output, so one padded
    plane serves all Q phases and the superpack — phase-major ``(Q·T·C,
    N)`` — reshapes to ``(Q, T, C, N)`` with zero data movement.  The T
    shared tap views stack to ``(T, B, H, W, C)`` (concat, no transpose)
    and a single ``dot_general`` contracting (tap, C) against (T, C) yields
    ``(B, H, W, Q, N)``; the trailing reshape/transpose/reshape IS
    depth-to-space (phases are q_h-major, matching the ``(s_h, s_w)``
    split) and is the route's only transpose."""
    spec = plan.spec
    sh, sw = spec.strides
    c, n = spec.in_c, spec.out_c
    th, tw = plan.phases[0].taps
    h, w = spec.in_hw
    xp = pad_or_crop(x4, plan.phases[0].pad)
    b = xp.shape[0]
    views = [jax.lax.slice(xp, [0, ti, tj, 0], [b, ti + h, tj + w, c])
             for ti in range(th) for tj in range(tw)]
    buf = jnp.stack(views, axis=0)                    # (T, B, H, W, C)
    w4 = packed.reshape(sh * sw, th * tw, c, n)       # (Q, T, C, N)
    y = jax.lax.dot_general(buf, w4, (((0, 4), (1, 2)), ((), ())),
                            preferred_element_type=jnp.float32)
    y = y.reshape(b, h, w, sh, sw, n).transpose(0, 1, 3, 2, 4, 5)
    return y.reshape(b, h * sh, w * sw, n)


def _taps_fallback_fwd(plan: ConvPlan, xg: jax.Array, packed: jax.Array):
    """General fallback: still one global pad (phases read the single
    resident plane through plan-time offsets), but per-phase GEMMs."""
    spec = plan.spec
    c, n = spec.in_c, spec.out_c
    b = xg.shape[0]
    outs = {}
    for ex in plan.phases:
        th, tw = ex.taps
        u, v = ex.out_hw
        if th * tw == 0 or u == 0 or v == 0:
            outs[ex.q] = jnp.zeros((b, u, v, n), xg.dtype)
            continue
        seg = jax.lax.slice(packed, [ex.tap_off * c, 0],
                            [(ex.tap_off + th * tw) * c, n])
        if u * v <= _FUSE_MAX_ROWS and th * tw > 2:
            buf = jnp.concatenate(
                [_phase_tap_view(xg, ex, *divmod(t, tw))
                 for t in range(th * tw)], axis=-1)
            acc = jax.lax.dot_general(buf, seg, (((3,), (0,)), ((), ())),
                                      preferred_element_type=jnp.float32)
        else:
            acc = None
            for t in range(th * tw):
                xs = _phase_tap_view(xg, ex, *divmod(t, tw))
                wt = jax.lax.slice(packed, [(ex.tap_off + t) * c, 0],
                                   [(ex.tap_off + t + 1) * c, n])
                term = jax.lax.dot_general(
                    xs, wt, (((3,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)
                acc = term if acc is None else acc + term
        outs[ex.q] = acc.astype(xg.dtype)
    return dec.interleave_phases(outs, spec.strides, plan.out_hw)


def _transposed_fwd(plan: ConvPlan, x, packed, interpret=None):
    spec = plan.spec
    lead = x.shape[:-3]
    x4 = x.reshape((-1,) + x.shape[-3:])
    b = x4.shape[0]
    if plan.total_taps == 0:
        y = jnp.zeros((b, *plan.out_hw, spec.out_c), x.dtype)
        return y.reshape(lead + y.shape[1:])
    # the bucket's route was sized against the byte caps at plan time —
    # a large batch lands on a bucket whose plane-GEMM intermediate fits
    route = plan.route_for_batch(b)
    path = route.path
    if path == "per_phase":
        # autotune-only route: the per-phase executor measured faster than
        # any fused whole-conv launch on this host (pads per phase, so it
        # bypasses the global plane below)
        y = _transposed_per_phase(plan, x4, _deq(packed))
        return y.reshape(lead + y.shape[1:])
    if path == "pixel_shuffle":
        # sub-pixel route: pads with the shared phase footprint directly
        # (eligibility guarantees one pad fits all phases), so it bypasses
        # the global plane below
        y = _pixel_shuffle_fwd(plan, x4, _deq(packed)).astype(x.dtype)
        return y.reshape(lead + y.shape[1:])
    # B_t divides the route's bucket, not every batch the bucket takes: the
    # fused kernel runs whole batch blocks of a zero-padded batch (never
    # past the bucket its working set was sized for)
    xg = _global_plane(plan, x4, -(-b // route.b_tile) * route.b_tile)
    if path == "pallas":
        from repro.kernels.untangled_conv import untangled_deconv2d_pallas
        quant = isinstance(packed, QuantizedSuperpack)
        y = untangled_deconv2d_pallas(
            xg, packed.q if quant else packed,
            scales=packed.scale if quant else None,
            phases=plan.phases, out_hw=plan.out_hw,
            strides=spec.strides, sum_uv=plan.sum_uv,
            c_tile=route.tiles[0], n_tile=route.tiles[1],
            b_tile=route.b_tile, sp_tiles=route.sp_tiles, out_dtype=x.dtype,
            interpret=interpret)[:b]
    elif path in ("fused_tap", "fused_plane"):
        fwd = _fused_tap_fwd if path == "fused_tap" else _fused_plane_fwd
        outs = fwd(plan, xg, _deq(packed))
        y = dec.interleave_uniform(outs, spec.strides, plan.out_hw) \
            .astype(x.dtype) if plan.uniform else dec.interleave_phases(
                {ex.q: o.astype(x.dtype)
                 for ex, o in zip(plan.phases, outs)},
                spec.strides, plan.out_hw)
    else:
        y = _taps_fallback_fwd(plan, xg, _deq(packed))
    return y.reshape(lead + y.shape[1:])


def _transposed_per_phase(plan: ConvPlan, x, packed):
    """Pre-fusion executor: pad/copy + GEMM chain per phase, then
    stack/transpose interleave (the PR-1 baseline)."""
    spec = plan.spec
    c, n = spec.in_c, spec.out_c
    itemsize = jnp.dtype(spec.dtype).itemsize
    outs = {}
    for ex in plan.phases:
        th, tw = ex.taps
        u, v = ex.out_hw
        if th * tw == 0 or u == 0 or v == 0:
            outs[ex.q] = jnp.zeros(
                (*x.shape[:-3], u, v, n), x.dtype)
            continue
        sub4 = jax.lax.slice(packed, [ex.tap_off * c, 0],
                             [(ex.tap_off + th * tw) * c, n]) \
            .reshape(th, tw, c, n)
        xp = pad_or_crop(x, ex.pad)
        hp, wp = xp.shape[-3], xp.shape[-2]
        # same per-phase path policy PR 1 used (incl. per-phase Pallas when
        # the plan's backend asks for it) — this IS the measured baseline
        path, tiles = _choose_path(spec.backend, hp, wp, c, n, ex.taps,
                                   ex.out_hw, itemsize)
        outs[ex.q] = _exec_phase(xp, sub4, path, tiles, ex.taps, ex.out_hw,
                                 (1, 1), (1, 1), x.dtype)
    return dec.interleave_phases(outs, spec.strides, plan.out_hw)


# -- single-correlation ('conv' / 'dilated'): superpack executors -----------

def _single_geom(plan: ConvPlan):
    spec = plan.spec
    (dh, dw) = spec.dilation if spec.kind == "dilated" else (1, 1)
    return spec.strides, (dh, dw), spec.kernel_hw, plan.out_hw


def _single_tap_view(xp: jax.Array, m: int, nn: int, strides: Pair,
                     dilation: Pair, out_hw: Pair):
    """Tap (m, n)'s strided/dilated window of the resident padded plane —
    the zero-free read the naive engine replaces with kernel zero-insertion."""
    (sh, sw), (dh, dw) = strides, dilation
    u, v = out_hw
    return jax.lax.slice(
        xp, [0, m * dh, nn * dw, 0],
        [xp.shape[0], m * dh + (u - 1) * sh + 1, nn * dw + (v - 1) * sw + 1,
         xp.shape[3]],
        [1, sh, sw, 1])


def _single_fwd(plan: ConvPlan, x, packed, interpret=None):
    """Planned single-correlation forward on the (R·S·C, N) superpack:
    pad once, keep the plane resident, shift-and-add tap GEMMs."""
    spec = plan.spec
    strides, dilation, (r, s), out_hw = _single_geom(plan)
    c, n = spec.in_c, spec.out_c
    lead = x.shape[:-3]
    x4 = x.reshape((-1,) + x.shape[-3:])
    xp = pad_or_crop(x4, spec.padding)
    route = plan.route_for_batch(x4.shape[0])
    path = route.path
    if path == "pallas":
        from repro.kernels.untangled_conv import untangled_conv2d_superpack_pallas
        quant = isinstance(packed, QuantizedSuperpack)
        y = untangled_conv2d_superpack_pallas(
            xp, packed.q if quant else packed,
            scales=packed.scale if quant else None,
            taps_hw=(r, s), strides=strides,
            rhs_dilation=dilation, c_tile=route.tiles[0],
            n_tile=route.tiles[1], sp_tiles=route.sp_tiles,
            out_dtype=x.dtype, interpret=interpret)
    elif path == "fused_tap":
        # ONE wide GEMM: tap views concatenated channel-major in superpack
        # row order against the whole (R·S·C, N) buffer (dequantized on the
        # fly for int8 superpacks — still exactly one dot_general).
        buf = jnp.concatenate(
            [_single_tap_view(xp, m, nn, strides, dilation, out_hw)
             for m in range(r) for nn in range(s)], axis=-1)
        y = jax.lax.dot_general(buf, _deq(packed), (((3,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)
        y = y.astype(x.dtype)
    else:
        # per-tap shift-and-add GEMMs; panels are superpack rows [t·C,(t+1)·C)
        w = _deq(packed)
        acc = None
        for (m, nn, row) in plan.dx_taps:
            xs = _single_tap_view(xp, m, nn, strides, dilation, out_hw)
            panel = jax.lax.slice(w, [row * c, 0], [(row + 1) * c, n])
            t = jax.lax.dot_general(xs, panel, (((3,), (0,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            acc = t if acc is None else acc + t
        y = acc.astype(x.dtype)
    return y.reshape(lead + y.shape[1:])


# ---------------------------------------------------------------------------
# transposed conv: custom VJP on the superpack (§3.2.3, Fig. 6)
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _planned_transposed(plan: ConvPlan, x, packed):
    return _transposed_fwd(plan, x, packed)


def _pt_fwd(plan, x, packed):
    return _transposed_fwd(plan, x, packed), (x, packed)


def _pt_bwd(plan, res, dy):
    x, packed = res
    spec = plan.spec
    h, w = spec.in_hw
    r, s = spec.kernel_hw
    (sh, sw) = spec.strides
    c = spec.in_c
    x4 = x.reshape((-1,) + x.shape[-3:])
    dy4 = dy.reshape((-1,) + dy.shape[-3:])
    dy_p = pad_or_crop(dy4, plan.bwd_pad)

    # dx — strided-conv form, panels fetched from the superpack at the
    # plan-time row offsets (dequantized once for int8 superpacks).
    wdq = _deq(packed)
    acc = None
    for (m, nn, row) in plan.dx_taps:
        panel = jax.lax.slice(wdq, [row * c, 0],
                              [(row + 1) * c, spec.out_c])   # (C, N)
        wnd = jax.lax.slice(
            dy_p, [0, m, nn, 0],
            [dy_p.shape[0], m + sh * (h - 1) + 1, nn + sw * (w - 1) + 1,
             dy_p.shape[3]], [1, sh, sw, 1])
        t = jax.lax.dot_general(wnd, panel, (((wnd.ndim - 1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        acc = t if acc is None else acc + t
    dx = acc.astype(x.dtype).reshape(x.shape)

    # dK — dilated-kernel form, emitted directly in superpack order.
    dk_segs = []
    for ex in plan.phases:
        th, tw = ex.taps
        if th * tw == 0:
            continue
        rows = []
        for t_h in range(th):
            rr = ex.rho[0] + sh * t_h
            cols = []
            for t_w in range(tw):
                ss = ex.rho[1] + sw * t_w
                wnd = jax.lax.slice(
                    dy_p, [0, r - 1 - rr, s - 1 - ss, 0],
                    [dy_p.shape[0], r - 1 - rr + sh * (h - 1) + 1,
                     s - 1 - ss + sw * (w - 1) + 1, dy_p.shape[3]],
                    [1, sh, sw, 1])
                cols.append(jnp.einsum("buvc,buvn->cn", x4, wnd,
                                       preferred_element_type=jnp.float32))
            rows.append(jnp.stack(cols, 0))
        sub = jnp.stack(rows, 0)                      # (T_h, T_w, C, N)
        dk_segs.append(sub.reshape(th * tw * c, spec.out_c))
    if dk_segs:
        dk = jnp.concatenate(dk_segs, axis=0)
    else:
        dk = jnp.zeros(packed.shape, jnp.float32)
    return dx, _weight_cotangent(packed, dk)


_planned_transposed.defvjp(_pt_fwd, _pt_bwd)


# ---------------------------------------------------------------------------
# single correlation ('conv' / 'dilated'): custom VJP on the superpack,
# mirroring _pt_bwd — no flipped kernel is ever assembled, no zero inserted
# ---------------------------------------------------------------------------

def _unpad_transpose(dxp: jax.Array, pads, in_hw: Pair) -> jax.Array:
    """Exact transpose of ``pad_or_crop``: slice off the positive pads,
    zero-pad back anything the forward cropped (negative pads)."""
    (ph, pw) = pads
    hp, wp = dxp.shape[-3], dxp.shape[-2]
    dx = dxp[..., max(0, ph[0]):hp - max(0, ph[1]),
             max(0, pw[0]):wp - max(0, pw[1]), :]
    grow = [(0, 0)] * (dxp.ndim - 3) + [
        (max(0, -ph[0]), max(0, -ph[1])),
        (max(0, -pw[0]), max(0, -pw[1])), (0, 0)]
    if any(g != (0, 0) for g in grow):
        dx = jnp.pad(dx, grow)
    assert dx.shape[-3] == in_hw[0] and dx.shape[-2] == in_hw[1]
    return dx


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _planned_single(plan: ConvPlan, x, packed):
    return _single_fwd(plan, x, packed)


def _ps_fwd(plan, x, packed):
    return _single_fwd(plan, x, packed), (x, packed)


def _ps_bwd(plan, res, dy):
    x, packed = res
    spec = plan.spec
    strides, dilation, (r, s), (oh, ow) = _single_geom(plan)
    (sh, sw), (dh, dw) = strides, dilation
    c, n = spec.in_c, spec.out_c
    x4 = x.reshape((-1,) + x.shape[-3:])
    dy4 = dy.reshape((-1,) + dy.shape[-3:])
    xp = pad_or_crop(x4, spec.padding)
    b, hp, wp = xp.shape[0], xp.shape[1], xp.shape[2]
    # the fused backward materializes (B, OH, OW, ΣT, C) f32 buffers; the
    # bucket's route carries the same plane-bytes verdict that governs the
    # forward, falling back to per-tap GEMMs on exactly the plans that need it
    fused_bwd = plan.route_for_batch(b).fused_bwd

    # dx — transposed-tap form: GEMMs of dy against superpack (C, N) panels
    # (one wide GEMM over the (ΣT, C, N) view when the buffer fits), each
    # tap's plane scattered back through the exact transpose of its forward
    # strided/dilated read.
    wdq = _deq(packed)
    g = None
    if fused_bwd:
        w3 = wdq.reshape(r * s, c, n)
        g = jax.lax.dot_general(dy4, w3, (((3,), (2,)), ((), ())),
                                preferred_element_type=jnp.float32)
        # g: (B, OH, OW, ΣT, C)
    dxp = jnp.zeros((b, hp, wp, c), jnp.float32)
    for (m, nn, row) in plan.dx_taps:
        if g is not None:
            gt = g[..., row, :]
        else:
            panel = jax.lax.slice(wdq, [row * c, 0], [(row + 1) * c, n])
            gt = jax.lax.dot_general(dy4, panel, (((3,), (1,)), ((), ())),
                                     preferred_element_type=jnp.float32)
        dxp = dxp.at[:, m * dh:m * dh + (oh - 1) * sh + 1:sh,
                     nn * dw:nn * dw + (ow - 1) * sw + 1:sw, :].add(gt)
    dx = _unpad_transpose(dxp, spec.padding, spec.in_hw)
    dx = dx.astype(x.dtype).reshape(x.shape)

    # dK — tap views of the resident plane against dy (one GEMM over the
    # stacked views when they fit, else per tap), emitted directly in
    # superpack row order (paper Fig. 6 step 3, packed layout).
    if fused_bwd:
        buf = jnp.stack(
            [_single_tap_view(xp, m, nn, strides, dilation, (oh, ow))
             for (m, nn, _) in plan.dx_taps], axis=0)
        dk3 = jax.lax.dot_general(buf, dy4,
                                  (((1, 2, 3), (0, 1, 2)), ((), ())),
                                  preferred_element_type=jnp.float32)
        dk = dk3.reshape(r * s * c, n)
    else:
        dk = jnp.concatenate(
            [jax.lax.dot_general(
                _single_tap_view(xp, m, nn, strides, dilation, (oh, ow)),
                dy4, (((0, 1, 2), (0, 1, 2)), ((), ())),
                preferred_element_type=jnp.float32)
             for (m, nn, _) in plan.dx_taps], axis=0)
    return dx, _weight_cotangent(packed, dk)


_planned_single.defvjp(_ps_fwd, _ps_bwd)
