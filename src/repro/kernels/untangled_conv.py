"""Pallas TPU kernel for the untangled (tap-accumulated GEMM) convolution.

One kernel instance computes a standard / strided / dilated correlation of an
NHWC input with an HWIO kernel as the paper's §3.2 sum of per-tap 1x1 convs:

    acc[(OH*OW), N_t] += X_vmem[tap-slice].reshape(OH*OW, C_t) @ K[m, n][C_t, N_t]

TPU mapping decisions (the HUGE2 "cache locality" story, restated for VMEM/MXU):

* the whole (padded) spatial plane of a batch item lives in VMEM for the
  duration of a (C_t, N_t) tile — every tap re-reads it from VMEM, never HBM.
  Edge-generative workloads have small planes (4..64 px) and fat channels,
  exactly the regime where this blocking wins (paper §4.1).
* the kernel is held tap-major ``(R·S, C_t, N_t)`` — the superpack layout
  ``ConvPlan.pack`` emits: each tap's (C_t, N_t) panel is a contiguous VMEM
  tile feeding the MXU with N on the lane axis — the TPU analogue of the
  paper's C×N×R×S coalescing layout.  Strided and dilated correlations run
  the *same* kernel; dilation only moves each tap's read origin inside the
  resident plane (no zero-inserted kernel exists anywhere).  A strided tap
  is a **strided ref load** (``pl.ds(start, size, stride)`` on the H and W
  dims of the VMEM block), never a strided value slice.
* taps are a *static* unrolled loop of MXU matmuls accumulating straight
  into an f32 VMEM scratch; the C grid axis is innermost-sequential so the
  accumulator carries across C tiles (revisiting semantics).
* channel tiles are one lane tile: ``C_t = min(C, 128)`` and ``N_t = min(N,
  128)`` (``lane_tile``).  128 is the v5e MXU width, and Mosaic's strided
  loads and stores accept a last dim of at most one lane tile.

``_deconv_kernel`` extends the same mapping to the *fused* transposed conv:
ONE launch computes every s_h*s_w output phase over a single VMEM residency
of the globally padded plane.  Each phase's taps accumulate into its segment
of a shared f32 scratch (plan-time ``acc_off`` row offsets), the superpack
weight buffer rides in tap-major ``(ΣT, C_t, N_t)``, and the flush writes
the **interleaved** output block directly with strided in-kernel stores —
no per-phase launches, no per-phase input copies, no stack/transpose
interleave pass.

Grid: ``(B/B_t, N/N_t, C/C_t)`` — C innermost (reduction).  The fused
kernel blocks the batch: ``B_t`` images share one VMEM residency of each
superpack tile, so a launch fetches every tile ``B/B_t`` times instead of
``B`` times.  The plan layer picks ``B_t`` (``Route.b_tile``): the largest
divisor of the batch bucket whose working set fits the VMEM budget, and
pads a smaller batch to whole blocks.  A block runs in chunks of ``B_c``
images (``deconv_chunk``), each tap's MXU dot multiplying ``B_c·U·V`` rows
by the resident ``(C_t, N_t)`` panel; past ``UNROLL_CHUNKS`` chunks a loop
runs them, so compile time does not grow with ``B_t``.

**Spatially tiled variants** (``sp_tiles`` on both public entries): when the
whole padded plane does not fit VMEM, the grid grows ``(oh_tiles, ow_tiles)``
axes — ``(B, OH/T_oh, OW/T_ow, N/N_t, C/C_t)``, C still innermost — and the
kernel computes one **halo'd output tile** per step.  The input stays whole
in ``pl.ANY`` (compiler-placed, HBM for big planes) and each step's
halo'd input slice — output-tile footprint plus the stride/dilation-aware
tap reach ``(T-1)·d`` (phase-aware tap-origin span for the multi-phase
deconv) — is fetched by an explicit **double-buffered DMA**: the next
step's halo slice streams into the other slot while the MXU runs the
current tap loop.  Per-output-pixel accumulation order (tap-major inside a
C tile, C tiles outer) is the same as the whole-plane kernels'.  Plane size
alone never pushes a site off the Pallas route (the plan layer keeps XLA
fallbacks only for non-uniform-phase transposed shapes and halos beyond
the VMEM budget).

Every launch compiles under an explicit scoped-VMEM limit
(``VMEM_LIMIT_BYTES``); the plan layer sizes tiles against the layout-exact
working set (``vmem_bytes_estimate_*``) with headroom below that limit.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

Pair = tuple[int, int]

# lanes of one vreg: the channel tile of every route (``lane_tile``)
LANES = 128

# rows of the largest tap dot the fused deconv kernel unrolls per chunk of
# its batch block (``deconv_chunk``).  Mosaic's compile time grows faster
# than the unrolled rows.  On a TPU v5e at B64, 512-row chunks leave the
# Table-1 dc0 site two chunks, which unroll and compile in 10.3 s (1.1 s at
# 256 rows); 128-row chunks run dc1 7% slower than 256 (0.242 / 0.226 ms).
CHUNK_ROWS = 256

# a batch block of at most this many chunks unrolls them: the chunk loop
# keeps one chunk's tap dots from overlapping the next chunk's.  On a TPU
# v5e at B64 the dc3 site's two one-image chunks run 0.665 ms unrolled and
# 0.704 ms looped, for 0.9-1.6 s more compile
UNROLL_CHUNKS = 2

# Mosaic's scoped-VMEM limit for every launch here.  A v5e core has
# 128 MiB of VMEM; the compiler's default scope is 16 MiB, which the
# double-buffered whole-plane blocks of real decoder layers outgrow.
VMEM_LIMIT_BYTES = 32 * 1024 * 1024

_COMPILER_PARAMS = pltpu.CompilerParams(vmem_limit_bytes=VMEM_LIMIT_BYTES)


def lane_tile(d: int) -> int:
    """The (C_t or N_t) tile of a channel dim: one 128-lane tile, or the
    whole dim when it is narrower — the only tiles Mosaic lays out without
    a lane-misaligned slice."""
    return min(d, LANES)


def _tap_panel(k_ref, s_ref, t: int):
    """Tap ``t``'s ``(C_t, N_t)`` MXU panel.  Dense superpacks read the raw
    VMEM tile; quantized superpacks carry per-tap-row scales in ``s_ref``
    (``(ΣT, C_t, 1)``) and dequantize here — int8 tile → f32 row-broadcast
    multiply — so the MXU dot below runs f32 into the existing f32 scratch.
    The scale sits on the *contraction* dim C, so it cannot be folded into
    the accumulator after the dot; per-panel pre-scaling is the exact
    placement."""
    panel = k_ref[t]
    if s_ref is None:
        return panel
    return panel.astype(jnp.float32) * s_ref[t]


def _tap_dot(acc_ref, rows: tuple, xs, k_ref, s_ref, t: int):
    """``acc[rows] += xs @ panel_t`` — one tap's MXU product, f32.  ``rows``
    indexes every accumulator dim but the lanes."""
    acc_ref[(*rows, slice(None))] += jnp.dot(
        xs.reshape(-1, xs.shape[-1]), _tap_panel(k_ref, s_ref, t),
        preferred_element_type=jnp.float32)


def _single_taps(load, acc_ref, k_ref, s_ref, taps_hw: Pair,
                 dilation: Pair):
    """The single-correlation tap loop: tap ``t = m·S + n`` reads
    ``load(m·d_h, n·d_w)`` (a strided window of the resident plane)."""
    r, s = taps_hw
    dh, dw = dilation
    for m in range(r):                 # static tap unroll -> MXU matmul chain
        for n in range(s):
            _tap_dot(acc_ref, (slice(None),), load(m * dh, n * dw), k_ref,
                     s_ref, m * s + n)


def _kernel(x_ref, k_ref, *rest, taps_hw: Pair, strides: Pair,
            dilation: Pair, out_hw: Pair, n_c_tiles: int):
    """Single-correlation kernel over the tap-major superpack: ``k_ref`` is
    ``(R·S, C_t, N_t)`` — tap ``t = m·S + n``'s panel is one contiguous VMEM
    tile, the same row order ``ConvPlan.pack`` emits, so the strided and the
    dilated kind run the *same* kernel (dilation only moves the tap's read
    origin inside the resident plane).  An int8 superpack rides with a third
    input ref of per-tap-row scales (see ``_tap_panel``)."""
    s_ref, o_ref, acc_ref = rest if len(rest) == 3 else (None, *rest)
    sh, sw = strides
    oh, ow = out_hw
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    _single_taps(
        lambda i, j: x_ref[0, pl.ds(i, oh, sh), pl.ds(j, ow, sw), :],
        acc_ref, k_ref, s_ref, taps_hw, dilation)

    @pl.when(ci == n_c_tiles - 1)
    def _flush():
        o_ref[0] = acc_ref[...].reshape(oh, ow, -1).astype(o_ref.dtype)


def _halo_stream(x_any, buf, sem, origin):
    """Double-buffered halo'd-tile fetch shared by both tiled kernels.

    ``origin(i, j)`` maps a spatial tile index to the slice origin (rows,
    cols) inside the ``pl.ANY``-resident plane; the channel slice comes
    from the innermost grid axis (the whole channel dim when one C tile
    covers it — a sub-lane-tile channel slice is not DMA-able).  Ravels the
    ``(b, i, j, n, c)`` grid into a linear step (the halo slice depends on
    everything but the N tile), starts the *next* step's DMA into the other
    slot so it streams while the caller's MXU loop runs, then waits on the
    current step's tile and returns its slot in ``buf``."""
    bi, oi, oj, ni, ci = (pl.program_id(d) for d in range(5))
    nb, n_oi, n_oj, nn, nc = (pl.num_programs(d) for d in range(5))
    step = (((bi * n_oi + oi) * n_oj + oj) * nn + ni) * nc + ci
    total = nb * n_oi * n_oj * nn * nc
    _, tin_h, tin_w, c_t = buf.shape
    whole_c = c_t == x_any.shape[-1]

    def tile_dma(slot, st):
        c_ = jax.lax.rem(st, nc)
        st = jax.lax.div(st, nc * nn)
        j_ = jax.lax.rem(st, n_oj)
        st = jax.lax.div(st, n_oj)
        i_ = jax.lax.rem(st, n_oi)
        b_ = jax.lax.div(st, n_oi)
        r0, c0 = origin(i_, j_)
        chans = slice(None) if whole_c else pl.ds(c_ * c_t, c_t)
        return pltpu.make_async_copy(
            x_any.at[b_, pl.ds(r0, tin_h), pl.ds(c0, tin_w), chans],
            buf.at[slot], sem.at[slot])

    slot = jax.lax.rem(step, 2)

    @pl.when(step == 0)
    def _warmup():
        tile_dma(0, 0).start()

    @pl.when(step + 1 < total)
    def _prefetch():                    # streams while the MXU loop runs
        tile_dma(jax.lax.rem(step + 1, 2), step + 1).start()

    tile_dma(slot, step).wait()
    return slot


def _tiled_kernel(x_any, k_ref, *rest, taps_hw: Pair,
                  strides: Pair, dilation: Pair, tile_hw: Pair,
                  n_c_tiles: int):
    """Spatially tiled single-correlation kernel: one halo'd output tile per
    grid step, the input whole in ``pl.ANY`` and each step's halo slice
    DMA'd into a double-buffered VMEM scratch (the next slice streams while
    the MXU runs the current tap loop).  Tap/C-tile accumulation order is
    the same as ``_kernel``'s."""
    s_ref, o_ref, buf, sem, acc_ref = \
        rest if len(rest) == 5 else (None, *rest)
    sh, sw = strides
    toh, tow = tile_hw
    ci = pl.program_id(4)

    @pl.when(ci == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    slot = _halo_stream(x_any, buf, sem,
                        lambda i_, j_: (i_ * toh * sh, j_ * tow * sw))
    _single_taps(
        lambda i, j: buf[slot, pl.ds(i, toh, sh), pl.ds(j, tow, sw), :],
        acc_ref, k_ref, s_ref, taps_hw, dilation)

    @pl.when(ci == n_c_tiles - 1)
    def _flush():
        o_ref[0] = acc_ref[...].reshape(toh, tow, -1).astype(o_ref.dtype)


def halo_extent(tile: int, taps: int, stride: int, dilation: int) -> int:
    """Input rows one halo'd output tile needs along one dim: the strided
    tile footprint plus the dilated tap reach ``(T-1)·d``."""
    return (tile - 1) * stride + (taps - 1) * dilation + 1


def _scale_tiles(scales, total_taps: int, c: int, cp: int):
    """Per-tap-row scales ``(ΣT·C, 1)`` → the kernel's ``(ΣT, C, 1)`` view,
    zero-padded along C to the C-tile grid (the matching q rows are zero
    there too, so padded lanes contribute exactly nothing)."""
    assert scales.shape == (total_taps * c, 1), (scales.shape, total_taps, c)
    s3 = scales.reshape(total_taps, c, 1)
    if cp != c:
        s3 = jnp.pad(s3, ((0, 0), (0, cp - c), (0, 0)))
    return s3


def untangled_conv2d_superpack_pallas(x: jax.Array, superpack: jax.Array, *,
                                      taps_hw: Pair,
                                      strides: Pair = (1, 1),
                                      rhs_dilation: Pair = (1, 1),
                                      scales: jax.Array | None = None,
                                      c_tile: int = LANES,
                                      n_tile: int = LANES,
                                      sp_tiles: Pair | None = None,
                                      out_dtype=None,
                                      interpret: bool | None = None
                                      ) -> jax.Array:
    """ONE launch of the valid (pre-padded) untangled correlation, weights in
    the superpacked layout.  x:(B,Hp,Wp,C); superpack:(R·S·C, N) tap-major
    (``ConvPlan.pack``).  Covers the strided and the dilated kind — the
    dilated kernel is never zero-inserted; taps read the raw plane at
    ``m·d_h`` / ``n·d_w`` offsets.  ``sp_tiles=(T_oh, T_ow)`` selects the
    spatially tiled grid (halo'd output tiles, double-buffered input DMA)
    instead of whole-plane VMEM residency.  ``scales`` (``(R·S·C, 1)`` f32)
    marks an int8 quantized superpack: 1-byte weight tiles in VMEM,
    dequantized per tap panel into the same f32 MXU chain.  ``interpret``
    defaults to the Pallas interpreter on a CPU backend only; pass
    ``False`` to hand the kernel to Mosaic regardless of the backend."""
    b, hp, wp, c = x.shape
    r, s = taps_hw
    n = superpack.shape[1]
    assert superpack.shape[0] == r * s * c, (superpack.shape, taps_hw, c)
    sh, sw = strides
    dh, dw = rhs_dilation
    oh = (hp - (r - 1) * dh - 1) // sh + 1
    ow = (wp - (s - 1) * dw - 1) // sw + 1
    assert oh > 0 and ow > 0, (oh, ow)
    out_dtype = out_dtype or x.dtype
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    if sp_tiles is not None:
        return _conv_superpack_tiled(
            x, superpack, taps_hw=taps_hw, strides=strides,
            rhs_dilation=rhs_dilation, scales=scales, c_tile=c_tile,
            n_tile=n_tile, sp_tiles=sp_tiles, out_hw=(oh, ow),
            out_dtype=out_dtype, interpret=interpret)

    k3 = superpack.reshape(r * s, c, n)
    c_tile = min(c_tile, c)
    n_tile = min(n_tile, n)
    cp = -(-c // c_tile) * c_tile
    np_ = -(-n // n_tile) * n_tile
    if cp != c:
        x = jnp.pad(x, ((0, 0), (0, 0), (0, 0), (0, cp - c)))
        k3 = jnp.pad(k3, ((0, 0), (0, cp - c), (0, 0)))
    if np_ != n:
        k3 = jnp.pad(k3, ((0, 0), (0, 0), (0, np_ - n)))
    n_c_tiles = cp // c_tile

    grid = (b, np_ // n_tile, n_c_tiles)
    in_specs = [
        pl.BlockSpec((1, hp, wp, c_tile), lambda b_, n_, c_: (b_, 0, 0, c_)),
        pl.BlockSpec((r * s, c_tile, n_tile),
                     lambda b_, n_, c_: (0, c_, n_)),
    ]
    operands = [x, k3]
    if scales is not None:
        in_specs.append(pl.BlockSpec((r * s, c_tile, 1),
                                     lambda b_, n_, c_: (0, c_, 0)))
        operands.append(_scale_tiles(scales, r * s, c, cp))
    out = pl.pallas_call(
        functools.partial(_kernel, taps_hw=(r, s), strides=strides,
                          dilation=rhs_dilation, out_hw=(oh, ow),
                          n_c_tiles=n_c_tiles),
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, oh, ow, n_tile),
                               lambda b_, n_, c_: (b_, 0, 0, n_)),
        out_shape=jax.ShapeDtypeStruct((b, oh, ow, np_), out_dtype),
        scratch_shapes=[pltpu.VMEM((oh * ow, n_tile), jnp.float32)],
        compiler_params=_COMPILER_PARAMS,
        name="untangled_conv",
        interpret=interpret,
    )(*operands)
    return out[..., :n]


def _conv_superpack_tiled(x, superpack, *, taps_hw, strides, rhs_dilation,
                          scales, c_tile, n_tile, sp_tiles, out_hw,
                          out_dtype, interpret):
    """Spatially tiled grid for the single-correlation superpack kernel:
    ``(B, OH/T_oh, OW/T_ow, N/N_t, C/C_t)``, C innermost."""
    b, hp, wp, c = x.shape
    r, s = taps_hw
    n = superpack.shape[1]
    sh, sw = strides
    dh, dw = rhs_dilation
    oh, ow = out_hw
    toh, tow = min(sp_tiles[0], oh), min(sp_tiles[1], ow)
    n_oi, n_oj = -(-oh // toh), -(-ow // tow)
    tin_h = halo_extent(toh, r, sh, dh)
    tin_w = halo_extent(tow, s, sw, dw)
    # grow the plane so every tile's halo read (incl. the ragged edge) is in
    # bounds; the zero rows only feed output pixels that are sliced off
    hp_need = (n_oi - 1) * toh * sh + tin_h
    wp_need = (n_oj - 1) * tow * sw + tin_w
    k3 = superpack.reshape(r * s, c, n)
    # C_t is not clipped to C: the DMA'd halo slice needs a lane-dense
    # channel dim, so narrow planes are zero-padded up to one C tile
    n_tile = min(n_tile, n)
    cp = -(-c // c_tile) * c_tile
    np_ = -(-n // n_tile) * n_tile
    pads = ((0, 0), (0, max(0, hp_need - hp)), (0, max(0, wp_need - wp)),
            (0, cp - c))
    if any(p != (0, 0) for p in pads):
        x = jnp.pad(x, pads)
    if cp != c:
        k3 = jnp.pad(k3, ((0, 0), (0, cp - c), (0, 0)))
    if np_ != n:
        k3 = jnp.pad(k3, ((0, 0), (0, 0), (0, np_ - n)))
    n_c_tiles = cp // c_tile

    grid = (b, n_oi, n_oj, np_ // n_tile, n_c_tiles)
    in_specs = [
        pl.BlockSpec(memory_space=pl.ANY),
        pl.BlockSpec((r * s, c_tile, n_tile),
                     lambda b_, i_, j_, n_, c_: (0, c_, n_)),
    ]
    operands = [x, k3]
    if scales is not None:
        in_specs.append(pl.BlockSpec((r * s, c_tile, 1),
                                     lambda b_, i_, j_, n_, c_: (0, c_, 0)))
        operands.append(_scale_tiles(scales, r * s, c, cp))
    out = pl.pallas_call(
        functools.partial(_tiled_kernel, taps_hw=(r, s), strides=strides,
                          dilation=rhs_dilation, tile_hw=(toh, tow),
                          n_c_tiles=n_c_tiles),
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, toh, tow, n_tile),
                               lambda b_, i_, j_, n_, c_: (b_, i_, j_, n_)),
        out_shape=jax.ShapeDtypeStruct((b, n_oi * toh, n_oj * tow, np_),
                                       out_dtype),
        scratch_shapes=[pltpu.VMEM((2, tin_h, tin_w, c_tile), x.dtype),
                        pltpu.SemaphoreType.DMA((2,)),
                        pltpu.VMEM((toh * tow, n_tile), jnp.float32)],
        compiler_params=_COMPILER_PARAMS,
        name="untangled_conv_tiled",
        interpret=interpret,
    )(*operands)
    return out[:, :oh, :ow, :n]


def untangled_conv2d_pallas(x: jax.Array, kernel: jax.Array, *,
                            strides: Pair = (1, 1),
                            rhs_dilation: Pair = (1, 1),
                            c_tile: int = LANES, n_tile: int = LANES,
                            out_dtype=None,
                            interpret: bool | None = None) -> jax.Array:
    """Valid (pre-padded) untangled convolution. x:(B,Hp,Wp,C), K:(R,S,C,N).

    Full-kernel entry: flattens into the tap-major superpack (free — same
    memory order) and runs the superpack kernel."""
    r, s, kc, n = kernel.shape
    assert kc == x.shape[-1], (kernel.shape, x.shape)
    return untangled_conv2d_superpack_pallas(
        x, kernel.reshape(r * s * kc, n), taps_hw=(r, s), strides=strides,
        rhs_dilation=rhs_dilation, c_tile=c_tile, n_tile=n_tile,
        out_dtype=out_dtype, interpret=interpret)


def _deconv_kernel(x_ref, k_ref, *rest, phases, strides: Pair,
                   n_c_tiles: int):
    """Multi-phase transposed conv: every phase's taps over one VMEM
    residency of ``B_t`` padded planes, flushed as direct interleaved
    writes.

    ``phases`` is a static tuple of per-phase records
    ``(q_h, q_w, tap_off, T_h, T_w, xoff_h, xoff_w, U, V, acc_off)`` — all
    plan-time constants, so the tap loop fully unrolls into an MXU matmul
    chain.  The ``B_t`` images of the block run in chunks of ``B_c``
    (``deconv_chunk``): chunk ``i`` owns ``acc[i]``, phase ``q``'s segment
    of it ``B_c·U·V`` rows from ``B_c·acc_off`` (image-major inside the
    phase), so one dot per tap covers the chunk against the resident
    panel.  A block of more than ``UNROLL_CHUNKS`` chunks loops over them,
    which keeps the unrolled code one chunk long; a shorter one unrolls.
    An int8 superpack rides with a third input ref of per-tap-row scales
    (see ``_tap_panel``).
    """
    s_ref, o_ref, acc_ref = rest if len(rest) == 3 else (None, *rest)
    sh, sw = strides
    n_chunks, bc = acc_ref.shape[0], x_ref.shape[0] // acc_ref.shape[0]
    ci = pl.program_id(2)

    def chunks(body):
        if n_chunks <= UNROLL_CHUNKS:
            for i in range(n_chunks):
                body(i)
            return

        def step(i, carry):
            body(i)
            return carry

        jax.lax.fori_loop(0, n_chunks, step, 0)

    @pl.when(ci == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def taps(i):
        imgs = pl.ds(i * bc, bc)
        for (qh, qw, tap_off, th, tw, xh, xw, u, v, acc_off) in phases:
            if th * tw == 0 or u * v == 0:
                continue
            for t in range(th * tw):   # static tap unroll -> MXU matmuls
                ti, tj = divmod(t, tw)
                _tap_dot(acc_ref, (i, pl.ds(bc * acc_off, bc * u * v)),
                         x_ref[imgs, pl.ds(xh + ti, u), pl.ds(xw + tj, v), :],
                         k_ref, s_ref, tap_off + t)

    chunks(taps)

    @pl.when(ci == n_c_tiles - 1)
    def _flush():
        def flush(i):
            imgs = pl.ds(i * bc, bc)
            for (qh, qw, tap_off, th, tw, xh, xw, u, v, acc_off) in phases:
                if u * v == 0:
                    continue
                blk = acc_ref[i, pl.ds(bc * acc_off, bc * u * v), :]
                o_ref[imgs, pl.ds(qh, u, sh), pl.ds(qw, v, sw), :] = (
                    blk.reshape(bc, u, v, blk.shape[-1]).astype(o_ref.dtype))

        chunks(flush)


def deconv_chunk(b_tile: int, tap_rows: int) -> int:
    """``B_c``, the images of one unrolled chunk of the fused kernel's
    batch block: the largest divisor of ``b_tile`` whose tap dot stays
    within ``CHUNK_ROWS`` rows (at least one image).  The superpack tile is
    fetched once per block whatever the chunk; the chunk bounds the code
    the compiler unrolls, and so its compile time."""
    return max((d for d in range(1, b_tile + 1)
                if b_tile % d == 0 and d * tap_rows <= CHUNK_ROWS), default=1)


def untangled_deconv2d_pallas(xg: jax.Array, superpack: jax.Array, *,
                              phases, out_hw: Pair, strides: Pair,
                              sum_uv: int,
                              scales: jax.Array | None = None,
                              c_tile: int = LANES,
                              n_tile: int = LANES,
                              b_tile: int = 1,
                              sp_tiles: Pair | None = None, out_dtype=None,
                              interpret: bool | None = None) -> jax.Array:
    """Fused transposed conv: ONE kernel launch for all s_h*s_w phases.

    xg: (B, Hg, Wg, C) globally padded plane; superpack: (ΣT·C, N) tap-major
    phase sub-kernels (``ConvPlan.pack`` layout); ``phases`` the plan's
    ``PhaseExec`` records.  Output (B, out_h, out_w, N), written interleaved
    inside the kernel — no stack/transpose pass afterwards.  ``b_tile``
    images share each grid step (and each fetched superpack tile); it must
    divide B (the plan layer pads the batch to whole blocks).
    ``sp_tiles=(T_u, T_v)`` (phase-output coordinates; uniform phases only)
    selects the spatially tiled grid with halo'd, double-buffered input
    slices instead of whole-plane VMEM residency.  ``scales`` (``(ΣT·C, 1)``
    f32) marks an int8 quantized superpack, dequantized per tap panel.
    """
    b, hg, wg, c = xg.shape
    n = superpack.shape[1]
    total_taps = superpack.shape[0] // max(1, c)
    oh, ow = out_hw
    out_dtype = out_dtype or xg.dtype
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    if sp_tiles is not None:
        return _deconv_tiled(xg, superpack, phases=phases, out_hw=out_hw,
                             strides=strides, scales=scales, c_tile=c_tile,
                             n_tile=n_tile, sp_tiles=sp_tiles,
                             out_dtype=out_dtype, interpret=interpret)

    k3 = superpack.reshape(total_taps, c, n)
    c_tile = min(c_tile, c)
    n_tile = min(n_tile, n)
    cp = -(-c // c_tile) * c_tile
    np_ = -(-n // n_tile) * n_tile
    if cp != c:
        xg = jnp.pad(xg, ((0, 0), (0, 0), (0, 0), (0, cp - c)))
        k3 = jnp.pad(k3, ((0, 0), (0, cp - c), (0, 0)))
    if np_ != n:
        k3 = jnp.pad(k3, ((0, 0), (0, 0), (0, np_ - n)))
    n_c_tiles = cp // c_tile

    if b % b_tile:
        raise ValueError(f"b_tile={b_tile} does not divide the batch {b}")
    chunk = deconv_chunk(b_tile, max(ex.out_hw[0] * ex.out_hw[1]
                                     for ex in phases))

    meta = tuple(
        (ex.q[0], ex.q[1], ex.tap_off, ex.taps[0], ex.taps[1],
         ex.xoff[0], ex.xoff[1], ex.out_hw[0], ex.out_hw[1], ex.acc_off)
        for ex in phases)
    grid = (b // b_tile, np_ // n_tile, n_c_tiles)
    in_specs = [
        pl.BlockSpec((b_tile, hg, wg, c_tile),
                     lambda b_, n_, c_: (b_, 0, 0, c_)),
        pl.BlockSpec((total_taps, c_tile, n_tile),
                     lambda b_, n_, c_: (0, c_, n_)),
    ]
    operands = [xg, k3]
    if scales is not None:
        in_specs.append(pl.BlockSpec((total_taps, c_tile, 1),
                                     lambda b_, n_, c_: (0, c_, 0)))
        operands.append(_scale_tiles(scales, total_taps, c, cp))
    out = pl.pallas_call(
        functools.partial(_deconv_kernel, phases=meta, strides=strides,
                          n_c_tiles=n_c_tiles),
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((b_tile, oh, ow, n_tile),
                               lambda b_, n_, c_: (b_, 0, 0, n_)),
        out_shape=jax.ShapeDtypeStruct((b, oh, ow, np_), out_dtype),
        scratch_shapes=[pltpu.VMEM((b_tile // chunk, chunk * sum_uv, n_tile),
                                   jnp.float32)],
        compiler_params=_COMPILER_PARAMS,
        name="untangled_deconv",
        interpret=interpret,
    )(*operands)
    return out[..., :n]


def deconv_tap_span(phases) -> tuple[Pair, Pair]:
    """((min_h, max_h), (min_w, max_w)) tap-origin span over the non-empty
    phases: phase q's taps read the padded plane at rows ``xoff_h + t_i + u``
    — the halo'd tile must cover every phase's origin, so its extent along
    one dim is ``(max - min) + T_u`` (the phase-aware halo)."""
    live = [ex for ex in phases if ex.taps[0] * ex.taps[1] > 0]
    assert live, "deconv_tap_span needs at least one non-empty phase"
    min_h = min(ex.xoff[0] for ex in live)
    max_h = max(ex.xoff[0] + ex.taps[0] - 1 for ex in live)
    min_w = min(ex.xoff[1] for ex in live)
    max_w = max(ex.xoff[1] + ex.taps[1] - 1 for ex in live)
    return ((min_h, max_h), (min_w, max_w))


def _deconv_tiled_kernel(x_any, k_ref, *rest, phases,
                         strides: Pair, tile_uv: Pair, min_off: Pair,
                         n_c_tiles: int):
    """Spatially tiled multi-phase transposed conv: one interleaved output
    tile of (T_u·s_h, T_v·s_w) pixels per grid step.  ``phases`` is a static
    tuple ``(q_h, q_w, tap_off, T_h, T_w, xoff_h, xoff_w)``; every phase's
    taps read the one double-buffered halo'd input tile at plan-time offsets
    relative to the phase-origin span ``min_off``."""
    s_ref, o_ref, buf, sem, acc_ref = \
        rest if len(rest) == 5 else (None, *rest)
    sh, sw = strides
    tu, tv = tile_uv
    mh, mw = min_off
    ci = pl.program_id(4)

    @pl.when(ci == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    slot = _halo_stream(x_any, buf, sem,
                        lambda i_, j_: (i_ * tu + mh, j_ * tv + mw))
    for pi, (qh, qw, tap_off, th, tw, xh, xw) in enumerate(phases):
        for t in range(th * tw):        # static tap unroll -> MXU matmuls
            ti, tj = divmod(t, tw)      # (an empty phase's acc stays zero)
            _tap_dot(acc_ref, (pl.ds(pi * tu * tv, tu * tv),),
                     buf[slot, pl.ds(xh - mh + ti, tu),
                         pl.ds(xw - mw + tj, tv), :],
                     k_ref, s_ref, tap_off + t)

    @pl.when(ci == n_c_tiles - 1)
    def _flush():
        for pi, (qh, qw, *_rest) in enumerate(phases):
            blk = acc_ref[pl.ds(pi * tu * tv, tu * tv), :]
            o_ref[0, pl.ds(qh, tu, sh), pl.ds(qw, tv, sw), :] = (
                blk.reshape(tu, tv, blk.shape[-1]).astype(o_ref.dtype))


def _deconv_tiled(xg, superpack, *, phases, out_hw, strides, scales, c_tile,
                  n_tile, sp_tiles, out_dtype, interpret):
    """Spatially tiled grid for the multi-phase deconv kernel:
    ``(B, U/T_u, V/T_v, N/N_t, C/C_t)``, C innermost.  Requires uniform
    phases (all share (U, V) — equivalently ``out % stride == 0``)."""
    b, hg, wg, c = xg.shape
    n = superpack.shape[1]
    total_taps = superpack.shape[0] // max(1, c)
    sh, sw = strides
    oh, ow = out_hw
    uu, vv = phases[0].out_hw
    assert all(ex.out_hw == (uu, vv) for ex in phases), \
        "sp_tiles requires uniform phases"
    assert uu * sh == oh and vv * sw == ow, (out_hw, (uu, vv), strides)
    tu, tv = min(sp_tiles[0], uu), min(sp_tiles[1], vv)
    n_oi, n_oj = -(-uu // tu), -(-vv // tv)
    ((mh, xh_max), (mw, xw_max)) = deconv_tap_span(phases)
    tin_h = xh_max - mh + tu
    tin_w = xw_max - mw + tv
    hg_need = mh + (n_oi - 1) * tu + tin_h
    wg_need = mw + (n_oj - 1) * tv + tin_w
    k3 = superpack.reshape(total_taps, c, n)
    # C_t is not clipped to C: the DMA'd halo slice needs a lane-dense
    # channel dim, so narrow planes are zero-padded up to one C tile
    n_tile = min(n_tile, n)
    cp = -(-c // c_tile) * c_tile
    np_ = -(-n // n_tile) * n_tile
    pads = ((0, 0), (0, max(0, hg_need - hg)), (0, max(0, wg_need - wg)),
            (0, cp - c))
    if any(p != (0, 0) for p in pads):
        xg = jnp.pad(xg, pads)
    if cp != c:
        k3 = jnp.pad(k3, ((0, 0), (0, cp - c), (0, 0)))
    if np_ != n:
        k3 = jnp.pad(k3, ((0, 0), (0, 0), (0, np_ - n)))
    n_c_tiles = cp // c_tile

    meta = tuple((ex.q[0], ex.q[1], ex.tap_off, ex.taps[0], ex.taps[1],
                  ex.xoff[0], ex.xoff[1]) for ex in phases)
    grid = (b, n_oi, n_oj, np_ // n_tile, n_c_tiles)
    in_specs = [
        pl.BlockSpec(memory_space=pl.ANY),
        pl.BlockSpec((total_taps, c_tile, n_tile),
                     lambda b_, i_, j_, n_, c_: (0, c_, n_)),
    ]
    operands = [xg, k3]
    if scales is not None:
        in_specs.append(pl.BlockSpec((total_taps, c_tile, 1),
                                     lambda b_, i_, j_, n_, c_: (0, c_, 0)))
        operands.append(_scale_tiles(scales, total_taps, c, cp))
    out = pl.pallas_call(
        functools.partial(_deconv_tiled_kernel, phases=meta, strides=strides,
                          tile_uv=(tu, tv), min_off=(mh, mw),
                          n_c_tiles=n_c_tiles),
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, tu * sh, tv * sw, n_tile),
                               lambda b_, i_, j_, n_, c_: (b_, i_, j_, n_)),
        out_shape=jax.ShapeDtypeStruct(
            (b, n_oi * tu * sh, n_oj * tv * sw, np_), out_dtype),
        scratch_shapes=[pltpu.VMEM((2, tin_h, tin_w, c_tile), xg.dtype),
                        pltpu.SemaphoreType.DMA((2,)),
                        pltpu.VMEM((len(phases) * tu * tv, n_tile),
                                   jnp.float32)],
        compiler_params=_COMPILER_PARAMS,
        name="untangled_deconv_tiled",
        interpret=interpret,
    )(*operands)
    return out[:, :oh, :ow, :n]


# ---------------------------------------------------------------------------
# VMEM working sets, in the layout Mosaic allocates
# ---------------------------------------------------------------------------

def _slab_bytes(rows: int, cols: int, itemsize: int) -> int:
    """VMEM bytes of one ``(rows, cols)`` slab: the last two dims of every
    VMEM buffer pad to the ``(sublane, lane)`` tile — ``(8, 128)`` for
    32-bit elements, 16 / 32 sublanes for 2- / 1-byte ones."""
    sub = 8 * 4 // itemsize
    return (-(-rows // sub) * sub) * (-(-cols // LANES) * LANES) * itemsize


def _working_set(in_hw: Pair, c_tile: int, total_taps: int, n_tile: int,
                 out_hw: Pair, acc_rows: int, tap_rows: int, itemsize: int,
                 witemsize: int | None) -> int:
    """Peak VMEM of any of the four kernels:

    - the input block (whole plane, or the halo tile) ``in_hw × C_t``,
      twice: Pallas double-buffers blocked inputs, and the tiled kernels'
      DMA scratch has two slots;
    - the superpack tile ``(ΣT, C_t, N_t)`` at the weight itemsize, twice,
      plus — int8 — its ``(ΣT, C_t, 1)`` f32 scale column, twice;
    - the output block ``out_hw × N_t``, twice;
    - the f32 accumulator ``(acc_rows, N_t)``, once;
    - the largest tap GEMM's live values: its ``(tap_rows, C_t)`` operand
      and ``(tap_rows, N_t)`` f32 product.

    ``witemsize`` is the weight element width when it differs from the
    activation ``itemsize`` (int8 superpacks: 1); ``None`` means weights
    ride at the activation width."""
    wit = itemsize if witemsize is None else witemsize
    weights = total_taps * _slab_bytes(c_tile, n_tile, wit)
    if wit != itemsize:
        weights += total_taps * _slab_bytes(c_tile, 1, 4)
    return (2 * in_hw[0] * _slab_bytes(in_hw[1], c_tile, itemsize)
            + 2 * weights
            + 2 * out_hw[0] * _slab_bytes(out_hw[1], n_tile, itemsize)
            + _slab_bytes(acc_rows, n_tile, 4)
            + _slab_bytes(tap_rows, c_tile, itemsize)
            + _slab_bytes(tap_rows, n_tile, 4))


def vmem_bytes_estimate_superpack(hp, wp, c_tile, total_taps, n_tile,
                                  oh, ow, itemsize=4, witemsize=None):
    """Working set of the whole-plane single-correlation kernel — the
    dilation-aware estimate: ``hp``/``wp`` are padded-plane dims that grow
    with the dilated tap reach ``(R-1)·d``, while the superpack tile stays
    ``total_taps = R·S`` rows no matter the dilation (no zero-inserted
    kernel is ever resident)."""
    return _working_set((hp, wp), c_tile, total_taps, n_tile, (oh, ow),
                        oh * ow, oh * ow, itemsize, witemsize)


def vmem_bytes_estimate_fused(hg, wg, c_tile, total_taps, n_tile, sum_uv,
                              oh, ow, tap_rows, itemsize=4, witemsize=None,
                              b_tile=1):
    """Working set of the fused multi-phase kernel at ``b_tile`` images per
    grid step: ``b_tile`` global plane blocks + the superpack tile (shared
    by the batch block) + ``b_tile`` full interleaved output blocks, the
    per-phase f32 accumulator (one ``B_c·sum_uv``-row slab per chunk of
    ``B_c`` images, ``deconv_chunk``), and the largest phase's
    ``B_c·tap_rows`` (``tap_rows = U·V``) tap GEMM."""
    chunk = deconv_chunk(b_tile, tap_rows)
    # one f32 slab per chunk, each padded to the 8-sublane tile
    acc_rows = b_tile // chunk * (-(-chunk * sum_uv // 8) * 8)
    return _working_set((b_tile * hg, wg), c_tile, total_taps, n_tile,
                        (b_tile * oh, ow), acc_rows, chunk * tap_rows,
                        itemsize, witemsize)


def vmem_bytes_estimate_tiled(tin_h, tin_w, c_tile, total_taps, n_tile,
                              out_tile, acc_rows, tap_rows, itemsize=4,
                              witemsize=None):
    """Working set of the spatially tiled kernels (both kinds): the halo'd
    input tile ``(tin_h, tin_w)`` in its two DMA slots, the superpack tile,
    the output tile ``out_tile`` (``(T_oh, T_ow)``; the deconv's interleaved
    ``(T_u·s_h, T_v·s_w)``), the f32 accumulator (``acc_rows``: ``T_oh·T_ow``
    single, ``s_h·s_w·T_u·T_v`` deconv) and one tap GEMM of ``tap_rows``
    (``T_oh·T_ow`` / ``T_u·T_v``).

    ``tin_* = halo_extent(tile, taps, stride, dilation)`` for the single
    kind; the deconv's halo is the phase tap-origin span plus the tile
    (``deconv_tap_span``)."""
    return _working_set((tin_h, tin_w), c_tile, total_taps, n_tile,
                        out_tile, acc_rows, tap_rows, itemsize, witemsize)
