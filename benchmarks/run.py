"""Benchmark harness: one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV and writes machine-readable
``BENCH_fig7.json`` (per-layer planned/naive/per-phase µs + the
fused-vs-per-phase speedup of the single-launch executor),
``BENCH_dilated.json`` (segmentation block suite: untangled vs the
rhs-dilation baseline engine + the lax oracle), ``BENCH_serve.json``
(dynamic image batcher vs the fixed-batch serve loop), and
``BENCH_slo.json`` (open-loop Poisson load through the SLO-aware control
plane: per-class tail latency + goodput-under-SLO), and
``BENCH_spatial.json`` (plane-parallel shard_map halo-exchange executor vs
single-device on the 385x385 dilated-context and transposed-decoder
geometries — run in a forced-8-device child process), and
``BENCH_quant.json`` (int8 quantized superpacks vs their f32 twins: weight
bytes, per-bucket route verdicts, forward parity), and ``BENCH_unet.json``
(diffusion U-Net denoising chains — many *sequential* decoder calls per
request — driven through the control plane, plus the sub-pixel route
verdicts per site) so the perf trajectory is tracked run over run.  See
``docs/BENCHMARKS.md`` for what every field means.  Run:

    PYTHONPATH=src python -m benchmarks.run [--quick] [--json PATH]
                                           [--dilated-json PATH]
                                           [--serve-json PATH]
                                           [--slo-json PATH]
                                           [--spatial-json PATH]
                                           [--quant-json PATH]
                                           [--unet-json PATH]

``--quick`` keeps the oracle-checked Fig.-7, dilated, and serving
wall-clocks (with short timing loops and 10x instead of 100x open-loop
traffic) so CI smoke still produces every JSON, and skips the remaining
slow benches.
"""
from __future__ import annotations

import argparse


def main() -> None:
    from repro.runtime.compile_cache import enable_compile_cache
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="short timing loops; skip the slowest benches")
    ap.add_argument("--json", default="BENCH_fig7.json",
                    help="where to write the fig7 JSON ('' disables)")
    ap.add_argument("--dilated-json", default="BENCH_dilated.json",
                    help="where to write the dilated JSON ('' disables)")
    ap.add_argument("--serve-json", default="BENCH_serve.json",
                    help="where to write the serving JSON ('' disables)")
    ap.add_argument("--slo-json", default="BENCH_slo.json",
                    help="where to write the open-loop SLO JSON "
                         "('' disables)")
    ap.add_argument("--spatial-json", default="BENCH_spatial.json",
                    help="where to write the plane-parallel JSON "
                         "('' disables)")
    ap.add_argument("--quant-json", default="BENCH_quant.json",
                    help="where to write the quantized-superpack JSON "
                         "('' disables)")
    ap.add_argument("--unet-json", default="BENCH_unet.json",
                    help="where to write the U-Net denoising-chain JSON "
                         "('' disables)")
    args = ap.parse_args()

    from benchmarks import (dilated_conv, fig7_speedup, fig8_memory,
                            serve_bench, table1_layers)
    print("# paper Table 1 — layer configs + MAC reduction")
    table1_layers.main(walltime=not args.quick)
    print("# paper Fig 8 (left) — memory-access reduction (plan-derived bytes)")
    fig8_memory.main()
    print("# paper Fig 7 — inference speedup vs naive engine (CPU wall-clock)")
    fig7_speedup.main(quick=args.quick, json_path=args.json or None)
    print("# paper §3.2.2 — dilated (atrous) conv, segmentation block suite")
    dilated_conv.main(quick=args.quick,
                      json_path=args.dilated_json or None)
    print("# serving — dynamic image batcher vs fixed-batch loop")
    serve_bench.main(quick=args.quick, json_path=args.serve_json or None)
    print("# serving — open-loop SLO/tail-latency harness (control plane)")
    serve_bench.slo_main(quick=args.quick, json_path=args.slo_json or None)
    print("# serving — U-Net denoising chains (sequential hops, "
          "sub-pixel routes)")
    serve_bench.unet_main(quick=args.quick, json_path=args.unet_json or None)
    if args.spatial_json:
        from benchmarks import spatial_bench
        print("# plane-parallel — shard_map halo exchange vs single device")
        spatial_bench.main(quick=args.quick, json_path=args.spatial_json)
    if args.quant_json:
        from benchmarks import quant_bench
        print("# quantized superpacks — int8 bytes / routes / parity "
              "vs f32 twins")
        quant_bench.main(quick=args.quick, json_path=args.quant_json)
    if not args.quick:
        from benchmarks import fig8_training
        print("# paper Fig 8 (right) — GAN training speedup (engine VJPs)")
        fig8_training.main()


if __name__ == "__main__":
    main()
