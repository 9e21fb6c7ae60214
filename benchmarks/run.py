# One function per paper table. Print ``name,us_per_call,derived`` CSV.
"""Benchmark harness — one module per paper table/figure:

  bench_latency_resources  Tables 2-4 + Figs 3-5 (HLS model vs paper numbers)
  bench_static_nonstatic   Table 5 + Fig 6 (II 315 -> 1) + measured modes
  bench_quantization       Fig 2 (PTQ AUC-ratio scans)
  bench_throughput         Sec 5.2 (FPGA vs V100 vs measured JAX batching)
  bench_kernels            Pallas kernel correctness + reuse Pareto
  bench_roofline           §Roofline rows from the dry-run artifacts

``--full`` widens sweeps (all 6 tagger models, finer quantization grid).
``--smoke`` is the CI fail-fast path: import every bench module (catching
import-time API drift), then run a minimal KernelSchedule conformance sweep;
exits non-zero on ANY failure instead of swallowing it.
``--json [PATH]`` writes BENCH_rnn_kernels.json — the persistent
hoisted-vs-in-loop perf-regression record (per-schedule wall clock + the
analytical estimate of the same schedule object); wired into
scripts/check.sh so the perf trajectory is tracked every run.  Exits
non-zero if the hoisted acceptance speedup (>= 1.3x on the flavor-tagging
fin~h LSTM) regresses.
"""

import argparse
import os
import sys
import time

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _ROOT)
sys.path.insert(0, os.path.join(_ROOT, "src"))


def smoke() -> int:
    """Fast import + conformance check; returns a process exit code."""
    t0 = time.time()
    from benchmarks import (bench_autotune, bench_decode,  # noqa: F401
                            bench_kernels, bench_latency_resources,
                            bench_quant, bench_quantization,
                            bench_roofline, bench_serving, bench_spec,
                            bench_static_nonstatic, bench_streaming,
                            bench_throughput, bench_warmup)
    print("smoke/imports,0,ok")

    from repro.kernels.schedule import KernelSchedule
    from repro.testing import assert_schedule_conformance
    for cell in ("lstm", "gru"):
        for sched in KernelSchedule.sweep((1, 4), block_batch=8,
                                          backend="pallas_interpret"):
            err = assert_schedule_conformance(cell, sched, B=3, T=5, F=4, H=8)
            print(f"smoke/{cell}/{sched.mode}/R{sched.reuse_factor},"
                  f"0,max_err={err:.1e}")
    # mixed-schedule serving path: co-batching by schedule hash must
    # bit-match direct predict without retracing (fail-fast, raises)
    bench_serving.smoke()
    print(f"smoke/wall_s,{(time.time()-t0)*1e6:.0f},ok")
    return 0


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--smoke", action="store_true",
                    help="import benches + minimal schedule sweep, fail fast")
    ap.add_argument("--json", nargs="?", const="BENCH_rnn_kernels.json",
                    default=None, metavar="PATH",
                    help="write the hoisted-vs-in-loop perf record + the "
                         "autotune frontier (BENCH_rnn_kernels.json) and "
                         "exit")
    ap.add_argument("--autotune-smoke", action="store_true",
                    help="explorer fail-fast: tiny space, non-empty "
                         "frontier, monotone latency-vs-R (analytical only)")
    ap.add_argument("--decode-smoke", action="store_true",
                    help="decode fail-fast: scheduled-vs-einsum bit-match, "
                         "RNN single-step conformance, batch-1 fast path")
    ap.add_argument("--quant-smoke", action="store_true",
                    help="quantized fail-fast: golden-model conformance "
                         "slice, native-vs-emulation bitwise identity, "
                         "packed-bytes == pricing")
    ap.add_argument("--warmup-smoke", action="store_true",
                    help="zero-warmup fail-fast: fresh engine over a warm "
                         "compile cache must serve its first request with "
                         "zero jit traces, bit-identical; records cold-vs-"
                         "warm first-request latency into the perf JSON")
    ap.add_argument("--spec-smoke", action="store_true",
                    help="speculative-decode fail-fast: the autotuned "
                         "(draft, verify, K) triple must beat the PR 5 "
                         "scheduled R4 decode path in tokens/s with greedy "
                         "exact-match enforced in the same run; measured-vs-"
                         "assumed accept rate rides the perf JSON under "
                         "'speculative'")
    ap.add_argument("--stream-smoke", action="store_true",
                    help="streaming fail-fast: overload replay at 0.5x/1x/2x "
                         "priced throughput; <=1x must never shed, 2x must "
                         "shed and/or downgrade, admitted p99 within "
                         "deadline, exact accounting, full drain; per-stage "
                         "percentiles ride the perf JSON under 'streaming'")
    ap.add_argument("--router-smoke", action="store_true",
                    help="replicated-serving fail-fast: mixed-schedule "
                         "stream at N=1 vs N=3 replicas with a mid-stream "
                         "replica kill; fails on lost/duplicated requests, "
                         "divergence from the single-replica oracle, broken "
                         "accounting, or sim-throughput scaling < 1.6x; "
                         "rides the perf JSON under 'router'")
    ap.add_argument("--only", default=None,
                    help="comma-separated bench names (e.g. roofline,kernels)")
    args, _ = ap.parse_known_args()

    from repro.serving.compile_cache import enable_jax_compilation_cache
    enable_jax_compilation_cache()

    if args.smoke:
        sys.exit(smoke())

    if args.autotune_smoke:
        from benchmarks import bench_autotune
        bench_autotune.smoke()
        sys.exit(0)

    if args.decode_smoke:
        from benchmarks import bench_decode
        bench_decode.smoke()
        sys.exit(0)

    if args.quant_smoke:
        from benchmarks import bench_quant
        bench_quant.smoke()
        sys.exit(0)

    if args.warmup_smoke:
        from benchmarks import bench_warmup
        bench_warmup.smoke(args.json or "BENCH_rnn_kernels.json")
        sys.exit(0)

    if args.stream_smoke:
        from benchmarks import bench_streaming
        bench_streaming.smoke(args.json or "BENCH_rnn_kernels.json")
        sys.exit(0)

    if args.spec_smoke:
        from benchmarks import bench_spec
        bench_spec.smoke(args.json or "BENCH_rnn_kernels.json")
        sys.exit(0)

    if args.router_smoke:
        from benchmarks import bench_router
        bench_router.smoke(args.json or "BENCH_rnn_kernels.json")
        sys.exit(0)

    if args.json is not None:
        from benchmarks import bench_kernels
        doc = bench_kernels.write_json(args.json, full=args.full)
        acc = doc["acceptance"]
        rank = doc["autotune"]["rank_check"]
        dec = doc["decode"]["acceptance"]
        qnt = doc["quant"]["acceptance"]
        conf = doc["quant"]["conformance"]
        print(f"json/acceptance,{acc['speedup'] * 1e6:.0f},"
              f"speedup={acc['speedup']:.2f}x|passed={acc['passed']}")
        print(f"json/autotune_rank,{rank['spearman'] * 1e6:.0f},"
              f"spearman={rank['spearman']:.3f}|passed={rank['passed']}")
        print(f"json/decode_acceptance,{dec['speedup'] * 1e6:.0f},"
              f"speedup={dec['speedup']:.2f}x|passed={dec['passed']}")
        print(f"json/quant_acceptance,0,"
              f"int4_ratio={qnt['int4_ratio']:.3f}"
              f"|conformance={conf['passed']}|passed={qnt['passed']}")
        sys.exit(0 if acc["passed"] and rank["passed"] and dec["passed"]
                 and qnt["passed"] else 1)

    from benchmarks import (bench_autotune, bench_decode, bench_kernels,
                            bench_latency_resources, bench_quant,
                            bench_quantization, bench_roofline,
                            bench_serving, bench_spec,
                            bench_static_nonstatic, bench_streaming,
                            bench_throughput, bench_warmup)
    benches = {
        "latency_resources": bench_latency_resources,
        "static_nonstatic": bench_static_nonstatic,
        "kernels": bench_kernels,
        "roofline": bench_roofline,
        "quantization": bench_quantization,
        "throughput": bench_throughput,
        "serving": bench_serving,
        "autotune": bench_autotune,
        "decode": bench_decode,
        "quant": bench_quant,
        "warmup": bench_warmup,
        "streaming": bench_streaming,
        "spec": bench_spec,
    }
    selected = (args.only.split(",") if args.only else list(benches))
    print("name,us_per_call,derived")
    failed = 0
    for name in selected:
        t0 = time.time()
        try:
            benches[name].run(full=args.full)
            print(f"bench/{name}/wall_s,{(time.time()-t0)*1e6:.0f},ok")
        except Exception as e:  # run the rest, then exit non-zero
            failed += 1
            print(f"bench/{name}/ERROR,0,{type(e).__name__}: "
                  f"{str(e)[:160]}")
    sys.exit(1 if failed else 0)


if __name__ == '__main__':
    main()
