"""Benchmark orchestrator — one function per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--quick]

  convergence   — §V.A  (SGD 4166 vs SMBGD 3166 iterations, 24 %)
  throughput    — Table I analogue (serial SGD vs batched SMBGD, P sweep)
  streams       — SeparatorBank scaling (fused S-stream step vs Python loop,
                  S sweep; writes BENCH_streams.json)
  nonlinearity  — §V.B  (tanh vs cubic vs relu cost)
  kernels       — Pallas hot-spot microbenches / structural VMEM report
  roofline      — §Roofline table from the dry-run artifacts
"""
from __future__ import annotations

import argparse
import sys
import time


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true", help="skip the slow convergence study")
    ap.add_argument("--only", default=None)
    args = ap.parse_args(argv)

    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    from benchmarks import (
        convergence,
        kernels,
        nonlinearity,
        roofline,
        stream_throughput,
        throughput,
    )

    suites = {
        "throughput": throughput.main,
        "streams": lambda: stream_throughput.run(
            quick=args.quick, out="BENCH_streams.json"
        ),
        "nonlinearity": nonlinearity.main,
        "kernels": kernels.main,
        "roofline": lambda: roofline.main([]),
        "convergence": convergence.main,
    }
    if args.quick:
        suites.pop("convergence")
    if args.only:
        suites = {args.only: suites[args.only]}

    print("name,us_per_call,derived")
    for name, fn in suites.items():
        t0 = time.time()
        print(f"== {name} ==")
        try:
            fn()
        except Exception as e:  # keep the harness running
            print(f"{name},ERROR,{type(e).__name__}: {e}")
        print(f"== {name} done in {time.time()-t0:.1f}s ==")
        sys.stdout.flush()


if __name__ == "__main__":
    main()
