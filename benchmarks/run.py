"""Benchmark harness: one module per paper table/figure.

  bench_simulator  -> Table I   (spec + scaling)
  bench_precision  -> Table II  (P@k at FP32/INT8/INT4)
  bench_latency    -> Table III (DIRC vs baselines)
  bench_error_opt  -> Fig. 6    (error-aware optimization ladder)
  bench_kernels    -> kernel micro-benchmarks
  bench_sharded    -> multi-macro sharded retrieval throughput
  bench_async_serving -> open-loop streaming latency vs flush deadline
  bench_continuous_batching -> decode throughput vs per-query generation
  bench_paged_cache -> paged vs fixed-slot KV cache at equal HBM
  bench_prefix_sharing -> CoW prefix sharing vs private blocks at equal HBM
  bench_prefix_cache -> tiered prefix retention + host offload, Zipf sweep
  bench_router     -> replicated-engine fleet scaling + prefix affinity
  bench_slo        -> SLO controller + priority preemption vs static knobs
  bench_drift      -> temporal drift vs the online recalibration loop
  roofline_report  -> dry-run roofline tables (EXPERIMENTS.md source)

Run: PYTHONPATH=src python -m benchmarks.run
"""
from __future__ import annotations

import sys
import time

from . import (bench_async_serving, bench_continuous_batching,
               bench_drift, bench_error_opt, bench_kernels, bench_latency,
               bench_paged_cache, bench_precision, bench_prefix_cache,
               bench_prefix_sharing, bench_router, bench_sharded,
               bench_simulator, bench_slo, roofline_report)

SECTIONS = [
    ("Table I — DIRC-RAG spec (calibrated model)", bench_simulator),
    ("Table II — retrieval precision vs quantization", bench_precision),
    ("Table III — latency/energy vs baselines", bench_latency),
    ("Fig. 6 — error-aware optimization ladder", bench_error_opt),
    ("Kernel micro-benchmarks", bench_kernels),
    ("Sharded multi-macro throughput", bench_sharded),
    ("Async open-loop serving latency", bench_async_serving),
    ("Continuous-batching decode throughput", bench_continuous_batching),
    ("Paged vs fixed-slot KV cache", bench_paged_cache),
    ("CoW prefix sharing on the paged pool", bench_prefix_sharing),
    ("Tiered prefix retention + host offload", bench_prefix_cache),
    ("Replicated-engine fleet + prefix affinity", bench_router),
    ("SLO controller + priority preemption", bench_slo),
    ("Drift vs the online recalibration loop", bench_drift),
    ("Roofline (from multi-pod dry-run)", roofline_report),
]


def main() -> None:
    failed = []
    for title, mod in SECTIONS:
        print(f"\n{'=' * 72}\n== {title}\n{'=' * 72}")
        t0 = time.time()
        try:
            mod.main()
        except Exception as e:  # noqa: BLE001 - report, run the rest, fail
            print(f"SECTION FAILED: {type(e).__name__}: {e}")
            failed.append(title)
        print(f"-- section took {time.time() - t0:.1f}s")
    if failed:
        sys.exit(f"{len(failed)} section(s) failed: {'; '.join(failed)}")


if __name__ == "__main__":
    main()
