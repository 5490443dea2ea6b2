"""Benchmark harness - one function per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [SUITE | --only NAME]

Prints ``name,us_per_call,derived`` CSV rows:
  * pareto_*    - Figs 4/5/6 error sweeps + knee detection
  * mac_*       - Tables 4/5/6 MAC comparison (f32 / FxP8-int8 / bit-exact
                  CORDIC kernel) + SYCore 3 GHz throughput model
  * caesar_*    - Table 3 VGG-16 mapping + pruning co-design speedups
  * accuracy_*  - Fig 11 accuracy under CORDIC execution (+QAT recovery)
  * roofline_*  - roofline terms for representative (arch x shape) cells
  * tune_*      - kernel tile-candidate sweep (smoke), heuristic vs tuned;
                  writes the persistent tuned table (REPRO_TUNE_CACHE).
                  Full sweep: ``python -m benchmarks.tune``.
  * grads_*     - fused Pallas backward vs STE fallback (smoke) for the
                  float families.  Full sweep with long-context shapes:
                  ``python -m benchmarks.grad_bench``.
  * serve_*     - continuous batching vs gang scheduling on an arrival
                  trace (smoke); writes ``BENCH_serving.json``.  Full
                  replay: ``python -m benchmarks.serve_bench``.
  * spec_*      - speculative decoding vs plain decode on the draftable
                  motif trace (smoke); writes ``BENCH_spec.json`` and
                  fails on greedy divergence.  Full replay:
                  ``python -m benchmarks.serve_bench --spec``.
  * quant_*     - int8 quantized slot cache vs fp32 (smoke): slots-per-GB,
                  max logit error, trace replay tok/s; writes
                  ``BENCH_quant.json``.  Full sweep:
                  ``python -m benchmarks.quant_bench``.
  * paged_*     - paged slot memory + radix prefix cache vs the dense
                  layout on a shared-prefix trace (smoke); writes
                  ``BENCH_paged.json`` and fails on greedy divergence.
                  Full replay: ``python -m benchmarks.serve_bench
                  --paged``.
  * chaos_*     - kill/restore recovery cost (smoke): injected worker
                  death mid-trace, supervisor restores the last slot
                  snapshot; writes ``BENCH_chaos.json`` and fails if the
                  recovered outputs diverge from the undisturbed run.
  * mesh_*      - sharded serving over fake devices (smoke): slot state
                  on a 1/2/4/8-way mesh data axis + prefill/decode
                  split; writes ``BENCH_mesh.json`` and fails if sharded
                  outputs diverge from the single-device engine.  Full
                  replay: ``python -m benchmarks.serve_bench --mesh``.
"""
from __future__ import annotations

import argparse
import sys
import traceback


SUITE_NAMES = ("pareto", "mac", "caesar", "accuracy", "roofline", "tune",
               "grads", "serve", "spec", "quant", "paged", "chaos", "mesh")


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("suite", nargs="?", default=None, choices=SUITE_NAMES,
                    help="run a single suite (same choices as --only)")
    ap.add_argument("--only", default=None, choices=SUITE_NAMES)
    args = ap.parse_args(argv)

    if (args.only or args.suite) == "mesh":
        # must land before jax initializes its backend (first bench import)
        import os
        os.environ.setdefault(
            "XLA_FLAGS", "--xla_force_host_platform_device_count=8")

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    from benchmarks import (accuracy_bench, caesar_bench, grad_bench,
                            mac_bench, pareto_bench, quant_bench,
                            roofline_bench, serve_bench, tune_bench)
    suites = {
        "pareto": pareto_bench.run,
        "mac": mac_bench.run,
        "caesar": caesar_bench.run,
        "accuracy": accuracy_bench.run,
        "roofline": roofline_bench.run,
        "tune": tune_bench.run,
        "grads": grad_bench.run,
        "serve": serve_bench.run,
        "spec": serve_bench.run_spec,
        "quant": quant_bench.run,
        "paged": serve_bench.run_paged,
        "chaos": serve_bench.run_chaos,
        "mesh": serve_bench.run_mesh,
    }
    only = args.only or args.suite
    if only:
        suites = {only: suites[only]}

    rows = []
    failed = 0
    for name, fn in suites.items():
        try:
            fn(rows)
        except Exception:
            failed += 1
            print(f"# suite {name} FAILED:", file=sys.stderr)
            traceback.print_exc()
    print("name,us_per_call,derived")
    for name, us, derived in rows:
        print(f"{name},{us:.1f},{derived}")
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
