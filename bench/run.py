#!/usr/bin/env python3
"""On-chip serving benchmark: run one cell of BENCHMARK.json.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell is one model configuration under one traffic mix, served by the
program's ``ServeEngine`` on one TPU (or four).  The run draws the
weights and the traffic from ``--seed``, warms every program the
traffic reaches on the engine that serves the window, serves the
window, then frees the engine and re-scores a sample of the served
tokens with a plain float32 reference.  The last stdout line is one JSON
object: ``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's
end-to-end metrics, or with ``--trace 1`` its per-layer ones),
``device``, with ``--trace 1`` a ``breakdown``, and ``checks`` (each
number ``correct`` compared, beside its limit).  Progress goes to
stderr, and the checks are its last lines.

Without a TPU, or with fewer chips than the cell asks for, it exits 2
and prints no result.

Options for measurements made by hand, not used by the benchmark's own
runs:

* ``--sweep R1,R2,...``: one process, one warm-up, one window per rate;
  prints load and latency per rate, no reference check;
* ``--control``: serve with the program's int8 (W8A8) matmul path, the
  control that ``correct`` must fail;
* ``--fault NAME``: plant one of ``benchlib/faults.py``'s faults in the
  timed path (``token-altered``, ``state-unchanged``, ``state-bf16``).
"""
import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def parse_args(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[1])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--sweep", default=None)
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--fault", default=None)
    return ap.parse_args(argv)


def use_compile_cache(root: str) -> str:
    """JAX's persistent compilation cache at ``<checkout>/.jax_cache``,
    a fixed path, set here so that the program's helper takes it."""
    import jax

    from repro.launch.compile_cache import enable_compile_cache

    path = os.path.join(root, ".jax_cache")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = path
    enable_compile_cache()
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def main(argv=None, root=ROOT, require_tpu=True, compile_cache=True,
         engine_hook=None) -> int:
    """Run the cell; returns the exit code.  ``require_tpu``,
    ``compile_cache`` and ``engine_hook`` exist for the benchmark's own
    tests, which run a tiny cell on the CPU."""
    args = parse_args(argv)
    sys.path.insert(0, os.path.join(root, "bench"))
    sys.path.insert(0, os.path.join(root, "src"))
    try:
        import jax
        from repro.runtime.serve_loop import ServeEngine  # noqa: F401
    except ImportError as e:
        print(f"the program under test is not importable from {root}/src: "
              f"{e}", file=sys.stderr)
        return 2
    from benchlib import driver
    from benchlib.cells import Bench
    from benchlib.faults import FAULTS

    bench = Bench(root)
    cell = bench.cell(args.workload)
    devices = jax.devices()
    driver.log(f"jax {jax.__version__}; {len(devices)} x "
               f"{devices[0].device_kind} ({devices[0].platform})")
    if require_tpu and devices[0].platform != "tpu":
        print(f"no TPU: JAX found {devices[0].platform}; the benchmark "
              f"never falls back to it", file=sys.stderr)
        return 2
    if len(devices) < cell.chips:
        print(f"cell {cell.name} needs {cell.chips} chips, JAX found "
              f"{len(devices)}", file=sys.stderr)
        return 2
    if compile_cache:
        driver.log(f"compilation cache: {use_compile_cache(root)}")
    clock = driver.CompileClock()
    if args.sweep:
        rates = [float(r) for r in args.sweep.split(",")]
        for line in driver.sweep(bench, cell, args.seed, args.seconds,
                                 rates, clock):
            print(json.dumps(line), flush=True)
        return 0
    if args.fault:
        engine_hook = FAULTS[args.fault]
    out = driver.run_cell(bench, cell, args.seed, args.seconds,
                          bool(args.trace), t_start=T_START, clock=clock,
                          control=args.control, engine_hook=engine_hook)
    for name, c in out["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
