"""Run one cell of the benchmark once and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

From the root of a checkout. Fails without a TPU, or with fewer chips than
the cell asks for, before it prints any result. ``--trace 0`` reports the
cell's end-to-end metrics, ``--trace 1`` its per-layer metrics from a
profiler trace of the window. The last line of stdout is the result's JSON
object; the numbers of the check, each beside its limit, are the last lines
of stderr and the last key of the result.
"""
from __future__ import annotations

import os
import time


def _process_age() -> float:
    """Seconds since this process started, from ``/proc``."""
    with open("/proc/self/stat") as f:
        start_ticks = int(f.read().rsplit(")", 1)[1].split()[19])
    with open("/proc/uptime") as f:
        uptime = float(f.read().split()[0])
    return max(0.0, uptime - start_ticks / os.sysconf("SC_CLK_TCK"))


T_START = time.perf_counter() - _process_age()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH_DIR = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH_DIR.parent / "src"))
#: JAX's persistent compilation cache: inside the checkout, at a fixed path
#: (the path is part of the cache's key). The program's entry points take
#: the directory from this variable.
CACHE_DIR = BENCH_DIR.parent / ".jax_cache"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    import harness

    bench = harness.Bench()
    cell = bench.cell(args.workload)

    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    import jax

    # persist every program of the cell, however fast it compiled
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    dev = harness.device_info()
    harness.log(f"device: {json.dumps(dev)}")
    if dev["platform"] != "tpu":
        harness.log(f"error: no TPU: JAX's devices are {dev['platform']!r}")
        return 2
    if dev["count"] < cell["chips"]:
        harness.log(f"error: {dev['count']} chips, the cell asks for {cell['chips']}")
        return 2

    result = harness.run_cell(bench, args.workload, args.seed, args.seconds,
                              bool(args.trace), T_START)
    print(json.dumps(harness.finite(result), allow_nan=False), flush=True)
    for name, row in result["checks"].items():
        harness.log(f"check {name}: {row['value']} (limit {row['limit']})")
    return 0


if __name__ == "__main__":
    sys.exit(main())
