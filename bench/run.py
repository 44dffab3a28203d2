"""Run one cell of the chip benchmark.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout, on a machine that holds the chips the
cell asks for (``BENCHMARK.json``). With ``--trace 0`` the result line
carries the cell's end-to-end metrics; with ``--trace 1`` its per-layer
metrics, read from one profiled wave. The last line of standard output is
one JSON object (``correct``, ``attempted``, ``failed``, ``metrics``,
``device``[, ``breakdown``], ``checks``); the last lines of standard error
give each number that ``correct`` compares beside its limit. Without a
TPU, or with fewer chips than the cell asks for, it prints no result and
exits 2.

JAX's persistent compilation cache goes to ``$JAX_COMPILATION_CACHE_DIR``
if set, else to ``.jax_cache`` at the root of the checkout.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = p.parse_args(argv)
    if args.seed < 0:
        p.error("--seed must be a non-negative whole number")
    for path in (os.path.join(ROOT, "src"), ROOT):
        if path not in sys.path:
            sys.path.insert(0, path)
    import jax

    jax.config.update("jax_compilation_cache_dir",
                      os.environ.get("JAX_COMPILATION_CACHE_DIR")
                      or os.path.join(ROOT, ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    from bench.harness import core

    return core.run(ROOT, args.workload, args.seed, args.seconds,
                    bool(args.trace), T_PROCESS)


if __name__ == "__main__":
    sys.exit(main())
