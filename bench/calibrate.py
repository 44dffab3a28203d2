"""Readings that set the limit of ``correct``, made on the chip.

    python bench/calibrate.py --workload <cell> --seeds 1,2,3 [--waves 1]
        [--control 1]

In one process: the cell's set-up once, then for each seed the weights and
adapters of that seed, ``--waves`` waves at the cell's own load (every
wave finishes its longest requests), the same sample a run compares, and
the widest gap of the served tokens (the lower reading). With
``--control 1`` also the int8 control's widest gap at the same positions
(the upper reading). One JSON line per seed on standard output.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True)
    p.add_argument("--waves", type=int, default=1)
    p.add_argument("--control", type=int, choices=(0, 1), default=0)
    args = p.parse_args(argv)
    for path in (os.path.join(ROOT, "src"), ROOT):
        if path not in sys.path:
            sys.path.insert(0, path)
    import jax

    jax.config.update("jax_compilation_cache_dir",
                      os.environ.get("JAX_COMPILATION_CACHE_DIR")
                      or os.path.join(ROOT, ".jax_cache"))
    from bench.harness import core, correct

    if jax.devices()[0].platform != "tpu":
        print("calibrate: needs a TPU", file=sys.stderr)
        return 2
    c = core.load_cell(ROOT, args.workload)
    first = None
    for seed in [int(s) for s in args.seeds.split(",")]:
        env = core.build(c, seed, steps_from=first)
        win = core.window(env, 0.0, min_waves=args.waves)
        got = core.served(win, env.vocab)
        model, loaded = env.model, win.loaded
        if first is None:
            first = core.steps_of(env.engine)
        del env, win
        gc.collect()
        rows = correct.sample(got.done, seed, loaded)
        g = correct.gaps(model, c["cfg"], seed, rows, control=bool(args.control))
        line = {"seed": seed, "failed": got.failed, "rows": len(rows),
                "tokens": int(len(g["served"])),
                **{f"served.{k}": v
                   for k, v in correct.numbers(g["served"]).items()}}
        if args.control:
            line.update({f"int8.{k}": v
                         for k, v in correct.numbers(g["int8"]).items()})
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
