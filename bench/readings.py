#!/usr/bin/env python3
"""Read a cell's compared numbers on many seeds in one process.

    python3 bench/readings.py --workload ml25m-ingest --seeds 1,2,3 \
        --seconds 4 [--matmul high | --control]

Each seed runs the cell's set-up, a short window at the cell's own load
and its check, as ``bench/run.py`` does; the compiled programs are shared
between seeds, so a dozen seeds cost one compile.  One JSON line per
seed gives every number compared beside its limit.  This is how the
limits in ``bench/traffic/*.json`` were set:

* the program's readings, over a dozen seeds or more, give the lower
  end;
* ``--matmul high`` runs the program with its own lower-precision path
  (``repro.precision.MATMUL = HIGH``, set before anything is traced),
  the control of the one-shot and streaming cells;
* ``--control`` puts the plain reference at ``HIGH`` in the program's
  place (each driver's ``control``), the control of the serving mix,
  whose kernel refuses ``HIGH``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                ".."))
from bench import run  # noqa: E402

run.use_checkout_paths()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--matmul", choices=("highest", "high"),
                    default="highest")
    ap.add_argument("--control", action="store_true")
    args = ap.parse_args(argv)

    spec = run.load_spec()
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(run.CACHE_DIR)
    import jax

    from bench import harness
    from repro import precision

    precision.MATMUL = {"highest": jax.lax.Precision.HIGHEST,
                        "high": jax.lax.Precision.HIGH}[args.matmul]
    seeds = [int(s) for s in args.seeds.split(",")]
    run.tpu_devices(run.make_cell(spec, args.workload, seeds[0]).chips)
    run.enable_cache()
    for seed in seeds:
        cell = run.make_cell(spec, args.workload, seed)
        driver = run.driver_module(cell.traffic["driver"])
        ctx = driver.setup(cell)
        if args.control:
            ctx.update(driver.control(ctx))
        win = driver.window(ctx, args.seconds)
        checks = driver.check(ctx, win)
        print(json.dumps({
            "workload": args.workload, "seed": seed, "matmul": args.matmul,
            "control": args.control,
            "correct": all(harness.passed(c) for c in checks.values()),
            "metrics": win["metrics"], "checks": checks}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
