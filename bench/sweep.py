#!/usr/bin/env python3
"""Find the highest wave rate a serving cell sustains, once, on the chip.

    python3 bench/sweep.py --config ml25m --traffic topk-poisson --seed 1 \
        --seconds 8 --fractions 0.5,0.7,0.8,0.9,1.0,1.1 --repeats 1

Sets the cell up once, times waves back to back (closed loop, one
client) for the capacity, then offers the open-loop mix at each fraction
of it, ``--repeats`` times with arrivals and users drawn from successive
seeds, and prints per window the p95 latency and how far the last wave
ended past its due time (a backlog that grows).  A serving cell offers
a fixed rate; this script is how that rate is chosen, for a
configuration and a serving mix that ``BENCHMARK.json`` need not list.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                ".."))
from bench import harness, run  # noqa: E402

run.use_checkout_paths()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--fractions", default="0.5,0.7,0.8,0.9,1.0,1.1")
    ap.add_argument("--repeats", type=int, default=1)
    args = ap.parse_args(argv)

    name = f"{args.config}.{args.traffic}"
    spec = {"workloads": [{"name": name, "config": args.config,
                           "traffic": args.traffic, "chips": 1}]}
    cell = run.make_cell(spec, name, args.seed)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(run.CACHE_DIR)
    run.tpu_devices(cell.chips)
    run.enable_cache()
    driver = run.driver_module(cell.traffic["driver"])
    ctx = driver.setup(cell)

    snap, handle, st0 = ctx["snap"], ctx["handle"], ctx["state0"]
    rng = np.random.default_rng([args.seed, 9])
    b = int(cell.traffic["serve"]["batch_size"])
    n, t0 = 0, harness.now()
    while harness.now() - t0 < args.seconds:
        ctx["wave"](handle, snap, rng.integers(0, st0.rows_seen, b))
        n += 1
    cap = n / (harness.now() - t0)
    print(json.dumps({"closed_loop_waves_per_s": cap}), flush=True)
    base = cell.seed
    for frac in (float(f) for f in args.fractions.split(",")):
        for rep in range(args.repeats):
            cell.seed = base + 1 + rep
            cell.traffic["rate_per_s"] = frac * cap
            win = driver.window(ctx, args.seconds)
            due_end = float(driver.arrivals(frac * cap, args.seconds,
                                            cell.seed)[-1])
            late = np.asarray(win["readers"]["late_ms"])
            print(json.dumps({
                "fraction": frac, "rate_per_s": frac * cap, "seed": cell.seed,
                "waves": win["attempted"],
                "p95_ms": win["metrics"]["serve_p95_ms"],
                "overrun_s": win["elapsed_s"] - due_end,
                "gen_late_p95_ms": float(np.percentile(late, 95))
                if late.size else None}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
