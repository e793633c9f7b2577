#!/usr/bin/env python3
"""What tracing costs on the one-shot cell's closed loop, on a TPU.

    python3 scripts/obs_cost.py [--seed N] [--seconds S] [--rounds R]

Sets up the ``paper-oneshot`` cell as ``bench/run.py`` does (its data,
warm-up and compile cache), then times closed-loop ``api.svd`` windows
of ``--seconds`` each, round-robin over three modes for ``--rounds``
rounds:

* ``off`` — as the benchmark's untraced runs: obs off, no profiler;
* ``profiler`` — a ``jax.profiler`` session open over the window, with
  ``bench/run.py``'s options: every span also enters a
  ``TraceAnnotation``;
* ``obs`` — ``obs.enable()``: the span ring, counters and
  ``Diagnostics`` digests record.

It ends with the host cost of one span (``with obs.span(...)``, in
microseconds, by ``timeit``) with obs off and no session, inside a
profiler session, and with obs on.  Prints one JSON line: seconds per
solve of every window by mode, and the span costs.  Refuses to run
without a TPU.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import tempfile
import timeit

ROOT = pathlib.Path(__file__).resolve().parents[1]
MODES = ("off", "profiler", "obs")
SPAN_CALLS = 200_000


def _profile(tdir: str):
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(tdir, profiler_options=opts)


def _span_us(obs) -> float:
    def one():
        with obs.span("svd.convert", nnz=1):
            pass

    return 1e6 * timeit.timeit(one, number=SPAN_CALLS) / SPAN_CALLS


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=2 ** 31 + 4099)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--rounds", type=int, default=3)
    args = ap.parse_args(argv)

    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    import jax

    from bench import run
    from repro import obs

    spec = run.load_spec()
    cell = run.make_cell(spec, "paper-oneshot", args.seed)
    devices = run.tpu_devices(cell.chips)
    run.enable_cache()
    driver = run.driver_module(cell.traffic["driver"])
    ctx = driver.setup(cell)

    per_solve = {m: [] for m in MODES}
    for _ in range(args.rounds):
        for mode in MODES:
            with tempfile.TemporaryDirectory() as tdir:
                if mode == "profiler":
                    _profile(tdir)
                elif mode == "obs":
                    obs.enable()
                try:
                    win = driver.window(ctx, args.seconds)
                finally:
                    if mode == "profiler":
                        jax.profiler.stop_trace()
                    elif mode == "obs":
                        obs.disable()
                        obs.reset()
            per_solve[mode].append(win["metrics"]["oneshot_s"])

    span_us = {"off": _span_us(obs)}
    with tempfile.TemporaryDirectory() as tdir:
        _profile(tdir)
        try:
            span_us["profiler"] = _span_us(obs)
        finally:
            jax.profiler.stop_trace()
    obs.enable()
    try:
        span_us["obs"] = _span_us(obs)
    finally:
        obs.disable()
        obs.reset()

    print(json.dumps({"device": run.device_line(devices),
                      "seconds": args.seconds, "oneshot_s": per_solve,
                      "span_us": span_us}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
