"""The benchmark's cells cut to a size the CPU runs in seconds.

Importing this module puts the checkout's root (for ``bench``) and
``src`` (for the program) on the path; the test modules import it first.
There is no ``conftest.py`` here: ``tests/`` imports its own by that
name."""
from __future__ import annotations

import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[2]
for _p in (str(ROOT / "src"), str(ROOT)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import jax  # noqa: E402

from bench import run  # noqa: E402

SEED = 2 ** 31 + 977       # larger than 32 signed bits hold

# The serving mix is kept, tested, for a later cell: BENCHMARK.json does
# not list it yet (PERF.md, Open questions).
KEPT = {"workloads": [{"name": "ml25m-serve", "config": "ml25m",
                       "traffic": "topk-poisson", "chips": 1}],
        "end_to_end": [{"name": "serve_p95_ms", "unit": "ms",
                        "workloads": ["ml25m-serve"]}]}


def spec() -> dict:
    """``BENCHMARK.json`` with the kept mix's cell added."""
    out = run.load_spec()
    for key, extra in KEPT.items():
        out[key] = out[key] + extra
    return out


def tiny_cell(name: str, seed: int = SEED, log=None) -> run.Cell:
    cell = run.make_cell(spec(), name, seed, log=log or (lambda msg: None))
    if cell.config["name"] == "paper-kariyer":
        cell.config.update(rows=48, cols=4096, density=2e-3)
    else:
        cell.config.update(
            items=3000, users=6 * 64, users_in_source=6 * 64,
            ratings_in_source=6 * 64 * 80, batch_rows=64,
            movie_ratings={"rated": 2800, "min": 1, "quartiles": [2, 5, 14],
                           "fifth": 150, "max": 190},
            user_ratings={"min": 20, "quartiles": [30, 45, 80], "max": 900})
        cell.settings["num_blocks"] = 4
        cell.traffic.update(history_batches=4, replay_batches=2)
        if "serve" in cell.traffic:
            cell.traffic["serve"]["batch_size"] = 8
            cell.traffic["check_waves"] = 6
    return cell


def run_tiny(cell: run.Cell, seconds: float = 0.5,
             trace: bool = False) -> dict:
    """One run of ``cell`` with the look for a chip skipped."""
    return run.run_cell(cell, seconds, trace, spec=spec(),
                        devices=jax.devices(),
                        counter=run.CompileCounter().install(),
                        t_start=time.monotonic())
