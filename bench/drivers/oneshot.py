"""One-shot traffic: one client, closed loop, the front door's
``api.svd`` of one matrix back to back.

Mix parameters (``bench/traffic/<mix>.json``): ``settings`` (front-door
fields over the configuration's, e.g. ``num_blocks``), ``plan`` (what the
planner must choose), ``trace_seconds`` and ``limits``.

Window: solves until ``seconds`` have passed, ending at a solve's end;
``oneshot_s`` is the window's seconds over the solves completed.  Each
solve is the whole call a user makes: the host's COO conversion, the
jitted solve, the wait for U, s and V, and the diagnostics.
"""
from __future__ import annotations

import types

import jax
import numpy as np

from bench import harness, lowp
from repro.core import api, sparse


def _solve(coo, cfg):
    res = api.svd(coo, cfg)
    jax.block_until_ready((res.u, res.s, res.v))
    return res


def setup(cell) -> dict:
    with harness.span("generate"):
        data = cell.ref.generate(cell.config, cell.seed,
                                 int(cell.settings["num_blocks"]))
    rows, cols, vals, shape = data
    coo = sparse.COOMatrix(rows=rows, cols=cols, vals=vals, shape=shape)
    s = cell.settings
    cfg = api.SolveConfig(method=s["method"], num_blocks=int(s["num_blocks"]),
                          want_right=bool(s["want_right"]))
    # The second call finds every program compiled (or read back).
    for _ in range(2):
        res = _solve(coo, cfg)
    harness.require_plan(res.plan, cell.traffic["plan"], cell.name)
    return {"cell": cell, "data": data, "coo": coo, "cfg": cfg,
            "solve": _solve}


def window(ctx: dict, seconds: float) -> dict:
    keep = harness.Reservoir(ctx["cell"].seed)
    n, t0 = 0, harness.now()
    while True:
        with harness.span("solve"):
            res = ctx["solve"](ctx["coo"], ctx["cfg"])
        n += 1
        keep.offer((res.u, res.s, res.v))
        if harness.now() - t0 >= seconds:
            break
    elapsed = harness.now() - t0
    return {"attempted": n, "failed": 0,
            "metrics": {"oneshot_s": elapsed / n},
            "readers": {"solves": n}, "kept": keep.item}


def check(ctx: dict, win: dict) -> dict:
    cell = ctx["cell"]
    u, s, v = (np.asarray(x) for x in win["kept"])
    win["kept"] = None
    nums = cell.ref.oneshot_reference(ctx["data"],
                                      int(cell.settings["num_blocks"]),
                                      u, s, v)
    return harness.checks(nums, cell.traffic["limits"])


def control(ctx: dict) -> dict:
    """The plain reference, its products at ``HIGH`` (``bench/lowp.py``),
    in the solve's place: the entries of the set-up context it replaces."""
    cell = ctx["cell"]
    d = int(cell.settings["num_blocks"])

    def solve(coo, cfg):
        data = (coo.rows, coo.cols, coo.vals, coo.shape)
        u, s, v = cell.ref.oneshot_solve(data, d, cell.seed,
                                         lowp.matmul_high)
        return types.SimpleNamespace(u=u, s=s, v=v)

    return {"solve": solve}
