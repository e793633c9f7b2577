"""Serving traffic: open-loop waves of known-user top-k queries.

Mix parameters: ``history_batches`` (folded into the served state during
set-up), ``serve`` (``batch_size``, ``k_top``, ``quantize``),
``rate_per_s`` (waves per second offered, fixed), ``check_waves`` (how
many of the window's waves the check compares, drawn from the seed),
``plan``, ``trace_seconds`` and ``limits``.

Arrivals are a Poisson process with the same set of gaps for every seed:
the ``rate * seconds`` gaps are the exponential distribution's quantiles
(i + 1/2) / n, in an order drawn from the seed.  Each wave draws
``batch_size`` user ids uniformly over the state's users, looks up their
rows (``ranker.user_queries``) and calls ``api.serve_topk``.  One host
thread sends a wave at its due time, or at once when the previous wave
ends late; a wave's latency runs from its due time until its ids and
scores are ready, so a stall counts against every wave it delays.
``serve_p95_ms`` is the 95th percentile over all waves of the window; a
wave that raised counts as infinitely late.

The check folds the history in float64 from the empty state (the
configuration's reference) and holds the served factors against it
(``history``), then a sample of the window's waves against a float64
top-k over the served factors (``missed``, ``score``).
"""
from __future__ import annotations

import time
import types

import jax
import numpy as np

from bench import harness, lowp
from repro.core import api, sparse
from repro.serve import ranker


def _wave(handle, snap, ids):
    q = ranker.user_queries(snap, ids)
    res = api.serve_topk(handle, q)
    jax.block_until_ready((res.indices, res.scores))
    return res


def setup(cell) -> dict:
    s, t = cell.settings, cell.traffic
    d = int(s["num_blocks"])
    with harness.span("generate"):
        data = cell.ref.generate(cell.config, cell.seed, d)
    cfg = api.SolveConfig(method=s["method"], num_blocks=d,
                          truncate_rank=int(s["truncate_rank"]))
    hist = [sparse.COOMatrix(rows=r, cols=c, vals=v, shape=sh)
            for r, c, v, sh in data[:int(t["history_batches"])]]
    state0 = api.svd_stream(hist, cfg).state
    sv = t["serve"]
    handle = api.serve_init(state0, api.ServeTopKConfig(
        batch_size=int(sv["batch_size"]), k_top=int(sv["k_top"]),
        quantize=bool(sv["quantize"]), keep_u=True))
    harness.require_plan(handle.plan, t["plan"], cell.name)
    snap = handle.read()
    rng = np.random.default_rng([cell.seed, 3])
    for _ in range(3):
        _wave(handle, snap, rng.integers(0, state0.rows_seen,
                                         int(sv["batch_size"])))
    return {"cell": cell, "cfg": cfg, "state0": state0,
            "history": data[:int(t["history_batches"])], "handle": handle,
            "snap": snap, "wave": _wave}


def arrivals(rate: float, seconds: float, seed: int) -> np.ndarray:
    """Due times (s from the window's start) of ``rate * seconds`` waves."""
    n = max(1, int(round(rate * seconds)))
    gaps = -np.log1p(-(np.arange(n) + 0.5) / n) / rate
    return np.cumsum(np.random.default_rng([seed, 1]).permutation(gaps))


def _sleep_until(t: float) -> None:
    while True:
        left = t - harness.now()
        if left <= 0:
            return
        if left > 2e-3:
            time.sleep(left - 1e-3)


def window(ctx: dict, seconds: float) -> dict:
    cell, st0 = ctx["cell"], ctx["state0"]
    sv = cell.traffic["serve"]
    due = arrivals(float(cell.traffic["rate_per_s"]), seconds, cell.seed)
    ids = np.random.default_rng([cell.seed, 2]).integers(
        0, st0.rows_seen, (due.size, int(sv["batch_size"])))
    lat, late, got = [], [], []
    failed = 0
    t0 = idle_since = harness.now()
    for i, d in enumerate(due):
        at = t0 + d
        idle = idle_since <= at
        _sleep_until(at)
        sent = harness.now()
        if idle:
            late.append(sent - at)
        try:
            with harness.span("wave"):
                res = ctx["wave"](ctx["handle"], ctx["snap"], ids[i])
            done = harness.now()
            lat.append(done - at)
            got.append((i, res))
        except Exception as exc:  # a failed wave is a missing answer
            cell.log(f"wave {i} failed: {exc!r}")
            failed += 1
            done = harness.now()
            lat.append(float("inf"))
        idle_since = done
    elapsed = harness.now() - t0
    p95 = float(np.percentile(np.asarray(lat), 95)) * 1e3
    return {"attempted": int(due.size),
            "failed": failed, "elapsed_s": elapsed,
            "metrics": {"serve_p95_ms": p95},
            "readers": {"waves": int(due.size),
                        "late_ms": [x * 1e3 for x in late],
                        "batch": int(sv["batch_size"]),
                        "k_top": int(sv["k_top"]),
                        "items": int(ctx["snap"].n),
                        "rank": int(ctx["snap"].rank)},
            "ids": ids, "got": got}


def check(ctx: dict, win: dict) -> dict:
    cell, snap = ctx["cell"], ctx["snap"]
    sv, t = cell.traffic["serve"], cell.traffic
    got = win["got"]
    rng = np.random.default_rng([cell.seed, 4])
    pick = sorted(rng.choice(len(got), size=min(len(got),
                                                int(t["check_waves"])),
                             replace=False).tolist())
    u0, s0, v0 = (np.asarray(x) for x in (snap.u_rows, snap.s, snap.v))
    ref0 = cell.ref.fold(*cell.ref.empty_state(int(cell.config["items"])),
                         ctx["history"],
                         k=int(cell.settings["truncate_rank"]),
                         oversample=int(ctx["cfg"].oversample))
    ids = np.concatenate([win["ids"][got[j][0]] for j in pick])
    out_ids = np.concatenate([np.asarray(got[j][1].indices) for j in pick])
    out_sc = np.concatenate([np.asarray(got[j][1].scores) for j in pick])
    nums = cell.ref.topk_numbers(v0, s0, int(snap.n), u0[ids],
                                 out_ids, out_sc, int(sv["k_top"]))
    nums["history"] = cell.ref.state_numbers(u0, s0, v0, ref0)["state"]
    nums["failed"] = float(win["failed"])
    return harness.checks(nums, t["limits"])


def control(ctx: dict) -> dict:
    """The plain top-k, its scores at ``HIGH`` (``bench/lowp.py``), in the
    wave's place: the entries of the set-up context it replaces."""
    cell = ctx["cell"]

    def wave(handle, snap, ids):
        q = np.asarray(snap.u_rows)[ids]
        got_ids, got_sc = cell.ref.topk_control(
            np.asarray(snap.v), np.asarray(snap.s), snap.n, q,
            handle.config.k_top, lowp.matmul_high)
        return types.SimpleNamespace(indices=got_ids, scores=got_sc)

    return {"wave": wave}
