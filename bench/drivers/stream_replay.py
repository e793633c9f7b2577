"""Streaming traffic: replays of the same batches into one history state.

Mix parameters: ``history_batches`` (folded into ``state0`` during
set-up), ``replay_batches`` (the batches each replay folds into
``state0``), ``plan``, ``trace_seconds`` and ``limits``.

Set-up generates the configuration's batches from the seed, folds the
history through ``api.svd_stream`` (the rank grows on the first batch,
then the scan windows run) and makes one untimed warm-up replay, so every
program of a replay is compiled.  The window runs replays
``api.svd_stream(replay, state=state0)`` back to back and ends at the
first replay boundary after ``seconds``; ``ingest_rows_per_s`` is every
row folded in the window over the window's seconds.  Every replay folds
the same rows into the same state, so the shapes repeat and the state
keeps its ``rows_seen``.

The check folds the history and then the replay in float64 from the
empty state (the configuration's reference), and holds the leading part
of both the program's ``state0`` (``history_lead``) and one replay of
the window (``state_lead``) against it (the configuration's
``lead_numbers``).
"""
from __future__ import annotations

import dataclasses

import numpy as np

from bench import harness, lowp
from repro.core import api, sparse


def _coo(batch):
    rows, cols, vals, shape = batch
    return sparse.COOMatrix(rows=rows, cols=cols, vals=vals, shape=shape)


def _replay(batches, cfg, state):
    res = api.svd_stream(batches, cfg, state=state)
    st = res.state
    # svd_stream waits for u, s and v before it returns.
    return st, res.plan


def setup(cell) -> dict:
    s, t = cell.settings, cell.traffic
    d = int(s["num_blocks"])
    with harness.span("generate"):
        data = cell.ref.generate(cell.config, cell.seed, d)
    hist = int(t["history_batches"])
    replay = data[hist:hist + int(t["replay_batches"])]
    if len(replay) != int(t["replay_batches"]):
        raise ValueError(f"{cell.name}: the configuration makes "
                         f"{len(data)} batches, fewer than the history and "
                         f"one replay")
    cfg = api.SolveConfig(method=s["method"], num_blocks=d,
                          truncate_rank=int(s["truncate_rank"]))
    res0 = api.svd_stream([_coo(b) for b in data[:hist]], cfg)
    state0 = res0.state
    replay_coo = [_coo(b) for b in replay]
    _, plan = _replay(replay_coo, cfg, state0)       # warm-up
    harness.require_plan(plan, t["plan"], cell.name)
    return {"cell": cell, "cfg": cfg, "state0": state0,
            "history": data[:hist], "replay": replay,
            "replay_coo": replay_coo, "run": _replay}


def window(ctx: dict, seconds: float) -> dict:
    keep = harness.Reservoir(ctx["cell"].seed)
    rows = sum(b[3][0] for b in ctx["replay"])
    n, t0 = 0, harness.now()
    while True:
        with harness.span("replay"):
            st, _ = ctx["run"](ctx["replay_coo"], ctx["cfg"], ctx["state0"])
        n += 1
        keep.offer(st)
        if harness.now() - t0 >= seconds:
            break
    elapsed = harness.now() - t0
    return {"attempted": n, "failed": 0,
            "metrics": {"ingest_rows_per_s": n * rows / elapsed},
            "readers": {"batches": n * len(ctx["replay"]),
                        "replay": ctx["replay"]},
            "kept": keep.item}


def check(ctx: dict, win: dict) -> dict:
    cell, st0 = ctx["cell"], ctx["state0"]
    st = win["kept"]
    win["kept"] = None
    got = [np.asarray(x) for x in (st.u, st.s, st.v)]
    rows_seen, repaired = st.rows_seen, st.repaired_rows_seen
    del st
    got0 = [np.asarray(x) for x in (st0.u, st0.s, st0.v)]
    k, p = int(cell.settings["truncate_rank"]), int(ctx["cfg"].oversample)
    ref0 = cell.ref.fold(*cell.ref.empty_state(int(cell.config["items"])),
                         ctx["history"], k=k, oversample=p)
    ref = cell.ref.fold(*ref0, ctx["replay"], k=k, oversample=p)
    nums = {"history_lead": cell.ref.lead_numbers(*got0, ref0)["lead"],
            "state_lead": cell.ref.lead_numbers(*got, ref)["lead"],
            "rows": abs(rows_seen - ref[0].shape[0]),
            "repairs": repaired}
    return harness.checks(nums, cell.traffic["limits"])


def control(ctx: dict) -> dict:
    """The plain reference, its products at ``HIGH`` (``bench/lowp.py``),
    in the program's place for the history and the replay: the entries of
    the set-up context it replaces."""
    cell, st0 = ctx["cell"], ctx["state0"]
    k, p = int(cell.settings["truncate_rank"]), int(ctx["cfg"].oversample)

    def fold(state, data):
        u, s, v = cell.ref.fold(*state, data, k=k, oversample=p,
                                matmul=lowp.matmul_high)
        return dataclasses.replace(
            st0, u=u, s=s, v=v,
            rows_seen=u.shape[0])

    def replay(batches, cfg, state):
        data = [(b.rows, b.cols, b.vals, b.shape) for b in batches]
        return fold((state.u, state.s, state.v), data), None

    empty = cell.ref.empty_state(int(cell.config["items"]), np.float32)
    return {"state0": fold(empty, ctx["history"]), "run": replay}
