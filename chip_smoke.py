#!/usr/bin/env python3
"""Bring-up check: Ranky's three jobs on a TPU, each against a float64
host reference.

    python3 chip_smoke.py            # one chip
    python3 chip_smoke.py --chips 4  # the shard_map paths on four chips

One chip runs, through the ``repro.core.api`` front door:

* **one-shot** — the paper's 539 x 170,897 matrix at density 5e-4
  (``configs/ranky_paper.py``, seed 2020) as a ``COOMatrix`` (the
  sparse-native path), ``SolveConfig(method="neighbor_random",
  num_blocks=8, want_right=True)``, once with the compiled
  ``sparse_gram`` kernel and once on XLA.  Reference: numpy's float64
  SVD of the same repaired matrix, the protocol of
  ``benchmarks/paper_tables.py``.
* **stream** — user rows over MovieLens-25M's item count (62,423
  items) at its mean of ~154 ratings per user (GroupLens ML-25M README),
  folded in as 8 batches of 2,048 rows by ``svd_stream`` with
  ``truncate_rank=32``, the kernel and the scan window.  The ratings are
  synthetic: 32 taste groups, items drawn uniformly within a group —
  MovieLens's popularity skew is not modelled (it would put a batch's
  largest column degree near 1,000, past the kernel's slot limit).
  Reference: float64 top-32 SVD of the stacked rows.
* **serve** — ``serve_init`` / ``serve_topk`` on that state with the
  fused top-k kernel, f32 and int8 factors, a few waves of 64 known-user
  queries.  Reference: float64 top-k over the same (dequantized)
  factors, compared tie-aware.

``--chips 4`` runs only the paths that exist across chips, at
``num_blocks=4`` and the same sizes: the one-shot under
``backend="shard_map"``, the stream under ``stream_backend="shard_map"``
and the ranker under ``serve_backend="shard_map"``, each beside the same
config on the single-device engine.

Each phase prints its plan, compile and run seconds, errors against the
reference and the device's peak bytes.  Any failed check raises; the
script has no CPU path.  The last line is one JSON object naming the
device.  ``tests/test_chip_smoke.py`` runs the phase functions on the
CPU at a small size against the same checks.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro import compile_cache  # noqa: E402
from repro.configs import ranky_paper  # noqa: E402
from repro.core import api, ranky, sparse  # noqa: E402
from repro.kernels import ops  # noqa: E402
from repro.kernels import ref as kref  # noqa: E402
from repro.obs import clock  # noqa: E402
from repro.serve import ranker  # noqa: E402
from repro.stream import state as stream_state  # noqa: E402

# ---------------------------------------------------------------------------
# Tolerances.  Every bound is float32 round-off (EPS32) times a small
# factor: a result within it is as exact as f32 arithmetic allows; one
# matmul pass in bfloat16 (the TPU's DEFAULT precision for f32 operands,
# relative error ~2^-9) lands orders of magnitude outside.
# ---------------------------------------------------------------------------
EPS32 = float(np.finfo(np.float32).eps)

# One-shot, normwise against the float64 SVD of the repaired matrix A:
#   gram:  max_i |s_i^2 - s_ref_i^2| / s_ref_1^2  (the gram + eigh path's
#          backward error; small s_i lose relative accuracy as
#          (s_1 / s_i)^2 by design, so per-value relative error is
#          reported, not bounded)
#   recon: ||U diag(s) V^T - A||_F / ||A||_F
#   orth:  max |U^T U - I|
ONESHOT_TOL = {"gram": 128 * EPS32, "recon": 128 * EPS32,
               "orth": 128 * EPS32}

# Serving: a returned score may differ from the float64 score by this
# much, relative to sum_i |q_i s_i v_ji| (a rank-k dot product in f32).
SERVE_TOL = 16 * EPS32

# Stream: merge-and-truncate keeps 32 of each merge's 32 + 40 directions
# by design, so its error against the exact top-32 SVD is that
# truncation error plus round-off.  The truncation error is what the
# same phase reached on the CPU backend (XLA, float32) at the same size
# and seed, measured once; the slack is f32-level.
STREAM_SLACK = 64 * EPS32
STREAM_CPU = {"sv_rel": 0.0010500305442942568, "v_sin": 0.00841367863435033}

PAPER = ranky_paper.config()
# 160 draws per user leave ~154 distinct ratings once duplicates
# collapse.  A batch's largest column degree then lands near 16, on
# either side of the ELL bucket boundary, so the stream meets more than
# one bucket shape.
STREAM_SHAPE = dict(items=62_423, draws_per_row=160, batch_rows=2_048,
                    batches=8, groups=32, seed=25)
STREAM_RANK = 32
SERVE_SHAPE = dict(batch=64, waves=4, k_top=10, seed=7)


# ---------------------------------------------------------------------------
# Data
# ---------------------------------------------------------------------------

def paper_matrix(rows: int = PAPER.rows, cols: int = PAPER.cols,
                 density: float = PAPER.density,
                 seed: int = PAPER.seed) -> sparse.COOMatrix:
    """The paper's job-candidate matrix shape, weighted edges (a
    non-degenerate spectrum), every global row non-empty."""
    return sparse.ensure_full_row_rank(
        sparse.random_bipartite(rows, cols, density, seed=seed,
                                weighted=True), seed=seed)


# Half-star ratings 0.5 .. 5.0, weighted toward 3-4 stars.
_RATINGS = np.arange(1, 11, dtype=np.float32) / 2
_RATING_P = np.array([1, 3, 2, 7, 5, 20, 13, 27, 8, 14], np.float64) / 100


def rating_rows(*, items: int, draws_per_row: int, batch_rows: int,
                batches: int, groups: int, num_blocks: int, seed: int,
                in_group: float = 0.9):
    """``batches`` COO batches of user rows over an ``items`` universe.

    Each user has a taste group (items are dealt into ``groups`` groups
    at random) and draws ``draws_per_row`` ratings uniformly, ``in_group``
    of them from the group's items and the rest from all items;
    duplicates collapse.  Every row gets at least one rating in every
    column block, so no row is lonely and the stacked rows are already
    the repaired matrix."""
    rng = np.random.default_rng(seed)
    r_all = batch_rows * batches
    item_group = rng.permutation(items) % groups
    by_group = np.argsort(item_group, kind="stable")
    sizes = np.bincount(item_group, minlength=groups)
    starts = np.concatenate([[0], np.cumsum(sizes)[:-1]])
    user_group = rng.integers(0, groups, r_all)

    rows = np.repeat(np.arange(r_all), draws_per_row)
    g = user_group[rows]
    pick = by_group[starts[g] + rng.integers(0, sizes[g])]
    cols = np.where(rng.random(rows.size) < in_group, pick,
                    rng.integers(0, items, rows.size))
    w = sparse.block_width(items, num_blocks)
    hit = np.zeros((r_all, num_blocks), bool)
    hit[rows, cols // w] = True
    miss_r, miss_d = np.nonzero(~hit)
    lo = miss_d * w
    fill = lo + rng.integers(0, np.minimum(w, items - lo))
    rows = np.concatenate([rows, miss_r])
    cols = np.concatenate([cols, fill])
    key = np.unique(rows.astype(np.int64) * items + cols)
    rows, cols = key // items, key % items
    vals = rng.choice(_RATINGS, size=rows.size, p=_RATING_P)
    out = []
    for b in range(batches):
        sel = (rows >= b * batch_rows) & (rows < (b + 1) * batch_rows)
        out.append(sparse.COOMatrix(
            rows=(rows[sel] - b * batch_rows).astype(np.int32),
            cols=cols[sel].astype(np.int32),
            vals=vals[sel].astype(np.float32), shape=(batch_rows, items)))
    return out


def stacked(batches) -> tuple:
    """(rows, cols, vals, shape) of the batches stacked in order."""
    off = np.cumsum([0] + [b.shape[0] for b in batches[:-1]])
    rows = np.concatenate([b.rows.astype(np.int64) + o
                           for b, o in zip(batches, off)])
    cols = np.concatenate([b.cols for b in batches]).astype(np.int64)
    vals = np.concatenate([b.vals for b in batches]).astype(np.float64)
    return rows, cols, vals, (int(sum(b.shape[0] for b in batches)),
                              batches[0].shape[1])


# ---------------------------------------------------------------------------
# Phases: each runs through the front door and returns its errors
# ---------------------------------------------------------------------------

def _plan_line(plan) -> dict:
    return {"backend": plan.backend, "strategy": plan.strategy,
            "rank": plan.rank, "window": plan.window,
            "degraded": any("degrad" in r or "NO " in r or "EXCEEDS" in r
                            for r in plan.reasons)}


def repaired_dense(coo: sparse.COOMatrix, config: api.SolveConfig,
                   num_blocks: int) -> np.ndarray:
    """The matrix the solver factors, float64 on the host: the input
    plus the checker's one-entry-per-lonely-row repairs (drawn with the
    solve's own key, as benchmarks/paper_tables.py does)."""
    ell = sparse.block_ell_from_coo(coo, num_blocks)
    rep = ranky.split_and_repair(ell, num_blocks, config.method,
                                 config.resolved_key())
    a = np.zeros((coo.shape[0], num_blocks * ell.width), np.float64)
    np.add.at(a, (coo.rows, coo.cols), coo.vals.astype(np.float64))
    d_idx, r_idx = np.nonzero(np.asarray(rep.repair_mask))
    cols = d_idx * ell.width + np.asarray(rep.repair_cols)[d_idx, r_idx]
    a[r_idx, cols] += 1.0
    return a[:, :coo.shape[1]]


def oneshot_phase(coo: sparse.COOMatrix, config: api.SolveConfig,
                  reference=None) -> dict:
    """One ``api.svd`` solve of ``coo`` (want_right) against numpy's
    float64 SVD of the same repaired matrix.  ``reference`` is a previous
    run's ``out["reference"]`` when the repair is the same."""
    res = api.svd(coo, config)
    if reference is None:
        a = repaired_dense(coo, config, res.plan.num_blocks)
        reference = (a, *np.linalg.svd(a, full_matrices=False)[:2])
    a, u_ref, s_ref = reference
    u = np.asarray(res.u, np.float64)
    s = np.asarray(res.s, np.float64)
    v = np.asarray(res.v, np.float64)
    r = s_ref.shape[0]
    u, s, v = u[:, :r], s[:r], v[:, :r]
    signs = np.sign(np.sum(u * u_ref, axis=0))
    signs[signs == 0] = 1.0
    recon = (u * s[None, :]) @ v.T
    return {
        "plan": _plan_line(res.plan),
        "compile_s": res.diagnostics.compile_time_s,
        "run_s": res.diagnostics.run_time_s,
        "repaired_rows": res.diagnostics.repaired_rows,
        "e_sigma": float(np.abs(s - s_ref).sum()),
        "e_u": float(np.abs(u * signs[None, :] - u_ref).sum()),
        "sv_rel_max": float(np.max(np.abs(s - s_ref) / s_ref)),
        "errors": {
            "gram": float(np.max(np.abs(s ** 2 - s_ref ** 2)) / s_ref[0] ** 2),
            "recon": float(np.linalg.norm(recon - a) / np.linalg.norm(a)),
            "orth": float(np.max(np.abs(u.T @ u - np.eye(r)))),
        },
        "result": res,
        "reference": reference,
    }


def _subspace_sin(a: np.ndarray, b: np.ndarray) -> float:
    """sin of the largest principal angle between span(a) and span(b)."""
    qa, _ = np.linalg.qr(a)
    qb, _ = np.linalg.qr(b)
    cos = np.linalg.svd(qa.T @ qb, compute_uv=False)
    return float(np.sqrt(max(0.0, 1.0 - float(np.min(cos)) ** 2)))


def stream_reference(batches, k: int):
    """Float64 top-k (s, v) of the stacked rows (ARPACK on the host)."""
    from scipy.sparse import csr_matrix
    from scipy.sparse.linalg import svds

    rows, cols, vals, shape = stacked(batches)
    a = csr_matrix((vals, (rows, cols)), shape=shape)
    v0 = np.random.default_rng(0).standard_normal(min(shape))
    _, s, vt = svds(a, k=k, tol=0, v0=v0)
    order = np.argsort(s)[::-1]
    return s[order], vt[order].T


def stream_phase(batches, config: api.SolveConfig, reference=None) -> dict:
    """``api.svd_stream`` over the batches against the float64 top-k
    SVD of the stacked rows: the top-k singular values and the right
    subspace.  ``reference`` is a previous run's ``out["reference"]``."""
    res = api.svd_stream(batches, config)
    if reference is None:
        reference = stream_reference(batches, config.truncate_rank)
    s_ref, v_ref = reference
    s = np.asarray(res.s, np.float64)
    v = np.asarray(res.state.trimmed_v(), np.float64)
    return {
        "plan": _plan_line(res.plan),
        "compile_s": res.diagnostics.compile_time_s,
        "run_s": res.diagnostics.run_time_s,
        "repaired_rows": res.diagnostics.repaired_rows,
        "errors": {
            "sv_rel": float(np.max(np.abs(s - s_ref) / s_ref)),
            "v_sin": _subspace_sin(v, v_ref),
        },
        "result": res,
        "reference": reference,
    }


def serve_phase(state, config: api.ServeTopKConfig, *, waves: int,
                seed: int) -> dict:
    """``waves`` request waves of known-user queries through
    ``serve_topk`` against a float64 top-k over the served factors,
    tie-aware: every item whose reference score beats the k-th by more
    than the tolerance must be returned, and every returned score must
    match its reference score within it.  Also reports whether the
    compiled kernel equals the ``kernels/ref.py`` oracle bit for bit."""
    clock.install_compile_probe()
    c0, t0 = clock.compile_seconds(), clock.now()
    handle = api.serve_init(state, dataclasses.replace(config, keep_u=True))
    snap = handle.read()
    rng = np.random.default_rng(seed)
    got = []
    for _ in range(waves):
        ids = rng.integers(0, state.rows_seen, config.batch_size)
        q = ranker.user_queries(snap, ids)
        res = api.serve_topk(handle, q)
        got.append((q, res))
    jax.block_until_ready([r.indices for _, r in got])
    wall = clock.now() - t0
    comp = min(wall, clock.compile_seconds() - c0)

    factors, scale = ranker._factor_pair(snap)
    f64 = np.asarray(factors, np.float64)
    if scale is not None:
        f64 = f64 * np.asarray(scale, np.float64)[:, None]
    f64 = f64[:snap.n]
    s = np.asarray(snap.s, np.float64)
    worst, bitwise = 0.0, True
    for q, res in got:
        qs = np.asarray(q, np.float64) * s[None, :]
        ref_scores = qs @ f64.T                                 # (B, n)
        tol = SERVE_TOL * (np.abs(qs) @ np.abs(f64).T).max(axis=1)
        idx = np.asarray(res.indices)
        vals = np.asarray(res.scores, np.float64)
        kth = np.sort(ref_scores, axis=1)[:, -config.k_top]
        for b in range(idx.shape[0]):
            must = np.nonzero(ref_scores[b] > kth[b] + tol[b])[0]
            if not set(must.tolist()) <= set(idx[b].tolist()):
                raise AssertionError(
                    f"serve: query {b} misses items {sorted(set(must) - set(idx[b]))}"
                    f" that beat the k-th reference score by more than "
                    f"the tolerance")
            dev = np.abs(vals[b] - ref_scores[b, idx[b]]) / tol[b]
            worst = max(worst, float(dev.max()))
        want = kref.topk_score(ranker.fold_queries(snap, q), factors,
                               config.k_top, scale=scale, valid_n=snap.n)
        bitwise &= bool(np.array_equal(np.asarray(want[0]),
                                       np.asarray(res.scores))
                        and np.array_equal(np.asarray(want[1]),
                                           np.asarray(res.indices)))
    return {
        "plan": _plan_line(handle.plan),
        "compile_s": comp,
        "run_s": wall - comp,
        "errors": {"score_over_tol": worst},
        "bitwise_vs_ref": bitwise,
        "result": got,
    }


# ---------------------------------------------------------------------------
# Checks
# ---------------------------------------------------------------------------

def stream_bounds() -> dict:
    return {k: v + STREAM_SLACK for k, v in STREAM_CPU.items()}


def check(name: str, out: dict, bounds: dict, *, backend: str,
          strategy: str) -> None:
    """Raise unless the plan is what the phase asked for (no degrade)
    and every error is within its bound."""
    plan = out["plan"]
    if plan["degraded"] or plan["backend"] != backend \
            or plan["strategy"] != strategy:
        raise AssertionError(
            f"{name}: plan {plan} is not the requested "
            f"backend={backend} strategy={strategy}")
    for key, bound in bounds.items():
        if not out["errors"][key] <= bound:
            raise AssertionError(
                f"{name}: {key} error {out['errors'][key]:.3e} exceeds "
                f"{bound:.3e}")


def peak_bytes():
    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def report(name: str, out: dict, bounds: dict) -> None:
    line = {k: v for k, v in out.items() if k not in ("result", "reference")}
    line["bounds"] = bounds
    line["peak_bytes_in_use"] = peak_bytes()
    line["elapsed_s"] = clock.now()          # since the process started
    print(f"{name}: {json.dumps(line, default=str)}", flush=True)


def lowered_text(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


def assert_kernels_lowered(shapes) -> None:
    """Each kernel path must compile to a ``tpu_custom_call`` at the
    widths the phases ran."""
    f32, i32, i8 = jnp.float32, jnp.int32, jnp.int8
    sds = jax.ShapeDtypeStruct
    cases = {}
    for m, c, k in shapes["sparse_gram"]:
        cases[f"sparse_gram M={m}"] = (
            lambda r, v, m=m: ops.sparse_gram(r, v, m),
            sds((c, k), i32), sds((c, k), f32))
    b, r, n, k_top = shapes["topk_score"]
    cases["topk_score f32"] = (lambda q, v: ops.topk_score(q, v, k_top),
                               sds((b, r), f32), sds((n, r), f32))
    cases["topk_score int8"] = (
        lambda q, v, s: ops.topk_score(q, v, k_top, scale=s),
        sds((b, r), f32), sds((n, r), i8), sds((n,), f32))
    for name, (fn, *args) in cases.items():
        if "tpu_custom_call" not in lowered_text(fn, *args):
            raise AssertionError(f"{name} did not lower to a Pallas kernel")
        print(f"kernel {name}: tpu_custom_call present", flush=True)


def _max_diff(a, b) -> float:
    return float(np.max(np.abs(np.asarray(a, np.float64)
                               - np.asarray(b, np.float64))))


# ---------------------------------------------------------------------------
# Entry point
# ---------------------------------------------------------------------------

def one_chip() -> None:
    coo = paper_matrix()
    cfg = api.SolveConfig(method="neighbor_random", num_blocks=8,
                          want_right=True)
    ell = sparse.block_ell_from_coo(coo, 8)
    ref = None
    for use_kernel in (True, False):
        out = oneshot_phase(coo, dataclasses.replace(cfg,
                                                     use_kernel=use_kernel),
                            ref)
        ref = out["reference"]
        report(f"oneshot use_kernel={use_kernel}", out, ONESHOT_TOL)
        check("oneshot", out, ONESHOT_TOL, backend="single",
              strategy="exact_gram")

    batches = rating_rows(num_blocks=8, **STREAM_SHAPE)
    scfg = api.SolveConfig(method="neighbor_random", num_blocks=8,
                           truncate_rank=STREAM_RANK, use_kernel=True,
                           stream_backend="single")
    out = stream_phase(batches, scfg)
    report("stream", out, stream_bounds())
    check("stream", out, stream_bounds(), backend="single",
          strategy="streaming")
    if out["plan"]["rank"] is not None or out["plan"]["window"] < 2:
        raise AssertionError(f"stream: plan {out['plan']} is not the exact "
                             f"batch gram under a scan window")
    state = out["result"].state

    serve_cfg = api.ServeTopKConfig(batch_size=SERVE_SHAPE["batch"],
                                    k_top=SERVE_SHAPE["k_top"],
                                    use_kernel=True, serve_backend="single")
    for quantize in (False, True):
        out = serve_phase(state, dataclasses.replace(serve_cfg,
                                                     quantize=quantize),
                          waves=SERVE_SHAPE["waves"],
                          seed=SERVE_SHAPE["seed"])
        report(f"serve quantize={quantize}", out, {"score_over_tol": 1.0})
        check("serve", out, {"score_over_tol": 1.0}, backend="single",
              strategy="serve_fused")

    c_cap, k_cap = ell.capacity
    stream_ell = sparse.block_ell_from_coo(batches[0], 8)
    assert_kernels_lowered({
        "sparse_gram": [(coo.shape[0], c_cap, k_cap),
                        (STREAM_SHAPE["batch_rows"], *stream_ell.capacity)],
        "topk_score": (SERVE_SHAPE["batch"], STREAM_RANK,
                       state.n_pad, SERVE_SHAPE["k_top"])})


def four_chips(coo: sparse.COOMatrix, batches, *, d: int = 4,
               rank: int = STREAM_RANK, serve: dict = SERVE_SHAPE) -> None:
    """The shard_map one-shot, stream and ranker on ``d`` devices, each
    beside the same config on the single-device engine."""
    cfg = api.SolveConfig(method="neighbor_random", num_blocks=d,
                          want_right=True, use_kernel=True)
    outs, ref = {}, None
    for backend in ("shard_map", "single"):
        out = oneshot_phase(coo, dataclasses.replace(cfg, backend=backend),
                            ref)
        ref = out["reference"]
        report(f"oneshot backend={backend}", out, ONESHOT_TOL)
        check("oneshot", out, ONESHOT_TOL, backend=backend,
              strategy="exact_gram")
        outs[backend] = out["result"]
    sh, one = outs["shard_map"], outs["single"]
    print("oneshot shard_map vs single: " + json.dumps({
        "s_max_diff": _max_diff(sh.s, one.s),
        "u_max_diff": _max_diff(sh.u, one.u),
        "bitwise": bool(np.array_equal(np.asarray(sh.s), np.asarray(one.s))
                        and np.array_equal(np.asarray(sh.u),
                                           np.asarray(one.u)))}), flush=True)

    scfg = api.SolveConfig(method="neighbor_random", num_blocks=d,
                           truncate_rank=rank, use_kernel=True)
    outs, ref = {}, None
    # The single-device engine first: its error against the reference
    # (the truncation error by design) bounds the sharded engine's.
    for backend in ("single", "shard_map"):
        out = stream_phase(batches, dataclasses.replace(
            scfg, stream_backend=backend), ref)
        ref = out["reference"]
        bounds = ({k: v + STREAM_SLACK
                   for k, v in outs["single"]["errors"].items()}
                  if outs else {})
        report(f"stream backend={backend}", out, bounds)
        check("stream", out, bounds, backend=backend, strategy="streaming")
        outs[backend] = out
    sh, one = (outs[b]["result"].state for b in ("shard_map", "single"))
    devs = len(sh.v.sharding.device_set)
    if devs != d:
        raise AssertionError(f"stream: sharded v spans {devs} devices, "
                             f"not {d}")
    print("stream shard_map vs single: " + json.dumps({
        "v_devices": devs,
        "s_max_diff": _max_diff(sh.s, one.s),
        "v_sin": _subspace_sin(np.asarray(sh.v, np.float64),
                               np.asarray(one.v, np.float64)),
        "bitwise": bool(np.array_equal(np.asarray(sh.s), np.asarray(one.s))
                        and np.array_equal(np.asarray(sh.v),
                                           np.asarray(one.v)))}), flush=True)

    serve_cfg = api.ServeTopKConfig(batch_size=serve["batch"],
                                    k_top=serve["k_top"], use_kernel=True)
    got = {}
    for backend, st in (("shard_map", sh),
                        ("single", stream_state.gather_state(sh))):
        out = serve_phase(st, dataclasses.replace(serve_cfg,
                                                  serve_backend=backend),
                          waves=serve["waves"], seed=serve["seed"])
        report(f"serve backend={backend}", out, {"score_over_tol": 1.0})
        check("serve", out, {"score_over_tol": 1.0}, backend=backend,
              strategy="serve_fused")
        got[backend] = out["result"]
    pairs = list(zip(got["shard_map"], got["single"]))
    print("serve shard_map vs single: " + json.dumps({
        "score_max_diff": max(_max_diff(a.scores, b.scores)
                              for (_, a), (_, b) in pairs),
        "bitwise": all(np.array_equal(np.asarray(a.indices),
                                      np.asarray(b.indices))
                       and np.array_equal(np.asarray(a.scores),
                                          np.asarray(b.scores))
                       for (_, a), (_, b) in pairs)}), flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    mode = os.environ.get("REPRO_KERNELS")
    if mode not in (None, "pallas"):
        raise SystemExit(f"REPRO_KERNELS={mode!r}: the chip check runs the "
                         f"compiled kernels only (unset it or set 'pallas')")
    if jax.default_backend() != "tpu":
        raise SystemExit(f"no TPU: JAX's default backend is "
                         f"{jax.default_backend()!r}")
    cache_dir = compile_cache.enable()
    if len(jax.devices()) < args.chips:
        raise SystemExit(f"--chips {args.chips} but JAX sees "
                         f"{len(jax.devices())} device(s)")
    if args.chips == 4:
        four_chips(paper_matrix(), rating_rows(num_blocks=4, **STREAM_SHAPE))
    else:
        one_chip()
    print("compile cache: " + json.dumps(compile_cache.usage(cache_dir)),
          flush=True)
    dev = jax.devices()[0]
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
