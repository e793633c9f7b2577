"""A MovieLens-25M-shaped user x movie stream: data from the seed, and
the plain float64 references for folding it in and serving from it.

Nothing here imports the program.

* :func:`fold` is the stream's semantics, from the empty state or any
  other: each batch B of new rows (no row lonely in any block, so no
  repair) gives U_b, the top ``k + oversample`` eigenvectors of B B^T;
  the panel P = [V diag(s) | B^T U_b] is factored P = V' diag(s') W^T
  and cut to rank k; the left factor becomes [U W_old ; U_b W_new].
* :func:`state_numbers` compares two rank-k states X = U diag(s) V^T by
  ||X - X_ref||_F / ||X_ref||_F, formed from k x k products only
  (X itself has rows_seen x items entries); :func:`lead_numbers` does
  the same for their leading parts, cut where the reference's singular
  values fall by a tenth or more.
* :func:`topk_numbers` checks served top-k ids and scores against a
  float64 top-k over the served factors, tie-aware.
"""
from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np
import scipy.linalg as sla
import scipy.sparse as sp
from scipy.optimize import brentq

EPS32 = float(np.finfo(np.float32).eps)
# Half stars 0.5 .. 5.0, weighted toward 3-4 stars.
_RATINGS = np.arange(1, 11) / 2
_RATING_P = np.array([1, 3, 2, 7, 5, 20, 13, 27, 8, 14], np.float64) / 100


def _count_curve(n: int, anchors, mean: float) -> np.ndarray:
    """``n`` counts by rank (most first) through ``anchors`` [(rank,
    count)], log-linear in log(rank + q) between them, with q set so the
    counts average ``mean``."""
    ranks = np.array([a[0] for a in anchors], np.float64)
    logc = np.log(np.array([a[1] for a in anchors], np.float64))
    r = np.arange(1, n + 1, dtype=np.float64)

    def curve(q):
        return np.exp(np.interp(np.log(r + q), np.log(ranks + q),
                                logc))

    q = brentq(lambda q: curve(q).mean() - mean, 0.0, 1e9)
    return curve(q)


def _anchors(stats: dict, n: int, first: list):
    """Rank anchors of a count summary: ``first`` [(rank, count)] for the
    top ranks, then the quartiles and the minimum."""
    q1, q2, q3 = stats["quartiles"]
    return first + [(round(0.25 * n), q3), (round(0.5 * n), q2),
                    (round(0.75 * n), q1), (n, stats["min"])]


def _lambdas(levels: np.ndarray, w: np.ndarray) -> np.ndarray:
    """lambda per expected count in ``levels``: sum_i 1 - exp(-lambda w_i)
    = level, by interpolation on a log grid."""
    grid = np.exp(np.linspace(0.0, np.log(1e13), 600))
    g = np.array([np.sum(-np.expm1(-x * w)) for x in grid])
    return np.exp(np.interp(levels, g, np.log(grid)))


def _expected(levels, counts, lam, w) -> np.ndarray:
    """Expected ratings of each movie: sum over levels of count *
    (1 - exp(-lambda w)), in float32."""
    w32 = w.astype(np.float32)[None, :]
    out = np.zeros(w.size, np.float64)
    for lo in range(0, levels.size, 128):
        lm = lam[lo:lo + 128, None].astype(np.float32)
        out += counts[lo:lo + 128].astype(np.float32) @ -np.expm1(-lm * w32)
    return out


def _template(config: dict, num_blocks: int):
    """The pattern every seed shares, drawn from ``template_seed``:
    sorted (rows, cols) of every user's ratings.

    Per-movie and per-user counts follow the source's count summaries
    (``movie_ratings``, ``user_ratings``; see ``_count_curve``), movie
    targets scaled to this many users.  User u rates movie i once with
    probability 1 - exp(-lambda_u w_i), lambda_u giving the user's
    expected count and w calibrated so each movie's expected count is its
    target.  A user with no rating in a column block gets one there, a
    movie of the block drawn by w, so no row is lonely; a user left below
    the source's floor gets movies drawn by w up to it."""
    items, users = int(config["items"]), int(config["users"])
    src_users = int(config["users_in_source"])
    total = float(config["ratings_in_source"])
    mv, us = config["movie_ratings"], config["user_ratings"]
    rated = int(mv["rated"])
    target = _count_curve(rated, _anchors(mv, rated, [(1, mv["max"]),
                                                      (5, mv["fifth"])]),
                          total / rated) * users / src_users
    per_src = _count_curve(src_users, _anchors(us, src_users,
                                               [(1, us["max"])]),
                           total / src_users)
    n_u = np.rint(per_src[((np.arange(users) + 0.5) * src_users
                           / users).astype(np.int64)])
    levels, level_of, counts = np.unique(n_u, return_inverse=True,
                                         return_counts=True)
    # The calibration takes the levels in 256 groups, log-spaced.
    grp = np.minimum((np.log(levels / levels[0]) / np.log(
        levels[-1] / levels[0] + 1e-9) * 256).astype(np.int64), 255)
    g_count = np.bincount(grp, weights=counts)
    keep = g_count > 0
    g_level = np.bincount(grp, weights=counts * levels)[keep] / g_count[keep]
    g_count = g_count[keep]
    w = target / target.sum()
    for _ in range(int(config["calibration_steps"])):
        w *= target / _expected(g_level, g_count, _lambdas(g_level, w), w)
        w /= w.sum()
    lam = _lambdas(levels.astype(np.float64), w)[level_of]

    rng = np.random.default_rng(int(config["template_seed"]))
    movie = np.sort(rng.permutation(items)[:rated])[rng.permutation(rated)]
    order = np.argsort(-w, kind="stable")
    w_s = w[order]
    cum = np.concatenate([[0.0], np.cumsum(w_s)])
    user_row = rng.permutation(users)
    # Movies with lambda_u w_i > 36 are rated for certain (a miss has
    # probability below 1e-15); the rest by Poisson draws, counted once.
    head = np.searchsorted(-w_s, -36.0 / lam, side="left")
    h_rows = np.repeat(user_row, head)
    h_rank = np.arange(head.sum()) - np.repeat(np.cumsum(head) - head, head)
    n_draw = rng.poisson(lam * (cum[-1] - cum[head]))
    t_user = np.repeat(np.arange(users), n_draw)
    # Each user's draws as sorted uniforms (normalised sums of
    # exponential gaps), so the search below walks forward.
    gaps = rng.exponential(size=t_user.size + users)
    ends = np.cumsum(n_draw + 1)
    run = np.cumsum(gaps)
    base = np.concatenate([[0.0], run[ends[:-1] - 1]])
    span = run[ends - 1] - base
    pos = np.delete(run, ends - 1) - base[t_user]
    lo = cum[head][t_user]
    t_rank = np.minimum(np.searchsorted(
        cum, lo + pos / span[t_user] * (cum[-1] - lo), side="right") - 1,
        rated - 1)
    rows = np.concatenate([h_rows, user_row[t_user]]).astype(np.int64)
    cols = movie[order[np.concatenate([h_rank, t_rank])]].astype(np.int64)

    wb = -(-items // num_blocks)
    hit = np.zeros((users, num_blocks), bool)
    hit[rows, cols // wb] = True
    miss_r, miss_d = np.nonzero(~hit)
    by_col = np.argsort(movie)
    col_sorted, w_col = movie[by_col], w[by_col]
    cum_col = np.concatenate([[0.0], np.cumsum(w_col)])
    first = np.searchsorted(col_sorted, np.arange(num_blocks + 1) * wb)
    a, b = cum_col[first[miss_d]], cum_col[first[miss_d + 1]]
    pick = np.searchsorted(cum_col, a + rng.random(miss_d.size) * (b - a),
                           side="right") - 1
    fill = col_sorted[np.clip(pick, first[miss_d], first[miss_d + 1] - 1)]
    key = np.unique(np.concatenate([rows * items + cols,
                                    miss_r * items + fill]))
    # Every user of the source rated at least ``min``: a user the draws
    # left short gets more movies drawn by w.
    short = np.maximum(int(us["min"]) - np.bincount(key // items,
                                                    minlength=users), 0)
    s_row = np.repeat(np.arange(users), 4 * short)
    cand = s_row * items + movie[order[np.minimum(np.searchsorted(
        cum, rng.random(s_row.size) * cum[-1], side="right") - 1,
        rated - 1)]]
    cand, first = np.unique(cand, return_index=True)
    cand = cand[np.argsort(first, kind="stable")]
    cand = cand[~np.isin(cand, key)]
    c_row = cand // items
    rank = np.arange(cand.size) - np.searchsorted(np.sort(c_row), c_row)
    key = np.union1d(key, cand[rank < short[c_row]])
    return key // items, key % items


def generate(config: dict, seed: int, num_blocks: int):
    """All ``users / batch_rows`` batches as (rows, cols, vals, shape) for
    ``seed``: the shared pattern with its users relabelled inside each
    batch, its items relabelled inside each column block and fresh
    ratings.  Every seed thus gives every batch the same sizes (entries,
    stored columns and column degrees per block), so every seed runs the
    same compiled programs."""
    items, users = int(config["items"]), int(config["users"])
    batch = int(config["batch_rows"])
    rows, cols = _template(config, num_blocks)
    rng = np.random.default_rng(seed)
    w = -(-items // num_blocks)
    relabel = np.concatenate([
        lo + rng.permutation(min(w, items - lo)) for lo in range(0, items, w)])
    cols = relabel[cols]
    within = np.concatenate([rng.permutation(batch) for _ in
                             range(users // batch)])
    rows = rows - rows % batch + within[rows]
    vals = rng.choice(_RATINGS, size=rows.size, p=_RATING_P)
    # Relabelling inside a batch keeps the rows sorted by batch.
    bounds = np.searchsorted(rows // batch, np.arange(users // batch + 1))
    return [((rows[a:b] - i * batch).astype(np.int32),
             cols[a:b].astype(np.int32), vals[a:b].astype(np.float32),
             (batch, items))
            for i, (a, b) in enumerate(zip(bounds[:-1], bounds[1:]))]


def _left_vectors(batch, n: int, r: int, dt, mm):
    """(B, U_b): the batch as a sparse matrix of ``n`` columns and the top
    ``r`` eigenvectors of B B^T, largest first."""
    rows, cols, vals, (m_b, _) = batch
    b = sp.csr_matrix((np.asarray(vals, dt), (rows, cols)), shape=(m_b, n))
    bt = b.T.tocsr()
    g = mm(b, bt)
    g = np.asarray(g.toarray() if sp.issparse(g) else g, dt)
    r_b = min(m_b, r)
    _, evecs = sla.eigh(g, subset_by_index=[m_b - r_b, m_b - 1])
    return bt, np.ascontiguousarray(evecs[:, ::-1], dt)


def fold(u, s, v, batches, *, k: int, oversample: int, matmul=None):
    """Fold ``batches`` into the state (u, s, v) — ``v`` in padded column
    order (its rows may outnumber the items); an empty state is u (0, 0),
    s (0,), v (items, 0).  Float64 unless ``matmul`` is given, which then
    takes every product in float32.  The batches' eigenvectors, which
    depend on the batch alone, are found on a pool of threads."""
    dt = np.float64 if matmul is None else np.float32
    mm = matmul or (lambda a, b: a @ b)
    u, s, v = (np.asarray(x, dt) for x in (u, s, v))
    n = v.shape[0]
    with ThreadPoolExecutor(min(8, os.cpu_count() or 1)) as pool:
        parts = list(pool.map(
            lambda bt: _left_vectors(bt, n, k + oversample, dt, mm), batches))
    for bt, u_b in parts:
        p = np.concatenate([v * s[None, :], np.asarray(mm(bt, u_b), dt)],
                           axis=1)
        # P = V' diag(s') W^T through the eigenvectors of P^T P.
        evals, evecs = np.linalg.eigh(np.asarray(mm(p.T, p), dt))
        k_new, k_old = min(k, p.shape[1]), s.shape[0]
        w = np.ascontiguousarray(evecs[:, ::-1][:, :k_new], dt)
        s = np.sqrt(np.clip(evals[::-1][:k_new], 0.0, None)).astype(dt)
        v = np.asarray(mm(p, w), dt) / np.where(s > 0, s, 1.0)
        u = np.concatenate([mm(u, w[:k_old]), mm(u_b, w[k_old:])], axis=0)
    return u, s, v


def empty_state(items: int, dt=np.float64):
    """The state before any batch: no rows, rank 0."""
    return np.zeros((0, 0), dt), np.zeros(0, dt), np.zeros((items, 0), dt)


def state_numbers(u, s, v, ref) -> dict:
    """``state``: ||X - X_ref||_F / ||X_ref||_F of the rank-k products
    X = U diag(s) V^T; a state with fewer rows reads its missing rows as
    zero."""
    u_r, s_r, v_r = (np.asarray(x, np.float64) for x in ref)
    u, s, v = (np.asarray(x, np.float64) for x in (u, s, v))
    if u.shape[0] < u_r.shape[0]:
        u = np.concatenate([u, np.zeros((u_r.shape[0] - u.shape[0],
                                         u.shape[1]))])
    u = u[:u_r.shape[0]]
    n = min(v.shape[0], v_r.shape[0])
    v, v_r = v[:n], v_r[:n]

    def inner(u1, s1, v1, u2, s2, v2):
        return float(np.sum((s1[:, None] * (u1.T @ u2) * s2[None, :])
                            * (v1.T @ v2)))

    xx = inner(u, s, v, u, s, v)
    yy = inner(u_r, s_r, v_r, u_r, s_r, v_r)
    xy = inner(u, s, v, u_r, s_r, v_r)
    return {"state": float(np.sqrt(max(xx + yy - 2 * xy, 0.0) / yy))}


# The leading part of a state ends where the reference's singular values
# fall by this share or more.  Its product is then set by the data to
# within rounding over that gap, whereas the rank-k cut of every fold
# falls among nearly equal values (the 40th and 41st eigenvalues of a
# batch gram, the 32nd and 33rd singular values of a merged panel), where
# float32 rounding can keep the other of two directions.
LEAD_GAP = 0.1


def lead_numbers(u, s, v, ref) -> dict:
    """``lead``: :func:`state_numbers` of the leading r singular triplets
    of both states, r the last place before which the reference's
    singular values fall by ``LEAD_GAP`` or more (all of them where they
    never do); ``lead_rank``: that r."""
    s_r = np.asarray(ref[1], np.float64)
    drop = np.nonzero((s_r[:-1] - s_r[1:]) >= LEAD_GAP * s_r[:-1])[0]
    r = int(drop[-1]) + 1 if drop.size else s_r.size
    cut = [(np.asarray(x)[:, :r], np.asarray(y)[:r], np.asarray(z)[:, :r])
           for x, y, z in ((u, s, v), ref)]
    return {"lead": state_numbers(*cut[0], cut[1])["state"],
            "lead_rank": r}


# Ties: an item must be returned when its float64 score beats the k-th
# best by more than this many float32 roundings of the query's scale.
TIE_EPS = 64


def topk_numbers(v, s, n: int, queries, ids, scores, k_top: int) -> dict:
    """``missed``: queries that lack an item whose float64 score beats
    the k-th by more than the tie margin, or return an id outside the
    universe; ``score``: the widest gap between a returned score and its
    float64 score, in float32 roundings of the query's scale
    max_j sum_i |q_i s_i v_ji|."""
    v = np.asarray(v, np.float64)[:n]
    qs_all = np.asarray(queries, np.float64) * np.asarray(s, np.float64)[None]
    ids = np.asarray(ids)
    scores = np.asarray(scores, np.float64)
    missed, worst = 0, 0.0
    for lo in range(0, qs_all.shape[0], 256):
        qs = qs_all[lo:lo + 256]
        ref = qs @ v.T                                          # (b, n)
        scale = (np.abs(qs) @ np.abs(v).T).max(axis=1) * EPS32  # (b,)
        kth = np.partition(ref, -k_top, axis=1)[:, -k_top]
        for b in range(qs.shape[0]):
            got = ids[lo + b]
            if np.any((got < 0) | (got >= n)):
                missed += 1
                continue
            must = np.nonzero(ref[b] > kth[b] + TIE_EPS * scale[b])[0]
            if not set(must.tolist()) <= set(got.tolist()):
                missed += 1
            worst = max(worst, float(np.max(
                np.abs(scores[lo + b] - ref[b, got]) / scale[b])))
    return {"missed": float(missed), "score": worst}


def topk_control(v, s, n: int, queries, k_top: int, matmul):
    """The top-k in the program's place, its scores taken by ``matmul``
    in float32: (ids, scores), ties to the lowest id."""
    qs = np.asarray(queries, np.float32) * np.asarray(s, np.float32)[None]
    sc = np.asarray(matmul(qs, np.asarray(v, np.float32)[:n].T), np.float32)
    ids = np.argsort(-sc, axis=1, kind="stable")[:, :k_top]
    return ids, np.take_along_axis(sc, ids, axis=1)
