"""The paper's job x candidate matrix: data from the seed, and the plain
float64 reference that decides whether a one-shot solve is correct.

Nothing here imports the program.  The repair is a random choice by
design (the checker draws one column per lonely row and block), so the
reference does not redraw it: it reads the repaired matrix off the
solve's own output and holds it to the checker's rules, then factors it
in float64.

* Every (row, block) pair with no entry in the block (a lonely pair)
  must show exactly one unit entry in that block of ``U diag(s) V^T - A``
  and no other pair may show one.  Where a neighbour of the row has an
  entry in the block, the unit entry must sit at one of those columns.
  A repair drawn into the last block's zero padding (columns past N) is
  invisible in ``V``, which is trimmed to N columns; it shows in
  ``U diag(s^2) U^T - Ahat Ahat^T`` as a unit diagonal entry (and a unit
  off-diagonal one for two rows that drew the same padding column).
* ``gram``: max |s_i^2 - s_ref_i^2| / s_ref_1^2 against the float64
  eigenvalues of the repaired gram — the gram + eigh path's error.
* ``recon``: ||U diag(s) V^T - Ahat||_F / ||Ahat||_F over the N columns.
"""
from __future__ import annotations

import numpy as np
import scipy.sparse as sp


def _template(config: dict):
    """The pattern every seed shares, drawn from ``template_seed``:
    (rows, cols) of weighted edges with Pareto row popularity and uniform
    columns, duplicates dropped, every global row non-empty."""
    m, n = int(config["rows"]), int(config["cols"])
    rng = np.random.default_rng(int(config["template_seed"]))
    nnz = max(1, int(round(m * n * float(config["density"]))))
    row_p = rng.pareto(1.5, size=m) + 1.0
    row_p /= row_p.sum()
    rows = rng.choice(m, size=nnz, p=row_p).astype(np.int64)
    cols = rng.integers(0, n, size=nnz).astype(np.int64)
    _, idx = np.unique(rows * n + cols, return_index=True)
    rows, cols = rows[idx], cols[idx]
    empty = np.setdiff1d(np.arange(m), rows)
    extra = [rng.choice(n, size=2, replace=False) for _ in empty]
    rows = np.concatenate([rows, np.repeat(empty, 2)])
    cols = np.concatenate([cols, np.asarray(extra, np.int64).reshape(-1)])
    return rows, cols


def generate(config: dict, seed: int, num_blocks: int):
    """(rows, cols, vals, shape) for ``seed``: the shared pattern with its
    rows relabelled, its columns relabelled inside each of the
    ``num_blocks`` column blocks, and fresh values.  Every seed thus
    gives the same sizes (entries, stored columns and column degrees per
    block, lonely rows), so every seed runs the same compiled programs."""
    m, n = int(config["rows"]), int(config["cols"])
    rows, cols = _template(config)
    rng = np.random.default_rng(seed)
    w = _block_width(n, num_blocks)
    relabel = np.concatenate([
        lo + rng.permutation(min(w, n - lo)) for lo in range(0, n, w)])
    rows = rng.permutation(m)[rows]
    cols = relabel[cols]
    vals = rng.uniform(0.5, 2.0, size=rows.shape[0])
    return (rows.astype(np.int32), cols.astype(np.int32),
            vals.astype(np.float32), (m, n))


def _csr(data) -> sp.csr_matrix:
    rows, cols, vals, shape = data
    return sp.csr_matrix((np.asarray(vals, np.float64), (rows, cols)),
                         shape=shape)


def _block_width(n: int, d: int) -> int:
    return -(-n // d)


def _lonely(data, d: int) -> np.ndarray:
    """(M, D) mask of the (row, block) pairs with no entry."""
    rows, cols, _, (m, n) = data
    present = np.zeros((m, d), bool)
    present[rows, np.asarray(cols) // _block_width(n, d)] = True
    return ~present


def _neighbor_cols(a: sp.csr_matrix) -> sp.csr_matrix:
    """(M, N) pattern: columns where some graph neighbour of the row (a
    row sharing a column with it) has an entry."""
    b = (a != 0).astype(np.float64)
    adj = (b @ b.T).tocsr()
    adj.setdiag(0)
    adj.eliminate_zeros()
    adj.data[:] = 1.0
    return (adj @ b).tocsr()


def oneshot_reference(data, num_blocks: int, u, s, v) -> dict:
    """The numbers compared for one solve (see the module docstring);
    ``u`` (M, r), ``s`` (r,), ``v`` (N, r) are what the solve returned."""
    a = _csr(data)
    m, n = a.shape
    d, w = num_blocks, _block_width(n, num_blocks)
    u = np.asarray(u, np.float64)
    s = np.asarray(s, np.float64)
    v = np.asarray(v, np.float64)

    resid = np.zeros((m, d * w))
    resid[:, :n] = (u * s) @ v.T
    coo = a.tocoo()
    np.subtract.at(resid, (coo.row, coo.col), coo.data)
    blocks = resid.reshape(m, d, w)
    best = blocks.argmax(axis=2)                               # (M, D)
    top = np.take_along_axis(blocks, best[:, :, None], 2)[:, :, 0]

    lonely = _lonely(data, d)
    shown = top > 0.5
    faults = int(np.sum(shown & ~lonely))      # a unit entry in a full pair
    real = lonely & shown
    hidden = lonely & ~shown
    if n < d * w:
        hidden_rows = np.nonzero(hidden[:, d - 1])[0]
        faults += int(np.sum(hidden[:, :d - 1]))
    else:
        hidden_rows = np.zeros(0, np.int64)
        faults += int(np.sum(hidden))

    ri, rd = np.nonzero(real)
    rc = rd * w + best[ri, rd]
    nb = _neighbor_cols(a)
    for i, blk, c in zip(ri, rd, rc):
        row = nb.indices[nb.indptr[i]:nb.indptr[i + 1]]
        cand = row[(row >= blk * w) & (row < (blk + 1) * w)]
        if cand.size and c not in cand:
            faults += 1                       # not a neighbour's column
    fix = sp.csr_matrix((np.ones(ri.size), (ri, rc)), shape=(m, n))
    ahat = (a + fix).tocsr()
    blocks[ri, rd, best[ri, rd]] -= 1.0
    recon = float(np.linalg.norm(resid) / sp.linalg.norm(ahat))

    gram = (ahat @ ahat.T).toarray()
    pad = (u * s ** 2) @ u.T - gram         # padding repairs, if any
    pad_int = np.rint(pad)
    expect = np.zeros(m)
    expect[hidden_rows] = 1.0
    off = pad_int - np.diag(np.diag(pad_int))
    outside = np.ones(m, bool)
    outside[hidden_rows] = False
    if (not np.array_equal(np.diag(pad_int), expect)
            or np.any((off != 0) & (off != 1))
            or np.any(off[outside]) or np.any(off[:, outside])):
        faults += 1
    s_ref2 = np.sort(np.linalg.eigvalsh(gram + pad_int))[::-1]
    r = min(s.size, s_ref2.size)
    return {
        "repair": float(faults),
        "gram": float(np.max(np.abs(s[:r] ** 2 - s_ref2[:r])) / s_ref2[0]),
        "recon": recon,
    }


def repair(data, num_blocks: int, seed: int):
    """A NeighborRandomChecker repair of ``data`` drawn with numpy: the
    data with one unit entry added per lonely pair."""
    a = _csr(data)
    m, n = a.shape
    w = _block_width(n, num_blocks)
    rng = np.random.default_rng(seed)
    nb = _neighbor_cols(a)
    ri, rd = np.nonzero(_lonely(data, num_blocks))
    rc = []
    for i, blk in zip(ri, rd):
        row = nb.indices[nb.indptr[i]:nb.indptr[i + 1]]
        cand = row[(row >= blk * w) & (row < min((blk + 1) * w, n))]
        lo, hi = blk * w, min((blk + 1) * w, n)
        rc.append(rng.choice(cand) if cand.size else rng.integers(lo, hi))
    rows, cols, vals, shape = data
    return (np.concatenate([rows, ri]).astype(np.int32),
            np.concatenate([cols, np.asarray(rc, np.int64)]).astype(np.int32),
            np.concatenate([vals, np.ones(ri.size)]).astype(np.float32),
            shape)


def oneshot_solve(data, num_blocks: int, seed: int, matmul):
    """The plain computation in the program's place: repair, gram, eigh,
    right vectors, with every product taken by ``matmul`` in float32."""
    a = _csr(repair(data, num_blocks, seed)).astype(np.float32)
    g = np.asarray(matmul(a, a.T.tocsr()), np.float32)
    evals, evecs = np.linalg.eigh(g)
    order = np.argsort(evals)[::-1]
    s = np.sqrt(np.clip(evals[order], 0.0, None)).astype(np.float32)
    u = evecs[:, order].astype(np.float32)
    inv = np.where(s > 0, 1.0 / np.where(s > 0, s, 1.0), 0.0)
    v = np.asarray(matmul(a.T.tocsr(), u), np.float32) * inv[None, :]
    return u, s, v
