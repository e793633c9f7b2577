"""Distributed randomized truncated rank-k SVD (the tall-row regime).

Every exact Ranky path recovers (U, S) through an M x M gram (or an
M x (D*M) proxy) plus a dense eigh/SVD — O(M^2 * nnz/M) compute and
O(M^3) factorization, which hard-caps the row dimension far below
production scale.  Following Li, Kluger & Tygert ("Randomized
algorithms for distributed computation of PCA and SVD"), this module
computes the top-k factorization from an (k+p)-row sketch instead:

  L = k + p (oversampled),  Omega ~ N(0, 1) of shape (L, M)
  G   = Omega @ A                      per column block, O(nnz * L)
  repeat q times (power iteration, re-orthonormalized):
      T = G @ A^T  (psum over blocks)  (L, M)
      Q = qr(T^T).Q                    (M, L) — the only M-sized QR
      G = Q^T @ A                      per column block
  T = G @ A^T (psum),  H = G @ G^T (psum, (L, L))
  whiten H (eigh, floor-masked)  ->  Vtilde = G^T @ W orthonormal
  B = A @ Vtilde = T^T @ W (M, L);  svd(B) -> top-k (U, S, V)

Nothing bigger than (L, M) is ever reduced across blocks and the only
dense factorizations are (M, L) QR/SVD and an (L, L) eigh — O(M * L^2)
total, so M can grow to hundreds of thousands of rows.  Because
G = Omega @ A sketches through A itself, every pass applies one extra
power of A A^T for free (q passes give spectral weight (q + 1)).

Per sparse block the contractions are gather/scatter index algebra over
the padded-ELL arrays — ``kernels.ops.sketch_panel`` for Omega @ E
(Pallas on TPU, O(nnz * L)) plus the <=1-entry-per-row repair side-band
terms — a block is never densified to (M, W).

Rank repair runs BEFORE sketching (the shared split_and_repair
prologue): a rank-deficient block leaves lonely rows with no weight in
the sketch, so the components repair would have created are truncated
away unrecoverably (see tests/test_randomized.py).

Drivers: ``ranky.ranky_svd(rank=k)`` (single host),
``hierarchy.hierarchical_ranky_svd(sketch=True)`` (truncated leaves for
the tree merge) and ``distributed.distributed_ranky_svd(rank=k)`` (the
same loop with psums over the mesh block axes inside shard_map).
"""
from __future__ import annotations

from typing import Callable, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.core import sparse
from repro.core.ranky import default_key
from repro.precision import mm

# Key fold tag for the test matrix: shared by the single-host and
# distributed drivers so both draw the identical Omega for a given key.
_SKETCH_TAG = 0x5EED


def sketch_width(rank: int, oversample: int, m: int) -> int:
    """L = min(rank + oversample, M), validating the requested rank."""
    if rank < 1 or rank > m:
        raise ValueError(f"rank={rank} must be in [1, M={m}]")
    if oversample < 0:
        raise ValueError(f"oversample={oversample} must be >= 0")
    return min(rank + oversample, m)


def draw_omega(key: jax.Array, l: int, m: int) -> jnp.ndarray:
    """(L, M) gaussian test matrix, identical for a given key across the
    single-host and distributed drivers (no device-index folding — Omega
    must be REPLICATED across the mesh)."""
    return jax.random.normal(jax.random.fold_in(key, _SKETCH_TAG),
                             (l, m), jnp.float32)


# ---------------------------------------------------------------------------
# Per-block contractions (dense twin is the oracle for the sparse one)
# ---------------------------------------------------------------------------

def sketch_block_dense(omega: jnp.ndarray, blk: jnp.ndarray) -> jnp.ndarray:
    """(L, M) @ (M, W) -> (L, W): the dense-twin sketch of one block."""
    return mm(omega, blk.astype(jnp.float32))


def pullback_block_dense(g: jnp.ndarray, blk: jnp.ndarray) -> jnp.ndarray:
    """(L, W) @ (W, M) -> (L, M): G_d @ B_d^T (summed over blocks by the
    caller — the psum in the distributed driver)."""
    return mm(g, blk.astype(jnp.float32).T)


def sketch_block_sparse(
    omega: jnp.ndarray,
    col_ids: jnp.ndarray,
    col_rows: jnp.ndarray,
    col_vals: jnp.ndarray,
    repair_cols: jnp.ndarray,
    repair_mask: jnp.ndarray,
    width: int,
) -> jnp.ndarray:
    """Sparse-native Omega @ (E + R) for one repaired block -> (L, W).

    E part: the (L, C) stored-column panel (kernels.ops.sketch_panel)
    scattered to local column ids.  R part: row r contributes
    omega[:, r] at column repair_cols[r] iff repair_mask[r].  Both are
    O(nnz * L); the (M, W) block is never materialized.
    """
    from repro.kernels import ops as kops

    l = omega.shape[0]
    panel = kops.sketch_panel(omega, col_rows, col_vals)       # (L, C)
    g = jnp.zeros((l, width), jnp.float32).at[:, col_ids].add(panel)
    rmask = repair_mask.astype(jnp.float32)
    return g.at[:, repair_cols].add(omega * rmask[None, :])


def pullback_block_sparse(
    g: jnp.ndarray,
    col_ids: jnp.ndarray,
    col_rows: jnp.ndarray,
    col_vals: jnp.ndarray,
    repair_cols: jnp.ndarray,
    repair_mask: jnp.ndarray,
    m: int,
) -> jnp.ndarray:
    """Sparse-native G_d @ (E + R)^T for one repaired block -> (L, M).

    E part: gather G at stored column ids ((L, C)), scatter-add through
    the ELL (row, value) slots.  R part: T[l, r] += mask_r * G[l, c_r].
    """
    l = g.shape[0]
    ge = jnp.take(g, col_ids, axis=1)                          # (L, C)
    t = jnp.zeros((l, m), jnp.float32).at[:, col_rows].add(
        ge[:, :, None] * col_vals.astype(jnp.float32)[None])
    rmask = repair_mask.astype(jnp.float32)
    return t + jnp.take(g, repair_cols, axis=1) * rmask[None, :]


# ---------------------------------------------------------------------------
# The (k+p)-sized tail factorization (shared by all drivers)
# ---------------------------------------------------------------------------

def truncate_sketch(
    t: jnp.ndarray, h: jnp.ndarray, rank: int
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Top-k factorization from the reduced sketch statistics.

    t = G @ A^T (L, M), h = G @ G^T (L, L) — both already summed (psum)
    over blocks.  Whitens the sketch rows through a floor-masked eigh of
    h (rank-deficient sketch directions are dropped, not inverted), so
    Vtilde = G^T @ w has orthonormal columns and B = A @ Vtilde = t^T @ w.
    Returns (U (M, k), S (k,), vproj (L, k)) where a block's slice of the
    right vectors is V_d = G_d^T @ vproj.
    """
    l = h.shape[0]
    evals, evecs = jnp.linalg.eigh(h)                 # ascending
    floor = jnp.finfo(h.dtype).eps * jnp.max(evals) * l
    good = evals > floor
    inv_sqrt = jnp.where(good,
                         1.0 / jnp.sqrt(jnp.where(good, evals, 1.0)), 0.0)
    w = evecs * inv_sqrt[None, :]                     # (L, L)
    b = mm(t.T, w)                                      # (M, L) = A @ Vtilde
    u_b, s, w_bt = jnp.linalg.svd(b, full_matrices=False)
    return u_b[:, :rank], s[:rank], mm(w, w_bt.T[:, :rank])


def _range_finder(
    sketch: Callable[[jnp.ndarray], jnp.ndarray],
    pullback: Callable[[jnp.ndarray], jnp.ndarray],
    omega: jnp.ndarray,
    power_iters: int,
):
    """The shared sketch loop: returns (G, T) after q re-orthonormalized
    power passes.  ``pullback`` must already include the cross-block
    reduction (sum on one host, psum on a mesh)."""
    g = sketch(omega)
    for _ in range(power_iters):
        t = pullback(g)                               # (L, M)
        q, _ = jnp.linalg.qr(t.T)                     # (M, L) orthonormal
        g = sketch(q.T)
    return g, pullback(g)


# ---------------------------------------------------------------------------
# Single-host driver (over a repaired block stack, either representation)
# ---------------------------------------------------------------------------

def randomized_svd_blocks(
    blocks,
    *,
    rank: int,
    oversample: int = 8,
    power_iters: int = 2,
    key: Optional[jax.Array] = None,
    want_right: bool = False,
):
    """Top-k (U, S[, V]) of a repaired block stack — dense (D, M, W)
    array or sparse.RepairedSparseBlocks (sparse-native, the dense stack
    is the oracle twin).  V, when requested, is (D*W, k) in padded
    column order (zero-pad columns carry zero rows)."""
    if key is None:
        key = default_key()

    if isinstance(blocks, sparse.RepairedSparseBlocks):
        ell = blocks.ell
        m, width = ell.m, ell.width

        def sketch(om):
            return jax.vmap(
                lambda i, r, v, rc, rm: sketch_block_sparse(
                    om, i, r, v, rc, rm, width)
            )(ell.col_ids, ell.col_rows, ell.col_vals,
              blocks.repair_cols, blocks.repair_mask)

        def pullback(g):
            per = jax.vmap(
                lambda gd, i, r, v, rc, rm: pullback_block_sparse(
                    gd, i, r, v, rc, rm, m)
            )(g, ell.col_ids, ell.col_rows, ell.col_vals,
              blocks.repair_cols, blocks.repair_mask)
            return per.sum(axis=0)
    else:
        m = blocks.shape[1]

        def sketch(om):
            return jnp.einsum("lm,dmw->dlw", om,
                              blocks.astype(jnp.float32))

        def pullback(g):
            return jnp.einsum("dlw,dmw->lm", g,
                              blocks.astype(jnp.float32))

    l = sketch_width(rank, oversample, m)
    omega = draw_omega(key, l, m)
    g, t = _range_finder(sketch, pullback, omega, power_iters)
    h = jnp.einsum("dlw,dkw->lk", g, g)
    u, s, vproj = truncate_sketch(t, h, rank)
    if not want_right:
        return u, s
    v = jnp.einsum("dlw,lk->dwk", g, vproj)           # (D, W, k)
    return u, s, v.reshape(-1, rank)


def block_truncated_panels(
    blocks,
    *,
    rank: int,
    oversample: int = 8,
    power_iters: int = 2,
    key: Optional[jax.Array] = None,
) -> jnp.ndarray:
    """(D, M, rank) truncated ``U_d S_d`` leaf panels via an independent
    per-block sketch — the randomized leaves that feed
    hierarchy.hierarchical_ranky_svd's tree merge in place of the
    O(M^3)-per-block gram+eigh leaves."""
    if key is None:
        key = default_key()

    def one_block(sketch1, pullback1, m):
        l = sketch_width(rank, oversample, m)
        omega = draw_omega(key, l, m)
        g, t = _range_finder(sketch1, pullback1, omega, power_iters)
        u, s, _ = truncate_sketch(t, mm(g, g.T), rank)
        return u * s[None, :]

    if isinstance(blocks, sparse.RepairedSparseBlocks):
        ell = blocks.ell
        m, width = ell.m, ell.width

        def leaf(ids, rows, vals, rc, rm):
            return one_block(
                lambda om: sketch_block_sparse(om, ids, rows, vals,
                                               rc, rm, width),
                lambda g: pullback_block_sparse(g, ids, rows, vals,
                                                rc, rm, m),
                m)

        return jax.vmap(leaf)(ell.col_ids, ell.col_rows, ell.col_vals,
                              blocks.repair_cols, blocks.repair_mask)

    m = blocks.shape[1]
    return jax.vmap(
        lambda blk: one_block(lambda om: sketch_block_dense(om, blk),
                              lambda g: pullback_block_dense(g, blk), m)
    )(blocks)


# ---------------------------------------------------------------------------
# Distributed tail (called inside core/distributed.py's shard_map region)
# ---------------------------------------------------------------------------

def randomized_tail_over(
    sketch: Callable[[jnp.ndarray], jnp.ndarray],
    pullback_local: Callable[[jnp.ndarray], jnp.ndarray],
    axes: Sequence[str],
    m: int,
    *,
    rank: int,
    oversample: int,
    power_iters: int,
    key: jax.Array,
    want_right: bool,
):
    """The sketch loop on a mesh: ``sketch``/``pullback_local`` act on
    this device's block only; the (L, M) pullback and (L, L) sketch gram
    are psummed over ``axes``.  Omega, the QRs and the tail eigh/SVD run
    replicated on every device (same collective pattern as the exact
    gram merge).  Returns (U, S) replicated, plus this device's V_blk
    (W, k) when ``want_right``."""
    axes = tuple(axes)
    l = sketch_width(rank, oversample, m)
    omega = draw_omega(key, l, m)

    def pullback(g):
        return jax.lax.psum(pullback_local(g), axes)

    g, t = _range_finder(sketch, pullback, omega, power_iters)
    h = jax.lax.psum(mm(g, g.T), axes)
    u, s, vproj = truncate_sketch(t, h, rank)
    if not want_right:
        return u, s
    return u, s, mm(g.T, vproj)
