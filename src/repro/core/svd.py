"""Local (per-block) SVD primitives, on either block representation.

Two interchangeable local factorizations of a short-and-fat block
``A_blk (M x N_b)``, both returning ``(U, S)`` with U: (M, M), S: (M,)
sorted descending:

* ``local_svd_gram``  — TPU-native: ``G = A A^T`` (M x M) via one big MXU
  matmul (optionally the Pallas blockgram kernel), then ``eigh(G)``.
  Cost: O(M^2 N) matmul + O(M^3) eigh.  This is the fast path; it squares
  the condition number, losing singular values below ~sqrt(eps)*smax.
* ``local_svd_exact`` — ``jnp.linalg.svd`` on the block (LAPACK-style,
  the paper's dgesvd analogue).  More accurate, slower on TPU.

The merge step needs only ``U @ diag(S)`` per block (the proxy panel).

Representation dispatch: ``gram_stack`` / ``local_svd_gram_stack``
accept either a dense (D, M, N_b) block stack or a
``sparse.RepairedSparseBlocks`` (the sparse-native path).  The sparse
gram is EXACT — ``sparse_gram_block`` expands
``G = (E + R)(E + R)^T = G_E + C + C^T + G_R`` where E is the immutable
ELL part (Pallas sparse_gram kernel or jnp oracle), R the <=1-entry-per-
row repair side-band, and the cross/repair terms are nnz-proportional
jnp contractions — a block is never densified to (M, N_b).
"""
from __future__ import annotations

from functools import partial
from typing import Tuple, Union

import jax
import jax.numpy as jnp

from repro.core import sparse
from repro.precision import mm


def gram(a_blk: jnp.ndarray, *, use_kernel: bool = False) -> jnp.ndarray:
    """G = A_blk @ A_blk^T, optionally via the Pallas blockgram kernel."""
    if use_kernel:
        from repro.kernels import ops as kops

        return kops.blockgram(a_blk)
    return mm(a_blk, a_blk.T)


def sparse_gram_block(
    col_ids: jnp.ndarray,
    col_rows: jnp.ndarray,
    col_vals: jnp.ndarray,
    repair_cols: jnp.ndarray,
    repair_mask: jnp.ndarray,
    m: int,
    *,
    use_kernel: bool = False,
) -> jnp.ndarray:
    """Exact (M, M) gram of one repaired sparse block, never densified.

    With E the padded-ELL part and R the repair side-band (row j gains a
    1 at local column repair_cols[j] iff repair_mask[j]):

      G = E E^T  +  E R^T  +  (E R^T)^T  +  R R^T

    * ``E E^T``  — Pallas sparse_gram kernel (use_kernel) or the (C, M)
      stored-column panel contraction; C ~ nnz either way.
    * ``E R^T [r, j] = E[r, c_j] * mask_j`` — one (M, C) x (C, M) matmul
      against the stored-column match matrix (a repair may hit a column
      E already stores; this is the cross term that an append-only ELL
      would silently drop).
    * ``R R^T [i, j] = mask_i mask_j [c_i == c_j]`` — two repairs hitting
      the same column see each other.
    """
    panel = sparse.stored_col_panel(col_rows, col_vals, m)  # (C, M)
    if use_kernel:
        from repro.kernels import ops as kops

        g_e = kops.sparse_gram(col_rows, col_vals, m)
    else:
        g_e = mm(panel.T, panel)
    rmask = repair_mask.astype(jnp.float32)
    match = (col_ids[:, None] == repair_cols[None, :]).astype(jnp.float32) \
        * rmask[None, :]                                     # (C, M)
    cross = mm(panel.T, match)                               # (M, M)
    g_r = (repair_cols[:, None] == repair_cols[None, :]).astype(jnp.float32) \
        * (rmask[:, None] * rmask[None, :])
    return g_e + cross + cross.T + g_r


BlockStack = Union[jnp.ndarray, "sparse.RepairedSparseBlocks"]


def gram_stack(blocks: BlockStack, *, use_kernel: bool = False) -> jnp.ndarray:
    """(D, M, M) grams of a block stack, dispatching on representation:
    dense (D, M, N_b) array or sparse.RepairedSparseBlocks."""
    if isinstance(blocks, sparse.RepairedSparseBlocks):
        ell = blocks.ell

        def one(ids, rows, vals, rc, rm):
            return sparse_gram_block(ids, rows, vals, rc, rm, ell.m,
                                     use_kernel=use_kernel)

        return jax.vmap(one)(ell.col_ids, ell.col_rows, ell.col_vals,
                             blocks.repair_cols, blocks.repair_mask)
    return jax.vmap(lambda b: gram(b, use_kernel=use_kernel))(blocks)


def local_svd_gram_stack(
    blocks: BlockStack, *, use_kernel: bool = False
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """(U (D, M, M), S (D, M)) via gram + eigh for either representation."""
    grams = gram_stack(blocks, use_kernel=use_kernel)
    return jax.vmap(eigh_to_svd)(grams)


def eigh_to_svd(g: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Convert eigh(G) of a PSD gram matrix into (U, S) sorted descending."""
    evals, evecs = jnp.linalg.eigh(g)  # ascending
    evals = jnp.flip(evals, axis=-1)
    evecs = jnp.flip(evecs, axis=-1)
    s = jnp.sqrt(jnp.clip(evals, 0.0, None))
    return evecs, s


@partial(jax.jit, static_argnames=("use_kernel",))
def local_svd_gram(
    a_blk: jnp.ndarray, *, use_kernel: bool = False
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """(U, S) of a block via gram + eigh (TPU-native path)."""
    return eigh_to_svd(gram(a_blk, use_kernel=use_kernel))


@jax.jit
def local_svd_exact(a_blk: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """(U, S) of a block via full SVD (paper's dgesvd analogue).

    Pads S with zeros up to M when N_b < M so panel shapes are static.
    """
    m = a_blk.shape[0]
    u, s, _ = jnp.linalg.svd(a_blk, full_matrices=True)
    k = s.shape[0]
    if k < m:
        s = jnp.concatenate([s, jnp.zeros((m - k,), s.dtype)])
    return u, s[:m]


def proxy_panel(u: jnp.ndarray, s: jnp.ndarray) -> jnp.ndarray:
    """The block's contribution to the proxy matrix: U @ diag(S)."""
    return u * s[None, :]


@jax.jit
def merge_panels_svd(panels: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Paper-faithful merge: SVD of the proxy P = concat(panels, axis=1).

    panels: (D, M, M) stacked U^i Sigma^i panels.
    Returns (U, S) of P — equal to (U, S) of A up to block-diag unitary W.
    """
    d, m, _ = panels.shape
    p = jnp.transpose(panels, (1, 0, 2)).reshape(m, d * m)
    # Economy SVD: V is discarded and M <= D*M, so U and S are the same
    # either way — full_matrices=True would allocate a dead (D*M, D*M)
    # right-vector buffer that dominated the measured R1 peak (caught by
    # the tests/test_api.py memory_checker).
    u, s, _ = jnp.linalg.svd(p, full_matrices=False)
    k = s.shape[0]
    if k < m:
        s = jnp.concatenate([s, jnp.zeros((m - k,), s.dtype)])
    return u, s[:m]


@jax.jit
def merge_grams_eigh(grams: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Beyond-paper merge: PP^T = sum_i G_i, so eigh of the summed gram
    replaces the proxy SVD entirely.

    grams: (D, M, M) local gram matrices (or a pre-reduced (M, M)).
    Its ops carry the scope ``ranky.eigh`` wherever it runs.
    """
    with jax.named_scope("ranky.eigh"):
        g = grams.sum(axis=0) if grams.ndim == 3 else grams
        return eigh_to_svd(g)


def right_vectors(
    a_blk: jnp.ndarray, u: jnp.ndarray, s: jnp.ndarray, *, rcond: float = 1e-7
) -> jnp.ndarray:
    """Recover this block's slice of the right singular vectors:
    V_blk = A_blk^T @ U @ diag(1/S)  (rows of V for this block's columns).

    The paper lists right-vector recovery as future work; it falls out of
    the factorization with one local matmul per block (U is M x M and is
    broadcast, never the full V).
    """
    smax = jnp.max(s)
    inv = jnp.where(s > rcond * smax, 1.0 / jnp.where(s == 0, 1.0, s), 0.0)
    return mm(a_blk.T, u) * inv[None, :]


def sparse_right_vectors(
    col_ids: jnp.ndarray,
    col_rows: jnp.ndarray,
    col_vals: jnp.ndarray,
    repair_cols: jnp.ndarray,
    repair_mask: jnp.ndarray,
    width: int,
    u: jnp.ndarray,
    s: jnp.ndarray,
    *,
    rcond: float = 1e-7,
) -> jnp.ndarray:
    """Sparse-native right_vectors: V_blk (W, r) for one repaired sparse
    block.  A_blk^T @ U reduces to one (C, M) x (M, r) matmul over stored
    columns scattered to their local ids, plus the repair rows of U.
    U may be square (exact paths) or truncated (M, r) (hierarchical
    truncated merge)."""
    m = u.shape[0]
    panel = sparse.stored_col_panel(col_rows, col_vals, m)   # (C, M)
    atu = jnp.zeros((width, u.shape[1]), u.dtype).at[col_ids].add(mm(panel, u))
    atu = atu.at[repair_cols].add(repair_mask[:, None] * u)
    smax = jnp.max(s)
    inv = jnp.where(s > rcond * smax, 1.0 / jnp.where(s == 0, 1.0, s), 0.0)
    return atu * inv[None, :]
