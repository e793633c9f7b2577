"""Ranky rank-repair methods (the paper's core contribution) + the
single-host reference pipeline.

The paper's per-row pseudocode loops are re-expressed as vectorized mask
algebra so they run as a handful of XLA ops per block instead of Python
loops (TPU adaptation; semantics preserved — see the literal numpy
reference implementations ``ref_*`` used by the property tests).

Terminology (paper): a *lonely node/row* is a row that is all-zero inside
one column block (it may have entries in other blocks).  Lonely rows make
``rank(A^i) < rank(A)`` which breaks the proxy-matrix SVD recovery.

Methods:
  * random   — RandomChecker: each lonely row gets a 1 at a uniformly
               random column inside the block.
  * neighbor — NeighborChecker: a lonely row m gets a 1 at a column of
               this block where one of m's graph neighbors (rows sharing
               a nonzero column with m *anywhere* in A) has a nonzero.
               If m has no neighbor with entries in this block, the row
               stays lonely (this is the paper's observed weakness).
  * neighbor_random — NeighborRandomChecker: neighbor first, random
               fallback for rows the neighbor pass could not fix.
"""
from __future__ import annotations

from functools import partial
from typing import Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import sparse

METHODS = ("none", "random", "neighbor", "neighbor_random")

# The ONE documented deterministic default: every driver (single-host,
# hierarchical, shard_map, randomized) resolves key=None to this exact
# key, so unkeyed solves are reproducible across drivers and sessions.
DEFAULT_SEED = 0


def default_key() -> jax.Array:
    """``jax.random.PRNGKey(DEFAULT_SEED)`` — the shared ``key=None``
    default of every Ranky driver (see repro.core.api)."""
    return jax.random.PRNGKey(DEFAULT_SEED)


# ---------------------------------------------------------------------------
# Mask helpers
# ---------------------------------------------------------------------------

def lonely_rows(a_blk: jnp.ndarray) -> jnp.ndarray:
    """Boolean (M,) mask of rows that are all-zero inside this block."""
    return ~jnp.any(a_blk != 0, axis=1)


def row_adjacency(a_dense: jnp.ndarray) -> jnp.ndarray:
    """Global boolean row-adjacency R[m, m'] = rows m and m' share a
    nonzero column somewhere in A.  Diagonal is cleared.

    Distributed equivalent: psum of binarized local grams (see
    core/distributed.py) — this routine is the single-host reference.
    """
    b = (a_dense != 0).astype(jnp.float32)
    adj = (b @ b.T) > 0
    return adj & ~jnp.eye(adj.shape[0], dtype=bool)


def _random_cols(key: jax.Array, m: int, n: int) -> jnp.ndarray:
    return jax.random.randint(key, (m,), 0, n)


def _choose_masked_col(key: jax.Array, mask: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Per row, uniformly choose a column among ``mask`` (M, N) candidates.

    Returns (cols (M,), has_candidate (M,)).  Rows without candidates get
    an arbitrary column index (callers must gate on has_candidate).
    """
    scores = jax.random.uniform(key, mask.shape)
    scores = jnp.where(mask, scores, -1.0)
    return jnp.argmax(scores, axis=1), jnp.any(mask, axis=1)


def _fill_rows(a_blk: jnp.ndarray, rows_mask: jnp.ndarray, cols: jnp.ndarray) -> jnp.ndarray:
    """Set A[m, cols[m]] = 1 for every row m with rows_mask[m]."""
    onehot = jax.nn.one_hot(cols, a_blk.shape[1], dtype=a_blk.dtype)
    fill = rows_mask[:, None].astype(a_blk.dtype) * onehot
    # Rows being filled are all-zero inside the block, so add == set.
    return a_blk + fill


# ---------------------------------------------------------------------------
# Vectorized checkers (jit-able; the production path)
# ---------------------------------------------------------------------------

@jax.jit
def random_checker(a_blk: jnp.ndarray, key: jax.Array) -> jnp.ndarray:
    """RandomChecker: lonely rows get a 1 at a random in-block column."""
    lonely = lonely_rows(a_blk)
    cols = _random_cols(key, a_blk.shape[0], a_blk.shape[1])
    return _fill_rows(a_blk, lonely, cols)


@jax.jit
def neighbor_checker(
    a_blk: jnp.ndarray, row_adj: jnp.ndarray, key: jax.Array
) -> jnp.ndarray:
    """NeighborChecker: lonely rows get a 1 at a random column where one
    of their graph neighbors has an entry inside this block."""
    lonely = lonely_rows(a_blk)
    present = (a_blk != 0).astype(jnp.float32)
    # candidate_cols[m, n] = some neighbor of m has a nonzero at column n.
    candidate_cols = (row_adj.astype(jnp.float32) @ present) > 0
    cols, has_cand = _choose_masked_col(key, candidate_cols)
    return _fill_rows(a_blk, lonely & has_cand, cols)


@jax.jit
def neighbor_random_checker(
    a_blk: jnp.ndarray, row_adj: jnp.ndarray, key: jax.Array
) -> jnp.ndarray:
    """NeighborRandomChecker: neighbor pass, then random fallback for rows
    still lonely (no neighbor had entries inside this block)."""
    k_nb, k_rand = jax.random.split(key)
    lonely = lonely_rows(a_blk)
    present = (a_blk != 0).astype(jnp.float32)
    candidate_cols = (row_adj.astype(jnp.float32) @ present) > 0
    nb_cols, has_cand = _choose_masked_col(k_nb, candidate_cols)
    rand_cols = _random_cols(k_rand, a_blk.shape[0], a_blk.shape[1])
    cols = jnp.where(has_cand, nb_cols, rand_cols)
    return _fill_rows(a_blk, lonely, cols)


def repair_block(
    a_blk: jnp.ndarray,
    method: str,
    key: jax.Array,
    row_adj: Optional[jnp.ndarray] = None,
) -> jnp.ndarray:
    """Dispatch one of the Ranky methods on a block."""
    if method == "none":
        return a_blk
    if method == "random":
        return random_checker(a_blk, key)
    if row_adj is None:
        raise ValueError(f"method {method!r} needs the row adjacency")
    if method == "neighbor":
        return neighbor_checker(a_blk, row_adj, key)
    if method == "neighbor_random":
        return neighbor_random_checker(a_blk, row_adj, key)
    raise ValueError(f"unknown Ranky method {method!r}; want one of {METHODS}")


# ---------------------------------------------------------------------------
# Sparse-native checkers (index-array algebra; the dense checkers above
# are the semantic oracles — tests/test_sparse_path.py pins the parity)
# ---------------------------------------------------------------------------

def sparse_row_counts(
    col_rows: jnp.ndarray, col_vals: jnp.ndarray, m: int
) -> jnp.ndarray:
    """(M,) per-row nonzero counts of one ELL block (padding slots inert)."""
    present = (col_vals != 0).astype(jnp.int32)
    return jnp.zeros((m,), jnp.int32).at[col_rows].add(present)


def sparse_lonely_rows(
    col_rows: jnp.ndarray, col_vals: jnp.ndarray, m: int
) -> jnp.ndarray:
    """Boolean (M,) lonely mask straight from the index arrays."""
    return sparse_row_counts(col_rows, col_vals, m) == 0


@partial(jax.jit, static_argnames="m")
def _ell_lonely_counts(col_rows: jnp.ndarray, col_vals: jnp.ndarray,
                       m: int) -> jnp.ndarray:
    """(D,) lonely-row counts of a BlockEll's stacked index arrays."""
    lonely = jax.vmap(partial(sparse_lonely_rows, m=m))(col_rows, col_vals)
    return lonely.sum(axis=1, dtype=jnp.int32)


def lonely_counts(a_norm, num_blocks: int):
    """Per-block lonely-row counts of a normalized input — dense
    (M, N_pad) array (N_pad divisible by num_blocks) or BlockEll — as a
    (D,) array.  For a BlockEll this dispatches one compiled program
    (cached on shapes and M) and returns without waiting, so a caller
    can queue it behind other device work and read it back later."""
    if isinstance(a_norm, sparse.BlockEll):
        return _ell_lonely_counts(a_norm.col_rows, a_norm.col_vals,
                                  m=a_norm.m)
    m, n = a_norm.shape
    blocks = np.asarray(a_norm).reshape(m, num_blocks, n // num_blocks)
    return (~(blocks != 0).any(axis=2)).sum(axis=0)


def lonely_rows_per_block(a_norm, num_blocks: int) -> Tuple[int, ...]:
    """:func:`lonely_counts` read back as a host-side tuple of ints — the
    shared diagnostics helper behind ``api.svd`` and ``stream.ingest``."""
    return tuple(int(x) for x in np.asarray(lonely_counts(a_norm,
                                                          num_blocks)))


def row_adjacency_sparse(ell: "sparse.BlockEll") -> jnp.ndarray:
    """Global row adjacency from the blocked sparse container: psum-style
    sum of per-block binarized grams (counts of shared stored columns),
    identical in semantics to ``row_adjacency`` on the dense matrix."""
    def one(rows, vals):
        p = sparse.stored_col_panel(rows, vals, ell.m, binarize=True)
        return p.T @ p

    counts = jax.vmap(one)(ell.col_rows, ell.col_vals).sum(axis=0)
    return (counts > 0) & ~jnp.eye(ell.m, dtype=bool)


def sparse_random_checker(
    col_rows: jnp.ndarray, col_vals: jnp.ndarray, m: int, width: int,
    key: jax.Array,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """RandomChecker on index arrays: (repair_cols, repair_mask).

    Draws the same ``_random_cols(key, M, W)`` the dense checker draws,
    so for a given key the sparse and dense repairs are bit-identical.
    """
    lonely = sparse_lonely_rows(col_rows, col_vals, m)
    return _random_cols(key, m, width), lonely


def sparse_neighbor_checker(
    col_ids: jnp.ndarray, col_rows: jnp.ndarray, col_vals: jnp.ndarray,
    row_adj: jnp.ndarray, m: int, key: jax.Array,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """NeighborChecker on index arrays.

    Candidate columns of a lonely row are columns of this block where a
    graph neighbor has an entry — all such columns are *stored* columns,
    so the choice runs over the (M, C) stored-column candidate mask and
    maps back through col_ids.  Same candidate set as the dense checker
    (non-stored columns are all-zero and never candidates).
    """
    lonely = sparse_lonely_rows(col_rows, col_vals, m)
    presence = sparse.stored_col_panel(col_rows, col_vals, m, binarize=True)
    cand = (row_adj.astype(jnp.float32) @ presence.T) > 0  # (M, C)
    stored_idx, has_cand = _choose_masked_col(key, cand)
    return col_ids[stored_idx], lonely & has_cand


def sparse_neighbor_random_checker(
    col_ids: jnp.ndarray, col_rows: jnp.ndarray, col_vals: jnp.ndarray,
    row_adj: jnp.ndarray, m: int, width: int, key: jax.Array,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Neighbor pass, random fallback for rows without reachable columns."""
    k_nb, k_rand = jax.random.split(key)
    nb_cols, nb_mask = sparse_neighbor_checker(
        col_ids, col_rows, col_vals, row_adj, m, k_nb)
    lonely = sparse_lonely_rows(col_rows, col_vals, m)
    rand_cols = _random_cols(k_rand, m, width)
    cols = jnp.where(nb_mask, nb_cols, rand_cols)
    return cols, lonely


def repair_block_sparse(
    col_ids: jnp.ndarray,
    col_rows: jnp.ndarray,
    col_vals: jnp.ndarray,
    method: str,
    key: jax.Array,
    *,
    m: int,
    width: int,
    row_adj: Optional[jnp.ndarray] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Dispatch one Ranky method on one sparse block; returns the repair
    side-band (repair_cols (M,), repair_mask (M,)) — the at-most-one
    1-valued entry per row the checker adds, landing in the reserved
    capacity of sparse.RepairedSparseBlocks instead of mutating the ELL."""
    if method == "none":
        return (jnp.zeros((m,), jnp.int32), jnp.zeros((m,), bool))
    if method == "random":
        return sparse_random_checker(col_rows, col_vals, m, width, key)
    if row_adj is None:
        raise ValueError(f"method {method!r} needs the row adjacency")
    if method == "neighbor":
        return sparse_neighbor_checker(
            col_ids, col_rows, col_vals, row_adj, m, key)
    if method == "neighbor_random":
        return sparse_neighbor_random_checker(
            col_ids, col_rows, col_vals, row_adj, m, width, key)
    raise ValueError(f"unknown Ranky method {method!r}; want one of {METHODS}")


# ---------------------------------------------------------------------------
# Literal per-row numpy references (paper pseudocode transliterated).
# Used only by property tests to pin the vectorized semantics.
# ---------------------------------------------------------------------------

def ref_lonely_rows(a_blk: np.ndarray) -> np.ndarray:
    out = np.ones(a_blk.shape[0], dtype=bool)
    for m in range(a_blk.shape[0]):
        for n in range(a_blk.shape[1]):
            if a_blk[m, n] != 0:
                out[m] = False
                break
    return out


def ref_random_checker(a_blk: np.ndarray, rng: np.random.Generator) -> np.ndarray:
    a = a_blk.copy()
    for m in range(a.shape[0]):
        if not a[m].any():
            a[m, rng.integers(0, a.shape[1])] = 1.0
    return a


def ref_neighbor_candidates(
    a_full: np.ndarray, blk_lo: int, blk_hi: int, m: int
) -> np.ndarray:
    """Paper NeighborChecker inner loops: the set of columns inside block
    [blk_lo, blk_hi) where any graph-neighbor of row m has a nonzero."""
    mcount = a_full.shape[0]
    neighbors = set()
    for n1 in range(a_full.shape[1]):
        if blk_lo <= n1 < blk_hi:
            continue  # other blocks only (d1 == d is skipped in the paper)
        if a_full[m, n1] != 0:
            for m1 in range(mcount):
                if m1 != m and a_full[m1, n1] != 0:
                    neighbors.add(m1)
    cols = set()
    for m1 in neighbors:
        for n2 in range(blk_lo, blk_hi):
            if a_full[m1, n2] != 0:
                cols.add(n2 - blk_lo)
    return np.asarray(sorted(cols), dtype=np.int64)


# ---------------------------------------------------------------------------
# Shared prologue + single-host end-to-end pipeline (reference for the
# distributed version)
# ---------------------------------------------------------------------------

BlockInput = Union[jnp.ndarray, "sparse.BlockEll"]


def split_and_repair(
    a: BlockInput,
    num_blocks: int,
    method: str,
    key: Optional[jax.Array] = None,
):
    """The block-split -> row-adjacency -> vmapped-repair prologue shared
    by ``ranky_svd``, ``hierarchy.hierarchical_ranky_svd`` and the
    benchmark evaluation protocol (benchmarks/paper_tables.py).

    * dense (M, N) array  -> repaired (D, M, N/D) block stack
      (N must already divide by num_blocks — sparse.pad_to_block_multiple)
    * sparse.BlockEll     -> sparse.RepairedSparseBlocks (the immutable
      ELL plus the per-block repair side-band; nothing is densified)
    """
    if key is None:
        key = default_key()
    keys = jax.random.split(key, num_blocks)
    needs_adj = method in ("neighbor", "neighbor_random")

    if isinstance(a, sparse.BlockEll):
        if a.num_blocks != num_blocks:
            raise ValueError(
                f"BlockEll has {a.num_blocks} blocks, got num_blocks={num_blocks}")
        adj = row_adjacency_sparse(a) if needs_adj else None

        def fix(ids, rows, vals, k):
            return repair_block_sparse(ids, rows, vals, method, k,
                                       m=a.m, width=a.width, row_adj=adj)

        rc, rm = jax.vmap(fix)(a.col_ids, a.col_rows, a.col_vals, keys)
        return sparse.RepairedSparseBlocks(a, rc, rm)

    m, n = a.shape
    if n % num_blocks:
        raise ValueError("pad columns so N % num_blocks == 0")
    blocks = jnp.transpose(
        a.reshape(m, num_blocks, n // num_blocks), (1, 0, 2)
    )  # (D, M, N/D)
    adj = row_adjacency(a) if needs_adj else None

    def fix(blk, k):
        return repair_block(blk, method, k, adj)

    return jax.vmap(fix)(blocks, keys)


def right_vectors_stack(blocks, u: jnp.ndarray, s: jnp.ndarray) -> jnp.ndarray:
    """Right vectors of the REPAIRED matrix from a repaired block stack:
    per block ``V_blk = A_blk^T U diag(1/S)``, stacked to (D*W, r) in
    padded column order — the single-host twin of the per-device
    ``want_right`` recovery in core/distributed.py."""
    from repro.core import svd as lsvd

    if isinstance(blocks, sparse.RepairedSparseBlocks):
        ell = blocks.ell
        v = jax.vmap(
            lambda ids, rows, vals, rc, rm: lsvd.sparse_right_vectors(
                ids, rows, vals, rc, rm, ell.width, u, s)
        )(ell.col_ids, ell.col_rows, ell.col_vals,
          blocks.repair_cols, blocks.repair_mask)     # (D, W, r)
        return v.reshape(ell.num_blocks * ell.width, -1)
    d, _, w = blocks.shape
    v = jax.vmap(lambda blk: lsvd.right_vectors(blk, u, s))(blocks)
    return v.reshape(d * w, -1)


@partial(jax.jit, static_argnames=("num_blocks", "method", "local_mode",
                                   "merge_mode", "undetermined_tail",
                                   "rank", "oversample", "power_iters",
                                   "want_right", "use_kernel"))
def solve_single(
    a: BlockInput,
    *,
    num_blocks: int,
    method: str = "neighbor_random",
    local_mode: str = "gram",  # "gram" (TPU-native) | "svd" (paper dgesvd)
    merge_mode: str = "proxy",  # "proxy" (paper) | "gram" (beyond-paper)
    undetermined_tail: bool = False,
    rank: Optional[int] = None,
    oversample: int = 8,
    power_iters: int = 2,
    want_right: bool = False,
    use_kernel: bool = False,
    key: Optional[jax.Array] = None,
):
    """One-level Ranky distributed SVD, single host: the ``backend="single"``
    engine behind ``repro.core.api.svd`` (and the legacy ``ranky_svd``
    shim).  Returns (U, S) of A — or (U, S, V) with ``want_right``, V in
    padded column order.

    ``a`` is either a dense (M, N) array — N must divide by num_blocks,
    pad with zero columns first (lossless for U and S; see
    sparse.pad_to_block_multiple) — or a sparse.BlockEll container, in
    which case the whole pipeline is sparse-native (gram local mode only;
    no (M, N/D) block is ever materialized).

    ``rank=k`` switches to the randomized truncated path
    (core/randomized.py): rank repair still runs first, then the top-k
    (U (M, k), S (k,)) come from a (k+oversample)-row sketch with
    ``power_iters`` re-orthonormalized power passes — O(nnz * k) per
    block instead of the O(M^2) gram, the only path viable in the
    tall-row regime.  ``local_mode``/``merge_mode`` do not apply to the
    sketch (it replaces both the local factorization and the merge).

    ``undetermined_tail`` emulates the rank problem the paper fixes: a
    rank-deficient block's SVD has zero singular values whose left-vector
    columns are numerically UNDETERMINED (the reference C implementation
    communicates d panel columns regardless of the block's actual rank,
    so the dead columns carry whatever noise the factorization left
    there).  With the flag on, dead panel columns are filled with
    sqrt(eps)-scale noise — the exact failure Ranky's checkers prevent by
    making every block full-rank.  See benchmarks/rank_problem.py.  The
    emulation lives in the proxy-panel merge: requesting it under
    ``merge_mode="gram"`` or ``rank=k`` (neither builds panels) is an
    error rather than a silent no-op.
    Cross-field validation lives in ``api.SolveConfig`` (the shims build
    one); this engine only keeps the input-dependent checks.
    """
    from repro.core import svd as lsvd

    is_sparse = isinstance(a, sparse.BlockEll)
    if key is None:
        key = default_key()

    # Stable scope names for the device ops (the trace's ``tf_op``):
    # ranky.repair, ranky.gram, ranky.eigh (inside merge_grams_eigh),
    # ranky.right.
    with jax.named_scope("ranky.repair"):
        blocks = split_and_repair(a, num_blocks, method, key)

    if rank is not None:
        from repro.core import randomized

        return randomized.randomized_svd_blocks(
            blocks, rank=rank, oversample=oversample,
            power_iters=power_iters, key=key, want_right=want_right)

    if merge_mode == "gram":
        with jax.named_scope("ranky.gram"):
            grams = lsvd.gram_stack(blocks, use_kernel=use_kernel)
        u, s = lsvd.merge_grams_eigh(grams)
    elif merge_mode == "proxy":
        if local_mode == "gram":
            us = lsvd.local_svd_gram_stack(blocks, use_kernel=use_kernel)
        elif local_mode == "svd":
            if is_sparse:
                raise ValueError(
                    "the sparse path is gram-native; use local_mode='gram'")
            us = jax.vmap(lsvd.local_svd_exact)(blocks)
        else:
            raise ValueError(f"unknown local_mode {local_mode!r}")
        panels = jax.vmap(lsvd.proxy_panel)(*us)  # (D, M, M)
        if undetermined_tail:
            u_all, s_all = us
            smax = jnp.max(s_all, axis=1, keepdims=True)          # (D, 1)
            dead = s_all <= 1e-9 * smax                           # (D, M)
            nkeys = jax.random.split(jax.random.fold_in(key, 0xDEAD),
                                     num_blocks)
            noise = jax.vmap(
                lambda k, p: jax.random.normal(k, p.shape, p.dtype))(
                    nkeys, panels)
            eps_scale = jnp.sqrt(jnp.finfo(panels.dtype).eps)
            panels = jnp.where(dead[:, None, :],
                               noise * smax[:, :, None] * eps_scale, panels)
        u, s = lsvd.merge_panels_svd(panels)
    else:
        raise ValueError(f"unknown merge_mode {merge_mode!r}")

    if not want_right:
        return u, s
    with jax.named_scope("ranky.right"):
        return u, s, right_vectors_stack(blocks, u, s)


def ranky_svd(
    a: BlockInput,
    *,
    num_blocks: int,
    method: str = "neighbor_random",
    local_mode: str = "gram",
    merge_mode: str = "proxy",
    undetermined_tail: bool = False,
    rank: Optional[int] = None,
    oversample: int = 8,
    power_iters: int = 2,
    want_right: bool = False,
    key: Optional[jax.Array] = None,
):
    """DEPRECATED legacy entry point — use ``repro.core.api.svd`` with a
    ``SolveConfig(backend="single", ...)``.

    Thin shim: builds the SolveConfig (centralized validation) and runs
    the same ``solve_single`` engine ``api.svd`` dispatches to, so the
    two surfaces are bit-identical.  Returns the legacy (U, S) tuple —
    or (U, S, V) with ``want_right=True`` (V in padded column order).
    """
    import warnings

    from repro.core import api

    warnings.warn(
        "ranky_svd is deprecated; use repro.core.api.svd with "
        "SolveConfig(backend='single', ...)", DeprecationWarning,
        stacklevel=2)
    cfg = api.SolveConfig(
        backend="single", method=method, local_mode=local_mode,
        merge_mode=merge_mode, undetermined_tail=undetermined_tail,
        rank=rank, oversample=oversample, power_iters=power_iters,
        want_right=want_right, num_blocks=num_blocks, key=key)
    return api._run_single(a, cfg)
