"""Hierarchical / incremental Ranky SVD (paper §V future work, and the
Iwen & Ong incremental algorithm the paper builds on).

Motivation: with thousands of blocks (D >> number of devices) the proxy
matrix M x (D*M) becomes the bottleneck.  The fix is a *tree merge*:
merge panels in groups of ``fanout`` per level — each merge produces a
single M x r panel — until one panel remains.  With truncation rank
r < M this is exactly Iwen & Ong's memory-bounded incremental algorithm,
and it exposes the paper's *rank problem*: if a block's rank falls below
r (lonely rows!), the truncated merge loses components it can never
recover.  Ranky's checkers run before level 0 to prevent that.

This module is the host-orchestrated variant (Python loop over levels,
jitted per-level vmapped merges); the two-level device-scheduled variant
lives in core/distributed.py (hierarchical=True).
"""
from __future__ import annotations

from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro import obs
from repro.core import ranky
from repro.core import svd as lsvd


def merge_svd(p: jnp.ndarray, rank: int):
    """SVD-merge a wide (M, R) panel concatenation, truncated to ``rank``.

    The ONE merge primitive of the incremental algorithm, shared by the
    tree merge below, the streaming merge-and-truncate engine
    (``repro.stream.ingest``), and the scan-window driver
    (``repro.stream.window``), whose ``lax.scan`` body calls it once per
    folded batch — fixed-shape at steady rank, which is exactly what
    makes whole ingestion windows one compiled dispatch.  Returns
    ``(U (M, rank), S (rank,),
    W (R, rank))`` with ``P = U diag(S) W^T + (discarded tail)``; all
    three are zero-padded when ``rank > min(M, R)`` so output shapes
    stay static.  ``W`` is what streaming needs: for
    ``P = [V_old diag(s_old) | B^T U_b]`` it is the small rotation that
    carries the old and batch left vectors into the merged basis.
    """
    m, rtot = p.shape
    # The span is inert inside jit/scan tracing (trace_state_clean guard
    # in obs.trace) — it records only for eager merges, e.g. the
    # per-batch streaming ingest.  The scope names the merge's device
    # ops wherever it is traced (the scan window's step among them).
    with obs.span("merge.svd", m=m, r_tot=rtot, rank=rank), \
            jax.named_scope("stream.merge"):
        u, s, wt = jnp.linalg.svd(p, full_matrices=False)
        k = min(m, rtot)
        if k < rank:
            u = jnp.pad(u, ((0, 0), (0, rank - k)))
            s = jnp.pad(s, (0, rank - k))
            wt = jnp.pad(wt, ((0, rank - k), (0, 0)))
        return u[:, :rank], s[:rank], wt[:rank].T


@partial(jax.jit, static_argnames=("rank",))
def _merge_group(panels: jnp.ndarray, rank: int) -> jnp.ndarray:
    """SVD-merge a (G, M, r) group of panels into one (M, rank) panel."""
    g, m, r = panels.shape
    p = jnp.transpose(panels, (1, 0, 2)).reshape(m, g * r)
    u, s, _ = merge_svd(p, rank)
    return u * s[None, :]


def solve_hierarchical(
    a,
    *,
    num_blocks: int,
    fanout: int = 4,
    rank: Optional[int] = None,
    method: str = "neighbor_random",
    sketch: bool = False,
    oversample: int = 8,
    power_iters: int = 2,
    want_right: bool = False,
    use_kernel: bool = False,
    key: Optional[jax.Array] = None,
):
    """Tree-merged Ranky SVD — the ``backend="hierarchical"`` engine
    behind ``repro.core.api.svd`` (and the legacy
    ``hierarchical_ranky_svd`` shim).  Returns (U, S) with S of length
    ``rank`` (defaults to M — exact; r < M gives the truncated
    incremental algorithm whose failure on rank-deficient blocks
    motivates Ranky) — or (U, S, V) with ``want_right``, V (D*W, r) in
    padded column order recovered per block as ``A_blk^T U diag(1/S)``.

    ``a`` is a dense (M, N) array (N must divide by num_blocks) or a
    sparse.BlockEll container (sparse-native leaves, no block ever
    densified) — the same shared prologue as ranky.ranky_svd handles
    both.

    ``sketch=True`` replaces the exact gram+eigh leaves with randomized
    truncated rank-``rank`` leaf panels (core/randomized.py): each
    block's (M, r) panel comes from a per-block (r+oversample)-row
    sketch in O(nnz_d * r) instead of the O(M^2 W + M^3) gram+eigh, and
    the existing tree merge consumes the panels unchanged.  This is the
    tall-row-regime form of the Iwen & Ong incremental algorithm — and
    makes Ranky's repair MORE load-bearing: a rank-deficient block's
    lonely rows carry no sketch weight, so the truncated leaves lose
    their components unrecoverably unless repair runs first.
    """
    from repro.core import sparse

    m = a.m if isinstance(a, sparse.BlockEll) else a.shape[0]
    r = m if rank is None else min(rank, m)
    if key is None:
        key = ranky.default_key()

    blocks = ranky.split_and_repair(a, num_blocks, method, key)

    # Level 0: per-block factorization -> (D, M, r) truncated proxy panels.
    if sketch:
        from repro.core import randomized

        panels = randomized.block_truncated_panels(
            blocks, rank=r, oversample=oversample,
            power_iters=power_iters, key=key)
    else:
        us, ss = lsvd.local_svd_gram_stack(blocks, use_kernel=use_kernel)
        panels = (us * ss[:, None, :])[:, :, :r]

    # Tree merge, groups of ``fanout`` per level.
    while panels.shape[0] > 1:
        d = panels.shape[0]
        pad = (-d) % fanout
        if pad:
            panels = jnp.concatenate(
                [panels, jnp.zeros((pad,) + panels.shape[1:], panels.dtype)]
            )
        groups = panels.reshape(-1, fanout, m, r)
        panels = jax.vmap(lambda g: _merge_group(g, r))(groups)

    panel = panels[0]  # (M, r) == U * S of A (up to unitary, exactly if r = rank(A))
    u, s, _ = jnp.linalg.svd(panel, full_matrices=False)
    if not want_right:
        return u, s
    return u, s, ranky.right_vectors_stack(blocks, u, s)


def hierarchical_ranky_svd(
    a,
    *,
    num_blocks: int,
    fanout: int = 4,
    rank: Optional[int] = None,
    method: str = "neighbor_random",
    sketch: bool = False,
    oversample: int = 8,
    power_iters: int = 2,
    want_right: bool = False,
    key: Optional[jax.Array] = None,
):
    """DEPRECATED legacy entry point — use ``repro.core.api.svd`` with a
    ``SolveConfig(backend="hierarchical", ...)``.

    Thin shim: builds the SolveConfig (centralized validation) and runs
    the same ``solve_hierarchical`` engine ``api.svd`` dispatches to.
    Returns the legacy (U, S) tuple — or (U, S, V) with
    ``want_right=True`` (V in padded column order).
    """
    import warnings

    from repro.core import api

    warnings.warn(
        "hierarchical_ranky_svd is deprecated; use repro.core.api.svd "
        "with SolveConfig(backend='hierarchical', ...)",
        DeprecationWarning, stacklevel=2)
    cfg = api.SolveConfig(
        backend="hierarchical", method=method, num_blocks=num_blocks,
        fanout=fanout, rank=rank, sketch=sketch, oversample=oversample,
        power_iters=power_iters, want_right=want_right, key=key)
    return api._run_hierarchical(a, cfg)
