"""Distributed Ranky SVD with shard_map.

The input matrix is column-sharded over one or more mesh axes — each
device owns exactly one column block A^i, which *is* the paper's block
decomposition mapped onto the mesh.  Everything (rank repair, local
factorization, merge) happens inside a single shard_map region so XLA can
schedule the collectives.

Merge modes
  * ``proxy`` (paper-faithful): all-gather the M x M proxy panels
    ``U^i Sigma^i`` and SVD the proxy on every device.
    Communication: O(M^2 * D) all-gather + O((DM)^2 M) redundant SVD.
  * ``gram`` (beyond-paper): PP^T == sum_i G_i, so a single psum of the
    M x M local grams + one eigh replaces gather + proxy SVD.
    Communication: O(M^2) all-reduce.  This is the optimization we report
    against the paper baseline in benchmarks/merge_modes.py.

Hierarchical merge (``hierarchical=True`` with two axes, e.g.
("pod", "model")): merge within the fast inner axis first (intra-pod ICI),
then across the slow outer axis (inter-pod DCI) — a 2-level tree like the
paper's future-work hierarchy, scheduled to match the network hierarchy.
"""
from __future__ import annotations

from functools import partial
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import svd as lsvd
from repro.core import randomized
from repro.core import ranky
from repro.core import sparse

from repro.compat import shard_map_nocheck as shard_map


def _axis_size(axes: Sequence[str]) -> jnp.ndarray:
    sz = 1
    for ax in axes:
        sz = sz * jax.lax.axis_size(ax)
    return sz


def _flat_index(axes: Sequence[str]) -> jnp.ndarray:
    """Row-major flat device index across the given mesh axes."""
    idx = jnp.zeros((), jnp.int32)
    for ax in axes:
        idx = idx * jax.lax.axis_size(ax) + jax.lax.axis_index(ax)
    return idx


def _local_repair(
    blk: jnp.ndarray, method: str, key: jax.Array, axes: Sequence[str]
) -> jnp.ndarray:
    """Rank-repair the local block; neighbor methods need the *global*
    row adjacency = psum of binarized local grams over the block axes."""
    key = jax.random.fold_in(key, _flat_index(axes))
    if method in ("neighbor", "neighbor_random"):
        b = (blk != 0).astype(jnp.float32)
        adj_local = b @ b.T
        adj = jax.lax.psum(adj_local, axes)
        # Clear self-adjacency (paper: a node is not its own neighbor).
        adj = (adj > 0) & ~jnp.eye(adj.shape[0], dtype=bool)
        return ranky.repair_block(blk, method, key, adj)
    return ranky.repair_block(blk, method, key, None)


def _local_factorize(blk: jnp.ndarray, local_mode: str, use_kernel: bool):
    if local_mode == "gram":
        return lsvd.local_svd_gram(blk, use_kernel=use_kernel)
    if local_mode == "svd":
        return lsvd.local_svd_exact(blk)
    raise ValueError(f"unknown local_mode {local_mode!r}")


def _merge_proxy_over(panel: jnp.ndarray, axes: Sequence[str]):
    """All-gather panels over ``axes`` and SVD the proxy (replicated)."""
    panels = panel
    for ax in reversed(axes):
        panels = jax.lax.all_gather(panels, ax, tiled=False)
        panels = panels.reshape((-1,) + panel.shape)
    return lsvd.merge_panels_svd(panels)


def _svd_shard_fn(
    a_blk: jnp.ndarray,
    key: jax.Array,
    *,
    axes: Tuple[str, ...],
    method: str,
    local_mode: str,
    merge_mode: str,
    hierarchical: bool,
    use_kernel: bool,
    want_right: bool,
    rank: Optional[int],
    oversample: int,
    power_iters: int,
):
    blk = _local_repair(a_blk, method, key, axes)

    if rank is not None:
        # Randomized truncated path: the (L, M) pullback / (L, L) sketch
        # gram are the only collectives (psum over the block axes); the
        # merge modes do not apply.  Omega is drawn from the UN-folded
        # key so it is replicated across the mesh.
        return randomized.randomized_tail_over(
            lambda om: randomized.sketch_block_dense(om, blk),
            lambda g: randomized.pullback_block_dense(g, blk),
            axes, blk.shape[0], rank=rank, oversample=oversample,
            power_iters=power_iters, key=key, want_right=want_right)

    if merge_mode == "gram":
        # Beyond-paper: one M x M all-reduce; eigh redundantly everywhere.
        # psum over all block axes is already hierarchy-optimal (XLA lowers
        # multi-axis psum as in-node reduce then cross-node).
        g = jax.lax.psum(lsvd.gram(blk, use_kernel=use_kernel), axes)
        u, s = lsvd.eigh_to_svd(g)
    elif merge_mode == "proxy":
        u_i, s_i = _local_factorize(blk, local_mode, use_kernel)
        panel = lsvd.proxy_panel(u_i, s_i)
        if hierarchical and len(axes) > 1:
            # Level 1: merge within the innermost (fast, intra-pod) axis.
            u1, s1 = _merge_proxy_over(panel, axes[-1:])
            # Level 2: merge the per-pod panels across the outer axes.
            u, s = _merge_proxy_over(lsvd.proxy_panel(u1, s1), axes[:-1])
        else:
            u, s = _merge_proxy_over(panel, axes)
    else:
        raise ValueError(f"unknown merge_mode {merge_mode!r}")

    if not want_right:
        return u, s
    v_blk = lsvd.right_vectors(blk, u, s)
    return u, s, v_blk


def _sparse_local_repair(
    ids: jnp.ndarray, rows: jnp.ndarray, vals: jnp.ndarray,
    method: str, key: jax.Array, axes: Sequence[str], m: int, width: int,
):
    """Sparse-native twin of _local_repair: the global row adjacency is
    the psum of binarized local grams, computed from the stored-column
    panel (C x M, nnz-proportional) instead of the dense block."""
    key = jax.random.fold_in(key, _flat_index(axes))
    adj = None
    if method in ("neighbor", "neighbor_random"):
        p = sparse.stored_col_panel(rows, vals, m, binarize=True)
        adj_local = p.T @ p
        adj = jax.lax.psum(adj_local, axes)
        adj = (adj > 0) & ~jnp.eye(m, dtype=bool)
    return ranky.repair_block_sparse(ids, rows, vals, method, key,
                                     m=m, width=width, row_adj=adj)


def _sparse_svd_shard_fn(
    ids: jnp.ndarray,
    rows: jnp.ndarray,
    vals: jnp.ndarray,
    key: jax.Array,
    *,
    m: int,
    width: int,
    axes: Tuple[str, ...],
    method: str,
    merge_mode: str,
    hierarchical: bool,
    use_kernel: bool,
    want_right: bool,
    rank: Optional[int],
    oversample: int,
    power_iters: int,
):
    """Per-device body for the sparse container: each device owns one
    column block's ELL arrays (leading block axis sharded to size 1).
    The merge is representation-agnostic — psum of grams / all-gather of
    panels is identical to the dense shard fn."""
    ids, rows, vals = ids[0], rows[0], vals[0]
    rc, rm = _sparse_local_repair(ids, rows, vals, method, key, axes,
                                  m, width)

    if rank is not None:
        return randomized.randomized_tail_over(
            lambda om: randomized.sketch_block_sparse(
                om, ids, rows, vals, rc, rm, width),
            lambda g: randomized.pullback_block_sparse(
                g, ids, rows, vals, rc, rm, m),
            axes, m, rank=rank, oversample=oversample,
            power_iters=power_iters, key=key, want_right=want_right)

    g_local = lsvd.sparse_gram_block(ids, rows, vals, rc, rm, m,
                                     use_kernel=use_kernel)

    if merge_mode == "gram":
        u, s = lsvd.eigh_to_svd(jax.lax.psum(g_local, axes))
    elif merge_mode == "proxy":
        u_i, s_i = lsvd.eigh_to_svd(g_local)
        panel = lsvd.proxy_panel(u_i, s_i)
        if hierarchical and len(axes) > 1:
            u1, s1 = _merge_proxy_over(panel, axes[-1:])
            u, s = _merge_proxy_over(lsvd.proxy_panel(u1, s1), axes[:-1])
        else:
            u, s = _merge_proxy_over(panel, axes)
    else:
        raise ValueError(f"unknown merge_mode {merge_mode!r}")

    if not want_right:
        return u, s
    v_blk = lsvd.sparse_right_vectors(ids, rows, vals, rc, rm, width, u, s)
    return u, s, v_blk


def solve_shard_map(a: jax.Array, mesh: Mesh, *,
                    block_axes: Sequence[str], config):
    """The ``backend="shard_map"`` engine behind ``repro.core.api.svd``
    (and the legacy ``distributed_ranky_svd`` shim): unpacks the
    validated ``api.SolveConfig`` and runs the shard_map pipeline."""
    return _solve_shard_map(
        a, mesh,
        block_axes=tuple(block_axes),
        method=config.method,
        local_mode=config.local_mode,
        merge_mode=config.merge_mode,
        hierarchical=config.two_level,
        use_kernel=config.use_kernel,
        want_right=config.want_right,
        rank=config.rank,
        oversample=config.oversample,
        power_iters=config.power_iters,
        key=config.resolved_key(),
    )


def _solve_shard_map(
    a: jax.Array,
    mesh: Mesh,
    *,
    block_axes: Sequence[str] = ("model",),
    method: str = "neighbor_random",
    local_mode: str = "gram",
    merge_mode: str = "gram",
    hierarchical: bool = False,
    use_kernel: bool = False,
    want_right: bool = False,
    rank: Optional[int] = None,
    oversample: int = 8,
    power_iters: int = 2,
    key: Optional[jax.Array] = None,
):
    """Distributed Ranky SVD of a column-sharded short-and-fat matrix.

    Args:
      a: (M, N) array, placed with columns sharded over ``block_axes``
        (N must divide by the product of those axis sizes) — or a
        sparse.BlockEll whose block count equals that product, in which
        case each device owns one block's ELL arrays and the whole
        pipeline is sparse-native (gram-local only; merge collectives
        are identical to the dense path).
      mesh: the device mesh.
      block_axes: mesh axes the columns (= paper blocks) shard over.
        ``("pod", "model")`` + ``hierarchical=True`` gives the two-level
        tree merge.
      method: one of ranky.METHODS.
      merge_mode: "proxy" (paper) or "gram" (beyond-paper all-reduce).
      want_right: also return this device's shard of V — (N/D, M) for
        the exact paths, (N/D, k) for the randomized path —
        column-sharded like the input.
      rank: rank=k switches to the randomized truncated sketch path
        (core/randomized.py): rank repair still runs per device, then
        the only collectives are a (k+oversample, M) psum per power
        pass plus one (L, L) psum — no proxy gather, no M x M gram.
        This is the tall-row-regime path; ``merge_mode`` does not apply.

    Returns (U, S) replicated — or (U, S, V) with V column-sharded.
    """
    axes = tuple(block_axes)
    if key is None:
        key = ranky.default_key()
    d_total = 1
    for ax in axes:
        d_total *= mesh.shape[ax]

    if isinstance(a, sparse.BlockEll):
        if a.num_blocks != d_total:
            raise ValueError(
                f"BlockEll has {a.num_blocks} blocks; mesh axes {axes} "
                f"give {d_total} devices (one block per device)")
        if local_mode == "svd":
            raise ValueError(
                "the sparse path is gram-native; use local_mode='gram'")
        in_spec = (P(axes), P(axes), P(axes), P())
        out_spec = (P(), P()) if not want_right else (P(), P(), P(axes, None))
        fn = partial(
            _sparse_svd_shard_fn,
            m=a.m,
            width=a.width,
            axes=axes,
            method=method,
            merge_mode=merge_mode,
            hierarchical=hierarchical,
            use_kernel=use_kernel,
            want_right=want_right,
            rank=rank,
            oversample=oversample,
            power_iters=power_iters,
        )
        sharded = shard_map(fn, mesh=mesh, in_specs=in_spec,
                            out_specs=out_spec)
        blk_sh = NamedSharding(mesh, P(axes))
        ids = jax.device_put(jnp.asarray(a.col_ids), blk_sh)
        rows = jax.device_put(jnp.asarray(a.col_rows), blk_sh)
        vals = jax.device_put(jnp.asarray(a.col_vals), blk_sh)
        return jax.jit(sharded)(ids, rows, vals, key)

    if a.shape[1] % d_total:
        # Same friendly error as the BlockEll branch — without it the
        # shard_map call fails with an opaque XLA sharding error.
        raise ValueError(
            f"dense a has N={a.shape[1]} columns; mesh axes {axes} give "
            f"{d_total} devices and N must divide evenly (pad with "
            f"sparse.pad_to_block_multiple first — zero columns change "
            f"nothing about U or S)")
    in_spec = (P(None, axes), P())
    out_spec = (P(), P()) if not want_right else (P(), P(), P(axes, None))

    fn = partial(
        _svd_shard_fn,
        axes=axes,
        method=method,
        local_mode=local_mode,
        merge_mode=merge_mode,
        hierarchical=hierarchical,
        use_kernel=use_kernel,
        want_right=want_right,
        rank=rank,
        oversample=oversample,
        power_iters=power_iters,
    )
    sharded = shard_map(fn, mesh=mesh, in_specs=in_spec, out_specs=out_spec)
    a = jax.device_put(a, NamedSharding(mesh, P(None, axes)))
    return jax.jit(sharded)(a, key)


def distributed_ranky_svd(
    a: jax.Array,
    mesh: Mesh,
    *,
    block_axes: Sequence[str] = ("model",),
    method: str = "neighbor_random",
    local_mode: str = "gram",
    merge_mode: str = "gram",
    hierarchical: bool = False,
    use_kernel: bool = False,
    want_right: bool = False,
    rank: Optional[int] = None,
    oversample: int = 8,
    power_iters: int = 2,
    key: Optional[jax.Array] = None,
):
    """DEPRECATED legacy entry point — use ``repro.core.api.svd`` with a
    ``SolveConfig(backend="shard_map", ...)`` and ``mesh=``/
    ``block_axes=``.

    Thin shim: builds the SolveConfig (centralized validation) and runs
    the same ``solve_shard_map`` engine ``api.svd`` dispatches to, so
    the two surfaces are bit-identical.
    """
    import warnings

    from repro.core import api

    warnings.warn(
        "distributed_ranky_svd is deprecated; use repro.core.api.svd "
        "with SolveConfig(backend='shard_map', ...) and mesh=",
        DeprecationWarning, stacklevel=2)
    cfg = api.SolveConfig(
        backend="shard_map", method=method, local_mode=local_mode,
        merge_mode=merge_mode, two_level=hierarchical,
        use_kernel=use_kernel, want_right=want_right, rank=rank,
        oversample=oversample, power_iters=power_iters, key=key)
    return solve_shard_map(a, mesh, block_axes=block_axes, config=cfg)
