"""Batched latent-factor top-k retrieval over a :class:`ServingSnapshot`.

The query path of the recommender front end: a batch of factor-space
queries ``q`` (B, k) scores every item as ``q . diag(s) V^T`` and keeps
the top ``k_top`` — the fused kernel (kernels/topk_score.py) never
materializes the (B, N) score matrix, so the per-query working set is
one (B, block_n) tile regardless of universe size.

Two backends, bit-identical results:

* **dense** — one :func:`ops.topk_score` call over the whole (n_pad, k)
  factor matrix (``valid_n`` masks the block padding);
* **sharded** — ``v`` stays sharded over the stream mesh (one column
  block per device, the R5d residency): each device runs the SAME fused
  kernel on its (W, k) slice with its global column offset, the
  (B, k_top) candidates are all-gathered device-major (ascending global
  index, so the oracle's ties-to-lowest-index rule survives the merge)
  and a final top-k over the D*k_top candidates is replicated back.

The int8 path scores ``(q . v_q[j]) * scale[j]`` — the per-item kvquant
scale folds into the contraction, no dequantized factor matrix is ever
resident.  Raw interaction rows project into factor space through
``V diag(1/s)`` (:func:`project_rows`).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro import obs
from repro.compat import shard_map_nocheck as shard_map
from repro.kernels import ops
from repro.kernels import ref as _ref
from repro.precision import mm
from repro.serve.snapshot import ServingSnapshot
from repro.stream.state import STREAM_AXIS, stream_mesh


@dataclasses.dataclass(frozen=True)
class TopKResult:
    """One answered request wave: per-query item ids + scores, stamped
    with the snapshot version that produced them (freshness audit)."""

    scores: jnp.ndarray   # (B, k_top) f32, descending
    indices: jnp.ndarray  # (B, k_top) i32 global item ids
    version: int


def fold_queries(snapshot: ServingSnapshot, queries: jnp.ndarray) -> jnp.ndarray:
    """(B, k) factor-space queries -> ``q * s`` (diag(s) folded in)."""
    return queries.astype(jnp.float32) * snapshot.s.astype(jnp.float32)[None, :]


def project_rows(snapshot: ServingSnapshot, rows: jnp.ndarray) -> jnp.ndarray:
    """(B, n) raw interaction rows -> (B, k) queries via ``V diag(1/s)``.

    A user's fresh interaction vector lands in the same factor space as
    ``u`` rows: ``a_b V diag(1/s)`` (the row-factor identity
    ``U = A V diag(1/s)``).  On the int8 snapshot the per-item scale
    folds into the rows — the f32 factor matrix is never materialized.
    Trailing padding rows of ``v`` meet zero-padded row entries, so the
    projection ignores them.
    """
    rows = rows.astype(jnp.float32)
    if rows.shape[1] != snapshot.n:
        raise ValueError(
            f"rows have {rows.shape[1]} columns but the snapshot's "
            f"universe has n={snapshot.n}")
    if snapshot.quantized:
        n_pad = snapshot.v_q.shape[0]
        rows = jnp.pad(rows, ((0, 0), (0, n_pad - snapshot.n)))
        scaled = rows * snapshot.v_scale[:, 0][None, :]
        proj = mm(scaled, snapshot.v_q.astype(jnp.float32))
    else:
        n_pad = snapshot.v.shape[0]
        rows = jnp.pad(rows, ((0, 0), (0, n_pad - snapshot.n)))
        proj = mm(rows, snapshot.v)
    return proj / snapshot.s.astype(jnp.float32)[None, :]


def user_queries(snapshot: ServingSnapshot, row_ids) -> jnp.ndarray:
    """Known-user queries: the stored ``u`` rows for ``row_ids``."""
    if snapshot.u_rows is None:
        raise ValueError(
            "snapshot has no u_rows: build it with keep_u=True for "
            "user-id lookups")
    return snapshot.u_rows[jnp.asarray(row_ids)]


def _factor_pair(
    snapshot: ServingSnapshot,
) -> Tuple[jnp.ndarray, Optional[jnp.ndarray]]:
    """(factor matrix, per-item scale or None) for the score contraction."""
    if snapshot.quantized:
        return snapshot.v_q, snapshot.v_scale[:, 0]
    return snapshot.v, None


def _local_topk(qs, v, k_top, *, scale, valid_n, index_offset, block_n,
                use_kernel):
    """One device's (or the dense path's) fused top-k; ``use_kernel=False``
    forces the jnp fallback (the oracle — full local score matrix) that
    planner rule R7 prices as ``serve_fallback_bytes``."""
    if not use_kernel:
        return _ref.topk_score(qs, v, k_top, scale=scale,
                               valid_n=valid_n, index_offset=index_offset)
    return ops.topk_score(qs, v, k_top, scale=scale, valid_n=valid_n,
                          index_offset=index_offset, block_n=block_n)


@functools.lru_cache(maxsize=None)
def _sharded_topk_fn(num_blocks, width, n, k_top, block_n, quantized,
                     use_kernel):
    """Jitted shard_map top-k for one static (universe, request) shape.

    Each device scores its (W, k) slice with its global column offset
    (off/valid are traced from axis_index, carried into the kernel as
    SMEM scalars), then the (B, k_top) local winners are all-gathered
    device-major and merged with one final top-k — stable, so ties still
    resolve to the lowest global index, bit-identical to the dense path.
    """
    mesh = stream_mesh(num_blocks)

    def fn(qs, v, scale):
        d = jax.lax.axis_index(STREAM_AXIS)
        off = (d * width).astype(jnp.int32)
        valid = jnp.clip(n - off, 0, width).astype(jnp.int32)
        vals, idx = _local_topk(
            qs, v, k_top,
            scale=scale[:, 0] if quantized else None,
            valid_n=valid, index_offset=off, block_n=block_n,
            use_kernel=use_kernel,
        )
        cand_v = jax.lax.all_gather(vals, STREAM_AXIS)  # (D, B, k_top)
        cand_i = jax.lax.all_gather(idx, STREAM_AXIS)
        b = qs.shape[0]
        cand_v = jnp.swapaxes(cand_v, 0, 1).reshape(b, -1)
        cand_i = jnp.swapaxes(cand_i, 0, 1).reshape(b, -1)
        fv, pos = jax.lax.top_k(cand_v, k_top)
        return fv, jnp.take_along_axis(cand_i, pos, axis=1)

    blk = P(STREAM_AXIS, None)
    sharded = shard_map(
        fn, mesh=mesh,
        in_specs=(P(), blk, blk), out_specs=(P(), P()),
    )
    return jax.jit(sharded)


def score_topk(
    snapshot: ServingSnapshot,
    queries: jnp.ndarray,
    k_top: int,
    *,
    block_n: int = 512,
    sharded: bool = False,
    use_kernel: bool = True,
    plan_bytes: Optional[int] = None,
) -> TopKResult:
    """Answer one request wave: top ``k_top`` items per query row.

    ``queries`` are factor-space rows (B, k) — use :func:`project_rows`
    for raw interaction deltas or :func:`user_queries` for known users.

    ``plan_bytes`` (the R7 closed-form estimate, threaded down by
    ``api.serve_topk``) arms the drift monitor when observability is
    on: the compiled wave's measured peak is priced once per shape via
    compile-only lowering — no extra dispatch — and recorded as the
    ``drift_ratio{rule="R7"}`` gauge.
    """
    if queries.ndim != 2 or queries.shape[1] != snapshot.rank:
        raise ValueError(
            f"queries must be (B, {snapshot.rank}) factor-space rows, "
            f"got {queries.shape}")
    if not 0 < k_top <= snapshot.n:
        raise ValueError(
            f"k_top={k_top} must be in (0, n={snapshot.n}]")
    qs = fold_queries(snapshot, queries)
    factors, scale = _factor_pair(snapshot)
    if sharded:
        width = factors.shape[0] // snapshot.num_blocks
        fn = _sharded_topk_fn(
            snapshot.num_blocks, width, snapshot.n, k_top, block_n,
            snapshot.quantized, use_kernel)
        if snapshot.quantized:
            scale_arg = snapshot.v_scale
        else:
            # unused by the body; a (D, 1) placeholder keeps the
            # shard_map signature uniform without shipping n_pad floats
            scale_arg = jnp.zeros((snapshot.num_blocks, 1), jnp.float32)
        if plan_bytes is not None and obs.enabled():
            # memory_analysis on the SPMD jit reports PER-DEVICE sizes,
            # matching serving_bytes(..., per_device=True) in the plan.
            obs.observe_compiled(
                "R7", lambda: fn, (qs, factors, scale_arg), plan_bytes,
                component="total", label="sharded")
        vals, idx = fn(qs, factors, scale_arg)
        return TopKResult(vals, idx, snapshot.version)
    if plan_bytes is not None and obs.enabled():
        valid_n, off = snapshot.n, 0
        if scale is None:
            make = lambda: jax.jit(lambda q, f: _local_topk(
                q, f, k_top, scale=None, valid_n=valid_n, index_offset=off,
                block_n=block_n, use_kernel=use_kernel))
            drift_args = (qs, factors)
        else:
            make = lambda: jax.jit(lambda q, f, sc: _local_topk(
                q, f, k_top, scale=sc, valid_n=valid_n, index_offset=off,
                block_n=block_n, use_kernel=use_kernel))
            drift_args = (qs, factors, scale)
        obs.observe_compiled("R7", make, drift_args, plan_bytes,
                             component="total", label="dense")
    vals, idx = _local_topk(
        qs, factors, k_top,
        scale=scale, valid_n=snapshot.n, index_offset=0, block_n=block_n,
        use_kernel=use_kernel)
    return TopKResult(vals, idx, snapshot.version)
