"""Fused score+top-k Pallas kernel for the serving path.

Scores a batch of factor-space queries against the item factor matrix —
``scores = qs @ v.T`` with ``diag(s)`` already folded into ``qs`` — and
keeps a running per-row top-k across column tiles, so the full (B, N)
score matrix is never materialized: the working set is one (B, block_n)
tile plus the (B, k_top) running buffers, independent of N.

Selection semantics (the bit-identity contract with the ref oracle):
scores descending, ties broken by lowest global column index.  The
running buffer is kept in that order, and each tile's candidates are
appended AFTER it with ascending in-tile indices; since tiles are
visited in ascending column order, every candidate list is ordered by
ascending global index within equal scores, and first-occurrence argmax
selection reproduces ``jax.lax.top_k``'s documented tie rule exactly.

``valid`` masks padding columns (global index >= valid) to -inf so they
can never be selected; ``offset`` shifts returned indices (the sharded
backend passes per-device column offsets).  Both arrive as (1, 1) SMEM
scalars so they may be traced values inside shard_map.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro import precision

_NEG_INF = float("-inf")


def _select_topk(cand_vals, cand_idx, k_top):
    """First-occurrence selection sort: top k_top of the candidate row.

    cand_vals/cand_idx are (B, C).  Returns ((B, k_top), (B, k_top))
    ordered by descending value, ties by candidate position (which the
    callers arrange to be ascending global index).  k_top static, so the
    loop unrolls at trace time.
    """
    b, c = cand_vals.shape
    cols = jax.lax.broadcasted_iota(jnp.int32, (b, c), 1)
    out_vals = []
    out_idx = []
    for _ in range(k_top):
        best = jnp.max(cand_vals, axis=1, keepdims=True)          # (B, 1)
        pos = jnp.argmax(cand_vals, axis=1)[:, None]              # (B, 1)
        hit = cols == pos                                          # (B, C)
        out_vals.append(best[:, 0])
        out_idx.append(jnp.sum(jnp.where(hit, cand_idx, 0), axis=1))
        cand_vals = jnp.where(hit, _NEG_INF, cand_vals)
    return (
        jnp.stack(out_vals, axis=1),
        jnp.stack(out_idx, axis=1).astype(jnp.int32),
    )


def _topk_score_kernel(
    valid_ref,   # (1, 1) SMEM i32: columns >= valid are padding
    offset_ref,  # (1, 1) SMEM i32: added to emitted indices
    qs_ref,      # (B, k) VMEM f32 queries, diag(s) folded in
    v_ref,       # (block_n, k) VMEM factor tile (f32 or int8)
    scale_ref,   # (block_n, 1) VMEM f32 per-item dequant scales
    vals_ref,    # (B, k_top) VMEM f32 out
    idx_ref,     # (B, k_top) VMEM i32 out
    run_vals,    # (B, k_top) VMEM f32 scratch: running top-k values
    run_idx,     # (B, k_top) VMEM i32 scratch: running top-k indices
    *,
    k_top: int,
):
    t = pl.program_id(0)
    b, _ = qs_ref.shape
    block_n = v_ref.shape[0]

    @pl.when(t == 0)
    def _init():
        run_vals[...] = jnp.full_like(run_vals, _NEG_INF)
        run_idx[...] = jnp.zeros_like(run_idx)

    tile = v_ref[...].astype(jnp.float32)                          # (BN, k)
    scores = jax.lax.dot_general(
        qs_ref[...], tile, (((1,), (1,)), ((), ())),
        precision=precision.MATMUL,
        preferred_element_type=jnp.float32,
    )                                                              # (B, BN)
    scores = scores * scale_ref[...][:, 0][None, :]
    local = jax.lax.broadcasted_iota(jnp.int32, (b, block_n), 1)
    col = local + t * block_n                                      # global
    scores = jnp.where(col < valid_ref[0, 0], scores, _NEG_INF)

    cand_vals = jnp.concatenate([run_vals[...], scores], axis=1)
    cand_idx = jnp.concatenate([run_idx[...], col], axis=1)
    new_vals, new_idx = _select_topk(cand_vals, cand_idx, k_top)
    run_vals[...] = new_vals
    run_idx[...] = new_idx

    @pl.when(t == pl.num_programs(0) - 1)
    def _flush():
        vals_ref[...] = run_vals[...]
        idx_ref[...] = run_idx[...] + offset_ref[0, 0]


@functools.partial(
    jax.jit, static_argnames=("k_top", "block_n", "interpret")
)
def topk_score(
    qs: jnp.ndarray,      # (B, k) f32, B a multiple of 8, k of 128
    v: jnp.ndarray,       # (n_pad, k), n_pad a multiple of block_n
    scale: jnp.ndarray,   # (n_pad, 1) f32 (ones on the f32 path)
    valid,                # scalar i32: columns >= valid are padding
    offset,               # scalar i32: added to emitted indices
    *,
    k_top: int,
    block_n: int = 512,
    interpret: bool = False,
):
    """(vals (B, k_top) f32, idx (B, k_top) i32), oracle-bit-identical."""
    b, k = qs.shape
    n_pad = v.shape[0]
    assert n_pad % block_n == 0, (n_pad, block_n)
    grid = (n_pad // block_n,)
    valid2 = jnp.asarray(valid, jnp.int32).reshape(1, 1)
    offset2 = jnp.asarray(offset, jnp.int32).reshape(1, 1)
    kernel = functools.partial(_topk_score_kernel, k_top=k_top)
    call = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1), lambda t: (0, 0), memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 1), lambda t: (0, 0), memory_space=pltpu.SMEM),
            pl.BlockSpec((b, k), lambda t: (0, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec(
                (block_n, k), lambda t: (t, 0), memory_space=pltpu.VMEM
            ),
            pl.BlockSpec(
                (block_n, 1), lambda t: (t, 0), memory_space=pltpu.VMEM
            ),
        ],
        out_specs=[
            pl.BlockSpec((b, k_top), lambda t: (0, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((b, k_top), lambda t: (0, 0), memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, k_top), jnp.float32),
            jax.ShapeDtypeStruct((b, k_top), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.VMEM((b, k_top), jnp.float32),
            pltpu.VMEM((b, k_top), jnp.int32),
        ],
        interpret=interpret,
    )
    with jax.named_scope("serve.topk"):
        return call(valid2, offset2, qs, v, scale)
