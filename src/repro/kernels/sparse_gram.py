"""Pallas TPU kernel: sparse gram G = E @ E^T from a padded-ELL block.

This is the sparse-native twin of kernels/blockgram.py.  The dense
kernel streams (M, block_n) column panels of A from HBM — at the paper's
5e-4 density that is >99.9% zeros through the MXU *and* the memory
system.  Here the operand is the BlockEll container (core/sparse.py):
per stored (= nonempty) column, up to K (row, value) slots.

Layout (ops.py transposes from the container's (C, K) and pads):
  rows (K, C) int32 — row index of slot k of stored column c
  vals (K, C) f32   — value (padding slots carry 0)

The (M, M) output is one (bm, bm) block up to bm = M, or tiled beyond
(ops.py picks the tile).  The gram is symmetric, so only the tiles on
and above the diagonal are computed: grid = (pairs, C/block_c) over the
(i <= j) tile pairs, which arrive as scalar-prefetched tile indices,
with the stored-column axis innermost.  Step (p, c) expands its
(K, block_c) ELL slice twice into dense (bm, block_c) panels in VMEM —
rows of output tile i and rows of tile j — with K one-hot compares
against a row iota (VPU work, K is small), then accumulates
panel_i @ panel_j^T on the MXU into one (bm, bm) f32 accumulator; the
lower tiles are mirrored from the upper ones afterwards.  VMEM per step
is independent of M: a tall batch tiles instead of asking for an
(M, M) accumulator.
HBM traffic is nnz-proportional: 8 bytes per ELL slot per output tile
instead of 4*M bytes per dense column, and the MXU contraction runs over
stored columns only (C ~ nnz) instead of all W columns.

Duplicate (column, row) slots accumulate additively, matching the
ref.py scatter-add oracle.
"""
from __future__ import annotations

import functools
from typing import Optional

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro import precision


def _sparse_gram_kernel(ti_ref, tj_ref, rows_ref, vals_ref, out_ref,
                        acc_ref, *, slots, one_tile):
    """One grid step: expand an ELL tile against output row tiles i and
    j of tile pair p, acc += P_i P_j^T; flush after the last
    stored-column tile.  With ``one_tile`` (the whole gram one block)
    the single panel is expanded once."""
    p, c = pl.program_id(0), pl.program_id(1)

    @pl.when(c == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    bm = acc_ref.shape[0]
    block_c = rows_ref.shape[1]
    row_iota = jax.lax.broadcasted_iota(jnp.int32, (bm, block_c), 0)

    def panel(tile):
        pan = jnp.zeros((bm, block_c), jnp.float32)
        for k in range(slots):  # static unroll; K is small (max column degree)
            pan += jnp.where(rows_ref[k:k + 1, :] - tile * bm == row_iota,
                             vals_ref[k:k + 1, :], 0.0)
        return pan

    pan_i = panel(ti_ref[p])
    acc_ref[...] += jax.lax.dot_general(
        pan_i,
        pan_i if one_tile else panel(tj_ref[p]),
        (((1,), (1,)), ((), ())),  # contract stored columns: P_i @ P_j^T
        precision=precision.MATMUL,
        preferred_element_type=jnp.float32,
    )

    @pl.when(c == pl.num_programs(1) - 1)
    def _flush():
        out_ref[...] = acc_ref[...]


@functools.partial(jax.jit,
                   static_argnames=("m", "block_m", "block_c", "interpret"))
def sparse_gram(
    rows: jnp.ndarray,
    vals: jnp.ndarray,
    m: int,
    *,
    block_m: Optional[int] = None,
    block_c: int = 512,
    interpret: bool = False,
) -> jnp.ndarray:
    """G = E @ E^T via the Pallas kernel, (m, m) in (block_m, block_m)
    tiles (one tile when block_m is None).  Requires m % block_m == 0,
    C % block_c == 0 and K % 8 == 0 (ops.py pads; val-0 slots are
    inert)."""
    k, c = rows.shape
    block_m = m if block_m is None else block_m
    if c % block_c:
        raise ValueError(f"C={c} must divide block_c={block_c}")
    if m % block_m:
        raise ValueError(f"M={m} must divide block_m={block_m}")
    tiles = m // block_m
    ti, tj = np.triu_indices(tiles)
    g = pl.pallas_call(
        functools.partial(_sparse_gram_kernel, slots=k, one_tile=tiles == 1),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(ti.size, c // block_c),
            in_specs=[
                pl.BlockSpec((k, block_c), lambda p, s, ti, tj: (0, s)),
                pl.BlockSpec((k, block_c), lambda p, s, ti, tj: (0, s)),
            ],
            out_specs=pl.BlockSpec((block_m, block_m),
                                   lambda p, s, ti, tj: (ti[p], tj[p])),
            scratch_shapes=[pltpu.VMEM((block_m, block_m), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((m, m), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(jnp.asarray(ti, jnp.int32), jnp.asarray(tj, jnp.int32), rows, vals)
    if tiles == 1:
        return g
    # Only the (i <= j) tiles were written: mirror the upper ones down.
    tile = jnp.arange(m) // block_m
    return jnp.where(tile[:, None] <= tile[None, :], g, g.T)
