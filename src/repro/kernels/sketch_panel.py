"""Pallas TPU kernel: sketch panel S = Omega @ E from a padded-ELL block.

The randomized range finder (core/randomized.py) contracts an (L, M)
test matrix against each sparse column block, L = rank + oversample.
Like kernels/sparse_gram.py the operand is the BlockEll container
(core/sparse.py): per stored column, up to K (row, value) slots.

Layout (ops.py transposes from the container's (C, K) and pads):
  omega (L, Mp) f32  — test matrix, M padded to the block_m grid
  rows  (K, C)  int32 — row index of slot k of stored column c
  vals  (K, C)  f32   — value (padding slots carry 0)

Grid = (C/block_c, Mp/block_m) with the M axis innermost: each step
expands its (K, block_c) ELL slice into a dense (block_m, block_c)
panel in VMEM with K one-hot compares against a row iota offset to the
M tile (VPU work, K is small), then accumulates
``omega_tile @ panel`` on the MXU into the (L, block_c) output tile.
HBM traffic is one pass over omega per C tile plus 8 bytes per ELL
slot — never the (M, W) dense block.

Duplicate (column, row) slots accumulate additively, matching the
ref.py gather-and-reduce oracle.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro import precision


def _sketch_panel_kernel(omega_ref, rows_ref, vals_ref, out_ref, *, slots):
    """One grid step: expand an ELL tile against one M tile, accumulate."""
    j = pl.program_id(1)

    block_m = omega_ref.shape[1]
    block_c = rows_ref.shape[1]
    row_iota = jax.lax.broadcasted_iota(jnp.int32, (block_m, block_c), 0) \
        + j * block_m
    panel = jnp.zeros((block_m, block_c), jnp.float32)
    for k in range(slots):  # static unroll; K is small (max column degree)
        panel += jnp.where(rows_ref[k:k + 1, :] == row_iota,
                           vals_ref[k:k + 1, :], 0.0)
    contrib = jax.lax.dot_general(
        omega_ref[...],
        panel,
        (((1,), (0,)), ((), ())),  # (L, block_m) @ (block_m, block_c)
        precision=precision.MATMUL,
        preferred_element_type=jnp.float32,
    )

    @pl.when(j == 0)
    def _init():
        out_ref[...] = contrib

    @pl.when(j > 0)
    def _acc():
        out_ref[...] += contrib


@functools.partial(jax.jit,
                   static_argnames=("block_c", "block_m", "interpret"))
def sketch_panel(
    omega: jnp.ndarray,
    rows: jnp.ndarray,
    vals: jnp.ndarray,
    *,
    block_c: int = 512,
    block_m: int = 512,
    interpret: bool = False,
) -> jnp.ndarray:
    """S = Omega @ E ((L, C) over stored columns) via the Pallas kernel.
    Requires L % 8 == 0, Mp % block_m == 0, C % block_c == 0 and
    K % 8 == 0 (ops.py pads; val-0 slots are inert)."""
    l, mp = omega.shape
    k, c = rows.shape
    if c % block_c:
        raise ValueError(f"C={c} must divide block_c={block_c}")
    if mp % block_m:
        raise ValueError(f"Mp={mp} must divide block_m={block_m}")
    grid = (c // block_c, mp // block_m)  # M innermost: sequential acc
    return pl.pallas_call(
        functools.partial(_sketch_panel_kernel, slots=k),
        grid=grid,
        in_specs=[
            pl.BlockSpec((l, block_m), lambda i, j: (0, j)),
            pl.BlockSpec((k, block_c), lambda i, j: (0, i)),
            pl.BlockSpec((k, block_c), lambda i, j: (0, i)),
        ],
        out_specs=pl.BlockSpec((l, block_c), lambda i, j: (0, i)),
        out_shape=jax.ShapeDtypeStruct((l, c), jnp.float32),
        interpret=interpret,
    )(omega, rows, vals)
