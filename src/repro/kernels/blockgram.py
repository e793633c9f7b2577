"""Pallas TPU kernel: block gram G = A @ A^T for short-and-fat blocks.

This is the FLOP hot-spot of the TPU-native Ranky local factorization
(core/svd.py local_svd_gram): an (M x N_b) block with M ~ O(100..1k) and
N_b ~ O(100k) reduces to an (M x M) gram.  Arithmetic intensity is high
(each loaded column of A participates in M MACs), so the kernel streams
N-tiles of A HBM -> VMEM and accumulates the full (M x M) gram in a VMEM
scratch buffer that never leaves the chip until the last tile.

Tiling: grid = (N // block_n,); each step loads an (M, block_n) panel.
M is padded to a multiple of 128 by ops.py so both MXU operands are
lane-aligned; block_n defaults to 512 giving a (128..512, 512) panel
comfortably inside the ~16 MiB/core VMEM and a 128-multiple contraction.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro import precision


def _gram_kernel(a_ref, out_ref, acc_ref):
    """One grid step: acc += A_tile @ A_tile^T ; flush on the last tile."""
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    tile = a_ref[...].astype(jnp.float32)  # (M, block_n)
    acc_ref[...] += jax.lax.dot_general(
        tile,
        tile,
        (((1,), (1,)), ((), ())),  # contract the N dimension: A @ A^T
        precision=precision.MATMUL,
        preferred_element_type=jnp.float32,
    )

    @pl.when(i == pl.num_programs(0) - 1)
    def _flush():
        out_ref[...] = acc_ref[...]


@functools.partial(jax.jit, static_argnames=("block_n", "interpret"))
def blockgram(
    a_blk: jnp.ndarray,
    *,
    block_n: int = 512,
    interpret: bool = False,
) -> jnp.ndarray:
    """G = A @ A^T via the Pallas kernel.  Requires M % 8 == 0 and
    N % block_n == 0 (ops.py pads; zero columns don't change the gram)."""
    m, n = a_blk.shape
    if n % block_n:
        raise ValueError(f"N={n} must divide block_n={block_n}")
    grid = (n // block_n,)
    return pl.pallas_call(
        _gram_kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((m, block_n), lambda i: (0, i))],
        out_specs=pl.BlockSpec((m, m), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((m, m), jnp.float32),
        scratch_shapes=[pltpu.VMEM((m, m), jnp.float32)],
        interpret=interpret,
    )(a_blk)
