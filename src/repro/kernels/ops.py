"""Public jit'd wrappers around the Pallas kernels.

Handles: backend dispatch (compiled Pallas on TPU, interpret=True
elsewhere, pure-jnp oracle as an escape hatch via REPRO_KERNELS=ref),
shape padding to hardware-aligned tiles, and dtype policy (bf16 inputs,
f32 accumulation).
"""
from __future__ import annotations

import math
import os
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels import ref as _ref
from repro.kernels import blockgram as _bg
from repro.kernels import flash_attention as _fa
from repro.kernels import sketch_panel as _sp
from repro.kernels import sparse_gram as _sg
from repro.kernels import ssd_scan as _ssd
from repro.kernels import topk_score as _tk


def _mode() -> str:
    """'pallas' (compiled), 'interpret' (kernel emulation), or 'ref'
    (pure-jnp oracle).  Non-TPU backends default to 'ref': it is
    differentiable and lowers clean HLO; 'interpret' executes the actual
    kernel bodies and is what the kernel test-suite pins against."""
    env = os.environ.get("REPRO_KERNELS", "auto")
    if env in ("ref", "interpret", "pallas"):
        return env
    return "pallas" if jax.default_backend() == "tpu" else "ref"


# Widths beyond which a kernel no longer fits the v5e's 16 MiB of scoped
# VMEM (found by compiling for a described v5e; the next width up is
# refused).  The ELL kernels unroll K slots (the largest column degree of
# a block) into (rows, block_c) temporaries; blockgram keeps one (M, M)
# accumulator.  sparse_gram keeps one (tile, tile) accumulator: its
# (M, M) output is one block up to MAX_GRAM_BLOCK rows (768 is refused
# at K=128) and tiles of at most MAX_GRAM_TILE rows beyond (a tile
# expands two panels).  A call past a limit raises instead of quietly
# routing to XLA.
MAX_ELL_SLOTS = {"sparse_gram": 128, "sketch_panel": 512}
MAX_BLOCKGRAM_ROWS = 1024
MAX_GRAM_BLOCK = 640
MAX_GRAM_TILE = 512


def _check_slots(kernel: str, slots: int) -> None:
    limit = MAX_ELL_SLOTS[kernel]
    if slots > limit:
        raise ValueError(
            f"{kernel}: a column with {slots} stored slots exceeds the "
            f"kernel limit MAX_ELL_SLOTS[{kernel!r}]={limit} (K, the "
            f"largest column degree of a block); run this input with "
            f"use_kernel=False")


def _pad_axis(x: jnp.ndarray, axis: int, multiple: int) -> Tuple[jnp.ndarray, int]:
    size = x.shape[axis]
    pad = (-size) % multiple
    if pad == 0:
        return x, 0
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths), pad


def blockgram(a_blk: jnp.ndarray, *, block_n: int = 512) -> jnp.ndarray:
    """G = A @ A^T (f32) for a short-and-fat block; pads M to the 8-sublane
    grid and N to block_n (zero columns leave the gram unchanged)."""
    mode = _mode()
    if mode == "ref":
        return _ref.blockgram(a_blk)
    m = a_blk.shape[0]
    if m > MAX_BLOCKGRAM_ROWS:
        raise ValueError(
            f"blockgram: M={m} rows exceed the kernel limit "
            f"MAX_BLOCKGRAM_ROWS={MAX_BLOCKGRAM_ROWS} (one (M, M) f32 "
            f"accumulator in VMEM); run this input with use_kernel=False")
    a_pad, pad_m = _pad_axis(a_blk, 0, 8)
    block_n = min(block_n, max(128, a_pad.shape[1]))
    a_pad, _ = _pad_axis(a_pad, 1, block_n)
    g = _bg.blockgram(a_pad, block_n=block_n, interpret=(mode == "interpret"))
    return g[:m, :m] if pad_m else g


def _ell_tiles(
    col_rows: jnp.ndarray, col_vals: jnp.ndarray, block_c: int
) -> Tuple[jnp.ndarray, jnp.ndarray, int]:
    """Shared ELL kernel layout: transpose (C, K) -> (K, C) so the lane
    dim is stored columns, pad K to 8 sublanes and C to block_c lanes
    (clamped to the data).  Padding slots carry val 0 / row 0 and are
    inert.  Returns (rows_t, vals_t, block_c)."""
    rows_t = col_rows.astype(jnp.int32).T
    vals_t = col_vals.astype(jnp.float32).T
    rows_t, _ = _pad_axis(rows_t, 0, 8)
    vals_t, _ = _pad_axis(vals_t, 0, 8)
    block_c = min(block_c, max(128, rows_t.shape[1]))
    rows_t, _ = _pad_axis(rows_t, 1, block_c)
    vals_t, _ = _pad_axis(vals_t, 1, block_c)
    return rows_t, vals_t, block_c


def _gram_tiles(m: int) -> Tuple[int, int]:
    """(tile, padded M) for sparse_gram's (M, M) output: one 8-aligned
    block up to MAX_GRAM_BLOCK rows, else the fewest 128-aligned tiles
    of at most MAX_GRAM_TILE rows."""
    m8 = -(-m // 8) * 8
    if m8 <= MAX_GRAM_BLOCK:
        return m8, m8
    tiles = -(-m8 // MAX_GRAM_TILE)
    tile = -(-m8 // (tiles * 128)) * 128
    return tile, tiles * tile


def sparse_gram(
    col_rows: jnp.ndarray,
    col_vals: jnp.ndarray,
    m: int,
    *,
    block_c: int = 512,
) -> jnp.ndarray:
    """G = E @ E^T ((M, M) f32) from one block's padded-ELL arrays
    (C, K) — see core/sparse.py:BlockEll.  M pads to the tile grid of
    :func:`_gram_tiles`, K to 8 sublanes and C to block_c lanes; padding
    slots carry val 0 so they are inert in both the kernel and the
    oracle."""
    mode = _mode()
    if mode == "ref":
        return _ref.sparse_gram(col_rows, col_vals, m)
    _check_slots("sparse_gram", col_rows.shape[1])
    rows_t, vals_t, block_c = _ell_tiles(col_rows, col_vals, block_c)
    tile, m_pad = _gram_tiles(m)
    g = _sg.sparse_gram(rows_t, vals_t, m_pad, block_m=tile, block_c=block_c,
                        interpret=(mode == "interpret"))
    return g[:m, :m] if m_pad != m else g


def sketch_panel(
    omega: jnp.ndarray,
    col_rows: jnp.ndarray,
    col_vals: jnp.ndarray,
    *,
    block_c: int = 512,
    block_m: int = 512,
) -> jnp.ndarray:
    """S = Omega @ E ((L, C) f32) — the (L, M) test matrix contracted
    against one block's padded-ELL arrays (C, K), restricted to stored
    columns (see core/randomized.py; callers scatter through col_ids).
    Pads L to the 8-sublane grid, M to block_m lanes, K to 8 sublanes
    and C to block_c lanes; padding slots carry val 0 / row 0 so they
    are inert in both the kernel and the oracle."""
    mode = _mode()
    if mode == "ref":
        return _ref.sketch_panel(omega, col_rows, col_vals)
    _check_slots("sketch_panel", col_rows.shape[1])
    l, c = omega.shape[0], col_rows.shape[0]
    om = omega.astype(jnp.float32)
    om, _ = _pad_axis(om, 0, 8)
    block_m = min(block_m, max(128, om.shape[1]))
    om, _ = _pad_axis(om, 1, block_m)
    rows_t, vals_t, block_c = _ell_tiles(col_rows, col_vals, block_c)
    out = _sp.sketch_panel(om, rows_t, vals_t, block_c=block_c,
                           block_m=block_m, interpret=(mode == "interpret"))
    return out[:l, :c]


def flash_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    causal: bool = True,
    window: int = 0,
    softcap: float = 0.0,
    scale: Optional[float] = None,
    block_q: int = 128,
    block_k: int = 128,
) -> jnp.ndarray:
    """Fused GQA attention.  For causal self-attention (sq == sk) with
    unaligned lengths, Q and KV are both padded at the END: padded keys
    sit strictly in the future of every real query, so causality masks
    them and real rows are unchanged.  Other unaligned cases (cross /
    non-causal / right-aligned) fall back to the oracle."""
    mode = _mode()
    sq, sk = q.shape[2], k.shape[2]
    pq, pk = (-sq) % block_q, (-sk) % block_k
    need_pad = bool(pq or pk)
    if mode == "ref" or sq < 8 or \
            (need_pad and not (causal and sq == sk)):
        return _ref.flash_attention(
            q, k, v, causal=causal, window=window, softcap=softcap, scale=scale
        )
    if need_pad:
        # Q and KV must be padded to one COMMON length aligned to BOTH
        # block sizes: the kernel right-aligns queries by (sk - sq), so
        # unequal pads (e.g. Q by pq, KV by pk) would shift every real
        # query's position and mis-mask real rows whenever
        # block_q != block_k.  Equal padding keeps the offset at 0 and
        # the padded keys strictly in the future of every real query,
        # where causality masks them.
        step = block_q * block_k // math.gcd(block_q, block_k)
        target = -(-sq // step) * step
        q = jnp.pad(q, ((0, 0), (0, 0), (0, target - sq), (0, 0)))
        k = jnp.pad(k, ((0, 0), (0, 0), (0, target - sk), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, target - sk), (0, 0)))
    out = _fa.flash_attention(
        q, k, v,
        causal=causal, window=window, softcap=softcap, scale=scale,
        block_q=block_q, block_k=block_k, interpret=(mode == "interpret"),
    )
    return out[:, :, :sq, :] if need_pad else out


def topk_score(
    qs: jnp.ndarray,
    v: jnp.ndarray,
    k_top: int,
    *,
    scale: Optional[jnp.ndarray] = None,
    valid_n=None,
    index_offset=0,
    block_n: int = 512,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Top-k of ``qs @ v.T`` without materializing the (B, N) scores.

    qs is (B, k) queries with diag(s) folded in; v is (N, k) item
    factors (f32, or int8 with per-item ``scale`` (N,) folded into the
    score).  Returns (vals (B, k_top) f32, idx (B, k_top) i32), scores
    descending, ties broken by lowest index — bit-identical to the ref
    oracle.  ``valid_n`` (default N) masks trailing padding rows of v;
    ``index_offset`` shifts emitted indices; both may be traced scalars
    (the sharded serving backend passes per-device values).  Pads B to
    the 8-sublane grid, the factor dim to 128 lanes (zero columns are
    inert in the contraction) and N to block_n tiles (masked to -inf by
    ``valid_n`` so they can never be selected); requires k_top <= valid
    rows so padding never reaches the output.
    """
    mode = _mode()
    if mode == "ref":
        return _ref.topk_score(
            qs, v, k_top,
            scale=scale, valid_n=valid_n, index_offset=index_offset,
        )
    b, n = qs.shape[0], v.shape[0]
    if valid_n is None:
        valid_n = n
    qs_pad, pad_b = _pad_axis(qs.astype(jnp.float32), 0, 8)
    qs_pad, _ = _pad_axis(qs_pad, 1, 128)
    v_pad, _ = _pad_axis(v, 1, 128)
    block_n = min(block_n, max(128, n))
    v_pad, _ = _pad_axis(v_pad, 0, block_n)
    if scale is None:
        scale2 = jnp.ones((v_pad.shape[0], 1), jnp.float32)
    else:
        scale2, _ = _pad_axis(
            scale.astype(jnp.float32).reshape(-1, 1), 0, block_n
        )
    vals, idx = _tk.topk_score(
        qs_pad, v_pad, scale2, valid_n, index_offset,
        k_top=k_top, block_n=block_n, interpret=(mode == "interpret"),
    )
    return (vals[:b], idx[:b]) if pad_b else (vals, idx)


def ssd_scan(
    x: jnp.ndarray,
    dt: jnp.ndarray,
    a: jnp.ndarray,
    b_mat: jnp.ndarray,
    c_mat: jnp.ndarray,
    *,
    chunk: int = 128,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Mamba-2 SSD chunked scan; returns (y, final_state)."""
    from repro import perf

    mode = _mode()
    seq = x.shape[1]
    if mode == "ref" or seq % chunk or seq < chunk:
        if perf.enabled("ssd_chunked") and seq % chunk == 0 and seq >= chunk:
            return _ref.ssd_scan_chunked(x, dt, a, b_mat, c_mat, chunk=chunk)
        return _ref.ssd_scan(x, dt, a, b_mat, c_mat, return_state=True)
    return _ssd.ssd_scan(
        x, dt, a, b_mat, c_mat, chunk=chunk, interpret=(mode == "interpret")
    )
