"""One-compilation streaming: a window of ingests in a single ``lax.scan``.

``stream/ingest.py`` folds one batch per jitted call, so at high batch
rates the Python dispatch + per-batch host syncs dominate the
O(nnz * k) math.  The Iwen-Ong merge that :func:`hierarchy.merge_svd`
implements is associative and *fixed-shape per step* once the state sits
at ``truncate_rank``, which makes a whole window of ingests expressible
as one rolled ``lax.scan``:

* **Bucketing prologue** — variable-size deltas are padded to a small
  set of canonical shapes (rows to the next power of two >= 8; an ELL
  delta's stored-column capacity ``(C, K)`` likewise), so a stream of
  ragged batches reuses a handful of compiled scans instead of
  retracing per shape.  :func:`bucket_signature` names the bucket,
  :func:`build_window` stacks a group of same-bucket deltas into the
  scan's ``xs``.

  Padded rows are **masked, not merely small**: a zero-padded row looks
  lonely, so the Ranky checkers would repair it — the step therefore
  repairs first and then *zeroes the invalid rows back out* (dense) or
  ANDs the repair mask with the row-validity mask (sparse) before any
  gram / panel touches the block.  A padded row thus contributes
  *exactly* 0 to every gram, adjacency and right panel, and the padded
  rows of the emitted ``u_b`` panels are sliced off (host-side
  ``true_m``) before they ever reach ``u``.  Padding slots in the ELL
  arrays are all-zero values — inert by the container's own convention.

* **Exact batch factorization outside the scan** — on the exact path
  each batch's gram is its own small program and its eigh the one
  program per batch height that every engine shares
  (``ingest.batch_left_vectors``); the scan receives the batches' U_b in
  ``xs``.  An eigh fused into the scan would recompile with every bucket
  and window length, and on a TPU it is by far the costliest compile.

* **Scan body** — the existing ingest math (repair -> factor -> panel
  merge) with the wrinkle that ``u`` grows with ``rows_seen`` and
  cannot live in a fixed-shape carry.  The carry holds
  ``(s, v, batch-index key-chain counter, lonely/repaired side-band
  accumulators)`` — all device-resident for the whole window — while
  the per-batch small rotation ``uk`` and the ``u_b`` panel are emitted
  as stacked scan outputs and folded into ``u`` once, after the scan.
  Batch ``b`` still draws ``fold_in(root, batches_seen + b)``: the
  batch index rides in the carry as a traced int32, so a
  resumed-from-checkpoint stream re-draws the same columns mid-window.

* **Loop mode is the same function** — a "per-batch loop" is nothing
  but length-1 windows through the *same* jitted scan, so scan-vs-loop
  A/B comparisons (and planner rule R6's honest degrade) share one code
  path and are bit-identical by construction.

* **Sharded windows** — the shard_map engine gets the same treatment
  with the scan *inside* the region: ``v`` stays column-block-sharded
  in the carry for the whole window, collectives per step mirror
  ``ingest_shard_map``, and no device ever materializes anything
  N-sized — planner rule R5d's per-device flat-peak invariant holds for
  the window, not just a batch (rule R6's per-device form).

* **Tail-adaptive merge width** — :func:`adaptive_oversample` picks the
  exact path's merge width ``l_b = k + p_eff`` from the observed
  spectral tail of the running state (Li et al., arXiv:1612.08709: a
  fast-decaying spectrum needs little oversampling) instead of the
  static ``k + oversample``; widths are quantized so a drift in the
  tail re-buckets rarely.

Side-band counters stay device arrays for the whole window and are
materialized into Python ints ONCE per window (a single device_get),
not once per batch — the per-ingest host sync that serialized the
legacy loop is gone.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import List, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import obs
from repro.compat import shard_map_nocheck as shard_map
from repro.core import hierarchy, planner, randomized, ranky, sparse
from repro.core import svd as lsvd
from repro.precision import mm
from repro.stream import state as stream_state
from repro.stream.ingest import (IngestInfo, _dense_repair_shard,
                                 _fire_seam, _merge_truncate_local,
                                 _sparse_repair_shard, batch_left_vectors)
from repro.stream.state import STREAM_AXIS, StreamingSVDState

# Smallest row bucket: padding everything below 8 rows to one shape
# costs a few masked rows and saves a compile per tiny-batch size.
MIN_BUCKET_ROWS = 8

# Dispatch bookkeeping (benchmarks/streaming_scan.py reads these): one
# "window" is one jitted-callable invocation, however many batches rode
# inside it.  The legacy loop would have counted windows == batches.
_DISPATCH = {"windows": 0, "batches": 0}

# Every built scan callable, keyed by its static bucket signature —
# lets tests/benchmarks assert "one trace per bucket shape, not per
# batch" via jit's _cache_size() (number of argument avals traced).
_BUILT = {}


def dispatch_counts() -> dict:
    """{"windows": jitted dispatches, "batches": batches ingested}."""
    return dict(_DISPATCH)


def reset_dispatch_counts() -> None:
    for k in _DISPATCH:
        _DISPATCH[k] = 0


def trace_count() -> int:
    """Total number of traces across every built scan callable (each
    distinct window length T adds one aval to its bucket's jit cache)."""
    return sum(fn._cache_size() for fn in _BUILT.values())


def bucket_count() -> int:
    """Number of distinct bucket shapes that built a scan callable."""
    return len(_BUILT)


def clear_caches() -> None:
    """Forget every built scan (fresh compile-count measurements)."""
    _window_fn.cache_clear()
    _sharded_window_fn.cache_clear()
    _gram_fn.cache_clear()
    _sharded_gram_fn.cache_clear()
    _BUILT.clear()
    reset_dispatch_counts()


# ---------------------------------------------------------------------------
# Bucketing prologue
# ---------------------------------------------------------------------------

def _pow2_at_least(x: int) -> int:
    """Smallest power of two >= x (x >= 1)."""
    return 1 << max(0, int(x) - 1).bit_length()


def bucket_rows(m_b: int) -> int:
    """Canonical padded row count of a batch: next power of two >= 8."""
    return max(MIN_BUCKET_ROWS, _pow2_at_least(m_b))


def bucket_signature(a_norm) -> Tuple:
    """Canonical scan-bucket shape of a NORMALIZED delta (the output of
    ``stream.state.as_delta``): every delta with the same signature runs
    through the same compiled scan.

    * dense (m_b, n_pad) array -> ``("dense", m_pad)``
    * BlockEll                 -> ``("ell", m_pad, C_pad, K_pad)``

    Rows pad to the next power of two >= 8; an ELL delta's stored-column
    capacity ``(C, K)`` pads the same way (all-zero padding slots are
    inert by the container's convention), so COO batches whose nnz
    drifts a little still land in one bucket.
    """
    if isinstance(a_norm, sparse.BlockEll):
        c, k = a_norm.capacity
        return ("ell", bucket_rows(a_norm.m),
                _pow2_at_least(max(8, c)), _pow2_at_least(max(1, k)))
    m_b = int(a_norm.shape[0])
    return ("dense", bucket_rows(m_b))


def bucket_nnz_slots(sig: Tuple, num_blocks: int) -> Optional[int]:
    """Stored slots of one bucketed ELL batch (None for dense buckets) —
    the ``nnz_slots`` the R6 closed form prices a window's inputs with."""
    if sig[0] != "ell":
        return None
    return num_blocks * sig[2] * sig[3]


def _pad_dense(a_norm, m_pad: int) -> np.ndarray:
    a = np.asarray(a_norm, np.float32)
    if a.shape[0] == m_pad:
        return a
    out = np.zeros((m_pad, a.shape[1]), np.float32)
    out[:a.shape[0]] = a
    return out


def _pad_ell(e: "sparse.BlockEll", c_pad: int, k_pad: int):
    d, c = e.col_ids.shape
    k = e.col_vals.shape[2]
    ids = np.zeros((d, c_pad), np.int32)
    rows = np.zeros((d, c_pad, k_pad), np.int32)
    vals = np.zeros((d, c_pad, k_pad), np.float32)
    ids[:, :c] = np.asarray(e.col_ids)
    rows[:, :c, :k] = np.asarray(e.col_rows)
    vals[:, :c, :k] = np.asarray(e.col_vals)
    return ids, rows, vals


def build_window(norm_deltas: Sequence, true_m: Sequence[int], sig: Tuple):
    """Stack a group of same-bucket normalized deltas into the scan's
    ``xs`` (host-side padding, ONE device transfer per array).  Returns
    ``xs`` — dense: ``(a (T, m_pad, n_pad), tm (T,))``; ell:
    ``(ids (T, D, C), rows (T, D, C, K), vals (T, D, C, K), tm (T,))``.
    """
    tm = jnp.asarray(np.asarray(true_m, np.int32))
    if sig[0] == "dense":
        m_pad = sig[1]
        a = np.stack([_pad_dense(x, m_pad) for x in norm_deltas])
        return (jnp.asarray(a), tm)
    _, _, c_pad, k_pad = sig
    padded = [_pad_ell(x, c_pad, k_pad) for x in norm_deltas]
    ids = jnp.asarray(np.stack([p[0] for p in padded]))
    rows = jnp.asarray(np.stack([p[1] for p in padded]))
    vals = jnp.asarray(np.stack([p[2] for p in padded]))
    return (ids, rows, vals, tm)


# ---------------------------------------------------------------------------
# Tail-adaptive merge width (the l_b of planner rule R6)
# ---------------------------------------------------------------------------

def adaptive_oversample(s, rank: int, base: int) -> int:
    """Oversample p_eff for the exact merge width l_b = k + p_eff, from
    the observed spectral tail of the running state.

    ``tail = s[k-1] / s[0]`` measures how much weight the truncation
    boundary still carries: a fast-decaying spectrum (tail ~ 0) loses
    almost nothing to a narrow merge, a flat one (tail ~ 1) needs the
    full width to keep the discarded directions' energy (Li et al.,
    arXiv:1612.08709).  The tail interpolates p_eff over
    ``[max(4, base // 2), 2 * base]``, quantized to multiples of 4 so a
    slowly drifting tail re-buckets (and retraces) rarely.  Falls back
    to ``base`` while the state has no full-rank spectrum yet.
    """
    s = np.asarray(s, np.float64)
    if rank < 1 or s.size < rank or float(s[0]) <= 0.0:
        return base
    tail = float(np.clip(s[rank - 1] / s[0], 0.0, 1.0))
    lo, hi = max(4, base // 2), 2 * base
    p_eff = lo + tail * (hi - lo)
    return int(np.clip(int(round(p_eff / 4.0)) * 4, lo, hi))


# ---------------------------------------------------------------------------
# The scan step (single-host) — the ingest math with masked padding
# ---------------------------------------------------------------------------

def _repair_single(kind: str, d: int, m_pad: int, width: int, n_univ: int,
                   method: str, k_batch, xs):
    """Repair one bucketed batch with its padded rows inert: (blocks,
    lonely rows per block (D,), repairs made)."""
    tm = xs[-1]
    valid = jnp.arange(m_pad, dtype=jnp.int32) < tm      # (m_pad,) rows

    if kind == "dense":
        a = xs[0]                                        # (m_pad, n_pad)
        blocks0 = jnp.transpose(
            a.reshape(m_pad, d, width), (1, 0, 2))       # (D, m_pad, W)
        lonely_mask = jax.vmap(ranky.lonely_rows)(blocks0) & valid[None, :]
        blocks = ranky.split_and_repair(a, d, method, k_batch)
        # Mask, don't trust smallness: the checkers fill every lonely
        # row, padded ones included — zero the invalid rows back out so
        # they are EXACTLY absent from the grams and panels below.
        blocks = jnp.where(valid[None, :, None], blocks, 0.0)
        still = jax.vmap(ranky.lonely_rows)(blocks) & valid[None, :]
        repaired_b = (lonely_mask.sum() - still.sum()).astype(jnp.int32)
    else:
        ids, rows, vals = xs[0], xs[1], xs[2]            # (D, C[, K])
        lonely_mask = jax.vmap(
            lambda rr, vv: ranky.sparse_lonely_rows(rr, vv, m_pad)
        )(rows, vals) & valid[None, :]
        ell = sparse.BlockEll(ids, rows, vals,
                              m=m_pad, width=width, n=n_univ)
        rep = ranky.split_and_repair(ell, d, method, k_batch)
        rm = rep.repair_mask & valid[None, :]            # padded rows inert
        blocks = sparse.RepairedSparseBlocks(ell, rep.repair_cols, rm)
        repaired_b = rm.sum().astype(jnp.int32)

    lonely_pb = lonely_mask.sum(axis=1).astype(jnp.int32)  # (D,)
    return blocks, lonely_pb, repaired_b


def _gram_single(kind: str, d: int, m_pad: int, width: int, n_univ: int,
                 method: str, use_kernel: bool, key, bidx, *xs):
    """The exact path's batch gram (m_pad, m_pad) of one bucketed batch:
    the step's own repair, then the summed gram stack.  Its eigh runs
    outside (``ingest.batch_left_vectors``)."""
    with jax.named_scope("ranky.repair"):
        blocks, _, _ = _repair_single(kind, d, m_pad, width, n_univ,
                                      method, jax.random.fold_in(key, bidx),
                                      xs)
    with jax.named_scope("ranky.gram"):
        return lsvd.gram_stack(blocks, use_kernel=use_kernel).sum(axis=0)


def _step_single(kind: str, d: int, m_pad: int, width: int, n_univ: int,
                 r_b: int, k_state: int, sk_rank: Optional[int],
                 oversample: int, power_iters: int, method: str,
                 decay: float, key, carry, xs):
    s, v, bidx, lonely_acc, repaired_acc = carry
    k_batch = jax.random.fold_in(key, bidx)
    with jax.named_scope("ranky.repair"):
        blocks, lonely_pb, repaired_b = _repair_single(
            kind, d, m_pad, width, n_univ, method, k_batch, xs)

    if sk_rank is None:
        u_b = xs[-2]                       # (m_pad, r_b), from the gram's eigh
        with jax.named_scope("ranky.right"):
            panel_b = ranky.right_vectors_stack(
                blocks, u_b, jnp.ones((r_b,), jnp.float32))
    else:
        u_b, s_b, v_b = randomized.randomized_svd_blocks(
            blocks, rank=sk_rank, oversample=oversample,
            power_iters=power_iters, key=k_batch, want_right=True)
        panel_b = v_b * s_b[None, :]

    s_old = s * jnp.float32(decay)
    p = jnp.concatenate([v * s_old[None, :], panel_b], axis=1)
    v_new, s_new, uk = hierarchy.merge_svd(p, k_state)

    carry = (s_new, v_new, bidx + 1,
             lonely_acc + lonely_pb.sum(), repaired_acc + repaired_b)
    return carry, (uk, u_b, lonely_pb)


@functools.lru_cache(maxsize=64)
def _window_fn(kind: str, d: int, m_pad: int, width: int, n_univ: int,
               r_b: int, k_state: int, sk_rank: Optional[int],
               oversample: int, power_iters: int, method: str,
               decay: float):
    """Jitted ``lax.scan`` ingest for one static bucket shape.  The jit
    cache keys on argument avals underneath, so every window length T
    of one bucket adds one trace to THIS callable (counted by
    :func:`trace_count`); a new bucket shape builds a new callable.
    On the exact path ``xs`` carries each batch's U_b (T, m_pad, r_b)
    just before the row counts."""
    step = functools.partial(_step_single, kind, d, m_pad, width, n_univ,
                             r_b, k_state, sk_rank, oversample,
                             power_iters, method, decay)

    @jax.jit
    def run(key, s, v, bidx, lonely0, repaired0, xs):
        return jax.lax.scan(functools.partial(step, key),
                            (s, v, bidx, lonely0, repaired0), xs)

    _BUILT[("single", kind, d, m_pad, width, n_univ, r_b, k_state, sk_rank,
            oversample, power_iters, method, decay)] = run
    return run


@functools.lru_cache(maxsize=64)
def _gram_fn(kind: str, d: int, m_pad: int, width: int, n_univ: int,
             method: str, use_kernel: bool):
    """Jitted :func:`_gram_single` for one bucket shape:
    ``(key, bidx, *batch) -> g``."""
    return jax.jit(functools.partial(_gram_single, kind, d, m_pad, width,
                                     n_univ, method, use_kernel))


# ---------------------------------------------------------------------------
# The scan step (shard_map) — scan INSIDE the region, v sharded in carry
# ---------------------------------------------------------------------------

def _repair_sharded(kind: str, d: int, m_pad: int, width: int,
                    method: str, axes: Tuple[str, ...], k_batch, xs):
    """Repair this device's block of one bucketed batch, padded rows
    inert: (repaired block — dense (m_pad, W) or the ELL arrays with
    their repair side-band — this device's lonely rows, repairs made
    across the mesh)."""
    tm = xs[-1]
    # Device d draws split(k_batch, D)[d] — the exact key the
    # single-host split_and_repair hands block d.
    key_d = jax.random.split(k_batch, d)[jax.lax.axis_index(axes[0])]
    valid = jnp.arange(m_pad, dtype=jnp.int32) < tm

    if kind == "dense":
        a_d = xs[0]                                      # (m_pad, W)
        lon_d = (ranky.lonely_rows(a_d) & valid).sum().astype(jnp.int32)
        blk = _dense_repair_shard(a_d, key_d, axes=axes, method=method)
        blk = jnp.where(valid[:, None], blk, 0.0)        # padded rows inert
        still = (ranky.lonely_rows(blk) & valid).sum().astype(jnp.int32)
        return blk, lon_d, jax.lax.psum(lon_d - still, axes)

    ids, rows, vals = xs[0][0], xs[1][0], xs[2][0]       # (C,), (C, K) x2
    lon_row = ranky.sparse_lonely_rows(rows, vals, m_pad) & valid
    lon_d = lon_row.sum().astype(jnp.int32)
    rc, rm = _sparse_repair_shard(ids, rows, vals, key_d, m=m_pad,
                                  width=width, axes=axes, method=method)
    rm = rm & valid                                      # padded rows inert
    return ((ids, rows, vals, rc, rm), lon_d,
            jax.lax.psum(rm.sum().astype(jnp.int32), axes))


def _gram_sharded(kind: str, d: int, m_pad: int, width: int, method: str,
                  use_kernel: bool, axes: Tuple[str, ...], key, bidx, *xs):
    """Sharded twin of :func:`_gram_single`: the psum'd batch gram,
    replicated."""
    blk, _, _ = _repair_sharded(kind, d, m_pad, width, method, axes,
                                jax.random.fold_in(key, bidx), xs)
    if kind == "dense":
        g = lsvd.gram(blk, use_kernel=use_kernel)
    else:
        g = lsvd.sparse_gram_block(*blk, m_pad, use_kernel=use_kernel)
    return jax.lax.psum(g, axes)


def _step_sharded(kind: str, d: int, m_pad: int, width: int,
                  r_b: int, k_state: int, sk_rank: Optional[int],
                  oversample: int, power_iters: int, method: str,
                  decay: float, axes: Tuple[str, ...], key, carry, xs):
    s, v_d, bidx, lonely_acc, repaired_acc = carry
    k_batch = jax.random.fold_in(key, bidx)
    blk, lon_d, repaired_b = _repair_sharded(kind, d, m_pad, width, method,
                                             axes, k_batch, xs)

    if sk_rank is None:
        u_b = xs[-2]                       # (m_pad, r_b), from the gram's eigh
        if kind == "dense":
            panel_d = mm(blk.T, u_b)
        else:
            panel_d = lsvd.sparse_right_vectors(
                *blk, width, u_b, jnp.ones((r_b,), jnp.float32))
    elif kind == "dense":
        u_b, s_b, v_b_d = randomized.randomized_tail_over(
            lambda om: randomized.sketch_block_dense(om, blk),
            lambda gg: randomized.pullback_block_dense(gg, blk),
            axes, m_pad, rank=sk_rank, oversample=oversample,
            power_iters=power_iters, key=k_batch, want_right=True)
        panel_d = v_b_d * s_b[None, :]
    else:
        u_b, s_b, v_b_d = randomized.randomized_tail_over(
            lambda om: randomized.sketch_block_sparse(om, *blk, width),
            lambda gg: randomized.pullback_block_sparse(gg, *blk, m_pad),
            axes, m_pad, rank=sk_rank, oversample=oversample,
            power_iters=power_iters, key=k_batch, want_right=True)
        panel_d = v_b_d * s_b[None, :]

    s_old = s * jnp.float32(decay)
    p_d = jnp.concatenate([v_d * s_old[None, :], panel_d], axis=1)
    s_new, uk, v_new_d = _merge_truncate_local(p_d, axes, k_state)

    carry = (s_new, v_new_d, bidx + 1,
             lonely_acc + jax.lax.psum(lon_d, axes),
             repaired_acc + repaired_b)
    # lon_d as a (1,)-vector so the stacked ys concatenate to (T, D).
    return carry, (uk, u_b, lon_d[None])


def _batch_specs(kind: str, axes: Tuple[str, ...], stacked: bool) -> Tuple:
    """in_specs of a bucketed batch's arrays before its row count: the
    ELL arrays split on their block axis, a dense batch on its columns
    (after a leading window axis when ``stacked``)."""
    lead = (None,) if stacked else ()
    if kind == "ell":
        return (P(*lead, axes),) * 3
    return (P(*lead, None, axes),)


@functools.lru_cache(maxsize=64)
def _sharded_window_fn(devices_key: Tuple[int, ...], kind: str, d: int,
                       m_pad: int, width: int,
                       r_b: int, k_state: int, sk_rank: Optional[int],
                       oversample: int, power_iters: int, method: str,
                       decay: float):
    """(mesh, jitted shard_map scan) for one static bucket shape.  The
    scan lives INSIDE the region: ``v`` stays column-block-sharded in
    the carry across the whole window and the per-step collectives are
    exactly ``ingest_shard_map``'s, so rule R5d's per-device flat peak
    holds for the window (rule R6's per-device form).  On the exact path
    ``xs`` carries each batch's replicated U_b before the row counts."""
    mesh = stream_state.stream_mesh(d)
    axes = (STREAM_AXIS,)
    step = functools.partial(_step_sharded, kind, d, m_pad, width,
                             r_b, k_state, sk_rank, oversample,
                             power_iters, method, decay, axes)

    def region(key, s, v_d, bidx, lonely0, repaired0, *xs):
        return jax.lax.scan(functools.partial(step, key),
                            (s, v_d, bidx, lonely0, repaired0), xs)

    xs_specs = _batch_specs(kind, axes, stacked=True)
    if sk_rank is None:
        xs_specs += (P(),)                               # U_b
    in_specs = (P(), P(), P(axes, None), P(), P(), P()) + xs_specs + (P(),)
    out_specs = ((P(), P(axes, None), P(), P(), P()),   # carry
                 (P(), P(), P(None, axes)))             # uk, u_b, lonely
    fn = jax.jit(shard_map(region, mesh=mesh,
                           in_specs=in_specs, out_specs=out_specs))
    _BUILT[("shard_map", kind, d, m_pad, width, r_b, k_state, sk_rank,
            oversample, power_iters, method, decay)] = fn
    return mesh, fn


@functools.lru_cache(maxsize=64)
def _sharded_gram_fn(devices_key: Tuple[int, ...], kind: str, d: int,
                     m_pad: int, width: int, method: str, use_kernel: bool):
    """Jitted shard_map :func:`_gram_sharded` for one bucket shape:
    ``(key, bidx, *batch) -> g`` replicated."""
    mesh = stream_state.stream_mesh(d)
    axes = (STREAM_AXIS,)
    fn = functools.partial(_gram_sharded, kind, d, m_pad, width, method,
                           use_kernel, axes)
    in_specs = (P(), P()) + _batch_specs(kind, axes, stacked=False) + (P(),)
    return jax.jit(shard_map(fn, mesh=mesh, in_specs=in_specs,
                             out_specs=P()))


def _window_left_vectors(gram_fn, key, bidx0: int, xs, r_b: int,
                         mesh=None) -> jnp.ndarray:
    """Each batch's U_b, stacked (T, m_pad, r_b): the batch gram from
    ``gram_fn``, then the eigh program shared by every engine
    (``ingest.batch_left_vectors``, keyed on m_pad alone)."""
    t_len = int(xs[-1].shape[0])
    return jnp.stack([
        batch_left_vectors(
            gram_fn(key, jnp.asarray(bidx0 + t, jnp.int32),
                    *(x[t] for x in xs)), r_b, mesh)
        for t in range(t_len)])


# ---------------------------------------------------------------------------
# The window driver
# ---------------------------------------------------------------------------

def ingest_window(
    state: StreamingSVDState,
    deltas: Sequence,
    config,
    plan,
) -> Tuple[StreamingSVDState, IngestInfo]:
    """Fold a window of same-bucket batches into the state with ONE
    jitted scan dispatch (see module docstring; the exact path first
    dispatches each batch's gram and eigh, with no host sync).

    ``deltas`` must share one :func:`bucket_signature`; the state must
    already sit at ``config.truncate_rank`` (the scan carry is
    fixed-shape — ``api.svd_stream`` grows a fresh state through the
    legacy per-batch path first).  ``plan`` is an R5/R5d/R6 plan:
    ``plan.rank`` is the batch-factorization decision and
    ``plan.backend`` routes single-host vs shard_map.  A length-1
    ``deltas`` IS the per-batch loop mode — same compiled function.

    Returns ``(new_state, IngestInfo)`` where the info aggregates the
    window (``batch_rows`` sums the window's rows;
    ``lonely_rows_per_block`` is the LAST batch's split, matching what a
    caller polling per-batch diagnostics would have seen last).
    """
    _fire_seam("ingest.window")
    k = int(config.truncate_rank)
    if state.rank != k:
        raise ValueError(
            f"scan windows need a steady-state carry: state.rank="
            f"{state.rank} != truncate_rank={k}; grow the rank with "
            f"per-batch svd_update ingests first")
    d = state.num_blocks
    t_len = len(deltas)
    if t_len < 1:
        raise ValueError("ingest_window needs at least one delta")

    norm = [stream_state.as_delta(x, state) for x in deltas]
    true_m = [stream_state.delta_shape(x)[0] for x in norm]
    sig = bucket_signature(norm[0])
    for x in norm[1:]:
        if bucket_signature(x) != sig:
            raise ValueError(
                f"ingest_window got mixed buckets {bucket_signature(x)} "
                f"vs {sig}; group deltas by bucket_signature first")
    kind, m_pad = sig[0], sig[1]
    width, n_univ = state.width, state.n

    r_b = (min(m_pad, k + config.oversample)
           if plan.rank is None else plan.rank)
    xs = build_window(norm, true_m, sig)

    bidx0 = jnp.asarray(state.batches_seen, jnp.int32)
    zero = jnp.asarray(0, jnp.int32)
    exact = plan.rank is None
    common = (kind, d, m_pad, width, r_b, k, plan.rank,
              config.oversample, config.power_iters, config.method,
              float(config.history_decay))

    if plan.backend == "shard_map":
        devices_key = stream_state.stream_devices_key()
        mesh, fn = _sharded_window_fn(devices_key, *common)
        rep_sh = NamedSharding(mesh, P())
        v0 = jax.device_put(state.v, NamedSharding(mesh,
                                                   P(STREAM_AXIS, None)))
        batch_sh = [NamedSharding(mesh, spec) for spec in
                    _batch_specs(kind, (STREAM_AXIS,), stacked=True)]
        xs_dev = tuple(jax.device_put(x, sh)
                       for x, sh in zip(xs, batch_sh)) + (
            jax.device_put(xs[-1], rep_sh),)
        key_dev = jax.device_put(state.key, rep_sh)
        if exact:
            gram_fn = _sharded_gram_fn(devices_key, kind, d, m_pad, width,
                                       config.method, config.use_kernel)
            u_bs = _window_left_vectors(gram_fn, key_dev,
                                        state.batches_seen, xs_dev, r_b,
                                        mesh)
            xs_dev = xs_dev[:-1] + (jax.device_put(u_bs, rep_sh),
                                    xs_dev[-1])
        call_args = (key_dev,
                     jax.device_put(state.s, rep_sh), v0,
                     jax.device_put(bidx0, rep_sh),
                     jax.device_put(zero, rep_sh),
                     jax.device_put(zero, rep_sh)) + xs_dev
    else:
        # Bucket signature minus m_pad-independent fields: width/n_univ
        # ride along as statics of the single-host builder.
        fn = _window_fn(kind, d, m_pad, width, n_univ, r_b, k, plan.rank,
                        config.oversample, config.power_iters,
                        config.method, float(config.history_decay))
        if exact:
            gram_fn = _gram_fn(kind, d, m_pad, width, n_univ,
                               config.method, config.use_kernel)
            u_bs = _window_left_vectors(gram_fn, state.key,
                                        state.batches_seen, xs, r_b)
            xs = xs[:-1] + (u_bs, xs[-1])
        call_args = (state.key, state.s, state.v, bidx0, zero, zero, xs)

    # Merge-phase fault seam: brackets the one compiled dispatch (a
    # raise cannot come from inside the scan's collectives).
    _fire_seam("ingest.merge")
    # Compile-vs-execute split via the trace-count probe: the jit cache
    # grows iff this window's shape had not been traced yet.
    with obs.span("ingest.window", bucket=str(sig), batches=t_len,
                  backend=plan.backend) as args:
        pre_traces = fn._cache_size()
        carry, ys = fn(*call_args)
        compiled = args["compiled"] = fn._cache_size() > pre_traces
    if obs.enabled():
        obs.counter_add("window_dispatch_total")
        if compiled:
            obs.counter_add("window_compile_total")
        obs.counter_add("ingest_batches_total", float(t_len))
        obs.counter_add("ingest_rows_total", float(sum(true_m)))
        obs.gauge_set("jit_cache_size", trace_count())
        # R6 drift at the ACTUAL window length (tail windows are shorter
        # than plan.window): re-price the closed form for t_len batches
        # and compare XLA's buffer plan — compile-only, no dispatch, one
        # measurement per bucket shape.  Dense nnz = the padded block
        # input; ell nnz = slot capacity (upper bound, so the estimate
        # can only be conservative).
        nnz_slots = bucket_nnz_slots(sig, d)
        spec = planner.ASpec(
            m=m_pad, n=n_univ,
            nnz=nnz_slots if nnz_slots is not None else m_pad * n_univ,
            num_blocks=d, kind="stream")
        est = planner.window_bytes(
            spec, k, config.oversample, exact=plan.rank is None,
            window=t_len, batch_rank=plan.rank, nnz_slots=nnz_slots,
            per_device=plan.backend == "shard_map")
        obs.observe_compiled("R6", lambda: fn, call_args, est,
                             component="total", label=plan.backend)

    _DISPATCH["windows"] += 1
    _DISPATCH["batches"] += t_len

    s_new, v_new, _, lonely_dev, repaired_dev = carry
    uk_stack, ub_stack, lonely_stack = ys

    # Fold the stacked small rotations into u AFTER the scan — u grows
    # with rows_seen and never rides in the carry.  Padded u_b rows are
    # sliced off with the host-side true row counts before they touch u.
    # The fold runs eagerly, where no jax.named_scope reaches the ops'
    # metadata, so it is named by a host span.
    u = state.u
    with obs.span("stream.fold", batches=t_len):
        for t in range(t_len):
            uk_t = uk_stack[t]
            ub_t = ub_stack[t, :true_m[t]]
            u = jnp.concatenate([mm(u, uk_t[:k]), mm(ub_t, uk_t[k:])],
                                axis=0)

    # The ONE host materialization of the window: the side-band counters
    # lived on device the whole way (no per-batch sync).
    lonely_total, repaired_total, last_pb = jax.device_get(
        (lonely_dev, repaired_dev, lonely_stack[t_len - 1]))

    new_state = StreamingSVDState(
        u=u, s=s_new, v=v_new, key=state.key,
        n=state.n, num_blocks=d,
        rows_seen=state.rows_seen + int(sum(true_m)),
        batches_seen=state.batches_seen + t_len,
        lonely_rows_seen=state.lonely_rows_seen + int(lonely_total),
        repaired_rows_seen=state.repaired_rows_seen + int(repaired_total))
    info = IngestInfo(
        batch_rows=int(sum(true_m)),
        lonely_rows_per_block=tuple(int(x) for x in last_pb),
        lonely_rows=int(lonely_total),
        repaired_rows=int(repaired_total))
    return new_state, info
