"""The incremental merge-and-truncate engine behind ``api.svd_update``.

One ingest folds a batch ``B`` of new rows into an existing truncated
factorization ``A_old ~ U diag(s) V^T`` without ever touching the rows
already seen:

1. **Normalize** the delta into the state's column universe
   (``stream.state.as_delta``) — COO deltas become ``BlockEll`` and run
   sparse-natively end to end.
2. **Repair** the batch with the configured Ranky checker
   (``ranky.split_and_repair``) *before* anything is truncated: a
   rank-deficient batch block leaves its lonely rows with no weight in
   the truncated factors, and the merge can never recover components a
   leaf lost (the paper's rank problem, streaming edition — pinned by
   tests/test_streaming.py).
3. **Factor** the repaired batch sparse-natively, per the plan's R5
   decision (core/planner.py): the exact per-block gram stack + eigh
   when the batch is small enough, otherwise the randomized
   (k+p)-row sketch (core/randomized.py — Pallas sparse_gram /
   sketch_panel kernels underneath).  Either way the batch contributes
   an (n_pad, r_b) right panel ``P_b = B^T U_b`` (= ``V_b diag(s_b)``,
   computed without any 1/s division).
4. **Merge and truncate**: with ``P_old = V diag(decay * s)`` the
   stacked matrix ``K = [diag(decay*s) V^T ; diag(s_b) V_b^T]``
   satisfies ``[decay*A_old ; B] = blockdiag(U, U_b) @ K``, so one SVD
   of ``K^T = [P_old | P_b]`` — the same panel merge as the
   hierarchical tree engine (``hierarchy.merge_svd``) — yields the new
   ``(V', s')`` plus the small rotation ``U_k`` that updates the left
   vectors: ``U' = [U @ U_k[:k] ; U_b @ U_k[k:]]``.  Truncation back to
   ``truncate_rank`` closes the loop.

Nothing in steps 3–4 depends on ``rows_seen``: the merge works on an
(n_pad, k + r_b) panel and the batch factorization on the batch alone —
planner rule R5's closed form, ``O(batch + (k+p) * N)`` peak.

The exact batch factorization's eigh is its own program, keyed on the
batch height alone (:func:`batch_left_vectors`), never fused into an
engine's step: every engine and window shape reuses one compile per
batch height.

**Distributed ingestion** (``plan.backend == "shard_map"``, rule R5d):
the same four steps run in ``shard_map`` regions over a
one-block-per-device mesh (the exact path's gram in one region, its
eigh between, panel and merge in a second), and no device ever
materializes anything N-sized:

* the state's ``v`` is row-sharded (device d owns its column block's
  (W, k) slice), deltas shard like every other path (dense columns /
  BlockEll leading block axis);
* repair replays the single-host prologue bit-identically: device d
  uses ``jax.random.split(k_batch, D)[d]`` — the exact key
  ``split_and_repair`` hands block d — and the neighbor methods' global
  row adjacency is the psum of binarized local grams (the same matrix
  ``row_adjacency`` computes on one host);
* the exact batch factorization psums the per-device (m_b, m_b) grams
  into one eigh (on the mesh's first device, the result replicated
  back); the randomized one runs ``randomized_tail_over`` —
  identical Omega and the same (L, m_b) psum'd pullbacks as the
  distributed one-shot driver;
* the merge never stacks the (N_pad, k + r_b) panel: each device forms
  its (W, k + r_b) slice ``[V_d diag(decay*s) | B_d^T U_b]``, one psum
  of the (k + r_b)^2 panel Gram yields the small rotation ``W`` and the
  new singular values ONCE (replicated), and each device applies ``W``
  locally to produce its shard of the new ``v``.  The left factor
  update ``U' = [U W[:k] ; U_b W[k:]]`` happens outside the region —
  ``u`` is host-resident, in ingestion order, and only ever touched by
  the small rotation.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import obs
from repro.compat import shard_map_nocheck as shard_map
from repro.compat import trace_state_clean
from repro.core import hierarchy, randomized, ranky, sparse
from repro.core import svd as lsvd
from repro.precision import mm
from repro.stream import state as stream_state
from repro.stream.state import STREAM_AXIS, StreamingSVDState


# ---------------------------------------------------------------------------
# Deterministic fault-injection seam (ft/inject.py)
# ---------------------------------------------------------------------------
# ``ft.inject.FaultInjector.install`` points this at its ``fire``
# callable so chaos tests and CI can script device failures without
# real hardware; ``None`` (the default) is production — the seam
# short-circuits to nothing.  The seam only ever fires from EAGER code
# (``trace_state_clean`` guard, the same idiom as ``obs.trace``), so
# the jitted math and its compile-only drift twin are never perturbed
# and observe-on/-off bit-identity is untouched.
_fault_seam = None


def install_fault_seam(fn) -> None:
    """Install (or with ``None`` remove) the fault-injection callable.
    ``fn(phase)`` is called at the seam points — ``"ingest.batch"`` /
    ``"ingest.window"`` at engine entry, ``"ingest.merge"`` just before
    the merge/collective work — and simulates a fault by raising."""
    global _fault_seam
    _fault_seam = fn


def _fire_seam(phase: str) -> None:
    if _fault_seam is not None and trace_state_clean():
        _fault_seam(phase)


@dataclasses.dataclass(frozen=True)
class IngestInfo:
    """Side-band observations of one ingest (per batch, not cumulative —
    the cumulative counters live on the state)."""

    batch_rows: int
    lonely_rows_per_block: Tuple[int, ...]
    lonely_rows: int
    repaired_rows: int


def _repaired_count(blocks, lonely_total: int) -> int:
    """Exact number of side-band repairs the checker made on this batch.

    Sparse blocks carry the repair mask explicitly; dense blocks were
    repaired in place, so the count is lonely-before minus lonely-after.
    """
    if isinstance(blocks, sparse.RepairedSparseBlocks):
        return int(np.asarray(blocks.repair_mask).sum())
    still_lonely = jax.vmap(ranky.lonely_rows)(blocks)
    return lonely_total - int(np.asarray(still_lonely).sum())


def batch_left_vectors(g: jnp.ndarray, r_b: int, mesh=None) -> jnp.ndarray:
    """U_b (m, r_b): the top ``r_b`` eigenvectors of a repaired batch's
    summed (m, m) gram — the exact batch factorization.

    The eigh runs as its own program (``lsvd.merge_grams_eigh``) keyed on
    m alone, never inside an engine's region or scan: the per-batch and
    windowed engines, single-host or sharded, share one compile per batch
    height, whatever the batch's ELL bucket or window length.  On a TPU
    that compile dominates (XLA's eigh of a 2,048-square gram emits
    ~600 MB of code).  With ``mesh``, ``g`` is replicated over it: the
    eigh runs on the mesh's first device and U_b comes back replicated."""
    if mesh is None:
        return lsvd.merge_grams_eigh(g)[0][:, :r_b]
    g0 = jax.device_put(g, mesh.devices.flat[0])
    return jax.device_put(lsvd.merge_grams_eigh(g0)[0][:, :r_b],
                          NamedSharding(mesh, P()))


def _factor_batch(blocks, m_b: int, config, plan, k_batch: jax.Array):
    """(U_b (m_b, r_b), P_b (n_pad, r_b)) of the repaired batch, per the
    plan's R5 strategy.  ``P_b = B^T U_b`` exactly — the batch's
    contribution to the merge panel, carrying the batch singular values
    implicitly and formed without dividing by them (so rank-deficient
    batches stay finite)."""
    if plan.rank is None:
        # Exact: summed per-block gram stack (sparse-native E+R grams) +
        # eigh, truncated to the merge width r_b = min(m_b, k + oversample).
        r_b = min(m_b, config.truncate_rank + config.oversample)
        u_b = batch_left_vectors(
            lsvd.gram_stack(blocks, use_kernel=config.use_kernel).sum(axis=0),
            r_b)
        panel_b = ranky.right_vectors_stack(
            blocks, u_b, jnp.ones((r_b,), jnp.float32))   # B^T U_b
    else:
        # Randomized (k+p)-row sketch (the tall-batch regime).  The
        # sketch path's right vectors come from the sketch statistics
        # (G^T vproj), so V_b diag(s_b) is finite by construction.
        u_b, s_b, v_b = randomized.randomized_svd_blocks(
            blocks, rank=plan.rank, oversample=config.oversample,
            power_iters=config.power_iters, key=k_batch, want_right=True)
        panel_b = v_b * s_b[None, :]
    return u_b, panel_b


def _ingest_math(a_norm, k_batch, s, v, *, d, m_b, config, plan):
    """The device math of one single-host ingest — repair, batch
    factorization, merge-and-truncate — WITHOUT the left-factor update
    (``u`` grows with rows_seen; rule R5's closed form excludes it).

    Split out so the drift monitor can lower+compile the SAME ops
    (``jax.jit(functools.partial(_ingest_math, **statics))``) and ask
    XLA for the measured peak of exactly what runs; :func:`ingest`
    calls it EAGERLY, so op order — and therefore the result — is
    bit-identical with observability on or off.
    """
    # Repair BEFORE factorization/truncation (the rank problem).
    blocks = ranky.split_and_repair(a_norm, d, config.method, k_batch)

    u_b, panel_b = _factor_batch(blocks, m_b, config, plan, k_batch)
    _fire_seam("ingest.merge")

    # Merge-and-truncate: one hierarchy-style panel SVD of
    # [V diag(decay*s) | B^T U_b], nothing bigger than (n_pad, k + r_b).
    s_old = s * jnp.float32(config.history_decay)
    p = jnp.concatenate([v * s_old[None, :], panel_b], axis=1)
    k_new = min(config.truncate_rank, p.shape[1])
    v_new, s_new, uk = hierarchy.merge_svd(p, k_new)  # uk: (k_old+r_b, k_new)
    return blocks, u_b, v_new, s_new, uk


def ingest(
    state: StreamingSVDState,
    delta,
    config,
    plan,
) -> Tuple[StreamingSVDState, IngestInfo]:
    """Fold one batch of new rows into the state (see module docstring).

    ``config`` is an ``api.SolveConfig`` with ``truncate_rank`` set;
    ``plan`` is the R5/R5d plan from ``planner.make_stream_plan`` (its
    ``rank`` field is the batch-factorization decision: ``None`` =
    exact gram stack, ``r`` = randomized sketch of rank r; its
    ``backend`` field routes to the single-host or the shard_map
    engine).  Returns ``(new_state, IngestInfo)``.
    """
    if plan.backend == "shard_map":
        return ingest_shard_map(state, delta, config, plan)
    _fire_seam("ingest.batch")
    a_norm = stream_state.as_delta(delta, state)
    m_b, _ = stream_state.delta_shape(delta)
    d = state.num_blocks

    # The PRNG chain: batch b always draws fold_in(root, b), so a
    # restored-from-checkpoint stream re-draws the same repair columns
    # and sketch matrices as the uninterrupted one (bit-identical).
    k_batch = jax.random.fold_in(state.key, state.batches_seen)

    statics = dict(d=d, m_b=m_b, config=config, plan=plan)
    with obs.span("ingest.batch", rows=m_b, backend="single"):
        blocks, u_b, v_new, s_new, uk = _ingest_math(
            a_norm, k_batch, state.s, state.v, **statics)
        k_old = state.rank
        u_new = jnp.concatenate(
            [mm(state.u, uk[:k_old]), mm(u_b, uk[k_old:])], axis=0)
    if obs.enabled():
        obs.counter_add("ingest_batches_total")
        obs.counter_add("ingest_rows_total", float(m_b))
        # R5 drift: lower+compile a jit twin of the math above (partial
        # keywords are trace-time constants) — compile-only, memoized
        # per batch shape, never dispatched.
        obs.observe_compiled(
            "R5",
            lambda: jax.jit(functools.partial(_ingest_math, **statics)),
            (a_norm, k_batch, state.s, state.v),
            plan.estimated_peak_bytes, component="temp", label="single")

    # Side-band diagnostics LAST: the device-to-host reads happen only
    # after the whole factor/merge pipeline is enqueued, so the sync
    # overlaps the math instead of serializing the dispatch.  (The
    # scan-window driver in stream/window.py goes further and keeps
    # the counters in the scan carry for a whole window.)
    lonely_pb = ranky.lonely_rows_per_block(a_norm, d)
    lonely_total = sum(lonely_pb)
    repaired = _repaired_count(blocks, lonely_total)

    new_state = StreamingSVDState(
        u=u_new, s=s_new, v=v_new, key=state.key,
        n=state.n, num_blocks=d,
        rows_seen=state.rows_seen + m_b,
        batches_seen=state.batches_seen + 1,
        lonely_rows_seen=state.lonely_rows_seen + lonely_total,
        repaired_rows_seen=state.repaired_rows_seen + repaired)
    info = IngestInfo(
        batch_rows=m_b, lonely_rows_per_block=lonely_pb,
        lonely_rows=lonely_total, repaired_rows=repaired)
    return new_state, info


# ---------------------------------------------------------------------------
# The shard_map engine (plan.backend == "shard_map", planner rule R5d)
# ---------------------------------------------------------------------------

def _merge_truncate_local(p_d: jnp.ndarray, axes: Tuple[str, ...],
                          k_new: int):
    """Per-device tail of the merge-and-truncate: from this device's
    (W, k_tot) panel slice, psum the (k_tot, k_tot) panel Gram, eigh it
    ONCE (replicated), and apply the small rotation locally.

    ``P = V' diag(s') W^T`` means ``P^T P = W diag(s'^2) W^T``, so the
    eigh of the psum'd Gram yields the rotation ``W`` and the new
    singular values without any device touching the (N_pad, k_tot)
    panel; the new ``v`` shard is ``P_d W diag(1/s')`` with a
    floor-masked inverse (rank-deficient merge directions get zero
    columns instead of noise — they carry zero weight into every later
    merge, exactly like the single-host SVD's arbitrary null-space
    columns).  Returns (s_new (k_new,), w (k_tot, k_new) — the ``uk``
    rotation of ``hierarchy.merge_svd`` — and v_new_d (W, k_new))."""
    k_tot = p_d.shape[1]
    g = jax.lax.psum(mm(p_d.T, p_d), axes)            # (k_tot, k_tot)
    evals, evecs = jnp.linalg.eigh(g)                 # ascending
    evals = jnp.flip(evals, -1)
    evecs = jnp.flip(evecs, -1)
    s_all = jnp.sqrt(jnp.clip(evals, 0.0, None))
    floor = jnp.finfo(g.dtype).eps * jnp.max(evals) * k_tot
    good = evals[:k_new] > floor
    inv = jnp.where(good, 1.0 / jnp.where(good, s_all[:k_new], 1.0), 0.0)
    w = evecs[:, :k_new]
    v_new_d = mm(p_d, w * inv[None, :])
    return s_all[:k_new], w, v_new_d


def _dense_repair_shard(a_d: jnp.ndarray, key_d: jax.Array, *,
                        axes: Tuple[str, ...], method: str) -> jnp.ndarray:
    """This device's repaired (m_b, W) column block — same key chain and
    same (psum'd == global) adjacency as the single-host split_and_repair
    prologue, so the repaired batch is bit-identical to what the
    single-host engine factors."""
    m_b = a_d.shape[0]
    adj = None
    if method in ("neighbor", "neighbor_random"):
        b = (a_d != 0).astype(jnp.float32)
        adj = jax.lax.psum(b @ b.T, axes)
        adj = (adj > 0) & ~jnp.eye(m_b, dtype=bool)
    return ranky.repair_block(a_d, method, key_d, adj)


def _sparse_repair_shard(ids, rows, vals, key_d: jax.Array, *, m: int,
                         width: int, axes: Tuple[str, ...], method: str):
    """(repair_cols, repair_mask) of this device's ELL block, keyed and
    adjacency-matched like the single-host prologue."""
    adj = None
    if method in ("neighbor", "neighbor_random"):
        p = sparse.stored_col_panel(rows, vals, m, binarize=True)
        adj = jax.lax.psum(p.T @ p, axes)
        adj = (adj > 0) & ~jnp.eye(m, dtype=bool)
    return ranky.repair_block_sparse(ids, rows, vals, method, key_d,
                                     m=m, width=width, row_adj=adj)


def _dense_gram_shard(a_d, keys_d, *, axes, method, use_kernel):
    """The exact path's first region: the psum'd (m_b, m_b) gram of the
    repaired batch.  Its eigh runs outside (:func:`batch_left_vectors`)
    and the merge region repairs again to form the panel."""
    blk = _dense_repair_shard(a_d, keys_d[0], axes=axes, method=method)
    return jax.lax.psum(lsvd.gram(blk, use_kernel=use_kernel), axes)


def _sparse_gram_shard(ids, rows, vals, keys_d, *, m, width, axes, method,
                       use_kernel):
    """Sparse twin of :func:`_dense_gram_shard`."""
    ids, rows, vals = ids[0], rows[0], vals[0]
    rc, rm = _sparse_repair_shard(ids, rows, vals, keys_d[0], m=m,
                                  width=width, axes=axes, method=method)
    return jax.lax.psum(
        lsvd.sparse_gram_block(ids, rows, vals, rc, rm, m,
                               use_kernel=use_kernel), axes)


def _dense_stream_shard_fn(
    a_d: jnp.ndarray,       # (m_b, W) this device's delta column block
    keys_d: jnp.ndarray,    # (1, ...) this device's split_and_repair key
    k_batch: jax.Array,     # replicated batch key (sketch Omega)
    v_d: jnp.ndarray,       # (W, k_old) this device's shard of state.v
    s_old: jnp.ndarray,     # (k_old,) decayed singular values, replicated
    u_b: Optional[jnp.ndarray] = None,  # (m_b, r_b) exact path, replicated
    *,
    axes: Tuple[str, ...],
    method: str,
    r_b: int,
    k_new: int,
    sk_rank: Optional[int],
    oversample: int,
    power_iters: int,
):
    m_b = a_d.shape[0]
    blk = _dense_repair_shard(a_d, keys_d[0], axes=axes, method=method)
    repaired = jax.lax.psum(
        ranky.lonely_rows(a_d).sum() - ranky.lonely_rows(blk).sum(), axes)

    if sk_rank is None:
        panel_d = mm(blk.T, u_b)                       # B_d^T U_b, (W, r_b)
    else:
        u_b, s_b, v_b_d = randomized.randomized_tail_over(
            lambda om: randomized.sketch_block_dense(om, blk),
            lambda gg: randomized.pullback_block_dense(gg, blk),
            axes, m_b, rank=sk_rank, oversample=oversample,
            power_iters=power_iters, key=k_batch, want_right=True)
        panel_d = v_b_d * s_b[None, :]                 # V_d diag(s_b)

    p_d = jnp.concatenate([v_d * s_old[None, :], panel_d], axis=1)
    s_new, w, v_new_d = _merge_truncate_local(p_d, axes, k_new)
    return u_b, s_new, w, v_new_d, repaired


def _sparse_stream_shard_fn(
    ids: jnp.ndarray,       # (1, C) this device's block's ELL arrays
    rows: jnp.ndarray,      # (1, C, K)
    vals: jnp.ndarray,      # (1, C, K)
    keys_d: jnp.ndarray,
    k_batch: jax.Array,
    v_d: jnp.ndarray,
    s_old: jnp.ndarray,
    u_b: Optional[jnp.ndarray] = None,
    *,
    m: int,
    width: int,
    axes: Tuple[str, ...],
    method: str,
    r_b: int,
    k_new: int,
    sk_rank: Optional[int],
    oversample: int,
    power_iters: int,
):
    ids, rows, vals = ids[0], rows[0], vals[0]
    rc, rm = _sparse_repair_shard(ids, rows, vals, keys_d[0], m=m,
                                  width=width, axes=axes, method=method)
    repaired = jax.lax.psum(rm.sum(), axes)

    if sk_rank is None:
        panel_d = lsvd.sparse_right_vectors(
            ids, rows, vals, rc, rm, width, u_b,
            jnp.ones((r_b,), jnp.float32))             # B_d^T U_b
    else:
        u_b, s_b, v_b_d = randomized.randomized_tail_over(
            lambda om: randomized.sketch_block_sparse(
                om, ids, rows, vals, rc, rm, width),
            lambda gg: randomized.pullback_block_sparse(
                gg, ids, rows, vals, rc, rm, m),
            axes, m, rank=sk_rank, oversample=oversample,
            power_iters=power_iters, key=k_batch, want_right=True)
        panel_d = v_b_d * s_b[None, :]

    p_d = jnp.concatenate([v_d * s_old[None, :], panel_d], axis=1)
    s_new, w, v_new_d = _merge_truncate_local(p_d, axes, k_new)
    return u_b, s_new, w, v_new_d, repaired


def _delta_specs(kind: str, axes: Tuple[str, ...]) -> Tuple:
    """in_specs of a delta: the ELL arrays (ids, rows, vals) split on
    their leading block axis, or the dense delta's columns."""
    if kind == "ell":
        return (P(axes), P(axes), P(axes))
    return (P(None, axes),)


@functools.lru_cache(maxsize=64)
def _sharded_ingest_fn(devices_key: Tuple[int, ...], d: int, kind: str,
                       m_b: int, width: int,
                       r_b: int, k_new: int, sk_rank: Optional[int],
                       oversample: int, power_iters: int, method: str):
    """(mesh, jitted shard_map callable) for one static ingest shape:
    repair, the batch panel (from a given U_b on the exact path, from
    the sketch otherwise) and the merge.

    Cached so a steady-state stream (same batch shape, state at
    truncate_rank) compiles its sharded update ONCE and replays it
    every ingest — the jit cache keys on argument avals underneath, so
    a shape change (e.g. the rank still growing toward truncate_rank)
    retraces exactly like the single-host engine would.
    ``devices_key`` is the active stream-device pool's identity
    (``stream_state.stream_devices_key()``): after an elastic re-mesh
    onto survivors the pool changes, so the entry keyed on the dead
    mesh is never reused."""
    mesh = stream_state.stream_mesh(d)
    axes = (STREAM_AXIS,)
    common = dict(axes=axes, method=method, r_b=r_b, k_new=k_new,
                  sk_rank=sk_rank, oversample=oversample,
                  power_iters=power_iters)
    if kind == "ell":
        fn = functools.partial(_sparse_stream_shard_fn, m=m_b, width=width,
                               **common)
    else:
        fn = functools.partial(_dense_stream_shard_fn, **common)
    in_specs = _delta_specs(kind, axes) + (
        P(axes), P(),                   # keys, k_batch
        P(axes, None), P())             # v, s_old
    if sk_rank is None:
        in_specs += (P(),)              # U_b
    out_specs = (P(), P(), P(), P(axes, None), P())
    sharded = shard_map(fn, mesh=mesh, in_specs=in_specs,
                        out_specs=out_specs)
    return mesh, jax.jit(sharded)


@functools.lru_cache(maxsize=64)
def _sharded_gram_fn(devices_key: Tuple[int, ...], d: int, kind: str,
                     m_b: int, width: int, method: str, use_kernel: bool):
    """(mesh, jitted shard_map callable): the exact path's batch gram,
    psum'd and replicated — ``(delta..., keys) -> g (m_b, m_b)``."""
    mesh = stream_state.stream_mesh(d)
    axes = (STREAM_AXIS,)
    common = dict(axes=axes, method=method, use_kernel=use_kernel)
    if kind == "ell":
        fn = functools.partial(_sparse_gram_shard, m=m_b, width=width,
                               **common)
    else:
        fn = functools.partial(_dense_gram_shard, **common)
    sharded = shard_map(fn, mesh=mesh,
                        in_specs=_delta_specs(kind, axes) + (P(axes),),
                        out_specs=P())
    return mesh, jax.jit(sharded)


def ingest_shard_map(
    state: StreamingSVDState,
    delta,
    config,
    plan,
) -> Tuple[StreamingSVDState, IngestInfo]:
    """The distributed twin of :func:`ingest` — same four steps, one
    ``shard_map`` region, per-device peak per planner rule R5d.  The
    repaired batch is bit-identical to the single-host engine's (same
    per-block key chain, same global adjacency), the collectives mirror
    ``core/distributed.py``, and the factors agree with the single-host
    result up to reduction-order float error and column signs."""
    d = state.num_blocks
    if stream_state.stream_device_count() < d:
        raise ValueError(
            f"plan.backend='shard_map' needs one device per column "
            f"block: num_blocks={d} but only "
            f"{stream_state.stream_device_count()} healthy device(s)")
    _fire_seam("ingest.batch")
    a_norm = stream_state.as_delta(delta, state)
    m_b, _ = stream_state.delta_shape(delta)

    k_batch = jax.random.fold_in(state.key, state.batches_seen)
    keys = jax.random.split(k_batch, d)   # block d's split_and_repair key

    k_old = state.rank
    r_b = (min(m_b, config.truncate_rank + config.oversample)
           if plan.rank is None else plan.rank)
    k_new = min(config.truncate_rank, k_old + r_b)
    s_old = state.s * jnp.float32(config.history_decay)

    sparse_in = isinstance(a_norm, sparse.BlockEll)
    kind = "ell" if sparse_in else "dense"
    width = a_norm.width if sparse_in else a_norm.shape[1] // d
    mesh, fn = _sharded_ingest_fn(
        stream_state.stream_devices_key(), d, kind, m_b, width,
        r_b, k_new, plan.rank, config.oversample, config.power_iters,
        config.method)
    blk_sh = NamedSharding(mesh, P(STREAM_AXIS))
    rep_sh = NamedSharding(mesh, P())
    tail = (jax.device_put(keys, blk_sh),
            jax.device_put(k_batch, rep_sh),
            jax.device_put(state.v, NamedSharding(mesh, P(STREAM_AXIS, None))),
            jax.device_put(s_old, rep_sh))
    if sparse_in:
        args = (jax.device_put(jnp.asarray(a_norm.col_ids), blk_sh),
                jax.device_put(jnp.asarray(a_norm.col_rows), blk_sh),
                jax.device_put(jnp.asarray(a_norm.col_vals), blk_sh))
    else:
        args = (jax.device_put(a_norm,
                               NamedSharding(mesh, P(None, STREAM_AXIS))),)
    if plan.rank is None:
        _, gram_fn = _sharded_gram_fn(
            stream_state.stream_devices_key(), d, kind, m_b, width,
            config.method, config.use_kernel)
        tail += (batch_left_vectors(gram_fn(*args, tail[0]), r_b, mesh),)
    if obs.enabled():
        # R5d drift: memory_analysis on the SPMD jit reports PER-DEVICE
        # sizes, matching streaming_bytes_per_device in the plan.
        obs.observe_compiled(
            "R5d", lambda: fn, args + tail, plan.estimated_peak_bytes,
            component="temp", label="shard_map")
    # The merge seam brackets the compiled region (a raise cannot come
    # from inside an XLA collective): "during merge" faults surface at
    # the dispatch covering the merge.
    _fire_seam("ingest.merge")
    with obs.span("ingest.batch", rows=m_b, backend="shard_map"):
        u_b, s_new, uk, v_new, repaired = fn(*args, *tail)

        # The left-factor update stays outside the region: u is in
        # ingestion order and only the small (k_tot, k_new) rotation
        # ever touches it.
        u_new = jnp.concatenate(
            [mm(state.u, uk[:k_old]), mm(u_b, uk[k_old:])], axis=0)
    obs.counter_add("ingest_batches_total")
    obs.counter_add("ingest_rows_total", float(m_b))

    # Side-band diagnostics AFTER the sharded dispatch: the lonely-count
    # host read no longer serializes the region launch (the scan-window
    # driver removes even this per-batch read).
    lonely_pb = ranky.lonely_rows_per_block(a_norm, d)
    lonely_total = sum(lonely_pb)
    repaired = int(np.asarray(repaired))
    new_state = StreamingSVDState(
        u=u_new, s=s_new, v=v_new, key=state.key,
        n=state.n, num_blocks=d,
        rows_seen=state.rows_seen + m_b,
        batches_seen=state.batches_seen + 1,
        lonely_rows_seen=state.lonely_rows_seen + lonely_total,
        repaired_rows_seen=state.repaired_rows_seen + repaired)
    info = IngestInfo(
        batch_rows=m_b, lonely_rows_per_block=lonely_pb,
        lonely_rows=lonely_total, repaired_rows=repaired)
    return new_state, info
