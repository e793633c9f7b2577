"""The checkpointable state of a long-lived streaming Ranky SVD.

A streaming solve never sees the whole matrix: rows arrive in batches
(a day of user-item interactions, a window of network logs) and the
service must keep serving an up-to-date truncated factorization of
everything ingested so far.  :class:`StreamingSVDState` is the entire
durable state of such a service:

* ``u`` (rows_seen, k) / ``s`` (k,) / ``v`` (n_pad, k) — the truncated
  factorization of every row ingested so far (after ``history_decay``
  weighting).  ``v`` is load-bearing for ingestion, not an optional
  extra: ``diag(s) @ v.T`` is the rank-k proxy of the whole history
  that the next merge-and-truncate folds the next batch into (Iwen &
  Ong's hierarchical merge, re-used as an *incremental* update).  ``u``
  rows are in ingestion order, so it grows with ``rows_seen`` — the
  merge itself never touches anything bigger than
  O(batch + (k+p) * N) (planner rule R5).
* the *column universe*: ``n`` global columns split into ``num_blocks``
  column blocks of width ``ceil(n / num_blocks)`` — the same ONE
  block-splitting convention as every other path (core/sparse.py).
  Every delta must live in this universe; ``v`` rows are in padded
  column order (n_pad = num_blocks * width).
* the Ranky repair side-band, accumulated: ``lonely_rows_seen`` /
  ``repaired_rows_seen`` count the lonely rows each batch exposed and
  the repairs the checkers made before each merge (the rank problem is
  MORE load-bearing here than in one-shot solves — a deficient batch
  truncated before repair loses components every later merge inherits).
* the PRNG key chain: ``key`` is the root; ingest ``b`` draws
  ``fold_in(key, b)`` so a replayed/restored stream re-draws the exact
  repair columns and sketch matrices (checkpoint resume is
  bit-identical by construction).

The state is a frozen, registered JAX pytree — it flows through
``jax.tree`` utilities and, via the pytree-dataclass support in
``checkpoint/ckpt.py``, through ``Checkpointer.save`` / ``restore``
unchanged.

**Sharded residency** (the distributed-ingestion path,
``stream_backend="shard_map"``): ``v`` rows are in padded column order,
so sharding them over a one-axis device mesh gives each device exactly
one column block's (W, k) slice — the same one-block-per-device layout
as ``core/distributed.py``.  :func:`shard_state` / :func:`gather_state`
move a state between the sharded and single-device layouts without
changing a single value; checkpoint saves always gather (the on-disk
layout never bakes in a mesh) and ``Checkpointer.restore`` re-shards
onto the CURRENT device count via ``reshard_for_restore``.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple, Union

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.compat import make_mesh
from repro.core import ranky, sparse

# The one mesh-axis name of the streaming shard_map engine (one column
# block per device, like core/distributed.py's block axes).
STREAM_AXIS = "blocks"

# ---------------------------------------------------------------------------
# Active stream-device registry (elastic recovery support)
# ---------------------------------------------------------------------------
# The pool of devices the streaming engines are allowed to place work
# on.  ``None`` (the default) means "all local devices" — every existing
# call path behaves exactly as before.  ``ft/supervise.py`` restricts
# the pool to the surviving devices after a failure/eviction so
# ``stream_mesh`` / ``shard_state`` / ``reshard_for_restore`` rebuild
# onto the survivors instead of the dead mesh.
_STREAM_DEVICES: Optional[Tuple] = None


def set_stream_devices(devices) -> None:
    """Restrict (or with ``None`` reset) the device pool streaming
    placement draws from.  Order matters: ``stream_mesh`` takes the
    first ``num_blocks`` devices of the pool and single-host placement
    uses the pool's first device."""
    global _STREAM_DEVICES
    _STREAM_DEVICES = None if devices is None else tuple(devices)


def stream_devices() -> Tuple:
    """The active stream-device pool (all local devices by default)."""
    if _STREAM_DEVICES is not None:
        return _STREAM_DEVICES
    return tuple(jax.devices())


def stream_device_count() -> int:
    """``len(stream_devices())`` — what the planner's R5/R5d backend
    gate and the sharded engines see as "the device count"."""
    return len(stream_devices())


def stream_devices_key() -> Tuple[int, ...]:
    """Hashable identity of the active pool, for compile caches: a
    re-mesh onto different survivors must not reuse a stale mesh."""
    return tuple(d.id for d in stream_devices())


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class StreamingSVDState:
    """Everything a streaming SVD service needs to survive a restart.

    Children (arrays): ``u``, ``s``, ``v``, ``key``.  Aux (static):
    the column universe (``n``, ``num_blocks``) and the ingestion
    counters.  ``rank`` is ``s.shape[0]`` — it grows batch by batch
    until it reaches the configured ``truncate_rank`` and stays there.
    """

    u: jnp.ndarray      # (rows_seen, k) left vectors, ingestion order
    s: jnp.ndarray      # (k,) singular values (history-decayed)
    v: jnp.ndarray      # (n_pad, k) right vectors, padded column order
    key: jax.Array      # PRNG chain root; batch b uses fold_in(key, b)
    n: int              # column universe (unpadded)
    num_blocks: int     # column-block count D of the universe
    rows_seen: int      # total rows ingested
    batches_seen: int   # total svd_update calls folded in
    lonely_rows_seen: int    # cumulative lonely rows across batches
    repaired_rows_seen: int  # cumulative Ranky side-band repairs

    def tree_flatten(self):
        return ((self.u, self.s, self.v, self.key),
                (self.n, self.num_blocks, self.rows_seen,
                 self.batches_seen, self.lonely_rows_seen,
                 self.repaired_rows_seen))

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children, *aux)

    @property
    def rank(self) -> int:
        """Current truncation rank k (0 for a freshly initialized state)."""
        return int(self.s.shape[0])

    @property
    def width(self) -> int:
        """Column-block width W = ceil(n / num_blocks)."""
        return sparse.block_width(self.n, self.num_blocks)

    @property
    def n_pad(self) -> int:
        """Padded column count D*W that ``v`` rows are indexed by."""
        return self.num_blocks * self.width

    def trimmed_v(self) -> jnp.ndarray:
        """``v`` with the padding columns trimmed back off — rows in
        ORIGINAL column order, the front-door convention."""
        return self.v[:self.n]

    def reshard_for_restore(self) -> "StreamingSVDState":
        """Called by ``Checkpointer.restore`` after the pytree rebuild:
        re-shard ``v`` onto the CURRENT device count when it matches the
        column universe (checkpoints are saved gathered, so a state
        saved on 8 devices restores onto 1 — and vice versa — without
        the file knowing either layout).  Placement follows the ACTIVE
        device pool (:func:`set_stream_devices`), so a post-failure
        restore re-shards onto the survivors — or lands gathered on the
        pool's first device when too few survive for one block each."""
        if (stream_device_count() == self.num_blocks
                and stream_device_count() > 1):
            return shard_state(self)
        if _STREAM_DEVICES is not None:
            # Restricted pool: make sure nothing stays resident on an
            # evicted device (the default placement may be the dead one).
            return gather_state(self)
        return self


def stream_mesh(num_blocks: int, devices=None):
    """The one-axis (num_blocks,) mesh the sharded ingest runs on — one
    column block per device, same convention as core/distributed.py.
    The mesh takes the first ``num_blocks`` devices of ``devices`` (the
    active pool by default), so after an eviction the supervisor only
    has to shrink the pool and every mesh built here lands on
    survivors."""
    pool = tuple(devices) if devices is not None else stream_devices()
    if len(pool) < num_blocks:
        raise ValueError(
            f"sharded streaming needs one device per column block: "
            f"num_blocks={num_blocks} but only {len(pool)} healthy "
            f"device(s) in the stream pool")
    if _STREAM_DEVICES is None and devices is None:
        # Unrestricted default: keep jax.make_mesh's device ordering so
        # pre-recovery behavior (and compiled caches) are untouched.
        if jax.device_count() != num_blocks:
            raise ValueError(
                f"sharded streaming needs one device per column block: "
                f"num_blocks={num_blocks} but device_count="
                f"{jax.device_count()}")
        return make_mesh((num_blocks,), (STREAM_AXIS,))
    return make_mesh((num_blocks,), (STREAM_AXIS,), devices=pool[:num_blocks])


def shard_state(state: StreamingSVDState, mesh=None) -> StreamingSVDState:
    """``v`` sharded row-wise over the mesh (one column block's (W, k)
    slice per device).  Values are untouched — ``u``/``s``/``key`` stay
    replicated-small and placement is the only thing that changes."""
    if mesh is None:
        mesh = stream_mesh(state.num_blocks)
    return dataclasses.replace(
        state, v=jax.device_put(state.v, NamedSharding(mesh,
                                                       P(STREAM_AXIS, None))))


def gather_state(state: StreamingSVDState, device=None) -> StreamingSVDState:
    """Every array on one device (the active pool's first by default) —
    the layout a single-host ingest (or any host-side consumer)
    expects.  Inverse of :func:`shard_state`; values are untouched."""
    dev = device if device is not None else stream_devices()[0]
    return jax.tree.map(lambda x: jax.device_put(x, dev), state)


def init_state(
    n: int,
    *,
    num_blocks: int,
    key: Optional[jax.Array] = None,
    mesh=None,
) -> StreamingSVDState:
    """A rank-0 state over an ``n``-column universe split ``num_blocks``
    ways.  The first ingest grows it to the batch's rank; no
    special-casing anywhere (empty panels concatenate away).  Passing a
    ``mesh`` (or ``mesh="auto"`` for the default one-block-per-device
    mesh) starts the state in the sharded layout for
    ``stream_backend="shard_map"`` streams."""
    if n < 1:
        raise ValueError(f"init_state needs n >= 1 columns, got {n}")
    if num_blocks < 1:
        raise ValueError(f"init_state needs num_blocks >= 1, got {num_blocks}")
    if key is None:
        key = ranky.default_key()
    w = sparse.block_width(n, num_blocks)
    state = StreamingSVDState(
        u=jnp.zeros((0, 0), jnp.float32),
        s=jnp.zeros((0,), jnp.float32),
        v=jnp.zeros((num_blocks * w, 0), jnp.float32),
        key=key,
        n=n, num_blocks=num_blocks,
        rows_seen=0, batches_seen=0,
        lonely_rows_seen=0, repaired_rows_seen=0)
    if mesh is None:
        return state
    return shard_state(state, None if mesh == "auto" else mesh)


# ---------------------------------------------------------------------------
# Delta normalization: one adapter for the three accepted representations
# ---------------------------------------------------------------------------

Delta = Union[np.ndarray, jnp.ndarray, "sparse.COOMatrix", "sparse.BlockEll"]


def delta_shape(delta: Delta) -> Tuple[int, int]:
    """(batch rows, columns) of any accepted delta representation."""
    if isinstance(delta, sparse.BlockEll):
        return delta.m, delta.n
    if isinstance(delta, sparse.COOMatrix):
        return delta.shape
    arr = np.asarray(delta)
    if arr.ndim != 2:
        raise ValueError(f"dense delta must be 2-D, got shape {arr.shape}")
    return arr.shape[0], arr.shape[1]


def as_delta(delta: Delta, state: StreamingSVDState):
    """Normalize a batch of new rows into the state's column universe.

    * dense (m_b, n) rows — zero-padded to the universe's block multiple
      (lossless) and handed to the dense engine path;
    * ``COOMatrix`` — converted to a ``BlockEll`` over the universe's
      ``num_blocks`` (sparse-native; the batch is never densified);
    * ``BlockEll`` — passed through (its universe must match).

    Every representation must already be indexed by the state's column
    universe: ``delta`` columns == ``state.n``.
    """
    m_b, n_d = delta_shape(delta)
    if m_b < 1:
        raise ValueError(f"delta has {m_b} rows; an ingest needs >= 1")
    if n_d != state.n:
        if (n_d == state.n_pad
                and not isinstance(delta, (sparse.BlockEll,
                                           sparse.COOMatrix))):
            # Already in padded column order (n_pad = D * W): the
            # normalization is idempotent, so the window driver can
            # normalize once for bucketing and re-submit the result.
            return jnp.asarray(delta, dtype=jnp.float32)
        raise ValueError(
            f"delta has {n_d} columns but the streaming state's column "
            f"universe is n={state.n}; deltas must be indexed by the "
            f"universe (pad new-column data into it up front)")
    if isinstance(delta, sparse.BlockEll):
        if delta.num_blocks != state.num_blocks:
            raise ValueError(
                f"BlockEll delta has {delta.num_blocks} blocks but the "
                f"state's universe has num_blocks={state.num_blocks}")
        return delta
    if isinstance(delta, sparse.COOMatrix):
        return sparse.block_ell_from_coo(delta, state.num_blocks)
    arr = np.asarray(delta)
    return jnp.asarray(
        sparse.pad_to_block_multiple(arr, state.num_blocks).astype(
            np.float32))
