"""The few JAX entry points the package wraps, in one place.

* ``shard_map_nocheck`` — ``jax.shard_map`` with the replication check
  off.
* ``make_mesh`` — ``jax.make_mesh`` with Auto axis types.
* ``trace_state_clean`` — True in eager code, False while a JAX
  transformation (jit, scan, shard_map, ...) is tracing.
"""
from __future__ import annotations

import jax


def shard_map_nocheck(fn, *, mesh, in_specs, out_specs):
    """shard_map with replication checking off (merge collectives produce
    replicated outputs the static checker can't see)."""
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def make_mesh(axis_shapes, axis_names, *, devices=None):
    """``jax.make_mesh`` with Auto axis types.  The default became
    Explicit, whose arrays carry their mesh in the type and then refuse
    any op that meets an array placed on another device set (a
    single-device oracle, a re-meshed survivor pool)."""
    return jax.make_mesh(axis_shapes, axis_names,
                         axis_types=(jax.sharding.AxisType.Auto,)
                         * len(axis_names),
                         devices=devices)


def trace_state_clean() -> bool:
    """True when no JAX transformation is tracing: host-side effects
    (spans, fault seams) fire only then, never into a traced program."""
    return jax.core.trace_ctx.is_top_level()
