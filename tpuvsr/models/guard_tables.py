"""Guards as tables of the state: what the kernels whose stage 1 is one
table a state share (`VSRKernel`; `ST03Kernel` and, through it,
`CP06Kernel`).

A kernel writes ``guard_x_table(st)``: the action's enabling for ALL
its lanes, shaped like its lane decode ([R], [R, V], [M], [M, R], ...;
row-major = the lane number ``_lane_count`` and the ``act_*`` use),
and hands the engines ``guard_x = lanes_of(guard_x_table)``.  Why, and
the rules a table keeps (what it may read; that the action bodies stay
the oracle; what a lost lane costs), stand in cp06_kernel.py above
`CP06Kernel`'s tables and hold for every class alike.
"""

import jax.numpy as jnp

from .vsr import H_DEST


def lanes_of(table):
    """The guard a lane that the engines call (`_guard_fns`), read off
    the guard's table of the state: under a vmap over lanes the table
    stays unbatched, so it is computed once a state.  The function
    carries its table (``guard.table``): the mark `table_lanes`
    counts, which a subclass's override a lane does not bear."""
    def guard(self, st, lane):
        return table(self, st).reshape(-1)[lane]
    guard.table = table
    return guard


def table_lanes(kern):
    """The lanes of `kern`'s actions whose guard, as `_guard_fns`
    hands it to the engines, is one table a state (gauge
    ``guard_table_lanes``)."""
    return sum(kern._lane_count(name)
               for name, guard in zip(kern.action_names, kern._guard_fns())
               if hasattr(guard, "table"))


def replica_ids(kern):
    """[R]: the replica ids 1..R."""
    return jnp.arange(1, kern.R + 1, dtype=jnp.int32)


def at_dest(kern, st):
    """``at(plane)``: an ``[R, ...]`` plane (or ``st[plane]``) at each
    message's dest replica, ``[M, ...]``.  The replica is `_dest_i`'s:
    dest - 1 clipped into 0..R-1 (AnyDest and a free slot's 0 read
    replica 0, as the guards a lane do)."""
    dest_i = jnp.clip(st["m_hdr"][:, H_DEST] - 1, 0, kern.R - 1)
    hot = dest_i[:, None] == jnp.arange(kern.R, dtype=jnp.int32)  # [M, R]

    def at(plane):
        if isinstance(plane, str):
            plane = st[plane]
        sel = hot.reshape(hot.shape + (1,) * (plane.ndim - 1))
        if plane.dtype == jnp.bool_:
            return (sel & plane).any(1)
        return jnp.where(sel, plane, 0).sum(1)
    return at
