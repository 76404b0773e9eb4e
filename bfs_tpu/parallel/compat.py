"""The one place the mesh programs touch JAX's ``shard_map`` surface.

Every mesh program in :mod:`bfs_tpu.parallel` calls :func:`shard_map` and
:func:`pcast_varying` from here instead of the jax API directly, so the
varying-axis rules of the installed JAX are handled in one module.
"""

from __future__ import annotations

import jax
import numpy as np


def shard_map(f, *, mesh, in_specs, out_specs, axis_names=None):
    """``jax.shard_map``; ``axis_names`` is the set of mesh axes the body
    is manual over (None = all of them)."""
    kwargs = {}
    if axis_names is not None:
        kwargs["axis_names"] = set(axis_names)
    return jax.shard_map(
        f, mesh=mesh, in_specs=in_specs, out_specs=out_specs, **kwargs
    )


def pcast_varying(x, axes):
    """Mark ``x`` varying over ``axes``.

    ``jax.lax.pcast(..., to="varying")`` refuses an axis the value already
    varies over, so only the missing ones are cast (``jax.typeof(x).vma``
    is the set of axes a value varies over inside ``shard_map``)."""
    missing = tuple(a for a in axes if a not in jax.typeof(x).vma)
    if not missing:
        return x
    return jax.lax.pcast(x, missing, to="varying")


def pcast_carry(tree, axes):
    """:func:`pcast_varying` mapped over a pytree of loop-carry leaves.

    A ``while_loop`` carry leaf initialised replicated whose body output
    derives from axis-varying values (telemetry accumulators, the Beamer
    ``mu``/``prev`` frontier masses) must be cast to varying up front."""
    return jax.tree_util.tree_map(lambda x: pcast_varying(x, axes), tree)


#: Debug switch (set by tests): :func:`unvary` then also checks, when the
#: program runs, that every leaf it replicates is equal on every shard,
#: and raises if not.  Off, a diverging leaf would be silently maxed.
CHECK_REPLICATED = False


def _assert_replicated(lo, hi):
    for a, b in zip(lo, hi):
        if not np.array_equal(a, b):
            raise AssertionError(
                "unvary: a leaf typed replicated differs across shards"
            )


def unvary(tree, axes):
    """Make leaves that are equal on every shard but typed varying over
    ``axes`` invariant, so an out_spec can name them replicated.

    The all-gathered frontier words are the same on every shard, yet
    ``all_gather`` types them varying, and so is everything derived from
    them (telemetry, the direction hysteresis). ``pmax`` of equal values
    is that value; only the axes a leaf varies over are reduced.  With
    :data:`CHECK_REPLICATED` set, ``pmin`` must agree with it."""
    lows, highs = [], []

    def one(x):
        varying = tuple(a for a in axes if a in jax.typeof(x).vma)
        if not varying:
            return x
        v = x.astype("int32") if x.dtype == bool else x
        hi = jax.lax.pmax(v, varying)
        if CHECK_REPLICATED:
            lows.append(jax.lax.pmin(v, varying))
            highs.append(hi)
        return hi.astype(bool) if x.dtype == bool else hi

    out = jax.tree_util.tree_map(one, tree)
    if lows:
        jax.debug.callback(_assert_replicated, lows, highs)
    return out
