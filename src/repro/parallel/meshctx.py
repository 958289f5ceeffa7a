"""Mesh discovery and the fleet's rank mesh.

``jax.set_mesh`` makes a mesh ambient; :func:`ambient_mesh` reads it back.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def ambient_mesh():
    """The mesh made ambient by ``jax.set_mesh``, or None."""
    mesh = jax.sharding.get_abstract_mesh()
    return None if mesh.empty else mesh


def make_rank_mesh(num_ranks: int, axis_name: str = "ranks"):
    """1-D mesh over every device for a `num_ranks`-rank fleet.

    Each device carries the same number of rank shards, so the device count
    must divide ``num_ranks``: anything else raises rather than quietly
    spreading the fleet over fewer devices than there are.

    The axis is ``AxisType.Auto``: the fleet step is shard_mapped over it,
    and the slot file, request grids and response pytrees around that
    shard_map stay ordinary arrays whose placement the compiler chooses
    (an ``Explicit`` axis would type every response as rank-sharded and
    refuse to mix it with them).
    """
    n_dev = jax.device_count()
    if num_ranks % n_dev:
        raise ValueError(
            f"num_ranks={num_ranks} cannot be split evenly over the "
            f"{n_dev} devices; use a multiple of {n_dev} ranks, pass a mesh "
            f"of your own, or mesh=False")
    return jax.make_mesh((n_dev,), (axis_name,), axis_types=(AxisType.Auto,))
