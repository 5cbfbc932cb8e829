"""Pallas kernels inside a program that spans several devices.

GSPMD cannot partition a Mosaic kernel: a ``jit`` over more than one device
that holds a bare ``pallas_call`` is refused at lowering ("Mosaic kernels
cannot be automatically partitioned. Please wrap the call in a shard_map").
The local engines are mesh-agnostic — they run wherever their parameters
live, and on a role submesh of several chips (``number_of_actors`` > 1) that
is a replicated multi-device program. Two small pieces make the kernels legal
there, with no mesh threaded through any signature:

* the engines enter ``params_mesh(params)`` around a ``generate`` call: the
  mesh the parameters live on becomes JAX's context mesh, which is part of
  every trace's cache key;
* kernel call sites go through ``per_device(kernel)``: under a context mesh
  of several devices the call is wrapped in a ``shard_map`` with every operand
  and result replicated — each device runs the whole kernel on its own copy,
  which is what the replicated program around it does anyway. On one device,
  or already inside a ``shard_map`` (the dp-sharded paged engine), it is the
  bare call.
"""

from __future__ import annotations

import contextlib
import functools

import jax
from jax.sharding import NamedSharding, PartitionSpec as P


def per_device(kernel):
    """``kernel`` with array operands passed positionally, made legal inside
    a multi-device ``jit`` (see module docstring). Keyword arguments are
    static configuration."""

    @functools.wraps(kernel)
    def call(*args, **static):
        mesh = jax.sharding.get_abstract_mesh()
        if mesh.empty or mesh.size == 1 or mesh.manual_axes:
            return kernel(*args, **static)
        return jax.shard_map(
            functools.partial(kernel, **static),
            in_specs=P(), out_specs=P(), check_vma=False,
        )(*args)

    return call


@contextlib.contextmanager
def params_mesh(params):
    """Make the mesh ``params`` live on the context mesh for the duration
    (nothing to do for parameters on one device)."""
    leaves = jax.tree_util.tree_leaves(params)
    sharding = getattr(leaves[0], "sharding", None) if leaves else None
    if isinstance(sharding, NamedSharding) and sharding.mesh.size > 1:
        with jax.set_mesh(sharding.mesh):
            yield
    else:
        yield
