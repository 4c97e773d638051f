"""Thin helpers over mesh / shard_map plumbing used by BMUF, GTC and the
examples: building host-local worker meshes, replicating trees, and a
data-parallel shard_map runner that works on any device count (including 1).
"""
from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.runtime.cluster import auto_mesh

tmap = jax.tree_util.tree_map


def worker_mesh(n: int = 0) -> Mesh:
    """1D worker mesh over the host's devices (capped at n if given)."""
    devs = jax.devices()
    if n:
        devs = devs[:n]
    return auto_mesh((len(devs),), ("worker",), devices=devs)


def replicate(tree, mesh: Mesh):
    sh = NamedSharding(mesh, P())
    return tmap(lambda x: jax.device_put(x, sh), tree)


def shard_batch(tree, mesh: Mesh, axis: str = "worker"):
    """Shard the leading dim over `axis`."""
    def put(x):
        return jax.device_put(x, NamedSharding(mesh, P(axis)))
    return tmap(put, tree)


def data_parallel(fn: Callable, mesh: Mesh, axis: str = "worker",
                  *, replicated_args=(0, 1)):
    """shard_map wrapper: args in `replicated_args` positions are replicated
    (params-like); the rest shard their leading dim over `axis`.  The
    returned fn has the same signature."""
    def wrapped(*args):
        in_specs = tuple(P() if i in replicated_args else P(axis)
                         for i in range(len(args)))

        def body(*sargs):
            return fn(*sargs)

        out = jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                            out_specs=P(axis), check_vma=False)(*args)
        return out
    return wrapped


def psum_tree(tree, axis: str):
    return tmap(lambda x: jax.lax.psum(x, axis), tree)


def pmean_tree(tree, axis: str):
    return tmap(lambda x: jax.lax.pmean(x, axis), tree)
