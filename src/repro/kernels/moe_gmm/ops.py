"""Public wrapper: the Pallas grouped matmul on TPU, ``lax.ragged_dot``
elsewhere (kernels/_dispatch).  Both are differentiable, and both take a
``group_offset`` for a shard that holds only some of the groups."""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels._dispatch import auto_interpret, auto_use_kernel
from repro.kernels.moe_gmm.kernel import gmm_tiles


def moe_gmm(x, w, group_sizes, *, group_offset=None,
            use_kernel: Optional[bool] = None,
            interpret: Optional[bool] = None):
    """x (M, K) rows sorted by group, w (G, K, N), group_sizes (G,) int32
    with sum M -> (M, N) in x's dtype (float32 accumulation): row r of
    group g is ``x[r] @ w[g]``.  Empty groups are allowed.

    With ``group_offset`` (an int32 scalar, may be traced), ``group_sizes``
    has more groups than ``w`` and ``w`` holds groups ``group_offset`` ..
    ``group_offset + G - 1`` of them (an expert-parallel shard's experts);
    the rows of every other group come out zero."""
    if auto_use_kernel(use_kernel):
        return gmm_tiles(x, w, group_sizes, group_offset=group_offset,
                         interpret=auto_interpret(interpret))
    w = w.astype(x.dtype)
    if group_offset is not None:
        # the rows before and after this shard's groups form two more
        # groups, whose weights are zero
        g = w.shape[0]
        first = jnp.arange(group_sizes.shape[0]) < group_offset
        before = jnp.sum(jnp.where(first, group_sizes, 0))
        local = jax.lax.dynamic_slice_in_dim(group_sizes, group_offset, g)
        group_sizes = jnp.concatenate(
            [before[None], local, (x.shape[0] - before - local.sum())[None]])
        zero = jnp.zeros((1,) + w.shape[1:], w.dtype)
        w = jnp.concatenate([zero, w, zero])
    return jax.lax.ragged_dot(x, w, group_sizes.astype(jnp.int32),
                              preferred_element_type=x.dtype)
