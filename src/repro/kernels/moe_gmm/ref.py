"""Pure-jnp oracle for the grouped matmul: each row times its group's
weight, by gather, in float32 at the highest precision."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def moe_gmm_ref(x, w, group_sizes):
    """x (M, K), w (G, K, N), group_sizes (G,) -> (M, N) in x's dtype; rows
    past the last group are zero."""
    m = x.shape[0]
    ends = jnp.cumsum(group_sizes)
    rows = jnp.arange(m)
    gid = jnp.searchsorted(ends, rows, side="right")
    out = jnp.einsum("mk,mkn->mn", x.astype(jnp.float32),
                     w[jnp.minimum(gid, w.shape[0] - 1)].astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    return jnp.where((rows < ends[-1])[:, None], out, 0.0).astype(x.dtype)
