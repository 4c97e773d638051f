"""Pallas TPU kernel: the grouped matmul of the dropless expert layer.

Rows of ``x`` sorted by expert (group g is ``group_sizes[g]`` consecutive
rows) times that expert's weight: ``out[r] = x[r] @ w[g(r)]``.  The kernel
is the grouped matmul that ships with JAX,
``jax.experimental.pallas.ops.tpu.megablox.gmm`` (tiles visit only the
rows of the groups they cover; a custom VJP for training).  This module
chooses its tiles from the shapes.  Its compiled custom call carries the
wrapper's name, ``gmm`` (``gmm.N`` among a trace's op events).
"""
from __future__ import annotations

import jax.numpy as jnp
from jax.experimental.pallas.ops.tpu.megablox import gmm

M_TILE = 512           # rows per tile
K_TILE = 512           # contraction per step; a shorter K is one whole step
N_WHOLE = 2048         # outputs up to this width are one tile


def tiling(m: int, k: int, n: int):
    """(tm, tk, tn) for an (m, k) x (k, n) group product: tk and tn are the
    whole dimension when it is small (a whole dim needs no 128 alignment)
    and 128-aligned tiles otherwise; a K remainder is masked in the kernel,
    so no tile of the output is computed twice."""
    tm = M_TILE if m >= M_TILE else max(16, -(-m // 16) * 16)
    tk = k if k <= K_TILE else K_TILE
    tn = n if n <= N_WHOLE else 1024
    return tm, tk, tn


def gmm_tiles(x, w, group_sizes, *, group_offset=None,
              interpret: bool = False):
    """x (M, K), w (G, K, N), group_sizes (G,) int32 -> (M, N) in x's dtype,
    float32 accumulation.  Rows are padded to the row tile here; rows past
    the groups are not written (the caller's groups cover its rows).  With
    ``group_offset``, ``w`` holds groups ``group_offset`` .. ``+ G - 1`` of
    ``group_sizes`` and the kernel zeroes the rows of the others."""
    m, k = x.shape
    n = w.shape[-1]
    tm, tk, tn = tiling(m, k, n)
    pad = (-m) % tm
    xp = jnp.pad(x, ((0, pad), (0, 0))) if pad else x
    out = gmm(xp, w.astype(x.dtype), group_sizes.astype(jnp.int32),
              x.dtype, (tm, tk, tn),
              None if group_offset is None
              else jnp.asarray(group_offset, jnp.int32),
              None, False, interpret)
    return out[:m] if pad else out
