"""Pallas TPU kernel: fused logsumexp + top-k gather over vocab tiles.

The student loss (paper §3.2.2) needs, per frame, (a) the full-vocab
logsumexp of the student logits and (b) the k student logits at the
teacher's stored indices.  Materializing (T, V) logits for V=262k at
train batch sizes would blow HBM; the fused kernel streams (D, Vt) tiles
of the unembedding through the MXU and keeps only:

  m, l : online logsumexp state            (Tt, 1)   f32
  g    : gathered logits at teacher ids    (Tt, K)   f32

VMEM working set per program: h (Tt, D) + w (D, Vt) + logits (Tt, Vt)
+ scratch — with Tt=128, D<=8192 f32 h-tile is 4 MB; callers chunk D
upstream for the few archs above that (ops.py notes).  Grid is
(T/Tt, V/Vt), vocab innermost ("arbitrary" order semantics: scratch
accumulates across the V dimension; outputs written on the last step).

The gather never leaves VREGs: for each of the K teacher ids,
`gathered = sum over tile columns of (logits where col == idx)` — a
one-hot compare and a lane reduction, the TPU-native replacement for
the GPU's per-thread gather (Mosaic lowers no lane gather).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG = -1e30


def _kernel(h_ref, w_ref, idx_ref, lse_ref, g_ref, m_sc, l_sc, g_sc, *,
            v_tile: int, v_total: int, n_v: int, softcap: float):
    vj = pl.program_id(1)
    base = vj * v_tile

    @pl.when(vj == 0)
    def _init():
        m_sc[...] = jnp.full_like(m_sc[...], NEG)
        l_sc[...] = jnp.zeros_like(l_sc[...])
        g_sc[...] = jnp.full_like(g_sc[...], NEG)

    h = h_ref[...].astype(jnp.float32)                    # (Tt, D)
    w = w_ref[...].astype(jnp.float32)                    # (D, Vt)
    logits = jax.lax.dot(h, w, precision=jax.lax.Precision.HIGHEST)
    if softcap:
        logits = jnp.tanh(logits / softcap) * softcap
    col = jax.lax.broadcasted_iota(jnp.int32, logits.shape, 1)
    valid = base + col < v_total
    logits = jnp.where(valid, logits, NEG)

    # online logsumexp
    m_old = m_sc[...]                                     # (Tt, 1)
    m_new = jnp.maximum(m_old, logits.max(axis=1, keepdims=True))
    l_sc[...] = (l_sc[...] * jnp.exp(m_old - m_new)
                 + jnp.exp(logits - m_new).sum(axis=1, keepdims=True))
    m_sc[...] = m_new

    # gather teacher ids that live in this tile: select-by-equality, one
    # one-hot compare + lane reduction per k (Mosaic has no lane gather);
    # exactly one column matches, so the sum is the logit bitwise
    idx = idx_ref[...]                                    # (Tt, K)
    loc = idx - base
    k = idx.shape[1]
    kcol = jax.lax.broadcasted_iota(jnp.int32, idx.shape, 1)
    picked = jnp.zeros(idx.shape, jnp.float32)
    for j in range(k):
        hit = col == loc[:, j:j + 1]
        pj = jnp.sum(jnp.where(hit, logits, 0.0), axis=1, keepdims=True)
        picked = jnp.where(kcol == j, pj, picked)
    inside = (loc >= 0) & (loc < v_tile)
    g_sc[...] = jnp.where(inside, picked, g_sc[...])

    @pl.when(vj == n_v - 1)
    def _finish():
        lse_ref[...] = m_sc[...] + jnp.log(jnp.maximum(l_sc[...], 1e-30))
        g_ref[...] = g_sc[...]


@functools.partial(jax.jit, static_argnames=("t_tile", "v_tile", "softcap",
                                             "interpret", "v_total"))
def sparse_ce_tiles(h, w, idx, *, t_tile: int = 128, v_tile: int = 1024,
                    softcap: float = 0.0, interpret: bool = False,
                    v_total: int = 0):
    """h (T,D) T%Tt==0; w (D,V) V%Vt==0; idx (T,K).

    ``v_total``: the true (unpadded) vocab size — columns past it are
    masked out of the logsumexp.  Defaults to w's (padded) width.

    -> (lse (T,1) f32, gathered (T,K) f32).
    """
    t, d = h.shape
    v = w.shape[1]
    k = idx.shape[1]
    n_t, n_v = t // t_tile, v // v_tile
    kern = functools.partial(_kernel, v_tile=v_tile,
                             v_total=v_total or v, n_v=n_v,
                             softcap=softcap)
    lse, g = pl.pallas_call(
        kern,
        grid=(n_t, n_v),
        in_specs=[
            pl.BlockSpec((t_tile, d), lambda i, j: (i, 0)),
            pl.BlockSpec((d, v_tile), lambda i, j: (0, j)),
            pl.BlockSpec((t_tile, k), lambda i, j: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((t_tile, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((t_tile, k), lambda i, j: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((t, 1), jnp.float32),
            jax.ShapeDtypeStruct((t, k), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((t_tile, 1), jnp.float32),
            pltpu.VMEM((t_tile, 1), jnp.float32),
            pltpu.VMEM((t_tile, k), jnp.float32),
        ],
        interpret=interpret,
    )(h, w, idx)
    return lse, g
