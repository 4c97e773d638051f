"""Pallas TPU kernel: candidate merge + Gumbel-max sampling, one pass.

Stage 2 of the fused sampler.  Stage 1 is ``topk_logits_tiles`` (reused
from kernels/topk_logits): per-vocab-tile top-k_cap candidates.  This
kernel takes the (R, C = nTiles*k_cap) candidate values/indices and, in
one VMEM pass per row block:

  1. merges them to the global top-k_cap (k_cap rounds of iterative
     max-extraction with min-position tie-break — candidate positions
     are ordered by vocab tile then rank, so min position == min vocab
     index, matching ``lax.top_k``'s stable ordering bitwise);
  2. temperature-scales, softmaxes over the k_cap candidates, builds
     the exclusive cumulative mass with a strict-upper-triangular
     matmul (no cumsum — Mosaic-friendly and bitwise vs the ref);
  3. applies the per-row top-k / top-p keep mask, adds the precomputed
     Gumbel noise, argmaxes, and emits the sampled vocab id — greedy
     sentinel rows (temperature <= 0) emit rank 0.

Everything after stage 1 is (R, k_cap)-shaped arithmetic: the sampler
never materializes a (B, V) sort or argsort.  ``greedy=True`` (static)
compiles steps 2–3 away entirely; the token is the rank-0 index, which
equals ``jnp.argmax(logits)`` bitwise.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG = -3.4e38          # candidate-extraction mask (~f32 min)
NEG_INF = -1e30        # sampling keep-mask, matches serve/sampling


def _kernel(cv_ref, ci_ref, t_ref, tk_ref, tp_ref, g_ref,
            vals_ref, idx_ref, tok_ref, *, k_cap: int, greedy: bool):
    cv = cv_ref[...].astype(jnp.float32)                  # (R, C)
    ci = ci_ref[...]
    r, c = cv.shape
    col = jax.lax.broadcasted_iota(jnp.int32, cv.shape, 1)
    rank = jax.lax.broadcasted_iota(jnp.int32, (r, k_cap), 1)

    def round_(i, carry):
        cv, vals, idx = carry
        m = jnp.max(cv, axis=1)
        is_max = cv == m[:, None]
        a = jnp.min(jnp.where(is_max, col, c), axis=1)    # min position
        one = col == a[:, None]
        vocab = jnp.sum(jnp.where(one, ci, 0), axis=1)
        # rank i written by select (Mosaic has no lane dynamic_update_slice)
        vals = jnp.where(rank == i, m[:, None], vals)
        idx = jnp.where(rank == i, vocab[:, None], idx)
        cv = jnp.where(one, NEG, cv)
        return cv, vals, idx

    vals0 = jnp.full((r, k_cap), NEG, jnp.float32)
    idx0 = jnp.zeros((r, k_cap), jnp.int32)
    _, vals, idx = jax.lax.fori_loop(0, k_cap, round_, (cv, vals0, idx0))
    vals_ref[...] = vals
    idx_ref[...] = idx
    if greedy:
        tok_ref[...] = idx[:, :1]
        return

    t = t_ref[...]                                        # (R, 1)
    safe_t = jnp.where(t > 0, t, 1.0).astype(jnp.float32)
    svals = vals / safe_t
    e = jnp.exp(svals - svals[:, :1])                     # rank 0 = max
    probs = e / e.sum(axis=1, keepdims=True)
    ri = jax.lax.broadcasted_iota(jnp.int32, (k_cap, k_cap), 0)
    rj = jax.lax.broadcasted_iota(jnp.int32, (k_cap, k_cap), 1)
    tri = (ri < rj).astype(jnp.float32)
    excl = jax.lax.dot(probs, tri,
                       precision=jax.lax.Precision.HIGHEST)
    k_eff = jnp.where(tk_ref[...] > 0,
                      jnp.minimum(tk_ref[...], k_cap), k_cap)   # (R, 1)
    keep = rank < k_eff
    keep &= excl < tp_ref[...]
    keep |= rank == 0
    score = jnp.where(keep, svals, NEG_INF) + g_ref[...]
    m = jnp.max(score, axis=1)
    a = jnp.min(jnp.where(score == m[:, None], rank, k_cap), axis=1)
    sampled = jnp.sum(jnp.where(rank == a[:, None], idx, 0), axis=1)
    tok_ref[...] = jnp.where(t > 0, sampled[:, None],
                             idx[:, :1]).astype(jnp.int32)


@functools.partial(jax.jit,
                   static_argnames=("k_cap", "greedy", "interpret"))
def topk_sample_tiles(cand_v, cand_i, temp, top_k, top_p, gumbel, *,
                      k_cap: int, greedy: bool = False,
                      interpret: bool = False):
    """cand_v/cand_i (R, C) per-tile candidates (C padded, NEG-filled);
    temp/top_k/top_p (R, 1); gumbel (R, k_cap).  R % r_tile == 0.

    Returns (vals (R,k_cap) f32 desc, idx (R,k_cap) i32, token (R,1) i32).
    """
    rr, c = cand_v.shape
    r_tile = 128 if rr >= 128 else rr
    kern = functools.partial(_kernel, k_cap=k_cap, greedy=greedy)
    row = lambda i: (i, 0)
    vals, idx, tok = pl.pallas_call(
        kern,
        grid=(rr // r_tile,),
        in_specs=[pl.BlockSpec((r_tile, c), row),
                  pl.BlockSpec((r_tile, c), row),
                  pl.BlockSpec((r_tile, 1), row),
                  pl.BlockSpec((r_tile, 1), row),
                  pl.BlockSpec((r_tile, 1), row),
                  pl.BlockSpec((r_tile, k_cap), row)],
        out_specs=[pl.BlockSpec((r_tile, k_cap), row),
                   pl.BlockSpec((r_tile, k_cap), row),
                   pl.BlockSpec((r_tile, 1), row)],
        out_shape=[jax.ShapeDtypeStruct((rr, k_cap), jnp.float32),
                   jax.ShapeDtypeStruct((rr, k_cap), jnp.int32),
                   jax.ShapeDtypeStruct((rr, 1), jnp.int32)],
        interpret=interpret,
    )(cand_v, cand_i, temp, top_k, top_p, gumbel)
    return vals, idx, tok
