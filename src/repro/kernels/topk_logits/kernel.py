"""Pallas TPU kernel: blockwise top-k selection over the vocab axis.

Teacher target generation (paper §3.2.2) runs top-k(V=3,183 senones or up
to 262k tokens, k=20) over every frame — the selection is the hot loop the
paper parallelizes.  GPU implementations use warp-level bitonic/heap
selection; the TPU-native adaptation (DESIGN.md §2) is *iterative
max-extraction over VMEM tiles*: k rounds of (rowmax -> argmax-by-iota ->
mask) on an (R, Vt) tile, entirely in VREGs, no scatter, no sort network.
k=20 rounds x cheap vector ops beat a full sort when k << V.

Two-stage scheme for large V:
  stage 1 (this kernel): grid (rows/R, V/Vt); each program extracts the
    local top-k of its (R, Vt) tile into (R, k) candidate (val, idx) pairs.
  stage 2 (ops.py): merge the per-tile candidates — (R, nV*k) is tiny —
    with one jax.lax.top_k (itself a k-round extraction on one tile).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG = -3.4e38          # ~f32 min: masks extracted candidates


def _kernel(x_ref, vals_ref, idx_ref, *, k: int, v_tile: int, v_total: int):
    vj = pl.program_id(1)
    x = x_ref[...].astype(jnp.float32)                    # (R, Vt)
    r = x.shape[0]
    kp = vals_ref.shape[1]                                # k padded to lanes
    base = vj * v_tile
    col = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    lane = jax.lax.broadcasted_iota(jnp.int32, (r, kp), 1)
    # mask vocab padding tail so it never wins
    x = jnp.where(base + col < v_total, x, NEG)

    def round_(i, carry):
        x, vals, idx = carry
        m = jnp.max(x, axis=1)                            # (R,)
        # first column achieving the max (iota tie-break, matches lax.top_k)
        is_max = x == m[:, None]
        a = jnp.min(jnp.where(is_max, col, v_tile), axis=1)  # (R,)
        # candidate i lands in lane i by select: no lane-offset store
        vals = jnp.where(lane == i, m[:, None], vals)
        idx = jnp.where(lane == i, (base + a)[:, None], idx)
        x = jnp.where(col == a[:, None], NEG, x)
        return x, vals, idx

    vals0 = jnp.full((r, kp), NEG, jnp.float32)
    idx0 = jnp.zeros((r, kp), jnp.int32)
    _, vals, idx = jax.lax.fori_loop(0, k, round_, (x, vals0, idx0))
    vals_ref[...] = vals
    idx_ref[...] = idx


@functools.partial(jax.jit,
                   static_argnames=("k", "r_tile", "v_tile", "interpret"))
def topk_logits_tiles(x, *, k: int, r_tile: int = 128, v_tile: int = 2048,
                      interpret: bool = False):
    """x (R, V) f32/bf16, R % r_tile == 0, V % v_tile == 0 (pre-padded).

    Returns per-tile candidates (R, nV*k) vals f32 + idx i32, ordered by
    vocab tile, then rank within the tile.

    Each program writes its k candidates into a (r_tile, kp) block, kp =
    k rounded up to a lane multiple of 128: a (r_tile, k) block of a
    (R, nV*k) array is not (8, 128)-aligned and Mosaic refuses it.  The
    lane padding is dropped here, in XLA, before any consumer sees it.
    """
    rr, vv = x.shape
    grid = (rr // r_tile, vv // v_tile)
    kp = -(-k // 128) * 128
    kern = functools.partial(_kernel, k=k, v_tile=v_tile, v_total=vv)
    vals, idx = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[pl.BlockSpec((r_tile, v_tile), lambda i, j: (i, j))],
        out_specs=[pl.BlockSpec((r_tile, kp), lambda i, j: (i, j)),
                   pl.BlockSpec((r_tile, kp), lambda i, j: (i, j))],
        out_shape=[
            jax.ShapeDtypeStruct((rr, grid[1] * kp), jnp.float32),
            jax.ShapeDtypeStruct((rr, grid[1] * kp), jnp.int32),
        ],
        interpret=interpret,
    )(x)
    compact = lambda a: a.reshape(rr, grid[1], kp)[:, :, :k].reshape(
        rr, grid[1] * k)
    return compact(vals), compact(idx)
