"""Pallas TPU kernel: one-shot single-token decode attention.

The decode hot loop's tail — RoPE rotation, per-row one-hot K/V ring
write, slot-validity masking, masked softmax·V — is five separate XLA
passes today, each materializing a (B,Hkv,S,hd) intermediate (rotated
k, ck copy, cv copy, scores, probs).  At decode batch sizes the tail is
pure HBM bandwidth: ~5 full-cache round-trips per token.  This kernel
fuses all of it into one ``pallas_call`` over grid (B, Hkv): each
program pulls its row's (S, hd) K and V tiles into VMEM **once**,
applies the rotation to the incoming q/k vectors in VREGs, writes the
new token into its ring slot with an iota==slot select (no scatter),
masks by slot validity, and runs the (G,S)x(S,hd) softmax·V entirely
on-chip — cache traffic drops from ~5 passes to one read + one
token-row write (``input_output_aliases`` keeps the cache update
in-place on TPU).

Mask variants (static):
  window=0            linear layout: slot j valid iff j <= pos
  window=W            SWA ring: slot j holds the latest p <= pos with
                      p % S == j; valid iff 0 <= p and pos - p < W
  write=False         paged-gather view: the pool write + block-table
                      gather ran upstream (indices are data, not
                      schedule); the kernel fuses the mask + softmax·V
                      tail only, and emits no cache outputs.

The mask arithmetic mirrors ``models/attention.decode_slot_validity``
(the shared helper the ref oracle uses) in ``broadcasted_iota`` form —
parity is pinned kernel-vs-ref in tests/test_decode_kernels.py.

S and G are whole-row blocks: decode caches are short (a ring is at
most the window), so one program's VMEM working set — q (G,128) + 2x
(S,128) K/V + (G,S) scores — is ~70 KB at S=1024, far under the 16 MB
budget; no online-softmax banding is needed.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _rope_rotate(x, cos, sin, hd: int):
    """Rotate the first ``hd`` lanes of x (rows, hd_padded) in f32;
    padding lanes pass through untouched (they are zero)."""
    hd2 = hd // 2
    x1 = x[:, :hd2]
    x2 = x[:, hd2:hd]
    o1 = x1 * cos - x2 * sin
    o2 = x2 * cos + x1 * sin
    parts = [o1, o2]
    if x.shape[1] > hd:
        parts.append(x[:, hd:])
    return jnp.concatenate(parts, axis=1)


def _kernel(pos_ref, q_ref, kn_ref, vn_ref, ck_ref, cv_ref, cos_ref,
            sin_ref, *refs, hd: int, window: int, scale: float,
            softcap: float, rope: bool, write: bool):
    if write:
        o_ref, nk_ref, nv_ref = refs
    else:
        (o_ref,) = refs
    p = pos_ref[pl.program_id(0)]                        # SMEM scalar
    s = ck_ref.shape[2]
    q = q_ref[0, 0].astype(jnp.float32)                  # (G, hdp)
    kn = kn_ref[0, 0].astype(jnp.float32)                # (1, hdp)
    if rope:
        cos = cos_ref[0].astype(jnp.float32)             # (1, hd/2)
        sin = sin_ref[0].astype(jnp.float32)
        q = _rope_rotate(q, cos, sin, hd)
        kn = _rope_rotate(kn, cos, sin, hd)
    ck = ck_ref[0, 0]                                    # (S, hdp)
    cv = cv_ref[0, 0]
    if write:
        slot = jax.lax.rem(p, s)
        row = jax.lax.broadcasted_iota(jnp.int32, (s, 1), 0)
        ck = jnp.where(row == slot, kn.astype(ck.dtype), ck)
        cv = jnp.where(row == slot, vn_ref[0, 0].astype(cv.dtype), cv)
        nk_ref[0, 0] = ck
        nv_ref[0, 0] = cv

    sc = jax.lax.dot_general(q, ck.astype(jnp.float32),
                             (((1,), (1,)), ((), ())),
                             precision=jax.lax.Precision.HIGHEST) * scale
    if softcap:
        sc = jnp.tanh(sc / softcap) * softcap
    idx = jax.lax.broadcasted_iota(jnp.int32, sc.shape, 1)   # (G, S)
    if window:
        # decode_slot_validity ring math, iota form
        kpos = p - jax.lax.rem(p - idx, s)
        kpos = jnp.where(kpos > p, kpos - s, kpos)
        valid = (kpos >= 0) & (p - kpos < window) & (kpos <= p)
    else:
        valid = idx <= p
    sc = jnp.where(valid, sc, NEG_INF)
    m = sc.max(axis=1, keepdims=True)
    e = jnp.exp(sc - m)
    pr = e / e.sum(axis=1, keepdims=True)
    o_ref[0, 0] = jax.lax.dot(pr, cv.astype(jnp.float32),
                              precision=jax.lax.Precision.HIGHEST)


@functools.partial(jax.jit,
                   static_argnames=("hd", "window", "scale", "softcap",
                                    "rope", "write", "interpret"))
def decode_attention_tiles(q, k_new, v_new, ck, cv, pos, cos, sin, *,
                           hd: int, window: int, scale: float,
                           softcap: float, rope: bool, write: bool,
                           interpret: bool = False):
    """q (B,Hkv,G,hdp); k_new/v_new (B,Hkv,1,hdp); ck/cv (B,Hkv,S,hdp);
    pos (B,) i32; cos/sin (B, 1, hd/2) f32.  ``hd`` is the real head dim
    (lanes past it are padding).  Returns o (B,Hkv,G,hdp) f32 and, when
    ``write``, the updated caches (aliased in-place over ck/cv).

    ``pos`` is scalar-prefetched into SMEM (a (1, 1) VMEM block of a
    (B, 1) array is not (8, 128)-aligned); cos/sin carry a unit middle
    axis so each row's block spans the array's last two dims.
    """
    b, hkv, g, hdp = q.shape
    s = ck.shape[2]
    kern = functools.partial(_kernel, hd=hd, window=window, scale=scale,
                             softcap=softcap, rope=rope, write=write)
    row4 = lambda bi, hi, pos_ref: (bi, hi, 0, 0)
    row3 = lambda bi, hi, pos_ref: (bi, 0, 0)
    in_specs = [
        pl.BlockSpec((1, 1, g, hdp), row4),
        pl.BlockSpec((1, 1, 1, hdp), row4),
        pl.BlockSpec((1, 1, 1, hdp), row4),
        pl.BlockSpec((1, 1, s, hdp), row4),
        pl.BlockSpec((1, 1, s, hdp), row4),
        pl.BlockSpec((1, 1, cos.shape[2]), row3),
        pl.BlockSpec((1, 1, sin.shape[2]), row3),
    ]
    out_specs = [pl.BlockSpec((1, 1, g, hdp), row4)]
    out_shape = [jax.ShapeDtypeStruct((b, hkv, g, hdp), jnp.float32)]
    aliases = {}
    if write:
        out_specs += [pl.BlockSpec((1, 1, s, hdp), row4),
                      pl.BlockSpec((1, 1, s, hdp), row4)]
        out_shape += [jax.ShapeDtypeStruct(ck.shape, ck.dtype),
                      jax.ShapeDtypeStruct(cv.shape, cv.dtype)]
        # operand indices count the prefetched pos first: ck is 4, cv 5
        aliases = {4: 1, 5: 2}          # ck -> new k, cv -> new v
    out = pl.pallas_call(
        kern,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, hkv),
            in_specs=in_specs,
            out_specs=out_specs),
        out_shape=out_shape,
        input_output_aliases=aliases,
        interpret=interpret,
    )(pos, q, k_new, v_new, ck, cv, cos, sin)
    return out if write else (out[0],)
