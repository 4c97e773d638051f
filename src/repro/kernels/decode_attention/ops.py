"""Public entry point for fused single-token decode attention.

``decode_attention`` is the op ``models/attention.attention_decode``
dispatches to when built with ``use_kernel`` — one call replacing the
separate RoPE / ring-write / mask / softmax·V passes of the XLA tail.

Backend resolution follows the ``kernels._dispatch`` convention:

  use_kernel=None   Pallas kernel on TPU, the pure-jnp ref twin
                    everywhere else (the twin is the *same math* as the
                    pre-kernel XLA path, so off-TPU greedy decode stays
                    bitwise token-identical; the Pallas interpreter is
                    ~5x slower than XLA on CPU and is reserved for
                    parity tests via use_kernel=True, interpret=True).
  interpret=None    compiled Mosaic on TPU, interpreter elsewhere.

Kernel-path layout notes: the head dim is zero-padded to a multiple of
128 lanes (zero lanes contribute nothing to either dot; RoPE rotates
only the real ``hd`` lanes), and the grouped-query dim G is zero-padded
to a sublane multiple of 8 (pad rows are sliced off the output).  The
cache slot count S is used as-is — padding S would corrupt the ring
``pos % S`` arithmetic — so the compiled path expects S % 8 == 0, which
every cache in this repo satisfies (slot counts are powers of two).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels._dispatch import auto_interpret, auto_use_kernel
from repro.kernels.decode_attention.kernel import decode_attention_tiles
from repro.kernels.decode_attention.ref import decode_attention_ref


def _pad_last(x, to: int):
    d = x.shape[-1]
    pad = -d % to
    if not pad:
        return x
    return jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, pad)])


@functools.partial(jax.jit,
                   static_argnames=("window", "softcap", "rope_theta",
                                    "write", "interpret"))
def _decode_attention_kernel(q, k_new, v_new, cache_k, cache_v, pos, *,
                             window, softcap, rope_theta, write, interpret):
    from repro.models import layers  # avoid import cycle at module load

    b, hq, _, hd = q.shape
    hkv = cache_k.shape[1]
    g = hq // hkv
    gp = -g % 8
    qg = q.reshape(b, hkv, g, hd)
    if gp:
        qg = jnp.pad(qg, ((0, 0), (0, 0), (0, gp), (0, 0)))
    qg = _pad_last(qg, 128)
    kn = _pad_last(k_new, 128)
    vn = _pad_last(v_new, 128)
    ck = _pad_last(cache_k, 128)
    cv = _pad_last(cache_v, 128)
    if rope_theta:
        cos, sin = layers.rope_tables(pos, hd, rope_theta)  # (B, hd/2)
        cos = cos.astype(jnp.float32)[:, None]           # (B, 1, hd/2)
        sin = sin.astype(jnp.float32)[:, None]
    else:
        cos = sin = jnp.zeros((b, 1, 1), jnp.float32)
    out = decode_attention_tiles(
        qg, kn, vn, ck, cv, pos.astype(jnp.int32), cos, sin,
        hd=hd, window=window, scale=float(1.0 / np.sqrt(hd)),
        softcap=softcap, rope=bool(rope_theta), write=write,
        interpret=interpret)
    o = out[0][:, :, :g, :hd].reshape(b, hq, 1, hd)
    if write:
        nk, nv = out[1], out[2]
        return o, nk[..., :hd], nv[..., :hd]
    return o, cache_k, cache_v


def decode_attention(q, k_new, v_new, cache_k, cache_v, pos, *,
                     window: int = 0, softcap: float = 0.0,
                     rope_theta: float = 0.0, write: bool = True,
                     use_kernel=None, interpret=None):
    """Fused decode-attention tail for one token per row.

    q (B,Hq,1,hd), k_new/v_new (B,Hkv,1,hd) post-projection pre-RoPE;
    cache_k/cache_v (B,Hkv,S,hd); pos (B,) int32.  Static knobs:
    ``rope_theta>0`` rotates q/k_new at pos inside the op; ``write``
    ring-writes the new token at ``pos % S`` (paged callers pre-write
    their pool and pass the gathered view with ``write=False``);
    ``window>0`` selects the SWA-ring validity mask.

    Returns (o (B,Hq,1,hd) f32, new cache_k, new cache_v) — caches are
    returned unchanged when ``write=False``.
    """
    if not auto_use_kernel(use_kernel):
        return decode_attention_ref(
            q, k_new, v_new, cache_k, cache_v, pos, window=window,
            softcap=softcap, rope_theta=rope_theta, write=write)
    return _decode_attention_kernel(
        q, k_new, v_new, cache_k, cache_v, pos, window=window,
        softcap=softcap, rope_theta=rope_theta, write=write,
        interpret=auto_interpret(interpret))
