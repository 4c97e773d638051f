"""GQA attention: flash-style KV-chunked full attention, sliding-window
attention with static banded slicing, and cached single-token decode.

Memory discipline: scores are never materialized beyond a
(chunk_q x chunk_kv) or (chunk_q x window+chunk_q) tile, so 32k prefill
lowers with bounded temporaries.  The Pallas kernel in
``repro.kernels.swa_attention`` is the TPU twin of the windowed path; this
file is the XLA-lowerable implementation used by the dry-run and on CPU.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import layers
from repro.models import paging as paging_mod

NEG_INF = -1e30


def init_attention(key, cfg, spec):
    d, hq, hkv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    ks = jax.random.split(key, 6)
    p = {
        "wq": layers.dense_init(ks[0], d, hq * hd),
        "wk": layers.dense_init(ks[1], d, hkv * hd),
        "wv": layers.dense_init(ks[2], d, hkv * hd),
        "wo": layers.dense_init(ks[3], hq * hd, d),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((hq * hd,), jnp.float32)
        p["bk"] = jnp.zeros((hkv * hd,), jnp.float32)
        p["bv"] = jnp.zeros((hkv * hd,), jnp.float32)
    if cfg.qk_norm:
        p["q_norm"] = jnp.zeros((hd,), jnp.float32)
        p["k_norm"] = jnp.zeros((hd,), jnp.float32)
    return p


def _project_qkv(params, cfg, x, positions, *, rope=True):
    """x (B,S,D) -> q (B,Hq,S,hd), k/v (B,Hkv,S,hd), rope applied.

    ``rope=False`` skips the rotation (the fused decode kernel applies
    it inside the ``pallas_call`` instead — see ``kernels/decode_attention``).
    """
    b, s, _ = x.shape
    hq, hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    q = x @ params["wq"].astype(x.dtype)
    k = x @ params["wk"].astype(x.dtype)
    v = x @ params["wv"].astype(x.dtype)
    if cfg.qkv_bias:
        q = q + params["bq"].astype(x.dtype)
        k = k + params["bk"].astype(x.dtype)
        v = v + params["bv"].astype(x.dtype)
    q = q.reshape(b, s, hq, hd).transpose(0, 2, 1, 3)
    k = k.reshape(b, s, hkv, hd).transpose(0, 2, 1, 3)
    v = v.reshape(b, s, hkv, hd).transpose(0, 2, 1, 3)
    if cfg.qk_norm:
        q = layers.rms_head_norm(params["q_norm"], q)
        k = layers.rms_head_norm(params["k_norm"], k)
    if cfg.pos_emb == "rope" and rope:
        cos, sin = layers.rope_tables(positions, hd, cfg.rope_theta)
        q = layers.apply_rope(q, cos, sin)
        k = layers.apply_rope(k, cos, sin)
    return q, k, v


def _group(q, hkv):
    """(B,Hq,S,hd) -> (B,Hkv,G,S,hd)."""
    b, hq, s, hd = q.shape
    return q.reshape(b, hkv, hq // hkv, s, hd)


def flash_full_attention(q, k, v, q_pos, kv_pos, *, causal=True,
                         attn_softcap=0.0, chunk_q=512, chunk_kv=1024,
                         bias_mask=None, scale=None):
    """Two-level chunked flash attention.

    q (B,Hkv,G,Sq,hd); k/v (B,Hkv,Skv,hd); q_pos (Sq,), kv_pos (Skv,).
    ``scale`` multiplies the scores (default ``1/sqrt(hd)``).
    Returns (B,Hkv,G,Sq,hd).
    """
    b, hkv, g, sq, hd = q.shape
    hdv = v.shape[-1]                 # may differ from hd (e.g. MLA)
    skv = k.shape[2]
    if scale is None:
        scale = 1.0 / np.sqrt(hd)
    cq = min(chunk_q, sq)
    ckv = min(chunk_kv, skv)
    # pad seq dims to chunk multiples
    pq = (-sq) % cq
    pkv = (-skv) % ckv
    qp = jnp.pad(q, ((0, 0), (0, 0), (0, 0), (0, pq), (0, 0)))
    kp = jnp.pad(k, ((0, 0), (0, 0), (0, pkv), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, 0), (0, pkv), (0, 0)))
    qpos = jnp.pad(q_pos, (0, pq), constant_values=-1)
    kpos = jnp.pad(kv_pos, (0, pkv), constant_values=2**30)
    nq, nkv = (sq + pq) // cq, (skv + pkv) // ckv
    qp = qp.reshape(b, hkv, g, nq, cq, hd)
    kp = kp.reshape(b, hkv, nkv, ckv, hd)
    vp = vp.reshape(b, hkv, nkv, ckv, hdv)
    qpos = qpos.reshape(nq, cq)
    kpos = kpos.reshape(nkv, ckv)

    def q_chunk(carry, qi):
        qc, qpc = qi                      # (B,Hkv,G,cq,hd), (cq,)
        m0 = jnp.full((b, hkv, g, cq), NEG_INF, jnp.float32)
        l0 = jnp.zeros((b, hkv, g, cq), jnp.float32)
        a0 = jnp.zeros((b, hkv, g, cq, hdv), jnp.float32)

        def kv_chunk(acc, ki):
            m, l, a = acc
            kc, vc, kpc = ki              # (B,Hkv,ckv,hd), ..., (ckv,)
            s_ = jnp.einsum("bhgqd,bhkd->bhgqk", qc.astype(jnp.float32),
                            kc.astype(jnp.float32)) * scale
            s_ = layers.softcap(s_, attn_softcap)
            mask = qpc[:, None] >= 0
            if causal:
                mask = mask & (qpc[:, None] >= kpc[None, :])
            s_ = jnp.where(mask, s_, NEG_INF)
            m_new = jnp.maximum(m, s_.max(axis=-1))
            corr = jnp.exp(m - m_new)
            p = jnp.exp(s_ - m_new[..., None])
            l_new = l * corr + p.sum(axis=-1)
            a_new = a * corr[..., None] + jnp.einsum(
                "bhgqk,bhkd->bhgqd", p, vc.astype(jnp.float32))
            return (m_new, l_new, a_new), None

        (m, l, a), _ = jax.lax.scan(
            kv_chunk, (m0, l0, a0),
            (kp.transpose(2, 0, 1, 3, 4), vp.transpose(2, 0, 1, 3, 4), kpos))
        out = a / jnp.maximum(l[..., None], 1e-30)
        return carry, out

    _, outs = jax.lax.scan(q_chunk, None,
                           (qp.transpose(3, 0, 1, 2, 4, 5), qpos))
    # outs (nq, B, Hkv, G, cq, hd)
    out = outs.transpose(1, 2, 3, 0, 4, 5).reshape(b, hkv, g, nq * cq, hdv)
    return out[..., :sq, :].astype(q.dtype)


def windowed_attention(q, k, v, q_pos0, window, *, attn_softcap=0.0,
                       chunk_q=512):
    """Sliding-window causal attention; Sq == Skv (prefill/train).

    q (B,Hkv,G,S,hd); k/v (B,Hkv,S,hd).  For query chunk i only the
    [i*cq - window, i*cq + cq) key band is touched (static slice), so FLOPs
    scale as S * (window + cq) instead of S^2.
    """
    b, hkv, g, s, hd = q.shape
    scale = 1.0 / np.sqrt(hd)
    cq = min(chunk_q, s)
    pq = (-s) % cq
    # pad keys left by `window` (masked) and right to a chunk multiple
    w = int(window)
    kp = jnp.pad(k, ((0, 0), (0, 0), (w, pq), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, 0), (w, pq), (0, 0)))
    qp = jnp.pad(q, ((0, 0), (0, 0), (0, 0), (0, pq), (0, 0)))
    nq = (s + pq) // cq
    band = w + cq

    def q_chunk(carry, i):
        qc = jax.lax.dynamic_slice_in_dim(qp, i * cq, cq, axis=3)
        kc = jax.lax.dynamic_slice_in_dim(kp, i * cq, band, axis=2)
        vc = jax.lax.dynamic_slice_in_dim(vp, i * cq, band, axis=2)
        qpos = q_pos0 + i * cq + jnp.arange(cq)          # absolute positions
        kpos = q_pos0 + i * cq - w + jnp.arange(band)
        s_ = jnp.einsum("bhgqd,bhkd->bhgqk", qc.astype(jnp.float32),
                        kc.astype(jnp.float32)) * scale
        s_ = layers.softcap(s_, attn_softcap)
        valid = (kpos[None, :] >= q_pos0) & (kpos[None, :] <= qpos[:, None]) \
            & (qpos[:, None] - kpos[None, :] < w)
        s_ = jnp.where(valid, s_, NEG_INF)
        p = jax.nn.softmax(s_, axis=-1)
        out = jnp.einsum("bhgqk,bhkd->bhgqd", p, vc.astype(jnp.float32))
        return carry, out

    _, outs = jax.lax.scan(q_chunk, None, jnp.arange(nq))
    out = outs.transpose(1, 2, 3, 0, 4, 5).reshape(b, hkv, g, nq * cq, hd)
    return out[..., :s, :].astype(q.dtype)


def attention_apply(params, cfg, spec, x, positions):
    """Full-sequence (train/prefill) attention block body. x (B,S,D)."""
    q, k, v = _project_qkv(params, cfg, x, positions)
    qg = _group(q, cfg.n_kv_heads)
    s = x.shape[1]
    # cost-probe mode: one whole-sequence chunk (no scan; same FLOPs as
    # the chunked schedule, which also executes masked blocks)
    cq = s if cfg.attn_whole_seq else 512
    ckv = s if cfg.attn_whole_seq else 1024
    if spec.mixer == "swa" and spec.window and spec.window < x.shape[1]:
        o = windowed_attention(qg, k, v, 0, spec.window,
                               attn_softcap=cfg.attn_softcap, chunk_q=cq)
    else:
        o = flash_full_attention(qg, k, v, positions, positions,
                                 attn_softcap=cfg.attn_softcap,
                                 chunk_q=cq, chunk_kv=ckv)
    b, s, _ = x.shape
    o = o.reshape(b, cfg.n_heads, s, cfg.resolved_head_dim)
    o = o.transpose(0, 2, 1, 3).reshape(b, s, -1)
    return o @ params["wo"].astype(x.dtype)


# ---------------------------------------------------------------- decode

def init_attn_cache(cfg, spec, batch, seq_len, dtype, paging=None):
    hkv, hd = cfg.n_kv_heads, cfg.resolved_head_dim
    if paging is not None and paging_mod.is_paged_spec(spec):
        # pooled layout: no batch axis — rows reach their pages through
        # the shared block table (cache root "pages"); see models/paging
        slots = paging.pool_slots
        return {"k": jnp.zeros((slots, hkv, hd), dtype),
                "v": jnp.zeros((slots, hkv, hd), dtype)}
    slots = min(spec.window, seq_len) if (spec.mixer == "swa" and spec.window) \
        else seq_len
    return {"k": jnp.zeros((batch, hkv, slots, hd), dtype),
            "v": jnp.zeros((batch, hkv, slots, hd), dtype)}


def row_update(cache_arr, new, slot, *, axis=2):
    """Per-row cache write: row b of ``cache_arr`` takes ``new[b]`` at its
    own slot index.  ``axis`` is the slot axis of the *full* batched array
    (2 for a (B, heads, S, hd) KV cache, 1 for a (B, S, d) latent cache);
    slot (B,) int32.  Written as a one-hot select rather than a vmapped
    dynamic_update_slice: identical values, but it lowers to a fused
    elementwise op instead of a scatter (~3x faster per step on CPU)."""
    slots = cache_arr.shape[axis]
    m = jnp.arange(slots)[None, :] == slot[:, None]            # (B, slots)
    m = m.reshape((slot.shape[0],) + (1,) * (axis - 1) + (slots,)
                  + (1,) * (cache_arr.ndim - axis - 1))
    return jnp.where(m, new, cache_arr)


def decode_slot_validity(pos, slots, *, window: int = 0):
    """Validity mask over cache slots for single-token decode — THE mask
    math shared by the XLA decode path, the MLA decode path, and the
    fused kernel's ref oracle (``kernels/decode_attention/ref.py``), so
    the implementations can't drift.

    ``pos``: scalar or (B,) int32 position(s); ``slots``: cache slot
    count.  ``window=0`` — linear layout: slot j holds position j, valid
    iff ``j <= pos``.  ``window>0`` — SWA ring: slot j holds the latest
    position ``p <= pos`` with ``p % slots == j``, valid iff that p is
    in ``(pos - window, pos]`` and ``>= 0``.  Returns bool, shaped
    (slots,) for scalar pos and (B, slots) for per-row pos.
    """
    idx = jnp.arange(slots)
    posb = pos[..., None] if getattr(pos, "ndim", 0) else pos
    if window:
        # slot j holds position: the latest p <= pos, p % slots == j
        kpos = posb - jax.lax.rem(posb - idx, slots)
        kpos = jnp.where(kpos > posb, kpos - slots, kpos)  # safety
        return (kpos >= 0) & (posb - kpos < window) & (kpos <= posb)
    return idx <= posb


def attention_decode(params, cfg, spec, x, cache, pos, pages=None,
                     use_kernel=False):
    """One-token decode. x (B,1,D); pos int32: a scalar (all rows in
    lockstep — the legacy shape, kept bitwise) or (B,) per-row positions
    (continuous batching: each row writes and reads its cache at its own
    position; ring indexing, masking and RoPE become row-indexed).

    A 3-D (pool) cache selects the paged path: the row's K/V live in the
    pages its block-table row (``pages``) maps, the write is a flat
    one-hot into the pool, and the read gathers the row's logical
    context back into the same (B, Hkv, S, hd) layout the contiguous
    masked-softmax tail consumes (masked columns contribute exact zeros,
    keeping greedy decode token-identical — tests/test_paged_cache.py).

    ``use_kernel=True`` routes per-row decode through the fused
    ``kernels/decode_attention`` op (RoPE + ring write + mask +
    softmax·V in one pass: compiled Pallas on TPU, the fused-XLA ref
    twin — bitwise-identical math — elsewhere).  Scalar-pos lockstep
    decode keeps the XLA path below."""
    b = x.shape[0]
    hq, hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    per_row = pos.ndim == 1 and pos.shape[0] == b
    paged = cache["k"].ndim == 3
    if paged and (pages is None or not per_row):
        raise ValueError("paged attention cache requires per-row positions "
                         "and a PageRef (cache['pages'])")
    if use_kernel and per_row:
        return _attention_decode_fused(params, cfg, spec, x, cache, pos,
                                       pages)
    q, k, v = _project_qkv(params, cfg, x,
                           pos[:, None, None] if per_row
                           else (pos[None] if pos.ndim == 0 else pos))
    if paged:
        widx = paging_mod.write_index(pages, pos)
        pool_k = paging_mod.pool_write(cache["k"], k[:, :, 0], widx)
        pool_v = paging_mod.pool_write(cache["v"], v[:, :, 0], widx)
        gidx = paging_mod.gather_indices(pages)          # (B, max_ctx)
        ck = pool_k[gidx].transpose(0, 2, 1, 3)          # (B, Hkv, S, hd)
        cv = pool_v[gidx].transpose(0, 2, 1, 3)
        valid = decode_slot_validity(pos, gidx.shape[1])
        new_cache = {"k": pool_k, "v": pool_v}
    else:
        slots = cache["k"].shape[2]
        slot = jax.lax.rem(pos, slots) if slots else pos
        if per_row:
            ck = row_update(cache["k"], k.astype(cache["k"].dtype), slot)
            cv = row_update(cache["v"], v.astype(cache["v"].dtype), slot)
        else:
            ck = jax.lax.dynamic_update_slice_in_dim(
                cache["k"], k.astype(cache["k"].dtype), slot, axis=2)
            cv = jax.lax.dynamic_update_slice_in_dim(
                cache["v"], v.astype(cache["v"].dtype), slot, axis=2)
        new_cache = {"k": ck, "v": cv}
        # positions held by each cache slot (ring for swa, linear
        # otherwise); per-row, pos (B,1) broadcasts against idx (slots,)
        # -> (B, slots)
        win = spec.window if (spec.mixer == "swa" and spec.window
                              and slots < 2**30) else 0
        valid = decode_slot_validity(pos, slots, window=win)
    scale = 1.0 / np.sqrt(hd)
    qg = q.reshape(b, hkv, hq // hkv, 1, hd)
    s_ = jnp.einsum("bhgqd,bhkd->bhgqk", qg.astype(jnp.float32),
                    ck.astype(jnp.float32)) * scale
    s_ = layers.softcap(s_, cfg.attn_softcap)
    vmask = (valid[:, None, None, None, :] if per_row
             else valid[None, None, None, None, :])
    s_ = jnp.where(vmask, s_, NEG_INF)
    p = jax.nn.softmax(s_, axis=-1)
    o = jnp.einsum("bhgqk,bhkd->bhgqd", p, cv.astype(jnp.float32))
    o = o.reshape(b, hq, 1, hd).transpose(0, 2, 1, 3).reshape(b, 1, hq * hd)
    o = o.astype(x.dtype) @ params["wo"].astype(x.dtype)
    return o, new_cache


def _attention_decode_fused(params, cfg, spec, x, cache, pos, pages):
    """Per-row decode through ``kernels/decode_attention``: projections
    stay XLA (MXU matmuls fuse fine), the memory-bound tail — RoPE
    rotation, one-hot ring write, slot-validity mask, softmax·V — runs
    as one fused op instead of five materializing passes.

    Paged dispatch: the pool write and block-table gather stay XLA
    (gather indices are data, not schedule), RoPE is applied before the
    pool write as on the XLA path, and the kernel fuses the mask +
    softmax·V tail over the gathered view (``write=False``)."""
    # local import: kernels/decode_attention/ref.py imports this module
    # for the shared mask helper, so the edge must stay lazy here
    from repro.kernels.decode_attention import decode_attention
    b = x.shape[0]
    hq, hd = cfg.n_heads, cfg.resolved_head_dim
    paged = cache["k"].ndim == 3
    theta = cfg.rope_theta if cfg.pos_emb == "rope" else 0.0
    if paged:
        q, k, v = _project_qkv(params, cfg, x, pos[:, None, None])
        widx = paging_mod.write_index(pages, pos)
        pool_k = paging_mod.pool_write(cache["k"], k[:, :, 0], widx)
        pool_v = paging_mod.pool_write(cache["v"], v[:, :, 0], widx)
        gidx = paging_mod.gather_indices(pages)          # (B, max_ctx)
        ck = pool_k[gidx].transpose(0, 2, 1, 3)          # (B, Hkv, S, hd)
        cv = pool_v[gidx].transpose(0, 2, 1, 3)
        o, _, _ = decode_attention(q, k, v, ck, cv, pos,
                                   softcap=cfg.attn_softcap, write=False)
        new_cache = {"k": pool_k, "v": pool_v}
    else:
        q, k, v = _project_qkv(params, cfg, x, pos[:, None, None],
                               rope=False)
        slots = cache["k"].shape[2]
        win = spec.window if (spec.mixer == "swa" and spec.window
                              and slots < 2**30) else 0
        o, ck, cv = decode_attention(q, k, v, cache["k"], cache["v"], pos,
                                     window=win, softcap=cfg.attn_softcap,
                                     rope_theta=theta)
        new_cache = {"k": ck, "v": cv}
    o = o.transpose(0, 2, 1, 3).reshape(b, 1, hq * hd)
    o = o.astype(x.dtype) @ params["wo"].astype(x.dtype)
    return o, new_cache
