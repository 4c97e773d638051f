"""Multi-head Latent Attention (DeepSeek-V2 / V3).

Train/prefill: decompress the latent c_kv into per-head K/V and run standard
flash attention.  Decode: *absorbed* form — the cache stores only
(c_kv, k_rope) per token, W_uk is folded into the query and W_uv applied
after attention, so per-step work is O(S * (kv_rank + rope_dim)) per head
instead of rematerializing full K/V (which at 32k x 128 heads would be
hundreds of GB).  See DESIGN.md §Perf for the naive-vs-absorbed accounting.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import attention as attn_mod
from repro.models import layers

NEG_INF = -1e30


def init_mla(key, cfg):
    m = cfg.mla
    d, h = cfg.d_model, cfg.n_heads
    qk_dim = m.qk_nope_head_dim + m.qk_rope_head_dim
    ks = jax.random.split(key, 8)
    if m.q_lora_rank:
        q = {"w_dq": layers.dense_init(ks[0], d, m.q_lora_rank),
             "q_norm": layers.norm_init(m.q_lora_rank, "rmsnorm"),
             "w_uq": layers.dense_init(ks[1], m.q_lora_rank, h * qk_dim)}
    else:
        q = {"w_q": layers.dense_init(ks[0], d, h * qk_dim)}
    return {
        **q,
        "w_dkv": layers.dense_init(ks[2], d,
                                   m.kv_lora_rank + m.qk_rope_head_dim),
        "kv_norm": layers.norm_init(m.kv_lora_rank, "rmsnorm"),
        "w_uk": layers.dense_init(ks[3], m.kv_lora_rank,
                                  h * m.qk_nope_head_dim),
        "w_uv": layers.dense_init(ks[4], m.kv_lora_rank, h * m.v_head_dim),
        "wo": layers.dense_init(ks[5], h * m.v_head_dim, d),
    }


def softmax_scale(cfg) -> float:
    """1/sqrt(qk head dim), times YaRN's mscale squared where it is set."""
    m = cfg.mla
    scale = 1.0 / np.sqrt(m.qk_nope_head_dim + m.qk_rope_head_dim)
    if cfg.rope_scaling is not None:
        scale *= cfg.rope_scaling.softmax_mscale() ** 2
    return scale


def _rope(cfg, positions):
    return layers.rope_tables(positions, cfg.mla.qk_rope_head_dim,
                              cfg.rope_theta, cfg.rope_scaling)


def _queries(params, cfg, x, positions):
    """-> q_nope (B,H,S,nope), q_rope (B,H,S,rope) with rope applied.
    With a query LoRA: x @ w_dq -> q_norm -> @ w_uq; without: x @ w_q."""
    m = cfg.mla
    b, s, _ = x.shape
    h = cfg.n_heads
    if m.q_lora_rank:
        cq = x @ params["w_dq"].astype(x.dtype)
        cq = layers.norm_apply(params["q_norm"], cq, "rmsnorm")
        q = cq @ params["w_uq"].astype(x.dtype)
    else:
        q = x @ params["w_q"].astype(x.dtype)
    q = q.reshape(b, s, h, m.qk_nope_head_dim
                  + m.qk_rope_head_dim).transpose(0, 2, 1, 3)
    q_nope = q[..., :m.qk_nope_head_dim]
    q_rope = q[..., m.qk_nope_head_dim:]
    cos, sin = _rope(cfg, positions)
    q_rope = layers.apply_rope(q_rope, cos, sin)
    return q_nope, q_rope


def _latents(params, cfg, x, positions):
    """-> c_kv (B,S,rank) normalized, k_rope (B,S,rope) with rope applied."""
    m = cfg.mla
    dkv = x @ params["w_dkv"].astype(x.dtype)
    c_kv = layers.norm_apply(params["kv_norm"], dkv[..., :m.kv_lora_rank],
                             "rmsnorm")
    k_rope = dkv[..., m.kv_lora_rank:]
    cos, sin = _rope(cfg, positions)
    k_rope = layers.apply_rope(k_rope, cos, sin)
    return c_kv, k_rope


def mla_apply(params, cfg, x, positions):
    """Full-sequence MLA (decompressed path). x (B,S,D)."""
    m = cfg.mla
    b, s, _ = x.shape
    h = cfg.n_heads
    q_nope, q_rope = _queries(params, cfg, x, positions)
    c_kv, k_rope = _latents(params, cfg, x, positions)
    k_nope = (c_kv @ params["w_uk"].astype(x.dtype)).reshape(
        b, s, h, m.qk_nope_head_dim).transpose(0, 2, 1, 3)
    v = (c_kv @ params["w_uv"].astype(x.dtype)).reshape(
        b, s, h, m.v_head_dim).transpose(0, 2, 1, 3)
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    k = jnp.concatenate([k_nope,
                         jnp.broadcast_to(k_rope[:, None],
                                          (b, h, s, m.qk_rope_head_dim))],
                        axis=-1)
    # MHA == GQA with G=1 groups per head
    cq = s if cfg.attn_whole_seq else 512
    ckv = s if cfg.attn_whole_seq else 1024
    o = attn_mod.flash_full_attention(q[:, :, None], k, v, positions,
                                      positions, chunk_q=cq, chunk_kv=ckv,
                                      scale=softmax_scale(cfg))
    o = o[:, :, 0].transpose(0, 2, 1, 3).reshape(b, s, h * m.v_head_dim)
    return o @ params["wo"].astype(x.dtype)


def init_mla_cache(cfg, batch, seq_len, dtype, paging=None):
    m = cfg.mla
    if paging is not None:
        # pooled latent cache (no batch axis): rows reach their pages
        # through the shared block table — see models/paging
        slots = paging.pool_slots
        return {"c_kv": jnp.zeros((slots, m.kv_lora_rank), dtype),
                "k_rope": jnp.zeros((slots, m.qk_rope_head_dim), dtype)}
    return {"c_kv": jnp.zeros((batch, seq_len, m.kv_lora_rank), dtype),
            "k_rope": jnp.zeros((batch, seq_len, m.qk_rope_head_dim), dtype)}


def mla_decode(params, cfg, x, cache, pos, pages=None):
    """Absorbed single-token decode. x (B,1,D); pos scalar (lockstep rows,
    kept bitwise) or (B,) per-row positions (continuous batching).  A 2-D
    (pool) latent cache selects the paged path — flat one-hot write, flat
    gather back to (B, S, rank); see models/paging."""
    m = cfg.mla
    b = x.shape[0]
    h = cfg.n_heads
    scale = softmax_scale(cfg)
    per_row = pos.ndim == 1 and pos.shape[0] == b
    paged = cache["c_kv"].ndim == 2
    if paged and (pages is None or not per_row):
        raise ValueError("paged MLA cache requires per-row positions and "
                         "a PageRef (cache['pages'])")
    q_nope, q_rope = _queries(params, cfg, x,
                              pos[:, None, None] if per_row else pos[None])
    c_new, kr_new = _latents(params, cfg, x,
                             pos[:, None] if per_row else pos[None])
    if paged:
        from repro.models import paging as paging_mod
        widx = paging_mod.write_index(pages, pos)
        pool_c = paging_mod.pool_write(cache["c_kv"], c_new[:, 0], widx)
        pool_kr = paging_mod.pool_write(cache["k_rope"], kr_new[:, 0], widx)
        gidx = paging_mod.gather_indices(pages)          # (B, max_ctx)
        c = pool_c[gidx]                                 # (B, S, rank)
        kr = pool_kr[gidx]                               # (B, S, rope)
        new_cache = {"c_kv": pool_c, "k_rope": pool_kr}
    elif per_row:
        c = attn_mod.row_update(
            cache["c_kv"], c_new.astype(cache["c_kv"].dtype), pos, axis=1)
        kr = attn_mod.row_update(
            cache["k_rope"], kr_new.astype(cache["k_rope"].dtype), pos,
            axis=1)
        new_cache = {"c_kv": c, "k_rope": kr}
    else:
        c = jax.lax.dynamic_update_slice_in_dim(
            cache["c_kv"], c_new.astype(cache["c_kv"].dtype), pos, axis=1)
        kr = jax.lax.dynamic_update_slice_in_dim(
            cache["k_rope"], kr_new.astype(cache["k_rope"].dtype), pos,
            axis=1)
        new_cache = {"c_kv": c, "k_rope": kr}
    # absorb W_uk into the query: q_c (B,H,rank)
    w_uk = params["w_uk"].astype(x.dtype).reshape(m.kv_lora_rank, h,
                                                  m.qk_nope_head_dim)
    q_c = jnp.einsum("bhd,rhd->bhr", q_nope[:, :, 0], w_uk)
    s_nope = jnp.einsum("bhr,bsr->bhs", q_c.astype(jnp.float32),
                        c.astype(jnp.float32))
    s_rope = jnp.einsum("bhd,bsd->bhs", q_rope[:, :, 0].astype(jnp.float32),
                        kr.astype(jnp.float32))
    s_ = (s_nope + s_rope) * scale
    valid = attn_mod.decode_slot_validity(pos, c.shape[1])
    if per_row:
        s_ = jnp.where(valid[:, None], s_, NEG_INF)       # (B,1,S)
    else:
        s_ = jnp.where(valid[None, None], s_, NEG_INF)    # (1,1,S)
    p = jax.nn.softmax(s_, axis=-1)
    # attention over latents, then decompress once per head
    o_c = jnp.einsum("bhs,bsr->bhr", p, c.astype(jnp.float32))  # (B,H,rank)
    w_uv = params["w_uv"].astype(x.dtype).reshape(m.kv_lora_rank, h,
                                                  m.v_head_dim)
    o = jnp.einsum("bhr,rhv->bhv", o_c.astype(x.dtype), w_uv)
    o = o.reshape(b, 1, h * m.v_head_dim)
    return o @ params["wo"].astype(x.dtype), new_cache
