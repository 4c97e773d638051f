"""Generic decoder LM assembled from a ModelConfig's segments.

Every segment (pattern x repeat) is executed with ``jax.lax.scan`` over
stacked params, so HLO size is independent of depth — 95-layer models
compile as a handful of scanned groups.  Mixers dispatch on LayerSpec.mixer:
attn | swa | rglru | mlstm | slstm; channel mixers on LayerSpec.ffn:
mlp | moe | none.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.models import attention as attn_mod
from repro.models import layers, mla, moe, recurrent


# ------------------------------------------------------------------ blocks

def _init_mixer(key, cfg, spec):
    if spec.mixer in ("attn", "swa"):
        if cfg.mla is not None:
            return mla.init_mla(key, cfg)
        return attn_mod.init_attention(key, cfg, spec)
    if spec.mixer == "rglru":
        return recurrent.init_rglru_block(key, cfg)
    if spec.mixer == "mlstm":
        return recurrent.init_mlstm_block(key, cfg)
    if spec.mixer == "slstm":
        return recurrent.init_slstm_block(key, cfg)
    raise ValueError(f"unknown mixer {spec.mixer}")


def init_block(key, cfg, spec):
    ks = jax.random.split(key, 3)
    p = {"norm1": layers.norm_init(cfg.d_model, cfg.norm),
         "mixer": _init_mixer(ks[0], cfg, spec)}
    if spec.ffn == "mlp":
        p["norm2"] = layers.norm_init(cfg.d_model, cfg.norm)
        p["ffn"] = layers.mlp_init(ks[1], cfg.d_model, cfg.d_ff, gated=True)
    elif spec.ffn == "moe":
        p["norm2"] = layers.norm_init(cfg.d_model, cfg.norm)
        p["ffn"] = moe.init_moe(ks[1], cfg)
    return p


def block_apply(params, cfg, spec, x, positions):
    """Full-sequence block. Returns (x, aux, state) — state for recurrent
    mixers (None-free pytree only when requested via init_block_cache)."""
    aux = {}
    h = layers.norm_apply(params["norm1"], x, cfg.norm)
    if spec.mixer in ("attn", "swa"):
        if cfg.mla is not None:
            y = mla.mla_apply(params["mixer"], cfg, h, positions)
        else:
            y = attn_mod.attention_apply(params["mixer"], cfg, spec, h,
                                         positions)
    elif spec.mixer == "rglru":
        y, _ = recurrent.rglru_block_apply(params["mixer"], cfg, h)
    elif spec.mixer == "mlstm":
        y, _ = recurrent.mlstm_block_apply(params["mixer"], cfg, h)
    elif spec.mixer == "slstm":
        y, _ = recurrent.slstm_block_apply(params["mixer"], cfg, h)
    x = x + y
    if spec.ffn == "mlp":
        x = x + layers.mlp_apply(params["ffn"],
                                 layers.norm_apply(params["norm2"], x,
                                                   cfg.norm), cfg.act)
    elif spec.ffn == "moe":
        y, aux = moe.moe_apply(params["ffn"],
                               cfg, layers.norm_apply(params["norm2"], x,
                                                      cfg.norm))
        x = x + y
    return x, aux


def init_block_cache(cfg, spec, batch, seq_len, dtype, paging=None):
    if spec.mixer in ("attn", "swa"):
        if cfg.mla is not None:
            return mla.init_mla_cache(cfg, batch, seq_len, dtype,
                                      paging=paging)
        return attn_mod.init_attn_cache(cfg, spec, batch, seq_len, dtype,
                                        paging=paging)
    if spec.mixer == "rglru":
        return recurrent.init_rglru_state(cfg, batch, dtype)
    if spec.mixer == "mlstm":
        return recurrent.init_mlstm_state(cfg, batch, dtype)
    if spec.mixer == "slstm":
        return recurrent.init_slstm_state(cfg, batch, dtype)
    raise ValueError(spec.mixer)


def block_decode(params, cfg, spec, x, cache, pos, pages=None,
                 use_kernel=False):
    h = layers.norm_apply(params["norm1"], x, cfg.norm)
    if spec.mixer in ("attn", "swa"):
        if cfg.mla is not None:
            # MLA decodes over the compressed latent cache — no fused
            # kernel variant; it shares decode_slot_validity with the
            # XLA path instead
            y, cache = mla.mla_decode(params["mixer"], cfg, h, cache, pos,
                                      pages=pages)
        else:
            y, cache = attn_mod.attention_decode(params["mixer"], cfg, spec,
                                                 h, cache, pos, pages=pages,
                                                 use_kernel=use_kernel)
    elif spec.mixer == "rglru":
        y, cache = recurrent.rglru_block_decode(params["mixer"], cfg, h,
                                                cache)
    elif spec.mixer == "mlstm":
        y, cache = recurrent.mlstm_block_decode(params["mixer"], cfg, h,
                                                cache)
    elif spec.mixer == "slstm":
        y, cache = recurrent.slstm_block_decode(params["mixer"], cfg, h,
                                                cache)
    x = x + y
    if spec.ffn == "mlp":
        x = x + layers.mlp_apply(params["ffn"],
                                 layers.norm_apply(params["norm2"], x,
                                                   cfg.norm), cfg.act)
    elif spec.ffn == "moe":
        y, _ = moe.moe_apply(params["ffn"],
                             cfg, layers.norm_apply(params["norm2"], x,
                                                    cfg.norm))
        x = x + y
    return x, cache


def _reduce_aux(name, stacked):
    """A segment's aux, stacked over its layers: the expert token counts
    stay per layer ((repeat, E)); the losses sum over layers."""
    return stacked if name.endswith("expert_counts") else jnp.sum(stacked)


# --------------------------------------------------------------- the model

class Transformer:
    def __init__(self, cfg, paging=None, decode_kernel=False):
        self.cfg = cfg
        self.paging = paging        # PagedCacheConfig or None (contiguous)
        # route per-row decode attention through kernels/decode_attention
        # (fused RoPE + ring write + mask + softmax·V); scalar-pos
        # lockstep decode and MLA keep the XLA path regardless
        self.decode_kernel = decode_kernel

    # ---- init ----
    def init(self, key):
        cfg = self.cfg
        keys = jax.random.split(key, len(cfg.segments) + 3)
        params = {
            "embed": layers.embed_init(keys[-1], cfg.vocab_size, cfg.d_model),
            "final_norm": layers.norm_init(cfg.d_model, cfg.norm),
        }
        if not cfg.tie_embeddings:
            params["out"] = layers.dense_init(keys[-2], cfg.d_model,
                                              cfg.vocab_size)
        if cfg.pos_emb == "learned":
            params["pos"] = layers.embed_init(keys[-3], 33_216, cfg.d_model)
        for si, seg in enumerate(cfg.segments):
            seg_key = keys[si]

            def one_group(k):
                pks = jax.random.split(k, len(seg.pattern))
                return {f"p{i}": init_block(pks[i], cfg, sp)
                        for i, sp in enumerate(seg.pattern)}

            gkeys = jax.random.split(seg_key, seg.repeat)
            params[f"seg{si}"] = jax.vmap(one_group)(gkeys)
        if cfg.mtp_depth:
            params["mtp"] = {
                "norm": layers.norm_init(cfg.d_model, cfg.norm),
                "proj": layers.dense_init(keys[0], 2 * cfg.d_model,
                                          cfg.d_model),
                "block": jax.vmap(lambda k: init_block(
                    k, cfg, cfg.segments[-1].pattern[-1]))(
                        jax.random.split(keys[0], 1)),
            }
        return params

    # ---- embedding / unembedding ----
    def embed(self, params, tokens):
        h = params["embed"][tokens]
        if self.cfg.emb_scale:
            h = h * jnp.asarray(self.cfg.d_model ** 0.5, h.dtype)
        return h

    def unembed_matrix(self, params):
        if self.cfg.tie_embeddings:
            return params["embed"].T
        return params["out"]

    def unembed(self, params, h):
        """Logits in float32 (float32 accumulation of h's dtype)."""
        logits = jnp.matmul(h, self.unembed_matrix(params).astype(h.dtype),
                            preferred_element_type=jnp.float32)
        return layers.softcap(logits, self.cfg.logit_softcap)

    # ---- full-sequence forward ----
    def apply(self, params, tokens, *, embeds=None, positions=None):
        """tokens (B,S) int32 (or embeds (B,S,D)). Returns (hidden, aux)."""
        cfg = self.cfg
        x = self.embed(params, tokens) if embeds is None else embeds
        b, s, _ = x.shape
        if positions is None:
            positions = jnp.arange(s)
        if cfg.pos_emb == "learned":
            x = x + params["pos"].astype(x.dtype)[
                jnp.clip(positions, 0, params["pos"].shape[0] - 1)]
        aux_total = {}
        for si, seg in enumerate(cfg.segments):
            seg_params = params[f"seg{si}"]

            def body(carry, gp, seg=seg):
                x = carry
                auxs = {}
                for i, sp in enumerate(seg.pattern):
                    x, aux = block_apply(gp[f"p{i}"], cfg, sp, x, positions)
                    for k_, v_ in aux.items():
                        auxs[f"p{i}/{k_}"] = v_
                return x, auxs

            if cfg.scan_unroll:                     # cost-probe path
                per_layer = []
                for gi in range(seg.repeat):
                    gp = jax.tree_util.tree_map(lambda a: a[gi], seg_params)
                    x, auxs = body(x, gp)
                    per_layer.append(auxs)
                auxs = {k_: jnp.stack([a[k_] for a in per_layer])
                        for k_ in (per_layer[0] if per_layer else {})}
            else:
                if cfg.remat:
                    body = jax.checkpoint(body)
                x, auxs = jax.lax.scan(body, x, seg_params)
            for k_, v_ in auxs.items():
                aux_total[f"seg{si}/{k_}"] = _reduce_aux(k_, v_)
        x = layers.norm_apply(params["final_norm"], x, cfg.norm)
        return x, aux_total

    # ---- decode ----
    def init_cache(self, batch, seq_len, dtype=jnp.bfloat16, *,
                   per_row=False):
        """Decode cache.  ``per_row=True`` carries one position *per batch
        row* ((B,) int32) instead of a shared scalar, making ragged
        continuous batching legal: rows may sit at different sequence
        positions within one decode step.  The scalar default keeps every
        existing lockstep jit bitwise.

        With a ``paging`` config (model built via ``build_model(cfg,
        paging=...)``) the full-attention/MLA caches become shared pools
        and the cache root carries the block table (``cache["pages"]``);
        swa rings and recurrent state keep their per-row layout.  Paged
        caches are per-row only."""
        cfg = self.cfg
        if self.paging is not None and not per_row:
            raise ValueError("paged caches are per-row only "
                             "(init_cache(per_row=True))")
        cache = {"pos": jnp.zeros((batch,) if per_row else (), jnp.int32)}
        if self.paging is not None:
            cache["pages"] = {
                "tables": jnp.zeros((batch, self.paging.max_blocks),
                                    jnp.int32),
                "caps": jnp.zeros((batch,), jnp.int32),
            }
        for si, seg in enumerate(cfg.segments):
            def one(sp):
                return init_block_cache(cfg, sp, batch, seq_len, dtype,
                                        paging=self.paging)
            group = {f"p{i}": one(sp) for i, sp in enumerate(seg.pattern)}
            cache[f"seg{si}"] = jax.tree_util.tree_map(
                lambda a: jnp.broadcast_to(a, (seg.repeat,) + a.shape).copy()
                if seg.repeat else a, group)
        return cache

    def decode_step(self, params, cache, tokens):
        """tokens (B,1). Returns (logits (B,1,V), new cache).  With a
        per-row cache (see ``init_cache``) every positional lookup is
        row-indexed; the scalar-position path is unchanged."""
        cfg = self.cfg
        pos = cache["pos"]
        x = self.embed(params, tokens)
        if cfg.pos_emb == "learned":
            pe = params["pos"].astype(x.dtype)[
                jnp.clip(pos, 0, params["pos"].shape[0] - 1)]
            x = x + (pe[:, None] if pos.ndim else pe[None, None])
        new_cache = {"pos": pos + 1}
        pages = None
        if "pages" in cache:
            from repro.models.paging import PageRef
            pages = PageRef(cache["pages"]["tables"], cache["pages"]["caps"],
                            self.paging.page_size)
            new_cache["pages"] = cache["pages"]       # host-owned, carried
        for si, seg in enumerate(cfg.segments):
            seg_params = params[f"seg{si}"]

            def body(carry, xs, seg=seg):
                x = carry
                gp, gc = xs
                new_gc = {}
                for i, sp in enumerate(seg.pattern):
                    x, c = block_decode(gp[f"p{i}"], cfg, sp, x,
                                        gc[f"p{i}"], pos, pages=pages,
                                        use_kernel=self.decode_kernel)
                    new_gc[f"p{i}"] = c
                return x, new_gc

            if cfg.scan_unroll:                     # cost-probe path
                gcs = []
                for gi in range(seg.repeat):
                    take = lambda a: a[gi]
                    x, gc = body(x, (jax.tree_util.tree_map(take, seg_params),
                                     jax.tree_util.tree_map(
                                         take, cache[f"seg{si}"])))
                    gcs.append(gc)
                new_cache[f"seg{si}"] = jax.tree_util.tree_map(
                    lambda *a: jnp.stack(a), *gcs)
                continue
            x, new_seg_cache = jax.lax.scan(body, x,
                                            (seg_params, cache[f"seg{si}"]))
            new_cache[f"seg{si}"] = new_seg_cache
        x = layers.norm_apply(params["final_norm"], x, cfg.norm)
        return self.unembed(params, x), new_cache

    def reset_cache_rows(self, cache, rows, starts=None):
        """Reset the cache rows selected by the (B,) bool mask ``rows`` —
        the continuous batcher's slot admission hook.  Per-row caches
        only (pos must be (B,)).

        Contiguous mode zeroes everything (KV entries past a row's
        position are masked out by decode anyway; zeroing also covers
        recurrent/conv state, whose whole content is live) — bitwise
        unchanged from before paging existed.  Paged mode leaves the
        pools alone (a row's stale pages are unreachable once its table
        row changes; garbage past ``pos`` is masked) and zeroes only the
        per-row leaves (swa rings, recurrent state).

        ``starts`` ((B,) int32, default 0) is the admitted rows' initial
        position — nonzero when a prompt prefix was served from the
        prefix cache and the row resumes mid-prompt."""
        pos0 = jnp.zeros_like(cache["pos"]) if starts is None else starts
        new = {"pos": jnp.where(rows, pos0, cache["pos"])}

        def zero(a):
            m = rows.reshape((1, -1) + (1,) * (a.ndim - 2))   # (rep, B, ...)
            return jnp.where(m, jnp.zeros((), a.dtype), a)

        if self.paging is None:
            for si in range(len(self.cfg.segments)):
                new[f"seg{si}"] = jax.tree_util.tree_map(
                    zero, cache[f"seg{si}"])
            return new
        from repro.models.paging import is_paged_spec
        new["pages"] = cache["pages"]
        for si, seg in enumerate(self.cfg.segments):
            group = {}
            for i, sp in enumerate(seg.pattern):
                sub = cache[f"seg{si}"][f"p{i}"]
                if sp.mixer in ("attn", "swa") and is_paged_spec(sp):
                    group[f"p{i}"] = sub               # pooled: untouched
                else:
                    group[f"p{i}"] = jax.tree_util.tree_map(zero, sub)
            new[f"seg{si}"] = group
        return new

    # ---- MTP auxiliary hidden (deepseek-v3) ----
    def mtp_hidden(self, params, hidden, tokens_shifted, positions):
        """Predict t+2: combine hidden with embedding of the next token."""
        cfg = self.cfg
        if not cfg.mtp_depth or "mtp" not in params:
            return None
        h = layers.norm_apply(params["mtp"]["norm"], hidden, cfg.norm)
        e = self.embed(params, tokens_shifted)
        x = jnp.concatenate([h, e], axis=-1) @ params["mtp"]["proj"].astype(
            hidden.dtype)
        spec = cfg.segments[-1].pattern[-1]

        def body(carry, gp):
            y, _ = block_apply(gp, cfg, spec, carry, positions)
            return y, {}

        x, _ = jax.lax.scan(body, x, params["mtp"]["block"])
        return x
