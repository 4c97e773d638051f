"""The token-LM teacher cell's shared pieces: the program's model config at
a configuration file's sizes, the token traffic, and the reference's
logits at sampled positions."""
from __future__ import annotations

import jax
import numpy as np

from bench import data


def model_config(cfg: dict):
    """The program's ``ModelConfig`` for a configuration file with the
    model's own config.json keys: the arch ``cfg["arch"]`` at the file's
    widths and depth (leading dense layers, then MoE layers)."""
    from repro.configs import get_arch
    from repro.configs.base import MLAConfig, Segment, YaRNConfig
    base = get_arch(cfg["arch"])
    dense, moe = base.segments[0].pattern, base.segments[-1].pattern
    n_dense = cfg["first_k_dense_replace"]
    ys = cfg["rope_scaling"]
    return base.replace(
        d_model=cfg["hidden_size"], n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], d_ff=cfg["intermediate_size"],
        vocab_size=cfg["vocab_size"],
        segments=(Segment(dense, n_dense),
                  Segment(moe, cfg["num_hidden_layers"] - n_dense)),
        rope_theta=float(cfg["rope_theta"]),
        rope_scaling=YaRNConfig(
            factor=float(ys["factor"]),
            original_max_position=ys["original_max_position_embeddings"],
            beta_fast=float(ys["beta_fast"]),
            beta_slow=float(ys["beta_slow"]), mscale=ys["mscale"],
            mscale_all_dim=ys["mscale_all_dim"]),
        n_experts=cfg["n_routed_experts"],
        n_shared_experts=cfg["n_shared_experts"],
        moe_top_k=cfg["num_experts_per_tok"],
        moe_d_ff=cfg["moe_intermediate_size"],
        moe_renorm_topk=cfg["norm_topk_prob"],
        mla=MLAConfig(q_lora_rank=cfg["q_lora_rank"] or 0,
                      kv_lora_rank=cfg["kv_lora_rank"],
                      qk_rope_head_dim=cfg["qk_rope_head_dim"],
                      qk_nope_head_dim=cfg["qk_nope_head_dim"],
                      v_head_dim=cfg["v_head_dim"]))


def zipf_tokens(spec: dict, seed: int, shape, vocab: int) -> np.ndarray:
    """Token ids i.i.d. Zipf with exponent ``spec["zipf_exponent"]`` over
    ``vocab`` ranks (P(rank r) proportional to r^-s), ranks mapped to ids
    by a permutation of the vocabulary drawn from the seed."""
    r = data.rng(seed, 11)
    w = np.arange(1, vocab + 1, dtype=np.float64) ** -spec["zipf_exponent"]
    cdf = np.cumsum(w / w.sum())
    ranks = np.searchsorted(cdf, r.random(int(np.prod(shape))),
                            side="right")
    ids = r.permutation(vocab)[np.minimum(ranks, vocab - 1)]
    return ids.reshape(shape).astype(np.int32)


def token_pool(tf: dict, seed: int, vocab: int):
    """-> ``pool_batches`` batches {'tokens': (batch_seqs, seq_len) int32}."""
    n, b, s = tf["pool_batches"], tf["batch_seqs"], tf["seq_len"]
    toks = zipf_tokens(tf["tokens"], seed, (n, b, s), vocab)
    return [{"tokens": toks[i]} for i in range(n)]


def reference_logits(cfg: dict, seed: int, seqs, positions, device, *,
                     prec="f32"):
    """The reference's logits (len(positions), V) on the host: each of
    ``seqs`` (n, S) run whole (causal prefixes), then the head at
    ``positions`` (flat indices into the n x S positions)."""
    import jax.numpy as jnp

    from bench import ref_deepseek_v2 as ref
    n, s = seqs.shape
    eps = cfg["rms_norm_eps"]
    with jax.default_device(device), jax.default_matmul_precision("highest"):
        params = ref.init_params(cfg, seed)
        # one layer per call, so that one layer's weights are widened to
        # float32 at a time
        block = jax.jit(lambda p, x, m: ref.block(p, x, m, cfg, prec),
                        static_argnums=2)
        head = jax.jit(lambda p, x: ref.head(
            p, ref.rms_norm(p["final_norm"], x, eps), prec))
        out = np.empty((len(positions), cfg["vocab_size"]), np.float32)
        for i in range(n):
            sel = np.nonzero(positions // s == i)[0]
            if not len(sel):
                continue
            x = params["embed"][jnp.asarray(seqs[i:i + 1])].astype(
                jnp.float32)
            for p, is_moe in ref.layers(params, cfg):
                x = block(p, x, is_moe)
            rows = x[0][jnp.asarray(positions[sel] % s)]
            out[sel] = np.asarray(head({"final_norm": params["final_norm"],
                                        "out": params["out"]}, rows))
        del params
    return out
