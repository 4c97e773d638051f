"""Operations and bytes of the DeepSeek-V2 token teacher, computed from a
configuration dict with the model's own ``config.json`` keys.  The
benchmark's yardstick: the program never supplies these numbers."""
from __future__ import annotations


def mla_weights(cfg: dict) -> int:
    """Matmul weights of one latent-attention block."""
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    qk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    r, ql = cfg["kv_lora_rank"], cfg["q_lora_rank"]
    q = d * ql + ql * h * qk if ql else d * h * qk
    return (q + d * (r + cfg["qk_rope_head_dim"])
            + r * h * (cfg["qk_nope_head_dim"] + cfg["v_head_dim"])
            + h * cfg["v_head_dim"] * d)


def dense_mlp_weights(cfg: dict) -> int:
    return 3 * cfg["hidden_size"] * cfg["intermediate_size"]


def expert_weights(cfg: dict) -> int:
    """One routed expert's gate, up and down."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def moe_active_weights(cfg: dict) -> int:
    """Weights one token multiplies in an MoE block's channel mixer: the
    router, its k routed experts and the shared experts."""
    d = cfg["hidden_size"]
    return (d * cfg["n_routed_experts"]
            + (cfg["num_experts_per_tok"] + cfg["n_shared_experts"])
            * expert_weights(cfg))


def layer_counts(cfg: dict):
    """(dense layers, MoE layers)."""
    n_dense = cfg["first_k_dense_replace"]
    return n_dense, cfg["num_hidden_layers"] - n_dense


def model_weights(cfg: dict) -> int:
    """Every parameter held: embedding, head, and all layers' matrices
    (norm weights are not counted)."""
    n_dense, n_moe = layer_counts(cfg)
    d, e = cfg["hidden_size"], cfg["n_routed_experts"]
    moe = (d * e + (e + cfg["n_shared_experts"]) * expert_weights(cfg)
           + mla_weights(cfg))
    return (2 * cfg["vocab_size"] * d
            + n_dense * (mla_weights(cfg) + dense_mlp_weights(cfg))
            + n_moe * moe)


def attention_flops_per_token(cfg: dict, seq_len: int) -> int:
    """Causal attention at ``seq_len``: scores over q.k (qk_nope +
    qk_rope) and the weighted sum of values, over seq_len / 2 positions
    on average."""
    h = cfg["num_attention_heads"]
    qk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    return 2 * h * (qk + cfg["v_head_dim"]) * (seq_len // 2)


def forward_flops_per_token(cfg: dict, seq_len: int) -> int:
    """Model FLOPs per position labelled: 2 per active matmul weight
    (embedding lookup excluded), causal attention, and the head."""
    n_dense, n_moe = layer_counts(cfg)
    att = attention_flops_per_token(cfg, seq_len)
    dense = 2 * (mla_weights(cfg) + dense_mlp_weights(cfg)) + att
    moe = 2 * (mla_weights(cfg) + moe_active_weights(cfg)) + att
    return (n_dense * dense + n_moe * moe
            + 2 * cfg["hidden_size"] * cfg["vocab_size"])


def moe_gmm_cost(positions: int, cfg: dict, weight_bytes: int = 2,
                 act_bytes: int = 2) -> dict:
    """The routed experts' grouped matmuls over ``positions`` in every MoE
    layer: pairs = k x positions rows through gate, up (D -> F) and down
    (F -> D), 2 FLOPs a multiply-add; bytes: every expert's weights read
    once, the rows read by gate and up, their two outputs written, h read
    and the output written, all in bfloat16."""
    _, n_moe = layer_counts(cfg)
    d, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    pairs = cfg["num_experts_per_tok"] * positions
    ops = 2 * pairs * 3 * d * f
    rows = (2 * pairs * d + 2 * pairs * f + pairs * f + pairs * d)
    bytes_ = (cfg["n_routed_experts"] * expert_weights(cfg) * weight_bytes
              + rows * act_bytes)
    return {"ops": n_moe * ops, "bytes": n_moe * bytes_}
