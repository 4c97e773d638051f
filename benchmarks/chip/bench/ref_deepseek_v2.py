"""Plain float32 reference of DeepSeek-V2 / V2-Lite (arXiv:2405.04434; the
HF ``modeling_deepseek.py`` of deepseek-ai/DeepSeek-V2-Lite): embedding;
per layer RMSNorm -> MLA -> residual -> RMSNorm -> dense MLP or MoE ->
residual; final RMSNorm; head; logits.

Straight ``jax.numpy``; it imports nothing of the program under test and
takes nothing it made.  Sizes come from a dict with the model's own
``config.json`` keys.  The weights come from :func:`init_params`, which
the benchmark also hands to the program (same tree layout, in bfloat16 as
the model is released); here every weight is widened to float32.

Every weight matmul goes through :func:`mm`, whose ``prec`` selects the
operand precision: ``"f32"`` (float32 at ``Precision.HIGHEST``, the
reference), ``"bf16"`` or ``"fp8"`` (operands rounded to bfloat16 /
float8_e4m3fn with a per-tensor scale, then the same float32 product) --
the controls.  Attention scores and the weighted sum of values stay float32.

- MLA, decompressed: q = x W_q (no query LoRA where ``q_lora_rank`` is
  null; else x W_dq -> RMSNorm -> W_uq), [c_kv, k_pe] = x W_dkv, c_kv
  RMS-normed, per-head k_nope = c_kv W_uk and v = c_kv W_uv; YaRN rope on
  q_pe and k_pe; causal softmax at scale mscale(factor, mscale_all_dim)^2
  / sqrt(qk_nope + qk_rope); o W_o.
- MoE: router logits x W_r in float32, softmax, greedy top-k (renormed
  only if ``norm_topk_prob``), times ``routed_scaling_factor``; each token
  gets the sum of its k experts' SiLU-gated MLPs weighted by those
  probabilities, plus the shared experts as one gated MLP of width
  ``n_shared_experts * moe_intermediate_size``.

Departures from the HF code, each without effect on what is compared:
- rope rotates dims as halves (x[:d/2], x[d/2:]) where HF interleaves the
  pairs then permutes; with random weights that is a fixed permutation of
  the rope columns of W_q and W_dkv;
- RMSNorm weights are stored as ``scale`` with the weight ``1 + scale``
  (the program's parametrisation);
- the MoE is evaluated as the sum over all experts of each token's router
  weight (zero outside its top-k) times that expert's MLP: every expert on
  every token, with no sort, no grouped buffer and no kernel.  HF gathers
  each token's k experts; gathering their weights per token would take
  ~200 MB a token at the published widths;
- no MTP (the model has none), no KV cache, inference only (no aux loss).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
FP8_MAX = 448.0                      # largest finite float8_e4m3fn


def _round(a, prec):
    if prec == "f32":
        return a
    if prec == "bf16":
        return a.astype(jnp.bfloat16).astype(jnp.float32)
    if prec == "fp8":
        # per-tensor scale that maps the largest magnitude to FP8_MAX / 2
        s = jnp.maximum(jnp.max(jnp.abs(a)), 1e-30) / (FP8_MAX / 2)
        return (a / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s
    raise ValueError(f"unknown precision {prec!r}")


def mm(a, b, prec="f32"):
    """a (..., K) @ b (K, N) in float32, operands rounded to ``prec``."""
    a = a.astype(jnp.float32)
    b = b.astype(jnp.float32)
    return jnp.matmul(_round(a, prec), _round(b, prec), precision=HIGHEST)


# ------------------------------------------------------------------ weights

def layer_shapes(cfg: dict, moe: bool):
    """{leaf path: shape} of one layer."""
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    qk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    r, ql = cfg["kv_lora_rank"], cfg["q_lora_rank"]
    s = {("norm1", "scale"): (d,), ("norm2", "scale"): (d,),
         ("mixer", "w_dkv"): (d, r + cfg["qk_rope_head_dim"]),
         ("mixer", "kv_norm", "scale"): (r,),
         ("mixer", "w_uk"): (r, h * cfg["qk_nope_head_dim"]),
         ("mixer", "w_uv"): (r, h * cfg["v_head_dim"]),
         ("mixer", "wo"): (h * cfg["v_head_dim"], d)}
    if ql:
        s[("mixer", "w_dq")] = (d, ql)
        s[("mixer", "q_norm", "scale")] = (ql,)
        s[("mixer", "w_uq")] = (ql, h * qk)
    else:
        s[("mixer", "w_q")] = (d, h * qk)
    if not moe:
        f = cfg["intermediate_size"]
        s.update({("ffn", "gate"): (d, f), ("ffn", "up"): (d, f),
                  ("ffn", "down"): (f, d)})
        return s
    e, f = cfg["n_routed_experts"], cfg["moe_intermediate_size"]
    s.update({("ffn", "router"): (d, e), ("ffn", "w_gate"): (e, d, f),
              ("ffn", "w_up"): (e, d, f), ("ffn", "w_down"): (e, f, d)})
    if cfg["n_shared_experts"]:
        fs = cfg["n_shared_experts"] * f
        s.update({("ffn", "shared", "gate"): (d, fs),
                  ("ffn", "shared", "up"): (d, fs),
                  ("ffn", "shared", "down"): (fs, d)})
    return s


def param_shapes(cfg: dict):
    """{leaf path: shape} of the program's tree: ``seg0`` the leading dense
    layers, ``seg1`` the MoE layers, each stacked over its layers."""
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    n_dense = cfg["first_k_dense_replace"]
    n_moe = cfg["num_hidden_layers"] - n_dense
    out = {("embed",): (v, d), ("out",): (d, v),
           ("final_norm", "scale"): (d,)}
    for seg, n, moe in (("seg0", n_dense, False), ("seg1", n_moe, True)):
        if n:
            for path, shape in layer_shapes(cfg, moe).items():
                out[(seg, "p0") + path] = (n,) + shape
    return out


def _nest(flat):
    tree = {}
    for path, v in flat.items():
        node = tree
        for p in path[:-1]:
            node = node.setdefault(p, {})
        node[path[-1]] = v
    return tree


@functools.partial(jax.jit, static_argnums=(1,))
def _init(key, spec):
    flat = {}
    keys = jax.random.split(key, len(spec))
    for k, (path, shape) in zip(keys, spec):
        if path[-1] == "scale":                 # RMSNorm weight 1 + 0
            flat[path] = jnp.zeros(shape, jnp.bfloat16)
        elif path == ("embed",):
            flat[path] = jax.random.normal(k, shape, jnp.bfloat16)
        else:                                   # fan-in is shape[-2]
            flat[path] = (jax.random.normal(k, shape, jnp.float32)
                          / np.sqrt(shape[-2])).astype(jnp.bfloat16)
    return _nest(flat)


def init_params(cfg: dict, seed: int):
    """bfloat16 weights from the seed, made on the device in one jitted
    call: an N(0, 1) embedding, normal matrices with variance 1/fan_in
    (the router too, so that its logits have unit spread), RMSNorm weights
    1."""
    spec = tuple(sorted(param_shapes(cfg).items()))
    word = np.random.SeedSequence(int(seed) & (2 ** 64 - 1)).generate_state(1)
    return _init(jax.random.key(int(word[0]) & 0x7FFFFFFF), spec)


# ------------------------------------------------------------------ forward

def rms_norm(p, x, eps):
    x = x.astype(jnp.float32)
    y = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return y * (1.0 + p["scale"].astype(jnp.float32))


def yarn_mscale(scale: float, m: float) -> float:
    return 1.0 if scale <= 1 else 0.1 * m * math.log(scale) + 1.0


def rope_inv_freq(cfg: dict) -> np.ndarray:
    """DeepseekV2YarnRotaryEmbedding's inverse frequencies (plain rope
    when ``rope_scaling`` is null)."""
    dim, base = cfg["qk_rope_head_dim"], cfg["rope_theta"]
    extra = 1.0 / base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    ys = cfg.get("rope_scaling")
    if not ys:
        return extra
    factor = ys["factor"]
    orig = ys["original_max_position_embeddings"]

    def corr(rot):
        return (dim * math.log(orig / (rot * 2 * math.pi))
                / (2 * math.log(base)))
    lo = max(math.floor(corr(ys["beta_fast"])), 0)
    hi = min(math.ceil(corr(ys["beta_slow"])), dim - 1)
    if lo == hi:
        hi += 0.001
    ramp = np.clip((np.arange(dim // 2) - lo) / (hi - lo), 0.0, 1.0)
    keep = 1.0 - ramp                 # 1: original frequency; 0: interpolated
    return extra / factor * (1.0 - keep) + extra * keep


def attn_scale(cfg: dict) -> float:
    qk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    s = qk ** -0.5
    ys = cfg.get("rope_scaling")
    if ys and ys.get("mscale_all_dim"):
        s *= yarn_mscale(ys["factor"], ys["mscale_all_dim"]) ** 2
    return s


def rope(x, pos, cfg: dict):
    """x (..., S, dim) rotated by position, dims paired as halves."""
    inv = jnp.asarray(rope_inv_freq(cfg), jnp.float32)
    ang = pos.astype(jnp.float32)[:, None] * inv              # (S, dim/2)
    ys = cfg.get("rope_scaling")
    m = (yarn_mscale(ys["factor"], ys["mscale"])
         / yarn_mscale(ys["factor"], ys["mscale_all_dim"])) if ys else 1.0
    cos, sin = jnp.cos(ang) * m, jnp.sin(ang) * m
    d2 = x.shape[-1] // 2
    x1, x2 = x[..., :d2], x[..., d2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def mla(p, x, cfg: dict, prec):
    """x (B, S, D) normed -> (B, S, D); causal over the whole sequence."""
    b, s, _ = x.shape
    h = cfg["num_attention_heads"]
    nope, rd, vd = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                    cfg["v_head_dim"])
    r, eps = cfg["kv_lora_rank"], cfg["rms_norm_eps"]
    if cfg["q_lora_rank"]:
        q = mm(rms_norm(p["q_norm"], mm(x, p["w_dq"], prec), eps),
               p["w_uq"], prec)
    else:
        q = mm(x, p["w_q"], prec)
    q = q.reshape(b, s, h, nope + rd).transpose(0, 2, 1, 3)
    dkv = mm(x, p["w_dkv"], prec)
    c = rms_norm(p["kv_norm"], dkv[..., :r], eps)
    pos = jnp.arange(s)
    q_pe = rope(q[..., nope:], pos, cfg)                      # (B,H,S,rd)
    k_pe = rope(dkv[..., r:], pos, cfg)                       # (B,S,rd)
    k_nope = mm(c, p["w_uk"], prec).reshape(b, s, h, nope)
    v = mm(c, p["w_uv"], prec).reshape(b, s, h, vd)
    sc = (jnp.einsum("bhqd,bkhd->bhqk", q[..., :nope], k_nope,
                     precision=HIGHEST)
          + jnp.einsum("bhqd,bkd->bhqk", q_pe, k_pe, precision=HIGHEST))
    sc = sc * attn_scale(cfg)
    causal = pos[:, None] >= pos[None, :]
    sc = jnp.where(causal, sc, -jnp.inf)
    o = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(sc, axis=-1), v,
                   precision=HIGHEST)
    return mm(o.reshape(b, s, h * vd), p["wo"], prec)


def gated_mlp(gate, up, down, x, prec):
    return mm(jax.nn.silu(mm(x, gate, prec)) * mm(x, up, prec), down, prec)


def moe(p, x, cfg: dict, prec):
    """x (B, S, D) normed -> (B, S, D)."""
    shape = x.shape
    x = x.reshape(-1, shape[-1])
    probs = jax.nn.softmax(mm(x, p["router"], prec), axis=-1)
    top_p, top_i = jax.lax.top_k(probs, cfg["num_experts_per_tok"])
    if cfg["norm_topk_prob"]:
        top_p = top_p / top_p.sum(-1, keepdims=True)
    top_p = top_p * cfg["routed_scaling_factor"]
    e = cfg["n_routed_experts"]
    # (T, E): each token's weight on each expert, zero outside its top-k
    weight = jnp.sum(jax.nn.one_hot(top_i, e) * top_p[..., None], axis=1)

    def one_expert(y, xs):
        wg, wu, wd, w = xs
        return y + w[:, None] * gated_mlp(wg, wu, wd, x, prec), None

    y, _ = jax.lax.scan(one_expert, jnp.zeros(x.shape, jnp.float32),
                        (p["w_gate"], p["w_up"], p["w_down"], weight.T))
    if cfg["n_shared_experts"]:
        sh = p["shared"]
        y = y + gated_mlp(sh["gate"], sh["up"], sh["down"], x, prec)
    return y.reshape(shape)


def layers(params, cfg: dict):
    """(layer params, is_moe) in depth order, one layer sliced at a time."""
    for seg, moe_ in (("seg0", False), ("seg1", True)):
        if seg not in params:
            continue
        p = params[seg]["p0"]
        for i in range(jax.tree_util.tree_leaves(p)[0].shape[0]):
            yield jax.tree_util.tree_map(lambda a, i=i: a[i], p), moe_


def block(p, x, is_moe: bool, cfg: dict, prec="f32"):
    """One layer on the residual stream x (B, S, D) float32."""
    eps = cfg["rms_norm_eps"]
    x = x + mla(p["mixer"], rms_norm(p["norm1"], x, eps), cfg, prec)
    h = rms_norm(p["norm2"], x, eps)
    if is_moe:
        return x + moe(p["ffn"], h, cfg, prec)
    f = p["ffn"]
    return x + gated_mlp(f["gate"], f["up"], f["down"], h, prec)


def hidden(params, tokens, cfg: dict, prec="f32"):
    """tokens (B, S) int -> final-normed hidden (B, S, D) float32."""
    x = params["embed"][tokens].astype(jnp.float32)
    for p, is_moe in layers(params, cfg):
        x = block(p, x, is_moe, cfg, prec)
    return rms_norm(params["final_norm"], x, cfg["rms_norm_eps"])


def head(params, h, prec="f32"):
    """hidden (..., D) -> logits (..., V) float32."""
    return mm(h, params["out"], prec)


def logits(params, tokens, cfg: dict, prec="f32"):
    return head(params, hidden(params, tokens, cfg, prec), prec)
