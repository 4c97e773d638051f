"""Mixture-of-Experts channel mixer with dropless sorted dispatch.

Every token reaches each of its top-k experts, whatever the routing: the
(token, expert) pairs are stable-sorted by expert, their rows gathered into
expert order, and each expert's contiguous group goes through a grouped
matmul (``kernels/moe_gmm``: the Pallas kernel on TPU, ``lax.ragged_dot``
elsewhere) for gate, up and down, with SiLU gating between them.  The
outputs are weighted by their router probabilities, unsorted and summed
per token.  Shapes are static (T*k rows), so nothing depends on a capacity
and no token is dropped.  Shared experts run as one gated MLP of
``n_shared_experts * moe_d_ff`` beside them.

The router's softmax runs in float32 over a float32-accumulated product.
The expert weights are (E, D, F) / (E, F, D); the expert dim shards over
the ``model`` mesh axis (``distributed/sharding``).  Under a mesh in
context whose ``model`` axis divides the experts, the routed experts run
expert-parallel in a ``shard_map``: each shard its own experts over its
token rows (``moe_gmm``'s ``group_offset``), summed over ``model``.

Aux outputs: load-balance loss (Switch-style f*P), router z-loss, and the
per-expert token counts (``expert_counts``, the group sizes).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.models import layers


def init_moe(key, cfg):
    d, e, f = cfg.d_model, cfg.n_experts, cfg.moe_d_ff
    ks = jax.random.split(key, 5)
    p = {
        "router": layers.dense_init(ks[0], d, e, scale=0.1),
        "w_gate": (jax.random.normal(ks[1], (e, d, f), jnp.float32)
                   / np.sqrt(d)),
        "w_up": (jax.random.normal(ks[2], (e, d, f), jnp.float32)
                 / np.sqrt(d)),
        "w_down": (jax.random.normal(ks[3], (e, f, d), jnp.float32)
                   / np.sqrt(f)),
    }
    if cfg.n_shared_experts:
        p["shared"] = layers.mlp_init(ks[4], d,
                                      cfg.n_shared_experts * f, gated=True)
    return p


def route(params, cfg, xt):
    """xt (T, D) -> (router logits (T, E) f32, probs (T, E) f32, top_p
    (T, k) f32, top_i (T, k) int32)."""
    logits = jnp.dot(xt, params["router"].astype(xt.dtype),
                     preferred_element_type=jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_i = jax.lax.top_k(probs, cfg.moe_top_k)
    if cfg.moe_renorm_topk:
        top_p = top_p / jnp.maximum(top_p.sum(-1, keepdims=True), 1e-9)
    return logits, probs, top_p, top_i


def _routed(cfg, xt, top_p, top_i, w_gate, w_up, w_down,
            group_offset=None):
    """The routed experts over the rows of ``xt``: -> (y (T, D) float32,
    counts (E,) int32).  With ``group_offset`` the weights hold experts
    ``group_offset`` .. ``+ E_local - 1`` alone and the other experts' rows
    add nothing to ``y``."""
    # imported here: importing the kernels package loads Pallas, which a
    # model without experts never needs
    from repro.kernels.moe_gmm import moe_gmm
    t, d = xt.shape
    k, e = cfg.moe_top_k, cfg.n_experts
    flat_e = top_i.reshape(-1)                                # (T*k,)
    order = jnp.argsort(flat_e, stable=True)
    counts = jnp.bincount(flat_e, length=e).astype(jnp.int32)
    xs = xt[order // k]                                       # expert order
    g = moe_gmm(xs, w_gate, counts, group_offset=group_offset)
    u = moe_gmm(xs, w_up, counts, group_offset=group_offset)
    h = layers.act_fn(cfg.act)(g) * u
    ys = moe_gmm(h, w_down, counts, group_offset=group_offset)  # (T*k, D)
    inv = jnp.zeros_like(order).at[order].set(
        jnp.arange(t * k, dtype=order.dtype))                 # unsort
    y = (ys[inv].reshape(t, k, d).astype(jnp.float32)
         * top_p[..., None]).sum(axis=1)
    return y, counts


def _expert_mesh(n_experts):
    """The mesh in context (``jax.set_mesh``) when its ``model`` axis
    shards the experts, else None (also inside a ``shard_map`` that has
    already made ``model`` manual)."""
    mesh = jax.sharding.get_abstract_mesh()
    if (mesh.empty or "model" not in mesh.axis_names
            or "model" in mesh.manual_axes):
        return None
    m = mesh.shape["model"]
    return mesh if m > 1 and n_experts % m == 0 else None


def _expert_parallel(mesh, cfg, xt, top_p, top_i, w_gate, w_up, w_down):
    """The routed experts with the expert dim sharded over ``model``: each
    shard runs its own experts over its token rows (the tokens split over
    the other axes where they divide), and the shards' outputs are summed
    over ``model``.  No shard gathers another shard's experts; a Mosaic
    kernel cannot be partitioned by GSPMD, so this is the kernel's path on
    a mesh too."""
    others = tuple(a for a in mesh.axis_names
                   if a != "model" and a not in mesh.manual_axes)
    n_tok = int(np.prod([mesh.shape[a] for a in others]))
    tok = others if others and xt.shape[0] % n_tok == 0 else None

    def body(xt, top_p, top_i, w_gate, w_up, w_down):
        off = jax.lax.axis_index("model") * w_gate.shape[0]
        y, counts = _routed(cfg, xt, top_p, top_i, w_gate, w_up, w_down,
                            group_offset=off)
        y = jax.lax.psum(y, "model")
        return y, (jax.lax.psum(counts, tok) if tok else counts)

    rows, experts = P(tok, None), P("model", None, None)
    return jax.shard_map(
        body, mesh=mesh, in_specs=(rows, rows, rows, experts, experts,
                                   experts),
        out_specs=(rows, P()), check_vma=False)(
            xt, top_p, top_i, w_gate, w_up, w_down)


def dispatch_combine(params, cfg, xt, top_p, top_i):
    """Dropless routed experts: xt (T, D), top_* (T, k) -> (y (T, D),
    counts (E,) int32 tokens per expert).  Expert-parallel over the
    ``model`` axis of the mesh in context, when it shards the experts."""
    ws = (params["w_gate"], params["w_up"], params["w_down"])
    mesh = _expert_mesh(cfg.n_experts)
    if mesh is None:
        y, counts = _routed(cfg, xt, top_p, top_i, *ws)
    else:
        y, counts = _expert_parallel(mesh, cfg, xt, top_p, top_i, *ws)
    return y.astype(xt.dtype), counts


def moe_apply(params, cfg, x):
    """x (B,S,D) -> (y (B,S,D), aux dict)."""
    b, s, d = x.shape
    t = b * s
    k = cfg.moe_top_k
    e = cfg.n_experts
    xt = x.reshape(t, d)
    logits, probs, top_p, top_i = route(params, cfg, xt)
    y, counts = dispatch_combine(params, cfg, xt, top_p, top_i)
    if cfg.n_shared_experts:
        y = y + layers.mlp_apply(params["shared"], xt, cfg.act)

    frac_tokens = counts.astype(jnp.float32) / (t * k)        # f_e
    mean_prob = probs.mean(axis=0)                            # P_e
    lb_loss = e * jnp.sum(frac_tokens * mean_prob)
    z_loss = jnp.mean(jnp.square(jax.nn.logsumexp(logits, axis=-1)))
    aux = {"moe_lb_loss": lb_loss, "moe_z_loss": z_loss,
           "expert_counts": counts}
    return y.reshape(b, s, d), aux
