"""Plain float32 reference of the paper's acoustic models (arXiv:1904.01624
§2, §3): stacked LSTM / biLSTM over 192-d features, a senone unembedding,
top-k teacher targets, the top-k distillation loss and the clip + Nesterov
momentum step.

Straight ``jax.numpy``; it imports nothing of the program under test and
takes nothing it made.  The weights come from :func:`init_params`, which
the benchmark also hands to the program (same tree layout).

Every matmul goes through :func:`mm`, whose ``prec`` selects the operand
precision: ``"f32"`` (float32 at ``Precision.HIGHEST``, the reference),
``"bf16"`` or ``"fp8"`` (operands rounded to bfloat16 / float8_e4m3fn
with a per-tensor scale, then the same float32 product) -- the controls.
``"bf16_state"`` goes further: bfloat16 parameters (rounded again after
every update), bfloat16 matmul operands, and the gates and the (h, c)
state computed and carried in bfloat16.

LSTM cell, as the program runs it and as is common for LSTM AMs: gates
z = x Wx + h Wh + b split (i, f, g, o); c' = s(f + 1) c + s(i) tanh(g);
h' = s(o) tanh(c').  The +1 forget bias is the program's convention (the
paper does not state one).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
FP8_MAX = 448.0                      # largest finite float8_e4m3fn


BF16_STATE = "bf16_state"


def _round(a, prec):
    if prec == BF16_STATE:
        prec = "bf16"
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
    """a (..., D) @ b (D, N) in float32 with operands rounded to ``prec``;
    the backward's two matmuls round their operands (the cotangent with
    its own per-tensor scale) the same way."""
    if prec == "f32":
        return jnp.matmul(a, b, precision=HIGHEST)
    return _mm_low(a, b, prec)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _mm_low(a, b, prec):
    return jnp.matmul(_round(a, prec), _round(b, prec), precision=HIGHEST)


def _mm_low_fwd(a, b, prec):
    return _mm_low(a, b, prec), (a, b)


def _mm_low_bwd(prec, res, g):
    a, b = res
    g_r, a_r, b_r = _round(g, prec), _round(a, prec), _round(b, prec)
    da = jnp.matmul(g_r, b_r.T, precision=HIGHEST)
    db = jnp.matmul(a_r.reshape(-1, a.shape[-1]).T,
                    g_r.reshape(-1, g.shape[-1]), precision=HIGHEST)
    return da.astype(a.dtype), db.astype(b.dtype)


_mm_low.defvjp(_mm_low_fwd, _mm_low_bwd)


# ------------------------------------------------------------------ weights

def param_shapes(cfg: dict):
    """{leaf path: shape} of the model tree for a config dict with
    ``n_layers``, ``lstm_hidden``, ``feat_dim``, ``n_senones`` and
    ``bidirectional``."""
    h, d_in = cfg["lstm_hidden"], cfg["feat_dim"]
    dirs = ("fwd", "bwd") if cfg["bidirectional"] else (None,)
    shapes = {}
    for i in range(cfg["n_layers"]):
        for dr in dirs:
            pre = (f"l{i}",) + ((dr,) if dr else ())
            shapes[pre + ("wx",)] = (d_in, 4 * h)
            shapes[pre + ("wh",)] = (h, 4 * h)
            shapes[pre + ("b",)] = (4 * h,)
        d_in = h * len(dirs)
    shapes[("out",)] = (d_in, cfg["n_senones"])
    return shapes


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
        if path[-1] == "b":
            flat[path] = 0.1 * jax.random.normal(k, shape, jnp.float32)
        else:
            flat[path] = (jax.random.normal(k, shape, jnp.float32)
                          / np.sqrt(shape[0]))
    return _nest(flat)


def init_params(cfg: dict, seed: int):
    """Float32 weights from the seed, made on the device in one jitted
    call: normal matrices with variance 1/fan_in, normal biases with
    standard deviation 0.1."""
    spec = tuple(sorted(param_shapes(cfg).items()))
    word = np.random.SeedSequence(int(seed) & (2 ** 64 - 1)).generate_state(1)
    return _init(jax.random.key(int(word[0]) & 0x7FFFFFFF), spec)


# ------------------------------------------------------------------ forward

def lstm_layer(p, x, valid, prec="f32"):
    """x (B,T,D), valid (B,T) bool -> (B,T,H); the state holds where a
    frame is not valid."""
    b = x.shape[0]
    hdim = p["wh"].shape[0]
    sd = jnp.bfloat16 if prec == BF16_STATE else jnp.float32
    xz = (mm(x, p["wx"], prec) + p["b"]).astype(sd)  # (B,T,4H)

    def step(carry, inp):
        h, c = carry
        z_t, v_t = inp
        z = z_t + mm(h, p["wh"], prec).astype(sd)
        i, f, g, o = jnp.split(z, 4, axis=-1)
        c2 = jax.nn.sigmoid(f + 1.0) * c + jax.nn.sigmoid(i) * jnp.tanh(g)
        h2 = jax.nn.sigmoid(o) * jnp.tanh(c2)
        v = v_t[:, None]
        return (jnp.where(v, h2, h), jnp.where(v, c2, c)), h2

    zero = jnp.zeros((b, hdim), sd)
    _, ys = jax.lax.scan(step, (zero, zero),
                         (xz.transpose(1, 0, 2), valid.T))
    return ys.transpose(1, 0, 2).astype(jnp.float32)


def reverse_valid(x, lens):
    """Reverse each row's first lens[b] frames; zero the rest."""
    t = x.shape[1]
    ar = jnp.arange(t)
    src = jnp.clip(lens[:, None] - 1 - ar[None, :], 0, t - 1)
    out = jnp.take_along_axis(x, src[..., None], axis=1)
    return jnp.where((ar[None, :] < lens[:, None])[..., None], out, 0.0)


def hidden(params, feats, lens, cfg: dict, prec="f32"):
    """feats (B,T,F) float32, lens (B,) -> top layer output (B,T,H')."""
    t = feats.shape[1]
    valid = jnp.arange(t)[None, :] < lens[:, None]
    x = feats.astype(jnp.float32)
    for i in range(cfg["n_layers"]):
        p = params[f"l{i}"]
        if cfg["bidirectional"]:
            yf = lstm_layer(p["fwd"], x, valid, prec)
            yb = lstm_layer(p["bwd"], reverse_valid(x, lens), valid, prec)
            x = jnp.concatenate([yf, reverse_valid(yb, lens)], axis=-1)
        else:
            x = lstm_layer(p, x, valid, prec)
    return x


def logits(params, feats, lens, cfg: dict, prec="f32"):
    params = low_params(params, prec)
    return mm(hidden(params, feats, lens, cfg, prec), params["out"], prec)


def low_params(params, prec):
    """The parameters as ``prec`` stores them."""
    if prec != BF16_STATE:
        return params
    return jax.tree_util.tree_map(lambda x: _round(x, prec), params)


def emit_topk(lg, k: int):
    """Logits -> the store's emission: top-k values shifted by the row
    maximum, rounded to bfloat16, and their senone ids."""
    v, i = jax.lax.top_k(lg, k)
    return (v - v[..., :1]).astype(jnp.bfloat16).astype(jnp.float32), i


def emission_gap(vals, idx, ref_logits):
    """Per (frame, rank): how far a stored (shifted) value lies from the
    reference's logit at the stored id, shifted by the reference's best,
    in units of the frame's reference logit RMS.  Rank 0 reads how far
    the emitted best lies below the reference's best.  -> (N, k)."""
    ref = ref_logits.astype(jnp.float32)
    best = ref.max(axis=-1, keepdims=True)
    rms = jnp.sqrt(jnp.mean(ref * ref, axis=-1, keepdims=True))
    at = jnp.take_along_axis(ref, idx.astype(jnp.int32), axis=-1) - best
    return jnp.abs(vals.astype(jnp.float32) - at) / rms


# --------------------------------------------------------------- training

def distill_loss(params, batch, cfg: dict, prec="f32"):
    """Mean over valid frames of  sum_j q_j (lse - z_j),  q = softmax of
    the k stored teacher values, z the student logits at the stored ids
    (paper §3.2.2: missing logits are large negative, so the teacher
    distribution is the renormalized top-k)."""
    feats, mask = batch["feats"], batch["mask"]
    t = feats.shape[1]
    lens = jnp.full((feats.shape[0],), t, jnp.int32)
    lg = logits(params, feats, lens, cfg, prec)
    lse = jax.nn.logsumexp(lg, axis=-1)
    z = jnp.take_along_axis(lg, batch["topk_idx"].astype(jnp.int32), axis=-1)
    q = jax.nn.softmax(batch["topk_vals"].astype(jnp.float32), axis=-1)
    nll = jnp.sum(q * (lse[..., None] - z), axis=-1)
    m = mask.astype(jnp.float32)
    return jnp.sum(nll * m) / jnp.maximum(m.sum(), 1.0)


def sgd_step(params, mu, batch, lr, cfg: dict, *, clip: float, beta: float,
             prec="f32"):
    """Gradient, clip to global norm ``clip``, Nesterov momentum:
    mu' = beta mu + g;  p' = p - lr (beta mu' + g).
    -> (params', mu', loss)."""
    loss, g = jax.value_and_grad(distill_loss)(params, batch, cfg, prec)
    leaves = jax.tree_util.tree_leaves(g)
    gn = jnp.sqrt(sum(jnp.sum(x * x) for x in leaves))
    s = jnp.minimum(1.0, clip / jnp.maximum(gn, 1e-9))
    g = jax.tree_util.tree_map(lambda x: x * s, g)
    mu = jax.tree_util.tree_map(lambda m, x: beta * m + x, mu, g)
    params = jax.tree_util.tree_map(lambda p, m, x: p - lr * (beta * m + x),
                                    params, mu, g)
    return low_params(params, prec), mu, loss
