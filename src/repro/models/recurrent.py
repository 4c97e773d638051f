"""Recurrent sequence mixers: LSTM / biLSTM (the paper's AM), RG-LRU
(RecurrentGemma/Griffin), mLSTM and sLSTM (xLSTM).

Training/prefill forms:
  - LSTM / sLSTM: strictly sequential -> ``lax.scan`` over time.
  - RG-LRU: linear recurrence -> ``lax.associative_scan`` (parallel).
  - mLSTM: baseline is the sequential scan; a chunkwise-parallel form lives in
    ``mlstm_chunked`` (used when seq is long) — both are tested equal.
Decode forms: single-step recurrences over an explicit state pytree.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import layers


# =================================================================== LSTM

def init_lstm(key, d_in: int, d_h: int):
    ks = jax.random.split(key, 2)
    return {"wx": layers.dense_init(ks[0], d_in, 4 * d_h),
            "wh": layers.dense_init(ks[1], d_h, 4 * d_h),
            "b": jnp.zeros((4 * d_h,), jnp.float32)}


def lstm_cell(params, x_t, h, c):
    z = x_t @ params["wx"].astype(x_t.dtype) \
        + h @ params["wh"].astype(x_t.dtype) + params["b"].astype(x_t.dtype)
    i, f, g, o = jnp.split(z.astype(jnp.float32), 4, axis=-1)
    c = jax.nn.sigmoid(f + 1.0) * c + jax.nn.sigmoid(i) * jnp.tanh(g)
    h = jax.nn.sigmoid(o) * jnp.tanh(c)
    return h.astype(x_t.dtype), c


def time_mask(lens, s: int):
    """lens (B,) -> the time-major (S,B,1) mask of each row's valid frames."""
    return (jnp.arange(s)[None, :] < lens[:, None]).T[..., None]


def lstm_zero_state(params, b: int, dtype):
    """The (h, c) a row starts from: h in the activations' dtype, c f32."""
    d_h = params["wh"].shape[0]
    return (jnp.zeros((b, d_h), dtype), jnp.zeros((b, d_h), jnp.float32))


def lstm_scan(params, xs, state, mask=None, reverse=False):
    """Time-major LSTM: xs (S,B,D), state (h, c) -> (ys (S,B,H), (h, c)).

    mask (S,B,1) optional: (h, c) holds wherever it is False, so a row's
    state stays what it was before its first valid frame and after its
    last.  With ``reverse`` the scan runs from the last frame to the first
    and writes each output at its own frame.  Outputs where the mask is
    False are unspecified — callers mask or slice them.
    """
    if mask is None:
        def step(carry, x_t):
            h, c = carry
            h, c = lstm_cell(params, x_t, h, c)
            return (h, c), h

        (h, c), ys = jax.lax.scan(step, state, xs, reverse=reverse)
        return ys, (h, c)

    def step(carry, xm):
        x_t, m_t = xm
        h, c = carry
        h2, c2 = lstm_cell(params, x_t, h, c)
        h = jnp.where(m_t, h2, h)
        c = jnp.where(m_t, c2, c)
        return (h, c), h2

    (h, c), ys = jax.lax.scan(step, state, (xs, mask), reverse=reverse)
    return ys, (h, c)


def lstm_apply(params, x, state=None, lens=None):
    """x (B,S,D) -> (B,S,H). state: optional (h, c) carried (chunked BPTT).

    lens (B,) optional valid lengths: the carried (h, c) freezes once a
    row passes its length, so a padded batch hands back exactly the state
    an unpadded per-row run would (the serving engine's batching
    invariant).  Outputs past a row's length are unspecified — callers
    mask or slice them.
    """
    if state is None:
        state = lstm_zero_state(params, x.shape[0], x.dtype)
    mask = None if lens is None else time_mask(lens, x.shape[1])
    ys, (h, c) = lstm_scan(params, x.transpose(1, 0, 2), state, mask)
    return ys.transpose(1, 0, 2), (h, c)


def bilstm_apply(fwd_params, bwd_params, xs, mask=None):
    """One time-major biLSTM layer: xs (S,B,D) -> (S,B,2H), the forward
    and backward directions' outputs side by side.

    The backward direction is a reverse scan over the same input.  With
    mask (S,B,1) the state holds at zero through a row's padded tail, so
    the backward pass starts at each row's last *valid* frame and padded
    batches match per-row runs on the valid region (positions past a
    row's length are unspecified).
    """
    state = lstm_zero_state(fwd_params, xs.shape[1], xs.dtype)
    yf, _ = lstm_scan(fwd_params, xs, state, mask)
    yb, _ = lstm_scan(bwd_params, xs, state, mask, reverse=True)
    return jnp.concatenate([yf, yb], axis=-1)


# ================================================================= RG-LRU

def init_rglru_block(key, cfg):
    """Griffin recurrent block: in/gate proj -> conv -> RG-LRU -> out proj."""
    d, w = cfg.d_model, cfg.lru_width or cfg.d_model
    ks = jax.random.split(key, 6)
    # Lambda init so that a = sigmoid(L) in (0.9, 0.999) roughly
    lam = jnp.log(jnp.expm1(
        jnp.linspace(2.0, 6.0, w, dtype=jnp.float32)))  # softplus^-1 spread
    return {
        "w_in": layers.dense_init(ks[0], d, w),
        "w_gate": layers.dense_init(ks[1], d, w),
        "conv": (jax.random.normal(ks[2], (cfg.conv_width, w), jnp.float32)
                 / np.sqrt(cfg.conv_width)),
        "w_a": layers.dense_init(ks[3], w, w, scale=0.5),
        "w_i": layers.dense_init(ks[4], w, w, scale=0.5),
        "lam": lam,
        "w_out": layers.dense_init(ks[5], w, d),
    }


def _causal_conv(x, kernel, state=None):
    """Depthwise causal conv along time. x (B,S,W), kernel (K,W).

    state (B,K-1,W) holds trailing context for decode; returns (y, new_state).
    """
    k = kernel.shape[0]
    if state is None:
        xp = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    else:
        xp = jnp.concatenate([state.astype(x.dtype), x], axis=1)
    y = sum(xp[:, i:i + x.shape[1], :] * kernel[i].astype(x.dtype)
            for i in range(k))
    new_state = xp[:, -(k - 1):, :] if k > 1 else None
    return y, new_state


def _rglru_coeffs(params, x):
    """Per-step gate a_t (decay) and gated input, float32."""
    xf = x.astype(jnp.float32)
    r = jax.nn.sigmoid(xf @ params["w_a"])
    i = jax.nn.sigmoid(xf @ params["w_i"])
    log_a = -8.0 * r * jax.nn.softplus(params["lam"])      # log a_t <= 0
    a = jnp.exp(log_a)
    gated = jnp.sqrt(jnp.maximum(1.0 - jnp.exp(2.0 * log_a), 1e-12)) * (i * xf)
    return a, gated


def rglru_scan(params, x, h0=None):
    """Linear recurrence h_t = a_t h_{t-1} + b_t via associative scan."""
    a, bseq = _rglru_coeffs(params, x)
    if h0 is not None:
        # fold initial state into the first input
        bseq = bseq.at[:, 0].add(a[:, 0] * h0.astype(jnp.float32))

    def combine(l, r):
        al, bl = l
        ar, br = r
        return al * ar, ar * bl + br

    _, h = jax.lax.associative_scan(combine, (a, bseq), axis=1)
    return h.astype(x.dtype), h[:, -1]


def rglru_block_apply(params, cfg, x, state=None):
    """x (B,S,D) -> (B,S,D). state = {"h": (B,W) f32, "conv": (B,K-1,W)}."""
    gate = jax.nn.gelu(x @ params["w_gate"].astype(x.dtype))
    u = x @ params["w_in"].astype(x.dtype)
    u, conv_state = _causal_conv(u, params["conv"],
                                 None if state is None else state["conv"])
    h, h_last = rglru_scan(params, u, None if state is None else state["h"])
    y = (h * gate) @ params["w_out"].astype(x.dtype)
    return y, {"h": h_last, "conv": conv_state}


def rglru_block_decode(params, cfg, x, state):
    """Single step. x (B,1,D)."""
    gate = jax.nn.gelu(x @ params["w_gate"].astype(x.dtype))
    u = x @ params["w_in"].astype(x.dtype)
    u, conv_state = _causal_conv(u, params["conv"], state["conv"])
    a, b = _rglru_coeffs(params, u)
    h = a[:, 0] * state["h"] + b[:, 0]
    y = (h[:, None].astype(x.dtype) * gate) @ params["w_out"].astype(x.dtype)
    return y, {"h": h, "conv": conv_state}


def init_rglru_state(cfg, batch, dtype):
    w = cfg.lru_width or cfg.d_model
    return {"h": jnp.zeros((batch, w), jnp.float32),
            "conv": jnp.zeros((batch, cfg.conv_width - 1, w), dtype)}


# ================================================================== mLSTM

def init_mlstm_block(key, cfg):
    d = cfg.d_model
    inner = int(cfg.mlstm_proj_factor * d)
    h = cfg.n_heads
    ks = jax.random.split(key, 8)
    return {
        "w_up": layers.dense_init(ks[0], d, inner),
        "w_gate": layers.dense_init(ks[1], d, inner),
        "conv": (jax.random.normal(ks[2], (cfg.conv_width, inner), jnp.float32)
                 / np.sqrt(cfg.conv_width)),
        "wq": layers.dense_init(ks[3], inner, inner),
        "wk": layers.dense_init(ks[4], inner, inner),
        "wv": layers.dense_init(ks[5], inner, inner),
        "w_if": layers.dense_init(ks[6], inner, 2 * h),   # i,f gate logits
        "b_if": jnp.concatenate([jnp.zeros((h,)), 3.0 * jnp.ones((h,))]),
        "gn": jnp.ones((inner,), jnp.float32),            # group-norm scale
        "w_down": layers.dense_init(ks[7], inner, d),
    }


def _mlstm_qkv(params, cfg, x, conv_state=None):
    """x (B,S,D) -> conv'd qkv (B,H,S,hd) and gate logits (B,S,2H)."""
    u = x @ params["w_up"].astype(x.dtype)
    c, conv_state = _causal_conv(u, params["conv"], conv_state)
    c = jax.nn.silu(c)
    b, s, inner = c.shape
    h = cfg.n_heads
    hd = inner // h

    def heads(m):
        return m.reshape(b, s, h, hd).transpose(0, 2, 1, 3)
    q = heads(c @ params["wq"].astype(x.dtype)) / np.sqrt(hd)
    k = heads(c @ params["wk"].astype(x.dtype)) / np.sqrt(hd)
    v = heads(u @ params["wv"].astype(x.dtype))
    gates = (c @ params["w_if"].astype(x.dtype)).astype(jnp.float32) \
        + params["b_if"]
    return q, k, v, gates, u, conv_state


def _mlstm_step(carry, t):
    C, n, m = carry
    qt, kt, vt, il, fl = t
    m_new = jnp.maximum(fl + m, il)
    i_ = jnp.exp(il - m_new)[..., None]
    f_ = jnp.exp(fl + m - m_new)[..., None]
    C = f_[..., None] * C + i_[..., None] * (vt[..., :, None]
                                             * kt[..., None, :])
    n = f_ * n + i_ * kt
    num = jnp.einsum("bhvk,bhk->bhv", C, qt)
    den = jnp.maximum(jnp.abs(jnp.einsum("bhk,bhk->bh", n, qt)),
                      jnp.exp(-m_new))[..., None]
    return (C, n, m_new), (num / den)


MLSTM_CHUNK = 64     # remat-chunk: backward saves only chunk-boundary
                     # states ((B,H,hd,hd) every 64 steps instead of every
                     # step) — see EXPERIMENTS.md §Perf (xlstm train_4k)


def mlstm_scan(q, k, v, gates, *, chunk: int = MLSTM_CHUNK):
    """Sequential stabilized mLSTM. q/k/v (B,H,S,hd); gates (B,S,2H).

    Chunked + remat: an outer scan over S/chunk blocks whose body (an
    inner scan over `chunk` steps) is jax.checkpoint'ed.  Numerically
    identical to the flat scan; activation residuals for backward drop
    from O(S) per-step (B,H,hd,hd) C-states to O(S/chunk) boundary states
    + recompute.  Returns h (B,H,S,hd) and final state (C, n, m).
    """
    b, h, s, hd = q.shape
    i_log = gates[..., :h].transpose(0, 2, 1)       # (B,H,S)
    f_log = jax.nn.log_sigmoid(gates[..., h:]).transpose(0, 2, 1)

    C0 = jnp.zeros((b, h, hd, hd), jnp.float32)
    n0 = jnp.zeros((b, h, hd), jnp.float32)
    m0 = jnp.zeros((b, h), jnp.float32)
    xs = (q.transpose(2, 0, 1, 3).astype(jnp.float32),
          k.transpose(2, 0, 1, 3).astype(jnp.float32),
          v.transpose(2, 0, 1, 3).astype(jnp.float32),
          i_log.transpose(2, 0, 1), f_log.transpose(2, 0, 1))

    c = min(chunk, s)
    if s % c:
        (C, n, m), hs = jax.lax.scan(_mlstm_step, (C0, n0, m0), xs)
        return hs.transpose(1, 2, 0, 3), (C, n, m)

    nchunks = s // c
    xs_c = jax.tree_util.tree_map(
        lambda a: a.reshape(nchunks, c, *a.shape[1:]), xs)

    @jax.checkpoint
    def chunk_body(carry, xc):
        return jax.lax.scan(_mlstm_step, carry, xc)

    (C, n, m), hs = jax.lax.scan(chunk_body, (C0, n0, m0), xs_c)
    hs = hs.reshape(s, b, h, hd)
    return hs.transpose(1, 2, 0, 3), (C, n, m)


def mlstm_block_apply(params, cfg, x, state=None):
    q, k, v, gates, u, conv_state = _mlstm_qkv(
        params, cfg, x, None if state is None else state["conv"])
    if state is not None:
        hseq, st = _mlstm_with_state(q, k, v, gates, state)
        st["conv"] = conv_state
    else:
        hseq, (C, n, m) = mlstm_scan(q, k, v, gates)
        st = {"C": C, "n": n, "m": m, "conv": conv_state}
    b, h, s, hd = hseq.shape
    y = hseq.transpose(0, 2, 1, 3).reshape(b, s, h * hd)
    y = _groupnorm(y, params["gn"], h)
    gate = jax.nn.silu(x @ params["w_gate"].astype(x.dtype))
    y = (y.astype(x.dtype) * gate) @ params["w_down"].astype(x.dtype)
    return y, st


def _mlstm_with_state(q, k, v, gates, state):
    # prefill continuing from a state: fold state via scan init
    b, h, s, hd = q.shape
    i_log = gates[..., :h].transpose(0, 2, 1)
    f_log = jax.nn.log_sigmoid(gates[..., h:]).transpose(0, 2, 1)

    def step(carry, t):
        C, n, m = carry
        qt, kt, vt, il, fl = t
        m_new = jnp.maximum(fl + m, il)
        i_ = jnp.exp(il - m_new)[..., None]
        f_ = jnp.exp(fl + m - m_new)[..., None]
        C = f_[..., None] * C + i_[..., None] * (vt[..., :, None]
                                                 * kt[..., None, :])
        n = f_ * n + i_ * kt
        num = jnp.einsum("bhvk,bhk->bhv", C, qt)
        den = jnp.maximum(jnp.abs(jnp.einsum("bhk,bhk->bh", n, qt)),
                          jnp.exp(-m_new))[..., None]
        return (C, n, m_new), (num / den)

    init = (state["C"], state["n"], state["m"])
    (C, n, m), hs = jax.lax.scan(
        step, init,
        (q.transpose(2, 0, 1, 3).astype(jnp.float32),
         k.transpose(2, 0, 1, 3).astype(jnp.float32),
         v.transpose(2, 0, 1, 3).astype(jnp.float32),
         i_log.transpose(2, 0, 1), f_log.transpose(2, 0, 1)))
    return hs.transpose(1, 2, 0, 3), {"C": C, "n": n, "m": m,
                                      "conv": state["conv"]}


def mlstm_block_decode(params, cfg, x, state):
    """x (B,1,D); single recurrent step."""
    u = x @ params["w_up"].astype(x.dtype)
    c, conv_state = _causal_conv(u, params["conv"], state["conv"])
    c = jax.nn.silu(c)
    b, _, inner = c.shape
    h = cfg.n_heads
    hd = inner // h

    def heads(m):
        return m.reshape(b, h, hd)
    q = heads(c[:, 0] @ params["wq"].astype(x.dtype)).astype(jnp.float32) / np.sqrt(hd)
    k = heads(c[:, 0] @ params["wk"].astype(x.dtype)).astype(jnp.float32) / np.sqrt(hd)
    v = heads(u[:, 0] @ params["wv"].astype(x.dtype)).astype(jnp.float32)
    gl = (c[:, 0] @ params["w_if"].astype(x.dtype)).astype(jnp.float32) \
        + params["b_if"]
    il, fl = gl[..., :h], jax.nn.log_sigmoid(gl[..., h:])
    C, n, m = state["C"], state["n"], state["m"]
    m_new = jnp.maximum(fl + m, il)
    i_ = jnp.exp(il - m_new)[..., None]
    f_ = jnp.exp(fl + m - m_new)[..., None]
    C = f_[..., None] * C + i_[..., None] * (v[..., :, None] * k[..., None, :])
    n = f_ * n + i_ * k
    num = jnp.einsum("bhvk,bhk->bhv", C, q)
    den = jnp.maximum(jnp.abs(jnp.einsum("bhk,bhk->bh", n, q)),
                      jnp.exp(-m_new))[..., None]
    hvec = (num / den).reshape(b, 1, inner)
    y = _groupnorm(hvec, params["gn"], h)
    gate = jax.nn.silu(x @ params["w_gate"].astype(x.dtype))
    y = (y.astype(x.dtype) * gate) @ params["w_down"].astype(x.dtype)
    return y, {"C": C, "n": n, "m": m_new, "conv": conv_state}


def init_mlstm_state(cfg, batch, dtype):
    inner = int(cfg.mlstm_proj_factor * cfg.d_model)
    h = cfg.n_heads
    hd = inner // h
    return {"C": jnp.zeros((batch, h, hd, hd), jnp.float32),
            "n": jnp.zeros((batch, h, hd), jnp.float32),
            "m": jnp.zeros((batch, h), jnp.float32),
            "conv": jnp.zeros((batch, cfg.conv_width - 1, inner), dtype)}


def _groupnorm(x, scale, n_groups, eps=1e-6):
    """Head-wise group norm over the channel axis. x (B,S,C)."""
    b, s, cdim = x.shape
    xf = x.astype(jnp.float32).reshape(b, s, n_groups, cdim // n_groups)
    mu = xf.mean(axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps)
    return (y.reshape(b, s, cdim) * scale).astype(x.dtype)


# ================================================================== sLSTM

def init_slstm_block(key, cfg):
    d = cfg.d_model
    h = cfg.n_heads
    ks = jax.random.split(key, 4)
    up = int(cfg.slstm_proj_factor * d)
    return {
        "conv": (jax.random.normal(ks[0], (cfg.conv_width, d), jnp.float32)
                 / np.sqrt(cfg.conv_width)),
        "wx": layers.dense_init(ks[1], d, 4 * d),
        # block-diagonal recurrent weights: per head (hd x 4hd)
        "rh": (jax.random.normal(ks[2], (h, d // h, 4 * (d // h)),
                                 jnp.float32) / np.sqrt(d // h)),
        "b": jnp.zeros((4 * d,), jnp.float32),
        "gn": jnp.ones((d,), jnp.float32),
        # post-block gated MLP (the sLSTM block's own ffn)
        "mlp": layers.mlp_init(ks[3], d, up, gated=True),
    }


def _slstm_gates(params, cfg, xz, hprev):
    """xz (B,4D) precomputed input part; hprev (B,D)."""
    b, d4 = xz.shape
    d = d4 // 4
    h = cfg.n_heads
    hd = d // h
    rec = jnp.einsum("bhk,hkj->bhj", hprev.reshape(b, h, hd),
                     params["rh"]).reshape(b, 4 * d)
    z = xz + rec + params["b"]
    # layout: [i, f, z, o] each d wide
    return jnp.split(z, 4, axis=-1)


def slstm_block_apply(params, cfg, x, state=None):
    b, s, d = x.shape
    c_in, conv_state = _causal_conv(x, params["conv"],
                                    None if state is None else state["conv"])
    c_in = jax.nn.silu(c_in)
    xz = (c_in @ params["wx"].astype(x.dtype)).astype(jnp.float32)
    if state is None:
        st = init_slstm_state(cfg, b, x.dtype)
    else:
        st = state

    def step(carry, xz_t):
        c, n, m, hprev = carry
        il, fl, zl, ol = _slstm_gates(params, cfg, xz_t, hprev)
        m_new = jnp.maximum(fl + m, il)
        i_ = jnp.exp(il - m_new)
        f_ = jnp.exp(fl + m - m_new)
        c = f_ * c + i_ * jnp.tanh(zl)
        n = f_ * n + i_
        hv = jax.nn.sigmoid(ol) * c / jnp.maximum(n, 1e-6)
        return (c, n, m_new, hv), hv

    init = (st["c"], st["n"], st["m"], st["h"])
    xs = xz.transpose(1, 0, 2)
    seq = xs.shape[0]
    ck = min(MLSTM_CHUNK, seq)
    if seq % ck == 0 and seq > ck:      # remat-chunked (see mlstm_scan)
        xs_c = xs.reshape(seq // ck, ck, *xs.shape[1:])

        @jax.checkpoint
        def chunk_body(carry, xc):
            return jax.lax.scan(step, carry, xc)

        (c, n, m, hlast), hs = jax.lax.scan(chunk_body, init, xs_c)
        hs = hs.reshape(seq, *hs.shape[2:])
    else:
        (c, n, m, hlast), hs = jax.lax.scan(step, init, xs)
    hs = hs.transpose(1, 0, 2)
    y = _groupnorm(hs, params["gn"], cfg.n_heads).astype(x.dtype)
    y = y + layers.mlp_apply(params["mlp"], y, "gelu")
    new_state = {"c": c, "n": n, "m": m, "h": hlast, "conv": conv_state}
    return y, new_state


def slstm_block_decode(params, cfg, x, state):
    y, st = slstm_block_apply(params, cfg, x, state)
    return y, st


def init_slstm_state(cfg, batch, dtype):
    d = cfg.d_model
    return {"c": jnp.zeros((batch, d), jnp.float32),
            "n": jnp.zeros((batch, d), jnp.float32),
            "m": jnp.zeros((batch, d), jnp.float32),
            "h": jnp.zeros((batch, d), jnp.float32),
            "conv": jnp.zeros((batch, cfg.conv_width - 1, d), dtype)}
