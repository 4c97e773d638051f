"""Student/teacher losses (paper §3.2) with vocab-chunked streaming math.

The paper's objective: CE between the teacher's senone posterior and the
student's posterior, with the teacher distribution reconstructed from the
stored top-k logits (missing entries = large negative  =>  renormalized
top-k softmax).  Generalized here to any softmax output (senones or token
vocabs up to 262k).

No loss here materializes the full (tokens x vocab) logit matrix: logsumexp
and the label/top-k gathers stream over vocab chunks of the unembedding
matrix.  ``repro.kernels.sparse_ce`` is the Pallas twin of the fused
gather+logsumexp inner loop.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

NEG_FILL = -1e9          # paper: "filling the missing logits with large
                         # negative values"


# ------------------------------------------------------------- full-logit
# reference implementations (small vocab / tests)

def soft_ce(student_logits, teacher_logits, temperature: float = 1.0):
    """CE(teacher || student), mean over frames."""
    t = jax.nn.log_softmax(teacher_logits / temperature, axis=-1)
    s = jax.nn.log_softmax(student_logits / temperature, axis=-1)
    return -jnp.mean(jnp.sum(jnp.exp(t) * s, axis=-1))


def topk_soft_ce(student_logits, topk_vals, topk_idx):
    """CE against the reconstructed top-k teacher distribution."""
    # reconstruct: scatter top-k values into a NEG_FILL canvas
    canvas = jnp.full(student_logits.shape, NEG_FILL, jnp.float32)
    canvas = jax.vmap(lambda c, i, v: c.at[i].set(v.astype(jnp.float32)))(
        canvas.reshape(-1, canvas.shape[-1]),
        topk_idx.reshape(-1, topk_idx.shape[-1]),
        topk_vals.reshape(-1, topk_vals.shape[-1]))
    canvas = canvas.reshape(student_logits.shape)
    return soft_ce(student_logits, canvas)


# ------------------------------------------------------------ chunked CE

def _chunked_logsumexp_and_gather(h, w_unembed, gather_idx, *, chunk: int,
                                  softcap: float = 0.0):
    """Stream over vocab chunks of w_unembed (D, V).

    h: (T, D) hidden states; gather_idx: (T, K) vocab ids to gather logits
    for.  Returns (logsumexp (T,), gathered (T, K)) in float32 without ever
    materializing (T, V).
    """
    t, d = h.shape
    v = w_unembed.shape[1]
    k = gather_idx.shape[-1]
    nchunks = (v + chunk - 1) // chunk
    vpad = nchunks * chunk
    wpad = jnp.pad(w_unembed, ((0, 0), (0, vpad - v)))
    hf = h

    def body(carry, ci):
        m, l, g = carry
        wc = jax.lax.dynamic_slice_in_dim(wpad, ci * chunk, chunk, axis=1)
        logits = (hf @ wc.astype(hf.dtype)).astype(jnp.float32)
        if softcap:
            logits = jnp.tanh(logits / softcap) * softcap
        # mask padded vocab tail
        vid = ci * chunk + jnp.arange(chunk)
        logits = jnp.where(vid[None, :] < v, logits, -jnp.inf)
        m_new = jnp.maximum(m, logits.max(axis=-1))
        l = l * jnp.exp(m - m_new) + jnp.exp(logits - m_new[:, None]).sum(-1)
        # gather any requested ids that live in this chunk
        loc = gather_idx - ci * chunk
        inside = (loc >= 0) & (loc < chunk)
        picked = jnp.take_along_axis(logits, jnp.clip(loc, 0, chunk - 1),
                                     axis=-1)
        g = jnp.where(inside, picked, g)
        return (m_new, l, g), None

    m0 = jnp.full((t,), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((t,), jnp.float32)
    g0 = jnp.full((t, k), NEG_FILL, jnp.float32)
    (m, l, g), _ = jax.lax.scan(body, (m0, l0, g0), jnp.arange(nchunks))
    return m + jnp.log(jnp.maximum(l, 1e-30)), g


def chunked_ce(h, w_unembed, labels, *, chunk: int = 8192,
               softcap: float = 0.0, mask=None):
    """Hard-label CE from hidden states, vocab-chunked. h (B,S,D)."""
    b, s, d = h.shape
    hf = h.reshape(b * s, d)
    lab = labels.reshape(b * s, 1)
    lse, gathered = _chunked_logsumexp_and_gather(hf, w_unembed, lab,
                                                  chunk=chunk,
                                                  softcap=softcap)
    nll = lse - gathered[:, 0]
    if mask is not None:
        mk = mask.reshape(b * s).astype(jnp.float32)
        return jnp.sum(nll * mk) / jnp.maximum(mk.sum(), 1.0)
    return jnp.mean(nll)


def chunked_topk_distill_ce(h, w_unembed, topk_vals, topk_idx, *,
                            chunk: int = 8192, softcap: float = 0.0,
                            mask=None, use_kernel: Optional[bool] = None,
                            interpret=None):
    """Paper §3.2.2 loss: CE between the renormalized top-k teacher
    distribution and the student's full-vocab distribution.

    teacher q_i = softmax over the k stored logits (missing = NEG_FILL,
    i.e. effectively zero mass).  loss = Σ_i q_i (lse_student - z_i).

    ``use_kernel=True`` routes the logsumexp+gather inner loop through
    ``kernels.sparse_ce`` (Pallas; differentiable via its custom_vjp);
    ``False`` runs the streamed XLA scan below, the oracle.  ``None``
    and ``interpret`` follow the kernels/_dispatch convention: the
    compiled kernel on TPU, the XLA scan elsewhere.
    """
    b, s, d = h.shape
    k = topk_idx.shape[-1]
    hf = h.reshape(b * s, d)
    idx = topk_idx.reshape(b * s, k)
    vals = topk_vals.reshape(b * s, k).astype(jnp.float32)
    from repro.kernels._dispatch import auto_use_kernel
    if auto_use_kernel(use_kernel):
        from repro.kernels.sparse_ce import topk_distill_ce
        return topk_distill_ce(
            hf, w_unembed, vals, idx, softcap=softcap, interpret=interpret,
            mask=None if mask is None else mask.reshape(b * s))
    lse, z = _chunked_logsumexp_and_gather(hf, w_unembed, idx, chunk=chunk,
                                           softcap=softcap)
    q = jax.nn.softmax(vals, axis=-1)                    # teacher top-k mass
    nll = jnp.sum(q * (lse[:, None] - z), axis=-1)
    if mask is not None:
        mk = mask.reshape(b * s).astype(jnp.float32)
        return jnp.sum(nll * mk) / jnp.maximum(mk.sum(), 1.0)
    return jnp.mean(nll)


def frame_accuracy(student_logits, labels):
    return jnp.mean((jnp.argmax(student_logits, -1) == labels)
                    .astype(jnp.float32))
