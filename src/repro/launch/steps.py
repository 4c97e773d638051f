"""Jittable train / prefill / serve steps shared by the trainer, the serving
loop, and the multi-pod dry-run.

``train_step`` loss kinds:
  "ce"           — hard-label CE (baseline supervised recipe, paper §2)
  "distill_topk" — the paper's SSL objective: CE against reconstructed
                   top-k teacher logits (§3.2.2), vocab-chunked.
Both stream over vocab chunks; full (tokens x vocab) logits are never
materialized.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.core import distill

MTP_WEIGHT = 0.3


def model_forward(model, cfg, params, batch):
    """Dispatch on input kind; returns (hidden, aux)."""
    if cfg.family == "lstm_am":
        return model.apply(params, batch["feats"])
    if cfg.encoder is not None:
        return model.apply(params, batch["tokens"],
                           enc_embeds=batch["enc_embeds"])
    return model.apply(params, batch["tokens"])


def make_loss_fn(model, cfg, loss_kind: str, *, vocab_chunk: int = 8192,
                 distill_kernel: Optional[bool] = None):
    # the trailing ``rng`` opts into the Trainer's per-update key folding
    # (repro.train.strategies): today's forwards are deterministic so the
    # key is unused (and DCE'd), but any stochastic regularizer added to
    # a model family picks it up without touching the step plumbing
    def loss_fn(params, batch, rng=None):
        del rng
        h, aux = model_forward(model, cfg, params, batch)
        w = model.unembed_matrix(params)
        cap = cfg.logit_softcap
        mask = batch.get("mask")
        if loss_kind == "distill_topk":
            # distill_kernel: Pallas sparse_ce inner loop (grad via its
            # custom_vjp) vs the streamed-XLA oracle; None: the kernel
            # on TPU only (kernels._dispatch)
            loss = distill.chunked_topk_distill_ce(
                h, w, batch["topk_vals"], batch["topk_idx"],
                chunk=vocab_chunk, softcap=cap, mask=mask,
                use_kernel=distill_kernel)
        else:
            loss = distill.chunked_ce(h, w, batch["labels"],
                                      chunk=vocab_chunk, softcap=cap,
                                      mask=mask)
        metrics = {"loss": loss}
        # MoE auxiliary losses
        lb = sum(v for k_, v in aux.items() if k_.endswith("moe_lb_loss"))
        zl = sum(v for k_, v in aux.items() if k_.endswith("moe_z_loss"))
        if aux:
            loss = loss + cfg.router_aux_weight * lb + 1e-4 * zl
            metrics["moe_lb"] = jnp.asarray(lb)
        # multi-token prediction (deepseek-v3)
        if cfg.mtp_depth and loss_kind == "ce" and cfg.family != "lstm_am" \
                and cfg.encoder is None:
            nxt = jnp.roll(batch["tokens"], -1, axis=1)
            h2 = model.mtp_hidden(params, h, nxt,
                                  jnp.arange(batch["tokens"].shape[1]))
            if h2 is not None:
                mtp_labels = jnp.roll(batch["labels"], -1, axis=1)
                loss = loss + MTP_WEIGHT * distill.chunked_ce(
                    h2, w, mtp_labels, chunk=vocab_chunk, softcap=cap)
        metrics["total_loss"] = loss
        return loss, metrics
    return loss_fn


def make_train_step(model, cfg, *, loss_kind: str = "ce",
                    optimizer: str = "momentum", clip: float = 1.0,
                    vocab_chunk: int = 8192,
                    distill_kernel: Optional[bool] = None):
    """-> train_step(params, opt_state, batch, lr).

    lr is a *traced* argument (not baked into the closure): an LR
    schedule sweeping any number of phases reuses one executable per
    batch shape — tests/test_trainer.py pins the compile count.
    """
    from repro.train.strategies import make_sgd_step
    loss_fn = make_loss_fn(model, cfg, loss_kind, vocab_chunk=vocab_chunk,
                           distill_kernel=distill_kernel)
    return make_sgd_step(loss_fn, optimizer=optimizer, clip=clip)


def init_opt_state(params, optimizer: str = "momentum"):
    from repro.train.strategies import init_opt
    return init_opt(params, optimizer)


def make_prefill_step(model, cfg):
    """Forward over the prompt; emit last-position logits."""
    def prefill_step(params, batch):
        h, _ = model_forward(model, cfg, params, batch)
        return model.unembed(params, h[:, -1:])
    return prefill_step


def make_serve_step(model, cfg, *, greedy: bool = True,
                    use_kernel: bool = False, wide_fallback: bool = False):
    """One decode step: next-token + logits + updated cache.

    ``greedy=False`` returns a step taking an extra ``samp`` dict of
    (B,)-shaped per-row knobs (``temperature``/``top_k``/``top_p``/
    ``seed``); rows with temperature <= 0 still take bitwise argmax.
    The sampling key is derived from the *pre-step* cache position so a
    request samples identically regardless of batch composition.

    ``use_kernel=True`` routes next-token selection through the fused
    ``kernels.topk_sample`` op (one top-k extraction + Gumbel-max over
    a k_cap candidate set instead of a full-vocab argsort).  Greedy
    tokens stay bitwise identical to ``jnp.argmax``; sampled tokens
    follow the fused sampler's truncated-nucleus semantics (see
    kernels/topk_sample/ref.py), so the fused path is an explicit
    opt-in, never a silent swap.

    ``wide_fallback=True`` (fused-sampling only) builds the *mixed*
    step: rows whose ``top_k`` the k_cap candidate set can't honor
    (``top_k <= 0`` — full vocab — or ``top_k > k_cap``) take the
    full-vocab argsort sampler, bitwise what the non-kernel server
    draws; every other row keeps the fused path.  The server picks this
    step only for windows that actually hold a wide row.
    """
    if use_kernel:
        # serve/kernels packages import this module at import time;
        # keep these edges lazy and one-directional
        from repro.kernels.topk_sample import K_CAP_DEFAULT, topk_sample

    if greedy:
        def serve_step(params, cache, tokens):
            logits, cache = model.decode_step(params, cache, tokens)
            if use_kernel:
                _, _, nxt = topk_sample(logits[:, -1], greedy=True)
                nxt = nxt[:, None]
            else:
                nxt = jnp.argmax(logits[:, -1],
                                 axis=-1).astype(jnp.int32)[:, None]
            return nxt, logits, cache
        return serve_step

    if not use_kernel or wide_fallback:
        from repro.serve.sampling import sample_tokens

    def serve_step_sample(params, cache, tokens, samp):
        pos = cache["pos"]
        logits, cache = model.decode_step(params, cache, tokens)
        if use_kernel:
            _, _, nxt = topk_sample(logits[:, -1], samp["temperature"],
                                    samp["top_k"], samp["top_p"],
                                    samp["seed"], pos)
            if wide_fallback:
                wide_nxt = sample_tokens(logits[:, -1], samp["temperature"],
                                         samp["top_k"], samp["top_p"],
                                         samp["seed"], pos)
                wide = ((samp["top_k"] <= 0)
                        | (samp["top_k"] > K_CAP_DEFAULT))
                nxt = jnp.where(wide, wide_nxt, nxt)
        else:
            nxt = sample_tokens(logits[:, -1], samp["temperature"],
                                samp["top_k"], samp["top_p"], samp["seed"],
                                pos)
        return nxt[:, None], logits, cache
    return serve_step_sample
