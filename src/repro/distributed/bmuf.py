"""Blockwise Model-Update Filtering (paper §3.5; Chen & Huo, ICASSP 2016).

The paper's 64-GPU trainer for the SSL CE stage: each worker runs local
SGD for a *block* of steps on its own data shard, then the workers sync:

    G_t      = mean_w(theta_w) - theta_g            (block "gradient")
    Delta_t  = eta * Delta_{t-1} + zeta * G_t        (block momentum eta,
                                                      block LR zeta)
    theta_g <- theta_g + Delta_t
    restart  = theta_g + eta * Delta_t               (Nesterov, NBM —
                                                      "Nesterov-like momentum
                                                      updates at block level")

Two interchangeable execution paths over the same math:

  * ``vmap`` path (CPU tests / laptop): worker params carry a leading W dim,
    local steps via jax.vmap, sync via mean over W.
  * ``shard_map`` path (production): the W dim is sharded over the mesh's
    (pod, data) axes; local steps touch no cross-worker collective
    (BMUF's entire point — communication every tau steps instead of every
    minibatch), the block sync is one psum per leaf.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Callable

import jax
import jax.numpy as jnp

tmap = jax.tree_util.tree_map


@dataclass(frozen=True)
class BMUFConfig:
    n_workers: int = 64
    block_steps: int = 8             # tau: local steps per block
    block_momentum: float = 0.875    # eta; Chen&Huo suggest 1 - 1/W-ish
    block_lr: float = 1.0            # zeta
    nesterov: bool = True            # NBM variant


def bmuf_init(global_params, cfg: BMUFConfig):
    """-> {theta_g, delta, workers} — workers stacked on a leading W dim."""
    workers = tmap(
        lambda p: jnp.broadcast_to(p, (cfg.n_workers,) + p.shape).copy(),
        global_params)
    delta = tmap(lambda p: jnp.zeros_like(p, dtype=jnp.float32),
                 global_params)
    return {"theta_g": global_params, "delta": delta, "workers": workers}


def active_mean_fn(active):
    """Worker-mean over live lanes only: ``active`` is a (W,) 0/1 mask.

    Dead lanes contribute nothing to the block average; the divisor is
    the live count (floored at 1 so an all-dead mask degrades to a
    frozen model instead of NaNs).  The block-momentum ``delta`` is
    global, not per-worker, so it needs no masking — it carries
    unchanged across a membership change, which is what the
    shrink-mid-run-vs-fresh-W pin relies on.
    """
    a = jnp.asarray(active, jnp.float32)
    denom = jnp.maximum(jnp.sum(a), 1.0)

    def mean_fn(w):
        aw = a.reshape((-1,) + (1,) * (w.ndim - 1))
        return jnp.sum(w.astype(jnp.float32) * aw, axis=0) / denom

    return mean_fn


def block_sync(state, cfg: BMUFConfig, *, mean_fn=None, active=None):
    """One BMUF sync. ``mean_fn`` overrides the worker-mean (shard_map path
    passes a lax.pmean closure); default = mean over the leading W dim.
    ``active`` (a (W,) 0/1 mask, ignored when ``mean_fn`` is given)
    restricts the average to live workers — the elastic-membership hook.
    The Nesterov restart still broadcasts to *all* lanes, so a dead
    lane holds current params and can rejoin warm by flipping its mask
    bit back on."""
    if mean_fn is None:
        if active is not None:
            mean_fn = active_mean_fn(active)
        else:
            mean_fn = lambda w: jnp.mean(w.astype(jnp.float32), axis=0)
    theta_g, delta = state["theta_g"], state["delta"]
    wbar = tmap(mean_fn, state["workers"])
    g = tmap(lambda wb, tg: wb - tg.astype(jnp.float32), wbar, theta_g)
    delta = tmap(lambda d, g_: cfg.block_momentum * d + cfg.block_lr * g_,
                 delta, g)
    theta_g = tmap(lambda tg, d: (tg.astype(jnp.float32) + d).astype(tg.dtype),
                   theta_g, delta)
    if cfg.nesterov:
        restart = tmap(
            lambda tg, d: (tg.astype(jnp.float32)
                           + cfg.block_momentum * d).astype(tg.dtype),
            theta_g, delta)
    else:
        restart = theta_g
    workers = tmap(
        lambda r, w: jnp.broadcast_to(r, w.shape).astype(w.dtype),
        restart, state["workers"])
    return {"theta_g": theta_g, "delta": delta, "workers": workers}


def _make_local_tau(train_step: Callable, lr, rng):
    """tau local steps for one worker, scanned; ``rng`` (when given) is
    that worker's block key, folded per tau index so every microbatch
    in the block sees a distinct stream."""
    from repro.utils.introspect import takes_rng as _takes
    takes_rng = _takes(train_step)

    def local_tau(params, opt_state, bt, wkey):
        def one(carry, xs):
            p, o = carry
            b, ti = xs
            if takes_rng and wkey is not None:
                p, o, m = train_step(p, o, b, lr,
                                     rng=jax.random.fold_in(wkey, ti))
            else:
                p, o, m = train_step(p, o, b, lr)
            return (p, o), m

        tau = jax.tree_util.tree_leaves(bt)[0].shape[0]
        (params, opt_state), ms = jax.lax.scan(
            one, (params, opt_state), (bt, jnp.arange(tau)))
        return params, opt_state, ms

    if rng is None:
        return lambda p, o, bt: local_tau(p, o, bt, None)
    return local_tau


def make_bmuf_block_step(train_step: Callable, cfg: BMUFConfig):
    """One *block*: tau vmapped local steps + the sync, jittable.

    train_step(params, opt_state, batch, lr[, rng]) -> (params,
    opt_state, metrics) with lr a traced scalar — one compile serves
    every LR-schedule phase.  batches: pytree with leading dims
    (tau, W, ...).  ``rng`` (optional trailing argument of the returned
    block) is a per-block key folded per (worker, tau-step) and threaded
    into steps that declare it — legacy 4-argument calls are unchanged.
    ``active`` (optional (W,) 0/1 mask) drops dead lanes from the block
    average: their local steps still run (vmap lanes are free and keep
    shapes static) but contribute nothing to the sync.
    """
    def block(state, opt_states, batches, lr, rng=None, active=None):
        local_tau = _make_local_tau(train_step, lr, rng)
        if rng is None:
            workers, opt_states, metrics = jax.vmap(
                local_tau, in_axes=(0, 0, 1))(state["workers"], opt_states,
                                              batches)
        else:
            wkeys = jax.vmap(lambda i: jax.random.fold_in(rng, i))(
                jnp.arange(cfg.n_workers))
            workers, opt_states, metrics = jax.vmap(
                local_tau, in_axes=(0, 0, 1, 0))(state["workers"],
                                                 opt_states, batches, wkeys)
        state = dict(state, workers=workers)
        state = block_sync(state, cfg, active=active)
        return state, opt_states, metrics

    return block


# ----------------------------------------------------------- shard_map path

def make_sharded_bmuf_block_step(train_step: Callable, cfg: BMUFConfig,
                                 mesh, worker_axes=("data",)):
    """Production BMUF: worker dim sharded over `worker_axes` of `mesh`.

    Inside shard_map each shard holds W/|axes| worker replicas; local steps
    are collective-free, the sync is a single pmean over the worker axes.
    Model-parallel sharding *within* a worker stays on the 'model' axis and
    is handled by the step's own pjit partitioning (params enter with their
    usual 2D specs plus the leading worker dim).
    """
    from jax.sharding import PartitionSpec as P

    ax = worker_axes if len(worker_axes) > 1 else worker_axes[0]

    from repro.utils.introspect import takes_rng as _takes
    takes_rng = _takes(train_step)

    def block(state, opt_states, batches, lr, rng=None, active=None):
        have_rng = rng is not None
        have_act = active is not None

        def shard_body(workers, opt_states, batches, lr, theta_g, delta,
                       *extra):
            wkey_data = extra[0] if have_rng else None
            act = extra[int(have_rng)] if have_act else None
            def local_tau(params, opt_state, bt, wkd):
                def one(carry, xs):
                    p, o = carry
                    b, ti = xs
                    if takes_rng and wkd is not None:
                        k = jax.random.fold_in(
                            jax.random.wrap_key_data(wkd), ti)
                        p, o, m = train_step(p, o, b, lr, rng=k)
                    else:
                        p, o, m = train_step(p, o, b, lr)
                    return (p, o), m
                tau = jax.tree_util.tree_leaves(bt)[0].shape[0]
                (params, opt_state), ms = jax.lax.scan(
                    one, (params, opt_state), (bt, jnp.arange(tau)))
                return params, opt_state, ms

            if wkey_data is None:
                workers, opt_states, metrics = jax.vmap(
                    lambda p, o, bt: local_tau(p, o, bt, None),
                    in_axes=(0, 0, 1))(workers, opt_states, batches)
            else:
                workers, opt_states, metrics = jax.vmap(
                    local_tau, in_axes=(0, 0, 1, 0))(
                        workers, opt_states, batches, wkey_data)
            # block sync: mean over the local W slice, then over the axis.
            # With a mask: psum of masked local sums / psum'd live count —
            # each shard contributes only its live lanes.
            if act is None:
                def wmean(w):
                    local = jnp.mean(w.astype(jnp.float32), axis=0)
                    return jax.lax.pmean(local, ax)
            else:
                a = act.astype(jnp.float32)
                denom = jnp.maximum(jax.lax.psum(jnp.sum(a), ax), 1.0)

                def wmean(w):
                    aw = a.reshape((-1,) + (1,) * (w.ndim - 1))
                    s = jnp.sum(w.astype(jnp.float32) * aw, axis=0)
                    return jax.lax.psum(s, ax) / denom
            wbar = tmap(wmean, workers)
            g = tmap(lambda wb, tg: wb - tg.astype(jnp.float32), wbar,
                     theta_g)
            new_delta = tmap(
                lambda d, g_: cfg.block_momentum * d + cfg.block_lr * g_,
                delta, g)
            new_theta = tmap(
                lambda tg, d: (tg.astype(jnp.float32) + d).astype(tg.dtype),
                theta_g, new_delta)
            restart = tmap(
                lambda tg, d: (tg.astype(jnp.float32)
                               + (cfg.block_momentum * d if cfg.nesterov
                                  else 0.0)).astype(tg.dtype),
                new_theta, new_delta)
            workers = tmap(lambda r, w: jnp.broadcast_to(r, w.shape)
                           .astype(w.dtype), restart, workers)
            return workers, opt_states, metrics, new_theta, new_delta

        wspec = P(ax)       # leading worker dim sharded
        rspec = P()         # theta_g / delta / lr replicated
        in_specs = [wspec, wspec, P(None, ax), rspec, rspec, rspec]
        args = [state["workers"], opt_states, batches,
                jnp.asarray(lr, jnp.float32), state["theta_g"],
                state["delta"]]
        if have_rng:
            # per-worker keys are folded OUTSIDE shard_map with the
            # *global* worker index, so the sharded path stays bitwise
            # equal to the vmap path; raw key data crosses the shard_map
            # boundary (uint32 — extended key dtypes and sharding specs
            # don't mix on every jax version) and is re-wrapped inside
            wkd = jax.vmap(lambda i: jax.random.key_data(
                jax.random.fold_in(rng, i)))(jnp.arange(cfg.n_workers))
            in_specs.append(wspec)
            args.append(wkd)
        if have_act:
            in_specs.append(wspec)
            args.append(jnp.asarray(active, jnp.float32))
        fn = jax.shard_map(
            shard_body, mesh=mesh,
            in_specs=tuple(in_specs),
            out_specs=(wspec, wspec, P(None, ax), rspec, rspec),
            check_vma=False)
        workers, opt_states, metrics, theta_g, delta = fn(*args)
        return ({"theta_g": theta_g, "delta": delta, "workers": workers},
                opt_states, metrics)

    return block
