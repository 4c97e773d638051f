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

The math lives once: ``_make_local_tau`` runs the tau local steps of
every worker (a scan per worker, vmapped over the leading W dim) and
``block_sync`` is the worker mean, block momentum, global update and
restart.  ``make_sharded_bmuf_block_step`` is the trainer's step: the W
dim is sharded over the mesh's worker axes (one device holds them all on
a one-device mesh); local steps touch no cross-worker collective (BMUF's
entire point — communication every tau steps instead of every
minibatch), and the block sync's worker mean is one pmean per leaf.
``make_bmuf_block_step`` composes the same two pieces with a plain mean
over W on one device: the reference the step-level tests compare
against.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Callable

import jax
import jax.numpy as jnp

from repro.utils.introspect import takes_rng

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


def active_mean_fn(active, axis_name=None):
    """Worker-mean over live lanes only: ``active`` is a (W,) 0/1 mask.

    Dead lanes contribute nothing to the block average; the divisor is
    the live count (floored at 1 so an all-dead mask degrades to a
    frozen model instead of NaNs).  The block-momentum ``delta`` is
    global, not per-worker, so it needs no masking — it carries
    unchanged across a membership change, which is what the
    shrink-mid-run-vs-fresh-W pin relies on.  ``axis_name`` (inside a
    shard_map) psums the masked sums and the live count over the
    workers held by the other shards.
    """
    a = jnp.asarray(active, jnp.float32)
    total = (lambda x: x) if axis_name is None else \
        (lambda x: jax.lax.psum(x, axis_name))
    denom = jnp.maximum(total(jnp.sum(a)), 1.0)

    def mean_fn(w):
        aw = a.reshape((-1,) + (1,) * (w.ndim - 1))
        return total(jnp.sum(w.astype(jnp.float32) * aw, axis=0)) / denom

    return mean_fn


def block_sync(state, cfg: BMUFConfig, *, mean_fn=None, active=None):
    """One BMUF sync. ``mean_fn`` overrides the worker-mean (the shard_map
    step passes a ``lax.pmean`` closure, or the masked psum of
    ``active_mean_fn``); default = mean over the leading W dim.
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


def _worker_keys(rng, n_workers: int):
    """The block key folded with each *global* worker index, so how the
    workers are laid over devices never changes their streams."""
    return jax.vmap(lambda i: jax.random.fold_in(rng, i))(
        jnp.arange(n_workers))


def _make_local_tau(train_step: Callable, lr):
    """-> run(workers, opt_states, batches, wkeys=None): tau local steps
    for every worker on the leading W dim, one scan per worker, vmapped
    over W.  ``wkeys`` (when given) are the workers' block keys, folded
    per tau index so every microbatch in the block sees a distinct
    stream."""
    wants_rng = takes_rng(train_step)

    def local_tau(params, opt_state, bt, wkey):
        def one(carry, xs):
            p, o = carry
            b, ti = xs
            if wants_rng and wkey is not None:
                p, o, m = train_step(p, o, b, lr,
                                     rng=jax.random.fold_in(wkey, ti))
            else:
                p, o, m = train_step(p, o, b, lr)
            return (p, o), m

        tau = jax.tree_util.tree_leaves(bt)[0].shape[0]
        (params, opt_state), ms = jax.lax.scan(
            one, (params, opt_state), (bt, jnp.arange(tau)))
        return params, opt_state, ms

    def run(workers, opt_states, batches, wkeys=None):
        if wkeys is None:
            return jax.vmap(lambda p, o, bt: local_tau(p, o, bt, None),
                            in_axes=(0, 0, 1))(workers, opt_states, batches)
        return jax.vmap(local_tau, in_axes=(0, 0, 1, 0))(
            workers, opt_states, batches, wkeys)

    return run


def make_bmuf_block_step(train_step: Callable, cfg: BMUFConfig):
    """One *block* with the W dim vmapped on one device: the reference
    that the step-level tests hold ``make_sharded_bmuf_block_step`` to
    (the program trains through the sharded step alone).

    train_step(params, opt_state, batch, lr[, rng]) -> (params,
    opt_state, metrics) with lr a traced scalar — one compile serves
    every LR-schedule phase.  batches: pytree with leading dims
    (tau, W, ...).  ``rng`` (optional trailing argument of the returned
    block) is a per-block key folded per (worker, tau-step) and threaded
    into steps that declare it.  ``active`` (optional (W,) 0/1 mask)
    drops dead lanes from the block average: their local steps still run
    (vmap lanes are free and keep shapes static) but contribute nothing
    to the sync.
    """
    def block(state, opt_states, batches, lr, rng=None, active=None):
        wkeys = None if rng is None else _worker_keys(rng, cfg.n_workers)
        workers, opt_states, metrics = _make_local_tau(train_step, lr)(
            state["workers"], opt_states, batches, wkeys)
        state = block_sync(dict(state, workers=workers), cfg, active=active)
        return state, opt_states, metrics

    return block


def make_sharded_bmuf_block_step(train_step: Callable, cfg: BMUFConfig,
                                 mesh, worker_axes=("data",)):
    """BMUF with the worker dim sharded over `worker_axes` of `mesh`.

    Inside shard_map each shard holds W/|axes| worker replicas; local steps
    are collective-free, the sync is a single pmean over the worker axes
    (a psum of the masked sums when ``active`` is given).  A one-device
    mesh holds every worker.  Model-parallel sharding *within* a worker
    stays on the 'model' axis and is handled by the step's own pjit
    partitioning (params enter with their usual 2D specs plus the leading
    worker dim).
    """
    from jax.sharding import PartitionSpec as P

    ax = worker_axes if len(worker_axes) > 1 else worker_axes[0]

    def block(state, opt_states, batches, lr, rng=None, active=None):
        have_rng = rng is not None
        have_act = active is not None

        def shard_body(workers, opt_states, batches, lr, theta_g, delta,
                       *extra):
            wkeys = jax.random.wrap_key_data(extra[0]) if have_rng else None
            workers, opt_states, metrics = _make_local_tau(train_step, lr)(
                workers, opt_states, batches, wkeys)
            if have_act:
                mean_fn = active_mean_fn(extra[-1], axis_name=ax)
            else:
                mean_fn = lambda w: jax.lax.pmean(
                    jnp.mean(w.astype(jnp.float32), axis=0), ax)
            out = block_sync({"theta_g": theta_g, "delta": delta,
                              "workers": workers}, cfg, mean_fn=mean_fn)
            return (out["workers"], opt_states, metrics, out["theta_g"],
                    out["delta"])

        wspec = P(ax)       # leading worker dim sharded
        rspec = P()         # theta_g / delta / lr replicated
        in_specs = [wspec, wspec, P(None, ax), rspec, rspec, rspec]
        args = [state["workers"], opt_states, batches,
                jnp.asarray(lr, jnp.float32), state["theta_g"],
                state["delta"]]
        if have_rng:
            # raw key data crosses the shard_map boundary (uint32 —
            # extended key dtypes and sharding specs don't mix on every
            # jax version) and is re-wrapped inside
            in_specs.append(wspec)
            args.append(jax.random.key_data(
                _worker_keys(rng, cfg.n_workers)))
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
