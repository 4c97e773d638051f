"""DistributedStrategy: how one optimizer update is computed.

The Trainer treats every trainer in the paper as the same loop; the
strategy is the only part that differs, and it is a constructor argument
instead of a forked code path:

  Local          — single-worker SGD/Adam (baseline CE, teacher, smoke)
  BMUFShardMap   — blockwise model-update filtering (paper §3.5's 64-GPU
                   trainer): worker replicas on a leading W dim sharded
                   over mesh axes, one device holding them all on a
                   one-device mesh (distributed/bmuf.py)
  GTCShardMap    — Strom threshold-compressed SGD with error feedback
                   (paper §2/§3.4's 16-GPU trainer; works with any loss,
                   including sMBR): per-worker residuals on the sharded
                   worker axis, int8-packed wire psum; W=1 on a
                   one-device mesh is the single-worker form
                   (distributed/gtc.py)

A strategy exposes:

  microbatches          how many source batches one update consumes
                        (1 for Local; W for GTC; tau*W for BMUF)
  n_workers             the *current* worker membership W — a runtime
                        value, not a construction-time constant
  stack(group)          fold that many batches into the update's input
  places_block          (optional, default False) True when ``stack``
                        lays the block out in the update's own input
                        sharding, so its dispatch moves no data
  init_opt(params)      optimizer state (worker-stacked for BMUF)
  init_state(params)    strategy-private state carried in TrainState
  make_update(loss_fn)  (TrainState, batch, lr) -> (TrainState, metrics)
                        — pure and jittable, lr a traced scalar so one
                        compile serves every LR-schedule phase
  resize(state, W_new)  re-partition W-stacked state onto a new
                        membership (elastic join/leave, cross-W resume);
                        returns the adjusted TrainState and retunes the
                        strategy so subsequent make_update calls build
                        W_new-shaped executables
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, List, Protocol, runtime_checkable

import jax
import jax.numpy as jnp

from repro.distributed import bmuf as bmuf_lib
from repro.distributed import gtc as gtc_lib
from repro.optim import (adam_init, adam_update, clip_by_global_norm,
                         momentum_init, momentum_update)
from repro.train.state import TrainState, restack_workers
from repro.utils.introspect import takes_rng

tmap = jax.tree_util.tree_map


def loss_takes_rng(loss_fn: Callable) -> bool:
    """A loss opts into stochasticity by declaring an ``rng`` parameter:
    loss_fn(params, batch, rng=key) -> (loss, metrics).  Two-argument
    losses stay deterministic and are called exactly as before."""
    return takes_rng(loss_fn)


def call_loss(loss_fn: Callable, params, batch, rng=None):
    """Dispatch on the loss's arity; a stochastic loss with no key gets
    a fixed one (the deterministic legacy behavior, e.g. direct step
    calls outside the Trainer)."""
    if loss_takes_rng(loss_fn):
        return loss_fn(params, batch,
                       rng=jax.random.key(0) if rng is None else rng)
    return loss_fn(params, batch)


def make_sgd_step(loss_fn: Callable, *, optimizer: str = "momentum",
                  clip: float = 1.0):
    """The shared local step: grad -> clip -> optimizer, lr traced.

    loss_fn(params, batch[, rng]) -> (loss, metrics).  Returns
    step(params, opt_state, batch, lr, rng=None) -> (params, opt_state,
    metrics), compiled once per batch shape regardless of how lr
    changes; ``rng`` (when given) is the per-update key the Trainer
    folds from TrainState — threaded into losses that declare it.
    """
    upd = momentum_update if optimizer == "momentum" else adam_update

    def step(params, opt_state, batch, lr, rng=None):
        (_, metrics), grads = jax.value_and_grad(
            lambda p, b: call_loss(loss_fn, p, b, rng),
            has_aux=True)(params, batch)
        if clip:
            grads, gn = clip_by_global_norm(grads, clip)
            metrics["grad_norm"] = gn
        params, opt_state = upd(params, grads, opt_state, lr=lr)
        return params, opt_state, metrics

    return step


def init_opt(params, optimizer: str = "momentum"):
    return (momentum_init if optimizer == "momentum" else adam_init)(params)


@runtime_checkable
class DistributedStrategy(Protocol):
    microbatches: int
    n_workers: int

    def init_opt(self, params) -> Any: ...
    def init_state(self, params) -> Any: ...
    def stack(self, group: List[dict]) -> Any: ...
    def make_update(self, loss_fn: Callable) -> Callable: ...
    def resize(self, state: "TrainState", w_new: int) -> "TrainState": ...


class Local:
    """Plain single-worker training — the degenerate strategy."""

    microbatches = 1
    n_workers = 1

    def __init__(self, *, optimizer: str = "momentum", clip: float = 1.0):
        self.optimizer = optimizer
        self.clip = clip

    def resize(self, state: TrainState, w_new: int) -> TrainState:
        """The only membership Local can express is W=1: any other
        target is a caller error, not something to silently absorb."""
        if w_new != 1:
            raise ValueError(
                f"Local is single-worker; cannot resize to W={w_new}")
        return state

    def init_opt(self, params):
        return init_opt(params, self.optimizer)

    def init_state(self, params):
        return {}

    def stack(self, group):
        return group[0]

    def make_update(self, loss_fn):
        step = make_sgd_step(loss_fn, optimizer=self.optimizer,
                             clip=self.clip)

        def update(state: TrainState, batch, lr):
            # per-update folding: the carried key is the stream root and
            # never advances; fold(root, step) is unique per update and
            # exact under mid-stream resume (step is checkpointed)
            rng = jax.random.fold_in(state.rng, state.step)
            params, opt, metrics = step(state.params, state.opt_state,
                                        batch, lr, rng)
            return state.replace(params=params, opt_state=opt,
                                 step=state.step + 1), metrics

        return update


def _worker_spec(mesh, worker_axes):
    """PartitionSpec of a leading W dim sharded over ``worker_axes``."""
    from jax.sharding import PartitionSpec as P
    # a worker axis of size 1 canonicalizes to replicated under GSPMD;
    # placing it that way keeps first-call == steady-state
    if all(mesh.shape[a] == 1 for a in worker_axes):
        return P()
    return P(worker_axes if len(worker_axes) > 1 else worker_axes[0])


def _mesh_shardings(mesh, worker_axes):
    """(replicated, worker-sharded) NamedShardings on ``mesh``."""
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P
    return (NamedSharding(mesh, P()),
            NamedSharding(mesh, _worker_spec(mesh, worker_axes)))


class GTCShardMap:
    """Multi-worker GTC: the worker axis sharded over mesh axes.

    The paper's 16-GPU sequence trainer inside the unified Trainer:
    each update consumes ``n_workers`` microbatches (one per worker,
    stacked on a leading W dim and sharded over the mesh), every worker
    compresses its clipped grads against its own carried error-feedback
    residual (``TrainState.strategy_state`` — per-worker, W-stacked,
    even at W=1), and the wire is ``gtc_lib.wire_pack`` /
    ``wire_unpack``: int8-packed sends, integer accumulation (int8-exact
    to 127 workers, int32 beyond), one psum per leaf.  Params and
    optimizer state stay replicated — synchronous SGD, every worker
    applies the same averaged update.

    W=1 on a one-device mesh is the single-worker trainer: its wire is a
    pack/unpack round-trip, bitwise identity on the ternary sends.
    Stochastic losses get per-(update, worker) folded keys (global
    worker index, folded outside the shard_map), matching the BMUF
    folding scheme.
    """

    def __init__(self, cfg: gtc_lib.GTCConfig, mesh, *,
                 worker_axes=("data",), optimizer: str = "momentum",
                 clip: float = 1.0):
        self.cfg = cfg
        self.mesh = mesh
        self.worker_axes = worker_axes
        self.optimizer = optimizer
        self.clip = clip

    @property
    def microbatches(self) -> int:
        return self.cfg.n_workers

    @property
    def n_workers(self) -> int:
        return self.cfg.n_workers

    def init_opt(self, params):
        return init_opt(params, self.optimizer)

    def init_state(self, params):
        return gtc_lib.gtc_init(params, self.cfg)

    def resize(self, state: TrainState, w_new: int) -> TrainState:
        """Re-partition the per-worker error-feedback residuals onto a
        new membership.  fold=True: a dropped worker's unshipped error
        mass is scatter-added onto a survivor, a joiner starts with zero
        residual — both sum-preserving, so the conservation invariant
        (sum of sends + final residuals == sum of grads) holds across
        the resize; pinned in tests.  The mesh is rebuilt for the new W
        when this strategy owns a plain 1-axis worker mesh."""
        if w_new == self.cfg.n_workers:
            return state
        self.cfg = dataclasses.replace(self.cfg, n_workers=w_new)
        if len(self.worker_axes) == 1:
            from repro.runtime.cluster import worker_mesh
            self.mesh = worker_mesh(w_new, axis=self.worker_axes[0])
        # re-placed on the (possibly narrower) new mesh: the restacked
        # residual still lives on the old mesh's devices
        return self.place(state.replace(strategy_state=restack_workers(
            state.strategy_state, w_new, fold=True)))

    def place(self, state: TrainState) -> TrainState:
        """Lay a (fresh or resumed) TrainState out on the mesh the way
        the sharded step returns it — params/opt replicated, per-worker
        residuals sharded over the worker axis — so the first update
        compiles the same executable as every later one (the Trainer
        calls this from init_state and after a resume load)."""
        rep, wrk = _mesh_shardings(self.mesh, self.worker_axes)
        put = jax.device_put
        return state.replace(
            params=put(state.params, rep),
            opt_state=put(state.opt_state, rep),
            strategy_state=put(state.strategy_state, wrk),
            step=put(state.step, rep), rng=put(state.rng, rep))

    def stack(self, group):
        return tmap(lambda *xs: jnp.stack([jnp.asarray(x) for x in xs]),
                    *group)

    def _grad_transform(self):
        clip = self.clip
        if not clip:
            return None

        def transform(grads):
            grads, gn = clip_by_global_norm(grads, clip)
            return grads, {"grad_norm": gn}

        return transform

    def make_update(self, loss_fn):
        upd = momentum_update if self.optimizer == "momentum" \
            else adam_update
        step = gtc_lib.make_sharded_gtc_train_step(
            loss_fn, upd, self.cfg, self.mesh,
            worker_axes=self.worker_axes,
            grad_transform=self._grad_transform())

        _, wrk = _mesh_shardings(self.mesh, self.worker_axes)

        def update(state: TrainState, batches, lr):
            rng = jax.random.fold_in(state.rng, state.step)
            params, opt, gstate, ms = step(
                state.params, state.opt_state, state.strategy_state,
                batches, lr, rng)
            # pin the residual's output sharding to the worker spec: on
            # a 1-axis-size mesh GSPMD would otherwise canonicalize it
            # to replicated, and the next call would miss the jit cache
            gstate = tmap(
                lambda r: jax.lax.with_sharding_constraint(r, wrk), gstate)
            # metrics arrive (W,)-shaped from the sharded worker slice
            metrics = tmap(jnp.mean, ms)
            return state.replace(params=params, opt_state=opt,
                                 strategy_state=gstate,
                                 step=state.step + 1), metrics

        return update


@functools.partial(jax.jit, static_argnums=1)
def _stack_local(parts, lead):
    """One device's share of a block: its batches stacked into
    ``lead`` = (tau, workers on the device) leading dims, on the device
    that holds them."""
    return jnp.stack(parts).reshape(lead + parts[0].shape)


class BMUFShardMap:
    """BMUF with the worker dim sharded over mesh axes; a one-device mesh
    holds every worker."""

    places_block = True

    def __init__(self, cfg: bmuf_lib.BMUFConfig, mesh, *,
                 worker_axes=("data",), optimizer: str = "momentum",
                 clip: float = 1.0):
        self.cfg = cfg
        self.mesh = mesh
        self.worker_axes = worker_axes
        self.optimizer = optimizer
        self.clip = clip

    @property
    def microbatches(self) -> int:
        return self.cfg.block_steps * self.cfg.n_workers

    @property
    def n_workers(self) -> int:
        return self.cfg.n_workers

    def resize(self, state: TrainState, w_new: int) -> TrainState:
        """Re-stack worker replicas + per-worker optimizer state onto a
        new membership.  Safe at block boundaries (the only place the
        Trainer calls it): the Nesterov restart has just broadcast
        identical params to every lane, so shrink keeps the first W_new
        replicas and grow warm-starts joiners from lane 0 — both exact.
        The block-momentum ``delta`` is global and carries unchanged,
        which is why a shrink-mid-run matches a fresh smaller-W run
        only to float32-ULP (the momentum history differs from a
        cold start) — pinned in tests.  The mesh is rebuilt for the new
        W when this strategy owns a plain 1-axis worker mesh."""
        if w_new == self.cfg.n_workers:
            return state
        self.cfg = dataclasses.replace(self.cfg, n_workers=w_new)
        ss = dict(state.strategy_state)
        ss["workers"] = restack_workers(ss["workers"], w_new)
        if len(self.worker_axes) == 1:
            from repro.runtime.cluster import worker_mesh
            self.mesh = worker_mesh(w_new, axis=self.worker_axes[0])
        return self.place(state.replace(
            opt_state=restack_workers(state.opt_state, w_new),
            strategy_state=ss))

    def init_opt(self, params):
        one = init_opt(params, self.optimizer)
        return tmap(lambda x: jnp.broadcast_to(
            x, (self.cfg.n_workers,) + x.shape).copy(), one)

    def init_state(self, params):
        st = bmuf_lib.bmuf_init(params, self.cfg)
        return {"delta": st["delta"], "workers": st["workers"]}

    def place(self, state: TrainState) -> TrainState:
        """Worker replicas and per-worker optimizer state sharded over
        the worker axis, the global params and block momentum
        replicated — one replica per device when W equals the device
        count."""
        rep, wrk = _mesh_shardings(self.mesh, self.worker_axes)
        put = jax.device_put
        ss = state.strategy_state
        return state.replace(
            params=put(state.params, rep),
            opt_state=put(state.opt_state, wrk),
            strategy_state={"delta": put(ss["delta"], rep),
                            "workers": put(ss["workers"], wrk)},
            step=put(state.step, rep), rng=put(state.rng, rep))

    def stack(self, group):
        """The block in the update's own input sharding, (tau, W, ...)
        with W over the worker axes of the mesh held now (after a
        ``resize``, the new W's): batch j is local step j // W of worker
        j % W.  Each device is sent only its own workers' batches,
        straight from where they are (host memory, or another device for
        a ``jax.Array``), and stacks them itself; nothing goes through the default device, so the
        update's dispatch has nothing to reshard, and nothing waits on
        the device."""
        from jax.sharding import NamedSharding
        from jax.sharding import PartitionSpec as P
        tau, w = self.cfg.block_steps, self.cfg.n_workers
        sharding = NamedSharding(self.mesh, P(
            None, *_worker_spec(self.mesh, self.worker_axes)))

        def lay_out(*xs):
            shape = (tau, w) + tuple(jnp.shape(xs[0]))
            shards = []
            for dev, idx in sharding.addressable_devices_indices_map(
                    shape).items():
                mine = range(w)[idx[1]]
                parts = jax.device_put(
                    [xs[s * w + k] for s in range(tau) for k in mine], dev)
                shards.append(_stack_local(parts, (tau, len(mine))))
            return jax.make_array_from_single_device_arrays(
                shape, sharding, shards)

        return tmap(lay_out, *group)

    def make_update(self, loss_fn):
        step = make_sgd_step(loss_fn, optimizer=self.optimizer,
                             clip=self.clip)
        block = bmuf_lib.make_sharded_bmuf_block_step(
            step, self.cfg, self.mesh, worker_axes=self.worker_axes)

        def update(state: TrainState, batches, lr):
            rng = jax.random.fold_in(state.rng, state.step)
            bstate = {"theta_g": state.params, **state.strategy_state}
            bstate, opts, ms = block(bstate, state.opt_state, batches, lr,
                                     rng)
            # metrics arrive (W, tau)-shaped from the vmapped scan
            metrics = tmap(jnp.mean, ms)
            return state.replace(
                params=bstate["theta_g"], opt_state=opts,
                strategy_state={"delta": bstate["delta"],
                                "workers": bstate["workers"]},
                step=state.step + 1), metrics

        return update
