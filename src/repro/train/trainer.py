"""Trainer.fit(): the one loop every stage of the paper's recipe runs.

    trainer = Trainer(strategy, {"ce": loss_fn}, checkpoint=store,
                      ckpt_every=25, metrics=sink)
    state = trainer.init_state(params)
    state = trainer.fit(state, source)

One jitted update per (loss kind x batch shape), with the learning rate
a *traced argument* — an LR schedule sweeping a hundred phases reuses
the same executable (the seed pipeline re-jitted its step on every
phase change).  The strategy decides how many source microbatches one
update consumes (tau*W for BMUF) and what the update does; the source
decides what data arrives with which lr/loss; the Trainer only grooms
batches into blocks, counts, checkpoints, and emits metrics.

Resume: every ``ckpt_every`` updates the full TrainState plus the
consumed-microbatch count goes to the CheckpointStore; ``fit`` with
``resume=True`` (default) reloads the latest state and fast-forwards
the (deterministic) source past the consumed prefix, so a killed stage
continues instead of restarting.

Stochasticity: each update folds the carried TrainState key with the
step counter (strategy-side) and threads the folded key into losses
that declare an ``rng`` parameter — dropout-style losses get a fresh
stream per update, and resume stays bitwise (the fold depends only on
checkpointed state).  LR: ``TrainBatch.lr`` may be a float or an
``optim.schedules.Schedule``; schedules are evaluated at the update
counter on the host and fed through the same traced lr argument.
``prefetch=N`` (constructor or fit kwarg) wraps the source in
``repro.pipeline.PrefetchingSource`` so shard decode + device_put run
ahead of the jitted update.

Elasticity: ``fit(..., membership=...)`` polls a live-worker count at
update (== BMUF block) boundaries; when it changes, ``Trainer.resize``
re-partitions the TrainState through the strategy's ``resize`` hook and
rebuilds the jitted updates for the new W.  Checkpoints record the
membership they were saved at (``meta["n_workers"]``), and resume at a
*different* W re-partitions the loaded state — a W=4 save restarts
cleanly on a W=2 fleet.
"""
from __future__ import annotations

import time
from typing import Callable, Dict, Optional, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import CheckpointStore
from repro.train.data import DataSource, TrainBatch
from repro.train.metrics import MetricsSink
from repro.train.state import TrainState
from repro.train.strategies import DistributedStrategy
from repro.utils.tracing import span

_END = object()


def _shape_sig(data):
    """Hashable (shape, dtype-free) signature of a batch pytree."""
    return tuple(tuple(getattr(l, "shape", ()))
                 for l in jax.tree_util.tree_leaves(data))


class Trainer:
    def __init__(self, strategy: DistributedStrategy,
                 loss_fns: Union[Callable, Dict[str, Callable]], *,
                 checkpoint: Optional[CheckpointStore] = None,
                 ckpt_every: int = 0,
                 metrics: Optional[MetricsSink] = None,
                 prefetch: int = 0):
        self.strategy = strategy
        if callable(loss_fns):
            loss_fns = {"default": loss_fns}
        self._loss_fns = loss_fns
        self._build_updates()
        # membership-change accounting, read by the elastic bench
        self.resize_stats = {"count": 0, "seconds": 0.0}
        self.checkpoint = checkpoint
        self.ckpt_every = ckpt_every
        self.metrics = metrics
        # prefetch > 0: fit() wraps its source in a PrefetchingSource of
        # that depth — decode + device_put run ahead on a host thread so
        # the jitted update never blocks on shard reads (repro.pipeline)
        self.prefetch = prefetch

    def _build_updates(self):
        self.updates = {tag: jax.jit(self.strategy.make_update(fn))
                        for tag, fn in self._loss_fns.items()}

    # ------------------------------------------------------------- state

    def init_state(self, params, *, seed: int = 0) -> TrainState:
        state = TrainState(params=params,
                           opt_state=self.strategy.init_opt(params),
                           strategy_state=self.strategy.init_state(params),
                           step=jnp.zeros((), jnp.int32),
                           rng=jax.random.key(seed))
        return self._place(state)

    def _place(self, state: TrainState) -> TrainState:
        """Strategies that shard their state over a mesh (GTCShardMap)
        lay it out here so the first update hits the same executable as
        the steady state — identity for everything else."""
        place = getattr(self.strategy, "place", None)
        return state if place is None else place(state)

    def resize(self, state: TrainState, w_new: int) -> TrainState:
        """Adopt a new worker membership mid-run: re-partition the
        TrainState through the strategy, rebuild the jitted updates for
        the new W-shaped inputs, and re-place on the (possibly rebuilt)
        mesh.  Called from fit() at update boundaries when a membership
        poll reports a change, and from resume when the checkpoint was
        saved at a different W."""
        if w_new == getattr(self.strategy, "n_workers", w_new):
            return state
        t0 = time.perf_counter()
        state = self.strategy.resize(state, w_new)
        self._build_updates()
        state = self._place(state)
        self.resize_stats["count"] += 1
        self.resize_stats["seconds"] += time.perf_counter() - t0
        return state

    def _save(self, state: TrainState, consumed: int):
        meta = {"consumed": consumed}
        w = getattr(self.strategy, "n_workers", None)
        if w is not None:
            meta["n_workers"] = int(w)
        self.checkpoint.save(int(state.step), state.to_dict(), meta=meta)

    def _try_resume(self, state: TrainState):
        """-> (state, consumed) from the latest checkpoint, or None.

        Cross-W resume: when the checkpoint's saved membership differs
        from the strategy's current W, the load template is first
        resized to the *saved* W (load_tree is strict about shapes),
        then the loaded state is resized back to the current W — so a
        W=4 save resumes on a W=2 fleet with residuals folded
        sum-preservingly and BMUF replicas re-stacked."""
        if self.checkpoint is None:
            return None
        step = self.checkpoint.latest()
        if step is None:
            return None
        meta = self.checkpoint.load_meta(step) or {}
        cur_w = getattr(self.strategy, "n_workers", None)
        saved_w = meta.get("n_workers")
        if (cur_w is not None and saved_w is not None
                and int(saved_w) != int(cur_w)
                and hasattr(self.strategy, "resize")):
            template = self.strategy.resize(state, int(saved_w))
            tree, step = self.checkpoint.load(template.to_dict(), step)
            loaded = TrainState.from_dict(tree)
            return (self.resize(loaded, cur_w),
                    int(meta.get("consumed", 0)))
        tree, step = self.checkpoint.load(state.to_dict(), step)
        return (self._place(TrainState.from_dict(tree)),
                int(meta.get("consumed", 0)))

    # --------------------------------------------------------------- fit

    def fit(self, state: TrainState, source: DataSource, *,
            resume: bool = True,
            max_updates: Optional[int] = None,
            prefetch: Optional[int] = None,
            membership=None) -> TrainState:
        consumed = 0
        if resume:
            loaded = self._try_resume(state)
            if loaded is not None:
                state, consumed = loaded
        if membership is not None:
            state = self._poll_membership(state, membership)
        depth = self.prefetch if prefetch is None else prefetch
        wrapped = None
        if depth:
            from repro.pipeline.prefetch import PrefetchingSource
            if not isinstance(source, PrefetchingSource):
                # skip_put: the resume replay drops the consumed prefix,
                # so the producer must not pay its device transfers
                source = PrefetchingSource(source, depth=depth,
                                           skip_put=consumed)
            wrapped = source
        try:
            return self._fit_loop(state, source, consumed, max_updates,
                                  membership)
        finally:
            if wrapped is not None:         # early exit must not leak the
                wrapped.close()             # producer thread across stages

    def _poll_membership(self, state: TrainState, membership) -> TrainState:
        """One membership check (anything with live_count()); a changed
        live count resizes state + strategy + updates.  The floor is 1:
        an all-dead fleet freezes rather than divides by zero."""
        live = max(1, int(membership.live_count()))
        if live != getattr(self.strategy, "n_workers", live):
            state = self.resize(state, live)
        return state

    def _fit_loop(self, state: TrainState, source, consumed: int,
                  max_updates: Optional[int],
                  membership=None) -> TrainState:
        # step is mirrored on the host (updates are +1 each) so the loop
        # never blocks on the device unless a sink/checkpoint needs to
        step = start_step = int(state.step)
        need = self.strategy.microbatches
        n_seen = 0
        group, gtag, gsig, glr = [], None, None, None
        # spans: ``train.source`` is the wait for the source's next batch
        # (the last one finds it exhausted), ``train.stack`` the block's
        # layout (``placed`` 1 where it is laid out in the update's own
        # input sharding, ``nbytes`` its input bytes), ``train.update``
        # the jitted update's dispatch
        placed = int(getattr(self.strategy, "places_block", False))
        batches = iter(source)
        while True:
            with span("train.source"):
                tb = next(batches, _END)
            if tb is _END:
                break
            n_seen += 1
            if n_seen <= consumed:          # resume: replay + skip
                continue
            # a partial block cannot straddle a loss-kind, batch-shape,
            # or lr boundary; drop it (BMUF block semantics — blocks
            # stack their microbatches, so ragged full-sequence batches
            # only fill blocks with exact shape-mates, and a block never
            # blurs two schedule phases' lrs.  Local and GTC at W=1 never
            # hit this: need == 1 means no block is ever partial).  Schedule
            # objects compare by identity, so one schedule spanning many
            # updates never splits a block.
            sig = _shape_sig(tb.data) if need > 1 else None
            if group and (tb.loss != gtag or sig != gsig
                          or tb.lr != glr):
                group = []
            if not group:
                gtag, gsig, glr = tb.loss, sig, tb.lr
            group.append(tb.data)
            if len(group) < need:
                continue
            if gtag not in self.updates:
                raise KeyError(
                    f"source yielded loss kind {gtag!r} but the Trainer "
                    f"only has {sorted(self.updates)}")
            with span("train.stack", placed=placed,
                      nbytes=sum(getattr(x, "nbytes", 0) for x in
                                 jax.tree_util.tree_leaves(group))):
                batch = self.strategy.stack(group)
            # an LR Schedule is evaluated here, at the update counter, on
            # the host — the update still sees a traced float, so the
            # one-compile-per-(loss kind, shape) property is untouched.
            # Beside a block laid out in the update's own sharding it goes
            # as a host scalar, which the dispatch sends straight to every
            # device the update runs on; elsewhere to the default device,
            # since a host scalar there lets the host queue about twice as
            # many updates ahead on a TPU, each holding its outputs
            lr = glr(step) if callable(glr) else glr
            lr = np.float32(lr) if placed else jnp.asarray(lr, jnp.float32)
            with span("train.update"):
                state, metrics = self.updates[gtag](state, batch, lr)
            group = []
            consumed = n_seen
            step += 1
            if self.metrics is not None:
                host = jax.device_get(metrics)
                self.metrics.emit(step, gtag,
                                  {k: float(v) for k, v in host.items()
                                   if getattr(v, "size", 1) == 1})
            if (self.checkpoint is not None and self.ckpt_every
                    and step % self.ckpt_every == 0):
                self._save(state, consumed)
            if max_updates is not None and step - start_step >= max_updates:
                break
            if membership is not None:
                # update == block boundary: the only membership-safe
                # point (BMUF lanes have just been re-broadcast, GTC
                # residuals are between compressions)
                new = self._poll_membership(state, membership)
                if new is not state:
                    state = new
                    need = self.strategy.microbatches
        return state

    # ------------------------------------------------------------ finish

    def finalize(self, state: TrainState):
        """Mark the run complete: drop the resume checkpoints so a fresh
        invocation of the same stage trains anew (a *killed* run, by
        contrast, still has them and resumes)."""
        if self.checkpoint is not None:
            self.checkpoint.clear()
        return state
