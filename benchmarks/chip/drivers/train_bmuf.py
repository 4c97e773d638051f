"""Closed-loop BMUF training of the student from the logit store, one
worker per chip: ``Trainer.fit`` with ``BMUFShardMap`` over
``runtime.cluster.worker_mesh``, the paper's blockwise model-update
filtering across its training fleet (arXiv:1904.01624 §3.4).

Traffic parameters: as ``train.py`` per worker (``utterances``,
``chunk_frames``, ``batch_chunks`` rows per local step, ``pool_batches``,
``k``, ``lr``, ``clip``, ``beta`` local Nesterov momentum), and ``bmuf``:
``n_workers``, ``block_steps`` (local steps per block), ``block_momentum``,
``block_lr``, ``nesterov``.

Set-up writes synthetic top-k targets for every pool batch into a
``LogitStoreV2``, builds one ``Trainer`` and runs the first two block
updates (the first compiles) on the pool's first
``2 * n_workers * block_steps`` batches: batch j of a block is local step
j // n_workers of worker j % n_workers.  The window continues with the
same Trainer and state.  End-to-end: ``train_frames_per_s``, valid frames
of every worker's local steps in the block updates the window completed,
over the window.  After the window each of the two blocks' change of the
global parameters is compared with the reference's (each worker's local
steps from ``ref_lstm_am``, its local momentum carried across blocks, the
block average, the block-momentum step and the Nesterov restart), by the
worst leaf, and each block's mean loss with the reference's.  The first
block's block momentum starts at zero; the second is where the momentum
and the restart show.
"""
from __future__ import annotations

import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from bench import checks, data, ref_lstm_am as ref
from bench.program import model_config

BLOCKS = 2
# planted in the reference's arithmetic, in the program's place, to read
# how far each fault lands from the sound program (tests/controls_bmuf.py)
FAULTS = ("no_momentum", "no_restart", "no_exchange", "half_batch")


class Driver:
    def __init__(self, run):
        self.run = run
        self.cfg = run.cell.config
        self.tf = run.cell.traffic
        b = self.tf["bmuf"]
        self.w, self.tau = b["n_workers"], b["block_steps"]
        self.need = self.w * self.tau

    # ------------------------------------------------------------ set-up

    def targets(self, i):
        b = self.pool[i]
        return data.topk_targets(self.run.seed * 1000 + i, b["mask"].shape,
                                 self.tf["k"], self.cfg["n_senones"])

    def setup(self):
        from repro.distributed.bmuf import BMUFConfig
        from repro.launch.steps import make_loss_fn
        from repro.models import build_model
        from repro.runtime.cluster import worker_mesh
        from repro.store import LogitStoreV2
        from repro.train import BMUFShardMap, ListSink, Trainer
        tf, cfg, b = self.tf, self.cfg, self.tf["bmuf"]
        if tf["pool_batches"] < BLOCKS * self.need:
            raise ValueError("pool too small for the compared blocks")
        with jax.default_device(self.run.devices[0]):
            params = ref.init_params(cfg, self.run.seed)
        self.pool = data.chunk_pool(
            tf, self.run.seed, batch_chunks=tf["batch_chunks"],
            chunk_frames=tf["chunk_frames"], n_batches=tf["pool_batches"],
            feat_dim=cfg["feat_dim"])
        self.valid = [int(x["mask"].sum()) for x in self.pool]
        self.store = LogitStoreV2(os.path.join(self.run.out_dir, "store"),
                                  k=tf["k"], vocab=cfg["n_senones"])
        for i, x in enumerate(self.pool):
            vals, idx = self.targets(i)
            self.store.append_shard(i, vals, idx, x["mask"].sum(axis=-1))
        mcfg = model_config(cfg)
        loss = make_loss_fn(build_model(mcfg), mcfg, "distill_topk")
        strategy = BMUFShardMap(
            BMUFConfig(n_workers=self.w, block_steps=self.tau,
                       block_momentum=b["block_momentum"],
                       block_lr=b["block_lr"], nesterov=b["nesterov"]),
            worker_mesh(self.w), clip=tf["clip"])
        self.sink = ListSink()
        self.trainer = Trainer(strategy, {"distill_topk": loss},
                               metrics=self.sink)
        state = self.trainer.init_state(params, seed=self.run.seed)
        norms = jax.jit(lambda a, b: jax.tree_util.tree_map(
            lambda x, y: jnp.sqrt(jnp.sum(jnp.square(x - y))), a, b))
        # the compared blocks: the window's own Trainer, call and feed
        self.change_norms = []
        for blk in range(BLOCKS):
            before = state.params
            state = self.trainer.fit(
                state, self._batches(blk * self.need, (blk + 1) * self.need),
                resume=False)
            self.change_norms.append(jax.device_get(
                norms(state.params, before)))
        self.block_losses = [float(v) for v in self.sink.values("loss")]
        del params, before
        self.trainer.metrics = None
        self.state = state
        self.pos = BLOCKS * self.need % len(self.pool)

    def _batches(self, lo, hi):
        from repro.train import distill_shard_source
        return distill_shard_source(self.pool, self.store, lo, hi,
                                    self.tf["lr"])

    # ------------------------------------------------------------ window

    def window(self, seconds: float) -> dict:
        from repro.train import distill_shard_source
        span = self.run.span
        n_pool = len(self.pool)
        counts = {"blocks": 0, "frames": 0}

        def source():
            while time.perf_counter() - t0 < seconds:
                frames = 0
                for _ in range(self.need):
                    i = self.pos
                    self.pos = (self.pos + 1) % n_pool
                    with span("source_next"):
                        tb = next(distill_shard_source(
                            self.pool, self.store, i, i + 1, self.tf["lr"]))
                    frames += self.valid[i]
                    with span("trainer"):
                        yield tb
                # the Trainer asks again only after the block's update
                counts["frames"] += frames
                counts["blocks"] += 1

        t0 = time.perf_counter()
        state = self.trainer.fit(self.state, source(), resume=False)
        with span("wait_ready"):
            jax.block_until_ready(state.params)
        dt = time.perf_counter() - t0
        self.state = state
        frames, n = counts["frames"], counts["blocks"]
        return {"e2e": {"train_frames_per_s": frames / dt},
                "attempted": n, "failed": 0,
                "frames": frames, "window_s": dt, "updates": n,
                "context": {"window": f"{n} block updates ({n * self.need} "
                                      f"local steps), {frames} valid frames "
                                      f"in {dt:.3f} s"}}

    def release(self):
        del self.state, self.trainer

    # ------------------------------------------------------------- check

    def ref_batch(self, i, half=False):
        b = self.pool[i]
        vals, idx = self.targets(i)
        mask = b["mask"]
        if half:        # the fault: the second half of the rows left out
            mask = mask.copy()
            mask[mask.shape[0] // 2:] = 0
        return {"feats": jnp.asarray(b["feats"]), "mask": jnp.asarray(mask),
                "topk_vals": jnp.asarray(vals), "topk_idx": jnp.asarray(idx)}

    def reference(self, prec="f32", fault=None):
        """The reference's first ``BLOCKS`` blocks: (each block's mean loss
        over its local steps, each block's leaf norms of the global
        parameters' change).  ``fault`` (one of ``FAULTS``) plants a
        fault in the block arithmetic or the feed."""
        tf, cfg, b = self.tf, self.cfg, self.tf["bmuf"]
        eta = 0.0 if fault == "no_momentum" else b["block_momentum"]
        nesterov = b["nesterov"] and fault != "no_restart"
        tmap = jax.tree_util.tree_map
        with jax.default_device(self.run.devices[0]), \
                jax.default_matmul_precision("highest"):
            p0 = ref.init_params(cfg, self.run.seed)
            step = jax.jit(lambda p, m, x, lr: ref.sgd_step(
                p, m, x, lr, cfg, clip=tf["clip"], beta=tf["beta"],
                prec=prec))
            lr = jnp.float32(tf["lr"])
            theta, start = p0, p0
            delta = tmap(jnp.zeros_like, p0)
            mus = [tmap(jnp.zeros_like, p0) for _ in range(self.w)]
            losses, changes = [], []
            for blk in range(BLOCKS):
                workers, block_losses = [], []
                for w in range(self.w):
                    p = start
                    for s in range(self.tau):
                        j = blk * self.need + s * self.w + w
                        p, mus[w], loss = step(p, mus[w], self.ref_batch(
                            j, half=fault == "half_batch"), lr)
                        block_losses.append(float(loss))
                    workers.append(p)
                # block average (each chip its own worker alone when the
                # exchange is left out), block-momentum step, restart
                avg = workers[:1] if fault == "no_exchange" else workers
                g = tmap(lambda t, *ps: sum(ps) / len(ps) - t, theta, *avg)
                delta = tmap(lambda d, g_: eta * d + b["block_lr"] * g_,
                             delta, g)
                new = tmap(lambda t, d: t + d, theta, delta)
                changes.append(jax.device_get(tmap(
                    lambda x, y: jnp.sqrt(jnp.sum(jnp.square(x - y))),
                    new, theta)))
                theta = new
                start = (tmap(lambda t, d: t + eta * d, theta, delta)
                         if nesterov else theta)
                losses.append(float(np.mean(block_losses)))
            return losses, changes

    def check(self, program=None) -> dict:
        """Each block's ``block_loss_gap`` / ``block_change_gap`` (the
        second block's prefixed ``block2_``); ``program`` (losses, change
        norms) replaces the program's readings (for the control and the
        planted faults)."""
        if not hasattr(self, "_ref"):
            self._ref = self.reference("f32")
        r_losses, r_changes = self._ref
        p_losses, p_changes = program or (self.block_losses,
                                          self.change_norms)
        out = {}
        for blk in range(BLOCKS):
            p_change = checks.leaf_dict(p_changes[blk])
            r_change = checks.leaf_dict(r_changes[blk])
            med = float(np.median(list(r_change.values())))
            keep = [k for k, v in r_change.items()
                    if v >= checks.NEGLIGIBLE * med]
            pre = "block_" if blk == 0 else f"block{blk + 1}_"
            out[pre + "loss_gap"] = (abs(p_losses[blk] - r_losses[blk])
                                     / abs(r_losses[blk]))
            out[pre + "change_gap"] = checks.worst_leaf_gap(
                p_change, r_change, keep)
        return out
