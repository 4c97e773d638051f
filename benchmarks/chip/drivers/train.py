"""Closed-loop student training from the logit store through
``Trainer.fit``: the paper's distillation updates on one chip (``Local``,
one BMUF worker's local step).

Traffic parameters: ``utterances``, ``chunk_frames``, ``batch_chunks``
(rows per step), ``pool_batches``, ``k``, ``lr``, ``clip``, ``beta``
(Nesterov momentum) and ``steps_compared``.

Set-up writes synthetic top-k targets for every pool batch into a
``LogitStoreV2``, builds one ``Trainer`` and drives it through its first
``steps_compared`` updates (which compile) on distinct batches read back
through ``distill_shard_source``; the window continues with the same
Trainer and state.  End-to-end: ``train_frames_per_s``, valid frames
consumed by the updates the window completed, over the window, summed
over chips.  After the window the first updates are compared with the
reference's: each update's loss, the first gradient as the optimizer
holds it, and the parameters' change over the compared updates, by the
worst leaf.
"""
from __future__ import annotations

import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from bench import checks, data, ref_lstm_am as ref
from bench.program import model_config


class Driver:
    def __init__(self, run):
        self.run = run
        self.cfg = run.cell.config
        self.tf = run.cell.traffic

    # ------------------------------------------------------------ set-up

    def targets(self, i):
        b = self.pool[i]
        return data.topk_targets(self.run.seed * 1000 + i, b["mask"].shape,
                                 self.tf["k"], self.cfg["n_senones"])

    def setup(self):
        from repro.launch.steps import make_loss_fn
        from repro.models import build_model
        from repro.store import LogitStoreV2
        from repro.train import ListSink, Local, Trainer
        tf, cfg = self.tf, self.cfg
        self.n_cmp = tf["steps_compared"]
        if tf["pool_batches"] < self.n_cmp:
            raise ValueError("pool too small for distinct compared steps")
        with jax.default_device(self.run.devices[0]):
            params = ref.init_params(cfg, self.run.seed)
        self.pool = data.chunk_pool(
            tf, self.run.seed, batch_chunks=tf["batch_chunks"],
            chunk_frames=tf["chunk_frames"], n_batches=tf["pool_batches"],
            feat_dim=cfg["feat_dim"])
        self.valid = [int(b["mask"].sum()) for b in self.pool]
        self.store = LogitStoreV2(os.path.join(self.run.out_dir, "store"),
                                  k=tf["k"], vocab=cfg["n_senones"])
        for i, b in enumerate(self.pool):
            vals, idx = self.targets(i)
            self.store.append_shard(i, vals, idx, b["mask"].sum(axis=-1))
        mcfg = model_config(cfg)
        loss = make_loss_fn(build_model(mcfg), mcfg, "distill_topk")
        self.sink = ListSink()
        self.trainer = Trainer(Local(clip=tf["clip"]), {"distill_topk": loss},
                               metrics=self.sink)
        state = self.trainer.init_state(params, seed=self.run.seed)
        leafnorms = jax.jit(lambda t: jax.tree_util.tree_map(
            lambda x: jnp.sqrt(jnp.sum(jnp.square(x))), t))
        # the compared updates: the window's own Trainer, call and feed
        state = self.trainer.fit(state, self._batches(0, 1), resume=False)
        first = state.opt_state["mu"]
        self.first_norms = jax.device_get(leafnorms(first))
        state = self.trainer.fit(state, self._batches(1, self.n_cmp),
                                 resume=False)
        diff = jax.tree_util.tree_map(lambda a, b: a - b, state.params,
                                      params)
        self.change_norms = jax.device_get(leafnorms(diff))
        self.losses = [float(x) for x in self.sink.values("loss")]
        del params, first, diff
        self.trainer.metrics = None
        self.state = state
        self.pos = self.n_cmp

    def _batches(self, lo, hi):
        from repro.train import distill_shard_source
        return distill_shard_source(self.pool, self.store, lo, hi,
                                    self.tf["lr"])

    # ------------------------------------------------------------ window

    def window(self, seconds: float) -> dict:
        from repro.train import distill_shard_source
        span = self.run.span
        n_pool = len(self.pool)
        counts = {"updates": 0, "frames": 0}

        def source():
            while time.perf_counter() - t0 < seconds:
                i = self.pos
                self.pos = (self.pos + 1) % n_pool
                with span("source_next"):
                    tb = next(distill_shard_source(
                        self.pool, self.store, i, i + 1, self.tf["lr"]))
                counts["frames"] += self.valid[i]
                with span("trainer"):
                    yield tb
                counts["updates"] += 1

        t0 = time.perf_counter()
        state = self.trainer.fit(self.state, source(), resume=False)
        with span("wait_ready"):
            jax.block_until_ready(state.params)
        dt = time.perf_counter() - t0
        self.state = state
        frames, n = counts["frames"], counts["updates"]
        return {"e2e": {"train_frames_per_s": frames / dt},
                "attempted": n, "failed": 0,
                "frames": frames, "window_s": dt, "updates": n,
                "loss_rows": n * self.tf["batch_chunks"]
                * self.tf["chunk_frames"],
                "context": {"window": f"{n} updates, {frames} valid frames "
                                      f"in {dt:.3f} s"}}

    def release(self):
        del self.state, self.trainer

    # ------------------------------------------------------------- check

    def ref_batch(self, i):
        b = self.pool[i]
        vals, idx = self.targets(i)
        return {"feats": jnp.asarray(b["feats"]), "mask": jnp.asarray(b["mask"]),
                "topk_vals": jnp.asarray(vals), "topk_idx": jnp.asarray(idx)}

    def reference(self, prec="f32", fault=None):
        """The reference's first updates: (losses, first-gradient leaf
        norms, change leaf norms).  ``fault="half_batch"`` plants the
        control fault in which each step sees the first half of its
        rows."""
        tf, cfg = self.tf, self.cfg
        with jax.default_device(self.run.devices[0]), \
                jax.default_matmul_precision("highest"):
            p0 = ref.init_params(cfg, self.run.seed)
            step = jax.jit(lambda p, m, b, lr: ref.sgd_step(
                p, m, b, lr, cfg, clip=tf["clip"], beta=tf["beta"],
                prec=prec))
            zeros = lambda: jax.tree_util.tree_map(jnp.zeros_like, p0)

            def batch(i):
                b = self.ref_batch(i)
                if fault == "half_batch":
                    h = b["feats"].shape[0] // 2
                    b = {k: v[:h] for k, v in b.items()}
                return b

            lr = jnp.float32(tf["lr"])
            losses, first = [], None
            p, mu = p0, zeros()
            for s in range(self.n_cmp):
                p, mu, loss = step(p, mu, batch(s), lr)
                losses.append(float(loss))
                if s == 0:
                    first = mu
            change = jax.tree_util.tree_map(lambda a, b: a - b, p, p0)
            norm = lambda t: jax.device_get(jax.tree_util.tree_map(
                lambda x: jnp.sqrt(jnp.sum(x * x)), t))
            return losses, norm(first), norm(change)

    def check(self, program=None) -> dict:
        """{"loss_gap", "first_grad_gap", "change_gap"}; ``program``
        (losses, first norms, change norms) replaces the program's own
        readings (for the control runs)."""
        if not hasattr(self, "_ref"):
            self._ref = self.reference("f32")
        r_losses, r_first, r_change = self._ref
        if program is None:
            program = (self.losses, self.first_norms, self.change_norms)
        p_losses, p_first, p_change = program
        return checks.train_gaps(p_losses, p_first, p_change,
                                 r_losses, r_first, r_change)
