"""Closed-loop bulk target generation: the teacher labels chunked batches
into a ``LogitStoreV2`` through ``pipeline.generate_sharded``.

Traffic parameters: ``utterances`` (the length distribution),
``chunk_frames``, ``batch_chunks``, ``pool_batches`` (batches made in
set-up and cycled), ``group_batches`` (batches per ``generate_sharded``
call), ``k`` (targets per frame), ``sample_rows`` (rows compared with the
reference after the window).

End-to-end: ``gen_frames_per_s``, valid frames whose top-k targets the
store committed in the window, over the window.  After the window the
store's live shards are read back, and a sample of their rows, drawn from
the seed, is compared with the reference forward + top-k.
"""
from __future__ import annotations

import os
import time

import jax
import numpy as np

from bench import data, ref_lstm_am as ref
from bench.emission import gaps, reference_logits
from bench.program import model_config


class Driver:
    def __init__(self, run):
        self.run = run
        self.cfg = run.cell.config
        self.tf = run.cell.traffic

    # ------------------------------------------------------------ set-up

    def setup(self):
        from repro.core.teacher import TeacherRunner
        from repro.store import LogitStoreV2
        tf, cfg = self.tf, self.cfg
        with jax.default_device(self.run.devices[0]):
            self.params = ref.init_params(cfg, self.run.seed)
        self.pool = data.chunk_pool(
            tf, self.run.seed, batch_chunks=tf["batch_chunks"],
            chunk_frames=tf["chunk_frames"], n_batches=tf["pool_batches"],
            feat_dim=cfg["feat_dim"])
        self.valid = [int(b["mask"].sum()) for b in self.pool]
        self.runner = TeacherRunner(model_config(cfg), self.params,
                                    k=tf["k"])
        self.store = LogitStoreV2(os.path.join(self.run.out_dir, "store"),
                                  k=tf["k"], vocab=cfg["n_senones"])
        self.ledger = os.path.join(self.run.out_dir, "gen_ledger.json")
        self.next = 0
        self.last_group = None
        # warm-up: one call of the window's own shape compiles the
        # forward + top-k and exercises the store and ledger
        self._call(self._group())

    def _group(self):
        g = self.tf["group_batches"]
        idx = [(self.next + i) % len(self.pool) for i in range(g)]
        self.next = (self.next + g) % len(self.pool)
        return idx

    def _call(self, idx):
        from repro.pipeline import generate_sharded
        with self.run.span("generate_sharded"):
            rep = generate_sharded(lambda w: self.runner,
                                   [self.pool[i] for i in idx], self.store,
                                   n_workers=1, ledger_path=self.ledger)
        if rep["n_written"] != len(idx):
            raise RuntimeError(f"generate_sharded wrote {rep}, want "
                               f"{len(idx)} shards")
        self.last_group = idx

    # ------------------------------------------------------------ window

    def window(self, seconds: float) -> dict:
        frames = calls = 0
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < seconds:
            idx = self._group()
            self._call(idx)
            frames += sum(self.valid[i] for i in idx)
            calls += 1
        dt = time.perf_counter() - t0
        n = calls * self.tf["group_batches"]
        return {"e2e": {"gen_frames_per_s": frames / dt},
                "attempted": n, "failed": 0,
                "frames": frames, "window_s": dt, "batches": n,
                "emit_rows": n * self.tf["batch_chunks"]
                * self.tf["chunk_frames"],
                "context": {"window": f"{n} batches, {frames} valid frames "
                                      f"in {dt:.3f} s"}}

    def release(self):
        del self.runner, self.params

    # ------------------------------------------------------------- check

    def stored(self):
        """The store's live shards of the last call, read back: rows of
        (vals, idx) and the batch rows they belong to."""
        vals, idx = [], []
        for sid, b in enumerate(self.last_group):
            v, i = self.store.read_shard(sid)
            vals.append(np.asarray(v, np.float32))
            idx.append(np.asarray(i))
        return np.concatenate(vals), np.concatenate(idx)

    def sample_rows(self):
        n = len(self.last_group) * self.tf["batch_chunks"]
        r = data.rng(self.run.seed, 7)
        return np.sort(r.choice(n, min(self.tf["sample_rows"], n),
                                replace=False))

    def inputs(self, rows):
        feats = np.concatenate([self.pool[i]["feats"]
                                for i in self.last_group])[rows]
        mask = np.concatenate([self.pool[i]["mask"]
                               for i in self.last_group])[rows]
        return feats, mask

    def check(self, program=None) -> dict:
        """{"emission_gap": the widest gap, in units of the frame's logit
        RMS, between a stored value and the reference's logit at the
        stored id shifted by the reference's best}.  ``program`` replaces
        the store's read-back (for the control runs)."""
        rows = self.sample_rows()
        feats, mask = self.inputs(rows)
        if program is None:
            vals, idx = self.stored()
            vals, idx = vals[rows], idx[rows]
        else:
            vals, idx = program(feats, mask)
        if not hasattr(self, "_ref"):
            self._ref = reference_logits(self.cfg, self.run.seed, feats,
                                         mask, self.run.devices[0])
        ref_lg = self._ref
        gap = gaps(vals, idx, ref_lg, mask)
        return {"emission_gap": gap}
