"""Closed-loop bulk target generation by a token-LM teacher: the teacher
labels packed token sequences into a ``LogitStoreV2`` through
``pipeline.generate_sharded``, one store shard per batch.

Traffic parameters: ``tokens`` (the id distribution), ``seq_len``,
``batch_seqs``, ``pool_batches`` (batches made in set-up and cycled),
``group_batches`` (batches per ``generate_sharded`` call), ``k`` (targets
per position), ``sample_seqs`` and ``sample_positions`` (compared with the
reference after the window).

End-to-end: ``gen_frames_per_s``, positions whose top-k targets the store
committed in the window, over the window (a frame here is one labelled
token position).  The program's ``expert_load`` of each call is kept for
the per-layer metric.  After the window ``sample_seqs`` of the last
call's sequences, drawn from the seed, and ``sample_positions`` of their
positions are read back from the store and compared with the reference
run over those whole sequences.
"""
from __future__ import annotations

import gc
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from bench import data, lm, ref_deepseek_v2 as ref, ref_lstm_am


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
        mcfg = lm.model_config(cfg)       # a program without the arch
        with jax.default_device(self.run.devices[0]):    # stops here
            self.params = ref.init_params(cfg, self.run.seed)
        self.pool = lm.token_pool(tf, self.run.seed, cfg["vocab_size"])
        self.per_batch = tf["batch_seqs"] * tf["seq_len"]
        self.runner = TeacherRunner(mcfg, self.params, k=tf["k"])
        self.store = LogitStoreV2(os.path.join(self.run.out_dir, "store"),
                                  k=tf["k"], vocab=cfg["vocab_size"])
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
        return rep

    # ------------------------------------------------------------ window

    def window(self, seconds: float) -> dict:
        positions = calls = 0
        loads = []
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < seconds:
            idx = self._group()
            rep = self._call(idx)
            if "expert_load" in rep:
                loads.append(max(rep["expert_load"]))
            positions += len(idx) * self.per_batch
            calls += 1
        dt = time.perf_counter() - t0
        n = calls * self.tf["group_batches"]
        out = {"e2e": {"gen_frames_per_s": positions / dt},
               "attempted": n, "failed": 0,
               "frames": positions, "window_s": dt, "batches": n,
               "emit_rows": positions,
               "context": {"window": f"{n} batches, {positions} positions "
                                     f"in {dt:.3f} s"}}
        if loads:
            out["expert_loads"] = loads
            out["context"]["expert_load"] = (
                f"worst MoE layer per call: {min(loads):.3f}-"
                f"{max(loads):.3f}")
        return out

    def release(self):
        # the engine and its jitted methods hold each other: collect the
        # cycle so that the weights leave the device before the reference
        # makes its own
        del self.runner, self.params
        gc.collect()

    # ------------------------------------------------------------- check

    def sample(self):
        """(sequences (n, S) of the last call, flat positions into them)."""
        tf = self.tf
        seqs = np.concatenate([self.pool[i]["tokens"]
                               for i in self.last_group])
        r = data.rng(self.run.seed, 7)
        pick = np.sort(r.choice(len(seqs), tf["sample_seqs"], replace=False))
        n = tf["sample_seqs"] * tf["seq_len"]
        pos = np.sort(r.choice(n, min(tf["sample_positions"], n),
                               replace=False))
        return pick, seqs[pick], pos

    def stored(self, pick, pos):
        """The store's (vals, idx) at ``pos`` of the sequences ``pick``."""
        b, s = self.tf["batch_seqs"], self.tf["seq_len"]
        vals, idx = [], []
        for j in pick:
            v, i = self.store.read_shard(int(j) // b)
            vals.append(np.asarray(v[int(j) % b], np.float32))
            idx.append(np.asarray(i[int(j) % b]))
        return (np.concatenate(vals)[pos], np.concatenate(idx)[pos])

    def check(self, program=None) -> dict:
        """{"emission_gap": the widest gap, in units of the position's
        logit RMS, between a stored value and the reference's logit at the
        stored id shifted by the reference's best; "emission_gap_p99": the
        99th percentile over positions of each position's widest gap}.

        The percentile is there because bf16 inference flips a near-tied
        route (a token's 6th against its 7th expert) at a few positions,
        and those few set the maximum; a fault that moves many positions
        a little moves the percentile.  ``program`` (seqs, pos) -> (vals,
        idx) replaces the store's read-back (for the control runs)."""
        pick, seqs, pos = self.sample()
        if program is None:
            vals, idx = self.stored(pick, pos)
        else:
            vals, idx = program(seqs, pos)
        if not hasattr(self, "_ref"):
            self._ref = lm.reference_logits(self.cfg, self.run.seed, seqs,
                                            pos, self.run.devices[0])
        per_pos = np.asarray(ref_lstm_am.emission_gap(
            jnp.asarray(vals), jnp.asarray(idx),
            jnp.asarray(self._ref))).max(axis=-1)
        return {"emission_gap": float(per_pos.max()),
                "emission_gap_p99": float(np.quantile(per_pos, 0.99))}
