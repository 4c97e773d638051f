"""Trainer throughput: steps/sec (and frames/sec) for Local vs BMUF.

The unified Trainer compiles one lr-as-argument update per (loss kind,
batch shape); this records what that buys as a *number*:

  PYTHONPATH=src python benchmarks/train_bench.py
  PYTHONPATH=src python benchmarks/train_bench.py --updates 16 --hidden 128

Both strategies run the same CE workload on the same synthetic corpus
through the same Trainer.fit() loop.  BMUF consumes tau*W microbatches
per update, so the fair comparison is *frames*/sec (each BMUF update
does tau*W local steps of work); steps/sec is reported as the raw
update cadence.  Also recorded: the wall-clock cost of sweeping the
learning rate across every update (re-jit would pay a compile per
distinct lr; the lr-as-argument step must not).
"""
from __future__ import annotations

import argparse
import json
import os
import time

import jax
import numpy as np

from repro.core.ssl_pipeline import PipelineConfig, SSLPipeline
from repro.distributed.bmuf import BMUFConfig
from repro.launch.steps import make_loss_fn
from repro.models import build_model
from repro.runtime.cluster import worker_mesh
from repro.train import BMUFShardMap, ListSink, Local, TrainBatch, Trainer


def bench_strategy(strategy, label, *, model, cfg, batches, updates, lrs):
    trainer = Trainer(strategy, {"ce": make_loss_fn(model, cfg, "ce")},
                      metrics=ListSink())
    need = strategy.microbatches

    def source(n_updates, lr_list):
        i = 0
        for u in range(n_updates):
            for _ in range(need):
                yield TrainBatch(batches[i % len(batches)],
                                 lr_list[u % len(lr_list)], "ce")
                i += 1

    # warmup: one update compiles the executable
    state = trainer.init_state(model.init(jax.random.key(0)))
    state = trainer.fit(state, source(1, [lrs[0]]), resume=False)
    jax.block_until_ready(state.params)

    t0 = time.time()
    state = trainer.fit(state, source(updates, lrs), resume=False)
    jax.block_until_ready(state.params)
    wall = time.time() - t0

    frames_per_micro = int(np.prod(batches[0]["mask"].shape))
    frames = updates * need * frames_per_micro
    rec = {"strategy": label, "updates": updates,
           "microbatches_per_update": need,
           "distinct_lrs": len(set(lrs)),
           "steps_per_sec": round(updates / wall, 2),
           "frames_per_sec": round(frames / wall, 1),
           "wall_s": round(wall, 3),
           "compiles": trainer.updates["ce"]._cache_size()}
    print(f"  {label:10s} {rec['steps_per_sec']:8.2f} updates/s "
          f"{rec['frames_per_sec']:10.1f} frames/s "
          f"({need} microbatch(es)/update, "
          f"{rec['compiles']} compile(s) across {rec['distinct_lrs']} lrs)")
    return rec


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--updates", type=int, default=12)
    ap.add_argument("--hidden", type=int, default=64)
    ap.add_argument("--layers", type=int, default=2)
    ap.add_argument("--workers", type=int, default=4)
    ap.add_argument("--block-steps", type=int, default=2)
    ap.add_argument("--out", default="experiments/benchmarks")
    args = ap.parse_args(argv)

    pc = PipelineConfig(n_labeled=32, n_val=8,
                        lstm_hidden=args.hidden, n_layers=args.layers)
    pipe = SSLPipeline(pc, out_dir=os.path.join(args.out, "_train_bench"))
    cfg = pipe.student_cfg
    model = build_model(cfg)
    batches = pipe._batches(pipe.rng_labeled, chunked=True, seed=0)
    # exponential LR sweep: every update sees a different lr — the
    # compile count staying at 1 is the tentpole's perf claim
    lrs = [5e-2 * (0.9 ** i) for i in range(args.updates)]
    print(f"{len(batches)} chunked batches of {pc.batch}x{pc.chunk_len}, "
          f"{args.updates} updates, {len(set(lrs))} distinct lrs")

    records = [
        bench_strategy(Local(), "local", model=model, cfg=cfg,
                       batches=batches, updates=args.updates, lrs=lrs),
        bench_strategy(
            BMUFShardMap(BMUFConfig(n_workers=args.workers,
                                    block_steps=args.block_steps),
                         worker_mesh(args.workers)),
            "bmuf", model=model, cfg=cfg, batches=batches,
            updates=args.updates, lrs=lrs),
    ]
    for r in records:
        assert r["compiles"] == 1, r      # lr sweep must not re-compile
    os.makedirs(args.out, exist_ok=True)
    path = os.path.join(args.out, "train_bench.json")
    with open(path, "w") as f:
        json.dump({"config": vars(args), "records": records}, f, indent=1)
    print(f"wrote {path}")


if __name__ == "__main__":
    main()
