"""GTC trainer: wire bytes/update and updates/s across worker counts.

  PYTHONPATH=src python benchmarks/gtc_bench.py
  PYTHONPATH=src python benchmarks/gtc_bench.py --updates 16 --hidden 128

The paper's 16-GPU sequence trainer ships threshold-compressed sends;
this records what the int8 pack buys as *numbers*:

  * **wire bytes/update** — what one worker ships into the all-reduce
    per update under each wire format (dense f32 send vs packed int8;
    int8 holds through the accumulation for <= 127 workers, so the
    claim asserted here is int8 >= 3x smaller than f32 at equal
    density — the sends are identical tensors, only the encoding
    differs; the observed ratio is 4x).
  * **updates/s** at workers ∈ {1, 2, 4} through the same Trainer.fit
    loop and the same GTCShardMap on a one-device mesh, with the lr
    swept every update — the compile count staying at 1 per worker
    count is asserted, as in train_bench.
  * **gtc_density** — fraction of elements actually nonzero on the
    wire (the sparsity Strom's threshold buys; diagnostic).

On one CPU the W>1 workers are time-sliced so updates/s *per update*
falls with W while frames/s stays comparable — the scale-out claim is
the wire format + the sharded exchange, exercised bitwise in
tests/test_distributed.py.
"""
from __future__ import annotations

import argparse
import json
import os
import time

import jax
import numpy as np

from repro.core.ssl_pipeline import PipelineConfig, SSLPipeline
from repro.distributed import gtc as gtc_lib
from repro.launch.steps import make_loss_fn
from repro.models import build_model
from repro.runtime.cluster import auto_mesh
from repro.train import GTCShardMap, ListSink, TrainBatch, Trainer


def bench_workers(workers, *, model, cfg, batches, updates, lrs, tau):
    gcfg = gtc_lib.GTCConfig(tau=tau, n_workers=workers)
    strategy = GTCShardMap(gcfg, auto_mesh((1,), ("data",)), clip=0.0)
    sink = ListSink()
    trainer = Trainer(strategy, {"ce": make_loss_fn(model, cfg, "ce")},
                      metrics=sink)
    need = strategy.microbatches

    def source(n_updates, lr_list):
        i = 0
        for u in range(n_updates):
            for _ in range(need):
                yield TrainBatch(batches[i % len(batches)],
                                 lr_list[u % len(lr_list)], "ce")
                i += 1

    params = model.init(jax.random.key(0))
    state = trainer.init_state(params)
    state = trainer.fit(state, source(1, [lrs[0]]), resume=False)  # warm
    jax.block_until_ready(state.params)

    t0 = time.time()
    state = trainer.fit(state, source(updates, lrs), resume=False)
    jax.block_until_ready(state.params)
    wall = time.time() - t0

    frames_per_micro = int(np.prod(batches[0]["mask"].shape))
    int8_bytes = gtc_lib.wire_bytes_per_update(params, gcfg)
    f32_bytes = gtc_lib.wire_bytes_per_update(
        params, gtc_lib.GTCConfig(tau=tau, n_workers=workers,
                                  quantize_int8=False))
    rec = {"workers": workers, "updates": updates,
           "microbatches_per_update": need,
           "steps_per_sec": round(updates / wall, 2),
           "frames_per_sec": round(updates * need * frames_per_micro
                                   / wall, 1),
           "wall_s": round(wall, 3),
           "wire_bytes_int8": int8_bytes,
           "wire_bytes_f32": f32_bytes,
           "wire_ratio_f32_over_int8": round(f32_bytes / int8_bytes, 2),
           "gtc_density": round(sink.last("gtc_density"), 4),
           "compiles": trainer.updates["ce"]._cache_size()}
    print(f"  W={workers}  {rec['steps_per_sec']:7.2f} updates/s "
          f"{rec['frames_per_sec']:9.1f} frames/s  wire "
          f"{int8_bytes}B (int8) vs {f32_bytes}B (f32) = "
          f"{rec['wire_ratio_f32_over_int8']}x, density "
          f"{rec['gtc_density']}, {rec['compiles']} compile(s)")
    return rec


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--updates", type=int, default=12)
    ap.add_argument("--hidden", type=int, default=64)
    ap.add_argument("--layers", type=int, default=2)
    ap.add_argument("--tau", type=float, default=2e-4)
    ap.add_argument("--min-wire-ratio", type=float, default=3.0)
    ap.add_argument("--out", default="experiments/benchmarks")
    args = ap.parse_args(argv)

    pc = PipelineConfig(n_labeled=32, n_val=8,
                        lstm_hidden=args.hidden, n_layers=args.layers)
    pipe = SSLPipeline(pc, out_dir=os.path.join(args.out, "_gtc_bench"))
    cfg = pipe.student_cfg
    model = build_model(cfg)
    batches = pipe._batches(pipe.rng_labeled, chunked=True, seed=0)
    lrs = [5e-2 * (0.9 ** i) for i in range(args.updates)]
    print(f"{len(batches)} chunked batches of {pc.batch}x{pc.chunk_len}, "
          f"{args.updates} updates, tau={args.tau}")

    records = [bench_workers(w, model=model, cfg=cfg, batches=batches,
                             updates=args.updates, lrs=lrs, tau=args.tau)
               for w in (1, 2, 4)]
    for r in records:
        assert r["compiles"] == 1, r          # lr sweep must not re-jit
        assert r["wire_ratio_f32_over_int8"] >= args.min_wire_ratio, r

    os.makedirs(args.out, exist_ok=True)
    path = os.path.join(args.out, "gtc_bench.json")
    with open(path, "w") as f:
        json.dump({"config": vars(args), "records": records}, f, indent=1)
    print(f"wrote {path}")


if __name__ == "__main__":
    main()
