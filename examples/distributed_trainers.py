"""GTC vs BMUF on the same workload (paper §3.5 / Tables 1-2).

  PYTHONPATH=src python examples/distributed_trainers.py

Trains the same reduced AM with (a) GTC: gradient-threshold-compressed
synchronous SGD (the 16-GPU trainer) and (b) BMUF: blockwise model-update
filtering with Nesterov block momentum (the 64-GPU trainer), printing the
loss curves and the GTC wire density — the trade the paper's §5.2
quantifies as "in attempting to scale to 64 GPUs, we lose some of the
gains".

Both runs are the *same* Trainer.fit() loop over the same data source;
only the DistributedStrategy constructor argument differs — the point
of the unified Trainer API.

Topology flags (repro.runtime):
  REPRO_HOST_DEVICES=8 python examples/distributed_trainers.py
      — run the shard_map trainers on a real 8-device host mesh
  python examples/distributed_trainers.py --cluster host:port,N,i
      — multi-host launch via jax.distributed (single-process specs
        are a no-op)
"""
from repro.runtime.env import bootstrap_from_env
bootstrap_from_env()    # before the first jax import (locks XLA flags)

import argparse

import jax

from repro.core.ssl_pipeline import PipelineConfig, SSLPipeline
from repro.distributed.bmuf import BMUFConfig
from repro.distributed.gtc import GTCConfig
from repro.launch.steps import make_loss_fn
from repro.models import build_model
from repro.runtime.cluster import ClusterConfig, initialize, worker_mesh
from repro.train import (BMUFShardMap, GTCShardMap, ListSink, Trainer,
                         epoch_source)


def run(strategy, label, *, model, cfg, batches, epochs=3, lr=5e-2):
    sink = ListSink()
    trainer = Trainer(strategy, {"ce": make_loss_fn(model, cfg, "ce")},
                      metrics=sink)
    state = trainer.init_state(model.init(jax.random.key(0)))
    state = trainer.fit(state, epoch_source(lambda ep: batches, epochs,
                                            lr, "ce"))
    print(f"  {label}: {int(state.step)} updates, "
          f"loss {sink.first('loss'):.3f} -> {sink.last('loss'):.3f}")
    return state, sink


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--cluster", default="",
                    help="'env' or 'host:port,N,i' (see runtime.cluster)")
    args = ap.parse_args()
    if args.cluster:
        info = initialize(ClusterConfig.from_spec(args.cluster))
        print(f"cluster: process {info.process_index}/{info.process_count}")

    pc = PipelineConfig(n_labeled=32, n_val=8, epochs_baseline=1)
    pipe = SSLPipeline(pc, out_dir="experiments/trainers")
    cfg = pipe.student_cfg
    model = build_model(cfg)
    batches = pipe._batches(pipe.rng_labeled, chunked=True, seed=0)
    print(f"{len(batches)} chunked batches of {pc.batch}x{pc.chunk_len}")

    print("\n== GTC (1 worker: threshold compression, error feedback) ==")
    _, sink = run(GTCShardMap(GTCConfig(tau=5e-4, n_workers=1),
                              worker_mesh(1)), "gtc",
                  model=model, cfg=cfg, batches=batches)
    dens = sink.last("gtc_density")
    print(f"  wire density {dens:.3f} "
          f"(bandwidth saving ~{1 / max(dens, 1e-3):.0f}x)")

    mesh = worker_mesh(2)     # widest device mesh 2 workers divide onto
    print(f"\n== GTC (2 workers, int8 wire over a "
          f"{mesh.devices.size}-device mesh) ==")
    run(GTCShardMap(GTCConfig(tau=5e-4, n_workers=2), mesh),
        "gtc_shardmap", model=model, cfg=cfg, batches=batches)

    bc = BMUFConfig(n_workers=4, block_steps=2)
    print(f"\n== BMUF ({bc.n_workers} workers, block sync every "
          f"{bc.block_steps} steps) ==")
    run(BMUFShardMap(bc, worker_mesh(bc.n_workers)), "bmuf", model=model,
        cfg=cfg, batches=batches)

    print("\nGTC communicates every step (a compressed int8 psum over "
          "the worker axis); BMUF every "
          f"{bc.block_steps} steps (full model mean + block momentum).")


if __name__ == "__main__":
    main()
