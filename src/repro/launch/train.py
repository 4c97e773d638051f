"""End-to-end trainer CLI — the paper's recipe on synthetic data.

Drives the full SSL pipeline (repro.core.ssl_pipeline) at laptop scale with
the exact *structure* of the 1M-hour build: baseline CE -> teacher (+sMBR)
-> teacher target generation into the logit store -> scheduled student
training (BMUF or GTC) -> student sMBR on labeled data only.

  PYTHONPATH=src python -m repro.launch.train --stage all --scale tiny
  PYTHONPATH=src python -m repro.launch.train --stage student --trainer bmuf

Every stage runs through repro.train.Trainer: a killed stage resumes
from its last periodic TrainState checkpoint on the next invocation
(pass nothing — resume is automatic; delete <out>/ckpt_<stage>/state to
force a fresh run).

For LLM archs (`--arch qwen2.5-3b --smoke`), runs a few CE steps on
synthetic token batches with the reduced config — the multi-arch smoke
path; the full-size path is the dry-run (launch/dryrun.py).
"""
from __future__ import annotations

from repro.runtime.env import bootstrap_from_env
bootstrap_from_env()
# ^ REPRO_HOST_DEVICES / REPRO_PLATFORM / ... must land in os.environ
# before the first jax import locks the XLA client config.

import argparse
import json
import os
import time

import jax
import numpy as np


def train_llm_smoke(arch: str, steps: int = 4, batch: int = 2, seq: int = 64):
    from repro.configs import get_arch, reduced
    from repro.data.loader import token_batches
    from repro.launch.steps import make_loss_fn
    from repro.models import build_model
    from repro.train import ListSink, Local, Trainer, epoch_source

    cfg = reduced(get_arch(arch))
    model = build_model(cfg)
    sink = ListSink()
    trainer = Trainer(Local(optimizer="adam"),
                      {"ce": make_loss_fn(model, cfg, "ce")}, metrics=sink)
    state = trainer.init_state(model.init(jax.random.key(0)))
    state = trainer.fit(state, epoch_source(
        lambda ep: token_batches(cfg.vocab_size, batch, seq, steps),
        1, 3e-4, "ce"))
    losses = sink.values("loss")
    for l in losses:
        print(f"  step loss={l:.4f}")
    assert np.isfinite(losses).all()
    return losses


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="lstm-am-7khr")
    ap.add_argument("--stage", default="all",
                    choices=["all", "baseline", "teacher", "targets",
                             "student", "smbr"])
    ap.add_argument("--trainer", default="gtc", choices=["gtc", "bmuf"])
    ap.add_argument("--scale", default="tiny",
                    choices=["tiny", "small", "paper"],
                    help="paper: the published 5x768 / 3,183-senone "
                         "widths (for the chip; synthetic data)")
    ap.add_argument("--smoke", action="store_true",
                    help="LLM-arch reduced-config smoke run")
    ap.add_argument("--steps", type=int, default=4)
    ap.add_argument("--gen-workers", type=int, default=None,
                    help="target-generation workers (ledgered disjoint "
                         "shard ranges; default: PipelineConfig's 2)")
    ap.add_argument("--gtc-workers", type=int, default=None,
                    help="sMBR sequence-training workers of "
                         "GTCShardMap (int8 wire over a mesh worker "
                         "axis; default: PipelineConfig's 2)")
    ap.add_argument("--prefetch", type=int, default=None,
                    help="async feed depth for Trainer.fit "
                         "(0 = synchronous; default: PipelineConfig's 2)")
    ap.add_argument("--gen-procs", type=int, default=0,
                    help="target generation as N real OS processes "
                         "racing the shared ledger (0 = in-process; "
                         "the manifest is bitwise-identical either way; "
                         "host-CPU runs only: on an accelerator the "
                         "children would need this process's device)")
    ap.add_argument("--cluster", default="",
                    help="multi-host launch: 'env' (JAX_COORDINATOR_"
                         "ADDRESS/JAX_NUM_PROCESSES/JAX_PROCESS_ID or "
                         "REPRO_* equivalents) or 'host:port,N,i'; "
                         "single-process specs are a no-op")
    ap.add_argument("--out", default="experiments/train")
    args = ap.parse_args(argv)

    if args.cluster:
        from repro.runtime.cluster import ClusterConfig, initialize
        info = initialize(ClusterConfig.from_spec(args.cluster))
        print(f"[train] cluster: process {info.process_index}/"
              f"{info.process_count}"
              f"{' (coordinator)' if info.is_coordinator else ''}")

    if args.arch != "lstm-am-7khr" or args.smoke:
        print(f"[train] LLM smoke: {args.arch}")
        losses = train_llm_smoke(args.arch, steps=args.steps)
        print(f"[train] done, final loss {losses[-1]:.4f}")
        return

    from repro.core.ssl_pipeline import PipelineConfig, SSLPipeline
    scale = getattr(PipelineConfig, args.scale)()
    if args.gen_workers is not None:
        scale.gen_workers = args.gen_workers
    if args.gtc_workers is not None:
        scale.gtc_workers = args.gtc_workers
    if args.prefetch is not None:
        scale.prefetch = args.prefetch
    if args.gen_procs:
        from repro.runtime.procs import refuse_children_on_accelerator
        refuse_children_on_accelerator("--gen-procs")
        scale.gen_procs = args.gen_procs
    pipe = SSLPipeline(scale, out_dir=args.out,
                       student_trainer=args.trainer)
    t0 = time.time()
    results = pipe.run(stage=args.stage)
    print(f"[train] stage={args.stage} done in {time.time()-t0:.1f}s")
    os.makedirs(args.out, exist_ok=True)
    with open(os.path.join(args.out, f"train_{args.stage}.json"), "w") as f:
        json.dump(results, f, indent=1, default=float)
    for k, v in results.items():
        print(f"  {k}: {v}")


if __name__ == "__main__":
    main()
