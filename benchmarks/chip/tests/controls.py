"""Readings that set each cell's limits: the program's numbers on many
seeds (the lower readings) and the control's and the planted faults' on
a few (the upper readings), at the cell's own size on the chip.

    python benchmarks/chip/tests/controls.py --workload teacher-gen \
        --seeds 2001-2012 --controls 3 --seconds 2

For each seed: set-up, a short window at the cell's own load, then the
program's numbers as a benchmark run computes them.  On the first
``--controls`` seeds also, each put in the program's place: the control,
the reference in the precision below the configuration's (fp8 operands);
as context, the reference with bf16 operands and with bf16 parameters,
gates and state ("bf16_state"); for training cells the fault
"half_batch", planted in the reference.
One JSON line per seed goes to standard output.  The benchmark's own runs never run this.

``--cpu`` runs the tiny stand-in of the cell on the CPU (``tiny.py``).
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
import tiny  # noqa: E402  (puts the benchmark and the program on the path)


def emission_control(drv, prec):
    """The reference at ``prec`` in the program's place: its top-k
    emission of the same rows."""
    import jax.numpy as jnp
    import numpy as np

    from bench import ref_lstm_am as ref
    from bench.emission import reference_logits

    def program(feats, mask):
        lg = reference_logits(drv.cfg, drv.run.seed, feats, mask,
                              drv.run.devices[0], prec=prec)
        v, i = ref.emit_topk(jnp.asarray(lg), drv.tf["k"])
        return np.asarray(v), np.asarray(i)
    return program


LOWER = ("fp8", "bf16", "bf16_state")


def readings(cell, seed, seconds, controls: bool, out_dir,
             lower=LOWER):
    import jax

    from bench import harness
    devs = jax.devices()[:cell.chips]
    run = harness.Run(cell=cell, seed=seed, seconds=seconds, trace=False,
                      out_dir=out_dir, devices=devs,
                      device_kind=devs[0].device_kind)
    drv = cell.driver().Driver(run)
    t0 = time.perf_counter()
    drv.setup()
    res = drv.window(seconds)
    if hasattr(drv, "finish"):
        res = drv.finish(res)
    drv.release()
    out = {"seed": seed, "failed": res["failed"],
           "attempted": res["attempted"],
           "program": drv.check(), "run_s": time.perf_counter() - t0}
    if controls:
        train = cell.traffic["driver"] == "train"
        for prec in lower:
            out[prec] = drv.check(program=drv.reference(prec) if train
                                  else emission_control(drv, prec))
        if train:
            out["half_batch"] = drv.check(
                program=drv.reference(fault="half_batch"))
    return out


def seeds_of(spec: str):
    lo, _, hi = spec.partition("-")
    return list(range(int(lo), int(hi or lo) + 1))


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="2001-2012")
    ap.add_argument("--controls", type=int, default=3)
    ap.add_argument("--seconds", type=float, default=2.0)
    ap.add_argument("--lower", default=",".join(LOWER),
                    help="the lower-precision references to read")
    ap.add_argument("--cpu", action="store_true")
    args = ap.parse_args(argv)
    if not args.cpu:
        from repro.runtime.env import bootstrap_from_env
        bootstrap_from_env()
    import shutil

    from bench import harness, loader
    cell = tiny.tiny_cell(args.workload) if args.cpu \
        else loader.resolve(args.workload)
    if not args.cpu:
        harness.devices_for(cell)
        harness.configure_cache()
    out_dir = os.path.join(loader.ROOT, ".bench_out", "controls")
    for n, seed in enumerate(seeds_of(args.seeds)):
        shutil.rmtree(out_dir, ignore_errors=True)
        os.makedirs(out_dir)
        out = readings(cell, seed, args.seconds, n < args.controls,
                       out_dir, lower=args.lower.split(","))
        print(json.dumps(out), flush=True)
    shutil.rmtree(out_dir, ignore_errors=True)


if __name__ == "__main__":
    main()
