"""Readings that set the BMUF cell's limits: the program's gaps over its
two compared blocks on many seeds (the lower readings), and on a few the
control's (the reference with fp8 operands in the program's place) and the
planted faults' (the float32 reference with the block momentum left out,
the Nesterov restart left out, the exchange between chips left out, half
of every batch left out, each in the program's place), at the cell's own
size on four chips, in one process.

    python benchmarks/chip/tests/controls_bmuf.py --seeds 2001-2020 \
        --controls 1 --faults 1 --seconds 1

One JSON line per seed.  The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
import tiny  # noqa: E402,F401  (puts the benchmark and the program on the path)


def readings(cell, seed, seconds, controls: bool, faults: bool, out_dir):
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
    drv.release()
    out = {"seed": seed, "failed": res["failed"],
           "attempted": res["attempted"],
           "train_frames_per_s": res["e2e"]["train_frames_per_s"],
           "program": drv.check()}
    if controls:
        out["fp8"] = drv.check(program=drv.reference("fp8"))
    if faults:
        for name in cell.driver().FAULTS:
            out[name] = drv.check(program=drv.reference(fault=name))
    out["run_s"] = time.perf_counter() - t0
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seeds", default="2001-2010")
    ap.add_argument("--controls", type=int, default=1)
    ap.add_argument("--faults", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=1.0)
    args = ap.parse_args(argv)
    from repro.runtime.env import bootstrap_from_env
    bootstrap_from_env()
    from bench import harness, loader
    cell = loader.resolve("student-bmuf-w4")
    harness.devices_for(cell)
    harness.configure_cache()
    out_dir = os.path.join(loader.ROOT, ".bench_out", "controls_bmuf")
    lo, _, hi = args.seeds.partition("-")
    for n, seed in enumerate(range(int(lo), int(hi or lo) + 1)):
        shutil.rmtree(out_dir, ignore_errors=True)
        os.makedirs(out_dir)
        print(json.dumps(readings(cell, seed, args.seconds,
                                  n < args.controls, n < args.faults,
                                  out_dir)), flush=True)
    shutil.rmtree(out_dir, ignore_errors=True)


if __name__ == "__main__":
    main()
