"""One run of a cell with some traffic parameters changed, for sizing a
cell on the chip: the end-to-end numbers, the device's memory readings
after set-up and after the window, and, for generation cells, the
compiled forward's own memory analysis.  One JSON line to standard
output; run one process per setting, so that each reads its own peak.

    python benchmarks/chip/tests/sweep.py --workload teacher-gen \
        --set batch_chunks=2048 --set group_batches=2 --seconds 10
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import time

T0 = time.perf_counter()
HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
import tiny  # noqa: E402,F401  (puts the benchmark and the program on the path)


def memory(dev) -> dict:
    s = dev.memory_stats() or {}
    return {k: s.get(k) for k in ("bytes_in_use", "peak_bytes_in_use",
                                  "bytes_limit")}


def forward_memory(drv) -> dict:
    import jax.numpy as jnp
    batch = {k: jnp.asarray(v) for k, v in drv.pool[0].items()}
    c = drv.runner.engine._fwd_dict.lower(drv.params, batch).compile()
    m = c.memory_analysis()
    return {k: getattr(m, k, None) for k in (
        "argument_size_in_bytes", "output_size_in_bytes",
        "temp_size_in_bytes", "generated_code_size_in_bytes")}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--set", action="append", default=[],
                    help="traffic key=value (a number)")
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args(argv)
    from repro.runtime.env import bootstrap_from_env
    bootstrap_from_env()
    from bench import harness, loader
    cell = loader.resolve(args.workload)
    changed = {}
    for kv in args.set:
        k, _, v = kv.partition("=")
        changed[k] = json.loads(v)
    cell.traffic = dict(cell.traffic, **changed)
    devs = harness.devices_for(cell)
    harness.configure_cache()
    out_dir = os.path.join(loader.ROOT, ".bench_out", "sweep")
    shutil.rmtree(out_dir, ignore_errors=True)
    os.makedirs(out_dir)
    run = harness.Run(cell=cell, seed=args.seed, seconds=args.seconds,
                      trace=False, out_dir=out_dir, devices=devs,
                      device_kind=devs[0].device_kind)
    try:
        drv = cell.driver().Driver(run)
        drv.setup()
        out = {"workload": args.workload, "set": changed,
               "setup_s": time.perf_counter() - T0,
               "after_setup": memory(devs[0])}
        res = drv.window(args.seconds)
        out["e2e"] = res["e2e"]
        out["attempted"] = res["attempted"]
        out["after_window"] = memory(devs[0])
        if hasattr(drv, "runner"):
            out["forward"] = forward_memory(drv)
        t = time.perf_counter()
        out["checks"] = drv.check()
        out["check_s"] = time.perf_counter() - t
    finally:
        shutil.rmtree(out_dir, ignore_errors=True)
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
