#!/usr/bin/env python3
"""On-chip benchmark of the million-hour acoustic-model system: one
process runs one cell once.

    python benchmarks/chip/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

The cell (BENCHMARK.json ``workloads``) names a model configuration and a
traffic mix; both, the cell's limits and its per-layer metric readers are
found by name under this directory (``bench/loader.py``).  Set-up builds
the weights and data from ``--seed`` and warms every shape the window
uses; the window then runs for ``--seconds``; after it, the program's
output is compared with a plain float32 reference.  The last line of
standard output is the result as one JSON object; the numbers compared
are printed beside their limits as the last lines of standard error and
under the result's last key, ``checks``.

``--trace 0`` reports the cell's end-to-end metrics; ``--trace 1`` runs
the window under the JAX profiler and reports its per-layer metrics, the
device's busy and window seconds, and a breakdown.  Exits nonzero, with
no result, when JAX finds no TPU or fewer chips than the cell asks for.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    src = os.path.join(ROOT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        print(f"run.py: the program (src/repro) is not in {ROOT}",
              file=sys.stderr)
        return 2
    sys.path.insert(0, src)
    sys.path.insert(0, HERE)
    # the TPU runtime's own logs would go to a fixed path outside the
    # checkout
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from repro.runtime.env import bootstrap_from_env
    bootstrap_from_env()            # compile cache: <checkout>/.jax_cache

    from bench import harness
    return harness.run(args.workload, args.seed, args.seconds,
                       bool(args.trace), t_start=T_START)


if __name__ == "__main__":
    sys.exit(main())
