"""Tiny stand-ins for the cells, for rehearsals on the CPU: the real
cell's files with the widths and batches cut to CPU size."""
from __future__ import annotations

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
CHIP = os.path.dirname(HERE)
ROOT = os.path.dirname(os.path.dirname(CHIP))
for p in (CHIP, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

TINY_MODEL = {"n_layers": 2, "lstm_hidden": 32, "feat_dim": 24,
              "n_senones": 300}
TINY_TRAFFIC = {
    "generate": {"batch_chunks": 16, "chunk_frames": 8, "pool_batches": 3,
                 "group_batches": 2, "k": 5, "sample_rows": 24},
    "train": {"batch_chunks": 8, "chunk_frames": 8, "k": 5},
}
TINY_UTTERANCES = {"mean_frames": 20, "min_frames": 5, "max_frames": 60}


def tiny_cell(workload: str):
    from bench import loader
    cell = loader.resolve(workload)
    cell.config = dict(cell.config, **TINY_MODEL)
    tf = dict(cell.traffic, **TINY_TRAFFIC[cell.traffic["driver"]])
    tf["utterances"] = dict(tf["utterances"], **TINY_UTTERANCES)
    cell.traffic = tf
    return cell


def run_cell(cell, seed: int = 7, seconds: float = 1.0, trace: bool = False,
             out_dir: str = None):
    """Drive the harness's whole run of ``cell`` on the CPU; -> result."""
    import tempfile

    import jax

    from bench import harness
    devs = jax.devices()[:cell.chips]
    out_dir = out_dir or tempfile.mkdtemp()
    r = harness.Run(cell=cell, seed=seed, seconds=seconds, trace=trace,
                    out_dir=out_dir, devices=devs,
                    device_kind="TPU v5 lite")
    return harness.execute(r, cell.driver(), t_start=0.0)
