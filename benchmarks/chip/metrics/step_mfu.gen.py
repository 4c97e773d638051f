"""Whole teacher step's share of the chips' peak: 2 N_matmul FLOPs per
valid frame x valid frames/s over the window / (chips x peak)."""
from bench import costs, readers


def read(run, res, tr):
    return readers.step_mfu(run, res,
                            costs.forward_flops_per_frame(run.cell.config))
