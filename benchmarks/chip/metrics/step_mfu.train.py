"""Whole update's share of the chips' peak: 6 N_matmul FLOPs per valid
frame (forward and backward; recompute not counted) x valid frames/s over
the window / (chips x peak)."""
from bench import costs, readers


def read(run, res, tr):
    return readers.step_mfu(run, res,
                            costs.train_flops_per_frame(run.cell.config))
