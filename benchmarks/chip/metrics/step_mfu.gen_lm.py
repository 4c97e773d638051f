"""Whole LM teacher step's share of the chips' peak: model FLOPs per
labelled position (``bench/costs_lm.py``: active matmul weights, causal
attention at the traffic's length, the head) x positions over the window
/ (chips x peak)."""
from bench import costs_lm, readers


def read(run, res, tr):
    return readers.step_mfu(run, res, costs_lm.forward_flops_per_token(
        run.cell.config, run.cell.traffic["seq_len"]))
