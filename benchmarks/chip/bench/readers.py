"""Readers shared by the per-layer metric files under ``metrics/``.

A reader takes the run, the window's result and the trace reduction, and
returns the metric's value, or None where it finds nothing to read.
"""
from __future__ import annotations

from bench import costs
from bench.harness import log


def device_idle(run, res, tr):
    """Share of the traced window in which no op ran on the device: 1 -
    union of device-op intervals / window, averaged over the chips used."""
    if tr.window_s() <= 0 or not tr.devices():
        return None
    return 100.0 * (1.0 - tr.busy_s() / tr.window_s())


def step_mfu(run, res, flops_per_frame: int):
    """Whole step's share of the chips' peak: model FLOPs per valid frame
    x valid frames over the window / (window x chips x peak)."""
    if not res.get("frames"):
        return None
    pk = costs.peaks(run.device_kind)["flops"]
    return (100.0 * flops_per_frame * res["frames"] / res["window_s"]
            / (len(run.devices) * pk))


def topk_logits_roofline(run, res, tr):
    """``topk_logits`` kernel's share of its roofline: the least time for
    the logit rows it was given (every float32 logit read once, k values
    and ids written), over the summed device time of its
    ``topk_logits_tiles`` ops."""
    t = tr.op_time_s("topk_logits_tiles")
    if t <= 0 or not res.get("emit_rows"):
        return None
    cost = costs.topk_logits_cost(res["emit_rows"],
                                  run.cell.config["n_senones"],
                                  run.cell.traffic["k"])
    share, bound = costs.roofline_share(cost, t, costs.peaks(run.device_kind))
    log(f"topk_logits: {tr.op_count('topk_logits_tiles')} ops, {t:.6f} s, "
        f"bound by {bound}")
    return share
