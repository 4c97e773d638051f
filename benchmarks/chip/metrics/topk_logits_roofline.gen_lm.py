"""``topk_logits`` kernel's share of its roofline on the LM teacher: the
least time for the float32 logit rows of every emitter chunk (each of the
vocabulary's logits read once, k values and ids written), over the summed
device time of its ``topk_logits_tiles`` ops."""
from bench import costs, harness

KERNEL = "topk_logits_tiles"


def read(run, res, tr):
    t = tr.op_time_s(KERNEL)
    if t <= 0 or not res.get("emit_rows"):
        return None
    cost = costs.topk_logits_cost(res["emit_rows"],
                                  run.cell.config["vocab_size"],
                                  run.cell.traffic["k"])
    share, bound = costs.roofline_share(cost, t, costs.peaks(run.device_kind))
    harness.log(f"topk_logits (LM): {tr.op_count(KERNEL)} ops, {t:.6f} s, "
                f"bound by {bound}")
    return share
