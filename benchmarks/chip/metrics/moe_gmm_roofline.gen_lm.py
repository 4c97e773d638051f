"""The routed experts' grouped matmul (``kernels/moe_gmm``, the ``gmm``
custom call) share of its roofline: the operations and bytes of every
MoE layer's gate, up and down over the positions emitted
(``bench/costs_lm.py``), over the summed device time of its ``gmm`` ops."""
from bench import costs, costs_lm, harness

KERNEL = r"^gmm(\.\d+)?$"


def read(run, res, tr):
    t = tr.op_time_s(KERNEL)
    if t <= 0 or not res.get("emit_rows"):
        return None
    cost = costs_lm.moe_gmm_cost(res["emit_rows"], run.cell.config)
    share, bound = costs.roofline_share(cost, t, costs.peaks(run.device_kind))
    harness.log(f"moe_gmm: {tr.op_count(KERNEL)} ops, {t:.6f} s, "
                f"bound by {bound}")
    return share
