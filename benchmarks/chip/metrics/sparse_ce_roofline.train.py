"""``sparse_ce`` kernel (fused unembedding + logsumexp + gather, the
distill loss's forward) share of its roofline: the least time for the
frames it was given, over the summed device time of its
``sparse_ce_tiles`` ops on all chips."""
from bench import costs, harness

KERNEL = r"sparse_ce_tiles"


def read(run, res, tr):
    t = tr.op_time_s(KERNEL)
    if t <= 0 or not res.get("loss_rows"):
        return None
    cfg = run.cell.config
    rows = run.cell.traffic["batch_chunks"] * run.cell.traffic["chunk_frames"]
    one = costs.sparse_ce_cost(rows, cfg["lstm_hidden"], cfg["n_senones"],
                               run.cell.traffic["k"])
    n = res["loss_rows"] // rows
    cost = {k: v * n for k, v in one.items()}
    share, bound = costs.roofline_share(cost, t, costs.peaks(run.device_kind))
    harness.log(f"sparse_ce: {tr.op_count(KERNEL)} ops for {n} calls, "
                f"{t:.6f} s, bound by {bound}")
    return share
