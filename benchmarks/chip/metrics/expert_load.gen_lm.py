"""Routing evenness the program counts: per ``generate_sharded`` call, the
worst MoE layer's ``expert_load`` (largest expert's tokens over the mean,
worst over the call's shards), averaged over the window's calls.  Nothing
to read where the program reports no expert load."""


def read(run, res, tr):
    loads = res.get("expert_loads")
    if not loads:
        return None
    return sum(loads) / len(loads)
