"""Peaks of the chip and the operations and bytes the work needs,
computed from shapes.  The benchmark's yardstick: the program never
supplies these numbers.

PEAKS is copied from the repository's ``benchmarks/roofline.py`` table.
Source: Google Cloud documentation, "TPU v5e" (system architecture):
197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s, 1,600 Gbit/s
interconnect per chip.  A device kind missing from the table is an error.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {"flops": 197e12, "int8_ops": 393e12,
                    "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9,
                    "ici_bytes_per_s": 1600e9 / 8},
}


def peaks(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(f"no peaks for device kind {device_kind!r}; known: "
                       f"{sorted(PEAKS)}")
    return PEAKS[device_kind]


# --------------------------------------------------------------- the model

def lstm_matmul_weights(n_layers: int, hidden: int, feat_dim: int,
                        n_senones: int, bidirectional: bool) -> int:
    """Matmul weights of a stacked (bi)LSTM AM with a senone output layer:
    per layer and direction Wx (d_in, 4H) + Wh (H, 4H)."""
    dirs = 2 if bidirectional else 1
    n, d_in = 0, feat_dim
    for _ in range(n_layers):
        n += dirs * (d_in * 4 * hidden + hidden * 4 * hidden)
        d_in = dirs * hidden
    return n + d_in * n_senones


def model_weights(cfg: dict) -> int:
    return lstm_matmul_weights(cfg["n_layers"], cfg["lstm_hidden"],
                               cfg["feat_dim"], cfg["n_senones"],
                               cfg["bidirectional"])


def forward_flops_per_frame(cfg: dict) -> int:
    """2 N_matmul: one multiply-add per matmul weight per frame."""
    return 2 * model_weights(cfg)


def train_flops_per_frame(cfg: dict) -> int:
    """6 N_matmul: forward, and backward to activations and to weights.
    Recompute is not counted."""
    return 6 * model_weights(cfg)


# ------------------------------------------------------------- the kernels

def topk_logits_cost(rows: int, vocab: int, k: int) -> dict:
    """Top-k over rows of float32 logits: every logit read once and
    compared once; k float32 values and k int32 ids written per row.
    The kernel's 128-lane padded candidate writes are not needed bytes."""
    return {"ops": rows * vocab,
            "bytes": rows * vocab * 4 + rows * k * 8}


def sparse_ce_cost(rows: int, d: int, vocab: int, k: int) -> dict:
    """Fused unembedding + logsumexp + gather at k ids (forward):
    2 T D V multiply-adds, one compare and one exp-accumulate per logit;
    reads h (T,D) f32, W (D,V) f32 once, the ids (T,k) i32; writes the
    lse (T) and the gathered logits (T,k) f32."""
    return {"ops": 2 * rows * d * vocab + 2 * rows * vocab,
            "bytes": rows * d * 4 + d * vocab * 4 + rows * k * 4
            + rows * 4 + rows * k * 4}


def roofline_share(cost: dict, seconds: float, pk: dict):
    """(share in %, which bound): the least time the chip could take
    (operations over peak FLOP/s or bytes over peak bandwidth, the larger)
    over the measured time."""
    t_ops = cost["ops"] / pk["flops"]
    t_bytes = cost["bytes"] / pk["hbm_bytes_per_s"]
    bound = "bytes" if t_bytes >= t_ops else "ops"
    return 100.0 * max(t_ops, t_bytes) / seconds, bound
