"""§Roofline: three-term roofline per (arch x shape) from dry-run artifacts.

Reads experiments/dryrun/*.json (written by launch/dryrun.py), computes

  compute term    = HLO_FLOPs_per_device / peak_FLOPs
  memory term     = HLO_bytes_per_device / HBM_bw
  collective term = wire_bytes_per_device / link_bw

using the cost-probe numbers when present (the scanned production artifact
undercounts loop bodies — see configs/base.py).  cost_analysis() of the
SPMD-partitioned module reports the *per-device* program, so no further
/chips.  Also reports MODEL_FLOPS = 6*N*D (dense) or 6*N_active*D (MoE)
and the usefulness ratio MODEL_FLOPS / HLO_FLOPs.

Hardware: the peaks of the device kind the dry-run record names
(``device_kind``, the part its meshes stand for), from the ``PEAKS``
table; a record without one, or a kind not in the table, is an error.
"""
from __future__ import annotations

import glob
import json
import os
from dataclasses import dataclass


@dataclass(frozen=True)
class Peaks:
    """Published per-chip peaks."""
    flops: float            # dense bf16 FLOP/s
    hbm_bw: float           # HBM bytes/s
    ici_link_bw: float      # bytes/s per inter-chip link


# Keyed by JAX's ``Device.device_kind``.  TPU v5e: Google Cloud
# documentation, "TPU v5e" — 197 TFLOP/s bf16 (393 TOP/s int8), 16 GB of
# HBM at 819 GB/s, 1,600 Gbit/s of inter-chip interconnect over 4 links
# (~50 GB/s each).
PEAKS = {
    "TPU v5 lite": Peaks(flops=197e12, hbm_bw=819e9, ici_link_bw=50e9),
}


def peaks(device_kind: str) -> Peaks:
    """The peaks of ``device_kind``; a device not in the table is an
    error, never a default."""
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}: add them to roofline.PEAKS with "
                       f"their source")
    return PEAKS[device_kind]


def active_params(cfg) -> int:
    """Analytic per-token active parameter count (MoE-aware)."""
    d, v = cfg.d_model, cfg.vocab_size
    n = v * d * (1 if cfg.tie_embeddings else 2)
    hd = cfg.resolved_head_dim
    for seg in cfg.segments:
        for spec in seg.pattern:
            layer = 0
            if spec.mixer in ("attn", "swa"):
                if cfg.mla:
                    m = cfg.mla
                    qk = m.qk_nope_head_dim + m.qk_rope_head_dim
                    layer += d * m.q_lora_rank \
                        + m.q_lora_rank * cfg.n_heads * qk
                    layer += d * (m.kv_lora_rank + m.qk_rope_head_dim)
                    layer += m.kv_lora_rank * cfg.n_heads * \
                        (m.qk_nope_head_dim + m.v_head_dim)
                    layer += cfg.n_heads * m.v_head_dim * d
                else:
                    layer += d * hd * (cfg.n_heads + 2 * cfg.n_kv_heads) \
                        + cfg.n_heads * hd * d
            elif spec.mixer == "rglru":
                w = cfg.lru_width or d
                layer += 2 * d * w + 2 * w * w + w * d
            elif spec.mixer == "mlstm":
                inner = int(cfg.mlstm_proj_factor * d)
                layer += 2 * d * inner + 3 * inner * inner + inner * d
            elif spec.mixer == "slstm":
                layer += d * 4 * d + 4 * d * (d // cfg.n_heads) \
                    + 2 * d * int(cfg.slstm_proj_factor * d)
            if spec.ffn == "mlp":
                layer += 3 * d * cfg.d_ff
            elif spec.ffn == "moe":
                layer += d * cfg.n_experts                      # router
                per_expert = 3 * d * cfg.moe_d_ff
                layer += per_expert * cfg.moe_top_k             # routed
                layer += per_expert * cfg.n_shared_experts      # shared
            n += layer * seg.repeat
    if cfg.encoder is not None:
        enc_layer = d * hd * (cfg.n_heads + 2 * cfg.n_kv_heads) \
            + cfg.n_heads * hd * d + 2 * d * cfg.d_ff
        n += enc_layer * cfg.encoder.n_layers
        # decoder cross attention
        n += cfg.n_layers * 4 * d * cfg.n_heads * hd
    if cfg.family == "lstm_am":
        n = 0
        d_in = cfg.feat_dim
        mult = 2 if "bilstm" in cfg.mixers() else 1
        for _ in range(cfg.n_layers):
            n += mult * (d_in * 4 * cfg.lstm_hidden
                         + cfg.lstm_hidden * 4 * cfg.lstm_hidden)
            d_in = mult * cfg.lstm_hidden
        n += d_in * cfg.n_senones
    return int(n)


def param_bytes(cfg, dtype_bytes: int = 2) -> int:
    return active_params_total(cfg) * dtype_bytes


def active_params_total(cfg) -> int:
    """Total stored params (all experts), for memory accounting."""
    na = active_params(cfg)
    for seg in cfg.segments:
        for spec in seg.pattern:
            if spec.ffn == "moe":
                per_expert = 3 * cfg.d_model * cfg.moe_d_ff
                na += per_expert * (cfg.n_experts - cfg.moe_top_k) \
                    * seg.repeat
    return na


def memory_traffic(cfg, shape, n_devices: int, record: dict) -> float:
    """Analytic per-device HBM traffic per step (bytes).

    cost_analysis() bytes are pre-fusion operand counts (order-of-magnitude
    overcounts), so the memory roofline term uses a standard analytic
    model instead:
      train:   3x params (bf16 read + grad write + opt update) +
               activation traffic ~ 8 bytes x L x tokens x d_model
               (fwd write + bwd read + recompute under remat)
      prefill: params read + 4 bytes x L x tokens x d_model
      decode:  params read + full KV/state cache read per token
    """
    pb = param_bytes(cfg)
    d = cfg.d_model
    L = max(cfg.n_layers, 1)
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        traffic = 3 * pb * 2 + 8.0 * L * tokens * d
    elif shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        traffic = pb + 4.0 * L * tokens * d
    else:
        cache = record["memory"]["argument_bytes"] / n_devices  # incl cache
        traffic = pb / n_devices + cache
        return traffic
    return traffic / n_devices


def model_flops(cfg, shape, n_devices: int) -> float:
    """6*N_active*D training / 2*N_active*D prefill / 2*N_active*B decode,
    GLOBAL; divide by devices for the per-device roofline."""
    na = active_params(cfg)
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        if cfg.encoder is not None:
            tokens = shape.global_batch * min(cfg.max_target_len,
                                              shape.seq_len)
        return 6.0 * na * tokens
    if shape.kind == "prefill":
        return 2.0 * na * shape.global_batch * shape.seq_len
    return 2.0 * na * shape.global_batch          # decode: one token


@dataclass
class Roofline:
    arch: str
    shape: str
    tag: str
    compute_s: float
    memory_s: float
    collective_s: float
    dominant: str
    model_flops: float
    hlo_flops: float
    useful_ratio: float
    corrected: bool
    peak_gb: float

    def suggestion(self) -> str:
        if self.dominant == "collective":
            return ("reduce resharding: fewer all-gathers via better "
                    "param/activation layout or collective overlap")
        if self.dominant == "memory":
            return ("raise arithmetic intensity: fuse/bigger tiles, bf16 "
                    "cache, avoid full-logit materialization")
        if self.useful_ratio < 0.4:
            return ("cut non-model FLOPs: masked attention blocks, MoE "
                    "capacity padding, remat recompute")
        return "near compute roofline: overlap collectives into the MXU"


def analyze(record: dict, cfg, shape) -> Roofline:
    peak = peaks(record.get("device_kind"))
    n_dev = record["n_devices"]
    probe = record.get("probe") or {}
    corrected = "flops" in probe
    mf = model_flops(cfg, shape, n_dev)
    if corrected:
        flops = probe["flops"]
        wire = probe["wire_bytes_per_device"]
    else:
        # scanned production artifact: XLA counts loop bodies once, so raw
        # flops undercount by ~depth.  Best available per-device estimate:
        # max(analytic MODEL_FLOPS/chips, raw HLO) — analytic is a lower
        # bound on executed flops, raw catches non-model overheads when
        # the model is shallow.  wire: raw, flagged (collectives inside
        # scan bodies count once; probe rows are exact).
        flops = max(mf / n_dev, record["flops"])
        wire = record["wire_bytes_per_device"]
    # memory term: analytic traffic model for ALL rows — cost_analysis
    # bytes are pre-fusion operand counts, overcounted by orders of
    # magnitude (probe rows additionally materialize whole-seq attention)
    byts = memory_traffic(cfg, shape, n_dev, record)
    terms = {"compute": flops / peak.flops,
             "memory": byts / peak.hbm_bw,
             "collective": wire / peak.ici_link_bw}
    dom = max(terms, key=terms.get)
    return Roofline(
        arch=record["arch"], shape=record["shape"],
        tag=record.get("tag", ""),
        compute_s=terms["compute"], memory_s=terms["memory"],
        collective_s=terms["collective"], dominant=dom,
        model_flops=mf, hlo_flops=flops * n_dev,
        useful_ratio=mf / max(flops * n_dev, 1.0),
        corrected=corrected,
        peak_gb=record["memory"]["peak_bytes_per_device"] / n_dev / 2**30)


def run(dryrun_dir: str = "experiments/dryrun",
        out_dir: str = "experiments/benchmarks", mesh: str = "pod"):
    from repro.configs import get_arch, get_shape
    os.makedirs(out_dir, exist_ok=True)
    rows = []
    for path in sorted(glob.glob(os.path.join(dryrun_dir, "*.json"))):
        with open(path) as f:
            rec = json.load(f)
        if rec.get("status") != "ok" or rec.get("mesh") != mesh:
            continue
        cfg = get_arch(rec["arch"])
        shape = get_shape(rec["shape"])
        rows.append(analyze(rec, cfg, shape))

    lines = ["| arch | shape | variant | compute s | memory s | "
             "collective s | dominant | useful | GB/chip | src |",
             "|---|---|---|---|---|---|---|---|---|---|"]
    for r in sorted(rows, key=lambda r: (r.arch, r.shape, r.tag)):
        lines.append(
            f"| {r.arch} | {r.shape} | {r.tag or 'base'} | "
            f"{r.compute_s:.3e} | "
            f"{r.memory_s:.3e} | {r.collective_s:.3e} | {r.dominant} | "
            f"{r.useful_ratio:.2f} | {r.peak_gb:.1f} | "
            f"{'probe' if r.corrected else 'analytic'} |")
    table = "\n".join(lines)
    with open(os.path.join(out_dir, f"roofline_{mesh}.md"), "w") as f:
        f.write(table + "\n")
    with open(os.path.join(out_dir, f"roofline_{mesh}.json"), "w") as f:
        json.dump([r.__dict__ | {"suggestion": r.suggestion()}
                   for r in rows], f, indent=1)
    return rows, table
