"""Kernel micro-benchmarks: interpret-mode correctness timings + the
roofline-relevant tile accounting (VMEM working set, arithmetic intensity).

Wall-clock on CPU interpret mode is NOT TPU perf; the value here is the
analytic table: bytes touched, FLOPs, and VMEM footprint per tile — the
numbers the BlockSpec choices are justified by (see EXPERIMENTS.md §Perf).
Every kernel section also carries an in-bench parity assert against its
oracle (the ``parity`` field is what tier2-kernels gates on) and, for
the decode-path kernels, ``pct_roofline`` = min(1, AI / machine balance)
— the fraction of HBM-bound peak the kernel's arithmetic intensity can
sustain on the reference part (TPU v5e).

  PYTHONPATH=src python benchmarks/kernels_bench.py

Writes experiments/benchmarks/kernels.json and mirrors it to the
repo-root BENCH_kernels.json (the tier2-kernels CI artifact).
"""
from __future__ import annotations

import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.launch.mesh import PRODUCTION_DEVICE_KIND

try:
    from benchmarks.roofline import peaks
except ImportError:                       # run as a script from benchmarks/
    from roofline import peaks

# flops/byte at the roofline ridge of the v5e, the part every kernel here
# is tiled for; the analytic roofline shares below are against it
_PEAK = peaks(PRODUCTION_DEVICE_KIND)
MACHINE_BALANCE = _PEAK.flops / _PEAK.hbm_bw


def _pct_roofline(flops: float, bytes_: float) -> float:
    """Fraction of peak a kernel of this arithmetic intensity can reach:
    memory-bound kernels sit at AI / machine-balance, compute-bound ones
    at the flat top."""
    return round(min(1.0, (flops / bytes_) / MACHINE_BALANCE), 4)


def _t(f, *a, n=3):
    f(*a)
    t0 = time.time()
    for _ in range(n):
        r = f(*a)
    jax.block_until_ready(r)
    return (time.time() - t0) / n


def run(out_dir: str = "experiments/benchmarks"):
    os.makedirs(out_dir, exist_ok=True)
    rng = np.random.default_rng(0)
    out = {}

    # ---- topk_logits: the paper's target-generation hot loop ----
    from repro.kernels import topk_logits, topk_logits_ref
    v, k, rows = 3183, 20, 256
    x = jnp.asarray(rng.normal(size=(rows, v)), jnp.float32)
    t_kern = _t(lambda a: topk_logits(a, k, interpret=True), x)
    t_ref = _t(lambda a: topk_logits_ref(a, k), x)
    out["topk_logits"] = {
        "shape": [rows, v], "k": k,
        "interpret_s": round(t_kern, 4), "ref_s": round(t_ref, 4),
        "bytes_in_per_row": v * 4, "bytes_out_per_row": k * 6,
        "compression_x": round(v * 4 / (k * 6), 1),
        "vmem_tile_bytes": 128 * 2048 * 4,
    }

    # ---- sparse_ce: fused lse+gather vs full-logit materialization ----
    from repro.kernels import sparse_ce_lse_gather, sparse_ce_lse_gather_ref
    t, d, v = 128, 512, 32768
    h = jnp.asarray(rng.normal(size=(t, d)), jnp.float32) * 0.1
    w = jnp.asarray(rng.normal(size=(d, v)), jnp.float32) * 0.1
    idx = jnp.asarray(rng.integers(0, v, (t, 20)), jnp.int32)
    t_kern = _t(lambda *a: sparse_ce_lse_gather(*a, interpret=True),
                h, w, idx)
    t_ref = _t(sparse_ce_lse_gather_ref, h, w, idx)
    out["sparse_ce"] = {
        "shape": {"T": t, "D": d, "V": v},
        "interpret_s": round(t_kern, 4), "ref_s": round(t_ref, 4),
        "full_logit_bytes": t * v * 4,
        "fused_state_bytes": t * (2 + 20) * 4,
        "hbm_saving_x": round(v / 22, 1),
    }

    # ---- swa_attention: banded grid vs dense flash ----
    from repro.kernels import swa_attention, swa_attention_ref
    s, w_, hd = 1024, 256, 128
    q = jnp.asarray(rng.normal(size=(1, 2, s, hd)), jnp.float32) * 0.3
    kk = jnp.asarray(rng.normal(size=(1, 2, s, hd)), jnp.float32) * 0.3
    vv = jnp.asarray(rng.normal(size=(1, 2, s, hd)), jnp.float32)
    t_kern = _t(lambda *a: swa_attention(*a, interpret=True), q, kk, vv, w_)
    dense_flops = 4 * s * s * hd
    banded_flops = 4 * s * (w_ + 128) * hd
    out["swa_attention"] = {
        "S": s, "window": w_, "interpret_s": round(t_kern, 4),
        "dense_flops": dense_flops, "banded_flops": banded_flops,
        "flop_saving_x": round(dense_flops / banded_flops, 1),
        "long_500k_saving_x": round(524_288 / (4096 + 128), 1),
    }

    # ---- gtc_compress: fused pass vs 4-op unfused chain ----
    from repro.kernels import gtc_compress
    g = jnp.asarray(rng.normal(size=(1 << 20,)), jnp.float32) * 1e-3
    r = jnp.zeros((1 << 20,), jnp.float32)
    t_kern = _t(lambda *a: gtc_compress(*a, 1e-3, interpret=True), g, r)
    n = g.size
    out["gtc_compress"] = {
        "n": n, "interpret_s": round(t_kern, 4),
        "fused_hbm_bytes": 4 * n * 4,        # 2 reads + 2 writes
        "unfused_hbm_bytes": 10 * n * 4,     # acc/mask/send/resid round-trips
        "hbm_saving_x": 2.5,
    }

    # ---- decode_attention: fused RoPE + ring write + masked SDPA ----
    from repro.kernels import decode_attention, decode_attention_ref
    b, hq, hkv, s_, hd = 4, 4, 2, 64, 64
    g = hq // hkv
    q = jnp.asarray(rng.normal(size=(b, hq, 1, hd)), jnp.float32) * 0.3
    kn = jnp.asarray(rng.normal(size=(b, hkv, 1, hd)), jnp.float32) * 0.3
    vn = jnp.asarray(rng.normal(size=(b, hkv, 1, hd)), jnp.float32)
    ck = jnp.asarray(rng.normal(size=(b, hkv, s_, hd)), jnp.bfloat16)
    cv = jnp.asarray(rng.normal(size=(b, hkv, s_, hd)), jnp.bfloat16)
    pos = jnp.asarray(rng.integers(1, s_, (b,)), jnp.int32)
    kw = dict(rope_theta=10_000.0)
    o_k, nk_k, nv_k = decode_attention(q, kn, vn, ck, cv, pos,
                                       use_kernel=True, interpret=True,
                                       **kw)
    o_r, nk_r, nv_r = decode_attention_ref(q, kn, vn, ck, cv, pos, **kw)
    parity = (bool(jnp.allclose(o_k, o_r, atol=1e-5))
              and bool(jnp.array_equal(nk_k, nk_r))
              and bool(jnp.array_equal(nv_k, nv_r)))
    t_kern = _t(lambda *a: decode_attention(*a, use_kernel=True,
                                            interpret=True, **kw),
                q, kn, vn, ck, cv, pos)
    t_ref = _t(lambda *a: decode_attention_ref(*a, **kw),
               q, kn, vn, ck, cv, pos)
    kv_bytes = 2 * b * hkv * s_ * hd * 2                 # bf16 K+V caches
    flops = 4 * b * hq * s_ * hd                         # QK^T + PV
    fused_bytes = 2 * kv_bytes + (b * hq + 2 * b * hkv) * hd * 4 * 2
    # unfused XLA tail: row-update read+write of both caches, then the
    # attention re-reads them and materializes softmax scores twice
    unfused_bytes = 4 * kv_bytes + 2 * b * hq * s_ * 4 * 2 + fused_bytes
    out["decode_attention"] = {
        "shape": {"B": b, "Hq": hq, "Hkv": hkv, "S": s_, "hd": hd,
                  "groups": g},
        "interpret_s": round(t_kern, 4), "ref_s": round(t_ref, 4),
        "parity": parity,
        "flops": flops, "fused_hbm_bytes": fused_bytes,
        "unfused_hbm_bytes": unfused_bytes,
        "hbm_saving_x": round(unfused_bytes / fused_bytes, 2),
        "arith_intensity": round(flops / fused_bytes, 2),
        "pct_roofline": _pct_roofline(flops, fused_bytes),
        "vmem_tile_bytes": (s_ * 128 * 2 + 8 * 128) * 4 * 2,
    }

    # ---- topk_sample: fused top-k + truncated-nucleus Gumbel pick ----
    from repro.kernels import topk_sample, topk_sample_ref
    from repro.kernels.topk_sample import gumbel_rows
    rows, v, k_cap = 64, 4096, 32
    logits = jnp.asarray(rng.normal(size=(rows, v)), jnp.float32)
    temp = jnp.full((rows,), 0.8, jnp.float32)
    tk = jnp.full((rows,), 20, jnp.int32)
    tp = jnp.full((rows,), 0.95, jnp.float32)
    seeds = jnp.arange(rows, dtype=jnp.int32)
    pos_r = jnp.asarray(rng.integers(0, 63, (rows,)), jnp.int32)
    v_k, i_k, t_k = topk_sample(logits, temp, tk, tp, seeds, pos_r,
                                use_kernel=True, interpret=True)
    gum = gumbel_rows(seeds, pos_r, k_cap)
    v_r, i_r, t_r = topk_sample_ref(logits, temp, tk, tp, gum)
    parity = (bool(jnp.array_equal(v_k, v_r))
              and bool(jnp.array_equal(i_k, i_r))
              and bool(jnp.array_equal(t_k, t_r)))
    t_kern = _t(lambda *a: topk_sample(*a, use_kernel=True, interpret=True),
                logits, temp, tk, tp, seeds, pos_r)
    t_ref = _t(lambda l, s, p: topk_sample(l, temp, tk, tp, s, p,
                                           use_kernel=False),
               logits, seeds, pos_r)
    flops = rows * v * k_cap                  # k_cap max-extraction sweeps
    bytes_ = rows * v * 4 + rows * (k_cap * 8 + 4)
    out["topk_sample"] = {
        "shape": {"rows": rows, "V": v, "k_cap": k_cap},
        "interpret_s": round(t_kern, 4), "ref_s": round(t_ref, 4),
        "parity": parity,
        "flops": flops, "hbm_bytes": bytes_,
        "argsort_bytes": rows * v * (4 + 4 + 4) * 2,   # sorted vals+order
        "arith_intensity": round(flops / bytes_, 2),
        "pct_roofline": _pct_roofline(flops, bytes_),
    }

    # ---- sparse_ce distill route: chunked XLA loss vs kernel reroute ----
    from repro.core.distill import chunked_topk_distill_ce
    bt, st, d, v, kk2 = 2, 64, 512, 32768, 20
    h3 = jnp.asarray(rng.normal(size=(bt, st, d)), jnp.float32) * 0.1
    w2 = jnp.asarray(rng.normal(size=(d, v)), jnp.float32) * 0.1
    tv = jnp.asarray(rng.normal(size=(bt, st, kk2)), jnp.float32)
    ti = jnp.asarray(rng.integers(0, v, (bt, st, kk2)), jnp.int32)
    loss_x = chunked_topk_distill_ce(h3, w2, tv, ti, chunk=4096)
    loss_k = chunked_topk_distill_ce(h3, w2, tv, ti, use_kernel=True,
                                     interpret=True)
    parity = bool(jnp.allclose(loss_x, loss_k, atol=1e-5))
    t_kern = _t(lambda *a: chunked_topk_distill_ce(*a, use_kernel=True,
                                                   interpret=True),
                h3, w2, tv, ti)
    t_ref = _t(lambda *a: chunked_topk_distill_ce(*a, chunk=4096),
               h3, w2, tv, ti)
    t_ = bt * st
    flops = 2 * t_ * d * v
    fused_bytes = (t_ * d + d * v) * 4 + t_ * (kk2 * 8 + 4)
    out["sparse_ce_distill"] = {
        "shape": {"T": t_, "D": d, "V": v, "k": kk2},
        "interpret_s": round(t_kern, 4), "ref_s": round(t_ref, 4),
        "parity": parity,
        "loss_xla": float(loss_x), "loss_kernel": float(loss_k),
        "flops": flops, "fused_hbm_bytes": fused_bytes,
        "full_logit_bytes": fused_bytes + t_ * v * 4 * 2,
        "arith_intensity": round(flops / fused_bytes, 2),
        "pct_roofline": _pct_roofline(flops, fused_bytes),
    }

    with open(os.path.join(out_dir, "kernels.json"), "w") as f:
        json.dump(out, f, indent=1)
    # repo-root mirror: the tier2-kernels CI artifact
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "BENCH_kernels.json"), "w") as f:
        json.dump(out, f, indent=1)
    return out


if __name__ == "__main__":
    res = run()
    for name, rec in res.items():
        pr = rec.get("pct_roofline")
        tail = "" if pr is None else f"  {pr:.1%} of roofline"
        par = rec.get("parity")
        ptxt = "" if par is None else f"  parity={par}"
        print(f"{name:<18}{rec['interpret_s']:>9.4f}s interpret{ptxt}{tail}")
    bad = [n for n, r in res.items() if r.get("parity") is False]
    assert not bad, f"kernel parity failed: {bad}"
    print("wrote experiments/benchmarks/kernels.json + BENCH_kernels.json")
