"""Readings that set the LM teacher cell's limits: the program's emission
gaps on many seeds (the lower readings), and on a few the control's (the
reference with fp8 operands in the program's place) and three planted
faults' (the upper readings), at the cell's own size on the chip.

    python benchmarks/chip/tests/controls_lm.py --seeds 2001-2020 \
        --controls 2 --faults 1 --seconds 2

For each seed: set-up, a short window at the cell's own load, then the
stored rows' gaps as a benchmark run computes them, summarised as the
maximum, the mean and quantiles of the per-position gap (each position's
widest rank).  On the first ``--controls`` seeds also the fp8- and
bf16-operand references in the program's place.  On the first
``--faults`` seeds also the program rerun on the sampled sequences with a
fault planted (the busiest expert of each layer zeroed, ``drop_expert``;
the shared experts left out, ``no_shared``; YaRN's mscale left out of the
softmax scale, ``no_mscale``), and the share of (position, MoE layer)
pairs whose top-k experts differ between the program and the reference
(``route_flips``).  One JSON line per seed.  The benchmark's own runs
never run this.

``--cpu`` runs the tiny stand-in of the cell on the CPU (``tiny_lm.py``).
"""
from __future__ import annotations

import argparse
import contextlib
import gc
import json
import os
import shutil
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
import tiny_lm  # noqa: E402  (puts the benchmark and the program on the path)

QUANTILES = (0.5, 0.9, 0.99)


def summary(gap):
    """Per-(position, rank) gaps -> the numbers a limit could be set on."""
    import numpy as np
    per_pos = gap.max(axis=-1)
    out = {"max": float(per_pos.max()), "mean": float(gap.mean())}
    for q in QUANTILES:
        out[f"p{int(q * 100)}"] = float(np.quantile(per_pos, q))
    return out


def gap_array(vals, idx, ref_lg):
    import jax.numpy as jnp
    import numpy as np

    from bench import ref_lstm_am as ref
    return np.asarray(ref.emission_gap(jnp.asarray(vals), jnp.asarray(idx),
                                       jnp.asarray(ref_lg)))


@contextlib.contextmanager
def fault(name):
    """Plant ``name`` in the program for the duration."""
    import jax.numpy as jnp
    import numpy as np

    from repro.models import mla, moe
    saved = (moe.dispatch_combine, moe.moe_apply, mla.softmax_scale)
    if name == "drop_expert":
        orig = moe.dispatch_combine

        def dispatch_combine(params, cfg, xt, top_p, top_i):
            busiest = jnp.argmax(jnp.bincount(top_i.reshape(-1),
                                              length=cfg.n_experts))
            return orig(params, cfg, xt,
                        jnp.where(top_i == busiest, 0.0, top_p), top_i)
        moe.dispatch_combine = dispatch_combine
    elif name == "no_shared":
        orig_apply = moe.moe_apply
        moe.moe_apply = lambda p, cfg, x: orig_apply(
            p, cfg.replace(n_shared_experts=0), x)
    elif name == "no_mscale":
        mla.softmax_scale = lambda cfg: 1.0 / np.sqrt(
            cfg.mla.qk_nope_head_dim + cfg.mla.qk_rope_head_dim)
    try:
        yield
    finally:
        moe.dispatch_combine, moe.moe_apply, mla.softmax_scale = saved


def program_topk(drv, seqs, pos):
    """The program (a fresh runner, so that a planted fault is traced) on
    the sampled sequences: (vals, idx) at ``pos``."""
    import jax
    import numpy as np

    from bench import lm, ref_deepseek_v2 as ref
    from repro.core.teacher import TeacherRunner
    with jax.default_device(drv.run.devices[0]):
        params = ref.init_params(drv.cfg, drv.run.seed)
    runner = TeacherRunner(lm.model_config(drv.cfg), params, k=drv.tf["k"])
    v, i = runner.forward_topk({"tokens": seqs})
    v = np.asarray(v, np.float32).reshape(-1, v.shape[-1])[pos]
    i = np.asarray(i).reshape(-1, i.shape[-1])[pos]
    del runner, params
    gc.collect()                    # the engine's cycle holds the weights
    return v, i


def route_flips(drv, seqs, pos):
    """Share of (sampled position, MoE layer) pairs, and of sampled
    positions, whose top-k expert sets differ between the program (bf16,
    as it runs) and the reference (float32), each on its own residual
    stream."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from bench import lm, ref_deepseek_v2 as ref
    from repro.models import build_model, layers as L, mla, moe
    from repro.models.transformer import block_apply
    cfg, mcfg = drv.cfg, lm.model_config(drv.cfg)
    eps, k = cfg["rms_norm_eps"], cfg["num_experts_per_tok"]
    s = seqs.shape[1]
    ar = jnp.arange(s)
    spec = {False: mcfg.segments[0].pattern[0],
            True: mcfg.segments[-1].pattern[0]}

    def p_route(gp, x):
        x = x + mla.mla_apply(gp["mixer"], mcfg, L.norm_apply(
            gp["norm1"], x, mcfg.norm), ar)
        h = L.norm_apply(gp["norm2"], x, mcfg.norm)
        return moe.route(gp["ffn"], mcfg, h.reshape(s, -1))[3]

    def r_route(rp, x):
        x = x + ref.mla(rp["mixer"], ref.rms_norm(rp["norm1"], x, eps), cfg,
                        "f32")
        h = ref.rms_norm(rp["norm2"], x, eps)[0]
        return jax.lax.top_k(jax.nn.softmax(ref.mm(h, rp["ffn"]["router"]),
                                            -1), k)[1]

    p_route, r_route = jax.jit(p_route), jax.jit(r_route)
    p_block = jax.jit(lambda gp, x, m: block_apply(gp, mcfg, spec[m], x,
                                                   ar)[0], static_argnums=2)
    r_block = jax.jit(lambda rp, x, m: ref.block(rp, x, m, cfg),
                      static_argnums=2)
    flips = []
    with jax.default_device(drv.run.devices[0]), \
            jax.default_matmul_precision("highest"):
        params = ref.init_params(cfg, drv.run.seed)
        model = build_model(mcfg)
        for i in range(len(seqs)):
            toks = jnp.asarray(seqs[i:i + 1])
            x = model.embed(params, toks)
            xr = params["embed"][toks].astype(jnp.float32)
            per_layer = []
            for lp, is_moe in ref.layers(params, cfg):
                if is_moe:
                    with jax.default_matmul_precision("default"):
                        tp = np.sort(np.asarray(p_route(lp, x)), -1)
                    tr = np.sort(np.asarray(r_route(lp, xr)), -1)
                    per_layer.append(np.any(tp != tr, axis=-1))
                with jax.default_matmul_precision("default"):
                    x = p_block(lp, x, is_moe)
                xr = r_block(lp, xr, is_moe)
            flips.append(np.stack(per_layer, axis=1))       # (S, L)
        del params
    diff = np.concatenate(flips)[pos]
    return {"pairs": float(diff.mean()),
            "positions": float(diff.any(axis=1).mean())}


def readings(cell, seed, seconds, controls: bool, faults: bool, out_dir):
    import jax
    import jax.numpy as jnp

    from bench import harness, lm, ref_lstm_am
    devs = jax.devices()[:cell.chips]
    run = harness.Run(cell=cell, seed=seed, seconds=seconds, trace=False,
                      out_dir=out_dir, devices=devs,
                      device_kind=devs[0].device_kind)
    drv = cell.driver().Driver(run)
    t0 = time.perf_counter()
    drv.setup()
    res = drv.window(seconds)
    drv.release()
    out = {"seed": seed, "failed": res["failed"],
           "attempted": res["attempted"],
           "expert_load": res.get("expert_loads"),
           "checked": drv.check()}
    pick, seqs, pos = drv.sample()
    ref_lg = drv._ref
    out["program"] = summary(gap_array(*drv.stored(pick, pos), ref_lg))
    if controls:
        for prec in ("fp8", "bf16"):
            lg = lm.reference_logits(drv.cfg, seed, seqs, pos, devs[0],
                                     prec=prec)
            v, i = ref_lstm_am.emit_topk(jnp.asarray(lg), drv.tf["k"])
            out[prec] = summary(gap_array(v, i, ref_lg))
    if faults:
        for name in ("drop_expert", "no_shared", "no_mscale"):
            with fault(name):
                out[name] = summary(gap_array(
                    *program_topk(drv, seqs, pos), ref_lg))
        out["route_flips"] = route_flips(drv, seqs, pos)
    out["run_s"] = time.perf_counter() - t0
    return out


def seeds_of(spec: str):
    lo, _, hi = spec.partition("-")
    return list(range(int(lo), int(hi or lo) + 1))


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seeds", default="2001-2020")
    ap.add_argument("--controls", type=int, default=2)
    ap.add_argument("--faults", type=int, default=1,
                    help="seeds (of the first) that also read the faults "
                         "and the route flips")
    ap.add_argument("--seconds", type=float, default=2.0)
    ap.add_argument("--cpu", action="store_true")
    args = ap.parse_args(argv)
    if not args.cpu:
        from repro.runtime.env import bootstrap_from_env
        bootstrap_from_env()
    from bench import harness, loader
    cell = tiny_lm.tiny_lm_cell() if args.cpu \
        else loader.resolve("lm-teacher-gen")
    if not args.cpu:
        harness.devices_for(cell)
        harness.configure_cache()
    out_dir = os.path.join(loader.ROOT, ".bench_out", "controls_lm")
    for n, seed in enumerate(seeds_of(args.seeds)):
        shutil.rmtree(out_dir, ignore_errors=True)
        os.makedirs(out_dir)
        print(json.dumps(readings(cell, seed, args.seconds,
                                  n < args.controls, n < args.faults,
                                  out_dir)), flush=True)
    shutil.rmtree(out_dir, ignore_errors=True)


if __name__ == "__main__":
    main()
