import os

from repro.runtime.env import bootstrap
bootstrap(host_device_count=512)
# ^ MUST precede the first jax import (jax locks device count on first
# init); runtime.env composes the flag idempotently with any existing
# XLA_FLAGS instead of blindly appending a duplicate.

"""Multi-pod dry-run: prove the distribution config is coherent.

For every (architecture x input shape) pair, lower + compile the step
function against the production mesh — 16x16=256 chips single-pod and
2x16x16=512 chips multi-pod — with ShapeDtypeStruct inputs (no
allocation), then record:

  memory_analysis()  — bytes/device: does it fit 16 GB v5e HBM
  cost_analysis()    — HLO FLOPs + bytes accessed (roofline numerator)
  collective bytes   — parsed from the post-SPMD HLO (utils/hlo.py)

Artifacts: experiments/dryrun/<arch>__<shape>__<mesh>.json — read by
benchmarks/roofline.py for §Roofline.

Usage:
  python -m repro.launch.dryrun --arch qwen2.5-3b --shape train_4k
  python -m repro.launch.dryrun --all [--mesh pod|multipod|both]
  python -m repro.launch.dryrun --all --variant swa   # +swa long_500k rows
"""
import argparse
import json
import sys
import time
import traceback

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import ARCHS, SHAPES, get_arch, get_shape, supports
from repro.distributed import sharding as sh
from repro.launch.mesh import PRODUCTION_DEVICE_KIND, make_production_mesh
from repro.launch import steps as step_lib
from repro.models import build_model
from repro.models.api import abstract_params, input_specs
from repro.utils import hlo as hlo_lib
from repro.utils.trees import map_with_path, param_count

PARAM_DTYPE = jnp.bfloat16        # storage dtype for the dry-run lowering
TOPK = 20                         # the paper's k


def _with_sharding(tree_sds, tree_specs, mesh):
    return jax.tree_util.tree_map(
        lambda s, spec: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=NamedSharding(mesh, spec)),
        tree_sds, tree_specs,
        is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct))


def _cast(tree_sds, dtype):
    def c(s):
        if jnp.issubdtype(s.dtype, jnp.floating):
            return jax.ShapeDtypeStruct(s.shape, dtype)
        return s
    return jax.tree_util.tree_map(
        c, tree_sds, is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct))


def batch_specs_tree(batch_sds, mesh, mode="fsdp_tp"):
    """Shard every batch input's leading (global-batch) dim over
    (pod,data) — or all axes for pure-FSDP; cache entries use the cache
    policy."""
    def spec(path, s):
        if path.startswith("cache"):
            return sh.cache_spec(path.removeprefix("cache/"), s.shape, mesh)
        return sh.batch_spec(mesh, s.shape[0], extra_dims=len(s.shape) - 1,
                             mode=mode)
    return map_with_path(lambda p, a: spec(p, a), batch_sds)


def build_step(cfg, shape, *, loss_kind="distill_topk", vocab_chunk=8192,
               optimizer="momentum", shard_mode="fsdp_tp"):
    """-> (fn, example_args_fn(mesh) -> tuple of sharded SDS trees)."""
    model = build_model(cfg)
    if shape.kind == "train":
        kind = loss_kind
        if cfg.family == "lstm_am" and kind == "distill_topk":
            pass                                  # AM distills over senones
        fn = step_lib.make_train_step(model, cfg, loss_kind=kind,
                                      optimizer=optimizer,
                                      vocab_chunk=vocab_chunk)

        def args(mesh):
            params = _cast(abstract_params(cfg), PARAM_DTYPE)
            pspecs = sh.tree_param_specs(params, mesh, mode=shard_mode)
            opt = jax.eval_shape(
                lambda p: step_lib.init_opt_state(p, optimizer), params)
            ospecs = jax.tree_util.tree_map(
                lambda _: pspecs, {"x": 0})["x"]  # same structure per slot
            # opt state: momentum/adam slots mirror param specs leaf-wise
            ospecs = _opt_specs(opt, pspecs)
            batch = input_specs(cfg, shape,
                                topk=TOPK if kind == "distill_topk" else 0)
            bspecs = batch_specs_tree(batch, mesh, mode=shard_mode)
            # lr: traced replicated scalar (the lr-as-argument step)
            lr = jax.ShapeDtypeStruct(
                (), jnp.float32, sharding=NamedSharding(mesh, P()))
            return ((_with_sharding(params, pspecs, mesh),
                     _with_sharding(opt, ospecs, mesh),
                     _with_sharding(batch, bspecs, mesh), lr),
                    (pspecs, ospecs, bspecs, P()))
        return fn, args

    if shape.kind == "prefill":
        fn = step_lib.make_prefill_step(model, cfg)

        def args(mesh):
            params = _cast(abstract_params(cfg), PARAM_DTYPE)
            pspecs = sh.tree_param_specs(params, mesh, mode=shard_mode)
            batch = input_specs(cfg, shape)
            bspecs = batch_specs_tree(batch, mesh, mode=shard_mode)
            return ((_with_sharding(params, pspecs, mesh),
                     _with_sharding(batch, bspecs, mesh)),
                    (pspecs, bspecs))
        return fn, args

    # decode
    serve = step_lib.make_serve_step(model, cfg)

    def fn(params, cache, tokens):
        return serve(params, cache, tokens)

    def args(mesh):
        params = _cast(abstract_params(cfg), PARAM_DTYPE)
        pspecs = sh.tree_param_specs(params, mesh, mode=shard_mode)
        specs = input_specs(cfg, shape)
        cache, tokens = specs["cache"], specs["tokens"]
        cspecs = map_with_path(lambda p, a: sh.cache_spec(p, a.shape, mesh),
                               cache)
        tspec = sh.batch_spec(mesh, tokens.shape[0],
                              extra_dims=len(tokens.shape) - 1)
        return ((_with_sharding(params, pspecs, mesh),
                 _with_sharding(cache, cspecs, mesh),
                 jax.ShapeDtypeStruct(tokens.shape, tokens.dtype,
                                      sharding=NamedSharding(mesh, tspec))),
                (pspecs, cspecs, tspec))
    return fn, args


def _opt_specs(opt_sds, pspecs):
    """Momentum/adam state: each param-shaped slot inherits param specs;
    scalars (t) replicated."""
    def build(sub):
        if isinstance(sub, jax.ShapeDtypeStruct):
            return P()
        return None
    out = {}
    for k, v in opt_sds.items():
        if isinstance(v, jax.ShapeDtypeStruct):      # scalar like t
            out[k] = P()
        else:
            out[k] = pspecs
    return out


def _lower_compile(cfg, shape, mesh, *, loss_kind, vocab_chunk,
                   shard_mode="fsdp_tp"):
    fn, args_fn = build_step(cfg, shape, loss_kind=loss_kind,
                             vocab_chunk=vocab_chunk,
                             shard_mode=shard_mode)
    (args, _specs) = args_fn(mesh)
    t0 = time.time()
    with jax.set_mesh(mesh):
        jitted = jax.jit(fn, donate_argnums=(0,) if shape.kind != "train"
                         else (0, 1))
        lowered = jitted.lower(*args)
        t_lower = time.time() - t0
        t0 = time.time()
        compiled = lowered.compile()
        t_compile = time.time() - t0
    return compiled, t_lower, t_compile


def dryrun_one(arch: str, shape_name: str, *, multi_pod: bool,
               loss_kind: str = "distill_topk", donate: bool = True,
               vocab_chunk: int = 8192, extra_tag: str = "",
               out_dir: str = "experiments/dryrun", verbose: bool = True,
               probe: bool = True, shard_mode: str = "fsdp_tp",
               remat: bool = False):
    cfg = get_arch(arch)
    if remat:
        cfg = cfg.replace(remat=True)
    shape = get_shape(shape_name)
    ok, why = supports(cfg, shape)
    if not ok:
        return {"arch": arch, "shape": shape_name,
                "mesh": "multipod" if multi_pod else "pod",
                "status": "skipped", "reason": why}
    mesh = make_production_mesh(multi_pod=multi_pod)
    # --- production artifact: scanned segments, chunked attention ---
    compiled, t_lower, t_compile = _lower_compile(
        cfg, shape, mesh, loss_kind=loss_kind, vocab_chunk=vocab_chunk,
        shard_mode=shard_mode)
    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    if isinstance(cost, list):           # older jax: one dict per device
        cost = cost[0] if cost else {}
    txt = compiled.as_text()
    coll = hlo_lib.collective_stats(txt)
    n_dev = mesh.devices.size
    record = {
        "arch": arch, "shape": shape_name,
        "mesh": "multipod" if multi_pod else "pod",
        "status": "ok", "n_devices": int(n_dev),
        "device_kind": PRODUCTION_DEVICE_KIND,
        "tag": extra_tag,
        "loss_kind": loss_kind if shape.kind == "train" else shape.kind,
        "n_params": param_count(abstract_params(cfg)),
        "lower_s": round(t_lower, 2), "compile_s": round(t_compile, 2),
        "flops": float(cost.get("flops", 0.0)),
        "bytes_accessed": float(cost.get("bytes accessed", 0.0)),
        "memory": {
            "argument_bytes": int(mem.argument_size_in_bytes),
            "output_bytes": int(mem.output_size_in_bytes),
            "temp_bytes": int(mem.temp_size_in_bytes),
            "peak_bytes_per_device": int(mem.argument_size_in_bytes
                                         + mem.temp_size_in_bytes),
        },
        "collectives": coll.to_dict(),
        "wire_bytes_per_device": hlo_lib.wire_bytes(coll, n_dev),
    }
    # --- cost probe: unrolled segments + whole-seq attention + one vocab
    # chunk, so cost_analysis / collective parsing see every rep of every
    # op (XLA counts while-loop bodies once — configs/base.py note) ---
    if probe:
        pcfg = cfg.replace(scan_unroll=True, attn_whole_seq=True)
        try:
            pcomp, pl_, pc_ = _lower_compile(
                pcfg, shape, mesh, loss_kind=loss_kind,
                vocab_chunk=max(cfg.vocab_size, 1),
                shard_mode=shard_mode)
            pcost = pcomp.cost_analysis()
            pcoll = hlo_lib.collective_stats(pcomp.as_text())
            record["probe"] = {
                "flops": float(pcost.get("flops", 0.0)),
                "bytes_accessed": float(pcost.get("bytes accessed", 0.0)),
                "collectives": pcoll.to_dict(),
                "wire_bytes_per_device": hlo_lib.wire_bytes(pcoll, n_dev),
                "compile_s": round(pc_, 2),
            }
        except Exception as e:                     # probe is best-effort
            record["probe"] = {"error": repr(e)}
    if out_dir:
        os.makedirs(out_dir, exist_ok=True)
        tag = f"__{extra_tag}" if extra_tag else ""
        fname = f"{arch.replace('/','_')}__{shape_name}__" \
                f"{record['mesh']}{tag}.json"
        with open(os.path.join(out_dir, fname), "w") as f:
            json.dump(record, f, indent=1)
    if verbose:
        gb = record["memory"]["peak_bytes_per_device"] / 2**30 / n_dev
        print(f"OK  {arch:20s} {shape_name:12s} {record['mesh']:8s} "
              f"compile={t_compile:6.1f}s flops={record['flops']:.3e} "
              f"coll={coll.total_bytes/2**30:8.2f}GiB", flush=True)
    return record


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="both",
                    choices=["pod", "multipod", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--variant", default=None, choices=[None, "swa"])
    ap.add_argument("--loss", default="distill_topk",
                    choices=["ce", "distill_topk"])
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--probe", default="off", choices=["on", "off"],
                    help="also compile the cost probe (expensive; used "
                         "for the roofline subset)")
    ap.add_argument("--shard-mode", default="fsdp_tp",
                    choices=["fsdp_tp", "tp", "fsdp"],
                    help="param sharding policy (tp = inference TP-only)")
    ap.add_argument("--remat", action="store_true",
                    help="activation-checkpoint scanned segments")
    ap.add_argument("--tag", default="", help="artifact filename tag")
    args = ap.parse_args(argv)

    meshes = {"pod": [False], "multipod": [True],
              "both": [False, True]}[args.mesh]
    if args.all:
        archs = [a for a in ARCHS if not a.startswith("lstm-am")]
        shapes = list(SHAPES)
    else:
        archs = [args.arch]
        shapes = [args.shape] if args.shape else list(SHAPES)
    if args.variant == "swa":
        archs = [a + "+swa" for a in archs]

    failures = []
    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                try:
                    # cost probes only on the single-pod mesh: §Roofline is
                    # single-pod; the multipod pass proves the pod axis
                    rec = dryrun_one(arch, shape, multi_pod=mp,
                                     loss_kind=args.loss, out_dir=args.out,
                                     probe=(args.probe == "on" and not mp),
                                     shard_mode=args.shard_mode,
                                     remat=args.remat,
                                     extra_tag=args.tag)
                    if rec["status"] == "skipped":
                        print(f"SKIP {arch:20s} {shape:12s} "
                              f"{'multipod' if mp else 'pod':8s} "
                              f"({rec['reason']})", flush=True)
                except Exception as e:
                    failures.append((arch, shape, mp, repr(e)))
                    print(f"FAIL {arch} {shape} "
                          f"{'multipod' if mp else 'pod'}: {e}", flush=True)
                    traceback.print_exc(limit=4)
    if failures:
        print(f"\n{len(failures)} failures"); sys.exit(1)
    print("\nall dry-runs green")


if __name__ == "__main__":
    main()
