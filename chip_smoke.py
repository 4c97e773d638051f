#!/usr/bin/env python3
"""On-chip smoke run of the paper's main path at its published widths.

    python chip_smoke.py              # one chip: phases (a)-(d)
    python chip_smoke.py --chips 4    # four chips: BMUF / GTC at W=4

One chip, all at 5x768 / 3,183 senones / 192-d features / top-20:
  (a) device check: the platform must be a TPU;
  (b) teacher target generation: a biLSTM ``TeacherRunner`` with the
      Pallas top-k kernel fills a ``LogitStoreV2``, then ``verify()``;
  (c) student distill updates through ``Trainer``, reading the targets
      back from that store with the Pallas ``sparse_ce`` loss, plus one
      GTC update with the Pallas compression kernel;
  (d) streaming: ``StreamServer`` with the top-k kernel serves firehose
      and interactive streams.

``--chips 4`` runs only the multi-chip path and what it is compared
with: ``BMUFShardMap`` at W=4 (one worker per chip) against
``BMUFShardMap`` at W=4 on a one-chip mesh, and ``GTCShardMap``'s W=4
step against ``simulate_gtc_round``.

Every phase is checked against a plain float32 reference of the same
computation, and every jitted step must hold the compiled Pallas kernels
it is built from, each found by name (``tpu_custom_call``).  Weights are random and all data is synthetic,
made from ``--seed``; scratch output goes to ``experiments/chip_smoke``.
Lines before the last are context (compile times, cold and warm call
times, peak device memory), not metrics.  The last line is the result:
``{"ok": true, "device": {"platform", "kind", "count"}}``.  Any failed
check raises, and the script exits nonzero; so does a run on a machine
with no TPU, which prints no result.
"""
from __future__ import annotations

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.join(HERE, "src")

# Tolerances of the checks against the float32 reference (computed under
# jax.default_matmul_precision("highest")).
#
# LOGIT_RTOL: the system paths run at the TPU's default matmul precision,
# one bfloat16 pass per f32 matmul: each product carries ~2^-8 relative
# rounding, so one matmul's output strays by ~2^-8 of its scale (RMS).
# Six matmuls in sequence (five LSTM layers, the unembedding) grow that
# by ~sqrt(6), and the check bounds the worst of ~10^5 values, a ~5 sigma
# tail: 2^-8 x 2.5 x 5 ~ 5e-2 of the row's logit RMS.  Emitted values
# are max-shifted and stored in bfloat16, so a shifted value may differ
# by twice the logit error plus its own bfloat16 rounding; a rank's
# index must match wherever the reference value is separated from both
# neighbours by more than twice the logit error.
LOGIT_RTOL = 5e-2
# Both sides at "highest" (distill loss and gradients, kernel vs XLA; a
# Trainer update vs a float64 step from the reference gradients): they
# differ only in f32 summation order and the f32 rounding of the updated
# parameters.  Measured on a TPU v5e: 2.5e-7 (loss), 1.4e-6 (gradients).
HIGHEST_RTOL = 1e-4
# The Trainer's default-precision distill loss against the "highest"
# reference at the same parameters: measured 5e-6 relative on a TPU v5e
# at seed 0 (the loss averages ~1,000 frames, so bfloat16 pass errors
# largely cancel); 5e-5 leaves a 10x margin.
LOSS_RTOL = 5e-5
# The Local strategy's optimizer (optim.sgd): gradients clipped to
# global norm CLIP, then Nesterov momentum with coefficient BETA.
CLIP, BETA = 1.0, 0.9


class SmokeFailure(AssertionError):
    """A phase's result disagreed with its reference."""


def check(cond, msg: str):
    if not cond:
        raise SmokeFailure(msg)


def log(phase: str, msg: str):
    print(f"[smoke {phase}] {msg}", flush=True)


def _bootstrap():
    """Put src on the path, apply the env bootstrap (compile cache), and
    import JAX; exit nonzero unless JAX finds a TPU."""
    if not os.path.isdir(os.path.join(SRC, "repro")):
        sys.exit(f"chip_smoke: no {SRC}/repro next to this script — run "
                 f"it from a checkout of the repository")
    sys.path.insert(0, SRC)
    from repro.runtime.env import bootstrap_from_env
    bootstrap_from_env()
    import jax
    try:
        platform = jax.devices()[0].platform
    except RuntimeError as e:           # a backend that failed to start
        sys.exit(f"chip_smoke: JAX found no device: {e}")
    if platform != "tpu":
        sys.exit(f"chip_smoke: no TPU found (JAX platform {platform!r}); "
                 f"this smoke runs only on the chip")
    return jax


# ------------------------------------------------------------- helpers

def assert_kernels(name: str, want: dict, jitted, *args):
    """The compiled program of ``jitted`` at these arguments holds at
    least ``want[kernel]`` compiled calls of each named Pallas kernel —
    no kernel silently falls back to XLA.  Lower it under the matmul
    precision it ran with: that is part of the executable."""
    from repro.kernels._dispatch import compiled_kernels
    found = compiled_kernels(jitted.lower(*args).compile().as_text())
    short = {k: n for k, n in want.items() if found[k] < n}
    check(not short, f"{name}: compiled step holds Pallas kernels "
                     f"{dict(found)}, want at least {want}")
    log(name, f"compiled Pallas kernels {dict(sorted(found.items()))}")


def timed(fn, *args):
    """-> (result, seconds), waiting for the device."""
    import time

    import jax
    t0 = time.perf_counter()
    out = fn(*args)
    jax.block_until_ready(out)
    return out, time.perf_counter() - t0


def highest():
    import jax
    return jax.default_matmul_precision("highest")


def check_topk(name: str, vals, idx, ref_logits, k: int):
    """System emission (max-shifted values, bf16-rounded; indices) of N
    frames against the float32 reference logits (N, V)."""
    import jax
    import numpy as np
    ref = np.asarray(ref_logits, np.float32)
    rv, ri = jax.device_get(jax.lax.top_k(ref, k + 1))
    eps = LOGIT_RTOL * np.sqrt(np.mean(ref ** 2, axis=1, keepdims=True))
    ref_shift = rv[:, :k] - rv[:, :1]
    vals = np.asarray(vals, np.float32)
    err = np.abs(vals - ref_shift)
    allowed = 2 * eps + 2.0 ** -8 * np.abs(ref_shift)
    over = ~(err <= allowed)            # NaN counts as over
    if over.any():
        raise SmokeFailure(
            f"{name}: {int(over.sum())} top-{k} values off the reference, "
            f"worst {np.nanmax(err):.4g} (allowed there "
            f"{allowed[over].min():.4g})")
    gap_before = np.concatenate(
        [np.full((rv.shape[0], 1), np.inf), rv[:, :k - 1] - rv[:, 1:k]], 1)
    gap_after = rv[:, :k] - rv[:, 1:k + 1]
    sep = (gap_before > 2 * eps) & (gap_after > 2 * eps)
    bad = sep & (np.asarray(idx) != ri[:, :k])
    check(not bad.any(), f"{name}: {int(bad.sum())} separated top-{k} "
                         f"ranks carry the wrong senone")
    log(name, f"vs f32 reference: {ref.shape[0]} frames, max value error "
              f"{err.max():.3g} (max err/logit-RMS "
              f"{(err / (eps / LOGIT_RTOL)).max():.3g}), "
              f"{sep.mean():.1%} of ranks index-checked")


def pipeline_config(seed: int):
    from repro.core.ssl_pipeline import PipelineConfig
    pc = PipelineConfig.paper()
    pc.seed = seed
    pc.n_unlabeled = 36             # a few dozen utterances
    pc.n_labeled = 24               # the MVN estimate's utterances
    pc.gen_workers = 1
    pc.prefetch = 0
    return pc


def peak_bytes(jax) -> str:
    stats = jax.devices()[0].memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    return "not reported" if peak is None else f"{peak / 2**20:.1f} MiB"


# ------------------------------------------------------ one-chip phases

def phase_teacher(pipe, out_dir: str, seed: int):
    """(b) TeacherRunner(topk_impl="kernel") -> LogitStoreV2 -> verify."""
    import jax
    import numpy as np

    from repro.core.teacher import TeacherRunner
    from repro.kernels.topk_logits import topk_logits
    from repro.models import build_model
    from repro.pipeline import generate_sharded
    from repro.store import LogitStoreV2

    pc, cfg = pipe.pc, pipe.teacher_cfg
    model = build_model(cfg)
    params = model.init(jax.random.key(seed))
    runner = TeacherRunner(cfg, params, k=pc.topk, topk_impl="kernel")
    batches = [{"feats": b["feats"], "mask": b["mask"]}
               for b in pipe.loader.chunked_batches(
                   *pipe.rng_unlabeled, batch_size=pc.batch,
                   chunk_len=pc.chunk_len, seed=7)]
    batches = [b for b in batches if b["feats"].shape == batches[0]["feats"]
               .shape]                  # one shape: one compiled forward
    check(len(batches) >= 3, f"only {len(batches)} teacher batches")
    _, cold = timed(runner.generate, batches[0])
    _, warm = timed(runner.generate, batches[0])
    log("b", f"teacher forward+top-k, batch {batches[0]['feats'].shape}: "
             f"first call {cold:.2f} s (incl. compile), next {warm:.4f} s "
             f"(context)")
    # the jitted step the runner ran, held to its kernel
    assert_kernels("b teacher step", {"topk_logits_tiles": 1},
                   runner.engine._fwd_dict, params, batches[0])

    store = LogitStoreV2(os.path.join(out_dir, "logit_store"), k=pc.topk,
                         vocab=pc.n_senones)
    rep = generate_sharded(lambda w: runner, batches, store, n_workers=1,
                           ledger_path=os.path.join(out_dir, "ledger.json"))
    n = store.verify()
    check(n == len(batches) == rep["n_shards"],
          f"store verified {n} shards, wrote {rep['n_shards']}, "
          f"want {len(batches)}")
    log("b", f"{n} shards, {store.stats().n_frames} frames verified")

    # reference: the XLA forward at "highest", lax.top_k
    def ref_logits(p, b):
        lens = b["mask"].sum(axis=-1).astype(np.int32)
        h, _ = model.apply(p, b["feats"], lens=lens)
        return model.unembed(p, h)

    with highest():
        ref_fn = jax.jit(ref_logits)
        logits = [ref_fn(params, b) for b in batches]
    sv, si, rl = [], [], []
    for bi, (b, lg) in enumerate(zip(batches, logits)):
        vals, idx = store.read_shard(bi)
        m = np.asarray(b["mask"]).reshape(-1) > 0
        sv.append(np.asarray(vals, np.float32).reshape(-1, pc.topk)[m])
        si.append(np.asarray(idx).reshape(-1, pc.topk)[m])
        rl.append(np.asarray(lg).reshape(-1, pc.n_senones)[m])
    check_topk("b", np.concatenate(sv), np.concatenate(si),
               np.concatenate(rl), pc.topk)

    # the kernel alone, same logits in: exactly lax.top_k
    kern = jax.jit(lambda x: topk_logits(x, pc.topk))
    assert_kernels("b topk_logits", {"topk_logits_tiles": 1}, kern,
                   logits[0].reshape(-1, pc.n_senones))
    for lg in logits:
        x = lg.reshape(-1, pc.n_senones)
        kv, ki = kern(x)
        rv, ri = jax.lax.top_k(x, pc.topk)
        check(np.array_equal(np.asarray(kv), np.asarray(rv))
              and np.array_equal(np.asarray(ki), np.asarray(ri)),
              "b: topk_logits kernel differs from lax.top_k on the same "
              "logits")
    log("b", "topk_logits kernel == lax.top_k exactly on the teacher logits")
    return batches, store


def phase_student(pipe, batches, store, seed: int):
    """(c) Trainer distill updates from the store (sparse_ce kernel) and
    one GTC update with the compression kernel."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.distributed.gtc import GTCConfig, compress_tree
    from repro.launch.steps import make_loss_fn
    from repro.models import build_model
    from repro.runtime.cluster import worker_mesh
    from repro.train import (GTCShardMap, ListSink, Local, Trainer,
                             distill_shard_source)

    pc, cfg = pipe.pc, pipe.student_cfg
    model = build_model(cfg)
    params = model.init(jax.random.key(seed + 1))
    loss_k = make_loss_fn(model, cfg, "distill_topk", distill_kernel=True)
    loss_x = make_loss_fn(model, cfg, "distill_topk", distill_kernel=False)
    source = lambda n: distill_shard_source(batches, store, 0, n, pc.lr)
    first = next(iter(source(1)))
    batch = jax.tree_util.tree_map(jnp.asarray, first.data)
    lr = jnp.asarray(pc.lr, jnp.float32)
    leaves = lambda t: [np.asarray(x, np.float64)
                        for x in jax.tree_util.tree_leaves(t)]

    # loss and grads: kernel vs the XLA path, both at "highest"
    vg = lambda fn: jax.jit(jax.value_and_grad(lambda p, b: fn(p, b)[0]))
    with highest():
        (lref, gref), t_ref = timed(vg(loss_x), params, batch)
        vgk = vg(loss_k)
        (lk, gk), t_k = timed(vgk, params, batch)
        assert_kernels("c distill grad", {"sparse_ce_tiles": 1}, vgk,
                       params, batch)
    log("c", f"distill value_and_grad first call: kernel {t_k:.2f} s, "
             f"XLA {t_ref:.2f} s (incl. compile; context)")
    lref, lk = float(lref), float(lk)
    check(abs(lk - lref) <= HIGHEST_RTOL * abs(lref),
          f"c: kernel loss {lk} vs reference {lref}")
    gmax = max(np.max(np.abs(g)) for g in leaves(gref))
    gerr = max(np.max(np.abs(a - b)) for a, b in
               zip(leaves(gk), leaves(gref)))
    check(gerr <= HIGHEST_RTOL * gmax,
          f"c: kernel grads off by {gerr:.3g} (max |grad| {gmax:.3g})")
    log("c", f"kernel loss {lk!r} vs reference {lref!r}; grad max err "
             f"{gerr:.3g} of max |grad| {gmax:.3g}")

    # one Trainer update at "highest" against a plain float64 step from
    # the reference gradients: clip to global norm CLIP, then the first
    # Nesterov-momentum step from zero momentum, p - lr (1 + BETA) g
    with highest():
        hi = Trainer(Local(clip=CLIP), {"distill_topk": loss_k})
        h0 = hi.init_state(params, seed=seed)
        h1 = hi.fit(h0, source(1), resume=False)
        assert_kernels("c trainer update (highest)", {"sparse_ce_tiles": 1},
                       hi.updates["distill_topk"], h0, first.data, lr)
    g64 = leaves(gref)
    gnorm = np.sqrt(sum(np.sum(g * g) for g in g64))
    scale = min(1.0, CLIP / max(gnorm, 1e-9))
    d_ref = [-pc.lr * (1 + BETA) * scale * g for g in g64]
    d_tr = [a - b for a, b in zip(leaves(h1.params), leaves(params))]
    # the updated parameter is rounded to float32: one ulp of it on top
    ulp = [np.spacing(np.abs(np.asarray(x))).astype(np.float64)
           for x in jax.tree_util.tree_leaves(h1.params)]
    dmax = max(np.max(np.abs(d)) for d in d_ref)
    moved = max(np.max(np.abs(d)) for d in d_tr)
    derr = max(np.max(np.abs(a - b)) for a, b in zip(d_tr, d_ref))
    n_off = sum(int(np.sum(np.abs(a - b) > HIGHEST_RTOL * dmax + u))
                for a, b, u in zip(d_tr, d_ref, ulp))
    check(moved > 0.5 * dmax and n_off == 0,
          f"c: Trainer update off a plain step in {n_off} params: moved "
          f"{moved:.3g}, max error {derr:.3g} of a reference update "
          f"{dmax:.3g}")
    log("c", f"Trainer update at highest vs float64 step: max error "
             f"{derr:.3g} of update {dmax:.3g} (grad norm {gnorm:.4g})")

    # a few Trainer updates reading the store, at default precision
    n_up = min(3, len(batches))
    sink = ListSink()
    tr = Trainer(Local(clip=CLIP), {"distill_topk": loss_k}, metrics=sink)
    state0 = tr.init_state(params, seed=seed)
    state = tr.fit(state0, source(n_up), resume=False)
    losses = sink.values("loss")
    check(len(losses) == n_up and np.all(np.isfinite(losses)),
          f"c: trainer losses {losses}")
    check(abs(losses[0] - lref) <= LOSS_RTOL * abs(lref),
          f"c: first trainer loss {losses[0]!r} vs reference {lref!r}")
    check(int(state.step) == n_up, f"c: step {int(state.step)} != {n_up}")
    moved = max(np.max(np.abs(a - b)) for a, b in
                zip(leaves(state.params), leaves(params)))
    check(moved > 0, "c: default-precision Trainer left params unchanged")
    assert_kernels("c trainer update", {"sparse_ce_tiles": 1},
                   tr.updates["distill_topk"], state0, first.data, lr)
    log("c", f"{n_up} Trainer updates from the store, losses "
             f"{[float(x) for x in losses]}; first vs reference: "
             f"{abs(losses[0] - lref) / abs(lref):.3g} relative")

    # compression kernel == reference on the same gradients
    res0 = jax.tree_util.tree_map(jnp.zeros_like, gref)
    sk, rk = compress_tree(gref, res0, pc.gtc_tau, use_kernel=True)
    sx, rx = compress_tree(gref, res0, pc.gtc_tau, use_kernel=False)
    same = all(np.array_equal(np.asarray(a), np.asarray(b)) for a, b in zip(
        jax.tree_util.tree_leaves((sk, rk)),
        jax.tree_util.tree_leaves((sx, rx))))
    check(same, "c: gtc_compress kernel differs from its reference")

    # one GTC update through the Trainer: kernel vs reference compression
    n_leaves = len(jax.tree_util.tree_leaves(params))
    out = {}
    for use_kernel in (True, False):
        gtr = Trainer(GTCShardMap(GTCConfig(tau=pc.gtc_tau, n_workers=1,
                                            use_kernel=use_kernel),
                                  worker_mesh(1)),
                      {"distill_topk": loss_k})
        g0 = gtr.init_state(params, seed=seed)
        out[use_kernel] = gtr.fit(g0, source(1), resume=False)
        if use_kernel:
            assert_kernels("c GTC update", {"sparse_ce_tiles": 1,
                                            "gtc_compress_flat": n_leaves},
                           gtr.updates["distill_topk"], g0,
                           gtr.strategy.stack([first.data]), lr)
    pk, px = leaves(out[True].params), leaves(out[False].params)
    n_diff = sum(int(np.sum(a != b)) for a, b in zip(pk, px))
    n_all = sum(a.size for a in pk)
    # a send flipped by one ulp of a gradient at the threshold moves that
    # parameter by lr * tau; allow a handful, nothing larger
    dmax = max(np.max(np.abs(a - b)) for a, b in zip(pk, px))
    check(n_diff <= 1e-4 * n_all and dmax <= 1.01 * pc.lr * pc.gtc_tau,
          f"c: GTC kernel update differs in {n_diff}/{n_all} params, max "
          f"{dmax:.3g}")
    log("c", f"GTC update: kernel == reference in {n_all - n_diff}/"
             f"{n_all} params")


def _stream_feats(pipe, n: int, start: int, utts_per_stream: int):
    import numpy as np
    pairs = pipe.loader.featurized(start, n * utts_per_stream)
    return [np.concatenate([f for f, _, _ in
                            pairs[i::n]], axis=0).astype(np.float32)
            for i in range(n)]


def phase_stream(pipe, seed: int):
    """(d) StreamServer(topk_impl="kernel"): firehose + interactive."""
    import jax
    import numpy as np

    from repro.models import build_model
    from repro.serve import SLO_DEFAULT, StreamServer

    pc, cfg = pipe.pc, pipe.student_cfg
    model = build_model(cfg)
    params = model.init(jax.random.key(seed + 2))
    srv = StreamServer(cfg, params, n_slots=4, chunk_frames=16, k=pc.topk,
                       tiers=SLO_DEFAULT, topk_impl="kernel")
    fire = _stream_feats(pipe, 4, 200_000, 3)
    inter = _stream_feats(pipe, 2, 300_000, 1)
    rids = [srv.submit(f, tier="firehose") for f in fire]
    done, t_first = timed(srv.pump)
    rids += [srv.submit(f, tier="interactive") for f in inter]
    rest, t_rest = timed(srv.drain)
    done.update(rest)
    st = srv.stats
    log("d", f"{len(fire)} firehose + {len(inter)} interactive streams, "
             f"{sum(f.shape[0] for f in fire + inter)} frames; first pump "
             f"{t_first:.2f} s (incl. compile), drain {t_rest:.2f} s; "
             f"{st['syncs']} syncs, {st['parked']} parks (context)")
    check(sorted(done) == sorted(rids), f"d: finished {sorted(done)}, "
                                        f"submitted {sorted(rids)}")
    for kw, win in srv._window_jits.items():       # every compiled window
        feats = np.zeros((kw, srv.b, srv.chunk, srv.feat_dim), np.float32)
        lens = np.zeros((kw, srv.b), np.int32)
        assert_kernels(f"d window {kw}", {"topk_logits_tiles": 1}, win,
                       params, srv._state, feats, lens)

    with highest():
        ref = jax.jit(lambda p, x: model.unembed(p, model.apply(p, x)[0]))
        logits = [np.asarray(ref(params, f[None]))[0] for f in fire + inter]
    vals, idx = zip(*(done[r].emissions() for r in rids))
    for v, f in zip(vals, fire + inter):
        check(v.shape[0] == f.shape[0], f"d: {v.shape[0]} emissions for "
                                        f"{f.shape[0]} frames")
    check_topk("d", np.concatenate(vals), np.concatenate(idx),
               np.concatenate(logits), pc.topk)


def one_chip(seed: int, out_dir: str):
    import jax

    from repro.core.ssl_pipeline import SSLPipeline

    pc = pipeline_config(seed)
    pipe = SSLPipeline(pc, out_dir=out_dir)
    log("config", f"student {pipe.student_cfg.n_layers}x"
                  f"{pc.lstm_hidden} lstm, teacher {pipe.teacher_cfg.n_layers}"
                  f"x{pc.lstm_hidden} bilstm, {pc.n_senones} senones, "
                  f"feat_dim {pc.feat_dim}, top-{pc.topk}")
    batches, store = phase_teacher(pipe, out_dir, seed)
    log("b", "PASSED")
    phase_student(pipe, batches, store, seed)
    log("c", "PASSED")
    phase_stream(pipe, seed)
    log("d", "PASSED")
    log("memory", f"peak_bytes_in_use {peak_bytes(jax)} (context)")


# ---------------------------------------------------- four-chip phase

def _distill_batches(rng, n: int, pc):
    """Synthetic distill microbatches from the seed: features and sorted
    top-k teacher targets with distinct senone ids."""
    import numpy as np
    out = []
    for _ in range(n):
        feats = rng.standard_normal(
            (pc.batch, pc.chunk_len, pc.feat_dim)).astype(np.float32)
        vals = -np.sort(rng.exponential(size=(pc.batch, pc.chunk_len,
                                              pc.topk)), axis=-1)
        vals -= vals[..., :1]
        idx = np.argsort(rng.random((pc.batch, pc.chunk_len,
                                     pc.n_senones)), axis=-1)[..., :pc.topk]
        out.append({"feats": feats,
                    "mask": np.ones((pc.batch, pc.chunk_len), np.float32),
                    "topk_vals": vals.astype(np.float16),
                    "topk_idx": idx.astype(np.int32)})
    return out


def _spread_over(name: str, tree, n_dev: int):
    """Every leaf's leading W dim is split one row per device over all
    ``n_dev`` devices."""
    import jax
    for leaf in jax.tree_util.tree_leaves(tree):
        devs = leaf.sharding.device_set
        rows = {s.data.shape[0] for s in leaf.addressable_shards}
        check(len(devs) == n_dev and rows == {leaf.shape[0] // n_dev},
              f"{name}: leaf {leaf.shape} on {len(devs)} devices, shard "
              f"rows {rows}")


def four_chips(seed: int, n_dev: int = 4):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    from repro.core.ssl_pipeline import am_configs
    from repro.distributed import gtc as gtc_lib
    from repro.distributed.bmuf import BMUFConfig
    from repro.launch.steps import make_loss_fn
    from repro.models import build_model
    from repro.runtime.cluster import auto_mesh, worker_mesh
    from repro.train import BMUFShardMap, TrainBatch, Trainer

    devs = jax.devices()
    check(len(devs) == n_dev and len({d.id for d in devs}) == n_dev,
          f"want {n_dev} distinct devices, JAX has {len(devs)}")
    pc = pipeline_config(seed)
    cfg, _ = am_configs(n_layers=pc.n_layers, lstm_hidden=pc.lstm_hidden,
                        n_senones=pc.n_senones, feat_dim=pc.feat_dim)
    model = build_model(cfg)
    params = model.init(jax.random.key(seed + 1))
    loss = make_loss_fn(model, cfg, "distill_topk", distill_kernel=True)
    rng = np.random.default_rng(seed)
    w = n_dev
    mesh = worker_mesh(w)
    check(len({d.id for d in mesh.devices.flat}) == n_dev,
          f"worker mesh spans {mesh.devices.size} devices, want {n_dev}")

    # BMUF: W=4 one worker per chip vs W=4 on a one-chip mesh
    bcfg = BMUFConfig(n_workers=w, block_steps=2)
    n_up = 2
    data = _distill_batches(rng, n_up * bcfg.block_steps * w, pc)
    src = lambda: (TrainBatch(b, pc.lr, "distill_topk") for b in data)
    res = {}
    with jax.default_matmul_precision("highest"):
        one_chip = auto_mesh((1,), ("data",), devices=devs[:1])
        for name, strat in (("shard_map", BMUFShardMap(bcfg, mesh)),
                            ("one_chip", BMUFShardMap(bcfg, one_chip))):
            tr = Trainer(strat, {"distill_topk": loss})
            s0 = tr.init_state(params, seed=seed)
            st, t = timed(lambda: tr.fit(s0, src(), resume=False))
            res[name] = st
            log("4", f"BMUF {name} W={w}: {n_up} block updates in {t:.2f} s "
                     f"(incl. compile; context)")
            if name == "shard_map":
                stacked = strat.stack(data[:bcfg.block_steps * w])
                assert_kernels("4 BMUF shard_map update",
                               {"sparse_ce_tiles": 1},
                               tr.updates["distill_topk"], s0, stacked,
                               jnp.asarray(pc.lr, jnp.float32))
    sm, one = res["shard_map"], res["one_chip"]
    check(int(sm.step) == int(one.step) == n_up, "4: BMUF update count")
    _spread_over("4 BMUF workers", sm.strategy_state["workers"], n_dev)
    _spread_over("4 BMUF optimizer state", sm.opt_state, n_dev)
    leaves = lambda t: [np.asarray(x, np.float64)
                        for x in jax.tree_util.tree_leaves(t)]
    moved = max(np.max(np.abs(a - b)) for a, b in
                zip(leaves(one.params), leaves(params)))
    err = max(np.max(np.abs(a - b)) for a, b in
              zip(leaves(sm.params), leaves(one.params)))
    # at "highest" the two differ only in the order of f32 sums (the
    # cross-chip pmean, batched vs per-chip matmuls)
    check(moved > 0 and err <= HIGHEST_RTOL * 10 * moved,
          f"4: BMUF shard_map params off by {err:.3g} (update {moved:.3g})")
    log("4", f"BMUF four chips == one chip: max param diff {err:.3g} of "
             f"update {moved:.3g}; worker replicas and optimizer state one "
             f"per device")

    # GTC: the sharded W=4 step vs simulate_gtc_round, two rounds
    gcfg = gtc_lib.GTCConfig(tau=pc.gtc_tau, n_workers=w, use_kernel=True)
    capture = lambda p, u, o, lr: (u, o)        # return the applied update
    step = jax.jit(gtc_lib.make_sharded_gtc_train_step(loss, capture, gcfg,
                                                       mesh))
    wrk = NamedSharding(mesh, P("data"))
    gstate = jax.device_put(gtc_lib.gtc_init(params, gcfg), wrk)
    ref_res = [jax.tree_util.tree_map(jnp.zeros_like, params)] * w
    grad = jax.jit(jax.grad(lambda p, b: loss(p, b)[0]))
    with jax.default_matmul_precision("highest"):
        for rnd in range(2):
            bs = _distill_batches(rng, w, pc)
            stacked = jax.tree_util.tree_map(lambda *x: jnp.stack(x), *bs)
            (upd, _, gstate, ms), t = timed(step, params, None, gstate,
                                            stacked, 0.0)
            _spread_over("4 GTC residuals", gstate, n_dev)
            grads = [grad(params, b) for b in bs]
            ref_upd, ref_res = gtc_lib.simulate_gtc_round(
                grads, ref_res, pc.gtc_tau, quantize_int8=True)
            u, r = leaves(upd), leaves(ref_upd)
            n_diff = sum(int(np.sum(a != b)) for a, b in zip(u, r))
            n_all = sum(a.size for a in u)
            dmax = max(np.max(np.abs(a - b)) for a, b in zip(u, r))
            rerr = max(np.max(np.abs(a - b)) for a, b in zip(
                leaves(gstate["residual"]),
                leaves(jax.tree_util.tree_map(lambda *x: jnp.stack(x),
                                              *ref_res))))
            # a send flipped at the threshold moves the average by tau/W
            # and that worker's residual by tau; allow a handful
            check(n_diff <= 1e-4 * n_all
                  and dmax <= 1.0001 * pc.gtc_tau / w
                  and rerr <= 1.0001 * pc.gtc_tau,
                  f"4: GTC round {rnd}: {n_diff}/{n_all} update elements "
                  f"differ (max {dmax:.3g}), residual max err {rerr:.3g}")
            density = float(np.mean(np.asarray(ms["gtc_density"])))
            log("4", f"GTC round {rnd}: shard_map == simulate_gtc_round in "
                     f"{n_all - n_diff}/{n_all} update elements, density "
                     f"{density:.3g}, step {t:.2f} s (context)")
        # the executable that ran: lowered under the same precision
        assert_kernels("4 GTC shard_map step", {
            "sparse_ce_tiles": 1,
            "gtc_compress_flat": len(jax.tree_util.tree_leaves(params))},
            step, params, None, gstate, stacked, 0.0)


def main(argv=None):
    import argparse
    import json
    import shutil

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: only the W=4 BMUF/GTC path across four chips")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    jax = _bootstrap()
    from repro.runtime.env import describe
    log("a", "describe " + json.dumps(describe(), sort_keys=True))
    dev = jax.devices()[0]
    log("a", f"PASSED: platform {dev.platform}, {dev.device_kind}, "
             f"{len(jax.devices())} device(s)")
    out_dir = os.path.join(HERE, "experiments", "chip_smoke")
    shutil.rmtree(out_dir, ignore_errors=True)
    os.makedirs(out_dir)
    if args.chips == 4:
        four_chips(args.seed)
        log("4", "PASSED")
    else:
        one_chip(args.seed, out_dir)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)


if __name__ == "__main__":
    main()
