"""Unified Trainer API (ISSUE 2): compile-count pinning, strategy
equivalences through Trainer.fit, mid-stream resume, data sources,
metrics sinks, and TrainState checkpoint round-trips."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import CheckpointStore
from repro.distributed.bmuf import BMUFConfig
from repro.distributed.gtc import GTCConfig
from repro.optim import momentum_init
from repro.runtime.cluster import worker_mesh
from repro.train import (BMUFShardMap, GTCShardMap, JsonlSink, ListSink,
                         Local, TrainBatch, Trainer, TrainState, chain,
                         epoch_source, make_sgd_step)

D = 8


def quad_loss(params, batch):
    e = batch["x"] @ params["w"] - batch["y"]
    return jnp.mean(e ** 2), {"loss": jnp.mean(e ** 2)}


def _problem(seed=0, n=64):
    rng = np.random.default_rng(seed)
    w_true = rng.normal(size=(D,))
    x = rng.normal(size=(n, D)).astype(np.float32)
    y = (x @ w_true).astype(np.float32)
    return {"x": jnp.asarray(x), "y": jnp.asarray(y)}


def _params():
    return {"w": jnp.zeros((D,))}


def _source(batch, lrs, loss="quad"):
    return [TrainBatch(batch, lr, loss) for lr in lrs]


# ------------------------------------------------------- compile counts

def test_single_compile_across_lr_phases():
    """The tentpole perf fix: lr is traced, so an LR schedule sweeping
    many phases reuses ONE executable per (loss kind, batch shape) —
    the seed pipeline re-jitted its step on every phase change."""
    batch = _problem()
    tr = Trainer(Local(clip=0.0), {"quad": quad_loss})
    state = tr.init_state(_params())
    lrs = [0.1 * (0.85 ** i) for i in range(6)]     # 6 distinct lr phases
    state = tr.fit(state, _source(batch, lrs), resume=False)
    assert int(state.step) == 6
    assert tr.updates["quad"]._cache_size() == 1    # one compile, 6 lrs


def test_make_train_step_single_compile():
    """launch.steps.make_train_step: same property on the real AM step
    (the ssl_pipeline re-jit regression pin)."""
    from repro.configs.lstm_am_7khr import CONFIG
    from repro.configs.base import LayerSpec, Segment
    from repro.launch.steps import init_opt_state, make_train_step
    from repro.models import build_model

    cfg = CONFIG.replace(
        lstm_hidden=16, feat_dim=6, n_senones=11, vocab_size=11,
        segments=(Segment((LayerSpec(mixer="lstm", ffn="none"),),
                          repeat=1),))
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    step = jax.jit(make_train_step(model, cfg, loss_kind="ce"))
    opt = init_opt_state(params)
    rng = np.random.default_rng(0)
    batch = {"feats": jnp.asarray(rng.normal(size=(2, 12, 6)),
                                  jnp.float32),
             "labels": jnp.asarray(rng.integers(0, 11, (2, 12))),
             "mask": jnp.ones((2, 12), jnp.float32)}
    for lr in (5e-2, 2e-2, 1e-2):
        params, opt, m = step(params, opt, batch, lr)
        assert jnp.isfinite(m["loss"])
    assert step._cache_size() == 1


# --------------------------------------------------- strategy via fit()

def test_local_fit_converges():
    batch = _problem(n=256)
    sink = ListSink()
    tr = Trainer(Local(clip=0.0), {"quad": quad_loss}, metrics=sink)
    state = tr.fit(tr.init_state(_params()),
                   _source(batch, [0.05] * 60), resume=False)
    assert sink.values("loss")[-1] < 0.05 * sink.values("loss")[0]
    assert int(state.step) == 60


def test_bmuf_fit_matches_manual_block_step():
    """BMUFShardMap through Trainer.fit == driving bmuf_lib's vmap
    reference block step by hand: same theta_g after the same
    microbatch stream."""
    from repro.distributed import bmuf as bmuf_lib
    cfg = BMUFConfig(n_workers=2, block_steps=2, block_momentum=0.5)
    rng = np.random.default_rng(3)
    full = _problem(n=64)
    micro = []
    for _ in range(8):                       # 2 full blocks of tau*W=4
        sel = rng.integers(0, 64, (16,))
        micro.append({"x": full["x"][sel], "y": full["y"][sel]})

    strat = BMUFShardMap(cfg, worker_mesh(2), clip=0.0)
    tr = Trainer(strat, {"quad": quad_loss})
    state = tr.fit(tr.init_state(_params()),
                   [TrainBatch(m, 0.05, "quad") for m in micro],
                   resume=False)
    assert int(state.step) == 2              # 8 microbatches / (tau*W)

    step = make_sgd_step(quad_loss, clip=0.0)
    block = jax.jit(bmuf_lib.make_bmuf_block_step(step, cfg))
    bstate = bmuf_lib.bmuf_init(_params(), cfg)
    opt = jax.vmap(lambda _: momentum_init(_params()))(jnp.arange(2))
    for blk in range(2):
        group = micro[blk * 4:(blk + 1) * 4]
        batches = jax.tree_util.tree_map(
            lambda *xs: jnp.stack(xs).reshape(2, 2, *xs[0].shape), *group)
        bstate, opt, _ = block(bstate, opt, batches, 0.05)
    np.testing.assert_allclose(np.asarray(state.params["w"]),
                               np.asarray(bstate["theta_g"]["w"]),
                               rtol=1e-6)


def test_bmuf_partial_block_dropped_at_loss_boundary():
    """A block cannot straddle a loss-kind change: the partial group is
    dropped (BMUF semantics), and full blocks on either side still run."""
    cfg = BMUFConfig(n_workers=2, block_steps=1)
    batch = _problem(n=16)
    src = ([TrainBatch(batch, 0.05, "quad")] * 2      # 1 full block
           + [TrainBatch(batch, 0.05, "quad")]        # partial -> dropped
           + [TrainBatch(batch, 0.05, "other")] * 2)  # 1 full block
    tr = Trainer(BMUFShardMap(cfg, worker_mesh(2), clip=0.0),
                 {"quad": quad_loss, "other": quad_loss})
    state = tr.fit(tr.init_state(_params()), src, resume=False)
    assert int(state.step) == 2


# ---------------------------------------------------------- GTCShardMap

def test_gtc_shardmap_single_compile_across_lr_phases():
    """The new strategy keeps the Trainer's one-executable property:
    an lr sweep through the shard_map step compiles exactly once (the
    strategy's place() lays init state out on the mesh so even the
    first call hits the steady-state executable).

    Count actual XLA compilations via the jax_log_compiles log, not
    ``_cache_size()``: on a >1-device mesh the C++ fastpath can hold a
    second cache entry for the same single executable."""
    import logging

    class _CompileCounter(logging.Handler):
        def __init__(self):
            super().__init__()
            self.compiles = []

        def emit(self, record):
            msg = record.getMessage()
            if "Finished XLA compilation" in msg:
                self.compiles.append(msg)

    batch = _problem()
    mesh = worker_mesh(2)
    tr = Trainer(GTCShardMap(GTCConfig(tau=1e-3, n_workers=2), mesh,
                             clip=0.0), {"quad": quad_loss})
    state = tr.init_state(_params())
    lrs = [0.1 * (0.85 ** i) for i in range(6)]
    # 2 microbatches per update: 12 source items -> 6 updates
    src = [TrainBatch(batch, lr, "quad") for lr in lrs for _ in range(2)]
    counter = _CompileCounter()
    logger = logging.getLogger("jax._src.dispatch")
    old_level = logger.level
    logger.addHandler(counter)
    logger.setLevel(logging.DEBUG)
    try:
        with jax.log_compiles():
            state = tr.fit(state, src, resume=False)
    finally:
        logger.removeHandler(counter)
        logger.setLevel(old_level)
    assert int(state.step) == 6
    updates = [m for m in counter.compiles if "jit(update)" in m]
    assert len(updates) == 1, counter.compiles


def test_gtc_shardmap_groups_microbatches_per_worker():
    """Each update consumes n_workers microbatches; a trailing partial
    group is dropped (same block semantics as BMUF)."""
    batch = _problem(n=16)
    mesh = worker_mesh(2)
    tr = Trainer(GTCShardMap(GTCConfig(tau=1e-3, n_workers=2), mesh,
                             clip=0.0), {"quad": quad_loss})
    state = tr.fit(tr.init_state(_params()),
                   _source(batch, [0.05] * 5), resume=False)
    assert int(state.step) == 2              # 5 microbatches -> 2 updates


def test_gtc_shardmap_resume_preserves_worker_residuals(tmp_path):
    """The per-worker (W-stacked) error-feedback residuals round-trip
    through the checkpoint and the resumed run lands bitwise on the
    uninterrupted result."""
    batch = _problem(n=32)
    mesh = worker_mesh(2)
    lrs = [0.05] * 12                        # 6 updates at W=2
    mk = lambda ck: Trainer(
        GTCShardMap(GTCConfig(tau=1e-3, n_workers=2), mesh, clip=0.0),
        {"quad": quad_loss},
        checkpoint=CheckpointStore(os.path.join(tmp_path, "state"))
        if ck else None, ckpt_every=2)
    ref = mk(False)
    ref_state = ref.fit(ref.init_state(_params()), _source(batch, lrs),
                        resume=False)
    t1 = mk(True)
    t1.fit(t1.init_state(_params()), _source(batch, lrs), max_updates=3)
    t2 = mk(True)
    state = t2.fit(t2.init_state(_params()), _source(batch, lrs))
    assert int(state.step) == 6
    assert state.strategy_state["residual"]["w"].shape == (2, D)
    np.testing.assert_array_equal(
        np.asarray(state.strategy_state["residual"]["w"]),
        np.asarray(ref_state.strategy_state["residual"]["w"]))
    np.testing.assert_array_equal(np.asarray(state.params["w"]),
                                  np.asarray(ref_state.params["w"]))


def test_gtc_shardmap_rng_distinct_per_worker():
    """Stochastic losses through GTCShardMap get per-(update, worker)
    folded keys — the sharded step's emitted per-worker noise equals
    normal(fold(fold(root, step), global_worker)) exactly, so every
    worker sees a distinct stream with the same folding scheme as the
    BMUF paths (global worker index, folded outside the shard_map)."""
    from repro.distributed import gtc as gtc_lib

    def spy_loss(params, batch, rng):
        noise = jax.random.normal(rng, ())
        e = batch["x"] @ params["w"] - batch["y"] - noise
        return jnp.mean(e ** 2), {"loss": jnp.mean(e ** 2),
                                  "n0": noise}

    batch = _problem(n=16)
    mesh = worker_mesh(2)
    strat = GTCShardMap(GTCConfig(tau=1e-3, n_workers=2), mesh, clip=0.0)
    # drive the gtc_lib step directly: its metrics keep the (W,) worker
    # dim the strategy's update would average away
    step = jax.jit(gtc_lib.make_sharded_gtc_train_step(
        spy_loss, lambda p, u, o, lr: (p, o), strat.cfg, mesh))
    tr = Trainer(strat, {"noisy": spy_loss})
    state = tr.init_state(_params(), seed=0)
    root = jax.random.fold_in(state.rng, state.step)
    _, _, _, ms = step(state.params, state.opt_state,
                       state.strategy_state, strat.stack([batch] * 2),
                       jnp.float32(0.05), root)
    got = np.asarray(ms["n0"])
    expect = np.asarray([jax.random.normal(jax.random.fold_in(root, w), ())
                         for w in range(2)])
    assert got.shape == (2,)
    np.testing.assert_array_equal(got, expect)
    assert got[0] != got[1]                  # distinct per worker

    # ...and the strategy's update threads the same rng (its averaged
    # n0 metric is the mean of the per-worker noises)
    state2, metrics = tr.updates["noisy"](state, strat.stack([batch] * 2),
                                          jnp.float32(0.05))
    assert int(state2.step) == 1
    np.testing.assert_allclose(float(metrics["n0"]), expect.mean(),
                               rtol=1e-6)


# --------------------------------------------------------------- resume

def test_fit_resumes_from_periodic_checkpoint(tmp_path):
    """Kill-and-reinvoke: a run interrupted after the step-4 checkpoint
    resumes there (not from scratch) and lands bitwise on the
    uninterrupted result; finalize() retires the resume state."""
    batch = _problem(n=64)
    lrs = [0.05 * (0.9 ** i) for i in range(10)]

    # uninterrupted reference
    ref = Trainer(Local(clip=0.0), {"quad": quad_loss})
    ref_state = ref.fit(ref.init_state(_params()), _source(batch, lrs),
                        resume=False)

    store = CheckpointStore(os.path.join(tmp_path, "state"))
    t1 = Trainer(Local(clip=0.0), {"quad": quad_loss},
                 checkpoint=store, ckpt_every=2)
    t1.fit(t1.init_state(_params()), _source(batch, lrs), max_updates=5)
    assert store.latest() == 4               # ckpts at 2 and 4; kill at 5

    t2 = Trainer(Local(clip=0.0), {"quad": quad_loss},
                 checkpoint=store, ckpt_every=2)
    sink = ListSink()
    t2.metrics = sink
    state = t2.fit(t2.init_state(_params()), _source(batch, lrs))
    assert int(state.step) == 10
    # resumed run only executed steps 5..10, not 1..10
    assert len(sink) == 6
    np.testing.assert_array_equal(np.asarray(state.params["w"]),
                                  np.asarray(ref_state.params["w"]))
    t2.finalize(state)
    assert store.latest() is None            # completed: resume retired


def test_resume_preserves_strategy_state(tmp_path):
    """GTC's error-feedback residual survives the checkpoint boundary —
    resume must not silently zero strategy state."""
    batch = _problem(n=32)
    lrs = [0.05] * 6
    gtc = lambda: GTCShardMap(GTCConfig(tau=1e-3, n_workers=1),
                              worker_mesh(1), clip=0.0)
    mk = lambda: Trainer(gtc(), {"quad": quad_loss},
                         checkpoint=CheckpointStore(
                             os.path.join(tmp_path, "state")),
                         ckpt_every=2)
    ref = Trainer(gtc(), {"quad": quad_loss})
    ref_state = ref.fit(ref.init_state(_params()), _source(batch, lrs),
                        resume=False)
    t1 = mk()
    t1.fit(t1.init_state(_params()), _source(batch, lrs), max_updates=3)
    t2 = mk()
    state = t2.fit(t2.init_state(_params()), _source(batch, lrs))
    np.testing.assert_array_equal(
        np.asarray(state.strategy_state["residual"]["w"][0]),
        np.asarray(ref_state.strategy_state["residual"]["w"][0]))
    np.testing.assert_array_equal(np.asarray(state.params["w"]),
                                  np.asarray(ref_state.params["w"]))


def test_trainstate_dict_roundtrip():
    tr = Trainer(Local(), {"quad": quad_loss})
    state = tr.init_state(_params(), seed=3)
    back = TrainState.from_dict(
        jax.tree_util.tree_map(np.asarray, state.to_dict()))
    assert int(back.step) == 0
    np.testing.assert_array_equal(np.asarray(back.params["w"]),
                                  np.asarray(state.params["w"]))
    # rng round-trips through raw key data
    a = jax.random.uniform(state.rng)
    b = jax.random.uniform(back.rng)
    assert float(a) == float(b)


# ------------------------------------------------- per-update RNG folding

def noisy_loss(params, batch, rng):
    """Stochastic loss: declares `rng` and gets a per-update folded key."""
    noise = jax.random.normal(rng, batch["y"].shape) * 0.01
    e = batch["x"] @ params["w"] - (batch["y"] + noise)
    return jnp.mean(e ** 2), {"loss": jnp.mean(e ** 2),
                              "n0": noise.reshape(-1)[0]}


def test_rng_folded_per_update():
    """The carried key folds with the step counter: every update sees a
    distinct stream (the seed bug: the key was carried but never split),
    and the sequence is a pure function of (seed, step) — two identical
    runs agree exactly."""
    batch = _problem(n=16)
    traces = []
    for _ in range(2):
        sink = ListSink()
        tr = Trainer(Local(clip=0.0), {"noisy": noisy_loss}, metrics=sink)
        tr.fit(tr.init_state(_params(), seed=7),
               _source(batch, [0.05] * 5, "noisy"), resume=False)
        traces.append(sink.values("n0"))
    assert len(set(traces[0])) == 5          # distinct stream per update
    assert traces[0] == traces[1]            # deterministic in the seed


def test_stochastic_loss_resume_is_bitwise(tmp_path):
    """Determinism under resume: a killed-and-reinvoked run of a
    stochastic (rng-consuming) loss lands bitwise on the uninterrupted
    result — the fold depends only on checkpointed state."""
    batch = _problem(n=32)
    lrs = [0.05] * 8

    ref = Trainer(Local(clip=0.0), {"noisy": noisy_loss})
    ref_state = ref.fit(ref.init_state(_params(), seed=3),
                        _source(batch, lrs, "noisy"), resume=False)

    store = CheckpointStore(os.path.join(tmp_path, "state"))
    t1 = Trainer(Local(clip=0.0), {"noisy": noisy_loss},
                 checkpoint=store, ckpt_every=2)
    t1.fit(t1.init_state(_params(), seed=3), _source(batch, lrs, "noisy"),
           max_updates=5)                     # "killed" after step 5
    t2 = Trainer(Local(clip=0.0), {"noisy": noisy_loss},
                 checkpoint=store, ckpt_every=2)
    state = t2.fit(t2.init_state(_params(), seed=3),
                   _source(batch, lrs, "noisy"))
    assert int(state.step) == 8
    np.testing.assert_array_equal(np.asarray(state.params["w"]),
                                  np.asarray(ref_state.params["w"]))


def test_bmuf_rng_distinct_per_worker_and_step():
    """Through BMUF, the block key folds per (worker, tau-step): all
    tau*W microbatches of a block see distinct noise."""
    from repro.distributed.bmuf import BMUFConfig

    def spy_loss(params, batch, rng):
        noise = jax.random.normal(rng, ())
        e = batch["x"] @ params["w"] - batch["y"] - noise
        return jnp.mean(e ** 2), {"loss": jnp.mean(e ** 2)}

    batch = _problem(n=16)
    strat = BMUFShardMap(BMUFConfig(n_workers=2, block_steps=2),
                         worker_mesh(2), clip=0.0)
    update = jax.jit(strat.make_update(spy_loss))
    state = Trainer(strat, {"noisy": spy_loss}).init_state(_params(),
                                                           seed=0)
    state2, _ = update(state, strat.stack([batch] * 4),
                       jnp.float32(0.05))    # runs under jit with rng
    assert int(state2.step) == 1
    # the folding scheme: fold(fold(fold(root, step), worker), tau_idx)
    # gives 4 distinct streams for the block's 4 microbatches
    root = jax.random.fold_in(state.rng, state.step)
    keys = [jax.random.fold_in(jax.random.fold_in(root, w), t)
            for w in range(2) for t in range(2)]
    noises = {float(jax.random.normal(k, ())) for k in keys}
    assert len(noises) == 4


def test_bmuf_sharded_rng_matches_vmap_path():
    """Stochastic losses through BMUFShardMap == the vmap reference block
    step driven by hand with the same folded keys, bitwise on a 1-device
    mesh: the per-worker keys are folded with *global* worker indices
    outside the shard_map (crossing as raw key data), so both forms of
    the same math see the same streams.  On a >1-device mesh the
    cross-device psum reduction order shifts the block mean by float32
    ULPs, so equality relaxes to that tolerance."""
    from repro.distributed import bmuf as bmuf_lib

    batch = _problem(n=32)
    cfg = BMUFConfig(n_workers=2, block_steps=2, block_momentum=0.5)

    mesh = worker_mesh(2)
    tr = Trainer(BMUFShardMap(cfg, mesh, clip=0.0), {"noisy": noisy_loss})
    st0 = tr.init_state(_params(), seed=5)
    st_s = tr.fit(st0, _source(batch, [0.05] * 8, "noisy"), resume=False)

    block = jax.jit(bmuf_lib.make_bmuf_block_step(
        make_sgd_step(noisy_loss, clip=0.0), cfg))
    bstate = bmuf_lib.bmuf_init(_params(), cfg)
    opt = jax.vmap(lambda _: momentum_init(_params()))(jnp.arange(2))
    batches = jax.tree_util.tree_map(
        lambda x: jnp.stack([x] * 4).reshape(2, 2, *x.shape), batch)
    for step in range(2):
        bstate, opt, _ = block(bstate, opt, batches, 0.05,
                               jax.random.fold_in(st0.rng, step))
    assert int(st_s.step) == 2
    if mesh.devices.size == 1:
        np.testing.assert_array_equal(np.asarray(bstate["theta_g"]["w"]),
                                      np.asarray(st_s.params["w"]))
    else:
        np.testing.assert_allclose(np.asarray(bstate["theta_g"]["w"]),
                                   np.asarray(st_s.params["w"]),
                                   atol=1e-7, rtol=0)


# ------------------------------------------------- LR schedules as lr

def test_schedule_object_lr_single_compile():
    """An optim.schedules Schedule rides through the source as
    TrainBatch.lr, is evaluated at the update counter, and keeps the
    one-compile-per-loss-kind property."""
    from repro.optim import exponential_decay
    batch = _problem(n=16)
    sched = exponential_decay(0.1, 0.5, 2)   # lr halves every 2 updates
    tr = Trainer(Local(clip=0.0), {"quad": quad_loss})
    state = tr.init_state(_params())
    src = [TrainBatch(batch, sched, "quad") for _ in range(6)]
    state = tr.fit(state, src, resume=False)
    assert int(state.step) == 6
    assert tr.updates["quad"]._cache_size() == 1   # schedule != re-jit
    # schedule evaluated at the counter: steps 0,1 -> 0.1; 2,3 -> 0.05...
    assert sched(0) == pytest.approx(0.1)
    assert sched(2) == pytest.approx(0.05)
    assert sched(5) == pytest.approx(0.025)


def test_schedule_through_epoch_source_and_resume(tmp_path):
    """epoch_source passes Schedule objects through (no per-epoch
    evaluation), and a resumed run continues the schedule at the right
    step — bitwise vs uninterrupted."""
    from repro.optim import exponential_decay
    batch = _problem(n=32)
    mk_src = lambda: epoch_source(lambda ep: [batch] * 3, 2,
                                  exponential_decay(0.1, 0.7, 1), "quad")
    for tb in mk_src():
        assert callable(tb.lr)               # passed through, not a float

    ref = Trainer(Local(clip=0.0), {"quad": quad_loss})
    ref_state = ref.fit(ref.init_state(_params()), mk_src(), resume=False)

    store = CheckpointStore(os.path.join(tmp_path, "state"))
    t1 = Trainer(Local(clip=0.0), {"quad": quad_loss},
                 checkpoint=store, ckpt_every=2)
    t1.fit(t1.init_state(_params()), mk_src(), max_updates=3)
    t2 = Trainer(Local(clip=0.0), {"quad": quad_loss},
                 checkpoint=store, ckpt_every=2)
    state = t2.fit(t2.init_state(_params()), mk_src())
    np.testing.assert_array_equal(np.asarray(state.params["w"]),
                                  np.asarray(ref_state.params["w"]))


# ------------------------------------------------------ sources + sinks

def test_epoch_source_and_chain():
    batch = _problem(n=8)
    src = list(chain(
        epoch_source(lambda ep: [batch, batch], 2, lambda ep: 0.1 / (ep + 1),
                     "ce"),
        epoch_source(lambda ep: [batch], 1, 0.01, "ft")))
    assert len(src) == 5
    assert [tb.loss for tb in src] == ["ce"] * 4 + ["ft"]
    assert src[0].lr == pytest.approx(0.1) and src[2].lr == pytest.approx(0.05)
    assert src[-1].lr == pytest.approx(0.01)


def test_unknown_loss_kind_raises():
    tr = Trainer(Local(), {"quad": quad_loss})
    with pytest.raises(KeyError):
        tr.fit(tr.init_state(_params()),
               [TrainBatch(_problem(n=8), 0.1, "nope")], resume=False)


def test_jsonl_sink(tmp_path):
    import json
    path = os.path.join(tmp_path, "m", "metrics.jsonl")
    sink = JsonlSink(path)
    tr = Trainer(Local(clip=0.0), {"quad": quad_loss}, metrics=sink)
    tr.fit(tr.init_state(_params()), _source(_problem(n=8), [0.1] * 3),
           resume=False)
    rows = [json.loads(l) for l in open(path)]
    assert [r["step"] for r in rows] == [1, 2, 3]
    assert all(r["tag"] == "quad" and np.isfinite(r["loss"]) for r in rows)
