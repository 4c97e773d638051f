"""Elastic membership across the scheduled-learning stack (ISSUE 10):
BMUF/GTC surviving workers joining and leaving mid-run.

Pins, layer by layer:
  * restack_workers — the one re-partitioning primitive (shrink keeps /
    folds, grow broadcasts / zero-pads; fold is sum-preserving)
  * bmuf.active_mean_fn / block_sync(active=...) — dead lanes drop out
    of the block average; the masked W=4 run matches a fresh W=3 run to
    float32-ULP; dead lanes stay broadcast-warm for rejoin
  * Trainer.resize + fit(membership=...) — a lane killed mid-run via a
    scripted LaneCrashPlan produces bitwise the params of a fresh
    smaller-W trainer resuming the same cross-W checkpoint
  * GTCShardMap.resize — error-feedback residual conservation holds
    across a W=4 -> W=2 resize (fold scatter-adds dropped rows)
  * TrainerMembership / LaneCrashPlan — the roster + chaos machinery
  * WorkLedger.reclaim_stale claim-age signal + structured steal events
  * warmup_hold_decay — shape and the 1-compile pin
"""
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import CheckpointStore
from repro.distributed import bmuf as B
from repro.distributed import gtc as G
from repro.optim import momentum_init, momentum_update, warmup_hold_decay
from repro.pipeline.generate import WorkLedger, shard_ranges
from repro.runtime import procs
from repro.runtime.cluster import worker_mesh
from repro.runtime.workers import LaneCrashPlan, TrainerMembership
from repro.train import (BMUFShardMap, GTCShardMap, Local, TrainBatch,
                         Trainer, TrainState, restack_workers)
from repro.train.state import worker_dim

tmap = jax.tree_util.tree_map
D = 8


def quad_loss(params, batch):
    e = batch["x"] @ params["w"] - batch["y"]
    return jnp.mean(e ** 2), {"loss": jnp.mean(e ** 2)}


def quad_step():
    def step(params, opt_state, batch, lr):
        (_, m), g = jax.value_and_grad(quad_loss, has_aux=True)(params,
                                                                batch)
        params, opt_state = momentum_update(params, g, opt_state, lr=lr,
                                            beta=0.0, nesterov=False)
        return params, opt_state, m
    return step


def _problem(seed=0, n=64, d=D):
    rng = np.random.default_rng(seed)
    w_true = rng.normal(size=(d,))
    x = rng.normal(size=(n, d)).astype(np.float32)
    y = (x @ w_true).astype(np.float32)
    return {"x": jnp.asarray(x), "y": jnp.asarray(y)}


def lin_loss(params, batch):
    l = jnp.sum(params["w"] * batch["c"])
    return l, {"loss": l}


# ===================================================== restack_workers

def test_restack_shrink_fold_preserves_sum():
    """fold=True scatter-adds dropped rows round-robin onto survivors:
    the column sums (all the information carried on the W axis) are
    exactly preserved — the GTC residual-conservation primitive."""
    rng = np.random.default_rng(0)
    tree = {"a": jnp.asarray(rng.normal(size=(5, 3)), jnp.float32),
            "b": {"c": jnp.asarray(rng.normal(size=(5,)), jnp.float32)}}
    out = restack_workers(tree, 2, fold=True)
    assert out["a"].shape == (2, 3) and out["b"]["c"].shape == (2,)
    for src, dst in ((tree["a"], out["a"]), (tree["b"]["c"],
                                             out["b"]["c"])):
        np.testing.assert_allclose(np.asarray(src).sum(0),
                                   np.asarray(dst).sum(0), rtol=1e-6)


def test_restack_shrink_nofold_keeps_head():
    x = jnp.arange(12.0).reshape(4, 3)
    out = restack_workers({"w": x}, 3)["w"]
    np.testing.assert_array_equal(np.asarray(out), np.asarray(x[:3]))


def test_restack_grow_broadcasts_lane0():
    """no-fold grow = BMUF semantics: a joiner warm-starts from lane 0
    (all lanes are identical right after a Nesterov restart anyway)."""
    x = jnp.asarray([[1.0, 2.0], [3.0, 4.0]])
    out = restack_workers({"w": x}, 4)["w"]
    assert out.shape == (4, 2)
    np.testing.assert_array_equal(np.asarray(out[2]), np.asarray(x[0]))
    np.testing.assert_array_equal(np.asarray(out[3]), np.asarray(x[0]))


def test_restack_grow_fold_pads_zero():
    """fold grow = GTC semantics: a joiner starts with zero residual
    (sum-preserving in the grow direction too)."""
    x = jnp.asarray([[1.0, 2.0]])
    out = restack_workers({"w": x}, 3, fold=True)["w"]
    np.testing.assert_array_equal(np.asarray(out[1:]),
                                  np.zeros((2, 2), np.float32))


def test_restack_rejects_bad_w():
    with pytest.raises(ValueError):
        restack_workers({"w": jnp.zeros((2, 3))}, 0)


def test_worker_dim():
    assert worker_dim({"w": jnp.zeros((4, 3))}) == 4
    assert worker_dim({}) == 0


# ===================================================== masked block sync

def test_active_mean_fn_drops_dead_lanes():
    w = jnp.asarray([[1.0], [3.0], [5.0], [999.0]])
    got = B.active_mean_fn(jnp.asarray([1, 1, 1, 0]))(w)
    np.testing.assert_allclose(np.asarray(got), [3.0], rtol=1e-7)
    # all-dead degrades to zero contribution, not NaN
    got = B.active_mean_fn(jnp.zeros(4))(w)
    assert np.isfinite(np.asarray(got)).all()


def test_bmuf_masked_w4_matches_fresh_w3_ulp():
    """The acceptance pin: BMUF at W=4 with one lane masked dead (fed a
    junk duplicate batch — its local steps still run, its contribution
    is dropped at the sync) matches a fresh W=3 run over several blocks
    within float32-ULP.  Not bitwise: masked-sum/denom vs jnp.mean
    reassociate differently."""
    tau = 2
    step = quad_step()
    blk4 = jax.jit(B.make_bmuf_block_step(
        step, B.BMUFConfig(n_workers=4, block_steps=tau)))
    blk3 = jax.jit(B.make_bmuf_block_step(
        step, B.BMUFConfig(n_workers=3, block_steps=tau)))
    params = {"w": jnp.zeros((D,))}
    s4 = B.bmuf_init(params, B.BMUFConfig(n_workers=4, block_steps=tau))
    s3 = B.bmuf_init(params, B.BMUFConfig(n_workers=3, block_steps=tau))
    o4 = tmap(lambda x: jnp.broadcast_to(x, (4,) + x.shape).copy(),
              momentum_init(params))
    o3 = tmap(lambda x: jnp.broadcast_to(x, (3,) + x.shape).copy(),
              momentum_init(params))
    active = jnp.asarray([1.0, 1.0, 1.0, 0.0])
    for blk in range(3):
        bs = [_problem(seed=10 * blk + i, n=16) for i in range(tau * 3)]
        b3 = tmap(lambda *xs: jnp.stack(xs).reshape(tau, 3, *xs[0].shape),
                  *bs)
        # lane 3 chews on junk (lane 0's batches) — masked out anyway
        b4 = tmap(lambda x: jnp.concatenate([x, x[:, :1]], axis=1), b3)
        s4, o4, _ = blk4(s4, o4, b4, 0.1, None, active)
        s3, o3, _ = blk3(s3, o3, b3, 0.1)
    np.testing.assert_allclose(np.asarray(s4["theta_g"]["w"]),
                               np.asarray(s3["theta_g"]["w"]),
                               rtol=0, atol=5e-7)


def test_bmuf_dead_lane_stays_warm():
    """The Nesterov restart broadcasts to ALL lanes, dead ones included:
    a rejoining worker resumes from current params by flipping its mask
    bit back on — no state transfer needed."""
    cfg = B.BMUFConfig(n_workers=4, block_steps=1)
    state = B.bmuf_init({"w": jnp.zeros((D,))}, cfg)
    rng = np.random.default_rng(1)
    state = dict(state, workers={"w": jnp.asarray(
        rng.normal(size=(4, D)), jnp.float32)})
    out = B.block_sync(state, cfg, active=jnp.asarray([1, 1, 0, 0]))
    w = np.asarray(out["workers"]["w"])
    for lane in range(1, 4):
        np.testing.assert_array_equal(w[lane], w[0])


def test_sharded_masked_sync_matches_vmap():
    """make_sharded_bmuf_block_step(active=...) — psum-of-masked-sums /
    psum-of-live-count — agrees with the vmap path's masked mean."""
    tau = 2
    step = quad_step()
    cfg = B.BMUFConfig(n_workers=4, block_steps=tau)
    blkv = jax.jit(B.make_bmuf_block_step(step, cfg))
    blks = jax.jit(B.make_sharded_bmuf_block_step(step, cfg,
                                                  worker_mesh(4)))
    params = {"w": jnp.zeros((D,))}
    opt = tmap(lambda x: jnp.broadcast_to(x, (4,) + x.shape).copy(),
               momentum_init(params))
    bs = [_problem(seed=i, n=16) for i in range(tau * 4)]
    bt = tmap(lambda *xs: jnp.stack(xs).reshape(tau, 4, *xs[0].shape), *bs)
    active = jnp.asarray([1.0, 0.0, 1.0, 1.0])
    sv, _, _ = blkv(B.bmuf_init(params, cfg), opt, bt, 0.1, None, active)
    ss, _, _ = blks(B.bmuf_init(params, cfg), opt, bt, 0.1, None, active)
    tol = ({"rtol": 0, "atol": 0} if jax.device_count() == 1
           else {"atol": 1e-7})
    np.testing.assert_allclose(np.asarray(sv["theta_g"]["w"]),
                               np.asarray(ss["theta_g"]["w"]), **tol)


# ============================================ trainer-level elasticity

def _batches(n, seed0=100):
    return [_problem(seed=seed0 + i, n=16) for i in range(n)]


def _src(batches):
    return [TrainBatch(b, 0.05, "quad") for b in batches]


def test_trainer_elastic_kill_matches_cross_w_resume(tmp_path):
    """The end-to-end acceptance pin.  Run A: W=4 BMUF fit, checkpoint
    at step 2, then a LaneCrashPlan kills one lane (resize to W=3 at
    the block boundary) and training continues.  Run B: a *fresh* W=3
    trainer resumes the W=4 checkpoint (cross-W resume: the template is
    resized up to the saved W for the strict-shape load, then resized
    back down).  Both reach step 4 with bitwise-identical params — the
    roster path and the restart path agree exactly."""
    ck = str(tmp_path / "ck")
    batches = _batches(4 * 2 + 3 * 2)          # 2 updates @W4, 2 @W3
    params = {"w": jnp.zeros((D,))}

    # --- run A: elastic shrink mid-run
    trA = Trainer(BMUFShardMap(B.BMUFConfig(n_workers=4, block_steps=1),
                               worker_mesh(4), clip=0.0),
                  {"quad": quad_loss},
                  checkpoint=CheckpointStore(ck), ckpt_every=2)
    sA = trA.fit(trA.init_state(params), _src(batches[:8]), resume=False)
    assert int(sA.step) == 2                   # W=4 checkpoint on disk
    trA.ckpt_every = 0                         # keep it the latest save

    m = TrainerMembership(str(tmp_path / "members.json"), timeout_s=30.0)
    for i in range(4):
        m.join(f"lane{i}")
    plan = LaneCrashPlan(m, kills={0: "lane3"})   # dies before block 3
    sA = trA.fit(sA, _src(batches[8:]), resume=False, membership=plan)
    assert int(sA.step) == 4
    assert trA.strategy.n_workers == 3
    assert trA.resize_stats["count"] == 1

    # --- run B: fresh W=3 trainer, cross-W resume of the W=4 save
    trB = Trainer(BMUFShardMap(B.BMUFConfig(n_workers=3, block_steps=1),
                               worker_mesh(3), clip=0.0),
                  {"quad": quad_loss},
                  checkpoint=CheckpointStore(ck))
    sB = trB.fit(trB.init_state(params), _src(batches), resume=True)
    assert int(sB.step) == 4
    assert trB.resize_stats["count"] == 1      # the cross-W load resized
    np.testing.assert_array_equal(np.asarray(sA.params["w"]),
                                  np.asarray(sB.params["w"]))


def test_trainer_revive_grows_back(tmp_path):
    """Kill then revive: the trainer shrinks at one block boundary and
    grows back at a later one; the revived lane warm-starts from the
    broadcast params and the run completes at full W."""
    m = TrainerMembership(str(tmp_path / "members.json"), timeout_s=30.0)
    for i in range(4):
        m.join(f"lane{i}")
    plan = LaneCrashPlan(m, kills={1: "lane2"}, revives={3: "lane2"})
    tr = Trainer(BMUFShardMap(B.BMUFConfig(n_workers=4, block_steps=1),
                              worker_mesh(4), clip=0.0),
                 {"quad": quad_loss})
    # enough batches for 5 updates at worst-case W (partial tail dropped)
    state = tr.fit(tr.init_state({"w": jnp.zeros((D,))}),
                   _src(_batches(24)), resume=False, membership=plan)
    assert tr.strategy.n_workers == 4          # grew back
    assert tr.resize_stats["count"] == 2
    assert [e["event"] for e in plan.log] == ["kill", "revive"]
    assert int(state.step) >= 4


def test_gtc_resize_conserves_residual():
    """GTC error feedback conserves information ACROSS A RESIZE: sum of
    everything shipped (W_t * averaged updates, W_t per round) plus the
    final residuals equals the sum of all gradients, with a W=4 -> W=2
    resize (fold scatter-adds the dropped workers' unshipped error onto
    survivors) in the middle."""
    tau = 2e-3
    rounds, d = 3, 16
    capture = lambda p, u, o, lr: (u, o)
    params = {"w": jnp.zeros((d,))}
    strat = GTCShardMap(G.GTCConfig(tau=tau, n_workers=4), worker_mesh(4),
                        clip=0.0)
    gstate = strat.init_state(params)
    rng = np.random.default_rng(3)
    total_g = np.zeros(d)
    total_sent = np.zeros(d)
    for w_phase in (4, 2):
        step = jax.jit(G.make_sharded_gtc_train_step(
            lin_loss, capture, strat.cfg, strat.mesh))
        for _ in range(rounds):
            cs = [{"c": jnp.asarray(rng.normal(size=(d,)) * tau,
                                    jnp.float32)} for _ in range(w_phase)]
            upd, _, gstate, _ = step(
                params, None, gstate,
                tmap(lambda *xs: jnp.stack(xs), *cs), 0.05)
            total_g += sum(np.asarray(c["c"], np.float64) for c in cs)
            total_sent += w_phase * np.asarray(upd["w"], np.float64)
        if w_phase == 4:                       # shrink between phases
            before = np.asarray(gstate["residual"]["w"],
                                np.float64).sum(0)
            ts = TrainState(params=params, opt_state=None,
                            strategy_state=gstate, step=jnp.asarray(0),
                            rng=jax.random.PRNGKey(0))
            ts = strat.resize(ts, 2)
            gstate = ts.strategy_state
            after = np.asarray(gstate["residual"]["w"], np.float64).sum(0)
            np.testing.assert_allclose(after, before, atol=1e-7)
            assert gstate["residual"]["w"].shape[0] == 2
    final_res = np.asarray(gstate["residual"]["w"], np.float64).sum(0)
    np.testing.assert_allclose(total_sent + final_res, total_g, atol=1e-5)


def test_gtc_cross_w_resume_preserves_residual_sum(tmp_path):
    """A GTC checkpoint saved at W=4 resumes into a W=2 trainer: the
    strict-shape load goes through the saved-W template, the resize
    folds residuals sum-preservingly, and training continues."""
    ck = str(tmp_path / "ck")
    batch = _problem(n=32)
    src = lambda n: [TrainBatch(batch, 0.05, "quad") for _ in range(n)]
    tr4 = Trainer(GTCShardMap(G.GTCConfig(tau=1e-3, n_workers=4),
                              worker_mesh(4), clip=0.0),
                  {"quad": quad_loss}, checkpoint=CheckpointStore(ck),
                  ckpt_every=2)
    s4 = tr4.fit(tr4.init_state({"w": jnp.zeros((D,))}), src(8),
                 resume=False)
    assert int(s4.step) == 2
    res_sum = np.asarray(s4.strategy_state["residual"]["w"],
                         np.float64).sum(0)

    tr2 = Trainer(GTCShardMap(G.GTCConfig(tau=1e-3, n_workers=2),
                              worker_mesh(2), clip=0.0),
                  {"quad": quad_loss}, checkpoint=CheckpointStore(ck))
    # pure replay (source == consumed prefix): the state right after
    # the cross-W load, before any new update
    s2 = tr2.fit(tr2.init_state({"w": jnp.zeros((D,))}), src(8),
                 resume=True)
    assert tr2.resize_stats["count"] == 1      # the cross-W load resized
    assert int(s2.step) == 2
    assert s2.strategy_state["residual"]["w"].shape[0] == 2
    np.testing.assert_array_equal(np.asarray(s4.params["w"]),
                                  np.asarray(s2.params["w"]))
    np.testing.assert_allclose(
        np.asarray(s2.strategy_state["residual"]["w"],
                   np.float64).sum(0), res_sum, atol=1e-7)
    # and training continues at the new W
    s2 = tr2.fit(s2, src(4), resume=False)
    assert int(s2.step) == 4


# =============================================== membership machinery

def test_membership_join_leave_kill(tmp_path):
    m = TrainerMembership(str(tmp_path / "members.json"), timeout_s=5.0)
    assert m.live() == [] and m.live_count() == 0   # trainer floors at 1
    m.join("a")
    m.join("b")
    assert m.live() == ["a", "b"]
    m.leave("b")
    assert m.live() == ["a"]
    m.kill("a")                                # backdated heartbeat
    assert m.live() == []
    m.join("a")                                # warm rejoin: same name
    assert m.live() == ["a"]
    roster = m.roster()
    assert roster["a"]["left"] is None and roster["b"]["left"] is not None


def test_lane_crash_plan_poll_indexing(tmp_path):
    """Polls are the chaos clock: poll 0 is fit()'s pre-loop check,
    poll N fires right after update N.  Kills/revives land at exact
    indices — chaos runs are replayable."""
    m = TrainerMembership(str(tmp_path / "members.json"), timeout_s=5.0)
    m.join("a")
    m.join("b")
    plan = LaneCrashPlan(m, kills={1: "b"}, revives={2: "b"})
    assert plan.live_count() == 2              # poll 0: nothing fires
    assert plan.live_count() == 1              # poll 1: kill b
    assert plan.live_count() == 2              # poll 2: revive b
    assert [(e["event"], e["poll"]) for e in plan.log] == [("kill", 1),
                                                           ("revive", 2)]


# =========================================== ledger: claim-age + events

def _open_shared(tmp_path, n=4):
    return WorkLedger.open(str(tmp_path / "ledger.json"),
                           shard_ranges(8, n))


def test_reclaim_stale_claim_age_zombie(tmp_path):
    """The zombie case: the heartbeat thread outlives a hung main loop,
    so the heartbeat stays fresh forever while the claim never
    completes.  ``claim_timeout_s`` ages the claim by its own
    timestamp, independent of the heartbeat."""
    led = _open_shared(tmp_path)
    procs.beat(led.heartbeat_dir, "z")
    claim = led.claim_shared("z")
    late = time.time() + 120
    procs.beat(led.heartbeat_dir, "z")         # heartbeat stays fresh
    # without the claim timeout the zombie holds its claim forever
    assert led.reclaim_stale(max_age_s=300.0, now=late) == []
    stolen = led.reclaim_stale(max_age_s=300.0, now=late,
                               claim_timeout_s=60.0)
    assert [(r.lo, r.hi) for r in stolen] == [(claim.lo, claim.hi)]
    modes = [e["mode"] for e in led.events if e["event"] == "steal"]
    assert modes == ["claim_age"]


def test_reclaim_events_structured(tmp_path):
    """Every steal is a structured event: who lost what, by which
    staleness signal, how old — the supervisor surfaces these up
    through stage_targets."""
    led = _open_shared(tmp_path)
    procs.beat(led.heartbeat_dir, "a")
    led.claim_shared("a")
    hb = procs.heartbeat_path(led.heartbeat_dir, "a")
    past = time.time() - 60
    os.utime(hb, (past, past))
    led.reclaim_stale(max_age_s=5.0)
    led.claim_shared("dead")
    led.reclaim_stale(max_age_s=0.0, owners=["dead"])
    evs = [e for e in led.events if e["event"] == "steal"]
    assert [e["mode"] for e in evs] == ["hb_age", "owner"]
    assert evs[0]["from"] == "a" and evs[0]["age_s"] > 5.0
    assert evs[1]["from"] == "dead" and evs[1]["age_s"] is None
    assert all({"lo", "hi", "t"} <= set(e) for e in evs)
    json.dumps(led.events)                     # wire-safe


# ================================================== warmup-hold-decay

def test_warmup_hold_decay_shape():
    s = warmup_hold_decay(0.1, warmup_steps=4, hold_steps=6, decay=0.5,
                          steps_per_epoch=2, floor=0.004)
    assert s(0) == pytest.approx(0.1 * 1 / 4)          # ramping
    assert s(3) == pytest.approx(0.1)                  # warm
    for step in range(4, 10):
        assert s(step) == pytest.approx(0.1)           # hold at peak
    assert s(12) == pytest.approx(0.05)                # decaying
    assert s(1000) == pytest.approx(0.004)             # floor clamp
    # monotone non-increasing after the warmup
    lrs = [s(i) for i in range(3, 40)]
    assert all(a >= b for a, b in zip(lrs, lrs[1:]))


def test_warmup_hold_decay_single_compile():
    """The 1-compile pin extends to the new shape: lr stays a traced
    argument, so the whole warmup-hold-decay sweep reuses one
    executable."""
    batch = _problem()
    tr = Trainer(Local(clip=0.0), {"quad": quad_loss})
    sched = warmup_hold_decay(0.1, warmup_steps=2, hold_steps=3,
                              decay=0.8, steps_per_epoch=2)
    src = [TrainBatch(batch, sched, "quad") for _ in range(10)]
    state = tr.fit(tr.init_state({"w": jnp.zeros((D,))}), src,
                   resume=False)
    assert int(state.step) == 10
    assert tr.updates["quad"]._cache_size() == 1


# ======================================================= wave driver

@pytest.mark.slow
def test_elastic_waves_end_to_end(tmp_path):
    """Two full generate -> train -> promote waves with an injected
    kill+revive per wave: resizes absorbed, the student of wave 0
    regenerates wave 1's targets (store wave supersede), final manifest
    checksum-clean, ledger done."""
    import dataclasses

    from repro.core.ssl_pipeline import PipelineConfig, SSLPipeline

    pc = dataclasses.replace(PipelineConfig.tiny(), bmuf_workers=4,
                             bmuf_block_steps=2, n_sub_epochs=4,
                             labeled_every=2, chunked_until=3)
    p = SSLPipeline(pc, out_dir=str(tmp_path / "waves"),
                    student_trainer="bmuf")
    p.stage_baseline()
    p.stage_teacher()
    rep = p.run_waves(2, kill_at=1, revive_after=2)
    assert rep["n_waves"] == 2
    assert rep["manifest_clean"] and rep["ledger_clean"]
    assert rep["restarts_absorbed"] == 2       # one kill per wave
    assert rep["resize_count"] == 4            # shrink+grow per wave
    assert [wv["wave"] for wv in rep["waves"]] == [0, 1]   # superseded
    for wv in rep["waves"]:
        assert wv["student"]["final_workers"] == 4
