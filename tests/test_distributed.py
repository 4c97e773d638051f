"""BMUF + GTC: algebraic invariants and trainer equivalences."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
try:
    from hypothesis import given, settings, strategies as st
except ImportError:          # [test] extra absent: deterministic shim
    from _hypothesis_fallback import given, settings, st

from repro.distributed import bmuf as B
from repro.distributed import gtc as G
from repro.optim import momentum_init, momentum_update
from repro.runtime.cluster import worker_mesh

tmap = jax.tree_util.tree_map


def quad_loss(params, batch):
    """Simple strongly-convex test problem."""
    w = params["w"]
    e = (batch["x"] @ w - batch["y"])
    return jnp.mean(e ** 2), {"loss": jnp.mean(e ** 2)}


def quad_step():
    """lr is a traced argument — the contract every strategy step uses."""
    def step(params, opt_state, batch, lr):
        (_, m), g = jax.value_and_grad(quad_loss, has_aux=True)(params,
                                                                batch)
        params, opt_state = momentum_update(params, g, opt_state, lr=lr,
                                            beta=0.0, nesterov=False)
        return params, opt_state, m
    return step


def _problem(seed=0, n=64, d=8):
    rng = np.random.default_rng(seed)
    w_true = rng.normal(size=(d,))
    x = rng.normal(size=(n, d)).astype(np.float32)
    y = (x @ w_true).astype(np.float32)
    return jnp.asarray(x), jnp.asarray(y)


# ------------------------------------------------------------------- BMUF

def test_bmuf_single_worker_tau1_equals_sgd():
    """W=1, tau=1, eta=0, zeta=1 reduces exactly to plain SGD."""
    x, y = _problem()
    params = {"w": jnp.zeros((8,))}
    cfg = B.BMUFConfig(n_workers=1, block_steps=1, block_momentum=0.0,
                       block_lr=1.0, nesterov=False)
    state = B.bmuf_init(params, cfg)
    opt = jax.vmap(lambda _: momentum_init(params))(jnp.arange(1))
    block = jax.jit(B.make_bmuf_block_step(quad_step(), cfg))
    batches = {"x": x[None, None], "y": y[None, None]}
    state, opt, _ = block(state, opt, batches, 0.05)

    ref_params = {"w": jnp.zeros((8,))}
    ref_opt = momentum_init(ref_params)
    ref_params, ref_opt, _ = quad_step()(ref_params, ref_opt,
                                         {"x": x, "y": y}, 0.05)
    np.testing.assert_allclose(np.asarray(state["theta_g"]["w"]),
                               np.asarray(ref_params["w"]), rtol=1e-5,
                               atol=1e-7)


def test_bmuf_sync_math():
    """Block sync: theta' = theta + eta*delta + zeta*(mean(w) - theta)."""
    params = {"w": jnp.asarray([1.0, 2.0])}
    cfg = B.BMUFConfig(n_workers=2, block_momentum=0.5, block_lr=0.8,
                       nesterov=True)
    state = B.bmuf_init(params, cfg)
    state["delta"] = {"w": jnp.asarray([0.1, -0.1])}
    state["workers"] = {"w": jnp.asarray([[2.0, 2.0], [4.0, 0.0]])}
    out = B.block_sync(state, cfg)
    g = np.asarray([3.0 - 1.0, 1.0 - 2.0])
    delta = 0.5 * np.asarray([0.1, -0.1]) + 0.8 * g
    theta = np.asarray([1.0, 2.0]) + delta
    np.testing.assert_allclose(np.asarray(out["theta_g"]["w"]), theta,
                               rtol=1e-6)
    np.testing.assert_allclose(np.asarray(out["delta"]["w"]), delta,
                               rtol=1e-6)
    # Nesterov restart: workers start from theta + eta*delta
    np.testing.assert_allclose(np.asarray(out["workers"]["w"][0]),
                               theta + 0.5 * delta, rtol=1e-6)


def test_bmuf_converges_on_quadratic():
    x, y = _problem(n=256)
    params = {"w": jnp.zeros((8,))}
    cfg = B.BMUFConfig(n_workers=4, block_steps=2, block_momentum=0.5,
                       block_lr=1.0)
    state = B.bmuf_init(params, cfg)
    opt = jax.vmap(lambda _: momentum_init(params))(jnp.arange(4))
    block = jax.jit(B.make_bmuf_block_step(quad_step(), cfg))
    rng = np.random.default_rng(1)
    start = float(quad_loss(state["theta_g"], {"x": x, "y": y})[0])
    for it in range(60):
        sel = rng.integers(0, 256, (2, 4, 32))
        batches = {"x": jnp.asarray(np.asarray(x)[sel]),
                   "y": jnp.asarray(np.asarray(y)[sel])}
        state, opt, ms = block(state, opt, batches, 0.05)
    final = float(quad_loss(state["theta_g"], {"x": x, "y": y})[0])
    assert final < 0.05 * start, (start, final)


def test_sharded_bmuf_matches_vmap_path():
    """shard_map BMUF on a 1-device CPU mesh == the vmap reference —
    bitwise on theta_g AND delta, held across >= 2 blocks (the second
    block exercises the carried block momentum and the Nesterov
    restart, not just the first sync).  When the worker mesh spans >1
    real device the cross-device psum reduction order differs from the
    single-device vmap mean, so equality relaxes to a float32-ULP
    tolerance."""
    x, y = _problem(n=64)
    params = {"w": jnp.zeros((8,))}
    cfg = B.BMUFConfig(n_workers=2, block_steps=2, block_momentum=0.5,
                       block_lr=1.0)
    rng = np.random.default_rng(7)

    state_v = B.bmuf_init(params, cfg)
    opt_v = jax.vmap(lambda _: momentum_init(params))(jnp.arange(2))
    block_v = jax.jit(B.make_bmuf_block_step(quad_step(), cfg))

    mesh = worker_mesh(2)
    state_s = B.bmuf_init(params, cfg)
    opt_s = jax.vmap(lambda _: momentum_init(params))(jnp.arange(2))
    block_s = B.make_sharded_bmuf_block_step(quad_step(), cfg, mesh,
                                             worker_axes=("data",))

    check = (np.testing.assert_array_equal if mesh.devices.size == 1
             else lambda a, b, err_msg: np.testing.assert_allclose(
                 a, b, atol=1e-7, rtol=0, err_msg=err_msg))
    for blk in range(3):
        sel = rng.integers(0, 64, (2, 2, 32))
        batches = {"x": jnp.asarray(np.asarray(x)[sel]),
                   "y": jnp.asarray(np.asarray(y)[sel])}
        state_v, opt_v, _ = block_v(state_v, opt_v, batches, 0.05)
        state_s, opt_s, _ = block_s(state_s, opt_s, batches, 0.05)
        check(np.asarray(state_s["theta_g"]["w"]),
              np.asarray(state_v["theta_g"]["w"]),
              err_msg=f"theta_g, block {blk}")
        check(np.asarray(state_s["delta"]["w"]),
              np.asarray(state_v["delta"]["w"]),
              err_msg=f"delta, block {blk}")


# -------------------------------------------------------------------- GTC

@given(seed=st.integers(0, 200), tau_exp=st.integers(-5, -1))
@settings(max_examples=30, deadline=None)
def test_gtc_conservation(seed, tau_exp):
    """send + residual' == residual + grad, always (error feedback)."""
    tau = 10.0 ** tau_exp
    rng = np.random.default_rng(seed)
    g = jnp.asarray(rng.normal(size=(17, 5)), jnp.float32) * 0.01
    r = jnp.asarray(rng.normal(size=(17, 5)), jnp.float32) * 0.01
    s, nr = G.compress_leaf(g, r, tau)
    np.testing.assert_allclose(np.asarray(s + nr), np.asarray(g + r),
                               atol=1e-6)
    # ternary wire alphabet
    vals = np.unique(np.abs(np.asarray(s)).round(8))
    assert set(vals).issubset({0.0, np.float32(tau).item()}) or \
        np.allclose(vals[vals > 0], tau, rtol=1e-5)


def test_gtc_eventually_transmits():
    """A constant small gradient accumulates in the residual and is
    eventually sent — no information is lost, only delayed."""
    tau = 1.0
    g = jnp.full((4,), 0.3, jnp.float32)
    r = jnp.zeros((4,))
    sent = jnp.zeros((4,))
    for _ in range(10):
        s, r = G.compress_leaf(g, r, tau)
        sent = sent + s
    total = np.asarray(sent + r)
    np.testing.assert_allclose(total, 3.0, atol=1e-5)
    assert float(jnp.abs(sent).sum()) > 0


def test_gtc_int8_roundtrip():
    tau = 0.125
    s = jnp.asarray([-tau, 0.0, tau, 0.0], jnp.float32)
    packed = G.pack_int8(s, tau)
    assert packed.dtype == jnp.int8
    np.testing.assert_allclose(np.asarray(G.unpack_int8(packed, tau)),
                               np.asarray(s), atol=1e-7)


def test_pack_int8_overflow_guard():
    """Summing > 127 ternary messages at int8 width would wrap; pack
    refuses to build that wire unless the accumulation widens."""
    s = jnp.zeros((4,), jnp.float32)
    G.pack_int8(s, 1e-3, n_workers=127)               # fits
    with pytest.raises(ValueError, match="int32_accum"):
        G.pack_int8(s, 1e-3, n_workers=128)
    G.pack_int8(s, 1e-3, n_workers=128, int32_accum=True)  # widened: fine
    with pytest.raises(ValueError):
        G.wire_pack(s, G.GTCConfig(tau=1e-3, n_workers=200))
    G.wire_pack(s, G.GTCConfig(tau=1e-3, n_workers=200, int32_accum=True))


def test_unpack_int8_averages_summed_workers():
    """unpack_int8 honors n_workers_summed: a summed wire of W packed
    messages unpacks to the worker-averaged update."""
    tau = 0.5
    summed = jnp.asarray([2, -2, 1, 0], jnp.int8)     # sum of 2 messages
    out = G.unpack_int8(summed, tau, n_workers_summed=2)
    np.testing.assert_allclose(np.asarray(out),
                               [0.5, -0.5, 0.25, 0.0], atol=1e-7)


def test_wire_reduce_single_worker_is_identity_on_sends():
    """The degenerate wire (pack -> unpack, no axes) is bitwise-identity
    on ternary sends — what lets GTCShardMap at W=1 be the single-worker
    trainer with the multi-worker arithmetic."""
    tau = 1e-3
    rng = np.random.default_rng(0)
    g = jnp.asarray(rng.normal(size=(33,)) * tau, jnp.float32)
    s, _ = G.compress_leaf(g, jnp.zeros((33,)), tau)
    cfg = G.GTCConfig(tau=tau, n_workers=1)
    out = G.wire_unpack(G.wire_pack(s, cfg), cfg)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(s))


def test_gtc_ring_converges_to_mean():
    """Repeated rounds on a constant gradient: cumulative applied update
    approaches rounds*mean(g) — 1-bit/threshold quantization delays but
    never loses information (error feedback)."""
    rng = np.random.default_rng(3)
    tau = 0.05
    # |g| < tau: the regime where the ±tau-per-round send keeps up with
    # the residual inflow (Strom picks tau above the typical grad scale)
    grads = [{"w": jnp.asarray(rng.normal(size=(6,)) * tau / 3,
                               jnp.float32)} for _ in range(4)]
    res = [{"w": jnp.zeros((6,))} for _ in range(4)]
    total = jnp.zeros((6,))
    rounds = 50
    for _ in range(rounds):
        avg, res = G.simulate_gtc_round(grads, res, tau)
        total = total + avg["w"]
    ref = rounds * np.mean([np.asarray(g["w"]) for g in grads], axis=0)
    # per-element residual is bounded by tau per worker
    np.testing.assert_allclose(np.asarray(total), ref, atol=4 * tau)


def test_gtc_strategy_matches_compress_tree():
    """GTCShardMap's update at W=1 == the manual reference: grads
    compressed by gtc_lib.compress_tree against the carried residual,
    with the *sent* sparse tensor driving the optimizer."""
    from repro.train import GTCShardMap, Trainer
    x, y = _problem(n=32)
    params = {"w": jnp.zeros((8,))}
    tau = 1e-3
    strat = GTCShardMap(G.GTCConfig(tau=tau, n_workers=1), worker_mesh(1),
                        clip=0.0)
    tr = Trainer(strat, {"quad": quad_loss})
    state = tr.init_state(params)
    lr = 0.05

    ref_params = {"w": jnp.zeros((8,))}
    ref_opt = momentum_init(ref_params)
    ref_res = {"w": jnp.zeros((8,))}
    batch = {"x": x, "y": y}
    for _ in range(3):
        state, _ = tr.updates["quad"](state, strat.stack([batch]),
                                      jnp.asarray(lr, jnp.float32))
        (_, _), g = jax.value_and_grad(quad_loss, has_aux=True)(
            ref_params, batch)
        send, ref_res = G.compress_tree(g, ref_res, tau)
        ref_params, ref_opt = momentum_update(ref_params, send, ref_opt,
                                              lr=lr)
    np.testing.assert_allclose(np.asarray(state.params["w"]),
                               np.asarray(ref_params["w"]), rtol=1e-6)
    np.testing.assert_allclose(
        np.asarray(state.strategy_state["residual"]["w"][0]),
        np.asarray(ref_res["w"]), rtol=1e-6)


# -------------------------------------------------- GTC sharded (tentpole)

def lin_loss(params, batch):
    """Linear probe: grad == batch["c"] bitwise (no float reassociation
    between eager references and the jitted step) — isolates the
    exchange arithmetic for the bitwise comparisons."""
    l = jnp.sum(params["w"] * batch["c"])
    return l, {"loss": l}


def _stack(dicts):
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *dicts)


@pytest.mark.parametrize("n_workers,quantize",
                         [(1, True), (2, True), (4, True), (2, False)])
def test_sharded_gtc_wire_matches_simulate_bitwise(n_workers, quantize):
    """The tentpole pin: make_sharded_gtc_train_step's applied update
    and per-worker residuals == simulate_gtc_round, BITWISE, for both
    the float and the packed-int8 wire (integer accumulation is exact,
    so the shard_map plumbing must add nothing).  W=1 is the
    single-worker trainer: its wire is a pack/unpack round-trip."""
    tau = 1e-3
    cfg = G.GTCConfig(tau=tau, n_workers=n_workers, quantize_int8=quantize)
    mesh = worker_mesh(n_workers)
    capture = lambda p, u, o, lr: (u, o)       # "params" := applied update
    step = jax.jit(G.make_sharded_gtc_train_step(lin_loss, capture, cfg,
                                                 mesh))
    params = {"w": jnp.zeros((9,))}
    state = {"residual": {"w": jnp.zeros((n_workers, 9))}}
    ref_res = [{"w": jnp.zeros((9,))} for _ in range(n_workers)]
    rng = np.random.default_rng(11)
    for it in range(4):
        cs = [{"c": jnp.asarray(rng.normal(size=(9,)) * tau, jnp.float32)}
              for _ in range(n_workers)]
        upd, _, state, ms = step(params, None, state, _stack(cs), 0.05)
        ref_upd, ref_res = G.simulate_gtc_round(
            [{"w": c["c"]} for c in cs], ref_res, tau,
            quantize_int8=quantize)
        np.testing.assert_array_equal(np.asarray(upd["w"]),
                                      np.asarray(ref_upd["w"]),
                                      err_msg=f"update, round {it}")
        for w in range(n_workers):
            np.testing.assert_array_equal(
                np.asarray(state["residual"]["w"][w]),
                np.asarray(ref_res[w]["w"]),
                err_msg=f"residual, worker {w}, round {it}")


def test_sharded_gtc_residual_conservation():
    """Error feedback conserves information across workers and rounds:
    sum of everything shipped (W * the averaged updates) plus the final
    residuals equals the sum of all gradients — compression delays,
    never drops."""
    tau = 2e-3
    W, D, rounds = 4, 16, 6
    cfg = G.GTCConfig(tau=tau, n_workers=W)     # int8 wire, /4 is exact
    mesh = worker_mesh(W)
    capture = lambda p, u, o, lr: (u, o)
    step = jax.jit(G.make_sharded_gtc_train_step(lin_loss, capture, cfg,
                                                 mesh))
    params = {"w": jnp.zeros((D,))}
    state = {"residual": {"w": jnp.zeros((W, D))}}
    rng = np.random.default_rng(3)
    total_g = np.zeros(D)
    total_sent = np.zeros(D)
    for _ in range(rounds):
        cs = [{"c": jnp.asarray(rng.normal(size=(D,)) * tau, jnp.float32)}
              for _ in range(W)]
        upd, _, state, _ = step(params, None, state, _stack(cs), 0.05)
        total_g += sum(np.asarray(c["c"], np.float64) for c in cs)
        total_sent += W * np.asarray(upd["w"], np.float64)
    final_res = np.asarray(state["residual"]["w"], np.float64).sum(0)
    np.testing.assert_allclose(total_sent + final_res, total_g, atol=1e-5)


def test_gtc_strategy_kernel_path_matches_ref():
    """GTCConfig(use_kernel=True) routes compression through the Pallas
    kernel (interpret mode on CPU) and matches the ref path to float32
    round-off.  (The kernel itself is element-exact vs the ref oracle —
    test_kernels pins that; across a *full jitted update* the pallas_call
    boundary blocks the elementwise fusion XLA applies to the inline
    ref, so the carried residual can drift by ~1 ulp.)"""
    from repro.train import GTCShardMap, Trainer, TrainBatch
    x, y = _problem(n=32)
    batch = {"x": x, "y": y}
    params = {"w": jnp.zeros((8,))}
    src = lambda: [TrainBatch(batch, 0.05, "quad") for _ in range(3)]
    outs = []
    for use_kernel in (False, True):
        tr = Trainer(GTCShardMap(G.GTCConfig(tau=1e-3, n_workers=1,
                                             use_kernel=use_kernel),
                                 worker_mesh(1), clip=0.0),
                     {"quad": quad_loss})
        st = tr.fit(tr.init_state(params), src(), resume=False)
        outs.append(st)
    np.testing.assert_allclose(np.asarray(outs[0].params["w"]),
                               np.asarray(outs[1].params["w"]),
                               rtol=1e-6)
    np.testing.assert_allclose(
        np.asarray(outs[0].strategy_state["residual"]["w"][0]),
        np.asarray(outs[1].strategy_state["residual"]["w"][0]),
        rtol=1e-5, atol=1e-6)


def test_adaptive_tau_density():
    rng = np.random.default_rng(4)
    g = jnp.asarray(rng.normal(size=(1000,)), jnp.float32)
    tau = G.adaptive_tau(g, 0.1)
    frac = float(jnp.mean(jnp.abs(g) > tau))
    assert 0.05 < frac < 0.15
