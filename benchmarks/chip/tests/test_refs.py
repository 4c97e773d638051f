"""The plain float32 references against the program's paths, at a tiny
size on the CPU."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import data, ref_lstm_am as ref
from bench.program import model_config

TINY = {"n_layers": 2, "lstm_hidden": 16, "feat_dim": 12, "n_senones": 40}
TOL = dict(rtol=2e-5, atol=2e-5)


def cfg(bidirectional):
    return dict(TINY, bidirectional=bidirectional)


def batch(seed=0, b=5, t=9):
    r = np.random.default_rng(seed)
    feats = r.standard_normal((b, t, TINY["feat_dim"])).astype(np.float32)
    lens = np.array([t, 1, 4, t - 2, 6][:b], np.int32)
    return feats, lens


def program_logits(c, params, feats, lens):
    from repro.models import build_model
    model = build_model(model_config(c))
    h, _ = model.apply(params, jnp.asarray(feats), lens=jnp.asarray(lens))
    return np.asarray(model.unembed(params, h))


@pytest.mark.parametrize("bidirectional", [False, True],
                         ids=["lstm", "bilstm"])
def test_forward_matches_program_on_valid_frames(bidirectional):
    c = cfg(bidirectional)
    params = ref.init_params(c, 3)
    feats, lens = batch()
    with jax.default_matmul_precision("highest"):
        want = np.asarray(ref.logits(params, jnp.asarray(feats),
                                     jnp.asarray(lens), c))
        got = program_logits(c, params, feats, lens)
    for b, n in enumerate(lens):
        np.testing.assert_allclose(got[b, :n], want[b, :n], **TOL)


def test_reverse_valid_is_an_involution_on_valid_frames():
    feats, lens = batch()
    x = jnp.asarray(feats)
    twice = ref.reverse_valid(ref.reverse_valid(x, jnp.asarray(lens)),
                              jnp.asarray(lens))
    for b, n in enumerate(lens):
        np.testing.assert_array_equal(np.asarray(twice[b, :n]), feats[b, :n])
        assert not np.asarray(twice[b, n:]).any()


def test_emission_matches_program_emitter():
    from repro.serve.engine import make_topk_emitter
    lg = jnp.asarray(np.random.default_rng(1).standard_normal((7, 40)),
                     jnp.float32)
    pv, pi = make_topk_emitter(5, "lax")(lg)
    rv, ri = ref.emit_topk(lg, 5)
    np.testing.assert_array_equal(np.asarray(pi), np.asarray(ri))
    np.testing.assert_array_equal(np.asarray(pv, np.float32), np.asarray(rv))
    gap = ref.emission_gap(rv, ri, lg)
    assert float(jnp.max(gap)) < 2 ** -7      # bfloat16 rounding only


def train_batch(i, c, b=4, t=6, k=5):
    r = np.random.default_rng(10 + i)
    mask = np.ones((b, t), np.float32)
    mask[0, 3:] = 0
    vals, idx = data.topk_targets(i, (b, t), k, c["n_senones"])
    return {"feats": r.standard_normal((b, t, c["feat_dim"])).astype(
                np.float32),
            "mask": mask, "topk_vals": vals, "topk_idx": idx}


def loss_fn(c):
    from repro.launch.steps import make_loss_fn
    from repro.models import build_model
    mc = model_config(c)
    return make_loss_fn(build_model(mc), mc, "distill_topk")


def test_distill_step_matches_trainer_local():
    from repro.train import Local, ListSink, TrainBatch, Trainer
    c = cfg(False)
    p0 = ref.init_params(c, 5)
    sink = ListSink()
    tr = Trainer(Local(clip=0.05), {"distill_topk": loss_fn(c)}, metrics=sink)
    with jax.default_matmul_precision("highest"):
        st = tr.fit(tr.init_state(p0, seed=0),
                    (TrainBatch(train_batch(i, c), 0.1, "distill_topk")
                     for i in range(2)), resume=False)
        p, mu = p0, jax.tree_util.tree_map(jnp.zeros_like, p0)
        losses = []
        for i in range(2):
            b = jax.tree_util.tree_map(jnp.asarray, train_batch(i, c))
            p, mu, loss = ref.sgd_step(p, mu, b, 0.1, c, clip=0.05, beta=0.9)
            losses.append(float(loss))
    np.testing.assert_allclose(sink.values("loss"), losses, rtol=1e-5)
    for a, b in zip(jax.tree_util.tree_leaves(st.params),
                    jax.tree_util.tree_leaves(p)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), **TOL)
    for a, b in zip(jax.tree_util.tree_leaves(st.opt_state["mu"]),
                    jax.tree_util.tree_leaves(mu)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), **TOL)


def test_bf16_state_control_keeps_parameters_in_bfloat16():
    """The bf16_state control stores bfloat16 parameters after a step and
    its logits lie near, but not on, the float32 reference's."""
    c = cfg(False)
    p0 = ref.init_params(c, 4)
    feats, lens = batch(2)
    b = {"feats": jnp.asarray(feats), "mask": jnp.ones(feats.shape[:2]),
         "topk_vals": jnp.zeros(feats.shape[:2] + (3,)),
         "topk_idx": jnp.tile(jnp.arange(3), feats.shape[:2] + (1,))}
    with jax.default_matmul_precision("highest"):
        p1, _, _ = ref.sgd_step(p0, jax.tree_util.tree_map(jnp.zeros_like, p0),
                                b, 0.1, c, clip=1.0, beta=0.9,
                                prec=ref.BF16_STATE)
        want = ref.logits(p0, jnp.asarray(feats), jnp.asarray(lens), c)
        low = ref.logits(p0, jnp.asarray(feats), jnp.asarray(lens), c,
                         ref.BF16_STATE)
    for x in jax.tree_util.tree_leaves(p1):
        np.testing.assert_array_equal(
            np.asarray(x), np.asarray(x.astype(jnp.bfloat16), np.float32))
    err = float(jnp.max(jnp.abs(low - want)) / jnp.max(jnp.abs(want)))
    assert 1e-4 < err < 0.1


def test_reference_imports_nothing_of_the_program():
    import ast
    import inspect
    for mod in (ref,):
        tree = ast.parse(inspect.getsource(mod))
        names = [a.name for n in ast.walk(tree)
                 if isinstance(n, (ast.Import, ast.ImportFrom))
                 for a in n.names] + [n.module for n in ast.walk(tree)
                                      if isinstance(n, ast.ImportFrom)]
        assert not any(str(x).startswith("repro") for x in names)
