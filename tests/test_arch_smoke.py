"""Per-architecture smoke tests: reduced variant, one forward/train step on
CPU, asserting output shapes + no NaNs (the assignment's required smoke)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS, ASSIGNED, get_arch, reduced
from repro.configs.base import ShapeConfig
from repro.launch.steps import (init_opt_state, make_prefill_step,
                                make_serve_step, make_train_step)
from repro.models import build_model
from repro.models.api import input_specs

SMOKE_TRAIN = ShapeConfig("smoke", seq_len=16, global_batch=2, kind="train")

# XLA-compile-heavy reduced configs: their train/distill smokes dominate
# tier-1 wall clock, so they ride in the slow lane (run with -m slow or a
# plain unfiltered pytest; CI's fast lane deselects them).  The cheap
# representatives of each family stay in the fast lane.
SLOW_COMPILE = {"recurrentgemma-2b", "deepseek-v3-671b", "whisper-medium",
                "gemma3-27b", "xlstm-350m", "qwen3-moe-30b-a3b",
                "chameleon-34b", "deepseek-67b", "h2o-danube-3-4b",
                "deepseek-v2-lite"}


def _arch_params(archs):
    return [pytest.param(a, marks=pytest.mark.slow) if a in SLOW_COMPILE
            else a for a in archs]


def concrete_batch(cfg, shape, *, topk=0, seed=0):
    rng = np.random.default_rng(seed)
    specs = input_specs(cfg, shape, topk=topk)

    def mk(s):
        if jnp.issubdtype(s.dtype, jnp.integer):
            return jnp.asarray(rng.integers(0, cfg.vocab_size, s.shape),
                               s.dtype)
        return jnp.asarray(rng.normal(size=s.shape) * 0.1, s.dtype)
    return jax.tree_util.tree_map(
        mk, specs, is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct))


@pytest.mark.parametrize("arch", _arch_params(ASSIGNED + ["lstm-am-7khr",
                                                          "lstm-am-teacher"]))
def test_train_step_smoke(arch):
    cfg = reduced(get_arch(arch))
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    batch = concrete_batch(cfg, SMOKE_TRAIN)
    step = jax.jit(make_train_step(model, cfg, loss_kind="ce"))
    opt = init_opt_state(params)
    params2, opt2, metrics = step(params, opt, batch, 1e-2)
    assert jnp.isfinite(metrics["loss"]), arch
    # params actually move
    moved = jax.tree_util.tree_map(
        lambda a, b: float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                           - b.astype(jnp.float32)))),
        params, params2)
    assert max(jax.tree_util.tree_leaves(moved)) > 0


@pytest.mark.parametrize("arch", _arch_params(ASSIGNED))
def test_distill_step_smoke(arch):
    cfg = reduced(get_arch(arch))
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    batch = concrete_batch(cfg, SMOKE_TRAIN, topk=5)
    step = jax.jit(make_train_step(model, cfg, loss_kind="distill_topk"))
    opt = init_opt_state(params)
    _, _, metrics = step(params, opt, batch, 1e-2)
    assert jnp.isfinite(metrics["loss"]), arch


@pytest.mark.parametrize("arch", _arch_params(ASSIGNED))
def test_decode_smoke(arch):
    cfg = reduced(get_arch(arch))
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    cache = model.init_cache(2, 16, jnp.bfloat16)
    serve = jax.jit(make_serve_step(model, cfg))
    toks = jnp.array([[1], [2]], jnp.int32)
    for _ in range(3):
        toks, logits, cache = serve(params, cache, toks)
    assert logits.shape == (2, 1, cfg.vocab_size)
    assert bool(jnp.all(jnp.isfinite(logits)))


@pytest.mark.parametrize("arch", [
    "qwen2.5-3b",                           # rope + GQA, linear cache
    pytest.param("gemma3-27b", marks=pytest.mark.slow),        # swa ring
    pytest.param("deepseek-v3-671b", marks=pytest.mark.slow),  # mla latent
    pytest.param("recurrentgemma-2b", marks=pytest.mark.slow)])  # rglru
def test_per_row_cache_matches_scalar(arch):
    """A per-row position cache run in lockstep is bitwise-identical to
    the scalar-position cache — the shape-compatible special case the
    continuous batcher's parity rests on (ring indexing, masking and
    RoPE lookups row-indexed vs shared)."""
    cfg = reduced(get_arch(arch))
    model = build_model(cfg)
    params = model.init(jax.random.key(1))
    rng = np.random.default_rng(5)
    toks = jnp.asarray(rng.integers(1, cfg.vocab_size, (2, 8)), jnp.int32)
    c_s = model.init_cache(2, 12, jnp.float32)
    c_r = model.init_cache(2, 12, jnp.float32, per_row=True)
    assert c_r["pos"].shape == (2,) and c_s["pos"].shape == ()
    step = jax.jit(model.decode_step)
    for t in range(8):
        lg_s, c_s = step(params, c_s, toks[:, t:t + 1])
        lg_r, c_r = step(params, c_r, toks[:, t:t + 1])
        np.testing.assert_array_equal(np.asarray(lg_r), np.asarray(lg_s))
    np.testing.assert_array_equal(np.asarray(c_r["pos"]), [8, 8])


@pytest.mark.parametrize("arch", [
    "qwen2.5-3b",                           # rope + GQA, plain pool pages
    pytest.param("gemma3-27b", marks=pytest.mark.slow),        # swa ring
    pytest.param("deepseek-v3-671b", marks=pytest.mark.slow),  # mla latent
    pytest.param("whisper-medium", marks=pytest.mark.slow)])  # enc-dec
def test_paged_cache_matches_contiguous(arch):
    """The block-table pool layout decodes like the contiguous per-row
    cache: identical cache contents at every written slot and the same
    greedy argmax at every step (logits match to fp-reassociation
    tolerance — the gather-based contraction may fuse differently)."""
    from repro.models.paging import PagedCacheConfig
    cfg = reduced(get_arch(arch))
    model = build_model(cfg)
    params = model.init(jax.random.key(1))
    paging = PagedCacheConfig(page_size=4, n_pages=8, max_ctx=16)
    paged = build_model(cfg, paging=paging)
    rng = np.random.default_rng(5)
    toks = jnp.asarray(rng.integers(1, cfg.vocab_size, (2, 8)), jnp.int32)
    c_r = model.init_cache(2, 16, jnp.float32, per_row=True)
    c_p = paged.init_cache(2, 16, jnp.float32, per_row=True)
    if cfg.encoder is not None:
        feats = jnp.asarray(rng.standard_normal((2, 16, cfg.d_model)),
                            jnp.float32)
        c_r = model.prefill_cache(params, feats, c_r)
        c_p = paged.prefill_cache(params, feats, c_p)
    # hand each row a disjoint page run (what the serve-side allocator
    # does); page 0 stays the trash page
    c_p["pages"]["tables"] = jnp.asarray([[1, 2, 3, 4], [5, 6, 7, 8]],
                                         jnp.int32)
    c_p["pages"]["caps"] = jnp.asarray([16, 16], jnp.int32)
    step_r = jax.jit(model.decode_step)
    step_p = jax.jit(paged.decode_step)
    for t in range(8):
        lg_r, c_r = step_r(params, c_r, toks[:, t:t + 1])
        lg_p, c_p = step_p(params, c_p, toks[:, t:t + 1])
        np.testing.assert_allclose(np.asarray(lg_p), np.asarray(lg_r),
                                   rtol=1e-3, atol=1e-4)
        np.testing.assert_array_equal(np.asarray(lg_p).argmax(-1),
                                      np.asarray(lg_r).argmax(-1))
    np.testing.assert_array_equal(np.asarray(c_p["pos"]), [8, 8])


def test_per_row_ragged_reset_matches_solo():
    """Rows at *different* positions in one batch: row 1 is admitted
    mid-decode via reset_cache_rows and fed its own stream — each row's
    logits match its solo decode (row purity + per-row positions)."""
    cfg = reduced(get_arch("qwen2.5-3b"))
    model = build_model(cfg)
    params = model.init(jax.random.key(1))
    rng = np.random.default_rng(6)
    toks = jnp.asarray(rng.integers(1, cfg.vocab_size, (2, 10)), jnp.int32)
    step = jax.jit(model.decode_step)
    cache = model.init_cache(2, 16, jnp.float32, per_row=True)
    for t in range(4):                     # row 0 runs alone (row 1 junk)
        feed = jnp.stack([toks[0, t:t + 1], jnp.asarray([7], jnp.int32)])
        _, cache = step(params, cache, feed)
    cache = jax.jit(model.reset_cache_rows)(cache,
                                            jnp.asarray([False, True]))
    np.testing.assert_array_equal(np.asarray(cache["pos"]), [4, 0])
    got = {0: [], 1: []}
    for t in range(6):                     # ragged: rows 4 positions apart
        feed = jnp.stack([toks[0, 4 + t:5 + t], toks[1, t:t + 1]])
        lg, cache = step(params, cache, feed)
        got[0].append(np.asarray(lg[0, 0]))
        got[1].append(np.asarray(lg[1, 0]))
    for row, start in ((0, 4), (1, 0)):
        solo_cache = model.init_cache(1, 16, jnp.float32, per_row=True)
        ref = []
        for t in range(start + 6):
            lg, solo_cache = step(params, solo_cache,
                                  toks[row:row + 1, t:t + 1])
            ref.append(np.asarray(lg[0, 0]))
        np.testing.assert_allclose(np.stack(got[row]),
                                   np.stack(ref[start:]), atol=1e-4)


@pytest.mark.slow
def test_whisper_per_row_decode_smoke():
    """The enc-dec arch also exposes the per-row surface: positions
    advance per row and reset_cache_rows keeps the cross-attention K/V
    (encoder side) while zeroing the self-attention rows."""
    cfg = reduced(get_arch("whisper-medium"))
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    enc = jnp.asarray(np.random.default_rng(7).normal(
        size=(2, 12, cfg.d_model)) * 0.1, jnp.float32)
    cache = model.init_cache(2, 12, jnp.float32, per_row=True)
    cache = model.prefill_cache(params, enc, cache)
    step = jax.jit(model.decode_step)
    toks = jnp.array([[1], [2]], jnp.int32)
    for _ in range(3):
        logits, cache = step(params, cache, toks)
        toks = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)[:, None]
    assert bool(jnp.all(jnp.isfinite(logits)))
    np.testing.assert_array_equal(np.asarray(cache["pos"]), [3, 3])
    ck_before = np.asarray(cache["ck"])
    cache = model.reset_cache_rows(cache, jnp.asarray([True, False]))
    np.testing.assert_array_equal(np.asarray(cache["pos"]), [0, 3])
    np.testing.assert_array_equal(np.asarray(cache["ck"]), ck_before)
    assert float(jnp.abs(cache["k"][:, 0]).max()) == 0.0   # row 0 zeroed


@pytest.mark.parametrize("arch", [
    "qwen2.5-3b",
    pytest.param("xlstm-350m", marks=pytest.mark.slow),
    pytest.param("recurrentgemma-2b", marks=pytest.mark.slow),
    pytest.param("gemma3-27b", marks=pytest.mark.slow),
    # MLA without query LoRA, YaRN, dropless MoE
    pytest.param("deepseek-v2-lite", marks=pytest.mark.slow)])
def test_decode_matches_apply(arch):
    """Strong consistency: token-by-token decode logits == full forward."""
    cfg = reduced(get_arch(arch))
    model = build_model(cfg)
    params = model.init(jax.random.key(1))
    rng = np.random.default_rng(3)
    s = 12
    toks = jnp.asarray(rng.integers(1, cfg.vocab_size, (1, s)), jnp.int32)
    h, _ = model.apply(params, toks)
    full_logits = model.unembed(params, h)          # (1, S, V)
    cache = model.init_cache(1, s, jnp.float32)
    outs = []
    step = jax.jit(model.decode_step)
    for t in range(s):
        lg, cache = step(params, cache, toks[:, t:t + 1])
        outs.append(lg[:, 0])
    dec_logits = jnp.stack(outs, axis=1)
    np.testing.assert_allclose(np.asarray(dec_logits),
                               np.asarray(full_logits), rtol=0.05, atol=0.15)


@pytest.mark.slow
def test_mla_absorbed_decode_matches_apply():
    """deepseek-v3's absorbed decode == decompressed full attention."""
    cfg = reduced(get_arch("deepseek-v3-671b"))
    model = build_model(cfg)
    params = model.init(jax.random.key(2))
    rng = np.random.default_rng(4)
    s = 10
    toks = jnp.asarray(rng.integers(1, cfg.vocab_size, (1, s)), jnp.int32)
    h, _ = model.apply(params, toks)
    full_logits = model.unembed(params, h)
    cache = model.init_cache(1, s, jnp.float32)
    outs = []
    step = jax.jit(model.decode_step)
    for t in range(s):
        lg, cache = step(params, cache, toks[:, t:t + 1])
        outs.append(lg[:, 0])
    dec = jnp.stack(outs, axis=1)
    np.testing.assert_allclose(np.asarray(dec), np.asarray(full_logits),
                               rtol=0.05, atol=0.2)


def test_moe_aux_outputs():
    cfg = reduced(get_arch("qwen3-moe-30b-a3b"))
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    toks = jnp.ones((2, 16), jnp.int32)
    _, aux = model.apply(params, toks)
    lb = [v for k, v in aux.items() if k.endswith("moe_lb_loss")]
    assert lb and all(jnp.isfinite(v) for v in lb)
    # load-balance loss >= 1 for any router (equality at perfect balance)
    assert all(float(v) > 0.5 for v in lb)


@pytest.mark.slow
def test_whisper_encdec_shapes():
    cfg = reduced(get_arch("whisper-medium"))
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    enc = jnp.zeros((2, 24, cfg.d_model), jnp.float32)
    toks = jnp.ones((2, 8), jnp.int32)
    h, _ = model.apply(params, toks, enc_embeds=enc)
    assert h.shape == (2, 8, cfg.d_model)
    logits = model.unembed(params, h)
    assert logits.shape == (2, 8, cfg.vocab_size)
