"""DeepSeek-V2-Lite on the teacher path, against the plain float32
reference (the benchmark's ``benchmarks/chip/bench/ref_deepseek_v2.py``,
read from there) on seeded random weights, at the
arch's ``reduced()`` size with 8 routed experts (top-2, 1 shared expert,
kv_lora_rank 32, no query LoRA, YaRN on): the teacher's top-20, the dropless
MoE under a skewed router, YaRN, the row-chunked emitter, ``moe_gmm`` and
``generate_sharded``'s expert load."""
import importlib.util
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_arch, reduced
from repro.core.teacher import TeacherRunner
from repro.kernels.moe_gmm import moe_gmm, moe_gmm_ref
from repro.models import layers, mla, moe
from repro.serve import engine as engine_mod

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REF_PATH = os.path.join(ROOT, "benchmarks", "chip", "bench",
                        "ref_deepseek_v2.py")
K = 20


def _load_reference():
    spec = importlib.util.spec_from_file_location("ref_deepseek_v2",
                                                  REF_PATH)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ref = _load_reference()


def small_cfg():
    return reduced(get_arch("deepseek-v2-lite")).replace(n_experts=8)


def hf_config(cfg) -> dict:
    """The ModelConfig under the model's own config.json keys (what the
    reference reads)."""
    m, y = cfg.mla, cfg.rope_scaling
    return dict(
        hidden_size=cfg.d_model, num_attention_heads=cfg.n_heads,
        q_lora_rank=m.q_lora_rank or None, kv_lora_rank=m.kv_lora_rank,
        qk_nope_head_dim=m.qk_nope_head_dim,
        qk_rope_head_dim=m.qk_rope_head_dim, v_head_dim=m.v_head_dim,
        intermediate_size=cfg.d_ff, moe_intermediate_size=cfg.moe_d_ff,
        n_routed_experts=cfg.n_experts,
        n_shared_experts=cfg.n_shared_experts,
        num_experts_per_tok=cfg.moe_top_k, num_hidden_layers=cfg.n_layers,
        first_k_dense_replace=cfg.segments[0].n_layers,
        vocab_size=cfg.vocab_size, rope_theta=cfg.rope_theta,
        rms_norm_eps=1e-6, norm_topk_prob=cfg.moe_renorm_topk,
        routed_scaling_factor=1.0,
        rope_scaling=dict(factor=y.factor,
                          original_max_position_embeddings=(
                              y.original_max_position),
                          beta_fast=y.beta_fast, beta_slow=y.beta_slow,
                          mscale=y.mscale, mscale_all_dim=y.mscale_all_dim))


def tokens(cfg, shape, seed=0):
    return jnp.asarray(np.random.default_rng(seed).integers(
        0, cfg.vocab_size, shape), jnp.int32)


def ref_logits(params, toks, hf):
    with jax.default_matmul_precision("highest"):
        return np.asarray(ref.logits(params, toks, hf))


def emission_gap(vals, idx, lg):
    """As the benchmark reads it: |stored value - (ref logit at the stored
    id - ref best)| in units of the position's ref logit RMS."""
    lg = lg.reshape(-1, lg.shape[-1])
    best = lg.max(-1, keepdims=True)
    rms = np.sqrt(np.mean(lg * lg, -1, keepdims=True))
    at = np.take_along_axis(lg, np.asarray(idx).reshape(len(lg), -1), -1)
    return np.abs(np.asarray(vals, np.float32).reshape(len(lg), -1)
                  - (at - best)) / rms


def test_reference_copy_is_the_benchmarks():
    """These tests read the benchmark's own reference file, the one copy,
    which imports nothing of the program under test."""
    assert os.path.samefile(ref.__file__, REF_PATH)
    with open(REF_PATH) as f:
        assert "repro" not in f.read()


# --------------------------------------------------------------- forward

@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_teacher_topk_matches_reference(dtype):
    """TeacherRunner.forward_topk (a dense first layer, then MoE) against
    the reference's logits.  float32 parameters: the same ids in the same
    order, values within bf16 rounding of the emitted values (2^-8 of the
    largest shifted magnitude, plus 1e-4 for float32 reassociation).
    bfloat16 parameters (as released): the emission gap the benchmark
    reads stays under 0.1 logit RMS (bf16 operands over two layers read
    0.03 here; the fp8 control reads several times that)."""
    cfg = small_cfg().replace(param_dtype=dtype)
    hf = hf_config(cfg)
    params = ref.init_params(hf, 11)
    toks = tokens(cfg, (2, 32), seed=1)
    lg = ref_logits(params, toks, hf)
    out = TeacherRunner(cfg, params, k=K).forward_topk({"tokens": toks})
    vals, idx = out
    counts = out.expert_counts
    assert counts.shape == (cfg.n_layers - 1, cfg.n_experts)
    assert np.all(np.asarray(counts).sum(-1) == toks.size * cfg.moe_top_k)
    if dtype == "float32":
        rv, ri = jax.lax.top_k(jnp.asarray(lg), K)
        np.testing.assert_array_equal(np.asarray(idx), np.asarray(ri))
        want = np.asarray(rv - rv[..., :1])
        np.testing.assert_allclose(np.asarray(vals, np.float32), want,
                                   atol=1e-4 + np.abs(want).max() * 2 ** -8)
    else:
        assert emission_gap(vals, idx, lg).max() < 0.1


# ------------------------------------------------------------------ MoE

def test_dropless_moe_matches_per_token_reference_under_skew():
    """A router that sends every token to expert 0 (T tokens, against a
    mean of T*k/E; a 1.25 capacity would have dropped most of them): the
    layer equals the reference's per-token sum of each token's experts.
    float32 throughout; 1e-5 is reassociation."""
    cfg = small_cfg()
    hf = hf_config(cfg)
    p = jax.tree_util.tree_map(
        lambda a: a[0].astype(jnp.float32),
        ref.init_params(hf, 5)["seg1"]["p0"]["ffn"])
    # inputs share a positive offset on every dim, and the router's
    # expert-0 column reads it: expert 0 wins every row by a wide margin
    p["router"] = p["router"].at[:, 0].add(0.5)
    x = jnp.asarray(np.random.default_rng(2).normal(
        size=(2, 16, cfg.d_model)) + 2.0, jnp.float32)
    x = x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True))    # normed input
    with jax.default_matmul_precision("highest"):
        y, aux = moe.moe_apply(p, cfg, x)
        want = ref.moe(p, x, hf, "f32")
    counts = np.asarray(aux["expert_counts"])
    t = x.shape[0] * x.shape[1]
    assert counts[0] == t > 1.25 * t * cfg.moe_top_k / cfg.n_experts
    assert counts.sum() == t * cfg.moe_top_k
    np.testing.assert_allclose(np.asarray(y), np.asarray(want), atol=1e-5,
                               rtol=1e-5)


@pytest.mark.parametrize("use_kernel", [False, True],
                         ids=["ragged_dot", "pallas-interpret"])
@pytest.mark.parametrize("sizes", [(5, 0, 7, 4), (0, 16, 0, 0),
                                   (3, 3, 3, 7)],
                         ids=["one-empty", "one-full", "even"])
def test_moe_gmm_matches_ref(use_kernel, sizes):
    """Grouped matmul against the gather oracle, empty groups included.
    float32 operands: 1e-5 is reassociation."""
    rng = np.random.default_rng(8)
    x = jnp.asarray(rng.normal(size=(16, 64)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(4, 64, 128)), jnp.float32)
    gs = jnp.asarray(sizes, jnp.int32)
    with jax.default_matmul_precision("highest"):
        got = moe_gmm(x, w, gs, use_kernel=use_kernel)
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(moe_gmm_ref(x, w, gs)),
                               atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("use_kernel", [False, True],
                         ids=["ragged_dot", "pallas-interpret"])
@pytest.mark.parametrize("offset", [0, 2], ids=["first-half", "second-half"])
def test_moe_gmm_group_offset_matches_ref(use_kernel, offset):
    """An expert-parallel shard's grouped matmul: ``w`` holds groups
    ``offset`` and ``offset + 1`` of four (one of them empty in each
    half), and the rows of the other groups come out zero.  float32
    operands: 1e-5 is reassociation."""
    rng = np.random.default_rng(9)
    x = jnp.asarray(rng.normal(size=(16, 64)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(4, 64, 128)), jnp.float32)
    gs = jnp.asarray((5, 0, 0, 11), jnp.int32)
    ends = np.cumsum(gs)
    rows = np.arange(16)
    mine = (rows >= ([0] + list(ends))[offset]) & (rows < ends[offset + 1])
    want = np.where(mine[:, None], np.asarray(moe_gmm_ref(x, w, gs)), 0.0)
    with jax.default_matmul_precision("highest"):
        got = moe_gmm(x, w[offset:offset + 2], gs,
                      group_offset=jnp.int32(offset), use_kernel=use_kernel)
    np.testing.assert_allclose(np.asarray(got), want, atol=1e-5, rtol=1e-5)


EXPERT_PARALLEL = """
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import Mesh
from repro.configs import get_arch, reduced
from repro.models import moe
cfg = reduced(get_arch("deepseek-v2-lite")).replace(n_experts=8)
p = moe.init_moe(jax.random.PRNGKey(0), cfg)
x = jax.random.normal(jax.random.PRNGKey(1), (4, 16, cfg.d_model))
mesh = Mesh(np.array(jax.devices()).reshape(2, 2), ("data", "model"))
def loss(p, x):
    y, aux = moe.moe_apply(p, cfg, x)
    return jnp.sum(y * y), (y, aux["expert_counts"])
f = jax.jit(jax.value_and_grad(loss, has_aux=True))
with jax.default_matmul_precision("highest"):
    (_, (y1, c1)), g1 = f(p, x)
    with jax.set_mesh(mesh):
        assert moe._expert_mesh(cfg.n_experts) is not None
        (_, (y2, c2)), g2 = jax.jit(jax.value_and_grad(loss, has_aux=True))(
            p, x)
np.testing.assert_array_equal(np.asarray(c1), np.asarray(c2))
np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), atol=1e-5,
                           rtol=1e-5)
for a, b in zip(jax.tree_util.tree_leaves(g1), jax.tree_util.tree_leaves(g2)):
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4,
                               rtol=1e-4)
print("EQUAL")
"""


def test_expert_parallel_equals_one_device():
    """The MoE layer under a 2x2 (data, model) mesh of four CPU devices,
    expert-parallel in its ``shard_map``, against the same layer with no
    mesh: outputs, expert counts and the parameters' gradients.  Its own
    process, which alone can ask for four host devices.  float32 at the
    highest precision: 1e-5 (outputs) and 1e-4 (gradients, summed over
    every row) are reassociation."""
    import subprocess
    import sys
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.path.join(ROOT, "src"))
    out = subprocess.run([sys.executable, "-c", EXPERT_PARALLEL], env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0 and "EQUAL" in out.stdout, out.stderr[-3000:]


# ------------------------------------------------------------------ YaRN

def test_yarn_matches_closed_form():
    """DeepSeek-V2-Lite's YaRN (factor 40 over 4,096, beta_fast 32,
    beta_slow 1, 64 rope dims, theta 1e4): pairs 0..10 keep the original
    frequency, 23..31 run 40x slower, the ramp between is linear; softmax
    scale mscale^2 / sqrt(192) with mscale = 0.1 * 0.707 * ln 40 + 1."""
    cfg = get_arch("deepseek-v2-lite")
    base = 1.0 / 10_000.0 ** (np.arange(0, 64, 2) / 64)
    lo = math.floor(64 * math.log(4096 / (32 * 2 * math.pi))
                    / (2 * math.log(10_000)))
    hi = math.ceil(64 * math.log(4096 / (1 * 2 * math.pi))
                   / (2 * math.log(10_000)))
    assert (lo, hi) == (10, 23)
    ramp = np.clip((np.arange(32) - 10) / 13, 0, 1)
    want = base * (1 - ramp) + base / 40 * ramp
    got = layers.rope_inv_freq(64, 1e4, cfg.rope_scaling)
    np.testing.assert_allclose(got, want, rtol=1e-6)
    np.testing.assert_allclose(ref.rope_inv_freq(hf_config(cfg)), want,
                               rtol=1e-12)
    ms = 0.1 * 0.707 * math.log(40) + 1
    assert abs(ms - 1.2608) < 1e-4
    assert mla.softmax_scale(cfg) == pytest.approx(ms ** 2 / math.sqrt(192),
                                                   rel=1e-12)
    assert cfg.rope_scaling.mscale_ratio() == 1.0


# ------------------------------------------------------------ the emitter

def test_emitter_chunks_from_shapes():
    """teacher-gen's 1,024 x 64 frames x 3,183 senones are one chunk; the
    LM cell's 8 x 4,096 positions x 102,400 tokens are 16 of 2,048 rows."""
    assert engine_mod.logit_chunks(1024 * 64, 3183) == 1
    assert engine_mod.logit_chunks(8 * 4096, 102_400) == 16
    assert engine_mod.logit_chunks(7, 1 << 40) == 7


def test_teacher_gen_emitter_is_one_unchunked_program():
    """At teacher-gen's shape the emitter is exactly head + top-k over the
    whole batch: the same jaxpr as without chunking."""
    from repro.core.ssl_pipeline import am_configs
    _, tea = am_configs(n_layers=1, lstm_hidden=768, n_senones=3183,
                        feat_dim=192)
    eng = engine_mod.StreamingEngine(tea, None, k=K)
    p = {"out": jax.ShapeDtypeStruct((1536, 3183), jnp.float32)}
    h = jax.ShapeDtypeStruct((1024, 64, 1536), jnp.float32)
    got = jax.make_jaxpr(eng._emit_rows)(p, h)
    want = jax.make_jaxpr(lambda p, h: eng._emit(
        eng.model.unembed(p, h) / eng.temperature))(p, h)
    assert str(got) == str(want)


def test_chunked_emitter_equals_one_chunk(monkeypatch):
    """Forced to row chunks, the emitter gives the one-chunk output
    exactly (each row's head and top-k are computed alike; float32
    parameters, so that no bf16 rounding of a row's logit can differ)."""
    cfg = small_cfg().replace(param_dtype="float32")
    params = ref.init_params(hf_config(cfg), 2)
    batch = {"tokens": tokens(cfg, (2, 16), seed=3)}
    one = TeacherRunner(cfg, params, k=K).forward_topk(batch)
    monkeypatch.setattr(engine_mod, "LOGIT_CHUNK_BYTES",
                        8 * cfg.vocab_size * 4)       # 8 rows a chunk
    assert engine_mod.logit_chunks(32, cfg.vocab_size) == 4
    four = TeacherRunner(cfg, params, k=K).forward_topk(batch)
    for a, b in zip((*one, one.expert_counts), (*four, four.expert_counts)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ------------------------------------------------------- generate_sharded

def test_generate_sharded_writes_lm_shards_with_expert_load(tmp_path):
    """The LM teacher through generate_sharded: one shard per batch holding
    the forward's top-k, and the report's ``expert_load`` per MoE layer, the
    worst over shards of max / mean expert tokens."""
    from repro.pipeline import generate_sharded
    from repro.pipeline.generate import expert_load
    from repro.store import LogitStoreV2
    cfg = small_cfg()
    runner = TeacherRunner(cfg, ref.init_params(hf_config(cfg), 4), k=K)
    batches = [{"tokens": tokens(cfg, (2, 16), seed=s)} for s in range(3)]
    store = LogitStoreV2(str(tmp_path), k=K, vocab=cfg.vocab_size)
    rep = generate_sharded(lambda w: runner, batches, store)
    assert rep["n_written"] == 3
    loads = []
    for i, b in enumerate(batches):
        out = runner.forward_topk(b)
        vals, idx = out
        counts = out.expert_counts
        sv, si = store.read_shard(i)
        np.testing.assert_array_equal(np.asarray(si), np.asarray(idx))
        np.testing.assert_array_equal(
            np.asarray(sv), np.asarray(vals).astype(np.float16))
        loads.append(expert_load(counts))
    assert len(rep["expert_load"]) == cfg.n_layers - 1
    np.testing.assert_allclose(rep["expert_load"], np.max(loads, axis=0))
    assert all(v >= 1.0 for v in rep["expert_load"])
