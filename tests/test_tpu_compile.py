"""Ahead-of-time compiles of the Pallas kernels for a described TPU v5e.

Interpret-mode parity (test_kernels.py, test_decode_kernels.py) cannot
show that Mosaic accepts a kernel's tiles; these tests lower and compile
each kernel of the main path at its real width for a v5e chip that is
described, not attached, and check that the compiled program still holds
each expected kernel, by name, as a ``tpu_custom_call`` rather than an
XLA fallback.

The topology is described inside a module-scoped fixture, never at
import time: only one process may load the TPU compiler library, and a
module that decided at collection time whether its tests exist would
hand multi-worker pytest runs different test lists.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest

from repro.kernels._dispatch import compiled_kernels
from repro.kernels.decode_attention import decode_attention
from repro.kernels.gtc_compress import gtc_compress
from repro.kernels.moe_gmm import moe_gmm
from repro.kernels.sparse_ce import topk_distill_ce
from repro.kernels.swa_attention import swa_attention
from repro.kernels.topk_logits import topk_logits
from repro.kernels.topk_sample import topk_sample


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:          # no TPU compiler library here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def spec(topo):
    """shape, dtype -> a ShapeDtypeStruct placed on one described chip."""
    from jax.sharding import SingleDeviceSharding
    one_chip = SingleDeviceSharding(topo.devices[0])

    def make(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    return make


def _compiled_text(fn, *args):
    return jax.jit(fn).lower(*args).compile().as_text()


def _assert_kernels(txt, *names):
    assert "tpu_custom_call" in txt
    found = compiled_kernels(txt)
    assert all(found[n] >= 1 for n in names), (names, found)


def test_topk_logits_teacher_emit(spec):
    """Teacher emit: 4096 frames x 3,183 senones, top-20."""
    _assert_kernels(_compiled_text(
        lambda x: topk_logits(x, 20, interpret=False), spec((4096, 3183))),
        "topk_logits_tiles")


@pytest.mark.parametrize("greedy", [False, True], ids=["sample", "greedy"])
def test_topk_sample_vocab(spec, greedy):
    """Fused sampler at qwen2.5-3b's vocab: B8 x V151,936, k_cap 32."""
    b = 8
    if greedy:
        fn = lambda x: topk_sample(x, greedy=True, use_kernel=True,
                                   interpret=False)
        args = (spec((b, 151936)),)
    else:
        fn = lambda x, t, k, p, s, pos: topk_sample(
            x, t, k, p, s, pos, use_kernel=True, interpret=False)
        args = (spec((b, 151936)), spec((b,)), spec((b,), jnp.int32),
                spec((b,)), spec((b,), jnp.int32), spec((b,), jnp.int32))
    names = ("topk_logits_tiles",) if greedy else ("topk_logits_tiles",
                                                    "topk_sample_tiles")
    _assert_kernels(_compiled_text(fn, *args), *names)


def _distill_args(spec):
    return (spec((4096, 768)), spec((768, 3183)), spec((4096, 20)),
            spec((4096, 20), jnp.int32))


def test_sparse_ce_forward(spec):
    """Distill loss at the student's width: T4096 D768 V3183 K20."""
    _assert_kernels(_compiled_text(
        lambda h, w, v, i: topk_distill_ce(h, w, v, i, interpret=False),
        *_distill_args(spec)), "sparse_ce_tiles")


def test_sparse_ce_grad(spec):
    """The custom_vjp backward keeps the Pallas forward in the program."""
    grad = jax.grad(lambda h, w, v, i: topk_distill_ce(
        h, w, v, i, interpret=False), argnums=(0, 1))
    _assert_kernels(_compiled_text(grad, *_distill_args(spec)),
                    "sparse_ce_tiles")


def test_gtc_compress_leaf(spec):
    """GTC error-feedback compression of a 768 x 3072 LSTM weight."""
    _assert_kernels(_compiled_text(
        lambda g, r: gtc_compress(g, r, 1e-3, interpret=False),
        spec((768, 3072)), spec((768, 3072))), "gtc_compress_flat")


@pytest.mark.parametrize("window,write", [(0, True), (512, True),
                                          (0, False)],
                         ids=["linear", "swa-ring", "paged-view"])
def test_decode_attention(spec, window, write):
    """qwen2.5-3b decode: Hq16 Hkv2 hd128, S1024, B8."""
    b, hq, hkv, hd, s = 8, 16, 2, 128, 1024
    fn = lambda q, k, v, ck, cv, pos: decode_attention(
        q, k, v, ck, cv, pos, window=window, rope_theta=1e6, write=write,
        use_kernel=True, interpret=False)
    _assert_kernels(_compiled_text(
        fn, spec((b, hq, 1, hd)), spec((b, hkv, 1, hd)),
        spec((b, hkv, 1, hd)), spec((b, hkv, s, hd)), spec((b, hkv, s, hd)),
        spec((b,), jnp.int32)), "decode_attention_tiles")


def test_swa_attention(spec):
    """Sliding-window prefill: Hq16 Hkv2 hd128, S4096, window 1024."""
    _assert_kernels(_compiled_text(
        lambda q, k, v: swa_attention(q, k, v, 1024, interpret=False),
        spec((1, 16, 4096, 128)), spec((1, 2, 4096, 128)),
        spec((1, 2, 4096, 128))), "swa_attention_tiles")


def test_teacher_bilstm_writes_aligned(spec):
    """The biLSTM teacher's forward with its head and top-k emitter, at
    128 x 64 frames x 192 features: every one of the ten scans (five
    layers, two directions) stacks its outputs time-major, so each step's
    write is a whole aligned tile, and no gather reverses a direction."""
    from repro.core.ssl_pipeline import am_configs
    from repro.models import build_model
    from repro.serve import StreamingEngine
    _, cfg = am_configs(n_layers=5, lstm_hidden=768, n_senones=3183,
                        feat_dim=192)
    params = jax.eval_shape(
        lambda: build_model(cfg).init(jax.random.key(0)))
    params = jax.tree_util.tree_map(lambda a: spec(a.shape, a.dtype), params)
    engine = StreamingEngine(cfg, params, k=20, topk_impl="kernel",
                             interpret=False)
    b, s = 128, 64
    txt = engine._fwd_dict.lower(
        engine.params, {"feats": spec((b, s, 192)), "mask": spec((b, s))}
    ).compile().as_text()
    outs = [m.group(1) for ln in txt.splitlines()
            if re.match(r"\s*%while[.\d]* = \(", ln)
            for m in re.finditer(rf"bf16\[{s},{b},768\]\{{([\d,]+)", ln)]
    assert len(outs) == 10 and set(outs) == {"2,1,0"}, outs
    aligned = re.findall(r"dynamic-update-slice\(.*?\"is_index_aligned\":"
                         r"\[(\w+)", txt)
    assert len(aligned) >= 10 and "false" not in aligned, aligned
    assert txt.count("gather(") <= 1, txt.count("gather(")


@pytest.mark.parametrize("k,n", [(2048, 1408), (1408, 2048)],
                         ids=["gate-up", "down"])
def test_moe_gmm_expert_widths(spec, k, n):
    """DeepSeek-V2-Lite's routed experts on one chip: 64 groups, 32,768
    positions x 6 experts = 196,608 rows, bf16.  The custom call is the
    instruction ``gmm.N``, the name a trace's op events carry."""
    txt = _compiled_text(
        lambda x, w, g: moe_gmm(x, w, g, use_kernel=True, interpret=False),
        spec((196_608, k), jnp.bfloat16), spec((64, k, n), jnp.bfloat16),
        spec((64,), jnp.int32))
    _assert_kernels(txt, "gmm")
    assert re.search(r"%gmm(\.\d+)? = \S+ custom-call\(.*"
                     r'custom_call_target="tpu_custom_call"', txt)


def test_moe_expert_parallel_on_a_mesh(topo, monkeypatch):
    """DeepSeek-V2-Lite's MoE layer at its widths on a described 2x2 v5e
    mesh (data 2 x model 2), its weights laid out by
    ``distributed/sharding`` (experts over ``model``): GSPMD cannot
    partition a Mosaic kernel, so the layer runs expert-parallel in a
    ``shard_map``.  It compiles with the three ``gmm`` calls, and no
    all-gather assembles all 64 experts of a weight."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding

    from repro.configs import get_arch
    from repro.distributed.sharding import batch_spec, tree_param_specs
    from repro.kernels import _dispatch
    from repro.models import moe
    monkeypatch.setattr(_dispatch, "on_tpu", lambda: True)
    cfg = get_arch("deepseek-v2-lite")
    mesh = Mesh(np.array(topo.devices).reshape(2, 2), ("data", "model"))
    params = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, jnp.bfloat16),
        jax.eval_shape(lambda: moe.init_moe(jax.random.PRNGKey(0), cfg)))
    specs = tree_param_specs({"moe": params}, mesh)["moe"]
    assert specs["w_gate"][0] == "model", specs
    params = jax.tree_util.tree_map(
        lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                          sharding=NamedSharding(mesh, s)),
        params, specs)
    b, s = 8, 1024
    x = jax.ShapeDtypeStruct((b, s, cfg.d_model), jnp.bfloat16,
                             sharding=NamedSharding(mesh,
                                                    batch_spec(mesh, b, 2)))
    with jax.set_mesh(mesh):
        txt = _compiled_text(lambda p, x: moe.moe_apply(p, cfg, x)[0],
                             params, x)
    assert compiled_kernels(txt)["gmm"] == 3, compiled_kernels(txt)
    e = cfg.n_experts
    gathers = [ln for ln in txt.splitlines() if "all-gather" in ln
               and re.search(rf"\[{e},\d+,\d+\]", ln)]
    assert not gathers, gathers[:2]


@pytest.mark.parametrize("kernel", ["topk_logits", "sparse_ce_grad",
                                    "gtc_compress_bias"])
def test_default_kernels_at_tiny_width(spec, kernel):
    """The kernels run by default on a TPU, so the pipeline's tiny preset
    (2x64 LSTM, 49 senones, top-10) must compile too: vocab and leaf
    sizes far below one tile."""
    t, d, v, k = 256, 64, 49, 10
    if kernel == "topk_logits":
        fn, args = lambda x: topk_logits(x, k, interpret=False), \
            (spec((t, v)),)
        name = "topk_logits_tiles"
    elif kernel == "sparse_ce_grad":
        fn = jax.grad(lambda h, w, tv, ti: topk_distill_ce(
            h, w, tv, ti, interpret=False), argnums=(0, 1))
        args = (spec((t, d)), spec((d, v)), spec((t, k)),
                spec((t, k), jnp.int32))
        name = "sparse_ce_tiles"
    else:
        fn = lambda g, r: gtc_compress(g, r, 2e-4, interpret=False)
        args = (spec((v,)), spec((v,)))
        name = "gtc_compress_flat"
    _assert_kernels(_compiled_text(fn, *args), name)
