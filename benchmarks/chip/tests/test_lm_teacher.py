"""The LM teacher cell on the CPU: its cost functions against hand counts,
its token traffic, its expert-load reader, a tiny run through the
harness against the reference, and ``correct`` coming out false under the
fp8 control and three planted faults."""
import jax.numpy as jnp
import numpy as np
import pytest

import tiny
import tiny_lm
from bench import costs, costs_lm, lm, loader

CFG = loader.read_json(f"{loader.HERE}/configs/deepseek-v2-lite-teacher.json")


# ----------------------------------------------------------------- costs

def test_config_file_is_the_catalog_config_at_depth_7():
    assert CFG["num_hidden_layers"] == 7 and CFG["reduced"] == [
        "num_hidden_layers"]
    widths = (CFG["hidden_size"], CFG["num_attention_heads"],
              CFG["kv_lora_rank"], CFG["q_lora_rank"],
              CFG["intermediate_size"], CFG["moe_intermediate_size"],
              CFG["n_routed_experts"], CFG["n_shared_experts"],
              CFG["num_experts_per_tok"], CFG["vocab_size"])
    assert widths == (2048, 16, 512, None, 10944, 1408, 64, 2, 6, 102400)


def test_weights_and_flops_per_token():
    # MLA 2048x3072 + 2048x576 + 512x4096 + 2048x2048; dense MLP
    # 3x2048x10944; per MoE layer the router, 64 + 2 experts of 3x2048x1408
    mla = 2048 * 3072 + 2048 * 576 + 512 * 4096 + 2048 * 2048
    assert costs_lm.mla_weights(CFG) == mla == 13_762_560
    moe = 2048 * 64 + 66 * 3 * 2048 * 1408 + mla
    n = 2 * 102_400 * 2048 + (mla + 3 * 2048 * 10944) + 6 * moe
    assert costs_lm.model_weights(CFG) == n == CFG["matmul_weights"]
    assert n == 4_009_492_480
    att = 2 * 16 * (192 + 128) * 2048
    per_token = (2 * (mla + 3 * 2048 * 10944) + att
                 + 6 * (2 * (mla + 2048 * 64 + 8 * 3 * 2048 * 1408) + att)
                 + 2 * 2048 * 102_400)
    assert costs_lm.forward_flops_per_token(CFG, 4096) == per_token
    assert per_token == CFG["forward_flops_per_token"] == 1_725_431_808


def test_moe_gmm_cost_one_batch():
    # 32,768 positions x 6 pairs through gate, up, down in 6 MoE layers
    p = 6 * 32_768
    c = costs_lm.moe_gmm_cost(32_768, CFG)
    assert c["ops"] == 6 * 2 * p * 3 * 2048 * 1408
    assert c["bytes"] == 6 * (64 * 3 * 2048 * 1408 * 2 + 2 * (
        2 * p * 2048 + 2 * p * 1408 + p * 1408 + p * 2048))
    share, bound = costs.roofline_share(c, 1.0, costs.peaks("TPU v5 lite"))
    assert bound == "ops"


# --------------------------------------------------------------- traffic

def test_zipf_tokens_follow_the_seed_and_the_law():
    spec = {"zipf_exponent": 1.0}
    a = lm.zipf_tokens(spec, 2 ** 31 + 77, (4, 4096), 102_400)
    b = lm.zipf_tokens(spec, 2 ** 31 + 77, (4, 4096), 102_400)
    c = lm.zipf_tokens(spec, 5, (4, 4096), 102_400)
    np.testing.assert_array_equal(a, b)
    assert not np.array_equal(a, c)
    assert a.dtype == np.int32 and a.min() >= 0 and a.max() < 102_400
    # the most frequent id takes 1/H(102,400) = 8.1% of draws
    h = np.sum(1.0 / np.arange(1, 102_401))
    top = np.bincount(a.ravel()).max() / a.size
    assert abs(top - 1 / h) < 0.01


def test_token_pool_shapes():
    tf = loader.resolve("lm-teacher-gen").traffic
    pool = lm.token_pool(dict(tf, pool_batches=2), 3, 102_400)
    assert len(pool) == 2 and pool[0]["tokens"].shape == (8, 4096)


# ---------------------------------------------------------------- reader

def test_expert_load_reader():
    cell = loader.resolve("lm-teacher-gen")
    reader = cell.metric_reader("expert_load.gen_lm")
    assert reader.read(None, {"expert_loads": [1.5, 2.5]}, None) == 2.0
    assert reader.read(None, {}, None) is None


def test_program_config_at_the_files_sizes():
    mcfg = lm.model_config(CFG)
    assert mcfg.n_layers == 7 and [s.n_layers for s in mcfg.segments] == [
        1, 6]
    assert (mcfg.d_model, mcfg.moe_d_ff, mcfg.n_experts, mcfg.moe_top_k,
            mcfg.vocab_size, mcfg.mla.q_lora_rank) == (
        2048, 1408, 64, 6, 102_400, 0)
    assert mcfg.rope_scaling.factor == 40.0 and not mcfg.moe_renorm_topk


# ------------------------------------------------------------ tiny runs

def run(tmp_path, seed=5, trace=False):
    return tiny.run_cell(tiny_lm.tiny_lm_cell(), seed=seed, seconds=1.0,
                         trace=trace, out_dir=str(tmp_path))


@pytest.mark.parametrize("trace", [False, True], ids=["plain", "traced"])
def test_tiny_run_against_the_reference(tmp_path, trace):
    res = run(tmp_path, seed=2 ** 31 + 3, trace=trace)
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    if trace:
        assert res["metrics"]["expert_load.gen_lm"]["value"] >= 1.0
        assert res["metrics"]["step_mfu.gen_lm"]["value"] > 0
    else:
        assert set(res["metrics"]) == {"setup_s", "gen_frames_per_s"}


def assert_caught(res):
    assert not res["correct"], res["checks"]
    assert any(c["value"] > c["limit"] for c in res["checks"].values())


def test_fp8_control_is_incorrect(tmp_path):
    """The reference with fp8 operands, in the program's place."""
    import jax

    from bench import harness, ref_lstm_am
    cell = tiny_lm.tiny_lm_cell()
    r = harness.Run(cell=cell, seed=5, seconds=1.0, trace=False,
                    out_dir=str(tmp_path), devices=jax.devices()[:1],
                    device_kind="TPU v5 lite")
    drv = cell.driver().Driver(r)
    drv.setup()
    drv.window(0.5)
    drv.release()

    def fp8(seqs, pos):
        lg = lm.reference_logits(cell.config, r.seed, seqs, pos,
                                 r.devices[0], prec="fp8")
        v, i = ref_lstm_am.emit_topk(jnp.asarray(lg), cell.traffic["k"])
        return np.asarray(v), np.asarray(i)
    got = drv.check(program=fp8)
    assert got["emission_gap"] > cell.limits["emission_gap"], got


@pytest.mark.parametrize("fault", ["drop_expert", "no_shared",
                                   "no_mscale"])
def test_planted_fault_is_incorrect(tmp_path, fault):
    """The busiest expert of each layer zeroed; the shared experts left
    out; YaRN's mscale left out of the softmax scale (controls_lm.py)."""
    import controls_lm
    with controls_lm.fault(fault):
        assert_caught(run(tmp_path))
