"""Assigned-architecture configs: exact topology vs the assignment table."""
import pytest

from repro.configs import ARCHS, ASSIGNED, SHAPES, get_arch, reduced, supports

# (layers, d_model, heads, kv, d_ff, vocab)
EXPECTED = {
    "recurrentgemma-2b": (26, 2560, 10, 1, 7680, 256000),
    "gemma3-27b": (62, 5376, 32, 16, 21504, 262144),
    "deepseek-67b": (95, 8192, 64, 8, 22016, 102400),
    "h2o-danube-3-4b": (24, 3840, 32, 8, 10240, 32000),
    "whisper-medium": (24, 1024, 16, 16, 4096, 51865),
    "qwen3-moe-30b-a3b": (48, 2048, 32, 4, 768, 151936),
    "qwen2.5-3b": (36, 2048, 16, 2, 11008, 151936),
    "chameleon-34b": (48, 8192, 64, 8, 22016, 65536),
    "deepseek-v3-671b": (61, 7168, 128, 128, 2048, 129280),
    "xlstm-350m": (24, 1024, 4, 4, 0, 50304),
}

FAMILY = {
    "recurrentgemma-2b": "hybrid", "gemma3-27b": "dense",
    "deepseek-67b": "dense", "h2o-danube-3-4b": "dense",
    "whisper-medium": "audio", "qwen3-moe-30b-a3b": "moe",
    "qwen2.5-3b": "dense", "chameleon-34b": "vlm",
    "deepseek-v3-671b": "moe", "xlstm-350m": "ssm",
}


@pytest.mark.parametrize("arch", list(EXPECTED))
def test_exact_topology(arch):
    cfg = get_arch(arch)
    layers, d, h, kv, dff, vocab = EXPECTED[arch]
    assert cfg.n_layers == layers
    assert cfg.d_model == d
    assert cfg.n_heads == h
    assert cfg.n_kv_heads == kv
    assert cfg.vocab_size == vocab
    if cfg.family == "moe":
        assert cfg.moe_d_ff == dff
    elif cfg.family != "ssm":
        assert cfg.d_ff == dff
    assert cfg.family == FAMILY[arch]
    assert cfg.source, "every config must cite its source"


def test_assignment_complete():
    assert len(ASSIGNED) == 10
    assert set(EXPECTED) == set(ASSIGNED)
    assert len({FAMILY[a] for a in ASSIGNED}) == 6   # 6 arch types


def test_moe_specs():
    q = get_arch("qwen3-moe-30b-a3b")
    assert (q.n_experts, q.moe_top_k) == (128, 8)
    d = get_arch("deepseek-v3-671b")
    assert (d.n_experts, d.moe_top_k, d.n_shared_experts) == (256, 8, 1)
    assert d.mla is not None and d.mtp_depth == 1


@pytest.mark.parametrize("arch", list(EXPECTED))
def test_reduced_constraints(arch):
    r = reduced(get_arch(arch))
    assert r.n_layers <= 2 * max(len(s.pattern) for s in r.segments)
    assert r.d_model <= 512
    assert r.n_experts <= 4
    assert r.vocab_size <= 512


def test_supports_matrix():
    # long_500k: runs for subquadratic + swa-dominant; skips pure full attn
    runs = {a for a in ASSIGNED
            if supports(get_arch(a), SHAPES["long_500k"])[0]}
    assert runs == {"recurrentgemma-2b", "gemma3-27b", "h2o-danube-3-4b",
                    "xlstm-350m"}
    # +swa variant makes the dense archs lower
    assert supports(get_arch("deepseek-67b+swa"), SHAPES["long_500k"])[0]
    # everything runs train/prefill/decode_32k
    for a in ASSIGNED:
        for s in ("train_4k", "prefill_32k", "decode_32k"):
            assert supports(get_arch(a), SHAPES[s])[0], (a, s)


def test_swa_variant():
    v = get_arch("deepseek-67b+swa")
    assert all(m == "swa" for m in v.mixers())
    assert v.n_layers == 95


def test_paper_scale_is_the_published_am():
    """PipelineConfig.paper() builds exactly the published student and
    teacher widths of configs/lstm_am_7khr.py (5x768, 3,183 senones,
    192-d features), top-20 targets."""
    from repro.configs.lstm_am_7khr import CONFIG, TEACHER
    from repro.core.ssl_pipeline import PipelineConfig, am_configs
    pc = PipelineConfig.paper()
    student, teacher = am_configs(n_layers=pc.n_layers,
                                  lstm_hidden=pc.lstm_hidden,
                                  n_senones=pc.n_senones,
                                  feat_dim=pc.feat_dim)
    for got, want in ((student, CONFIG), (teacher, TEACHER)):
        assert got.mixers() == want.mixers()
        for key in ("lstm_hidden", "n_senones", "vocab_size", "feat_dim"):
            assert getattr(got, key) == getattr(want, key), key
    assert pc.topk == 20 and pc.n_mels == 64
