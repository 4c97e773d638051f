"""Model FLOPs and kernel operations and bytes against hand counts at the
cells' shapes."""
from bench import costs, loader


def cfg(name):
    return loader.read_json(f"{loader.HERE}/configs/{name}.json")


def test_teacher_forward_flops_per_frame():
    # per direction: layer 0 (192+768) x 3072, layers 1-4 (1536+768) x
    # 3072; two directions; output 1536 x 3183
    n = 2 * (960 * 3072) + 4 * 2 * (2304 * 3072) + 1536 * 3183
    assert n == 67_410_432
    assert costs.model_weights(cfg("lstm-am-teacher")) == n
    assert costs.forward_flops_per_frame(cfg("lstm-am-teacher")) \
        == 134_820_864


def test_student_train_flops_per_frame():
    n = 960 * 3072 + 4 * (1536 * 3072) + 768 * 3183
    assert n == 24_268_032
    assert costs.model_weights(cfg("lstm-am-7khr")) == n
    assert costs.train_flops_per_frame(cfg("lstm-am-7khr")) == 145_608_192


def test_config_files_state_their_weights():
    for name in ("lstm-am-teacher", "lstm-am-7khr"):
        c = cfg(name)
        assert c["matmul_weights"] == costs.model_weights(c)


def test_topk_logits_cost_teacher_batch():
    # one teacher batch: 1024 chunks x 64 frames of 3,183 logits, top-20
    c = costs.topk_logits_cost(65_536, 3183, 20)
    assert c["bytes"] == 65_536 * 3183 * 4 + 65_536 * 20 * 8
    assert c["bytes"] == 844_890_112
    assert c["ops"] == 208_601_088


def test_sparse_ce_cost_student_step():
    # one student step: 256 chunks x 64 frames, D 768, V 3,183, k 20
    t, d, v, k = 16_384, 768, 3183, 20
    c = costs.sparse_ce_cost(t, d, v, k)
    assert c["ops"] == 2 * t * d * v + 2 * t * v == 80_207_118_336
    assert c["bytes"] == t * d * 4 + d * v * 4 + t * k * 4 + t * 4 \
        + t * k * 4


def test_roofline_share_names_its_bound():
    pk = costs.peaks("TPU v5 lite")
    c = costs.topk_logits_cost(65_536, 3183, 20)
    share, bound = costs.roofline_share(c, 2e-3, pk)
    assert bound == "bytes"
    assert abs(share - 100 * c["bytes"] / 819e9 / 2e-3) < 1e-9
    share, bound = costs.roofline_share({"ops": 197e9, "bytes": 1}, 1e-3, pk)
    assert bound == "ops" and abs(share - 100.0) < 1e-9


def test_unknown_device_kind_is_an_error():
    import pytest
    with pytest.raises(KeyError):
        costs.peaks("TPU v4")
