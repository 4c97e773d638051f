"""Checkpoint store + HLO collective parsing."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import CheckpointStore, load_tree, save_tree
from repro.utils import hlo


def _tree():
    return {"a": jnp.arange(6, dtype=jnp.float32).reshape(2, 3),
            "nested": {"b": jnp.ones((4,), jnp.bfloat16),
                       "c": jnp.zeros((), jnp.int32)}}


def test_save_load_roundtrip(tmp_path):
    t = _tree()
    save_tree(str(tmp_path / "x.npz"), t, meta={"step": 3})
    out = load_tree(str(tmp_path / "x.npz"), t)
    for a, b in zip(jax.tree_util.tree_leaves(t),
                    jax.tree_util.tree_leaves(out)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32))
        assert a.dtype == b.dtype


def test_store_retention(tmp_path):
    store = CheckpointStore(str(tmp_path), keep=2)
    t = _tree()
    for s in (1, 2, 3, 4):
        store.save(s, t)
    assert store.steps() == [3, 4]
    out, step = store.load(t)
    assert step == 4


def test_load_shape_mismatch(tmp_path):
    t = _tree()
    save_tree(str(tmp_path / "x.npz"), t)
    bad = dict(t, a=jnp.zeros((5, 5)))
    with pytest.raises(ValueError):
        load_tree(str(tmp_path / "x.npz"), bad)


# ----------------------------------------------------------------- HLO

SAMPLE = """
  %ag = bf16[8,128]{1,0} all-gather(%x), replica_groups={}
  %ar.1 = f32[256]{0} all-reduce(%y), to_apply=%sum
  %rs = (f32[16,16]{1,0}, f32[4]{0}) reduce-scatter(%a, %b)
  %cp = f32[2,2]{1,0} collective-permute(%z), source_target_pairs={{0,1}}
  %dot = f32[8,8]{1,0} dot(%p, %q)
"""


def test_collective_stats_parse():
    st = hlo.collective_stats(SAMPLE)
    assert st.count_by_kind == {"all-gather": 1, "all-reduce": 1,
                                "reduce-scatter": 1,
                                "collective-permute": 1}
    assert st.bytes_by_kind["all-gather"] == 8 * 128 * 2
    assert st.bytes_by_kind["all-reduce"] == 256 * 4
    assert st.bytes_by_kind["reduce-scatter"] == 16 * 16 * 4 + 4 * 4
    assert st.total_count == 4


def test_shape_bytes_tuple():
    assert hlo.shape_bytes("(f32[2,2]{1,0}, bf16[4]{0})") == 16 + 8
    assert hlo.shape_bytes("pred[8]") == 8
    assert hlo.shape_bytes("f32[]") == 4


def test_wire_bytes_factors():
    st = hlo.CollectiveStats(bytes_by_kind={"all-reduce": 100},
                             count_by_kind={"all-reduce": 1})
    # 2(D-1)/D for D=4 -> 1.5x
    assert hlo.wire_bytes(st, 4) == pytest.approx(150.0)


def test_real_lowered_collectives():
    """End-to-end: a psum under shard_map shows up in the parse."""
    from jax.sharding import PartitionSpec as P

    from repro.runtime.cluster import auto_mesh
    mesh = auto_mesh((1,), ("w",))

    def f(x):
        return jax.lax.psum(x, "w")

    sf = jax.shard_map(f, mesh=mesh, in_specs=P("w"), out_specs=P(),
                       check_vma=False)
    txt = jax.jit(sf).lower(jnp.ones((4, 8))).compile().as_text()
    st = hlo.collective_stats(txt)
    # 1-device psum may fold away; just assert the parser doesn't crash
    assert st.total_bytes >= 0
