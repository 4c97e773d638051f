"""Sharding policy: every param/cache leaf of every arch gets a legal spec
on the production meshes (divisibility-checked via AbstractMesh — no device
init needed)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AbstractMesh, PartitionSpec as P

from repro.configs import ASSIGNED, get_arch, reduced
from repro.distributed import sharding as sh
from repro.models.api import abstract_params
from repro.utils.trees import map_with_path, tree_paths

POD = AbstractMesh((16, 16), ("data", "model"))
MULTI = AbstractMesh((2, 16, 16), ("pod", "data", "model"))


def _check_specs(cfg, mesh):
    params = abstract_params(cfg)
    specs = sh.tree_param_specs(params, mesh)
    for (path, leaf), (_, spec) in zip(tree_paths(params),
                                       tree_paths(specs)):
        assert isinstance(spec, P), path
        shape = leaf.shape
        offset = len(shape) - len(spec)
        assert offset >= 0, (path, shape, spec)
        for i, ax in enumerate(spec):
            if ax is None:
                continue
            size = sh._axis_size(mesh, ax)
            assert shape[i] % size == 0, (path, shape, spec, ax)


@pytest.mark.parametrize("arch", ASSIGNED)
@pytest.mark.parametrize("mesh", [POD, MULTI], ids=["pod", "multipod"])
def test_param_specs_divisible(arch, mesh):
    _check_specs(get_arch(arch), mesh)


@pytest.mark.parametrize("arch", ASSIGNED)
def test_param_specs_degrade_on_tiny_mesh(arch):
    """Reduced configs on a 1-device mesh: everything degrades to
    replicated (or still-divisible) specs, never an error."""
    tiny = AbstractMesh((1, 1), ("data", "model"))
    _check_specs(reduced(get_arch(arch)), tiny)


def test_big_matrices_are_2d_sharded():
    """The FSDP+TP policy must actually split the big matrices both ways
    on the pod mesh (this is what makes 671B fit)."""
    cfg = get_arch("deepseek-67b")
    params = abstract_params(cfg)
    specs = sh.tree_param_specs(params, POD)
    flat = dict(tree_paths(specs))
    # find an attention projection inside the scanned segment
    keys = [k for k in flat if k.endswith("mixer/wq")]
    assert keys
    spec = flat[keys[0]]
    used = set()
    for entry in spec:
        if entry is None:
            continue
        if isinstance(entry, tuple):
            used.update(entry)
        else:
            used.add(entry)
    assert "model" in used and "data" in used, spec


def test_moe_expert_dim_sharded():
    cfg = get_arch("qwen3-moe-30b-a3b")
    params = abstract_params(cfg)
    specs = dict(tree_paths(sh.tree_param_specs(params, POD)))
    k = [p for p in specs if p.endswith("ffn/w_gate")][0]
    spec = specs[k]
    # (lead, E, D, F): expert dim on model axis (expert parallelism)
    assert spec[1] == "model", spec


def test_batch_spec():
    assert sh.batch_spec(POD, 256) == P("data", None)
    assert sh.batch_spec(MULTI, 256) == P(("pod", "data"), None)
    # batch=1 (long_500k): degrades to replicated
    assert sh.batch_spec(MULTI, 1) == P(None, None)


def test_cache_specs_legal():
    cfg = get_arch("gemma3-27b")
    from repro.models import build_model
    model = build_model(cfg)
    cache = jax.eval_shape(lambda: model.init_cache(128, 1024,
                                                    jnp.bfloat16))
    specs = sh.tree_cache_specs(cache, POD)
    for (path, leaf), (_, spec) in zip(tree_paths(cache),
                                       tree_paths(specs)):
        offset = len(leaf.shape) - len(spec)
        assert offset >= 0
        for i, ax in enumerate(spec):
            if ax is None:
                continue
            assert leaf.shape[i] % sh._axis_size(POD, ax) == 0, (path, spec)


@pytest.mark.parametrize("mode", ["tp", "fsdp"])
def test_alternate_sharding_modes_legal(mode):
    """§Perf sharding variants: every leaf still divisibility-legal."""
    cfg = get_arch("gemma3-27b")
    params = abstract_params(cfg)
    specs = sh.tree_param_specs(params, POD, mode=mode)
    from repro.utils.trees import tree_paths
    for (path, leaf), (_, spec) in zip(tree_paths(params),
                                       tree_paths(specs)):
        offset = len(leaf.shape) - len(spec)
        assert offset >= 0, (path, leaf.shape, spec)
        for i, ax in enumerate(spec):
            if ax is None:
                continue
            assert leaf.shape[i] % sh._axis_size(POD, ax) == 0, (path, spec)
    if mode == "tp":
        # no data-axis entries anywhere
        for _, spec in tree_paths(specs):
            for ax in spec:
                axes = ax if isinstance(ax, tuple) else (ax,)
                assert "data" not in axes, spec
