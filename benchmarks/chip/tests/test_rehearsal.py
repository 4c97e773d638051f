"""Rehearsals without the chip.

1. Every cell's driver runs end to end through the harness at a tiny size
   on the CPU, traced and not.
2. Every cell's jitted steps compile at the cell's real shapes for a
   described TPU v5e 2x2, with the Pallas kernels in them.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import tiny
from bench import loader

CELLS = [w["name"] for w in loader.benchmark()["workloads"]]


@pytest.mark.parametrize("workload", CELLS)
def test_cell_runs_end_to_end_on_cpu(workload, tmp_path):
    cell = tiny.tiny_cell(workload)
    res = tiny.run_cell(cell, seconds=1.0, out_dir=str(tmp_path))
    assert res["correct"], res
    assert res["attempted"] > 0 and res["failed"] == 0
    assert set(res["metrics"]) == {m["name"] for m in cell.end_to_end}
    assert all(v["value"] > 0 for v in res["metrics"].values())
    assert list(res)[-1] == "checks"


@pytest.mark.parametrize("workload", CELLS)
def test_traced_run_on_cpu(workload, tmp_path):
    """The traced run reduces its trace (the CPU has no TPU planes, so
    the device numbers are empty and their readers return nothing) and
    still decides ``correct``."""
    res = tiny.run_cell(tiny.tiny_cell(workload), seconds=1.0,
                        trace=True, out_dir=str(tmp_path))
    assert res["correct"] and "busy_s" in res["device"]
    assert any(n.startswith("step_mfu.") for n in res["metrics"])
    assert list(res)[-1] == "checks"


def test_no_compilation_inside_the_window(tmp_path):
    from bench import harness
    cell = tiny.tiny_cell("student-distill")
    r = harness.Run(cell=cell, seed=3, seconds=1.0, trace=False,
                    out_dir=str(tmp_path), devices=jax.devices()[:1],
                    device_kind="TPU v5 lite")
    harness.execute(r, cell.driver(), t_start=0.0)
    assert r.extra["compiles_in_window"] == 0


def test_run_without_tpu_exits_nonzero():
    import subprocess
    import sys
    out = subprocess.run(
        [sys.executable, os.path.join(loader.HERE, "run.py"), "--workload",
         "teacher-gen", "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, timeout=120,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert out.returncode != 0 and not out.stdout.strip()


# ------------------------------------------------ AOT compile for a v5e

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
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def kernels_on(monkeypatch):
    """Steer the program's kernel dispatch to the compiled TPU kernels."""
    from repro.kernels import _dispatch
    monkeypatch.setattr(_dispatch, "on_tpu", lambda: True)


def placed(tree, sharding):
    return jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding),
        tree)


def real(workload):
    cell = loader.resolve(workload)
    from bench import ref_lstm_am as ref
    from bench.program import model_config
    shapes = jax.eval_shape(lambda: ref.init_params(cell.config, 0))
    return cell, model_config(cell.config), shapes


def kernels_in(compiled):
    from repro.kernels._dispatch import compiled_kernels
    return compiled_kernels(compiled.as_text())


def test_teacher_step_compiles_for_v5e(one_chip, kernels_on):
    from repro.core.teacher import TeacherRunner
    cell, mcfg, pshapes = real("teacher-gen")
    tf = cell.traffic
    runner = TeacherRunner(mcfg, None, k=tf["k"])
    b, t = tf["batch_chunks"], tf["chunk_frames"]
    batch = {"feats": jax.ShapeDtypeStruct((b, t, 192), jnp.float32),
             "mask": jax.ShapeDtypeStruct((b, t), jnp.float32)}
    c = runner.engine._fwd_dict.lower(placed(pshapes, one_chip),
                                      placed(batch, one_chip)).compile()
    assert kernels_in(c)["topk_logits_tiles"] == 1


def train_state_shapes(strategy, pshapes, seed=0):
    from repro.train.state import TrainState
    return jax.eval_shape(lambda p: TrainState(
        params=p, opt_state=strategy.init_opt(p),
        strategy_state=strategy.init_state(p),
        step=jnp.zeros((), jnp.int32), rng=jax.random.key(seed)), pshapes)


def distill_batch(tf, lead=()):
    b, t, k = tf["batch_chunks"], tf["chunk_frames"], tf["k"]
    s = jax.ShapeDtypeStruct
    return {"feats": s(lead + (b, t, 192), jnp.float32),
            "mask": s(lead + (b, t), jnp.float32),
            "topk_vals": s(lead + (b, t, k), jnp.float16),
            "topk_idx": s(lead + (b, t, k), jnp.int32)}


def test_student_update_compiles_for_v5e(one_chip, kernels_on):
    from repro.launch.steps import make_loss_fn
    from repro.models import build_model
    from repro.train import Local, Trainer
    cell, mcfg, pshapes = real("student-distill")
    tf = cell.traffic
    strat = Local(clip=tf["clip"])
    tr = Trainer(strat, {"distill_topk": make_loss_fn(
        build_model(mcfg), mcfg, "distill_topk")})
    state = placed(train_state_shapes(strat, pshapes), one_chip)
    lr = jax.ShapeDtypeStruct((), jnp.float32, sharding=one_chip)
    c = tr.updates["distill_topk"].lower(
        state, placed(distill_batch(tf), one_chip), lr).compile()
    assert kernels_in(c)["sparse_ce_tiles"] >= 1
