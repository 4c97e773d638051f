"""The BMUF W=4 cell on the CPU: its traffic, its collective reader, a tiny
run through the harness (the four workers on the CPU's one device) against
the reference's first two blocks, and ``correct`` coming out false under
the fp8 control, the reference's planted faults, a block whose workers are
all fed one batch, and a program whose block momentum or Nesterov restart
is left out."""
import dataclasses

import jax
import pytest

import tiny
from bench import harness, loader


def tiny_bmuf_cell():
    cell = loader.resolve("student-bmuf-w4")
    cell.config = dict(cell.config, **tiny.TINY_MODEL)
    tf = dict(cell.traffic, **tiny.TINY_TRAFFIC["train"])
    tf["utterances"] = dict(tf["utterances"], **tiny.TINY_UTTERANCES)
    cell.traffic = tf
    return cell


def test_traffic_is_student_distills_per_worker():
    bm = loader.resolve("student-bmuf-w4").traffic
    sd = loader.resolve("student-distill").traffic
    for key in ("utterances", "chunk_frames", "batch_chunks", "k", "lr",
                "clip", "beta"):
        assert bm[key] == sd[key], key
    assert bm["bmuf"] == {"n_workers": 4, "block_steps": 2,
                          "block_momentum": 0.875, "block_lr": 1.0,
                          "nesterov": True}
    assert bm["pool_batches"] >= 4 * 2


def test_block_batches_map_to_workers_and_steps(tmp_path):
    """Batch j of a block is local step j // W of worker j % W, as
    ``BMUFShardMap.stack`` lays a block out."""
    from repro.distributed.bmuf import BMUFConfig
    from repro.train import BMUFShardMap
    from repro.runtime.cluster import worker_mesh
    s = BMUFShardMap(BMUFConfig(n_workers=4, block_steps=2), worker_mesh(4))
    st = s.stack([{"x": jax.numpy.full((1,), j)} for j in range(8)])["x"]
    assert [[int(st[t, w, 0]) for w in range(4)] for t in range(2)] == [
        [0, 1, 2, 3], [4, 5, 6, 7]]


def test_collective_reader_reads_nothing_without_device_ops():
    class Tr:
        def window_s(self):
            return 1.0

        def devices(self):
            return []
    reader = loader.resolve("student-bmuf-w4").metric_reader(
        "collective_exposed.bmuf")
    assert reader.read(None, {}, Tr()) is None


def run_tiny(tmp_path, seed=5, trace=False):
    return tiny.run_cell(tiny_bmuf_cell(), seed=seed, seconds=1.0,
                         trace=trace, out_dir=str(tmp_path))


@pytest.mark.parametrize("trace", [False, True], ids=["plain", "traced"])
def test_tiny_run_against_the_reference(tmp_path, trace):
    res = run_tiny(tmp_path, seed=2 ** 31 + 9, trace=trace)
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    if not trace:
        assert set(res["metrics"]) == {"setup_s", "train_frames_per_s"}


def controls(tmp_path, prec=None):
    cell = tiny_bmuf_cell()
    r = harness.Run(cell=cell, seed=5, seconds=1.0, trace=False,
                    out_dir=str(tmp_path), devices=jax.devices()[:1],
                    device_kind="TPU v5 lite")
    drv = cell.driver().Driver(r)
    drv.setup()
    drv.window(0.5)
    drv.release()
    return cell, drv


def test_fp8_control_is_incorrect(tmp_path):
    cell, drv = controls(tmp_path)
    got = drv.check(program=drv.reference("fp8"))
    assert any(got[k] > cell.limits[k] for k in got), got


@pytest.mark.parametrize("fault", ["no_momentum", "no_restart",
                                   "no_exchange", "half_batch"])
def test_reference_fault_is_incorrect(tmp_path, fault):
    cell, drv = controls(tmp_path)
    got = drv.check(program=drv.reference(fault=fault))
    assert any(got[k] > cell.limits[k] for k in got), got


@pytest.mark.parametrize("change", [{"block_momentum": 0.0},
                                    {"nesterov": False}],
                         ids=["momentum_off", "restart_off"])
def test_program_block_fault_is_incorrect(tmp_path, monkeypatch, change):
    """The program's block sync with its block momentum, or its Nesterov
    restart, left out: the first block still agrees (its momentum starts
    at zero), the second does not."""
    from repro.distributed import bmuf
    orig = bmuf.make_sharded_bmuf_block_step

    def planted(step, cfg, *a, **kw):
        return orig(step, dataclasses.replace(cfg, **change), *a, **kw)
    monkeypatch.setattr(bmuf, "make_sharded_bmuf_block_step", planted)
    res = run_tiny(tmp_path)
    checks = res["checks"]
    assert not res["correct"], checks
    assert checks["block_change_gap"]["value"] <= \
        checks["block_change_gap"]["limit"], checks


def test_workers_fed_one_batch_is_incorrect(tmp_path, monkeypatch):
    """Every worker of a block fed the first worker's batch: the block
    averages four copies of one worker's steps."""
    from repro.train import strategies
    orig = strategies.BMUFShardMap.stack

    def stack(self, group):
        w = self.cfg.n_workers
        return orig(self, [group[j - j % w] for j in range(len(group))])
    monkeypatch.setattr(strategies.BMUFShardMap, "stack", stack)
    res = run_tiny(tmp_path)
    assert not res["correct"], res["checks"]
