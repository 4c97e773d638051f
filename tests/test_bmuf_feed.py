"""How a BMUF block reaches the devices: ``BMUFShardMap.stack`` lays the
block out in the update's own input sharding, each device holding only its
workers' batches, so the update's dispatch moves no data; and the
benchmark's ``idle_feed.bmuf`` reader of the ``train.stack`` span.

The multi-device cases run in a subprocess of their own with four CPU
devices."""
import functools
import importlib.util
import os
import subprocess
import sys
from types import SimpleNamespace

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CHIP = os.path.join(ROOT, "benchmarks", "chip")

FEED = r'''
import sys
import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from repro.distributed.bmuf import BMUFConfig
from repro.train import BMUFShardMap, TrainBatch, Trainer

W, NDEV, TAU, D = int(sys.argv[1]), int(sys.argv[2]), 2, 8


def loss(params, batch):
    e = batch["x"] @ params["w"] - batch["y"]
    return jnp.mean(e ** 2), {"loss": jnp.mean(e ** 2)}


rng = np.random.default_rng(3)
# two blocks; leaf "j" carries the batch's index
batches = [{"x": rng.normal(size=(16, D)).astype(np.float32),
            "y": rng.normal(size=(16,)).astype(np.float32),
            "j": np.full((3,), j, np.int32)} for j in range(2 * TAU * W)]
mesh = Mesh(np.array(jax.devices()[:NDEV]), ("data",))
cfg = BMUFConfig(n_workers=W, block_steps=TAU, block_momentum=0.5)

def source():
    return [TrainBatch(b, 0.05, "q") for b in batches]

def fit(strategy, n=None):
    tr = Trainer(strategy, {"q": loss})
    st = tr.init_state({"w": jnp.zeros((D,))}, seed=1)
    return tr, st, tr.fit(st, source()[:n], resume=False)

# layout: the update's input sharding, each device its own workers
sm = BMUFShardMap(cfg, mesh, clip=1.0)
block = sm.stack(batches[:TAU * W])
want = NamedSharding(mesh, P(None, "data"))
for leaf in jax.tree_util.tree_leaves(block):
    assert leaf.sharding.is_equivalent_to(want, leaf.ndim), leaf.sharding
got = {}
for shard in block["j"].addressable_shards:
    workers = range(W)[shard.index[1]]
    held = np.asarray(shard.data)[..., 0]
    assert held.shape == (TAU, len(workers))
    for s in range(TAU):
        for i, w in enumerate(workers):
            got[(s, w)] = int(held[s, i])
assert got == {(s, w): s * W + w for s in range(TAU) for w in range(W)}, got
print("LAYOUT OK")

def plain_stack(group):
    """The block stacked on the default device, (tau, W, ...)."""
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(
        [jnp.asarray(x) for x in xs]).reshape(TAU, W, *xs[0].shape), *group)

# two blocks through Trainer.fit: equal to the same update fed by the
# plain stack, and to W workers on a one-device mesh on the same batches
def leaves(state):
    return [np.asarray(x) for x in jax.tree_util.tree_leaves(
        (state.params, state.opt_state, state.strategy_state, state.step))]

_, _, new = fit(BMUFShardMap(cfg, mesh, clip=1.0))
plain = BMUFShardMap(cfg, mesh, clip=1.0)
plain.stack = plain_stack
_, _, old = fit(plain)
_, _, one = fit(BMUFShardMap(cfg, Mesh(np.array(jax.devices()[:1]),
                                       ("data",)), clip=1.0))
assert int(new.step) == 2
for a, b in zip(leaves(new), leaves(old)):
    np.testing.assert_array_equal(a, b)
print("EQUAL PLAIN STACK")
for a, b in zip(leaves(new), leaves(one)):
    if NDEV == 1:
        np.testing.assert_array_equal(a, b)
    else:
        # the cross-device pmean sums the workers in another order than
        # the one device's mean over all W: float32 rounding, never more
        np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-7)
print("EQUAL ONE DEVICE")

# no data moves at the update's dispatch: one block of Trainer.fit under
# guards that refuse implicit device-to-device and device-to-host copies
tr, st, _ = fit(BMUFShardMap(cfg, mesh, clip=1.0), TAU * W)
with jax.transfer_guard_device_to_device("disallow"), \
        jax.transfer_guard_device_to_host("disallow"):
    out = tr.fit(st, source()[:TAU * W], resume=False)
assert int(out.step) == 1
print("GUARDED BLOCK OK")
# the control: the plain stack on the default device, fed to the same
# sharded update, has to be resharded at dispatch
plain_block = plain_stack(batches[:TAU * W])
try:
    with jax.transfer_guard_device_to_device("disallow"), \
            jax.transfer_guard_device_to_host("disallow"):
        tr.updates["q"](st, plain_block, np.float32(0.05))
except Exception as e:
    assert "Disallowed device-to-device transfer" in str(e), e
    print("CONTROL TRIPPED")
else:
    print("CONTROL PASSED")
'''

CASES = [(4, 4), (8, 4), (4, 1)]
IDS = ["W4-on-4-devices", "W8-on-4-devices", "W4-on-1-device"]


@functools.lru_cache(maxsize=None)
def _feed(w, ndev):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.path.join(ROOT, "src"))
    out = subprocess.run([sys.executable, "-c", FEED, str(w), str(ndev)],
                         env=env, capture_output=True, text=True,
                         timeout=300)
    return out.returncode, out.stdout, out.stderr[-3000:]


@pytest.mark.parametrize("w,ndev", CASES, ids=IDS)
def test_block_layout_and_equality(w, ndev):
    """Batch j of a block sits on the device of worker j % W as its local
    step j // W, in the update's ``P(None, worker axes)`` sharding; two
    blocks of ``Trainer.fit`` then give params, optimizer state and block
    momentum bitwise equal to the same update fed by the plain stack, and
    to ``BMUFShardMap`` on a one-device mesh (bitwise on one device, to
    float32 rounding of the cross-device mean on four)."""
    rc, out, err = _feed(w, ndev)
    assert rc == 0, err
    for mark in ("LAYOUT OK", "EQUAL PLAIN STACK", "EQUAL ONE DEVICE"):
        assert mark in out, (mark, out, err)


@pytest.mark.parametrize("w,ndev", CASES[:2], ids=IDS[:2])
def test_update_moves_no_data(w, ndev):
    """A block of ``Trainer.fit`` runs under guards that refuse implicit
    device-to-device and device-to-host copies; the plain stack fed to the
    same update trips the first, so the guard sees the reshard that the
    layout removes."""
    rc, out, err = _feed(w, ndev)
    assert rc == 0, err
    assert "GUARDED BLOCK OK" in out, (out, err)
    assert "CONTROL TRIPPED" in out, (out, err)


# ------------------------------------------------ the benchmark's reader

def _reader():
    if CHIP not in sys.path:
        sys.path.insert(0, CHIP)
    path = os.path.join(CHIP, "metrics", "idle_feed.bmuf.py")
    spec = importlib.util.spec_from_file_location("metric_idle_feed_bmuf",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _synthetic(names):
    """A window (0, 100) of three blocks: a draw, the block's stack and its
    update's dispatch, each span named as ``names`` maps it (None: not
    recorded).  The device runs 12-30, 45-60 and 75-100, so it idles
    0-12, 30-45 and 60-75."""
    from bench import trace
    from bench.spans import HostSpans, Span
    spans = []
    for lo in (0, 30, 60):
        for a, b, kind in ((lo, lo + 5, "source"), (lo + 5, lo + 10, "stack"),
                           (lo + 10, lo + 13, "update")):
            if names.get(kind):
                spans.append(Span(a, b, "repro.train." + names[kind]))

    class FakeTrace(trace.Trace):
        def __init__(self):
            self.ops = {0: [(12, 30, "while.1"), (45, 60, "while.1"),
                            (75, 100, "while.1")]}
            self.spans = [(0, 100, "bench.window")]

    run = SimpleNamespace(extra={"host_spans": HostSpans((0, 100), spans)})
    return run, FakeTrace()


def test_idle_feed_reads_the_stack_and_update_share():
    """Idle under ``train.stack`` or ``train.update`` counts, the draw's
    does not; a program without ``train.stack`` (the parent) reads the
    update's share alone, and one without either span reads nothing."""
    read = _reader().read
    run, tr = _synthetic({"source": "source", "stack": "stack",
                          "update": "update"})
    # per block: stack 5-10 (5 idle in the first block, 5 in the others),
    # update 10-13 (2 idle in the first, 3 in the others)
    assert read(run, {}, tr) == pytest.approx((5 + 2) + 2 * (5 + 3))
    run, tr = _synthetic({"source": "source", "update": "update"})
    assert read(run, {}, tr) == pytest.approx(2 + 2 * 3)
    run, tr = _synthetic({"source": "source"})
    assert read(run, {}, tr) is None
