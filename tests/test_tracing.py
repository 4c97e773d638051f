"""Host spans on the profiler's clock (repro.utils.tracing): what
generate_sharded, LogitStoreV2 and Trainer.fit record under a profiler
session, and that the numpy-only modules stay free of jax."""
import glob
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np

from repro.pipeline import generate_sharded
from repro.store import LogitStoreV2
from repro.train import BMUFShardMap, Local, TrainBatch, Trainer

K, V = 4, 30


def _record(trace_dir, fn):
    """Run ``fn`` under a profiler session; -> [(name, start, end, args)]
    of the ``repro.*`` host spans, by start."""
    from jax.profiler import ProfileData
    jax.profiler.start_trace(str(trace_dir))
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(str(trace_dir), "**", "*.xplane.pb"),
                      recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                out += [(e.name, e.start_ns, e.end_ns, dict(e.stats))
                        for e in line.events
                        if e.name.startswith("repro.")]
    return sorted(out, key=lambda s: s[1])


def _named(spans, name):
    return [s for s in spans if s[0] == name]


class _DeviceEngine:
    """Top-k of a fixed projection, left on the device as an
    accelerator engine's output is."""

    def forward_topk(self, batch):
        w = jax.random.normal(jax.random.key(0), (batch["feats"].shape[-1], V))
        vals, idx = jax.lax.top_k(jnp.asarray(batch["feats"]) @ w, K)
        return vals - vals[..., :1], idx


def _ragged_batches(n, b=3, s=6, f=8):
    rng = np.random.default_rng(5)
    out = []
    for _ in range(n):
        lens = rng.integers(1, s + 1, size=b)
        mask = (np.arange(s)[None] < lens[:, None]).astype(np.float32)
        out.append({"feats": rng.normal(size=(b, s, f)).astype(np.float32),
                    "mask": mask})
    return out


def test_generate_sharded_spans_one_append_per_shard(tmp_path):
    batches = _ragged_batches(5)
    store = LogitStoreV2(str(tmp_path / "store"), k=K, vocab=V)
    rep = {}
    spans = _record(tmp_path / "trace", lambda: rep.update(generate_sharded(
        lambda w: _DeviceEngine(), batches, store, n_workers=2)))
    appends = _named(spans, "repro.store.append_shard")
    assert len(appends) == rep["n_written"] == 5
    assert sorted(a[3]["shard"] for a in appends) == list(range(5))
    for a in appends:
        sid = a[3]["shard"]
        assert a[3]["frames"] == int(batches[sid]["mask"].sum())
    committed = sum(int(store.read_lens(sid).sum()) for sid in store.shards())
    assert sum(a[3]["frames"] for a in appends) == committed
    # the store's phases nest inside their append_shard
    for child in ("fetch", "write", "checksum", "manifest"):
        kids = _named(spans, "repro.store." + child)
        assert len(kids) == 5
        for a, c in zip(appends, kids):
            assert a[1] <= c[1] and c[2] <= a[2]
    forwards = _named(spans, "repro.gen.forward")
    assert [f[3]["shard"] for f in forwards] == list(range(5))
    # ranges (0, 3) and (3, 5): each batch but a range's first is
    # dispatched ahead, with the shard before it still to commit, and
    # that commit begins after the dispatch has ended
    assert [f[3]["ahead"] for f in forwards] == [0, 1, 1, 0, 1]
    by_shard = {a[3]["shard"]: a for a in appends}
    for f in forwards:
        if f[3]["ahead"]:
            assert f[2] <= by_shard[f[3]["shard"] - 1][1]
    # prepare, then a claim and a done mark per range, then the last claim
    ledger = _named(spans, "repro.gen.ledger")
    assert len(ledger) == 1 + 2 * 2 + 1


D = 8


def _quad_loss(params, batch):
    e = batch["x"] @ params["w"] - batch["y"]
    return jnp.mean(e ** 2), {"loss": jnp.mean(e ** 2)}


def _train_source(n):
    rng = np.random.default_rng(0)
    batch = {"x": jnp.asarray(rng.normal(size=(16, D)), jnp.float32),
             "y": jnp.asarray(rng.normal(size=(16,)), jnp.float32)}
    return [TrainBatch(batch, 0.1, "quad") for _ in range(n)]


def _strategy(kind):
    """-> (strategy, batches per update, ``placed`` its stack reports)."""
    if kind == "local":
        return Local(clip=0.0), 1, 0
    from repro.distributed.bmuf import BMUFConfig
    from repro.runtime.cluster import worker_mesh
    return BMUFShardMap(BMUFConfig(n_workers=2, block_steps=1),
                        worker_mesh(2), clip=0.0), 2, 1


def test_trainer_fit_spans_per_update_and_per_draw(tmp_path):
    """An exhausted source: one ``train.source`` per batch drawn and one
    for the draw that finds it empty, one ``train.stack`` per update
    (``placed`` 1 where the strategy lays the block out in the update's
    sharding, ``nbytes`` the block's input bytes); with ``max_updates``
    the loop stops without drawing again.  Under ``Local`` and under
    ``BMUFShardMap``."""
    for kind in ("local", "bmuf_shard_map"):
        _check_fit_spans(tmp_path / kind, kind)


def _check_fit_spans(tmp_path, kind):
    strategy, need, placed = _strategy(kind)
    tr = Trainer(strategy, {"quad": _quad_loss})
    state = tr.init_state({"w": jnp.zeros((D,))})
    tr.fit(state, _train_source(need), resume=False)       # compile
    spans = _record(tmp_path / "a", lambda: tr.fit(
        state, _train_source(4 * need), resume=False))
    updates = _named(spans, "repro.train.update")
    assert len(updates) == 4
    sources = _named(spans, "repro.train.source")
    assert len(sources) == 4 * need + 1
    stacks = _named(spans, "repro.train.stack")
    assert len(stacks) == 4
    block_bytes = need * sum(x.nbytes
                             for x in _train_source(1)[0].data.values())
    for i, (st, u) in enumerate(zip(stacks, updates)):
        # the block's last draw, then its stack, then its update
        assert sources[(i + 1) * need - 1][2] <= st[1]
        assert st[2] <= u[1]
        assert st[3] == {"placed": placed, "nbytes": block_bytes}

    spans = _record(tmp_path / "b", lambda: tr.fit(
        state, _train_source(6 * need), resume=False, max_updates=3))
    assert len(_named(spans, "repro.train.update")) == 3
    assert len(_named(spans, "repro.train.stack")) == 3
    assert len(_named(spans, "repro.train.source")) == 3 * need


def test_numpy_only_modules_import_without_jax():
    """Generation workers import the store and the generation module on a
    spawn-time budget; a span there is a no-op without jax."""
    code = ("import sys, contextlib\n"
            "import repro.utils.tracing as t, repro.store, "
            "repro.pipeline.generate\n"
            "assert 'jax' not in sys.modules, sorted(sys.modules)\n"
            "with t.span('store.fetch', shard=1) as s:\n"
            "    assert s is None\n"
            "assert isinstance(t.span('x'), contextlib.nullcontext)\n"
            "assert 'jax' not in sys.modules\n")
    src = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "src")
    env = dict(os.environ, PYTHONPATH=src)
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr
