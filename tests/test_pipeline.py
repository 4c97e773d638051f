"""Data-plane pipeline (ISSUE 3): sharded target generation over the
work ledger, and the async prefetching feed's ordering/determinism."""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.pipeline import (PrefetchingSource, WorkLedger, generate_sharded,
                            shard_ranges)
from repro.store import LogitStoreV2
from repro.train import (ListSink, Local, TrainBatch, Trainer,
                         distill_shard_source)

K, V = 4, 30


# ----------------------------------------------------------- partitioning

def test_shard_ranges_partition():
    assert shard_ranges(8, 2) == [(0, 4), (4, 8)]
    assert shard_ranges(7, 3) == [(0, 3), (3, 5), (5, 7)]
    assert shard_ranges(2, 4) == [(0, 1), (1, 2)]     # empty ranges dropped
    ranges = shard_ranges(23, 5)
    covered = [i for lo, hi in ranges for i in range(lo, hi)]
    assert covered == list(range(23))                 # disjoint + complete


# ----------------------------------------------------------------- ledger

def test_ledger_claim_done_resume(tmp_path):
    path = os.path.join(tmp_path, "ledger.json")
    led = WorkLedger.open(path, [(0, 2), (2, 4), (4, 6)])
    a = led.claim("w0")
    b = led.claim("w1")
    assert (a.lo, a.hi) == (0, 2) and (b.lo, b.hi) == (2, 4)
    led.mark_done(a)
    # "kill" the run: b stays claimed on disk.  A fresh open demotes the
    # dead worker's claim to pending; done work stays done.
    led2 = WorkLedger.open(path, [(0, 2), (2, 4), (4, 6)])
    assert led2.n_done == 1 and not led2.all_done
    statuses = [r.status for r in led2.ranges]
    assert statuses == ["done", "pending", "pending"]
    c = led2.claim("w0")
    assert (c.lo, c.hi) == (2, 4)                     # re-claimed
    led2.mark_done(c)
    led2.mark_done(led2.claim("w0"))
    assert led2.all_done


def test_ledger_repartition_rejected(tmp_path):
    path = os.path.join(tmp_path, "ledger.json")
    WorkLedger.open(path, [(0, 2), (2, 4)])
    with pytest.raises(ValueError):
        WorkLedger.open(path, [(0, 4)])


# ------------------------------------------------------ sharded generation

class _FakeEngine:
    """Deterministic stand-in for a StreamingEngine: top-k of a fixed
    random projection of the batch — content depends only on the batch,
    never on which worker ran it."""

    def __init__(self, worker: int, calls: list):
        self.worker = worker
        self.calls = calls

    def forward_topk(self, batch):
        self.calls.append(self.worker)
        feats = np.asarray(batch["feats"], np.float32)
        rng = np.random.default_rng(0)
        w = rng.normal(size=(feats.shape[-1], V)).astype(np.float32)
        logits = feats @ w
        idx = np.argsort(-logits, axis=-1)[..., :K].astype(np.int32)
        vals = np.take_along_axis(logits, idx, axis=-1)
        vals = vals - vals[..., :1]
        return vals, idx


def _batches(n, b=2, s=5, f=8):
    rng = np.random.default_rng(3)
    return [{"feats": rng.normal(size=(b, s, f)).astype(np.float32),
             "mask": np.ones((b, s), np.float32)} for _ in range(n)]


def test_generate_sharded_two_workers_single_consumer(tmp_path):
    """workers=2 production, workers=1 consumption: the manifest is the
    contract — complete, checksummed, and bitwise equal to what a
    single worker would have produced."""
    batches = _batches(6)
    calls = []
    store2 = LogitStoreV2(str(tmp_path / "w2"), k=K, vocab=V)
    rep = generate_sharded(lambda w: _FakeEngine(w, calls), batches, store2,
                           n_workers=2)
    assert rep["n_shards"] == 6 and rep["n_workers"] == 2
    assert set(calls) == {0, 1}                       # both workers ran
    assert store2.verify() == 6                       # manifest-verified

    store1 = LogitStoreV2(str(tmp_path / "w1"), k=K, vocab=V)
    generate_sharded(lambda w: _FakeEngine(w, []), batches, store1,
                     n_workers=1)
    for j in range(6):                                # workers=1 reader
        v2, i2 = store2.read_shard(j, verify=True)
        v1, i1 = store1.read_shard(j)
        np.testing.assert_array_equal(np.asarray(i2), np.asarray(i1))
        np.testing.assert_array_equal(np.asarray(v2), np.asarray(v1))


def test_generate_sharded_resumes_killed_range(tmp_path):
    """A worker dying mid-range leaves a claimed ledger entry; the next
    invocation re-claims exactly the unfinished ranges and the final
    store is complete."""
    batches = _batches(6)
    store = LogitStoreV2(str(tmp_path), k=K, vocab=V)
    ledger_path = os.path.join(tmp_path, "ledger.json")

    class _DyingEngine(_FakeEngine):
        def forward_topk(self, batch):
            if len(self.calls) == 3:
                raise RuntimeError("worker killed")
            return super().forward_topk(batch)

    calls = []
    with pytest.raises(RuntimeError):
        generate_sharded(lambda w: _DyingEngine(w, calls), batches, store,
                         n_workers=2, ledger_path=ledger_path)
    # the dying engine completes range (0,3) and dies entering (3,6):
    # genuinely partial progress, visible in both store and ledger
    assert 0 < len(store.shards()) < 6
    done_before = WorkLedger.open(ledger_path, shard_ranges(6, 2)).n_done
    assert done_before == 1

    calls2 = []
    rep = generate_sharded(lambda w: _FakeEngine(w, calls2), batches, store,
                           n_workers=2, ledger_path=ledger_path)
    assert rep["resumed"]
    assert store.verify() == 6
    assert store.shards() == list(range(6))
    # resumed pass only processed what the dead run left unfinished
    assert len(calls2) == 3


def test_generate_sharded_rerun_supersedes_wave(tmp_path):
    """A completed generation pass re-run (new teacher) supersedes the
    previous wave atomically rather than interleaving with it."""
    batches = _batches(4)
    store = LogitStoreV2(str(tmp_path), k=K, vocab=V)
    r0 = generate_sharded(lambda w: _FakeEngine(w, []), batches, store,
                          n_workers=2)
    r1 = generate_sharded(lambda w: _FakeEngine(w, []), batches, store,
                          n_workers=2)
    assert r0["wave"] == 0 and r1["wave"] == 1
    assert all(store.manifest.entry(j).wave == 1 for j in store.shards())
    store.verify()


def test_generate_sharded_completed_pass_repartitions(tmp_path):
    """A completed pass re-run with a different n_workers is a fresh
    wave with a fresh partition — only an *unfinished* ledger pins its
    ranges."""
    batches = _batches(6)
    store = LogitStoreV2(str(tmp_path), k=K, vocab=V)
    lp = os.path.join(tmp_path, "ledger.json")
    generate_sharded(lambda w: _FakeEngine(w, []), batches, store,
                     n_workers=2, ledger_path=lp)
    rep = generate_sharded(lambda w: _FakeEngine(w, []), batches, store,
                           n_workers=3, ledger_path=lp)
    assert rep["n_workers"] == 3 and rep["wave"] == 1
    assert store.verify() == 6


def test_generate_sharded_fresh_ledger_respects_live_wave(tmp_path):
    """A deleted ledger (or a new ledger_path) against a store already
    at a higher wave must start at next_wave(), not crash the first
    append with StaleWaveError."""
    batches = _batches(4)
    store = LogitStoreV2(str(tmp_path), k=K, vocab=V)
    lp = os.path.join(tmp_path, "ledger.json")
    generate_sharded(lambda w: _FakeEngine(w, []), batches, store,
                     n_workers=2, ledger_path=lp)
    generate_sharded(lambda w: _FakeEngine(w, []), batches, store,
                     n_workers=2, ledger_path=lp)   # store now at wave 1
    os.remove(lp)                                   # repartition hygiene
    rep = generate_sharded(lambda w: _FakeEngine(w, []), batches, store,
                           n_workers=1, ledger_path=lp)
    assert rep["wave"] == 2
    store.verify()


# ------------------------------------------- one batch ahead of the commit

def _plain_store(path, batches):
    """The serial loop: forward then commit, shard by shard."""
    store = LogitStoreV2(path, k=K, vocab=V)
    eng = _FakeEngine(0, [])
    for i, b in enumerate(batches):
        vals, idx = eng.forward_topk(b)
        store.append_shard(i, vals, idx, b["mask"].sum(-1).astype(np.int32))
    return store


@pytest.mark.parametrize("n_workers", [1, 2, 3])
def test_generate_sharded_dispatches_next_batch_before_commit(
        tmp_path, monkeypatch, n_workers):
    """Within a range, batch i+1 is dispatched before shard i's commit;
    no range's first forward comes before the previous range is done."""
    batches = _batches(7)
    events = []

    class _Engine(_FakeEngine):
        def forward_topk(self, batch):
            events.append(("forward", next(j for j, b in enumerate(batches)
                                           if b is batch)))
            return super().forward_topk(batch)

    class _Store(LogitStoreV2):
        def append_shard(self, shard_id, *a, **kw):
            events.append(("append", shard_id))
            return super().append_shard(shard_id, *a, **kw)

    mark_done = WorkLedger.mark_done

    def _mark_done(self, rng):
        events.append(("done", rng.lo))
        return mark_done(self, rng)

    monkeypatch.setattr(WorkLedger, "mark_done", _mark_done)
    store = _Store(str(tmp_path), k=K, vocab=V)
    generate_sharded(lambda w: _Engine(w, []), batches, store,
                     n_workers=n_workers)
    want = []
    for lo, hi in shard_ranges(7, n_workers):
        want.append(("forward", lo))
        for i in range(lo, hi):
            if i + 1 < hi:
                want.append(("forward", i + 1))
            want.append(("append", i))
        want.append(("done", lo))
    assert events == want


@pytest.mark.parametrize("n_workers", [1, 2])
def test_generate_sharded_shards_match_serial_loop(tmp_path, n_workers):
    batches = _batches(5)
    plain = _plain_store(str(tmp_path / "plain"), batches)
    store = LogitStoreV2(str(tmp_path / "gen"), k=K, vocab=V)
    generate_sharded(lambda w: _FakeEngine(w, []), batches, store,
                     n_workers=n_workers)
    assert store.shards() == plain.shards() == list(range(5))
    for j in range(5):
        assert (store.manifest.entry(j).checksum
                == plain.manifest.entry(j).checksum)


@pytest.mark.parametrize("fail_at, committed, done", [
    (1, [0], 0),                 # the first range's look-ahead
    (4, [0, 1, 2, 3], 1),        # the second range's look-ahead
])
def test_generate_sharded_failed_lookahead_commits_shard_before(
        tmp_path, fail_at, committed, done):
    """A look-ahead forward that raises leaves the shard before it
    committed and its range claimed; a re-run completes the store."""
    batches = _batches(6)
    store = LogitStoreV2(str(tmp_path / "gen"), k=K, vocab=V)
    lp = os.path.join(tmp_path, "ledger.json")

    class _FailingEngine(_FakeEngine):
        def forward_topk(self, batch):
            if batch is batches[fail_at]:
                raise RuntimeError("forward failed")
            return super().forward_topk(batch)

    with pytest.raises(RuntimeError, match="forward failed"):
        generate_sharded(lambda w: _FailingEngine(w, []), batches, store,
                         n_workers=2, ledger_path=lp)
    assert store.shards() == committed
    with open(lp) as f:
        status = [r["status"] for r in json.load(f)["ranges"]]
    assert status == ["done"] * done + ["claimed"] + ["pending"] * (1 - done)

    calls = []
    rep = generate_sharded(lambda w: _FakeEngine(w, calls), batches, store,
                           n_workers=2, ledger_path=lp)
    assert len(calls) == rep["n_written"] == 6 - 3 * done
    assert store.verify() == 6
    plain = _plain_store(str(tmp_path / "plain"), batches)
    for j in range(6):
        assert (store.manifest.entry(j).checksum
                == plain.manifest.entry(j).checksum)


@pytest.mark.parametrize("n, n_workers", [
    (6, 1), (6, 2), (7, 3), (2, 4), (1, 1)])
def test_generate_sharded_counts_overlapped_commits(tmp_path, n, n_workers):
    """Every commit but a range's last runs with the next batch out."""
    store = LogitStoreV2(str(tmp_path), k=K, vocab=V)
    rep = generate_sharded(lambda w: _FakeEngine(w, []), _batches(n), store,
                           n_workers=n_workers)
    claims = len(shard_ranges(n, n_workers))
    assert rep["n_written"] == n
    assert rep["overlapped"] == rep["n_written"] - claims


# ------------------------------------------------------- prefetching feed

def _quad(params, batch):
    e = batch["x"] @ params["w"] - batch["y"]
    return jnp.mean(e ** 2), {"loss": jnp.mean(e ** 2)}


def _quad_problem(n=32, d=8, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(n, d)).astype(np.float32)
    y = (x @ rng.normal(size=(d,))).astype(np.float32)
    return {"x": jnp.asarray(x), "y": jnp.asarray(y)}


def test_prefetch_preserves_order():
    src = [TrainBatch({"i": np.asarray([i])}, 0.1, "t") for i in range(20)]
    out = [int(np.asarray(tb.data["i"])[0])
           for tb in PrefetchingSource(src, depth=3)]
    assert out == list(range(20))


def test_prefetch_training_bitwise_equals_sync():
    """The acceptance pin: training through the prefetching feed is
    bitwise-identical to the synchronous feed — same loss trace, same
    final params."""
    batch = _quad_problem()
    src = lambda: [TrainBatch(batch, 0.05 * (0.9 ** i), "q")
                   for i in range(12)]
    sink_s, sink_p = ListSink(), ListSink()
    tr_s = Trainer(Local(clip=0.0), {"q": _quad}, metrics=sink_s)
    st_s = tr_s.fit(tr_s.init_state({"w": jnp.zeros((8,))}), src(),
                    resume=False)
    tr_p = Trainer(Local(clip=0.0), {"q": _quad}, metrics=sink_p,
                   prefetch=3)
    st_p = tr_p.fit(tr_p.init_state({"w": jnp.zeros((8,))}), src(),
                    resume=False)
    assert sink_s.values("loss") == sink_p.values("loss")
    np.testing.assert_array_equal(np.asarray(st_s.params["w"]),
                                  np.asarray(st_p.params["w"]))


def test_prefetch_distill_shard_source_bitwise(tmp_path):
    """End-to-end over the real store: distill shards fed sync vs
    prefetched (with checksum verify on the decode thread) produce the
    same training loss bitwise."""
    from repro.launch.steps import make_loss_fn
    from repro.models import build_model
    from repro.configs.lstm_am_7khr import CONFIG
    from repro.configs.base import LayerSpec, Segment

    cfg = CONFIG.replace(
        lstm_hidden=16, feat_dim=8, n_senones=V, vocab_size=V,
        segments=(Segment((LayerSpec(mixer="lstm", ffn="none"),),
                          repeat=1),))
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    rng = np.random.default_rng(1)
    batches = [{"feats": rng.normal(size=(2, 6, 8)).astype(np.float32),
                "mask": np.ones((2, 6), np.float32)} for _ in range(4)]
    store = LogitStoreV2(str(tmp_path), k=K, vocab=V)
    for j in range(4):
        vals = rng.normal(size=(2, 6, K)).astype(np.float32)
        vals = vals - vals.max(-1, keepdims=True)
        idx = np.stack([rng.choice(V, K, replace=False)
                        for _ in range(12)]).reshape(2, 6, K)
        store.append_shard(j, vals, idx)

    loss_fns = {"distill_topk": make_loss_fn(model, cfg, "distill_topk")}
    outs = []
    for depth in (0, 2):
        sink = ListSink()
        tr = Trainer(Local(clip=0.0), loss_fns, metrics=sink,
                     prefetch=depth)
        st = tr.fit(tr.init_state(params),
                    distill_shard_source(batches, store, 0, 4, 0.05,
                                         verify=depth > 0),
                    resume=False)
        outs.append((sink.values("loss"), jax.device_get(st.params)))
    assert outs[0][0] == outs[1][0]
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_array_equal(np.asarray(a),
                                                   np.asarray(b)),
        outs[0][1], outs[1][1])


def test_prefetch_exhausted_iterator_stays_exhausted():
    """next() on an exhausted prefetch iterator raises StopIteration
    again instead of parking forever on the drained queue."""
    it = iter(PrefetchingSource([TrainBatch({"i": np.zeros(1)}, 0.1, "t")],
                                depth=2))
    assert len(list(it)) == 1
    with pytest.raises(StopIteration):
        next(it)


def test_prefetch_propagates_producer_error():
    def bad():
        yield TrainBatch({"i": np.zeros(1)}, 0.1, "t")
        raise ValueError("decode failed")
    it = iter(PrefetchingSource(bad, depth=2))
    next(it)
    with pytest.raises(ValueError, match="decode failed"):
        next(it)


def test_distill_source_pin_wave_survives_mid_epoch_supersede(tmp_path):
    """The wave-consistency fix: a pinned source snapshots its shards'
    manifest entries at iteration start, so a teacher regeneration
    superseding shards *mid-sub-epoch* cannot switch the pass onto
    new-wave targets half way through — while an unpinned source
    silently mixes the two waves (the bug)."""
    batches = _batches(4)
    store = LogitStoreV2(str(tmp_path), k=K, vocab=V)
    old = {}
    rng = np.random.default_rng(5)
    for j in range(4):
        vals = rng.normal(size=(2, 5, K)).astype(np.float32)
        vals = vals - vals.max(-1, keepdims=True)
        idx = rng.integers(0, V, (2, 5, K)).astype(np.int32)
        store.append_shard(j, vals, idx)
        old[j] = idx

    def supersede_all():
        for j in range(4):
            vals = np.zeros((2, 5, K), np.float32)
            idx = np.full((2, 5, K), j % V, np.int32)   # distinctive
            store.append_shard(j, vals, idx, wave=1)

    # pinned: iterate two shards, regenerate everything, keep iterating
    # — every batch still carries wave-0 targets
    it = iter(distill_shard_source(batches, store, 0, 4, 0.1,
                                   pin_wave=True, verify=True))
    got = [next(it), next(it)]
    supersede_all()
    got += list(it)
    for j, tb in enumerate(got):
        np.testing.assert_array_equal(np.asarray(tb.data["topk_idx"]),
                                      old[j], err_msg=f"shard {j}")

    # unpinned (the old behavior): the same interleaving mixes waves
    it = iter(distill_shard_source(batches, store, 0, 4, 0.1))
    first = next(it)
    # a third wave lands mid-epoch
    for j in range(4):
        store.append_shard(j, np.zeros((2, 5, K), np.float32),
                           np.full((2, 5, K), (j + 7) % V, np.int32),
                           wave=2)
    rest = list(it)
    assert np.asarray(first.data["topk_idx"]).max() != \
        np.asarray(rest[0].data["topk_idx"]).max()


def test_prefetch_early_close_stops_producer():
    produced = []

    def src():
        for i in range(1000):
            produced.append(i)
            yield TrainBatch({"i": np.asarray([i])}, 0.1, "t")

    ps = PrefetchingSource(src, depth=2)
    it = iter(ps)
    for _ in range(3):
        next(it)
    ps.close()
    n = len(produced)
    assert n < 1000                       # producer stopped early
    import time
    time.sleep(0.1)
    assert len(produced) == n             # ...and stays stopped
