"""End-to-end SSL pipeline — the paper's recipe, laptop-scaled.

Stages (paper sections in brackets):
  baseline : student-architecture LSTM AM, CE on labeled data [§2]
  teacher  : bidirectional LSTM AM, CE (+ sMBR) on labeled data [§3.2]
  targets  : teacher inference over the unlabeled firehose -> top-k=20
             logits into the manifest-backed LogitStore v2, partitioned
             across gen_workers ledgered shard ranges [§3.2.2];
             resumable (work ledger) and wave-versioned (re-runs
             supersede atomically)
  student  : scheduled learning over unlabeled sub-epochs with labeled
             interleaves [§3.3], GTC or BMUF trainer [§3.5]
  smbr     : sequence training on labeled data only [§3.4], under
             threshold-compressed SGD

Every training stage is one ``Trainer.fit()`` call (repro.train): the
stage picks a DistributedStrategy (Local / BMUFShardMap / GTCShardMap),
a dict of loss fns, and a DataSource; the Trainer owns the jit (one
executable per loss kind x batch shape, lr traced), periodic TrainState
checkpoints under <out>/ckpt_<stage>/state (killed stages resume
mid-stream; completed stages retire their resume state), and the
metrics sink.  Batches reach the jitted update through the async
prefetching feed (repro.pipeline.PrefetchingSource, depth
PipelineConfig.prefetch) so host-side shard decode overlaps device
compute.  Final params land in <out>/ckpt_<stage> — the cross-stage
interface.

Metrics include the frame-error-rate (FER) on a held-out synthetic VAL
set and the relative FER reduction vs the baseline — the
container-scale proxy for the paper's relative WERR (the paper only
ever reports relative numbers).
"""
from __future__ import annotations

import os
from dataclasses import dataclass
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import CheckpointStore
from repro.configs.lstm_am_7khr import CONFIG as AM_CONFIG
from repro.configs.base import LayerSpec, Segment
from repro.core import scheduled
from repro.core.teacher import TeacherRunner
from repro.pipeline import generate_sharded
from repro.store import LogitStoreV2
from repro.data import FeatureConfig, SynthConfig
from repro.data.loader import CorpusLoader
from repro.distributed.bmuf import BMUFConfig
from repro.distributed.gtc import GTCConfig
from repro.launch.steps import make_loss_fn
from repro.models import build_model
from repro.runtime.cluster import worker_mesh
from repro.seqtrain import build_denominator_graph, make_smbr_loss_fn
from repro.seqtrain.smbr import frame_error_rate
from repro.train import (BMUFShardMap, GTCShardMap, ListSink, Local,
                         TrainBatch, Trainer, chain, distill_shard_source,
                         epoch_source, scheduled_source)


def am_configs(*, n_layers: int, lstm_hidden: int, n_senones: int,
               feat_dim: int):
    """(student, teacher) ModelConfigs from the pipeline's scale knobs.

    Module-level (not a method) because the multi-process generation
    workers rebuild the teacher config from these same scalars on the
    far side of a process boundary (:func:`pipeline_teacher_engine`).
    """
    base = AM_CONFIG.replace(
        segments=(Segment((LayerSpec(mixer="lstm", ffn="none"),),
                          repeat=n_layers),),
        lstm_hidden=lstm_hidden, n_senones=n_senones,
        vocab_size=n_senones, feat_dim=feat_dim)
    teacher = base.replace(
        name="teacher",
        segments=(Segment((LayerSpec(mixer="bilstm", ffn="none"),),
                          repeat=n_layers),))
    return base, teacher


def _engine_from_ckpt(cfg, ckpt_dir: str, topk: int) -> TeacherRunner:
    model = build_model(cfg)
    like = jax.eval_shape(lambda: model.init(jax.random.key(0)))
    like = jax.tree_util.tree_map(
        lambda s: jnp.zeros(s.shape, s.dtype), like)
    params, _ = CheckpointStore(ckpt_dir).load(like)
    return TeacherRunner(cfg, params, k=topk)


def pipeline_teacher_engine(worker_id: int, kwargs: dict):
    """Engine factory spec ``repro.core.ssl_pipeline:
    pipeline_teacher_engine`` — a generation worker process rebuilds
    the pipeline's TeacherRunner from the teacher checkpoint on disk
    (kwargs: ckpt_dir + the :func:`am_configs` scalars + topk)."""
    del worker_id
    _, teacher_cfg = am_configs(
        n_layers=int(kwargs["n_layers"]),
        lstm_hidden=int(kwargs["lstm_hidden"]),
        n_senones=int(kwargs["n_senones"]),
        feat_dim=int(kwargs["feat_dim"]))
    return _engine_from_ckpt(teacher_cfg, kwargs["ckpt_dir"],
                             int(kwargs["topk"]))


def pipeline_student_engine(worker_id: int, kwargs: dict):
    """Engine factory spec ``repro.core.ssl_pipeline:
    pipeline_student_engine`` — the *promoted student* as the
    generation engine (iterative distillation: after a wave of
    scheduled learning the student regenerates the targets for the
    next wave).  Same kwargs as the teacher factory; the rebuilt config
    is the student (unidirectional) architecture."""
    del worker_id
    student_cfg, _ = am_configs(
        n_layers=int(kwargs["n_layers"]),
        lstm_hidden=int(kwargs["lstm_hidden"]),
        n_senones=int(kwargs["n_senones"]),
        feat_dim=int(kwargs["feat_dim"]))
    return _engine_from_ckpt(student_cfg, kwargs["ckpt_dir"],
                             int(kwargs["topk"]))


def _pad_time(batch: dict, t: int) -> dict:
    """Zero-pad every (B, T, ...) leaf of a full-seq batch to T = t
    (mask rows stay 0 over the padding, so losses are unchanged)."""
    out = {}
    for k, v in batch.items():
        if getattr(v, "ndim", 0) >= 2 and v.shape[1] < t:
            pad = [(0, 0)] * v.ndim
            pad[1] = (0, t - v.shape[1])
            out[k] = np.pad(v, pad)
        else:
            out[k] = v
    return out


@dataclass
class PipelineConfig:
    # data
    n_labeled: int = 48
    n_unlabeled: int = 192
    n_val: int = 16
    n_speakers: int = 16
    n_senones: int = 49
    mean_utt_sec: float = 1.2
    n_mels: int = 16
    # model
    n_layers: int = 2
    lstm_hidden: int = 64
    # training
    batch: int = 8
    chunk_len: int = 32
    epochs_baseline: int = 5
    lr: float = 5e-2
    topk: int = 10
    ckpt_every: int = 20              # TrainState resume-ckpt cadence
    # data plane
    gen_workers: int = 2              # target-generation workers (ledgered
                                      # disjoint shard ranges, engine each)
    gen_procs: int = 0                # >0: generation as N real OS
                                      # processes racing the shared ledger
                                      # (runtime.workers; manifest bitwise-
                                      # identical to in-process)
    prefetch: int = 2                 # async feed depth for Trainer.fit
                                      # (0 = synchronous)
    # schedule (paper-structured, scaled)
    n_sub_epochs: int = 4
    labeled_every: int = 2
    chunked_until: int = 3
    # trainers
    gtc_tau: float = 2e-4
    gtc_workers: int = 2              # sMBR sequence-training workers
                                      # of GTCShardMap (int8 wire, worker
                                      # axis on a mesh)
    bmuf_workers: int = 4
    bmuf_block_steps: int = 2
    smbr_epochs: int = 2
    smbr_kappa: float = 0.3
    smbr_lr: float = 5e-3
    seed: int = 0

    @classmethod
    def tiny(cls) -> "PipelineConfig":
        return cls()

    @classmethod
    def small(cls) -> "PipelineConfig":
        return cls(n_labeled=128, n_unlabeled=640, n_val=32, n_speakers=32,
                   n_senones=97, lstm_hidden=128, n_layers=3,
                   epochs_baseline=4, n_sub_epochs=6, labeled_every=2,
                   chunked_until=4)

    @classmethod
    def paper(cls) -> "PipelineConfig":
        """The paper's published widths (§2): 5x768 LSTM student and
        biLSTM teacher, 3,183 senones, 64 log-mel x3 stacked = 192-d
        features, top-20 targets.  The synthetic corpus keeps ``small``'s
        size: width is what the chip must see, corpus size only sets how
        long a run takes."""
        return cls(n_labeled=128, n_unlabeled=640, n_val=32, n_speakers=32,
                   n_senones=3183, n_mels=64, n_layers=5, lstm_hidden=768,
                   topk=20, epochs_baseline=4, n_sub_epochs=6,
                   labeled_every=2, chunked_until=4)

    @property
    def feat_dim(self) -> int:
        return self.n_mels * 3


class SSLPipeline:
    def __init__(self, pc: PipelineConfig, *, out_dir: str = "experiments/train",
                 student_trainer: str = "gtc"):
        self.pc = pc
        self.out = out_dir
        self.student_trainer = student_trainer
        os.makedirs(out_dir, exist_ok=True)

        self.synth = SynthConfig(n_speakers=pc.n_speakers,
                                 n_senones=pc.n_senones,
                                 mean_utt_sec=pc.mean_utt_sec, seed=pc.seed)
        self.feat = FeatureConfig(n_mels=pc.n_mels)
        # look-ahead 0 at laptop scale: the label-shift mechanism itself is
        # exercised by tests/test_data.py; a 30-90ms output delay is not
        # learnable by a 2x64 LSTM on minutes of audio (the paper's value
        # of 3 is one config knob away)
        self.loader = CorpusLoader(synth=self.synth, feat=self.feat,
                                   lookahead=0)
        self.loader.estimate_mvn(min(24, pc.n_labeled))

        self.student_cfg, self.teacher_cfg = am_configs(
            n_layers=pc.n_layers, lstm_hidden=pc.lstm_hidden,
            n_senones=pc.n_senones, feat_dim=pc.feat_dim)

        # utterance-id ranges: labeled / unlabeled / val are disjoint
        self.rng_labeled = (0, pc.n_labeled)
        self.rng_unlabeled = (10_000, pc.n_unlabeled)
        self.rng_val = (100_000, pc.n_val)
        self._val_batch = None

    # ------------------------------------------------------------- helpers

    def _batches(self, rng, *, chunked: bool, offset: int = 0, seed: int = 0,
                 uniform_len: bool = False):
        start, count = rng
        if chunked:
            return list(self.loader.chunked_batches(
                start, count, batch_size=self.pc.batch,
                chunk_len=self.pc.chunk_len, offset=offset, seed=seed))
        bs = list(self.loader.full_seq_batches(
            start, count, batch_size=max(2, self.pc.batch // 2),
            offset=offset))
        if uniform_len and bs:
            # pad every batch to the corpus max: multi-microbatch
            # strategies (GTCShardMap consumes one batch per worker)
            # group shape-mates, so ragged full-seq batches would drop
            # partial groups at every length boundary
            t = max(b["feats"].shape[1] for b in bs)
            bs = [_pad_time(b, t) for b in bs]
        return bs

    def val_batch(self):
        if self._val_batch is None:
            bs = self._batches(self.rng_val, chunked=False)
            self._val_batch = {k: jnp.asarray(v) for k, v in bs[0].items()}
        return self._val_batch

    def fer(self, cfg, params) -> float:
        model = build_model(cfg)
        vb = self.val_batch()
        h, _ = model.apply(params, vb["feats"])
        logits = model.unembed(params, h)
        return float(frame_error_rate(logits, vb["labels"], vb["mask"]))

    def _ckpt(self, stage) -> CheckpointStore:
        return CheckpointStore(os.path.join(self.out, f"ckpt_{stage}"))

    def _trainer(self, stage, strategy, loss_fns, sink) -> Trainer:
        """One Trainer per stage: resume state under ckpt_<stage>/state."""
        store = CheckpointStore(
            os.path.join(self.out, f"ckpt_{stage}", "state"))
        return Trainer(strategy, loss_fns, checkpoint=store,
                       ckpt_every=self.pc.ckpt_every, metrics=sink,
                       prefetch=self.pc.prefetch)

    def _load_or_none(self, stage, cfg):
        store = self._ckpt(stage)
        model = build_model(cfg)
        like = jax.eval_shape(lambda: model.init(jax.random.key(0)))
        like = jax.tree_util.tree_map(
            lambda s: jnp.zeros(s.shape, s.dtype), like)
        try:
            params, _ = store.load(like)
            return params
        except FileNotFoundError:
            return None

    def _ce_source(self, *, n_epochs, lr, seed0=0):
        """The supervised recipe: chunked-BPTT epochs with rotating
        feature offsets, then one full-sequence fine-tune epoch."""
        return chain(
            epoch_source(
                lambda ep: self._batches(self.rng_labeled, chunked=True,
                                         offset=ep % 3, seed=seed0 + ep),
                n_epochs, lr, "ce"),
            epoch_source(
                lambda ep: self._batches(self.rng_labeled, chunked=False),
                1, lr * 0.3, "ce"))

    # -------------------------------------------------------------- stages

    def stage_baseline(self) -> Dict:
        pc = self.pc
        model = build_model(self.student_cfg)
        sink = ListSink()
        tr = self._trainer("baseline", Local(),
                           {"ce": make_loss_fn(model, self.student_cfg,
                                               "ce")}, sink)
        state = tr.init_state(model.init(jax.random.key(pc.seed)),
                              seed=pc.seed)
        state = tr.fit(state, self._ce_source(n_epochs=pc.epochs_baseline,
                                              lr=pc.lr))
        tr.finalize(state)
        self._ckpt("baseline").save(0, state.params)
        # sink only saw post-resume updates: first/last may be None on a
        # run resumed at (or past) its final periodic checkpoint
        return {"loss_first": sink.first("loss"),
                "loss_last": sink.last("loss"),
                "val_fer": self.fer(self.student_cfg, state.params)}

    def stage_teacher(self) -> Dict:
        pc = self.pc
        model = build_model(self.teacher_cfg)
        sink = ListSink()
        tr = self._trainer("teacher", Local(),
                           {"ce": make_loss_fn(model, self.teacher_cfg,
                                               "ce")}, sink)
        state = tr.init_state(model.init(jax.random.key(pc.seed + 1)),
                              seed=pc.seed + 1)
        state = tr.fit(state, self._ce_source(n_epochs=pc.epochs_baseline,
                                              lr=pc.lr, seed0=100))

        # sMBR fine-tune of the teacher (paper's "with sMBR teacher" arm);
        # no grad clip — sMBR grads are already bounded by the posteriors
        smbr_sink = ListSink()
        smbr_tr = self._trainer(
            "teacher_smbr", Local(clip=0.0),
            {"smbr": make_smbr_loss_fn(model, self.teacher_cfg,
                                       self._graph(),
                                       kappa=pc.smbr_kappa)}, smbr_sink)
        sstate = smbr_tr.init_state(state.params, seed=pc.seed + 1)
        sstate = smbr_tr.fit(sstate, epoch_source(
            lambda ep: self._batches(self.rng_labeled, chunked=False),
            1, pc.smbr_lr, "smbr"))
        # retire resume state only once the whole stage is done — a kill
        # during the sMBR sub-fit must still resume (not retrain) the CE
        # part on re-invocation
        tr.finalize(state)
        smbr_tr.finalize(sstate)
        self._ckpt("teacher").save(0, sstate.params)
        return {"loss_last": sink.last("loss"),
                "val_fer": self.fer(self.teacher_cfg, sstate.params),
                "smbr_eacc": smbr_sink.last("expected_frame_acc")}

    def _graph(self):
        pairs = self.loader.featurized(*self.rng_labeled)
        return build_denominator_graph([l for _, l, _ in pairs],
                                       self.pc.n_senones)

    def stage_targets(self, *, promoted_stage: str = None) -> Dict:
        """Sharded generation through the data plane: the unlabeled
        corpus is partitioned across ``gen_workers`` ledgered shard
        ranges, one TeacherRunner (engine) per worker, into the
        manifest-backed LogitStore v2 — a killed run re-claims its
        unfinished ranges, a completed re-run supersedes the previous
        wave atomically.  ``promoted_stage`` switches the engine from
        the bidirectional teacher to that stage's *student* checkpoint
        (iterative distillation: the wave driver promotes the student
        to teacher between waves)."""
        pc = self.pc
        if promoted_stage is None:
            gen_cfg, ckpt_name = self.teacher_cfg, "teacher"
            factory = "pipeline_teacher_engine"
        else:
            gen_cfg, ckpt_name = self.student_cfg, promoted_stage
            factory = "pipeline_student_engine"
        gparams = self._load_or_none(ckpt_name, gen_cfg)
        assert gparams is not None, f"run stage {ckpt_name} first"
        store = LogitStoreV2(os.path.join(self.out, "logit_store"),
                             k=pc.topk, vocab=pc.n_senones)
        # host (numpy) batches: the jitted forward converts one batch at
        # a time, so device memory stays O(1 batch) over the whole corpus
        batches = [{"feats": b["feats"], "mask": b["mask"]}
                   for b in self._batches(self.rng_unlabeled, chunked=True,
                                          seed=7)]

        if pc.gen_procs >= 1:
            # real OS processes: each rebuilds the engine from the
            # checkpoint (the factory spec crosses the process boundary;
            # params cannot) — manifest bitwise-identical to in-process
            make_engine = f"repro.core.ssl_pipeline:{factory}"
            engine_kwargs = {
                "ckpt_dir": os.path.join(self.out, f"ckpt_{ckpt_name}"),
                "n_layers": pc.n_layers, "lstm_hidden": pc.lstm_hidden,
                "n_senones": pc.n_senones, "feat_dim": pc.feat_dim,
                "topk": pc.topk}
        else:
            engine_kwargs = None

            def make_engine(worker: int):
                return TeacherRunner(gen_cfg, gparams, k=pc.topk)

        report = generate_sharded(
            make_engine, batches, store, n_workers=pc.gen_workers,
            ledger_path=os.path.join(self.out, "gen_ledger.json"),
            processes=pc.gen_procs, engine_kwargs=engine_kwargs)
        store.verify()                    # manifest-checksum every shard
        meta = store.stats()
        full = meta.n_frames * pc.n_senones * 4
        packed = meta.n_frames * (pc.topk * 6)
        out = {"n_shards": report["n_shards"], "n_frames": meta.n_frames,
               "n_workers": report["n_workers"], "wave": report["wave"],
               "resumed": report["resumed"],
               "storage_compression_x": round(full / packed, 1)}
        if pc.gen_procs >= 1:             # the fleet's completion report
            out.update({k: report[k] for k in ("processes", "restarts",
                                               "reclaimed")})
            # structured steal/lifecycle events from the supervisor +
            # ledger: who stole what from whom, by which signal, how old
            events = report.get("events", [])
            out["n_steals"] = sum(e.get("event") == "steal"
                                  for e in events)
            out["events"] = events[-20:]
        return out

    def _student_strategy(self):
        pc = self.pc
        if self.student_trainer == "bmuf":
            return BMUFShardMap(BMUFConfig(n_workers=pc.bmuf_workers,
                                           block_steps=pc.bmuf_block_steps),
                                worker_mesh(pc.bmuf_workers))
        return GTCShardMap(GTCConfig(tau=pc.gtc_tau, n_workers=1),
                           worker_mesh(1))

    def stage_student(self, *, membership=None, init_params=None,
                      stage: str = None) -> Dict:
        """Scheduled learning on unlabeled top-k targets + labeled
        passes — same loop for both trainers; only the strategy differs.
        ``membership`` (anything with ``live_count()``) makes the fit
        elastic: worker deaths shrink the BMUF block at the next block
        boundary, revivals grow it back.  ``init_params``/``stage``
        let the wave driver chain waves (each wave trains from the
        previous wave's promoted params under its own checkpoint
        stage)."""
        pc = self.pc
        baseline = (init_params if init_params is not None
                    else self._load_or_none("baseline", self.student_cfg))
        assert baseline is not None, "run stage baseline first"
        # the workers=1 consumer of whatever N workers generated: the
        # manifest is the contract — verify() checksums every live shard
        store = LogitStoreV2(os.path.join(self.out, "logit_store"),
                             k=pc.topk, vocab=pc.n_senones)
        unl_batches = self._batches(self.rng_unlabeled, chunked=True, seed=7)
        assert len(store.shards()) == len(unl_batches), "regenerate targets"
        store.verify()
        per_sub = max(1, len(unl_batches) // pc.n_sub_epochs)
        sched = scheduled.ScheduleConfig(
            n_sub_epochs=pc.n_sub_epochs, sub_epoch_hours=1.0,
            labeled_every=pc.labeled_every, chunked_until=pc.chunked_until,
            lr0=pc.lr, labeled_lr_boost=1.5)

        stage = stage or f"student_{self.student_trainer}"
        model = build_model(self.student_cfg)
        sink = ListSink()
        tr = self._trainer(
            stage, self._student_strategy(),
            {"distill_topk": make_loss_fn(model, self.student_cfg,
                                          "distill_topk"),
             "ce": make_loss_fn(model, self.student_cfg, "ce")}, sink)
        state = tr.init_state(baseline, seed=pc.seed)

        def unlabeled(phase):
            lo = (phase.sub_epoch - 1) * per_sub
            # pin_wave: each sub-epoch snapshots its shards' manifest
            # entries at start — a teacher regeneration landing a new
            # wave mid-sub-epoch cannot mix targets into this pass
            return distill_shard_source(unl_batches, store, lo,
                                        lo + per_sub, phase.lr,
                                        pin_wave=True)

        def labeled(phase):
            return (TrainBatch(b, phase.lr, "ce")
                    for b in self._batches(
                        self.rng_labeled, chunked=phase.chunked,
                        offset=max(phase.feature_offset, 0)))

        state = tr.fit(state, scheduled_source(sched, unlabeled=unlabeled,
                                               labeled=labeled),
                       membership=membership)
        tr.finalize(state)
        self._ckpt(stage).save(0, state.params)
        out = self._student_metrics(state.params, sink.values("loss"))
        if membership is not None:
            out["resizes"] = dict(tr.resize_stats)
            out["final_workers"] = getattr(tr.strategy, "n_workers", 1)
        return out

    def _student_metrics(self, params, losses):
        fer = self.fer(self.student_cfg, params)
        base = self._load_or_none("baseline", self.student_cfg)
        base_fer = self.fer(self.student_cfg, base)
        return {"n_steps": len(losses),
                "loss_first": losses[0] if losses else None,
                "loss_last": losses[-1] if losses else None,
                "val_fer": fer, "baseline_fer": base_fer,
                "rel_fer_reduction_pct":
                    round(100 * (base_fer - fer) / max(base_fer, 1e-9), 2)}

    def _smbr_strategy(self):
        """The paper's 16-GPU sMBR trainer: threshold-compressed SGD,
        the worker axis on a mesh (the axis spans the devices when the
        worker count divides them, else one device carries all workers
        — the same math either way, pinned bitwise in tests)."""
        pc = self.pc
        # widest mesh the worker count divides onto: each device carries
        # workers/n_dev unrolled workers (all of them on 1 device at
        # laptop scale; one each on the paper's 16-GPU shape)
        mesh = worker_mesh(pc.gtc_workers)
        return GTCShardMap(
            GTCConfig(tau=pc.gtc_tau, n_workers=pc.gtc_workers),
            mesh, clip=0.0)

    def stage_smbr(self) -> Dict:
        """Sequence training of the SSL student on labeled data only,
        under threshold-compressed SGD — the paper's sMBR trainer
        (§3.4), multi-worker by default (``gtc_workers``): each update
        consumes one batch per worker and exchanges int8-packed sends
        over the worker axis."""
        pc = self.pc
        stage = f"student_{self.student_trainer}"
        params = self._load_or_none(stage, self.student_cfg)
        if params is None:
            params = self._load_or_none("baseline", self.student_cfg)
        model = build_model(self.student_cfg)
        sink = ListSink()
        tr = self._trainer(
            "smbr", self._smbr_strategy(),
            {"smbr": make_smbr_loss_fn(model, self.student_cfg,
                                       self._graph(),
                                       kappa=pc.smbr_kappa)}, sink)
        state = tr.init_state(params, seed=pc.seed)
        state = tr.fit(state, epoch_source(
            lambda ep: self._batches(self.rng_labeled, chunked=False,
                                     uniform_len=pc.gtc_workers > 1),
            pc.smbr_epochs, pc.smbr_lr, "smbr"))
        tr.finalize(state)
        self._ckpt("smbr").save(0, state.params)
        fer = self.fer(self.student_cfg, state.params)
        base = self._load_or_none("baseline", self.student_cfg)
        base_fer = self.fer(self.student_cfg, base)
        return {"eacc_first": sink.first("expected_frame_acc"),
                "eacc_last": sink.last("expected_frame_acc"),
                "val_fer": fer, "baseline_fer": base_fer,
                "rel_fer_reduction_pct":
                    round(100 * (base_fer - fer) / max(base_fer, 1e-9), 2)}

    # ----------------------------------------------------------------- run

    def run(self, stage: str = "all") -> Dict:
        if stage != "all":
            return getattr(self, f"stage_{stage}")()
        out = {}
        for s in ("baseline", "teacher", "targets", "student", "smbr"):
            out[s] = getattr(self, f"stage_{s}")()
            print(f"[pipeline] {s}: {out[s]}")
        return out

    # ---------------------------------------------------------------- waves

    def run_waves(self, n_waves: int = 2, *, kill_at: int = 1,
                  revive_after: int = 2) -> Dict:
        """Continuous elastic scheduled learning: generate -> train ->
        promote, repeated, surviving injected worker deaths.

        Wave 0 distills from the bidirectional teacher; every later
        wave *regenerates* the targets with the previous wave's student
        promoted to teacher (iterative distillation — "Exploiting
        Large-scale Teacher-Student Training", PAPERS.md) through the
        v2 store's atomic wave supersede.  Each wave's BMUF student fit
        runs under a :class:`~repro.runtime.workers.TrainerMembership`
        with a scripted :class:`~repro.runtime.workers.LaneCrashPlan`:
        one lane is killed after block ``kill_at`` (the block average
        shrinks to the survivors at the next sync) and revived
        ``revive_after`` blocks later (warm rejoin — lanes are kept
        broadcast-current exactly for this).  Requires the ``bmuf``
        student trainer (the only one with worker-stacked state to be
        elastic over).

        Returns per-wave generation + student reports plus the final
        health checks: manifest checksum-verified, superseded waves
        garbage-collected, generation ledger fully done.
        """
        from repro.pipeline.generate import WorkLedger
        from repro.runtime.workers import LaneCrashPlan, TrainerMembership

        pc = self.pc
        assert self.student_trainer == "bmuf", \
            "elastic waves need the BMUF student trainer"
        assert pc.bmuf_workers >= 2, "need >= 2 lanes to kill one"
        assert self._load_or_none("baseline", self.student_cfg) \
            is not None, "run stage baseline first"

        membership = TrainerMembership(
            os.path.join(self.out, "trainer_members.json"),
            timeout_s=30.0)
        lanes = [f"lane{i}" for i in range(pc.bmuf_workers)]

        waves = []
        prev_stage = None       # None -> the bilstm teacher generates
        for w in range(n_waves):
            gen = self.stage_targets(promoted_stage=prev_stage)
            # every lane rejoins at the wave boundary (revived workers
            # come back warm; the roster is the ground truth mid-wave)
            for lane in lanes:
                membership.join(lane)
            victim = lanes[-1 - (w % (len(lanes) - 1))]  # rotate, keep lane0
            plan = LaneCrashPlan(
                membership,
                kills={} if kill_at is None else {kill_at: victim},
                revives={} if kill_at is None or revive_after is None
                else {kill_at + revive_after: victim})
            stage = f"student_wave{w}"
            init = (None if prev_stage is None
                    else self._load_or_none(prev_stage, self.student_cfg))
            rep = self.stage_student(membership=plan, init_params=init,
                                     stage=stage)
            rep["chaos"] = plan.log
            waves.append({"wave": gen["wave"], "gen": gen, "student": rep})
            print(f"[waves] wave {w}: gen wave={gen['wave']} "
                  f"fer={rep['val_fer']:.3f} resizes={rep['resizes']} "
                  f"chaos={plan.log}")
            prev_stage = stage  # student promoted to teacher

        store = LogitStoreV2(os.path.join(self.out, "logit_store"),
                             k=pc.topk, vocab=pc.n_senones)
        n_verified = store.verify()
        removed = store.gc()    # superseded waves leave no orphans
        ledger_clean = WorkLedger.peek_all_done(
            os.path.join(self.out, "gen_ledger.json"))
        return {"n_waves": n_waves, "waves": waves,
                "manifest_clean": True, "n_verified": n_verified,
                "gc_removed": len(removed), "ledger_clean": ledger_clean,
                "restarts_absorbed": sum(
                    1 for wv in waves
                    for e in wv["student"].get("chaos", [])
                    if e.get("event") == "kill"),
                "resize_count": sum(
                    wv["student"]["resizes"]["count"] for wv in waves),
                "resize_seconds": round(sum(
                    wv["student"]["resizes"]["seconds"] for wv in waves),
                    3),
                "final_fer": waves[-1]["student"]["val_fer"],
                "rel_fer_reduction_pct":
                    waves[-1]["student"]["rel_fer_reduction_pct"]}
