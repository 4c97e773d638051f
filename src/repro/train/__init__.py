"""Unified Trainer API (ISSUE 2) + the data-plane feed (ISSUE 3).

  TrainState            — params + opt + step + rng + strategy state
  DistributedStrategy   — Local / BMUFShardMap / GTCShardMap
  DataSource            — iterables of TrainBatch (epoch_source,
                          distill_shard_source, scheduled_source, chain);
                          compose with repro.pipeline.PrefetchingSource
                          for the async host->device feed
  Trainer               — fit() with one lr-as-argument jitted update
                          per loss kind (floats or Schedule objects),
                          per-update RNG folding for stochastic losses,
                          periodic checkpointing, mid-stage resume,
                          optional prefetching feed, metrics sinks
"""
from repro.optim.schedules import Schedule
from repro.pipeline.prefetch import PrefetchingSource
from repro.train.data import (DataSource, TrainBatch, chain,
                              distill_shard_source, epoch_source,
                              scheduled_source)
from repro.train.metrics import JsonlSink, ListSink, MetricsSink
from repro.train.state import TrainState, restack_workers
from repro.train.strategies import (BMUFShardMap, DistributedStrategy,
                                    GTCShardMap, Local, init_opt,
                                    make_sgd_step)
from repro.train.trainer import Trainer

__all__ = [
    "TrainState", "Trainer", "TrainBatch", "DataSource",
    "DistributedStrategy", "Local", "BMUFShardMap", "GTCShardMap",
    "make_sgd_step", "init_opt", "restack_workers",
    "epoch_source", "distill_shard_source", "scheduled_source", "chain",
    "PrefetchingSource", "Schedule",
    "MetricsSink", "ListSink", "JsonlSink",
]
