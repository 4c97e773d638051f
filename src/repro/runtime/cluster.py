"""``jax.distributed`` launch paths and topology-aware mesh builders.

The paper's two trainers are defined by their topology — BMUF across 64
GPUs, GTC sequence training across 16 — and this module is where that
topology becomes a concrete ``jax.distributed`` launch plus a mesh:

* :class:`ClusterConfig` carries (coordinator address, process count,
  process id), resolved from ``REPRO_COORDINATOR`` /
  ``REPRO_NUM_PROCESSES`` / ``REPRO_PROCESS_ID`` env vars (falling back
  to the ``JAX_*`` spellings) or from a ``--cluster host:port,N,i``
  flag (``--cluster env`` reads the env vars).
* :func:`initialize` calls ``jax.distributed.initialize`` exactly once
  for multi-process configs and **degrades to a no-op for
  single-process runs** — every existing example/test runs unchanged,
  and the same entry point serves one laptop or a 64-host fleet.
* :func:`worker_mesh` builds the 1-D ``("data",)`` worker-axis mesh the
  GTCShardMap/BMUFShardMap strategies shard over: the widest axis the
  worker count divides onto the *global* device set (``jax.devices()``
  spans processes after ``initialize``), so W=16 on 16 GPUs is one
  worker per device, W=2 in 8-device CI spans 2 devices, and W=anything
  on one CPU degenerates to today's 1-device mesh with every worker
  vmap-carried — the same math either way, pinned bitwise in tests.
"""
from __future__ import annotations

import os
from dataclasses import dataclass
from typing import Mapping, Optional


@dataclass(frozen=True)
class ClusterConfig:
    """One process's view of the fleet.  num_processes<=1 means
    single-process: :func:`initialize` is then a no-op."""

    coordinator_address: str = ""     # "host:port" of process 0
    num_processes: int = 1
    process_id: int = 0

    @classmethod
    def from_env(cls, environ: Optional[Mapping[str, str]] = None
                 ) -> "ClusterConfig":
        e = os.environ if environ is None else environ

        def get(*names, default=""):
            for n in names:
                if e.get(n):
                    return e[n]
            return default

        return cls(
            coordinator_address=get("REPRO_COORDINATOR",
                                    "JAX_COORDINATOR_ADDRESS"),
            num_processes=int(get("REPRO_NUM_PROCESSES",
                                  "JAX_NUM_PROCESSES", default="1")),
            process_id=int(get("REPRO_PROCESS_ID", "JAX_PROCESS_ID",
                               default="0")))

    @classmethod
    def from_spec(cls, spec: str,
                  environ: Optional[Mapping[str, str]] = None
                  ) -> "ClusterConfig":
        """Parse a ``--cluster`` flag value.

        ``"env"`` -> :meth:`from_env`;
        ``"host:port,N,i"`` -> explicit coordinator, fleet size, rank.
        """
        if spec.strip().lower() in ("", "env"):
            return cls.from_env(environ)
        parts = [p.strip() for p in spec.split(",")]
        if len(parts) != 3:
            raise ValueError(
                f"--cluster spec {spec!r}: want 'host:port,num_procs,"
                f"process_id' or 'env'")
        return cls(coordinator_address=parts[0],
                   num_processes=int(parts[1]), process_id=int(parts[2]))

    def validate(self):
        if self.num_processes > 1:
            if not self.coordinator_address:
                raise ValueError(
                    "multi-process cluster needs a coordinator address")
            if not 0 <= self.process_id < self.num_processes:
                raise ValueError(
                    f"process_id {self.process_id} outside "
                    f"[0, {self.num_processes})")


@dataclass(frozen=True)
class ClusterInfo:
    """What :func:`initialize` actually did."""

    initialized: bool                 # did jax.distributed.initialize run
    process_index: int
    process_count: int

    @property
    def is_coordinator(self) -> bool:
        return self.process_index == 0


_ACTIVE: Optional[ClusterInfo] = None


def initialize(cfg: Optional[ClusterConfig] = None) -> ClusterInfo:
    """Bring this process into the fleet (idempotent).

    Single-process configs (the default, and every existing test /
    example) return a no-op ClusterInfo without touching
    ``jax.distributed`` at all.  Multi-process configs run
    ``jax.distributed.initialize`` once; a second call returns the
    recorded info instead of re-initializing (jax raises on double
    init — a supervisor retrying a launcher must not trip that).
    """
    global _ACTIVE
    if _ACTIVE is not None:
        return _ACTIVE
    cfg = cfg or ClusterConfig.from_env()
    cfg.validate()
    if cfg.num_processes <= 1:
        _ACTIVE = ClusterInfo(initialized=False, process_index=0,
                              process_count=1)
        return _ACTIVE
    import jax
    jax.distributed.initialize(
        coordinator_address=cfg.coordinator_address,
        num_processes=cfg.num_processes,
        process_id=cfg.process_id)
    _ACTIVE = ClusterInfo(initialized=True,
                          process_index=jax.process_index(),
                          process_count=jax.process_count())
    return _ACTIVE


def active() -> Optional[ClusterInfo]:
    """The ClusterInfo of a prior :func:`initialize`, or None."""
    return _ACTIVE


def _reset_for_tests():
    global _ACTIVE
    _ACTIVE = None


# ----------------------------------------------------------------- meshes

def widest_divisor(n_workers: int, n_devices: int) -> int:
    """The largest device count <= n_devices that divides n_workers —
    the worker axis size :func:`worker_mesh` uses."""
    if n_workers < 1:
        raise ValueError(f"n_workers must be >= 1, got {n_workers}")
    return max(d for d in range(1, min(n_workers, max(n_devices, 1)) + 1)
               if n_workers % d == 0)


def auto_mesh(axis_shapes, axis_names, *, devices=None):
    """``jax.make_mesh`` with every axis ``AxisType.Auto`` — the one mesh
    rule of this repo.  The installed JAX defaults ``make_mesh`` to
    ``Explicit`` axes, under which the strategies' scatter-adds and
    sharding constraints raise ``ShardingTypeError``; every mesh here is
    built through this function instead."""
    import jax
    from jax.sharding import AxisType
    return jax.make_mesh(tuple(axis_shapes), tuple(axis_names),
                         axis_types=(AxisType.Auto,) * len(axis_names),
                         devices=devices)


def worker_mesh(n_workers: int, *, axis: str = "data"):
    """The worker-axis mesh for a W-worker shard_map strategy.

    Axis size = the widest divisor of W the global device set admits:
    each device then carries W/size unrolled workers (all of them on 1
    device at laptop scale; one each on the paper's 16-GPU shape).  The
    strategies' batch stacking requires W divisible by the axis size —
    this builder guarantees it by construction for any device count.
    """
    import jax
    n = widest_divisor(n_workers, len(jax.devices()))
    return auto_mesh((n,), (axis,))


# The paper's deployment shapes (§3.4-3.5): name -> worker count.  The
# names are CLI/StrEnum-ish on purpose — `--topology bmuf-64` in a
# launcher maps straight through topology_mesh.
PAPER_TOPOLOGIES = {
    "bmuf-64": 64,       # SSL CE stage: BMUF across 64 GPUs
    "gtc-16": 16,        # sMBR sequence training: GTC across 16 GPUs
}


def topology_mesh(name: str, *, axis: str = "data"):
    """worker_mesh for a named paper topology (``bmuf-64``/``gtc-16``)."""
    if name not in PAPER_TOPOLOGIES:
        raise KeyError(f"unknown topology {name!r}; "
                       f"have {sorted(PAPER_TOPOLOGIES)}")
    return worker_mesh(PAPER_TOPOLOGIES[name], axis=axis)
