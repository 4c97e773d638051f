"""Computation-environment bootstrap — applied *before* the first JAX
import.

JAX locks the XLA client configuration (platform, host device count,
GPU scheduler flags) when the backend first initializes, so everything
here operates on ``os.environ`` and must run ahead of ``import jax``.
Entry points call :func:`bootstrap_from_env` as their very first
statement (see ``repro.launch.train`` / ``launch.dryrun``); tests and
CI drive :func:`bootstrap` directly in a fresh interpreter.

The three knob families, mirroring the million-hour deployment:

* **host-platform device count** — ``--xla_force_host_platform_device_count=N``
  splits one CPU into N XLA devices, so the GTC/BMUF ``shard_map``
  worker axes exercise a real >1-device mesh in CI (the paper's
  BMUF-64 / GTC-16 topologies at laptop scale);
* **GPU execution flags** — async collectives + latency-hiding
  scheduler + highest-priority async stream, the overlap flags that let
  BMUF's block sync hide behind local steps on real GPUs;
* **numerics/debug toggles** — x64, NaN debugging, client preallocation.

:func:`describe` snapshots the *resulting* environment (jax version,
backend, devices, process topology, the exact flag string) and is
logged as a startup artifact — the first thing to diff when two hosts
of a fleet disagree.
"""
from __future__ import annotations

import json
import os
import re
import sys
import warnings
from dataclasses import dataclass, field
from typing import Dict, Mapping, MutableMapping, Optional, Tuple

_HOST_DEVICES_FLAG = "--xla_force_host_platform_device_count"

# the overlap flags for multi-GPU runs (SNIPPETS #1: async collectives
# so psums overlap compute, latency-hiding scheduler to move them early)
GPU_XLA_FLAGS: Tuple[str, ...] = (
    "--xla_gpu_enable_async_collectives=true",
    "--xla_gpu_enable_latency_hiding_scheduler=true",
    "--xla_gpu_enable_highest_priority_async_stream=true",
)


@dataclass(frozen=True)
class EnvConfig:
    """What :func:`bootstrap` applies.  Zero/None means "leave alone"."""

    host_device_count: int = 0        # >0: N-device host-platform CPU mesh
    platform: str = ""                # "", "cpu", "gpu", "tpu"
    gpu_flags: bool = True            # apply GPU_XLA_FLAGS when platform=gpu
    enable_x64: Optional[bool] = None
    debug_nans: Optional[bool] = None
    preallocate: Optional[bool] = None
    extra_xla_flags: Tuple[str, ...] = field(default_factory=tuple)

    @classmethod
    def from_env(cls, environ: Optional[Mapping[str, str]] = None
                 ) -> "EnvConfig":
        """REPRO_* knobs -> EnvConfig (unset knobs stay neutral).

        REPRO_HOST_DEVICES=N, REPRO_PLATFORM=cpu|gpu|tpu,
        REPRO_X64=0|1, REPRO_DEBUG_NANS=0|1, REPRO_PREALLOCATE=0|1,
        REPRO_XLA_FLAGS="--flag=a --flag=b" (appended verbatim).
        """
        e = os.environ if environ is None else environ

        def _bool(name):
            v = e.get(name)
            return None if v is None else v.strip().lower() in (
                "1", "true", "yes", "on")

        return cls(
            host_device_count=int(e.get("REPRO_HOST_DEVICES", 0) or 0),
            platform=e.get("REPRO_PLATFORM", "").strip().lower(),
            enable_x64=_bool("REPRO_X64"),
            debug_nans=_bool("REPRO_DEBUG_NANS"),
            preallocate=_bool("REPRO_PREALLOCATE"),
            extra_xla_flags=tuple(e.get("REPRO_XLA_FLAGS", "").split()))


def _jax_already_imported() -> bool:
    return "jax" in sys.modules


def compose_xla_flags(existing: str, cfg: EnvConfig) -> str:
    """Merge cfg's managed flags into an existing XLA_FLAGS string.

    Idempotent: a managed flag already present is *replaced*, not
    duplicated, so repeated bootstraps (supervisor -> worker -> nested
    tool) converge to one spelling.  Unmanaged flags pass through in
    their original order.
    """
    managed: Dict[str, str] = {}
    if cfg.host_device_count > 0:
        managed[_HOST_DEVICES_FLAG] = (
            f"{_HOST_DEVICES_FLAG}={cfg.host_device_count}")
    gpu = GPU_XLA_FLAGS if (cfg.platform == "gpu" and cfg.gpu_flags) else ()
    for f in tuple(gpu) + tuple(cfg.extra_xla_flags):
        managed[f.split("=", 1)[0]] = f
    out = []
    for tok in existing.split():
        key = tok.split("=", 1)[0]
        if key in managed:
            out.append(managed.pop(key))      # replace in place
        else:
            out.append(tok)
    out.extend(managed.values())
    return " ".join(out)


def bootstrap(cfg: Optional[EnvConfig] = None, *,
              environ: Optional[MutableMapping[str, str]] = None,
              **kwargs) -> EnvConfig:
    """Apply cfg to the process environment.  Call before ``import jax``.

    Keyword form: ``bootstrap(host_device_count=8, platform="gpu")``.
    Returns the applied config.  If JAX is already imported the XLA
    flag changes cannot take effect — a loud warning is raised and the
    environment is still updated (children inherit it, which is exactly
    what the process-worker supervisor relies on).
    """
    if cfg is None:
        cfg = EnvConfig(**kwargs)
    elif kwargs:
        raise TypeError("pass either a config or kwargs, not both")
    e = os.environ if environ is None else environ

    wants_flags = (cfg.host_device_count > 0 or cfg.extra_xla_flags
                   or (cfg.platform == "gpu" and cfg.gpu_flags))
    if wants_flags and _jax_already_imported() and environ is None:
        warnings.warn(
            "repro.runtime.env.bootstrap: jax is already imported — "
            "XLA flag changes will NOT affect this process (only "
            "subprocesses inheriting the environment). Bootstrap "
            "before the first jax import.", RuntimeWarning, stacklevel=2)
    if wants_flags:
        e["XLA_FLAGS"] = compose_xla_flags(e.get("XLA_FLAGS", ""), cfg)
    if cfg.platform:
        e["JAX_PLATFORMS"] = cfg.platform
    if cfg.enable_x64 is not None:
        e["JAX_ENABLE_X64"] = "1" if cfg.enable_x64 else "0"
    if cfg.debug_nans is not None:
        e["JAX_DEBUG_NANS"] = "true" if cfg.debug_nans else "false"
    if cfg.preallocate is not None:
        e["XLA_PYTHON_CLIENT_PREALLOCATE"] = \
            "true" if cfg.preallocate else "false"
    return cfg


COMPILE_CACHE_VAR = "JAX_COMPILATION_CACHE_DIR"


def default_compile_cache_dir() -> str:
    """``<checkout>/.jax_cache``: one fixed path per checkout (git-ignored).
    The path is part of the cache key, so it never carries a temp name,
    pid or time."""
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__)))))
    return os.path.join(root, ".jax_cache")


def bootstrap_from_env(environ: Optional[MutableMapping[str, str]] = None
                       ) -> EnvConfig:
    """``bootstrap(EnvConfig.from_env())`` plus the persistent compile
    cache — the entry-point one-liner.

    Cache rule: a ``JAX_COMPILATION_CACHE_DIR`` already in the
    environment is used as it is and no other directory is set;
    otherwise the cache goes to :func:`default_compile_cache_dir`.  JAX
    reads the variable when it is imported, so this too must run first.
    """
    e = os.environ if environ is None else environ
    cfg = bootstrap(EnvConfig.from_env(e), environ=environ)
    e.setdefault(COMPILE_CACHE_VAR, default_compile_cache_dir())
    return cfg


def forced_host_device_count(
        environ: Optional[Mapping[str, str]] = None) -> int:
    """The host-platform device count the current XLA_FLAGS forces
    (0 when unforced) — readable without importing jax."""
    e = os.environ if environ is None else environ
    m = re.search(_HOST_DEVICES_FLAG + r"=(\d+)", e.get("XLA_FLAGS", ""))
    return int(m.group(1)) if m else 0


# ------------------------------------------------------------- describe

def describe() -> dict:
    """Snapshot the effective runtime environment (imports jax).

    Everything a fleet debugger wants in one JSON-serializable dict:
    versions, backend, device inventory, process topology, the exact
    flag strings, and the REPRO_*/JAX_* env vars that produced them.
    """
    import platform as _platform

    import jax

    devices = jax.devices()
    try:
        proc_idx, proc_cnt = jax.process_index(), jax.process_count()
    except Exception:                       # uninitializable backend
        proc_idx, proc_cnt = 0, 1
    return {
        "jax_version": jax.__version__,
        "backend": jax.default_backend(),
        "device_count": len(devices),
        "local_device_count": jax.local_device_count(),
        "devices": [str(d) for d in devices],
        "process_index": proc_idx,
        "process_count": proc_cnt,
        "forced_host_devices": forced_host_device_count(),
        "xla_flags": os.environ.get("XLA_FLAGS", ""),
        "x64": bool(jax.config.jax_enable_x64),
        "debug_nans": bool(jax.config.jax_debug_nans),
        "env": {k: v for k, v in sorted(os.environ.items())
                if k.startswith(("REPRO_", "JAX_", "XLA_"))},
        "python": sys.version.split()[0],
        "hostname": _platform.node(),
        "pid": os.getpid(),
    }


def save_describe(path: str) -> dict:
    """Write the :func:`describe` snapshot to `path` (the startup
    artifact tier-2 CI uploads); returns the snapshot."""
    snap = describe()
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    with open(path, "w") as f:
        json.dump(snap, f, indent=1)
    return snap


def main(argv=None):
    import argparse
    ap = argparse.ArgumentParser(
        description="bootstrap the env, then print/save describe()")
    ap.add_argument("--host-devices", type=int, default=0)
    ap.add_argument("--platform", default="")
    ap.add_argument("--x64", action="store_true")
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)
    bootstrap(host_device_count=args.host_devices, platform=args.platform,
              enable_x64=True if args.x64 else None)
    snap = save_describe(args.out) if args.out else describe()
    print(json.dumps(snap, indent=1))


if __name__ == "__main__":
    main()
