"""The run of one cell: device check, set-up, the measured window, the
optional trace, the comparison with the reference, and the result line."""
from __future__ import annotations

import contextlib
import json
import math
import os
import shutil
import sys
import time
from dataclasses import dataclass, field
from typing import Dict, Optional

from bench import loader

BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class NoChip(RuntimeError):
    pass


@dataclass
class Run:
    """What a driver sees: the cell, the seed, where to put scratch files,
    the devices it may use, and the harness's host spans."""
    cell: loader.Cell
    seed: int
    seconds: float
    trace: bool
    out_dir: str
    devices: list
    device_kind: str
    extra: Dict = field(default_factory=dict)

    def span(self, name: str):
        """A host span on the profiler's clock (only while tracing)."""
        if not self.trace:
            return contextlib.nullcontext()
        import jax
        return jax.profiler.TraceAnnotation("bench." + name)


def log(msg: str):
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def devices_for(cell, *, allow_cpu: bool = False):
    """The first ``cell.chips`` devices; a run off a TPU or with too few
    chips raises NoChip."""
    import jax
    try:
        devs = jax.devices()
    except RuntimeError as e:
        raise NoChip(f"JAX found no device: {e}")
    if devs[0].platform != "tpu" and not allow_cpu:
        raise NoChip(f"no TPU: JAX platform is {devs[0].platform!r}")
    if len(devs) < cell.chips:
        raise NoChip(f"cell {cell.name} needs {cell.chips} chips, JAX "
                     f"has {len(devs)}")
    return devs[:cell.chips]


def memory_peak_bytes(devs) -> Optional[int]:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in devs]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


class CompileCounter:
    """Counts executables built or loaded (compile or cache hit) while
    ``on`` is set."""

    def __init__(self):
        import jax
        self.on = False
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._hear)

    def _hear(self, event, duration, **_):
        if self.on and event == BACKEND_COMPILE_EVENT:
            self.n += 1


def configure_cache():
    """Keep every program in the persistent cache, however quick to
    compile, so that only a checkout's first run compiles."""
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def trace_options():
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    return opts


def execute(run: Run, driver_mod, *, t_start: float) -> dict:
    """Set-up, window, trace reduction, reference check -> result dict."""
    import jax
    cell = run.cell
    drv = driver_mod.Driver(run)
    drv.setup()
    counter = CompileCounter()
    setup_s = time.perf_counter() - t_start
    log(f"set-up {setup_s:.3f} s")

    trace_dir = os.path.join(run.out_dir, "trace")
    if run.trace:
        jax.profiler.start_trace(trace_dir, profiler_options=trace_options())
    counter.on = True
    with run.span("window"):
        res = drv.window(run.seconds)
    counter.on = False
    if run.trace:
        jax.profiler.stop_trace()
    if hasattr(drv, "finish"):
        res = drv.finish(res)
    log(f"compilations inside the window: {counter.n}")
    run.extra["compiles_in_window"] = counter.n
    peak = memory_peak_bytes(run.devices)
    drv.release()

    dev = run.devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": peak}
    out = {"attempted": res["attempted"], "failed": res["failed"]}
    if run.trace:
        from bench.trace import Trace, find_xplane
        tr = Trace(find_xplane(trace_dir))
        device["busy_s"] = tr.busy_s()
        device["window_s"] = tr.window_s()
        metrics = {}
        for m in cell.per_layer:
            val = cell.metric_reader(m["name"]).read(run, res, tr)
            if val is not None:
                metrics[m["name"]] = {"value": val, "unit": m["unit"]}
        out["breakdown"] = {"device_ops": [[n, s] for n, s in tr.top_ops()],
                            "idle_gaps": [[n, s] for n, s in
                                          tr.idle_by_span()]}
        shutil.rmtree(trace_dir, ignore_errors=True)
    else:
        e2e = dict(res["e2e"], setup_s=setup_s)
        metrics = {m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
                   for m in cell.end_to_end}
    for k, v in res.get("context", {}).items():
        log(f"{k}: {v}")

    t_check = time.perf_counter()
    readings = drv.check()
    log(f"comparison with the reference: {time.perf_counter() - t_check:.3f} s")
    checks = {}
    for name, val in readings.items():
        if name not in cell.limits:
            raise KeyError(f"no limit for {name!r} in limits/{cell.name}")
        checks[name] = {"value": val, "limit": cell.limits[name]}
    correct = (res["failed"] == 0 and bool(checks)
               and all(c["value"] <= c["limit"] for c in checks.values()))
    result = {"correct": correct, **out, "metrics": metrics,
              "device": device}
    if "breakdown" in out:
        result["breakdown"] = result.pop("breakdown")
    result["checks"] = checks
    return finite(result)


def finite(x):
    """The result with every non-finite number replaced by None (JSON has
    no infinity; such a run is not correct or has failed requests)."""
    if isinstance(x, dict):
        return {k: finite(v) for k, v in x.items()}
    if isinstance(x, list):
        return [finite(v) for v in x]
    if isinstance(x, float) and not math.isfinite(x):
        return None
    return x


def run(workload: str, seed: int, seconds: float, trace: bool, *,
        t_start: float, allow_cpu: bool = False) -> int:
    cell = loader.resolve(workload)
    try:
        devs = devices_for(cell, allow_cpu=allow_cpu)
    except NoChip as e:
        print(f"run.py: {e}", file=sys.stderr)
        return 3
    configure_cache()
    out_dir = os.path.join(loader.ROOT, ".bench_out", workload)
    shutil.rmtree(out_dir, ignore_errors=True)
    os.makedirs(out_dir)
    r = Run(cell=cell, seed=seed, seconds=seconds, trace=trace,
            out_dir=out_dir, devices=devs, device_kind=devs[0].device_kind)
    try:
        result = execute(r, cell.driver(), t_start=t_start)
    finally:
        shutil.rmtree(out_dir, ignore_errors=True)
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0
