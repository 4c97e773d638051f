"""Record the small trace that ``test_trace.py`` reads, and print what a
trace holds.

    python benchmarks/chip/tests/record_trace.py --out <file.xplane.pb>
    python benchmarks/chip/tests/record_trace.py --dump <file.xplane.pb>

Recording runs, on every chip JAX finds, twice: a matmul, the program's
``topk_logits`` kernel and a ``psum`` over the chips, under the harness's
host spans (``bench.window`` around both calls, ``bench.compute`` around
each call, ``bench.host_wait`` around a 3 ms host sleep before, between
and after them).  The
``.xplane.pb`` is copied to ``--out``.  Dumping prints each plane's lines
and the most frequent event names on each, with the stats of one event.
"""
from __future__ import annotations

import argparse
import collections
import glob
import os
import shutil
import sys
import tempfile
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
import tiny  # noqa: E402,F401  (puts the benchmark and the program on the path)


def record(out: str):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding
    from jax.sharding import PartitionSpec as P

    from repro.serve.engine import make_topk_emitter

    devs = jax.devices()
    mesh = Mesh(np.array(devs), ("d",))
    emit = make_topk_emitter(20, "kernel")

    def body(x, w):
        vals, idx = emit(x @ w)
        return jax.lax.psum(jnp.sum(vals.astype(jnp.float32)), "d"), idx

    step = jax.jit(jax.shard_map(body, mesh=mesh, in_specs=(P("d"), P()),
                                 out_specs=(P(), P("d")), check_vma=False))
    n = len(devs)
    x = jax.device_put(
        jax.random.normal(jax.random.key(0), (256 * n, 512), jnp.float32),
        NamedSharding(mesh, P("d")))
    w = jax.device_put(
        jax.random.normal(jax.random.key(1), (512, 3183), jnp.float32),
        NamedSharding(mesh, P()))
    jax.block_until_ready(step(x, w))            # compile outside the trace
    tmp = tempfile.mkdtemp()
    jax.profiler.start_trace(tmp)
    with jax.profiler.TraceAnnotation("bench.window"):
        for i in range(3):
            with jax.profiler.TraceAnnotation("bench.host_wait"):
                time.sleep(0.003)
            if i < 2:
                with jax.profiler.TraceAnnotation("bench.compute"):
                    jax.block_until_ready(step(x, w))
    jax.profiler.stop_trace()
    src = sorted(glob.glob(os.path.join(tmp, "**", "*.xplane.pb"),
                           recursive=True))[-1]
    shutil.copyfile(src, out)
    shutil.rmtree(tmp, ignore_errors=True)
    print(f"recorded {out}: {os.path.getsize(out)} bytes, {n} "
          f"{devs[0].device_kind}")


def dump(path: str, top: int = 25):
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    for plane in pd.planes:
        lines = list(plane.lines)
        print(f"PLANE {plane.name!r}: {len(lines)} lines")
        for line in lines:
            evs = list(line.events)
            names = collections.Counter(e.name for e in evs)
            print(f"  LINE {line.name!r}: {len(evs)} events")
            for name, c in names.most_common(top):
                print(f"    {c:6d} {name[:120]}")
            if evs:
                e = evs[0]
                stats = [(k, str(v)[:80]) for k, v in e.stats]
                print(f"    first event stats: {stats[:12]}")


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    g = ap.add_mutually_exclusive_group(required=True)
    g.add_argument("--out")
    g.add_argument("--dump")
    args = ap.parse_args(argv)
    if args.out:
        record(args.out)
        dump(args.out)
    else:
        dump(args.dump)


if __name__ == "__main__":
    main()
