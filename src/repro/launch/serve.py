"""Online serving entrypoint: both session types of the slot core.

All serving machinery lives in ``repro.serve`` — this module is the
CLI.  Token LMs go through ``serve.TokenServer``, streaming-capable
AMs through ``serve.StreamServer``: both are session types over the
same slot-based core (``serve.slots.SlotServer`` — mid-flight
admission, one host sync per window, SLO tiers).  Bidirectional AMs
have no streaming form and use ``StreamingEngine``'s batched path.

  PYTHONPATH=src python -m repro.launch.serve --arch qwen2.5-3b
  PYTHONPATH=src python -m repro.launch.serve --arch lstm-am-7khr --full

``--full`` serves the published config unreduced (5x768 / 3,183
senones for ``lstm-am-7khr``); the default is the ``reduced()`` smoke
size.  On a TPU the AM's top-k emission runs the Pallas kernel.
"""
from __future__ import annotations

from repro.runtime.env import bootstrap_from_env
bootstrap_from_env()
# ^ REPRO_* knobs and the compile cache must land in os.environ before
# the first jax import locks the XLA client config.

import argparse
import time

import jax
import numpy as np

from repro.configs import get_arch, reduced
from repro.models import build_model
from repro.models.api import supports_streaming
from repro.serve import (LATENCY, SLO_DEFAULT, BatchPolicy,
                         StreamingEngine, StreamServer, TokenServer)


def serve_tokens(cfg, params, *, n_requests: int = 6, max_new: int = 8,
                 policy: BatchPolicy = LATENCY, seed: int = 0):
    srv = TokenServer(cfg, params, policy=policy, max_seq=128)
    rng = np.random.default_rng(seed)
    rids = [srv.submit(rng.integers(1, cfg.vocab_size, rng.integers(3, 10)),
                       max_new=max_new) for _ in range(n_requests)]
    t0 = time.time()
    done = srv.drain()
    dt = time.time() - t0
    total = sum(len(done[r].out) for r in rids)
    st = srv.stats
    print(f"[serve] {n_requests} requests, {total} tokens "
          f"in {dt:.2f}s ({total / dt:.1f} tok/s; {st['syncs']} host "
          f"syncs over {st['steps']} steps, slot occupancy "
          f"{st['active_slot_steps'] / max(st['slot_steps'], 1):.0%})")
    for r in rids:
        print(f"  req {r}: {done[r].out}")
    return done


def serve_batch(cfg, params, *, n_requests: int = 6,
                policy: BatchPolicy = LATENCY, seed: int = 0):
    """Batched full-utterance AM serving — the path for bidirectional
    models, which have no streaming form."""
    eng = StreamingEngine(cfg, params, k=10, policy=policy)
    rng = np.random.default_rng(seed)
    rids = [eng.submit(rng.normal(size=(int(rng.integers(24, 96)),
                                        cfg.feat_dim)).astype(np.float32))
            for _ in range(n_requests)]
    t0 = time.time()
    res = eng.run()
    dt = time.time() - t0
    frames = sum(res[r].vals.shape[0] for r in rids)
    print(f"[serve] {n_requests} utterances, {frames} frames batched "
          f"in {dt:.2f}s ({frames / dt:.0f} frames/s)")
    return res


def serve_stream(cfg, params, *, n_streams: int = 3, chunk: int = 16,
                 seed: int = 0):
    """Streaming AM serving on the slot core: long firehose streams
    plus interactive arrivals under SLO tiers, top-k senone posteriors
    per frame, one host sync per window."""
    srv = StreamServer(cfg, params, n_slots=n_streams, chunk_frames=chunk,
                       k=10, tiers=SLO_DEFAULT)
    rng = np.random.default_rng(seed)
    fire = [rng.normal(size=(int(rng.integers(8, 14)) * chunk,
                             cfg.feat_dim)).astype(np.float32)
            for _ in range(n_streams)]
    inter = [rng.normal(size=(chunk, cfg.feat_dim)).astype(np.float32)
             for _ in range(2)]
    t0 = time.time()
    rids = [srv.submit(u, tier="firehose") for u in fire]
    done = srv.pump()                  # firehose saturates the slots ...
    rids += [srv.submit(u, tier="interactive") for u in inter]
    done.update(srv.drain())           # ... interactive preempts it
    dt = time.time() - t0
    frames = sum(u.shape[0] for u in fire + inter)
    st = srv.stats
    print(f"[serve] {len(rids)} streams ({len(inter)} interactive), "
          f"{frames} frames in {dt:.2f}s ({frames / dt:.0f} frames/s; "
          f"{st['syncs']} host syncs over {st['steps']} steps, "
          f"{st['parked']} parks, utilization {srv.utilization():.0%})")
    for r in rids:
        v, _ = done[r].emissions()
        print(f"  stream {r} ({done[r].tier or 'default'}): "
              f"{v.shape[0]} emissions, finished sync "
              f"{done[r].finished_sync}")
    return done


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2.5-3b")
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--max-new", type=int, default=8)
    ap.add_argument("--full", action="store_true",
                    help="serve the published config unreduced")
    args = ap.parse_args(argv)

    cfg = get_arch(args.arch)
    if not args.full:
        cfg = reduced(cfg)
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    if cfg.family == "lstm_am":
        if supports_streaming(cfg):
            serve_stream(cfg, params, n_streams=args.requests)
        else:                       # bidirectional: batch path only
            serve_batch(cfg, params, n_requests=args.requests)
    else:
        serve_tokens(cfg, params, n_requests=args.requests,
                     max_new=args.max_new)


if __name__ == "__main__":
    main()
