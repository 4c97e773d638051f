"""The one traffic generator: utterance lengths, chunked batches and
synthetic teacher targets, all from parameters in a traffic file and a
seed.

Utterance lengths are drawn once from the traffic file's fixed
``size_seed``, so every run seed gets the same work;
the run seed only orders it and draws the feature values.  Chunking
mirrors ``repro.data.chunking.chunk_utterances``: each utterance is cut
into ``chunk_frames`` chunks, the last one zero-padded and masked, and the
chunks are shuffled.
"""
from __future__ import annotations

import numpy as np


def rng(seed: int, *stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed) & (2 ** 64 - 1), *stream])


def draw_lengths(spec: dict, n: int) -> np.ndarray:
    """n utterance lengths in frames: lognormal with mean ``mean_frames``
    and shape ``sigma``, clipped to [min_frames, max_frames], from the
    fixed ``size_seed``."""
    r = rng(spec["size_seed"], 1)
    median = spec["mean_frames"] * np.exp(-spec["sigma"] ** 2 / 2)
    x = median * np.exp(spec["sigma"] * r.standard_normal(n))
    return np.clip(np.round(x), spec["min_frames"],
                   spec["max_frames"]).astype(np.int64)


def chunk_lengths(spec: dict, n_chunks: int, chunk: int) -> np.ndarray:
    """Valid frames of exactly ``n_chunks`` chunks cut from a fixed run of
    utterances (the last utterance is cut short to fit)."""
    out, i = [], 0
    lens = draw_lengths(spec, 4 * n_chunks)
    while len(out) < n_chunks:
        t = int(lens[i])
        i += 1
        out += [chunk] * (t // chunk) + ([t % chunk] if t % chunk else [])
    return np.asarray(out[:n_chunks], np.int64)


def chunk_pool(spec: dict, seed: int, *, batch_chunks: int, chunk_frames: int,
               n_batches: int, feat_dim: int):
    """-> list of {'feats' (B,L,F) f32, 'mask' (B,L) f32} batches: the
    fixed multiset of chunks, shuffled by the seed, features N(0, 1) from
    the seed (as after mean/variance normalization), zero past each
    chunk's valid frames."""
    n = batch_chunks * n_batches
    valid = chunk_lengths(spec["utterances"], n, chunk_frames)
    r = rng(seed, 2)
    valid = valid[r.permutation(n)]
    mask = (np.arange(chunk_frames)[None, :] < valid[:, None]).astype(
        np.float32)
    feats = r.standard_normal((n, chunk_frames, feat_dim), np.float32)
    feats *= mask[..., None]
    return [{"feats": feats[b * batch_chunks:(b + 1) * batch_chunks],
             "mask": mask[b * batch_chunks:(b + 1) * batch_chunks]}
            for b in range(n_batches)]


def topk_targets(seed: int, shape, k: int, vocab: int):
    """Synthetic teacher targets for a (B, L) batch: k distinct senone ids
    per frame (an arithmetic progression mod vocab with a step coprime to
    it) and descending max-shifted values, as the store holds them."""
    r = rng(seed, 3)
    base = r.integers(0, vocab, shape + (1,))
    steps = np.array([s for s in range(1, 64) if np.gcd(s, vocab) == 1])
    step = steps[r.integers(0, len(steps), shape + (1,))]
    idx = (base + step * np.arange(k)) % vocab
    vals = -np.cumsum(r.exponential(0.5, shape + (k,)), axis=-1)
    vals -= vals[..., :1]
    return vals.astype(np.float16), idx.astype(np.int32)
