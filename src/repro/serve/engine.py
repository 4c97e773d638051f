"""Batched streaming-inference engine.

One engine, two consumers (the paper's framing: target generation *is*
inference-as-a-service):

  * **Teacher target generation** (paper §3.2.2): submit the unlabeled
    firehose as per-utterance requests; the batcher buckets them into
    padded batches (THROUGHPUT policy), one jitted forward per bucket
    shape emits top-k logits, and the caller drains results into the
    LogitStore.  Embarrassingly parallel across engine instances — the
    paper's "parallelize target generation".
  * **Online serving**: the same engine under a LATENCY policy, plus a
    slot-based *streaming* path that carries each stream's LSTM (h, c)
    across chunks, so audio can be fed incrementally with batched compute
    across concurrent streams.  ``feed_async``/``feed_pipelined``
    double-buffer the host→device transfer: the next chunk is staged
    while the current jitted step computes (the serve-side analogue of
    the training feed's ``pipeline.PrefetchingSource``).

Length correctness is delegated to the model's ``lens`` support
(``models/recurrent.py``): padded rows freeze their recurrent state at
their true length, and the biLSTM's backward direction, a reverse scan
whose state holds at zero through each row's padded tail, starts at the
last valid frame, so batched == sequential to fp tolerance (pinned by
tests/test_serve_engine.py).

Top-k emission reuses ``kernels/topk_logits`` (the Pallas selection
kernel) when ``topk_impl="kernel"``; "lax" is the
``logit_store.topk_compress`` codec (same output format — shifted bf16
values + int32 indices).  The default ``None`` follows
``kernels._dispatch``: the kernel on TPU, the codec elsewhere.
"""
from __future__ import annotations

from collections import deque
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import logit_store as ls
from repro.kernels import _dispatch
from repro.kernels.topk_logits import topk_logits
from repro.launch import steps
from repro.models import build_model
from repro.models.api import stream_feat_dim, supports_streaming
from repro.serve.batcher import (LATENCY, THROUGHPUT, BatchPolicy,
                                 bucket_length, form_batches)
from repro.serve.request import CompletedRequest, RequestQueue


def make_topk_emitter(k: int, impl: Optional[str] = None, *,
                      interpret: Optional[bool] = None):
    """logits (..., V) -> (vals (..., k) bf16 shifted, idx (..., k) i32).

    impl="kernel" routes selection through the Pallas tile kernel
    (``kernels/topk_logits``); "lax" uses the logit-store codec.  Both
    produce the LogitStore wire format (max logit shifted to 0, bf16).
    ``impl=None`` and ``interpret=None`` auto-detect via
    ``kernels._dispatch``: the compiled kernel on TPU, the codec
    elsewhere (an explicit "kernel" off TPU runs the interpreter).
    """
    if impl is None:
        impl = "kernel" if _dispatch.auto_use_kernel() else "lax"
    interpret = _dispatch.auto_interpret(interpret)
    if impl == "kernel":
        def emit(logits):
            vals, idx = topk_logits(logits, k, interpret=interpret)
            vals = vals - vals[..., :1]
            return vals.astype(jnp.bfloat16), idx
        return emit
    if impl != "lax":
        raise ValueError(f"unknown topk impl {impl!r}")
    return lambda logits: ls.topk_compress(logits, k)


# float32 logits of one emitter chunk: a batch whose (rows x vocab) logits
# exceed this runs the head matmul and top-k over row chunks, so the whole
# batch's logits never exist at once (a 32,768 x 102,400 LM batch is 13.4
# GB); the AM teacher's 65,536 x 3,183 (0.83 GB) stays one chunk
LOGIT_CHUNK_BYTES = 1 << 30


def logit_chunks(rows: int, vocab: int) -> int:
    """The fewest row chunks, each an equal share of ``rows``, whose float32
    logits fit ``LOGIT_CHUNK_BYTES`` (1 when the batch fits)."""
    for n in range(1, rows + 1):
        if rows % n == 0 and (rows // n) * vocab * 4 <= LOGIT_CHUNK_BYTES:
            return n
    return rows


def cast_params(params, dtype: str):
    """Floating parameters in the model's released dtype (a no-op for
    float32 models)."""
    dt = jnp.dtype(dtype)
    return jax.tree_util.tree_map(
        lambda a: a.astype(dt) if jnp.issubdtype(a.dtype, jnp.floating)
        and a.dtype != dt else a, params)


class TopK(tuple):
    """One batch's emitted targets, the pair ``(vals, idx)``, carrying
    ``expert_counts``: the (n_moe_layers, E) expert token counts of a model
    with experts, else None (never fetched here: they stay where the
    forward left them)."""

    def __new__(cls, vals, idx, expert_counts=None):
        out = super().__new__(cls, (vals, idx))
        out.expert_counts = expert_counts
        return out


class StreamFeed:
    """Handle for a dispatched streaming step: holds the (still
    device-resident) padded outputs plus the chunk map needed to unpad.
    ``result()`` is the step's only host sync and is idempotent."""

    def __init__(self, vals, idx, chunk_lens: Dict[int, int]):
        self._vals, self._idx = vals, idx
        self._chunk_lens = chunk_lens
        self._out: Optional[dict] = None
        self._done = not chunk_lens

    def result(self) -> Dict[int, tuple]:
        """{sid: (vals (t, k), idx (t, k))} — blocks until the step's
        outputs are on host."""
        if self._done:
            return self._out or {}
        vals = np.asarray(jax.device_get(self._vals).astype(jnp.float32))
        idx = np.asarray(jax.device_get(self._idx))
        # copies, not views: accumulating consumers must not pin the
        # whole padded slot batch per chunk (same invariant as run())
        self._out = {sid: (vals[sid, :t].copy(), idx[sid, :t].copy())
                     for sid, t in self._chunk_lens.items()}
        self._vals = self._idx = None        # release the device refs
        self._done = True
        return self._out


class StreamingEngine:
    """Batched inference over an acoustic model with top-k emission.

    Batch path: ``submit()`` feature utterances, ``run()`` drains the
    queue through the policy's batcher.  Streaming path: ``open_stream``/
    ``feed``/``close_stream`` carry per-stream recurrent state across
    chunks (causal models only).
    """

    def __init__(self, cfg, params, *, k: int = 20, temperature: float = 1.0,
                 policy: BatchPolicy = THROUGHPUT, n_slots: int = 4,
                 topk_impl: Optional[str] = None,
                 interpret: Optional[bool] = None):
        self.cfg = cfg
        self.model = build_model(cfg)
        self.params = cast_params(params, cfg.param_dtype)
        self.k = k
        self.temperature = temperature
        self.policy = policy
        self.queue = RequestQueue()
        self._emit = make_topk_emitter(k, topk_impl, interpret=interpret)
        self._fwd = jax.jit(self._batch_forward)
        self._fwd_dict = jax.jit(self._dict_forward)
        # ---- streaming slots
        self.n_slots = n_slots
        self._stream_state = None
        self._slot_free = list(range(n_slots))
        self._stream_fwd = jax.jit(self._stream_forward)

    # ------------------------------------------------------------ forwards

    def _batch_forward(self, params, feats, lens):
        h, _ = self.model.apply(params, feats, lens=lens)
        return self._emit(self.model.unembed(params, h) / self.temperature)

    def _dict_forward(self, params, batch):
        """Family-generic forward for pre-formed dict batches, the teacher
        path: the AMs (``feats``, lens-aware padding), token LMs
        (``tokens``: dense, MoE, MLA, hybrid, recurrent) and enc-dec
        (``tokens`` + ``enc_embeds``) through the train path's dispatch.
        -> (vals, idx, the per-layer expert token counts (n_moe_layers, E)
        of a model with experts, else None)."""
        if self.cfg.family == "lstm_am":
            lens = batch.get("lens")
            if lens is None and "mask" in batch:
                # chunked pipeline batches carry a frame mask, not lens —
                # without this the biLSTM backward pass would read the
                # zero padding of partial chunks
                lens = batch["mask"].sum(axis=-1).astype(jnp.int32)
            h, aux = self.model.apply(params, batch["feats"], lens=lens)
        else:
            h, aux = steps.model_forward(self.model, self.cfg, params, batch)
        out = self._emit_rows(params, h)
        counts = [aux[k_] for k_ in sorted(aux)
                  if k_.endswith("expert_counts")]
        return out + (jnp.concatenate(counts) if counts else None,)

    def _emit_rows(self, params, h):
        """Head + top-k over h (..., D), in ``logit_chunks`` row chunks."""
        lead = h.shape[:-1]
        rows = int(np.prod(lead))
        n = logit_chunks(rows, self.cfg.vocab_size)
        emit = lambda x: self._emit(  # noqa: E731
            self.model.unembed(params, x) / self.temperature)
        if n == 1:
            return emit(h)
        vals, idx = jax.lax.map(emit, h.reshape(n, rows // n, h.shape[-1]))
        return (vals.reshape(lead + vals.shape[-1:]),
                idx.reshape(lead + idx.shape[-1:]))

    def _stream_forward(self, params, state, feats, lens):
        h, new_state = self.model.stream_step(params, state, feats,
                                              lens=lens)
        vals, idx = self._emit(self.model.unembed(params, h)
                               / self.temperature)
        return vals, idx, new_state

    # ---------------------------------------------------------- batch path

    def forward_topk(self, batch: dict):
        """One pre-formed batch -> a ``TopK``: (vals, idx), with the
        per-layer expert token counts as its ``expert_counts``.  No queue,
        no padding bookkeeping — the thinnest engine surface."""
        return TopK(*self._fwd_dict(self.params, batch))

    def submit(self, feats: np.ndarray, meta: Optional[dict] = None) -> int:
        """Enqueue one (T, F) utterance; returns its request id.

        Shape is validated here, at the API boundary: a malformed
        request failing later inside run() would strand the valid
        requests batched alongside it.
        """
        if self.cfg.family != "lstm_am":
            raise ValueError(
                "the queued feature path is the acoustic-model surface; "
                "use forward_topk (dict batches) or TokenServer")
        feats = np.asarray(feats)
        if feats.ndim != 2 or feats.shape[1] != self.cfg.feat_dim:
            raise ValueError(
                f"expected (T, {self.cfg.feat_dim}) features, got "
                f"{feats.shape}")
        return self.queue.submit(feats, meta)

    def run(self, on_batch=None) -> Dict[int, CompletedRequest]:
        """Drain the queue: bucket, batch, forward, unpad, complete.

        Returns the results completed by *this* call, keyed by rid, and
        evicts them from the queue's ledger — the engine's memory must
        not grow with uptime, so results live with the caller.  One XLA
        program per distinct bucket length.  ``on_batch`` (FormedBatch ->
        None), if given, fires after each batch completes — load
        generators use it for per-request latency accounting.
        """
        reqs = self.queue.pop_pending()
        try:
            for fb in form_batches(reqs, self.policy):
                vals, idx = self._fwd(self.params, jnp.asarray(fb.feats),
                                      jnp.asarray(fb.lens))
                vals = np.asarray(jax.device_get(vals).astype(jnp.float32))
                idx = np.asarray(jax.device_get(idx))
                for i, r in enumerate(fb.requests):
                    # copy: a slice view would pin the whole padded batch
                    # array in the results ledger for its lifetime
                    self.queue.complete(r.rid, (vals[i, :r.length].copy(),
                                                idx[i, :r.length].copy()))
                if on_batch is not None:
                    on_batch(fb)
        except BaseException:
            # a failed forward must not strand its sibling requests:
            # everything unfulfilled goes back to pending for retry
            self.queue.restore_in_flight()
            raise
        return self.queue.pop_completed()

    # ------------------------------------------------------ streaming path

    def _ensure_stream_state(self):
        if self._stream_state is None:
            self._stream_state = self.model.init_stream_state(self.n_slots)

    def open_stream(self) -> int:
        """Claim a slot with fresh recurrent state; returns stream id."""
        if not supports_streaming(self.cfg):
            raise ValueError("model has no streaming form (bidirectional)")
        if not self._slot_free:
            raise RuntimeError("all stream slots busy")
        self._ensure_stream_state()
        sid = self._slot_free.pop(0)
        self._stream_state = jax.tree_util.tree_map(
            lambda a: a.at[sid].set(0), self._stream_state)
        return sid

    def close_stream(self, sid: int):
        if not 0 <= sid < self.n_slots or sid in self._slot_free:
            raise ValueError(f"stream {sid} is not open")
        self._slot_free.append(sid)
        self._slot_free.sort()

    def feed_async(self, chunks: Dict[int, np.ndarray]) -> "StreamFeed":
        """Stage and dispatch one batched streaming step without waiting
        for its results.

        The H2D transfer (``jax.device_put``) and the jitted step are
        both async, so a caller that dispatches chunk *n+1* before
        collecting chunk *n*'s results (``StreamFeed.result()``)
        overlaps next-chunk host-side staging with the current step's
        device compute — host↔device double buffering, the serve-side
        analogue of the training feed's ``pipeline.PrefetchingSource``.
        ``feed_pipelined`` is the packaged driver.

        A zero-frame ``(0, F)`` chunk is refused: it would write
        ``lens[sid] = 0`` and silently waste a batched step.  An empty
        ``chunks`` dict (e.g. every stream closed) is an explicit no-op
        — no step is dispatched.
        """
        if not chunks:
            return StreamFeed(None, None, {})
        chunks = {sid: np.asarray(c) for sid, c in chunks.items()}
        fd = stream_feat_dim(self.cfg)
        for sid, c in chunks.items():
            if not 0 <= sid < self.n_slots or sid in self._slot_free:
                raise ValueError(f"stream {sid} is not open")
            if c.ndim != 2 or c.shape[1] != fd:
                raise ValueError(
                    f"stream {sid}: expected (t, {fd}) chunk, got "
                    f"{c.shape}")
            if c.shape[0] == 0:
                raise ValueError(
                    f"stream {sid}: zero-frame chunk — skip the stream "
                    f"this step instead of feeding an empty chunk")
        self._ensure_stream_state()
        t_max = bucket_length(max(c.shape[0] for c in chunks.values()),
                              self.policy.bucket_multiple)
        feats = np.zeros((self.n_slots, t_max, fd), np.float32)
        lens = np.zeros((self.n_slots,), np.int32)
        for sid, c in chunks.items():
            feats[sid, :c.shape[0]] = c
            lens[sid] = c.shape[0]
        vals, idx, self._stream_state = self._stream_fwd(
            self.params, self._stream_state, jax.device_put(feats),
            jax.device_put(lens))
        return StreamFeed(vals, idx,
                          {sid: c.shape[0] for sid, c in chunks.items()})

    def feed(self, chunks: Dict[int, np.ndarray]):
        """One batched streaming step over all active streams.

        chunks: {sid: (t, F)} — chunk lengths may differ per stream
        (each stream's state freezes at its own valid length); every
        chunk must have at least one frame.  Returns
        {sid: (vals (t, k), idx (t, k))}.  Synchronous wrapper over
        ``feed_async``.
        """
        return self.feed_async(chunks).result()

    def feed_pipelined(self, chunk_iter, *, depth: int = 2):
        """Drive ``feed_async`` over an iterator of chunk dicts with a
        ``depth``-deep in-flight window, yielding each step's results in
        order.  While step *n* computes on device, step *n+1* is already
        assembled and its H2D transfer issued — the interactive path's
        double-buffered feed.  Results are identical to sequential
        ``feed()`` calls (pinned in tests/test_serve_engine.py)."""
        if depth < 1:
            raise ValueError(f"depth must be >= 1, got {depth}")
        pending: deque = deque()
        for chunks in chunk_iter:
            pending.append(self.feed_async(chunks))
            while len(pending) >= depth:
                yield pending.popleft().result()
        while pending:
            yield pending.popleft().result()
