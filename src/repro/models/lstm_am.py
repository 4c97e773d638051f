"""The paper's acoustic models (Section 2 / 3.2).

Student: 5x768 unidirectional LSTM over 192-d stacked log-mel features,
3,183 senone outputs, ~24M params, 3-frame look-ahead (realized as a feature
shift in the data pipeline).  Teacher: 5x768 *bidirectional* LSTM (~78M).
No residuals/norms — faithful to the plain stacked-LSTM hybrid AM of 2019.
Supports chunked-BPTT: ``apply`` takes and returns per-layer (h, c) states.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models import layers, recurrent


def is_bidirectional(cfg) -> bool:
    """Single source of truth for the AM's directionality (the streaming
    surface in models/api.py keys off the same predicate)."""
    return any(m == "bilstm" for m in cfg.mixers())


class LstmAM:
    def __init__(self, cfg):
        self.cfg = cfg
        self.bidirectional = is_bidirectional(cfg)
        self.n_layers = cfg.n_layers

    def init(self, key):
        cfg = self.cfg
        ks = jax.random.split(key, self.n_layers + 1)
        params = {"out": layers.dense_init(
            ks[-1],
            cfg.lstm_hidden * (2 if self.bidirectional else 1),
            cfg.n_senones)}
        d_in = cfg.feat_dim
        for i in range(self.n_layers):
            if self.bidirectional:
                kf, kb = jax.random.split(ks[i])
                params[f"l{i}"] = {
                    "fwd": recurrent.init_lstm(kf, d_in, cfg.lstm_hidden),
                    "bwd": recurrent.init_lstm(kb, d_in, cfg.lstm_hidden)}
                d_in = 2 * cfg.lstm_hidden
            else:
                params[f"l{i}"] = recurrent.init_lstm(ks[i], d_in,
                                                      cfg.lstm_hidden)
                d_in = cfg.lstm_hidden
        return params

    def apply(self, params, feats, *, state=None, positions=None, lens=None):
        """feats (B,T,F) -> (hidden (B,T,H), aux). state: list of (h,c).

        lens (B,) optional valid lengths for padded batches: recurrent
        state freezes at each row's length and the backward direction of a
        biLSTM starts at the last valid frame, so batched outputs match
        per-utterance runs on the valid region (see recurrent.lstm_apply).
        The bidirectional stack runs time-major: one transpose in, one
        out, and each layer's backward direction is a reverse scan over
        the same input (recurrent.bilstm_apply).
        """
        if self.bidirectional:
            xs = feats.transpose(1, 0, 2)
            mask = None if lens is None else recurrent.time_mask(
                lens, xs.shape[0])
            for i in range(self.n_layers):
                xs = recurrent.bilstm_apply(params[f"l{i}"]["fwd"],
                                            params[f"l{i}"]["bwd"], xs, mask)
            return xs.transpose(1, 0, 2), {"state": None}
        x = feats
        new_state = []
        for i in range(self.n_layers):
            st = None if state is None else state[i]
            x, st = recurrent.lstm_apply(params[f"l{i}"], x, st, lens=lens)
            new_state.append(st)
        return x, {"state": new_state}

    def unembed_matrix(self, params):
        return params["out"]

    def unembed(self, params, h):
        return (h @ params["out"].astype(h.dtype)).astype(jnp.float32)

    def logits(self, params, feats, state=None):
        h, aux = self.apply(params, feats, state=state)
        return self.unembed(params, h), aux

    def init_state(self, batch, dtype=jnp.float32):
        if self.bidirectional:
            return None
        h = self.cfg.lstm_hidden
        return [(jnp.zeros((batch, h), dtype), jnp.zeros((batch, h),
                                                         jnp.float32))
                for _ in range(self.n_layers)]

    # ------------------------------------------------- streaming surface
    # Chunked online inference: feed arbitrary-length feature chunks, carry
    # the per-layer (h, c) pytree across calls.  Feeding an utterance in
    # chunks is exactly equivalent to one full-utterance apply().

    def init_stream_state(self, batch, dtype=jnp.float32, **_sizing):
        """Fresh per-stream recurrent state (batch = concurrent streams).
        Sizing kwargs (``max_frames``/``max_tokens``) are accepted for
        surface uniformity with the whisper streaming state and ignored:
        LSTM state is O(1) per stream."""
        if self.bidirectional:
            raise ValueError(
                "bidirectional AM has no streaming form; use the batched "
                "full-utterance path (serve.StreamingEngine.run)")
        return self.init_state(batch, dtype)

    def stream_step(self, params, state, feats, *, lens=None):
        """One streaming chunk: feats (B,T,F) -> (hidden (B,T,H), state).

        lens (B,) marks each stream's valid frames within the chunk;
        shorter streams' states freeze at their length, so ragged chunks
        batch safely.
        """
        h, aux = self.apply(params, feats, state=state, lens=lens)
        return h, aux["state"]

    def reset_stream_rows(self, state, rows):
        """Zero the (h, c) rows selected by the (B,) bool mask — slot
        admission for the stream surface (the ``reset_cache_rows``
        convention of the decode caches, applied to recurrent state)."""
        return jax.tree_util.tree_map(
            lambda a: jnp.where(rows[:, None], jnp.zeros((), a.dtype), a),
            state)

    def pull_stream_row(self, state, i):
        """Extract stream ``i``'s state row (detach: the serving layer
        parks it host-side).  Round-trips bitwise through
        ``put_stream_row``."""
        return jax.tree_util.tree_map(lambda a: a[i], state)

    def put_stream_row(self, state, i, row):
        """Write a previously pulled state row back into slot ``i``."""
        return jax.tree_util.tree_map(
            lambda a, r: a.at[i].set(jnp.asarray(r, a.dtype)), state, row)
