"""Emission checks shared by the cells that emit top-k targets: the
reference's logits over the same inputs, and the widest emission gap."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from bench import ref_lstm_am as ref


def reference_logits(cfg, seed, feats, mask, device, *, prec="f32",
                     block=128):
    """Reference logits (rows, T, V) on the host, in blocks of rows."""
    lens = mask.sum(axis=-1).astype(np.int32)
    with jax.default_device(device), jax.default_matmul_precision("highest"):
        params = ref.init_params(cfg, seed)
        fn = jax.jit(lambda p, f, l: ref.logits(p, f, l, cfg, prec))
        out = [np.asarray(fn(params, jnp.asarray(feats[i:i + block]),
                             jnp.asarray(lens[i:i + block])))
               for i in range(0, len(feats), block)]
    return np.concatenate(out)


def gaps(vals, idx, ref_lg, mask) -> float:
    m = mask.reshape(-1) > 0
    k = vals.shape[-1]
    g = ref.emission_gap(jnp.asarray(vals.reshape(-1, k)[m]),
                         jnp.asarray(idx.reshape(-1, k)[m]),
                         jnp.asarray(ref_lg.reshape(-1, ref_lg.shape[-1])[m]))
    return float(jnp.max(g))
