"""Chunked+remat recurrent scans == flat scans (bitwise math, fewer saved
residuals) — the §Perf memory lever for xlstm train_4k — and the
time-major biLSTM stack == per-row runs on a ragged batch."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models import recurrent as R


def test_mlstm_chunked_equals_flat():
    rng = np.random.default_rng(0)
    b, h, s, hd = 2, 2, 128, 16
    q = jnp.asarray(rng.normal(size=(b, h, s, hd)), jnp.float32) * 0.3
    k = jnp.asarray(rng.normal(size=(b, h, s, hd)), jnp.float32) * 0.3
    v = jnp.asarray(rng.normal(size=(b, h, s, hd)), jnp.float32)
    gates = jnp.asarray(rng.normal(size=(b, s, 2 * h)), jnp.float32)
    h_flat, (C1, n1, m1) = R.mlstm_scan(q, k, v, gates, chunk=s + 1)
    h_chunk, (C2, n2, m2) = R.mlstm_scan(q, k, v, gates, chunk=32)
    np.testing.assert_allclose(np.asarray(h_flat), np.asarray(h_chunk),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(C1), np.asarray(C2), rtol=1e-5,
                               atol=1e-5)


@pytest.mark.slow
def test_mlstm_chunked_gradients_match():
    rng = np.random.default_rng(1)
    b, h, s, hd = 1, 2, 64, 8
    q = jnp.asarray(rng.normal(size=(b, h, s, hd)), jnp.float32) * 0.3
    k = jnp.asarray(rng.normal(size=(b, h, s, hd)), jnp.float32) * 0.3
    v = jnp.asarray(rng.normal(size=(b, h, s, hd)), jnp.float32)
    gates = jnp.asarray(rng.normal(size=(b, s, 2 * h)), jnp.float32)

    def loss(qq, chunk):
        hs, _ = R.mlstm_scan(qq, k, v, gates, chunk=chunk)
        return jnp.sum(hs ** 2)

    g_flat = jax.grad(lambda qq: loss(qq, s + 1))(q)
    g_chunk = jax.grad(lambda qq: loss(qq, 16))(q)
    np.testing.assert_allclose(np.asarray(g_flat), np.asarray(g_chunk),
                               rtol=1e-4, atol=1e-5)


def test_rglru_assoc_scan_matches_sequential():
    """RG-LRU's associative scan == a step-by-step reference."""
    import jax
    from repro.configs import get_arch, reduced
    cfg = reduced(get_arch("recurrentgemma-2b"))
    key = jax.random.key(0)
    params = R.init_rglru_block(key, cfg)
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.normal(size=(2, 24, cfg.lru_width or cfg.d_model)),
                    jnp.float32) * 0.3
    h_par, h_last = R.rglru_scan(params, x)
    a, bseq = R._rglru_coeffs(params, x)
    hs = []
    hprev = jnp.zeros_like(a[:, 0])
    for t in range(x.shape[1]):
        hprev = a[:, t] * hprev + bseq[:, t]
        hs.append(hprev)
    h_ref = jnp.stack(hs, axis=1)
    np.testing.assert_allclose(np.asarray(h_par, jnp.float32),
                               np.asarray(h_ref.astype(h_par.dtype),
                                          jnp.float32),
                               rtol=2e-3, atol=2e-3)


def _bilstm_by_flips(params, n_layers, x):
    """The textbook biLSTM of one unpadded batch: the backward direction
    is a forward scan over the time-reversed input, flipped back."""
    for i in range(n_layers):
        p = params[f"l{i}"]
        yf, _ = R.lstm_apply(p["fwd"], x)
        yb, _ = R.lstm_apply(p["bwd"], x[:, ::-1])
        x = jnp.concatenate([yf, yb[:, ::-1]], axis=-1)
    return x


@pytest.mark.parametrize("case", ["ragged-vs-solo", "no-lens-vs-all-valid"])
def test_bilstm_time_major_matches_rows(case):
    """A 3-layer biLSTM AM over a ragged batch equals each row run alone
    and unpadded, bit for bit on the valid frames (float32), and each
    solo run equals the biLSTM written with time flips; lens=None equals
    lens that mark every frame valid.  Op by op, so the check is the
    masking and reverse scan, not a compiler's fusion choices."""
    from repro.core.ssl_pipeline import am_configs
    from repro.models.lstm_am import LstmAM
    _, cfg = am_configs(n_layers=3, lstm_hidden=16, n_senones=7, feat_dim=8)
    model = LstmAM(cfg)
    params = model.init(jax.random.key(0))
    lens = np.array([11, 1, 6, 9, 3], np.int32)
    b, s = len(lens), int(lens.max())
    rng = np.random.default_rng(3)
    x = rng.standard_normal((b, s, 8)).astype(np.float32)
    x *= np.arange(s)[None, :, None] < lens[:, None, None]
    with jax.disable_jit():
        if case == "ragged-vs-solo":
            h = np.asarray(model.apply(params, jnp.asarray(x),
                                       lens=jnp.asarray(lens))[0])
            for i, n in enumerate(lens):
                row = jnp.asarray(x[i:i + 1, :n])
                solo = np.asarray(model.apply(params, row)[0])
                np.testing.assert_array_equal(solo[0], h[i, :n])
                np.testing.assert_array_equal(
                    solo, np.asarray(_bilstm_by_flips(params, 3, row)))
        else:
            h = model.apply(params, jnp.asarray(x))[0]
            full = model.apply(params, jnp.asarray(x),
                               lens=jnp.full((b,), s, jnp.int32))[0]
            np.testing.assert_array_equal(np.asarray(h), np.asarray(full))
