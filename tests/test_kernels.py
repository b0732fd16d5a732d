"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps, interpret mode."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref


@pytest.mark.parametrize("shape", [(7,), (1153,), (64, 64), (3, 5, 257),
                                   (8192,)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("alpha", [0.0, 0.37, 1.0])
def test_meta_update(shape, dtype, alpha):
    k1, k2 = jax.random.split(jax.random.PRNGKey(0))
    w = jax.random.normal(k1, shape, jnp.float32).astype(dtype)
    wh = jax.random.normal(k2, shape, jnp.float32).astype(dtype)
    got = ops.meta_update(w, wh, alpha)
    want = ref.meta_update(w, wh, alpha)
    assert got.dtype == w.dtype and got.shape == w.shape
    tol = 1e-5 if dtype == jnp.float32 else 1e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("shape", [(129,), (1024, 33)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_online_sgd(shape, dtype):
    k1, k2 = jax.random.split(jax.random.PRNGKey(1))
    p = jax.random.normal(k1, shape, jnp.float32).astype(dtype)
    g = jax.random.normal(k2, shape, jnp.float32).astype(dtype)
    got = ops.online_sgd(p, g, 0.01)
    want = ref.online_sgd(p, g, 0.01)
    tol = 1e-6 if dtype == jnp.float32 else 1e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


def test_online_sgd_momentum():
    k1, k2 = jax.random.split(jax.random.PRNGKey(2))
    p = jax.random.normal(k1, (513,))
    g = jax.random.normal(k2, (513,))
    m = jnp.ones((513,), jnp.float32) * 0.3
    pn, mn = ops.online_sgd_momentum(p, g, m, 0.05, 0.9)
    pr, mr = ref.online_sgd(p, g, 0.05, m, 0.9)
    np.testing.assert_allclose(pn, pr, rtol=1e-5, atol=1e-7)
    np.testing.assert_allclose(mn, mr, rtol=1e-5, atol=1e-7)


@pytest.mark.parametrize("B,H,Kv,hd,S", [
    (1, 4, 4, 64, 512),      # MHA
    (2, 8, 2, 64, 1024),     # GQA
    (1, 8, 1, 128, 2048),    # MQA, paligemma-like head_dim
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_decode(B, H, Kv, hd, S, dtype):
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    q = jax.random.normal(ks[0], (B, H, hd), jnp.float32).astype(dtype)
    kc = jax.random.normal(ks[1], (B, S, Kv, hd), jnp.float32).astype(dtype)
    vc = jax.random.normal(ks[2], (B, S, Kv, hd), jnp.float32).astype(dtype)
    for cache_len, window in [(S // 2, 0), (S, 0), (1, 0), (S // 2, 128)]:
        got = ops.flash_decode(q, kc, vc, cache_len, window=window,
                               block_s=256)
        want = ref.flash_decode(q, kc, vc, cache_len, window=window)
        tol = 3e-4 if dtype == jnp.float32 else 2e-2
        np.testing.assert_allclose(np.asarray(got, np.float32), want,
                                   rtol=tol, atol=tol)


@pytest.mark.parametrize("B,H,nc,Q,P,N", [
    (1, 2, 2, 16, 64, 16),
    (2, 3, 4, 32, 64, 32),
    (1, 24, 2, 64, 64, 128),  # mamba2-130m geometry
])
def test_ssd_scan(B, H, nc, Q, P, N):
    ks = jax.random.split(jax.random.PRNGKey(4), 4)
    xd = jax.random.normal(ks[0], (B, H, nc, Q, P))
    dA = -jnp.abs(jax.random.normal(ks[1], (B, H, nc, Q))) * 0.1
    Bm = jax.random.normal(ks[2], (B, nc, Q, N)) * 0.3
    Cm = jax.random.normal(ks[3], (B, nc, Q, N)) * 0.3
    got = ops.ssd_scan(xd, dA, Bm, Cm)
    want = ref.ssd_scan(xd, dA, Bm, Cm)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


def _tifed_case(dims, S, seed, extreme=False):
    """Random (or all-extreme) int-valued fp32 inputs for the TIFeD
    epoch kernel, plus a power-of-two scales dict. ``extreme`` drives
    every tensor to its dtype rails (the int32-accumulation edge: the
    documented < 2^24 envelope for exact fp32 parity)."""
    rng = np.random.default_rng(seed)
    din, h1, h2, dout = dims

    def ints(lo, hi, shape):
        if extreme:
            return jnp.asarray(rng.choice([float(lo), float(hi)], shape),
                               jnp.float32)
        return jnp.asarray(rng.integers(lo, hi + 1, shape), jnp.float32)

    ws = tuple(ints(-127, 127, s)
               for s in ((din, h1), (h1, h2), (h2, dout)))
    bs = tuple(ints(-2 ** 22, 2 ** 22, (b,)) if extreme
               else ints(-2 ** 15, 2 ** 15, (b,)) for b in (h1, h2, dout))
    xq = ints(-127, 127, (S, din))
    yal = ints(-2 ** 21, 2 ** 21, (S, dout)) if extreme \
        else ints(-2 ** 15, 2 ** 15, (S, dout))
    fb = tuple(ints(-127, 127, (dout, h)) for h in (h1, h2))
    dither = tuple(jnp.asarray(rng.random(s), jnp.float32)
                   for s in ((din, h1), (h1, h2), (h2, dout)))
    f32 = jnp.float32
    scales = {"f0": f32(2.0 ** -7), "f1": f32(2.0 ** -7),
              "fe": f32(2.0 ** -9), "floss": f32(2.0 ** -4 / S),
              "ftw": (f32(2.0 ** -8), f32(2.0 ** -9), f32(2.0 ** -10)),
              "ftb": (f32(2.0 ** -6), f32(2.0 ** -7), f32(2.0 ** -8))}
    return ws, bs, xq, yal, fb, dither, scales


@pytest.mark.parametrize("dims", [(1, 16, 16, 1),   # sine-MLP shape class
                                  (5, 16, 12, 3)])  # din>1, dout>1 paths
@pytest.mark.parametrize("layer", [0, 1, 2])
def test_dfa_epoch_int8_matches_ref(dims, layer):
    """Kernel vs fp32-exact oracle: EXACT equality on weights and biases
    — both sides compute the same integers (ref in fp32 carrying exact
    ints, kernel in native int8/int32). The loss is an fp32 sum that can
    pass 2^24, where the two reduction orders may differ by an ulp."""
    ws, bs, xq, yal, fb, dither, scales = _tifed_case(dims, 32, layer + 10)
    gw, gb, gl = ops.dfa_epoch_int8(ws, bs, xq, yal, layer, fb, dither,
                                    scales)
    ww, wb, wl = ref.dfa_int8_epoch(ws, bs, xq, yal, layer, fb, dither,
                                    scales)
    for i in range(3):
        assert gw[i].dtype == jnp.int8 and gb[i].dtype == jnp.int32
        np.testing.assert_array_equal(np.asarray(gw[i], np.float32), ww[i])
        np.testing.assert_array_equal(np.asarray(gb[i], np.float32), wb[i])
    np.testing.assert_allclose(np.float32(gl), np.float32(wl), rtol=1e-6)
    # the untrained layers pass through unchanged
    for i in range(3):
        if i != layer:
            np.testing.assert_array_equal(np.asarray(gw[i], np.float32),
                                          np.asarray(ws[i]))


@pytest.mark.parametrize("layer", [0, 1, 2])
def test_dfa_epoch_int8_accumulation_edge(layer):
    """All-rails inputs at the documented envelope: S=512 samples of
    +/-127 against +/-127 weights and +/-2^22 biases keep every int32
    accumulator below 2^24, so kernel and oracle must still agree
    exactly and land inside the int8 / bias clip rails."""
    ws, bs, xq, yal, fb, dither, scales = _tifed_case(
        (1, 8, 8, 1), 512, 99, extreme=True)
    gw, gb, _ = ops.dfa_epoch_int8(ws, bs, xq, yal, layer, fb, dither,
                                   scales)
    ww, wb, _ = ref.dfa_int8_epoch(ws, bs, xq, yal, layer, fb, dither,
                                   scales)
    for i in range(3):
        np.testing.assert_array_equal(np.asarray(gw[i], np.float32), ww[i])
        np.testing.assert_array_equal(np.asarray(gb[i], np.float32), wb[i])
        assert np.abs(np.asarray(gw[i], np.float32)).max() <= ref.INT8_MAX
        assert np.abs(np.asarray(gb[i], np.float64)).max() <= ref.BIAS_MAX


def test_dfa_epoch_int8_rejects_wide_head():
    """The kernel splits the DFA delta into two int8 halves, exact only
    up to dout=128; a wider head is refused, not silently wrong."""
    ws, bs, xq, yal, fb, dither, scales = _tifed_case((1, 8, 8, 129), 8, 0)
    with pytest.raises(ValueError, match="dout <= 128"):
        ops.dfa_epoch_int8(ws, bs, xq, yal, 0, fb, dither, scales)


def test_stochastic_round_statistics():
    """floor(v + u), u ~ U[0,1): values land on the neighbouring
    integers only, and the mean over many dithers is unbiased."""
    rng = np.random.default_rng(7)
    v = jnp.asarray(rng.uniform(-5.0, 5.0, (64,)), jnp.float32)
    dithers = jnp.asarray(rng.random((4096, 64)), jnp.float32)
    r = np.asarray(ref.stochastic_round(v[None, :], dithers))
    lo, hi = np.floor(np.asarray(v)), np.ceil(np.asarray(v))
    assert np.all((r == lo[None, :]) | (r == hi[None, :]))
    np.testing.assert_allclose(r.mean(0), np.asarray(v), atol=0.05)


def test_ssd_kernel_matches_model_path():
    """Kernel agrees with the model's ssd_chunked (different layout)."""
    from repro.models.mamba2 import ssd_chunked
    B, S, H, P, N, chunk = 2, 128, 4, 32, 16, 32
    ks = jax.random.split(jax.random.PRNGKey(5), 4)
    x = jax.random.normal(ks[0], (B, S, H, P))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, H)))
    A = -jnp.abs(jax.random.normal(ks[2], (H,)))
    Bm = jax.random.normal(ks[3], (B, S, N)) * 0.3
    Cm = jax.random.normal(ks[0], (B, S, N)) * 0.3
    y_model, _ = ssd_chunked(x, dt, A, Bm, Cm, chunk)
    # kernel layout
    nc = S // chunk
    xd = (x * dt[..., None]).reshape(B, nc, chunk, H, P).transpose(0, 3, 1, 2, 4)
    dA = (dt * A).reshape(B, nc, chunk, H).transpose(0, 3, 1, 2)
    Bk = Bm.reshape(B, nc, chunk, N)
    Ck = Cm.reshape(B, nc, chunk, N)
    y_kernel = ops.ssd_scan(xd, dA, Bk, Ck)
    y_kernel = y_kernel.transpose(0, 2, 3, 1, 4).reshape(B, S, H, P)
    np.testing.assert_allclose(y_kernel, np.asarray(y_model, np.float32),
                               rtol=2e-4, atol=2e-4)
