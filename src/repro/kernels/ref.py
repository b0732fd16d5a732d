"""Pure-jnp oracles for every Pallas kernel (the allclose targets)."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def meta_update(w, w_hat, alpha):
    """Reptile interpolation: w + alpha * (w_hat - w), fp32 math."""
    w32 = w.astype(jnp.float32)
    return (w32 + alpha * (w_hat.astype(jnp.float32) - w32)).astype(w.dtype)


def online_sgd(p, g, lr, m=None, momentum=0.0):
    """Streaming SGD step; optional momentum (fp32 state)."""
    if m is None:
        p32 = p.astype(jnp.float32)
        return (p32 - lr * g.astype(jnp.float32)).astype(p.dtype)
    m_new = momentum * m + g.astype(jnp.float32)
    p_new = (p.astype(jnp.float32) - lr * m_new).astype(p.dtype)
    return p_new, m_new


def flash_decode(q, k_cache, v_cache, cache_len, *, window=0):
    """Decode attention oracle. q: (B, H, hd); caches: (B, S, Kv, hd);
    cache_len: scalar int. Returns (B, H, hd) fp32."""
    B, H, hd = q.shape
    S, Kv = k_cache.shape[1], k_cache.shape[2]
    R = H // Kv
    qg = q.reshape(B, Kv, R, hd).astype(jnp.float32) * hd ** -0.5
    s = jnp.einsum("bkrh,bskh->bkrs", qg, k_cache.astype(jnp.float32))
    pos = jnp.arange(S)
    valid = pos < cache_len
    if window:
        valid &= pos >= cache_len - window
    s = jnp.where(valid[None, None, None, :], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bkrs,bskh->bkrh", p, v_cache.astype(jnp.float32))
    return out.reshape(B, H, hd)


# ---------------------------------------------------------------------------
# TIFeD integer DFA (oracle for kernels/online_sgd_int8.py)
# ---------------------------------------------------------------------------
#
# The reference carries every integer quantity in fp32 arrays holding
# EXACT integer values: all intermediates stay below 2^24 (activations
# <= 127, int8 x int8 dot over S <= 512 samples peaks around 8.3e6), so
# fp32 arithmetic on them is bit-exact against the kernel's native
# int8/int32 arithmetic. That makes the parity tests exact-equality,
# not allclose.

INT8_MAX = 127.0
BIAS_MAX = 2.0 ** 23          # biases live at accumulator scale, int32-safe
DFA_SHIFT = 7                 # feedback projections are scaled by 2^-7
_DN = (((0,), (0,)), ((), ()))   # contract the sample axis; vmap batches


def _dot(a, b, dims):
    # HIGHEST: the default TPU precision is one bf16 pass, which drops
    # bits of integer products past 2^8 and breaks the exactness above
    return jax.lax.dot_general(a, b, dims,
                               precision=jax.lax.Precision.HIGHEST)


def pow2_exponent(maxabs, limit=INT8_MAX):
    """Smallest power-of-two exponent e with maxabs * 2^-e <= limit.

    The ceil/log2 form can land one short of the true ceiling when
    maxabs/limit sits exactly on a power of two boundary in fp32, so a
    single correction step nudges it up; the floor of -24 keeps
    all-zero tensors on a sane grid."""
    e = jnp.ceil(jnp.log2(jnp.maximum(maxabs, 1e-30) / limit))
    e = jnp.where(maxabs * jnp.exp2(-e) > limit, e + 1, e)
    return jnp.maximum(e, -24).astype(jnp.int32)


def quantize_pow2(w, limit=INT8_MAX):
    """Per-tensor power-of-two symmetric quantization.

    Returns (q, e): the int-valued fp32 code array in [-limit, limit]
    and the int32 exponent with w ~= q * 2^e."""
    e = pow2_exponent(jnp.max(jnp.abs(w)), limit)
    q = jnp.clip(jnp.round(w * jnp.exp2(-e.astype(jnp.float32))),
                 -limit, limit)
    return q, e


def stochastic_round(v, dither):
    """Unbiased stochastic rounding: floor(v + u) with u ~ U[0, 1).

    The dither plane is supplied by the caller (baked trace constants in
    the tifed strategy) so the operation itself is deterministic."""
    return jnp.floor(v + dither)


def dfa_int8_epoch(ws, bs, xq, yal, layer, fb, dither, scales):
    """One TIFeD epoch: int8 forward + single-layer DFA update.

    The layer-cyclic single-layer variant of TIFeD: each epoch runs the
    full integer forward pass but updates only ``layer`` (0, 1, or 2),
    selected at runtime by lax.switch so the scan over epochs stays one
    trace. Direct feedback alignment replaces the backprop transposes
    with fixed random matrices ``fb``; weight requantization uses
    stochastic rounding driven by ``dither``.

    All arrays are fp32 carrying exact integers (see module comment):

      ws:     (w0 (din,H1), w1 (H1,H2), w2 (H2,dout)) int8-valued
      bs:     (b0, b1, b2) int32-valued, at accumulator scale
      xq:     (S, din) int8-valued quantized inputs
      yal:    (S, dout) targets pre-scaled to the output accumulator grid
      layer:  int32 scalar in {0, 1, 2} — which layer trains this epoch
      fb:     (fb1 (dout,H1), fb2 (dout,H2)) int8-valued feedback
      dither: (d0 (din,H1), d1 (H1,H2), d2 (H2,dout)) U[0,1) fp32
      scales: dict of fp32 power-of-two multipliers —
              f0/f1 (activation requant), fe (error quant),
              floss (loss rescale incl. the 1/S mean),
              ftw/ftb (3-tuples: weight/bias learning-rate requant)

    Returns ((w0', w1', w2'), (b0', b1', b2'), loss)."""
    w0, w1, w2 = ws
    b0, b1, b2 = bs
    fb1, fb2 = fb
    d0_, d1_, d2_ = dither

    def mm(a, b):
        return _dot(a, b, (((1,), (0,)), ((), ())))

    z0 = (xq * w0 if w0.shape[0] == 1 else mm(xq, w0)) + b0
    a1 = jnp.clip(jnp.round(jnp.maximum(z0, 0.0) * scales["f0"]),
                  0.0, INT8_MAX)
    z1 = mm(a1, w1) + b1
    a2 = jnp.clip(jnp.round(jnp.maximum(z1, 0.0) * scales["f1"]),
                  0.0, INT8_MAX)
    z2 = mm(a2, w2) + b2
    err = z2 - yal
    eq = jnp.clip(jnp.round(err * scales["fe"]), -INT8_MAX, INT8_MAX)
    loss = jnp.sum(jnp.square(err)) * scales["floss"]
    ftw, ftb = scales["ftw"], scales["ftb"]

    def proj(fbm):
        # error fed straight back to the hidden layer; dout==1 is a
        # broadcast, larger heads contract the output axis
        return eq * fbm if fbm.shape[0] == 1 else mm(eq, fbm)

    def hidden_update(i, z, a_in, fbm, dith, c):
        d = jnp.round(jnp.where(z > 0, proj(fbm), 0.0) * 2.0 ** -DFA_SHIFT)
        g = ((a_in * d).sum(0, keepdims=True) if a_in.shape[1] == 1
             else _dot(a_in, d, _DN))
        w = jnp.clip(c[i] - stochastic_round(g * ftw[i], dith),
                     -INT8_MAX, INT8_MAX)
        b = jnp.clip(c[3 + i] - jnp.round(d.sum(0) * ftb[i]),
                     -BIAS_MAX, BIAS_MAX)
        return tuple(w if j == i else b if j == 3 + i else c[j]
                     for j in range(6))

    def u0(c):
        return hidden_update(0, z0, xq, fb1, d0_, c)

    def u1(c):
        return hidden_update(1, z1, a1, fb2, d1_, c)

    def u2(c):
        g = _dot(a2, eq, _DN)
        w = jnp.clip(c[2] - stochastic_round(g * ftw[2], d2_),
                     -INT8_MAX, INT8_MAX)
        b = jnp.clip(c[5] - jnp.round(eq.sum(0) * ftb[2]),
                     -BIAS_MAX, BIAS_MAX)
        return (c[0], c[1], w, c[3], c[4], b)

    c = jax.lax.switch(layer, (u0, u1, u2), (w0, w1, w2, b0, b1, b2))
    return (c[0], c[1], c[2]), (c[3], c[4], c[5]), loss


def ssd_scan(xd, dA, Bm, Cm):
    """Chunked SSD oracle (matches kernels/ssd_scan.py layout).

    xd: (B, H, nc, Q, P)  — dt-scaled inputs
    dA: (B, H, nc, Q)     — dt * A (negative decay log-increments)
    Bm: (B, nc, Q, N), Cm: (B, nc, Q, N) — shared across heads (ngroups=1)
    Returns y: (B, H, nc, Q, P) fp32.
    """
    B, H, nc, Q, P = xd.shape
    N = Bm.shape[-1]
    xd = xd.astype(jnp.float32)
    dA = dA.astype(jnp.float32)
    Bm = Bm.astype(jnp.float32)
    Cm = Cm.astype(jnp.float32)
    dA_cs = jnp.cumsum(dA, axis=-1)                       # (B,H,nc,Q)
    # intra-chunk
    diff = dA_cs[..., :, None] - dA_cs[..., None, :]      # (B,H,nc,Q,Q)
    mask = jnp.tril(jnp.ones((Q, Q), bool))
    L = jnp.exp(jnp.where(mask, diff, -1e30))  # mask inside exp (grad-safe)
    CB = jnp.einsum("bcin,bcjn->bcij", Cm, Bm)            # (B,nc,Q,Q)
    y_diag = jnp.einsum("bhcij,bcij,bhcjp->bhcip", L, CB, xd)
    # chunk states
    decay_out = jnp.exp(dA_cs[..., -1:] - dA_cs)          # (B,H,nc,Q)
    states = jnp.einsum("bcln,bhcl,bhclp->bhcpn", Bm, decay_out, xd)
    chunk_decay = jnp.exp(dA_cs[..., -1])                 # (B,H,nc)

    def step(state, inp):
        st, dec = inp
        return state * dec[..., None, None] + st, state

    init = jnp.zeros((B, H, P, N), jnp.float32)
    _, prev = jax.lax.scan(
        step, init, (states.transpose(2, 0, 1, 3, 4),
                     chunk_decay.transpose(2, 0, 1)))
    prev = prev.transpose(1, 2, 0, 3, 4)                  # (B,H,nc,P,N)
    y_off = jnp.einsum("bcln,bhcpn,bhcl->bhclp", Cm, prev, jnp.exp(dA_cs))
    return y_diag + y_off
