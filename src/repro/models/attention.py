"""GQA attention: RoPE, sliding window, blockwise-flash prefill/train path
(pure-JAX online softmax over KV blocks), and KV-cache decode path.

The blockwise path is the XLA fallback; on real TPU the decode hot-spot
dispatches to ``repro.kernels.flash_decode`` (validated in interpret mode).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.models.layers import normal_init
from repro.runtime.flags import feature, probe_mode
from repro.runtime.shardctx import shard

NEG_INF = -1e30


def init_attention(key, d_model, num_heads, num_kv_heads, head_dim, dtype,
                   cross=False):
    ks = jax.random.split(key, 4)
    p = {
        "wq": normal_init(ks[0], (d_model, num_heads, head_dim), 1.0, dtype),
        "wk": normal_init(ks[1], (d_model, num_kv_heads, head_dim), 1.0, dtype),
        "wv": normal_init(ks[2], (d_model, num_kv_heads, head_dim), 1.0, dtype),
        "wo": normal_init(ks[3], (num_heads, head_dim, d_model), 1.0, dtype),
    }
    return p


def apply_rope(x, positions, theta):
    """x: (B, S, N, hd); positions: (S,) or (B, S)."""
    hd = x.shape[-1]
    half = hd // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    if positions.ndim == 1:
        positions = positions[None, :]
    ang = positions[:, :, None].astype(jnp.float32) * freqs  # (B,S,half)
    cos = jnp.cos(ang)[:, :, None, :]
    sin = jnp.sin(ang)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def _block_mask(qpos, kpos, causal, window):
    """qpos: (qb,), kpos: (kb,) -> (qb, kb) validity."""
    valid = kpos[None, :] >= 0
    if causal:
        valid &= kpos[None, :] <= qpos[:, None]
    if window:
        valid &= kpos[None, :] > qpos[:, None] - window
    return valid


def _banded_attention(q, k, v, *, window, scale, q_block=512):
    """§Perf lever: sliding-window attention that GATHERS only the KV band
    per Q block — cuts attention FLOPs from O(S^2) to O(S * window)
    (mixtral prefill_32k: 32768 -> ~4608 per row). Causal self-attention
    only (aligned q/kv)."""
    B, Sq, H, hd = q.shape
    Kv = k.shape[2]
    R = H // Kv
    qb = min(q_block, Sq)
    pad = (-Sq) % qb
    if pad:
        q = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
    nq = q.shape[1] // qb
    band = (window // qb + 2) * qb          # covers (qs - window, qs + qb)
    band = min(band, k.shape[1])
    flat = feature("gqa_flat")

    def one_block(qi, q_blk):
        qs = qi * qb
        start = jnp.clip(qs + qb - band, 0, k.shape[1] - band)
        k_b = jax.lax.dynamic_slice_in_dim(k, start, band, axis=1)
        v_b = jax.lax.dynamic_slice_in_dim(v, start, band, axis=1)
        kpos = start + jnp.arange(band)
        qpos = qs + jnp.arange(qb)
        kv_, r_ = Kv, R
        if flat:
            k_b = shard(jnp.repeat(k_b, R, axis=2), "batch", None, "model",
                        None)
            v_b = shard(jnp.repeat(v_b, R, axis=2), "batch", None, "model",
                        None)
            kv_, r_ = H, 1
        qg = q_blk.reshape(B, qb, kv_, r_, hd) * jnp.asarray(scale, q.dtype)
        s = jnp.einsum("bqkrh,bskh->bkrqs", qg, k_b,
                       preferred_element_type=jnp.float32)
        valid = (kpos[None, :] <= qpos[:, None]) & \
                (kpos[None, :] > qpos[:, None] - window)
        s = jnp.where(valid[None, None, None], s, NEG_INF)
        p = jax.nn.softmax(s, axis=-1)
        out = jnp.einsum("bkrqs,bskh->bkrqh", p.astype(v_b.dtype), v_b,
                         preferred_element_type=jnp.float32)
        return out.transpose(0, 3, 1, 2, 4).reshape(B, qb, H, hd)

    qg_blocks = q.reshape(B, nq, qb, H, hd).transpose(1, 0, 2, 3, 4)
    if probe_mode():
        outs = [one_block(i, qg_blocks[i]) for i in range(nq)]
        out = jnp.stack(outs)
    else:
        _, out = jax.lax.scan(
            lambda c, xs: (c, one_block(xs[0], xs[1])), None,
            (jnp.arange(nq), qg_blocks))
    out = out.transpose(1, 0, 2, 3, 4).reshape(B, nq * qb, H, hd)
    return out[:, :Sq].astype(q.dtype)


def flash_attention(q, k, v, *, causal, window=0, q_positions=None,
                    kv_positions=None, q_block=512, kv_block=512,
                    scale=None, remat_q_blocks=False):
    """Blockwise online-softmax attention.

    q: (B, Sq, H, hd); k, v: (B, Skv, Kv, hd). H = Kv * R (GQA).
    ``scale`` multiplies the scores (None: 1/sqrt(hd)).
    ``remat_q_blocks`` recomputes each q block in the backward pass, so
    that only its inputs stay live and not its (nk, qb, kb) score tiles:
    for a caller that rematerialises the block around it one by one
    (an unscanned layer stack), not for one inside a checkpointed scan.
    Returns (B, Sq, H, hd).
    """
    B, Sq, H, hd = q.shape
    Skv, Kv = k.shape[1], k.shape[2]
    R = H // Kv
    if scale is None:
        scale = hd ** -0.5

    if q_positions is None:
        q_positions = jnp.arange(Sq)
    if kv_positions is None:
        kv_positions = jnp.arange(Skv)

    if feature("banded") and window and causal and Skv > window:
        return _banded_attention(q, k, v, window=window, scale=scale,
                                 q_block=q_block)

    if feature("seqpar"):
        # one q block (the q dim is model-sharded by the caller); the
        # blockwise online softmax runs over KV only.
        q_block = Sq

    if feature("gqa_flat"):
        # §Perf lever: repeat K/V to H flat heads so the head dim shards
        # even when Kv < model-axis size (Kv-grouped einsums force score
        # replication there). K/V activation cost x R, but sharded /16.
        k = jnp.repeat(k, R, axis=2)
        v = jnp.repeat(v, R, axis=2)
        k = shard(k, "batch", None, "model", None)
        v = shard(v, "batch", None, "model", None)
        q = shard(q, "batch", None, "model", None)
        Kv, R = H, 1

    if probe_mode():
        # single-shot masked attention: identical matmul FLOPs to the
        # blockwise path, no while loops -> exact cost_analysis.
        qg = q.reshape(B, Sq, Kv, R, hd) * jnp.asarray(scale, q.dtype)
        s = jnp.einsum("bqkrh,bskh->bkrqs", qg, k,
                       preferred_element_type=jnp.float32)
        mask = _block_mask(q_positions, kv_positions, causal, window)
        s = jnp.where(mask[None, None, None, :, :], s, NEG_INF)
        p = jax.nn.softmax(s, axis=-1)
        out = jnp.einsum("bkrqs,bskh->bkrqh", p.astype(v.dtype), v,
                         preferred_element_type=jnp.float32)
        return out.transpose(0, 3, 1, 2, 4).reshape(B, Sq, H, hd).astype(q.dtype)

    q_block = min(q_block, Sq)
    kv_block = min(kv_block, Skv)
    # pad to block multiples
    pq = (-Sq) % q_block
    pk = (-Skv) % kv_block
    if pq:
        q = jnp.pad(q, ((0, 0), (0, pq), (0, 0), (0, 0)))
        q_positions = jnp.pad(q_positions, (0, pq), constant_values=-1)
    if pk:
        k = jnp.pad(k, ((0, 0), (0, pk), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pk), (0, 0), (0, 0)))
        kv_positions = jnp.pad(kv_positions, (0, pk), constant_values=-1)
    nq = q.shape[1] // q_block
    nk = k.shape[1] // kv_block

    qg = q.reshape(B, nq, q_block, Kv, R, hd).transpose(1, 0, 2, 3, 4, 5)
    qp = q_positions.reshape(nq, q_block)
    kg = k.reshape(B, nk, kv_block, Kv, hd).transpose(1, 0, 2, 3, 4)
    vg = v.reshape(B, nk, kv_block, Kv, hd).transpose(1, 0, 2, 3, 4)
    kp = kv_positions.reshape(nk, kv_block)

    def q_step(_, qx):
        q_i, qp_i = qx  # (B,qb,Kv,R,hd), (qb,)
        q_i = q_i * jnp.asarray(scale, q_i.dtype)

        def kv_step(carry, kx):
            m, l, acc = carry
            k_j, v_j, kp_j = kx
            # bf16 MXU matmul, fp32 accumulation
            s = jnp.einsum("bqkrh,bskh->bkrqs", q_i, k_j,
                           preferred_element_type=jnp.float32)
            mask = _block_mask(qp_i, kp_j, causal, window)
            s = jnp.where(mask[None, None, None, :, :], s, NEG_INF)
            m_new = jnp.maximum(m, s.max(axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + p.sum(axis=-1)
            acc_new = acc * corr[..., None] + jnp.einsum(
                "bkrqs,bskh->bkrqh", p.astype(v_j.dtype), v_j,
                preferred_element_type=jnp.float32)
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((B, Kv, R, q_block), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, Kv, R, q_block), jnp.float32)
        a0 = jnp.zeros((B, Kv, R, q_block, hd), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(kv_step, (m0, l0, a0), (kg, vg, kp))
        out = acc / jnp.maximum(l, 1e-30)[..., None]
        return None, out.transpose(0, 3, 1, 2, 4)  # (B,qb,Kv,R,hd)

    if remat_q_blocks:
        q_step = jax.checkpoint(q_step, prevent_cse=False)
    _, outs = jax.lax.scan(q_step, None, (qg, qp))
    out = outs.transpose(1, 0, 2, 3, 4, 5).reshape(B, nq * q_block, H, hd)
    return out[:, :Sq].astype(q.dtype)


def decode_attention(q, k_cache, v_cache, cache_len, *, window=0,
                     scale=None):
    """Single-token attention against a (possibly seq-sharded) KV cache.

    q: (B, 1, H, hd); k_cache/v_cache: (B, S, Kv, hd); cache_len: scalar or
    (B,) number of valid cache entries. New token attends to cache[:len].
    ``scale`` multiplies the scores (None: 1/sqrt(hd)).
    """
    B, _, H, hd = q.shape
    S, Kv = k_cache.shape[1], k_cache.shape[2]
    R = H // Kv
    if scale is None:
        scale = hd ** -0.5
    qg = q.reshape(B, Kv, R, hd) * jnp.asarray(scale, q.dtype)
    s = jnp.einsum("bkrh,bskh->bkrs", qg, k_cache,
                   preferred_element_type=jnp.float32)
    pos = jnp.arange(S)
    cache_len = jnp.asarray(cache_len)
    clen = cache_len if cache_len.ndim else cache_len[None].repeat(B)
    valid = pos[None, :] < clen[:, None]
    if window:
        valid &= pos[None, :] >= clen[:, None] - window
    s = jnp.where(valid[:, None, None, :], s, NEG_INF)
    m = s.max(axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    out = jnp.einsum("bkrs,bskh->bkrh", p.astype(v_cache.dtype), v_cache,
                     preferred_element_type=jnp.float32)
    out = out / p.sum(axis=-1, keepdims=True)
    return out.reshape(B, 1, H, hd).astype(q.dtype)


def attention_block(params, x, *, num_kv_heads, rope_theta, causal=True,
                    window=0, positions=None, kv_x=None, use_rope=True,
                    scale=None, remat_q_blocks=False):
    """Full attention sub-block (projections + flash). kv_x for cross-attn;
    ``scale`` multiplies the scores (None: 1/sqrt(head_dim));
    ``remat_q_blocks`` as in ``flash_attention``."""
    B, S, _ = x.shape
    src = x if kv_x is None else kv_x
    q = jnp.einsum("bsd,dnh->bsnh", x, params["wq"])
    k = jnp.einsum("bsd,dnh->bsnh", src, params["wk"])
    v = jnp.einsum("bsd,dnh->bsnh", src, params["wv"])
    if positions is None:
        positions = jnp.arange(S)
    if use_rope and kv_x is None:
        q = apply_rope(q, positions, rope_theta)
        k = apply_rope(k, positions, rope_theta)
    if feature("seqpar"):
        # sequence-parallel attention: shard QUERY rows over the model
        # axis (head-count agnostic); K/V replicate over model (small).
        q = shard(q, "batch", "model", None, None)
        k = shard(k, "batch", None, None, None)
        v = shard(v, "batch", None, None, None)
    out = flash_attention(q, k, v, causal=causal, window=window,
                          q_positions=positions if kv_x is None else None,
                          kv_positions=positions if kv_x is None else None,
                          scale=scale, remat_q_blocks=remat_q_blocks)
    if feature("seqpar"):
        out = shard(out, "batch", "model", None, None)
    return jnp.einsum("bsnh,nhd->bsd", out, params["wo"])


def decode_attention_block(params, x, k_cache, v_cache, cache_len, *,
                           rope_theta, window=0, use_rope=True,
                           update_cache=True, scale=None):
    """Decode sub-block: project 1 token, append to cache, attend.

    With the ``ringkv`` lever active and a cache sized to the sliding
    window, the cache is a ring buffer: K carries RoPE from its true
    position, so scores stay correct and no window mask is needed —
    the ring structurally IS the window. Returns (out, new caches).
    """
    B = x.shape[0]
    S_cache = k_cache.shape[1]
    ring = feature("ringkv") and window and S_cache == window
    q = jnp.einsum("bsd,dnh->bsnh", x, params["wq"])
    k = jnp.einsum("bsd,dnh->bsnh", x, params["wk"])
    v = jnp.einsum("bsd,dnh->bsnh", x, params["wv"])
    pos = jnp.full((B, 1), cache_len, dtype=jnp.int32)
    if use_rope:
        q = apply_rope(q, pos, rope_theta)
        k = apply_rope(k, pos, rope_theta)
    write_at = jax.lax.rem(cache_len, S_cache) if ring else cache_len
    if update_cache:
        k_cache = jax.lax.dynamic_update_slice_in_dim(
            k_cache, k.astype(k_cache.dtype), write_at, axis=1)
        v_cache = jax.lax.dynamic_update_slice_in_dim(
            v_cache, v.astype(v_cache.dtype), write_at, axis=1)
    if ring:
        valid = jnp.minimum(cache_len + 1, S_cache)
        out = decode_attention(q, k_cache, v_cache, valid, window=0,
                               scale=scale)
    else:
        out = decode_attention(q, k_cache, v_cache, cache_len + 1,
                               window=window, scale=scale)
    out = jnp.einsum("bsnh,nhd->bsd", out, params["wo"])
    return out, k_cache, v_cache
