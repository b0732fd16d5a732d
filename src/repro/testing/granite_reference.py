"""Plain float32 reference of granite-4.0-h (hybrid Mamba-2 + GQA) and of
TinyReptile over it, for tests against the program.

Straightforward ``jax.numpy`` under ``jax.default_matmul_precision
("highest")``: no cache, no kernel, no chunked scan, no import of
``repro.models``. Each layer of ``layer_types`` computes

    h  = x + r * mixer(RMSNorm(x))
    x' = h + r * MLP(RMSNorm(h))

with r = ``residual_multiplier``; the mixer is Mamba-2 or causal GQA
attention with no positional encoding and scores scaled by
``attention_multiplier``; the MLP is SwiGLU. Embeddings are scaled by
``embedding_multiplier`` and the tied head's logits divided by
``logits_scaling``.

The Mamba-2 mixer's SSD is written in its quadratic masked-decay form,
``y = (L o C B^T)(dt x) + D x`` with ``L[t, s] = exp(sum_{r=s+1..t} dt_r
A)`` for s <= t, and attention as a plain softmax; both run over blocks
of ``block`` query rows, each rematerialised in the backward pass, so
that long sequences fit. The decay sums are taken from the block's first
row outwards, so that no two large cumulative sums are subtracted.

Departures from the published model, all shared with the program:

- RMSNorm weights are stored as offsets from one (``x_hat * (1 + w)``,
  zero at init); the published model stores the factor itself.
- The SwiGLU input projection is two matrices, ``w_gate`` and ``w_up``,
  where the published model concatenates them into one; the Mamba-2
  input projection is five (``w_z``, ``w_x``, ``w_B``, ``w_C``,
  ``w_dt``) where it has one. The functions are the same.
- Weights come from a seed, not from the released checkpoint.

``spec`` is a dict with the published config's key names
(``spec_of`` builds it from an ``ArchConfig``); ``params`` is the
program's tree: ``embed``, ``final_norm`` and ``layers``, a list of
``norm1``, ``norm2``, ``mlp`` and ``mamba`` or ``attn`` dicts.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def spec_of(cfg) -> dict:
    """The published-config keys the reference reads, from an
    ``repro.configs.ArchConfig``."""
    return {
        "hidden_size": cfg.d_model, "vocab_size": cfg.vocab_size,
        "num_hidden_layers": cfg.num_layers,
        "layer_types": list(cfg.layer_types),
        "num_attention_heads": cfg.num_heads,
        "num_key_value_heads": cfg.num_kv_heads,
        "mamba_d_state": cfg.ssm_state, "mamba_d_head": cfg.ssm_head_dim,
        "mamba_d_conv": cfg.ssm_conv_width,
        "rms_norm_eps": cfg.norm_eps,
        "embedding_multiplier": cfg.embedding_multiplier,
        "residual_multiplier": cfg.residual_multiplier,
        "attention_multiplier": cfg.attention_multiplier,
        "logits_scaling": cfg.logits_scaling,
    }


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * (1.0 + w)


def swiglu(p, u):
    return (jax.nn.silu(u @ p["w_gate"]) * (u @ p["w_up"])) @ p["w_down"]


def causal_conv(u, w, b):
    """Depthwise causal convolution: out[t] = sum_i u[t - i] w[W-1-i] + b.
    u: (S, C); w: (W, C)."""
    width, s = w.shape[0], u.shape[0]
    padded = jnp.pad(u, ((width - 1, 0), (0, 0)))
    return sum(padded[i:i + s] * w[i] for i in range(width)) + b


def _blocks(s, block):
    block = min(block or s, s)
    if s % block:
        raise ValueError(f"sequence {s} is not a multiple of block {block}")
    return block, jnp.arange(0, s, block)


def ssd(x, dt, a, bm, cm, d, block):
    """y[t] = sum_{s <= t} (C_t . B_s) L[t, s] dt_s x_s + D x_t.
    x: (S, H, P); dt: (S, H); a, d: (H,); bm, cm: (S, N)."""
    s_len = x.shape[0]
    block, starts = _blocks(s_len, block)
    da = dt * a                                        # (S, H), <= 0
    xdt = x * dt[..., None]
    pos = jnp.arange(s_len)

    @functools.partial(jax.checkpoint, prevent_cse=False)
    def rows(t0):
        t = t0 + jnp.arange(block)
        # decay exponents measured from row t0: for s <= t0 the sum over
        # (s, t0], for s > t0 minus the sum over (t0, s]
        upto = jnp.where((pos <= t0)[:, None], da, 0.0)
        after = jnp.cumsum(da - upto, axis=0)
        rev = jnp.cumsum(upto[::-1], axis=0)[::-1]
        before = jnp.concatenate([rev[1:], jnp.zeros_like(rev[:1])])
        expo = after[t][:, None, :] + (before - after)[None]   # (Q, S, H)
        causal = (pos[None, :] <= t[:, None])[..., None]
        decay = jnp.exp(jnp.where(causal, expo, -jnp.inf))
        w = (cm[t] @ bm.T)[..., None] * decay
        return jnp.einsum("tsh,shp->thp", w, xdt) + d[:, None] * x[t]

    return jax.lax.map(rows, starts).reshape(x.shape)


def mamba_mixer(spec, p, u, block):
    s_len = u.shape[0]
    n, hd = spec["mamba_d_state"], spec["mamba_d_head"]
    d_inner = p["w_x"].shape[1]
    z = u @ p["w_z"]
    xbc = jnp.concatenate([u @ p["w_x"], u @ p["w_B"], u @ p["w_C"]], -1)
    xbc = jax.nn.silu(causal_conv(xbc, p["conv_w"], p["conv_b"]))
    xin, bm, cm = xbc[:, :d_inner], xbc[:, d_inner:d_inner + n], \
        xbc[:, d_inner + n:]
    dt = jax.nn.softplus(u @ p["w_dt"] + p["dt_bias"])
    y = ssd(xin.reshape(s_len, -1, hd), dt, -jnp.exp(p["A_log"]), bm, cm,
            p["D"], block)
    y = rms_norm(y.reshape(s_len, d_inner) * jax.nn.silu(z), p["gate_norm"],
                 spec["rms_norm_eps"])
    return y @ p["w_out"]


def attention(spec, p, u, block):
    """Causal GQA softmax attention, no positional encoding."""
    s_len = u.shape[0]
    block, starts = _blocks(s_len, block)
    heads, kv = spec["num_attention_heads"], spec["num_key_value_heads"]
    q = jnp.einsum("sd,dnh->snh", u, p["wq"])
    k = jnp.repeat(jnp.einsum("sd,dnh->snh", u, p["wk"]), heads // kv, 1)
    v = jnp.repeat(jnp.einsum("sd,dnh->snh", u, p["wv"]), heads // kv, 1)
    pos = jnp.arange(s_len)

    @functools.partial(jax.checkpoint, prevent_cse=False)
    def rows(t0):
        t = t0 + jnp.arange(block)
        scores = jnp.einsum("tnh,snh->nts", q[t], k) \
            * spec["attention_multiplier"]
        scores = jnp.where(pos[None, None, :] <= t[None, :, None], scores,
                           -jnp.inf)
        return jnp.einsum("nts,snh->tnh", jax.nn.softmax(scores, -1), v)

    out = jax.lax.map(rows, starts).reshape(q.shape)
    return jnp.einsum("snh,nhd->sd", out, p["wo"])


def layer(spec, kind, p, x, block):
    r, eps = spec["residual_multiplier"], spec["rms_norm_eps"]
    u = rms_norm(x, p["norm1"], eps)
    mixed = (attention(spec, p["attn"], u, block) if kind == "attention"
             else mamba_mixer(spec, p["mamba"], u, block))
    h = x + r * mixed
    return h + r * swiglu(p["mlp"], rms_norm(h, p["norm2"], eps))


def sequence_nll(spec, params, tokens, labels, block=None):
    """Summed next-token NLL of one sequence over labels >= 0, and their
    count. tokens, labels: (S,) int."""
    x = params["embed"][tokens] * spec["embedding_multiplier"]
    kinds = spec["layer_types"][:spec["num_hidden_layers"]]
    for kind, p in zip(kinds, params["layers"]):
        x = jax.checkpoint(functools.partial(layer, spec, kind,
                                             block=block))(p, x)
    x = rms_norm(x, params["final_norm"], spec["rms_norm_eps"])
    logits = (x @ params["embed"].T) / spec["logits_scaling"]
    valid = labels >= 0
    gold = jnp.take_along_axis(logits, jnp.maximum(labels, 0)[:, None],
                               -1)[:, 0]
    nll = jax.nn.logsumexp(logits, -1) - gold
    return jnp.sum(jnp.where(valid, nll, 0.0)), jnp.sum(valid)


def loss(spec, params, tokens, labels, block=None):
    """Mean next-token NLL over every valid label of a (B, S) batch."""
    total, count = 0.0, 0
    for b in range(tokens.shape[0]):
        t, c = sequence_nll(spec, params, tokens[b], labels[b], block)
        total, count = total + t, count + c
    return total / jnp.maximum(count, 1)


def tinyreptile(spec, phi, rounds, beta, block=None):
    """TinyReptile client by client: each client takes one SGD step of
    rate ``beta`` per streamed sequence from the broadcast phi; the server
    moves phi by alpha toward the clients' mean. ``rounds`` is a list of
    (tokens (C, K, S), labels (C, K, S), alpha). Returns phi after every
    round and each round's mean client loss."""
    step = _sgd_step(_freeze(spec), block)
    out = []
    with jax.default_matmul_precision("highest"):
        for tokens, labels, alpha in rounds:
            total, losses = None, []
            for c in range(tokens.shape[0]):
                p = phi
                for k in range(tokens.shape[1]):
                    p, lo = step(p, tokens[c, k], labels[c, k], beta)
                    losses.append(float(lo))
                total = p if total is None else jax.tree.map(jnp.add, total, p)
            mean = jax.tree.map(lambda t: t / tokens.shape[0], total)
            phi = jax.tree.map(lambda w, m: w + alpha * (m - w), phi, mean)
            out.append((phi, sum(losses) / len(losses)))
    return out


def _freeze(spec):
    return tuple(sorted((k, tuple(v) if isinstance(v, list) else v)
                        for k, v in spec.items()))


@functools.lru_cache(maxsize=None)
def _sgd_step(spec_items, block):
    spec = dict(spec_items)

    @jax.jit
    def step(p, tokens, labels, beta):
        lo, g = jax.value_and_grad(
            lambda q: loss(spec, q, tokens[None], labels[None], block))(p)
        return jax.tree.map(lambda w, gg: w - beta * gg, p, g), lo
    return step
