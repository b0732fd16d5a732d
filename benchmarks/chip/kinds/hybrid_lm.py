"""Plain float32 reference and work counts of a granite-4.0-h style hybrid
language model (``"kind": "hybrid_lm"``): Mamba-2 and GQA attention
layers as the config's ``layer_types`` lists them, each followed by a
SwiGLU MLP, with embedding, residual, attention and logit multipliers,
and the embeddings tied to the head. The config uses the published
config.json's key names. Nothing here imports the program.

Each layer computes

    h  = x + r * mixer(RMSNorm(x))
    x' = h + r * MLP(RMSNorm(h))

with r = ``residual_multiplier``. The Mamba-2 mixer's SSD is the
quadratic masked-decay form ``y = (L o C B^T)(dt x) + D x``, with
``L[t, s] = exp(sum_{r=s+1..t} dt_r A)`` for s <= t; attention is a
plain causal softmax with no positional encoding and scores scaled by
``attention_multiplier``. Both run over blocks of ``block`` query rows,
each rematerialised in the backward pass, so that a 4,096-token
sequence fits one chip beside the weights. The decay sums are taken from
the block's first row outwards, so that no two large cumulative sums are
subtracted.

Departures from the published model, shared with the program: RMSNorm
weights are offsets from one (``x_hat * (1 + w)``, zero at init); the
SwiGLU input projection is two matrices (``w_gate``, ``w_up``) and the
Mamba-2 input projection five (``w_z``, ``w_x``, ``w_B``, ``w_C``,
``w_dt``), where the published model concatenates each into one;
weights come from the seed. The parameter tree is the program's:
``embed``, ``final_norm`` and ``layers``, a list of dicts with
``norm1``, ``norm2``, ``mlp`` and ``mamba`` or ``attn``.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

#: query rows per block of the reference's SSD and attention
BLOCK = 256


def layer_types(cfg):
    return cfg["layer_types"][:cfg["num_hidden_layers"]]


def _dims(cfg):
    d = cfg["hidden_size"]
    heads = cfg["num_attention_heads"]
    return dict(
        d=d, heads=heads, kv=cfg["num_key_value_heads"], hd=d // heads,
        ff=cfg["shared_intermediate_size"], v=cfg["vocab_size"],
        di=cfg["mamba_expand"] * d, n=cfg["mamba_d_state"],
        nh=cfg["mamba_n_heads"], ph=cfg["mamba_d_head"],
        w=cfg["mamba_d_conv"], chunk=cfg["mamba_chunk_size"])


def _normal(key, shape):
    """The program's initializer: normal, std 1/sqrt(shape[0])."""
    return jax.random.normal(key, shape, jnp.float32) / math.sqrt(shape[0])


def init(cfg, key):
    """Seeded float32 weights in the program's tree layout."""
    m = _dims(cfg)
    d, conv_dim = m["d"], m["di"] + 2 * m["n"]
    keys = iter(jax.random.split(key, 16 * cfg["num_hidden_layers"] + 2))
    layers = []
    for kind in layer_types(cfg):
        p = {"norm1": jnp.zeros((d,), jnp.float32),
             "norm2": jnp.zeros((d,), jnp.float32),
             "mlp": {"w_gate": _normal(next(keys), (d, m["ff"])),
                     "w_up": _normal(next(keys), (d, m["ff"])),
                     "w_down": _normal(next(keys), (m["ff"], d))}}
        if kind == "attention":
            p["attn"] = {
                "wq": _normal(next(keys), (d, m["heads"], m["hd"])),
                "wk": _normal(next(keys), (d, m["kv"], m["hd"])),
                "wv": _normal(next(keys), (d, m["kv"], m["hd"])),
                "wo": _normal(next(keys), (m["heads"], m["hd"], d))}
        else:
            p["mamba"] = {
                "w_z": _normal(next(keys), (d, m["di"])),
                "w_x": _normal(next(keys), (d, m["di"])),
                "w_B": _normal(next(keys), (d, m["n"])),
                "w_C": _normal(next(keys), (d, m["n"])),
                "w_dt": _normal(next(keys), (d, m["nh"])),
                "dt_bias": jnp.zeros((m["nh"],), jnp.float32),
                "A_log": jnp.log(jnp.linspace(1.0, 16.0, m["nh"],
                                              dtype=jnp.float32)),
                "D": jnp.ones((m["nh"],), jnp.float32),
                "conv_w": _normal(next(keys), (m["w"], conv_dim)),
                "conv_b": jnp.zeros((conv_dim,), jnp.float32),
                "gate_norm": jnp.zeros((m["di"],), jnp.float32),
                "w_out": _normal(next(keys), (m["di"], d))}
        layers.append(p)
    return {"embed": _normal(next(keys), (m["v"], d)),
            "final_norm": jnp.zeros((d,), jnp.float32), "layers": layers}


# -- counts ----------------------------------------------------------------------

def _matmul_params(cfg):
    """(mamba mixer, attention mixer, MLP) weights that multiply a token."""
    m = _dims(cfg)
    mamba = m["d"] * (2 * m["di"] + 2 * m["n"] + m["nh"]) + m["di"] * m["d"]
    attn = 2 * m["d"] * m["hd"] * (m["heads"] + m["kv"])
    return mamba, attn, 3 * m["d"] * m["ff"]


def param_count(cfg) -> int:
    m = _dims(cfg)
    mamba, attn, mlp = _matmul_params(cfg)
    conv_dim = m["di"] + 2 * m["n"]
    mamba += (m["w"] + 1) * conv_dim + 3 * m["nh"] + m["di"]
    total = m["v"] * m["d"] + m["d"]
    for kind in layer_types(cfg):
        total += (attn if kind == "attention" else mamba) + mlp + 2 * m["d"]
    return total


def update_bytes(cfg) -> int:
    """HBM bytes of one elementwise update of every parameter, as the
    server's interpolation makes it: two float32 trees read, one
    written."""
    return 3 * 4 * param_count(cfg)


def train_flops(cfg, seq: int) -> int:
    """One sequence's forward and backward pass (3 x the forward): every
    weight that multiplies a token (the tied head included) at 2 FLOPs a
    multiply-add; causal attention's scores and weighted values (half of
    the S x S products); and the chunked SSD: within each chunk the
    causal half of C B^T and of its product with x, and the chunk
    states' write and read (2 x S x heads x head size x state each)."""
    m = _dims(cfg)
    mamba, attn, mlp = _matmul_params(cfg)
    fwd = 2 * seq * m["v"] * m["d"]
    for kind in layer_types(cfg):
        fwd += 2 * seq * ((attn if kind == "attention" else mamba) + mlp)
        if kind == "attention":
            fwd += 2 * seq * seq * m["heads"] * m["hd"]
        else:
            chunk = min(m["chunk"], seq)
            fwd += seq * chunk * (m["n"] + m["nh"] * m["ph"])
            fwd += 4 * seq * m["nh"] * m["ph"] * m["n"]
    return 3 * fwd


# -- the reference -----------------------------------------------------------------

def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * (1.0 + w)


def swiglu(p, u):
    return (jax.nn.silu(u @ p["w_gate"]) * (u @ p["w_up"])) @ p["w_down"]


def causal_conv(u, w, b):
    """out[t] = sum_i u[t - i] w[W-1-i] + b; u: (S, C), w: (W, C)."""
    width, s = w.shape[0], u.shape[0]
    padded = jnp.pad(u, ((width - 1, 0), (0, 0)))
    return sum(padded[i:i + s] * w[i] for i in range(width)) + b


def _starts(s, block):
    block = min(block, s)
    if s % block:
        raise ValueError(f"sequence {s} is not a multiple of block {block}")
    return block, jnp.arange(0, s, block)


def ssd(x, dt, a, bm, cm, d, block):
    """y[t] = sum_{s <= t} (C_t . B_s) L[t, s] dt_s x_s + D x_t.
    x: (S, H, P); dt: (S, H); a, d: (H,); bm, cm: (S, N)."""
    s_len = x.shape[0]
    block, starts = _starts(s_len, block)
    da = dt * a
    xdt = x * dt[..., None]
    pos = jnp.arange(s_len)

    @functools.partial(jax.checkpoint, prevent_cse=False)
    def rows(t0):
        t = t0 + jnp.arange(block)
        # decay exponents from row t0: the sum over (s, t0] for s <= t0,
        # minus the sum over (t0, s] for s > t0
        upto = jnp.where((pos <= t0)[:, None], da, 0)
        after = jnp.cumsum(da - upto, axis=0)
        rev = jnp.cumsum(upto[::-1], axis=0)[::-1]
        before = jnp.concatenate([rev[1:], jnp.zeros_like(rev[:1])])
        expo = after[t][:, None, :] + (before - after)[None]
        causal = (pos[None, :] <= t[:, None])[..., None]
        decay = jnp.exp(jnp.where(causal, expo, -jnp.inf))
        w = (cm[t] @ bm.T)[..., None] * decay
        return jnp.einsum("tsh,shp->thp", w, xdt) + d[:, None] * x[t]

    return jax.lax.map(rows, starts).reshape(x.shape)


def mamba_mixer(cfg, p, u, block):
    s_len = u.shape[0]
    n, ph = cfg["mamba_d_state"], cfg["mamba_d_head"]
    di = p["w_x"].shape[1]
    z = u @ p["w_z"]
    xbc = jnp.concatenate([u @ p["w_x"], u @ p["w_B"], u @ p["w_C"]], -1)
    xbc = jax.nn.silu(causal_conv(xbc, p["conv_w"], p["conv_b"]))
    xin, bm, cm = xbc[:, :di], xbc[:, di:di + n], xbc[:, di + n:]
    dt = jax.nn.softplus(u @ p["w_dt"] + p["dt_bias"])
    y = ssd(xin.reshape(s_len, -1, ph), dt, -jnp.exp(p["A_log"]), bm, cm,
            p["D"], block)
    y = rms_norm(y.reshape(s_len, di) * jax.nn.silu(z), p["gate_norm"],
                 cfg["rms_norm_eps"])
    return y @ p["w_out"]


def attention(cfg, p, u, block):
    s_len = u.shape[0]
    block, starts = _starts(s_len, block)
    rep = cfg["num_attention_heads"] // cfg["num_key_value_heads"]
    q = jnp.einsum("sd,dnh->snh", u, p["wq"])
    k = jnp.repeat(jnp.einsum("sd,dnh->snh", u, p["wk"]), rep, 1)
    v = jnp.repeat(jnp.einsum("sd,dnh->snh", u, p["wv"]), rep, 1)
    pos = jnp.arange(s_len)

    @functools.partial(jax.checkpoint, prevent_cse=False)
    def rows(t0):
        t = t0 + jnp.arange(block)
        scores = jnp.einsum("tnh,snh->nts", q[t], k) \
            * cfg["attention_multiplier"]
        scores = jnp.where(pos[None, None, :] <= t[None, :, None], scores,
                           -jnp.inf)
        return jnp.einsum("nts,snh->tnh", jax.nn.softmax(scores, -1), v)

    out = jax.lax.map(rows, starts).reshape(q.shape)
    return jnp.einsum("snh,nhd->sd", out, p["wo"])


def layer(cfg, kind, p, x, block, residual):
    eps = cfg["rms_norm_eps"]
    u = rms_norm(x, p["norm1"], eps)
    mixed = (attention(cfg, p["attn"], u, block) if kind == "attention"
             else mamba_mixer(cfg, p["mamba"], u, block))
    h = x + residual * mixed
    return h + residual * swiglu(p["mlp"], rms_norm(h, p["norm2"], eps))


def sequence_loss(cfg, params, tokens, labels, block=BLOCK, residual=None):
    """Mean next-token NLL of one (S,) sequence over its labels >= 0.
    ``residual`` replaces ``residual_multiplier`` (a planted fault)."""
    if residual is None:
        residual = cfg["residual_multiplier"]
    x = params["embed"][tokens] * cfg["embedding_multiplier"]
    for kind, p in zip(layer_types(cfg), params["layers"]):
        x = jax.checkpoint(functools.partial(layer, cfg, kind, block=block,
                                             residual=residual))(p, x)
    x = rms_norm(x, params["final_norm"], cfg["rms_norm_eps"])
    logits = ((x @ params["embed"].T) / cfg["logits_scaling"]) \
        .astype(jnp.float32)
    valid = labels >= 0
    gold = jnp.take_along_axis(logits, jnp.maximum(labels, 0)[:, None],
                               -1)[:, 0]
    nll = jax.nn.logsumexp(logits, -1) - gold
    return jnp.sum(jnp.where(valid, nll, 0.0)) / jnp.maximum(valid.sum(), 1)


def loss(cfg, params, batch):
    """Mean NLL of a batch of one sequence, ``{"x": (1, S), "y": (1, S)}``:
    the form of a streamed sample."""
    return sequence_loss(cfg, params, batch["x"][0], batch["y"][0])


@functools.lru_cache(maxsize=None)
def _steps(cfg_items, dtype, block):
    cfg = dict(cfg_items)
    cfg["layer_types"] = list(cfg["layer_types"])
    precision = "highest" if dtype == jnp.dtype(jnp.float32) else "default"

    @functools.partial(jax.jit, donate_argnums=(0,))
    def sgd(p, tokens, labels, beta, residual):
        with jax.default_matmul_precision(precision):
            lo, g = jax.value_and_grad(
                lambda q: sequence_loss(cfg, q, tokens, labels, block,
                                        residual.astype(dtype)))(p)
        return jax.tree.map(lambda w, gg: w - beta.astype(w.dtype) * gg,
                            p, g), lo

    @functools.partial(jax.jit, donate_argnums=(0,))
    def add(total, p):
        return jax.tree.map(lambda t, q: t + q.astype(jnp.float32), total, p)

    @functools.partial(jax.jit, donate_argnums=(1,))
    def server(phi, total, alpha, clients):
        return jax.tree.map(
            lambda w, t: (w + alpha.astype(w.dtype)
                          * ((t / clients).astype(w.dtype) - w)), phi, total)

    copy = jax.jit(lambda t: jax.tree.map(jnp.copy, t))
    zeros = jax.jit(lambda t: jax.tree.map(
        lambda w: jnp.zeros(w.shape, jnp.float32), t))
    return sgd, add, server, copy, zeros


def _freeze(cfg):
    return tuple(sorted((k, tuple(v) if isinstance(v, list) else v)
                        for k, v in cfg.items()
                        if isinstance(v, (int, float, str, list))))


def tinyreptile(cfg, phi0, calls, beta, dtype=jnp.float32, block=BLOCK,
                half_cohort=False, residual=None):
    """TinyReptile client by client over the calls ``calls`` (each a dict
    of the support block ``x``, ``y`` (rounds, clients, support, S) and
    the per-round ``alpha``), from ``phi0``: each client takes one SGD step
    of rate ``beta`` per streamed sequence from the broadcast phi, and the
    server moves phi by alpha toward the clients' mean. float32 runs at
    the highest matmul precision; bfloat16 is the control. Returns, per
    call, phi after it (float32, on the host) and its per-round mean
    client loss. ``half_cohort`` plants a fault: each round aggregates the
    first half of its clients only; ``residual`` replaces the residual
    multiplier."""
    dtype = jnp.dtype(dtype)
    sgd, add, server, copy, zeros = _steps(_freeze(cfg), dtype, int(block))
    phi = jax.tree.map(lambda a: jnp.asarray(a, dtype), phi0)
    beta = jnp.float32(beta)
    residual = jnp.float32(cfg["residual_multiplier"] if residual is None
                           else residual)
    out = []
    for c in calls:
        losses = []
        for r in range(c["x"].shape[0]):
            clients = c["x"].shape[1] // (2 if half_cohort else 1)
            total, round_losses = zeros(phi), []
            for i in range(clients):
                p = copy(phi)
                for k in range(c["x"].shape[2]):
                    p, lo = sgd(p, jnp.asarray(c["x"][r, i, k]),
                                jnp.asarray(c["y"][r, i, k]), beta,
                                residual)
                    round_losses.append(lo)
                total = add(total, p)
                del p
            phi = server(phi, total, jnp.float32(c["alpha"][r]),
                         jnp.float32(clients))
            losses.append(float(np.mean([float(x) for x in round_losses])))
        out.append((jax.tree.map(lambda a: np.asarray(a, np.float32), phi),
                    np.asarray(losses, np.float64)))
    return out
