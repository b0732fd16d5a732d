"""Model assembly: decoder LMs (dense / MoE / SSM / hybrid / VLM) and the
whisper encoder-decoder, built from an ArchConfig.

Homogeneous layer stacks use scan-over-layers (params stacked over
pattern groups) to keep HLO compact; small / irregular stacks (whisper,
zamba2 hybrid, a single period of a published ``layer_types`` list) use
python loops, whose blocks the training loss rematerialises one by one.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import (ATTN, MAMBA, MAMBA_MLP, MOE, SHARED_ATTN,
                                ArchConfig)
from repro.models import attention as attn_lib
from repro.models import mamba2 as mamba_lib
from repro.models import moe as moe_lib
from repro.models.layers import init_mlp, mlp, normal_init, rms_norm
from repro.runtime.shardctx import shard

AUX_LOSS_WEIGHT = 0.01
LABEL_IGNORE = -1


# ---------------------------------------------------------------------------
# layer spec / scan-pattern machinery
# ---------------------------------------------------------------------------

def layer_specs(cfg: ArchConfig) -> List[Tuple[str, int]]:
    """Per-application (kind, window) list, including SHARED_ATTN entries."""
    specs = []
    attn_idx = 0  # index among attention layers, for global_attn_every
    for kind in cfg.block_kinds():
        if kind in (ATTN, MOE):
            window = cfg.sliding_window
            if cfg.global_attn_every and (attn_idx + 1) % cfg.global_attn_every == 0:
                window = 0  # periodic global layer (llama4 iRoPE)
            attn_idx += 1
            specs.append((kind, window))
        elif kind == SHARED_ATTN:
            specs.append((SHARED_ATTN, cfg.sliding_window))
        else:
            specs.append((kind, 0))
    return specs


def find_period(specs: List[Tuple[str, int]]) -> int:
    L = len(specs)
    for p in range(1, L + 1):
        if L % p == 0 and specs == specs[:p] * (L // p):
            return p
    return L


def _sinusoidal(positions, d_model):
    """positions: (S,) or (B,S) -> (..., d_model) float32."""
    half = d_model // 2
    freqs = jnp.exp(-jnp.arange(half, dtype=jnp.float32)
                    * (jnp.log(10000.0) / max(half - 1, 1)))
    ang = positions[..., None].astype(jnp.float32) * freqs
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


# ---------------------------------------------------------------------------
# per-block init / apply
# ---------------------------------------------------------------------------

def _init_block(cfg: ArchConfig, kind: str, key, *, cross: bool) -> Dict[str, Any]:
    d, dtype = cfg.d_model, jnp.dtype(cfg.dtype)
    hd = cfg.resolved_head_dim
    ks = jax.random.split(key, 6)
    if kind in (MAMBA, MAMBA_MLP):
        p = {
            "norm1": jnp.zeros((d,), dtype),
            "mamba": mamba_lib.init_mamba(
                ks[0], d, cfg.ssm_state, cfg.ssm_head_dim, cfg.ssm_expand,
                cfg.ssm_conv_width, dtype),
        }
        if kind == MAMBA_MLP:
            p["norm2"] = jnp.zeros((d,), dtype)
            p["mlp"] = init_mlp(ks[2], d, cfg.d_ff, cfg.act, dtype)
        return p
    p = {
        "norm1": jnp.zeros((d,), dtype),
        "attn": attn_lib.init_attention(ks[0], d, cfg.num_heads,
                                        cfg.num_kv_heads, hd, dtype),
        "norm2": jnp.zeros((d,), dtype),
    }
    if cross:
        p["norm_x"] = jnp.zeros((d,), dtype)
        p["cross"] = attn_lib.init_attention(ks[1], d, cfg.num_heads,
                                             cfg.num_kv_heads, hd, dtype)
    if kind == MOE:
        p["moe"] = moe_lib.init_moe(ks[2], d, cfg.d_ff, cfg.num_experts,
                                    cfg.shared_expert, dtype)
    else:
        p["mlp"] = init_mlp(ks[2], d, cfg.d_ff, cfg.act, dtype)
    return p


def _residual(cfg: ArchConfig, x, h):
    """x + residual_multiplier * h (plain x + h at the default 1)."""
    if cfg.residual_multiplier == 1.0:
        return x + h
    return x + jnp.asarray(cfg.residual_multiplier, h.dtype) * h


def _apply_block(cfg: ArchConfig, kind: str, window: int, bp, x, *,
                 positions=None, enc_out=None, use_rope=True,
                 remat_q_blocks=False):
    """Forward one block (train/prefill). Returns (x, aux).
    ``remat_q_blocks``: see ``attention.flash_attention``."""
    eps = cfg.norm_eps
    aux = jnp.zeros((), jnp.float32)
    if kind in (MAMBA, MAMBA_MLP):
        h = mamba_lib.mamba_block(
            bp["mamba"], rms_norm(x, bp["norm1"], eps),
            d_state=cfg.ssm_state, head_dim=cfg.ssm_head_dim,
            expand=cfg.ssm_expand, conv_width=cfg.ssm_conv_width,
            chunk=cfg.ssm_chunk, norm_eps=eps)
        x = _residual(cfg, x, h)
        if kind == MAMBA:
            return x, aux
        y = mlp(bp["mlp"], rms_norm(x, bp["norm2"], eps), cfg.act)
        return _residual(cfg, x, y), aux
    h = attn_lib.attention_block(
        bp["attn"], rms_norm(x, bp["norm1"], eps),
        num_kv_heads=cfg.num_kv_heads, rope_theta=cfg.rope_theta,
        causal=True, window=window, positions=positions,
        use_rope=use_rope and cfg.use_rope,
        scale=cfg.attention_multiplier or None,
        remat_q_blocks=remat_q_blocks)
    h = shard(h, "batch", None, None)
    x = _residual(cfg, x, h)
    if "cross" in bp:
        c = attn_lib.attention_block(
            bp["cross"], rms_norm(x, bp["norm_x"], eps),
            num_kv_heads=cfg.num_kv_heads, rope_theta=cfg.rope_theta,
            causal=False, kv_x=enc_out, use_rope=False,
            remat_q_blocks=remat_q_blocks)
        x = x + c
    y_in = rms_norm(x, bp["norm2"], eps)
    if kind == MOE:
        y, aux = moe_lib.moe_block(bp["moe"], y_in,
                                   experts_per_token=cfg.experts_per_token)
    else:
        y = mlp(bp["mlp"], y_in, cfg.act)
    y = shard(y, "batch", None, None)
    return _residual(cfg, x, y), aux


# ---------------------------------------------------------------------------
# caches
# ---------------------------------------------------------------------------

def _attn_cache(cfg, batch, seq_len, dtype, stack: int = 0):
    hd = cfg.resolved_head_dim
    kv = cfg.num_kv_heads
    lead = (stack,) if stack else ()
    shape = lead + (batch, seq_len, kv, hd)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def _mamba_cache(cfg, batch, dtype, stack: int = 0):
    d_inner, nheads, conv_dim = mamba_lib.mamba_dims(
        cfg.d_model, cfg.ssm_expand, cfg.ssm_head_dim, cfg.ssm_state)
    lead = (stack,) if stack else ()
    return {
        "conv": jnp.zeros(lead + (batch, cfg.ssm_conv_width - 1, conv_dim),
                          dtype),
        "ssm": jnp.zeros(lead + (batch, nheads, cfg.ssm_head_dim,
                                 cfg.ssm_state), jnp.float32),
    }


# ---------------------------------------------------------------------------
# the Model
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Model:
    cfg: ArchConfig

    # ----- structure ------------------------------------------------------
    @property
    def specs(self):
        return layer_specs(self.cfg)

    @property
    def is_hybrid(self):
        """zamba2's layout: one weight-shared block every n Mamba blocks."""
        return self.cfg.hybrid_attn_every > 0

    @property
    def is_encdec(self):
        return self.cfg.encoder_layers > 0

    @property
    def use_scan(self):
        from repro.runtime.flags import probe_mode
        if probe_mode():
            return False  # unrolled for exact cost_analysis
        if self.is_hybrid or self.is_encdec:
            return False
        p = find_period(self.specs)
        return len(self.specs) // p >= 4

    # ----- init -----------------------------------------------------------
    def init(self, key) -> Dict[str, Any]:
        cfg = self.cfg
        dtype = jnp.dtype(cfg.dtype)
        keys = iter(jax.random.split(key, 4 * len(self.specs) + 16))
        params: Dict[str, Any] = {
            "embed": normal_init(next(keys), (cfg.vocab_size, cfg.d_model),
                                 1.0, dtype),
            "final_norm": jnp.zeros((cfg.d_model,), dtype),
        }
        if not cfg.tie_embeddings:
            params["lm_head"] = normal_init(
                next(keys), (cfg.d_model, cfg.vocab_size), 1.0, dtype)

        specs = self.specs
        cross = self.is_encdec
        if self.is_hybrid:
            k = cfg.hybrid_attn_every
            n_full, r = divmod(cfg.num_layers, k)
            params["shared_block"] = _init_block(cfg, ATTN, next(keys),
                                                 cross=False)
            stacks = []
            for pos in range(k):
                blocks = [_init_block(cfg, MAMBA, next(keys), cross=False)
                          for _ in range(n_full)]
                stacks.append(jax.tree.map(lambda *xs: jnp.stack(xs),
                                           *blocks))
            params["layers"] = stacks            # scan over n_full groups
            params["tail"] = [_init_block(cfg, MAMBA, next(keys), cross=False)
                              for _ in range(r)]
        elif self.use_scan:
            p = find_period(specs)
            n_groups = len(specs) // p
            stacks = []
            for pos in range(p):
                kind = specs[pos][0]
                blocks = [_init_block(cfg, kind, next(keys), cross=cross)
                          for _ in range(n_groups)]
                stacks.append(jax.tree.map(lambda *xs: jnp.stack(xs), *blocks))
            params["layers"] = stacks
        else:
            params["layers"] = [
                _init_block(cfg, kind, next(keys), cross=cross)
                for kind, _ in specs]

        if self.is_encdec:
            enc_blocks = [_init_block(cfg, ATTN, next(keys), cross=False)
                          for _ in range(cfg.encoder_layers)]
            params["encoder"] = {
                "layers": enc_blocks,
                "final_norm": jnp.zeros((cfg.d_model,), dtype),
            }
        if cfg.frontend == "vision":
            # stub projector: patch embeddings arrive at d_model already;
            # a learned affine keeps the projector a real (tiny) substrate.
            params["vision_proj"] = normal_init(
                next(keys), (cfg.d_model, cfg.d_model), 1.0, dtype)
        return params

    # ----- shared forward pieces ------------------------------------------
    def _encode(self, params, frames):
        """Whisper encoder over precomputed frame embeddings (stub frontend)."""
        cfg = self.cfg
        S = frames.shape[1]
        x = frames + _sinusoidal(jnp.arange(S), cfg.d_model).astype(frames.dtype)
        for bp in params["encoder"]["layers"]:
            h = attn_lib.attention_block(
                bp["attn"], rms_norm(x, bp["norm1"], cfg.norm_eps),
                num_kv_heads=cfg.num_kv_heads, rope_theta=cfg.rope_theta,
                causal=False, use_rope=False)
            x = x + h
            x = x + mlp(bp["mlp"], rms_norm(x, bp["norm2"], cfg.norm_eps),
                        cfg.act)
        return rms_norm(x, params["encoder"]["final_norm"], cfg.norm_eps)

    def _embed_inputs(self, params, batch):
        """Token (+frontend) embedding. Returns (x, enc_out, text_offset)."""
        cfg = self.cfg
        tokens = batch["tokens"]
        x = self._embed_tokens(params, tokens)
        x = shard(x, "batch", None, None)
        enc_out = None
        offset = 0
        if cfg.frontend == "vision" and "patch_embeds" in batch:
            patches = batch["patch_embeds"].astype(x.dtype) @ params["vision_proj"]
            x = jnp.concatenate([patches, x], axis=1)
            offset = patches.shape[1]
        if self.is_encdec:
            enc_out = self._encode(params, batch["frames"])
            x = x + _sinusoidal(
                jnp.arange(x.shape[1]), cfg.d_model).astype(x.dtype)
        return x, enc_out, offset

    def _embed_tokens(self, params, tokens):
        x = params["embed"][tokens]
        if self.cfg.embedding_multiplier != 1.0:
            x = x * jnp.asarray(self.cfg.embedding_multiplier, x.dtype)
        return x

    def _backbone(self, params, x, *, enc_out=None, remat=False):
        """Run all blocks. Returns (hidden, aux_loss). ``remat``
        rematerialises each block of an unscanned stack in the backward
        pass, so that only block inputs stay live."""
        cfg = self.cfg
        specs = self.specs
        use_rope = not self.is_encdec
        positions = jnp.arange(x.shape[1])

        if self.is_hybrid:
            from repro.runtime.flags import probe_mode
            k = cfg.hybrid_attn_every
            n_full, r = divmod(cfg.num_layers, k)
            shared = params["shared_block"]

            def group_body(carry, group_params):
                h, aux = carry
                # the weight-SHARED transformer block precedes each group
                h, a = _apply_block(cfg, ATTN, cfg.sliding_window, shared,
                                    h, positions=positions)
                for pos in range(k):
                    h, _ = _apply_block(cfg, MAMBA, 0, group_params[pos], h)
                return (h, aux + a), None

            carry = (x, jnp.zeros((), jnp.float32))
            if probe_mode():
                for g in range(n_full):
                    gp = [jax.tree.map(lambda a, i=g: a[i], s)
                          for s in params["layers"]]
                    carry, _ = group_body(carry, gp)
            else:
                body = jax.checkpoint(group_body, prevent_cse=False)
                carry, _ = jax.lax.scan(body, carry, params["layers"])
            x, aux = carry
            if r:
                x, a = _apply_block(cfg, ATTN, cfg.sliding_window, shared,
                                    x, positions=positions)
                aux = aux + a
                for bp in params["tail"]:
                    x, _ = _apply_block(cfg, MAMBA, 0, bp, x)
            return x, aux

        if self.use_scan:
            p = find_period(specs)
            pattern = specs[:p]

            def body(carry, group_params):
                h, aux = carry
                for pos, (kind, window) in enumerate(pattern):
                    h, a = _apply_block(cfg, kind, window, group_params[pos],
                                        h, positions=positions,
                                        use_rope=use_rope)
                    aux = aux + a
                return (h, aux), None

            body = jax.checkpoint(body, prevent_cse=False)
            (x, aux), _ = jax.lax.scan(
                body, (x, jnp.zeros((), jnp.float32)), params["layers"])
            return x, aux

        from repro.runtime.flags import probe_mode
        remat = remat and not probe_mode()
        aux = jnp.zeros((), jnp.float32)
        for bp, (kind, window) in zip(params["layers"], specs):
            block = functools.partial(
                _apply_block, cfg, kind, window, positions=positions,
                enc_out=enc_out, use_rope=use_rope, remat_q_blocks=remat)
            if remat:
                # unscanned: CSE must not merge the recomputation with
                # the forward pass, or every activation stays live
                block = jax.checkpoint(block)
            x, a = block(bp, x)
            aux = aux + a
        return x, aux

    def _lm_head(self, params):
        if self.cfg.tie_embeddings:
            return params["embed"].T
        return params["lm_head"]

    def _logits_input(self, x):
        """The final hidden state as the head sees it: divided by
        ``logits_scaling``, which divides the logits."""
        if self.cfg.logits_scaling == 1.0:
            return x
        return x / jnp.asarray(self.cfg.logits_scaling, x.dtype)

    # ----- training loss ---------------------------------------------------
    def loss_fn(self, params, batch):
        """Mean next-token cross-entropy (+ MoE aux)."""
        cfg = self.cfg
        x, enc_out, offset = self._embed_inputs(params, batch)
        x, aux = self._backbone(params, x, enc_out=enc_out, remat=True)
        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
        if offset:
            x = x[:, offset:]
        labels = batch["labels"]
        loss = chunked_cross_entropy(self._logits_input(x),
                                     self._lm_head(params), labels)
        return loss + AUX_LOSS_WEIGHT * aux

    # ----- prefill ----------------------------------------------------------
    def prefill_fn(self, params, batch):
        """Returns (last-token logits, populated attention KV caches).

        Caches are rebuilt by re-projecting K/V per layer (python loop over
        specs when not scanning; for scanned stacks, a scan emitting ys).
        For simplicity and HLO compactness the prefill path recomputes the
        backbone and extracts caches via a dedicated pass.
        """
        cfg = self.cfg
        x, enc_out, offset = self._embed_inputs(params, batch)
        x, _ = self._backbone(params, x, enc_out=enc_out)
        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
        last = self._logits_input(x[:, -1:])
        logits = (last @ self._lm_head(params)).astype(jnp.float32)
        return logits

    # ----- decode -----------------------------------------------------------
    def init_cache(self, batch_size, seq_len, dtype=None):
        """Decode cache. Scanned stacks get caches stacked over groups
        (written via scan ys — no O(L^2) copies); loop archs get per-layer
        lists (updated by element — no copies at all)."""
        cfg = self.cfg
        dtype = dtype or jnp.dtype(cfg.dtype)
        specs = self.specs
        from repro.runtime.flags import feature

        def eff_seq(window):
            if feature("ringkv") and window:
                return min(seq_len, window)   # ring buffer = the window
            return seq_len

        if self.is_hybrid:
            k = cfg.hybrid_attn_every
            n_full, r = divmod(cfg.num_layers, k)
            cache = {
                "group_attn": _attn_cache(cfg, batch_size,
                                          eff_seq(cfg.sliding_window),
                                          dtype, stack=n_full),
                "group_mamba": [_mamba_cache(cfg, batch_size, dtype,
                                             stack=n_full)
                                for _ in range(k)],
            }
            if r:
                cache["tail_attn"] = _attn_cache(cfg, batch_size,
                                                 eff_seq(cfg.sliding_window),
                                                 dtype)
                cache["tail_mamba"] = [_mamba_cache(cfg, batch_size, dtype)
                                       for _ in range(r)]
            return cache
        if self.use_scan:
            p = find_period(specs)
            n_groups = len(specs) // p
            layers = []
            for kind, window in specs[:p]:
                if kind in (MAMBA, MAMBA_MLP):
                    layers.append(_mamba_cache(cfg, batch_size, dtype,
                                               stack=n_groups))
                else:
                    layers.append(_attn_cache(cfg, batch_size,
                                              eff_seq(window), dtype,
                                              stack=n_groups))
            return {"layers": layers}
        layers = []
        for kind, window in specs:
            if kind in (MAMBA, MAMBA_MLP):
                layers.append(_mamba_cache(cfg, batch_size, dtype))
            else:
                layers.append(_attn_cache(cfg, batch_size, eff_seq(window),
                                          dtype))
        cache = {"layers": layers}
        if self.is_encdec:
            hd = cfg.resolved_head_dim
            n = len(specs)
            cache["cross"] = [
                {"k": jnp.zeros((batch_size, cfg.encoder_tokens,
                                 cfg.num_kv_heads, hd), dtype),
                 "v": jnp.zeros((batch_size, cfg.encoder_tokens,
                                 cfg.num_kv_heads, hd), dtype)}
                for _ in range(n)]
        return cache

    def _decode_block(self, kind, window, bp, x, cache_entry, cache_len,
                      cross_entry=None):
        """Apply one decode block. Returns (x, new_cache_entry)."""
        cfg = self.cfg
        eps = cfg.norm_eps
        if kind in (MAMBA, MAMBA_MLP):
            h = rms_norm(x, bp["norm1"], eps)
            out, conv_new, ssm_new = mamba_lib.mamba_decode_block(
                bp["mamba"], h, cache_entry["conv"], cache_entry["ssm"],
                d_state=cfg.ssm_state, head_dim=cfg.ssm_head_dim,
                expand=cfg.ssm_expand, conv_width=cfg.ssm_conv_width,
                norm_eps=eps)
            x = _residual(cfg, x, out)
            if kind == MAMBA_MLP:
                x = _residual(cfg, x, mlp(
                    bp["mlp"], rms_norm(x, bp["norm2"], eps), cfg.act))
            return x, {"conv": conv_new, "ssm": ssm_new}
        h = rms_norm(x, bp["norm1"], eps)
        out, k_new, v_new = attn_lib.decode_attention_block(
            bp["attn"], h, cache_entry["k"], cache_entry["v"], cache_len,
            rope_theta=cfg.rope_theta, window=window,
            use_rope=not self.is_encdec and cfg.use_rope,
            scale=cfg.attention_multiplier or None)
        x = _residual(cfg, x, out)
        if "cross" in bp and cross_entry is not None:
            q = jnp.einsum("bsd,dnh->bsnh",
                           rms_norm(x, bp["norm_x"], eps), bp["cross"]["wq"])
            c = attn_lib.decode_attention(q, cross_entry["k"],
                                          cross_entry["v"],
                                          cfg.encoder_tokens)
            x = x + jnp.einsum("bsnh,nhd->bsd", c, bp["cross"]["wo"])
        y_in = rms_norm(x, bp["norm2"], eps)
        if kind == MOE:
            y, _ = moe_lib.moe_block(bp["moe"], y_in,
                                     experts_per_token=cfg.experts_per_token)
        else:
            y = mlp(bp["mlp"], y_in, cfg.act)
        return _residual(cfg, x, y), {"k": k_new, "v": v_new}

    def decode_fn(self, params, batch):
        """One decode step. batch: tokens (B,1), cache, cache_len (scalar).

        Returns (logits (B,1,V) fp32, new cache).
        """
        cfg = self.cfg
        eps = cfg.norm_eps
        tokens, cache, cache_len = (batch["tokens"], batch["cache"],
                                    batch["cache_len"])
        x = self._embed_tokens(params, tokens)
        if self.is_encdec:
            pos = jnp.full((tokens.shape[0], 1), cache_len)
            x = x + _sinusoidal(pos, cfg.d_model).astype(x.dtype)
        specs = self.specs

        if self.is_hybrid:
            from repro.runtime.flags import probe_mode
            k = cfg.hybrid_attn_every
            n_full, r = divmod(cfg.num_layers, k)
            shared = params["shared_block"]
            window = cfg.sliding_window

            def group_body(h, xs):
                group_params, gattn, gmamba = xs
                h, attn_entry = self._decode_block(
                    ATTN, window, shared, h, gattn, cache_len)
                new_m = []
                for pos in range(k):
                    h, e = self._decode_block(
                        MAMBA, 0, group_params[pos], h, gmamba[pos],
                        cache_len)
                    new_m.append(e)
                return h, (attn_entry, new_m)

            xs = (params["layers"], cache["group_attn"],
                  cache["group_mamba"])
            if probe_mode():
                new_attn, new_mamba = [], []
                for g in range(n_full):
                    gxs = jax.tree.map(lambda a, i=g: a[i], xs)
                    x, (ae, me) = group_body(x, gxs)
                    new_attn.append(ae)
                    new_mamba.append(me)
                new_attn = jax.tree.map(lambda *v: jnp.stack(v), *new_attn)
                new_mamba = jax.tree.map(lambda *v: jnp.stack(v), *new_mamba)
            else:
                x, (new_attn, new_mamba) = jax.lax.scan(group_body, x, xs)
            new_cache = {"group_attn": new_attn, "group_mamba": new_mamba}
            if r:
                x, te = self._decode_block(ATTN, window, shared, x,
                                           cache["tail_attn"], cache_len)
                new_cache["tail_attn"] = te
                new_tail = []
                for pos in range(r):
                    x, e = self._decode_block(
                        MAMBA, 0, params["tail"][pos], x,
                        cache["tail_mamba"][pos], cache_len)
                    new_tail.append(e)
                new_cache["tail_mamba"] = new_tail
            x = self._logits_input(rms_norm(x, params["final_norm"], eps))
            logits = (x @ self._lm_head(params)).astype(jnp.float32)
            return logits, new_cache

        if self.use_scan:
            p = find_period(specs)
            pattern = specs[:p]

            def body(h, xs):
                group_params, group_cache = xs
                new_entries = []
                for pos, (kind, window) in enumerate(pattern):
                    h, entry = self._decode_block(
                        kind, window, group_params[pos], h,
                        group_cache[pos], cache_len)
                    new_entries.append(entry)
                return h, new_entries

            x, new_layers = jax.lax.scan(
                body, x, (params["layers"], cache["layers"]))
            new_cache = {"layers": new_layers}
        else:
            new_layers = list(cache["layers"])
            for li, (kind, window) in enumerate(specs):
                bp = (params["shared_block"] if kind == SHARED_ATTN else
                      self._layer_params(params, li, kind))
                cross_entry = (cache["cross"][li]
                               if self.is_encdec else None)
                x, new_layers[li] = self._decode_block(
                    kind, window, bp, x, cache["layers"][li], cache_len,
                    cross_entry=cross_entry)
            new_cache = dict(cache)
            new_cache["layers"] = new_layers

        x = self._logits_input(rms_norm(x, params["final_norm"], eps))
        logits = (x @ self._lm_head(params)).astype(jnp.float32)
        return logits, new_cache

    # ----- helpers ----------------------------------------------------------
    def _layer_params(self, params, layer_idx, kind):
        """Fetch per-layer params regardless of storage layout."""
        specs = self.specs
        if self.is_hybrid:
            mi = sum(1 for k, _ in specs[:layer_idx] if k == MAMBA)
            k = self.cfg.hybrid_attn_every
            group, pos = divmod(mi, k)
            n_full = self.cfg.num_layers // k
            if group >= n_full:
                return params["tail"][mi - n_full * k]
            return jax.tree.map(lambda a: a[group], params["layers"][pos])
        if self.use_scan:
            p = find_period(specs)
            group, pos = divmod(layer_idx, p)
            return jax.tree.map(lambda a: a[group], params["layers"][pos])
        return params["layers"][layer_idx]


def chunked_cross_entropy(x, lm_head, labels, chunk=1024):
    """Memory-efficient CE: scan over sequence chunks, recompute logits in
    the backward pass (jax.checkpoint). x: (B,S,d); labels: (B,S)."""
    from repro.runtime.flags import probe_mode
    B, S, d = x.shape
    chunk = S if probe_mode() else min(chunk, S)
    pad = (-S) % chunk
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
        labels = jnp.pad(labels, ((0, 0), (0, pad)),
                         constant_values=LABEL_IGNORE)
    nch = x.shape[1] // chunk
    xs = x.reshape(B, nch, chunk, d).transpose(1, 0, 2, 3)
    ls = labels.reshape(B, nch, chunk).transpose(1, 0, 2)

    @functools.partial(jax.checkpoint, prevent_cse=False)
    def body(carry, inp):
        total, count = carry
        xc, lc = inp
        logits = (xc @ lm_head).astype(jnp.float32)
        logz = jax.scipy.special.logsumexp(logits, axis=-1)
        safe = jnp.maximum(lc, 0)
        gold = jnp.take_along_axis(logits, safe[..., None], axis=-1)[..., 0]
        valid = (lc != LABEL_IGNORE)
        nll = jnp.where(valid, logz - gold, 0.0)
        return (total + nll.sum(), count + valid.sum()), None

    (total, count), _ = jax.lax.scan(
        body, (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.int32)),
        (xs, ls))
    return total / jnp.maximum(count, 1)


def build_model(cfg: ArchConfig) -> Model:
    return Model(cfg)
