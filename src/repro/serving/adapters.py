"""Per-request adaptation routines behind the AdaptationServer.

An adapter defines the three pure functions the server vmaps across its
slots — everything else (admission, masking, retirement) is shared:

- ``prepare(phi_pack, sx, sy)``: one request's support set -> the slot
  pytree the unit step carries (params init + prepared support);
- ``unit_step(phi_pack, slot, step)``: ONE adaptation step at cursor
  ``step`` (an online-SGD sample step for fp32, a full int8 DFA epoch
  for tifed) -> (new slot, step loss);
- ``query_loss(phi_pack, slot, qx, qy)``: score the adapted params on
  the request's query set;
- ``finish(phi_pack, slot)``: slot -> the fp32 params pytree handed
  back to the client (dequantized for tifed).

``phi_pack = pack_phi(phi)`` is whatever adapter-specific device form
of the meta-learned init the tick consumes; it is passed as a traced
ARGUMENT to the server's jitted tick, so swapping phi (e.g. for a
checkpoint-loaded init) reuses the same trace.

Numerics contract (pinned in tests/test_serving.py): a served request
is bit-for-bit the one-shot vmapped offline adaptation at the same slot
width (`serving.offline_adapt`); the int8 route is additionally exactly
equal to the engine's scalar `TifedStrategy` epochs (integer-valued
fp32 math is vmap-width invariant), while the fp32 route matches the
scalar `finetune_online` API to ~1e-6 (vmap changes fp reduction
lowering — same contract as the engine's 1-vs-N-device parity).
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.engine import resolve_use_pallas
from repro.core.strategies import (TIFED_ACT, TIFED_EX, TIFED_SERR,
                                   _tifed_constants)
from repro.kernels import ref as kref
from repro.models.paper_nets import relu_mlp_loss


@dataclasses.dataclass(frozen=True)
class Fp32Adapter:
    """TinyReptile deployment loop: one SGD step per streamed support
    sample (`core.meta.finetune_online`'s exact update math), vmapped
    across slots. ``use_pallas`` routes the weight update through the
    fused `kernels/online_sgd.py` kernel (None = TPU only)."""
    loss_fn: Callable
    lr: float = 0.01
    use_pallas: Optional[bool] = None

    name = "fp32"

    def pack_phi(self, phi):
        return phi

    def prepare(self, phi, sx, sy):
        return {"params": phi, "sx": sx, "sy": sy}

    def unit_step(self, phi, slot, step):
        del phi
        i = jnp.clip(step, 0, slot["sx"].shape[0] - 1)
        x = jax.lax.dynamic_index_in_dim(slot["sx"], i, keepdims=False)
        y = jax.lax.dynamic_index_in_dim(slot["sy"], i, keepdims=False)
        batch = {"x": x[None], "y": y[None]}
        loss, g = jax.value_and_grad(self.loss_fn)(slot["params"], batch)
        if resolve_use_pallas(self.use_pallas):
            from repro.kernels import ops as kops
            params = kops.tree_online_sgd(slot["params"], g,
                                          jnp.float32(self.lr))
        else:
            params = jax.tree.map(lambda w, gg: w - self.lr * gg,
                                  slot["params"], g)
        return {**slot, "params": params}, loss

    def query_loss(self, phi, slot, qx, qy):
        del phi
        return self.loss_fn(slot["params"], {"x": qx, "y": qy})

    def finish(self, phi, slot):
        del phi
        return slot["params"]


@dataclasses.dataclass(frozen=True)
class TifedAdapter:
    """TIFeD int8 deployment loop: one adaptation step = one integer
    DFA epoch over the request's full support set (layer-cyclic, the
    same `kernels/ref.dfa_int8_epoch` / Pallas `online_sgd_int8` math
    the TifedStrategy trains with), so a tifed-trained phi adapts on
    exactly the arithmetic the training run promised. phi must sit on
    the tifed integer grid (`tifed_requantize` output / a tifed run's
    params). ``support`` and ``k_max`` are fixed per adapter: the
    quantized-scale prologue folds 1/support into the bit-shift rate
    and the per-epoch dither planes are baked for epochs < k_max.
    """
    support: int
    k_max: int
    lr_shift: int = 6
    feedback_seed: int = 0
    use_pallas: Optional[bool] = None

    name = "tifed"

    def pack_phi(self, phi):
        """Quantize phi once onto the int8/accumulator grids; the pack
        rides the tick as traced arrays (phi-swap keeps the trace)."""
        for i in range(3):
            if f"w{i}" not in phi or f"b{i}" not in phi:
                raise ValueError(
                    "TifedAdapter expects the paper MLP pytree "
                    f"{{w0,b0,w1,b1,w2,b2}}; got keys {sorted(phi)}")
        f32 = jnp.float32
        ws, ew = [], []
        for i in range(3):
            q, e = kref.quantize_pow2(phi[f"w{i}"])
            ws.append(q)
            ew.append(e)
        ea = (TIFED_EX, TIFED_ACT, TIFED_ACT)
        sacc = [ew[i] + ea[i] for i in range(3)]
        bs = [jnp.clip(jnp.round(phi[f"b{i}"]
                                 * jnp.exp2(-sacc[i].astype(f32))),
                       -kref.BIAS_MAX, kref.BIAS_MAX) for i in range(3)]
        n = self.support
        lrs = self.lr_shift + int(np.floor(np.log2(n)))
        scales = {
            "f0": jnp.exp2((sacc[0] - TIFED_ACT).astype(f32)),
            "f1": jnp.exp2((sacc[1] - TIFED_ACT).astype(f32)),
            "fe": jnp.exp2((sacc[2] - TIFED_SERR).astype(f32)),
            "floss": jnp.exp2(2.0 * sacc[2].astype(f32)) / n,
            "ftw": tuple(
                jnp.exp2((ea[i] + TIFED_SERR - ew[i] - lrs).astype(f32))
                for i in range(3)),
            "ftb": tuple(
                jnp.exp2((TIFED_SERR - sacc[i] - lrs).astype(f32))
                for i in range(3)),
        }
        dims = (phi["w0"].shape[0], phi["w0"].shape[1],
                phi["w1"].shape[1], phi["w2"].shape[1])
        fb_np, dith_np = _tifed_constants(self.feedback_seed, self.k_max,
                                          dims)
        return {"ws": tuple(ws), "bs": tuple(bs),
                "ew": tuple(e.astype(f32) for e in ew),
                "sacc": tuple(s.astype(f32) for s in sacc),
                "scales": scales,
                "fb": tuple(jnp.asarray(f) for f in fb_np),
                "dith": tuple(jnp.asarray(d) for d in dith_np)}

    def prepare(self, pack, sx, sy):
        f32 = jnp.float32
        din = pack["ws"][0].shape[0]
        dout = pack["ws"][2].shape[1]
        x = sx.reshape(-1, din)
        y = sy.reshape(x.shape[0], dout)
        xq = jnp.clip(jnp.round(x * 2.0 ** -TIFED_EX), -127.0, 127.0)
        yal = jnp.round(y * jnp.exp2(-pack["sacc"][2].astype(f32)))
        return {"cw": pack["ws"], "cb": pack["bs"], "xq": xq, "yal": yal}

    def unit_step(self, pack, slot, step):
        e = jnp.clip(step, 0, self.k_max - 1)
        layer = (e % 3).astype(jnp.int32)
        dither = tuple(
            jax.lax.dynamic_index_in_dim(d, e, keepdims=False)
            for d in pack["dith"])
        if resolve_use_pallas(self.use_pallas):
            from repro.kernels import ops as kops
            epoch_fn = kops.dfa_epoch_int8
            cw = tuple(w.astype(jnp.int8) for w in slot["cw"])
            cb = tuple(b.astype(jnp.int32) for b in slot["cb"])
            xq = slot["xq"].astype(jnp.int8)
            yal = slot["yal"].astype(jnp.int32)
            nw, nb, loss = epoch_fn(cw, cb, xq, yal, layer, pack["fb"],
                                    dither, pack["scales"])
            nw = tuple(w.astype(jnp.float32) for w in nw)
            nb = tuple(b.astype(jnp.float32) for b in nb)
        else:
            nw, nb, loss = kref.dfa_int8_epoch(
                slot["cw"], slot["cb"], slot["xq"], slot["yal"], layer,
                pack["fb"], dither, pack["scales"])
        return {**slot, "cw": nw, "cb": nb}, loss

    def _dequantize(self, pack, slot):
        out = {}
        for i in range(3):
            out[f"w{i}"] = slot["cw"][i] * jnp.exp2(pack["ew"][i])
            out[f"b{i}"] = slot["cb"][i] * jnp.exp2(pack["sacc"][i])
        return out

    def query_loss(self, pack, slot, qx, qy):
        """fp32 ReLU-MLP MSE on the dequantized adapted params — the
        network the integer arithmetic computes (same eval route as the
        engine's tifed runs)."""
        return relu_mlp_loss(self._dequantize(pack, slot),
                             {"x": qx, "y": qy})

    def finish(self, pack, slot):
        return self._dequantize(pack, slot)
