"""Fused int8 TIFeD epoch kernel: DFA forward + single-layer update.

One client epoch of TIFeD integer training (arXiv 2307.03102 applied to
the paper's sine MLPs): an int8 forward pass with int32 accumulation,
direct-feedback-alignment error projection through fixed random int8
matrices, and a stochastic-rounding requantized update of the one layer
scheduled this epoch — all in a single kernel invocation, so the whole
local step is one fused VMEM-resident pass with no fp32 weight
round-trips to HBM.

Arithmetic contract: int8 operands feed the MXU directly (int8 x int8
-> int32 via ``preferred_element_type``; a DFA delta that outgrows int8
is split into two exact int8 halves), fp32 only for the power-of-two
requant multipliers (exact scalings) and the loss. The pure-jnp oracle
is ``kernels.ref.dfa_int8_epoch`` — it carries the same integers in
fp32 at HIGHEST dot precision, and every weight/bias intermediate stays
below 2^24, so the parity tests are exact-equality on weights/biases.
The loss is an fp32 sum that may pass 2^24; there the two reduction
orders can differ by an ulp, so it is compared to rtol 1e-6.

Blocking: the paper models are tiny (a few KB), so each operand is one
whole-array 2-D block and the grid is trivial; scalars and the loss
ride SMEM. 2-D operands let a vmapped call (cohort or serving slots)
block each one whole. A large-model variant would tile the hidden axis.
Off-TPU this runs in interpret mode (``pltpu_interpret``), matching the
other kernels; the engine's tifed strategy only routes through it on
TPU and uses the oracle math on CPU, where XLA's fusion is already at
the floor for these shapes.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.meta_update import pltpu_interpret
from repro.kernels.ref import BIAS_MAX, DFA_SHIFT, INT8_MAX

_DN_SAMPLE = (((0,), (0,)), ((), ()))   # contract the sample axis


def _idot(a, b, dims=(((1,), (0,)), ((), ()))):
    """int8 x int8 -> int32 on the MXU; operands are never widened."""
    return jax.lax.dot_general(a.astype(jnp.int8), b.astype(jnp.int8), dims,
                               preferred_element_type=jnp.int32)


def _dfa_epoch_kernel(scal_ref, layer_ref, xq_ref, yal_ref,
                      w0_ref, w1_ref, w2_ref, b0_ref, b1_ref, b2_ref,
                      fb1_ref, fb2_ref, d0_ref, d1_ref, d2_ref,
                      ow0_ref, ow1_ref, ow2_ref,
                      ob0_ref, ob1_ref, ob2_ref, loss_ref):
    f32, i32 = jnp.float32, jnp.int32
    f0, f1, fe, floss = (scal_ref[0, i] for i in range(4))
    ftw = tuple(scal_ref[0, i] for i in range(4, 7))
    ftb = tuple(scal_ref[0, i] for i in range(7, 10))
    layer = layer_ref[0, 0]

    def wide(ref):
        return ref[...].astype(i32)

    def linear(a, w_ref):
        # din == 1 is a broadcast on the VPU; wider inputs go to the MXU
        if w_ref.shape[0] == 1:
            return a.astype(i32) * wide(w_ref)
        return _idot(a, w_ref[...])

    def requant(z, f):
        # ReLU then requantize the activation to uint7 (fits int8)
        return jnp.clip(jnp.round(jnp.maximum(z, 0).astype(f32) * f),
                        0.0, INT8_MAX).astype(i32)

    x = xq_ref[...]
    z0 = linear(x, w0_ref) + b0_ref[...]
    a1 = requant(z0, f0)
    z1 = linear(a1, w1_ref) + b1_ref[...]
    a2 = requant(z1, f1)
    z2 = linear(a2, w2_ref) + b2_ref[...]
    err = (z2 - yal_ref[...]).astype(f32)
    eq = jnp.clip(jnp.round(err * fe), -INT8_MAX, INT8_MAX).astype(i32)
    loss_ref[0, 0] = jnp.sum(err * err) * floss

    def delta(z, fbm_ref):
        # DFA: error hits the hidden layer through a fixed random matrix
        proj = linear(eq, fbm_ref).astype(f32)
        return jnp.round(jnp.where(z > 0, proj, 0.0)
                         * 2.0 ** -DFA_SHIFT).astype(i32)

    def grad(a_in, d, d_fits_int8):
        if a_in.shape[1] == 1:
            return (a_in.astype(i32) * d).sum(0, keepdims=True)
        if d_fits_int8:
            return _idot(a_in, d, _DN_SAMPLE)
        # dout > 1: |d| <= 127^2 dout / 2^7 is past int8, so split it
        # into exact int8 halves (hi stays in int8 for dout <= 128)
        hi, lo = d >> 7, d & 127
        return (_idot(a_in, hi, _DN_SAMPLE) * 128
                + _idot(a_in, lo, _DN_SAMPLE))

    def wstep(w_ref, g, ftw_i, dith_ref):
        # stochastic rounding: floor(v + u), dither baked by the caller
        wn = (wide(w_ref).astype(f32)
              - jnp.floor(g.astype(f32) * ftw_i + dith_ref[...]))
        return jnp.clip(wn, -INT8_MAX, INT8_MAX)

    def bstep(b_ref, dsum, ftb_i):
        bn = b_ref[...].astype(f32) - jnp.round(dsum.astype(f32) * ftb_i)
        return jnp.clip(bn, -BIAS_MAX, BIAS_MAX)

    d0 = delta(z0, fb1_ref)
    d1 = delta(z1, fb2_ref)
    narrow = fb1_ref.shape[0] == 1      # dout == 1: |d| <= 127^2 / 2^7
    cand = (
        (wstep(w0_ref, grad(x, d0, narrow), ftw[0], d0_ref),
         bstep(b0_ref, d0.sum(0, keepdims=True), ftb[0])),
        (wstep(w1_ref, grad(a1, d1, narrow), ftw[1], d1_ref),
         bstep(b1_ref, d1.sum(0, keepdims=True), ftb[1])),
        (wstep(w2_ref, grad(a2, eq, True), ftw[2], d2_ref),
         bstep(b2_ref, eq.sum(0, keepdims=True), ftb[2])),
    )
    # all three candidates are computed; `layer` selects which one lands
    # (the others write back unchanged) — a runtime select keeps the
    # epoch scan at one trace
    for i, (w_ref, b_ref, ow_ref, ob_ref) in enumerate(
            ((w0_ref, b0_ref, ow0_ref, ob0_ref),
             (w1_ref, b1_ref, ow1_ref, ob1_ref),
             (w2_ref, b2_ref, ow2_ref, ob2_ref))):
        ow_ref[...] = jnp.where(layer == i, cand[i][0].astype(i32),
                                wide(w_ref)).astype(jnp.int8)
        ob_ref[...] = jnp.where(layer == i, cand[i][1].astype(i32),
                                b_ref[...])


def dfa_epoch_int8(ws, bs, xq, yal, layer, fb, dither, scales):
    """One TIFeD epoch on native dtypes (contract of ref.dfa_int8_epoch).

    ws: 3-tuple of int8 weights, bs: 3-tuple of int32 biases (at
    accumulator scale), xq: (S, din) int8, yal: (S, dout) int32,
    layer: int32 scalar in {0,1,2}, fb: (fb1, fb2) int8 feedback,
    dither: 3 fp32 U[0,1) planes, scales: the fp32 multiplier dict
    (f0, f1, fe, floss, ftw, ftb). Returns (ws', bs', loss)."""
    if fb[0].shape[-2] > 128:
        raise ValueError(f"dfa_epoch_int8 splits the DFA delta into two "
                         f"int8 halves, which holds for dout <= 128; got "
                         f"dout={fb[0].shape[-2]}")
    scal = jnp.stack([jnp.asarray(s, jnp.float32) for s in
                      (scales["f0"], scales["f1"], scales["fe"],
                       scales["floss"], *scales["ftw"], *scales["ftb"])
                      ]).reshape(1, -1)
    lay = jnp.asarray(layer, jnp.int32).reshape(1, 1)
    ws = tuple(w.astype(jnp.int8) for w in ws)
    # every operand is 2-D so that a vmapped call blocks each one whole:
    # biases ride as (1, H) rows, the SMEM scalars as (1, n)
    bs = tuple(b.astype(jnp.int32).reshape(1, -1) for b in bs)
    fb = tuple(f.astype(jnp.int8) for f in fb)
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    outs = pl.pallas_call(
        _dfa_epoch_kernel,
        in_specs=[smem] * 2 + [pl.BlockSpec()] * 13,
        out_specs=[pl.BlockSpec()] * 6 + [smem],
        out_shape=([jax.ShapeDtypeStruct(w.shape, jnp.int8) for w in ws]
                   + [jax.ShapeDtypeStruct(b.shape, jnp.int32) for b in bs]
                   + [jax.ShapeDtypeStruct((1, 1), jnp.float32)]),
        interpret=pltpu_interpret(),
    )(scal, lay, xq.astype(jnp.int8), yal.astype(jnp.int32),
      *ws, *bs, *fb, *dither)
    return (tuple(outs[:3]), tuple(b.reshape(-1) for b in outs[3:6]),
            outs[6][0, 0])
