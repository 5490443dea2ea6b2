"""Pallas TPU kernel: CORDIC SoftMax (paper §2.3 FIFO flow, blocked rows).

Per row block (the RPE's SoftMax FIFO):
  1. integer max-subtraction (keeps every exponent argument <= 0, so the
     fixed-point FIFO cannot overflow — our stability adaptation),
  2. hyperbolic-stage exponentials with ln2 barrel-shift range extension,
  3. running int32 sum (the FIFO accumulator),
  4. division-stage normalisation of every entry by the sum,
  5. zero-skip gating: underflowed exponentials bypass the divider
     (CAESAR sparsity co-design) instead of emitting the 1-ulp floor.

The whole datapath runs at Q(frac+G) internal precision (guard bits — the
paper's 2N+K AF precision) and rounds back at the output latch.  Bit-exact
vs :mod:`repro.kernels.cordic_softmax.ref`.  Rows are blocked on the grid;
the feature axis stays whole inside VMEM (true to the FIFO, which holds the
full SoftMax window).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core import cordic, fixed_point as fxp
from repro.core.fixed_point import FxpFormat
from repro.kernels import common
from repro.kernels.cordic_act.kernel import (EXP_ARG_CLAMP, GUARD_BITS,
                                             _divide, _exp_neg, _round_back)


def _softmax_kernel(x_ref, o_ref, *, fmt: FxpFormat, n_hyp: int, n_div: int,
                    guard: int):
    fb = fmt.frac_bits + guard
    a = jnp.left_shift(x_ref[...], guard)            # (br, C) Q(fb)
    clamp = jnp.int32(fxp.constant_raw(EXP_ARG_CLAMP, fb))
    m = jnp.max(a, axis=-1, keepdims=True)
    e = _exp_neg(jnp.maximum(a - m, -clamp), fb, n_hyp)   # <= 1.0 in Q(fb)
    tot = jnp.sum(e, axis=-1, keepdims=True)              # FIFO accumulator
    tot = jnp.maximum(tot, jnp.int32(1))                  # all-underflow guard
    q = _divide(e, jnp.broadcast_to(tot, e.shape), fb, n_div)
    q = jnp.where(e == 0, jnp.int32(0), q)                # zero-skip gating
    o_ref[...] = _round_back(q, guard)


def cordic_softmax_raw(x_raw: jax.Array, *, fmt: FxpFormat,
                       n_hyp: int = cordic.N_HYPERBOLIC_STAGES,
                       n_div: int = cordic.N_DIVISION_STAGES,
                       guard: int = GUARD_BITS,
                       block_rows: int = 128,
                       interpret: bool) -> jax.Array:
    assert fmt.frac_bits + guard <= 12, "internal precision capped at Q12"
    r, c = x_raw.shape
    br = common.largest_divisor(r, block_rows)
    kernel = functools.partial(_softmax_kernel, fmt=fmt, n_hyp=n_hyp,
                               n_div=n_div, guard=guard)
    return pl.pallas_call(
        kernel,
        grid=(r // br,),
        in_specs=[pl.BlockSpec((br, c), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((br, c), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((r, c), jnp.int32),
        compiler_params=common.compiler_params("parallel"),
        interpret=interpret,
    )(x_raw)
