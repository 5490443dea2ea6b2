"""Pallas TPU kernel: DA-VINCI activation datapath (hyperbolic + division).

Elementwise tanh / sigmoid / exp on raw int32 fixed-point tiles, computed
exactly as the RPE's iterative stages do it:

  * G guard bits of internal precision (the paper's "2N+K" AF input
    precision, §1.1) — inputs are up-shifted by G, iterated at
    frac_bits+G, and rounded back at the output latch,
  * hyperbolic micro-rotations -> cosh, sinh,
  * integer ln2 range extension (a = k*ln2 + r; barrel shift by k) — our
    TPU-side fidelity adaptation, see DESIGN.md §Hardware-adaptation,
  * division micro-iterations for the tanh/sigmoid quotients,
  * range-extended tanh identity tanh(-|a|) = (e^{-2|a|}-1)/(e^{-2|a|}+1).

Bit-exact against :mod:`repro.kernels.cordic_act.ref`, which composes the
same recurrences in plain jnp.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core import cordic, fixed_point as fxp
from repro.core.fixed_point import FxpFormat
from repro.kernels import common

LN2 = math.log(2.0)
GUARD_BITS = 4
# |a| clamp before the k-extraction multiply so Q(2*fb) products fit int32.
EXP_ARG_CLAMP = 30.0


# ---------------------------------------------------------------------------
# Integer building blocks — all operate at internal precision Q(fb)
# ---------------------------------------------------------------------------

def _hyperbolic(z, fb: int, n: int):
    """Unrolled hyperbolic rotation at Q(fb): returns (cosh_raw, sinh_raw)."""
    inv_gain = jnp.int32(fxp.constant_raw(1.0 / cordic.hyperbolic_gain(n), fb))
    x = jnp.full_like(z, inv_gain)
    y = jnp.zeros_like(z)
    for shift in cordic.hyperbolic_sequence(n):
        e_i = jnp.int32(fxp.constant_raw(math.atanh(2.0 ** (-shift)), fb))
        delta = jnp.where(z >= 0, jnp.int32(1), jnp.int32(-1))
        x, y, z = (x + delta * jnp.right_shift(y, shift),
                   y + delta * jnp.right_shift(x, shift),
                   z - delta * e_i)
    return x, y


def _divide(y, x, fb: int, n: int):
    """Unrolled linear vectoring at Q(fb): quotient y/x (x > 0, |y/x| < 2)."""
    q = jnp.zeros_like(y)
    for i in range(n):
        e_i = jnp.int32(fxp.constant_raw(2.0 ** (-i), fb))
        delta = jnp.where(y >= 0, jnp.int32(1), jnp.int32(-1))
        y = y - delta * jnp.right_shift(x, i)
        q = q + delta * e_i
    return q


def _exp_neg(a, fb: int, n_hyp: int):
    """e^a for a <= 0 at Q(fb) via integer ln2 range extension.

    k = round(a/ln2) (<= 0), r = a - k*ln2, e^a = (cosh r + sinh r) >> -k.
    Callers must clamp a >= -EXP_ARG_CLAMP so the Q(2*fb) product fits int32
    (requires fb <= 12).
    """
    inv_ln2 = jnp.int32(fxp.constant_raw(1.0 / LN2, fb))
    ln2 = jnp.int32(fxp.constant_raw(LN2, fb))
    t = a * inv_ln2                       # Q(2*fb) product
    k = jnp.right_shift(t + (jnp.int32(1) << (2 * fb - 1)), 2 * fb)
    r = a - k * ln2
    c, s = _hyperbolic(r, fb, n_hyp)
    return jnp.right_shift(c + s, jnp.clip(-k, 0, 31))


def _round_back(v, guard: int):
    """Round from Q(frac+guard) back to Q(frac) — the output latch."""
    return jnp.right_shift(v + (jnp.int32(1) << (guard - 1)), guard)


# ---------------------------------------------------------------------------
# Kernel body
# ---------------------------------------------------------------------------

def _act_kernel(x_ref, o_ref, *, af: str, fmt: FxpFormat, n_hyp: int,
                n_div: int, guard: int):
    fb = fmt.frac_bits + guard
    a = jnp.left_shift(x_ref[...], guard)            # Q(fb)
    one = jnp.int32(1) << fb
    clamp = jnp.int32(fxp.constant_raw(EXP_ARG_CLAMP, fb))

    if af == "exp":
        # decode paths feed max-subtracted (<= 0) arguments
        a = jnp.clip(a, -clamp, jnp.int32(0))
        o_ref[...] = _round_back(_exp_neg(a, fb, n_hyp), guard)
    elif af == "tanh":
        # tanh(-|a|) = (e^{-2|a|}-1)/(e^{-2|a|}+1), mirrored by sign.
        cap = jnp.int32(fxp.constant_raw(
            min(4.0, fmt.max_value / 2.0 - fmt.resolution), fb))
        a_abs = jnp.minimum(jnp.abs(a), cap)
        e2a = _exp_neg(-(a_abs + a_abs), fb, n_hyp)
        q = _divide(e2a - one, e2a + one, fb, n_div)  # in (-1, 0]
        o_ref[...] = _round_back(jnp.where(a >= 0, -q, q), guard)
    elif af == "sigmoid":
        e = _exp_neg(jnp.maximum(-jnp.abs(a), -clamp), fb, n_hyp)
        q = _divide(jnp.full_like(a, one), one + e, fb, n_div)
        o_ref[...] = _round_back(jnp.where(a >= 0, q, one - q), guard)
    else:
        raise ValueError(f"unsupported kernel AF {af!r}")


def cordic_act_raw(x_raw: jax.Array, *, af: str, fmt: FxpFormat,
                   n_hyp: int = cordic.N_HYPERBOLIC_STAGES,
                   n_div: int = cordic.N_DIVISION_STAGES,
                   guard: int = GUARD_BITS,
                   block: tuple[int, int] = (256, 256),
                   interpret: bool) -> jax.Array:
    """Elementwise CORDIC AF on a 2D raw-int32 array (tiles must divide)."""
    assert fmt.frac_bits + guard <= 12, (
        "internal precision capped at Q12 for int32 headroom in the "
        "ln2-extraction multiply")
    r, c = x_raw.shape
    br, bc = min(block[0], r), min(block[1], c)
    assert r % br == 0 and c % bc == 0
    kernel = functools.partial(_act_kernel, af=af, fmt=fmt, n_hyp=n_hyp,
                               n_div=n_div, guard=guard)
    return pl.pallas_call(
        kernel,
        grid=(r // br, c // bc),
        in_specs=[pl.BlockSpec((br, bc), lambda i, j: (i, j))],
        out_specs=pl.BlockSpec((br, bc), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((r, c), jnp.int32),
        compiler_params=common.compiler_params("parallel", "parallel"),
        interpret=interpret,
    )(x_raw)
