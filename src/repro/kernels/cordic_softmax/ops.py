"""Jit'd public wrapper for the CORDIC SoftMax kernel (float frontend)."""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core import cordic, fixed_point as fxp
from repro.core.fixed_point import FxpFormat
from repro.kernels import common
from repro.kernels.cordic_softmax.kernel import cordic_softmax_raw
from repro.kernels.cordic_softmax.ref import cordic_softmax_raw_ref

# The whole feature axis of a row block sits in VMEM with about eight
# int32 temporaries per element (input, output, exponentials, quotient,
# double buffers): 2**18 elements a block keep it near 8 MiB, half the
# 16 MiB of scoped VMEM a v5e core gives a kernel by default.
_BLOCK_ELEMS = 1 << 18


def block_rows(shape) -> int:
    """Row block for a (rows, features) input: aligned, VMEM-bounded."""
    r, c = shape
    return common.pick_block_rows("cordic_softmax", (r, c), jnp.int32,
                                  max_rows=max(8, min(128,
                                                      _BLOCK_ELEMS // c)))


@functools.partial(jax.jit, static_argnames=("fmt", "n_hyp", "n_div",
                                             "guard", "block_rows",
                                             "interpret"))
def _fwd(x, fmt: FxpFormat, n_hyp: int, n_div: int, guard: int,
         block_rows: int, interpret: bool):
    shape = x.shape
    x2 = x.reshape(-1, shape[-1])
    # Pre-scale into fmt range: softmax(x) == softmax(x - max) and the
    # kernel re-subtracts its own integer max, so only quantization of the
    # *differences* matters; clamp keeps huge logits finite in fmt.
    x2 = x2 - jax.lax.stop_gradient(jnp.max(x2, axis=-1, keepdims=True))
    raw = fxp.quantize(x2, fmt)
    # rows are independent: zero rows up to a whole row block
    r = raw.shape[0]
    pr = common.padded(r, block_rows) - r
    if pr:
        raw = jnp.pad(raw, ((0, pr), (0, 0)))
    out = cordic_softmax_raw(raw, fmt=fmt, n_hyp=n_hyp, n_div=n_div,
                             guard=guard, block_rows=block_rows,
                             interpret=interpret)
    return fxp.dequantize(out[:r], fmt).reshape(shape).astype(x.dtype)


def _exact_softmax(x: jax.Array) -> jax.Array:
    return jax.nn.softmax(x, axis=-1)


def cordic_softmax(x: jax.Array, *, fmt: FxpFormat = fxp.FXP16,
                   n_hyp: int = cordic.N_HYPERBOLIC_STAGES,
                   n_div: Optional[int] = None, guard: int = 4,
                   interpret: Optional[bool] = None) -> jax.Array:
    """Row softmax through the RPE FIFO datapath, STE gradients."""
    interpret = common.resolve_interpret(interpret)
    if n_div is None:
        n_div = max(cordic.N_DIVISION_STAGES, fmt.frac_bits + guard)
    # Pick the block OUTSIDE the jitted forward so autotuned cache entries
    # take effect (a lookup inside _fwd would be frozen into its trace).
    x2_shape = (x.size // x.shape[-1], x.shape[-1])
    f = common.ste(
        functools.partial(_fwd, fmt=fmt, n_hyp=n_hyp, n_div=n_div,
                          guard=guard, block_rows=block_rows(x2_shape),
                          interpret=interpret),
        _exact_softmax)
    return f(x)


def _candidates(shape, dtype):
    """Legal (rows, cols) tiles: the feature axis stays whole (the kernel
    reduces over it), so only the row-block varies, over divisors."""
    r, c = shape
    return tuple((br, c) for br in common.divisor_candidates(r, 128, 4))


common.register(common.KernelSpec(
    name="cordic_softmax", kernel=cordic_softmax_raw,
    ref=cordic_softmax_raw_ref, grad=_exact_softmax,
    candidates=_candidates, tags=("fixed-point", "rowwise")))
