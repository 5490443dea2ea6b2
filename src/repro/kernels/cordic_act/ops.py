"""Jit'd public wrapper for the CORDIC activation kernel (float frontend)."""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core import cordic, fixed_point as fxp
from repro.core.fixed_point import FxpFormat
from repro.kernels import common
from repro.kernels.cordic_act.kernel import cordic_act_raw
from repro.kernels.cordic_act.ref import cordic_act_raw_ref

_EXACT = {"tanh": jnp.tanh, "sigmoid": jax.nn.sigmoid, "exp": jnp.exp}


@functools.partial(jax.jit, static_argnames=("af", "fmt", "n_hyp", "n_div",
                                             "guard", "block", "interpret"))
def _fwd(x, af: str, fmt: FxpFormat, n_hyp: int, n_div: int, guard: int,
         block, interpret: bool):
    shape = x.shape
    x2 = x.reshape(-1, shape[-1]) if x.ndim != 2 else x
    r, c = x2.shape
    raw = fxp.quantize(x2, fmt)
    # elementwise: zero padding up to whole tiles changes no real element
    pr, pc = common.padded(r, block[0]) - r, common.padded(c, block[1]) - c
    if pr or pc:
        raw = jnp.pad(raw, ((0, pr), (0, pc)))
    out = cordic_act_raw(raw, af=af, fmt=fmt, n_hyp=n_hyp, n_div=n_div,
                         guard=guard, block=block, interpret=interpret)
    return fxp.dequantize(out[:r, :c], fmt).reshape(shape).astype(x.dtype)


def cordic_act(x: jax.Array, af: str, *, fmt: FxpFormat = fxp.FXP16,
               n_hyp: int = cordic.N_HYPERBOLIC_STAGES,
               n_div: Optional[int] = None, guard: int = 4,
               interpret: Optional[bool] = None) -> jax.Array:
    """tanh/sigmoid/exp through the DA-VINCI kernel, STE gradients."""
    if af not in _EXACT:
        raise ValueError(f"unsupported af {af!r}; kernel AFs: "
                         f"{sorted(_EXACT)} (composites like gelu live in "
                         "core/activations.py)")
    interpret = common.resolve_interpret(interpret)
    if n_div is None:
        n_div = max(cordic.N_DIVISION_STAGES, fmt.frac_bits + guard)
    # Pick the block OUTSIDE the jitted forward so autotuned cache entries
    # take effect (a lookup inside _fwd would be frozen into its trace).
    x2_shape = (x.size // x.shape[-1], x.shape[-1])
    block = common.pick_block_2d(f"cordic_act.{af}", x2_shape, jnp.int32)
    f = common.ste(
        functools.partial(_fwd, af=af, fmt=fmt, n_hyp=n_hyp, n_div=n_div,
                          guard=guard, block=block, interpret=interpret),
        _EXACT[af])
    return f(x)


def _exact_act(x: jax.Array, *, af: str) -> jax.Array:
    return _EXACT[af](x)


def _candidates(shape, dtype):
    """Legal (rows, cols) tiles for the flattened 2-d input: the Pallas
    BlockSpec requires exact division, so candidates are divisor pairs
    under the elementwise caps.  Cache keys are per-AF
    (``cordic_act.tanh`` etc.) but legality depends only on the shape."""
    r, c = shape
    return tuple((br, bc)
                 for br in common.divisor_candidates(r, 256, 3)
                 for bc in common.divisor_candidates(c, 512, 3))


common.register(common.KernelSpec(
    name="cordic_act", kernel=cordic_act_raw, ref=cordic_act_raw_ref,
    grad=_exact_act, candidates=_candidates,
    tags=("fixed-point", "elementwise")))
