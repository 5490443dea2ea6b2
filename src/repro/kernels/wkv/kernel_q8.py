"""Pallas TPU kernel: wkv recurrence over an int8 quantized state.

The serving counterpart of ``kernel.py``: the (dk x dv) state enters as
int8 with one float32 scale per dk row (the per-block format of
:mod:`repro.core.quant_cache`, block = the value axis), is dequantized
into the VMEM scratch once at the start of the sweep, carried there in
f32 across all T steps, and re-quantized **in-kernel** on the last grid
step.  One int8 round-trip per kernel call — identical numerics to the
jnp serving path, which also round-trips the state through int8 exactly
once per dispatched step (``models/transformer.py::decode_step``).

Same grid (batch*heads, T/bt) and sequential-time discipline as
``_wkv_kernel``.  Forward-only: a serving artifact, never differentiated.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import common
from repro.kernels.wkv.kernel import block_scratch, load_block, wkv_sweep

_TINY = 1e-30


def _wkv_q8_kernel(r_ref, k_ref, v_ref, w_ref, u_ref, s0_ref, s0s_ref,
                   o_ref, sq_ref, ss_ref, s_scr, out_scr, *blk, bt: int,
                   nt: int):
    # per-row scales travel as (1, dk) rows, used as (dk, 1) columns
    @pl.when(pl.program_id(1) == 0)
    def _init():
        # dequant the incoming int8 state once; f32 thereafter
        s_scr[...] = (s0_ref[0].astype(jnp.float32)
                      * jnp.transpose(s0s_ref[0]))

    load_block((r_ref, k_ref, v_ref, w_ref), blk)
    u_col = jnp.transpose(u_ref[0].astype(jnp.float32))    # (dk, 1)
    s_scr[...] = wkv_sweep(s_scr[...], *blk, u_col, out_scr, bt)
    o_ref[0] = out_scr[...].astype(o_ref.dtype)

    @pl.when(pl.program_id(1) == nt - 1)
    def _finish():
        # requantize: same ops as core.quant_cache.quantize_blocked with
        # the value axis as the block (one scale per dk row)
        s = s_scr[...]
        sc = jnp.max(jnp.abs(s), axis=1, keepdims=True) * (1.0 / 127.0)
        q = jnp.clip(jnp.round(s / jnp.maximum(sc, _TINY)), -127.0, 127.0)
        sq_ref[0] = q.astype(jnp.int8)
        ss_ref[0] = jnp.transpose(sc)                          # (1, dk)


def wkv_recurrence_q8(r: jax.Array, k: jax.Array, v: jax.Array,
                      w: jax.Array, u: jax.Array, s0: jax.Array,
                      s0_scale: jax.Array, *, block_t: int = 64,
                      interpret: bool):
    """r/k/w: (BH, T, dk); v: (BH, T, dv); u: (BH, dk); s0: (BH, dk, dv)
    int8 with per-row float32 scales (BH, dk).

    Returns ``(out (BH, T, dv), s_fin int8 (BH, dk, dv), s_scale float32
    (BH, dk))`` — the state after all T steps, requantized in-kernel.
    T must tile by block_t.
    """
    bh, t, dk = r.shape
    dv = v.shape[-1]
    assert s0.dtype == jnp.int8, s0.dtype
    bt = common.largest_divisor(t, block_t)
    nt = t // bt
    kernel = functools.partial(_wkv_q8_kernel, bt=bt, nt=nt)
    out, s_fin, s_scale = pl.pallas_call(
        kernel,
        grid=(bh, nt),
        in_specs=[
            pl.BlockSpec((1, bt, dk), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, bt, dk), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, bt, dv), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, bt, dk), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, 1, dk), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, dk, dv), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, 1, dk), lambda b, i: (b, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, bt, dv), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, dk, dv), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, 1, dk), lambda b, i: (b, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, t, dv), r.dtype),
            jax.ShapeDtypeStruct((bh, dk, dv), jnp.int8),
            jax.ShapeDtypeStruct((bh, 1, dk), jnp.float32),
        ],
        scratch_shapes=block_scratch(bt, dk, dv),
        compiler_params=common.compiler_params("parallel", "arbitrary"),
        interpret=interpret,
    )(r, k, v, w, u.reshape(bh, 1, dk), s0,
      s0_scale.astype(jnp.float32).reshape(bh, 1, dk))
    return out, s_fin, s_scale.reshape(bh, dk)
