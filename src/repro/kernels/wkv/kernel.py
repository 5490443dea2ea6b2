"""Pallas TPU kernel: RWKV6 (Finch) wkv recurrence.

The attention-free mixer's hotspot: per head, a (dk x dv) state S updated
per token with data-dependent per-channel decay,

    out_t = r_t · (S + (u ⊙ k_t) v_tᵀ)
    S    <- diag(w_t) S + k_t v_tᵀ

Grid (batch*heads, T/bt) with the time axis sequential ("arbitrary"); the
state S lives in VMEM scratch across the whole sweep — the recurrent
analogue of the SYCore output-stationary discipline (state stays, tokens
stream).  Inside a block the bt steps run as a fori loop of rank-1
updates on the VPU.  Each step reads token ``i`` as one dynamically
indexed row of an f32 VMEM copy of the block (a sublane offset, which
Mosaic lowers), turns the rows it needs into (dk, 1) columns by an
in-register transpose, and writes its output row the same way.

Bit-comparable (f32) to :mod:`repro.kernels.wkv.ref`.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import common


def load_block(refs, scratch):
    """Copy this grid step's (1, bt, d) input blocks into f32 scratch."""
    for ref, scr in zip(refs, scratch):
        scr[...] = ref[0].astype(jnp.float32)


def col(ref, i):
    """Row ``i`` of a 2-d ref as a (d, 1) column."""
    return jnp.transpose(ref[pl.ds(i, 1), :])


def wkv_sweep(s, rs, ks, vs, ws, u_col, out_scr, bt: int):
    """``bt`` recurrence steps from state ``s`` over the f32 block copies;
    token ``i``'s output row lands in ``out_scr[i]``.  Returns the state
    after the block."""

    def step(i, s):
        v_row = vs[pl.ds(i, 1), :]                       # (1, dv)
        kv = col(ks, i) * v_row                          # (dk, dv)
        out_scr[pl.ds(i, 1), :] = jnp.sum(
            col(rs, i) * (s + u_col * kv), axis=0, keepdims=True)
        return col(ws, i) * s + kv

    return jax.lax.fori_loop(0, bt, step, s)


def _wkv_kernel(r_ref, k_ref, v_ref, w_ref, u_ref, o_ref, *rest, bt: int,
                with_ckpt: bool):
    if with_ckpt:
        c_ref, rest = rest[0], rest[1:]
    else:
        c_ref = None
    s_scr, out_scr, *blk = rest

    @pl.when(pl.program_id(1) == 0)
    def _init():
        s_scr[...] = jnp.zeros_like(s_scr)

    if with_ckpt:
        # State at this block's start: the checkpoint the reverse-time
        # backward (kernel_bwd.py) restarts its in-block recompute from.
        c_ref[0, 0] = s_scr[...]

    load_block((r_ref, k_ref, v_ref, w_ref), blk)
    u_col = jnp.transpose(u_ref[0].astype(jnp.float32))    # (dk, 1)
    s_scr[...] = wkv_sweep(s_scr[...], *blk, u_col, out_scr, bt)
    o_ref[0] = out_scr[...].astype(o_ref.dtype)


def block_scratch(bt: int, dk: int, dv: int):
    """VMEM for the recurrence: state, output rows, and f32 copies of
    the r/k/v/w blocks (in that order)."""
    f32 = jnp.float32
    return [pltpu.VMEM((dk, dv), f32), pltpu.VMEM((bt, dv), f32),
            pltpu.VMEM((bt, dk), f32), pltpu.VMEM((bt, dk), f32),
            pltpu.VMEM((bt, dv), f32), pltpu.VMEM((bt, dk), f32)]


def wkv_recurrence(r: jax.Array, k: jax.Array, v: jax.Array, w: jax.Array,
                   u: jax.Array, *, block_t: int = 64,
                   interpret: bool,
                   return_residuals: bool = False):
    """r/k/w: (BH, T, dk); v: (BH, T, dv); u: (BH, dk).  -> (BH, T, dv).

    T must tile by block_t; state starts at zero (training semantics — the
    decode path carries S explicitly in jnp, see models/ssm.py).
    With ``return_residuals`` also returns the per-block-boundary state
    checkpoints, (BH, T/bt, dk, dv) float32 — O(T/bt) states instead of
    the O(T) a scan-based VJP would stash; the backward recomputes the
    in-block states from them.
    """
    bh, t, dk = r.shape
    dv = v.shape[-1]
    bt = common.largest_divisor(t, block_t)
    grid = (bh, t // bt)
    kernel = functools.partial(_wkv_kernel, bt=bt,
                               with_ckpt=return_residuals)
    out_specs = pl.BlockSpec((1, bt, dv), lambda b, i: (b, i, 0))
    out_shape = jax.ShapeDtypeStruct((bh, t, dv), r.dtype)
    if return_residuals:
        out_specs = [out_specs,
                     pl.BlockSpec((1, 1, dk, dv), lambda b, i: (b, i, 0, 0))]
        out_shape = [out_shape,
                     jax.ShapeDtypeStruct((bh, t // bt, dk, dv),
                                          jnp.float32)]
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bt, dk), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, bt, dk), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, bt, dv), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, bt, dk), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, 1, dk), lambda b, i: (b, 0, 0)),
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=block_scratch(bt, dk, dv),
        compiler_params=common.compiler_params("parallel", "arbitrary"),
        interpret=interpret,
    )(r, k, v, w, u.reshape(bh, 1, dk))
