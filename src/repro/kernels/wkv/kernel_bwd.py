"""Pallas TPU kernel: fused wkv backward (reverse-time recurrence).

Forward per token (state S (dk, dv), per-channel decay w):

    y_t = r_t (S_t + diag(u) k_t v_t^T)      S_{t+1} = diag(w_t) S_t + k_t v_t^T

The backward runs time *in reverse*, carrying the state adjoint
A_t = dL/dS_t across blocks in VMEM scratch:

    A_t = diag(w_t) A_{t+1} + r_t dy_t^T                (A after last token = 0)
    dr_t = S_t dy_t + u ⊙ k_t (v_t·dy_t)
    dk_t = r_t ⊙ u (v_t·dy_t) + A_{t+1} v_t
    dv_t = (Σ_j r_j u_j k_j) dy_t + A_{t+1}^T k_t
    dw_t = rowsum(A_{t+1} ⊙ S_t)
    du  += r_t ⊙ k_t (v_t·dy_t)

The forward states S_t it needs are *recomputed* inside each time block
from the per-block checkpoints the forward emits under
``return_residuals=True`` (kernel.py) — O(T/bt) checkpointed states
instead of the O(T) a scan-based VJP stashes.  Grid (BH, T/bt) with the
time axis sequential and **reversed through the index maps**: grid step i
processes time block nt-1-i.  du accumulates into a per-(BH) output block
revisited across the whole sweep.

As in the forward, token ``i`` is a dynamically indexed row of an f32
VMEM copy of the block; the recomputed states sit in a (bt, dk, dv)
scratch indexed on its leading axis, and each step writes its gradient
rows straight into the output blocks.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import common
from repro.kernels.wkv.kernel import col, load_block


def _wkv_bwd_kernel(r_ref, k_ref, v_ref, w_ref, u_ref, dy_ref, c_ref,
                    dr_ref, dk_ref, dv_ref, dw_ref, du_ref, a_scr, st_scr,
                    rs, ks, vs, ws, dys, *, bt: int):
    @pl.when(pl.program_id(1) == 0)
    def _init():
        a_scr[...] = jnp.zeros_like(a_scr)   # A after the final token
        du_ref[...] = jnp.zeros_like(du_ref)

    load_block((r_ref, k_ref, v_ref, w_ref, dy_ref), (rs, ks, vs, ws, dys))
    u_row = u_ref[0].astype(jnp.float32)     # (1, dk)
    dk_dim = u_row.shape[1]

    # Recompute the in-block forward states from the block checkpoint:
    # st_scr[i] = S before token i of this block.
    def fstep(i, s):
        st_scr[i] = s
        return col(ws, i) * s + col(ks, i) * vs[pl.ds(i, 1), :]

    jax.lax.fori_loop(0, bt, fstep, c_ref[0, 0])

    def row(x):                              # (d, 1) column -> (1, d) row
        return jnp.transpose(x)

    def bstep(j, carry):
        a, du = carry                        # a = A_{t+1} for token t below
        i = bt - 1 - j
        s_i = st_scr[i]
        at = pl.ds(i, 1)
        r_i, k_i, v_i, dy_i = rs[at, :], ks[at, :], vs[at, :], dys[at, :]
        vdy = jnp.sum(v_i * dy_i)
        dr_ref[0, at, :] = (row(jnp.sum(s_i * dy_i, axis=1, keepdims=True))
                            + u_row * k_i * vdy)
        du = du + r_i * k_i * vdy
        dk_ref[0, at, :] = (r_i * u_row * vdy
                            + row(jnp.sum(a * v_i, axis=1, keepdims=True)))
        dv_ref[0, at, :] = (jnp.sum(r_i * u_row * k_i) * dy_i
                            + jnp.sum(col(ks, i) * a, axis=0, keepdims=True))
        dw_ref[0, at, :] = row(jnp.sum(a * s_i, axis=1, keepdims=True))
        a = col(ws, i) * a + col(rs, i) * dy_i
        return a, du

    a_fin, du = jax.lax.fori_loop(
        0, bt, bstep, (a_scr[...], jnp.zeros((1, dk_dim), jnp.float32)))
    a_scr[...] = a_fin
    du_ref[0] += du


def wkv_recurrence_bwd(r: jax.Array, k: jax.Array, v: jax.Array,
                       w: jax.Array, u: jax.Array, dy: jax.Array,
                       ckpt: jax.Array, *, block_t: int = 64,
                       interpret: bool
                       ) -> Tuple[jax.Array, jax.Array, jax.Array,
                                  jax.Array, jax.Array]:
    """Fused backward on the (BH, T, d) layout, all outputs float32.

    r/k/w: (BH, T, dk); v/dy: (BH, T, dv); u: (BH, dk); ckpt: the
    (BH, T/bt, dk, dv) block-boundary states from the forward's
    ``return_residuals=True`` run — **block_t must match that run's** so
    the checkpoints align.  Returns (dr, dk, dv, dw, du) with du (BH, dk).
    """
    bh, t, dk = r.shape
    dv = v.shape[-1]
    bt = common.largest_divisor(t, block_t)
    nt = t // bt
    assert ckpt.shape == (bh, nt, dk, dv), (ckpt.shape, (bh, nt, dk, dv))

    # Reverse time through the index maps: grid step i -> block nt-1-i.
    def rev(b, i, nt=nt):
        return (b, nt - 1 - i, 0)

    tk_spec = pl.BlockSpec((1, bt, dk), rev)
    tv_spec = pl.BlockSpec((1, bt, dv), rev)
    f32 = jnp.float32
    shapes = [jax.ShapeDtypeStruct((bh, t, dk), f32),
              jax.ShapeDtypeStruct((bh, t, dk), f32),
              jax.ShapeDtypeStruct((bh, t, dv), f32),
              jax.ShapeDtypeStruct((bh, t, dk), f32),
              jax.ShapeDtypeStruct((bh, 1, dk), f32)]
    dr, dk_, dv_, dw, du = pl.pallas_call(
        functools.partial(_wkv_bwd_kernel, bt=bt),
        grid=(bh, nt),
        in_specs=[
            tk_spec, tk_spec, tv_spec, tk_spec,
            pl.BlockSpec((1, 1, dk), lambda b, i: (b, 0, 0)),
            tv_spec,
            pl.BlockSpec((1, 1, dk, dv),
                         lambda b, i, nt=nt: (b, nt - 1 - i, 0, 0)),
        ],
        out_specs=[tk_spec, tk_spec, tv_spec, tk_spec,
                   pl.BlockSpec((1, 1, dk), lambda b, i: (b, 0, 0))],
        out_shape=shapes,
        scratch_shapes=[pltpu.VMEM((dk, dv), f32),
                        pltpu.VMEM((bt, dk, dv), f32),
                        pltpu.VMEM((bt, dk), f32), pltpu.VMEM((bt, dk), f32),
                        pltpu.VMEM((bt, dv), f32), pltpu.VMEM((bt, dk), f32),
                        pltpu.VMEM((bt, dv), f32)],
        compiler_params=common.compiler_params("parallel", "arbitrary"),
        interpret=interpret,
    )(r, k, v, w, u.reshape(bh, 1, dk), dy, ckpt)
    return dr, dk_, dv_, dw, du.reshape(bh, dk)
