"""Pallas TPU kernel: output-stationary CORDIC matmul (SYCore on the VPU).

Dataflow = the paper's SYCore: the output tile is pinned in VMEM (the
"output-stationary partial sums"), K-slices of inputs and weights stream
through, and every scalar multiply is the RPE's n-stage linear-CORDIC
shift-add recurrence:

    for stage i in 0..n-1:
        delta = sign(z)            # z: weight residual
        y    += delta * (x >> i)   # arithmetic shift + add
        z    -= delta * 2^-i

All arithmetic is on raw int32 fixed-point words, so the kernel is
bit-exact against :mod:`repro.kernels.cordic_mac.ref` (which reduces the
same recurrence to a sum of signed-digit matmuls).

Grid: (M/bm, N/bn, K/bk) with the K axis innermost ("arbitrary"), so each
(i, j) output tile sees its K-slices back-to-back and accumulates in place —
exactly one output-stationary pass of the systolic array per tile.

The input streams in transposed, ``x.T`` in (bk, bm) tiles, so both
operands of step ``kk`` are one dynamically indexed *row* of a ref (a
sublane offset, which Mosaic lowers; a dynamic lane offset it refuses).
The input row is turned into the (bm, 1) column the shift-add needs by
an in-register transpose.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core import fixed_point as fxp
from repro.core.fixed_point import FxpFormat
from repro.kernels import common


def _mac_kernel(xt_ref, w_ref, out_ref, *, n_stages: int, fmt: FxpFormat,
                bk: int):
    """One grid step: out_tile += CORDIC(x_tile @ w_tile)."""

    @pl.when(pl.program_id(2) == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    acc = out_ref[...]        # (bm, bn) int32 raw — the stationary tile

    # Angle constants E_i = 2^-i in fmt (hard-wired per pipeline stage).
    e_consts = [jnp.int32(fxp.constant(2.0 ** (-i), fmt)) for i in range(n_stages)]

    def k_step(kk, acc):
        # One weight row enters the array; delta is a pure function of the
        # evolving weight residual, shared across the whole input column.
        xc = jnp.transpose(xt_ref[pl.ds(kk, 1), :])               # (bm, 1)
        z = w_ref[pl.ds(kk, 1), :]                                 # (1, bn)
        for i in range(n_stages):
            delta = jnp.where(z >= 0, jnp.int32(1), jnp.int32(-1))  # (1, bn)
            acc = acc + delta * jnp.right_shift(xc, i)              # (bm, bn)
            z = z - delta * e_consts[i]
        return acc

    acc = jax.lax.fori_loop(0, bk, k_step, acc)
    out_ref[...] = acc


def cordic_matmul_raw(x_raw: jax.Array, w_raw: jax.Array, *,
                      fmt: FxpFormat, n_stages: int,
                      block: tuple[int, int, int] = (128, 128, 128),
                      interpret: bool) -> jax.Array:
    """Raw int32 CORDIC matmul via pallas_call.  Shapes must tile evenly.

    On a TPU ``bm``/``bn`` must be multiples of 128 (or the whole axis)
    and ``bk`` a multiple of 8: the (8, 128) tiling of the x.T, w and
    output blocks.
    """
    m, k = x_raw.shape
    k2, n = w_raw.shape
    assert k == k2, (x_raw.shape, w_raw.shape)
    bm, bn, bk = block
    assert m % bm == 0 and n % bn == 0 and k % bk == 0, (
        f"shape ({m},{k},{n}) must tile by {block}; ops.py pads for you")

    grid = (m // bm, n // bn, k // bk)
    kernel = functools.partial(_mac_kernel, n_stages=n_stages, fmt=fmt, bk=bk)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bk, bm), lambda i, j, s: (s, i)),
            pl.BlockSpec((bk, bn), lambda i, j, s: (s, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, s: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.int32),
        compiler_params=common.compiler_params("parallel", "parallel",
                                               "arbitrary"),
        interpret=interpret,
    )(x_raw.T, w_raw)
