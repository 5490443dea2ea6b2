"""Pallas TPU kernel: causal flash attention (online softmax).

Beyond-paper perf layer for prefill_32k: never materialises the (S x S)
score matrix.  Grid (heads, q_blocks, k_blocks) with the K axis innermost;
the output tile plus running (max, sum) statistics stay pinned in VMEM
scratch across the K sweep — the same output-stationary discipline as the
paper's SYCore, applied to attention.

Causally-dead (q_block, k_block) pairs are skipped with ``pl.when`` (the
scheduler-level analogue of CAESAR's zero-skip gating).

GQA is handled in ops.py via the K/V BlockSpec index map (q head h reads
kv head h // group) — no materialised head replication.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import common

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, *rest, bq: int, bk: int,
                  scale: float, causal: bool, nk: int, with_lse: bool):
    if with_lse:
        lse_ref, m_scr, l_scr, acc_scr = rest
    else:
        lse_ref, (m_scr, l_scr, acc_scr) = None, rest
    iq = pl.program_id(1)
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_start = iq * bq
    k_start = ik * bk
    live = jnp.logical_or(not causal,
                          k_start <= q_start + bq - 1)

    @pl.when(live)
    def _step():
        q = q_ref[0].astype(jnp.float32)              # (bq, d)
        k = k_ref[0].astype(jnp.float32)              # (bk, d)
        v = v_ref[0].astype(jnp.float32)              # (bk, d)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale   # (bq, bk)
        if causal:
            qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            s = jnp.where(qpos >= kpos, s, NEG_INF)
        m_prev = m_scr[...]
        l_prev = l_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m_prev - m_new)
        l_scr[...] = l_prev * alpha + jnp.sum(p, axis=-1)
        m_scr[...] = m_new
        acc_scr[...] = acc_scr[...] * alpha[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(ik == nk - 1)
    def _finish():
        denom = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0] = (acc_scr[...] / denom[:, None]).astype(o_ref.dtype)
        if with_lse:
            # Per-row log-sum-exp of the scaled scores: the O(S) residual
            # the fused backward recomputes score tiles against.
            lse_ref[0] = (m_scr[...] + jnp.log(denom))[None, :]


def flash_attention_nhd(q: jax.Array, k: jax.Array, v: jax.Array, *,
                        causal: bool = True, block_q: int = 128,
                        block_k: int = 128, group: int = 1,
                        interpret: bool,
                        return_residuals: bool = False):
    """q: (Hq, Sq, d); k/v: (Hkv, Sk, d) with Hq = group * Hkv.

    Returns (Hq, Sq, d) in q's dtype.  Sq/Sk must tile by the blocks.
    With ``return_residuals`` also returns the per-row log-sum-exp of the
    scaled scores, shape (Hq, Sq) float32 — the O(S) residual the fused
    backward (see ``kernel_bwd.py``) recomputes score tiles against.
    """
    hq, sq, d = q.shape
    hkv, sk, _ = k.shape
    assert hq == group * hkv, (hq, hkv, group)
    bq = common.largest_divisor(sq, block_q)
    bk = common.largest_divisor(sk, block_k)
    nk = sk // bk
    grid = (hq, sq // bq, nk)
    kernel = functools.partial(_flash_kernel, bq=bq, bk=bk,
                               scale=1.0 / (d ** 0.5), causal=causal, nk=nk,
                               with_lse=return_residuals)
    out_specs = pl.BlockSpec((1, bq, d), lambda h, i, j: (h, i, 0))
    out_shape = jax.ShapeDtypeStruct((hq, sq, d), q.dtype)
    if return_residuals:
        # (Hq, 1, Sq) so the block's last two dims are (1, bq): whole
        # axis and lane-tiled, which the TPU lowers; (1, bq) blocks of an
        # (Hq, Sq) array it would refuse
        out_specs = [out_specs,
                     pl.BlockSpec((1, 1, bq), lambda h, i, j: (h, 0, i))]
        out_shape = [out_shape,
                     jax.ShapeDtypeStruct((hq, 1, sq), jnp.float32)]
    res = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda h, i, j: (h, i, 0)),
            pl.BlockSpec((1, bk, d), lambda h, i, j, g=group: (h // g, j, 0)),
            pl.BlockSpec((1, bk, d), lambda h, i, j, g=group: (h // g, j, 0)),
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq, d), jnp.float32),
        ],
        compiler_params=common.compiler_params("parallel", "parallel",
                                               "arbitrary"),
        interpret=interpret,
    )(q, k, v)
    if return_residuals:
        out, lse = res
        return out, lse.reshape(hq, sq)
    return res
