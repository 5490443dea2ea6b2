"""Pallas TPU kernel: causal flash attention over an int8 quantized cache.

The serving counterpart of ``kernel.py``: K/V arrive as int8 with one
float32 scale per (kv head, position) vector — the per-block quantized
cache format of :mod:`repro.core.quant_cache` — and are dequantized
**inside the kernel**, per K-tile, in VMEM.  The HBM traffic for the K/V
sweep (the decode/verify bottleneck) drops ~4x vs f32 / ~2x vs bf16; the
online-softmax math itself is unchanged f32, so the only divergence from
the float kernel is the cache round-trip the caller already accepted.

Same grid (heads, q_blocks, k_blocks) and output-stationary m/l/acc
discipline as ``_flash_kernel``; GQA again rides on the K/V index maps.
Forward-only: the quantized cache is a serving artifact, nothing
differentiates through it.

TPU note: int8 VMEM tiles want (32, 128) multiples — production shapes
(Sk >= 128, d a lane multiple) satisfy this; tiny smoke shapes run in
interpret mode anyway (see ``common.resolve_interpret``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import common

NEG_INF = -1e30


def _flash_q8_kernel(q_ref, k_ref, v_ref, ks_ref, vs_ref, o_ref,
                     m_scr, l_scr, acc_scr, *, bq: int, bk: int,
                     scale: float, causal: bool, nk: int):
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
        q = q_ref[0].astype(jnp.float32)                   # (bq, d)
        # in-VMEM dequant: one f32 scale per cached vector (row)
        k = k_ref[0].astype(jnp.float32) * ks_ref[0][:, None]   # (bk, d)
        v = v_ref[0].astype(jnp.float32) * vs_ref[0][:, None]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale    # (bq, bk)
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


def flash_attention_q8_nhd(q: jax.Array, k: jax.Array, v: jax.Array,
                           k_scale: jax.Array, v_scale: jax.Array, *,
                           causal: bool = True, block_q: int = 128,
                           block_k: int = 128, group: int = 1,
                           interpret: bool) -> jax.Array:
    """q: (Hq, Sq, d) float; k/v: (Hkv, Sk, d) int8 with per-vector
    float32 scales (Hkv, Sk); Hq = group * Hkv.  Returns (Hq, Sq, d) in
    q's dtype.  Sq/Sk must tile by the blocks (clamped to divisors)."""
    hq, sq, d = q.shape
    hkv, sk, _ = k.shape
    assert hq == group * hkv, (hq, hkv, group)
    assert k.dtype == jnp.int8 and v.dtype == jnp.int8, (k.dtype, v.dtype)
    bq = common.largest_divisor(sq, block_q)
    bk = common.largest_divisor(sk, block_k)
    nk = sk // bk
    grid = (hq, sq // bq, nk)
    kernel = functools.partial(_flash_q8_kernel, bq=bq, bk=bk,
                               scale=1.0 / (d ** 0.5), causal=causal, nk=nk)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda h, i, j: (h, i, 0)),
            pl.BlockSpec((1, bk, d), lambda h, i, j, g=group: (h // g, j, 0)),
            pl.BlockSpec((1, bk, d), lambda h, i, j, g=group: (h // g, j, 0)),
            pl.BlockSpec((1, bk), lambda h, i, j, g=group: (h // g, j)),
            pl.BlockSpec((1, bk), lambda h, i, j, g=group: (h // g, j)),
        ],
        out_specs=pl.BlockSpec((1, bq, d), lambda h, i, j: (h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((hq, sq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq, d), jnp.float32),
        ],
        compiler_params=common.compiler_params("parallel", "parallel",
                                               "arbitrary"),
        interpret=interpret,
    )(q, k, v, k_scale.astype(jnp.float32), v_scale.astype(jnp.float32))
