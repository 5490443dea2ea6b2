"""Pallas TPU kernels: fused flash-attention backward (recompute scheme).

The forward stashes one float per row — the log-sum-exp of the scaled
scores (``return_residuals=True`` in ``kernel.py``) — and the backward
rebuilds each probability tile on the fly as

    p = exp(q k^T * scale - lse)

instead of differentiating through a materialised (S x S) score matrix.
O(S) residual memory where the STE fallback pays O(S^2): the same
trade-cheap-recompute-for-expensive-storage move the paper's engines make
in hardware.

Two passes, both tiled and both skipping causally-dead tiles, and both
keeping the **head axis whole inside the block**: the grid runs over
sequence tiles only, and every contraction is one Hq-batched
``dot_general`` with a single contracting dim (what Mosaic lowers), the
kv tiles broadcast over each GQA group:

  * **dQ** — grid (q_blocks, k_blocks), K innermost; the (Hq, bq, d) dQ
    tile accumulates in VMEM scratch across the K sweep
    (output-stationary).
  * **dK/dV** — grid (k_blocks, q_blocks), Q innermost; the (Hkv, bk, d)
    dK and dV tiles accumulate across the Q sweep, each group of q
    heads' per-head contribution summed into its kv head.

Both consume ``delta = rowsum(dO * O)`` (the softmax-VJP correction term),
computed once in jnp by the wrapper — O(S d) work, no kernel needed.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import common
from repro.kernels.flash_attention.kernel import NEG_INF

# Every head's (bq, bk) score, probability and gradient tiles live at
# once (~20 MB at 32 heads and 128x128 tiles): above the default scoped
# VMEM, well inside the 128 MiB of a v5e core.
_VMEM_LIMIT = 96 * 1024 * 1024


def _per_q_head(x, group):
    """(Hkv, b, d) kv tile -> (Hq, b, d): q head h reads kv head h // g."""
    hkv, b, d = x.shape
    return jnp.broadcast_to(x[:, None], (hkv, group, b, d)).reshape(
        hkv * group, b, d)


def _bdot(a, b, contract):
    """Head-batched matmul contracting dim ``contract`` of each operand."""
    return jax.lax.dot_general(a, b, (contract, ((0,), (0,))),
                               preferred_element_type=jnp.float32)


def _tile_grads(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                q_start, k_start, *, bq, bk, scale, causal, group):
    """Recompute p and ds for one (all-heads, bq, bk) tile pair.

    Returns (p, ds, q, k, do) with p/ds shaped (Hq, bq, bk), q/do
    (Hq, bq, d) and k (Hq, bk, d) — everything the two passes contract
    from.
    """
    q = q_ref[...].astype(jnp.float32)                 # (hq, bq, d)
    do = do_ref[...].astype(jnp.float32)
    k = _per_q_head(k_ref[...].astype(jnp.float32), group)   # (hq, bk, d)
    v = _per_q_head(v_ref[...].astype(jnp.float32), group)
    s = _bdot(q, k, ((2,), (2,))) * scale              # (hq, bq, bk)
    if causal:
        qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        s = jnp.where((qpos >= kpos)[None], s, NEG_INF)
    p = jnp.exp(s - lse_ref[...][..., None])
    dp = _bdot(do, v, ((2,), (2,)))                    # dO V^T
    ds = p * (dp - delta_ref[...][..., None]) * scale
    return p, ds, q, k, do


def _group_sum(x, group):
    """(Hq, b, d) per-q-head tile -> (Hkv, b, d) summed over each group."""
    hq, b, d = x.shape
    return x.reshape(hq // group, group, b, d).sum(axis=1)


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
               acc_scr, *, bq: int, bk: int, scale: float, causal: bool,
               group: int, nk: int):
    iq = pl.program_id(0)
    ik = pl.program_id(1)

    @pl.when(ik == 0)
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_start = iq * bq
    k_start = ik * bk
    live = jnp.logical_or(not causal, k_start <= q_start + bq - 1)

    @pl.when(live)
    def _step():
        _, ds, _, k, _ = _tile_grads(
            q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
            q_start, k_start, bq=bq, bk=bk, scale=scale, causal=causal,
            group=group)
        acc_scr[...] += _bdot(ds, k, ((2,), (1,)))      # dS K: (hq,bq,d)

    @pl.when(ik == nk - 1)
    def _finish():
        dq_ref[...] = acc_scr[...].astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                dk_ref, dv_ref, dk_scr, dv_scr, *, bq: int, bk: int,
                scale: float, causal: bool, group: int, nq: int):
    ij = pl.program_id(0)   # k block
    iq = pl.program_id(1)   # q block (innermost, sequential)

    @pl.when(iq == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    q_start = iq * bq
    k_start = ij * bk
    live = jnp.logical_or(not causal, q_start + bq - 1 >= k_start)

    @pl.when(live)
    def _step():
        p, ds, q, _, do = _tile_grads(
            q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
            q_start, k_start, bq=bq, bk=bk, scale=scale, causal=causal,
            group=group)
        # per-q-head P^T dO and dS^T Q, (hq, bk, d), group-summed
        dv_scr[...] += _group_sum(_bdot(p, do, ((1,), (1,))), group)
        dk_scr[...] += _group_sum(_bdot(ds, q, ((1,), (1,))), group)

    @pl.when(iq == nq - 1)
    def _finish():
        dk_ref[...] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[...] = dv_scr[...].astype(dv_ref.dtype)


def flash_attention_bwd_nhd(q: jax.Array, k: jax.Array, v: jax.Array,
                            do: jax.Array, lse: jax.Array, delta: jax.Array,
                            *, causal: bool = True, block_q: int = 128,
                            block_k: int = 128, group: int = 1,
                            interpret: bool
                            ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Fused backward on the (H, S, d) layout.

    q/do: (Hq, Sq, d); k/v: (Hkv, Sk, d); lse/delta: (Hq, Sq) float32.
    Returns float32 (dq (Hq, Sq, d), dk (Hkv, Sk, d), dv (Hkv, Sk, d)) —
    dk/dv are already group-summed to kv heads.
    """
    hq, sq, d = q.shape
    hkv, sk, _ = k.shape
    assert hq == group * hkv, (hq, hkv, group)
    bq = common.largest_divisor(sq, block_q)
    bk = common.largest_divisor(sk, block_k)
    nq = sq // bq
    nk = sk // bk
    scale = 1.0 / (d ** 0.5)

    q_spec = pl.BlockSpec((hq, bq, d), lambda i, j: (0, i, 0))
    kv_spec = pl.BlockSpec((hkv, bk, d), lambda i, j: (0, j, 0))
    row_spec = pl.BlockSpec((hq, bq), lambda i, j: (0, i))

    dq = pl.pallas_call(
        functools.partial(_dq_kernel, bq=bq, bk=bk, scale=scale,
                          causal=causal, group=group, nk=nk),
        grid=(nq, nk),
        in_specs=[q_spec, kv_spec, kv_spec, q_spec, row_spec, row_spec],
        out_specs=pl.BlockSpec((hq, bq, d), lambda i, j: (0, i, 0)),
        out_shape=jax.ShapeDtypeStruct((hq, sq, d), jnp.float32),
        scratch_shapes=[pltpu.VMEM((hq, bq, d), jnp.float32)],
        compiler_params=common.compiler_params(
            "parallel", "arbitrary", vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
    )(q, k, v, do, lse, delta)

    # Same maps with the (k block, q block) grid order of the dK/dV pass.
    q_spec2 = pl.BlockSpec((hq, bq, d), lambda j, i: (0, i, 0))
    kv_spec2 = pl.BlockSpec((hkv, bk, d), lambda j, i: (0, j, 0))
    row_spec2 = pl.BlockSpec((hq, bq), lambda j, i: (0, i))
    dkv_out = pl.BlockSpec((hkv, bk, d), lambda j, i: (0, j, 0))

    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, bq=bq, bk=bk, scale=scale,
                          causal=causal, group=group, nq=nq),
        grid=(nk, nq),
        in_specs=[q_spec2, kv_spec2, kv_spec2, q_spec2, row_spec2, row_spec2],
        out_specs=[dkv_out, dkv_out],
        out_shape=[jax.ShapeDtypeStruct((hkv, sk, d), jnp.float32),
                   jax.ShapeDtypeStruct((hkv, sk, d), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((hkv, bk, d), jnp.float32),
                        pltpu.VMEM((hkv, bk, d), jnp.float32)],
        compiler_params=common.compiler_params(
            "parallel", "arbitrary", vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
    )(q, k, v, do, lse, delta)
    return dq, dk, dv
