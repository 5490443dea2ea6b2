"""Property-based numerics of the int8 per-block quantized cache.

The hypothesis layer over ``core/quant_cache.py`` — deterministic
spot-checks of the same contract live in ``tests/test_quant_cache.py``
(which runs even without hypothesis).  Three families of properties:

  * **round-trip bounds**: |x - dq(q(x))| <= scale/2 per trailing-axis
    block, over random shapes, block sizes, magnitudes and input dtypes
    (f32 / bf16 inputs — the serving cache quantizes both)
  * **scatter commutation**: quantize-then-scatter == scatter-then-
    quantize for any slot index set — the invariant ``slot_update``
    relies on to touch only the updated slot's rows and scales
  * **permutation invariance**: per-block scales depend only on the
    block's own values, so any permutation of the slot axis commutes
    with quantization bit-exactly

This module is wired into the interpret-consistency CI lane in both the
default and ``REPRO_KERNEL_INTERPRET=1`` runs: the properties are pure
jnp, so agreement across the two runs pins the quantizer itself (not
just the kernels) to one set of semantics.
"""
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis")  # property tests; see requirements-dev.txt
from hypothesis import given, settings, strategies as st

from repro.core.quant_cache import dequantize_blocked, quantize_blocked


def _arr(rng, shape, scale, dtype):
    x = rng.normal(0.0, scale, shape).astype(np.float32)
    return jnp.asarray(x, dtype)


_dtypes = st.sampled_from([jnp.float32, jnp.bfloat16])


@given(seed=st.integers(0, 2**31 - 1),
       rows=st.integers(1, 6), cols=st.sampled_from([8, 16, 32, 64]),
       blk=st.sampled_from([None, 8, 16]),
       mag=st.floats(1e-3, 1e3), dtype=_dtypes)
@settings(max_examples=40, deadline=None)
def test_roundtrip_bound(seed, rows, cols, blk, mag, dtype):
    if blk is not None and cols % blk:
        return
    rng = np.random.default_rng(seed)
    x = _arr(rng, (rows, cols), mag, dtype)
    q, s = quantize_blocked(x, block=blk)
    assert q.dtype == jnp.int8 and s.dtype == jnp.float32
    nb = 1 if blk is None else cols // blk
    assert s.shape == (rows, nb)
    dq = np.asarray(dequantize_blocked(q, s), np.float64)
    xf = np.asarray(x, np.float64)          # bound vs what was quantized
    step = np.repeat(np.asarray(s, np.float64), cols // nb, axis=-1)
    # Half a step, plus the f32 arithmetic around it.  bf16 inputs (8
    # significant bits) often land exactly on a half-step tie, x/s = k+0.5
    # (seed 52060: 6.8125 with max 13.625 gives 63.5), so the rounding of
    # x/s, of the stored s and of q*s all show: each is within 2**-24
    # relative of terms up to 128*s, so 3 * 128 * 2**-24 * s < 2**-15 * s.
    assert np.all(np.abs(xf - dq) <= step * (0.5 + 2.0 ** -15))
    # all-zero blocks round-trip exactly (scale stored as 0, not epsilon)
    zq, zs = quantize_blocked(jnp.zeros_like(x), block=blk)
    assert np.all(np.asarray(zs) == 0.0)
    assert np.all(np.asarray(dequantize_blocked(zq, zs)) == 0.0)


@given(seed=st.integers(0, 2**31 - 1), slots=st.integers(2, 8),
       nupd=st.integers(1, 4), dtype=_dtypes)
@settings(max_examples=40, deadline=None)
def test_scatter_then_read_equals_read_then_scatter(seed, slots, nupd, dtype):
    rng = np.random.default_rng(seed)
    nupd = min(nupd, slots)
    cache = _arr(rng, (slots, 5, 16), 1.0, dtype)
    rows = _arr(rng, (nupd, 5, 16), 2.0, dtype)
    idx = jnp.asarray(rng.choice(slots, nupd, replace=False))

    qc, sc = quantize_blocked(cache)
    qr, sr = quantize_blocked(rows)
    q1, s1 = qc.at[idx].set(qr), sc.at[idx].set(sr)     # scatter quantized
    q2, s2 = quantize_blocked(cache.at[idx].set(rows))  # quantize scattered
    assert np.array_equal(np.asarray(q1), np.asarray(q2))
    assert np.array_equal(np.asarray(s1), np.asarray(s2))
    # and the reads agree bit-exactly too
    assert np.array_equal(np.asarray(dequantize_blocked(q1, s1)),
                          np.asarray(dequantize_blocked(q2, s2)))


@given(seed=st.integers(0, 2**31 - 1), slots=st.integers(2, 8),
       blk=st.sampled_from([None, 8]), dtype=_dtypes)
@settings(max_examples=40, deadline=None)
def test_permutation_invariance(seed, slots, blk, dtype):
    rng = np.random.default_rng(seed)
    x = _arr(rng, (slots, 3, 16), 1.0, dtype)
    perm = jnp.asarray(rng.permutation(slots))
    q, s = quantize_blocked(x, block=blk)
    qp, sp = quantize_blocked(x[perm], block=blk)
    assert np.array_equal(np.asarray(q[perm]), np.asarray(qp))
    assert np.array_equal(np.asarray(s[perm]), np.asarray(sp))
