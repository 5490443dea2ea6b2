"""Per-kernel validation: shape/dtype/format sweeps, bit-exactness vs the
pure-jnp oracles, and allclose vs float references (interpret mode)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis")  # property tests; see requirements-dev.txt
from hypothesis import given, settings, strategies as st

from repro.core import fixed_point as fxp
from repro.kernels.cordic_mac.kernel import cordic_matmul_raw
from repro.kernels.cordic_mac.ops import cordic_matmul
from repro.kernels.cordic_mac.ref import (cordic_matmul_raw_ref,
                                          effective_weight)
from repro.kernels.cordic_act.kernel import cordic_act_raw
from repro.kernels.cordic_act.ops import cordic_act
from repro.kernels.cordic_act.ref import cordic_act_raw_ref
from repro.kernels.cordic_softmax.kernel import cordic_softmax_raw
from repro.kernels.cordic_softmax.ops import cordic_softmax
from repro.kernels.cordic_softmax.ref import cordic_softmax_raw_ref


class TestCordicMacKernel:
    @pytest.mark.parametrize("shape", [(16, 16, 16), (32, 48, 16),
                                       (64, 64, 128), (8, 256, 24)])
    @pytest.mark.parametrize("fmt", [fxp.FXP8, fxp.FXP16])
    def test_bit_exact_vs_ref(self, shape, fmt, rng):
        m, k, n = shape
        x = fxp.quantize(jnp.array(rng.uniform(-2, 2, (m, k)), jnp.float32), fmt)
        w = fxp.quantize(jnp.array(rng.uniform(-1.9, 1.9, (k, n)), jnp.float32), fmt)
        import math
        bm = math.gcd(m, 16); bn = math.gcd(n, 16); bk = math.gcd(k, 16)
        got = cordic_matmul_raw(x, w, fmt=fmt, n_stages=5,
                                block=(bm, bn, bk), interpret=True)
        want = cordic_matmul_raw_ref(x, w, fmt=fmt, n_stages=5)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    @pytest.mark.parametrize("n_stages", [5, 8, 12])
    def test_allclose_vs_float(self, n_stages, rng):
        fmt = fxp.FXP16
        x = jnp.array(rng.uniform(-2, 2, (32, 64)), jnp.float32)
        w = jnp.array(rng.uniform(-1.9, 1.9, (64, 16)), jnp.float32)
        got = cordic_matmul(x, w, fmt=fmt, n_stages=n_stages, block=(16, 16, 16))
        want = x @ w
        # per-element error ~ K * (|x| 2^-n + trunc); relative band:
        tol = 64 * (2.0 * 2.0 ** (-n_stages) + (n_stages + 2) * fmt.resolution)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=tol)

    def test_uneven_shapes_padded(self, rng):
        fmt = fxp.FXP16
        x = jnp.array(rng.uniform(-1, 1, (13, 70)), jnp.float32)
        w = jnp.array(rng.uniform(-1, 1, (70, 9)), jnp.float32)
        got = cordic_matmul(x, w, fmt=fmt, n_stages=10, block=(16, 16, 16))
        assert got.shape == (13, 9)
        np.testing.assert_allclose(np.asarray(got), np.asarray(x @ w), atol=0.5)

    def test_effective_weight_is_signed_digit_value(self, rng):
        fmt = fxp.FXP16
        w = jnp.array(rng.uniform(-1.9, 1.9, (32, 8)), jnp.float32)
        w_eff = effective_weight(w, fmt, n_stages=10)
        assert float(jnp.abs(w_eff - w).max()) < 2.0 ** (-9) + 2 * fmt.resolution

    def test_grad_is_exact_matmul_vjp(self, rng):
        x = jnp.array(rng.uniform(-1, 1, (16, 32)), jnp.float32)
        w = jnp.array(rng.uniform(-1, 1, (32, 16)), jnp.float32)
        gx, gw = jax.grad(
            lambda a, b: cordic_matmul(a, b, block=(16, 16, 16)).sum(),
            argnums=(0, 1))(x, w)
        np.testing.assert_allclose(np.asarray(gx),
                                   np.asarray(jnp.ones((16, 16)) @ w.T),
                                   rtol=1e-5)
        np.testing.assert_allclose(np.asarray(gw),
                                   np.asarray(x.T @ jnp.ones((16, 16))),
                                   rtol=1e-5)

    @given(st.integers(1, 4), st.integers(1, 4), st.integers(1, 4),
           st.integers(0, 2 ** 31 - 1))
    @settings(max_examples=10, deadline=None)
    def test_property_random_tiles_bit_exact(self, gm, gn, gk, seed):
        fmt = fxp.FXP8
        r = np.random.default_rng(seed)
        m, n, k = 8 * gm, 8 * gn, 8 * gk
        x = fxp.quantize(jnp.array(r.uniform(-2, 2, (m, k)), jnp.float32), fmt)
        w = fxp.quantize(jnp.array(r.uniform(-1.9, 1.9, (k, n)), jnp.float32), fmt)
        got = cordic_matmul_raw(x, w, fmt=fmt, n_stages=5, block=(8, 8, 8),
                                interpret=True)
        want = cordic_matmul_raw_ref(x, w, fmt=fmt, n_stages=5)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


class TestCordicActKernel:
    @pytest.mark.parametrize("af", ["tanh", "sigmoid", "exp"])
    @pytest.mark.parametrize("fmt", [fxp.FXP8, fxp.FXP16])
    @pytest.mark.parametrize("shape", [(8, 128), (64, 64), (32, 96)])
    def test_bit_exact_vs_ref(self, af, fmt, shape, rng):
        x = fxp.quantize(jnp.array(rng.uniform(-6, 6, shape), jnp.float32), fmt)
        got = cordic_act_raw(x, af=af, fmt=fmt, block=(8, 32),
                             interpret=True)
        want = cordic_act_raw_ref(x, af=af, fmt=fmt)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    @pytest.mark.parametrize("af,exact", [
        ("tanh", np.tanh),
        ("sigmoid", lambda v: 1 / (1 + np.exp(-v))),
        ("exp", lambda v: np.exp(np.minimum(v, 0)))])
    def test_allclose_vs_float(self, af, exact, rng):
        x = rng.uniform(-6, 6, (32, 64)).astype(np.float32)
        got = cordic_act(jnp.array(x), af, fmt=fxp.FXP16, n_hyp=12)
        np.testing.assert_allclose(np.asarray(got), exact(x), atol=0.02)

    def test_monotonicity_preserved(self, rng):
        """sigmoid/tanh outputs must be monotone in the input — the property
        QAT training relies on."""
        x = jnp.linspace(-5, 5, 257)[None, :]
        for af in ("tanh", "sigmoid"):
            y = np.asarray(cordic_act(x, af, fmt=fxp.FXP16, n_hyp=12))[0]
            assert np.all(np.diff(y) >= -1e-6), af

    def test_grad_shapes(self, rng):
        x = jnp.array(rng.normal(size=(8, 16)), jnp.float32)
        g = jax.grad(lambda v: cordic_act(v, "sigmoid").sum())(x)
        assert g.shape == x.shape


class TestCordicSoftmaxKernel:
    @pytest.mark.parametrize("fmt", [fxp.FXP8, fxp.FXP16])
    @pytest.mark.parametrize("shape", [(8, 32), (64, 256), (16, 1000)])
    def test_bit_exact_vs_ref(self, fmt, shape, rng):
        x = fxp.quantize(
            jnp.array(rng.normal(size=shape) * 2 - 3, jnp.float32), fmt)
        got = cordic_softmax_raw(x, fmt=fmt, block_rows=8, interpret=True)
        want = cordic_softmax_raw_ref(x, fmt=fmt)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    def test_rows_sum_near_one(self, rng):
        x = jnp.array(rng.normal(size=(32, 128)) * 4, jnp.float32)
        sm = cordic_softmax(x, fmt=fxp.FXP16, n_hyp=10)
        sums = np.asarray(sm.sum(-1))
        assert np.all(np.abs(sums - 1.0) < 0.1)

    def test_argmax_preserved(self, rng):
        # fixed-point ties can legitimately flip argmax between near-equal
        # logits; require the true argmax to be within 1 ulp of the top.
        x = jnp.array(rng.normal(size=(64, 32)) * 3, jnp.float32)
        got = np.asarray(cordic_softmax(x, fmt=fxp.FXP16, n_hyp=10))
        want = np.asarray(jax.nn.softmax(x, -1))
        top = got.max(-1)
        at_true = got[np.arange(64), want.argmax(-1)]
        assert np.all(top - at_true <= fxp.FXP16.resolution + 1e-7)

    def test_allclose_vs_float(self, rng):
        x = jnp.array(rng.normal(size=(16, 64)) * 2, jnp.float32)
        got = cordic_softmax(x, fmt=fxp.FXP16, n_hyp=12)
        want = jax.nn.softmax(x, -1)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=0.02)

    def test_translation_invariance(self, rng):
        """softmax(x) == softmax(x + c) — survives the integer pipeline."""
        x = jnp.array(rng.normal(size=(4, 32)), jnp.float32)
        a = cordic_softmax(x, fmt=fxp.FXP16)
        b = cordic_softmax(x + 7.25, fmt=fxp.FXP16)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-2)


class TestFlashAttentionKernel:
    @pytest.mark.parametrize("shape", [
        # (hq, hkv, sq, sk, d, bq, bk, causal)
        (4, 4, 64, 64, 16, 16, 16, True),
        (8, 2, 128, 128, 32, 32, 64, True),
        (4, 1, 64, 64, 16, 64, 16, True),
        (4, 4, 64, 64, 16, 64, 64, False),
    ])
    def test_matches_ref(self, shape, rng):
        from repro.kernels.flash_attention.kernel import flash_attention_nhd
        from repro.kernels.flash_attention.ref import attention_nhd_ref
        hq, hkv, sq, sk, d, bq, bk, causal = shape
        g = hq // hkv
        q = jnp.array(rng.normal(size=(hq, sq, d)), jnp.float32)
        k = jnp.array(rng.normal(size=(hkv, sk, d)), jnp.float32)
        v = jnp.array(rng.normal(size=(hkv, sk, d)), jnp.float32)
        got = flash_attention_nhd(q, k, v, causal=causal, block_q=bq,
                                  block_k=bk, group=g, interpret=True)
        want = attention_nhd_ref(q, k, v, causal=causal, group=g)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=2e-5, rtol=2e-5)

    def test_gqa_frontend(self, rng):
        from repro.kernels.flash_attention.ops import flash_attention
        from repro.kernels.flash_attention.ref import attention_nhd_ref
        q = jnp.array(rng.normal(size=(2, 64, 8, 16)), jnp.float32)
        k = jnp.array(rng.normal(size=(2, 64, 2, 16)), jnp.float32)
        v = jnp.array(rng.normal(size=(2, 64, 2, 16)), jnp.float32)
        got = flash_attention(q, k, v, block_q=32, block_k=32)
        for b in range(2):
            want = attention_nhd_ref(
                q[b].transpose(1, 0, 2), k[b].transpose(1, 0, 2),
                v[b].transpose(1, 0, 2), causal=True, group=4)
            np.testing.assert_allclose(
                np.asarray(got[b].transpose(1, 0, 2)), np.asarray(want),
                atol=2e-5, rtol=2e-5)

    def test_bf16_inputs(self, rng):
        from repro.kernels.flash_attention.kernel import flash_attention_nhd
        from repro.kernels.flash_attention.ref import attention_nhd_ref
        q = jnp.array(rng.normal(size=(2, 64, 32)), jnp.bfloat16)
        k = jnp.array(rng.normal(size=(2, 64, 32)), jnp.bfloat16)
        v = jnp.array(rng.normal(size=(2, 64, 32)), jnp.bfloat16)
        got = flash_attention_nhd(q, k, v, block_q=32, block_k=32,
                                  interpret=True)
        want = attention_nhd_ref(q, k, v)
        assert got.dtype == jnp.bfloat16
        np.testing.assert_allclose(
            np.asarray(got, np.float32), np.asarray(want, np.float32),
            atol=2e-2, rtol=2e-2)


class TestWkvKernel:
    @pytest.mark.parametrize("shape", [(4, 64, 16, 16, 16),
                                       (2, 128, 32, 32, 64),
                                       (8, 32, 8, 8, 32)])
    def test_matches_ref(self, shape, rng):
        from repro.kernels.wkv.kernel import wkv_recurrence
        from repro.kernels.wkv.ref import wkv_recurrence_ref
        bh, t, dk, dv, bt = shape
        r = jnp.array(rng.normal(size=(bh, t, dk)), jnp.float32)
        k = jnp.array(rng.normal(size=(bh, t, dk)), jnp.float32)
        v = jnp.array(rng.normal(size=(bh, t, dv)), jnp.float32)
        w = jnp.array(rng.uniform(0.3, 1.0, size=(bh, t, dk)), jnp.float32)
        u = jnp.array(rng.normal(size=(bh, dk)), jnp.float32)
        got = wkv_recurrence(r, k, v, w, u, block_t=bt, interpret=True)
        want = wkv_recurrence_ref(r, k, v, w, u)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=5e-5, rtol=5e-5)

    def test_matches_model_timemix_core(self, rng):
        """The kernel computes the same recurrence as models/ssm.py's
        chunked scan (state zero, identical inputs)."""
        from repro.kernels.wkv.ops import wkv
        from repro.kernels.wkv.ref import wkv_recurrence_ref
        b, t, h, d = 2, 32, 4, 8
        r = jnp.array(rng.normal(size=(b, t, h, d)), jnp.float32)
        k = jnp.array(rng.normal(size=(b, t, h, d)), jnp.float32)
        v = jnp.array(rng.normal(size=(b, t, h, d)), jnp.float32)
        w = jnp.array(rng.uniform(0.5, 1.0, size=(b, t, h, d)), jnp.float32)
        u = jnp.array(rng.normal(size=(h, d)), jnp.float32)
        got = wkv(r, k, v, w, u, block_t=16)

        # reference via the BH-flat oracle
        def flat(x):
            return x.transpose(0, 2, 1, 3).reshape(b * h, t, d)
        want = wkv_recurrence_ref(flat(r), flat(k), flat(v), flat(w),
                                  jnp.tile(u[None], (b, 1, 1)).reshape(-1, d))
        want = want.reshape(b, h, t, d).transpose(0, 2, 1, 3)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=5e-5, rtol=5e-5)
