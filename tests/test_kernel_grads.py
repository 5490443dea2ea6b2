"""Fused backward kernels vs the exact float-reference VJP.

Covers the tentpole contract: for both float families the fused Pallas
backward (kernel_bwd.py, routed through common.fused_vjp) must match
jax.vjp of the float reference within family tolerances — causal and
non-causal, GQA (hq != hkv), non-divisor sequence lengths, forced small
tiles — and REPRO_FUSED_BWD=0 must fall back to the STE path.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.kernels as K
from repro.kernels import common
from repro.kernels.flash_attention.ops import _exact_attention
from repro.kernels.flash_attention.ref import attention_bwd_ref
from repro.kernels.flash_attention.kernel import flash_attention_nhd
from repro.kernels.flash_attention.kernel_bwd import flash_attention_bwd_nhd
from repro.kernels.wkv.ops import _exact_wkv
from repro.kernels.wkv.kernel import wkv_recurrence
from repro.kernels.wkv.kernel_bwd import wkv_recurrence_bwd
from repro.kernels.wkv.ref import wkv_bwd_ref


@pytest.fixture(autouse=True)
def _clean_block_cache():
    common.clear_block_cache()
    yield
    common.clear_block_cache()


def _flash_case(rng, b, s, hq, hkv, d):
    q = jnp.array(rng.normal(size=(b, s, hq, d)), jnp.float32)
    k = jnp.array(rng.normal(size=(b, s, hkv, d)), jnp.float32)
    v = jnp.array(rng.normal(size=(b, s, hkv, d)), jnp.float32)
    g = jnp.array(rng.normal(size=(b, s, hq, d)), jnp.float32)
    return q, k, v, g


class TestFlashFusedBackward:
    @pytest.mark.parametrize("shape,causal", [
        # (b, s, hq, hkv, d)
        ((2, 64, 4, 4, 16), True),      # causal, MHA
        ((2, 64, 4, 4, 16), False),     # non-causal
        ((1, 64, 8, 2, 16), True),      # GQA group=4
        ((1, 64, 4, 1, 8), True),       # MQA
        ((2, 40, 4, 2, 8), True),       # non-divisor S (40 % 128 != 0)
        ((1, 96, 2, 2, 16), False),     # non-divisor S, non-causal
    ])
    def test_matches_reference_vjp(self, shape, causal, rng):
        q, k, v, g = _flash_case(rng, *shape)
        _, vjp = jax.vjp(
            lambda a, b_, c: K.flash_attention(a, b_, c, causal=causal),
            q, k, v)
        _, ref_vjp = jax.vjp(
            lambda a, b_, c: _exact_attention(a, b_, c, causal=causal),
            q, k, v)
        for name, got, want in zip("dq dk dv".split(), vjp(g), ref_vjp(g)):
            np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                       atol=2e-4, rtol=2e-4, err_msg=name)

    def test_forced_small_tiles(self, rng):
        """The backward tile resolves through the substrate cache, so a
        forced non-default block must still produce exact grads."""
        q, k, v, g = _flash_case(rng, 1, 96, 4, 2, 16)
        common.set_block("flash_attention.bwd", (96, 96), jnp.float32,
                         (32, 48))
        _, vjp = jax.vjp(lambda *a: K.flash_attention(*a), q, k, v)
        _, ref_vjp = jax.vjp(
            lambda *a: _exact_attention(*a, causal=True), q, k, v)
        for got, want in zip(vjp(g), ref_vjp(g)):
            np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                       atol=2e-4, rtol=2e-4)

    def test_fused_path_resolves_bwd_block(self, rng):
        """Differentiating installs a flash_attention.bwd cache entry —
        the observable sign the fused kernels (not STE) ran."""
        q, k, v, g = _flash_case(rng, 1, 32, 2, 1, 8)
        jax.vjp(lambda *a: K.flash_attention(*a), q, k, v)[1](g)
        assert common.cached_block("flash_attention.bwd", (32, 32),
                                   jnp.float32) is not None

    def test_ste_fallback_env(self, rng, monkeypatch):
        monkeypatch.setenv("REPRO_FUSED_BWD", "0")
        q, k, v, g = _flash_case(rng, 1, 32, 4, 2, 8)
        _, vjp = jax.vjp(lambda *a: K.flash_attention(*a), q, k, v)
        _, ref_vjp = jax.vjp(
            lambda *a: _exact_attention(*a, causal=True), q, k, v)
        for got, want in zip(vjp(g), ref_vjp(g)):
            np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                       atol=2e-4, rtol=2e-4)
        # and no backward block was resolved: the STE path really ran
        assert common.cached_block("flash_attention.bwd", (32, 32),
                                   jnp.float32) is None

    def test_lse_residual_matches_scores(self, rng):
        """The stashed LSE equals logsumexp of the scaled score rows."""
        hq, s, d = 2, 64, 16
        q = jnp.array(rng.normal(size=(hq, s, d)), jnp.float32)
        k = jnp.array(rng.normal(size=(hq, s, d)), jnp.float32)
        v = jnp.array(rng.normal(size=(hq, s, d)), jnp.float32)
        out, lse = flash_attention_nhd(q, k, v, causal=False, block_q=32,
                                       block_k=32, return_residuals=True,
                                       interpret=True)
        scores = jnp.einsum("hqd,hkd->hqk", q, k) / (d ** 0.5)
        want = jax.nn.logsumexp(scores, axis=-1)
        np.testing.assert_allclose(np.asarray(lse), np.asarray(want),
                                   atol=1e-5, rtol=1e-5)
        # the plain call is unchanged
        np.testing.assert_allclose(
            np.asarray(flash_attention_nhd(q, k, v, causal=False,
                                           block_q=32, block_k=32,
                                           interpret=True)),
            np.asarray(out), atol=1e-6)

    def test_raw_bwd_kernel_vs_ref(self, rng):
        """kernel_bwd entry point against the ref.py backward oracle."""
        hq, hkv, s, d, group = 4, 2, 64, 16, 2
        q = jnp.array(rng.normal(size=(hq, s, d)), jnp.float32)
        k = jnp.array(rng.normal(size=(hkv, s, d)), jnp.float32)
        v = jnp.array(rng.normal(size=(hkv, s, d)), jnp.float32)
        do = jnp.array(rng.normal(size=(hq, s, d)), jnp.float32)
        o, lse = flash_attention_nhd(q, k, v, causal=True, block_q=32,
                                     block_k=32, group=group,
                                     return_residuals=True, interpret=True)
        delta = jnp.einsum("hsd,hsd->hs", do, o)
        dq, dk, dv = flash_attention_bwd_nhd(
            q, k, v, do, lse, delta, causal=True, block_q=32, block_k=32,
            group=group, interpret=True)
        rdq, rdk, rdv = attention_bwd_ref(q, k, v, do, causal=True,
                                          group=group)
        np.testing.assert_allclose(np.asarray(dq), np.asarray(rdq),
                                   atol=2e-4, rtol=2e-4)
        np.testing.assert_allclose(np.asarray(dk), np.asarray(rdk),
                                   atol=2e-4, rtol=2e-4)
        np.testing.assert_allclose(np.asarray(dv), np.asarray(rdv),
                                   atol=2e-4, rtol=2e-4)


def _wkv_case(rng, b, t, h, d):
    r = jnp.array(rng.normal(size=(b, t, h, d)), jnp.float32)
    k = jnp.array(rng.normal(size=(b, t, h, d)), jnp.float32)
    v = jnp.array(rng.normal(size=(b, t, h, d)), jnp.float32)
    w = jnp.array(rng.uniform(0.1, 0.9, (b, t, h, d)), jnp.float32)
    u = jnp.array(rng.normal(size=(h, d)), jnp.float32)
    g = jnp.array(rng.normal(size=(b, t, h, d)), jnp.float32)
    return r, k, v, w, u, g


class TestWkvFusedBackward:
    @pytest.mark.parametrize("shape", [
        (2, 32, 2, 8),
        (1, 64, 4, 16),
        (1, 24, 2, 4),      # non-divisor T (24 % 64 != 0)
        (2, 40, 1, 8),      # non-divisor T, single head
    ])
    def test_matches_reference_vjp(self, shape, rng):
        r, k, v, w, u, g = _wkv_case(rng, *shape)
        _, vjp = jax.vjp(lambda *a: K.wkv(*a), r, k, v, w, u)
        _, ref_vjp = jax.vjp(_exact_wkv, r, k, v, w, u)
        for name, got, want in zip("dr dk dv dw du".split(), vjp(g),
                                   ref_vjp(g)):
            np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                       atol=5e-4, rtol=5e-4, err_msg=name)

    def test_forced_small_time_block(self, rng):
        r, k, v, w, u, g = _wkv_case(rng, 1, 48, 2, 8)
        common.set_block("wkv.bwd", (48, 8), jnp.float32, (12, 8))
        _, vjp = jax.vjp(lambda *a: K.wkv(*a), r, k, v, w, u)
        _, ref_vjp = jax.vjp(_exact_wkv, r, k, v, w, u)
        for got, want in zip(vjp(g), ref_vjp(g)):
            np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                       atol=5e-4, rtol=5e-4)

    def test_ste_fallback_env(self, rng, monkeypatch):
        monkeypatch.setenv("REPRO_FUSED_BWD", "0")
        r, k, v, w, u, g = _wkv_case(rng, 1, 16, 2, 4)
        _, vjp = jax.vjp(lambda *a: K.wkv(*a), r, k, v, w, u)
        _, ref_vjp = jax.vjp(_exact_wkv, r, k, v, w, u)
        for got, want in zip(vjp(g), ref_vjp(g)):
            np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                       atol=5e-4, rtol=5e-4)
        assert common.cached_block("wkv.bwd", (16, 4), jnp.float32) is None

    def test_checkpoints_are_block_boundary_states(self, rng):
        """The residual checkpoints equal the scan states at block starts."""
        bh, t, d, bt = 2, 32, 8, 8
        r = jnp.array(rng.normal(size=(bh, t, d)), jnp.float32)
        k = jnp.array(rng.normal(size=(bh, t, d)), jnp.float32)
        v = jnp.array(rng.normal(size=(bh, t, d)), jnp.float32)
        w = jnp.array(rng.uniform(0.1, 0.9, (bh, t, d)), jnp.float32)
        u = jnp.array(rng.normal(size=(bh, d)), jnp.float32)
        _, ckpt = wkv_recurrence(r, k, v, w, u, block_t=bt,
                                 return_residuals=True, interpret=True)
        assert ckpt.shape == (bh, t // bt, d, d)
        # state before token 0 is zero
        np.testing.assert_allclose(np.asarray(ckpt[:, 0]), 0.0)
        # replay the recurrence to the second block boundary
        s = jnp.zeros((bh, d, d))
        for i in range(bt):
            kv = k[:, i, :, None] * v[:, i, None, :]
            s = w[:, i, :, None] * s + kv
        np.testing.assert_allclose(np.asarray(ckpt[:, 1]), np.asarray(s),
                                   atol=1e-5, rtol=1e-5)

    def test_raw_bwd_kernel_vs_ref(self, rng):
        bh, t, d, bt = 2, 32, 8, 8
        r = jnp.array(rng.normal(size=(bh, t, d)), jnp.float32)
        k = jnp.array(rng.normal(size=(bh, t, d)), jnp.float32)
        v = jnp.array(rng.normal(size=(bh, t, d)), jnp.float32)
        w = jnp.array(rng.uniform(0.1, 0.9, (bh, t, d)), jnp.float32)
        u = jnp.array(rng.normal(size=(bh, d)), jnp.float32)
        dy = jnp.array(rng.normal(size=(bh, t, d)), jnp.float32)
        _, ckpt = wkv_recurrence(r, k, v, w, u, block_t=bt,
                                 return_residuals=True, interpret=True)
        got = wkv_recurrence_bwd(r, k, v, w, u, dy, ckpt, block_t=bt,
                                 interpret=True)
        want = wkv_bwd_ref(r, k, v, w, u, dy)
        for name, g_, w_ in zip("dr dk dv dw du".split(), got, want):
            np.testing.assert_allclose(np.asarray(g_), np.asarray(w_),
                                       atol=5e-4, rtol=5e-4, err_msg=name)


class TestFusedVjpHelper:
    def test_uses_fused_pair_when_given(self):
        calls = []

        def fwd(x):
            return x * 2.0

        def fwd_res(x):
            calls.append("fwd_res")
            return x * 2.0, (x,)

        def bwd(res, g):
            calls.append("bwd")
            return (g * 3.0,)       # deliberately not the STE grad

        f = common.fused_vjp(fwd, jnp.sin, fwd_res, bwd)
        x = jnp.ones((4,))
        g = jax.grad(lambda v: f(v).sum())(x)
        np.testing.assert_allclose(np.asarray(g), 3.0)
        assert calls == ["fwd_res", "bwd"]

    def test_falls_back_to_ste_without_pair(self):
        f = common.fused_vjp(jnp.round, jnp.tanh)
        x = jnp.linspace(-2.0, 2.0, 9)
        g = jax.grad(lambda v: f(v).sum())(x)
        np.testing.assert_allclose(np.asarray(g),
                                   np.asarray(1 - jnp.tanh(x) ** 2),
                                   rtol=1e-6)

    def test_env_disables_fused_pair(self, monkeypatch):
        monkeypatch.setenv("REPRO_FUSED_BWD", "0")

        def boom(*a):
            raise AssertionError("fused pair must not run")

        f = common.fused_vjp(jnp.round, jnp.tanh, boom, boom)
        x = jnp.linspace(-2.0, 2.0, 5)
        g = jax.grad(lambda v: f(v).sum())(x)
        np.testing.assert_allclose(np.asarray(g),
                                   np.asarray(1 - jnp.tanh(x) ** 2),
                                   rtol=1e-6)

    def test_enabled_flag(self, monkeypatch):
        monkeypatch.delenv("REPRO_FUSED_BWD", raising=False)
        assert common.fused_backward_enabled()
        monkeypatch.setenv("REPRO_FUSED_BWD", "0")
        assert not common.fused_backward_enabled()
        monkeypatch.setenv("REPRO_FUSED_BWD", "1")
        assert common.fused_backward_enabled()


class TestRegistrySeam:
    def test_float_families_register_grad_kernels(self):
        assert common.get_kernel("flash_attention").grad_kernel \
            is flash_attention_bwd_nhd
        assert common.get_kernel("wkv").grad_kernel is wkv_recurrence_bwd

    def test_bwd_specs_registered_with_candidates(self):
        for name in ("flash_attention.bwd", "wkv.bwd"):
            spec = common.get_kernel(name)
            assert "backward" in spec.tags
            cands = spec.candidates((64, 64), jnp.float32)
            assert cands and all(len(c) == 2 for c in cands)

    def test_fixed_point_families_have_no_grad_kernel(self):
        for name in ("cordic_act", "cordic_mac", "cordic_softmax"):
            assert common.get_kernel(name).grad_kernel is None


class TestExplicitBlockSkipsPick:
    """Satellite: explicit blocks must bypass pick_block_* entirely (no
    cache entry is written — the observable effect of the pick)."""

    def test_flash_explicit_blocks(self, rng):
        q, k, v, _ = _flash_case(rng, 1, 32, 2, 2, 8)
        K.flash_attention(q, k, v, block_q=16, block_k=16)
        assert common.cached_block("flash_attention", (32, 32),
                                   jnp.float32) is None

    def test_wkv_explicit_block(self, rng):
        r, k, v, w, u, _ = _wkv_case(rng, 1, 16, 2, 4)
        K.wkv(r, k, v, w, u, block_t=8)
        assert common.cached_block("wkv", (16, 4), jnp.float32) is None
