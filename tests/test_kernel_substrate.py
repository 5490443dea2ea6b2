"""Substrate + dispatch tests for kernels/common.py.

Deliberately hypothesis-free: this module must run even in minimal
environments where the property-test modules importorskip, so it carries
the smoke coverage for all five kernel families too.
"""
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.kernels as K
from repro.kernels import common
from repro.kernels.cordic_act.ref import cordic_act_raw_ref
from repro.kernels.cordic_softmax.ref import cordic_softmax_raw_ref
from repro.kernels.flash_attention.ops import _exact_attention
from repro.kernels.wkv.ops import _exact_wkv
from repro.core import fixed_point as fxp


class TestBlockPicker:
    def test_largest_divisor_invariants(self):
        for n in range(1, 200):
            for cap in (1, 3, 7, 8, 100, 128, 512):
                d = common.largest_divisor(n, cap)
                assert 1 <= d <= min(cap, n) or (cap < 1 and d == 1)
                assert n % d == 0
                # maximality: nothing between d and cap divides n
                assert all(n % e for e in range(d + 1, min(cap, n) + 1))

    def test_pick_block_2d_divides(self):
        for shape in [(1, 1), (8, 8), (13, 77), (256, 300), (1000, 4096)]:
            br, bc = common.pick_block_2d("t.p2d", shape)
            assert shape[0] % br == 0 and shape[1] % bc == 0
            assert br <= 256 and bc <= 512

    def test_cache_round_trip(self):
        common.clear_block_cache()
        assert common.cached_block("t.cache", (64, 64), jnp.int32) is None
        blk = common.pick_block_2d("t.cache", (64, 64))
        assert common.cached_block("t.cache", (64, 64), jnp.int32) == blk
        # dtype and kernel name are part of the key
        assert common.cached_block("t.cache", (64, 64), jnp.float32) is None
        assert common.cached_block("other", (64, 64), jnp.int32) is None

    def test_autotune_overrides_picker(self):
        common.clear_block_cache()
        calls = []

        def run(blk):
            calls.append(blk)
            # pretend (8, 8) is fastest by sleeping on everything else
            if blk != (8, 8):
                import time
                time.sleep(0.01)
            return jnp.zeros(())

        best = common.autotune("t.tune", (64, 64), jnp.int32,
                               [(64, 64), (8, 8), (16, 16)], run, repeats=1)
        assert best == (8, 8)
        assert common.pick_block_2d("t.tune", (64, 64)) == (8, 8)

    def test_autotune_skips_failing_candidates(self):
        common.clear_block_cache()

        def run(blk):
            if blk == (4, 4):
                raise RuntimeError("vmem overflow")
            return jnp.zeros(())

        best = common.autotune("t.fail", (16, 16), jnp.int32,
                               [(4, 4), (2, 2)], run, repeats=1)
        assert best == (2, 2)

    def test_pick_block_matmul_cached(self):
        common.clear_block_cache()
        blk = common.pick_block_matmul("t.mm", 512, 512, 512)
        assert len(blk) == 3 and all(b >= 8 for b in blk)
        assert common.cached_block("t.mm", (512, 512, 512), jnp.int32) == blk


class TestRegistry:
    def test_all_five_families_registered(self):
        names = common.registered_kernels()
        for want in ("cordic_act", "cordic_mac", "cordic_softmax",
                     "flash_attention", "wkv"):
            assert want in names

    def test_spec_round_trip(self):
        spec = common.get_kernel("cordic_mac")
        assert spec.name == "cordic_mac"
        assert callable(spec.kernel) and callable(spec.ref)
        assert callable(spec.grad)

    def test_unknown_kernel_raises(self):
        with pytest.raises(KeyError, match="no kernel"):
            common.get_kernel("does_not_exist")

    def test_register_is_idempotent(self):
        before = common.get_kernel("wkv")
        importlib.reload(importlib.import_module("repro.kernels.wkv.ops"))
        after = common.get_kernel("wkv")
        assert after.name == before.name and callable(after.kernel)


class TestCompat:
    """The jax API names the tree calls directly (one supported jax)."""

    def test_shard_map_importable(self):
        from repro.models import moe
        assert moe.shard_map is jax.shard_map

    def test_compiler_params_constructs(self):
        cp = common.compiler_params("parallel", "arbitrary")
        assert cp.dimension_semantics == ("parallel", "arbitrary")


class TestInterpretPolicy:
    def test_explicit_wins(self, monkeypatch):
        monkeypatch.setenv("REPRO_KERNEL_INTERPRET", "0")
        assert common.resolve_interpret(True) is True
        assert common.resolve_interpret(False) is False

    def test_env_override(self, monkeypatch):
        monkeypatch.setenv("REPRO_KERNEL_INTERPRET", "0")
        assert common.resolve_interpret(None) is False
        monkeypatch.setenv("REPRO_KERNEL_INTERPRET", "1")
        assert common.resolve_interpret(None) is True

    def test_default_interprets_off_tpu(self, monkeypatch):
        monkeypatch.delenv("REPRO_KERNEL_INTERPRET", raising=False)
        assert common.resolve_interpret(None) == (not common.on_tpu())

    def test_interpret_on_tpu_warns(self, monkeypatch):
        monkeypatch.setattr(common, "on_tpu", lambda: True)
        monkeypatch.setenv("REPRO_KERNEL_INTERPRET", "1")
        with pytest.warns(RuntimeWarning, match="interpret mode on a TPU"):
            assert common.resolve_interpret(None) is True
        monkeypatch.delenv("REPRO_KERNEL_INTERPRET")
        assert common.resolve_interpret(None) is False


class TestSte:
    def test_forward_is_kernel_backward_is_exact(self):
        fwd = lambda x: jnp.round(x)          # non-differentiable forward
        f = common.ste(fwd, jnp.tanh)
        x = jnp.linspace(-2.0, 2.0, 9)
        np.testing.assert_array_equal(np.asarray(f(x)), np.asarray(fwd(x)))
        g = jax.grad(lambda v: f(v).sum())(x)
        np.testing.assert_allclose(np.asarray(g),
                                   np.asarray(1 - jnp.tanh(x) ** 2),
                                   rtol=1e-6)

    def test_multi_arg(self):
        f = common.ste(lambda a, b: jnp.round(a) @ jnp.round(b),
                       lambda a, b: a @ b)
        a = jnp.ones((3, 4)) * 1.3
        b = jnp.ones((4, 2)) * 0.7
        ga, gb = jax.grad(lambda a_, b_: f(a_, b_).sum(), argnums=(0, 1))(a, b)
        assert ga.shape == a.shape and gb.shape == b.shape


class TestFamilySmoke:
    """Numeric coverage for the dispatch path of every family, vs oracles."""

    def test_cordic_act_bit_exact_and_band(self, rng):
        fmt = fxp.FXP16
        x = jnp.array(rng.uniform(-3, 3, (16, 32)), jnp.float32)
        raw = fxp.quantize(x, fmt)
        spec = common.get_kernel("cordic_act")
        got = spec.kernel(raw, af="tanh", fmt=fmt, interpret=True)
        want = spec.ref(raw, af="tanh", fmt=fmt)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        out = K.cordic_act(x, "tanh")
        assert float(jnp.abs(out - jnp.tanh(x)).max()) < 0.05

    def test_cordic_act_ste_gradient(self, rng):
        x = jnp.array(rng.uniform(-2, 2, (8, 8)), jnp.float32)
        g = jax.grad(lambda v: K.cordic_act(v, "sigmoid").sum())(x)
        s = jax.nn.sigmoid(x)
        np.testing.assert_allclose(np.asarray(g), np.asarray(s * (1 - s)),
                                   rtol=1e-5)

    def test_cordic_softmax_bit_exact_and_normalised(self, rng):
        fmt = fxp.FXP16
        x = jnp.array(rng.normal(size=(8, 64)) * 2, jnp.float32)
        raw = fxp.quantize(x - x.max(-1, keepdims=True), fmt)
        spec = common.get_kernel("cordic_softmax")
        got = spec.kernel(raw, fmt=fmt, interpret=True)
        want = spec.ref(raw, fmt=fmt)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        s = K.cordic_softmax(x)
        assert float(jnp.abs(s.sum(-1) - 1.0).max()) < 0.05

    def test_cordic_matmul_close_and_grads(self, rng):
        x = jnp.array(rng.uniform(-1, 1, (24, 40)), jnp.float32)
        w = jnp.array(rng.uniform(-1, 1, (40, 16)), jnp.float32)
        out = K.cordic_matmul(x, w, n_stages=12)
        ref = x @ w
        scale = float(jnp.abs(ref).max()) + 1.0
        assert float(jnp.abs(out - ref).max()) / scale < 0.05
        gx, gw = jax.grad(lambda a, b: K.cordic_matmul(a, b).sum(),
                          argnums=(0, 1))(x, w)
        # the STE backward is the exact matmul VJP: compare with that VJP
        # itself (a hand-written ones @ w.T may sum in another order)
        _, vjp = jax.vjp(lambda a: a @ w, x)
        np.testing.assert_allclose(np.asarray(gx),
                                   np.asarray(vjp(jnp.ones((24, 16)))[0]),
                                   rtol=1e-5)
        assert gw.shape == w.shape

    def test_flash_attention_matches_ref(self, rng):
        q = jnp.array(rng.normal(size=(2, 16, 4, 8)), jnp.float32)
        k = jnp.array(rng.normal(size=(2, 16, 2, 8)), jnp.float32)
        v = jnp.array(rng.normal(size=(2, 16, 2, 8)), jnp.float32)
        out = K.flash_attention(q, k, v, block_q=8, block_k=8)
        ref = _exact_attention(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=1e-5)
        g = jax.grad(lambda qq: K.flash_attention(
            qq, k, v, block_q=8, block_k=8).sum())(q)
        assert bool(jnp.isfinite(g).all())

    def test_wkv_matches_ref(self, rng):
        r = jnp.array(rng.normal(size=(2, 12, 2, 4)), jnp.float32)
        k = jnp.array(rng.normal(size=(2, 12, 2, 4)), jnp.float32)
        v = jnp.array(rng.normal(size=(2, 12, 2, 4)), jnp.float32)
        w = jnp.array(rng.uniform(0.1, 0.9, (2, 12, 2, 4)), jnp.float32)
        u = jnp.array(rng.normal(size=(2, 4)), jnp.float32)
        out = K.wkv(r, k, v, w, u, block_t=4)
        ref = _exact_wkv(r, k, v, w, u)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=1e-4)
        g = jax.grad(lambda uu: K.wkv(r, k, v, w, uu).sum())(u)
        assert bool(jnp.isfinite(g).all())

    def test_autotuned_block_reaches_the_kernel(self, rng):
        """A cache entry installed after a first call must change the block
        the next call runs with (the pick happens outside the jit trace)."""
        from repro.kernels.cordic_act import ops as act_ops
        common.clear_block_cache()
        x = jnp.array(rng.uniform(-2, 2, (8, 16)), jnp.float32)
        out_default = K.cordic_act(x, "tanh")
        n_traces = act_ops._fwd._cache_size()
        common.set_block("cordic_act.tanh", (8, 16), jnp.int32, (2, 4))
        out_tuned = K.cordic_act(x, "tanh")
        assert act_ops._fwd._cache_size() > n_traces  # new block => retrace
        np.testing.assert_array_equal(np.asarray(out_default),
                                      np.asarray(out_tuned))
        common.clear_block_cache()

    def test_odd_shapes_dispatch(self, rng):
        """The divisor-aware picker must handle prime-ish shapes."""
        x = jnp.array(rng.uniform(-2, 2, (7, 13)), jnp.float32)
        out = K.cordic_act(x, "tanh")
        assert out.shape == (7, 13)
        s = K.cordic_softmax(jnp.array(rng.normal(size=(5, 11)), jnp.float32))
        assert float(jnp.abs(s.sum(-1) - 1.0).max()) < 0.05
