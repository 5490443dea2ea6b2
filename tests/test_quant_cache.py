"""Deterministic invariants of the int8 per-block quantized slot cache.

Hypothesis-free counterpart of ``tests/test_quant_numerics.py`` (the
property-based layer): this module must run even in minimal
environments, so the quantized-serving contract keeps coverage when
hypothesis is absent.

The contract under test (see ``core/quant_cache.py`` and the serving
plumbing in ``models/transformer.py`` / ``runtime/serve_loop.py``):

  * round-trip |x - dq(q(x))| <= scale/2 per trailing-axis block, and
    all-zero blocks come back exactly zero (scale stored as 0)
  * quantization is per-vector deterministic, so quantize-then-scatter
    equals scatter-then-quantize and any slot permutation commutes
  * ``cache_quant="int8"`` and the legacy fixed-scale ``kv_cache_bits=8``
    KV format are mutually exclusive (ValueError, not silent precedence)
  * ``ServeEngine(cache_dtype="int8")`` serves all three families within
    the committed logit-error ceiling, one decode trace per bucket
"""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_arch
from repro.core.quant_cache import dequantize_blocked, quantize_blocked
from repro.models.model_zoo import build_model
from repro.runtime.serve_loop import Request, ServeEngine

_BASELINE = os.path.join(os.path.dirname(__file__), os.pardir,
                         "benchmarks", "quant_baseline.json")
ARCHS = ("glm4-9b", "rwkv6-3b", "hymba-1.5b", "falcon-h1-34b")


# ---------------------------------------------------------------- numerics

def test_roundtrip_error_bound():
    rng = np.random.default_rng(0)
    for shape in [(16,), (3, 5, 32), (2, 4, 8, 16)]:
        x = jnp.asarray(rng.normal(0, 3.0, shape).astype(np.float32))
        q, s = quantize_blocked(x)
        assert q.dtype == jnp.int8 and s.dtype == jnp.float32
        assert s.shape == x.shape[:-1] + (1,)
        dq = dequantize_blocked(q, s)
        # per-block bound: half a quantization step
        bound = np.broadcast_to(np.asarray(s) / 2.0 + 1e-12, x.shape)
        assert np.all(np.abs(np.asarray(x - dq)) <= bound)


def test_blocked_scales():
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.normal(0, 1.0, (4, 32)).astype(np.float32))
    q, s = quantize_blocked(x, block=8)
    assert s.shape == (4, 4)
    dq = dequantize_blocked(q, s)
    step = np.repeat(np.asarray(s), 8, axis=-1)
    assert np.all(np.abs(np.asarray(x - dq)) <= step / 2.0 + 1e-12)


def test_zero_block_exact():
    x = jnp.zeros((5, 16), jnp.float32)
    q, s = quantize_blocked(x)
    assert np.all(np.asarray(q) == 0)
    assert np.all(np.asarray(s) == 0.0)       # not a tiny epsilon scale
    assert np.all(np.asarray(dequantize_blocked(q, s)) == 0.0)
    # mixed: a zero row next to a live row stays exactly zero
    y = x.at[2].set(1.5)
    qy, sy = quantize_blocked(y)
    assert np.all(np.asarray(dequantize_blocked(qy, sy))[0] == 0.0)


def test_scatter_then_read_equals_read_then_scatter():
    """Per-vector scales make quantization commute with slot scatter:
    quantizing rows then scattering them into the int8 cache yields the
    same cache as quantizing the scattered fp cache (what slot_update
    relies on to touch only the updated slot)."""
    rng = np.random.default_rng(2)
    cache = jnp.asarray(rng.normal(0, 1.0, (4, 6, 16)).astype(np.float32))
    rows = jnp.asarray(rng.normal(0, 2.0, (2, 6, 16)).astype(np.float32))
    idx = jnp.asarray([3, 1])

    qc, sc = quantize_blocked(cache)
    qr, sr = quantize_blocked(rows)
    scat_q = qc.at[idx].set(qr)
    scat_s = sc.at[idx].set(sr)

    q2, s2 = quantize_blocked(cache.at[idx].set(rows))
    assert np.array_equal(np.asarray(scat_q), np.asarray(q2))
    assert np.array_equal(np.asarray(scat_s), np.asarray(s2))


def test_permutation_invariance():
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.normal(0, 1.0, (8, 3, 16)).astype(np.float32))
    perm = jnp.asarray(rng.permutation(8))
    q, s = quantize_blocked(x)
    qp, sp = quantize_blocked(x[perm])
    assert np.array_equal(np.asarray(q[perm]), np.asarray(qp))
    assert np.array_equal(np.asarray(s[perm]), np.asarray(sp))


# ------------------------------------------------------------- validation

def test_int8_and_legacy_kv_bits_are_mutually_exclusive():
    cfg = get_arch("glm4-9b").reduced().scaled(cache_quant="int8",
                                               kv_cache_bits=8)
    model = build_model(cfg)
    with pytest.raises(ValueError, match="mutually exclusive"):
        model.init_slot_state(2, 32, abstract=True)


def test_unknown_cache_quant_rejected():
    cfg = get_arch("glm4-9b").reduced().scaled(cache_quant="int4")
    with pytest.raises(ValueError):
        build_model(cfg).init_slot_state(2, 32, abstract=True)


def test_with_cache_dtype():
    model = build_model(get_arch("glm4-9b").reduced())
    assert model.with_cache_dtype(None) is model
    assert model.with_cache_dtype("none") is model
    q = model.with_cache_dtype("int8")
    assert q.cfg.cache_quant == "int8"
    assert q.with_cache_dtype("int8") is q
    with pytest.raises(ValueError):
        model.with_cache_dtype("fp8")


def test_int8_state_at_least_2x_smaller_than_fp32():
    base = json.load(open(_BASELINE))
    for arch in ARCHS:
        cfg = get_arch(arch).reduced().scaled(dtype="float32")
        model = build_model(cfg)
        sizes = {}
        for name, m in [("fp", model), ("q", model.with_cache_dtype("int8"))]:
            st = m.init_slot_state(4, 64, abstract=True)
            sizes[name] = sum(int(np.prod(x.shape)) * np.dtype(x.dtype).itemsize
                              for x in jax.tree_util.tree_leaves(st))
        ratio = sizes["fp"] / sizes["q"]
        assert ratio >= base["slots_per_gb_floor"], (arch, ratio)


# ------------------------------------------------------- engine integration

@pytest.mark.parametrize("arch", ARCHS)
def test_engine_int8_within_committed_ceiling(arch):
    """The acceptance criterion: int8-cache decode tracks fp32-cache
    decode within the committed per-arch logit-error ceiling, with the
    bucketed single-trace discipline intact."""
    ceiling = json.load(open(_BASELINE))["max_logit_err"][arch]
    cfg = get_arch(arch).reduced().scaled(dtype="float32")
    model = build_model(cfg)
    model_q = model.with_cache_dtype("int8")
    params = model.init(jax.random.PRNGKey(0))

    rng = np.random.default_rng(0)
    prompt = rng.integers(0, cfg.vocab_size, 7).astype(np.int32)
    batch = {"tokens": jnp.asarray(prompt[None, :])}
    lg_f, st_f = model.prefill(params, batch, headroom=16)
    lg_q, st_q = model_q.prefill(params, batch, headroom=16)
    worst = float(jnp.max(jnp.abs(lg_f - lg_q)))
    cur = int(jnp.argmax(lg_f.reshape(1, -1)[0]))
    for _ in range(8):
        nb = {"tokens": jnp.asarray([[cur]], jnp.int32)}
        lg_f, st_f = model.decode_step(params, st_f, nb)
        lg_q, st_q = model_q.decode_step(params, st_q, nb)
        worst = max(worst, float(jnp.max(jnp.abs(lg_f - lg_q))))
        cur = int(jnp.argmax(lg_f.reshape(1, -1)[0]))
    assert worst <= ceiling, (arch, worst, ceiling)

    # engine end to end: mixed lengths, no drops, one decode trace
    reqs = []
    for i, (n, m) in enumerate([(3, 4), (9, 3), (5, 5)]):
        p = rng.integers(0, cfg.vocab_size, n).astype(np.int32)
        reqs.append(Request(i, p, max_new_tokens=m))
    eng = ServeEngine(model, params, max_batch=4, max_seq=64,
                      cache_dtype="int8")
    done = {r.rid: r for r in eng.serve(reqs)}
    assert len(done) == 3
    assert all(len(done[i].output) == m
               for i, (_, m) in enumerate([(3, 4), (9, 3), (5, 5)]))
    assert eng.trace_counts["decode"] == 1, eng.trace_counts
