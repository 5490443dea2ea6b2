"""Per-architecture smoke tests (reduced configs) + model-level invariants.

Each of the 11 registered architectures instantiates a reduced config of the
same family and runs one forward/train step on CPU, asserting output shapes
and the absence of NaNs — plus serving-path consistency checks.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS, CORDIC_EXEC, get_arch
from repro.configs.base import ExecutionPolicy
from repro.models import transformer as T
from repro.models.model_zoo import build_model

ALL_ARCHS = sorted(ARCHS)


def _batch(model, key, b=2, s=16):
    return model.make_batch(key, b, s, "train")


@pytest.mark.parametrize("arch", ALL_ARCHS)
def test_smoke_forward_and_train_step(arch):
    cfg = get_arch(arch).reduced()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    batch = _batch(model, jax.random.PRNGKey(1))
    logits = model.forward(params, batch)
    b, s = 2, 16
    if cfg.n_codebooks:
        assert logits.shape == (b, s, cfg.n_codebooks, cfg.vocab_size)
    else:
        assert logits.shape == (b, s, cfg.vocab_size)
    assert bool(jnp.isfinite(logits.astype(jnp.float32)).all())

    # one SGD step: loss must be finite and decrease-able (grads finite)
    (loss, metrics), grads = jax.value_and_grad(
        lambda p: model.loss(p, batch), has_aux=True)(params)
    assert np.isfinite(float(loss))
    leaves = jax.tree_util.tree_leaves(grads)
    assert all(bool(jnp.isfinite(g.astype(jnp.float32)).all()) for g in leaves)
    params2 = jax.tree_util.tree_map(
        lambda p, g: p - 0.5 * g.astype(p.dtype), params, grads)
    loss2, _ = model.loss(params2, batch)
    assert np.isfinite(float(loss2))


@pytest.mark.parametrize("arch", ALL_ARCHS)
def test_smoke_serving_consistency(arch):
    """prefill's last logits == forward's last logits; decode runs."""
    cfg = get_arch(arch).reduced()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    batch = model.make_batch(jax.random.PRNGKey(1), 2, 16, "prefill")
    lf = model.forward(params, batch)[:, -1:]
    lp, state = model.prefill(params, batch)
    np.testing.assert_allclose(
        np.asarray(lp.astype(jnp.float32)).reshape(2, -1),
        np.asarray(lf.astype(jnp.float32)).reshape(2, -1),
        atol=1e-2, rtol=1e-2)
    nb = model.make_batch(jax.random.PRNGKey(2), 2, 1, "decode")
    dl, state2 = model.decode_step(params, state, nb)
    assert int(state2.pos) == 17
    assert bool(jnp.isfinite(dl.astype(jnp.float32)).all())


@pytest.mark.parametrize("arch", ["glm4-9b", "rwkv6-3b", "hymba-1.5b",
                                  "falcon-h1-34b"])
def test_decode_matches_forward(arch):
    """Teacher-forced decode of the last token == forward at that position."""
    cfg = get_arch(arch).reduced().scaled(dtype="float32")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    full = model.make_batch(jax.random.PRNGKey(1), 2, 16, "prefill")
    key = "tokens" if cfg.input_kind == "tokens" else "frames"
    prefix = {key: full[key][:, :15]}
    last = {key: full[key][:, 15:16]}
    _, state = model.prefill(params, prefix)
    dl, _ = model.decode_step(params, state, last)
    lf = model.forward(params, full)[:, -1:]
    np.testing.assert_allclose(
        np.asarray(dl.astype(jnp.float32)).reshape(2, -1),
        np.asarray(lf.astype(jnp.float32)).reshape(2, -1),
        atol=1e-3, rtol=1e-3)


def test_chunked_matches_naive_attention():
    cfg = get_arch("glm4-9b").reduced().scaled(attn_impl="naive",
                                               dtype="float32")
    cfg_c = cfg.scaled(attn_impl="chunked", attn_chunk=8)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    batch = model.make_batch(jax.random.PRNGKey(1), 2, 32, "prefill")
    a = build_model(cfg).forward(params, batch)
    b = build_model(cfg_c).forward(params, batch)
    np.testing.assert_allclose(np.asarray(a.astype(jnp.float32)),
                               np.asarray(b.astype(jnp.float32)),
                               atol=1e-4, rtol=1e-4)


def test_sliding_window_limits_context():
    """A token outside every window cannot influence the output."""
    cfg = get_arch("hymba-1.5b").reduced().scaled(
        sliding_window=4, global_attn_every=0, attn_impl="naive")
    # pure-window attention (no global layers): perturb token 0, check the
    # last position (t=15, window 4 => sees 12..15 only) via attention-only
    # model: isolate by zeroing the ssm branch is overkill; instead compare
    # attention masks directly.
    from repro.models.attention import _causal_window_mask
    m = _causal_window_mask(jnp.arange(16), jnp.arange(16), 4)
    assert not bool(m[15, 0])
    assert bool(m[15, 12]) and bool(m[15, 15])
    assert not bool(m[0, 1])  # causal


@pytest.mark.parametrize("arch", ["glm4-9b", "granite-moe-3b-a800m"])
def test_cordic_execution_mode(arch):
    """The paper's FxP8+DA-VINCI policy runs end-to-end without NaNs and
    stays close to the bf16 reference (QAT-grade fidelity)."""
    cfg = get_arch(arch).reduced().scaled(dtype="float32")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    batch = model.make_batch(jax.random.PRNGKey(1), 2, 16, "train")
    ref = model.forward(params, batch, ExecutionPolicy(matmul="bf16"))
    got = model.forward(params, batch, CORDIC_EXEC)
    assert bool(jnp.isfinite(got.astype(jnp.float32)).all())
    # logits correlate strongly with the float path
    a = np.asarray(ref.astype(jnp.float32)).ravel()
    g = np.asarray(got.astype(jnp.float32)).ravel()
    corr = np.corrcoef(a, g)[0, 1]
    assert corr > 0.95, corr


def test_moe_router_load_properties():
    """Capacity dispatch drops at most the expected fraction; gates sum 1."""
    from repro.models import moe as M
    cfg = get_arch("granite-moe-3b-a800m").reduced()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    bp = jax.tree_util.tree_map(lambda x: x[0], params["blocks"]["moe"])
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 16, cfg.d_model))
    out, aux = M.moe_ffn(x, M.MoEParams(**bp), cfg, cfg.exec_policy)
    assert out.shape == x.shape
    assert np.isfinite(float(aux))
    assert float(aux) >= 1.0 - 1e-3  # Switch aux lower bound ~1 at balance


def test_musicgen_codebook_heads():
    cfg = get_arch("musicgen-medium").reduced()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    batch = model.make_batch(jax.random.PRNGKey(1), 2, 8, "train")
    assert batch["labels"].shape == (2, 8, cfg.n_codebooks)
    loss, _ = model.loss(params, batch)
    assert np.isfinite(float(loss))


def test_long_context_state_is_o1_for_ssm():
    """rwkv6 decode state must not scale with context length."""
    cfg = get_arch("rwkv6-3b").reduced()
    model = build_model(cfg)
    s1 = model.init_decode_state(1, 1024, abstract=True)
    s2 = model.init_decode_state(1, 524288, abstract=True)
    sz = lambda s: sum(np.prod(x.shape) for x in jax.tree_util.tree_leaves(s))
    assert sz(s1) == sz(s2)


def test_hymba_long_mode_ring_cache():
    """long_500k: hybrid cache is the sliding window, not the full context."""
    cfg = get_arch("hymba-1.5b")
    model = build_model(cfg)
    st = model.init_decode_state(1, 524288, abstract=True)
    assert st.cache_k.shape[3] == cfg.sliding_window     # (L,B,Hkv,S,dh)


def test_int8_kv_cache_decode_fidelity():
    """FxP8 (Q3.4) KV cache: decode logits stay faithful to the bf16 cache
    (the #Perf decode hillclimb's accuracy leg)."""
    cfg16 = get_arch("glm4-9b").reduced().scaled(dtype="float32")
    cfg8 = cfg16.scaled(kv_cache_bits=8)
    m16, m8 = build_model(cfg16), build_model(cfg8)
    params = m16.init(jax.random.PRNGKey(0))
    batch = m16.make_batch(jax.random.PRNGKey(1), 2, 15, "prefill")
    last = {"tokens": jnp.zeros((2, 1), jnp.int32)}
    _, st16 = m16.prefill(params, batch)
    _, st8 = m8.prefill(params, batch)
    assert st8.cache_k.dtype == jnp.int8
    l16, _ = m16.decode_step(params, st16, last)
    l8, _ = m8.decode_step(params, st8, last)
    a = np.asarray(l16.astype(jnp.float32)).ravel()
    b = np.asarray(l8.astype(jnp.float32)).ravel()
    assert np.corrcoef(a, b)[0, 1] > 0.99


def test_fused_moe_ffn_matches_unfused():
    """arctic's fused dense-FFN+MoE psum == separate computation (local)."""
    cfg = get_arch("arctic-480b").reduced().scaled(dtype="float32")
    cfg_f = cfg.scaled(fuse_moe_ffn_ar=True)
    m, mf = build_model(cfg), build_model(cfg_f)
    params = m.init(jax.random.PRNGKey(0))
    batch = m.make_batch(jax.random.PRNGKey(1), 2, 16, "train")
    a = np.asarray(m.forward(params, batch))
    b = np.asarray(mf.forward(params, batch))
    np.testing.assert_allclose(a, b, atol=1e-4, rtol=1e-4)


# ---------------------------------------------------------------------------
# Falcon-H1's Mamba-2 mixer: the chunked SSD scan against the recurrence
# ---------------------------------------------------------------------------

def _mamba2_setup(seed=0):
    """A reduced falcon-h1-34b in float32 (two groups of two heads, chunks
    of 8), one layer's mixer weights with a non-trivial decay and dt."""
    from repro.models import ssm as S
    cfg = get_arch("falcon-h1-34b").reduced().scaled(dtype="float32")
    params = build_model(cfg).init(jax.random.PRNGKey(seed))
    bp = jax.tree.map(lambda a: a[0], params["blocks"]["mamba2"])
    h = cfg.ssm_heads
    bp["a_log"] = jax.random.uniform(jax.random.PRNGKey(5), (h,),
                                     minval=0.0, maxval=2.0)
    bp["dt_bias"] = jax.random.uniform(jax.random.PRNGKey(6), (h,),
                                       minval=-4.0, maxval=0.0)
    bp["conv_b"] = 0.1 * jax.random.normal(jax.random.PRNGKey(7),
                                           bp["conv_b"].shape)
    return cfg, S.Mamba2Params(**bp)


@pytest.mark.parametrize("t,lengths", [
    (13, None),                 # ends mid-chunk
    (8, None),                  # exactly one chunk
    (29, None),                 # several chunks, the last cut short
    (24, (24, 9, 17)),          # right padding: rows end in other chunks
], ids=["mid-chunk", "one-chunk", "several-chunks", "right-padded"])
def test_ssd_chunked_scan_equals_stepwise_recurrence(t, lengths):
    """The prefill's chunked SSD form gives the outputs, the final state
    and the conv tail of the step-by-step recurrence (the path decode and
    verify run), from a non-zero starting state; pad steps leave the
    state exactly where the row's last real token put it."""
    from repro.models import ssm as S
    cfg, p = _mamba2_setup()
    b = 3 if lengths else 2
    x = jax.random.normal(jax.random.PRNGKey(3), (b, t, cfg.d_model))
    tail, hs = S.ssm_state_shapes(cfg)
    st = (jax.random.normal(jax.random.PRNGKey(8), (b,) + tail),
          jax.random.normal(jax.random.PRNGKey(4), (b,) + hs))
    kw = {}
    if lengths:
        n = jnp.asarray(lengths, jnp.int32)
        kw = dict(mask=jnp.arange(t)[None, :] < n[:, None], lengths=n)
    out, (tail_c, s_c) = S.mamba2_mix(x, p, cfg, cfg.exec_policy, st, **kw)
    ref, (tail_r, s_r), (tails, steps) = S.mamba2_mix(
        x, p, cfg, cfg.exec_policy, st, return_states=True, **kw)
    scale = float(jnp.abs(s_r).max())
    np.testing.assert_allclose(np.asarray(s_c), np.asarray(s_r),
                               atol=2e-6 * scale)
    np.testing.assert_array_equal(np.asarray(tail_c), np.asarray(tail_r))
    for i in range(b):
        n_i = lengths[i] if lengths else t
        np.testing.assert_allclose(np.asarray(out[i, :n_i]),
                                   np.asarray(ref[i, :n_i]), atol=2e-5)
        # the last real step's checkpoint is the carried state
        np.testing.assert_allclose(np.asarray(steps[i, n_i - 1]),
                                   np.asarray(s_r[i]), atol=0)


@pytest.mark.parametrize("cut", [1, 7, 8, 13, 20])
def test_falcon_prefill_then_decode_equals_forward(cut):
    """A prompt prefilled to ``cut`` tokens (mid-chunk, on a chunk
    boundary, or one token), then decoded one token at a time through the
    KV cache and the Mamba-2 state, gives at every position the logits of
    one full forward pass."""
    cfg = get_arch("falcon-h1-34b").reduced().scaled(dtype="float32")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    full = model.make_batch(jax.random.PRNGKey(1), 2, 24, "prefill")
    want = model.forward(params, full)
    lg, st = model.prefill(params, {"tokens": full["tokens"][:, :cut]},
                           headroom=24)
    got = [lg[:, 0]]
    for j in range(cut, 24):
        lg, st = model.decode_step(params, st,
                                   {"tokens": full["tokens"][:, j:j + 1]})
        got.append(lg[:, 0])
    got = jnp.stack(got[:-1], axis=1)
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(want[:, cut - 1:23]), atol=2e-5)


def test_hybrid_fusion_follows_the_config():
    """hymba keeps its per-branch norms; Falcon-H1's summed branches have
    none, and its SSM state is shaped by its Mamba-2 heads."""
    hy = build_model(get_arch("hymba-1.5b").reduced())
    fa = build_model(get_arch("falcon-h1-34b").reduced())
    assert {"norm_attn", "norm_ssm", "mamba"} <= set(
        hy.abstract_params()["blocks"])
    blk = fa.abstract_params()["blocks"]
    assert "mamba2" in blk
    assert not {"norm_attn", "norm_ssm", "mamba"} & set(blk)
    cfg = fa.cfg
    st = T.init_decode_state(cfg, 3, 32, abstract=True)
    assert st.ssm_h.shape == (cfg.n_layers, 3, cfg.ssm_heads,
                              cfg.ssm_head_dim, cfg.ssm_state)
    assert st.conv_tail.shape == (
        cfg.n_layers, 3, cfg.ssm_conv - 1,
        cfg.ssm_heads * cfg.ssm_head_dim + 2 * cfg.ssm_groups * cfg.ssm_state)


def test_mamba2_step_checkpoints_are_one_loop_not_unrolled():
    """The per-step checkpoints (verify and the paged extend) come from one
    ``lax.scan`` over the steps: the traced program has as many equations
    at 64 steps as at 8, and each step's conv tail is the K-1 inputs
    before it."""
    from repro.models import ssm as S
    cfg, p = _mamba2_setup()
    tail, hs = S.ssm_state_shapes(cfg)

    def n_eqns(t):
        def f(x):
            st = (jnp.zeros((1,) + tail), jnp.zeros((1,) + hs))
            return S.mamba2_mix(x, p, cfg, cfg.exec_policy, st,
                                return_states=True)
        return len(jax.make_jaxpr(f)(jnp.zeros((1, t, cfg.d_model))).eqns)

    assert n_eqns(64) == n_eqns(8)
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 11, cfg.d_model))
    st = (jax.random.normal(jax.random.PRNGKey(8), (2,) + tail),
          jnp.zeros((2,) + hs))
    _, _, (tails, _) = S.mamba2_mix(x, p, cfg, cfg.exec_policy, st,
                                    return_states=True)
    _, (tail_6, _) = S.mamba2_mix(x[:, :6], p, cfg, cfg.exec_policy, st)
    np.testing.assert_array_equal(np.asarray(tails[:, 5]),
                                  np.asarray(tail_6))


# -- the decode layer loop writes each layer's slice in place ----------------

# (arch, cache format) of each case: dense GQA, Falcon-H1's hybrid
# (attention beside Mamba-2), rwkv6, the per-block int8 cache, the paged pool
_IN_PLACE = {"dense": ("glm4-9b", {}),
             "hybrid": ("falcon-h1-34b", {}),
             "rwkv6": ("rwkv6-3b", {}),
             "int8": ("glm4-9b", {"dtype": "int8"}),
             "paged": ("glm4-9b", {"paged": True, "page_size": 8})}
_KV = ("cache_k", "cache_v", "scale_k", "scale_v")


def _python_scan(f, init, xs=None, length=None, reverse=False, unroll=1):
    """``jax.lax.scan`` as a plain Python loop over the leading axis."""
    assert not reverse
    n = length if xs is None else jax.tree.leaves(xs)[0].shape[0]
    carry, ys = init, []
    for i in range(n):
        carry, y = f(carry, None if xs is None
                     else jax.tree.map(lambda a: a[i], xs))
        ys.append(y)
    return carry, jax.tree.map(lambda *a: jnp.stack(a), *ys)


def _random_slot_state(model, cfg, b, s, page, rng):
    """A slot state with every leaf drawn at random, so an unwritten
    entry that changed shows."""
    if model.cfg.cache_spec().paged:
        n = b * s // page + 2
        st = model.cache_ops(num_blocks=n).init_slot_state(b, s)
    else:
        st = model.init_slot_state(b, s)
    out = {}
    for name in st._fields:
        v = getattr(st, name)
        if v is None or name == "pos":
            continue
        if name == "block_tables":
            t = rng.permutation(n)[:v.size].reshape(v.shape)
            t[0, -1] = n                    # one unallocated page
            out[name] = jnp.asarray(t, jnp.int32)
        elif v.dtype == jnp.int8:
            out[name] = jnp.asarray(rng.integers(-100, 100, v.shape), v.dtype)
        elif name.startswith("scale") or name.endswith("_scale"):
            out[name] = jnp.asarray(rng.uniform(1e-3, 2e-2, v.shape), v.dtype)
        else:
            out[name] = jnp.asarray(rng.normal(0, 0.5, v.shape), v.dtype)
    # per-row positions: mid-cache, the last column, and one past the
    # end (a dense cache ring-writes it at column 8, a pool drops it)
    return st._replace(pos=jnp.asarray([5, s - 1, s + 8], jnp.int32), **out)


@pytest.mark.parametrize("case", sorted(_IN_PLACE))
def test_decode_step_writes_only_each_layers_slice(case):
    """One ``decode_step`` over per-row positions changes each layer's K/V
    only at each row's column ``pos % S`` (a pool: at its page slot, none
    past the table), that column holds the layer's new K/V, and the
    logits and state are bit for bit those of the same per-layer body
    run as a plain Python loop, with nothing carried through a scan."""
    from unittest import mock

    from repro.configs.base import CacheSpec
    from repro.core.quant_cache import quantize_blocked
    from repro.models import attention as A

    arch, spec = _IN_PLACE[case]
    # float32: in bf16, XLA fuses the unrolled loop's elementwise chains
    # across layers and may keep a value unrounded that the scanned body
    # rounds, so only float32 compares the loop's structure bit for bit
    cfg = get_arch(arch).reduced().scaled(dtype="float32")
    model = build_model(cfg).with_cache_spec(CacheSpec(**spec))
    params = model.init(jax.random.PRNGKey(0))
    b, s, page = 3, 32, 8
    rng = np.random.default_rng(11)
    st0 = _random_slot_state(model, cfg, b, s, page, rng)
    toks = {"tokens": jnp.asarray(rng.integers(0, cfg.vocab_size, (b, 1)),
                                  jnp.int32)}
    logits, st1 = jax.jit(model.decode_step)(params, st0, toks)

    # the reference: the layers (and every inner scan) as Python loops in
    # one program, returning each layer's new K/V as the body computes it
    qkv = A.qkv

    def unscanned(p, st, t):
        new_kv = []

        def recorded(*a, **kw):
            out = qkv(*a, **kw)
            new_kv.append(out[1:])
            return out

        with mock.patch.object(jax.lax, "scan", _python_scan), \
                mock.patch.object(A, "qkv", recorded):
            return model.decode_step(p, st, t) + (new_kv,)

    ref_logits, ref, new_kv = jax.jit(unscanned)(params, st0, toks)
    np.testing.assert_array_equal(np.asarray(logits), np.asarray(ref_logits))
    for name in st1._fields:
        if getattr(st1, name) is not None:
            np.testing.assert_array_equal(np.asarray(getattr(st1, name)),
                                          np.asarray(getattr(ref, name)),
                                          err_msg=name)
    if cfg.family == "ssm":
        assert st0.cache_k is None
        return

    pos = np.asarray(st0.pos)
    assert len(new_kv) == cfg.n_layers
    for name in _KV:
        old, new = getattr(st0, name), getattr(st1, name)
        if old is None:
            continue
        old, new = np.asarray(old), np.asarray(new)
        written = np.zeros(old.shape, bool)
        for layer, (k, v) in enumerate(new_kv):
            col = (k if name.endswith("_k") else v)[:, 0]   # (B, Hkv, dh)
            if spec.get("dtype") == "int8":
                q_, sc = quantize_blocked(col)
                col = sc if name.startswith("scale") else q_
            col = np.asarray(col.astype(old.dtype))
            for row in range(b):
                if spec.get("paged"):               # (L, N, page, Hkv, dh)
                    blk = int(st0.block_tables[row, pos[row] // page]) \
                        if pos[row] < s else None
                    if blk is None or blk >= old.shape[1]:
                        continue                    # the write drops
                    at = (layer, blk, pos[row] % page)
                else:                               # (L, B, Hkv, S, dh)
                    at = (layer, row, slice(None), pos[row] % s)
                written[at] = True
                np.testing.assert_array_equal(new[at], col[row])
        np.testing.assert_array_equal(new[~written], old[~written],
                                      err_msg=name)
        assert written.any()
