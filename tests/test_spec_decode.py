"""Speculative decoding: the contract under test.

  * **Greedy bit-exactness** — spec decode is a pure scheduling change:
    per-request outputs are bit-identical to plain single-token decode
    across the dense (KV cache), ssm (recurrent) and hybrid families.
  * **Rollback** — rejected draft positions leave no trace: after a
    partial commit the recurrent state equals the plain-decode state and
    the stale K/V writes stay masked until overwritten.
  * **Per-slot mixed acceptance** — one batch can advance every slot by a
    different 0..k+1 without cross-talk.
  * **Drafter** — n-gram prompt lookup proposes through runs/cycles,
    rolls its speculative index back, and never exceeds k; the
    draft-model drafter reproduces its model's greedy chain and tiers
    down to the n-gram fallback when the model has no signal.
  * **Ring caches** — the long-context sliding-window preset verifies
    too: outputs stay bit-exact at and past the window boundary, and
    only a verify window wider than the ring is refused.
  * **Adaptive spec_k** — per-slot draft budgets walk to 0 on
    undraftable traffic (cutting verify dispatches) and back to
    spec_k_max on draftable traffic.
  * **Metrics** — spec_acceptance / tokens_per_step bookkeeping is sane
    and token conservation holds.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_arch
from repro.models.model_zoo import build_model, draft_arch
from repro.runtime.drafter import (DraftModelDrafter, Drafter, DraftSession,
                                   NGramDrafter, make_drafter)
from repro.runtime.serve_loop import Request, ServeConfig, ServeEngine

MAX_SEQ = 64


@pytest.fixture(scope="module")
def served():
    """One model + params (+ jitted decode oracle) per family."""
    cache = {}

    def get(arch):
        if arch not in cache:
            cfg = get_arch(arch).reduced()
            model = build_model(cfg)
            params = model.init(jax.random.PRNGKey(0))
            dec = jax.jit(
                lambda p, s, t: model.decode_step(p, s, {"tokens": t}))
            cache[arch] = (cfg, model, params, dec)
        return cache[arch]

    return get


def _single_stream(model, params, dec, prompt, max_new):
    """Plain greedy decode — the engine's correctness oracle."""
    lg, st = model.prefill(
        params, {"tokens": jnp.asarray(prompt[None, :])},
        headroom=MAX_SEQ - len(prompt))
    cur = int(jnp.argmax(lg.reshape(1, -1), axis=-1)[0])
    seq = [cur]
    for _ in range(max_new - 1):
        lg, st = dec(params, st, jnp.asarray([[cur]], jnp.int32))
        cur = int(jnp.argmax(lg.reshape(1, -1), axis=-1)[0])
        seq.append(cur)
    return seq


def _mixed_requests(cfg, lens, max_news, seed=0):
    rng = np.random.default_rng(seed)
    return [Request(i, rng.integers(0, cfg.vocab_size, n).astype(np.int32),
                    max_new_tokens=m)
            for i, (n, m) in enumerate(zip(lens, max_news))]


# ---------------------------------------------------------------------------
# Greedy bit-exactness across every stateful family
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("arch", ["glm4-9b", "rwkv6-3b", "hymba-1.5b",
                                  "falcon-h1-34b"])
def test_greedy_bitexact_vs_plain_decode(served, arch):
    """Spec decode must not change a single token — attention KV, rwkv
    recurrent and hybrid conv/ssm state all roll back exactly."""
    cfg, model, params, dec = served(arch)
    engine = ServeEngine(model, params, max_batch=4, max_seq=MAX_SEQ,
                         spec_k=4)
    reqs = _mixed_requests(cfg, lens=[5, 11, 16, 3, 24, 8],
                           max_news=[4, 9, 2, 12, 1, 14])
    done = engine.serve(reqs)
    assert len(done) == len(reqs)
    for r in done:
        ref = _single_stream(model, params, dec, r.prompt, r.max_new_tokens)
        assert list(r.output) == ref, (arch, r.rid)
    # greedy engines take the fused verify+accept+commit path: at most one
    # verify trace for the whole run (none if no step had drafts worth
    # verifying — the plain fallback), never a separate commit program
    assert engine.trace_counts["verify"] <= 1
    assert engine.trace_counts["commit"] == 0


# ---------------------------------------------------------------------------
# Rollback correctness after rejection (model-layer contract)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("arch", ["glm4-9b", "rwkv6-3b", "hymba-1.5b",
                                  "falcon-h1-34b"])
def test_rollback_after_rejection(served, arch):
    """verify_step + spec_commit with a partial advance must reproduce the
    plain-decode state exactly: logits, pos, recurrent fields — and the
    continuation after the rollback."""
    cfg, model, params, dec = served(arch)
    rng = np.random.default_rng(1)
    prompt = rng.integers(0, cfg.vocab_size, 9).astype(np.int32)
    lg, st0 = model.prefill(params, {"tokens": jnp.asarray(prompt[None])},
                            headroom=MAX_SEQ - len(prompt))
    cur = int(jnp.argmax(lg.reshape(1, -1)))
    # plain chain: 5 steps from the prefill state
    st = st0
    seq = [cur]
    seq_logits = []
    for _ in range(5):
        lg, st = dec(params, st, jnp.asarray([[seq[-1]]], jnp.int32))
        seq_logits.append(np.asarray(lg.reshape(-1).astype(jnp.float32)))
        seq.append(int(jnp.argmax(lg.reshape(1, -1))))
    # verify a window where drafts go wrong after 2 matches
    window = [seq[0], seq[1], seq[2],
              (seq[3] + 1) % cfg.vocab_size, 7]
    logits, stv, rec = model.verify_step(
        params, st0, {"tokens": jnp.asarray(np.array([window], np.int32))})
    par = np.asarray(logits.astype(jnp.float32))[0]
    for j in range(3):      # scored positions match plain logits bit-exact
        np.testing.assert_array_equal(par[j], seq_logits[j], err_msg=arch)
    # commit only the 3 verified-correct tokens (advance = accepted+1)
    stc = model.spec_commit(stv, rec, jnp.asarray([3], jnp.int32))
    np.testing.assert_array_equal(np.asarray(stc.pos).ravel(),
                                  [len(prompt) + 3])
    # recurrent fields equal the plain-decode state after 3 steps, to the
    # last bit but one: verify runs the window's steps inside one program
    # and decode one program per token, and XLA may fuse a state update's
    # multiply-add (w*S + kv, decay*h + drive) into one FMA in one and
    # round twice in the other.  Each committed step may move the f32
    # state by an ulp of its largest element; the bf16 logits above and
    # below stay bit-identical.
    st3 = st0
    for tok in window[:3]:
        _, st3 = dec(params, st3, jnp.asarray([[tok]], jnp.int32))
    for f in ("x_prev", "cm_prev", "wkv", "conv_tail", "ssm_h"):
        a, b = getattr(stc, f), getattr(st3, f)
        if a is None:
            continue
        b = np.asarray(b.astype(jnp.float32))
        np.testing.assert_allclose(
            np.asarray(a.astype(jnp.float32)), b, rtol=0,
            atol=3 * np.spacing(np.abs(b).max()), err_msg=(arch, f))
    # and decode continues identically despite the stale rejected writes
    lg_c, _ = model.decode_step(params, stc,
                                {"tokens": jnp.asarray([[seq[3]]],
                                                       jnp.int32)})
    np.testing.assert_array_equal(
        np.asarray(lg_c.reshape(-1).astype(jnp.float32)), seq_logits[3],
        err_msg=arch)


# ---------------------------------------------------------------------------
# Per-slot mixed acceptance in one batch
# ---------------------------------------------------------------------------

class _ScriptedSession(DraftSession):
    def __init__(self, stream):
        self.stream = list(stream)
        self.pos = 0

    def extend(self, tokens):
        self.pos += len(tokens)

    def draft(self, k):
        return self.stream[self.pos:self.pos + k]


class _ScriptedDrafter(Drafter):
    """Drafts the request's true continuation (keyed by prompt) for some
    requests and garbage for the rest — forcing full and zero acceptance
    side by side in one batch."""

    def __init__(self, streams):
        self.streams = streams          # first-token -> oracle stream

    def begin(self, context, slot=None, rid=None):
        key = context[0]
        if key in self.streams:
            return _ScriptedSession(self.streams[key][1:])  # after tok 1
        return _ScriptedSession([])


def test_mixed_acceptance_one_batch(served):
    cfg, model, params, dec = served("glm4-9b")
    rng = np.random.default_rng(5)
    p_full = rng.integers(0, cfg.vocab_size, 7).astype(np.int32)
    p_none = rng.integers(0, cfg.vocab_size, 9).astype(np.int32)
    p_full[0], p_none[0] = 1, 2         # drafter keys
    ref_full = _single_stream(model, params, dec, p_full, 12)
    ref_none = _single_stream(model, params, dec, p_none, 12)
    drafter = _ScriptedDrafter({1: ref_full})
    engine = ServeEngine(model, params, max_batch=2, max_seq=MAX_SEQ,
                         spec_k=4, drafter=drafter)
    done = engine.serve([Request(0, p_full, max_new_tokens=12),
                         Request(1, p_none, max_new_tokens=12)])
    outs = {r.rid: list(r.output) for r in done}
    assert outs[0] == ref_full
    assert outs[1] == ref_none
    # the scripted slot advanced k+1 per step, the other 1 per step: the
    # perfectly-drafted request must finish in far fewer steps
    ev = {(kind, rid): step for kind, rid, _, step in engine.events}
    assert ev[("retire", 0)] < ev[("retire", 1)]
    assert engine.metrics["draft_accepted"] > 0
    assert engine.metrics["tokens_per_step"] > 1.0


# ---------------------------------------------------------------------------
# Drafter unit tests
# ---------------------------------------------------------------------------

def test_ngram_drafter_run_and_cycle():
    d = NGramDrafter()
    # token run: proposes through the whole window, not one token
    assert d.draft([1, 2, 3, 7, 7, 7, 7], 4) == [7, 7, 7, 7]
    # period-2 cycle continues in phase
    assert d.draft([8, 5, 9, 5, 9, 5], 4) == [9, 5, 9, 5]
    # prompt lookup: the continuation of the matched prefix
    assert d.draft([10, 11, 12, 13, 20, 10, 11, 12], 3) == [13, 20, 10]
    # no repetition -> nothing proposed (never a wild guess)
    assert d.draft([1, 2, 3, 4, 5, 6], 4) == []
    # never more than k
    assert len(d.draft([7] * 30, 3)) == 3


def test_ngram_session_rollback_and_extend():
    d = NGramDrafter()
    s = d.begin([1, 2, 3, 7, 7, 7])
    first = s.draft(4)
    # drafting is speculative: the internal index rolls back, so a repeat
    # draft from the same state is identical
    assert s.draft(4) == first == [7, 7, 7, 7]
    # committing tokens shifts proposals like a fresh session would
    s.extend([7, 9])
    fresh = d.begin([1, 2, 3, 7, 7, 7, 7, 9])
    assert s.draft(4) == fresh.draft(4)


def test_ngram_drafter_validation():
    with pytest.raises(ValueError):
        NGramDrafter(max_ngram=2, min_ngram=3)
    with pytest.raises(ValueError):
        NGramDrafter(min_ngram=0)


# ---------------------------------------------------------------------------
# Engine metrics, validation, sampling fallback
# ---------------------------------------------------------------------------

def test_acceptance_metrics_and_conservation(served):
    cfg, model, params, dec = served("glm4-9b")
    engine = ServeEngine(model, params, max_batch=2, max_seq=MAX_SEQ,
                         spec_k=4)
    # motif prompts so some drafts actually land
    rng = np.random.default_rng(2)
    reqs = []
    for i, m in enumerate([10, 14, 8, 12]):
        motif = rng.integers(0, cfg.vocab_size, 3)
        prompt = np.tile(motif, 6)[:14].astype(np.int32)
        reqs.append(Request(i, prompt, max_new_tokens=m))
    done = engine.serve(reqs)
    assert len(done) == len(reqs)
    m = engine.metrics
    # motif prompts draft from the first step; the last step of a request
    # (budget 1 left) may fall back to the plain program
    assert 0 < m["spec_steps"] <= m["decode_steps"]
    assert 0.0 <= m["spec_acceptance"] <= 1.0
    assert m["draft_accepted"] <= m["draft_tokens"]
    assert m["tokens_per_step"] >= 1.0
    # conservation: decode tokens + one prefill token per request
    assert m["decode_tokens"] + len(reqs) == sum(r.max_new_tokens
                                                 for r in reqs)
    for r in done:
        assert len(r.output) == r.max_new_tokens


def test_spec_validation(served):
    cfg, model, params, _ = served("glm4-9b")
    with pytest.raises(ValueError):
        ServeEngine(model, params, spec_k=-1)
    # frame frontends have no draftable vocabulary: engine and model layer
    # both refuse
    frames_cfg = get_arch("llava-next-mistral-7b").reduced()
    frames_model = build_model(frames_cfg)
    assert frames_cfg.input_kind != "tokens"
    with pytest.raises(ValueError):
        ServeEngine(frames_model, None, spec_k=4)
    with pytest.raises(ValueError):
        frames_model.verify_step(None, None, {"frames": None})


# ---------------------------------------------------------------------------
# Fallback paths: ring-cache refusal, no-draft plain fallback, rejection
# sampling distribution
# ---------------------------------------------------------------------------

class _EmptySession(DraftSession):
    def extend(self, tokens):
        pass

    def draft(self, k):
        return []


class _EmptyDrafter(Drafter):
    """A drafter that never proposes — every step must take the plain
    single-token program, not a degenerate (B, k+1) verify."""

    def begin(self, context, slot=None, rid=None):
        return _EmptySession()


RING_SEQ = 131072   # hymba reduced: sliding_window=32 -> 32-slot ring


def test_ring_cache_spec_greedy_bitexact(served):
    """Long-context sliding-window decode stores a ring K/V cache whose
    seq axis is shorter than max_seq.  Ring verify wraps candidate
    writes and restores rejected wrapped columns on commit, so greedy
    spec outputs must stay bit-identical to plain ring decode — at and
    well past the window boundary (prompt + output > window means every
    late step verifies against a fully wrapped ring)."""
    cfg, model, params, _ = served("hymba-1.5b")
    assert cfg.sliding_window and cfg.supports_long_context
    window = cfg.sliding_window
    # outputs cross the eviction boundary: 20 + 30 tokens > 32 window
    reqs = lambda: _mixed_requests(cfg, lens=[20, 7, 26],
                                   max_news=[30, 40, 18], seed=6)
    plain = ServeEngine(model, params, ServeConfig(max_batch=2,
                                                   max_seq=RING_SEQ))
    ref = {r.rid: list(r.output) for r in plain.serve(reqs())}
    spec = ServeEngine(model, params, ServeConfig(max_batch=2,
                                                  max_seq=RING_SEQ,
                                                  spec_k=4))
    st = spec._init_state()
    assert st.cache_k.shape[3] == window     # really a ring allocation
    done = spec.serve(reqs())
    for r in done:
        assert list(r.output) == ref[r.rid], r.rid
    assert max(len(r.prompt) + len(r.output) for r in done) > window
    # speculation engaged on the ring (motif-free prompts still draft
    # occasionally; conservation is the hard check above)
    assert spec.metrics["decode_steps"] > 0


def test_ring_cache_spec_window_guard(served):
    """The one remaining ring constraint: a k+1 verify window wider than
    the ring would evict columns the same verify still reads — refused
    up front (abstract shape check, no 128k allocation)."""
    cfg, model, params, _ = served("hymba-1.5b")
    with pytest.raises(ValueError, match="verify window"):
        ServeEngine(model, params, ServeConfig(
            max_batch=2, max_seq=RING_SEQ, spec_k=cfg.sliding_window))
    # at the boundary (k+1 == window) and below, construction succeeds
    ServeEngine(model, params, ServeConfig(
        max_batch=2, max_seq=RING_SEQ, spec_k=cfg.sliding_window - 1))


def test_no_draft_fallback_zero_verify_dispatches(served):
    """With a drafter that never proposes, the engine must ride the plain
    decode program every step: zero verify/commit dispatches, outputs
    still bit-exact."""
    cfg, model, params, dec = served("glm4-9b")
    engine = ServeEngine(model, params, max_batch=2, max_seq=MAX_SEQ,
                         spec_k=4, drafter=_EmptyDrafter())
    reqs = _mixed_requests(cfg, lens=[5, 9], max_news=[6, 8], seed=4)
    done = engine.serve(reqs)
    assert engine.trace_counts["verify"] == 0
    assert engine.trace_counts["commit"] == 0
    assert engine.trace_counts["decode"] == 1
    assert engine.metrics["draft_tokens"] == 0
    for r in done:
        ref = _single_stream(model, params, dec, r.prompt, r.max_new_tokens)
        assert list(r.output) == ref


# ---------------------------------------------------------------------------
# Draft-model drafter (tiered) + adaptive per-slot spec_k
# ---------------------------------------------------------------------------

def test_draft_model_drafter_greedy_bitexact(served):
    """The batched draft-model drafter with the *target* as its own draft
    model: drafts reproduce the greedy chain, so acceptance is ~total and
    outputs stay bit-identical to plain decode while advancing k+1 per
    step.  model-tier dispatches dominate (the model always has signal
    about itself)."""
    cfg, model, params, dec = served("glm4-9b")
    drafter = DraftModelDrafter(model, params, max_batch=4,
                                max_seq=MAX_SEQ, min_conf=0.0)
    engine = ServeEngine(model, params, ServeConfig(
        max_batch=4, max_seq=MAX_SEQ, spec_k=4, drafter=drafter))
    reqs = _mixed_requests(cfg, lens=[5, 11, 16, 3, 24, 8],
                           max_news=[12, 9, 6, 12, 8, 14], seed=7)
    done = engine.serve(reqs)
    assert len(done) == len(reqs)
    for r in done:
        ref = _single_stream(model, params, dec, r.prompt,
                             r.max_new_tokens)
        assert list(r.output) == ref, r.rid
    m = engine.metrics
    assert m["model_drafts"] > 0
    assert m["spec_acceptance"] > 0.9          # self-drafting: ~all accept
    assert m["tokens_per_step"] > 2.0
    # batched drafting holds the engine's trace discipline: one draft
    # decode trace total, regardless of slot churn
    assert drafter.trace_counts["draft_decode"] == 1


def test_draft_model_tiered_fallback_dispatch(served):
    """A draft model gated to zero confidence (min_conf > 1) must never
    place model-tier drafts: every drafting slot-step tiers down to the
    n-gram fallback, and outputs stay bit-exact."""
    cfg, model, params, dec = served("glm4-9b")
    drafter = DraftModelDrafter(model, params, max_batch=2,
                                max_seq=MAX_SEQ, min_conf=1.1)
    engine = ServeEngine(model, params, ServeConfig(
        max_batch=2, max_seq=MAX_SEQ, spec_k=4, drafter=drafter))
    rng = np.random.default_rng(2)
    motif = rng.integers(0, cfg.vocab_size, 3)
    prompt = np.tile(motif, 6)[:14].astype(np.int32)   # ngram-draftable
    done = engine.serve([Request(0, prompt, max_new_tokens=10)])
    assert engine.metrics["model_drafts"] == 0
    assert engine.metrics["fallback_drafts"] > 0
    ref = _single_stream(model, params, dec, prompt, 10)
    assert list(done[0].output) == ref


def test_drafter_factory(served):
    cfg, model, params, _ = served("glm4-9b")
    assert isinstance(make_drafter("ngram"), NGramDrafter)
    d = make_drafter("draft_model", target=model, max_batch=2,
                     max_seq=MAX_SEQ)
    assert isinstance(d, DraftModelDrafter)
    assert d.model.cfg.vocab_size == cfg.vocab_size
    assert d.model.cfg.n_layers < cfg.n_layers or d.model.cfg.d_model \
        <= cfg.d_model
    with pytest.raises(ValueError):
        make_drafter("nope")
    with pytest.raises(ValueError):
        make_drafter("draft_model")            # needs model= or target=
    # the derived tiny arch keeps the target's token space, dense family
    da = draft_arch(cfg)
    assert (da.family, da.vocab_size) == ("dense", cfg.vocab_size)
    # engines resolve factory names themselves (ServeConfig.drafter str)
    eng = ServeEngine(model, params, ServeConfig(
        max_batch=2, max_seq=MAX_SEQ, spec_k=2, drafter="ngram"))
    assert isinstance(eng.drafter, NGramDrafter)


class _WrongSession(DraftSession):
    """Proposes k tokens that (almost) never match the model."""

    def __init__(self, vocab):
        self.vocab = vocab
        self.t = 0

    def extend(self, tokens):
        self.t += len(tokens)

    def draft(self, k):
        return [(self.t * 7919 + j) % self.vocab for j in range(k)]


class _WrongDrafter(Drafter):
    def __init__(self, vocab):
        self.vocab = vocab

    def begin(self, context, slot=None, rid=None):
        return _WrongSession(self.vocab)


def test_adaptive_k_shrinks_to_zero_on_undraftable(served):
    """On an undraftable trace (a drafter whose proposals never land),
    the adaptive engine must walk every slot's budget to 0 and ride the
    plain program — measurably fewer verify dispatches than the fixed-k
    engine on the same trace, identical outputs."""
    cfg, model, params, dec = served("glm4-9b")
    trace = lambda: _mixed_requests(cfg, lens=[6, 9], max_news=[40, 40],
                                    seed=8)

    fixed = ServeEngine(model, params, ServeConfig(
        max_batch=2, max_seq=MAX_SEQ, spec_k=4,
        drafter=_WrongDrafter(cfg.vocab_size)))
    fixed_done = fixed.serve(trace())

    adapt = ServeEngine(model, params, ServeConfig(
        max_batch=2, max_seq=MAX_SEQ, spec_k=4, spec_adaptive=True,
        drafter=_WrongDrafter(cfg.vocab_size)))
    adapt_done = adapt.serve(trace())

    for r_f, r_a in zip(sorted(fixed_done, key=lambda r: r.rid),
                        sorted(adapt_done, key=lambda r: r.rid)):
        ref = _single_stream(model, params, dec, r_f.prompt,
                             r_f.max_new_tokens)
        assert list(r_f.output) == ref
        assert list(r_a.output) == ref
    # the fixed engine verifies every step; the adaptive one only until
    # the EWMA walks k to 0 (plus sparse probes)
    assert fixed.metrics["spec_steps"] > 2 * adapt.metrics["spec_steps"]
    assert 0 in adapt.metrics.spec_k_hist        # slots really hit k=0
    assert adapt.metrics.spec_k_hist[0] > 0


def test_adaptive_k_grows_to_max_on_draftable(served):
    """On a perfectly draftable trace, budgets must grow from spec_k to
    the spec_k_max ceiling (full acceptance pushes the EWMA up)."""
    cfg, model, params, dec = served("glm4-9b")
    rng = np.random.default_rng(5)
    p = rng.integers(0, cfg.vocab_size, 7).astype(np.int32)
    p[0] = 1
    ref = _single_stream(model, params, dec, p, 40)
    engine = ServeEngine(model, params, ServeConfig(
        max_batch=1, max_seq=MAX_SEQ, spec_k=1, spec_k_max=6,
        spec_adaptive=True, drafter=_ScriptedDrafter({1: ref})))
    done = engine.serve([Request(0, p, max_new_tokens=40)])
    assert list(done[0].output) == ref
    hist = engine.metrics.spec_k_hist
    assert max(hist) == 6, hist                  # ceiling reached
    assert engine.metrics["tokens_per_step"] > 2.0


def test_serve_metrics_mapping_surface(served):
    """ServeMetrics keeps the dict surface the benches index: get/in/
    [], extras for subclass counters, and a flat to_dict for JSON."""
    from repro.runtime.serve_loop import ServeMetrics
    m = ServeMetrics()
    m["decode_steps"] += 3
    assert m.decode_steps == 3 and m["decode_steps"] == 3
    assert "slot_occupancy" in m and "nope" not in m
    assert m.get("nope", 42) == 42
    m["async_prefills"] = 2                      # unknown key -> extras
    assert m.extras == {"async_prefills": 2} and m["async_prefills"] == 2
    m.spec_k_hist[4] = 9
    d = m.to_dict()
    assert d["decode_steps"] == 3 and d["async_prefills"] == 2
    assert d["spec_k_hist"] == {4: 9} and "extras" not in d
    import json
    json.dumps(d)                                # JSON-serializable


def test_paged_spec_greedy_bitexact_and_rollback_frees(served):
    """Spec decode on the paged backend: greedy outputs stay bit-exact to
    plain decode, and pages grown ahead of the frontier for rejected draft
    positions are returned to the pool (spec rollback frees blocks)."""
    from repro.configs import CacheSpec
    from repro.runtime.serve_loop import ServeConfig

    cfg, model, params, dec = served("glm4-9b")
    engine = ServeEngine(model, params, ServeConfig(
        max_batch=2, max_seq=MAX_SEQ, spec_k=4, prefix_cache=False,
        cache=CacheSpec(paged=True, page_size=8)))
    reqs = _mixed_requests(cfg, lens=[5, 14, 9], max_news=[12, 6, 10])
    done = engine.serve(reqs)
    assert len(done) == len(reqs)
    for r in done:
        ref = _single_stream(model, params, dec, r.prompt, r.max_new_tokens)
        assert list(r.output) == ref, r.rid
    assert engine.metrics["spec_steps"] >= 0
    engine.allocator.assert_balanced()
    assert engine.allocator.used_blocks == 0
    assert (engine._tables == engine.allocator.num_blocks).all()


def test_rejection_sampling_matches_plain_distribution(served):
    """The spec acceptance rule must leave the emitted-token marginal
    exactly the plain sampling distribution p: accept the (deterministic)
    draft with probability p[d], else sample the residual.  Empirical
    check on the first emitted token against ``_dist``."""
    import types

    cfg, model, params, _ = served("glm4-9b")
    engine = ServeEngine(model, params, max_batch=2, max_seq=MAX_SEQ,
                         greedy=False, spec_k=4)
    req = Request(0, np.zeros(1, np.int32), max_new_tokens=1)
    req.temperature = 0.9
    req.top_k = 6
    rng = np.random.default_rng(11)
    slot = types.SimpleNamespace(req=req, rng=rng)
    v = 8
    rows = np.asarray(np.random.default_rng(0).normal(0, 1.5, (2, v)),
                      np.float32)
    p = engine._dist(slot, rows[0])
    draft = int(np.argsort(p)[-2])          # a plausible but not top draft
    n = 4000
    counts = np.zeros(v)
    for _ in range(n):
        out = engine._accept_sampled(slot, rows, [draft], cap=1)
        counts[out[0]] += 1
    tvd = 0.5 * np.abs(counts / n - p).sum()
    assert tvd < 0.05, (tvd, counts / n, p)


def test_sampling_rejection_fallback_deterministic(served):
    """Temperature slots take the two-phase rejection-sampling path:
    seeded runs reproduce, and temp-0 slots in the same batch stay
    bit-exact to the oracle."""
    cfg, model, params, dec = served("glm4-9b")
    outs = []
    for _ in range(2):
        engine = ServeEngine(model, params, max_batch=2, max_seq=MAX_SEQ,
                             greedy=False, spec_k=4)
        reqs = _mixed_requests(cfg, lens=[6, 8], max_news=[7, 7], seed=3)
        reqs[0].temperature = 1.0
        reqs[0].top_k = 16
        reqs[0].seed = 7
        done = engine.serve(reqs)
        outs.append({r.rid: list(r.output) for r in done})
        for r in done:
            assert all(0 <= t < cfg.vocab_size for t in r.output)
        # the two-phase path traces verify and commit as a pair (neither
        # if every step fell back to the plain program)
        assert (engine.trace_counts["verify"]
                == engine.trace_counts["commit"] <= 1)
    assert outs[0] == outs[1]
    # the temp-0 request rode the sampling batch but stays greedy-exact
    ref = _single_stream(model, params, dec, reqs[1].prompt, 7)
    assert outs[0][1] == ref
