"""Scheduling invariants of the continuous-batching serve engine.

The contract under test (see ``runtime/serve_loop.py``):

  * per-request outputs are **bit-identical** to single-stream decoding —
    right-padded bucket prefill + per-slot decode changes nothing
  * retire-and-refill: a short request's slot is reused while a long one
    is still decoding (no gang drain)
  * bucketed shapes: batch-composition changes within a prompt bucket
    never retrace the jit'd prefill/decode callables (asserted via the
    engine's trace-count callbacks)
  * queue metrics (queue_wait_s, slot_occupancy) are exposed and sane
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_arch
from repro.models.model_zoo import build_model
from repro.runtime.serve_loop import (GangServeEngine, Request, ServeEngine,
                                      next_pow2)

MAX_SEQ = 64


@pytest.fixture(scope="module")
def served():
    """One model + params per family, shared across tests in this module."""
    cache = {}

    def get(arch):
        if arch not in cache:
            cfg = get_arch(arch).reduced()
            model = build_model(cfg)
            params = model.init(jax.random.PRNGKey(0))
            cache[arch] = (cfg, model, params)
        return cache[arch]

    return get


def _mixed_requests(cfg, lens, max_news, seed=0):
    rng = np.random.default_rng(seed)
    reqs = []
    for i, (n, m) in enumerate(zip(lens, max_news)):
        prompt = rng.integers(0, cfg.vocab_size, n).astype(np.int32)
        reqs.append(Request(i, prompt, max_new_tokens=m))
    return reqs


def _single_stream(model, params, prompt, max_new):
    """Greedy decode of one request through the plain (unbatched,
    unpadded) prefill/decode path — the engine's correctness oracle."""
    lg, st = model.prefill(
        params, {"tokens": jnp.asarray(prompt[None, :])},
        headroom=MAX_SEQ - len(prompt))
    cur = int(jnp.argmax(lg.reshape(1, -1), axis=-1)[0])
    seq = [cur]
    for _ in range(max_new - 1):
        lg, st = model.decode_step(
            params, st, {"tokens": jnp.asarray([[cur]], jnp.int32)})
        cur = int(jnp.argmax(lg.reshape(1, -1), axis=-1)[0])
        seq.append(cur)
    return seq


@pytest.mark.parametrize("arch", ["glm4-9b", "rwkv6-3b", "hymba-1.5b",
                                  "falcon-h1-34b"])
def test_output_equality_with_single_stream(served, arch):
    """Continuous batching must not change a single request's tokens —
    across attention (KV cache), rwkv (recurrent) and hybrid state."""
    cfg, model, params = served(arch)
    engine = ServeEngine(model, params, max_batch=4, max_seq=MAX_SEQ)
    reqs = _mixed_requests(cfg, lens=[5, 11, 16, 3, 24, 8],
                           max_news=[4, 9, 2, 12, 1, 6])
    done = engine.serve(reqs)
    assert len(done) == len(reqs)
    for r in done:
        ref = _single_stream(model, params, r.prompt, r.max_new_tokens)
        assert list(r.output) == ref, (arch, r.rid)


def test_refill_on_retire(served):
    """A short request's slot is reused while a long one still decodes."""
    cfg, model, params = served("glm4-9b")
    engine = ServeEngine(model, params, max_batch=2, max_seq=MAX_SEQ)
    reqs = _mixed_requests(cfg, lens=[6, 7, 5], max_news=[2, 24, 2])
    done = engine.serve(reqs)
    assert len(done) == 3
    ev = {(kind, rid): (slot, step)
          for kind, rid, slot, step in engine.events}
    # r2 was admitted into the slot r0 freed...
    assert ev[("admit", 2)][0] == ev[("retire", 0)][0]
    # ...before the long request r1 retired (mid-decode refill)
    assert ev[("admit", 2)][1] < ev[("retire", 1)][1]
    long_req = next(r for r in done if r.rid == 1)
    short_req = next(r for r in done if r.rid == 2)
    assert short_req.done_at < long_req.done_at


def test_bucket_reuse_no_retrace(served):
    """Within one prompt bucket, batch-composition changes must not
    retrace prefill/decode/insert; a new bucket adds one prefill trace."""
    cfg, model, params = served("glm4-9b")
    engine = ServeEngine(model, params, max_batch=4, max_seq=MAX_SEQ,
                         min_bucket=16)
    engine.serve(_mixed_requests(cfg, lens=[5, 9], max_news=[3, 5]))
    first = dict(engine.trace_counts)
    assert first["prefill"] == 1 and first["decode"] == 1

    # different group size, lengths and budgets — same 16-token bucket
    engine.serve(_mixed_requests(cfg, lens=[3, 12, 7], max_news=[6, 2, 4],
                                 seed=1))
    assert dict(engine.trace_counts) == first, "retrace within a bucket"

    # a longer prompt crosses into the 32 bucket: exactly one new trace
    engine.serve(_mixed_requests(cfg, lens=[20], max_news=[2], seed=2))
    assert engine.trace_counts["prefill"] == first["prefill"] + 1
    assert engine.trace_counts["decode"] == first["decode"]


@pytest.mark.parametrize("arch", ["glm4-9b", "rwkv6-3b"])
def test_group_prefills_one_row_per_request(served, arch):
    """An admission group of k requests makes k one-row prefill calls,
    and each request's greedy output, first token included, equals the
    same request served alone."""
    cfg, model, params = served(arch)
    engine = ServeEngine(model, params, max_batch=4, max_seq=MAX_SEQ)
    shapes = []
    prefill = engine._prefill

    def recorded(p, inputs, lengths):
        shapes.append(inputs["tokens"].shape)
        return prefill(p, inputs, lengths)
    engine._prefill = recorded
    reqs = _mixed_requests(cfg, lens=[5, 20, 9, 14], max_news=[3, 6, 2, 5],
                           seed=4)
    done = engine.serve(reqs)
    assert shapes == [(1, engine._bucket(len(r.prompt))) for r in reqs]
    admits = [ev[3] for ev in engine.events if ev[0] == "admit"]
    assert admits == [admits[0]] * len(reqs), "one group, no step between"
    # each request's admission starts with its own prefill, after the
    # first token of the request before it in the group
    order = [ev[1] for ev in engine.events if ev[0] == "admit"]
    by_rid = {r.rid: r for r in done}
    for a, b in zip(order, order[1:]):
        assert by_rid[b].admit_started_at >= by_rid[a].admitted_at
    alone = ServeEngine(model, params, max_batch=4, max_seq=MAX_SEQ)
    for r in done:
        solo = Request(r.rid, r.prompt, max_new_tokens=r.max_new_tokens)
        (ref,) = alone.serve([solo])
        assert list(r.output) == list(ref.output), (arch, r.rid)
        assert list(r.output) == _single_stream(
            model, params, r.prompt, r.max_new_tokens), (arch, r.rid)


@pytest.mark.parametrize("arch", ["glm4-9b", "rwkv6-3b"])
def test_one_request_per_bucket_warms_every_group_size(served, arch):
    """Once one request per prompt bucket has been served alone, serving
    admission groups of every size from 1 to max_batch traces nothing
    new: warming the buckets warms every shape a window can reach."""
    cfg, model, params = served(arch)
    engine = ServeEngine(model, params, max_batch=4, max_seq=MAX_SEQ,
                         min_bucket=16)
    for i, n in enumerate((16, 32, 60)):            # buckets 16, 32, 64
        engine.serve(_mixed_requests(cfg, lens=[n], max_news=[2],
                                     seed=10 + i))
    warm = dict(engine.trace_counts)
    assert warm["prefill"] == 3 and warm["decode"] == 1
    lens = [3, 40, 17, 9]
    for k in range(1, engine.max_batch + 1):
        engine.serve(_mixed_requests(cfg, lens=lens[:k], max_news=[3] * k,
                                     seed=20 + k))
        assert dict(engine.trace_counts) == warm, f"group of {k} retraced"


@pytest.mark.parametrize("arch", ["glm4-9b", "rwkv6-3b"])
def test_pad_correctness_mixed_lengths(served, arch):
    """Bucket-padded prefill with true lengths is bit-identical to the
    unpadded per-request prefill — logits and carried decode state."""
    cfg, model, params = served(arch)
    rng = np.random.default_rng(3)
    lens = [4, 10, 16, 7]
    bucket = 16
    toks = np.zeros((len(lens), bucket), np.int32)
    prompts = []
    for i, n in enumerate(lens):
        p = rng.integers(0, cfg.vocab_size, n).astype(np.int32)
        prompts.append(p)
        toks[i, :n] = p          # right-pad: real tokens first
    logits_b, st_b = model.prefill(
        params, {"tokens": jnp.asarray(toks)}, headroom=0,
        lengths=jnp.asarray(lens, jnp.int32))
    assert st_b.pos.shape == (len(lens),)
    np.testing.assert_array_equal(np.asarray(st_b.pos), lens)
    for i, p in enumerate(prompts):
        lg, st = model.prefill(params, {"tokens": jnp.asarray(p[None, :])},
                               headroom=0)
        np.testing.assert_array_equal(
            np.asarray(logits_b[i].astype(jnp.float32)).ravel(),
            np.asarray(lg[0].astype(jnp.float32)).ravel(),
            err_msg=f"{arch} row {i} (len {len(p)})")
        if cfg.family == "ssm":     # recurrent state must match exactly
            np.testing.assert_array_equal(
                np.asarray(st_b.wkv[:, i].astype(jnp.float32)),
                np.asarray(st.wkv[:, 0].astype(jnp.float32)))
            np.testing.assert_array_equal(
                np.asarray(st_b.x_prev[:, i].astype(jnp.float32)),
                np.asarray(st.x_prev[:, 0].astype(jnp.float32)))


def test_slot_update_scatter_and_sentinel(served):
    """slot_update inserts rows at slot indices and drops the sentinel."""
    cfg, model, params = served("glm4-9b")
    state = model.init_slot_state(4, MAX_SEQ)
    toks = np.ones((4, 16), np.int32)
    lengths = jnp.asarray([5, 5, 5, 5], jnp.int32)
    _, sub = model.prefill(params, {"tokens": jnp.asarray(toks)},
                           headroom=0, lengths=lengths)
    # rows 0,1 go to slots 2,0; rows 2,3 carry the drop sentinel (=4)
    state2 = model.slot_update(state, sub, jnp.asarray([2, 0, 4, 4]))
    assert state2.cache_k.shape[3] == MAX_SEQ   # bucket padded up
    np.testing.assert_array_equal(np.asarray(state2.pos), [5, 0, 5, 0])
    np.testing.assert_array_equal(
        np.asarray(state2.cache_k[:, 2, :, :16].astype(jnp.float32)),
        np.asarray(sub.cache_k[:, 0].astype(jnp.float32)))
    # untouched slots keep their (zero) state
    assert float(jnp.abs(state2.cache_k[:, 1].astype(jnp.float32)).sum()) == 0


def test_metrics_and_no_drops(served):
    """Queue metrics are exposed and every request completes in full."""
    cfg, model, params = served("glm4-9b")
    engine = ServeEngine(model, params, max_batch=2, max_seq=MAX_SEQ)
    reqs = _mixed_requests(cfg, lens=[5, 9, 3, 12, 6],
                           max_news=[2, 8, 3, 1, 5])
    done = engine.serve(reqs)
    assert len(done) == len(reqs)
    for r in done:
        assert len(r.output) == r.max_new_tokens
        assert r.admitted_at >= r.admit_started_at >= r.submitted_at
        assert r.done_at >= r.admitted_at
    m = engine.metrics
    assert m["queue_wait_s"] >= 0.0
    assert m["queue_wait_s"] == pytest.approx(np.mean(
        [r.admit_started_at - r.submitted_at for r in done]))
    assert 0.0 < m["slot_occupancy"] <= 1.0
    assert m["decode_tokens"] + len(reqs) == sum(r.max_new_tokens
                                                 for r in reqs)
    # capacity violations and empty prompts raise instead of serving
    # garbage or silently dropping
    with pytest.raises(ValueError):
        engine.serve([Request(99, np.zeros(40, np.int32),
                              max_new_tokens=MAX_SEQ)])
    with pytest.raises(ValueError):
        engine.serve([Request(98, np.zeros(0, np.int32))])


def test_non_pow2_max_seq_buckets_safely(served):
    """Buckets stay pow-2 under a non-pow2 max_seq: the ssm chunked scan
    only accepts pow2-friendly lengths, so the cap must not emit e.g. 96;
    prompts beyond the largest bucket raise instead of crashing."""
    cfg, model, params = served("rwkv6-3b")
    engine = ServeEngine(model, params, max_batch=2, max_seq=96)
    with pytest.raises(ValueError):
        engine.serve([Request(0, np.ones(70, np.int32), max_new_tokens=4)])
    done = engine.serve(_mixed_requests(cfg, lens=[60], max_news=[3]))
    assert len(done) == 1 and len(done[0].output) == 3


def test_per_request_sampling_deterministic(served):
    """Per-request temperature sampling is seeded and reproducible."""
    cfg, model, params = served("glm4-9b")
    outs = []
    for _ in range(2):
        engine = ServeEngine(model, params, max_batch=2, max_seq=MAX_SEQ,
                             greedy=False)
        reqs = _mixed_requests(cfg, lens=[6, 8], max_news=[5, 5])
        for r in reqs:
            r.temperature = 1.0
            r.top_k = 16
            r.seed = 7
        done = engine.serve(reqs)
        outs.append({r.rid: list(r.output) for r in done})
        for r in done:
            assert all(0 <= t < cfg.vocab_size for t in r.output)
    assert outs[0] == outs[1]


def test_gang_engine_still_serves(served):
    """The lockstep baseline stays functional (benchmark comparability)."""
    cfg, model, params = served("glm4-9b")
    engine = GangServeEngine(model, params, max_batch=2)
    reqs = _mixed_requests(cfg, lens=[5, 9, 3], max_news=[2, 4, 3])
    done = engine.serve(reqs)
    assert len(done) == 3
    assert all(len(r.output) == r.max_new_tokens for r in done)


def test_paged_backend_matches_single_stream(served):
    """The paged block-pool backend is a pure layout change: per-request
    outputs stay bit-identical to the unbatched single-stream oracle, and
    every block returns to the free list once the trace drains (see
    tests/test_paged_cache.py for the prefix-cache contract)."""
    from repro.configs import CacheSpec
    from repro.runtime.serve_loop import ServeConfig

    cfg, model, params = served("glm4-9b")
    engine = ServeEngine(model, params, ServeConfig(
        max_batch=4, max_seq=MAX_SEQ, prefix_cache=False,
        cache=CacheSpec(paged=True, page_size=8)))
    reqs = _mixed_requests(cfg, lens=[5, 11, 16, 3, 24, 8],
                           max_news=[4, 9, 2, 12, 1, 6])
    done = engine.serve(reqs)
    assert len(done) == len(reqs)
    for r in done:
        ref = _single_stream(model, params, r.prompt, r.max_new_tokens)
        assert list(r.output) == ref, r.rid
    engine.allocator.assert_balanced()
    assert engine.allocator.used_blocks == 0
    assert (engine._tables == engine.allocator.num_blocks).all()


def test_next_pow2():
    assert [next_pow2(n) for n in (1, 2, 3, 8, 9, 31)] == [1, 2, 4, 8, 16, 32]
