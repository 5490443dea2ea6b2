"""Paged slot memory + radix prefix cache invariants.

The contract under test (see ``models/paged.py``, ``runtime/block_pool.py``
and the paged paths of ``runtime/serve_loop.py``):

  * **bit-equality**: paged serving — block-table indirection, extend
    admissions, prefix-cache reuse — never changes a single output token
    vs the dense engine (native dtype), across attention / rwkv / hybrid
    state; warm (prefix-cached) admissions equal cold ones in every
    cache dtype, including the int8 requantize-on-load path
  * **no leaks**: the block free list balances after retire-and-refill
    and speculative rollback; retired slots return every page
  * **memory scaling**: resident K/V is ``num_blocks * page_size``
    tokens — an undersized pool still serves every request (blocks
    recycle through the free list), it never silently drops one
  * **one spelling of the cache format**: ``CacheSpec`` is validated and
    exclusive with the legacy knobs; ``ServeConfig`` replaces the kwarg
    sprawl (old kwargs warn but work); ``CacheOps`` is the documented
    backend seam (dense / paged are swappable implementations)
  * **trace discipline**: paged serving keeps one jit trace per program
    shape — extend/reset/decode counters stay flat across admissions
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import CacheSpec, get_arch
from repro.models import paged as paged_mod
from repro.models.model_zoo import (DenseCacheOps, PagedCacheOps,
                                    build_model)
from repro.runtime.block_pool import BlockAllocator, RadixCache
from repro.runtime.serve_loop import Request, ServeConfig, ServeEngine

MAX_SEQ = 64
PAGE = 8
FAMILIES = ["glm4-9b", "rwkv6-3b", "hymba-1.5b", "falcon-h1-34b"]


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


def _prefix_requests(cfg, n, seed=0, prefix_len=17, n_prefixes=2,
                     tail_range=(3, 10), max_news=(2, 4, 7)):
    """Shared-prefix trace: few long system prompts, many short tails."""
    rng = np.random.default_rng(seed)
    prefixes = [rng.integers(0, cfg.vocab_size, prefix_len)
                for _ in range(n_prefixes)]
    reqs = []
    for i in range(n):
        tail = rng.integers(0, cfg.vocab_size,
                            int(rng.integers(*tail_range)))
        prompt = np.concatenate([prefixes[i % n_prefixes],
                                 tail]).astype(np.int32)
        reqs.append(Request(i, prompt,
                            max_new_tokens=int(max_news[i % len(max_news)])))
    return reqs


def _paged_config(**kw):
    kw.setdefault("max_batch", 4)
    kw.setdefault("max_seq", MAX_SEQ)
    kw.setdefault("cache", CacheSpec(paged=True, page_size=PAGE))
    return ServeConfig(**kw)


# -- bit-equality ------------------------------------------------------------

@pytest.mark.parametrize("arch", FAMILIES)
def test_shared_prefix_bit_identical_to_dense(served, arch):
    """Paged serving with prefix reuse is a pure memory/scheduling change:
    every output token equals the dense engine's, and a real fraction of
    prompt tokens must have come from the radix cache (not recomputed)."""
    cfg, model, params = served(arch)
    paged = ServeEngine(model, params, _paged_config())
    dense = ServeEngine(model, params, ServeConfig(max_batch=4,
                                                   max_seq=MAX_SEQ))
    reqs_p = _prefix_requests(cfg, 8)
    reqs_d = _prefix_requests(cfg, 8)
    done_p = {r.rid: list(r.output) for r in paged.serve(reqs_p)}
    done_d = {r.rid: list(r.output) for r in dense.serve(reqs_d)}
    assert done_p == done_d, arch
    assert paged.metrics["prefix_hit_tokens"] > 0, \
        "the shared prefix never hit the radix cache"
    assert paged.metrics["prefill_tokens"] < dense.metrics["prefill_tokens"]


@pytest.mark.parametrize("arch,dtype", [("glm4-9b", "native"),
                                        ("glm4-9b", "int8"),
                                        ("rwkv6-3b", "native"),
                                        ("rwkv6-3b", "int8"),
                                        ("hymba-1.5b", "int8")])
def test_warm_admission_equals_cold(served, arch, dtype):
    """Replaying the same trace against a warm radix cache must be
    deterministic, and — whenever no new quantization boundary is
    introduced — reproduce the cold run token-for-token.

    Native state and int8 *attention* caches are exact regardless of how
    much prefix matched: stored K/V pages are bit-identical to what the
    cold run wrote, and exact-f32 recurrent snapshots reload losslessly.
    int8 *recurrent* state (rwkv wkv / hybrid ssm_h) requantizes at the
    admission point, so a *longer* warm match inserts a quantization
    boundary the cold run didn't have — there only warm-vs-warm (same
    match length) is bit-exact, and that is what gets pinned.
    """
    cfg, model, params = served(arch)
    spec = CacheSpec(dtype=dtype, paged=True, page_size=PAGE)
    engine = ServeEngine(model, params, _paged_config(cache=spec))
    runs = []
    for _ in range(3):
        done = engine.serve(_prefix_requests(cfg, 6, seed=5))
        runs.append({r.rid: list(r.output) for r in done})
    assert engine.metrics["prefix_hit_tokens"] > 0
    # run 2 inserted nothing new, so runs 2 and 3 match identical page
    # counts: bit-equality holds for every dtype/family combination
    assert runs[1] == runs[2], (arch, dtype)
    quant_recurrent = dtype == "int8" and cfg.family in ("ssm", "hybrid")
    if not quant_recurrent:
        assert runs[0] == runs[1], (arch, dtype)


def test_paged_int8_matches_dense_extend(served):
    """int8 paged numerics: the reference is the *dense extend* path (a
    quantized cache makes any incremental pass attend quantized K/V,
    while one-shot prefill attends the exact values — so prefill is the
    wrong oracle).  Same suffix scored through pooled pages must match
    the dense slot layout bit-for-bit."""
    cfg, model, params = served("glm4-9b")
    q = model.with_cache_spec(CacheSpec(dtype="int8"))
    qp = model.with_cache_spec(CacheSpec(dtype="int8", paged=True,
                                         page_size=PAGE))
    rng = np.random.default_rng(7)
    prompt = rng.integers(0, cfg.vocab_size, 13).astype(np.int32)
    toks = jnp.asarray(prompt[None, :])
    adv = np.array([len(prompt)], np.int32)

    st_d = q.init_slot_state(1, MAX_SEQ)
    lg_d, st_d, rec = q.verify_step(params, st_d, {"tokens": toks})
    st_d = q.spec_commit(st_d, rec, adv)

    ops = qp.cache_ops(num_blocks=MAX_SEQ // PAGE)
    st_p = ops.init_slot_state(1, MAX_SEQ)
    tables = np.arange(MAX_SEQ // PAGE, dtype=np.int32)[None, :]
    st_p = st_p._replace(block_tables=jnp.asarray(tables))
    lg_p, st_p, rec = qp.verify_step(params, st_p, {"tokens": toks})
    st_p = ops.spec_commit(st_p, rec, adv)
    np.testing.assert_array_equal(np.asarray(lg_d, np.float32),
                                  np.asarray(lg_p, np.float32))

    for _ in range(4):
        t = jnp.asarray([[int(jnp.argmax(lg_d[0, -1]))]], jnp.int32)
        lg_d, st_d = q.decode_step(params, st_d, {"tokens": t})
        lg_p, st_p = qp.decode_step(params, st_p, {"tokens": t})
        np.testing.assert_array_equal(np.asarray(lg_d, np.float32),
                                      np.asarray(lg_p, np.float32))


# -- block accounting --------------------------------------------------------

def _radix_block_count(radix):
    count = 0

    def walk(node):
        nonlocal count
        for c in node.children.values():
            if c.block is not None:
                count += 1
            walk(c)
    walk(radix.root)
    return count


def test_free_list_never_leaks(served):
    """After every request retires, only the radix cache may hold blocks
    — across plain decode, speculative rollback, and a refill run."""
    cfg, model, params = served("glm4-9b")
    engine = ServeEngine(model, params, _paged_config(max_batch=2,
                                                      spec_k=3))
    for seed in (0, 1):      # second run refills over a warm engine
        done = engine.serve(_prefix_requests(cfg, 6, seed=seed))
        assert len(done) == 6
        engine.allocator.assert_balanced()
        sentinel = engine.ops.num_blocks
        assert (engine._tables == sentinel).all(), \
            "a retired slot kept table entries"
        assert engine.allocator.used_blocks == \
            _radix_block_count(engine.radix)

    # without the prefix cache nothing may survive the trace at all
    bare = ServeEngine(model, params, _paged_config(prefix_cache=False))
    bare.serve(_prefix_requests(cfg, 5))
    bare.allocator.assert_balanced()
    assert bare.allocator.used_blocks == 0


def test_block_allocator_guards():
    alloc = BlockAllocator(2)
    a = alloc.alloc()
    alloc.free(a)
    with pytest.raises(ValueError, match="double free"):
        alloc.free(a)
    with pytest.raises(ValueError, match="dead block"):
        alloc.ref(a)
    b = alloc.alloc()
    alloc.ref(b)
    alloc.free(b)
    assert alloc.refcount(b) == 1      # still held by the second ref
    alloc.assert_balanced()


def test_radix_match_leaves_a_suffix_token():
    """A full-prompt match must still leave >= 1 token to compute (the
    extend pass has to produce the prompt's next-token logits)."""
    alloc = BlockAllocator(8)
    radix = RadixCache(alloc, page_size=4)
    toks = np.arange(8, dtype=np.int32)
    blocks = [alloc.alloc(), alloc.alloc()]
    radix.insert(toks, 8, blocks)
    m, nodes = radix.match(toks)
    assert m == 4 and len(nodes) == 1      # page 2 would leave no suffix
    m, nodes = radix.match(np.arange(9, dtype=np.int32))
    assert m == 8 and len(nodes) == 2


def test_memory_scales_with_live_tokens(served):
    """An undersized pool (far below max_batch * max_seq worth of pages)
    still serves the whole trace — blocks recycle at retire — and the
    resident pool is the allocation, not the dense worst case."""
    cfg, model, params = served("glm4-9b")
    num_blocks = 12           # vs 2 * 64/8 = 16 for full occupancy
    engine = ServeEngine(model, params,
                         _paged_config(max_batch=2, num_blocks=num_blocks))
    done = engine.serve(_prefix_requests(cfg, 10, seed=2))
    assert len(done) == 10, "undersized pool dropped requests"
    assert engine._state.cache_k.shape[1] == num_blocks
    dense_tokens = 2 * MAX_SEQ
    assert num_blocks * PAGE < dense_tokens
    assert engine.metrics["peak_blocks"] <= num_blocks


# -- API surface -------------------------------------------------------------

def test_cache_spec_validation():
    with pytest.raises(ValueError, match="dtype"):
        CacheSpec(dtype="fp8")
    with pytest.raises(ValueError, match="block"):
        CacheSpec(dtype="int8", block=0)
    with pytest.raises(ValueError, match="fxp8"):
        CacheSpec(dtype="fxp8", paged=True)
    assert CacheSpec(dtype="int8").quantized
    assert not CacheSpec().quantized


def test_cache_spec_excludes_legacy_knobs():
    cfg = get_arch("glm4-9b").reduced()
    mixed = dataclasses.replace(cfg, cache=CacheSpec(dtype="int8"),
                                cache_quant="int8")
    with pytest.raises(ValueError, match="legacy spelling"):
        mixed.cache_spec()
    # with_cache_spec clears the legacy knobs, so no conflict survives
    m = build_model(cfg).with_cache_dtype("int8")
    m2 = m.with_cache_spec(CacheSpec(dtype="int8", paged=True,
                                     page_size=PAGE))
    assert m2.cfg.cache_quant == "none"
    assert m2.cfg.cache_spec().paged


def test_serve_config_replaces_kwargs(served):
    cfg, model, params = served("glm4-9b")
    with pytest.raises(ValueError, match="exactly one"):
        ServeConfig(cache=CacheSpec(dtype="int8"), cache_dtype="int8")
    with pytest.raises(ValueError, match="max_batch"):
        ServeConfig(max_batch=0)
    with pytest.raises(TypeError, match="not both"):
        ServeEngine(model, params, ServeConfig(), max_batch=2)
    # the legacy kwarg spelling still works, with a deprecation warning
    with pytest.warns(DeprecationWarning, match="ServeConfig"):
        eng = ServeEngine(model, params, max_batch=2, max_seq=MAX_SEQ)
    assert eng.max_batch == 2 and not eng.paged


def test_cache_ops_backends(served):
    cfg, model, params = served("glm4-9b")
    assert isinstance(model.cache_ops(), DenseCacheOps)
    pm = model.with_cache_spec(CacheSpec(paged=True, page_size=PAGE))
    with pytest.raises(ValueError, match="num_blocks"):
        pm.cache_ops()
    ops = pm.cache_ops(num_blocks=4)
    assert isinstance(ops, PagedCacheOps) and ops.paged
    with pytest.raises(NotImplementedError, match="extend in place"):
        ops.slot_update(None, None, None)
    with pytest.raises(ValueError, match="multiple"):
        paged_mod.init_paged_slot_state(pm.cfg, 2, 30, 4, PAGE)
    # pool memory is num_blocks pages, not max_batch * max_seq
    st = ops.init_slot_state(4, MAX_SEQ, abstract=True)
    assert st.cache_k.shape[1] == 4 and st.cache_k.shape[2] == PAGE
    assert st.block_tables.shape == (4, MAX_SEQ // PAGE)


def test_paged_trace_discipline(served):
    """Admission-composition changes must not retrace the paged programs:
    one reset trace, one extend trace per suffix bucket, one decode."""
    cfg, model, params = served("glm4-9b")
    engine = ServeEngine(model, params, _paged_config(min_bucket=16))
    # 7 requests -> a cold first group (32-token suffix bucket) and warm
    # refill groups (16-token bucket): both extend shapes get traced
    engine.serve(_prefix_requests(cfg, 7, seed=0))
    first = dict(engine.trace_counts)
    assert first["reset"] == 1 and first["decode"] == 1
    assert first["extend"] == 2
    # fresh prefixes, different group sizes / tails / budgets — the same
    # two suffix buckets, so not a single new trace
    engine.serve(_prefix_requests(cfg, 7, seed=9))
    assert dict(engine.trace_counts) == first, "retrace within a bucket"
