"""The plain float32 references against the program's ServeEngine, at toy
widths on the CPU: the dense path, the paged path with a prefix hit,
and rwkv6; and the weights the reference draws are the program's."""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import bench_tiny  # noqa: E402
from benchlib import driver  # noqa: E402
from benchlib import weights as W  # noqa: E402
from benchlib.cells import Bench  # noqa: E402

SEED = 2 ** 31 + 4242
# bf16 serving against the float32 reference: at these widths the top
# logits sit ~0.1 apart and bf16 rounding moves them by ~0.01 (measured
# 0.005-0.02 over 12 seeds); a wrong layer moves them by O(1)
GAP = 0.1
REL = 0.05       # prefill logits, as a share of the reference's RMS


@pytest.fixture(scope="module")
def bench(tmp_path_factory):
    return Bench(bench_tiny.tiny_root(tmp_path_factory.mktemp("ref")))


@pytest.fixture(scope="module")
def built(bench):
    """One system per tiny cell, built once for the module."""
    cache = {}

    def get(cell):
        if cell not in cache:
            cache[cell] = driver.build(bench, bench.cell(cell), SEED)
        return cache[cell]
    return get


def serve(system, prompts, new=12, rid0=0):
    from repro.runtime.serve_loop import Request

    reqs = [Request(rid0 + i, p, max_new_tokens=new)
            for i, p in enumerate(prompts)]
    system.engine.serve(reqs)
    return [np.asarray(r.output, np.int32) for r in reqs]


def gaps(system, prompts, outs, width=128):
    m = system.cell.config["model"]
    return driver.served_gaps(system.ref, system.words, m, prompts, outs,
                              width).max()


@pytest.mark.parametrize("cell", ["tiny-glm4-chat", "tiny-rwkv6-chat"])
def test_weights_are_the_programs(built, cell):
    system = built(cell)
    m = system.cell.config["model"]
    draw = W.layer_f32(system.leaves)          # as the reference draws
    for layer in (0, m["n_layers"] - 1):
        for path, v in draw(system.words, layer).items():
            node = system.params["blocks"]
            for p in path.split("/"):
                node = node[p]
            assert np.array_equal(np.asarray(node[layer], np.float32),
                                  np.asarray(v)), path
    for path in ("embed", "ln_f", "lm_head"):
        assert np.array_equal(
            np.asarray(system.params[path], np.float32),
            np.asarray(W.global_leaf(system.leaves, path)(system.words),
                       np.float32)), path


@pytest.mark.parametrize("cell", ["tiny-glm4-chat", "tiny-rwkv6-chat"])
def test_prefill_logits_match(built, cell):
    system = built(cell)
    m = system.cell.config["model"]
    rng = np.random.default_rng(1)
    lengths = np.array([5, 17, 32, 9], np.int32)
    toks = np.zeros((4, 32), np.int32)
    for i, n in enumerate(lengths):
        toks[i, :n] = rng.integers(0, m["vocab_size"], n)
    got, _ = system.model.prefill(system.params, {"tokens": toks},
                                  headroom=0, lengths=jnp.asarray(lengths))
    x = system.ref.hidden(toks, system.words, m, m["n_layers"])
    head = system.ref.head(system.words, m)
    with jax.default_matmul_precision("highest"):
        want = np.stack([np.asarray(x[i, n - 1] @ head)
                         for i, n in enumerate(lengths)])
    got = np.asarray(got, np.float32)[:, 0]
    rms = np.sqrt((want ** 2).mean())
    assert np.abs(got - want).max() <= REL * rms


@pytest.mark.parametrize("cell", ["tiny-glm4-chat", "tiny-rwkv6-chat"])
def test_served_tokens_match_through_decode(built, cell):
    system = built(cell)
    rng = np.random.default_rng(2)
    vocab = system.cell.config["model"]["vocab_size"]
    prompts = [rng.integers(0, vocab, n).astype(np.int32)
               for n in (3, 20, 40, 64)]
    outs = serve(system, prompts)
    assert gaps(system, prompts, outs) <= GAP


def test_paged_prefix_hit_matches(built):
    system = built("tiny-glm4-prefix")
    rng = np.random.default_rng(3)
    shared = rng.integers(0, 256, 48).astype(np.int32)     # 3 pages
    first = [np.concatenate([shared, rng.integers(0, 256, 5)]).astype(
        np.int32)]
    outs_a = serve(system, first)
    assert system.engine.metrics["prefix_hit_tokens"] == 0
    second = [np.concatenate([shared, rng.integers(0, 256, n)]).astype(
        np.int32) for n in (3, 11)]
    outs_b = serve(system, second, rid0=10)
    assert system.engine.metrics["prefix_hit_tokens"] == 2 * 48
    assert gaps(system, first + second, outs_a + outs_b) <= GAP


def test_a_wrong_token_is_seen(built):
    system = built("tiny-glm4-chat")
    rng = np.random.default_rng(4)
    prompts = [rng.integers(0, 256, 30).astype(np.int32)]
    outs = serve(system, prompts, rid0=100)
    assert gaps(system, prompts, outs) <= GAP
    bad = outs[0].copy()
    bad[5] = (bad[5] + 1) % 256
    assert gaps(system, prompts, [bad]) > 10 * GAP


@pytest.mark.parametrize("cell", ["tiny-glm4-chat", "tiny-rwkv6-chat"])
def test_program_in_float32_serves_the_references_argmax(bench, cell):
    """With the program computing in float32 as well, every served token
    is the reference's argmax: the reference has the program's
    semantics, and bf16 rounding is all that the gaps above measure."""
    import functools

    from repro.configs import get_arch
    from repro.models.model_zoo import build_model
    from repro.runtime.serve_loop import ServeConfig, ServeEngine

    c = bench.cell(cell)
    m = c.config["model"]
    model = build_model(get_arch(c.config["arch"]).scaled(
        **m, dtype="float32"))
    ref = bench.reference(c.config["reference"])
    words = W.seed_words(SEED)
    params = jax.jit(functools.partial(W.draw_tree, ref.leaves(m),
                                       n_layers=m["n_layers"]))(words)
    params = jax.tree.map(lambda a: a.astype(jnp.float32), params)
    system = driver.System(c, model, params, ServeEngine(
        model, params, ServeConfig(max_batch=4, max_seq=128)), words, ref,
        ref.leaves(m))
    rng = np.random.default_rng(5)
    prompts = [rng.integers(0, m["vocab_size"], n).astype(np.int32)
               for n in (4, 33, 64)]
    outs = serve(system, prompts, new=40)
    assert gaps(system, prompts, outs) == 0.0
