"""Falcon-H1 in the benchmark, at toy widths on the CPU: the reference
draws the program's weights and agrees with the program's logits, its
served tokens and, with the program in float32, its argmax; a sound cell
through ``bench/run.py`` is correct, the int8 control, a state left
unchanged and faults in attention alone are not; the configuration file is the published config with
only its depth cut; and the per-layer readers of the cell read a
hand-built record."""
import functools
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import attention_faults  # noqa: E402
import falcon_tiny  # noqa: E402
import run as bench_run  # noqa: E402
from benchlib import costs as C  # noqa: E402
from benchlib import driver  # noqa: E402
from benchlib import weights as W  # noqa: E402
from benchlib.cells import Bench  # noqa: E402
from benchlib.faults import FAULTS  # noqa: E402
from benchlib.record import Req, Run  # noqa: E402

SEED = 2 ** 31 + 1601
CELL = falcon_tiny.CELL
# prefill logits against the float32 reference, as a share of the
# reference's RMS: bf16 weights and activations round each of the ~8
# matrix products on the path by ~0.4%; a wrong layer moves them by O(1)
REL = 0.05
# the served tokens' widest gap: the logits' spread at these widths is
# about 3e-3 (the head carries the published 1/128 multiplier), bf16
# rounding moves a logit by ~1e-5 (sound runs read at most 8.6e-5), and a
# wrong token reads about the spread
GAP = 2e-4


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return falcon_tiny.falcon_root(tmp_path_factory.mktemp("falcon"))


@pytest.fixture(scope="module")
def system(root):
    bench = Bench(root)
    return driver.build(bench, bench.cell(CELL), SEED)


def serve(system, prompts, new=12, rid0=0):
    from repro.runtime.serve_loop import Request

    reqs = [Request(rid0 + i, p, max_new_tokens=new)
            for i, p in enumerate(prompts)]
    system.engine.serve(reqs)
    return [np.asarray(r.output, np.int32) for r in reqs]


def widest_gap(system, prompts, outs, width=128):
    m = system.cell.config["model"]
    return driver.served_gaps(system.ref, system.words, m, prompts, outs,
                              width).max()


def test_weights_are_the_programs(system):
    m = system.cell.config["model"]
    draw = W.layer_f32(system.leaves)
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


def test_prefill_logits_match(system):
    """Rows that end mid-chunk, on a chunk's end and after several
    chunks, right-padded to one bucket."""
    m = system.cell.config["model"]
    rng = np.random.default_rng(1)
    lengths = np.array([5, 16, 45, 64], np.int32)
    toks = np.zeros((4, 64), np.int32)
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


def test_served_tokens_match_through_decode(system):
    rng = np.random.default_rng(2)
    vocab = system.cell.config["model"]["vocab_size"]
    prompts = [rng.integers(0, vocab, n).astype(np.int32)
               for n in (3, 20, 40, 64)]
    outs = serve(system, prompts)
    assert widest_gap(system, prompts, outs) <= GAP
    bad = outs[1].copy()
    bad[5] = (bad[5] + 1) % vocab
    assert widest_gap(system, prompts[1:2], [bad]) > 5 * GAP


def test_program_in_float32_serves_the_references_argmax(root):
    """With the program computing in float32 as well, every served token
    is the reference's argmax: the chunked SSD prefill and the one-step
    decode have the reference's step-by-step semantics, and bf16
    rounding is all that the gaps above measure."""
    from repro.configs import get_arch
    from repro.models.model_zoo import build_model
    from repro.runtime.serve_loop import ServeConfig, ServeEngine

    bench = Bench(root)
    c = bench.cell(CELL)
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
    assert widest_gap(system, prompts, outs) == 0.0


def result(capsys, root, *extra, hook=None):
    got = bench_run.main(["--workload", CELL, "--seed", str(SEED),
                          "--seconds", "2", *extra], root=root,
                         require_tpu=False, compile_cache=False,
                         engine_hook=hook)
    out = capsys.readouterr()
    assert got == 0, out.err[-2000:]
    lines = [ln for ln in out.out.splitlines() if ln.startswith("{")]
    return json.loads(lines[-1])


def compared(out):
    (name,) = set(out["checks"]) - {"unfinished"}
    return out["checks"][name]["value"], out["checks"][name]["limit"]


def test_sound_run_is_correct(capsys, root):
    out = result(capsys, root)
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] > 0
    assert {"ttft_p90_ms", "setup_s"} <= set(out["metrics"])
    value, limit = compared(out)
    assert 0 <= value <= limit


@pytest.mark.parametrize("fault", ["control", "state-unchanged", "rope-off",
                                   "kv-heads-swapped"])
def test_a_broken_run_is_not_correct(capsys, root, fault):
    """The int8 control, a state left unchanged, and two faults planted
    in the attention branch alone: RoPE skipped, the KV heads swapped.
    A decode reading or writing K/V one position off moves the toy
    cell's gaps no more than bf16 does (``falcon_tiny``), so it is not
    asserted here."""
    if fault == "control":
        out = result(capsys, root, "--control")
    else:
        hook = FAULTS.get(fault) or attention_faults.FAULTS[fault]
        out = result(capsys, root, hook=hook)
    assert out["correct"] is False
    value, limit = compared(out)
    assert value > limit


# ---------------------------------------------------------------------------
# the configuration as published, and the cell's readers
# ---------------------------------------------------------------------------

def test_configuration_is_the_published_one_cut_in_depth():
    """Every key of the published config is in the file at its published
    value, but the depth; the program's sizes are the registry's
    falcon-h1-34b but the depth; and the multipliers the reference folds
    are the published ones."""
    from repro.configs import get_arch

    bench = Bench(REPO)
    conf = bench.cell("falcon-h1-doc-chat").config
    spec = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
    (entry,) = [c for c in spec["configs"] if c["name"] == "falcon-h1-34b-6L"]
    assert entry["reduced"] == ["num_hidden_layers"]
    assert conf["num_hidden_layers"] == conf["model"]["n_layers"] == 6
    arch = get_arch(conf["arch"])
    assert arch.scaled(**conf["model"]) == arch.scaled(n_layers=6)
    m = conf["model"]
    assert (m["d_model"], m["d_ff"], m["vocab_size"]) == (
        conf["hidden_size"], conf["intermediate_size"], conf["vocab_size"])
    assert (m["ssm_heads"] * m["ssm_head_dim"], m["ssm_state"],
            m["ssm_groups"], m["ssm_chunk"]) == (
        conf["mamba_d_ssm"], conf["mamba_d_state"], conf["mamba_n_groups"],
        conf["mamba_chunk_size"])
    ref = bench.reference("falcon_h1")
    for key, value in ref.MUP.items():
        assert conf[key] == pytest.approx(value), key
        assert key in conf["mup"]
    # the published widths make 430.1 M parameters a layer
    t = ref.cost_terms(m)
    assert round((t["matmul_params"] + t["other_params"]) / 1e5) == 4301


def record(trace):
    """Two admitted requests (prompts of 3000 and 5000 tokens) and a
    trace of two prefill executions, 40 ms of them under ``wkv``."""
    m = json.load(open(os.path.join(
        BENCH, "configs", "falcon-h1-34b-6L.json")))["model"]
    reqs = [Req(rid=i, prompt_len=n, max_new=4, arrival_s=0.0,
                status="done", n_out=4, submitted_at=0.0, admitted_at=0.1,
                done_at=0.2, admit_step=0,
                token_times=np.array([0.1, 0.12, 0.14, 0.16]))
            for i, n in enumerate((3000, 5000))]
    ref = Bench(REPO).reference("falcon_h1")
    return Run(cell="falcon-h1-doc-chat", terms=ref.cost_terms(m), model=m,
               n_layers=m["n_layers"], max_batch=16, paged=False,
               seconds=1.0, t0=0.0, requests=reqs,
               step_walls=np.array([0.12, 0.14, 0.16]), d0=0,
               counters={}, slot_occupancy=0.1,
               peaks={"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9},
               trace=trace)


def test_readers_on_a_hand_built_record():
    """The chat cells' readers, which adapt to a configuration by its
    ``cost_terms``, read a Falcon-H1 record: the prefill's device time,
    and the decode roofline with the KV cache up to each live row's
    context and the Mamba-2 state and conv tail."""
    bench = Bench(REPO)
    step = {"step": 1, "seconds": 0.02}
    run = record({"modules": {"_prefill_fn": [0.3, 0.5]},
                  "decode_calls": [step]})
    assert bench.reader("prefill_ms.chat")(run) == pytest.approx(400.0)
    ctx = run.step_contexts()[1]
    assert sorted(ctx) == [3001, 5001]
    # a row's f32 Mamba-2 state and bf16 conv tail; bf16 K and V
    assert run.terms["state_bytes"] == 32 * 128 * 256 * 4 + 3 * 5120 * 2
    assert run.terms["ctx_bytes"] == 2 * 4 * 128 * 2
    f, b = C.decode_step_cost(run.terms, run.model, run.n_layers, ctx)
    assert bench.reader("decode_roofline.chat")(run) == pytest.approx(
        100 * C.least_seconds(f, b, run.peaks)[0] / 0.02)


@pytest.mark.parametrize("metric", ["prefill_ms.chat",
                                    "decode_roofline.chat"])
def test_readers_read_nothing_without_a_trace(metric):
    bench = Bench(REPO)
    run = record(None)
    assert bench.reader(metric)(run) is None
    run.trace = {"modules": {"_decode_fn": [0.01]}, "decode_calls": []}
    assert bench.reader(metric)(run) is None
