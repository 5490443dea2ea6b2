"""Metric arithmetic on a hand-built window record, and the cost model."""
import os
import sys

import numpy as np
import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

from benchlib import costs as C  # noqa: E402
from benchlib.cells import Bench  # noqa: E402
from benchlib.record import Req, Run  # noqa: E402

GLM4 = {"n_layers": 8, "d_model": 4096, "n_heads": 32, "n_kv_heads": 2,
        "head_dim": 128, "d_ff": 13696, "vocab_size": 151552}
RWKV6 = {"n_layers": 32, "d_model": 2560, "n_heads": 40, "head_dim": 64,
         "d_ff": 8960, "vocab_size": 65536}
PEAKS = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
REFS = Bench(os.path.dirname(BENCH))
T_GLM4 = REFS.reference("glm4").cost_terms(GLM4)
T_RWKV6 = REFS.reference("rwkv6").cost_terms(RWKV6)


def read(metric, run):
    """``bench/metrics/<metric>.py``'s reading of ``run``."""
    return REFS.reader(metric)(run)


def req(rid, prompt_len, arrival, admit_step, times, paged_first=None):
    times = np.asarray(times, float)
    return Req(rid=rid, prompt_len=prompt_len, max_new=len(times),
               arrival_s=arrival, status="done", n_out=len(times),
               submitted_at=100.0 + arrival, admitted_at=times[0],
               done_at=times[-1], admit_step=admit_step,
               token_times=times)


def window():
    """Two requests: A admitted at step 0 (4 tokens, steps 1-3), B at
    step 2 (3 tokens, steps 3-4); a 0.5 s idle gap before step 5's
    stray row C (admitted at 4, 2 tokens)."""
    walls = np.array([100.10, 100.12, 100.20, 100.22, 100.80])
    a = req(0, 100, 0.0, 0, [100.05, 100.10, 100.12, 100.20])
    b = req(1, 300, 0.1, 2, [100.15, 100.20, 100.22])
    c = req(2, 20, 0.6, 4, [100.70, 100.80])
    return Run(cell="t", terms=T_GLM4, model=GLM4, n_layers=8,
               max_batch=16, paged=False, seconds=0.2, t0=100.0,
               requests=[a, b, c], step_walls=walls, d0=0,
               counters={"prefill_tokens": 420.0, "decode_tokens": 6.0,
                         "decode_steps": 5.0, "prefix_hit_tokens": 0.0},
               slot_occupancy=0.125, peaks=PEAKS, min_bucket=16,
               bucket_cap=2048, setup_s=1.0)


def test_latencies_and_percentiles():
    run = window()
    # TTFT: 0.05, 0.05, 0.10 s; p90 by linear interpolation
    assert read("ttft_p90_ms", run) == pytest.approx(90.0)
    # TPOT: A (0.15/3), B (0.07/2), C (0.10/1)
    assert read("tpot_p90_ms", run) == pytest.approx(
        1e3 * np.percentile([0.05, 0.035, 0.10], 90))


def test_tokens_in_window_count_only_what_the_host_held_by_the_close():
    run = window()                  # closes at 100.2
    assert run.tokens_in_window() == 4 + 2
    assert read("output_tok_s", run) == pytest.approx(6 / 0.2)


def test_step_contexts_and_live_rows():
    run = window()
    ctx = run.step_contexts()
    assert ctx == {1: [101], 2: [102], 3: [103, 301], 4: [302], 5: [21]}
    live = run.live_after_step()
    # A waits after steps 1, 2; B after step 3; C never waits
    assert live == {1: 1, 2: 1, 3: 1}


def test_stalls_skip_gaps_after_which_no_row_was_live():
    run = window()
    gaps = run.stall_gaps()
    assert [s for s, _ in gaps] == [2, 3, 4]
    assert [g for _, g in gaps] == pytest.approx([0.02, 0.08, 0.02])
    assert read("decode_stall_p99_ms.chat", run) == pytest.approx(
        1e3 * np.percentile([0.02, 0.08, 0.02], 99))


def test_prefill_useful_share_counts_padded_rows():
    run = window()
    # three admissions: buckets 128, 512, 32, each of 16 rows
    assert read("prefill_useful_share.chat", run) == pytest.approx(
        (100 + 300 + 20) / (16 * (128 + 512 + 32)))


def test_counter_shares():
    run = window()
    assert read("slot_occupancy.batch", run) == 0.125
    assert read("prefix_hit_share.batch", run) == 0.0
    run.counters["prefix_hit_tokens"] = 1260.0
    assert read("prefix_hit_share.batch", run) == pytest.approx(1260 / 1680)


TRACE = {"window_s": 2.0, "busy_s": 1.5,
         "modules": {"_decode_fn": [0.006, 0.008]},
         "decode_calls": [{"step": 3, "seconds": 0.008},
                          {"step": None, "seconds": 0.006}]}


@pytest.mark.parametrize("metric", ["decode_ms", "decode_roofline",
                                    "device_idle_share"])
def test_chat_and_batch_readers_of_one_quantity_agree(metric):
    run = window()
    run.trace = TRACE
    assert read(f"{metric}.batch", run) == read(f"{metric}.chat", run)
    assert read(f"{metric}.batch", run) is not None


def test_trace_metrics_need_a_trace():
    run = window()
    for metric in ("prefill_ms.chat", "decode_ms.chat",
                   "decode_roofline.chat", "device_idle_share.chat",
                   "mfu.batch"):
        assert read(metric, run) is None
    run.trace = TRACE
    assert read("decode_ms.chat", run) == pytest.approx(7.0)
    assert read("prefill_ms.chat", run) is None
    assert read("device_idle_share.chat", run) == pytest.approx(0.25)
    f, b = C.decode_step_cost(T_GLM4, GLM4, 8, [103, 301])
    least, bound = C.least_seconds(f, b, PEAKS)
    assert bound == "memory"
    assert read("decode_roofline.chat", run) == pytest.approx(100 * least / 0.008)


def test_mfu_counts_computed_prompt_tokens_and_window_outputs():
    run = window()
    run.matched = {0: 64, 1: 0, 2: 0}
    got = read("mfu.batch", run)
    m = GLM4
    want = 0.0
    for r, hit in ((run.requests[0], 64), (run.requests[1], 0),
                   (run.requests[2], 0)):
        want += sum(C.token_flops(T_GLM4, m, 8, p + 1, head=False)
                    for p in range(hit, r.prompt_len))
        want += 2 * m["d_model"] * m["vocab_size"]
        want += sum(C.token_flops(T_GLM4, m, 8, r.prompt_len + k)
                    for k in range(1, r.n_out)
                    if r.token_times[k] <= 100.2)
    assert got == pytest.approx(100 * want / (0.2 * 197e12))


def test_step_mfu_uses_the_stall_gaps():
    run = window()
    flops = sum(C.token_flops(T_GLM4, GLM4, 8, c)
                for s in (2, 3, 4) for c in run.step_contexts()[s])
    assert read("step_mfu.chat", run) == pytest.approx(
        100 * flops / (0.12 * 197e12))


def test_cost_model_sizes():
    # glm4-9b with 8 layers: 204 M matmul weights a layer and a 621 M
    # head, so ~4.5 GB read per decode step in bf16
    assert T_GLM4["matmul_params"] == pytest.approx(204e6, rel=0.01)
    assert C.weight_bytes(T_GLM4, GLM4, 8) == pytest.approx(4.5e9, rel=0.02)
    # one token at context 1: 2 x (8 layers of matmuls + head) + attention
    assert C.token_flops(T_GLM4, GLM4, 8, 1) == pytest.approx(
        2 * (8 * 204.5e6 + 4096 * 151552), rel=0.01)
    # K and V of 2 heads of 128 in bf16, 8 layers, read 10 and write 1
    assert C.state_bytes(T_GLM4, 8, 10) == 8 * 2 * 2 * 128 * 2 * 11
    # rwkv6-3b whole: ~2.9 B matmul weights with the head, 5.8 GB
    assert C.weight_bytes(T_RWKV6, RWKV6, 32) == pytest.approx(5.83e9,
                                                               rel=0.02)
    wkv = 40 * 64 * 64 * 4
    assert C.state_bytes(T_RWKV6, 32, 10) == 32 * 2 * (wkv + 2 * 2560 * 2)
