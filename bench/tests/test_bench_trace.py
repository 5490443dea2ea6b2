"""The trace reduction, on a hand-built trace and on a slice of a trace
recorded on a TPU v5e (bench/tests/data/trace_slice.json, 1.5 s of the
glm4-chat window)."""
import json
import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

import trace_reduce as TRD  # noqa: E402

MS = 1e6      # ns


def hand_trace():
    """Device: decode 0-6 ms (two ops, overlapping), idle 6-10 ms while
    the host pulls ids, prefill 10-30 ms, idle to 40 ms outside any
    span.  Host spans: decode step 1 (0-9 ms) holding a pull (6-9 ms),
    an admit (9-31 ms)."""
    ops = [["fusion.1", 0.0, 4 * MS], ["fusion.2", 3 * MS, 3 * MS],
           ["fusion.7", 10 * MS, 20 * MS]]
    modules = [["jit__decode_fn(11)", 0.0, 6 * MS],
               ["jit__prefill_fn(12)", 10 * MS, 20 * MS]]
    host = [["bench.decode.1", 0.0, 9 * MS],
            ["bench.pull_logits", 6 * MS, 3 * MS],
            ["bench.admit", 9 * MS, 22 * MS],
            ["bench.enqueue", 39 * MS, 1 * MS]]
    return {"devices": [{"name": "/device:TPU:0", "ops": ops,
                         "modules": modules}], "host": host}


def test_hand_trace():
    r = TRD.reduce(hand_trace())
    assert r["window_s"] == pytest.approx(0.040)
    assert r["busy_s"] == pytest.approx(0.026)
    assert r["modules"] == {"_decode_fn": [pytest.approx(0.006)],
                            "_prefill_fn": [pytest.approx(0.020)]}
    assert r["decode_calls"] == [{"step": 1,
                                  "seconds": pytest.approx(0.006)}]
    ops = dict(r["device_ops"])
    assert ops["_prefill_fn/fusion.7"] == pytest.approx(0.020)
    assert ops["_decode_fn/fusion.1"] == pytest.approx(0.004)
    idle = dict(r["idle_gaps"])
    assert idle["pull_logits"] == pytest.approx(0.004)
    assert idle[TRD.NO_SPAN] == pytest.approx(0.010)


def test_device_clock_ahead_of_the_host_is_aligned():
    t = hand_trace()
    ahead = 1.5 * MS      # the device's clock reads earlier than the host's
    t["devices"][0]["ops"] = [[n, s - ahead, d]
                              for n, s, d in t["devices"][0]["ops"]]
    t["devices"][0]["modules"] = [[n, s - ahead, d]
                                  for n, s, d in t["devices"][0]["modules"]]
    assert TRD.clock_offset(t["devices"][0]["modules"],
                            t["host"]) == pytest.approx(-ahead)
    r = TRD.reduce(t)
    assert r["decode_calls"][0]["step"] == 1
    assert dict(r["idle_gaps"])["pull_logits"] == pytest.approx(0.004)


def test_self_time_excludes_a_loop_body():
    ops = [("while.1", 0.0, 10.0), ("fusion.1", 1.0, 3.0),
           ("fusion.2", 5.0, 4.0), ("fusion.3", 12.0, 1.0)]
    assert TRD._self_times(ops) == [3.0, 3.0, 4.0, 1.0]


def test_busy_is_a_union_averaged_over_devices():
    t = hand_trace()
    second = {"name": "/device:TPU:1", "ops": [["fusion.1", 0.0, 40 * MS]],
              "modules": []}
    t["devices"].append(second)
    assert TRD.reduce(t)["busy_s"] == pytest.approx((0.026 + 0.040) / 2)


def test_no_device_operation_is_an_error():
    with pytest.raises(ValueError):
        TRD.reduce({"devices": [], "host": []})


def test_names():
    assert TRD.program_name("jit__decode_fn(123)") == "_decode_fn"
    assert TRD.program_name("jit_argmax") == "argmax"
    assert TRD.span_label("bench.decode.118") == "decode"
    assert TRD.span_label("bench.ensure_pages") == "ensure_pages"


DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                    "trace_slice.json")


def test_recorded_tpu_slice():
    with open(DATA) as f:
        trace = json.load(f)
    r = TRD.reduce(trace)
    assert 0 < r["busy_s"] <= r["window_s"]
    assert r["modules"]["_decode_fn"]
    assert all(c["step"] is not None for c in r["decode_calls"][1:-1])
    steps = [c["step"] for c in r["decode_calls"] if c["step"]]
    assert steps == sorted(steps) and len(set(steps)) == len(steps)
    assert sum(v for _, v in r["idle_gaps"]) == pytest.approx(
        r["window_s"] - r["busy_s"], rel=1e-6)
    assert len(r["device_ops"]) == 10
