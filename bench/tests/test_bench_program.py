"""What the program records about itself, as the benchmark reads it: the
reduction of its spans and scopes (``trace_program.py``) on a hand-built
trace with a known skew and on a slice of a traced rwkv6-chat run on a
TPU v5e (bench/tests/data/trace_slice_rwkv6_chat.json.gz, 1.46 s of its
window, ``trace_program.load``'s output); the readers of the program's
stamps, counters and scopes; and the record's token times against the
engine's own stamps."""
import glob
import gzip
import json
import os
import sys

import numpy as np
import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(os.path.dirname(BENCH), "src"))

import trace_program as TP  # noqa: E402
import trace_reduce as TRD  # noqa: E402
from benchlib import record as R  # noqa: E402
from benchlib.cells import Bench  # noqa: E402
from test_bench_metrics import window  # noqa: E402

MS = 1e6      # ns
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
READERS = Bench(os.path.dirname(BENCH))


def hand_trace(ahead=1.5 * MS):
    """Host: decode step 1 (0-9 ms, its pull 6-9), an admission at step 1
    launching a bucket-16 prefill (9-31, its pull 29-31), decode step 2
    (31-40, pull 38-40), the loop idle (40-50), each with the span the
    benchmark wraps around it besides.  Device, on its own
    clock, which reads ``ahead`` earlier than the host's: a prefill cut
    by the trace's start (its span was open when the profiler started),
    then each program 0.5 ms after its span starts.  The wkv op runs
    inside a loop op of the prefill."""
    true = {"decode1": (0.5, 6.0), "prefill": (9.5, 29.0),
            "decode2": (31.5, 38.0)}

    def dev(start, end):
        return start * MS - ahead, (end - start) * MS

    s, d = dev(*true["prefill"])
    ops = [["fusion.9", -20 * MS, 5 * MS],
           ["fusion.1", *dev(*true["decode1"])],
           ["while.3", s, d], ["fusion.7", s + 1 * MS, 15 * MS],
           ["fusion.1", *dev(*true["decode2"])]]
    scopes = [None, "channel_mix", "time_mix", "wkv", "head"]
    modules = [["jit__prefill_fn(3)", -20 * MS, 5 * MS],
               ["jit__decode_fn(1)", *dev(*true["decode1"])],
               ["jit__prefill_fn(3)", *dev(*true["prefill"])],
               ["jit__decode_fn(1)", *dev(*true["decode2"])]]
    serve = [["serve.decode", 0.0, 9 * MS, {"step": 1}],
             ["serve.pull", 6 * MS, 3 * MS, {}],
             ["serve.admit", 9 * MS, 22 * MS,
              {"step": 1, "rows": 2, "bucket": 16}],
             ["serve.pull", 29 * MS, 2 * MS, {}],
             ["serve.decode", 31 * MS, 9 * MS, {"step": 2}],
             ["serve.pull", 38 * MS, 2 * MS, {}],
             ["serve.idle", 40 * MS, 10 * MS, {}]]
    host = [["bench.decode.1", 0.0, 9 * MS],
            ["bench.pull_logits", 6 * MS, 3 * MS],
            ["bench.admit", 9 * MS, 22 * MS],
            ["bench.pull_logits", 29 * MS, 2 * MS],
            ["bench.decode.2", 31 * MS, 9 * MS],
            ["bench.pull_logits", 38 * MS, 2 * MS],
            ["bench.poll_admissions", 49 * MS, 1 * MS]]
    return {"devices": [{"name": "/device:TPU:0", "ops": ops,
                         "modules": modules, "scopes": scopes}],
            "host": host, "serve": serve}


def test_hand_trace_tie_shift_scopes_and_idle():
    r = TP.reduce(hand_trace())
    # three of the four executions have their launching span
    assert r["tied"] == pytest.approx(3 / 4)
    # each program starts 1.0 ms before its span on the device's clock
    assert r["shift_s"] == pytest.approx(0.001)
    assert r["scopes"]["_decode_fn"] == {
        "channel_mix": pytest.approx(0.0055),
        "head": pytest.approx(0.0065)}
    # the loop's own time less its body's; the cut prefill's op is in no
    # scope
    assert r["scopes"]["_prefill_fn"] == {
        "time_mix": pytest.approx(0.0045), "wkv": pytest.approx(0.015),
        TP.NO_SCOPE: pytest.approx(0.005)}
    # on the shifted clock the device idles 5.5-9 and 28.5-31 ms (pulls),
    # 37.5-50 (the loop waits), and 14 ms after the cut prefill, before
    # the first recorded serve span
    idle = dict(r["idle_by_span"])
    assert idle == {"serve.pull": pytest.approx(0.0035 + 0.0025),
                    "serve.idle": pytest.approx(0.0125),
                    TP.CUT: pytest.approx(0.014)}
    assert sum(idle.values()) == pytest.approx(
        sum(v for _, v in r["idle_gaps"]))


@pytest.mark.parametrize("ahead_ms", [-2.0, 0.0, 1.5, 4.0])
def test_tie_needs_no_fitted_offset(ahead_ms):
    """Whichever way the device's clock is off, by less than a step, the
    tie is the same; the shift is applied only where an execution would
    start before its span."""
    r = TP.reduce(hand_trace(ahead=ahead_ms * MS))
    assert r["tied"] == pytest.approx(3 / 4)
    assert r["shift_s"] == pytest.approx(max(0.0, ahead_ms - 0.5) * 1e-3)


def test_existing_keys_read_as_before_on_the_committed_slices():
    with open(os.path.join(DATA, "trace_slice.json")) as f:
        old = json.load(f)
    base = TRD.reduce(old)
    got = TP.reduce(old)
    assert {k: got[k] for k in base} == base
    assert got["tied"] == 0.0 and got["shift_s"] == 0.0


@pytest.fixture(scope="module")
def rwkv6_slice():
    with gzip.open(os.path.join(DATA, "trace_slice_rwkv6_chat.json.gz"),
                   "rt") as f:
        return json.load(f)


def test_recorded_rwkv6_slice(rwkv6_slice):
    base = TRD.reduce(rwkv6_slice)
    r = TP.reduce(rwkv6_slice)
    assert {k: r[k] for k in base} == base
    # 31 executions; a prefill whose span opened before the profiler
    # started and two decode steps cut at the ends have no span
    assert r["tied"] == pytest.approx(28 / 31)
    assert 0 < r["shift_s"] < 0.005
    prefill = r["scopes"]["_prefill_fn"]
    per_call = prefill["wkv"] / len(r["modules"]["_prefill_fn"])
    assert 0 < per_call < sum(r["modules"]["_prefill_fn"]) / len(
        r["modules"]["_prefill_fn"])
    assert prefill["wkv"] == max(prefill.values())
    assert set(r["scopes"]["_decode_fn"]) == {"wkv", "time_mix",
                                              "channel_mix", "head",
                                              TP.NO_SCOPE}
    idle = dict(r["idle_by_span"])
    total = sum(v for _, v in base["idle_gaps"])
    assert sum(idle.values()) == pytest.approx(total, rel=0.01)
    assert idle.get(TP.NO_SPAN, 0.0) < 0.01 * total
    assert max(idle, key=idle.get) == "serve.pull"


def test_ties_follow_the_step_arguments(rwkv6_slice):
    """Tied decode executions take consecutive steps, in order."""
    serve = rwkv6_slice["serve"]
    mods = sorted((m for m in rwkv6_slice["devices"][0]["modules"]
                   if TRD.program_name(m[0]) == "_decode_fn"),
                  key=lambda m: m[1])
    spans = TP._launching(serve, "serve.decode")
    steps = [spans[k][3]["step"] for _, k in TP.tie(mods, spans)]
    assert steps == list(range(steps[0], steps[0] + len(steps)))


# ---------------------------------------------------------------------------
# the serialized trace's metadata
# ---------------------------------------------------------------------------

def _varint(n):
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def _field(num, value):
    if isinstance(value, int):
        return _varint(num << 3) + _varint(value)
    if isinstance(value, str):
        value = value.encode()
    return _varint(num << 3 | 2) + _varint(len(value)) + value


def _plane(name, stat_names, events):
    out = _field(2, name)
    for sid, sname in stat_names.items():
        out += _field(5, _field(1, sid) + _field(2, _field(1, sid)
                                                 + _field(2, sname)))
    for eid, (ename, stats) in events.items():
        body = _field(1, eid) + _field(2, ename)
        for sid, kind, value in stats:
            body += _field(5, _field(1, sid) + _field(kind, value))
        out += _field(4, _field(1, eid) + _field(2, body))
    return out


def test_op_scopes_read_the_metadata_stats():
    """tf_op as a string, or as a reference to a string kept once."""
    stats = {1: "hlo_category", 2: "tf_op", 9: "jit(f)/head/dot:"}
    dev = _plane("/device:TPU:0", stats, {
        5: ("%fusion.1 = f32[2] fusion()",
            [(1, 5, "loop fusion"), (2, 5, "jit(f)/time_mix/wkv/while:")]),
        6: ("%dot.2 = f32[2] dot()", [(2, 7, 9)]),
        7: ("%copy.3 = f32[2] copy()", [(1, 5, "data formatting")])})
    host = _plane("/host:CPU", stats, {8: ("serve.decode", [(2, 5, "x")])})
    got = TP.op_scopes(_field(1, dev) + _field(1, host))
    assert got == {"/device:TPU:0": {
        "%fusion.1 = f32[2] fusion()": "jit(f)/time_mix/wkv/while:",
        "%dot.2 = f32[2] dot()": "jit(f)/head/dot:"}}
    assert TP.innermost_scope("jit(f)/time_mix/wkv/while:") == "wkv"
    assert TP.innermost_scope("jit(f)/while/body/mul:") is None


# ---------------------------------------------------------------------------
# the engine on the CPU: a real trace, and the record's token times
# ---------------------------------------------------------------------------

def _tiny_engine(paged):
    import jax

    from repro.configs import get_arch
    from repro.configs.base import CacheSpec
    from repro.models.model_zoo import build_model
    from repro.runtime.serve_loop import ServeConfig, ServeEngine

    cfg = get_arch("glm4-9b").reduced()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    cache = CacheSpec(paged=True, page_size=8) if paged else None
    return cfg, ServeEngine(model, params, ServeConfig(
        max_batch=2, max_seq=64, cache=cache))


def _requests(cfg, seed):
    from repro.runtime.serve_loop import Request

    rng = np.random.default_rng(seed)
    return [Request(i, rng.integers(0, cfg.vocab_size, n).astype(np.int32),
                    max_new_tokens=m, arrival_s=0.01 * i)
            for i, (n, m) in enumerate([(5, 4), (20, 6), (9, 3), (30, 5),
                                        (12, 2)])]


def test_load_keeps_the_program_spans_of_a_cpu_trace(tmp_path):
    import jax

    cfg, engine = _tiny_engine(paged=False)
    engine.serve(_requests(cfg, 1))                 # compiles, untraced
    jax.profiler.start_trace(str(tmp_path))
    try:
        engine.serve(_requests(cfg, 2))
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    got = TP.load(path)
    base = TRD.load(path)
    assert got["host"] == base["host"]
    assert [{k: v for k, v in d.items() if k != "scopes"}
            for d in got["devices"]] == base["devices"]
    decodes = [s[3] for s in got["serve"] if s[0] == "serve.decode"]
    steps = [a["step"] for a in decodes]
    assert steps == list(range(steps[0], steps[0] + len(steps)))
    admits = [s[3] for s in got["serve"] if s[0] == "serve.admit"]
    assert admits and all({"step", "rows", "bucket"} <= set(a)
                          for a in admits)


@pytest.mark.parametrize("paged", [False, True])
def test_record_token_times_against_the_engine_stamps(paged):
    """The record dates each token by the end of the decode step that
    emitted it; the engine stamps it after that step's pull.  Dense: the
    first token is the same stamp, each later one lies between its own
    stamp and the next token's.  Paged: the record dates the first token
    by the end of the step after the admission, at most one step late."""
    cfg, engine = _tiny_engine(paged)
    engine.serve(_requests(cfg, 3))                 # compiles
    reqs = _requests(cfg, 4)
    before = {k: engine.metrics[k] for k in R.COUNTERS}
    d0 = int(engine.metrics["decode_steps"])
    engine.serve(reqs)
    run = R.build(engine, reqs, cell="t", terms={}, model={}, n_layers=1,
                  seconds=1.0, d0=d0, before=before, peaks={})
    for rec, r in zip(run.requests, reqs):
        stamps, rebuilt = r.token_times, list(rec.token_times)
        n = len(stamps)
        assert len(rebuilt) == n
        for k in range(1, n):
            assert stamps[k] <= rebuilt[k]
            assert k + 1 == n or rebuilt[k] <= stamps[k + 1]
        if paged:
            assert stamps[0] <= rebuilt[0]
            assert n < 3 or rebuilt[0] <= stamps[2]
        else:
            assert rebuilt[0] == stamps[0]


# ---------------------------------------------------------------------------
# the readers
# ---------------------------------------------------------------------------

def read(metric, run):
    return READERS.reader(metric)(run)


def with_program_records(run):
    """The hand-built window with what the program records: admission
    starts (A at once, B 20 ms after arriving, C 60 ms after), the padded
    positions (3 calls of 16 rows: two at bucket 16, one at 512), and a
    trace with scopes (two prefills, 30 ms each, 12 ms of it in wkv)."""
    for r, wait in zip(run.requests, (0.0, 0.02, 0.06)):
        r.admit_started_at = r.submitted_at + wait
    run.counters["prefill_positions"] = 16.0 * (16 + 16 + 512)
    run.trace = {"modules": {"_prefill_fn": [0.03, 0.03]},
                 "scopes": {"_prefill_fn": {"wkv": 0.024,
                                            "time_mix": 0.02}}}
    return run


def test_program_readers_on_a_hand_built_record():
    run = with_program_records(window())
    assert read("queue_wait_p90_ms.chat", run) == pytest.approx(
        1e3 * np.percentile([0.0, 0.02, 0.06], 90))
    assert read("prefill_pad_share.chat", run) == pytest.approx(
        1 - 420 / (16 * 544))
    assert read("prefill_wkv_ms.chat", run) == pytest.approx(12.0)


@pytest.mark.parametrize("metric", ["queue_wait_p90_ms.chat",
                                    "prefill_pad_share.chat",
                                    "prefill_wkv_ms.chat"])
def test_program_readers_read_nothing_without_the_records(metric):
    run = window()
    assert read(metric, run) is None
    run.trace = {"modules": {"_prefill_fn": [0.03]}, "busy_s": 1.0}
    assert read(metric, run) is None
