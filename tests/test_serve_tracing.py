"""What ServeEngine records about itself, and the scopes the model writes.

The contract under test (see ``runtime/serve_loop.py``):

  * request stamps are ordered — ``submitted_at <= admit_started_at <=
    admitted_at == token_times[0] <= ... <= done_at`` with one stamp per
    output token — on the dense, paged, recurrent and speculative paths,
    the first token is stamped after its admission's program on both the
    dense and the paged path, and ``queue_wait_s`` is the mean of
    ``admit_started_at - submitted_at`` on every one of them
  * ``prefill_positions`` counts ``rows x bucket`` per call: one row a
    request on the dense prefill, ``max_batch`` rows per paged extend;
    ``Request.prefix_hit_tokens`` sums to the engine's count
  * the ``serve.*`` host spans open with the expected names, nesting and
    ``step`` arguments (a recorder stands in for ``TraceAnnotation``),
    and a real ``jax.profiler`` trace holds them with their arguments
  * the model's named scopes reach the lowered programs
"""
import collections
import glob
import re
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_arch
from repro.configs.base import CacheSpec
from repro.models.model_zoo import build_model
from repro.runtime import serve_loop
from repro.runtime.serve_loop import Request, ServeConfig, ServeEngine

MAX_SEQ = 64
MAX_BATCH = 2
PAGE = 8
SPANS = {"serve.admit", "serve.decode", "serve.pull", "serve.pages",
         "serve.idle"}
ENGINES = {
    "dense": ("glm4-9b", {}),
    "paged": ("glm4-9b", {"cache": CacheSpec(paged=True, page_size=PAGE)}),
    "rwkv6": ("rwkv6-3b", {}),
    "spec": ("glm4-9b", {"spec_k": 2}),
}


@pytest.fixture(scope="module")
def served():
    cache = {}

    def get(arch):
        if arch not in cache:
            cfg = get_arch(arch).reduced()
            model = build_model(cfg)
            cache[arch] = (cfg, model, model.init(jax.random.PRNGKey(0)))
        return cache[arch]

    return get


def _engine(served, kind):
    arch, kw = ENGINES[kind]
    cfg, model, params = served(arch)
    engine = ServeEngine(model, params, ServeConfig(
        max_batch=MAX_BATCH, max_seq=MAX_SEQ, **kw))
    return cfg, engine


def _requests(cfg, arrivals=(0.0, 0.0, 0.0, 0.0, 0.0), seed=0):
    """Five requests sharing a two-page prefix (radix hits on the paged
    path), each repeating a short pattern (drafts on the speculative
    path); more requests than slots, so some wait in the queue."""
    rng = np.random.default_rng(seed)
    prefix = rng.integers(0, cfg.vocab_size, 2 * PAGE).astype(np.int32)
    reqs = []
    for i, arrival in enumerate(arrivals):
        own = np.tile(rng.integers(0, cfg.vocab_size, 3), 2 + i)
        prompt = np.concatenate([prefix, own]).astype(np.int32)
        reqs.append(Request(100 + i, prompt, max_new_tokens=3 + 2 * i,
                            arrival_s=arrival))
    return reqs


class SpanRecorder:
    """Stands in for ``jax.profiler.TraceAnnotation``: records each span's
    name, its arguments (``set_metadata`` included) and the span it was
    opened inside."""

    def __init__(self):
        self.spans = []
        self._open = []

    def __call__(self, name, **args):
        return _Span(self, name, args)

    def named(self, name):
        return [s for s in self.spans if s["name"] == name]

    def parent(self, span):
        p = span["parent"]
        return None if p is None else self.spans[p]["name"]


class _Span:
    def __init__(self, rec, name, args):
        self.rec, self.name, self.args = rec, name, args

    def __enter__(self):
        rec = self.rec
        self.i = len(rec.spans)
        rec.spans.append({"name": self.name, "args": dict(self.args),
                          "parent": rec._open[-1] if rec._open else None})
        rec._open.append(self.i)
        return self

    def __exit__(self, *exc):
        self.rec._open.pop()
        return False

    def set_metadata(self, **args):
        self.rec.spans[self.i]["args"].update(args)


@pytest.mark.parametrize("kind", sorted(ENGINES))
def test_request_stamps_are_ordered(served, kind):
    cfg, engine = _engine(served, kind)
    done = engine.serve(_requests(cfg))
    assert len(done) == 5
    for r in done:
        tt = r.token_times
        assert len(tt) == len(r.output) == r.max_new_tokens
        assert r.submitted_at <= r.admit_started_at <= r.admitted_at
        assert r.admitted_at == tt[0]
        assert tt == sorted(tt) and tt[-1] <= r.done_at
    waits = [r.admit_started_at - r.submitted_at for r in done]
    assert engine.metrics["queue_wait_s"] == pytest.approx(np.mean(waits))
    if kind == "spec":       # a step that accepted drafts stamps each
        assert engine.metrics["draft_accepted"] > 0
        assert any(len(set(r.token_times)) < len(r.token_times)
                   for r in done)


@pytest.mark.parametrize("kind", ["dense", "paged"])
def test_first_token_is_stamped_after_its_program(served, kind):
    """``admitted_at`` means the host holds the first token on both
    paths: it follows the end of the prefill or extend program of the
    request's own admission."""
    cfg, engine = _engine(served, kind)
    name = "_extend" if kind == "paged" else "_prefill"
    program, finished = getattr(engine, name), []

    def timed(*args):
        out = jax.block_until_ready(program(*args))
        finished.append(time.monotonic())
        return out
    setattr(engine, name, timed)
    for r in engine.serve(_requests(cfg)):
        assert any(r.admit_started_at <= t <= r.admitted_at
                   for t in finished), r.rid


@pytest.mark.parametrize("kind", ["dense", "paged", "rwkv6"])
def test_prefill_positions_count_every_call(served, kind, monkeypatch):
    cfg, engine = _engine(served, kind)
    rec = SpanRecorder()
    monkeypatch.setattr(serve_loop, "TraceAnnotation", rec)
    reqs = _requests(cfg)
    engine.serve(reqs)
    admits = rec.named("serve.admit")
    calls = [s["args"]["bucket"] for s in admits]
    if kind == "paged":      # one extend over every slot row a call
        rows = [MAX_BATCH] * len(calls)
    else:                    # one row a request, bucket fitted to it
        rows = [s["args"]["rows"] for s in admits]
        assert rows == [1] * len(reqs)
        lens = {r.rid: len(r.prompt) for r in reqs}
        assert calls == [engine._bucket(lens[ev[1]])
                         for ev in engine.events if ev[0] == "admit"]
    assert calls and engine.metrics["prefill_positions"] == \
        sum(r * b for r, b in zip(rows, calls))
    pad = 1 - engine.metrics["prefill_tokens"] / \
        engine.metrics["prefill_positions"]
    assert 0 < pad < 1


def test_prefix_hits_are_recorded_per_request(served):
    cfg, engine = _engine(served, "paged")
    done = engine.serve(_requests(cfg))
    hits = [r.prefix_hit_tokens for r in done]
    assert sum(hits) == engine.metrics["prefix_hit_tokens"] > 0
    assert all(h % PAGE == 0 for h in hits)


@pytest.mark.parametrize("kind", ["dense", "paged"])
def test_spans_names_nesting_and_steps(served, kind, monkeypatch):
    cfg, engine = _engine(served, kind)
    engine.serve(_requests(cfg, seed=1))            # compiles, untraced
    rec = SpanRecorder()
    monkeypatch.setattr(serve_loop, "TraceAnnotation", rec)
    d0 = int(engine.metrics["decode_steps"])
    # the last request arrives late, so the loop idles before it
    reqs = _requests(cfg, arrivals=(0.0, 0.0, 0.0, 0.0, 0.5))
    engine.serve(reqs)
    assert {s["name"] for s in rec.spans} == SPANS - (
        set() if kind == "paged" else {"serve.pages"})
    for s in rec.spans:
        if s["name"] in ("serve.admit", "serve.decode", "serve.idle"):
            assert rec.parent(s) is None, s
        elif s["name"] == "serve.pull":
            assert rec.parent(s) in ("serve.admit", "serve.decode"), s
        else:
            assert rec.parent(s) == "serve.decode", s
    steps = [s["args"]["step"] for s in rec.named("serve.decode")]
    assert steps == list(range(d0 + 1,
                               int(engine.metrics["decode_steps"]) + 1))
    # each admission's step and rows match the events, which carry rids
    # (a dense group opens one one-row span per request, at one step)
    admits = collections.Counter(ev[3] for ev in engine.events
                                 if ev[0] == "admit")
    spans = collections.Counter()
    for s in rec.named("serve.admit"):
        spans[s["args"]["step"]] += s["args"]["rows"]
    assert spans == admits


def test_real_profiler_trace_holds_the_spans(served, tmp_path):
    from jax.profiler import ProfileData

    cfg, engine = _engine(served, "paged")
    engine.serve(_requests(cfg, seed=2))            # compiles, untraced
    jax.profiler.start_trace(str(tmp_path))
    try:
        engine.serve(_requests(cfg, seed=3))
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    found = collections.defaultdict(list)
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("serve."):
                        found[e.name].append(dict(e.stats))
    assert set(found) == SPANS - {"serve.idle"}
    assert all("step" in a for a in found["serve.decode"])
    assert all({"step", "rows", "bucket"} <= set(a)
               for a in found["serve.admit"])


def _scopes(lowered) -> set:
    names = re.findall(r'loc\("([^"]*)"', lowered.as_text(debug_info=True))
    return {part for n in names for part in n.split("/")}


@pytest.mark.parametrize("kind,scopes", [
    ("rwkv6", {"time_mix", "wkv", "channel_mix", "head"}),
    ("dense", {"attention", "mlp", "head"})])
def test_model_scopes_reach_the_programs(served, kind, scopes):
    _, engine = _engine(served, kind)
    state = engine.ops.init_slot_state(MAX_BATCH, MAX_SEQ)
    tokens = jnp.zeros((MAX_BATCH, 16), jnp.int32)
    prefill = engine._prefill.lower(engine.params, {"tokens": tokens},
                                    jnp.ones((MAX_BATCH,), jnp.int32))
    decode = engine._decode.lower(engine.params, state,
                                  {"tokens": tokens[:, :1]})
    assert scopes <= _scopes(prefill)
    assert scopes <= _scopes(decode)
