"""Build a cell's engine, warm it, drive its window, and check its answers.

The system under test is the program's own served path: the model built
by ``build_model`` from the program's architecture table with the
configuration's sizes, and ``ServeEngine`` configured by
``launch/serve.py``'s engine flags (``ServeConfig.from_args``).
Everything else (weights, traffic, timing, the reference) is the
benchmark's.
"""
from __future__ import annotations

import argparse
import collections
import dataclasses
import functools
import gc
import glob
import os
import shutil
import sys
import tempfile
import threading
import time
from typing import Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from benchlib import costs
from benchlib import record as R
from benchlib import traffic as TR
from benchlib import weights as W
from benchlib.cells import Bench, Cell

COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/backend_compile_duration")
REF_ROWS = 8            # requests re-scored by the reference per run,
REF_TOKENS = 1024       # or fewer once their served tokens reach this


def log(msg: str) -> None:
    print(f"# {msg}", file=sys.stderr, flush=True)


class CompileClock:
    """Counts what JAX traces and compiles (or loads from the persistent
    cache), so compiles inside the window show."""

    def __init__(self):
        self.count = collections.Counter()
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if event in COMPILE_EVENTS:
            self.count[event] += 1
            self.seconds += duration

    def total(self) -> int:
        return sum(self.count.values())


# ---------------------------------------------------------------------------
# the system under test
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class System:
    cell: Cell
    model: object
    params: object
    engine: object
    words: np.ndarray
    ref: object            # the family's reference module
    leaves: list


def build(bench: Bench, cell: Cell, seed: int, control: bool = False
          ) -> System:
    from repro.configs import get_arch
    from repro.configs.base import ExecutionPolicy
    from repro.models.model_zoo import build_model
    from repro.runtime.serve_loop import ServeConfig, ServeEngine

    m = cell.config["model"]
    arch = get_arch(cell.config["arch"]).scaled(**m)
    if control:
        # the program's own int8 path (W8A8 matmuls), one precision below
        # the bf16 the configuration is served in
        arch = arch.scaled(exec_policy=ExecutionPolicy(matmul="fxp8"))
    model = build_model(arch)
    ref = bench.reference(cell.config["reference"])
    leaves = ref.leaves(m)
    W.check_layout(leaves, model.abstract_params(), m["n_layers"])
    words = W.seed_words(seed)
    t = time.perf_counter()
    draw = jax.jit(functools.partial(W.draw_tree, leaves,
                                     n_layers=m["n_layers"]))
    params = jax.block_until_ready(draw(words))
    log(f"{cell.config_name}: weights drawn from seed {seed} in "
        f"{time.perf_counter() - t:.2f} s (one program)")

    ap = argparse.ArgumentParser(prog=cell.name)
    ServeConfig.add_args(ap)
    args = ap.parse_args(cell.engine_flags)
    ServeConfig.check_args(ap, args)
    config = ServeConfig.from_args(args)
    if args.mesh_shards:
        from repro.runtime.mesh_serve import MeshServeEngine
        engine = MeshServeEngine(model, params, config)
    else:
        engine = ServeEngine(model, params, config)
    return System(cell, model, params, engine, words, ref, leaves)


def warm(system: System, seed: int) -> int:
    """Run every program the cell's traffic can reach, on the engine that
    serves the window: one prefill (or extend) per prompt bucket, the
    insert or reset, and decode.  Returns how many buckets."""
    from repro.runtime.serve_loop import Request, next_pow2

    engine = system.engine
    cap = TR.prompt_cap(system.cell.traffic)
    top = min(max(engine.min_bucket, next_pow2(cap)), engine._bucket_cap)
    lengths, b = [], engine.min_bucket
    while b <= top:
        lengths.append(b)
        b *= 2
    vocab = system.cell.config["model"]["vocab_size"]
    for i, p in enumerate(TR.warm_prompts(seed, lengths, vocab)):
        engine.serve([Request(10 ** 9 + i, p, max_new_tokens=2)])
    return len(lengths)


# ---------------------------------------------------------------------------
# the window
# ---------------------------------------------------------------------------

SPANS = ("_admit", "_admit_paged", "_pull_logits", "_ensure_pages",
         "_sweep_deadlines", "_enqueue", "_poll_admissions")


def open_spans(engine) -> Dict[int, int]:
    """Wrap the engine's host methods in profiler spans (traced runs
    only), so idle gaps on the device can be named by what the host was
    doing; the decode step's span carries its step count.  Also records
    the prefix-cache hit of each admitted prompt (keyed by ``id`` of the
    prompt array), which ``mfu.batch`` needs."""
    for name in SPANS:
        fn = getattr(engine, name, None)
        if fn is None:
            continue
        label = f"bench.{name.lstrip('_')}"

        def wrapped(*a, _fn=fn, _label=label, **kw):
            with jax.profiler.TraceAnnotation(_label):
                return _fn(*a, **kw)
        setattr(engine, name, wrapped)
    step = engine._plain_step

    def plain_step(*a, **kw):
        n = int(engine.metrics["decode_steps"]) + 1
        with jax.profiler.TraceAnnotation(f"bench.decode.{n}"):
            return step(*a, **kw)
    engine._plain_step = plain_step
    matched: Dict[int, int] = {}
    if getattr(engine, "radix", None) is not None:
        match = engine.radix.match

        def recorded(prompt, *a, **kw):
            m, nodes = match(prompt, *a, **kw)
            matched[id(prompt)] = int(m)
            return m, nodes
        engine.radix.match = recorded
    return matched


class Tracer:
    """Profiles [start, stop] seconds into the window from a side thread,
    so the serve loop is not paused to start or stop it."""

    def __init__(self, start: float, stop: float):
        self.dir = tempfile.mkdtemp(prefix="bench-trace-")
        self.start, self.stop = start, stop
        self.thread: Optional[threading.Thread] = None
        self.error: Optional[Exception] = None

    def _run(self, t0: float) -> None:
        try:
            time.sleep(max(0.0, t0 + self.start - time.monotonic()))
            # the Python tracer would log every call of the serve loop;
            # the benchmark's own spans name the host's work instead
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(self.dir, profiler_options=opts)
            time.sleep(max(0.0, t0 + self.stop - time.monotonic()))
            jax.profiler.stop_trace()
        except Exception as e:      # surfaced by result()
            self.error = e

    def begin(self, t0: float) -> None:
        self.thread = threading.Thread(target=self._run, args=(t0,),
                                       daemon=True)
        self.thread.start()

    def result(self) -> dict:
        from trace_reduce import load, reduce

        self.thread.join(timeout=120)
        try:
            if self.thread.is_alive():
                raise RuntimeError("the profiler did not stop")
            if self.error is not None:
                raise RuntimeError(f"tracing failed: {self.error!r}")
            files = glob.glob(os.path.join(self.dir, "**", "*.xplane.pb"),
                              recursive=True)
            if not files:
                raise RuntimeError("the profiler wrote no trace")
            return reduce(load(max(files, key=os.path.getmtime)))
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


def requests(specs):
    from repro.runtime.serve_loop import Request

    return [Request(s.rid, s.prompt, max_new_tokens=s.max_new,
                    arrival_s=s.arrival_s, deadline_s=s.deadline_s)
            for s in specs]


def window(system: System, specs, seconds: float, peaks: dict,
           clock: CompileClock, tracer: Optional[Tracer] = None):
    """Serve the window's requests through ``ServeEngine.serve``; returns
    the record and the number of programs compiled inside the window."""
    engine = system.engine
    reqs = requests(specs)
    before = {k: engine.metrics[k] for k in R.COUNTERS}
    d0 = int(engine.metrics["decode_steps"])
    compiled = clock.total()
    matched = None
    if tracer is not None:
        matched = open_spans(engine)
        tracer.begin(time.monotonic())
    engine.serve(reqs)
    in_window = clock.total() - compiled
    m = system.cell.config["model"]
    run = R.build(engine, reqs, cell=system.cell.name,
                  terms=system.ref.cost_terms(m), model=m,
                  n_layers=m["n_layers"], seconds=seconds, d0=d0,
                  before=before, peaks=peaks)
    if matched is not None:
        run.matched = {r.rid: matched[id(r.prompt)] for r in reqs
                       if id(r.prompt) in matched}
    return run, reqs, in_window


# ---------------------------------------------------------------------------
# correctness: served tokens against the plain float32 reference
# ---------------------------------------------------------------------------

def sample(run: R.Run, seed: int, served: Dict[int, np.ndarray]
           ) -> List[int]:
    """Requests to re-score: the one with most served tokens, the first
    admitted (a cold prefix on prefix traffic), then others drawn from
    the seed, up to REF_ROWS requests or REF_TOKENS served tokens."""
    done = [r for r in run.requests if r.n_out and r.rid in served]
    if not done:
        return []
    picks = {max(done, key=lambda r: (r.n_out, r.prompt_len)).rid: None,
             min(done, key=lambda r: (r.admit_step, r.rid)).rid: None}
    for i in TR.rng(seed, 4).permutation(len(done)):
        if (len(picks) >= REF_ROWS
                or sum(served[p].size for p in picks) >= REF_TOKENS):
            break
        picks[done[i].rid] = None
    return list(picks)


def served_gaps(ref, words, m: dict, prompts: List[np.ndarray],
                served: List[np.ndarray], width: int) -> np.ndarray:
    """At each served token, the gap by which its reference logit lies
    below the reference's best logit at that position (0 where the
    served token is the reference's argmax)."""
    tokens = np.zeros((REF_ROWS, width), np.int32)
    ri, ci, ids = [], [], []
    for i, (prompt, out) in enumerate(zip(prompts, served)):
        seq = np.concatenate([prompt, out[:-1]])
        tokens[i, :len(seq)] = seq
        p = len(prompt)
        ri += [i] * len(out)
        ci += list(range(p - 1, p - 1 + len(out)))
        ids += [int(t) for t in out]
    x = ref.hidden(tokens, words, m, m["n_layers"])
    head = ref.head(words, m)
    n, chunk = len(ids), 512
    pad = -n % chunk
    ri, ci, ids = (np.asarray(a + [0] * pad, np.int32) for a in (ri, ci, ids))
    out = [np.asarray(_gaps(x, head, ri[i:i + chunk], ci[i:i + chunk],
                            ids[i:i + chunk]))
           for i in range(0, len(ids), chunk)]
    return np.concatenate(out)[:n]


def readings(gaps: np.ndarray) -> Dict[str, float]:
    """The numbers ``correct`` may compare, from the served tokens' gaps:
    the widest, the mean, and the share of tokens that are not the
    reference's argmax."""
    return {"max_logit_gap": float(gaps.max()),
            "mean_logit_gap": float(gaps.mean()),
            "off_argmax_share": float((gaps > 0).mean())}


@jax.jit
def _gaps(x, head, rows, cols, ids):
    """Reference best logit less the served token's, at each position."""
    with jax.default_matmul_precision("highest"):
        logits = x[rows, cols] @ head
    return logits.max(-1) - jnp.take_along_axis(logits, ids[:, None],
                                                -1)[:, 0]


def outcome(run: R.Run, kind: str) -> tuple:
    """(attempted, failed): an open loop attempts every request that
    arrived in the window and fails any not served in full; a backlog
    attempts what it admitted, and a request cut by the window's close
    (status "timeout") is not a failure."""
    if kind == "open_loop":
        return (len(run.requests),
                sum(r.status != "done" for r in run.requests))
    admitted = [r for r in run.requests if r.admitted]
    return (len(admitted),
            sum(r.status not in ("done", "timeout") for r in admitted))


def check(ref, words, cell: Cell, run: R.Run, prompts, served, seed: int,
          failed: int) -> Dict[str, dict]:
    """Each number ``correct`` compares (those the cell gives a limit),
    beside its limit; every reading goes to the log."""
    m = cell.config["model"]
    picks = sample(run, seed, served)
    t = time.perf_counter()
    mix = cell.traffic
    width = -(-(TR.prompt_cap(mix) + TR.longest(mix["output"])) // 128) * 128
    # no finished request leaves nothing to compare: that fails
    got = (readings(served_gaps(ref, words, m, [prompts[p] for p in picks],
                                [served[p] for p in picks], width))
           if picks else {name: None for name in cell.limits})
    log(f"reference: {len(picks)} requests, "
        f"{int(sum(served[p].size for p in picks))} served tokens "
        f"re-scored in float32 in {time.perf_counter() - t:.1f} s; "
        f"readings {got}")
    checks = {name: {"value": got[name], "limit": limit}
              for name, limit in cell.limits.items()}
    checks["unfinished"] = {"value": failed, "limit": 0}
    return checks


def device_info(devices, trace: Optional[dict]) -> dict:
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    info = {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": peak}
    if trace is not None:
        info["busy_s"] = trace["busy_s"]
        info["window_s"] = trace["window_s"]
    return info


def run_cell(bench: Bench, cell: Cell, seed: int, seconds: float,
             trace: bool, *, t_start: float, clock: CompileClock,
             control: bool = False,
             engine_hook: Optional[Callable] = None) -> dict:
    """One run: set-up, the measured window, the reference check, the
    metrics.  Returns the result line's object."""
    devices = jax.devices()
    peaks = bench.peaks(devices[0].device_kind)
    gc.collect()            # an earlier run's reference, in this process
    system = build(bench, cell, seed, control)
    if engine_hook is not None:
        engine_hook(system.engine)
    n_buckets = warm(system, seed)
    vocab = cell.config["model"]["vocab_size"]
    specs = TR.make(cell.traffic, seed, seconds, vocab)
    log(f"warm: {n_buckets} prompt buckets and decode on the serving "
        f"engine; {clock.total()} programs traced or compiled so far "
        f"({clock.seconds:.1f} s)")
    tracer = None
    if trace:
        start = 0.25 * seconds
        tracer = Tracer(start, start + min(10.0, 0.5 * seconds))
    t_window = time.monotonic()
    setup_s = t_window - t_start
    run, reqs, compiles = window(system, specs, seconds, peaks, clock,
                                 tracer)
    run.setup_s = setup_s
    log(f"window: {len(specs)} requests offered over {seconds:g} s; "
        f"serve() started {1e3 * (run.t0 - t_window):.3f} ms after "
        f"set-up ended; the engine stamps each arrival at its schedule "
        f"(submitted_at = start + arrival_s), so the generator runs 0 s "
        f"late")
    log(f"window: {compiles} programs traced or compiled inside it; "
        f"{len(run.admission_groups())} admission calls and "
        f"{int(run.counters['decode_steps'])} decode steps in serve()")
    if tracer is not None:
        run.trace = tracer.result()
        log(f"trace: {run.trace['window_s']:.3f} s traced; the decode "
            f"step's roofline is {costs.decode_bound(run)}-bound")
    info = device_info(devices, run.trace)
    prompts = {s.rid: s.prompt for s in specs}
    served = {q.rid: np.asarray(q.output, np.int32) for q in reqs
              if q.output is not None and len(q.output)}
    attempted, failed = outcome(run, cell.traffic["kind"])
    # free the engine and its weights before the reference runs: the
    # device's peak was read above and must not be set by the check
    ref, words = system.ref, system.words
    del system, reqs
    gc.collect()
    checks = check(ref, words, cell, run, prompts, served, seed, failed)
    correct = all(c["value"] is not None and c["value"] <= c["limit"]
                  for c in checks.values())
    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        v = bench.reader(m["name"])(run)
        if v is not None:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    out = {"correct": bool(correct), "attempted": attempted,
           "failed": failed, "metrics": metrics, "device": info}
    if run.trace is not None:
        out["breakdown"] = {"device_ops": run.trace["device_ops"],
                            "idle_gaps": run.trace["idle_gaps"]}
    out["checks"] = checks
    return out


def load_profile(run: R.Run) -> dict:
    """How the window's load fared: requests waiting for their first
    token at each quarter of the window, mean time to first token of the
    requests arriving in each quarter, and latency percentiles."""
    from benchlib.stats import percentile

    reqs = [r for r in run.requests if r.admitted and r.n_out]
    quarters = [run.t0 + run.seconds * q / 4 for q in (1, 2, 3, 4)]
    waiting = [sum(r.submitted_at <= t < r.token_times[0] for r in reqs)
               for t in quarters]
    by_q = [[r.ttft_s for r in reqs
             if q * run.seconds / 4 <= r.arrival_s < (q + 1) * run.seconds / 4]
            for q in range(4)]
    ttft = [r.ttft_s for r in reqs]
    tpot = [r.tpot_s for r in reqs if r.tpot_s is not None]
    last = max(r.done_at for r in reqs)
    return {"requests": len(run.requests), "served": len(reqs),
            "waiting_at_quarters": [int(w) for w in waiting],
            "mean_ttft_s_by_quarter": [sum(v) / len(v) if v else None
                                       for v in by_q],
            "ttft_p50_ms": 1e3 * percentile(ttft, 50),
            "ttft_p90_ms": 1e3 * percentile(ttft, 90),
            "tpot_p50_ms": 1e3 * percentile(tpot, 50),
            "tpot_p90_ms": 1e3 * percentile(tpot, 90),
            "drain_s": last - run.window_end,
            "output_tokens": int(sum(r.n_out for r in reqs))}


def sweep(bench: Bench, cell: Cell, seed: int, seconds: float, rates,
          clock: CompileClock):
    """Serve the cell's mix at each rate in turn on one warmed engine."""
    devices = jax.devices()
    peaks = bench.peaks(devices[0].device_kind)
    system = build(bench, cell, seed)
    warm(system, seed)
    vocab = cell.config["model"]["vocab_size"]
    for rate in rates:
        mix = dict(cell.traffic, rate_per_s=rate)
        specs = TR.make(mix, seed, seconds, vocab)
        run, _, compiles = window(system, specs, seconds, peaks, clock)
        yield dict(rate_per_s=rate, compiles_in_window=compiles,
                   **load_profile(run))
