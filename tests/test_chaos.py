"""Chaos harness: kill/restore bit-identity, backpressure, accounting.

The fault-tolerance acceptance tests for the serving stack:

  * an engine killed at an arbitrary decode step (``kill_at_step`` fault
    injection) and restored by :class:`ServeSupervisor` into a *fresh*
    engine — different ``max_batch``, a smaller paged pool — completes
    every request **bit-identically** to an uninterrupted run, across
    dense/ssm/hybrid families, fp32 and int8 caches, dense and paged
    backends, plain and speculative decode, greedy and sampled;
  * bounded-queue shedding policies and per-request deadlines terminate
    every request with an explicit status and leak no accounting
    (block-pool ``assert_balanced`` holds after restore);
  * restore re-enters through the existing jitted programs — a restored
    engine decodes with exactly one trace (bucket discipline preserved).
"""
from __future__ import annotations

import dataclasses

import jax
import numpy as np
import pytest

from repro.configs import get_arch
from repro.configs.base import CacheSpec
from repro.models.model_zoo import build_model
from repro.parallel.fault_tolerance import WorkerKilled
from repro.runtime.serve_loop import Request, ServeConfig, ServeEngine
from repro.runtime.supervisor import ServeSupervisor

MAX_SEQ = 64
PAGE = 8


@pytest.fixture(scope="module")
def served():
    """One model + params per (family, cache format), shared per module."""
    cache = {}

    def get(arch, spec=None):
        key = (arch, spec)
        if key not in cache:
            cfg = get_arch(arch).reduced()
            if spec is not None:
                cfg = dataclasses.replace(cfg, cache=spec)
            model = build_model(cfg)
            params = model.init(jax.random.PRNGKey(0))
            cache[key] = (cfg, model, params)
        return cache[key]

    return get


def _requests(cfg, lens=(5, 9, 13, 3, 7), max_news=(10, 6, 12, 8, 5),
              temperature=0.0, seed=0):
    rng = np.random.default_rng(seed)
    return [Request(rid=i,
                    prompt=rng.integers(0, cfg.vocab_size, n)
                    .astype(np.int32),
                    max_new_tokens=m, temperature=temperature,
                    top_k=12 if temperature else 0, seed=7)
            for i, (n, m) in enumerate(zip(lens, max_news))]


def _outputs(done):
    return {r.rid: (r.status, list(np.asarray(r.output)))
            for r in done}


def _assert_drained(engine):
    """No accounting leaks: every non-radix block is back in the pool."""
    if engine.allocator is not None:
        engine.allocator.assert_balanced()
        if engine.radix is not None:
            engine.radix.evict(engine.allocator.num_blocks)
        assert engine.allocator.used_blocks == 0


# ---------------------------------------------------------------------------
# Kill/restore bit-identity across the serving matrix
# ---------------------------------------------------------------------------

CHAOS_MATRIX = [
    # (arch, cache spec, spec_k, kill_at_step)
    ("glm4-9b", None, 0, 1),
    ("glm4-9b", None, 0, 5),
    ("glm4-9b", CacheSpec(dtype="int8"), 0, 4),
    ("glm4-9b", CacheSpec(paged=True, page_size=PAGE), 0, 3),
    ("glm4-9b", CacheSpec(dtype="int8", paged=True, page_size=PAGE), 0, 6),
    ("glm4-9b", None, 3, 2),
    ("rwkv6-3b", None, 0, 4),
    ("rwkv6-3b", CacheSpec(dtype="int8"), 0, 3),
    ("rwkv6-3b", CacheSpec(paged=True, page_size=PAGE), 0, 5),
    ("hymba-1.5b", None, 0, 4),
    ("hymba-1.5b", CacheSpec(dtype="int8", paged=True, page_size=PAGE),
     0, 3),
    ("falcon-h1-34b", None, 0, 4),
    ("falcon-h1-34b", CacheSpec(dtype="int8", paged=True, page_size=PAGE),
     0, 3),
]


@pytest.mark.parametrize("arch,spec,spec_k,kill_at",
                         CHAOS_MATRIX,
                         ids=lambda v: str(v).replace(" ", ""))
def test_kill_restore_bit_identical(served, tmp_path, arch, spec, spec_k,
                                    kill_at):
    """Killed mid-trace, restored into a *smaller* fresh engine (fewer
    slots; paged: a smaller pool), every output matches the uninterrupted
    run bit for bit."""
    cfg, model, params = served(arch, spec)
    ref_eng = ServeEngine(model, params,
                          ServeConfig(max_batch=3, max_seq=MAX_SEQ,
                                      spec_k=spec_k))
    ref = _outputs(ref_eng.serve(_requests(cfg)))

    paged = spec is not None and spec.paged

    def factory(i):
        return ServeEngine(model, params, ServeConfig(
            max_batch=3 if i == 0 else 2, max_seq=MAX_SEQ, spec_k=spec_k,
            snapshot_dir=str(tmp_path), snapshot_every=2,
            kill_at_step=kill_at if i == 0 else None,
            num_blocks=(3 * MAX_SEQ // PAGE if i == 0 else 20)
            if paged else None))

    sup = ServeSupervisor(factory, max_restarts=2)
    got = _outputs(sup.run(_requests(cfg)))
    assert len(sup.history) == 1     # exactly one injected death
    assert got == ref
    _assert_drained(sup.engine)
    # liveness telemetry saw the death + respawn
    assert not sup.monitor.workers["serve"].alive
    assert sup.monitor.workers["serve-r1"].alive


def test_kill_before_first_snapshot_replays(served, tmp_path):
    """A death before any snapshot landed falls back to full replay —
    deterministic decode makes the re-run bit-identical too."""
    cfg, model, params = served("glm4-9b")
    ref_eng = ServeEngine(model, params,
                          ServeConfig(max_batch=3, max_seq=MAX_SEQ))
    ref = _outputs(ref_eng.serve(_requests(cfg)))

    def factory(i):
        return ServeEngine(model, params, ServeConfig(
            max_batch=3, max_seq=MAX_SEQ, snapshot_dir=str(tmp_path),
            snapshot_every=100,          # cadence never fires before kill
            kill_at_step=2 if i == 0 else None))

    sup = ServeSupervisor(factory)
    got = _outputs(sup.run(_requests(cfg)))
    assert got == ref
    assert sup.history[0].restored_step is None
    assert sorted(sup.history[0].replayed_rids) == [0, 1, 2, 3, 4]


def test_sampled_rng_state_restores(served, tmp_path):
    """Temperature slots resume their exact RNG stream mid-request."""
    cfg, model, params = served("glm4-9b")
    ref_eng = ServeEngine(model, params,
                          ServeConfig(max_batch=3, max_seq=MAX_SEQ,
                                      greedy=False))
    ref = _outputs(ref_eng.serve(_requests(cfg, temperature=0.9)))

    def factory(i):
        return ServeEngine(model, params, ServeConfig(
            max_batch=3, max_seq=MAX_SEQ, greedy=False,
            snapshot_dir=str(tmp_path), snapshot_every=3,
            kill_at_step=7 if i == 0 else None))

    sup = ServeSupervisor(factory)
    got = _outputs(sup.run(_requests(cfg, temperature=0.9)))
    assert got == ref
    # at least one request actually resumed mid-flight (not just replayed)
    assert sup.history[0].resumed_rids


def test_restore_does_not_retrace(served, tmp_path):
    """Bucket discipline survives restore: the respawned engine runs the
    whole resumed trace on ONE decode trace, and restores through the
    existing insert program."""
    cfg, model, params = served("glm4-9b")

    def factory(i):
        return ServeEngine(model, params, ServeConfig(
            max_batch=3, max_seq=MAX_SEQ, snapshot_dir=str(tmp_path),
            snapshot_every=2, kill_at_step=5 if i == 0 else None))

    sup = ServeSupervisor(factory)
    sup.run(_requests(cfg))
    eng = sup.engine
    assert eng.trace_counts["decode"] == 1, dict(eng.trace_counts)
    # restore rode the slot_update scatter seam (dense path), not a
    # bespoke per-restore program
    assert eng.trace_counts["insert"] >= 1


def test_restore_inserts_one_row_per_request(served, tmp_path):
    """A dense restore scatters each parked request as its own one-row
    sub-state, the shape a one-row prefill of its bucket inserts, so
    every insert the respawned engine makes has one row."""
    cfg, model, params = served("glm4-9b")
    rows = []

    def factory(i):
        eng = ServeEngine(model, params, ServeConfig(
            max_batch=3, max_seq=MAX_SEQ, snapshot_dir=str(tmp_path),
            snapshot_every=2, kill_at_step=5 if i == 0 else None))
        inner = eng._insert

        def recorded(st, sub, slots):
            rows.append((i, len(slots)))
            return inner(st, sub, slots)
        eng._insert = recorded
        return eng

    sup = ServeSupervisor(factory)
    sup.run(_requests(cfg))
    assert sup.history[0].resumed_rids
    assert any(i == 1 for i, _ in rows)
    assert all(n == 1 for _, n in rows), rows


def test_double_kill_two_recoveries(served, tmp_path):
    """Two injected deaths (the second on the respawned engine) still
    finish every request bit-identically."""
    cfg, model, params = served("glm4-9b")
    ref_eng = ServeEngine(model, params,
                          ServeConfig(max_batch=3, max_seq=MAX_SEQ))
    ref = _outputs(ref_eng.serve(_requests(cfg)))

    def factory(i):
        return ServeEngine(model, params, ServeConfig(
            max_batch=3, max_seq=MAX_SEQ, snapshot_dir=str(tmp_path),
            snapshot_every=2,
            kill_at_step={0: 3, 1: 2}.get(i)))

    sup = ServeSupervisor(factory, max_restarts=3)
    got = _outputs(sup.run(_requests(cfg)))
    assert got == ref
    assert len(sup.history) == 2


def test_restart_budget_exhausted(served, tmp_path):
    cfg, model, params = served("glm4-9b")

    def factory(i):
        return ServeEngine(model, params, ServeConfig(
            max_batch=2, max_seq=MAX_SEQ, snapshot_dir=str(tmp_path),
            snapshot_every=2, kill_at_step=2))       # every incarnation dies

    sup = ServeSupervisor(factory, max_restarts=2)
    with pytest.raises(RuntimeError, match="restart budget"):
        sup.run(_requests(cfg))


# ---------------------------------------------------------------------------
# Snapshot format / compatibility validation
# ---------------------------------------------------------------------------

def test_restore_rejects_fingerprint_mismatch(served, tmp_path):
    cfg, model, params = served("glm4-9b")
    eng = ServeEngine(model, params, ServeConfig(
        max_batch=2, max_seq=MAX_SEQ, snapshot_dir=str(tmp_path)))
    eng.serve(_requests(cfg, lens=(5, 3), max_news=(4, 4)))
    eng.snapshot()

    cfg2, model2, params2 = served("rwkv6-3b")
    eng2 = ServeEngine(model2, params2, ServeConfig(
        max_batch=2, max_seq=MAX_SEQ, snapshot_dir=str(tmp_path)))
    with pytest.raises(ValueError, match="fingerprint mismatch"):
        eng2.restore_snapshot()

    # int8 vs fp32 is also a fingerprint difference — a dequantized
    # restore could not be bit-identical, so it must refuse
    cfgq, modelq, paramsq = served("glm4-9b", CacheSpec(dtype="int8"))
    engq = ServeEngine(modelq, paramsq, ServeConfig(
        max_batch=2, max_seq=MAX_SEQ, snapshot_dir=str(tmp_path)))
    with pytest.raises(ValueError, match="fingerprint mismatch"):
        engq.restore_snapshot()


def test_restore_rejects_greedy_mismatch(served, tmp_path):
    cfg, model, params = served("glm4-9b")
    eng = ServeEngine(model, params, ServeConfig(
        max_batch=2, max_seq=MAX_SEQ, snapshot_dir=str(tmp_path)))
    eng.serve(_requests(cfg, lens=(5, 3), max_news=(4, 4)))
    eng.snapshot()
    eng2 = ServeEngine(model, params, ServeConfig(
        max_batch=2, max_seq=MAX_SEQ, greedy=False,
        snapshot_dir=str(tmp_path)))
    with pytest.raises(ValueError, match="sampling mode"):
        eng2.restore_snapshot()


def test_restore_rejects_request_too_large_for_max_seq(served, tmp_path):
    cfg, model, params = served("glm4-9b")

    def factory(i):
        return ServeEngine(model, params, ServeConfig(
            max_batch=2, max_seq=MAX_SEQ, snapshot_dir=str(tmp_path),
            snapshot_every=2, kill_at_step=4 if i == 0 else None))

    eng = factory(0)
    with pytest.raises(WorkerKilled):
        eng.serve(_requests(cfg, lens=(30, 20), max_news=(20, 20)))
    small = ServeEngine(model, params, ServeConfig(
        max_batch=2, max_seq=32, snapshot_dir=str(tmp_path)))
    with pytest.raises(ValueError, match="max_seq"):
        small.restore_snapshot()


def test_snapshot_is_atomic_and_versioned(served, tmp_path):
    cfg, model, params = served("glm4-9b")
    eng = ServeEngine(model, params, ServeConfig(
        max_batch=2, max_seq=MAX_SEQ, snapshot_dir=str(tmp_path)))
    done = eng.serve(_requests(cfg, lens=(5, 3), max_news=(4, 4)))
    step = eng.snapshot()
    meta = eng._ckpt.metadata(step)
    assert meta["snapshot_version"] == 1
    assert meta["fingerprint"] == cfg.fingerprint()
    # finished outputs ride along and restore as completed
    eng2 = ServeEngine(model, params, ServeConfig(
        max_batch=2, max_seq=MAX_SEQ, snapshot_dir=str(tmp_path)))
    survivors, completed = eng2.restore_snapshot()
    assert survivors == []
    got = {r.rid: list(np.asarray(r.output)) for r in completed}
    want = {r.rid: list(np.asarray(r.output)) for r in done}
    assert got == want


# ---------------------------------------------------------------------------
# Backpressure: bounded queue, shed policies, deadlines
# ---------------------------------------------------------------------------

def _burst(cfg, budgets):
    rng = np.random.default_rng(0)
    return [Request(rid=i,
                    prompt=rng.integers(0, cfg.vocab_size, 5)
                    .astype(np.int32), max_new_tokens=m)
            for i, m in enumerate(budgets)]


@pytest.mark.parametrize("policy", ["reject-new", "shed-oldest",
                                    "shed-lowest-budget"])
def test_shed_policies_terminal_status(served, policy):
    cfg, model, params = served("glm4-9b")
    eng = ServeEngine(model, params, ServeConfig(
        max_batch=1, max_seq=MAX_SEQ, max_queue=2,
        admission_policy=policy))
    budgets = [8, 8, 8, 2, 8, 8]
    done = eng.serve(_burst(cfg, budgets))
    assert len(done) == len(budgets)          # nobody vanishes
    shed = [r for r in done if r.status == "shed"]
    ok = [r for r in done if r.status == "done"]
    assert shed and ok
    assert eng.metrics["shed_count"] == len(shed)
    assert all(len(np.asarray(r.output)) == 0 for r in shed)
    assert all(len(np.asarray(r.output)) == r.max_new_tokens for r in ok)
    assert eng.metrics["peak_queue_depth"] <= 2
    if policy == "shed-lowest-budget":
        assert any(r.max_new_tokens == 2 for r in shed)
    # served outputs match an unbounded engine's for the same rids
    ref_eng = ServeEngine(model, params,
                          ServeConfig(max_batch=1, max_seq=MAX_SEQ))
    ref = _outputs(ref_eng.serve(_burst(cfg, budgets)))
    for r in ok:
        assert list(np.asarray(r.output)) == ref[r.rid][1]


def test_shed_policies_paged_no_leaks(served):
    cfg, model, params = served("glm4-9b",
                                CacheSpec(paged=True, page_size=PAGE))
    eng = ServeEngine(model, params, ServeConfig(
        max_batch=1, max_seq=MAX_SEQ, max_queue=1,
        admission_policy="shed-oldest", num_blocks=16))
    done = eng.serve(_burst(cfg, [6] * 5))
    assert len(done) == 5
    _assert_drained(eng)


def test_deadline_waiting_and_live(served):
    cfg, model, params = served("glm4-9b")
    eng = ServeEngine(model, params,
                      ServeConfig(max_batch=1, max_seq=MAX_SEQ))
    reqs = _burst(cfg, [6, 6, 40])
    reqs[1].deadline_s = 0.0          # expires while waiting
    done = eng.serve(reqs)
    by = {r.rid: r for r in done}
    assert by[1].status == "timeout" and len(np.asarray(by[1].output)) == 0
    assert by[0].status == "done" and by[2].status == "done"
    assert eng.metrics["timeout_count"] == 1


def test_deadline_live_graceful_retire(served):
    """A deadline expiring while the request *holds a slot* retires it
    gracefully: status "timeout", and the partial output is a bit-exact
    prefix of what an undisturbed run would have produced."""
    cfg, model, params = served("glm4-9b")
    reqs = _burst(cfg, [59])
    ref_eng = ServeEngine(model, params,
                          ServeConfig(max_batch=1, max_seq=MAX_SEQ))
    ref = list(np.asarray(ref_eng.serve(_burst(cfg, [59]))[0].output))

    eng = ServeEngine(model, params,
                      ServeConfig(max_batch=1, max_seq=MAX_SEQ))
    # long enough to survive the pre-admission sweep (~ms), short enough
    # to expire during decode (first decode step compiles, >> 0.25 s)
    reqs[0].deadline_s = 0.25
    r = eng.serve(reqs)[0]
    assert r.status == "timeout"
    out = list(np.asarray(r.output))
    assert len(out) < 59
    assert out == ref[:len(out)]
    assert eng.metrics["timeout_count"] == 1


def test_deadline_survives_snapshot(served, tmp_path):
    """deadline_s rides the snapshot: a restored request still carries
    its budget (the clock restarts at re-submission)."""
    cfg, model, params = served("glm4-9b")

    def factory(i):
        return ServeEngine(model, params, ServeConfig(
            max_batch=2, max_seq=MAX_SEQ, snapshot_dir=str(tmp_path),
            snapshot_every=2, kill_at_step=3 if i == 0 else None))

    reqs = _requests(cfg)
    for r in reqs:
        r.deadline_s = 60.0
    sup = ServeSupervisor(factory)
    done = sup.run(reqs)
    assert all(r.status == "done" for r in done)
    resumed = set(sup.history[0].resumed_rids)
    assert resumed
    assert all(r.deadline_s == 60.0 for r in done if r.rid in resumed)


def test_duplicate_rid_rejected(served):
    cfg, model, params = served("glm4-9b")
    eng = ServeEngine(model, params,
                      ServeConfig(max_batch=2, max_seq=MAX_SEQ))
    reqs = _burst(cfg, [4, 4])
    reqs[1].rid = reqs[0].rid
    with pytest.raises(ValueError, match="duplicate request id"):
        eng.serve(reqs)
