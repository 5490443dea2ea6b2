"""Sharded serving mesh: bit-identity, routing, prefill/decode split.

Two layers, matching how the mesh is exercisable on CPU:

  * **in-process** — the routing policy (a pure function), config
    validation, and the prefill-worker overlap contract, all on a
    1-device mesh (``MeshServeEngine(num_shards=1)`` is a legal
    degenerate mesh, so these run inside plain tier-1 too);
  * **subprocess with 8 fake devices** (``run_py`` from
    ``test_distributed.py``, ``--xla_force_host_platform_device_count``)
    — the sharded-vs-single-device bit-equality matrix across
    dense/ssm/hybrid × fp32/int8 × dense/paged, shard-aware admission
    routing under imbalance, the cross-shard token collective, and a
    snapshot taken on the mesh restoring into a *single-device* engine
    (the PR 8 chaos seam, across the mesh boundary).
"""
from __future__ import annotations

import textwrap
import threading
import time

import numpy as np
import pytest

import jax

from repro.configs import get_arch
from repro.models.model_zoo import build_model
from repro.runtime.mesh_serve import MeshServeEngine, route_free_slots
from repro.runtime.serve_loop import Request, ServeConfig, ServeEngine

from test_distributed import run_py


# ---------------------------------------------------------------------------
# Routing policy (pure function — no mesh, no engine)
# ---------------------------------------------------------------------------

class TestRouting:
    def test_empty_engine_is_index_order(self):
        assert route_free_slots([False] * 8, set(), 4) == list(range(8))

    def test_least_loaded_shard_first(self):
        # shard loads (2 slots each): s0=1, s1=0, s2=2, s3=0
        live = [True, False, False, False, True, True, False, False]
        free = route_free_slots(live, set(), 4)
        assert free == [2, 3, 6, 7, 1]

    def test_reserved_counts_as_load_and_is_excluded(self):
        live = [False] * 8
        free = route_free_slots(live, {0, 1}, 4)    # shard 0 fully pledged
        assert 0 not in free and 1 not in free
        assert free == [2, 3, 4, 5, 6, 7]

    def test_refill_stays_shard_local(self):
        # all shards equally loaded (1/2 each): a slot freed in shard 2
        # refills shard 0 first only if strictly less loaded — here loads
        # are equal, so index order keeps the freed slot in its shard
        # rotation rather than migrating ahead of it
        live = [True, False, True, False, False, True, True, False]
        free = route_free_slots(live, set(), 4)
        # every shard has load 1; ties break by slot index
        assert free == [1, 3, 4, 7]

    def test_indivisible_raises(self):
        with pytest.raises(ValueError):
            route_free_slots([False] * 6, set(), 4)


# ---------------------------------------------------------------------------
# Config / construction validation
# ---------------------------------------------------------------------------

class TestValidation:
    def test_max_batch_must_divide_shards(self):
        with pytest.raises(ValueError):
            ServeConfig(max_batch=6, num_shards=4)

    def test_num_shards_positive(self):
        with pytest.raises(ValueError):
            ServeConfig(num_shards=0)

    def test_prefill_workers_nonnegative(self):
        with pytest.raises(ValueError):
            ServeConfig(prefill_workers=-1)

    def test_more_shards_than_devices_raises(self):
        cfg = get_arch("glm4-9b").reduced()
        model = build_model(cfg)
        params = model.init(jax.random.PRNGKey(0))
        n = len(jax.devices())
        with pytest.raises(ValueError, match="devices"):
            MeshServeEngine(model, params, ServeConfig(
                max_batch=8 * n, num_shards=8 * n))


# ---------------------------------------------------------------------------
# Prefill/decode split (1-device mesh: runs inside tier-1)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def glm():
    cfg = get_arch("glm4-9b").reduced()
    model = build_model(cfg)
    return cfg, model, model.init(jax.random.PRNGKey(0))


def _mk_requests(cfg, lens, max_news, arrivals=None, seed=0):
    rng = np.random.default_rng(seed)
    arrivals = arrivals or [0.0] * len(lens)
    return [Request(rid=i, prompt=rng.integers(0, cfg.vocab_size, n)
                    .astype(np.int32), max_new_tokens=m, arrival_s=t)
            for i, (n, m, t) in enumerate(zip(lens, max_news, arrivals))]


def _outputs(done):
    return {r.rid: list(np.asarray(r.output)) for r in done}


class TestPrefillSplit:
    def test_single_shard_split_bit_identical(self, glm):
        """prefill_workers changes *when* prefill runs, never *what* it
        computes: async outputs match the inline single-device engine."""
        cfg, model, params = glm
        reqs = lambda: _mk_requests(cfg, (5, 21, 9, 13, 3, 17),
                                    (8, 4, 6, 10, 5, 7))
        ref = _outputs(ServeEngine(model, params, ServeConfig(
            max_batch=4, max_seq=64)).serve(reqs()))
        eng = MeshServeEngine(model, params, ServeConfig(
            max_batch=4, max_seq=64, num_shards=1, prefill_workers=2))
        got = _outputs(eng.serve(reqs()))
        assert got == ref
        assert eng.metrics["async_prefills"] == 6

    def test_workers_prefill_one_row_per_request(self, glm):
        """Each admitted request gets its own one-row prefill future, so
        the workers reach the same (1, bucket) shapes as the inline
        path, whatever the group size."""
        cfg, model, params = glm
        eng = MeshServeEngine(model, params, ServeConfig(
            max_batch=4, max_seq=64, num_shards=1, prefill_workers=2))
        shapes = []
        inner = eng._prefill

        def recorded(p, inputs, lengths):
            shapes.append(inputs["tokens"].shape)
            return inner(p, inputs, lengths)
        eng._prefill = recorded
        reqs = _mk_requests(cfg, (5, 21, 9, 13), (3, 4, 2, 5))
        eng.serve(reqs)
        assert sorted(shapes) == sorted((1, eng._bucket(len(r.prompt)))
                                        for r in reqs)
        assert eng.metrics["async_prefills"] == 4

    def test_decode_does_not_block_on_long_prompt(self, glm):
        """The split's whole point: with a slow prefill in flight, decode
        steps keep landing between the prefill submit and its admit."""
        cfg, model, params = glm
        eng = MeshServeEngine(model, params, ServeConfig(
            max_batch=2, max_seq=64, num_shards=1, prefill_workers=1))
        # make every prefill visibly slow *without* touching its result
        inner = eng._prefill
        def slow_prefill(p, inputs, lengths):
            out = jax.block_until_ready(inner(p, inputs, lengths))
            time.sleep(0.05)
            return out
        eng._prefill = slow_prefill
        # rid 0 decodes from t=0; rid 1's prompt arrives mid-decode
        reqs = _mk_requests(cfg, (5, 30), (40, 4), arrivals=(0.0, 0.02))
        done = eng.serve(reqs)
        ev = {(kind, rid): step for kind, rid, _, step in eng.events}
        submitted = ev[("prefill", 1)]
        admitted = ev[("admit", 1)]
        # decode advanced while the worker held rid 1's prefill
        assert admitted > submitted, (submitted, admitted)
        assert {r.rid for r in done} == {0, 1}
        assert all(len(r.output) == r.max_new_tokens for r in done)

    def test_drain_before_snapshot(self, glm, tmp_path):
        """snapshot() lands in-flight prefills first — no request can
        vanish into the admitted-but-unlanded window."""
        cfg, model, params = glm
        eng = MeshServeEngine(model, params, ServeConfig(
            max_batch=2, max_seq=64, num_shards=1, prefill_workers=1,
            snapshot_dir=str(tmp_path)))
        inner = eng._prefill
        def slow_prefill(p, inputs, lengths):
            time.sleep(0.03)
            return inner(p, inputs, lengths)
        eng._prefill = slow_prefill

        barrier = threading.Event()
        orig_poll = eng._poll_admissions
        def poll_then_snap(done):
            orig_poll(done)
            if eng._admissions_inflight() and not barrier.is_set():
                barrier.set()
                eng.snapshot()          # taken while a prefill is in flight
                assert not eng._admissions_inflight()
        eng._poll_admissions = poll_then_snap

        done = eng.serve(_mk_requests(cfg, (5, 9), (6, 4)))
        assert barrier.is_set(), "no in-flight window was ever observed"
        assert {r.rid for r in done} == {0, 1}
        assert all(len(r.output) == r.max_new_tokens for r in done)

    def test_paged_mode_serves_inline(self, glm):
        from repro.configs.base import CacheSpec
        cfg, model, params = glm
        eng = MeshServeEngine(model, params, ServeConfig(
            max_batch=2, max_seq=64, num_shards=1, prefill_workers=2,
            cache=CacheSpec(paged=True, page_size=8)))
        done = eng.serve(_mk_requests(cfg, (5, 9), (4, 4)))
        assert eng.metrics["async_prefills"] == 0     # documented no-op
        assert all(len(r.output) == r.max_new_tokens for r in done)


# ---------------------------------------------------------------------------
# 8 fake devices (subprocess)
# ---------------------------------------------------------------------------

_MESH_PRELUDE = textwrap.dedent("""
    import numpy as np, jax
    from repro.configs import get_arch
    from repro.configs.base import CacheSpec
    from repro.models.model_zoo import build_model
    from repro.runtime.serve_loop import Request, ServeConfig, ServeEngine
    from repro.runtime.mesh_serve import MeshServeEngine

    def requests(cfg, lens, max_news, seed=0):
        rng = np.random.default_rng(seed)
        return [Request(rid=i, prompt=rng.integers(0, cfg.vocab_size, n)
                        .astype(np.int32), max_new_tokens=m)
                for i, (n, m) in enumerate(zip(lens, max_news))]

    def outputs(done):
        return {r.rid: list(map(int, np.asarray(r.output))) for r in done}
""")

LENS = (5, 21, 9, 13, 3, 17, 7, 11, 4, 26)
NEWS = (8, 4, 6, 10, 5, 7, 3, 6, 9, 4)


class TestShardedEightDevices:
    def test_bit_equality_matrix(self):
        """Sharded (4 shards, async prefill) vs single-device outputs
        across dense/ssm/hybrid × fp32/int8 × dense/paged; one decode
        trace per engine (bucket discipline survives SPMD)."""
        out = run_py(_MESH_PRELUDE + textwrap.dedent(f"""
            MATRIX = [
                ("glm4-9b", None),
                ("rwkv6-3b", None),
                ("hymba-1.5b", None),
                ("glm4-9b", CacheSpec(dtype="int8")),
                ("glm4-9b", CacheSpec(paged=True, page_size=8)),
                ("glm4-9b", CacheSpec(dtype="int8", paged=True,
                                      page_size=8)),
            ]
            for arch, cache in MATRIX:
                cfg = get_arch(arch).reduced()
                model = build_model(cfg)
                params = model.init(jax.random.PRNGKey(0))
                ref = outputs(ServeEngine(model, params, ServeConfig(
                    max_batch=8, max_seq=64, cache=cache))
                    .serve(requests(cfg, {LENS}, {NEWS})))
                eng = MeshServeEngine(model, params, ServeConfig(
                    max_batch=8, max_seq=64, cache=cache, num_shards=4,
                    prefill_workers=2))
                got = outputs(eng.serve(requests(cfg, {LENS}, {NEWS})))
                assert got == ref, (arch, str(cache))
                assert eng.trace_counts["decode"] == 1, arch
                # the state really is distributed: some populated leaf
                # carries the mesh's data axis in its sharding
                sharded = [n for n in eng._state._fields
                           if getattr(eng._state, n) is not None
                           and "data" in str(getattr(
                               eng._state, n).sharding)]
                assert sharded, arch
                print(arch, str(cache), "ok")
            print("MATRIX_OK")
        """), timeout=560)
        assert "MATRIX_OK" in out

    def test_routing_imbalance_and_shard_telemetry(self):
        """Admissions spread over every shard; under an induced imbalance
        the next admission lands on the least-loaded shard; the
        cross-shard token collective agrees with host accounting."""
        out = run_py(_MESH_PRELUDE + textwrap.dedent("""
            cfg = get_arch("glm4-9b").reduced()
            model = build_model(cfg)
            params = model.init(jax.random.PRNGKey(0))
            eng = MeshServeEngine(model, params, ServeConfig(
                max_batch=8, max_seq=64, num_shards=4))
            # 8 simultaneous admissions fill all shards evenly
            done = eng.serve(requests(cfg, (5,) * 8, (4,) * 8))
            admits = [slot for kind, rid, slot, step in eng.events
                      if kind == "admit"]
            shards = {eng.shard_of(s) for s in admits}
            assert shards == {0, 1, 2, 3}, shards

            # induced imbalance: occupy shards 0+1 by hand, then admit
            live = [0, 1, 2, 3]
            from repro.runtime.serve_loop import _Slot, Request as Rq
            for i in live:
                eng._slots[i] = _Slot(req=Rq(100 + i, np.zeros(1, np.int32)),
                                      next_token=1, produced=0, tokens=[],
                                      rng=None, pos=3)
            free = eng._free_slots()
            assert eng.shard_of(free[0]) in (2, 3), free

            # collective telemetry == a host gather of the same rows
            # (device pos is authoritative; retired rows mask out)
            pos_host = np.asarray(eng._state.pos).astype(np.float64)
            exp = [float(pos_host[0:2].sum()), float(pos_host[2:4].sum()),
                   0.0, 0.0]
            per = eng.shard_live_tokens()
            assert per == exp, (per, exp)
            print("ROUTING_OK")
        """), timeout=420)
        assert "ROUTING_OK" in out

    def test_mesh_snapshot_restores_into_single_device_engine(self):
        """The PR 8 chaos seam across the mesh boundary: a snapshot taken
        on the sharded engine (mid-trace, injected kill) restores into a
        plain single-device engine and finishes bit-identically."""
        out = run_py(_MESH_PRELUDE + textwrap.dedent("""
            import tempfile
            from repro.parallel.fault_tolerance import WorkerKilled
            from repro.runtime.supervisor import ServeSupervisor

            cfg = get_arch("glm4-9b").reduced()
            model = build_model(cfg)
            params = model.init(jax.random.PRNGKey(0))
            lens, news = (5, 9, 13, 3, 7, 11), (10, 6, 12, 8, 5, 9)
            ref = outputs(ServeEngine(model, params, ServeConfig(
                max_batch=4, max_seq=64)).serve(requests(cfg, lens, news)))

            snap = tempfile.mkdtemp(prefix="mesh-snap-")
            def factory(i):
                if i == 0:
                    return MeshServeEngine(model, params, ServeConfig(
                        max_batch=8, max_seq=64, num_shards=4,
                        prefill_workers=2, snapshot_dir=snap,
                        snapshot_every=2, kill_at_step=4))
                return ServeEngine(model, params, ServeConfig(
                    max_batch=4, max_seq=64, snapshot_dir=snap))

            sup = ServeSupervisor(factory, max_restarts=2)
            got = outputs(sup.run(requests(cfg, lens, news)))
            assert len(sup.history) == 1
            assert got == ref
            print("CROSS_RESTORE_OK")
        """), timeout=420)
        assert "CROSS_RESTORE_OK" in out
