"""Sharded serving: slot state over a device mesh + prefill/decode split.

:class:`MeshServeEngine` is :class:`~repro.runtime.serve_loop.ServeEngine`
with two orthogonal upgrades, both reached through the seams the base
loop exposes (``_init_state`` / ``_free_slots`` / ``_poll_admissions`` /
the ``_prefill_args``/``_finish_admit`` admission split):

**Slot state sharded over a mesh data axis.**  Every slot leaf — dense
K/V, the paged block pool, int8 scale leaves, recurrent (rwkv/mamba)
state, per-slot ``pos`` — is placed with a ``NamedSharding`` resolved by
the logical-axis rule engine (:mod:`repro.parallel.sharding`:
``"slots"``/``"blocks"`` shard over ``data``, with the usual
divisibility fallback to replicate).  The engine's jitted programs are
*unchanged*: XLA's SPMD partitioner splits each bucketed prefill /
decode / insert program over the shards, so the one-trace-per-bucket
discipline holds exactly as on one device, and — because slot decode is
batch-parallel with no cross-slot reductions — per-request outputs are
**bit-identical** to the single-device engine across dense/ssm/hybrid ×
fp32/int8 × dense/paged (asserted by ``tests/test_mesh_serving.py`` and
the CI-gated ``mesh`` bench suite).

Admission routing is shard-aware: slot *i* lives on shard
``i // (max_batch / n_shards)``, free slots are offered to the scheduler
least-loaded-shard-first, and a retire refills its own shard before a
busier one grows — retire-and-refill stays shard-local, so slot traffic
never migrates state across the mesh.

**Prefill workers off the decode critical path.**  With
``ServeConfig(prefill_workers=N)``, dense admissions run their bucketed
prefill on a thread pool (the apex actor/learner topology: workers
produce, the decode loop consumes).  The scheduler reserves the target
slots, submits the prefill, and keeps decoding; finished prefills land
through ``_finish_admit`` on the scheduler thread, which owns the slot
state (the insert scatter is the same ``slot_update`` seam, so outputs
are unaffected — only the *stall* moves off the decode path).  Paged
admissions extend the shared pool state in place and therefore stay
inline; snapshot() drains in-flight prefills first so a checkpoint never
loses an admitted-but-unlanded request.

On CPU the whole subsystem is exercisable with fake devices::

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \\
        python -m benchmarks.run mesh

which is how the ``mesh-smoke`` CI lane runs it.
"""
from __future__ import annotations

import collections
from concurrent.futures import Future, ThreadPoolExecutor
from typing import List, Optional

import numpy as np

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from repro.parallel import collectives
from repro.parallel import sharding as shard
from repro.runtime.serve_loop import Request, ServeConfig, ServeEngine


def route_free_slots(live: List[bool], reserved, n_shards: int
                     ) -> List[int]:
    """Free slot indices, least-loaded shard first (ties: lowest shard,
    then lowest slot).

    Pure routing policy, unit-testable without a mesh: ``live[i]`` marks
    slot *i* occupied, ``reserved`` holds slots pledged to in-flight
    prefills (counted as load, excluded from the result), and slots are
    striped over shards contiguously — shard *s* owns
    ``[s*B/n, (s+1)*B/n)``.  Within one shard, slots stay in index order,
    so a retire-and-refill lands back in the shard that freed it unless a
    strictly less-loaded shard exists.
    """
    b = len(live)
    if b % n_shards != 0:
        raise ValueError(f"{b} slots cannot stripe over {n_shards} shards")
    per = b // n_shards
    load = [0] * n_shards
    for i in range(b):
        if live[i] or i in reserved:
            load[i // per] += 1
    free = [i for i in range(b) if not live[i] and i not in reserved]
    free.sort(key=lambda i: (load[i // per], i))
    return free


class MeshServeEngine(ServeEngine):
    """Slot-sharded, prefill-disaggregated serve engine (module doc)."""

    def __init__(self, model, params, config: Optional[ServeConfig] = None,
                 mesh: Optional[Mesh] = None, **legacy_kwargs):
        if config is None and legacy_kwargs:
            config = ServeConfig(**legacy_kwargs)
            legacy_kwargs = {}
        config = config or ServeConfig()
        if mesh is None:
            devices = jax.devices()
            n = config.num_shards or len(devices)
            if n > len(devices):
                raise ValueError(
                    f"num_shards={n} but only {len(devices)} devices are "
                    f"visible (CI fakes more with "
                    f"XLA_FLAGS=--xla_force_host_platform_device_count=N)")
            mesh = Mesh(np.array(devices[:n]), ("data",))
        if "data" not in mesh.shape:
            raise ValueError("the serving mesh needs a 'data' axis "
                             f"(got axes {tuple(mesh.shape)})")
        n_shards = mesh.shape["data"]
        if config.max_batch % n_shards != 0:
            raise ValueError(
                f"max_batch {config.max_batch} must divide evenly over "
                f"{n_shards} mesh shards")
        super().__init__(model, params, config, **legacy_kwargs)
        self.mesh = mesh
        self.n_shards = n_shards
        self._shard_sz = self.max_batch // n_shards
        # replicate params: every shard decodes its own slot rows against
        # a full copy (data parallelism over slots, not tensor parallelism
        # — the "model" axis profiles in parallel/sharding.py are the
        # training-side story)
        self.params = jax.device_put(
            self.params, NamedSharding(mesh, PartitionSpec()))
        # -- prefill workers -------------------------------------------------
        workers = config.prefill_workers
        if workers and self.paged:
            # paged admission mutates the shared pool state in place
            # (slot_reset + extend + commit against self._state); running
            # it concurrently with decode would race the state handoff,
            # so the pool serves inline and the knob is a documented no-op
            workers = 0
        self._pool = (ThreadPoolExecutor(max_workers=workers,
                                         thread_name_prefix="prefill")
                      if workers else None)
        # (group, free, slots, future) per in-flight async prefill
        self._inflight: collections.deque = collections.deque()
        self._reserved: set = set()
        self.metrics["async_prefills"] = 0

    # -- sharded state ------------------------------------------------------

    def _init_state(self):
        abs_st = self.ops.init_slot_state(self.max_batch, self.max_seq,
                                          abstract=True)
        shardings = shard.slot_state_shardings(abs_st, self.mesh)
        return self.ops.init_slot_state(self.max_batch, self.max_seq,
                                        shardings=shardings)

    def shard_of(self, slot: int) -> int:
        """Which mesh shard owns slot index ``slot``."""
        return slot // self._shard_sz

    def shard_loads(self) -> List[int]:
        """Occupied (or prefill-reserved) slots per shard, host view."""
        load = [0] * self.n_shards
        for i, s in enumerate(self._slots):
            if s is not None or i in self._reserved:
                load[self.shard_of(i)] += 1
        return load

    def shard_live_tokens(self) -> List[float]:
        """Committed tokens per shard, summed on-device.

        The cross-shard balance telemetry: masks the sharded ``pos``
        vector by host liveness (retired slots keep stale ``pos``) and
        reduces with one tiny all-gather
        (:func:`repro.parallel.collectives.per_shard_sums`) instead of
        pulling slot state to the host.
        """
        if self._state is None or self._state.pos is None:
            return [0.0] * self.n_shards
        live = np.array([1.0 if s is not None else 0.0
                         for s in self._slots], np.float32)
        sums = collectives.per_shard_sums(self._state.pos, self.mesh,
                                          weights=live)
        return [float(v) for v in np.asarray(sums)]

    # -- shard-aware admission routing --------------------------------------

    def _free_slots(self) -> List[int]:
        return route_free_slots([s is not None for s in self._slots],
                                self._reserved, self.n_shards)

    # -- async prefill (the prefill/decode split) ----------------------------

    def _admit(self, group: List[Request], free: List[int],
               done: List[Request]) -> None:
        if self._pool is None:
            super()._admit(group, free, done)
            return
        for r, slot_i in zip(group, free):
            inputs, lengths, slots = self._prefill_args(r, slot_i)
            self._reserved.add(slot_i)
            self.events.append(("prefill", r.rid, slot_i,
                                int(self.metrics["decode_steps"])))
            fut: Future = self._pool.submit(self._prefill, self.params,
                                            inputs, lengths)
            self._inflight.append(([r], [slot_i], slots, fut))
        self.metrics["async_prefills"] += len(group)

    def _poll_admissions(self, done: List[Request]) -> None:
        n = len(self._inflight)
        for _ in range(n):
            group, free, slots, fut = self._inflight.popleft()
            if not fut.done():
                self._inflight.append((group, free, slots, fut))
                continue
            self._reserved.difference_update(free[:len(group)])
            logits, sub = fut.result()   # re-raises worker exceptions
            self._finish_admit(group, free, logits, sub, slots, done)

    def _admissions_inflight(self) -> bool:
        return bool(self._inflight)

    def _drain_admissions(self, done: List[Request]) -> None:
        """Block until every in-flight prefill has landed in a slot."""
        while self._inflight:
            self._inflight[0][3].result()   # wait, don't spin
            self._poll_admissions(done)

    def snapshot(self) -> int:
        # an admitted-but-unlanded request is in no queue and no slot; a
        # snapshot taken in that window would silently drop it, so land
        # in-flight prefills first (prefill is pure compute — draining
        # costs one admission latency, never corrupts state)
        self._drain_admissions(self._done_live)
        return super().snapshot()
