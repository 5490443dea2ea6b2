"""Serving runtime: slot-based continuous batching over bucketed shapes.

The paper's SYCore keeps one reconfigurable engine resident and streams
heterogeneous workloads through it; the serving analogue is **continuous
batching**: ``max_batch`` persistent decode slots, an admission queue with
arrival times, retire-and-refill on *every* decode step (a finished short
request frees its slot immediately — it never rides dead-weight until the
slowest request in a gang finishes), and a scheduler that prefills newly
admitted requests into free slots while occupied slots keep decoding.

Shapes are **bucketed** so the jit'd callables — and the tuned-block table
keyed on kernel call shapes — are reused across admissions instead of
retracing per batch composition:

  * prefill:  (B = 1, S = next-pow2 prompt bucket) for each admitted
    request, its prompt right-padded and its true length passed to
    ``model.prefill(lengths=...)``; so one trace per bucket serves every
    admission group size, inline or on the mesh engine's prefill workers
  * decode:   (B = max_batch, 1) every step, against the fixed-shape slot
    state from ``model.init_slot_state`` (per-slot ``pos``)
  * insert:   ``model.slot_update`` scatters a one-row prefill's state
    (attention KV *and* rwkv/mamba recurrent state) into its slot, at the
    prefill's (1, bucket) shape; a snapshot restore reuses it row by row

Per-request outputs are bit-identical to single-stream decoding: the
model-level seam masks pad steps out of recurrent state updates and each
slot decodes against its own positions (see ``tests/test_serving.py``).

**Speculative decoding** (``spec_k > 0``): a pluggable drafter
(``runtime/drafter.py``; n-gram prompt lookup by default,
``drafter="draft_model"`` for the tiered tiny-LM drafter) proposes up to
``k`` tokens per slot and one bucketed ``verify_step`` call scores all
``k+1`` positions in a single pass — per-query verify numerics are the
exact single-token decode ops, so greedy outputs stay bit-identical to
plain decode while accepted prefixes advance a slot by up to ``k+1``
tokens per engine step (greedy engines fuse verify + longest-prefix
accept + commit into one program).  Batched drafters
(``Drafter.batched``) get one ``draft_all`` call covering every drafting
slot per step instead of per-slot sessions.  Ring caches (long-context
sliding-window presets) verify too: candidate columns wrap on write and
rejected wrapped writes restore on commit, so the only constraint is
that the ``k+1`` verify window fits the ring.  With ``spec_adaptive``,
each slot tracks a trailing-acceptance EWMA and walks its own draft
budget between 0 (plain decode, which is already the engine's free
fallback) and ``spec_k_max`` — undraftable traffic stops paying verify
width, draftable traffic keeps the full window.  Temperature slots use
the rejection-sampling fallback (see ``_accept_sampled``).  Acceptance
bookkeeping lands in the typed :class:`ServeMetrics`
(``spec_acceptance`` / ``tokens_per_step`` / ``spec_k_hist``).

``GangServeEngine`` preserves the previous lockstep scheduler as the
benchmark baseline (``benchmarks/serve_bench.py`` replays the same trace
through both and reports the throughput/latency gap).
"""
from __future__ import annotations

import collections
import dataclasses
import time
import warnings
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.checkpoint.manager import CheckpointManager
from repro.configs.base import CacheSpec
from repro.kernels import common as kernel_common
from repro.models.model_zoo import Model
from repro.models.transformer import (kv_pos_axis, slot_kv_history,
                                      slot_kv_leaf)
from repro.parallel.fault_tolerance import WorkerKilled
from repro.runtime.block_pool import BlockAllocator, RadixCache
from repro.runtime.drafter import (Drafter, DraftSession, NGramDrafter,
                                   make_drafter)

# Serving snapshot format version (bumped on any layout/meta change; a
# restore refuses snapshots it does not understand instead of guessing).
SNAPSHOT_VERSION = 1

ADMISSION_POLICIES = ("reject-new", "shed-oldest", "shed-lowest-budget")


@dataclasses.dataclass(frozen=True)
class ServeConfig:
    """Every knob of :class:`ServeEngine`, validated in one place.

    Replaces the kwarg sprawl of the original constructor (``max_batch``,
    ``max_seq``, ``greedy``, ... each positional-ish and undocumented);
    the old kwargs still work for one release through a deprecation shim.

    ``cache`` pins the slot-cache storage format (dtype, scale block,
    paged on/off — see :class:`repro.configs.base.CacheSpec`); the legacy
    ``cache_dtype`` string survives for compatibility but cannot be
    combined with ``cache``.  When the resolved spec is paged:

      * ``num_blocks`` sizes the shared block pool (default: full
        occupancy, ``max_batch * max_seq / page_size`` — size it *below*
        that to cap resident cache memory by live tokens instead of
        worst case);
      * ``prefix_cache`` keeps a radix trie over admitted prompts so an
        admission sharing a full-page prefix with earlier traffic
        references those blocks instead of recomputing them.

    Robustness knobs (all off by default — the engine's historical
    contract, "every request is served, over-budget raises", holds
    untouched unless a knob turns a policy on):

      * ``max_queue`` bounds the *arrived-but-unadmitted* queue;
        ``admission_policy`` picks the victim when it overflows —
        ``"reject-new"`` sheds the newcomer, ``"shed-oldest"`` sheds the
        longest-waiting entry, ``"shed-lowest-budget"`` sheds the
        smallest ``max_new_tokens`` (cheapest work to redo elsewhere).
        Shed requests come back with ``status="shed"`` and empty output.
      * ``snapshot_dir`` + ``snapshot_every`` persist an atomic, versioned
        slot snapshot every N decode steps (see :meth:`ServeEngine.snapshot`);
        a fresh engine restores it and resumed requests complete
        bit-identically.
      * ``kill_at_step`` injects a fault: the serve loop raises
        :class:`~repro.parallel.fault_tolerance.WorkerKilled` after that
        decode step, abandoning live state exactly like a preempted host
        (the chaos-harness hook; see ``runtime/supervisor.py``).

    Mesh knobs (consumed by
    :class:`repro.runtime.mesh_serve.MeshServeEngine`; the base engine
    validates but ignores them):

      * ``num_shards`` shards the slot batch axis over that many devices
        of the serving mesh (None = every visible device);
      * ``prefill_workers`` sizes the async prefill thread pool that
        keeps long prompts off the decode critical path (0 = prefill
        inline on the scheduler thread, the single-device behaviour).
    """

    max_batch: int = 8
    max_seq: int = 256
    greedy: bool = True
    min_bucket: int = 16
    # speculative decoding: spec_k > 0 turns it on; drafter is a Drafter
    # instance or a factory name ("ngram" | "draft_model", resolved by
    # the engine through runtime.drafter.make_drafter); spec_adaptive
    # walks each slot's draft budget between 0 and spec_k_max (defaults
    # to spec_k) by trailing acceptance
    spec_k: int = 0
    spec_k_max: Optional[int] = None
    spec_adaptive: bool = False
    drafter: Optional[Any] = None          # Drafter | "ngram" | "draft_model"
    cache_dtype: Optional[str] = None      # legacy string; prefer `cache`
    cache: Optional[CacheSpec] = None
    num_blocks: Optional[int] = None
    prefix_cache: bool = True
    # backpressure / fault tolerance
    max_queue: Optional[int] = None
    admission_policy: str = "reject-new"
    snapshot_dir: Optional[str] = None
    snapshot_every: int = 0
    kill_at_step: Optional[int] = None
    # serving mesh (MeshServeEngine)
    num_shards: Optional[int] = None
    prefill_workers: int = 0

    def __post_init__(self):
        if self.max_batch < 1:
            raise ValueError(f"max_batch must be >= 1, got {self.max_batch}")
        if self.max_seq < 1:
            raise ValueError(f"max_seq must be >= 1, got {self.max_seq}")
        if self.min_bucket < 1:
            raise ValueError(f"min_bucket must be >= 1, got "
                             f"{self.min_bucket}")
        if self.spec_k < 0:
            raise ValueError(f"spec_k must be >= 0, got {self.spec_k}")
        if self.spec_k_max is not None:
            if self.spec_k < 1:
                raise ValueError("spec_k_max needs spec_k > 0 (spec_k is "
                                 "the starting draft budget, spec_k_max "
                                 "the adaptive ceiling)")
            if self.spec_k_max < self.spec_k:
                raise ValueError(f"spec_k_max {self.spec_k_max} must be "
                                 f">= spec_k {self.spec_k}")
        if self.spec_adaptive and self.spec_k < 1:
            raise ValueError("spec_adaptive needs spec_k > 0")
        if (isinstance(self.drafter, str)
                and self.drafter not in ("ngram", "draft_model")):
            raise ValueError(f"unknown drafter name {self.drafter!r}; "
                             f"expected 'ngram' or 'draft_model' (or pass "
                             f"a Drafter instance)")
        if self.cache is not None and self.cache_dtype is not None:
            raise ValueError("cache (a CacheSpec) and the legacy "
                             "cache_dtype string are two spellings of the "
                             "same thing; pass exactly one")
        if self.num_blocks is not None and self.num_blocks < 1:
            raise ValueError(f"num_blocks must be >= 1, got "
                             f"{self.num_blocks}")
        if self.max_queue is not None and self.max_queue < 1:
            raise ValueError(f"max_queue must be >= 1, got "
                             f"{self.max_queue}")
        if self.admission_policy not in ADMISSION_POLICIES:
            raise ValueError(f"admission_policy must be one of "
                             f"{ADMISSION_POLICIES}, got "
                             f"{self.admission_policy!r}")
        if self.snapshot_every < 0:
            raise ValueError(f"snapshot_every must be >= 0, got "
                             f"{self.snapshot_every}")
        if self.snapshot_every and self.snapshot_dir is None:
            raise ValueError("snapshot_every > 0 needs a snapshot_dir")
        if self.kill_at_step is not None and self.kill_at_step < 1:
            raise ValueError(f"kill_at_step must be >= 1, got "
                             f"{self.kill_at_step}")
        if self.num_shards is not None and self.num_shards < 1:
            raise ValueError(f"num_shards must be >= 1, got "
                             f"{self.num_shards}")
        if (self.num_shards is not None
                and self.max_batch % self.num_shards != 0):
            raise ValueError(
                f"max_batch {self.max_batch} must divide evenly into "
                f"num_shards {self.num_shards} (every shard owns "
                f"max_batch / num_shards slots)")
        if self.prefill_workers < 0:
            raise ValueError(f"prefill_workers must be >= 0, got "
                             f"{self.prefill_workers}")

    # -- shared CLI plumbing -------------------------------------------------
    # launch/serve.py and examples/serve_batch.py used to carry identical
    # copies of these flags and their cross-checks; the one spelling lives
    # here now (add_args -> check_args -> from_args).

    @staticmethod
    def add_args(ap) -> None:
        """Install the engine's shared flags on an ArgumentParser."""
        ap.add_argument("--max-batch", type=int, default=4)
        ap.add_argument("--max-seq", type=int, default=256)
        ap.add_argument("--spec", type=int, default=0, metavar="K",
                        help="speculative decoding: draft K tokens per "
                             "slot per step (greedy outputs stay "
                             "bit-identical to plain decode)")
        ap.add_argument("--spec-k-max", type=int, default=None,
                        metavar="K", help="adaptive draft-budget ceiling "
                        "(defaults to --spec; implies a K+1-wide verify "
                        "window)")
        ap.add_argument("--spec-adaptive", action="store_true",
                        help="walk each slot's draft budget between 0 and "
                             "--spec-k-max by trailing acceptance")
        ap.add_argument("--drafter", choices=("ngram", "draft_model"),
                        default=None,
                        help="drafter tier: n-gram prompt lookup "
                             "(default) or the batched tiny-LM drafter "
                             "with n-gram fallback")
        ap.add_argument("--paged", action="store_true",
                        help="paged slot memory + radix prefix cache: K/V "
                             "lives in a shared block pool, shared-prefix "
                             "admissions reuse already-prefilled pages")
        ap.add_argument("--page-size", type=int, default=16,
                        help="tokens per cache page (--paged)")
        ap.add_argument("--snapshot-dir", default=None, metavar="DIR",
                        help="slot snapshot directory: enables periodic "
                             "snapshots and (with --kill-at-step) "
                             "preempt-and-resume")
        ap.add_argument("--snapshot-every", type=int, default=8,
                        metavar="STEPS",
                        help="snapshot cadence in decode steps "
                             "(--snapshot-dir)")
        ap.add_argument("--kill-at-step", type=int, default=None,
                        metavar="N",
                        help="chaos: kill the worker after decode step N "
                             "and let the supervisor restore + resume "
                             "(needs --snapshot-dir)")
        ap.add_argument("--mesh-shards", type=int, default=0, metavar="N",
                        help="shard the slot state over an N-way mesh "
                             "data axis (MeshServeEngine; outputs stay "
                             "bit-identical; fake devices on CPU with "
                             "XLA_FLAGS=--xla_force_host_platform_"
                             "device_count=N)")
        ap.add_argument("--prefill-workers", type=int, default=0,
                        metavar="N",
                        help="run dense prefills on N worker threads off "
                             "the decode critical path (needs "
                             "--mesh-shards; paged admissions stay "
                             "inline)")

    @staticmethod
    def check_args(ap, args, gang: bool = False) -> None:
        """The cross-flag ap.error checks both serving CLIs share.
        ``gang`` is the caller's --gang value (the lockstep baseline
        supports none of the engine features)."""
        if gang:
            for flag, name in ((args.spec, "--spec"),
                               (args.paged, "--paged"),
                               (args.snapshot_dir, "--snapshot-dir"),
                               (args.mesh_shards, "--mesh-shards")):
                if flag:
                    ap.error(f"{name} needs the continuous engine "
                             f"(drop --gang)")
        if args.kill_at_step is not None and not args.snapshot_dir:
            ap.error("--kill-at-step needs --snapshot-dir to recover from")
        if args.prefill_workers and not args.mesh_shards:
            ap.error("--prefill-workers needs --mesh-shards")
        if (args.drafter or args.spec_k_max or args.spec_adaptive) \
                and not args.spec:
            ap.error("--drafter/--spec-k-max/--spec-adaptive need --spec K")

    @classmethod
    def from_args(cls, args, incarnation: int = 0,
                  **overrides) -> "ServeConfig":
        """Build a ServeConfig from ``add_args``-parsed flags.

        ``incarnation`` guards the injected fault: only the first engine
        a supervisor spawns carries ``kill_at_step`` (the respawn must
        run the trace to completion).  ``overrides`` replace any derived
        kwarg (e.g. a caller-adjusted ``max_seq`` or custom ``cache``).
        """
        kw = dict(
            max_batch=args.max_batch, max_seq=args.max_seq,
            spec_k=args.spec, spec_k_max=args.spec_k_max,
            spec_adaptive=args.spec_adaptive, drafter=args.drafter,
            cache=(CacheSpec(paged=True, page_size=args.page_size)
                   if args.paged else None),
            num_shards=args.mesh_shards or None,
            prefill_workers=args.prefill_workers,
            snapshot_dir=args.snapshot_dir,
            snapshot_every=(args.snapshot_every if args.snapshot_dir
                            else 0),
            kill_at_step=(args.kill_at_step if incarnation == 0
                          else None))
        kw.update(overrides)
        return cls(**kw)


@dataclasses.dataclass
class ServeMetrics:
    """Typed engine metrics (one field per counter the dict used to hold).

    The engine historically exposed ``metrics`` as a plain dict, and the
    benches/gates index it with strings — so this dataclass keeps the
    mapping surface (``m["key"]``, ``"key" in m``, ``m.get``) over its
    typed fields, routes unknown keys to ``extras`` (the mesh engine's
    ``async_prefills`` lives there), and ``to_dict()`` flattens back to
    the exact dict shape the bench JSON writers have always serialized.
    """

    # token/step counters (accumulate over the engine lifetime);
    # prefill_positions counts the row-positions the prefill and extend
    # programs computed (rows x bucket a call: one row a request on the
    # prefill, max_batch on the paged extend), so 1 - prefill_tokens /
    # prefill_positions is the share of that work spent on padding
    prefill_tokens: int = 0
    prefill_positions: int = 0
    decode_tokens: int = 0
    decode_steps: int = 0
    # per-serve() averages/rates (recomputed at the end of each call);
    # queue_wait_s is the mean of admit_started_at - submitted_at over the
    # call's requests whose admission began
    queue_wait_s: float = 0.0
    slot_occupancy: float = 0.0
    wall_s: float = 0.0
    tok_s: float = 0.0
    # speculative decode: drafted vs accepted counters, derived rates,
    # tier dispatch counts, and the per-slot draft-budget histogram
    # (spec_k value -> slot-steps spent at that budget)
    spec_steps: int = 0
    draft_tokens: int = 0
    draft_accepted: int = 0
    spec_acceptance: float = 0.0
    tokens_per_step: float = 0.0
    model_drafts: int = 0
    fallback_drafts: int = 0
    spec_k_hist: Dict[int, int] = dataclasses.field(default_factory=dict)
    # paged mode: prompt tokens served from the radix prefix cache and
    # the block pool's high-water mark
    prefix_hit_tokens: int = 0
    peak_blocks: int = 0
    # mesh mode: decode steps taken while a prefill was in flight
    overlap_steps: int = 0
    # backpressure + fault tolerance
    queue_depth: int = 0
    peak_queue_depth: int = 0
    shed_count: int = 0
    timeout_count: int = 0
    snapshots: int = 0
    snapshot_s: float = 0.0
    restore_s: float = 0.0
    # escape hatch for engine subclasses (ServeMetrics is the base
    # engine's contract; a subclass counter is not a schema change)
    extras: Dict[str, float] = dataclasses.field(default_factory=dict)

    def _is_field(self, key: str) -> bool:
        return key in self.__dataclass_fields__ and key != "extras"

    def __getitem__(self, key: str):
        if self._is_field(key):
            return getattr(self, key)
        return self.extras[key]

    def __setitem__(self, key: str, value) -> None:
        if self._is_field(key):
            setattr(self, key, value)
        else:
            self.extras[key] = value

    def __contains__(self, key: str) -> bool:
        return self._is_field(key) or key in self.extras

    def get(self, key: str, default=None):
        return self[key] if key in self else default

    def to_dict(self) -> Dict[str, Any]:
        """The flat dict the bench JSON writers serialize (bit-compatible
        with the pre-dataclass metrics dict, plus the new fields)."""
        d = {k: getattr(self, k) for k in self.__dataclass_fields__
             if k not in ("extras", "spec_k_hist")}
        d["spec_k_hist"] = dict(self.spec_k_hist)
        d.update(self.extras)
        return d


@dataclasses.dataclass
class Request:
    rid: int
    prompt: np.ndarray            # (S,) int32 tokens (or (S,D) frames)
    max_new_tokens: int = 16
    arrival_s: float = 0.0        # arrival offset from serve() start
    # per-request sampling params (engine greedy=True overrides all)
    temperature: float = 0.0      # 0 => greedy
    top_k: int = 0                # 0 => full distribution
    seed: int = 0
    # wall-clock budget from submission; None = wait forever.  An expired
    # waiting request sheds; an expired *live* request retires gracefully
    # with whatever it produced (status "timeout", partial output).
    deadline_s: Optional[float] = None
    output: Optional[np.ndarray] = None
    # terminal disposition: "done" (full budget), "shed" (backpressure
    # victim, empty output), "timeout" (deadline expired)
    status: str = "pending"
    # host stamps (time.monotonic()); serve() clears the admission and
    # token stamps when it takes the request
    submitted_at: float = 0.0     # absolute arrival time
    # when the admission that placed the request began: serve() popped it
    # from the waiting queue (stamped again if a paged admission sends it
    # back to the queue and it is popped again)
    admit_started_at: Optional[float] = None
    admitted_at: float = 0.0      # the host holds the first token
    done_at: float = 0.0
    # when the host held each output token: token 0 after the admission's
    # pull, token k after the pull of the decode step that emitted it (a
    # speculative step stamps each token it accepted).  A request resumed
    # from a snapshot stamps only the tokens emitted after it resumed.
    token_times: List[float] = dataclasses.field(default_factory=list)
    # prompt tokens the radix prefix cache supplied (paged admissions)
    prefix_hit_tokens: int = 0


@dataclasses.dataclass
class _Slot:
    """Live decode-slot bookkeeping (host side)."""
    req: Request
    next_token: int               # last sampled token, fed next step
    produced: int                 # tokens emitted so far (incl. prefill's)
    tokens: List[int]
    rng: Optional[np.random.Generator]
    # per-request drafting state (spec mode only): seeded with prompt +
    # first token, extended with every committed token
    session: Optional[DraftSession] = None
    # host mirror of the device-side committed position (tokens in cache);
    # drives paged-mode page allocation ahead of each step's writes
    pos: int = 0
    # adaptive speculative decoding: trailing-acceptance EWMA, the slot's
    # current draft budget (0 = plain decode), and the probe countdown
    # that lets a k=0 slot periodically re-test draftability
    spec_ewma: float = 0.5
    spec_k: int = 0
    spec_probe: int = 0


@dataclasses.dataclass
class _Parked:
    """A snapshotted in-flight request awaiting re-admission.

    Produced by :meth:`ServeEngine.restore_snapshot`; consumed by
    ``_admit_restored`` when the serve loop reaches the request's rid.
    ``leaves`` hold the per-slot state in raw storage dtype (dense KV
    trimmed to ``pos`` tokens; recurrent + scale leaves as stored);
    ``pages`` hold the referenced pool blocks per leaf (paged mode),
    denormalized per request — restored slots never share pages, even
    where the dead engine's radix cache had them shared (identical bytes
    either way, so resumed decoding is unaffected).
    """
    tokens: List[int]
    next_token: int
    produced: int
    pos: int
    rng_state: Optional[dict]
    leaves: Dict[str, np.ndarray]
    pages: Dict[str, np.ndarray]


def next_pow2(n: int) -> int:
    b = 1
    while b < n:
        b *= 2
    return b


class ServeEngine:
    """Continuous-batching serve engine (slot scheduler, bucketed shapes)."""

    def __init__(self, model: Model, params,
                 config: Optional[ServeConfig] = None, **legacy_kwargs):
        if config is None:
            # deprecation shim: the pre-ServeConfig kwarg spelling
            # (``ServeEngine(m, p, max_batch=4, ...)``) still works for
            # one release; unknown names fail in ServeConfig as before
            config = ServeConfig(**legacy_kwargs)
            if legacy_kwargs:
                warnings.warn(
                    "ServeEngine(max_batch=..., ...) kwargs are "
                    "deprecated; pass ServeEngine(model, params, "
                    "ServeConfig(...))", DeprecationWarning, stacklevel=2)
        elif legacy_kwargs:
            raise TypeError("pass either a ServeConfig or legacy kwargs, "
                            "not both")
        self.config = config
        # cache format: `cache` (CacheSpec) is the one spelling going
        # forward (dtype + scale blocks + paging); cache_dtype="int8"
        # survives as the legacy string.  Scale leaves are ordinary pytree
        # leaves of the slot state, so bucketing/trace discipline is
        # untouched either way — same trace counts, ~4x smaller K/V +
        # wkv/ssm state in int8.
        if config.cache is not None:
            model = model.with_cache_spec(config.cache)
        elif config.cache_dtype is not None:
            model = model.with_cache_dtype(config.cache_dtype)
        self.model = model
        self.params = params
        max_batch = self.max_batch = config.max_batch
        max_seq = self.max_seq = config.max_seq
        self.greedy = config.greedy
        self.min_bucket = config.min_bucket
        spec_k = config.spec_k
        drafter = config.drafter
        # -- paged slot memory + radix prefix cache ------------------------
        # (cfg-less stand-in models — the warm-boot test's stub — serve
        # nothing and get the dense ops seam lazily, so guard the lookups)
        cfg = getattr(model, "cfg", None)
        spec = cfg.cache_spec() if cfg is not None else None
        self.paged = spec is not None and spec.paged
        if self.paged:
            if model.cfg.input_kind != "tokens":
                raise ValueError("paged serving admits through the extend "
                                 "(verify) pass, which needs token inputs")
            if max_seq % spec.page_size != 0:
                raise ValueError(f"max_seq {max_seq} must be a multiple of "
                                 f"page_size {spec.page_size}")
            self.page_size = spec.page_size
            self._n_pages = max_seq // spec.page_size
            num_blocks = (config.num_blocks
                          or max_batch * self._n_pages)
            self.ops = model.cache_ops(num_blocks=num_blocks,
                                       page_size=spec.page_size)
            pooled = model.cfg.family != "ssm"   # ssm: recurrent-only
            self.allocator = (BlockAllocator(num_blocks) if pooled
                              else None)
            self.radix = (RadixCache(self.allocator, spec.page_size)
                          if config.prefix_cache else None)
            # authoritative block tables live host-side; every jitted call
            # gets the current numpy copy (cheap C++ argument path) and
            # the device echo in the returned state is ignored
            self._tables = np.full((max_batch, self._n_pages),
                                   num_blocks, np.int32)
        else:
            self.ops = (model.cache_ops() if hasattr(model, "cache_ops")
                        else None)
            self.allocator = None
            self.radix = None
            self._tables = None
        # speculative decoding: a drafter proposes up to spec_k tokens per
        # slot and one bucketed verify call scores all spec_k+1 positions
        # in a single pass; greedy outputs stay bit-identical to plain
        # decode (per-query verify numerics are the exact decode ops).
        if spec_k and (model.cfg.input_kind != "tokens"
                       or model.cfg.n_codebooks):
            raise ValueError("speculative decoding needs a plain token "
                             "vocabulary (input_kind='tokens', no "
                             "codebook factorisation)")
        k_max = int(config.spec_k_max or spec_k)
        if spec_k and not self.paged:
            # derive the ring-cache predicate from the allocation itself
            # (abstract: no memory): a slot K/V cache shorter than max_seq
            # is a ring.  Ring verify wraps candidate writes and restores
            # rejected wrapped columns on commit (models/attention.py),
            # so the one hard constraint left is that the whole k+1
            # verify window fits the ring — wider would evict columns the
            # same verify still reads.  Paged caches are linear by
            # construction (their init refuses ring configs).
            abs_state = self.ops.init_slot_state(max_batch, max_seq,
                                                 abstract=True)
            ring_len = (abs_state.cache_k.shape[kv_pos_axis(abs_state)]
                        if abs_state.cache_k is not None else max_seq)
            if ring_len < max_seq and k_max + 1 > ring_len:
                raise ValueError(
                    f"speculative verify window k+1={k_max + 1} exceeds "
                    f"the sliding-window ring cache "
                    f"({ring_len} slots); lower "
                    f"spec_k/spec_k_max below the window")
        self.spec_k = int(spec_k)
        self.spec_k_max = k_max
        self.spec_adaptive = bool(config.spec_adaptive)
        if spec_k and isinstance(drafter, str):
            # factory names resolve here because the draft-model tier
            # needs the serving model to derive its tiny LM from
            drafter = make_drafter(drafter, target=model,
                                   max_batch=max_batch, max_seq=max_seq)
        self.drafter = (drafter or NGramDrafter()) if spec_k else None
        # Warm boot: pull the persistent tuned-block table (written by
        # `python -m benchmarks.tune`) into the substrate before the first
        # trace, so serving never re-derives — or worse, never measures —
        # its kernel tiles.  Missing/stale tables load as empty.
        self.tuned_blocks = kernel_common.load_tuned_table()
        # Retrace telemetry: each counter bumps only when jax *traces* the
        # wrapped python callable, so a steady-state engine shows
        # len(buckets) prefill traces and exactly one decode trace
        # (asserted by tests/test_serving.py::test_bucket_reuse_no_retrace).
        self.trace_counts: collections.Counter = collections.Counter()

        def _prefill_fn(p, inputs, lengths):
            self.trace_counts["prefill"] += 1
            return model.prefill(p, inputs, headroom=0, lengths=lengths)

        def _decode_fn(p, st, inputs):
            self.trace_counts["decode"] += 1
            return model.decode_step(p, st, inputs)

        def _insert_fn(st, sub, slots):
            self.trace_counts["insert"] += 1
            return self.ops.slot_update(st, sub, slots)

        def _reset_fn(st, slots, pos_values, rec):
            self.trace_counts["reset"] += 1
            return self.ops.slot_reset(st, slots, pos_values, rec)

        def _extend_fn(p, st, toks, adv):
            # paged admission: score the whole suffix window in one
            # verify pass and commit the per-row suffix lengths in the
            # same program (advance 0 restores non-admitted rows exactly
            # from their checkpoint-0 state; their stray K/V writes sit
            # past pos, invisible until overwritten — the spec-decode
            # rollback invariant).  rec_stack is returned so the radix
            # cache can snapshot recurrent state at page boundaries.
            self.trace_counts["extend"] += 1
            logits, st2, rec = model.verify_step(p, st, {"tokens": toks})
            ids = jnp.argmax(logits, axis=-1)
            st2 = model.spec_commit(st2, rec, adv)
            return ids, logits, st2, rec

        def _verify_fn(p, st, toks):
            self.trace_counts["verify"] += 1
            logits, st2, rec = model.verify_step(p, st, {"tokens": toks})
            # greedy targets computed in the same dispatch: the host pulls
            # (B, K) ints per step, never the logits (sampling slots pull
            # the full rows lazily — the logits stay on device otherwise)
            ids = jnp.argmax(logits, axis=-1)
            return ids, logits, st2, rec

        def _commit_fn(st, rec, adv):
            self.trace_counts["commit"] += 1
            return model.spec_commit(st, rec, adv)

        def _verify_greedy_fn(p, st, toks, caps):
            self.trace_counts["verify"] += 1
            return model.verify_commit_greedy(p, st, {"tokens": toks}, caps)

        def _slot_restore_fn(st, slots, pos_values, rec):
            # snapshot restore: raw-dtype pos + recurrent-leaf scatter
            # (bucket-padded to max_batch rows, sentinel rows drop — one
            # trace per engine, same discipline as _reset)
            self.trace_counts["restore"] += 1
            return self.ops.slot_restore(st, slots, pos_values, rec)

        self._prefill = jax.jit(_prefill_fn)
        # the old slot state is dead the moment a step returns: donate it
        # so XLA updates the caches in place (donation is a no-op warning
        # on CPU, so only ask for it on accelerators)
        donate = kernel_common.platform() != "cpu"
        self._decode = jax.jit(_decode_fn,
                               donate_argnums=(1,) if donate else ())
        self._insert = jax.jit(_insert_fn,
                               donate_argnums=(0,) if donate else ())
        self._reset = jax.jit(_reset_fn,
                              donate_argnums=(0,) if donate else ())
        self._extend = jax.jit(_extend_fn,
                               donate_argnums=(1,) if donate else ())
        self._verify = jax.jit(_verify_fn,
                               donate_argnums=(1,) if donate else ())
        self._commit = jax.jit(_commit_fn,
                               donate_argnums=(0,) if donate else ())
        self._verify_greedy = jax.jit(_verify_greedy_fn,
                                      donate_argnums=(1,) if donate else ())
        self._slot_restore = jax.jit(_slot_restore_fn,
                                     donate_argnums=(0,) if donate else ())
        # slot state allocates lazily on the first serve(): construction
        # stays cheap (warm boot = load the tuned table, nothing else)
        self._state = None
        self._slots: List[Optional[_Slot]] = [None] * max_batch
        # prompt buckets are powers of two (the ssm/hybrid chunked scans
        # also require pow2-friendly lengths), so the largest bucket is
        # the largest power of two that fits the slot cache
        self._bucket_cap = 1 << (max_seq.bit_length() - 1)
        # scheduler telemetry for the most recent serve() call:
        # ("admit"|"retire", rid, slot, decode_step); slot -1 marks a
        # request retired straight from prefill (1-token budget)
        self.events: List[tuple] = []
        self.step_walls: List[float] = []
        # typed metrics; keeps the historical dict surface (see
        # ServeMetrics) so benches and gates index it unchanged
        self.metrics = ServeMetrics()
        self._occ_num = 0
        self._occ_den = 0
        # -- fault tolerance -----------------------------------------------
        # snapshotted requests awaiting re-admission (rid -> _Parked)
        self._parked: Dict[int, _Parked] = {}
        # serve()'s live queues, lifted to attributes so a mid-trace
        # snapshot can persist not-yet-admitted and finished requests too
        self._pending: collections.deque = collections.deque()
        self._waiting: collections.deque = collections.deque()
        self._done_live: List[Request] = []
        self._ckpt: Optional[CheckpointManager] = None
        self._kill_fired = False
        self._last_snap_step = -1
        # supervisor hook: called once per serve-loop iteration (e.g.
        # HeartbeatMonitor.beat bound to this worker's name)
        self.heartbeat: Optional[Callable[[], None]] = None

    # -- scheduling ---------------------------------------------------------

    def _bucket(self, n: int) -> int:
        return min(max(self.min_bucket, next_pow2(n)), self._bucket_cap)

    def _validate(self, requests: List[Request]) -> None:
        # rids key scheduling events, snapshot/restore and re-admission;
        # a duplicate would silently corrupt accounting, so refuse early.
        live = {s.req.rid for s in self._slots if s is not None}
        seen: set = set()
        for r in requests:
            if r.rid in seen or r.rid in live:
                where = "another live request" if r.rid in live \
                    else "another request in this batch"
                raise ValueError(
                    f"duplicate request id {r.rid} (also used by {where}): "
                    f"request ids key scheduling, snapshot/restore and "
                    f"re-admission — give every request a unique rid")
            seen.add(r.rid)
        for r in requests:
            need = len(r.prompt) + r.max_new_tokens
            if need > self.max_seq:
                raise ValueError(
                    f"request {r.rid}: prompt {len(r.prompt)} + "
                    f"max_new {r.max_new_tokens} exceeds max_seq "
                    f"{self.max_seq}; requests are never silently dropped")
            if len(r.prompt) > self._bucket_cap:
                raise ValueError(
                    f"request {r.rid}: prompt {len(r.prompt)} exceeds the "
                    f"largest prompt bucket ({self._bucket_cap}) for "
                    f"max_seq {self.max_seq}")
            if r.max_new_tokens < 1:
                raise ValueError(f"request {r.rid}: max_new_tokens < 1")
            if len(r.prompt) < 1:
                raise ValueError(f"request {r.rid}: empty prompt")
            if self.allocator is not None:
                pages = min(-(-need // self.page_size), self._n_pages)
                if pages > self.allocator.num_blocks:
                    raise ValueError(
                        f"request {r.rid}: needs {pages} pages but the "
                        f"block pool only holds "
                        f"{self.allocator.num_blocks}; raise num_blocks")

    def _pull_logits(self, logits, sampling: bool):
        """Host-side view of a prefill's or step's logits, one row per
        program row (a one-row prefill has one, decode ``max_batch``):
        greedy needs only B ints (device argmax); only calls where some
        live request actually samples pull the full (B, vocab) float
        rows."""
        b = logits.shape[0]
        with TraceAnnotation("serve.pull"):
            if self.greedy or not sampling:
                return np.asarray(jnp.argmax(logits.reshape(b, -1),
                                             axis=-1)), None
            return None, np.asarray(
                logits.astype(jnp.float32)).reshape(b, -1)

    def _next_token(self, slot: _Slot, i: int, ids, rows) -> int:
        return (int(ids[i]) if rows is None
                else self._select_token(slot, rows[i]))

    def _dist(self, slot: _Slot, row: np.ndarray) -> np.ndarray:
        """The request's sampling distribution over one logits row
        (temperature + top_k), shared by plain sampling and the
        spec-decode rejection-sampling fallback."""
        r = slot.req
        z = row.astype(np.float64) / max(r.temperature, 1e-6)
        k = min(int(r.top_k), z.size)   # top_k >= vocab == no filter
        if 0 < k < z.size:
            kth = np.partition(z, -k)[-k]
            z = np.where(z >= kth, z, -np.inf)
        z -= z.max()
        p = np.exp(z)
        p /= p.sum()
        return p

    def _select_token(self, slot: _Slot, row: np.ndarray) -> int:
        if self.greedy or slot.req.temperature <= 0.0:
            return int(np.argmax(row))
        p = self._dist(slot, row)
        return int(slot.rng.choice(len(p), p=p))

    def _retire(self, i: Optional[int], slot: _Slot, done: List[Request]
                ) -> None:
        if slot.session is not None:
            # explicit close() is the drafter API's retire contract:
            # batched drafters free the request's device-side row
            slot.session.close()
            slot.session = None
        r = slot.req
        r.output = np.asarray(slot.tokens[:r.max_new_tokens])
        r.done_at = time.monotonic()
        if r.status == "pending":       # deadline retire pre-sets "timeout"
            r.status = "done"
        done.append(r)
        self.events.append(("retire", r.rid, -1 if i is None else i,
                            int(self.metrics["decode_steps"])))
        if i is not None:
            if self.paged:
                self._free_slot_pages(i)
            self._slots[i] = None

    # -- mesh seams ----------------------------------------------------------
    # Overridden by runtime/mesh_serve.py's MeshServeEngine; the base
    # implementations are the exact single-device behaviour the loop had
    # before the seams existed.

    def _init_state(self):
        """Allocate the slot-batch state (first serve() call).  The mesh
        engine overrides this to place every leaf with a NamedSharding
        over the serving mesh's data axis."""
        return self.ops.init_slot_state(self.max_batch, self.max_seq)

    def _free_slots(self) -> List[int]:
        """Free slot indices in admission-preference order.  The base
        engine fills lowest-index first; the mesh engine orders by shard
        load (least-loaded shard wins) and excludes slots reserved by
        in-flight async prefills."""
        return [i for i, s in enumerate(self._slots) if s is None]

    def _poll_admissions(self, done: List[Request]) -> None:
        """Complete any finished async prefills (mesh engine hook).  The
        base engine prefills inline, so there is never anything to poll."""

    def _admissions_inflight(self) -> bool:
        """Whether async prefills are still pending (keeps the serve loop
        alive while a prefill worker owns the only remaining work)."""
        return False

    # -- paged slot memory ---------------------------------------------------

    def _st(self):
        """The jit-call view of the slot state.  Paged engines substitute
        the authoritative host block tables on every call (numpy rides the
        cheap C++ argument path); the device echo in the returned state is
        one step stale the moment the host reallocates a page."""
        if not self.paged:
            return self._state
        return self._state._replace(block_tables=self._tables)

    def _alloc_block(self) -> Optional[int]:
        """One free pool block, evicting radix LRU leaves if dry."""
        blk = self.allocator.alloc()
        if blk is None and self.radix is not None:
            if self.radix.evict(1):
                blk = self.allocator.alloc()
        if blk is not None:
            self.metrics["peak_blocks"] = max(
                self.metrics["peak_blocks"], self.allocator.used_blocks)
        return blk

    def _ensure_pages(self, i: int, last_pos: int) -> None:
        """Allocate slot ``i``'s table entries for every page a step may
        write, up to absolute position ``last_pos`` (writes past
        ``max_seq`` drop at the model layer, so the cap is harmless)."""
        if self.allocator is None:       # recurrent-only: nothing pooled
            return
        sentinel = self.allocator.num_blocks
        row = self._tables[i]
        last = min(last_pos, self.max_seq - 1) // self.page_size
        for p in range(last + 1):
            if row[p] == sentinel:
                blk = self._alloc_block()
                if blk is None:
                    # every block is pinned by some live slot: with the
                    # default full-occupancy pool this is unreachable, an
                    # undersized pool oversubscribed by live tokens has no
                    # page to give (requests are never silently dropped)
                    raise RuntimeError(
                        f"block pool exhausted: slot {i} needs page {p} "
                        f"and eviction freed nothing; raise num_blocks")
                row[p] = blk

    def _free_slot_pages(self, i: int) -> None:
        """Return every block slot ``i`` references (retire path)."""
        if self.allocator is None:
            return
        sentinel = self.allocator.num_blocks
        row = self._tables[i]
        for p in range(self._n_pages):
            if row[p] != sentinel:
                self.allocator.free(int(row[p]))
        row[:] = sentinel

    def _admit_paged(self, group: List[Request], free: List[int],
                     done: List[Request]) -> List[Request]:
        """Extend-admission into paged slots; returns requests deferred
        for lack of blocks (the caller requeues them, order preserved).

        Per request: walk the radix trie for the longest full-page prompt
        prefix, take cache references on the matched blocks, allocate
        private pages for the suffix, then one ``slot_reset`` (resume
        ``pos`` at the matched length, load the page-boundary recurrent
        snapshot) and one bucket-padded extend program — a ``verify_step``
        over the suffix window committed by its per-row suffix lengths —
        compute every admitted request's prompt continuation at once.
        Rows not being admitted ride along with advance 0: the commit
        restores their exact pre-call state from checkpoint 0 and their
        stray K/V writes sit past ``pos`` (or drop at table sentinels),
        invisible until overwritten — the spec-decode rollback invariant.
        """
        with TraceAnnotation("serve.admit",
                             step=int(self.metrics["decode_steps"])) as span:
            b = self.max_batch
            page = self.page_size
            plan = []                     # (req, slot, matched, nodes)
            leftover: List[Request] = []
            free_iter = iter(free)
            for r in group:
                m, nodes = (self.radix.match(r.prompt)
                            if self.radix is not None else (0, []))
                if self.allocator is not None:
                    taken: List[int] = []
                    for node in nodes:    # slot's own refs on shared pages
                        self.allocator.ref(node.block)
                        taken.append(node.block)
                    new_blocks: List[int] = []
                    dry = False
                    for _ in range(m // page, (len(r.prompt) - 1) // page + 1):
                        blk = self._alloc_block()
                        if blk is None:
                            dry = True
                            break
                        new_blocks.append(blk)
                    if dry:               # roll this request back, keep going
                        for blk in taken + new_blocks:
                            self.allocator.free(blk)
                        leftover.append(r)
                        continue
                    slot_i = next(free_iter)
                    row = self._tables[slot_i]
                    for p, node in enumerate(nodes):
                        row[p] = node.block
                    for q, blk in enumerate(new_blocks):
                        row[m // page + q] = blk
                else:
                    slot_i = next(free_iter)
                if self.radix is not None:
                    self.radix.hits += m // page
                    self.radix.misses += (len(r.prompt) - 1) // page + 1 \
                        - m // page
                plan.append((r, slot_i, m, nodes))
            if not plan:
                return leftover
            # the extend program's suffix bucket
            bucket = self._bucket(max(len(r.prompt) - m
                                      for r, _, m, _ in plan))
            span.set_metadata(rows=len(plan), bucket=bucket)
            self.metrics["prefill_positions"] += b * bucket

            # one reset program: pos + recurrent snapshots for warm slots
            # (rec keys are the state's recurrent fields — fixed per family,
            # so the reset trace is reused across admissions)
            slots_arr = np.full((b,), b, np.int32)     # sentinel rows drop
            pos_vals = np.zeros((b,), np.int32)
            rec: Dict[str, np.ndarray] = {}
            for name in ("x_prev", "cm_prev", "wkv", "conv_tail", "ssm_h"):
                leaf = getattr(self._state, name, None)
                if leaf is not None:
                    rec[name] = np.zeros((leaf.shape[0], b) + tuple(
                        leaf.shape[2:]), np.float32)
            for j, (r, slot_i, m, nodes) in enumerate(plan):
                slots_arr[j] = slot_i
                pos_vals[j] = m
                if m and rec:
                    snap = nodes[-1].rec
                    for name, arr in rec.items():
                        arr[:, j] = snap[name]
            self._state = self._reset(self._st(), slots_arr, pos_vals, rec)

            # one extend program at the suffix bucket
            toks = np.zeros((b, bucket), np.int32)
            adv = np.zeros((b,), np.int32)
            for r, slot_i, m, _ in plan:
                sfx = len(r.prompt) - m
                toks[slot_i, :sfx] = r.prompt[m:]
                adv[slot_i] = sfx
            ids_dev, logits, self._state, rec_stack = self._extend(
                self.params, self._st(), toks, adv)
            with TraceAnnotation("serve.pull"):
                ids = np.asarray(ids_dev)                     # (B, bucket)
                now = time.monotonic()
                rows = None
                if not self.greedy and any(r.temperature > 0.0
                                           for r, _, _, _ in plan):
                    rows = np.asarray(
                        logits.astype(jnp.float32))           # (B, bkt, V)
                rec_np = ({name: np.asarray(stk, np.float32)
                           for name, stk in rec_stack.items()}
                          if self.radix is not None else {})

            for r, slot_i, m, nodes in plan:
                sfx = len(r.prompt) - m
                r.admitted_at = now
                r.token_times.append(now)
                r.prefix_hit_tokens = m
                self.metrics["prefill_tokens"] += sfx
                self.metrics["prefix_hit_tokens"] += m
                self.events.append(("admit", r.rid, slot_i,
                                    int(self.metrics["decode_steps"])))
                rng = (np.random.default_rng([r.seed, r.rid])
                       if not self.greedy and r.temperature > 0.0 else None)
                slot = _Slot(req=r, next_token=0, produced=0, tokens=[],
                             rng=rng, pos=len(r.prompt))
                if rows is None:
                    slot.next_token = int(ids[slot_i, sfx - 1])
                else:
                    slot.next_token = self._select_token(
                        slot, rows[slot_i, sfx - 1])
                slot.tokens.append(slot.next_token)
                slot.produced = 1
                if self.spec_k:
                    slot.spec_k = self.spec_k
                    slot.session = self.drafter.begin(
                        [int(t) for t in r.prompt] + [slot.next_token],
                        slot=slot_i, rid=r.rid)
                if self.radix is not None and len(r.prompt) // page:
                    # register this prompt's full pages; snapshot recurrent
                    # state at each page boundary from the extend checkpoints
                    # (checkpoint j = state after j suffix tokens, so the
                    # page-p boundary sits at j = (p+1)*page - m)
                    full = len(r.prompt) // page
                    blocks = ([int(self._tables[slot_i, p])
                               for p in range(full)]
                              if self.allocator is not None else None)
                    recs = []
                    for p in range(full):
                        j = (p + 1) * page - m
                        recs.append({name: stk[j, :, slot_i].copy()
                                     for name, stk in rec_np.items()}
                                    if j >= 1 else None)
                    self.radix.insert(r.prompt, len(r.prompt), blocks, recs)
                if slot.produced >= r.max_new_tokens:   # 1-token request
                    self._free_slot_pages(slot_i)
                    self._retire(None, slot, done)
                else:
                    self._slots[slot_i] = slot
            return leftover

    def _prefill_args(self, r: Request, slot_i: int):
        """One request's prefill arguments: its prompt right-padded to
        its bucket as one row, its true length, and its slot.

        Returns ``(inputs, lengths, slots)`` — array construction, shared
        by the inline admission path and the mesh engine's async prefill
        workers (the arrays are what a worker thread hands to the jitted
        prefill; ``slots`` drives the insert scatter afterwards).  Counts
        the ``bucket`` positions the prefill will compute.
        """
        cfg = self.model.cfg
        bucket = self._bucket(len(r.prompt))
        self.metrics["prefill_positions"] += bucket
        if cfg.input_kind == "tokens":
            arr = np.zeros((1, bucket), np.int32)
        else:
            arr = np.zeros((1, bucket, cfg.d_model), np.float32)
        arr[0, :len(r.prompt)] = r.prompt
        key = "tokens" if cfg.input_kind == "tokens" else "frames"
        return ({key: arr}, np.array([len(r.prompt)], np.int32),
                np.array([slot_i], np.int32))

    def _admit(self, group: List[Request], free: List[int],
               done: List[Request]) -> None:
        """Prefill each request of an admission group in its own one-row
        call, in order, and scatter its state into its free slot.  A
        request's admission starts when its own prefill does, and its
        first token is stamped once the host holds it, before the next
        request's prefill starts; no decode step runs between the calls,
        and one trace per bucket serves every group size."""
        for r, slot_i in zip(group, free):
            r.admit_started_at = time.monotonic()
            inputs, lengths, slots = self._prefill_args(r, slot_i)
            with TraceAnnotation("serve.admit",
                                 step=int(self.metrics["decode_steps"]),
                                 rows=1,
                                 bucket=next(iter(inputs.values())).shape[1]):
                logits, sub = self._prefill(self.params, inputs, lengths)
                self._finish_admit([r], [slot_i], logits, sub, slots, done)

    def _finish_admit(self, group: List[Request], free: List[int],
                      logits, sub, slots: np.ndarray,
                      done: List[Request]) -> None:
        """Insert prefilled sub-state into the slot batch + bookkeeping.

        The second half of :meth:`_admit`, split out so the mesh engine's
        prefill workers can run the prefill off-thread and hand
        ``(logits, sub)`` back to the scheduler thread, which owns the
        slot state and performs the insert scatter.
        """
        self._state = self._insert(self._state, sub, slots)
        ids, rows = self._pull_logits(
            logits, any(r.temperature > 0.0 for r in group))
        now = time.monotonic()
        for j, r in enumerate(group):
            r.admitted_at = now
            r.token_times.append(now)
            self.metrics["prefill_tokens"] += len(r.prompt)
            self.events.append(("admit", r.rid, free[j],
                                int(self.metrics["decode_steps"])))
            rng = (np.random.default_rng([r.seed, r.rid])
                   if not self.greedy and r.temperature > 0.0 else None)
            slot = _Slot(req=r, next_token=0, produced=0, tokens=[], rng=rng,
                         pos=len(r.prompt))
            slot.next_token = self._next_token(slot, j, ids, rows)
            slot.tokens.append(slot.next_token)
            slot.produced = 1
            if self.spec_k:
                slot.spec_k = self.spec_k
                slot.session = self.drafter.begin(
                    [int(t) for t in r.prompt] + [slot.next_token],
                    slot=free[j], rid=r.rid)
            if slot.produced >= r.max_new_tokens:
                self._retire(None, slot, done)     # 1-token request
            else:
                self._slots[free[j]] = slot

    def _plain_step(self, active: List[int], done: List[Request]) -> None:
        """One single-token decode step for every slot (fixed B).  Also
        the speculative engine's fallback when no slot drafted anything —
        a (B, k+1) verify that can only emit one token per slot would cost
        ~2x the plain program for the same result."""
        cfg = self.model.cfg
        b = self.max_batch
        tokens = np.zeros((b, 1), np.int32)
        for i in active:
            tokens[i, 0] = self._slots[i].next_token
        # numpy leaves go straight to the jitted callable: its C++
        # argument path transfers them ~10x cheaper than an explicit
        # python-level jnp.asarray + device_put per step
        if cfg.input_kind == "tokens":
            nb = {"tokens": tokens}
        else:               # frame stubs decode over embedded tokens
            nb = {"frames": np.zeros((b, 1, cfg.d_model), np.float32)}
        if self.paged:      # this step writes each slot's position `pos`
            with TraceAnnotation("serve.pages"):
                for i in active:
                    self._ensure_pages(i, self._slots[i].pos)
        logits, self._state = self._decode(self.params, self._st(), nb)
        ids, rows = self._pull_logits(
            logits, any(self._slots[i].rng is not None for i in active))
        now = time.monotonic()
        self.metrics["decode_steps"] += 1
        self.metrics["decode_tokens"] += len(active)
        self._occ_num += len(active)
        self._occ_den += b

        # retire-and-refill: a finished slot frees this very step
        for i in active:
            slot = self._slots[i]
            slot.next_token = self._next_token(slot, i, ids, rows)
            slot.tokens.append(slot.next_token)
            slot.req.token_times.append(now)
            slot.produced += 1
            slot.pos += 1
            if slot.session is not None:
                slot.session.extend([slot.next_token])
            if slot.produced >= slot.req.max_new_tokens:
                self._retire(i, slot, done)

    # -- speculative decoding ----------------------------------------------

    def _accept_greedy(self, ids_row: np.ndarray, drafts: List[int],
                       cap: int) -> List[int]:
        """Longest matching prefix: accept drafts while they equal the
        model's greedy choice, then append the first correction (the
        bonus token when every draft matched) — exactly the tokens plain
        greedy decode would have produced, one step at a time."""
        a = 0
        while a < cap and int(ids_row[a]) == drafts[a]:
            a += 1
        return drafts[:a] + [int(ids_row[a])]

    def _accept_sampled(self, slot: _Slot, rows: np.ndarray,
                        drafts: List[int], cap: int) -> List[int]:
        """Rejection-sampling fallback for temperature slots.  The drafter
        proposes deterministically (q = a point mass), so the standard
        speculative acceptance rule reduces to: accept draft d with
        probability p(d); on rejection sample from the residual p with d
        removed, renormalised — the emitted stream is distributed exactly
        as plain sampling from p."""
        out: List[int] = []
        a = 0
        while a < cap:
            p = self._dist(slot, rows[a])
            t = drafts[a]
            if slot.rng.random() < p[t]:
                out.append(t)
                a += 1
                continue
            q = p.copy()
            q[t] = 0.0
            s = q.sum()
            if s <= 0.0:            # p was a point mass on the draft
                out.append(int(np.argmax(p)))
            else:
                out.append(int(slot.rng.choice(len(q), p=q / s)))
            return out
        p = self._dist(slot, rows[a])         # bonus position
        out.append(int(slot.rng.choice(len(p), p=p)))
        return out

    # adaptive spec_k: EWMA smoothing weight, the shrink/grow thresholds
    # (hysteresis band between them holds k steady), and how many steps a
    # k=0 slot rides plain decode before probing with a single draft
    _SPEC_ALPHA = 0.3
    _SPEC_LO = 0.2
    _SPEC_HI = 0.5
    _PROBE_EVERY = 16

    def _want_k(self, slot: _Slot) -> int:
        """This step's draft budget for one slot.  Fixed engines always
        ask for the full window; adaptive engines ask for the slot's
        current budget, with a periodic 1-token probe out of k=0 so a
        workload that turns draftable can climb back."""
        if not self.spec_adaptive:
            return self.spec_k_max
        if slot.spec_k == 0:
            slot.spec_probe += 1
            if slot.spec_probe >= self._PROBE_EVERY:
                slot.spec_probe = 0
                return 1
            return 0
        return slot.spec_k

    def _spec_step(self, active: List[int], done: List[Request]) -> None:
        """One speculative engine step: draft, verify, commit, retire.

        Fixed shapes keep one verify trace: every step scores
        (B, spec_k_max+1) tokens; slots with fewer (or no) drafts pad the
        window and simply fail to match there.  Rejected positions roll
        back on commit — recurrent state to its per-step checkpoint,
        linear-cache K/V writes stay masked until the real token
        overwrites them, ring-cache wrapped writes restore their evicted
        columns (see ``models/transformer.py::verify_step``).  Batched
        drafters draft every participating slot in one ``draft_all``
        call; adaptive engines drop low-acceptance slots to k=0, which
        routes whole steps to the (cheaper) plain program below."""
        b = self.max_batch
        k = self.spec_k_max
        toks = np.zeros((b, k + 1), np.int32)
        # per-row ceiling on accepted drafts: real draft count and what is
        # left of the budget after the correction/bonus token; -1 keeps
        # empty slots from advancing at all
        caps = np.full((b,), -1, np.int32)
        drafts: Dict[int, List[int]] = {}
        hist = self.metrics.spec_k_hist
        want: Dict[int, int] = {}
        for i in active:
            slot = self._slots[i]
            hist[slot.spec_k] = hist.get(slot.spec_k, 0) + 1
            want[i] = max(0, min(self._want_k(slot),
                                 slot.req.max_new_tokens - slot.produced
                                 - 1))
        if getattr(self.drafter, "batched", False):
            got = self.drafter.draft_all(
                {i: w for i, w in want.items() if w > 0})
        else:
            got = {i: self._slots[i].session.draft(w)
                   for i, w in want.items() if w > 0}
        for i in active:
            slot = self._slots[i]
            d = got.get(i, [])[:want[i]]
            drafts[i] = d
            toks[i, 0] = slot.next_token
            if d:
                toks[i, 1:1 + len(d)] = d
            caps[i] = min(len(d), slot.req.max_new_tokens - slot.produced
                          - 1)
        if not any(caps[i] > 0 for i in active):
            # nothing worth verifying this step (no drafts, or every slot
            # is one token from its budget): the plain program emits the
            # identical tokens at a fraction of the verify cost
            self._plain_step(active, done)
            return
        emitted: Dict[int, List[int]] = {}
        if self.paged:      # the verify window writes pos..pos+k per slot
            with TraceAnnotation("serve.pages"):
                for i in active:
                    self._ensure_pages(i, self._slots[i].pos + k)
        if self.greedy:
            # fused path: verify + longest-prefix accept + commit in one
            # dispatch; the host pulls (B, k+1) ids + (B,) advances
            ids_dev, adv_dev, self._state = self._verify_greedy(
                self.params, self._st(), toks, caps)
            with TraceAnnotation("serve.pull"):
                ids = np.asarray(ids_dev)
                adv = np.asarray(adv_dev)
            now = time.monotonic()
            for i in active:
                a = int(adv[i]) - 1
                out = drafts[i][:a] + [int(ids[i, a])]
                emitted[i] = out
                self.metrics["draft_tokens"] += len(drafts[i])
                self.metrics["draft_accepted"] += a
        else:
            # two-phase path: sampling slots need the host-side rejection
            # test, so acceptance happens between verify and commit
            ids_dev, logits, self._state, rec = self._verify(
                self.params, self._st(), toks)
            sampling = any(self._slots[i].rng is not None for i in active)
            with TraceAnnotation("serve.pull"):
                ids = np.asarray(ids_dev)                     # (B, k+1)
                rows = (np.asarray(
                    logits.astype(jnp.float32))               # (B, k+1, V)
                    if sampling else None)
            now = time.monotonic()
            advance = np.zeros((b,), np.int32)
            for i in active:
                slot = self._slots[i]
                if slot.rng is None:
                    out = self._accept_greedy(ids[i], drafts[i], caps[i])
                else:
                    out = self._accept_sampled(slot, rows[i], drafts[i],
                                               caps[i])
                advance[i] = len(out)
                emitted[i] = out
                self.metrics["draft_tokens"] += len(drafts[i])
                self.metrics["draft_accepted"] += len(out) - 1
            self._state = self._commit(self._st(), rec, advance)
        self.metrics["decode_steps"] += 1
        self.metrics["spec_steps"] += 1
        self.metrics["decode_tokens"] += sum(len(v) for v in emitted.values())
        self._occ_num += len(active)
        self._occ_den += b
        for i in active:
            slot = self._slots[i]
            out = emitted[i]
            if self.spec_adaptive and drafts[i]:
                # trailing-acceptance EWMA drives the slot's budget:
                # below the low-water mark shrink toward 0 (plain decode,
                # already the engine's free fallback), above the
                # high-water mark grow back toward spec_k_max
                rate = (len(out) - 1) / len(drafts[i])
                slot.spec_ewma += self._SPEC_ALPHA * (rate - slot.spec_ewma)
                if slot.spec_ewma < self._SPEC_LO:
                    slot.spec_k = max(0, slot.spec_k - 1)
                elif slot.spec_ewma > self._SPEC_HI:
                    slot.spec_k = min(self.spec_k_max, slot.spec_k + 1)
            slot.tokens.extend(out)
            slot.req.token_times.extend([now] * len(out))
            slot.session.extend(out)
            slot.produced += len(out)
            slot.next_token = out[-1]
            old_pos = slot.pos
            slot.pos += len(out)
            if self.paged and self.allocator is not None:
                # spec rollback returns blocks: pages allocated for the
                # verify window but unreached by the committed advance go
                # straight back to the pool (their rejected writes are
                # dead — those positions recompute on a later step)
                sentinel = self.allocator.num_blocks
                last_ens = min(old_pos + k, self.max_seq - 1) \
                    // self.page_size
                for p in range(slot.pos // self.page_size + 1,
                               last_ens + 1):
                    if self._tables[i, p] != sentinel:
                        self.allocator.free(int(self._tables[i, p]))
                        self._tables[i, p] = sentinel
            if slot.produced >= slot.req.max_new_tokens:
                self._retire(i, slot, done)

    # -- snapshot / restore --------------------------------------------------

    _KV_LEAVES = ("cache_k", "cache_v", "scale_k", "scale_v")

    def _ckpt_mgr(self) -> CheckpointManager:
        if self.config.snapshot_dir is None:
            raise ValueError("snapshot/restore needs ServeConfig."
                             "snapshot_dir")
        if self._ckpt is None:
            # sync save: an async writer would race the host-authoritative
            # block tables (live numpy) mutating under the next admission
            self._ckpt = CheckpointManager(self.config.snapshot_dir,
                                           keep=3, async_save=False)
        return self._ckpt

    def snapshot(self) -> int:
        """Persist an atomic, versioned snapshot of every in-flight,
        queued and finished request; returns the step id (the engine's
        decode-step counter).

        Per live slot: prompt, emitted tokens, sampling RNG state, and the
        per-slot state leaves in **raw storage dtype** (int8 + scales
        verbatim) via the ``slot_extract`` gather seam — dense KV trimmed
        to ``pos`` tokens; paged KV as the referenced pool pages in
        logical order (the block table travels implicitly as that
        ordering).  A fresh engine — any ``max_batch``/pool size with the
        same model fingerprint — restores it and resumed requests
        complete bit-identically to an uninterrupted run.
        """
        mgr = self._ckpt_mgr()
        t_start = time.perf_counter()
        state = self._state
        if (not self.paged and state is not None
                and state.cache_k is not None
                and state.cache_k.shape[kv_pos_axis(state)] < self.max_seq):
            raise ValueError(
                "cannot snapshot a ring-cache engine (slot cache shorter "
                "than max_seq): ring positions alias, so a linear per-slot "
                "extract does not exist (ROADMAP: ring paging is open)")
        step = int(self.metrics["decode_steps"])
        arrays: Dict[str, np.ndarray] = {}
        slots_meta: List[dict] = []
        live = [(i, s) for i, s in enumerate(self._slots) if s is not None]
        if live:
            idx = np.asarray([i for i, _ in live], np.int32)
            sub = self.ops.slot_extract(state, idx)
            pos_dev = np.asarray(sub.pos)
            host: Dict[str, np.ndarray] = {}
            for name in sub._fields:
                if name in ("pos", "block_tables"):
                    continue
                leaf = getattr(sub, name)
                if leaf is not None:
                    host[name] = np.asarray(leaf)
        for j, (i, slot) in enumerate(live):
            r = slot.req
            pos = int(pos_dev[j])
            arrays[f"slot{j}.prompt"] = np.asarray(r.prompt)
            arrays[f"slot{j}.tokens"] = np.asarray(slot.tokens, np.int32)
            for name, arr in host.items():
                if name in self._KV_LEAVES and not self.paged:
                    arrays[f"slot{j}.{name}"] = slot_kv_history(
                        arr, j, pos).copy()
                else:
                    arrays[f"slot{j}.{name}"] = arr[:, j].copy()
            if self.paged and self.allocator is not None:
                n_used = (pos - 1) // self.page_size + 1
                ids = np.asarray([int(self._tables[i, p])
                                  for p in range(n_used)], np.int32)
                for name in self._KV_LEAVES:
                    leaf = getattr(state, name)
                    if leaf is not None:
                        arrays[f"slot{j}.pages.{name}"] = \
                            np.asarray(leaf[:, ids])
            slots_meta.append({
                "j": j, "rid": r.rid, "produced": slot.produced,
                "next_token": int(slot.next_token), "pos": pos,
                "max_new_tokens": r.max_new_tokens,
                "temperature": r.temperature, "top_k": r.top_k,
                "seed": r.seed, "deadline_s": r.deadline_s,
                "rng": (slot.rng.bit_generator.state
                        if slot.rng is not None else None),
            })
        queue_meta: List[dict] = []
        for qj, r in enumerate(list(self._waiting) + list(self._pending)):
            arrays[f"queue{qj}.prompt"] = np.asarray(r.prompt)
            queue_meta.append({
                "j": qj, "rid": r.rid,
                "max_new_tokens": r.max_new_tokens,
                "temperature": r.temperature, "top_k": r.top_k,
                "seed": r.seed, "deadline_s": r.deadline_s})
        done_meta: List[dict] = []
        for dj, r in enumerate(self._done_live):
            arrays[f"done{dj}.output"] = (
                np.asarray(r.output) if r.output is not None
                else np.zeros((0,), np.int32))
            done_meta.append({"j": dj, "rid": r.rid, "status": r.status})
        meta = {
            "snapshot_version": SNAPSHOT_VERSION,
            "fingerprint": (self.model.cfg.fingerprint()
                            if getattr(self.model, "cfg", None) is not None
                            else None),
            "engine": {"max_batch": self.max_batch,
                       "max_seq": self.max_seq, "greedy": self.greedy,
                       "paged": self.paged, "spec_k": self.spec_k,
                       "page_size": (self.page_size if self.paged
                                     else None)},
            "slots": slots_meta, "queue": queue_meta, "done": done_meta,
        }
        mgr.save(step, arrays, metadata=meta)
        self.metrics["snapshots"] += 1
        self.metrics["snapshot_s"] += time.perf_counter() - t_start
        return step

    def restore_snapshot(self, step: Optional[int] = None
                         ) -> Tuple[List[Request], List[Request]]:
        """Load a snapshot (latest step by default) into this engine;
        returns ``(requests, completed)``.

        Call on a **fresh** engine, then ``serve(requests)``: snapshotted
        in-flight requests re-enter through their saved state (parked by
        rid until the scheduler reaches them — a smaller ``max_batch``
        simply queues the overflow) and complete bit-identically;
        snapshotted-but-unadmitted requests re-admit from scratch.
        ``completed`` carries the dead engine's already-finished requests
        (outputs + status) for a supervisor to merge by rid.  The model
        fingerprint and sampling mode must match; capacity may differ as
        long as each request still fits (``prompt + max_new <= max_seq``,
        each slot's pages fit the pool).
        """
        mgr = self._ckpt_mgr()
        t_start = time.perf_counter()
        arrays, meta = mgr.load_arrays(step)
        if meta.get("snapshot_version") != SNAPSHOT_VERSION:
            raise ValueError(
                f"snapshot version {meta.get('snapshot_version')!r} is "
                f"not supported (this engine speaks {SNAPSHOT_VERSION})")
        fp = (self.model.cfg.fingerprint()
              if getattr(self.model, "cfg", None) is not None else None)
        if meta.get("fingerprint") != fp:
            raise ValueError(
                f"snapshot fingerprint mismatch: taken under "
                f"{meta.get('fingerprint')}, this engine is {fp} — "
                f"restoring across architectures or cache formats cannot "
                f"be bit-identical")
        eng = meta.get("engine", {})
        if bool(eng.get("greedy")) != bool(self.greedy):
            raise ValueError("snapshot sampling mode (greedy="
                             f"{eng.get('greedy')}) differs from this "
                             f"engine's (greedy={self.greedy})")
        requests: List[Request] = []
        for srec in meta.get("slots", []):
            j = srec["j"]
            prompt = arrays[f"slot{j}.prompt"]
            r = Request(rid=srec["rid"], prompt=prompt,
                        max_new_tokens=srec["max_new_tokens"],
                        temperature=srec["temperature"],
                        top_k=srec["top_k"], seed=srec["seed"],
                        deadline_s=srec.get("deadline_s"))
            need = len(prompt) + r.max_new_tokens
            if need > self.max_seq:
                raise ValueError(
                    f"restored request {r.rid} needs {need} cache "
                    f"positions but this engine's max_seq is "
                    f"{self.max_seq}")
            leaves: Dict[str, np.ndarray] = {}
            pages: Dict[str, np.ndarray] = {}
            pre = f"slot{j}."
            for key, arr in arrays.items():
                if not key.startswith(pre):
                    continue
                name = key[len(pre):]
                if name in ("prompt", "tokens"):
                    continue
                if name.startswith("pages."):
                    pages[name[len("pages."):]] = arr
                else:
                    leaves[name] = arr
            if self.paged and self.allocator is not None and pages:
                n_used = next(iter(pages.values())).shape[1]
                if n_used > self.allocator.num_blocks:
                    raise ValueError(
                        f"restored request {r.rid} holds {n_used} pages "
                        f"but this engine's pool only has "
                        f"{self.allocator.num_blocks} blocks; raise "
                        f"num_blocks")
            self._parked[r.rid] = _Parked(
                tokens=[int(t) for t in arrays[f"slot{j}.tokens"]],
                next_token=int(srec["next_token"]),
                produced=int(srec["produced"]), pos=int(srec["pos"]),
                rng_state=srec.get("rng"), leaves=leaves, pages=pages)
            requests.append(r)
        for qrec in meta.get("queue", []):
            requests.append(Request(
                rid=qrec["rid"], prompt=arrays[f"queue{qrec['j']}.prompt"],
                max_new_tokens=qrec["max_new_tokens"],
                temperature=qrec["temperature"], top_k=qrec["top_k"],
                seed=qrec["seed"], deadline_s=qrec.get("deadline_s")))
        completed = [
            Request(rid=drec["rid"], prompt=np.zeros((0,), np.int32),
                    output=arrays[f"done{drec['j']}.output"],
                    status=drec.get("status", "done"))
            for drec in meta.get("done", [])]
        self.metrics["restore_s"] += time.perf_counter() - t_start
        return requests, completed

    def _admit_restored(self, group: List[Request], free: List[int],
                        done: List[Request]) -> List[Request]:
        """Re-admit parked (snapshot-restored) requests into free slots;
        returns requests deferred for lack of pool blocks (paged only).

        Dense engines rebuild a one-row bucket-padded sub-state per
        request from the stored raw leaves and reuse the ``_insert``
        scatter program a one-row prefill of that bucket uses (restore
        never retraces a warm engine).  Paged engines allocate fresh blocks,
        write the stored pages back with fixed-shape *eager* pool updates
        (nothing traced), and scatter pos + recurrent leaves through the
        one jitted ``slot_restore`` program.
        """
        with TraceAnnotation("serve.admit",
                             step=int(self.metrics["decode_steps"]),
                             rows=len(group)):
            t_start = time.perf_counter()
            b = self.max_batch
            entries = [(r, self._parked[r.rid]) for r in group]
            leftover: List[Request] = []
            placed: List[tuple] = []
            if self.paged:
                free_iter = iter(free)
                for r, e in entries:
                    new_ids: List[int] = []
                    if self.allocator is not None:
                        n_used = (e.pos - 1) // self.page_size + 1
                        dry = False
                        for _ in range(n_used):
                            blk = self._alloc_block()
                            if blk is None:
                                dry = True
                                break
                            new_ids.append(blk)
                        if dry:           # roll back, requeue, keep going
                            for blk in new_ids:
                                self.allocator.free(blk)
                            leftover.append(r)
                            continue
                    slot_i = next(free_iter)
                    if self.allocator is not None:
                        for p, blk in enumerate(new_ids):
                            self._tables[slot_i, p] = blk
                    placed.append((r, e, slot_i, new_ids))
                if placed and self.allocator is not None:
                    all_ids = np.concatenate(
                        [np.asarray(ids, np.int32)
                         for _, _, _, ids in placed])
                    updates: Dict[str, Any] = {}
                    for name in self._KV_LEAVES:
                        tgt = getattr(self._state, name)
                        if tgt is None:
                            continue
                        pgs = np.concatenate(
                            [e.pages[name] for _, e, _, _ in placed], axis=1)
                        updates[name] = tgt.at[:, all_ids].set(
                            jnp.asarray(pgs, tgt.dtype))
                    self._state = self._state._replace(**updates)
                if placed:
                    slots_arr = np.full((b,), b, np.int32)
                    pos_vals = np.zeros((b,), np.int32)
                    rec_names = [
                        n for n in ("x_prev", "cm_prev", "wkv", "conv_tail",
                                    "ssm_h", "wkv_scale", "ssm_scale")
                        if getattr(self._state, n, None) is not None]
                    rec = {n: np.zeros(
                        (getattr(self._state, n).shape[0], b)
                        + tuple(getattr(self._state, n).shape[2:]),
                        getattr(self._state, n).dtype) for n in rec_names}
                    for g, (r, e, slot_i, _) in enumerate(placed):
                        slots_arr[g] = slot_i
                        pos_vals[g] = e.pos
                        for n in rec_names:
                            rec[n][:, g] = e.leaves[n]
                    self._state = self._slot_restore(self._st(), slots_arr,
                                                     pos_vals, rec)
            else:
                for g, (r, e) in enumerate(entries):
                    self._restore_row(e, free[g])
                    placed.append((r, e, free[g], []))

            now = time.monotonic()
            step = int(self.metrics["decode_steps"])
            for r, e, slot_i, _ in placed:
                r.admitted_at = now
                self.events.append(("restore", r.rid, slot_i, step))
                rng = None
                if e.rng_state is not None:
                    rng = np.random.default_rng()
                    rng.bit_generator.state = e.rng_state
                slot = _Slot(req=r, next_token=e.next_token,
                             produced=e.produced, tokens=list(e.tokens),
                             rng=rng, pos=e.pos)
                if self.spec_k:
                    slot.spec_k = self.spec_k
                    slot.session = self.drafter.begin(
                        [int(t) for t in r.prompt] + slot.tokens[:1],
                        slot=slot_i, rid=r.rid)
                    if len(slot.tokens) > 1:
                        slot.session.extend(slot.tokens[1:])
                self._slots[slot_i] = slot
                del self._parked[r.rid]
            self.metrics["restore_s"] += time.perf_counter() - t_start
            return leftover

    def _restore_row(self, e, slot_i: int) -> None:
        """Scatter one parked request's stored leaves into ``slot_i``
        through the ``_insert`` program: a one-row sub-state padded to
        the bucket of its position, the shape a one-row prefill of that
        bucket inserts, so a warm engine restores without a new trace."""
        state = self._state
        cache_len = (state.cache_k.shape[kv_pos_axis(state)]
                     if state.cache_k is not None else None)
        bk = self._bucket(e.pos)
        if cache_len is not None and bk < e.pos:
            bk = cache_len         # non-pow2 max_seq tail: one-off shape
        fields: Dict[str, Any] = {}
        for name in state._fields:
            leaf = getattr(state, name)
            if leaf is None:
                fields[name] = None
            elif name == "pos":
                fields[name] = np.array([e.pos], np.int32)
            elif name in self._KV_LEAVES:
                fields[name] = slot_kv_leaf(leaf, bk, e.leaves.get(name))
            else:
                fields[name] = np.zeros(
                    (leaf.shape[0], 1) + tuple(leaf.shape[2:]), leaf.dtype)
                if name in e.leaves:
                    fields[name][:, 0] = e.leaves[name]
        self._state = self._insert(state, type(state)(**fields),
                                   np.array([slot_i], np.int32))

    # -- backpressure / fault injection -------------------------------------

    def _shed(self, r: Request, done: List[Request], status: str) -> None:
        """Terminal no-service disposition: empty output, counted."""
        r.status = status
        r.output = np.zeros((0,), np.int32)
        r.done_at = time.monotonic()
        self.metrics["shed_count" if status == "shed"
                     else "timeout_count"] += 1
        self.events.append((status, r.rid, -1,
                            int(self.metrics["decode_steps"])))
        done.append(r)

    def _enqueue(self, r: Request, done: List[Request]) -> None:
        """Admit an arrival to the bounded waiting queue, shedding per
        the configured policy on overflow."""
        mq = self.config.max_queue
        w = self._waiting
        if mq is None or len(w) < mq:
            w.append(r)
        else:
            pol = self.config.admission_policy
            if pol == "reject-new":
                self._shed(r, done, "shed")
            elif pol == "shed-oldest":
                victim = w.popleft()
                w.append(r)
                self._shed(victim, done, "shed")
            else:                       # shed-lowest-budget
                lo = min(range(len(w)),
                         key=lambda i: w[i].max_new_tokens)
                if w[lo].max_new_tokens < r.max_new_tokens:
                    victim = w[lo]
                    del w[lo]
                    w.append(r)
                    self._shed(victim, done, "shed")
                else:                   # ties shed the newcomer
                    self._shed(r, done, "shed")
        self.metrics["queue_depth"] = len(w)
        self.metrics["peak_queue_depth"] = max(
            self.metrics["peak_queue_depth"], len(w))

    def _sweep_deadlines(self, done: List[Request]) -> None:
        """Expire deadlined requests: waiting ones shed outright; live
        ones retire gracefully with their partial output."""
        now = time.monotonic()
        w = self._waiting
        for _ in range(len(w)):         # rotate in place, order kept
            r = w.popleft()
            if (r.deadline_s is not None
                    and now - r.submitted_at >= r.deadline_s):
                self._shed(r, done, "timeout")
            else:
                w.append(r)
        for i, slot in enumerate(self._slots):
            if slot is None:
                continue
            r = slot.req
            if (r.deadline_s is not None
                    and now - r.submitted_at >= r.deadline_s):
                r.status = "timeout"
                self.metrics["timeout_count"] += 1
                self._retire(i, slot, done)

    def _tick(self) -> None:
        """Per-iteration housekeeping: heartbeat, snapshot cadence, fault
        injection.  Runs *after* the decode step so snapshots capture a
        consistent post-step state; the injected kill does NOT snapshot
        first — hard-kill semantics, forcing restore to replay from the
        last cadence snapshot (replayed steps are deterministic, so the
        resumed outputs stay bit-identical)."""
        if self.heartbeat is not None:
            self.heartbeat()
        ds = int(self.metrics["decode_steps"])
        ev = self.config.snapshot_every
        if (ev and self.config.snapshot_dir is not None and ds
                and ds % ev == 0 and ds != self._last_snap_step):
            self.snapshot()
            self._last_snap_step = ds
        if (self.config.kill_at_step is not None and not self._kill_fired
                and ds >= self.config.kill_at_step):
            self._kill_fired = True
            raise WorkerKilled(
                f"injected fault: worker killed after decode step {ds}")

    # -- the loop -----------------------------------------------------------

    def serve(self, requests: List[Request]) -> List[Request]:
        """Run the trace to completion; returns requests in finish order.

        Requests become visible to the scheduler at ``arrival_s`` seconds
        after the call (0 = immediately); every request is served —
        over-budget requests raise instead of being dropped — unless a
        backpressure policy (``max_queue``/``deadline_s``) explicitly
        sheds it, in which case it returns with a terminal ``status`` and
        empty/partial output.  Requests whose rid matches a
        :meth:`restore_snapshot` parked entry resume from their
        snapshotted state instead of prefilling.
        """
        self._validate(requests)
        if self._state is None:
            self._state = self._init_state()
        # events and the averaged metrics (queue_wait_s, slot_occupancy)
        # describe this call's trace; the token/step counters accumulate
        # over the engine lifetime.
        self.events = []
        # monotonic timestamp after every decode step (this call only):
        # consecutive diffs are the decode-stall distribution the mesh
        # bench reads (a long inline prefill shows up as one huge gap)
        self.step_walls: List[float] = []
        self._occ_num = self._occ_den = 0
        t0 = time.monotonic()
        for r in requests:
            r.submitted_at = t0 + r.arrival_s
            r.admit_started_at = None
            r.token_times = []
        # pending = not yet arrived; waiting = arrived, unadmitted (the
        # bounded admission queue).  Instance attributes so a mid-trace
        # snapshot persists them alongside the slots.
        self._pending = collections.deque(
            sorted(requests, key=lambda r: (r.arrival_s, r.rid)))
        self._waiting = collections.deque()
        done: List[Request] = []
        self._done_live = done

        while (self._pending or self._waiting or self._admissions_inflight()
               or any(s is not None for s in self._slots)):
            now_rel = time.monotonic() - t0
            while (self._pending
                   and self._pending[0].arrival_s <= now_rel):
                self._enqueue(self._pending.popleft(), done)
            self._sweep_deadlines(done)
            # land any prefills the worker pool finished since last step
            # (mesh engine; inline engines never have admissions in flight)
            self._poll_admissions(done)

            # admission: refill free slots from the waiting queue;
            # snapshot-restored rids re-enter through their saved state
            free = self._free_slots()
            group: List[Request] = []
            while self._waiting and len(group) < len(free):
                group.append(self._waiting.popleft())
            admitted_any = False
            if group:
                now = time.monotonic()
                for r in group:
                    r.admit_started_at = now
                parked = [r for r in group if r.rid in self._parked]
                fresh = [r for r in group if r.rid not in self._parked]
                nfree = free
                if parked:
                    leftover = self._admit_restored(parked, nfree, done)
                    for r in reversed(leftover):
                        self._waiting.appendleft(r)
                    n_placed = len(parked) - len(leftover)
                    admitted_any = n_placed > 0
                    nfree = nfree[n_placed:]
                if fresh and self.paged:
                    # extend-admission; requests the pool cannot hold yet
                    # go back to the queue head (order preserved) and wait
                    # for a retirement to return blocks
                    leftover = self._admit_paged(fresh, nfree, done)
                    for r in reversed(leftover):
                        self._waiting.appendleft(r)
                    admitted_any = (admitted_any
                                    or len(leftover) < len(fresh))
                elif fresh:
                    self._admit(fresh, nfree, done)
                    admitted_any = True
            self.metrics["queue_depth"] = len(self._waiting)

            active = [i for i, s in enumerate(self._slots) if s is not None]
            if (group and not admitted_any and not active
                    and not self._admissions_inflight()):
                raise RuntimeError(
                    "block pool exhausted: no queued request fits "
                    "with every slot idle; raise num_blocks")
            if not active:
                if self._admissions_inflight():
                    # nothing to decode until a prefill worker delivers
                    with TraceAnnotation("serve.idle"):
                        time.sleep(0.0005)
                elif self._pending and not self._waiting:
                    # idle: wait for the next arrival
                    with TraceAnnotation("serve.idle"):
                        time.sleep(min(
                            0.005,
                            max(0.0, self._pending[0].arrival_s
                                - (time.monotonic() - t0))))
                continue

            with TraceAnnotation(
                    "serve.decode",
                    step=int(self.metrics["decode_steps"]) + 1):
                if self.spec_k:
                    # speculative step: draft k per slot, verify k+1 at
                    # once, commit a variable 0..k+1 advance per slot
                    # (falls back to a plain step when no slot has
                    # anything worth verifying)
                    self._spec_step(active, done)
                else:
                    self._plain_step(active, done)
            self.step_walls.append(time.monotonic())
            if self._admissions_inflight():
                # a decode step ran while a prefill was still in flight —
                # the prefill/decode split working as intended (always 0
                # on the inline admission path)
                self.metrics["overlap_steps"] += 1
            # heartbeat + snapshot cadence + injected faults (may raise
            # WorkerKilled out of this call — the supervisor's job)
            self._tick()

        self.metrics["queue_depth"] = 0
        waits = [r.admit_started_at - r.submitted_at for r in requests
                 if r.admit_started_at is not None]
        self.metrics["queue_wait_s"] = sum(waits) / max(len(waits), 1)
        self.metrics["slot_occupancy"] = self._occ_num / max(self._occ_den, 1)
        self.metrics["spec_acceptance"] = (
            self.metrics["draft_accepted"]
            / max(self.metrics["draft_tokens"], 1))
        self.metrics["tokens_per_step"] = (
            self.metrics["decode_tokens"]
            / max(self.metrics["decode_steps"], 1))
        self.metrics["wall_s"] = time.monotonic() - t0
        self.metrics["tok_s"] = (
            sum(len(r.output) for r in done if r.output is not None)
            / max(self.metrics["wall_s"], 1e-9))
        # tiered drafters expose which tier served each drafting slot-step
        self.metrics["model_drafts"] = int(
            getattr(self.drafter, "model_dispatches", 0))
        self.metrics["fallback_drafts"] = int(
            getattr(self.drafter, "fallback_dispatches", 0))
        return done


class GangServeEngine:
    """The pre-continuous-batching scheduler, kept as the benchmark
    baseline: packs up to ``max_batch`` requests, prefills them together
    (left-padded to the longest prompt — a fresh trace per composition),
    decodes the gang in lockstep until the *slowest* request finishes, and
    only then admits more.  ``benchmarks/serve_bench.py`` replays the same
    trace through this and :class:`ServeEngine` to measure the gap."""

    def __init__(self, model: Model, params, max_batch: int = 8,
                 max_seq: int = 256, greedy: bool = True):
        self.model = model
        self.params = params
        self.max_batch = max_batch
        self.max_seq = max_seq
        self.tuned_blocks = kernel_common.load_tuned_table()
        self._prefill = jax.jit(
            lambda p, batch: model.prefill(p, batch))
        self._decode = jax.jit(
            lambda p, st, batch: model.decode_step(p, st, batch))
        self.metrics: Dict[str, float] = {"prefill_tokens": 0,
                                          "decode_tokens": 0}

    def _pad_prompts(self, reqs: List[Request]) -> Dict[str, jnp.ndarray]:
        cfg = self.model.cfg
        s = max(len(r.prompt) for r in reqs)
        b = len(reqs)
        if cfg.input_kind == "tokens":
            toks = np.zeros((b, s), np.int32)
            for i, r in enumerate(reqs):
                toks[i, s - len(r.prompt):] = r.prompt  # left-pad
            return {"tokens": jnp.asarray(toks)}
        d = cfg.d_model
        frames = np.zeros((b, s, d), np.float32)
        for i, r in enumerate(reqs):
            frames[i, s - len(r.prompt):] = r.prompt
        return {"frames": jnp.asarray(frames)}

    def serve(self, requests: List[Request]) -> List[Request]:
        """Gang scheduling: admit up to max_batch, prefill together,
        decode in lockstep, admit the next gang when all finish."""
        pending = sorted(requests, key=lambda r: (r.arrival_s, r.rid))
        t0 = time.monotonic()
        for r in pending:
            r.submitted_at = t0 + r.arrival_s
        done: List[Request] = []

        while pending:
            batch = pending[:self.max_batch]
            pending = pending[self.max_batch:]
            # gang admission waits until every member of the batch has
            # arrived (it cannot start a partial gang and refill later) —
            # keeps latencies non-negative and wall clocks comparable with
            # the continuous engine replaying the same arrival trace.
            wait = t0 + max(r.arrival_s for r in batch) - time.monotonic()
            if wait > 0:
                time.sleep(wait)
            inputs = self._pad_prompts(batch)
            logits, state = self._prefill(self.params, inputs)
            self.metrics["prefill_tokens"] += sum(len(r.prompt)
                                                  for r in batch)
            b = len(batch)
            outs = [[] for _ in range(b)]
            next_tok = jnp.argmax(logits.reshape(b, -1), axis=-1)
            steps = max(r.max_new_tokens for r in batch)
            for t in range(steps):
                for i in range(b):
                    if t < batch[i].max_new_tokens:
                        outs[i].append(int(next_tok[i]))
                if self.model.cfg.input_kind == "tokens":
                    nb = {"tokens": next_tok[:, None].astype(jnp.int32)}
                else:  # frame stubs decode over embedded tokens
                    nb = {"frames": jnp.zeros(
                        (b, 1, self.model.cfg.d_model), jnp.float32)}
                logits, state = self._decode(self.params, state, nb)
                v = logits.reshape(b, -1)
                next_tok = jnp.argmax(v, axis=-1)
                self.metrics["decode_tokens"] += b
            for i, r in enumerate(batch):
                r.output = np.asarray(outs[i][:r.max_new_tokens])
                r.done_at = time.monotonic()
                done.append(r)
        return done
