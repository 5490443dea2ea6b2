"""Drafters for speculative decoding: propose k cheap continuation tokens
per slot, which one bucketed ``verify_step`` call scores all at once.

The engine contract (``runtime/serve_loop.py``) is deliberately tiny: a
drafter opens one :class:`DraftSession` per request via
:meth:`Drafter.begin` (seeded with the prompt + first token, and told
which engine ``slot``/``rid`` it is drafting for), the engine feeds every
accepted token back through :meth:`DraftSession.extend`, asks for
proposals with :meth:`DraftSession.draft` (or, for batched drafters, one
:meth:`Drafter.draft_all` call covering every drafting slot per engine
step), and calls :meth:`DraftSession.close` when the request retires.
Returning fewer than ``k`` tokens — or none — is always safe: the engine
pads the verify window and unproposed positions simply never match,
degrading to plain decode for that step.

Two drafters ship:

* :class:`NGramDrafter` — the zero-parameter baseline (prompt-lookup /
  n-gram decoding): find the most recent earlier occurrence of the longest
  suffix n-gram of the context and propose the tokens that followed it.
  No model FLOPs; O(k · max_ngram) dict operations per step.

* :class:`DraftModelDrafter` — a tiny LM drafts by actually decoding.  It
  holds one batched decode state (``model_zoo`` ``prefill`` /
  ``slot_update`` / ``decode_step``, the same seam the main engine uses)
  with one row per engine slot, and advances **all drafting slots in one
  jitted decode step per draft position** — the draft cost is one tiny
  batched program per position, not one program per slot.  Prompt seeding
  buckets to powers of two exactly like the main engine, so the drafter
  adds ``len(buckets)`` prefill traces and one decode trace, ever.  Slots
  where the draft model has no signal (top-1 probability below
  ``min_conf``) tier down to an :class:`NGramDrafter` fallback.

``make_drafter`` is the factory the CLI flags route through.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np


class DraftSession:
    """Per-request drafting state.  Subclasses override the first two.

    Rollback contract: ``draft`` must not commit its own proposals — only
    tokens fed back through ``extend`` are part of the request's stream.
    A drafter is free to *speculatively* advance internal state during
    ``draft`` as long as the next ``extend``/``draft`` observes exactly
    the extended stream (the n-gram session keeps an undo log; the
    draft-model session re-synchronises its decode position).
    """

    def extend(self, tokens: Sequence[int]) -> None:
        """Feed tokens the engine committed (accepted drafts + the
        correction/bonus token of each verify step)."""
        raise NotImplementedError

    def draft(self, k: int) -> List[int]:
        """Propose 0..k continuation tokens (python ints)."""
        raise NotImplementedError

    def close(self) -> None:
        """The request retired: release any per-slot resources.  Safe to
        call more than once; the default is a no-op."""


class Drafter:
    """Drafter factory: one :class:`DraftSession` per request.

    ``begin`` receives the engine's ``slot`` index and request id so a
    batched drafter can key device-side state by slot; drafters that keep
    everything host-side ignore them.  A drafter with ``batched = True``
    additionally implements :meth:`draft_all`, which the engine calls
    once per step instead of per-slot :meth:`DraftSession.draft`.
    """

    batched = False

    def begin(self, context: Sequence[int], slot: Optional[int] = None,
              rid: Optional[int] = None) -> DraftSession:
        """``context``: the request's prompt + first emitted token."""
        raise NotImplementedError

    def draft_all(self, want: Dict[int, int]) -> Dict[int, List[int]]:
        """Batched drafting: ``want`` maps slot index -> k; returns
        slot -> 0..k proposed tokens.  Only for ``batched`` drafters."""
        raise NotImplementedError


class NGramDrafter(Drafter):
    """Prompt-lookup drafter: longest-suffix n-gram matching.

    For ``n = max_ngram .. min_ngram``, take the context's final n-gram
    and find its most recent *earlier* occurrence; on a hit, propose the
    tokens that followed it, then re-match on the extended pseudo-context
    until ``k`` tokens are proposed.  ``max_context`` bounds the seed
    context so session setup stays O(max_context) regardless of prompt
    length.
    """

    def __init__(self, max_ngram: int = 3, min_ngram: int = 1,
                 max_context: int = 512):
        if not 1 <= min_ngram <= max_ngram:
            raise ValueError(f"need 1 <= min_ngram <= max_ngram, got "
                             f"{min_ngram}..{max_ngram}")
        self.max_ngram = max_ngram
        self.min_ngram = min_ngram
        self.max_context = max_context

    def begin(self, context: Sequence[int], slot: Optional[int] = None,
              rid: Optional[int] = None) -> "_NGramSession":
        return _NGramSession(self, context)

    # convenience for tests / one-shot use
    def draft(self, context: Sequence[int], k: int) -> List[int]:
        return self.begin(context).draft(k)


class _NGramSession(DraftSession):
    """Incremental n-gram index over one request's context.

    ``last`` maps an n-gram tuple to the (latest, previous) *end*
    positions of its occurrences in ``ctx``.  ``extend`` registers the
    appended tokens; ``draft`` speculatively extends the context with its
    own proposals (recording an undo log) so a run or cycle keeps
    proposing through the whole window, then rolls the index back.
    """

    def __init__(self, drafter: NGramDrafter, context: Sequence[int]):
        self.max_ngram = drafter.max_ngram
        self.min_ngram = drafter.min_ngram
        self.ctx: List[int] = [int(t) for t in
                               context[-drafter.max_context:]]
        self.last: Dict[Tuple[int, ...],
                        Tuple[int, Optional[Tuple[int, ...]]]] = {}
        for end in range(1, len(self.ctx) + 1):
            self._register(end, None)

    def _register(self, end: int, undo: Optional[list]) -> None:
        ctx = self.ctx
        for n in range(self.min_ngram, self.max_ngram + 1):
            if end >= n:
                key = tuple(ctx[end - n:end])
                prev = self.last.get(key)
                if undo is not None:
                    undo.append((key, prev))
                self.last[key] = (end, prev)

    def extend(self, tokens: Sequence[int]) -> None:
        for t in tokens:
            self.ctx.append(int(t))
            self._register(len(self.ctx), None)

    def _lookup(self, k: int) -> List[int]:
        ctx = self.ctx
        n_ctx = len(ctx)
        for n in range(min(self.max_ngram, n_ctx - 1), self.min_ngram - 1,
                       -1):
            hit = self.last.get(tuple(ctx[n_ctx - n:]))
            if hit is None:
                continue
            # most recent *earlier* occurrence: the suffix registers
            # itself at n_ctx, so fall back to the previous occurrence
            end = hit[0]
            if end == n_ctx:
                if hit[1] is None:
                    continue
                end = hit[1][0]
            return ctx[end:end + k]
        return []

    def draft(self, k: int) -> List[int]:
        out: List[int] = []
        undo: list = []
        while len(out) < k:
            cont = self._lookup(k - len(out))
            if not cont:
                break
            for t in cont:
                out.append(t)
                self.ctx.append(t)
                self._register(len(self.ctx), undo)
        # roll the speculative extension back: the engine only commits
        # verified tokens, via extend()
        if out:
            del self.ctx[len(self.ctx) - len(out):]
            for key, prev in reversed(undo):
                if prev is None:
                    del self.last[key]
                else:
                    self.last[key] = prev
        return out


class DraftModelDrafter(Drafter):
    """Tiny-LM drafter over the ``model_zoo`` slot-state seam.

    One batched decode state mirrors the engine's slots (row ``slot`` of
    the draft cache belongs to engine slot ``slot``); each engine step
    runs the draft model forward once per draft position **across all
    drafting slots at once** — a single jitted ``decode_step`` trace with
    fixed ``(max_batch, 1)`` shape, mirroring the main engine's trace
    discipline.  Prompt seeding prefills through the same pow-2 buckets.

    The draft model must be a pure-KV-cache family (attention only, no
    recurrent leaves) with a linear cache: rollback after rejected
    proposals is then just a position reset — stale speculative writes
    sit past the committed position, invisible under the age mask until
    overwritten (the same invariant the main engine's verify relies on).

    Tiering: a slot whose top-1 draft probability drops below
    ``min_conf`` stops contributing draft-model tokens for the step; if
    it contributed none, its per-request :class:`NGramDrafter` fallback
    session proposes instead.  ``model_dispatches`` /
    ``fallback_dispatches`` count which tier served each drafting slot.
    """

    batched = True
    _SUSPEND_AFTER = 8   # consecutive all-fallback rounds before suspending
    _PROBE_EVERY = 64    # suspended rounds between single-slot probes
    _RESEED_FEEDS = 8    # catch-up gap beyond which re-seeding wins

    def __init__(self, model, params, max_batch: int, max_seq: int,
                 min_conf: float = 0.10, min_bucket: int = 16,
                 fallback: Optional[Drafter] = None, headroom: int = 64):
        cfg = model.cfg
        if cfg.input_kind != "tokens" or cfg.n_codebooks:
            raise ValueError("draft model needs a plain token vocabulary")
        self.model = model
        self.params = params
        self.max_batch = int(max_batch)
        self.min_conf = float(min_conf)
        self.min_bucket = int(min_bucket)
        self.fallback = NGramDrafter() if fallback is None else fallback
        self.ops = model.cache_ops()
        # headroom past max_seq: speculative continuations near a
        # request's end of budget must not ring-wrap the draft cache
        self._alloc = int(max_seq) + int(headroom)
        abs_state = self.ops.init_slot_state(self.max_batch, self._alloc,
                                             abstract=True)
        for name in ("x_prev", "cm_prev", "wkv", "conv_tail", "ssm_h"):
            if getattr(abs_state, name, None) is not None:
                raise ValueError(
                    f"draft model family {cfg.family!r} keeps recurrent "
                    f"state ({name}); the drafter's position-reset "
                    f"rollback needs a pure-KV-cache (attention) family")
        from repro.models.transformer import kv_pos_axis
        if (abs_state.cache_k is not None
                and abs_state.cache_k.shape[kv_pos_axis(abs_state)]
                < self._alloc):
            raise ValueError("draft model allocates a ring cache; the "
                             "drafter needs a linear cache for rollback")
        self._bucket_cap = 1 << (self._alloc.bit_length() - 1)
        self._state = None
        # per-slot host mirror: the committed token stream, how many of
        # its tokens have valid K/V in the draft cache (cache_pos), and
        # the in-flight draft bookkeeping extend() resolves
        self._stream: Dict[int, List[int]] = {}
        self._cache_pos: Dict[int, int] = {}
        self._inflight: Dict[int, Tuple[int, int, str]] = {}
        self._ngram: Dict[int, DraftSession] = {}
        # tier dispatch counters (per drafting slot-step)
        self.model_dispatches = 0
        self.fallback_dispatches = 0
        # tier suspension: after _SUSPEND_AFTER consecutive draft_all
        # rounds in which the model tier placed nothing (every drafting
        # slot tiered down), stop dispatching the draft model and serve
        # the fallback directly — its k sequential decode dispatches per
        # round are pure overhead on an uninformative model.  Every
        # _PROBE_EVERY suspended rounds a single-slot probe runs through
        # the model tier; any model-tier yield lifts the suspension.
        # The cache catches up lazily: suspended rounds leave _cache_pos
        # untouched, and the next real round reseeds/feeds the gap.
        self._dry_rounds = 0
        self._suspended_rounds = 0
        # retrace telemetry, same contract as the engine's trace_counts
        import collections
        import jax
        self.trace_counts = collections.Counter()

        def _prefill_fn(p, inputs, lengths):
            self.trace_counts["draft_prefill"] += 1
            return model.prefill(p, inputs, headroom=0, lengths=lengths)

        def _insert_fn(st, sub, slots):
            self.trace_counts["draft_insert"] += 1
            return self.ops.slot_update(st, sub, slots)

        def _decode_fn(p, st, toks):
            self.trace_counts["draft_decode"] += 1
            import jax.numpy as jnp
            logits, st2 = model.decode_step(p, st, {"tokens": toks})
            lg = logits.reshape(toks.shape[0], -1).astype(jnp.float32)
            ids = jnp.argmax(lg, axis=-1).astype(jnp.int32)
            conf = jnp.max(jax.nn.softmax(lg, axis=-1), axis=-1)
            return ids, conf, st2

        def _set_pos_fn(st, posv):
            self.trace_counts["draft_reset"] += 1
            return st._replace(pos=posv)

        self._prefill = jax.jit(_prefill_fn)
        self._insert = jax.jit(_insert_fn)
        self._decode = jax.jit(_decode_fn)
        self._set_pos = jax.jit(_set_pos_fn)

    # -- session plumbing ---------------------------------------------------

    def _bucket(self, n: int) -> int:
        b = self.min_bucket
        while b < n:
            b *= 2
        return min(b, self._bucket_cap)

    def begin(self, context: Sequence[int], slot: Optional[int] = None,
              rid: Optional[int] = None) -> "DraftSession":
        if slot is None:
            # no slot identity: nothing to key device state by — serve
            # this request from the fallback tier alone
            return self.fallback.begin(context, slot=slot, rid=rid)
        # host-only: device seeding is deferred to the first real draft
        # round (draft_all reseeds any slot whose gap outgrew the feeds),
        # so admissions while the model tier is suspended cost nothing
        self._stream[slot] = [int(t) for t in context]
        self._cache_pos[slot] = 0
        self._inflight.pop(slot, None)
        self._ngram[slot] = self.fallback.begin(context, slot=slot, rid=rid)
        return _DraftModelSession(self, slot)

    def warm(self) -> None:
        """Pre-compile every pow-2 prefill bucket plus the decode and
        position-reset traces.  Call before serving (a no-op once any
        session is live): benchmark warmup traces are short, so without
        this the first long stream pays a bucket compile mid-replay."""
        if self._stream:
            return
        if self._state is None:
            self._state = self.ops.init_slot_state(self.max_batch,
                                                   self._alloc)
        lengths = np.ones((self.max_batch,), np.int32)
        # scatter index == max_batch is out of bounds -> dropped write:
        # compiles the trace without touching any slot
        slots = np.full((self.max_batch,), self.max_batch, np.int32)
        b = self.min_bucket
        while True:
            arr = np.zeros((self.max_batch, b), np.int32)
            _, sub = self._prefill(self.params, {"tokens": arr}, lengths)
            self._state = self._insert(self._state, sub, slots)
            if b >= self._bucket_cap:
                break
            b *= 2
        toks = np.zeros((self.max_batch, 1), np.int32)
        _, _, self._state = self._decode(self.params, self._state, toks)
        self._state = self._set_pos(
            self._state, np.zeros((self.max_batch,), np.int32))

    def _reseed(self, slot: int) -> None:
        """(Re)prefill a slot's draft cache from its committed stream.

        One bucketed prefill + scatter caches everything but the newest
        token — used lazily at a slot's first real draft round and
        whenever the catch-up gap after suspended rounds outgrows what
        lockstep feeds amortize."""
        if self._state is None:
            self._state = self.ops.init_slot_state(self.max_batch,
                                                   self._alloc)
        ctx = self._stream[slot]
        seed = ctx[:-1][:self._bucket_cap]  # cache all but the last token
        bucket = self._bucket(max(len(seed), 1))
        arr = np.zeros((self.max_batch, bucket), np.int32)
        lengths = np.ones((self.max_batch,), np.int32)
        slots = np.full((self.max_batch,), self.max_batch, np.int32)
        arr[0, :len(seed)] = seed
        lengths[0] = max(len(seed), 1)
        slots[0] = slot
        _, sub = self._prefill(self.params, {"tokens": arr}, lengths)
        self._state = self._insert(self._state, sub, slots)
        self._cache_pos[slot] = len(seed)

    # -- the batched draft step ---------------------------------------------

    def draft_all(self, want: Dict[int, int]) -> Dict[int, List[int]]:
        want = {s: k for s, k in want.items() if k > 0
                and s in self._stream}
        if not want:
            return {}
        host_only = None     # slots served by the fallback, device untouched
        if self._dry_rounds >= self._SUSPEND_AFTER:
            self._suspended_rounds += 1
            if self._suspended_rounds % self._PROBE_EVERY:
                host_only = set(want)
            else:
                # probe the model tier with the single cheapest slot —
                # one reseed + k decode steps, not a full-batch round
                probe = min(want, key=lambda s: (len(self._stream[s])
                                                 - self._cache_pos[s]))
                host_only = set(want) - {probe}
        if host_only:
            # model tier suspended: serve the fallback without touching
            # the device; _cache_pos stays put (no _inflight entry ->
            # extend() leaves it unchanged) and the next real round's
            # reseed/feeds replay the gap
            host_out: Dict[int, List[int]] = {}
            for s in sorted(host_only):
                self.fallback_dispatches += 1
                host_out[s] = self._ngram[s].draft(want[s])
            want = {s: k for s, k in want.items() if s not in host_only}
            if not want:
                return host_out
        else:
            host_out = {}
        b = self.max_batch
        rows = sorted(want)
        # a never-seeded slot (begin defers device work) or one far
        # behind (lazy catch-up after suspended rounds) is cheaper to
        # (re)seed with one bucketed prefill than to replay
        # token-by-token through the lockstep loop — and prefill keeps
        # the context's FP accumulation order identical to begin-time
        # seeding
        for s in rows:
            if (self._cache_pos[s] == 0 and len(self._stream[s]) > 1) \
                    or (len(self._stream[s]) - self._cache_pos[s]
                        > self._RESEED_FEEDS):
                self._reseed(s)
        if self._state is None:     # every row small enough to feed inline
            self._state = self.ops.init_slot_state(self.max_batch,
                                                   self._alloc)
        # feeds before proposing: the not-yet-cached stream suffix (>= 1:
        # the newest committed token is always pending)
        feeds = {s: len(self._stream[s]) - self._cache_pos[s] for s in rows}
        steps = max(feeds[s] + want[s] - 1 for s in rows)
        # reset drafting rows to their committed position; live rows that
        # sit this step out keep theirs, so ride-along writes land past
        # their valid prefix (junk-permitted, rewritten on next catch-up)
        pos0 = np.zeros((b,), np.int32)
        for s, cp in self._cache_pos.items():
            if s < b:
                pos0[s] = cp
        self._state = self._set_pos(self._state, pos0)
        outs: Dict[int, List[int]] = {s: [] for s in rows}
        alive = {s: True for s in rows}
        toks = np.zeros((b, 1), np.int32)
        consumed = {s: 0 for s in rows}   # own proposals consumed
        for s in rows:
            toks[s, 0] = self._stream[s][self._cache_pos[s]]
        for step in range(steps):
            ids_d, conf_d, self._state = self._decode(self.params,
                                                      self._state, toks)
            ids = np.asarray(ids_d)
            conf = np.asarray(conf_d)
            nxt = np.zeros((b, 1), np.int32)
            any_alive = False
            for s in rows:
                fed = step + 1
                if fed < feeds[s]:
                    # still catching up on committed tokens
                    nxt[s, 0] = self._stream[s][self._cache_pos[s] + fed]
                    any_alive = True
                    continue
                if alive[s] and len(outs[s]) < want[s] \
                        and conf[s] >= self.min_conf:
                    outs[s].append(int(ids[s]))
                else:
                    alive[s] = False
                if alive[s] and len(outs[s]) < want[s]:
                    any_alive = True
                # feed the model its own greedy continuation (rows past
                # their window ride along; their writes roll back)
                nxt[s, 0] = int(ids[s])
                consumed[s] = max(0, fed - feeds[s])
            toks = nxt
            if not any_alive:
                break
        result: Dict[int, List[int]] = {}
        placed = False
        for s in rows:
            self._cache_pos[s] = len(self._stream[s])   # caught up
            if outs[s]:
                self.model_dispatches += 1
                self._inflight[s] = (len(self._stream[s]), consumed[s],
                                     "model")
                result[s] = outs[s]
                placed = True
            else:
                # no signal: tier down to the n-gram fallback
                self.fallback_dispatches += 1
                self._inflight[s] = (len(self._stream[s]), consumed[s],
                                     "fallback")
                result[s] = self._ngram[s].draft(want[s])
        if placed:
            self._dry_rounds = 0
            self._suspended_rounds = 0
        else:
            self._dry_rounds += 1
        result.update(host_out)
        return result

    # -- called by the per-slot session -------------------------------------

    def _extend(self, slot: int, tokens: Sequence[int]) -> None:
        toks = [int(t) for t in tokens]
        stream = self._stream.get(slot)
        if stream is None:
            return
        flight = self._inflight.pop(slot, None)
        stream.extend(toks)
        if flight is not None:
            base, consumed, tier = flight
            accepted = len(toks) - 1
            if tier == "model":
                # accepted proposals were already decoded by the draft
                # model itself — their K/V is valid; anything past the
                # consumed count (or rejected) re-feeds next round
                self._cache_pos[slot] = base + min(accepted, consumed)
            else:
                self._cache_pos[slot] = base
        ng = self._ngram.get(slot)
        if ng is not None:
            ng.extend(toks)

    def _close(self, slot: int) -> None:
        self._stream.pop(slot, None)
        self._cache_pos.pop(slot, None)
        self._inflight.pop(slot, None)
        ng = self._ngram.pop(slot, None)
        if ng is not None:
            ng.close()


class _DraftModelSession(DraftSession):
    """Slot-bound view over a :class:`DraftModelDrafter`.

    ``draft`` exists for API completeness (and for engines that do not
    batch): it runs a one-slot ``draft_all``.  The serving engine calls
    ``Drafter.draft_all`` directly instead.
    """

    def __init__(self, drafter: DraftModelDrafter, slot: int):
        self.drafter = drafter
        self.slot = slot

    def extend(self, tokens: Sequence[int]) -> None:
        self.drafter._extend(self.slot, tokens)

    def draft(self, k: int) -> List[int]:
        return self.drafter.draft_all({self.slot: k}).get(self.slot, [])

    def close(self) -> None:
        self.drafter._close(self.slot)


def make_drafter(kind: str, *, model=None, params=None,
                 target=None, target_params=None,
                 max_batch: int = 8, max_seq: int = 256,
                 seed: int = 0, **kwargs) -> Drafter:
    """Factory behind ``--drafter``: ``"ngram"`` or ``"draft_model"``.

    ``"draft_model"`` drafts with ``model``/``params`` when given;
    otherwise it derives a tiny dense LM from ``target`` (the serving
    model — vocabulary must match) via
    :func:`repro.models.model_zoo.draft_arch` and initialises it with
    ``seed``.  Extra ``kwargs`` pass through to the drafter class.
    """
    if kind == "ngram":
        return NGramDrafter(**kwargs)
    if kind == "draft_model":
        if model is None:
            if target is None:
                raise ValueError("draft_model needs either model=/params= "
                                 "or target= (the serving model) to "
                                 "derive a tiny draft LM from")
            import jax
            from repro.models.model_zoo import build_model, draft_arch
            model = build_model(draft_arch(target.cfg))
            params = model.init(jax.random.PRNGKey(seed))
        elif params is None:
            raise ValueError("draft_model with model= also needs params=")
        return DraftModelDrafter(model, params, max_batch=max_batch,
                                 max_seq=max_seq, **kwargs)
    raise ValueError(f"unknown drafter kind {kind!r}; expected 'ngram' or "
                     f"'draft_model'")
