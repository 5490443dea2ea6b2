"""GQA attention: naive, chunked (flash-style online softmax), and decode.

The chunked path is the memory-roofline workhorse for prefill_32k — it never
materialises the (S x S) score matrix, scanning KV blocks with running
max/sum statistics (the standard online-softmax recurrence) in pure JAX so
it lowers/shards through pjit like everything else.
"""
from __future__ import annotations

from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig, ExecutionPolicy
from repro.core.quant_cache import dequantize_blocked, quantize_blocked
from repro.models import layers as L
from repro.parallel.sharding import constrain, get_abstract_mesh

Array = jax.Array

NEG_INF = -1e30
# FxP8 (Q3.4) KV-cache quantization constants — the paper's 8-bit format
# applied to the decode cache (kv_cache_bits=8).
KV_Q_SCALE = 16.0


def quantize_kv(x: Array) -> Array:
    return jnp.clip(jnp.round(x.astype(jnp.float32) * KV_Q_SCALE),
                    -127, 127).astype(jnp.int8)


def dequantize_kv(x: Array, dtype) -> Array:
    if x.dtype != jnp.int8:
        return x.astype(dtype)
    return (x.astype(jnp.float32) * (1.0 / KV_Q_SCALE)).astype(dtype)


def _causal_window_mask(q_pos: Array, k_pos: Array, window) -> Array:
    """True = attend.  q_pos (Sq,), k_pos (Sk,); window traced or python."""
    d = q_pos[:, None] - k_pos[None, :]
    mask = d >= 0
    return jnp.logical_and(mask, d < window)


def _split_heads(x: Array, n_heads: int) -> Array:
    b, s, _ = x.shape
    return x.reshape(b, s, n_heads, -1)


class AttnParams(NamedTuple):
    wq: Array
    wk: Array
    wv: Array
    wo: Array
    bq: Optional[Array] = None
    bk: Optional[Array] = None
    bv: Optional[Array] = None


def qkv(x: Array, p: AttnParams, cfg: ArchConfig, pol: ExecutionPolicy,
        positions: Array) -> Tuple[Array, Array, Array]:
    dh = cfg.head_dim_
    q = _split_heads(L.dense(x, p.wq, pol, p.bq), cfg.n_heads)
    k = _split_heads(L.dense(x, p.wk, pol, p.bk), cfg.n_kv_heads)
    v = _split_heads(L.dense(x, p.wv, pol, p.bv), cfg.n_kv_heads)
    if cfg.family != "ssm":
        ang = L.rope_angles(positions, dh, cfg.rope_theta)
        q = L.apply_rope(q, ang)
        k = L.apply_rope(k, ang)
    # TP layout choice: head-sharded attention when heads divide the model
    # axis (no resharding between projection and attention); otherwise
    # query-sequence sharding (k/v replicated) — the misaligned-heads fix
    # recorded in EXPERIMENTS.md #Perf.
    mesh = get_abstract_mesh()
    tp = mesh.shape.get("model", 1) if mesh is not None and not mesh.empty \
        else 1
    if tp > 1 and cfg.n_heads % tp == 0:
        q = constrain(q, ("batch", None, "heads", None))
        k = constrain(k, ("batch", None, "kv_heads", None))
        v = constrain(v, ("batch", None, "kv_heads", None))
    else:
        q = constrain(q, ("batch", "seq", None, None))
        k = constrain(k, ("batch", None, None, None))
        v = constrain(v, ("batch", None, None, None))
    return q, k, v


def naive_attention(q: Array, k: Array, v: Array, cfg: ArchConfig,
                    pol: ExecutionPolicy, q_pos: Array, k_pos: Array,
                    window) -> Array:
    """Materialised-scores attention (small seq / reference)."""
    b, sq, hq, dh = q.shape
    hkv = k.shape[2]
    g = hq // hkv
    qg = q.reshape(b, sq, hkv, g, dh)
    scores = jnp.einsum("bskgd,btkd->bkgst", qg, k) / jnp.sqrt(float(dh))
    mask = _causal_window_mask(q_pos, k_pos, window)
    scores = jnp.where(mask[None, None, None], scores.astype(jnp.float32),
                       NEG_INF)
    probs = L.softmax(scores, pol).astype(q.dtype)
    ctx = jnp.einsum("bkgst,btkd->bskgd", probs, v)
    return ctx.reshape(b, sq, hq, dh)


def chunked_attention(q: Array, k: Array, v: Array, cfg: ArchConfig,
                      pol: ExecutionPolicy, q_pos: Array, k_pos: Array,
                      window, chunk: int) -> Array:
    """Flash-style online-softmax over KV chunks; O(S*chunk) live memory."""
    b, sq, hq, dh = q.shape
    sk = k.shape[1]
    hkv = k.shape[2]
    g = hq // hkv
    chunk = min(chunk, sk)
    assert sk % chunk == 0, (sk, chunk)
    n_chunks = sk // chunk
    qg = q.reshape(b, sq, hkv, g, dh)
    scale = 1.0 / jnp.sqrt(float(dh))

    kc = k.reshape(b, n_chunks, chunk, hkv, dh).transpose(1, 0, 2, 3, 4)
    vc = v.reshape(b, n_chunks, chunk, hkv, dh).transpose(1, 0, 2, 3, 4)
    kpc = k_pos.reshape(n_chunks, chunk)

    def step(carry, xs):
        m_prev, l_prev, o_prev = carry            # (b,hkv,g,sq[,dh])
        k_i, v_i, kp_i = xs
        s = jnp.einsum("bskgd,btkd->bkgst", qg, k_i).astype(jnp.float32) * scale
        mask = _causal_window_mask(q_pos, kp_i, window)
        s = jnp.where(mask[None, None, None], s, NEG_INF)
        m_i = jnp.maximum(m_prev, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_i[..., None])
        alpha = jnp.exp(m_prev - m_i)
        l_i = l_prev * alpha + jnp.sum(p, axis=-1)
        o_i = o_prev * alpha[..., None] + jnp.einsum(
            "bkgst,btkd->bkgsd", p.astype(q.dtype), v_i).astype(jnp.float32)
        return (m_i, l_i, o_i), None

    # carries shard like q: over heads when aligned, else over the query
    # sequence (keeps the online-softmax state at 1/tp per device)
    m0 = constrain(jnp.full((b, hkv, g, sq), NEG_INF, jnp.float32),
                   ("batch", "kv_heads", None, "seq"))
    l0 = constrain(jnp.zeros((b, hkv, g, sq), jnp.float32),
                   ("batch", "kv_heads", None, "seq"))
    o0 = constrain(jnp.zeros((b, hkv, g, sq, dh), jnp.float32),
                   ("batch", "kv_heads", None, "seq", None))
    (m, l, o), _ = jax.lax.scan(step, (m0, l0, o0), (kc, vc, kpc))
    o = o / jnp.maximum(l[..., None], 1e-30)
    ctx = o.transpose(0, 3, 1, 2, 4).reshape(b, sq, hq, dh)
    return ctx.astype(q.dtype)


@jax.named_scope("attention")
def attention(q, k, v, cfg: ArchConfig, pol: ExecutionPolicy, q_pos, k_pos,
              window=None) -> Array:
    window = window if window is not None else jnp.int32(2 ** 30)
    sk = k.shape[1]
    impl = cfg.attn_impl
    if impl == "auto":
        impl = "chunked" if sk > 2048 else "naive"
    if impl == "chunked":
        return chunked_attention(q, k, v, cfg, pol, q_pos, k_pos, window,
                                 cfg.attn_chunk)
    return naive_attention(q, k, v, cfg, pol, q_pos, k_pos, window)


# ---------------------------------------------------------------------------
# Decode (single-token) with a preallocated cache
# ---------------------------------------------------------------------------

def _attend_decode(q: Array, keys: Array, vals: Array, pos: Array,
                   pol: ExecutionPolicy, window) -> Array:
    """Single-token attend over a (B,S,Hkv,dh) key/value view.

    The mask/softmax/einsum half of :func:`decode_attention`, shared by
    the dense and paged layouts: both present the same logical
    (B, S, Hkv, dh) view, so the math (and its bit pattern) is layout-
    independent.
    """
    b, _, hq, dh = q.shape
    s_max = keys.shape[1]
    hkv = keys.shape[2]
    g = hq // hkv
    qg = q.reshape(b, 1, hkv, g, dh)
    scores = jnp.einsum("bskgd,btkd->bkgst", qg, keys) / jnp.sqrt(float(dh))
    # ring-buffer positions: slot t holds absolute position
    #   p_t = t            if t <= pos (current wrap)  [no-wrap case]
    # with wrapping, valid entries are the last min(pos+1, s_max) writes.
    per_row = jnp.ndim(pos) == 1
    t = jnp.arange(s_max)
    age = jnp.mod((pos[:, None] if per_row else pos) - t, s_max)  # 0 = newest
    valid = age < jnp.minimum((pos[:, None] if per_row else pos) + 1, s_max)
    in_window = age < window
    mask = jnp.logical_and(valid, in_window)
    if per_row:                             # (B, S): own history per slot
        mask = mask[:, None, None, None, :]
    else:
        mask = mask[None, None, None, None, :]
    scores = jnp.where(mask, scores.astype(jnp.float32), NEG_INF)
    probs = L.softmax(scores, pol).astype(q.dtype)
    ctx = jnp.einsum("bkgst,btkd->bskgd", probs, vals)
    return ctx.reshape(b, 1, hq, dh)


def _attend_verify(q: Array, keys: Array, vals: Array, posv: Array,
                   pol: ExecutionPolicy, window,
                   old_keys: Optional[Array] = None,
                   old_vals: Optional[Array] = None) -> Array:
    """K-candidate attend over a (B,S,Hkv,dh) view (see verify_attention).

    Shared mask/softmax/einsum half of the verify pass; per-query
    numerics are exactly :func:`_attend_decode` at that position, for
    both the dense and paged layouts.

    With ``old_keys``/``old_vals`` (the pre-write cache view) the cache
    is a **ring**: every candidate write landed at its wrapped slot, so
    a later candidate ``j`` has evicted absolute position
    ``pos + j - s_max`` — an entry that is still inside query ``i``'s
    window for ``j > i``.  Instead of masking those columns out, each
    query selects per-column between the old and new view (old where the
    column holds a strictly-later candidate's write), which restores
    exactly what plain decode attended to at that position.  The select
    happens on the gathered K/V (one fused einsum per call), so the FP
    contraction order over columns — and with it bit-exactness vs plain
    ring decode — is unchanged.
    """
    b, kq, hq, dh = q.shape
    s_max = keys.shape[1]
    hkv = keys.shape[2]
    g = hq // hkv
    offs = jnp.arange(kq, dtype=posv.dtype)
    wpos = posv[:, None] + offs[None, :]                  # (B,K) absolute
    qg = q.reshape(b, kq, hkv, g, dh)
    t = jnp.arange(s_max)
    age = jnp.mod(wpos[..., None] - t, s_max)             # (B,K,S); 0=self
    valid = age < jnp.minimum(wpos[..., None] + 1, s_max)
    in_window = age < window
    # this call's candidate columns: slot t holds candidate j = d when
    # d < K; query i must not see the *new* value of j > i
    d = jnp.mod(t[None, None, :] - posv[:, None, None], s_max)
    later = (d > offs[None, :, None]) & (d < kq)          # (B,K,S)
    if old_keys is not None:
        # ring mode: query i sees the pre-write (evicted) entry at a
        # later candidate's slot; the age mask decides whether that old
        # position was ever written at all
        sel = later[..., None, None]                      # (B,K,S,1,1)
        keys_q = jnp.where(sel, old_keys[:, None], keys[:, None])
        vals_q = jnp.where(sel, old_vals[:, None], vals[:, None])
        scores = jnp.einsum("bskgd,bstkd->bkgst", qg,
                            keys_q) / jnp.sqrt(float(dh))
        mask = valid & in_window
    else:
        # linear mode: a write landed only when pos + d < s_max (OOB
        # writes drop) — a dropped overflow write never shadows the old
        # entry that still lives at its wrapped index
        future = later & (posv[:, None, None] + d < s_max)
        scores = jnp.einsum("bskgd,btkd->bkgst", qg,
                            keys) / jnp.sqrt(float(dh))
        mask = valid & in_window & ~future
    mask = mask[:, None, None]                            # (B,1,1,K,S)
    scores = jnp.where(mask, scores.astype(jnp.float32), NEG_INF)
    probs = L.softmax(scores, pol).astype(q.dtype)
    if old_keys is not None:
        ctx = jnp.einsum("bkgst,bstkd->bskgd", probs, vals_q)
    else:
        ctx = jnp.einsum("bkgst,btkd->bskgd", probs, vals)
    return ctx.reshape(b, kq, hq, dh)


# Dense cache layout: head-major, (B, Hkv, S, dh) a layer, (L, B, Hkv, S,
# dh) stacked, and (..., 1) for the int8 scales.  Each (batch, head) is
# then one (S, dh) matrix, the layout the score and value products read
# on a TPU, so decode reads a layer's slice of the stack in place; the
# attend halves see the logical (B, S, Hkv, dh) view (`_token_major`).

def _token_major(cache: Array) -> Array:
    """The logical (B, S, Hkv, dh) view of a head-major layer cache."""
    return jnp.swapaxes(cache, 1, 2)


def kv_index(rows: Array, cols: Array, n_heads: int):
    """Index of every head's entry at (row, column) of a head-major
    cache: ``rows`` and ``cols`` broadcast to the leading axes of the
    (..., Hkv, dh) values written or read there."""
    heads = jnp.arange(n_heads)
    return rows[..., None], heads, cols[..., None]


@jax.named_scope("attention")
def decode_attention(q: Array, k_new: Array, v_new: Array, cache_k: Array,
                     cache_v: Array, pos: Array, cfg: ArchConfig,
                     pol: ExecutionPolicy, window,
                     scale_k: Optional[Array] = None,
                     scale_v: Optional[Array] = None, *, layer):
    """q/k_new/v_new: (B,1,H*,dh); cache: (L,B,Hkv,S,dh), every layer's,
    ring-written at pos in layer ``layer``.

    The new column is written into the stacked cache in place, and the
    layer's slice is read back from it for the attend: the decode layer
    loop carries the whole stack, so no layer's cache is copied out and
    back.

    ``pos`` is the tokens-seen counter: a scalar (every row at the same
    position — the classic single-stream path) or a ``(B,)`` vector (the
    serving engine's per-slot positions, where each decode slot was
    prefilled at a different time and length).

    With ``scale_k``/``scale_v`` (L,B,Hkv,S,nb) the cache is the per-block
    int8 format of :mod:`repro.core.quant_cache`: each new K/V vector is
    quantized on write (its scale lands at the same ring slot) and the
    layer's cache is dequantized on read.  Without them, an int8 cache is
    the legacy fixed-scale Q3.4 format (:data:`KV_Q_SCALE`).

    Returns (ctx (B,1,Hq,dh), cache_k, cache_v) — plus the updated
    (scale_k, scale_v) when per-block scales are in play.
    """
    b, _, hq, dh = q.shape
    s_max = cache_k.shape[3]
    slot = jnp.mod(pos, s_max)
    blocked = scale_k is not None
    if blocked:
        k_w, k_s = quantize_blocked(k_new)
        v_w, v_s = quantize_blocked(v_new)
    else:
        quant = cache_k.dtype == jnp.int8
        k_w = quantize_kv(k_new) if quant else k_new.astype(cache_k.dtype)
        v_w = quantize_kv(v_new) if quant else v_new.astype(cache_v.dtype)
    if jnp.ndim(pos) == 1:
        # batched scatter: each row's new K/V lands at its own column
        # (a one-column write, not a full-cache select)
        idx = (layer,) + kv_index(jnp.arange(b), slot, cache_k.shape[2])

        def put(cache, new):
            return cache.at[idx].set(new[:, 0])
    else:
        def put(cache, new):
            return jax.lax.dynamic_update_slice(
                cache, _token_major(new)[None], (layer, 0, 0, slot, 0))

    def view(cache):
        return _token_major(
            jax.lax.dynamic_index_in_dim(cache, layer, 0, keepdims=False))

    cache_k = put(cache_k, k_w)
    cache_v = put(cache_v, v_w)
    if blocked:
        scale_k = put(scale_k, k_s)
        scale_v = put(scale_v, v_s)
        keys = dequantize_blocked(view(cache_k), view(scale_k), q.dtype)
        vals = dequantize_blocked(view(cache_v), view(scale_v), q.dtype)
    else:
        keys = dequantize_kv(view(cache_k), q.dtype)
        vals = dequantize_kv(view(cache_v), q.dtype)
    ctx = _attend_decode(q, keys, vals, pos, pol, window)
    if blocked:
        return ctx, cache_k, cache_v, scale_k, scale_v
    return ctx, cache_k, cache_v


@jax.named_scope("attention")
def verify_attention(q: Array, k_new: Array, v_new: Array, cache_k: Array,
                     cache_v: Array, pos: Array, cfg: ArchConfig,
                     pol: ExecutionPolicy, window,
                     scale_k: Optional[Array] = None,
                     scale_v: Optional[Array] = None,
                     ring: bool = False):
    """Speculative verify: K candidate positions scored in one pass.

    q/k_new/v_new: (B,K,H*,dh); cache: a layer's (B,Hkv,S,dh) (scales
    (B,Hkv,S,nb)) — row b's candidates sit at absolute
    positions ``pos[b] .. pos[b]+K-1``.  All K K/V columns are written
    first, then every query is masked to its own committed history plus
    the *earlier* candidates of this call:

      * the age mask is the decode mask per candidate position,
      * ``ring=False`` (a cache at least ``max_seq`` long): the cache is
        treated as linear — writes past the cache end are dropped, and
        candidate columns ``j > i`` (this call's future writes) are
        explicitly invisible to query ``i`` even when the age mask
        saturates at a full cache, so a dropped overflow write never
        shadows the old entry that still lives at its wrapped index;
      * ``ring=True`` (a sliding-window ring shorter than the stream,
        e.g. the long_500k preset): every candidate write ring-wraps and
        lands, and query ``i`` reads the **pre-write** value at a later
        candidate's slot — the entry candidate ``j > i`` evicted is
        still inside query ``i``'s window, exactly as plain decode saw
        it.  The raw evicted columns are returned as an extra trailing
        tuple ``(ev_k, ev_v[, ev_sk, ev_sv])`` of shape (B,K,...) so the
        commit can restore the slots of rejected candidates.

    Per-query numerics are the plain :func:`decode_attention` ops at the
    same position, which is what keeps greedy spec decoding bit-identical
    to single-token decode.  With ``scale_k``/``scale_v`` the cache is the
    per-block int8 format (see :func:`decode_attention`): candidate scales
    land beside their values with the same drop/wrap semantics, so a
    rejected write's scale is just as invisible as its value until
    overwritten.  ``ring`` must be a static Python bool (it selects the
    traced program).  Callers guard ``K <= window`` in ring mode — a
    single call must not wrap onto its own writes.

    Returns (ctx (B,K,Hq,dh), cache_k, cache_v) — plus the updated
    (scale_k, scale_v) when per-block scales are in play, plus the
    evicted-column tuple as the last element in ring mode.
    """
    b, kq, hq, dh = q.shape
    s_max = cache_k.shape[2]
    posv = pos if jnp.ndim(pos) == 1 else jnp.broadcast_to(pos, (b,))
    offs = jnp.arange(kq, dtype=posv.dtype)
    wpos = posv[:, None] + offs[None, :]                  # (B,K) absolute
    blocked = scale_k is not None
    if blocked:
        k_w, k_s = quantize_blocked(k_new)
        v_w, v_s = quantize_blocked(v_new)
    else:
        quant = cache_k.dtype == jnp.int8
        k_w = quantize_kv(k_new) if quant else k_new.astype(cache_k.dtype)
        v_w = quantize_kv(v_new) if quant else v_new.astype(cache_v.dtype)
    rows = jnp.arange(b)[:, None]
    hkv = cache_k.shape[1]

    def view(cache, scale):
        return _token_major(dequantize_blocked(cache, scale, q.dtype)
                            if blocked else dequantize_kv(cache, q.dtype))

    old_keys = old_vals = None
    evicted = ()
    if ring:
        # ring-cache write: every column wraps and lands; keep the
        # pre-write view so earlier queries can still read what a later
        # candidate evicted, and hand the raw evicted columns back so
        # :func:`~repro.models.transformer.spec_commit` can restore the
        # ones whose candidate the host rejects (a rejected wrapped
        # write would otherwise shadow live history)
        old_keys = view(cache_k, scale_k)
        old_vals = view(cache_v, scale_v)
        idx = kv_index(rows, jnp.mod(wpos, s_max), hkv)
        evicted = (cache_k[idx], cache_v[idx])
        if blocked:
            evicted += (scale_k[idx], scale_v[idx])
        cache_k = cache_k.at[idx].set(k_w)
        cache_v = cache_v.at[idx].set(v_w)
        if blocked:
            scale_k = scale_k.at[idx].set(k_s)
            scale_v = scale_v.at[idx].set(v_s)
    else:
        # linear-cache write: out-of-range columns drop (never wrap)
        idx = kv_index(rows, wpos, hkv)
        cache_k = cache_k.at[idx].set(k_w, mode="drop")
        cache_v = cache_v.at[idx].set(v_w, mode="drop")
        if blocked:
            scale_k = scale_k.at[idx].set(k_s, mode="drop")
            scale_v = scale_v.at[idx].set(v_s, mode="drop")
    keys = view(cache_k, scale_k)
    vals = view(cache_v, scale_v)
    ctx = _attend_verify(q, keys, vals, posv, pol, window,
                         old_keys=old_keys, old_vals=old_vals)
    if ring:
        if blocked:
            return ctx, cache_k, cache_v, scale_k, scale_v, evicted
        return ctx, cache_k, cache_v, evicted
    if blocked:
        return ctx, cache_k, cache_v, scale_k, scale_v
    return ctx, cache_k, cache_v


# ---------------------------------------------------------------------------
# Paged decode/verify: pooled cache addressed through per-slot block tables
# ---------------------------------------------------------------------------
# Pool layout (per layer): (N, page, Hkv, dh); a (B, P) int32 block table
# maps logical page p of slot b to a pool block.  The sentinel value N
# marks an unallocated page: gathers through it clamp (jax gather
# semantics) into in-pool garbage the decode age mask already excludes,
# and writes through it drop — so the jitted program never needs to know
# which pages are live.  See models/paged.py for the invariants.

def paged_gather(pool: Array, table: Array) -> Array:
    """Logical (B, P*page, ...) view of a pooled cache via block tables."""
    b, p = table.shape
    g = pool[table]                              # (B, P, page, ...)
    return g.reshape((b, p * pool.shape[1]) + pool.shape[2:])


def _paged_write(pool: Array, idx: Array, new: Array, layer=None
                 ) -> Array:
    """Scatter rows into a pool through flat token indices (drop OOB);
    with ``layer``, into that layer's pool of a stacked (L, N, page, ...)
    one."""
    lead = () if layer is None else (layer,)
    k = len(lead)
    n, page = pool.shape[k:k + 2]
    flat = pool.reshape(pool.shape[:k] + (n * page,) + pool.shape[k + 2:])
    flat = flat.at[lead + (idx,)].set(new, mode="drop")
    return flat.reshape(pool.shape)


@jax.named_scope("attention")
def paged_decode_attention(q: Array, k_new: Array, v_new: Array,
                           pool_k: Array, pool_v: Array, table: Array,
                           pos: Array, cfg: ArchConfig,
                           pol: ExecutionPolicy, window,
                           scale_k: Optional[Array] = None,
                           scale_v: Optional[Array] = None, *, layer):
    """:func:`decode_attention` over a pooled cache (see module note),
    every layer's pool stacked (L, N, page, ...) as there.

    The new K/V vector lands in layer ``layer`` at flat pool index
    ``table[b, pos//page] * page + pos%page`` (drop through the sentinel
    / past logical capacity — the paged cache is linear, never
    ring-wrapped), then the layer's pool is gathered back to the logical
    (B, S, Hkv, dh) view and the shared :func:`_attend_decode` half runs
    unchanged — which is what keeps paged decode bit-identical to the
    dense layout.
    """
    b = q.shape[0]
    n, page = pool_k.shape[1:3]
    s_log = table.shape[1] * page
    posv = pos if jnp.ndim(pos) == 1 else jnp.broadcast_to(pos, (b,))
    blocked = scale_k is not None
    if blocked:
        k_w, k_s = quantize_blocked(k_new)
        v_w, v_s = quantize_blocked(v_new)
    else:
        k_w = k_new.astype(pool_k.dtype)
        v_w = v_new.astype(pool_v.dtype)
    rows = jnp.arange(b)
    blk = table[rows, jnp.minimum(posv // page, table.shape[1] - 1)]
    idx = blk * page + jnp.mod(posv, page)
    idx = jnp.where(posv < s_log, idx, n * page)          # linear: drop OOB

    def put(pool, new):
        return _paged_write(pool, idx, new[:, 0], layer)

    def view(pool):
        return paged_gather(
            jax.lax.dynamic_index_in_dim(pool, layer, 0, keepdims=False),
            table)

    pool_k = put(pool_k, k_w)
    pool_v = put(pool_v, v_w)
    if blocked:
        scale_k = put(scale_k, k_s)
        scale_v = put(scale_v, v_s)
        keys = dequantize_blocked(view(pool_k), view(scale_k), q.dtype)
        vals = dequantize_blocked(view(pool_v), view(scale_v), q.dtype)
    else:
        keys = dequantize_kv(view(pool_k), q.dtype)
        vals = dequantize_kv(view(pool_v), q.dtype)
    ctx = _attend_decode(q, keys, vals, posv, pol, window)
    if blocked:
        return ctx, pool_k, pool_v, scale_k, scale_v
    return ctx, pool_k, pool_v


@jax.named_scope("attention")
def paged_verify_attention(q: Array, k_new: Array, v_new: Array,
                           pool_k: Array, pool_v: Array, table: Array,
                           pos: Array, cfg: ArchConfig,
                           pol: ExecutionPolicy, window,
                           scale_k: Optional[Array] = None,
                           scale_v: Optional[Array] = None):
    """:func:`verify_attention` over a pooled cache.

    All K candidate columns scatter through the block tables first
    (sentinel/OOB writes drop — unallocated pages are never touched, so
    speculative garbage can only ever land in a slot's private frontier
    pages, never in radix-shared blocks), then the shared
    :func:`_attend_verify` half runs on the gathered logical view.  This
    is both the spec-decode verify pass and the admission extend pass
    (positions ``pos .. pos+K-1`` scored in one shot; rows the host did
    not admit simply have no pages allocated past their frontier and
    roll back via ``spec_commit(advance=0)``).
    """
    b, kq = q.shape[:2]
    n, page = pool_k.shape[:2]
    s_log = table.shape[1] * page
    posv = pos if jnp.ndim(pos) == 1 else jnp.broadcast_to(pos, (b,))
    offs = jnp.arange(kq, dtype=posv.dtype)
    wpos = posv[:, None] + offs[None, :]                  # (B,K) absolute
    blocked = scale_k is not None
    if blocked:
        k_w, k_s = quantize_blocked(k_new)
        v_w, v_s = quantize_blocked(v_new)
    else:
        k_w = k_new.astype(pool_k.dtype)
        v_w = v_new.astype(pool_v.dtype)
    rows = jnp.arange(b)[:, None]
    blk = table[rows, jnp.minimum(wpos // page, table.shape[1] - 1)]
    idx = blk * page + jnp.mod(wpos, page)
    idx = jnp.where(wpos < s_log, idx, n * page)          # linear: drop OOB
    pool_k = _paged_write(pool_k, idx, k_w)
    pool_v = _paged_write(pool_v, idx, v_w)
    if blocked:
        scale_k = _paged_write(scale_k, idx, k_s)
        scale_v = _paged_write(scale_v, idx, v_s)
        keys = dequantize_blocked(paged_gather(pool_k, table),
                                  paged_gather(scale_k, table), q.dtype)
        vals = dequantize_blocked(paged_gather(pool_v, table),
                                  paged_gather(scale_v, table), q.dtype)
    else:
        keys = dequantize_kv(paged_gather(pool_k, table), q.dtype)
        vals = dequantize_kv(paged_gather(pool_v, table), q.dtype)
    ctx = _attend_verify(q, keys, vals, posv, pol, window)
    if blocked:
        return ctx, pool_k, pool_v, scale_k, scale_v
    return ctx, pool_k, pool_v
