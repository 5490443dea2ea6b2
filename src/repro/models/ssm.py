"""Attention-free sequence mixers: RWKV6 (Finch) and a Mamba-style
selective SSM (the hybrid branch of hymba).

Both are implemented as chunked scans: an outer ``lax.scan`` over time
chunks carries the recurrent state (which is also exactly the decode-time
state — long_500k decode is O(1) per step), and the inner chunk is a short
unrolled recurrence.  Sequence length therefore never enters the memory
footprint beyond one chunk of activations.
"""
from __future__ import annotations

from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig, ExecutionPolicy
from repro.models import layers as L
from repro.parallel.sharding import constrain

Array = jax.Array


# ---------------------------------------------------------------------------
# RWKV6 time-mix (Finch: data-dependent per-channel decay)
# ---------------------------------------------------------------------------

class Rwkv6Params(NamedTuple):
    mu: Array        # (5, D) token-shift lerp factors for r,k,v,w,g
    w0: Array        # (D,) decay base
    w_lora_a: Array  # (D, 64) data-dependent decay LoRA
    w_lora_b: Array  # (64, D)
    bonus: Array     # (H, dk) the "u" current-token bonus
    wr: Array        # (D, D)
    wk: Array        # (D, D)
    wv: Array        # (D, D)
    wg: Array        # (D, D)
    wo: Array        # (D, D)
    ln_w: Array      # (D,) per-head group-norm gain


def _token_shift(x: Array, x_prev: Array) -> Array:
    """shifted[t] = x[t-1]; position 0 sees the carried boundary token."""
    return jnp.concatenate([x_prev[:, None, :], x[:, :-1, :]], axis=1)


def _last_valid(x: Array, lengths) -> Array:
    """x[:, n-1, :] per row — the boundary token carried into decode.

    With ``lengths=None`` (unpadded sequences) this is just ``x[:, -1]``;
    for right-padded serving prefill it gathers each row's last *real*
    position so the carried token-shift state matches single-stream decode.
    """
    if lengths is None:
        return x[:, -1, :]
    idx = (lengths - 1).astype(jnp.int32)[:, None, None]
    return jnp.take_along_axis(x, jnp.broadcast_to(
        idx, (x.shape[0], 1, x.shape[2])), axis=1)[:, 0, :]


@jax.named_scope("time_mix")
def rwkv6_timemix(x: Array, p: Rwkv6Params, cfg: ArchConfig,
                  pol: ExecutionPolicy, state: Tuple[Array, Array],
                  mask: Array = None, lengths: Array = None,
                  return_states: bool = False):
    """x: (B, T, D).  state = (x_boundary (B, D), S (B, H, dk, dv)).

    Returns (out (B,T,D), new state).  wkv recurrence per head:
        out_t = (r_t ( S + (u*k_t) v_t^T )) ; S <- diag(w_t) S + k_t v_t^T

    ``mask`` (B, T) marks real tokens in a right-padded batch: pad steps
    carry S through unchanged (decay forced to 1, k to 0), so the carried
    state is bit-identical to running the unpadded sequence; ``lengths``
    picks each row's last real token for the token-shift boundary.

    ``return_states`` appends a third result: the wkv state *after every
    step*, (B, T, H, dk, dv) float32 — the per-position checkpoints a
    speculative ``verify_step`` rolls back to when drafts are rejected.
    Only sensible for short T (the verify window).

    The recurrence runs under the named scope ``wkv``, the rest of the
    mixer under ``time_mix``, so a profile tells the two apart.
    """
    b, t, d = x.shape
    h = cfg.n_heads
    dk = d // h
    x_prev, s0 = state
    xs = _token_shift(x, x_prev)

    mixed = [x + (xs - x) * p.mu[i].astype(x.dtype) for i in range(5)]
    xr, xk, xv, xw, xg = mixed
    r = L.dense(xr, p.wr, pol).reshape(b, t, h, dk)
    k = L.dense(xk, p.wk, pol).reshape(b, t, h, dk)
    v = L.dense(xv, p.wv, pol).reshape(b, t, h, dk)
    g = L.dense(xg, p.wg, pol)
    # data-dependent decay (Finch): w = exp(-exp(w0 + lora(xw)))
    dd = jnp.tanh(xw.astype(jnp.float32) @ p.w_lora_a) @ p.w_lora_b
    logw = -jnp.exp(jnp.clip(p.w0.astype(jnp.float32) + dd, -8.0, 2.0))
    w = jnp.exp(logw).reshape(b, t, h, dk)                     # decay in (0,1)
    u = p.bonus.astype(jnp.float32)                            # (H, dk)
    if mask is not None:
        # pad steps are state no-ops: S <- 1*S + 0*v^T (exact)
        m = mask[:, :, None, None]
        w = jnp.where(m, w, jnp.ones((), w.dtype))
        k = jnp.where(m, k, jnp.zeros((), k.dtype))

    if t == 1:
        # decode/verify fast path: one recurrence step, no chunk
        # scaffolding (same primitive ops and casts as the scanned step
        # below — bit-identical, just without the length-1 scans)
        with jax.named_scope("wkv"):
            r1, k1, v1, w1 = (a[:, 0].astype(jnp.float32)
                              for a in (r, k, v, w))
            S = s0.astype(jnp.float32)
            kv = k1[..., :, None] * v1[..., None, :]           # (B,H,dk,dv)
            out = jnp.einsum("bhk,bhkv->bhv", r1,
                             S + u[..., None] * kv)[:, None]
            S = w1[..., None] * S + kv
        res = _timemix_out(out, x, g, p, pol, lengths, S)
        return res + (S[:, None],) if return_states else res

    chunk = max(1, min(64, t))
    assert t % chunk == 0
    n_chunks = t // chunk

    def scan_chunk(S, xs_c):
        r_c, k_c, v_c, w_c = xs_c  # (chunk, B, H, dk)

        def step(S, xs_t):
            r_t, k_t, v_t, w_t = (a.astype(jnp.float32) for a in xs_t)
            kv = k_t[..., :, None] * v_t[..., None, :]          # (B,H,dk,dv)
            out_t = jnp.einsum("bhk,bhkv->bhv", r_t, S + u[..., None] * kv)
            S = w_t[..., None] * S + kv
            ys = (S, out_t) if return_states else out_t
            return S, ys

        # unrolled: as a loop, each step is 8 device ops on a v5e (its
        # slices, the step, the output's update, the counter, the test),
        # each an event in a profiler trace; unrolled, about 2, though a
        # token takes about twice as long as looped
        S, ys_c = jax.lax.scan(step, S, (r_c, k_c, v_c, w_c),
                               unroll=chunk)
        return S, ys_c

    def to_chunks(a):  # (B,T,H,dk) -> (n_chunks, chunk, B, H, dk)
        return a.transpose(1, 0, 2, 3).reshape(n_chunks, chunk, b, h, dk)

    with jax.named_scope("wkv"):
        S, ys = jax.lax.scan(scan_chunk, s0.astype(jnp.float32),
                             (to_chunks(r), to_chunks(k), to_chunks(v),
                              to_chunks(w)))
    s_steps, out = ys if return_states else (None, ys)
    out = out.reshape(t, b, h, dk).transpose(1, 0, 2, 3)        # (B,T,H,dk)
    res = _timemix_out(out, x, g, p, pol, lengths, S)
    if return_states:  # (n_chunks, chunk, B, ...) -> (B, T, ...)
        s_steps = jnp.moveaxis(s_steps.reshape((t,) + s_steps.shape[2:]),
                               0, 1)
        return res + (s_steps,)
    return res


def _timemix_out(out: Array, x: Array, g: Array, p: Rwkv6Params,
                 pol: ExecutionPolicy, lengths, S: Array
                 ) -> Tuple[Array, Tuple[Array, Array]]:
    """Shared timemix epilogue: per-head group norm, gate, out proj."""
    b, t, d = x.shape
    mean = out.mean(-1, keepdims=True)
    var = out.var(-1, keepdims=True)
    out = (out - mean) * jax.lax.rsqrt(var + 64e-5)
    out = out.reshape(b, t, d) * p.ln_w.astype(jnp.float32)
    out = (out.astype(x.dtype) * L.af(g, "silu", pol))
    out = L.dense(out, p.wo, pol)
    return out, (_last_valid(x, lengths), S)


class Rwkv6ChannelParams(NamedTuple):
    mu_k: Array   # (D,)
    mu_r: Array   # (D,)
    wk: Array     # (D, F)
    wv: Array     # (F, D)
    wr: Array     # (D, D)


@jax.named_scope("channel_mix")
def rwkv6_channelmix(x: Array, p: Rwkv6ChannelParams, cfg: ArchConfig,
                     pol: ExecutionPolicy, x_prev: Array,
                     lengths: Array = None) -> Tuple[Array, Array]:
    xs = _token_shift(x, x_prev)
    xk = x + (xs - x) * p.mu_k.astype(x.dtype)
    xr = x + (xs - x) * p.mu_r.astype(x.dtype)
    k = L.af(L.dense(xk, p.wk, pol), "relu", pol)
    k = k * k                                        # squared ReLU
    kv = L.dense(k, p.wv, pol)
    r = L.af(L.dense(xr, p.wr, pol), "sigmoid", pol)
    return r * kv, _last_valid(x, lengths)


# ---------------------------------------------------------------------------
# Mamba-style selective SSM (hymba's parallel head branch)
# ---------------------------------------------------------------------------

class MambaParams(NamedTuple):
    w_in: Array      # (D, 2*Di)  -> x, z gate
    conv_w: Array    # (K, Di) depthwise causal conv
    w_bc: Array      # (Di, 2*N + 1) -> B, C, dt
    a_log: Array     # (Di, N)
    d_skip: Array    # (Di,)
    w_out: Array     # (Di, D)


def mamba_mix(x: Array, p: MambaParams, cfg: ArchConfig,
              pol: ExecutionPolicy, state: Tuple[Array, Array],
              mask: Array = None, lengths: Array = None,
              return_states: bool = False):
    """x: (B,T,D).  state = (conv tail (B, K-1, Di), h (B, Di, N)).

    ``mask``/``lengths`` as in :func:`rwkv6_timemix`: pad steps of a
    right-padded batch are forced to state no-ops (decay 1, drive 0) and
    the carried conv tail is gathered at each row's last real positions.

    ``return_states`` appends a third result ``(tails (B,T,K-1,Di),
    hs (B,T,Di,N))``: the conv tail and ssm state *after every step* —
    speculative verify checkpoints; short T only.
    """
    b, t, d = x.shape
    n = cfg.ssm_state
    conv_tail, h0 = state
    di = p.conv_w.shape[1]

    xz = L.dense(x, p.w_in, pol)
    # keep the mamba branch in the residual stream's (batch, seq) layout —
    # without this XLA reshards (B,T,2D) between the mlp- and seq-sharded
    # layouts every layer (hymba's 18x collective inflation, see
    # EXPERIMENTS.md #Perf)
    xz = constrain(xz, ("batch", "seq", None))
    xi, z = jnp.split(xz, 2, axis=-1)                # (B,T,Di)

    # depthwise causal conv via the carried tail
    kk = p.conv_w.shape[0]
    xi_pad = jnp.concatenate([conv_tail.astype(xi.dtype), xi], axis=1)
    conv = sum(xi_pad[:, i:i + t, :] * p.conv_w[i].astype(xi.dtype)
               for i in range(kk))
    conv = L.af(conv, "silu", pol)
    if kk == 1:
        new_tail = conv_tail
    elif lengths is None:
        new_tail = xi_pad[:, t:t + kk - 1, :]
    else:
        # last kk-1 *real* inputs per row: xi_pad cols [n, n + kk - 1)
        idx = lengths.astype(jnp.int32)[:, None] + jnp.arange(kk - 1)[None]
        new_tail = jnp.take_along_axis(xi_pad, idx[..., None], axis=1)

    bc = L.dense(conv, p.w_bc, pol).astype(jnp.float32)
    b_t, c_t, dt = bc[..., :n], bc[..., n:2 * n], bc[..., -1:]
    dt = jax.nn.softplus(dt)                          # (B,T,1)
    a = -jnp.exp(p.a_log.astype(jnp.float32))         # (Di,N)
    # dt (B,T,1) broadcasts over channels: decay (B,T,Di,N)
    decay = jnp.exp(dt[..., None] * a[None, None, :, :])
    drive = (dt[..., None] * b_t[:, :, None, :]) * conv.astype(
        jnp.float32)[..., None]                       # (B,T,Di,N)
    if mask is not None:
        # pad steps are state no-ops: h <- 1*h + 0 (exact)
        m = mask[:, :, None, None]
        decay = jnp.where(m, decay, jnp.ones((), decay.dtype))
        drive = jnp.where(m, drive, jnp.zeros((), drive.dtype))

    def step_tails():
        # conv-tail checkpoint after step j+1 = the last K-1 conv inputs
        # seen up to and including position j (sliding windows of xi_pad)
        return jnp.stack([xi_pad[:, j + 1:j + kk, :] for j in range(t)],
                         axis=1)                      # (B,T,K-1,Di)

    if t == 1:
        # decode/verify fast path: one recurrence step, no chunk
        # scaffolding (same ops as the scanned step — bit-identical)
        h = decay[:, 0] * h0.astype(jnp.float32) + drive[:, 0]  # (B,Di,N)
        y = jnp.einsum("bdn,bn->bd", h, c_t[:, 0])[:, None]     # (B,1,Di)
        y = y + conv.astype(jnp.float32) * p.d_skip.astype(jnp.float32)
        y = y.astype(x.dtype) * L.af(z, "silu", pol)
        out = L.dense(y, p.w_out, pol), (new_tail, h)
        return out + ((new_tail[:, None], h[:, None]),) if return_states \
            else out

    chunk = max(1, min(64, t))
    assert t % chunk == 0
    n_chunks = t // chunk

    def to_chunks(arr):  # (B,T,Di,N) -> (n_chunks, chunk, B, Di, N)
        return arr.transpose(1, 0, 2, 3).reshape(n_chunks, chunk, b, di, n)

    def scan_chunk(h, xs_c):
        dec_c, drv_c, c_c = xs_c

        def step(h, xs_t):
            dec_t, drv_t, c_tt = xs_t
            h = dec_t * h + drv_t                    # (B,Di,N)
            y_t = jnp.einsum("bdn,bn->bd", h, c_tt)
            ys = (h, y_t) if return_states else y_t
            return h, ys

        h, ys_c = jax.lax.scan(step, h, (dec_c, drv_c, c_c), unroll=chunk)
        return h, ys_c

    c_chunks = c_t.transpose(1, 0, 2).reshape(n_chunks, chunk, b, n)
    h, ys = jax.lax.scan(scan_chunk, h0.astype(jnp.float32),
                         (to_chunks(decay), to_chunks(drive), c_chunks))
    h_steps, y = ys if return_states else (None, ys)
    y = y.reshape(t, b, di).transpose(1, 0, 2)
    y = y + conv.astype(jnp.float32) * p.d_skip.astype(jnp.float32)
    y = y.astype(x.dtype) * L.af(z, "silu", pol)
    out = L.dense(y, p.w_out, pol), (new_tail, h)
    if return_states:
        h_steps = jnp.moveaxis(h_steps.reshape((t,) + h_steps.shape[2:]),
                               0, 1)                 # (B,T,Di,N)
        return out + ((step_tails(), h_steps),)
    return out
