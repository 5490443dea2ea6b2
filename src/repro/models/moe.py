"""Mixture-of-Experts FFN with capacity-based dispatch (GShard-style).

Two execution paths:

* **Sharded (mesh active)** — an explicit ``shard_map`` over (data, model):
  GSPMD cannot partition the dispatch scatter/gather along the batch dim
  (it materialises the global (B, S*k, D) gather — 56 GB/device for
  arctic), so we make the parallelism explicit instead:

    - **EP mode** (E % model == 0, arctic): experts split over the model
      axis; each (data, model) device routes its local tokens to its local
      experts and the partial outputs psum over model.  Expert FFN weights
      optionally keep an extra FSDP shard over data (arctic's 469B slab)
      and are all-gathered at use.
    - **expert-TP mode** (otherwise, granite's 40 experts): every model
      shard holds all experts with a 1/model slice of the FFN width; the
      F-contraction makes outputs partial sums, combined by the same psum.

* **Local (no mesh)** — plain capacity-based scatter dispatch (smoke tests,
  single-device training); numerically equivalent (tests assert it).

Aux load-balance loss follows Switch Transformer (mean gate * mean load).
"""
from __future__ import annotations

import math
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as PS

from repro.configs.base import ArchConfig, ExecutionPolicy
from repro.models import layers as L
from repro.parallel.sharding import constrain, get_abstract_mesh

Array = jax.Array

# expert-weight FSDP threshold (total expert params)
FSDP_MIN_PARAMS = 4e9


class MoEParams(NamedTuple):
    w_router: Array           # (D, E)
    w_gate: Array             # (E, D, F)
    w_up: Array               # (E, D, F)
    w_down: Array             # (E, F, D)


def capacity(cfg: ArchConfig, tokens_per_group: int) -> int:
    c = int(math.ceil(tokens_per_group * cfg.top_k * cfg.capacity_factor
                      / cfg.n_experts))
    return max(c, cfg.top_k)


def moe_ffn(x: Array, p: MoEParams, cfg: ArchConfig, pol: ExecutionPolicy,
            ffn=None) -> Tuple[Array, Array]:
    """x: (B, S, D) -> (out (B, S, D), aux_loss scalar).

    ``ffn``: optional (w_gate, w_up, w_down) of a dense-residual FFN to be
    computed *inside* the sharded region and combined in the same psum as
    the MoE output — one all-reduce per layer instead of two (§Perf).
    """
    mesh = get_abstract_mesh()
    if (mesh is not None and not mesh.empty and "model" in mesh.shape
            and mesh.shape.get("model", 1) > 1
            and x.shape[0] % mesh.shape.get("data", 1) == 0):
        if pol.moe_pure_dp and x.shape[0] % _total_devices(mesh) == 0:
            return _moe_ffn_pure_dp(x, p, cfg, pol, mesh, ffn)
        return _moe_ffn_sharded(x, p, cfg, pol, mesh, ffn)
    out, aux = _moe_ffn_local(x, p, cfg, pol)
    if ffn is not None:
        out = out + L.swiglu(x, ffn[0], ffn[1], ffn[2], pol, cfg.activation)
    return out, aux


def _total_devices(mesh) -> int:
    n = 1
    for v in mesh.shape.values():
        n *= v
    return n


def _moe_ffn_local(x: Array, p: MoEParams, cfg: ArchConfig,
                   pol: ExecutionPolicy) -> Tuple[Array, Array]:
    b, s, d = x.shape
    e, k = cfg.n_experts, cfg.top_k
    c = capacity(cfg, s)

    logits = L.dense(x, p.w_router, pol).astype(jnp.float32)   # (B,S,E)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, expert_idx = jax.lax.top_k(probs, k)             # (B,S,k)
    gate_vals = gate_vals / jnp.maximum(
        gate_vals.sum(-1, keepdims=True), 1e-9)

    # Switch-style aux loss: fraction routed * mean prob per expert.
    density = jnp.mean(
        jax.nn.one_hot(expert_idx[..., 0], e, dtype=jnp.float32), axis=(0, 1))
    density_prob = jnp.mean(probs, axis=(0, 1))
    aux = jnp.sum(density * density_prob) * e

    # Position of each (token, k) entry within its expert, per group (=seq).
    flat_idx = expert_idx.reshape(b, s * k)                     # (B, S*k)
    onehot = jax.nn.one_hot(flat_idx, e, dtype=jnp.int32)       # (B, S*k, E)
    pos_in_e = jnp.cumsum(onehot, axis=1) * onehot              # 1-based
    position = jnp.sum(pos_in_e, axis=-1) - 1                   # (B, S*k)
    keep = position < c

    token_of = jnp.broadcast_to(jnp.arange(s)[None, :, None],
                                (b, s, k)).reshape(b, s * k)

    # Scatter tokens into the expert slab (dropped entries write to a
    # garbage slot c which we slice off).
    slot = jnp.where(keep, position, c)
    x_flat = x  # (B, S, D)
    src = jnp.take_along_axis(
        x_flat, token_of[..., None], axis=1)                    # (B,S*k,D)
    slab = jnp.zeros((b, e, c + 1, d), x.dtype)
    slab = slab.at[jnp.arange(b)[:, None], flat_idx, slot].add(src)
    slab = slab[:, :, :c, :]                                    # (B,E,C,D)
    slab = constrain(slab, ("batch", "experts", None, None))

    # Batched expert SwiGLU.
    def emm(t, w):  # (B,E,C,*) x (E,*,*)
        return jnp.einsum("becd,edf->becf", t, w.astype(t.dtype))

    h = L.af(emm(slab, p.w_gate), cfg.activation, pol) * emm(slab, p.w_up)
    h = constrain(h, ("batch", "experts", None, "expert_mlp"))
    y = jnp.einsum("becf,efd->becd", h, p.w_down.astype(h.dtype))
    y = constrain(y, ("batch", "experts", None, None))

    # Combine: gather each kept entry back and weight by its gate.
    y_pad = jnp.concatenate([y, jnp.zeros((b, e, 1, d), y.dtype)], axis=2)
    gathered = y_pad[jnp.arange(b)[:, None], flat_idx, slot]    # (B,S*k,D)
    gathered = gathered * (gate_vals.reshape(b, s * k)[..., None]
                           * keep[..., None]).astype(gathered.dtype)
    out = gathered.reshape(b, s, k, d).sum(axis=2)
    return out.astype(x.dtype), aux


# ---------------------------------------------------------------------------
# Explicit shard_map path (production meshes)
# ---------------------------------------------------------------------------

def _dispatch_local(x2, probs, e_lo, e_count, e_total, k, c, act_dtype):
    """Local capacity dispatch for experts [e_lo, e_lo+e_count).

    x2: (T, D) local tokens; probs: (T, E) router probabilities.
    Returns (slab (e_count, C, D), flat_idx, slot, gates, keep).
    """
    t, d = x2.shape
    gate_vals, expert_idx = jax.lax.top_k(probs, k)          # (T, k)
    gate_vals = gate_vals / jnp.maximum(
        gate_vals.sum(-1, keepdims=True), 1e-9)
    flat_idx = expert_idx.reshape(t * k)
    local = jnp.logical_and(flat_idx >= e_lo, flat_idx < e_lo + e_count)
    local_e = jnp.where(local, flat_idx - e_lo, e_count)     # garbage bucket
    onehot = jax.nn.one_hot(local_e, e_count + 1, dtype=jnp.int32)
    pos = jnp.cumsum(onehot, axis=0) * onehot
    slot = jnp.sum(pos, axis=-1) - 1                         # (T*k,)
    keep = jnp.logical_and(local, slot < c)
    slot = jnp.where(keep, slot, c)
    token_of = jnp.repeat(jnp.arange(t), k)
    slab = jnp.zeros((e_count + 1, c + 1, d), act_dtype)
    slab = slab.at[local_e, slot].add(x2[token_of].astype(act_dtype))
    return (slab[:e_count, :c], flat_idx, local_e, slot, gate_vals, keep,
            token_of)


def _quantize_transport(w):
    """FxP8 transport for FSDP gathers (per-[e,d]-row absmax scales)."""
    amax = jnp.max(jnp.abs(w.astype(jnp.float32)), axis=-1, keepdims=True)
    scale = jnp.maximum(amax / 127.0, 1e-12)
    q = jnp.clip(jnp.round(w.astype(jnp.float32) / scale), -127, 127
                 ).astype(jnp.int8)
    return q, scale.astype(jnp.float32)


def _moe_ffn_sharded(x: Array, p: MoEParams, cfg: ArchConfig,
                     pol: ExecutionPolicy, mesh, ffn=None
                     ) -> Tuple[Array, Array]:
    b, s, d = x.shape
    e, k = cfg.n_experts, cfg.top_k
    m = mesh.shape["model"]
    dp_axes = tuple(a for a in ("pod", "data") if a in mesh.shape
                    and b % mesh.shape[a] == 0)
    # batch split over every usable DP axis
    dp = 1
    for a in dp_axes:
        dp *= mesh.shape[a]
    ep_mode = (e % m == 0)
    fm = cfg.moe_d_ff
    # FSDP shard of the expert FFN width over every DP axis (arctic's 469B
    # slab spreads over all 256/512 chips; gathered at use).  Gather order
    # permutes F consistently for w_gate/w_up/w_down, and F is contracted
    # between them, so any reassembly order is numerically exact.
    fsdp_axes = tuple(a for a in ("data", "pod") if a in mesh.shape)
    fsdp_ways = 1
    for a in fsdp_axes:
        fsdp_ways *= mesh.shape[a]
    fsdp = ep_mode and fsdp_axes and fm % fsdp_ways == 0 and \
        (e * d * fm * cfg.n_layers) > FSDP_MIN_PARAMS
    tp_f = (not ep_mode) and fm % m == 0

    t_loc = (b // dp) * s
    # local capacity: expected local tokens per expert, with headroom
    c = max(k, int(math.ceil(t_loc * k * cfg.capacity_factor / e)))

    bspec = dp_axes if len(dp_axes) > 1 else (dp_axes[0] if dp_axes else None)
    x_spec = PS(bspec, None, None)
    fspec = (fsdp_axes if len(fsdp_axes) > 1 else fsdp_axes[0]) if fsdp else None
    if ep_mode:
        w_spec = PS("model", None, fspec) if fsdp else PS("model", None, None)
        wd_spec = PS("model", fspec, None) if fsdp else PS("model", None, None)
    else:
        w_spec = PS(None, None, "model") if tp_f else PS(None, None, None)
        wd_spec = PS(None, "model", None) if tp_f else PS(None, None, None)

    def f(xb, wr, wg, wu, wd, *ffn_w):
        bl = xb.shape[0]
        x2 = xb.reshape(bl * s, d)
        logits = (x2 @ wr.astype(x2.dtype)).astype(jnp.float32)
        probs = jax.nn.softmax(logits, axis=-1)
        # aux loss from local tokens (identical across model shards)
        top1 = jnp.argmax(probs, axis=-1)
        density = jnp.mean(jax.nn.one_hot(top1, e, dtype=jnp.float32), 0)
        aux = jnp.sum(density * jnp.mean(probs, axis=0)) * e
        if dp_axes:
            aux = jax.lax.pmean(aux, dp_axes)

        if ep_mode:
            e_loc = e // m
            e_lo = jax.lax.axis_index("model") * e_loc
            if fsdp:
                if pol.fsdp_int8_gather:
                    # FxP8 transport (CAESAR co-design on collectives):
                    # quantize the local F-shard, gather int8 payload AND
                    # per-shard scales, dequantize segment-wise — link
                    # bytes halve vs bf16 (scales are negligible).
                    ways = 1
                    for a in fsdp_axes:
                        ways *= mesh.shape[a]

                    def gq_last(w):
                        # w (E, D, Fs): scales per (e, d) row of this shard
                        q, sc = _quantize_transport(w)
                        qg = jax.lax.all_gather(q, fsdp_axes, axis=2,
                                                tiled=True)       # (E,D,F)
                        sg = jax.lax.all_gather(sc, fsdp_axes, axis=2,
                                                tiled=True)       # (E,D,ways)
                        eh, dh_, fs = q.shape
                        out = (qg.reshape(eh, dh_, ways, fs).astype(
                            jnp.float32) * sg[..., :, None])
                        return out.reshape(eh, dh_, ways * fs).astype(w.dtype)

                    def gq_mid(w):
                        # w (E, Fs, D): scales per (e, f) row
                        q, sc = _quantize_transport(w)
                        qg = jax.lax.all_gather(q, fsdp_axes, axis=1,
                                                tiled=True)       # (E,F,D)
                        sg = jax.lax.all_gather(sc, fsdp_axes, axis=1,
                                                tiled=True)       # (E,F,1)
                        return (qg.astype(jnp.float32) * sg).astype(w.dtype)

                    wg_l = gq_last(wg)
                    wu_l = gq_last(wu)
                    wd_l = gq_mid(wd)
                else:
                    wg_l = jax.lax.all_gather(wg, fsdp_axes, axis=2,
                                              tiled=True)
                    wu_l = jax.lax.all_gather(wu, fsdp_axes, axis=2,
                                              tiled=True)
                    wd_l = jax.lax.all_gather(wd, fsdp_axes, axis=1,
                                              tiled=True)
            else:
                wg_l, wu_l, wd_l = wg, wu, wd
        else:
            e_loc, e_lo = e, 0
            wg_l, wu_l, wd_l = wg, wu, wd

        slab, flat_idx, local_e, slot, gates, keep, token_of = \
            _dispatch_local(x2, probs, e_lo, e_loc, e, k, c, xb.dtype)

        h = L.af(jnp.einsum("ecd,edf->ecf", slab, wg_l.astype(slab.dtype)),
                 cfg.activation, pol) * jnp.einsum(
            "ecd,edf->ecf", slab, wu_l.astype(slab.dtype))
        y = jnp.einsum("ecf,efd->ecd", h, wd_l.astype(h.dtype))

        # combine: gather back, weight by gate, scatter-add per token
        y_pad = jnp.pad(y, ((0, 1), (0, 1), (0, 0)))
        vals = y_pad[jnp.minimum(local_e, e_loc), slot]      # (T*k, D)
        w_gate_val = (gates.reshape(-1) * keep).astype(vals.dtype)
        vals = vals * w_gate_val[:, None]
        out = jnp.zeros((bl * s, d), vals.dtype).at[token_of].add(vals)
        if ffn_w:
            # dense-residual FFN fused into the same psum: its w_down
            # contraction is over the model-sharded F, so its local output
            # is a partial sum exactly like the MoE output.
            fg, fu, fd = ffn_w
            h2 = L.af(x2 @ fg.astype(x2.dtype), cfg.activation, pol) * (
                x2 @ fu.astype(x2.dtype))
            out = out + (h2 @ fd.astype(h2.dtype)).astype(out.dtype)
        if ep_mode or tp_f or ffn_w:
            out = jax.lax.psum(out, "model")
        return out.reshape(bl, s, d).astype(xb.dtype), aux

    ffn_args = ()
    ffn_specs = ()
    if ffn is not None:
        # dense FFN weights are "mlp"-sharded over model (column/row)
        ffn_args = (ffn[0], ffn[1], ffn[2])
        ffn_specs = (PS(None, "model"), PS(None, "model"), PS("model", None))
    out, aux = shard_map(
        f, mesh=mesh,
        in_specs=(x_spec, PS(), w_spec, w_spec, wd_spec) + ffn_specs,
        out_specs=(x_spec, PS()),
        check_vma=False,
    )(x, p.w_router, p.w_gate, p.w_up, p.w_down, *ffn_args)
    return out, aux


def _moe_ffn_pure_dp(x: Array, p: MoEParams, cfg: ArchConfig,
                     pol: ExecutionPolicy, mesh, ffn=None
                     ) -> Tuple[Array, Array]:
    """Whole-mesh data parallelism for small MoEs (granite at tp=16 is
    communication-bound: E=40 can't shard over 16 and the psum dominates).
    Batch shards over every axis; experts replicated; zero collectives in
    the layer body."""
    b, s, d = x.shape
    e, k = cfg.n_experts, cfg.top_k
    axes = tuple(a for a in ("pod", "data", "model") if a in mesh.shape)
    dp = 1
    for a in axes:
        dp *= mesh.shape[a]
    t_loc = (b // dp) * s
    c = max(k, int(math.ceil(t_loc * k * cfg.capacity_factor / e)))
    x_spec = PS(axes, None, None)

    def f(xb, wr, wg, wu, wd, *ffn_w):
        bl = xb.shape[0]
        x2 = xb.reshape(bl * s, d)
        logits = (x2 @ wr.astype(x2.dtype)).astype(jnp.float32)
        probs = jax.nn.softmax(logits, axis=-1)
        top1 = jnp.argmax(probs, axis=-1)
        density = jnp.mean(jax.nn.one_hot(top1, e, dtype=jnp.float32), 0)
        aux = jax.lax.pmean(
            jnp.sum(density * jnp.mean(probs, axis=0)) * e, axes)
        slab, flat_idx, local_e, slot, gates, keep, token_of = \
            _dispatch_local(x2, probs, 0, e, e, k, c, xb.dtype)
        h = L.af(jnp.einsum("ecd,edf->ecf", slab, wg.astype(slab.dtype)),
                 cfg.activation, pol) * jnp.einsum(
            "ecd,edf->ecf", slab, wu.astype(slab.dtype))
        y = jnp.einsum("ecf,efd->ecd", h, wd.astype(h.dtype))
        y_pad = jnp.pad(y, ((0, 1), (0, 1), (0, 0)))
        vals = y_pad[jnp.minimum(local_e, e), slot]
        vals = vals * (gates.reshape(-1) * keep).astype(vals.dtype)[:, None]
        out = jnp.zeros((bl * s, d), vals.dtype).at[token_of].add(vals)
        if ffn_w:
            fg, fu, fd = ffn_w
            h2 = L.af(x2 @ fg.astype(x2.dtype), cfg.activation, pol) * (
                x2 @ fu.astype(x2.dtype))
            out = out + (h2 @ fd.astype(h2.dtype)).astype(out.dtype)
        return out.reshape(bl, s, d).astype(xb.dtype), aux

    ffn_args = () if ffn is None else (ffn[0], ffn[1], ffn[2])
    ffn_specs = () if ffn is None else (PS(), PS(), PS())
    out, aux = shard_map(
        f, mesh=mesh,
        in_specs=(x_spec, PS(), PS(), PS(), PS()) + ffn_specs,
        out_specs=(x_spec, PS()),
        check_vma=False,
    )(x, p.w_router, p.w_gate, p.w_up, p.w_down, *ffn_args)
    return out, aux
