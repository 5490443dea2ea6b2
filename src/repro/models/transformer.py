"""The unified LM: dense / MoE / SSM / hybrid / audio / vlm families.

One blocks-scanned decoder whose per-layer mixer is selected by the family:
  dense|audio|vlm : GQA attention
  moe             : GQA attention + (dense residual?) MoE FFN
  ssm             : RWKV6 time-mix + channel-mix (attention-free)
  hybrid          : parallel GQA-attention + SSM heads on one normed input:
                    hymba's Mamba, fused by per-branch normalisation then
                    mean, or Falcon-H1's Mamba-2, summed into the residual
                    (``cfg.ssm_heads`` and ``cfg.hybrid_fusion``)

Layers are stacked along a leading "layers" axis and executed with
``jax.lax.scan`` so the 40-48 layer production configs compile as a single
block.  Per-layer heterogeneity (hymba's sliding-window vs global layers)
rides along as a scanned per-layer window scalar.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig, ExecutionPolicy
from repro.core.quant_cache import dequantize_blocked, quantize_blocked
from repro.models import attention as A
from repro.models import layers as L
from repro.models import moe as M
from repro.models import spec as pspec
from repro.models import ssm as S
from repro.models.spec import P
from repro.parallel.sharding import constrain

Array = jax.Array


def _dt(cfg: ArchConfig):
    return jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32


# ---------------------------------------------------------------------------
# Parameter specs
# ---------------------------------------------------------------------------

def params_spec(cfg: ArchConfig) -> Dict[str, Any]:
    """Declaration tree for the whole model (stacked layers)."""
    Lr, D, dh = cfg.n_layers, cfg.d_model, cfg.head_dim_
    Hq, Hkv, F = cfg.n_heads, cfg.n_kv_heads, cfg.d_ff
    dt = _dt(cfg)

    def ly(*shape, axes, **kw):
        return P((Lr,) + shape, ("layers",) + axes, dtype=dt, **kw)

    tree: Dict[str, Any] = {}
    if cfg.input_kind == "tokens":
        tree["embed"] = P((cfg.vocab_size, D), ("vocab", "embed"), dtype=dt)
    else:
        # modality stub: frames arrive pre-embedded; a small adapter remains
        tree["frame_adapter"] = P((D, D), ("embed", "qkv"), dtype=dt,
                                  init="scaled")
    tree["ln_f"] = P((D,), ("embed",), init="ones")
    if cfg.n_codebooks:
        tree["lm_head"] = P((D, cfg.n_codebooks * cfg.vocab_size),
                            ("embed", "vocab"), dtype=dt, init="scaled")
    else:
        tree["lm_head"] = P((D, cfg.vocab_size), ("embed", "vocab"),
                            dtype=dt, init="scaled")

    blk: Dict[str, Any] = {"ln1": ly(D, axes=("embed",), init="ones"),
                           "ln2": ly(D, axes=("embed",), init="ones")}

    if cfg.family != "ssm":
        attn = {
            "wq": ly(D, Hq * dh, axes=("embed", "heads"), init="scaled"),
            "wk": ly(D, Hkv * dh, axes=("embed", "kv_heads"), init="scaled"),
            "wv": ly(D, Hkv * dh, axes=("embed", "kv_heads"), init="scaled"),
            "wo": ly(Hq * dh, D, axes=("heads", "embed"), init="scaled"),
        }
        if cfg.qkv_bias:
            attn["bq"] = ly(Hq * dh, axes=("heads",), init="zeros")
            attn["bk"] = ly(Hkv * dh, axes=("kv_heads",), init="zeros")
            attn["bv"] = ly(Hkv * dh, axes=("kv_heads",), init="zeros")
        blk["attn"] = attn

    if cfg.family in ("dense", "audio", "vlm", "hybrid"):
        blk["ffn"] = {
            "w_gate": ly(D, F, axes=("embed", "mlp"), init="scaled"),
            "w_up": ly(D, F, axes=("embed", "mlp"), init="scaled"),
            "w_down": ly(F, D, axes=("mlp", "embed"), init="scaled"),
        }
    if cfg.family == "moe":
        E, Fm = cfg.n_experts, cfg.moe_d_ff
        blk["moe"] = {
            "w_router": ly(D, E, axes=("embed", None), init="scaled"),
            "w_gate": ly(E, D, Fm, axes=("experts", "embed", "expert_mlp"),
                         init="scaled"),
            "w_up": ly(E, D, Fm, axes=("experts", "embed", "expert_mlp"),
                       init="scaled"),
            "w_down": ly(E, Fm, D, axes=("experts", "expert_mlp", "embed"),
                         init="scaled"),
        }
        if cfg.dense_residual:
            blk["ffn"] = {
                "w_gate": ly(D, F, axes=("embed", "mlp"), init="scaled"),
                "w_up": ly(D, F, axes=("embed", "mlp"), init="scaled"),
                "w_down": ly(F, D, axes=("mlp", "embed"), init="scaled"),
            }
    if cfg.family == "ssm":
        H = cfg.n_heads
        blk["tm"] = {
            "mu": ly(5, D, axes=(None, "embed"), init="zeros"),
            "w0": ly(D, axes=("embed",), init="zeros"),
            "w_lora_a": ly(D, 64, axes=("embed", None), init="scaled"),
            "w_lora_b": ly(64, D, axes=(None, "embed"), init="scaled"),
            "bonus": ly(H, dh, axes=("heads", None), init="zeros"),
            "wr": ly(D, D, axes=("embed", "heads"), init="scaled"),
            "wk": ly(D, D, axes=("embed", "heads"), init="scaled"),
            "wv": ly(D, D, axes=("embed", "heads"), init="scaled"),
            "wg": ly(D, D, axes=("embed", "heads"), init="scaled"),
            "wo": ly(D, D, axes=("heads", "embed"), init="scaled"),
            "ln_w": ly(D, axes=("embed",), init="ones"),
        }
        blk["cm"] = {
            "mu_k": ly(D, axes=("embed",), init="zeros"),
            "mu_r": ly(D, axes=("embed",), init="zeros"),
            "wk": ly(D, F, axes=("embed", "mlp"), init="scaled"),
            "wv": ly(F, D, axes=("mlp", "embed"), init="scaled"),
            "wr": ly(D, D, axes=("embed", "qkv"), init="scaled"),
        }
        del blk["ln2"]  # channel-mix has its own pre-norm
        blk["ln2"] = ly(D, axes=("embed",), init="ones")
    if cfg.family == "hybrid":
        if cfg.ssm_heads:
            H, N = cfg.ssm_heads, cfg.ssm_state
            Di, GN = H * cfg.ssm_head_dim, cfg.ssm_groups * cfg.ssm_state
            blk["mamba2"] = {
                "w_z": ly(D, Di, axes=("embed", "mlp"), init="scaled"),
                "w_x": ly(D, Di, axes=("embed", "mlp"), init="scaled"),
                "w_b": ly(D, GN, axes=("embed", None), init="scaled"),
                "w_c": ly(D, GN, axes=("embed", None), init="scaled"),
                "w_dt": ly(D, H, axes=("embed", None), init="scaled"),
                "conv_w": ly(cfg.ssm_conv, Di + 2 * GN, axes=(None, None),
                             init="scaled"),
                "conv_b": ly(Di + 2 * GN, axes=(None,), init="zeros"),
                "dt_bias": ly(H, axes=(None,), init="zeros"),
                "a_log": ly(H, axes=(None,), init="zeros"),
                "d_skip": ly(H, axes=(None,), init="ones"),
                "norm": ly(Di, axes=(None,), init="ones"),
                "w_out": ly(Di, D, axes=("mlp", "embed"), init="scaled"),
            }
        else:
            Di = D  # mamba inner width = d_model (hymba parallel heads)
            N = cfg.ssm_state
            blk["mamba"] = {
                "w_in": ly(D, 2 * Di, axes=("embed", "mlp"), init="scaled"),
                "conv_w": ly(cfg.ssm_conv, Di, axes=(None, "embed"),
                             init="scaled"),
                "w_bc": ly(Di, 2 * N + 1, axes=("embed", None), init="scaled"),
                "a_log": ly(Di, N, axes=("embed", "state"), init="zeros"),
                "d_skip": ly(Di, axes=("embed",), init="ones"),
                "w_out": ly(Di, D, axes=("mlp", "embed"), init="scaled"),
            }
        if cfg.hybrid_fusion == "norm_mean":
            blk["norm_attn"] = ly(dh * cfg.n_heads, axes=("heads",),
                                  init="ones")
            blk["norm_ssm"] = ly(D, axes=("embed",), init="ones")
    tree["blocks"] = blk
    return tree


def layer_windows(cfg: ArchConfig, seq_len: int) -> np.ndarray:
    """Per-layer attention window (scanned alongside params)."""
    full = 2 ** 30
    if cfg.sliding_window <= 0:
        return np.full((cfg.n_layers,), full, np.int32)
    w = np.full((cfg.n_layers,), cfg.sliding_window, np.int32)
    if cfg.global_attn_every > 0 and seq_len <= 65536:
        # periodic global layers (hymba); in long_500k mode every layer is
        # windowed to keep the cache sub-quadratic (see DESIGN.md).
        w[::cfg.global_attn_every] = full
        w[-1] = full
    return w


# ---------------------------------------------------------------------------
# Block forward (training / prefill)
# ---------------------------------------------------------------------------

@jax.named_scope("head")
def _lm_head(x: Array, params: Dict[str, Any], pol: ExecutionPolicy
             ) -> Array:
    """The vocabulary projection, under the named scope ``head``."""
    return L.dense(x, params["lm_head"], pol)


def _attn_params(bp: Dict[str, Array], cfg: ArchConfig) -> A.AttnParams:
    return A.AttnParams(bp["attn"]["wq"], bp["attn"]["wk"], bp["attn"]["wv"],
                        bp["attn"]["wo"], bp["attn"].get("bq"),
                        bp["attn"].get("bk"), bp["attn"].get("bv"))


def _ssm_zero_state(cfg: ArchConfig, b: int, dtype) -> Tuple[Array, Array]:
    """A fresh hybrid layer's (conv tail, SSM state) for ``b`` rows."""
    tail, h = S.ssm_state_shapes(cfg)
    return (jnp.zeros((b,) + tail, dtype), jnp.zeros((b,) + h, jnp.float32))


def _ssm_branch(h: Array, bp: Dict[str, Any], cfg: ArchConfig,
                pol: ExecutionPolicy, state, **kw):
    """A hybrid layer's SSM heads: Mamba-2 where ``cfg.ssm_heads`` is
    set, else hymba's Mamba."""
    if cfg.ssm_heads:
        return S.mamba2_mix(h, S.Mamba2Params(**bp["mamba2"]), cfg, pol,
                            state, **kw)
    return S.mamba_mix(h, S.MambaParams(**bp["mamba"]), cfg, pol, state,
                       **kw)


def _fuse(x: Array, attn_out: Array, ssm_out: Array, bp: Dict[str, Any],
          cfg: ArchConfig) -> Array:
    """Join a hybrid layer's two branches into the residual stream."""
    if cfg.hybrid_fusion == "sum":         # Falcon-H1
        return x + (attn_out + ssm_out)
    if cfg.hybrid_fusion != "norm_mean":
        raise ValueError(f"unknown hybrid_fusion {cfg.hybrid_fusion!r}")
    # hymba: normalise each branch, then average
    attn_out = L.rms_norm(attn_out, bp["norm_attn"], cfg.norm_eps)
    ssm_out = L.rms_norm(ssm_out, bp["norm_ssm"], cfg.norm_eps)
    return x + 0.5 * (attn_out + ssm_out)


def block_forward(x: Array, bp: Dict[str, Any], cfg: ArchConfig,
                  pol: ExecutionPolicy, positions: Array, window: Array,
                  ) -> Tuple[Array, Array]:
    """One decoder block (full-sequence). Returns (x, aux_loss)."""
    aux = jnp.float32(0.0)
    if cfg.family == "ssm":
        h = L.rms_norm(x, bp["ln1"], cfg.norm_eps)
        b, t, d = h.shape
        dk = d // cfg.n_heads
        st = (jnp.zeros((b, d), h.dtype),
              jnp.zeros((b, cfg.n_heads, dk, dk), jnp.float32))
        tm_out, _ = S.rwkv6_timemix(h, S.Rwkv6Params(**bp["tm"]), cfg, pol, st)
        x = x + tm_out
        h = L.rms_norm(x, bp["ln2"], cfg.norm_eps)
        cm_out, _ = S.rwkv6_channelmix(h, S.Rwkv6ChannelParams(**bp["cm"]),
                                       cfg, pol, jnp.zeros((b, d), h.dtype))
        return x + cm_out, aux

    h = L.rms_norm(x, bp["ln1"], cfg.norm_eps)
    q, k, v = A.qkv(h, _attn_params(bp, cfg), cfg, pol, positions)
    ctx = A.attention(q, k, v, cfg, pol, positions, positions, window)
    attn_out = L.dense(ctx.reshape(*x.shape[:2], -1), bp["attn"]["wo"], pol)

    if cfg.family == "hybrid":
        st = _ssm_zero_state(cfg, h.shape[0], h.dtype)
        ssm_out, _ = _ssm_branch(h, bp, cfg, pol, st)
        x = _fuse(x, attn_out, ssm_out, bp, cfg)
    else:
        x = x + attn_out
    x = constrain(x, ("batch", "seq", "embed"))

    h = L.rms_norm(x, bp["ln2"], cfg.norm_eps)
    if cfg.family == "moe":
        fused = (cfg.fuse_moe_ffn_ar and cfg.dense_residual)
        ffn_w = (bp["ffn"]["w_gate"], bp["ffn"]["w_up"],
                 bp["ffn"]["w_down"]) if fused else None
        moe_out, aux = M.moe_ffn(h, M.MoEParams(**bp["moe"]), cfg, pol,
                                 ffn=ffn_w)
        if cfg.dense_residual and not fused:
            moe_out = moe_out + L.swiglu(h, bp["ffn"]["w_gate"],
                                         bp["ffn"]["w_up"],
                                         bp["ffn"]["w_down"], pol,
                                         cfg.activation)
        x = x + moe_out
    else:
        x = x + L.swiglu(h, bp["ffn"]["w_gate"], bp["ffn"]["w_up"],
                         bp["ffn"]["w_down"], pol, cfg.activation)
    return constrain(x, ("batch", "seq", "embed")), aux


def forward(params: Dict[str, Any], batch: Dict[str, Array],
            cfg: ArchConfig, pol: Optional[ExecutionPolicy] = None) -> Array:
    """Full-sequence forward -> logits.

    batch: {"tokens": (B,S) int32} or {"frames": (B,S,D)} for stub
    frontends.
    """
    pol = pol or cfg.exec_policy
    if cfg.input_kind == "tokens":
        x = L.embedding_lookup(batch["tokens"], params["embed"])
    else:
        x = batch["frames"].astype(_dt(cfg)) @ params["frame_adapter"]
    x = constrain(x, ("batch", "seq", "embed"))
    b, s = x.shape[:2]
    positions = jnp.arange(s, dtype=jnp.int32)
    windows = jnp.asarray(layer_windows(cfg, s))

    def body(carry, xs):
        x, aux = carry
        bp, win = xs
        x, a = block_forward(x, bp, cfg, pol, positions, win)
        return (x, aux + a), None

    block_fn = body
    if cfg.remat:
        block_fn = jax.checkpoint(body, prevent_cse=False)
    (x, aux), _ = jax.lax.scan(block_fn, (x, jnp.float32(0.0)),
                               (params["blocks"], windows))
    x = L.rms_norm(x, params["ln_f"], cfg.norm_eps)
    logits = _lm_head(x, params, pol)
    if cfg.n_codebooks:
        logits = logits.reshape(b, s, cfg.n_codebooks, cfg.vocab_size)
    return logits


def loss_fn(params, batch, cfg: ArchConfig,
            pol: Optional[ExecutionPolicy] = None) -> Tuple[Array, Dict]:
    pol = pol or cfg.exec_policy
    if cfg.input_kind == "tokens":
        x = L.embedding_lookup(batch["tokens"], params["embed"])
    else:
        x = batch["frames"].astype(_dt(cfg)) @ params["frame_adapter"]
    x = constrain(x, ("batch", "seq", "embed"))
    b, s = x.shape[:2]
    positions = jnp.arange(s, dtype=jnp.int32)
    windows = jnp.asarray(layer_windows(cfg, s))

    def body(carry, xs):
        xc, aux = carry
        bp, win = xs
        xc, a = block_forward(xc, bp, cfg, pol, positions, win)
        return (xc, aux + a), None

    block_fn = jax.checkpoint(body, prevent_cse=False) if cfg.remat else body
    (x, aux), _ = jax.lax.scan(block_fn, (x, jnp.float32(0.0)),
                               (params["blocks"], windows))
    x = L.rms_norm(x, params["ln_f"], cfg.norm_eps)
    logits = _lm_head(x, params, pol)
    if cfg.n_codebooks:
        logits = logits.reshape(b, s, cfg.n_codebooks, cfg.vocab_size)
    ce = L.cross_entropy(logits, batch["labels"], batch.get("mask"))
    total = ce + 0.01 * aux / max(cfg.n_layers, 1)
    return total, {"ce": ce, "aux": aux}


# ---------------------------------------------------------------------------
# Serving: prefill + single-token decode with stacked per-layer caches
# ---------------------------------------------------------------------------

class DecodeState(NamedTuple):
    """Stacked (n_layers leading dim) recurrent state for every family.

    :func:`decode_step` carries the stacked fields through its layer
    loop, and layer l writes only what it changes: one K/V column a row
    and its whole recurrent state.  The K/V
    cache is head-major, so a layer's (row, head) history is one
    (S, dh) matrix, the operand the attention products read in place.

    The ``*scale*`` fields carry the per-block float32 scales of the
    quantized cache mode (``cfg.cache_quant == "int8"``, see
    :mod:`repro.core.quant_cache`); they stay ``None`` otherwise.
    """
    cache_k: Optional[Array] = None     # (L,B,Hkv,S,dh), head-major
    cache_v: Optional[Array] = None
    pos: Optional[Array] = None         # scalar int32 tokens-seen
    # ssm / hybrid
    x_prev: Optional[Array] = None      # (L,B,D) rwkv token-shift boundary
    cm_prev: Optional[Array] = None     # (L,B,D) rwkv channel-mix boundary
    wkv: Optional[Array] = None         # (L,B,H,dk,dk) rwkv state
    # hybrid: mamba conv tail (L,B,K-1,C) and state, shaped by
    # ssm.ssm_state_shapes: hymba (L,B,D,N), Mamba-2 (L,B,H,P,N)
    conv_tail: Optional[Array] = None
    ssm_h: Optional[Array] = None
    # per-block int8 cache scales (cache_quant="int8" only)
    scale_k: Optional[Array] = None     # (L,B,Hkv,S,1)
    scale_v: Optional[Array] = None     # (L,B,Hkv,S,1)
    wkv_scale: Optional[Array] = None   # (L,B,H,dk,1)
    ssm_scale: Optional[Array] = None   # ssm_h's shape, last axis 1


def kv_pos_axis(state) -> int:
    """The position axis of a slot state's stacked K/V leaves: 3 in the
    dense head-major (L, B, Hkv, S, dh) cache, 2 (within a page) in a
    paged (L, N, page, Hkv, dh) pool."""
    return 2 if getattr(state, "block_tables", None) is not None else 3


def slot_kv_history(leaf, row: int, n: int):
    """Row ``row``'s first ``n`` positions of a dense stacked K/V leaf
    (L, B, Hkv, S, ...), token-major: (L, n, Hkv, ...)."""
    return leaf[:, row, :, :n].swapaxes(1, 2)


def slot_kv_leaf(like, length: int, hist=None) -> np.ndarray:
    """A one-row host K/V leaf in the dense layout of ``like`` (L, B,
    Hkv, S, ...), with B = 1 and S = ``length``: token-major ``hist``
    (L, n, Hkv, ...), as :func:`slot_kv_history` gives it, in its first
    n positions and zeros after."""
    out = np.zeros(tuple(like.shape[:1]) + (1, like.shape[2], length)
                   + tuple(like.shape[4:]), like.dtype)
    if hist is not None:
        out[:, 0, :, :hist.shape[1]] = hist.swapaxes(1, 2)
    return out


def _cache_quant(cfg: ArchConfig) -> bool:
    """Whether the per-block int8 serving-cache format is active.

    Delegates to :meth:`ArchConfig.cache_spec` — the one resolver for the
    cache format — so unknown ``cache_quant`` strings and the
    int8-vs-fxp8 mutual exclusion raise here exactly as before.
    """
    return cfg.cache_spec().quantized


def init_decode_state(cfg: ArchConfig, batch: int, max_seq: int,
                      abstract: bool = False) -> DecodeState:
    Lr, D, dh = cfg.n_layers, cfg.d_model, cfg.head_dim_
    dt = _dt(cfg)
    spec = cfg.cache_spec()
    qc = spec.quantized
    kv_dt = jnp.int8 if spec.dtype in ("int8", "fxp8") else dt
    mk = (jax.ShapeDtypeStruct if abstract
          else (lambda sh, d: jnp.zeros(sh, d)))
    fields: Dict[str, Any] = {"pos": (jax.ShapeDtypeStruct((), jnp.int32)
                                      if abstract else jnp.zeros((), jnp.int32))}
    if cfg.family != "ssm":
        cache_len = max_seq
        if cfg.sliding_window and cfg.supports_long_context and \
                max_seq > 65536:
            cache_len = cfg.sliding_window  # long_500k: ring cache only
        fields["cache_k"] = mk((Lr, batch, cfg.n_kv_heads, cache_len, dh),
                               kv_dt)
        fields["cache_v"] = mk((Lr, batch, cfg.n_kv_heads, cache_len, dh),
                               kv_dt)
        if qc:
            fields["scale_k"] = mk((Lr, batch, cfg.n_kv_heads, cache_len, 1),
                                   jnp.float32)
            fields["scale_v"] = mk((Lr, batch, cfg.n_kv_heads, cache_len, 1),
                                   jnp.float32)
    if cfg.family == "ssm":
        fields["x_prev"] = mk((Lr, batch, D), dt)
        fields["cm_prev"] = mk((Lr, batch, D), dt)
        # quantized mode stores the O(1) recurrent state itself as int8;
        # the tiny token-shift boundaries (x_prev/cm_prev) stay exact
        fields["wkv"] = mk((Lr, batch, cfg.n_heads, dh, dh),
                           jnp.int8 if qc else jnp.float32)
        if qc:
            fields["wkv_scale"] = mk((Lr, batch, cfg.n_heads, dh, 1),
                                     jnp.float32)
    if cfg.family == "hybrid":
        tail, hs = S.ssm_state_shapes(cfg)
        fields["conv_tail"] = mk((Lr, batch) + tail, dt)
        fields["ssm_h"] = mk((Lr, batch) + hs,
                             jnp.int8 if qc else jnp.float32)
        if qc:
            fields["ssm_scale"] = mk((Lr, batch) + hs[:-1] + (1,),
                                     jnp.float32)
    return DecodeState(**fields)


# DecodeState fields stacked over layers that the decode layer loop
# carries whole; layer l reads its slice and writes back only what it
# changes (one K/V column a row, its whole recurrent state)
_KV_FIELDS = ("cache_k", "cache_v", "scale_k", "scale_v")
_LAYER_FIELDS = _KV_FIELDS + ("conv_tail", "ssm_h", "ssm_scale", "x_prev",
                              "cm_prev", "wkv", "wkv_scale")
# the int8 recurrent fields and the per-block scale field of each
_SCALE_FOR = {"wkv": "wkv_scale", "ssm_h": "ssm_scale"}
# the head-major cache's logical axes (spec_for resolves kv heads first)
_CACHE_AXES = ("layers", "batch", "kv_heads", "seq", None)


def _read_layer(st: Dict[str, Array], name: str, layer) -> Array:
    """Layer ``layer``'s slice of carried field ``name``, dequantized
    where the field is held as int8 beside per-block scales."""
    v = jax.lax.dynamic_index_in_dim(st[name], layer, 0, keepdims=False)
    scale = _SCALE_FOR.get(name)
    if scale in st:
        v = dequantize_blocked(v, jax.lax.dynamic_index_in_dim(
            st[scale], layer, 0, keepdims=False))
    return v


def _write_layer(st: Dict[str, Array], layer, **new: Array
                 ) -> Dict[str, Array]:
    """``st`` with each ``new`` value written in place as layer
    ``layer``'s slice of its field (quantized first where int8)."""
    out = dict(st)
    for name, v in new.items():
        scale = _SCALE_FOR.get(name)
        if scale in st:
            v, s = quantize_blocked(v)
            out[scale] = jax.lax.dynamic_update_index_in_dim(st[scale], s,
                                                             layer, 0)
        out[name] = jax.lax.dynamic_update_index_in_dim(st[name], v, layer,
                                                        0)
    return out


def _scan_layers(body, x: Array, params: Dict[str, Any], windows: Array,
                 state, paged: bool):
    """Run ``body(x, bp, window, layer, st) -> (x, st)`` over the layers,
    with the state's stacked fields (:data:`_LAYER_FIELDS`) in the scan's
    carry as ``st``.  Only the weights and windows are scanned over, so
    no field is stacked anew as the scan's output and none is copied back
    after the loop: with the state donated, XLA updates it in place.
    Returns (x, state with the carried fields replaced)."""
    def keep_layout(st):
        if paged:
            return st
        return {n: constrain(v, _CACHE_AXES) if n in _KV_FIELDS else v
                for n, v in st.items()}

    def step(carry, xs):
        x, layer, st = carry
        x, st = body(x, *xs, layer, st)
        return (x, layer + 1, keep_layout(st)), None

    st = {n: getattr(state, n) for n in _LAYER_FIELDS
          if getattr(state, n) is not None}
    (x, _, st), _ = jax.lax.scan(
        step, (x, jnp.int32(0), keep_layout(st)), (params["blocks"], windows))
    return x, state._replace(**st)


def decode_step(params: Dict[str, Any], state: DecodeState,
                batch: Dict[str, Array], cfg: ArchConfig,
                pol: Optional[ExecutionPolicy] = None
                ) -> Tuple[Array, DecodeState]:
    """One new token for every sequence. batch: {"tokens": (B,1)} or
    {"frames": (B,1,D)}.  Returns (logits, new state).

    The layer loop carries the stacked state (:func:`_scan_layers`):
    layer l writes its new K/V column a row into the stacked cache, then
    attends over its slice of it, and overwrites its slice of the
    recurrent state (Mamba-2's or hymba's, rwkv6's).  Every other column
    and layer is left as it was."""
    pol = pol or cfg.exec_policy
    if cfg.input_kind == "tokens":
        x = L.embedding_lookup(batch["tokens"], params["embed"])
    else:
        x = batch["frames"].astype(_dt(cfg)) @ params["frame_adapter"]
    b = x.shape[0]
    pos = state.pos
    per_row = jnp.ndim(pos) == 1            # serving slots: own pos per row
    paged = getattr(state, "block_tables", None) is not None
    if state.cache_k is not None:
        cache_len = state.cache_k.shape[kv_pos_axis(state)]
        if paged:   # pool (L,N,page,...): logical capacity is the table's
            cache_len = state.block_tables.shape[1] * cache_len
        if cfg.sliding_window and cache_len <= cfg.sliding_window:
            # ring cache (long_500k): every layer is windowed
            windows = jnp.full((cfg.n_layers,), cfg.sliding_window, jnp.int32)
        else:
            windows = jnp.asarray(layer_windows(cfg, cache_len))
    else:
        windows = jnp.asarray(layer_windows(cfg, 4096))

    def body(x, bp, win, layer, st):
        h = L.rms_norm(x, bp["ln1"], cfg.norm_eps)
        if cfg.family == "ssm":
            # an int8 wkv state round-trips through the exact f32
            # recurrence step once per token (_read_layer/_write_layer)
            tm_out, (xp2, wkv2) = S.rwkv6_timemix(
                h, S.Rwkv6Params(**bp["tm"]), cfg, pol,
                (_read_layer(st, "x_prev", layer),
                 _read_layer(st, "wkv", layer)))
            x = x + tm_out
            h = L.rms_norm(x, bp["ln2"], cfg.norm_eps)
            cm_out, cp2 = S.rwkv6_channelmix(
                h, S.Rwkv6ChannelParams(**bp["cm"]), cfg, pol,
                _read_layer(st, "cm_prev", layer))
            return x + cm_out, _write_layer(st, layer, x_prev=xp2,
                                            cm_prev=cp2, wkv=wkv2)
        positions = (pos[:, None].astype(jnp.int32) if per_row
                     else jnp.full((1,), pos, jnp.int32))
        q, k, v = A.qkv(h, _attn_params(bp, cfg), cfg, pol, positions)
        scales = {n: st[n] for n in ("scale_k", "scale_v") if n in st}
        if paged:
            ctx, *caches = A.paged_decode_attention(
                q, k, v, st["cache_k"], st["cache_v"], state.block_tables,
                pos, cfg, pol, win, layer=layer, **scales)
        else:
            ctx, *caches = A.decode_attention(
                q, k, v, st["cache_k"], st["cache_v"], pos, cfg, pol, win,
                layer=layer, **scales)
        st = {**st, **dict(zip(_KV_FIELDS, caches))}
        attn_out = L.dense(ctx.reshape(b, 1, -1), bp["attn"]["wo"], pol)
        if cfg.family == "hybrid":
            ssm_out, (tail2, h2) = _ssm_branch(
                h, bp, cfg, pol, (_read_layer(st, "conv_tail", layer),
                                  _read_layer(st, "ssm_h", layer)))
            x = _fuse(x, attn_out, ssm_out, bp, cfg)
            st = _write_layer(st, layer, conv_tail=tail2, ssm_h=h2)
        else:
            x = x + attn_out
        h = L.rms_norm(x, bp["ln2"], cfg.norm_eps)
        if cfg.family == "moe":
            moe_out, _ = M.moe_ffn(h, M.MoEParams(**bp["moe"]), cfg, pol)
            if cfg.dense_residual:
                moe_out = moe_out + L.swiglu(h, bp["ffn"]["w_gate"],
                                             bp["ffn"]["w_up"],
                                             bp["ffn"]["w_down"], pol,
                                             cfg.activation)
            x = x + moe_out
        else:
            x = x + L.swiglu(h, bp["ffn"]["w_gate"], bp["ffn"]["w_up"],
                             bp["ffn"]["w_down"], pol, cfg.activation)
        return x, st

    x, new_state = _scan_layers(body, x, params, windows, state, paged)
    new_state = new_state._replace(pos=pos + 1)
    x = L.rms_norm(x, params["ln_f"], cfg.norm_eps)
    logits = _lm_head(x, params, pol)
    if cfg.n_codebooks:
        logits = logits.reshape(b, 1, cfg.n_codebooks, cfg.vocab_size)
    return logits, new_state


def prefill(params, batch, cfg: ArchConfig,
            pol: Optional[ExecutionPolicy] = None,
            headroom: int = 64,
            lengths: Optional[Array] = None) -> Tuple[Array, DecodeState]:
    """Full-sequence forward that also populates the decode state.

    For attention families the per-layer K/V are written into a cache with
    ``headroom`` extra decode slots (prefill_32k lowers this path);
    recurrent families fold the sequence into their O(1) state.

    ``lengths`` (B,) marks each row's true prompt length in a batch whose
    prompts are **right-padded** to a common bucket (the serving engine's
    shape buckets): causal attention already ignores the trailing pads for
    the real positions, recurrent state updates are masked to no-ops on pad
    steps, the returned logits are each row's *last real* position, and
    ``state.pos`` comes back per-row — ready for
    :func:`slot_update`/:func:`decode_step` with per-slot positions.
    Outputs for the real tokens are bit-identical to the unpadded run.
    """
    pol = pol or cfg.exec_policy
    if cfg.input_kind == "tokens":
        x = L.embedding_lookup(batch["tokens"], params["embed"])
    else:
        x = batch["frames"].astype(_dt(cfg)) @ params["frame_adapter"]
    b, s = x.shape[:2]
    positions = jnp.arange(s, dtype=jnp.int32)
    windows = jnp.asarray(layer_windows(cfg, s))
    state = init_decode_state(cfg, b, s + headroom)
    mask = (None if lengths is None
            else jnp.arange(s)[None, :] < lengths[:, None])

    def body(carry, xs):
        x = carry
        if cfg.family == "ssm":
            bp = xs
            h = L.rms_norm(x, bp["ln1"], cfg.norm_eps)
            dk = cfg.d_model // cfg.n_heads
            st = (jnp.zeros((b, cfg.d_model), h.dtype),
                  jnp.zeros((b, cfg.n_heads, dk, dk), jnp.float32))
            tm_out, (xp, wkv) = S.rwkv6_timemix(
                h, S.Rwkv6Params(**bp["tm"]), cfg, pol, st,
                mask=mask, lengths=lengths)
            x = x + tm_out
            h = L.rms_norm(x, bp["ln2"], cfg.norm_eps)
            cm_out, cp = S.rwkv6_channelmix(
                h, S.Rwkv6ChannelParams(**bp["cm"]), cfg, pol,
                jnp.zeros((b, cfg.d_model), h.dtype), lengths=lengths)
            return x + cm_out, (xp, cp, wkv)

        bp, win = xs
        h = L.rms_norm(x, bp["ln1"], cfg.norm_eps)
        q, k, v = A.qkv(h, _attn_params(bp, cfg), cfg, pol, positions)
        ctx = A.attention(q, k, v, cfg, pol, positions, positions, win)
        attn_out = L.dense(ctx.reshape(b, s, -1), bp["attn"]["wo"], pol)
        ys_extra = ()
        if cfg.family == "hybrid":
            ssm_out, (tail, hh) = _ssm_branch(
                h, bp, cfg, pol, _ssm_zero_state(cfg, b, h.dtype),
                mask=mask, lengths=lengths)
            x = _fuse(x, attn_out, ssm_out, bp, cfg)
            ys_extra = (tail, hh)
        else:
            x = x + attn_out
        h = L.rms_norm(x, bp["ln2"], cfg.norm_eps)
        if cfg.family == "moe":
            moe_out, _ = M.moe_ffn(h, M.MoEParams(**bp["moe"]), cfg, pol)
            if cfg.dense_residual:
                moe_out = moe_out + L.swiglu(h, bp["ffn"]["w_gate"],
                                             bp["ffn"]["w_up"],
                                             bp["ffn"]["w_down"], pol,
                                             cfg.activation)
            x = x + moe_out
        else:
            x = x + L.swiglu(h, bp["ffn"]["w_gate"], bp["ffn"]["w_up"],
                             bp["ffn"]["w_down"], pol, cfg.activation)
        return x, (k, v) + ys_extra

    qc = _cache_quant(cfg)

    def pad_seq(t):
        # (L,B,S,Hkv,...) layer outputs -> the head-major cache layout,
        # zero-padded along the sequence axis to the slot cache length
        t = jnp.swapaxes(t, 2, 3)
        tgt = state.cache_k.shape[3]
        if t.shape[3] != tgt:
            t = jnp.pad(t, ((0, 0),) * 3 + ((0, tgt - t.shape[3]),)
                        + ((0, 0),) * (t.ndim - 4))
        return t

    def pad_cache(t):
        # write the prefilled K/V into slots [0, s); headroom slots stay 0.
        # The cache lives seq-sharded over the model axis (the decode
        # memory-term fix) regardless of how the per-layer k/v were laid
        # out during the forward pass.  Already-int8 inputs (the per-block
        # quantized mode quantizes before padding) must not re-quantize
        # through the legacy fixed-scale path.
        if state.cache_k.dtype == jnp.int8 and t.dtype != jnp.int8:
            t = A.quantize_kv(t)
        return constrain(pad_seq(t), _CACHE_AXES)

    pos = (jnp.int32(s) if lengths is None else lengths.astype(jnp.int32))
    if cfg.family == "ssm":
        x, (xp, cp, wkv) = jax.lax.scan(body, x, params["blocks"])
        if qc:
            wkv, wkv_s = quantize_blocked(wkv)
            state = state._replace(x_prev=xp, cm_prev=cp, wkv=wkv,
                                   wkv_scale=wkv_s, pos=pos)
        else:
            state = state._replace(x_prev=xp, cm_prev=cp, wkv=wkv, pos=pos)
    elif cfg.family == "hybrid":
        x, (ks, vs, tails, hs) = jax.lax.scan(body, x,
                                              (params["blocks"], windows))
        if qc:
            ks, ks_s = quantize_blocked(ks)
            vs, vs_s = quantize_blocked(vs)
            hs, hs_s = quantize_blocked(hs)
            state = state._replace(cache_k=pad_cache(ks),
                                   cache_v=pad_cache(vs),
                                   scale_k=pad_seq(ks_s),
                                   scale_v=pad_seq(vs_s),
                                   conv_tail=tails, ssm_h=hs,
                                   ssm_scale=hs_s, pos=pos)
        else:
            state = state._replace(cache_k=pad_cache(ks),
                                   cache_v=pad_cache(vs),
                                   conv_tail=tails, ssm_h=hs, pos=pos)
    else:
        x, (ks, vs) = jax.lax.scan(body, x, (params["blocks"], windows))
        if qc:
            ks, ks_s = quantize_blocked(ks)
            vs, vs_s = quantize_blocked(vs)
            state = state._replace(cache_k=pad_cache(ks),
                                   cache_v=pad_cache(vs),
                                   scale_k=pad_seq(ks_s),
                                   scale_v=pad_seq(vs_s), pos=pos)
        else:
            state = state._replace(cache_k=pad_cache(ks),
                                   cache_v=pad_cache(vs), pos=pos)

    if lengths is None:
        x_last = x[:, -1:, :]
    else:                       # each row's last *real* position
        idx = (lengths - 1).astype(jnp.int32)[:, None, None]
        x_last = jnp.take_along_axis(
            x, jnp.broadcast_to(idx, (b, 1, x.shape[-1])), axis=1)
    x_last = L.rms_norm(x_last, params["ln_f"], cfg.norm_eps)
    logits = _lm_head(x_last, params, pol)
    return logits, state


# ---------------------------------------------------------------------------
# Serving slots: per-slot state insertion (the continuous-batching seam)
# ---------------------------------------------------------------------------

def init_slot_state(cfg: ArchConfig, max_batch: int, max_seq: int,
                    abstract: bool = False) -> DecodeState:
    """Decode state for ``max_batch`` persistent serving slots.

    Identical to :func:`init_decode_state` except ``pos`` is a ``(B,)``
    vector — every slot tracks its own tokens-seen counter, so slots
    prefilled at different times (and lengths) can decode in one batch.
    """
    st = init_decode_state(cfg, max_batch, max_seq, abstract)
    pos = (jax.ShapeDtypeStruct((max_batch,), jnp.int32) if abstract
           else jnp.zeros((max_batch,), jnp.int32))
    return st._replace(pos=pos)


def slot_update(state: DecodeState, sub: DecodeState, slots: Array
                ) -> DecodeState:
    """Scatter ``sub``'s per-request state into ``state`` at slot indices.

    ``state`` is the engine's persistent slot state (``pos`` per-row, from
    :func:`init_slot_state`); ``sub`` is a fresh prefill over a (possibly
    smaller, bucket-padded) batch; ``slots`` (B_sub,) maps each ``sub`` row
    to a target slot.  Out-of-range slot indices (>= max_batch) are
    dropped — the engine pads admission groups with a sentinel so one
    traced program covers every group size.  K/V caches shorter than the
    slot cache (prompt buckets < max_seq) are zero-padded along the
    sequence axis; every state family (attention KV, rwkv wkv/token-shift,
    mamba conv/ssm) scatters along its batch axis (axis 1 under the
    stacked layers axis).
    """
    slots = jnp.asarray(slots, jnp.int32)
    out: Dict[str, Any] = {}
    for name in DecodeState._fields:
        tgt = getattr(state, name)
        src = getattr(sub, name)
        if tgt is None or src is None:
            out[name] = tgt
            continue
        if name == "pos":
            src = jnp.broadcast_to(src.astype(tgt.dtype), slots.shape)
            out[name] = tgt.at[slots].set(src, mode="drop")
            continue
        if name in _KV_FIELDS and src.shape[3] != tgt.shape[3]:
            grow = tgt.shape[3] - src.shape[3]
            if grow < 0:
                raise ValueError(
                    f"prefill cache ({src.shape[3]}) exceeds slot cache "
                    f"({tgt.shape[3]}); raise the engine's max_seq")
            src = jnp.pad(src, [(0, 0)] * 3 + [(0, grow)]
                          + [(0, 0)] * (src.ndim - 4))
        out[name] = tgt.at[:, slots].set(src.astype(tgt.dtype), mode="drop")
    return DecodeState(**out)


def slot_extract(state: DecodeState, slots: Array) -> DecodeState:
    """Gather per-slot state rows at slot indices — the inverse of
    :func:`slot_update`, and the serving snapshot's extract seam.

    ``slots`` (G,) picks rows along the batch axis (axis 1 under the
    stacked layers axis; axis 0 for ``pos``) of every present leaf; the
    result is a sub-state shaped exactly like a prefill's output for G
    requests, so ``slot_update(state, slot_extract(state, slots), slots)``
    is an identity and a snapshot restores through the same scatter that
    admissions use.  Leaves come back **raw** (int8 cache leaves and
    their scale leaves verbatim) — restore must be bit-identical, never a
    dequant/requant round trip.
    """
    slots = jnp.asarray(slots, jnp.int32)
    out: Dict[str, Any] = {}
    for name in DecodeState._fields:
        leaf = getattr(state, name)
        if leaf is None:
            out[name] = None
        elif name == "pos":
            out[name] = leaf[slots]
        else:
            out[name] = leaf[:, slots]
    return DecodeState(**out)


# ---------------------------------------------------------------------------
# Speculative decode: k+1-position verify with variable per-row commit
# ---------------------------------------------------------------------------

# Recurrent DecodeState fields that must roll back when drafted tokens are
# rejected (everything O(1)-per-slot; the K/V caches never roll back — a
# rejected write sits at a position > the committed ``pos`` and is invalid
# by the age mask until the real token at that position overwrites it).
REC_FIELDS = ("x_prev", "cm_prev", "wkv", "conv_tail", "ssm_h")


# ring-cache verify: rec_stack keys carrying the raw evicted K/V columns
# (L, B, K, ...) that spec_commit restores for rejected candidates, and
# the cache field each one restores into
_RING_KEYS = ("ring_k", "ring_v", "ring_sk", "ring_sv")
_RING_FIELD = {"ring_k": "cache_k", "ring_v": "cache_v",
               "ring_sk": "scale_k", "ring_sv": "scale_v"}


def verify_step(params: Dict[str, Any], state: DecodeState,
                batch: Dict[str, Array], cfg: ArchConfig,
                pol: Optional[ExecutionPolicy] = None
                ) -> Tuple[Array, DecodeState, Dict[str, Array]]:
    """Score ``K = k+1`` candidate positions per row in **one pass**.

    ``batch = {"tokens": (B, K)}`` — column 0 is each row's committed next
    token, columns 1..k the drafter's proposals.  The whole window runs
    through the layer stack as a short sequence (weights read once — the
    speculative-decode win), with per-query masking in
    :func:`~repro.models.attention.verify_attention` and per-step
    recurrent-state checkpoints from the ssm/mamba scans, so
    ``logits[:, j]`` equals what ``decode_step`` would return after
    feeding columns ``0..j`` one at a time (asserted bit-exactly by
    ``tests/test_spec_decode.py`` across every stateful family).

    Returns ``(logits (B, K, V), state, rec_stack)``:

    * ``state``: K/V caches hold all K candidate writes (positions
      ``pos..pos+K-1``; linear caches drop writes past the cache end,
      ring caches — allocations smaller than the stream, the long_500k
      preset — wrap them, with the pre-write entry still readable by
      earlier queries; see
      :func:`~repro.models.attention.verify_attention`) and ``pos`` is
      *unchanged* — nothing is committed yet.  A rejected write sits past
      the committed ``pos`` (or, on a ring, at a slot the real token will
      ring-write again on commit) and stays invisible until overwritten.
    * ``rec_stack``: per-step checkpoints of the recurrent fields
      (:data:`REC_FIELDS`), leading axis ``K+1`` where index ``j`` is the
      state after ``j`` accepted steps (0 = pre-verify).  Feed it to
      :func:`spec_commit` with the host's per-row accepted counts.
    """
    pol = pol or cfg.exec_policy
    if cfg.input_kind != "tokens":
        raise ValueError("speculative verify needs token inputs; frame "
                         "frontends have no draftable vocabulary")
    x = L.embedding_lookup(batch["tokens"], params["embed"])
    b, kq = x.shape[:2]
    pos = state.pos
    per_row = jnp.ndim(pos) == 1
    offs = jnp.arange(kq, dtype=jnp.int32)
    positions = (pos[:, None].astype(jnp.int32) + offs[None, :] if per_row
                 else pos.astype(jnp.int32) + offs)
    paged = getattr(state, "block_tables", None) is not None
    ring = False
    if state.cache_k is not None:
        cache_len = state.cache_k.shape[kv_pos_axis(state)]
        if paged:   # pool (L,N,page,...): logical capacity is the table's
            cache_len = state.block_tables.shape[1] * cache_len
        if cfg.sliding_window and cache_len <= cfg.sliding_window:
            windows = jnp.full((cfg.n_layers,), cfg.sliding_window,
                               jnp.int32)
            # the cache really is a ring (long_500k: allocation is the
            # window, the stream is longer): candidate writes must wrap.
            # Paged caches are linear by construction, never a ring.
            ring = not paged
        else:
            windows = jnp.asarray(layer_windows(cfg, cache_len))
    else:
        windows = jnp.asarray(layer_windows(cfg, 4096))

    qc = _cache_quant(cfg)

    def body(x, xs):
        if cfg.family == "ssm":
            if qc:
                bp, xp, cp, wkv_q, wkv_s = xs
                wkv = dequantize_blocked(wkv_q, wkv_s)
            else:
                bp, xp, cp, wkv = xs
            h = L.rms_norm(x, bp["ln1"], cfg.norm_eps)
            tm_out, (xp2, wkv2), wkv_steps = S.rwkv6_timemix(
                h, S.Rwkv6Params(**bp["tm"]), cfg, pol, (xp, wkv),
                return_states=True)
            x = x + tm_out
            h2 = L.rms_norm(x, bp["ln2"], cfg.norm_eps)
            cm_out, cp2 = S.rwkv6_channelmix(
                h2, S.Rwkv6ChannelParams(**bp["cm"]), cfg, pol, cp)
            # token-shift checkpoints after step j+1 are the mixer inputs
            # themselves: h[:, j] / h2[:, j]
            if qc:
                # requantized placeholder keeps the returned pytree's
                # dtypes stable; spec_commit overwrites it from the exact
                # f32 checkpoints anyway
                wkv2, wkv2_s = quantize_blocked(wkv2)
                return x + cm_out, (h, h2, wkv_steps, xp2, cp2, wkv2,
                                    wkv2_s)
            return x + cm_out, (h, h2, wkv_steps, xp2, cp2, wkv2)

        bp, ck, cv = xs[0], xs[1], xs[2]
        if qc:
            sk_, sv_, win = xs[3], xs[4], xs[5]
            extra = xs[6:]
        else:
            win = xs[3]
            extra = xs[4:]
        h = L.rms_norm(x, bp["ln1"], cfg.norm_eps)
        q, k, v = A.qkv(h, _attn_params(bp, cfg), cfg, pol, positions)
        if paged:
            ev = ()
            if qc:
                ctx, ck2, cv2, sk2, sv2 = A.paged_verify_attention(
                    q, k, v, ck, cv, state.block_tables, pos, cfg, pol,
                    win, scale_k=sk_, scale_v=sv_)
                new_caches = (ck2, cv2, sk2, sv2)
            else:
                ctx, ck2, cv2 = A.paged_verify_attention(
                    q, k, v, ck, cv, state.block_tables, pos, cfg, pol,
                    win)
                new_caches = (ck2, cv2)
        elif qc:
            if ring:
                ctx, ck2, cv2, sk2, sv2, ev = A.verify_attention(
                    q, k, v, ck, cv, pos, cfg, pol, win,
                    scale_k=sk_, scale_v=sv_, ring=True)
            else:
                ctx, ck2, cv2, sk2, sv2 = A.verify_attention(
                    q, k, v, ck, cv, pos, cfg, pol, win,
                    scale_k=sk_, scale_v=sv_)
                ev = ()
            new_caches = (ck2, cv2, sk2, sv2)
        else:
            if ring:
                ctx, ck2, cv2, ev = A.verify_attention(
                    q, k, v, ck, cv, pos, cfg, pol, win, ring=True)
            else:
                ctx, ck2, cv2 = A.verify_attention(q, k, v, ck, cv, pos,
                                                   cfg, pol, win)
                ev = ()
            new_caches = (ck2, cv2)
        attn_out = L.dense(ctx.reshape(b, kq, -1), bp["attn"]["wo"], pol)
        new_extra = ()
        if cfg.family == "hybrid":
            if qc:
                tail, hq_, hs_ = extra
                hprev = dequantize_blocked(hq_, hs_)
            else:
                tail, hprev = extra
            ssm_out, (tail2, h2), (tail_steps, h_steps) = _ssm_branch(
                h, bp, cfg, pol, (tail, hprev), return_states=True)
            x = _fuse(x, attn_out, ssm_out, bp, cfg)
            if qc:
                h2, h2_s = quantize_blocked(h2)
                new_extra = (tail2, h2, h2_s, tail_steps, h_steps)
            else:
                new_extra = (tail2, h2, tail_steps, h_steps)
        else:
            x = x + attn_out
        h = L.rms_norm(x, bp["ln2"], cfg.norm_eps)
        if cfg.family == "moe":
            moe_out, _ = M.moe_ffn(h, M.MoEParams(**bp["moe"]), cfg, pol)
            if cfg.dense_residual:
                moe_out = moe_out + L.swiglu(h, bp["ffn"]["w_gate"],
                                             bp["ffn"]["w_up"],
                                             bp["ffn"]["w_down"], pol,
                                             cfg.activation)
            x = x + moe_out
        else:
            x = x + L.swiglu(h, bp["ffn"]["w_gate"], bp["ffn"]["w_up"],
                             bp["ffn"]["w_down"], pol, cfg.activation)
        return x, new_caches + new_extra + ev

    def stack(pre, steps):
        # steps (L, B, K, ...) stacked by the layer scan -> checkpoint
        # layout (K+1, L, B, ...): index j = state after j steps
        return jnp.concatenate([pre[None],
                                jnp.moveaxis(steps, 2, 0).astype(pre.dtype)])

    rec_stack: Dict[str, Array] = {}
    if cfg.family == "ssm":
        if qc:
            x, (xp_steps, cp_steps, wkv_steps, xp, cp, wkv,
                wkv_s) = jax.lax.scan(
                body, x, (params["blocks"], state.x_prev, state.cm_prev,
                          state.wkv, state.wkv_scale))
            new_state = state._replace(x_prev=xp, cm_prev=cp, wkv=wkv,
                                       wkv_scale=wkv_s)
            wkv_pre = dequantize_blocked(state.wkv, state.wkv_scale)
        else:
            x, (xp_steps, cp_steps, wkv_steps, xp, cp, wkv) = jax.lax.scan(
                body, x, (params["blocks"], state.x_prev, state.cm_prev,
                          state.wkv))
            new_state = state._replace(x_prev=xp, cm_prev=cp, wkv=wkv)
            wkv_pre = state.wkv
        # checkpoints stay exact f32: quantization (if any) happens only
        # at spec_commit, on the state actually committed
        rec_stack = {"x_prev": stack(state.x_prev, xp_steps),
                     "cm_prev": stack(state.cm_prev, cp_steps),
                     "wkv": stack(wkv_pre, wkv_steps)}
    elif cfg.family == "hybrid":
        if qc:
            x, (ck, cv, sk, sv, tail, hh, hh_s, tail_steps, h_steps,
                *ring_ev) = jax.lax.scan(
                body, x, (params["blocks"], state.cache_k, state.cache_v,
                          state.scale_k, state.scale_v, windows,
                          state.conv_tail, state.ssm_h, state.ssm_scale))
            new_state = state._replace(cache_k=ck, cache_v=cv, scale_k=sk,
                                       scale_v=sv, conv_tail=tail, ssm_h=hh,
                                       ssm_scale=hh_s)
            h_pre = dequantize_blocked(state.ssm_h, state.ssm_scale)
        else:
            x, (ck, cv, tail, hh, tail_steps, h_steps,
                *ring_ev) = jax.lax.scan(
                body, x, (params["blocks"], state.cache_k, state.cache_v,
                          windows, state.conv_tail, state.ssm_h))
            new_state = state._replace(cache_k=ck, cache_v=cv,
                                       conv_tail=tail, ssm_h=hh)
            h_pre = state.ssm_h
        rec_stack = {"conv_tail": stack(state.conv_tail, tail_steps),
                     "ssm_h": stack(h_pre, h_steps)}
        rec_stack.update(zip(_RING_KEYS, ring_ev))
    else:
        if qc:
            x, (ck, cv, sk, sv, *ring_ev) = jax.lax.scan(
                body, x, (params["blocks"], state.cache_k, state.cache_v,
                          state.scale_k, state.scale_v, windows))
            new_state = state._replace(cache_k=ck, cache_v=cv, scale_k=sk,
                                       scale_v=sv)
        else:
            x, (ck, cv, *ring_ev) = jax.lax.scan(
                body, x, (params["blocks"], state.cache_k, state.cache_v,
                          windows))
            new_state = state._replace(cache_k=ck, cache_v=cv)
        rec_stack.update(zip(_RING_KEYS, ring_ev))

    x = L.rms_norm(x, params["ln_f"], cfg.norm_eps)
    logits = _lm_head(x, params, pol)
    if cfg.n_codebooks:
        logits = logits.reshape(b, kq, cfg.n_codebooks, cfg.vocab_size)
    return logits, new_state, rec_stack


def verify_commit_greedy(params: Dict[str, Any], state: DecodeState,
                         batch: Dict[str, Array], caps: Array,
                         cfg: ArchConfig,
                         pol: Optional[ExecutionPolicy] = None
                         ) -> Tuple[Array, Array, DecodeState]:
    """Fused greedy speculative step: verify, accept, commit — one program.

    Greedy acceptance needs no host round trip: draft ``j`` is accepted
    iff ``argmax(logits[:, j]) == tokens[:, j+1]``, so the longest
    matching prefix, the budget clamp and the state commit all run on
    device and the host pulls a single ``(B, K)`` int array per engine
    step (the two-phase :func:`verify_step` + :func:`spec_commit` path
    remains for sampling, whose rejection test is host-side).

    ``caps`` (B,) int32 — per-row ceiling on *accepted drafts* (min of
    real draft count and remaining budget - 1); ``-1`` marks a row that
    must not advance at all (an empty serving slot).

    Returns ``(ids (B, K) greedy targets, advance (B,), new state)`` with
    ``advance = min(matched, caps) + 1`` (0 for capped-out rows) already
    committed into ``pos`` and the recurrent state.
    """
    logits, st, rec_stack = verify_step(params, state, batch, cfg, pol)
    ids = jnp.argmax(logits, axis=-1)
    toks = batch["tokens"]
    match = (ids[:, :-1] == toks[:, 1:]).astype(jnp.int32)
    matched = jnp.sum(jnp.cumprod(match, axis=1), axis=1)     # prefix len
    advance = jnp.maximum(jnp.minimum(matched, caps) + 1, 0)
    return ids, advance, spec_commit(st, rec_stack, advance)


def spec_commit(state: DecodeState, rec_stack: Dict[str, Array],
                advance: Array) -> DecodeState:
    """Commit a verify call: advance each row by its accepted length.

    ``advance`` — int32 ``(B,)`` (or scalar for single-stream state) in
    ``[0..K]``: the number of verified tokens the host accepted per row
    (accepted drafts + 1, or 0 for rows that must not move — e.g. empty
    serving slots).  ``pos`` advances by it and every recurrent field is
    gathered from its ``rec_stack`` checkpoint at that index — the rollback
    for rejected tokens.  Linear K/V caches pass through: rejected writes
    sit past the committed ``pos`` and stay masked until overwritten.  On
    a ring cache the rejected candidates' wrapped writes evicted live
    history, so ``rec_stack`` additionally carries the raw evicted columns
    (:data:`_RING_KEYS`) and the commit scatters them back into every slot
    past each row's accepted prefix.
    """
    advance = jnp.asarray(advance, jnp.int32)
    ring_cols = {k: rec_stack[k] for k in _RING_KEYS if k in rec_stack}
    rec_stack = {k: v for k, v in rec_stack.items() if k not in ring_cols}
    out: Dict[str, Any] = {"pos": state.pos + advance.astype(state.pos.dtype)}
    for name, ev in ring_cols.items():            # ev (L, B, K, ...)
        cache = getattr(state, _RING_FIELD[name])    # (L,B,Hkv,S,...)
        nb, kq = ev.shape[1], ev.shape[2]
        offs = jnp.arange(kq, dtype=jnp.int32)
        posv = jnp.broadcast_to(state.pos, (nb,)).astype(jnp.int32)
        slots = jnp.mod(posv[:, None] + offs[None, :], cache.shape[3])
        rej = offs[None, :] >= jnp.broadcast_to(advance, (nb,))[:, None]
        idx = (slice(None),) + A.kv_index(jnp.arange(nb)[:, None], slots,
                                           cache.shape[2])
        sel = rej.reshape((1, nb, kq) + (1,) * (ev.ndim - 3))
        out[_RING_FIELD[name]] = cache.at[idx].set(
            jnp.where(sel, ev, cache[idx]))
    for name, stack in rec_stack.items():         # stack (K+1, L, B, ...)
        if jnp.ndim(advance) == 0:
            picked = stack[advance]
        else:
            # picked[l, b] = stack[advance[b], l, b]
            picked = jax.vmap(lambda s, a: s[a], in_axes=(2, 0),
                              out_axes=1)(stack, advance)
        cur = getattr(state, name)
        if cur is not None and cur.dtype == jnp.int8:
            # quantize-on-commit: checkpoints are exact f32, the committed
            # int8 state is quantized exactly once per accepted prefix
            out[name], out[_SCALE_FOR[name]] = quantize_blocked(picked)
        else:
            out[name] = picked
    return state._replace(**out)
