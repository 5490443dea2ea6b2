"""Plain float32 reference for the attention-free recurrent model the
program serves as rwkv6-3b (Finch, arXiv:2404.05892): per layer an
RMSNorm, a time-mix with token-shift lerps, a data-dependent decay
w = exp(-exp(w0 + tanh(x_w A) B)) and the per-head wkv recurrence

    out_t = r_t (S + diag(u) k_t v_t^T),   S <- diag(w_t) S + k_t v_t^T

then a per-head group norm, a SiLU gate and the output projection; an
RMSNorm and a channel-mix (squared ReLU key, sigmoid receptance).  A
final RMSNorm and an untied head.  The recurrence runs step by step in
float32 at highest matmul precision; it imports nothing of the program.

Departures from the published Finch that the program makes, and this
reference follows, since it checks the program's own semantics: static
token-shift lerps (Finch makes them data-dependent through a LoRA),
RMSNorm where Finch uses LayerNorm (and no LayerNorm on the
embeddings), a group norm with gain and no bias, and the decay
exponent clipped to [-8, 2].
"""
from __future__ import annotations

import functools
import os
import sys
from typing import List

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
from benchlib import weights as W  # noqa: E402

F32 = jnp.float32
LORA = 64           # the decay LoRA's rank, as the program declares it


def leaves(m: dict) -> List[W.Leaf]:
    """The parameter leaves, as the program lays them out."""
    D, H, F, V = m["d_model"], m["n_heads"], m["d_ff"], m["vocab_size"]
    dk = D // H
    bf = "bfloat16"
    return [
        ("embed", (V, D), ("normal", 1.0), bf),
        ("ln_f", (D,), ("gain", 0.1), "float32"),
        ("lm_head", (D, V), ("fan_in",), bf),
        ("blocks/ln1", (D,), ("gain", 0.1), bf),
        ("blocks/ln2", (D,), ("gain", 0.1), bf),
        ("blocks/tm/mu", (5, D), ("uniform", 0.0, 1.0), bf),
        ("blocks/tm/w0", (D,), ("uniform", -6.0, -0.5), bf),
        ("blocks/tm/w_lora_a", (D, LORA), ("fan_in",), bf),
        ("blocks/tm/w_lora_b", (LORA, D), ("fan_in",), bf),
        ("blocks/tm/bonus", (H, dk), ("normal", 0.5), bf),
        ("blocks/tm/wr", (D, D), ("fan_in",), bf),
        ("blocks/tm/wk", (D, D), ("fan_in",), bf),
        ("blocks/tm/wv", (D, D), ("fan_in",), bf),
        ("blocks/tm/wg", (D, D), ("fan_in",), bf),
        ("blocks/tm/wo", (D, D), ("fan_in",), bf),
        ("blocks/tm/ln_w", (D,), ("gain", 0.1), bf),
        ("blocks/cm/mu_k", (D,), ("uniform", 0.0, 1.0), bf),
        ("blocks/cm/mu_r", (D,), ("uniform", 0.0, 1.0), bf),
        ("blocks/cm/wk", (D, F), ("fan_in",), bf),
        ("blocks/cm/wv", (F, D), ("fan_in",), bf),
        ("blocks/cm/wr", (D, D), ("fan_in",), bf),
    ]


def cost_terms(m: dict) -> dict:
    """Per-layer sizes for ``benchlib/costs.py``."""
    D, F, H = m["d_model"], m["d_ff"], m["n_heads"]
    dk = D // H
    return {"matmul_params": 5 * D * D + 2 * D * LORA + 2 * D * F + D * D,
            # norm gains, token-shift lerps (5 + 2), decay base, bonus,
            # group-norm gain
            "other_params": 12 * D,
            "ctx_flops": 0,
            "step_flops": 7 * H * dk * dk,            # one wkv step
            "ctx_bytes": 0,
            # f32 wkv state and two bf16 token-shift rows
            "state_bytes": H * dk * dk * 4 + 2 * D * 2}


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def _shift(x):
    """x[t-1] at t, zeros at t = 0 (a fresh request)."""
    return jnp.concatenate([jnp.zeros_like(x[:, :1]), x[:, :-1]], 1)


def _wkv(r, k, v, w, u):
    """(B, T, H, dk) each, u (H, dk) -> (B, T, H, dk), step by step."""
    b, _, h, dk = r.shape

    def step(S, t):
        r_t, k_t, v_t, w_t = t
        kv = k_t[..., :, None] * v_t[..., None, :]          # (B, H, dk, dk)
        out = jnp.einsum("bhk,bhkv->bhv", r_t, S + u[..., None] * kv)
        return w_t[..., None] * S + kv, out

    seq = tuple(jnp.moveaxis(a, 1, 0) for a in (r, k, v, w))
    _, out = jax.lax.scan(step, jnp.zeros((b, h, dk, dk), F32), seq)
    return jnp.moveaxis(out, 0, 1)


def _layer(x, w, m):
    """x (B, T, D) float32 -> float32, one Finch block."""
    b, t, d = x.shape
    H, eps = m["n_heads"], m["norm_eps"]
    dk = d // H
    h = _rms(x, w["ln1"], eps)
    hs = _shift(h)
    xr, xk, xv, xw, xg = (h + (hs - h) * w["tm/mu"][i] for i in range(5))
    r = (xr @ w["tm/wr"]).reshape(b, t, H, dk)
    k = (xk @ w["tm/wk"]).reshape(b, t, H, dk)
    v = (xv @ w["tm/wv"]).reshape(b, t, H, dk)
    g = xg @ w["tm/wg"]
    dd = jnp.tanh(xw @ w["tm/w_lora_a"]) @ w["tm/w_lora_b"]
    decay = jnp.exp(-jnp.exp(jnp.clip(w["tm/w0"] + dd, -8.0, 2.0)))
    out = _wkv(r, k, v, decay.reshape(b, t, H, dk), w["tm/bonus"])
    mean = out.mean(-1, keepdims=True)
    var = ((out - mean) ** 2).mean(-1, keepdims=True)
    out = ((out - mean) / jnp.sqrt(var + 64e-5)).reshape(b, t, d)
    out = out * w["tm/ln_w"] * jax.nn.silu(g)
    x = x + out @ w["tm/wo"]
    h = _rms(x, w["ln2"], eps)
    hs = _shift(h)
    ck = h + (hs - h) * w["cm/mu_k"]
    cr = h + (hs - h) * w["cm/mu_r"]
    kk = jnp.square(jax.nn.relu(ck @ w["cm/wk"]))
    return x + jax.nn.sigmoid(cr @ w["cm/wr"]) * (kk @ w["cm/wv"])


def hidden(tokens: np.ndarray, words, m: dict, n_layers: int) -> jax.Array:
    """Final-normed hidden states (B, T, D) float32 of token rows (B, T),
    at highest matmul precision; weights are drawn a layer at a time."""
    lv = leaves(m)
    with jax.default_matmul_precision("highest"):
        embed = W.global_leaf(lv, "embed")(words)
        x = jnp.take(embed, jnp.asarray(tokens), axis=0).astype(F32)
        del embed
        draw = W.layer_f32(lv)
        step = jax.jit(functools.partial(_layer, m=m))
        for layer in range(n_layers):
            x = step(x, draw(words, layer))
        ln_f = W.global_leaf(lv, "ln_f")(words).astype(F32)
        return _rms(x, ln_f, m["norm_eps"])


def head(words, m: dict) -> jax.Array:
    """The output head (D, V) in float32."""
    return W.global_leaf(leaves(m), "lm_head")(words).astype(F32)
