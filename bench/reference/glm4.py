"""Plain float32 reference for the dense GQA decoder the program serves as
glm4-9b: RMSNorm, q/k/v projections with bias, rotary embedding,
grouped-query causal softmax attention, SwiGLU, final RMSNorm and an
untied head.  Straight ``jax.numpy`` at highest matmul precision, one
layer at a time; it imports nothing of the program.

Departures from the published GLM-4 that the program makes, and this
reference follows, since it checks the program's own semantics: rotary
embedding on every channel of a head in rotate-half layout (GLM-4
rotates half of each head, interleaved), and RMSNorm epsilon 1e-5.
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


def leaves(m: dict) -> List[W.Leaf]:
    """The parameter leaves, as the program lays them out."""
    D, H, Hkv, dh = m["d_model"], m["n_heads"], m["n_kv_heads"], m["head_dim"]
    F, V = m["d_ff"], m["vocab_size"]
    bf = "bfloat16"
    return [
        ("embed", (V, D), ("normal", 1.0), bf),
        ("ln_f", (D,), ("gain", 0.1), "float32"),
        ("lm_head", (D, V), ("fan_in",), bf),
        ("blocks/ln1", (D,), ("gain", 0.1), bf),
        ("blocks/ln2", (D,), ("gain", 0.1), bf),
        ("blocks/attn/wq", (D, H * dh), ("fan_in",), bf),
        ("blocks/attn/wk", (D, Hkv * dh), ("fan_in",), bf),
        ("blocks/attn/wv", (D, Hkv * dh), ("fan_in",), bf),
        ("blocks/attn/wo", (H * dh, D), ("fan_in",), bf),
        ("blocks/attn/bq", (H * dh,), ("normal", 0.5), bf),
        ("blocks/attn/bk", (Hkv * dh,), ("normal", 0.5), bf),
        ("blocks/attn/bv", (Hkv * dh,), ("normal", 0.5), bf),
        ("blocks/ffn/w_gate", (D, F), ("fan_in",), bf),
        ("blocks/ffn/w_up", (D, F), ("fan_in",), bf),
        ("blocks/ffn/w_down", (F, D), ("fan_in",), bf),
    ]


def cost_terms(m: dict) -> dict:
    """Per-layer sizes for ``benchlib/costs.py``."""
    D, F = m["d_model"], m["d_ff"]
    q, kv = m["n_heads"] * m["head_dim"], m["n_kv_heads"] * m["head_dim"]
    return {"matmul_params": D * q + 2 * D * kv + q * D + 3 * D * F,
            "other_params": 2 * D + q + 2 * kv,      # norm gains, biases
            "ctx_flops": 4 * q,                      # QK^T and PV
            "step_flops": 0,
            "ctx_bytes": 2 * kv * 2,                 # bf16 K and V
            "state_bytes": 0}


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def _rope(x, theta):
    """x (S, H, dh): rotate-half rotary embedding at positions 0..S-1."""
    s, _, dh = x.shape
    inv = 1.0 / theta ** (np.arange(0, dh, 2, dtype=np.float64) / dh)
    ang = jnp.arange(s, dtype=F32)[:, None] * jnp.asarray(inv, F32)
    sin, cos = jnp.sin(ang)[:, None], jnp.cos(ang)[:, None]
    x1, x2 = x[..., :dh // 2], x[..., dh // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _attend(q, k, v):
    """One sequence: q (S, H, dh), k/v (S, Hkv, dh); causal GQA."""
    s, h, dh = q.shape
    g = h // k.shape[1]
    k = jnp.repeat(k, g, axis=1)
    v = jnp.repeat(v, g, axis=1)
    scores = jnp.einsum("shd,thd->hst", q, k) / np.sqrt(dh)
    causal = jnp.tril(jnp.ones((s, s), bool))
    scores = jnp.where(causal[None], scores, -jnp.inf)
    return jnp.einsum("hst,thd->shd", jax.nn.softmax(scores, -1), v)


def _layer(x, w, m):
    """x (B, S, D) float32 -> float32, one decoder block."""
    b, s, _ = x.shape
    H, Hkv, dh = m["n_heads"], m["n_kv_heads"], m["head_dim"]
    eps = m["norm_eps"]
    h = _rms(x, w["ln1"], eps)
    q = (h @ w["attn/wq"] + w["attn/bq"]).reshape(b, s, H, dh)
    k = (h @ w["attn/wk"] + w["attn/bk"]).reshape(b, s, Hkv, dh)
    v = (h @ w["attn/wv"] + w["attn/bv"]).reshape(b, s, Hkv, dh)
    rope = functools.partial(_rope, theta=m["rope_theta"])
    ctx = jax.lax.map(lambda t: _attend(rope(t[0]), rope(t[1]), t[2]),
                      (q, k, v))
    x = x + ctx.reshape(b, s, H * dh) @ w["attn/wo"]
    h = _rms(x, w["ln2"], eps)
    gate, up = h @ w["ffn/w_gate"], h @ w["ffn/w_up"]
    return x + (jax.nn.silu(gate) * up) @ w["ffn/w_down"]


def hidden(tokens: np.ndarray, words, m: dict, n_layers: int) -> jax.Array:
    """Final-normed hidden states (B, S, D) float32 of token rows (B, S),
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
