"""Plain float32 reference for the parallel hybrid the program serves as
falcon-h1-34b (Falcon-H1, hf tiiuae/Falcon-H1-34B-Instruct).  Every layer
runs attention heads and Mamba-2 heads on one RMSNormed input and adds
both to the residual, then a SwiGLU MLP on a second RMSNorm; a final
RMSNorm and an untied head.

Attention: grouped-query causal softmax attention with rotate-half
rotary embedding.  Mamba-2, per head h of group g = h // (H / G):

    xBC <- SiLU(causal depthwise conv_K(x|B|C) + bias)
    dt_t = softplus(dt_t + dt_bias_h),  A_h = -exp(A_log_h)
    S_t = exp(dt_t A_h) S_{t-1} + dt_t x_t B_t^T     (S in R^{P x N})
    y_t = S_t C_t + D_h x_t

then y * SiLU(z), RMS-normed over each group's channels with a gain
(norm after gate), and the out projection.  The recurrence runs step by
step, not in the program's chunked form.  Straight ``jax.numpy`` at
highest matmul precision, a layer at a time, attention by query blocks
and the MLP by position blocks so that 8 rows of 8704 positions fit on
one chip beside nothing else; it imports nothing of the program.

Departures from the published model, which the program makes and this
reference follows:

* the muP multipliers of the published config (``MUP``) are folded into
  the weights they multiply, as a checkpoint conversion would, so the
  weights here are drawn with them (``leaves``);
* in_proj is held as five column blocks (z, x, B, C, dt), one leaf each,
  so that each block's multiplier folds into one leaf; the same matrix;
* wq and wk are drawn so that the attention scores spread with std
  ``SCORE_STD`` (2) at any width, as a trained model's do: normal with
  std sqrt(2 / d_model), 0.0198 at the published 5120 (the published
  initialisation's 0.02 for wq, whose multiplier is 1).  The key
  multiplier, 0.011, is not folded into this random wk: with it the
  scores would spread by 0.02 and attention would be a uniform mean of
  V, which no comparison of logits can check.  Even so, a fault that
  moves one position of thousands (decode's K/V one position off)
  moves the logits less than bf16 rounding does;
* the other weights are random, drawn as the published initialisation
  draws them (normal with std ``INIT_STD`` times the folded
  multiplier), with mamba_ssm's Mamba-2 draws for dt_bias, A_log and
  the conv taps, and the conv bias drawn as the matrices are (the
  published initialisation zeroes it).
"""
from __future__ import annotations

import functools
import math
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
# hf FalconH1Config's initializer_range; the published config.json omits it
INIT_STD = 0.02
# the published config's muP multipliers
MUP = {
    "embedding_multiplier": 5.656854249492381,
    "lm_head_multiplier": 0.0078125,
    "attention_in_multiplier": 1.0,
    "attention_out_multiplier": 0.0375,
    "key_multiplier": 0.011048543456039804,
    "ssm_in_multiplier": 0.25,
    "ssm_out_multiplier": 0.08838834764831845,
    # z, x, B, C, dt blocks of in_proj
    "ssm_multipliers": [0.3535533905932738, 0.25, 0.1767766952966369, 0.5,
                        0.3535533905932738],
    # gate, down
    "mlp_multipliers": [0.1767766952966369, 0.011160714285714284],
}
# the attention scores' spread that wq and wk are drawn for (a trained
# model's scores spread by a few units; see the docstring)
SCORE_STD = 2.0
Q_BLOCK = 128       # query positions a block of the reference's attention
P_BLOCK = 1024      # positions a block of the reference's MLP


def _widths(m: dict):
    h, p, g, n = (m["ssm_heads"], m["ssm_head_dim"], m["ssm_groups"],
                  m["ssm_state"])
    return h, p, g, n, h * p, g * n


def leaves(m: dict) -> List[W.Leaf]:
    """The parameter leaves, as the program lays them out, each drawn
    with the multiplier folded into it."""
    D, Hq, Hkv, dh = m["d_model"], m["n_heads"], m["n_kv_heads"], m["head_dim"]
    F, V, K = m["d_ff"], m["vocab_size"], m["ssm_conv"]
    H, _, _, _, Di, GN = _widths(m)
    zm, xm, bm, cm, dtm = MUP["ssm_multipliers"]
    gate_m, down_m = MUP["mlp_multipliers"]
    attn_in, ssm_in = MUP["attention_in_multiplier"], MUP["ssm_in_multiplier"]

    def std(mult):
        return ("normal", INIT_STD * mult)

    # q and k elements of std sqrt(SCORE_STD), on a unit-RMS input
    qk = ("normal", math.sqrt(SCORE_STD / D))

    bf = "bfloat16"
    # mamba_ssm's dt draw: log-uniform in [1e-3, 1e-1], as its inverse
    # softplus; A uniform in [1, 16], here log-uniform
    lo, hi = (math.log(math.expm1(v)) for v in (1e-3, 1e-1))
    return [
        ("embed", (V, D), std(MUP["embedding_multiplier"]), bf),
        ("ln_f", (D,), ("gain", 0.1), "float32"),
        ("lm_head", (D, V), std(MUP["lm_head_multiplier"]), bf),
        ("blocks/ln1", (D,), ("gain", 0.1), bf),
        ("blocks/ln2", (D,), ("gain", 0.1), bf),
        ("blocks/attn/wq", (D, Hq * dh), qk, bf),
        ("blocks/attn/wk", (D, Hkv * dh), qk, bf),
        ("blocks/attn/wv", (D, Hkv * dh), std(attn_in), bf),
        ("blocks/attn/wo", (Hq * dh, D), std(MUP["attention_out_multiplier"]),
         bf),
        ("blocks/ffn/w_gate", (D, F), std(gate_m), bf),
        ("blocks/ffn/w_up", (D, F), std(1.0), bf),
        ("blocks/ffn/w_down", (F, D), std(down_m), bf),
        ("blocks/mamba2/w_z", (D, Di), std(ssm_in * zm), bf),
        ("blocks/mamba2/w_x", (D, Di), std(ssm_in * xm), bf),
        ("blocks/mamba2/w_b", (D, GN), std(ssm_in * bm), bf),
        ("blocks/mamba2/w_c", (D, GN), std(ssm_in * cm), bf),
        ("blocks/mamba2/w_dt", (D, H), std(ssm_in * dtm), bf),
        # torch's Conv1d draw for fan-in K: uniform +-1/sqrt(K)
        ("blocks/mamba2/conv_w", (K, Di + 2 * GN),
         ("uniform", -K ** -0.5, K ** -0.5), bf),
        # hf zeroes biases at initialisation; a draw exercises the add
        ("blocks/mamba2/conv_b", (Di + 2 * GN,), std(1.0), bf),
        ("blocks/mamba2/dt_bias", (H,), ("uniform", lo, hi), bf),
        ("blocks/mamba2/a_log", (H,), ("uniform", 0.0, math.log(16.0)), bf),
        ("blocks/mamba2/d_skip", (H,), ("gain", 0.1), bf),
        ("blocks/mamba2/norm", (Di,), ("gain", 0.1), bf),
        ("blocks/mamba2/w_out", (Di, D), std(MUP["ssm_out_multiplier"]), bf),
    ]


def cost_terms(m: dict) -> dict:
    """Per-layer sizes for ``benchlib/costs.py``."""
    D, F, K = m["d_model"], m["d_ff"], m["ssm_conv"]
    q, kv = m["n_heads"] * m["head_dim"], m["n_kv_heads"] * m["head_dim"]
    H, P, _, N, Di, GN = _widths(m)
    conv = Di + 2 * GN
    return {"matmul_params": (D * q + 2 * D * kv + q * D + 3 * D * F
                              + D * (2 * Di + 2 * GN + H) + Di * D),
            # norm gains, conv taps and bias, dt_bias, A_log, D, gated norm
            "other_params": 2 * D + (K + 1) * conv + 3 * H + Di,
            "ctx_flops": 4 * q,                       # QK^T and PV
            # one recurrence step (decay, drive, read-out) and the conv
            "step_flops": 5 * H * P * N + 2 * K * conv,
            "ctx_bytes": 2 * kv * 2,                  # bf16 K and V
            # f32 Mamba-2 state and the bf16 conv tail
            "state_bytes": H * P * N * 4 + (K - 1) * conv * 2}


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


def _blocks(fn, a, size):
    """fn over blocks of ``size`` along a's leading axis, one at a time."""
    n = a.shape[0]
    pad = -n % size
    a = jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1))
    out = jax.lax.map(fn, a.reshape((-1, size) + a.shape[1:]))
    return out.reshape((-1,) + out.shape[2:])[:n]


def _attend(q, k, v):
    """One sequence, by query blocks: q (S, H, dh), k/v (S, Hkv, dh)."""
    s, h, dh = q.shape
    g = h // k.shape[1]
    k = jnp.repeat(k, g, axis=1)
    v = jnp.repeat(v, g, axis=1)
    qpos = jnp.arange(s)

    def block(t):
        qb, pb = t
        scores = jnp.einsum("shd,thd->hst", qb, k) / np.sqrt(dh)
        scores = jnp.where(pb[None, :, None] >= qpos[None, None, :], scores,
                           -jnp.inf)
        return jnp.einsum("hst,thd->shd", jax.nn.softmax(scores, -1), v)

    pad = -s % Q_BLOCK
    qp = jnp.pad(q, ((0, pad), (0, 0), (0, 0)))
    pp = jnp.pad(qpos, (0, pad))
    out = jax.lax.map(block, (qp.reshape(-1, Q_BLOCK, h, dh),
                              pp.reshape(-1, Q_BLOCK)))
    return out.reshape(-1, h, dh)[:s]


def _mamba2(h, w, m):
    """h (B, T, D) -> (B, T, D): the Mamba-2 mixer, step by step."""
    b, t, _ = h.shape
    H, P, G, N, Di, GN = _widths(m)
    K = m["ssm_conv"]
    z = h @ w["mamba2/w_z"]
    xbc = jnp.concatenate([h @ w["mamba2/w_x"], h @ w["mamba2/w_b"],
                           h @ w["mamba2/w_c"]], -1)
    pad = jnp.pad(xbc, ((0, 0), (K - 1, 0), (0, 0)))
    conv = sum(pad[:, i:i + t] * w["mamba2/conv_w"][i] for i in range(K))
    conv = jax.nn.silu(conv + w["mamba2/conv_b"])
    x = conv[..., :Di].reshape(b, t, H, P)
    bb = jnp.repeat(conv[..., Di:Di + GN].reshape(b, t, G, N), H // G, 2)
    cc = jnp.repeat(conv[..., Di + GN:].reshape(b, t, G, N), H // G, 2)
    dt = jax.nn.softplus(h @ w["mamba2/w_dt"] + w["mamba2/dt_bias"])
    a = -jnp.exp(w["mamba2/a_log"])

    def step(S, u):
        x_t, b_t, c_t, dt_t = u                      # (B,H,P) (B,H,N) (B,H)
        S = (jnp.exp(dt_t * a)[..., None, None] * S
             + (dt_t[..., None] * x_t)[..., None] * b_t[:, :, None, :])
        return S, jnp.einsum("bhpn,bhn->bhp", S, c_t)

    seq = tuple(jnp.moveaxis(v, 1, 0) for v in (x, bb, cc, dt))
    _, y = jax.lax.scan(step, jnp.zeros((b, H, P, N), F32), seq)
    y = jnp.moveaxis(y, 0, 1) + w["mamba2/d_skip"][:, None] * x
    y = y.reshape(b, t, G, Di // G) * jax.nn.silu(z).reshape(b, t, G,
                                                             Di // G)
    y = y * jax.lax.rsqrt(jnp.mean(y * y, -1, keepdims=True) + m["norm_eps"])
    return (y.reshape(b, t, Di) * w["mamba2/norm"]) @ w["mamba2/w_out"]


def _layer(x, w, m):
    """x (B, T, D) float32 -> float32, one Falcon-H1 block."""
    b, t, d = x.shape
    Hq, Hkv, dh = m["n_heads"], m["n_kv_heads"], m["head_dim"]
    eps = m["norm_eps"]
    h = _rms(x, w["ln1"], eps)
    rope = functools.partial(_rope, theta=m["rope_theta"])

    def attn_row(hr):
        q = (hr @ w["attn/wq"]).reshape(t, Hq, dh)
        k = (hr @ w["attn/wk"]).reshape(t, Hkv, dh)
        v = (hr @ w["attn/wv"]).reshape(t, Hkv, dh)
        return _attend(rope(q), rope(k), v).reshape(t, Hq * dh) @ w["attn/wo"]

    attn = jax.lax.map(attn_row, h)
    x = x + (attn + _mamba2(h, w, m))
    h = _rms(x, w["ln2"], eps).reshape(b * t, d)

    def mlp(hb):
        gate, up = hb @ w["ffn/w_gate"], hb @ w["ffn/w_up"]
        return (jax.nn.silu(gate) * up) @ w["ffn/w_down"]

    return x + _blocks(mlp, h, P_BLOCK).reshape(b, t, d)


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
