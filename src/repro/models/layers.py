"""Common model layers, all routed through the ExecutionPolicy so the
paper's CORDIC datapath (FxP8 MAC + DA-VINCI AFs) is a first-class
execution mode for every architecture."""
from __future__ import annotations

from typing import Optional, Sequence

import jax
import jax.numpy as jnp

from repro.configs.base import ExecutionPolicy
from repro.core.activations import activate
from repro.core.quantization import QuantPolicy, quantized_dense
from repro.parallel.sharding import constrain

Array = jax.Array


def dense(x: Array, w: Array, policy: ExecutionPolicy,
          bias: Optional[Array] = None) -> Array:
    """Matmul through the policy-selected datapath."""
    if policy.matmul == "bf16":
        out = x @ w.astype(x.dtype)
    elif policy.matmul == "fxp8":
        out = quantized_dense(x, w, policy.quant)
    elif policy.matmul == "fxp8_weight":
        out = quantized_dense(x, w, QuantPolicy(act_bits=None))
    elif policy.matmul == "cordic_kernel":
        from repro.kernels.cordic_mac.ops import cordic_matmul
        x2 = x.reshape(-1, x.shape[-1]).astype(jnp.float32)
        out = cordic_matmul(x2, w.astype(jnp.float32))
        out = out.reshape(*x.shape[:-1], w.shape[-1]).astype(x.dtype)
    else:
        raise ValueError(f"unknown matmul mode {policy.matmul!r}")
    if bias is not None:
        out = out + bias.astype(out.dtype)
    return out


def af(x: Array, name: str, policy: ExecutionPolicy, axis: int = -1) -> Array:
    """Activation through DA-VINCI when the policy enables CORDIC AFs.

    The CORDIC path computes in f32 (dequantized fixed point); cast back so
    residual-stream dtypes are stable under any policy."""
    return activate(x, name, policy.af, axis=axis).astype(x.dtype)


def softmax(x: Array, policy: ExecutionPolicy, axis: int = -1) -> Array:
    if policy.softmax_cordic and policy.af is not None:
        return activate(x, "softmax", policy.af, axis=axis).astype(x.dtype)
    return jax.nn.softmax(x, axis=axis)


def rms_norm(x: Array, gamma: Array, eps: float = 1e-5) -> Array:
    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    return (x32 * jax.lax.rsqrt(var + eps)).astype(x.dtype) * gamma.astype(x.dtype)


def rope_angles(positions: Array, head_dim: int, theta: float) -> Array:
    """(..., head_dim/2) rotary angles for integer positions."""
    inv_freq = 1.0 / (theta ** (jnp.arange(0, head_dim, 2,
                                           dtype=jnp.float32) / head_dim))
    return positions.astype(jnp.float32)[..., None] * inv_freq


def apply_rope(x: Array, angles: Array) -> Array:
    """x: (..., S, H, D); angles: (..., S, D/2) broadcast over heads."""
    sin, cos = jnp.sin(angles), jnp.cos(angles)
    sin = sin[..., None, :].astype(x.dtype)   # add head axis
    cos = cos[..., None, :].astype(x.dtype)
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)


@jax.named_scope("mlp")
def swiglu(x: Array, w_gate: Array, w_up: Array, w_down: Array,
           policy: ExecutionPolicy, act: str = "silu") -> Array:
    g = dense(x, w_gate, policy)
    u = dense(x, w_up, policy)
    h = af(g, act, policy) * u
    h = constrain(h, ("batch", "seq", "mlp"))
    return dense(h, w_down, policy)


def embedding_lookup(tokens: Array, table: Array) -> Array:
    return jnp.take(table, tokens, axis=0)


def cross_entropy(logits: Array, labels: Array,
                  mask: Optional[Array] = None) -> Array:
    """Mean CE over valid positions; logits (..., V) may be vocab-sharded."""
    logits = logits.astype(jnp.float32)
    logz = jax.scipy.special.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    nll = logz - gold
    if mask is not None:
        mask = mask.astype(jnp.float32)
        return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    return jnp.mean(nll)
