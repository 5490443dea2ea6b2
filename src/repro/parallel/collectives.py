"""Distributed-optimization primitives: gradient compression and explicit
communication schedules.

  * **Error-feedback int8 gradient compression** — gradients compress to
    int8 (per-row absmax scales) before the data-parallel reduction;
    rounding residuals carry to the next step (EF-SGD), preserving
    convergence while cutting DP all-reduce bytes 2x vs bf16.
  * **Hierarchical pod all-reduce** — reduce-scatter intra-pod, all-reduce
    the 1/16-size shards across pods, all-gather intra-pod: inter-pod bytes
    drop by the intra-pod fan-in vs a flat all-reduce (the multi-pod mesh's
    thin axis).
  * **Ring all-reduce via ppermute** — the explicit 2(n-1)-step schedule,
    written out so chunks can interleave with other work (§Perf overlap
    experiment); numerically identical to psum.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as PS

Array = jax.Array


# ---------------------------------------------------------------------------
# Error-feedback int8 gradient compression
# ---------------------------------------------------------------------------

def compress_grad(g: Array) -> Tuple[Array, Array]:
    """g (fp) -> (int8 payload, fp32 per-row scale)."""
    g32 = g.astype(jnp.float32)
    if g.ndim == 0:
        scale = jnp.maximum(jnp.abs(g32) / 127.0, 1e-20)
        return jnp.round(g32 / scale).astype(jnp.int8), scale
    amax = jnp.max(jnp.abs(g32), axis=-1, keepdims=True)
    scale = jnp.maximum(amax / 127.0, 1e-20)
    q = jnp.clip(jnp.round(g32 / scale), -127, 127).astype(jnp.int8)
    return q, scale


def decompress_grad(q: Array, scale: Array, dtype=jnp.float32) -> Array:
    return (q.astype(jnp.float32) * scale).astype(dtype)


def compress_tree(grads, residuals):
    """Error-feedback compression over a pytree.

    Returns (tree of (q, scale) pairs, new residual tree).  The residual —
    what int8 rounding lost — is added back before the next compression,
    keeping the long-run gradient estimate unbiased (EF-SGD).
    """
    def one(g, r):
        g32 = g.astype(jnp.float32) + (r if r is not None else 0.0)
        q, s = compress_grad(g32)
        back = decompress_grad(q, s)
        return (q, s), g32 - back

    flat_g, treedef = jax.tree_util.tree_flatten(grads)
    flat_r = (treedef.flatten_up_to(residuals) if residuals is not None
              else [None] * len(flat_g))
    pairs = [one(g, r) for g, r in zip(flat_g, flat_r)]
    comp = jax.tree_util.tree_unflatten(treedef, [p[0] for p in pairs])
    res = jax.tree_util.tree_unflatten(treedef, [p[1] for p in pairs])
    return comp, res


def decompress_tree(comp, dtype=jnp.float32):
    is_pair = lambda x: (isinstance(x, tuple) and len(x) == 2
                         and hasattr(x[0], "dtype"))
    return jax.tree_util.tree_map(
        lambda qs: decompress_grad(qs[0], qs[1], dtype), comp,
        is_leaf=is_pair)


def init_residuals(grads):
    return jax.tree_util.tree_map(
        lambda g: jnp.zeros(g.shape, jnp.float32), grads)


# ---------------------------------------------------------------------------
# Explicit collective schedules
# ---------------------------------------------------------------------------

def hierarchical_allreduce(x: Array, mesh: Mesh, *, pod_axis: str = "pod",
                           data_axis: str = "data") -> Array:
    """x: (n_pod, n_data, *leaf) per-device contributions; returns the same
    shape where every slice holds the global sum.

    Schedule: psum_scatter intra-pod -> psum across pods on 1/n_data shards
    -> all-gather intra-pod.  Inter-pod traffic = leaf_bytes / n_data.
    """
    if pod_axis not in mesh.shape:
        def f1(xs):
            return jax.lax.psum(xs[0], data_axis)[None]
        return shard_map(f1, mesh=mesh, in_specs=PS(data_axis),
                         out_specs=PS(data_axis), check_vma=False)(x)

    def f(xs):
        v = xs[0, 0]                                    # this device's grad
        scattered = jax.lax.psum_scatter(v, data_axis, scatter_dimension=0,
                                         tiled=True)    # intra-pod RS
        reduced = jax.lax.psum(scattered, pod_axis)     # thin inter-pod hop
        full = jax.lax.all_gather(reduced, data_axis, axis=0,
                                  tiled=True)           # intra-pod AG
        return full[None, None]

    return shard_map(f, mesh=mesh, in_specs=PS(pod_axis, data_axis),
                     out_specs=PS(pod_axis, data_axis), check_vma=False)(x)


def per_shard_sums(x: Array, mesh: Mesh, axis: str = "data",
                   weights=None) -> Array:
    """Per-shard sums of a slot-batch leaf, all-gathered everywhere.

    ``x``: ``(B, ...)`` sharded (or shardable) over ``axis``; returns an
    ``(n_shards,)`` float32 vector where entry *s* is the sum of shard
    *s*'s rows — the serving mesh's balance telemetry (live tokens per
    shard) computed with one tiny all-gather instead of pulling the whole
    leaf to the host.  ``weights`` optionally masks rows first (e.g. a
    ``(B,)`` live-slot indicator), letting retired slots' stale ``pos``
    drop out of the sum.
    """
    def f(xs, ws):
        local = jnp.sum(xs.astype(jnp.float32) * ws.astype(jnp.float32))
        return jax.lax.all_gather(local, axis)

    if weights is None:
        weights = jnp.ones((x.shape[0],), jnp.float32)
    flat = x.reshape(x.shape[0], -1).sum(axis=-1)   # (B,) row totals
    return shard_map(f, mesh=mesh, in_specs=(PS(axis), PS(axis)),
                     out_specs=PS(), check_vma=False)(flat, weights)


def ring_allreduce(x: Array, mesh: Mesh, axis: str = "data") -> Array:
    """x: (n, *leaf) per-device contributions -> (n, *leaf) of global sums.

    Explicit 2(n-1)-step ring: reduce-scatter then all-gather, one chunk in
    flight per step (the overlap-friendly schedule).
    """
    n = mesh.shape[axis]
    perm = [(i, (i + 1) % n) for i in range(n)]

    def f(xs):
        v = xs[0]                                   # (*leaf)
        leaf_shape = v.shape
        chunks = v.reshape(n, -1)                   # n ring chunks
        idx = jax.lax.axis_index(axis)

        # reduce-scatter: after n-1 steps we own chunk (idx+1) % n
        buf = jnp.take(chunks, idx % n, axis=0)
        for s in range(n - 1):
            buf = jax.lax.ppermute(buf, axis, perm)
            j = (idx - s - 1) % n
            buf = buf + jnp.take(chunks, j, axis=0)

        # all-gather: circulate the owned chunk around the ring
        out = jnp.zeros_like(chunks)
        out = out.at[(idx + 1) % n].set(buf)
        cur = buf
        for s in range(n - 1):
            cur = jax.lax.ppermute(cur, axis, perm)
            out = out.at[(idx - s) % n].set(cur)
        return out.reshape(leaf_shape)[None]

    return shard_map(f, mesh=mesh, in_specs=PS(axis),
                     out_specs=PS(axis), check_vma=False)(x)
