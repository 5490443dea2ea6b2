"""Weights drawn from the seed by the benchmark, not by the program.

A family's reference module lists its leaves as ``(path, shape, kind,
dtype)``: ``path`` is where the leaf sits in the program's parameter
tree (``"blocks/attn/wq"``), ``shape`` is one layer's shape for leaves
under ``blocks/`` (stacked over layers in the program), and ``kind`` is
how it is drawn:

* ``("fan_in",)``        normal / sqrt(shape[-2])
* ``("normal", std)``    normal * std
* ``("gain", std)``      1 + normal * std
* ``("uniform", lo, hi)``

Each leaf's values depend only on the seed, its path and its layer, so
the program's stacked tree (drawn in one jitted call, in the type it is
served in) and the reference's one-layer-at-a-time draws hold the same
numbers.
"""
from __future__ import annotations

import zlib
from typing import Dict, Iterable, Tuple

import jax
import jax.numpy as jnp
import numpy as np

Leaf = Tuple[str, Tuple[int, ...], tuple, str]


def seed_words(seed: int) -> np.ndarray:
    """A seed of up to 64 bits as two uint32 words (a traced argument, so
    one compiled draw serves every seed)."""
    s = seed % 2 ** 64
    return np.array([s & 0xFFFFFFFF, s >> 32], np.uint32)


def _root(words) -> jax.Array:
    return jax.random.fold_in(jax.random.PRNGKey(words[0]), words[1])


def _leaf_key(root, path: str, layer=None):
    k = jax.random.fold_in(root, zlib.crc32(path.encode()) & 0x7FFFFFFF)
    return k if layer is None else jax.random.fold_in(k, layer)


def _draw(key, shape, kind, dtype) -> jax.Array:
    """One leaf.  Every step is a single rounding that no fusion can
    change (a multiply by a constant, an exact add after a bf16
    rounding), so a draw inside the one big program and the same draw in
    a small one give the same bits."""
    def bf16(x):
        return x.astype(jnp.bfloat16).astype(jnp.float32)

    if kind[0] == "uniform":
        u = jax.random.uniform(key, shape, jnp.float32)
        v = kind[1] + bf16(u * np.float32(kind[2] - kind[1]))
    else:
        z = jax.random.normal(key, shape, jnp.float32)
        if kind[0] == "fan_in":
            v = z * np.float32(1.0 / np.sqrt(shape[-2]))
        elif kind[0] == "normal":
            v = z * np.float32(kind[1])
        elif kind[0] == "gain":
            v = 1.0 + bf16(z * np.float32(kind[1]))
        else:
            raise ValueError(f"unknown weight kind {kind!r}")
    return v.astype(dtype)


def is_layer_leaf(path: str) -> bool:
    return path.startswith("blocks/")


def draw_tree(leaves: Iterable[Leaf], words, n_layers: int) -> Dict:
    """The program's whole parameter tree (jit this: one program)."""
    root = _root(words)
    tree: Dict = {}
    for path, shape, kind, dtype in leaves:
        if is_layer_leaf(path):
            val = jax.vmap(lambda l, p=path, s=shape, k=kind, d=dtype: _draw(
                _leaf_key(root, p, l), s, k, d))(jnp.arange(n_layers))
        else:
            val = _draw(_leaf_key(root, path), shape, kind, dtype)
        node = tree
        *parents, last = path.split("/")
        for p in parents:
            node = node.setdefault(p, {})
        node[last] = val
    return tree


def draw_layer(leaves: Iterable[Leaf], words, layer) -> Dict[str, jax.Array]:
    """One layer's leaves, keyed by path without ``blocks/``."""
    root = _root(words)
    return {path[len("blocks/"):]: _draw(_leaf_key(root, path, layer),
                                         shape, kind, dtype)
            for path, shape, kind, dtype in leaves if is_layer_leaf(path)}


def draw_global(leaves: Iterable[Leaf], words, path: str) -> jax.Array:
    root = _root(words)
    for p, shape, kind, dtype in leaves:
        if p == path:
            return _draw(_leaf_key(root, p), shape, kind, dtype)
    raise KeyError(path)


# The reference's draws, compiled as the program's are: an op run eagerly
# may round a normal draw differently from the same op inside a program.

def layer_f32(leaves):
    """A jitted ``(words, layer) -> {path: float32 array}``."""
    leaves = list(leaves)
    return jax.jit(lambda words, layer: {
        k: v.astype(jnp.float32)
        for k, v in draw_layer(leaves, words, layer).items()})


def global_leaf(leaves, path: str):
    """A jitted ``words -> the leaf`` in the type it is served in."""
    leaves = list(leaves)
    return jax.jit(lambda words: draw_global(leaves, words, path))


def check_layout(leaves: Iterable[Leaf], abstract_tree, n_layers: int
                 ) -> None:
    """Refuse to run when the program's parameter tree is not the one
    the reference describes (a renamed, reshaped or retyped leaf)."""
    want = {}
    for path, shape, _, dtype in leaves:
        full = ((n_layers,) + tuple(shape)) if is_layer_leaf(path) \
            else tuple(shape)
        want[path] = (full, jnp.dtype(dtype))
    got = {}
    for kp, leaf in jax.tree_util.tree_flatten_with_path(abstract_tree)[0]:
        path = "/".join(str(getattr(k, "key", k)) for k in kp)
        got[path] = (tuple(leaf.shape), jnp.dtype(leaf.dtype))
    if want != got:
        diff = sorted(set(want.items()) ^ set(got.items()))
        raise ValueError(f"program parameter tree differs from the "
                         f"reference's leaves: {diff[:6]}")
