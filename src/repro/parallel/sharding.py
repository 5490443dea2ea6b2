"""Logical-axis -> mesh-axis rule engine (DP / TP / EP / SP).

Rules map each logical axis name to an ordered list of candidate mesh-axis
tuples; the first candidate whose total size divides the dimension wins
(e.g. 40 experts cannot shard over model=16, so granite falls back to
sharding each expert's FFN instead).  This keeps every config compilable on
every mesh without per-arch hand-tuning — CAESAR's "adaptive resource
allocation" applied to the TPU mesh.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from repro.models import spec as pspec

MeshAxes = Tuple[str, ...]

# Candidates per logical axis, in preference order.  () = replicate.
DEFAULT_RULES: Dict[str, List[MeshAxes]] = {
    # data parallel over pod x data (global batch divides across both)
    "batch": [("pod", "data"), ("data",), ()],
    # sequence parallelism for long-context activations
    "seq": [("model",), ()],
    "embed": [()],                       # keep d_model whole on activations
    "embed_w": [("data",), ()],          # FSDP-style weight shard (opt-in)
    "vocab": [("model",), ()],
    "heads": [("model",), ()],
    "kv_heads": [("model",), ()],        # falls back to replicate when kv < tp
    "head_dim": [()],
    "qkv": [("model",), ()],
    "mlp": [("model",), ()],
    "experts": [("model",), ()],
    # 2D expert sharding: when "experts" already took the model axis
    # (arctic: 128 % 16 == 0) the per-expert FFN dim shards over data so
    # the 469B expert slab spreads over all 256/512 chips; when experts
    # can't shard (granite: 40 % 16 != 0) this falls back to model.
    "expert_mlp": [("model",), ("data", "pod"), ("data",), ()],
    "state": [()],
    "layers": [()],
    "codebooks": [()],
    # serving mesh (runtime/mesh_serve.py): the slot batch axis of the
    # engine's decode state, and the paged engine's shared block pool.
    # Both fall back to replicate when the dim doesn't divide the mesh
    # (e.g. an odd num_blocks pool on 8 shards serves replicated rather
    # than refusing).
    "slots": [("data",), ()],
    "blocks": [("data",), ()],
    None: [()],
}


# Context-scoped rule override (sharding profiles, e.g. the pure-DP
# profile for small MoEs — see EXPERIMENTS.md #Perf).
import contextlib
import threading

_ACTIVE = threading.local()


@contextlib.contextmanager
def use_rules(rules: Dict[str, List[MeshAxes]]):
    prev = getattr(_ACTIVE, "rules", None)
    _ACTIVE.rules = rules
    try:
        yield
    finally:
        _ACTIVE.rules = prev


def active_rules() -> Dict[str, List[MeshAxes]]:
    return getattr(_ACTIVE, "rules", None) or DEFAULT_RULES


# Pure data parallelism: batch over every axis, weights replicated.  The
# right profile when a model is too small for tp=16 (granite's 1.5k d_model
# at tp=16 is collective-bound 8:1 — see EXPERIMENTS.md #Perf).
PURE_DP_RULES: Dict[str, List[MeshAxes]] = {
    "batch": [("pod", "data", "model"), ("data", "model"), ("data",), ()],
    None: [()],
}

# ZeRO-1-style optimizer-moment sharding to pair with PURE_DP_RULES:
# params replicate, but Adam moments spread over the whole mesh.
ZERO1_OPT_RULES: Dict[str, List[MeshAxes]] = {
    "embed": [("model",), ("data",), ()],
    "mlp": [("data",), ("model",), ()],
    "expert_mlp": [("data",), ()],
    "heads": [("data",), ("model",), ()],
    "kv_heads": [("data",), ()],
    "qkv": [("data",), ()],
    "vocab": [("model",), ()],
    "experts": [()],
    "layers": [()],
    None: [()],
}


def _axis_size(mesh: Mesh, axes: MeshAxes) -> int:
    s = 1
    for a in axes:
        s *= mesh.shape[a]
    return s


def spec_for(shape: Sequence[int], axes: Sequence[Optional[str]], mesh: Mesh,
             rules: Optional[Dict[str, List[MeshAxes]]] = None
             ) -> PartitionSpec:
    """Resolve one tensor's PartitionSpec; never assigns a mesh axis twice."""
    rules = rules or active_rules()
    used: set = set()
    entries = []
    for dim, name in zip(shape, axes):
        chosen: Optional[MeshAxes] = ()
        for cand in rules.get(name, [()]):
            if not all(a in mesh.shape for a in cand):
                continue
            if any(a in used for a in cand):
                continue
            if cand and dim % _axis_size(mesh, cand) != 0:
                continue
            chosen = cand
            break
        for a in chosen:
            used.add(a)
        if not chosen:
            entries.append(None)
        elif len(chosen) == 1:
            entries.append(chosen[0])
        else:
            entries.append(tuple(chosen))
    # trim trailing Nones (canonical form)
    while entries and entries[-1] is None:
        entries.pop()
    return PartitionSpec(*entries)


def tree_shardings(param_tree, mesh: Mesh,
                   rules: Optional[Dict[str, List[MeshAxes]]] = None):
    """NamedSharding tree for a P-spec tree (or abstract tree + axes tree)."""
    def one(p: pspec.P):
        return NamedSharding(mesh, spec_for(p.shape, p.axes, mesh, rules))
    return pspec.tree_map_specs(one, param_tree)


def tree_pspecs(param_tree, mesh: Mesh,
                rules: Optional[Dict[str, List[MeshAxes]]] = None):
    def one(p: pspec.P):
        return spec_for(p.shape, p.axes, mesh, rules)
    return pspec.tree_map_specs(one, param_tree)


def constrain(x: jax.Array, axes: Sequence[Optional[str]],
              rules: Optional[Dict[str, List[MeshAxes]]] = None) -> jax.Array:
    """Activation sharding constraint by logical axes (no-op outside a mesh)."""
    mesh = get_abstract_mesh()
    if mesh is None or mesh.empty:
        return x
    ps = spec_for(x.shape, axes, mesh, rules)
    return jax.lax.with_sharding_constraint(x, ps)


def get_abstract_mesh():
    """The mesh set by ``jax.set_mesh``, or None outside one."""
    m = jax.sharding.get_abstract_mesh()
    return None if m.empty else m


def data_sharding(mesh: Mesh, *, batch_axes: MeshAxes = ("pod", "data")
                  ) -> NamedSharding:
    """Input-batch sharding: batch over every available DP axis."""
    axes = tuple(a for a in batch_axes if a in mesh.shape)
    return NamedSharding(mesh, PartitionSpec(axes if len(axes) > 1 else
                                             (axes[0] if axes else None)))


# -- serving slot state ------------------------------------------------------

# Leaves of DecodeState / PagedDecodeState whose *second* axis is the
# shared block pool rather than the slot batch (paged mode only — the
# recurrent leaves stay per-slot even in a paged state).
_POOL_LEAVES = ("cache_k", "cache_v", "scale_k", "scale_v")


def slot_leaf_axes(name: str, ndim: int, pooled: bool
                   ) -> Tuple[Optional[str], ...]:
    """Logical axes of one serving slot-state leaf.

    Every dense leaf is ``(L, B, ...)`` — layers leading, slot batch
    second; ``pos`` is ``(B,)`` and the paged ``block_tables`` are
    ``(B, P)``.  In a pooled (paged) state the K/V + scale leaves are
    ``(L, N_blocks, page, ...)`` and shard over the pool axis instead.
    """
    if name == "pos":
        return ("slots",) + (None,) * (ndim - 1)
    if name == "block_tables":
        return ("slots",) + (None,) * (ndim - 1)
    if pooled and name in _POOL_LEAVES:
        return ("layers", "blocks") + (None,) * (ndim - 2)
    return ("layers", "slots") + (None,) * (ndim - 2)


def slot_state_shardings(state, mesh: Mesh,
                         rules: Optional[Dict[str, List[MeshAxes]]] = None):
    """Per-leaf :class:`NamedSharding` for an engine slot state.

    ``state`` is a ``DecodeState`` / ``PagedDecodeState`` (concrete or
    abstract — only ``.shape``/``.ndim`` are read); returns the same
    namedtuple type with a sharding per populated leaf and ``None`` where
    the leaf is ``None``.  Divisibility fallback comes from the rule
    engine: a leaf whose slot (or pool) dim doesn't divide the mesh's
    data axis replicates instead of failing.
    """
    pooled = getattr(state, "block_tables", None) is not None
    out = {}
    for name in state._fields:
        leaf = getattr(state, name)
        if leaf is None:
            out[name] = None
            continue
        axes = slot_leaf_axes(name, leaf.ndim, pooled)
        out[name] = NamedSharding(mesh,
                                  spec_for(leaf.shape, axes, mesh, rules))
    return type(state)(**out)
