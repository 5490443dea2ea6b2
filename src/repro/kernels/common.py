"""Shared kernel substrate: the one dispatch layer under all five families.

The paper's RPE is a *single* reconfigurable datapath that serves MAC,
tanh/sigmoid, and SoftMax workloads; this module is the software analogue.
Every kernel family (``cordic_act``, ``cordic_mac``, ``cordic_softmax``,
``flash_attention``, ``wkv``) routes its public wrapper through here for:

  * **platform policy** — :func:`platform` / :func:`on_tpu` /
    :func:`resolve_interpret`: Pallas kernels compile on TPU and run in
    interpret mode everywhere else (the CPU path), overridable with
    ``REPRO_KERNEL_INTERPRET=0|1``; interpreting on a TPU warns.
  * **compiler params** — :func:`compiler_params` builds the
    ``pltpu.CompilerParams`` every family passes to ``pallas_call``.
  * **block sizing** — :func:`aligned_block` / :func:`pick_block_2d` /
    :func:`pick_block_rows` / :func:`pick_block_matmul` (tiles the TPU
    can lower: whole axes or (8, 128) multiples, padded by the wrapper
    where no such divisor exists), all answering through a three-level
    lookup:
    the in-process per-(kernel, shape, dtype) cache (which
    :func:`autotune` overwrites with measured winners), then the
    persistent tuned table from :mod:`repro.kernels.tuning`, then the
    shape heuristic.
  * **registry** — :class:`KernelSpec` maps a family name to its raw Pallas
    entry point, its bit/numeric oracle from ``ref.py``, the float
    function whose exact VJP is the STE backward pass, and (for families
    that have one) the fused Pallas backward entry point.
  * **gradients** — :func:`ste` packages the straight-through custom_vjp
    pattern (quantized forward, exact float backward) that every family
    used to hand-roll; :func:`fused_vjp` generalises it to a fused Pallas
    backward kernel when the family registers one
    (``REPRO_FUSED_BWD=0`` forces the STE fallback).

Adding a new family?  Read ``docs/KERNELS.md``.
"""
from __future__ import annotations

import dataclasses
import functools
import os
import time
import warnings
from typing import Any, Callable, Dict, Iterable, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.experimental.pallas import tpu as pltpu

from repro.core.caesar import pick_block_shape
from repro.kernels import tuning

# ---------------------------------------------------------------------------
# Platform policy
# ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=None)
def platform() -> str:
    """Primary accelerator platform: 'tpu', 'gpu' or 'cpu'.

    A backend that fails to initialise raises here: reporting "cpu"
    instead would run every kernel in the interpreter on a broken chip.
    """
    return jax.devices()[0].platform


def on_tpu() -> bool:
    return platform() == "tpu"


def resolve_interpret(interpret: Optional[bool]) -> bool:
    """The interpret-mode policy shared by every family.

    Explicit ``interpret=`` wins; else ``REPRO_KERNEL_INTERPRET=0|1``
    (``0`` forces compilation, which fails loudly off-TPU; ``1`` forces
    the interpreter while debugging); else interpret everywhere except
    real TPUs.  Interpreting on a TPU is never silent: it warns, since
    every timing taken that way measures the interpreter.
    """
    if interpret is None:
        env = os.environ.get("REPRO_KERNEL_INTERPRET")
        if env is None:
            return not on_tpu()
        interpret = env.lower() not in ("0", "false", "no")
    if interpret and on_tpu():
        warnings.warn("Pallas kernels are running in interpret mode on a "
                      "TPU (interpret=True or REPRO_KERNEL_INTERPRET=1)",
                      RuntimeWarning, stacklevel=2)
    return interpret


def compiler_params(*dimension_semantics: str,
                    vmem_limit_bytes: Optional[int] = None):
    """TPU compiler params for a kernel's grid axes (and, for kernels
    whose tiles outgrow the default scoped VMEM, a raised limit)."""
    return pltpu.CompilerParams(
        dimension_semantics=tuple(dimension_semantics),
        vmem_limit_bytes=vmem_limit_bytes)


# ---------------------------------------------------------------------------
# Block sizing + autotune cache
# ---------------------------------------------------------------------------

# (kernel name, shape tuple, dtype name) -> chosen block tuple
_BLOCK_CACHE: Dict[Tuple[str, Tuple[int, ...], str], Tuple[int, ...]] = {}

# Lazily-loaded snapshot of the on-disk tuned table (None = not loaded yet).
# Consulted by the pick_block_* helpers between the in-process cache and
# the heuristic: in-process beats disk beats heuristic.
_DISK_TABLE: Optional[Dict[Tuple[str, Tuple[int, ...], str],
                           Tuple[int, ...]]] = None


def _cache_key(kernel: str, shape: Sequence[int], dtype: Any
               ) -> Tuple[str, Tuple[int, ...], str]:
    return (kernel, tuple(int(s) for s in shape), jnp.dtype(dtype).name)


def clear_block_cache() -> None:
    _BLOCK_CACHE.clear()


def cached_block(kernel: str, shape: Sequence[int], dtype: Any
                 ) -> Optional[Tuple[int, ...]]:
    return _BLOCK_CACHE.get(_cache_key(kernel, shape, dtype))


def set_block(kernel: str, shape: Sequence[int], dtype: Any,
              block: Sequence[int]) -> None:
    _BLOCK_CACHE[_cache_key(kernel, shape, dtype)] = tuple(block)


def block_cache_snapshot() -> Dict[Tuple[str, Tuple[int, ...], str],
                                   Tuple[int, ...]]:
    """Copy of the in-process cache (what a tuner would persist)."""
    return dict(_BLOCK_CACHE)


def load_tuned_table(path: Optional[str] = None) -> int:
    """(Re)load the persistent tuned table; returns the entry count.

    Called eagerly by serving so boots are warm; the pick_block_* helpers
    also trigger a lazy load on first miss, so calling this is an
    optimisation, never a requirement.  A missing/stale/corrupt table
    loads as empty (see :mod:`repro.kernels.tuning`).
    """
    global _DISK_TABLE
    _DISK_TABLE = tuning.load(path)
    return len(_DISK_TABLE)


def reset_disk_table() -> None:
    """Forget the loaded tuned table (next lookup re-reads; test seam)."""
    global _DISK_TABLE
    _DISK_TABLE = None


def _disk_block(kernel: str, shape: Sequence[int], dtype: Any
                ) -> Optional[Tuple[int, ...]]:
    global _DISK_TABLE
    if _DISK_TABLE is None:
        _DISK_TABLE = tuning.load()
    return _DISK_TABLE.get(_cache_key(kernel, shape, dtype))


def _lookup(kernel: str, shape: Sequence[int], dtype: Any
            ) -> Optional[Tuple[int, ...]]:
    """Levels 1+2 of the lookup: in-process cache, then disk table.

    A disk hit is promoted into the in-process cache, so later
    ``set_block``/``autotune`` results still take precedence over it.
    """
    hit = cached_block(kernel, shape, dtype)
    if hit is not None:
        return hit
    hit = _disk_block(kernel, shape, dtype)
    if hit is not None:
        set_block(kernel, shape, dtype, hit)
    return hit


def largest_divisor(n: int, cap: int) -> int:
    """Largest d with 1 <= d <= cap and n % d == 0."""
    d = max(1, min(int(cap), int(n)))
    while n % d:
        d -= 1
    return d


def divisor_candidates(n: int, cap: int, limit: int = 4) -> Tuple[int, ...]:
    """Up to ``limit`` distinct divisors of ``n`` that are <= ``cap``,
    largest first.  The building block for ``KernelSpec.candidates``
    hooks of kernels whose tiles must divide the array."""
    out = []
    cap = min(int(cap), int(n))
    while len(out) < limit:
        d = largest_divisor(n, cap)
        out.append(d)
        if d == 1:
            break
        cap = d - 1
    return tuple(out)


# Mosaic tiles a block's last two dims in (sublanes, lanes) = (8, 128)
# for 32-bit types; narrower types pack more rows per sublane tile.
LANES = 128


def sublanes(dtype: Any) -> int:
    """Row multiple of a legal tile for ``dtype`` (8 for 32-bit types)."""
    return max(8, 32 // jnp.dtype(dtype).itemsize)


def aligned_block(n: int, cap: int, align: int) -> int:
    """Tile length along an axis of length ``n`` that the TPU can lower.

    The whole axis when it fits under ``cap``; else the largest divisor
    of ``n`` that is a multiple of ``align`` and at most ``cap``; else
    ``cap`` rounded down to ``align`` (at least ``align``), which does not
    divide ``n``: the caller pads the axis to a multiple of it.
    """
    n, cap = int(n), int(cap)
    if n <= cap:
        return n
    top = max(align, cap // align * align)
    for d in range(top, align - 1, -align):
        if n % d == 0:
            return d
    return top


def padded(n: int, block: int) -> int:
    """``n`` rounded up to a multiple of ``block``."""
    return -(-int(n) // block) * block


def pick_block_2d(kernel: str, shape: Tuple[int, int], dtype: Any = jnp.int32,
                  max_rows: int = 256, max_cols: int = 512) -> Tuple[int, int]:
    """(rows, cols) tile for an elementwise/row-wise kernel.

    Each side is the whole axis or a multiple of the (sublane, lane)
    tile, preferring divisors (:func:`aligned_block`); where no aligned
    divisor exists the tile does not divide the array and the wrapper
    pads.  Three-level lookup: the in-process cache (where
    :func:`autotune` winners land), then the persistent tuned table,
    then this heuristic.
    """
    hit = _lookup(kernel, shape, dtype)
    if hit is not None:
        return hit  # type: ignore[return-value]
    r, c = shape
    block = (aligned_block(r, max_rows, sublanes(dtype)),
             aligned_block(c, max_cols, LANES))
    set_block(kernel, shape, dtype, block)
    return block


def pick_block_rows(kernel: str, shape: Tuple[int, int],
                    dtype: Any = jnp.int32, max_rows: int = 128) -> int:
    """Row-block for kernels that keep the feature axis whole (softmax,
    the wkv time block); aligned as in :func:`pick_block_2d`."""
    hit = _lookup(kernel, shape, dtype)
    if hit is not None:
        return hit[0]
    br = aligned_block(shape[0], max_rows, sublanes(dtype))
    set_block(kernel, shape, dtype, (br, shape[1]))
    return br


def pick_block_matmul(kernel: str, m: int, n: int, k: int,
                      dtype: Any = jnp.int32, max_block: int = 256
                      ) -> Tuple[int, int, int]:
    """(bm, bn, bk) for an output-stationary matmul via the CAESAR
    VMEM-budget model (callers pad, so the block need not divide)."""
    hit = _lookup(kernel, (m, n, k), dtype)
    if hit is not None:
        return hit  # type: ignore[return-value]
    block = pick_block_shape(m, n, k,
                             bytes_per_el=jnp.dtype(dtype).itemsize,
                             max_block=max_block)
    set_block(kernel, (m, n, k), dtype, block)
    return block


def autotune(kernel: str, shape: Sequence[int], dtype: Any,
             candidates: Iterable[Sequence[int]],
             run: Callable[[Tuple[int, ...]], Any],
             repeats: int = 3) -> Tuple[int, ...]:
    """Measure ``run(block)`` per candidate; cache and return the winner.

    Each candidate gets one untimed call (compile/warmup) and ``repeats``
    timed calls, each blocked on individually — under jax's async dispatch,
    blocking only on the last result would let earlier calls overlap the
    timer and skew per-candidate numbers.  Candidates that raise (e.g.
    VMEM overflow on device) are skipped; ``KeyboardInterrupt`` is not
    swallowed.  The winner lands in the block cache under
    (kernel, shape, dtype), so the ``pick_block_*`` helpers serve it to
    every later trace of the same problem.
    """
    best: Optional[Tuple[int, ...]] = None
    best_t = float("inf")
    for cand in candidates:
        blk = tuple(int(b) for b in cand)
        try:
            jax.block_until_ready(run(blk))
            t0 = time.perf_counter()
            for _ in range(repeats):
                jax.block_until_ready(run(blk))
            dt = (time.perf_counter() - t0) / max(1, repeats)
        except KeyboardInterrupt:
            raise
        except Exception:
            continue
        if dt < best_t:
            best, best_t = blk, dt
    if best is None:
        raise ValueError(f"autotune({kernel!r}): no candidate ran")
    set_block(kernel, shape, dtype, best)
    return best


# ---------------------------------------------------------------------------
# Kernel registry
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class KernelSpec:
    """One kernel family, as the substrate sees it.

    kernel: the raw Pallas entry point (tiled, takes ``interpret=``).
    ref:    the oracle from the family's ``ref.py`` — bit-exact for the
            fixed-point families, float-allclose for flash/wkv.
    grad:   float function whose exact VJP is the backward pass (STE);
            None for forward-only families.
    grad_kernel: the raw fused Pallas backward entry point (tiled, takes
            ``interpret=``), consuming the residuals the forward emits
            under ``return_residuals=True``.  None = the family trains
            through the STE fallback only.
    candidates: ``candidates(shape, dtype) -> iterable of block tuples``
            — the family's legal tile candidates for the cache-key shape
            its wrapper uses, enumerated for :func:`autotune` /
            ``benchmarks.tune``.  None = family is not tunable.
            Backward tiles get their own registry entry (a ``<family>.bwd``
            spec) so the sweep tunes them independently.
    tags:   free-form labels ("fixed-point", "attention", ...).
    """
    name: str
    kernel: Callable[..., Any]
    ref: Callable[..., Any]
    grad: Optional[Callable[..., Any]] = None
    grad_kernel: Optional[Callable[..., Any]] = None
    candidates: Optional[Callable[..., Tuple[Tuple[int, ...], ...]]] = None
    tags: Tuple[str, ...] = ()


_REGISTRY: Dict[str, KernelSpec] = {}


def register(spec: KernelSpec) -> KernelSpec:
    """Idempotent by name (module re-imports re-register the same spec)."""
    _REGISTRY[spec.name] = spec
    return spec


def get_kernel(name: str) -> KernelSpec:
    try:
        return _REGISTRY[name]
    except KeyError:
        raise KeyError(
            f"no kernel {name!r} registered; known: {registered_kernels()} "
            "(import repro.kernels to populate the registry)") from None


def registered_kernels() -> Tuple[str, ...]:
    return tuple(sorted(_REGISTRY))


# ---------------------------------------------------------------------------
# Straight-through gradients
# ---------------------------------------------------------------------------


def ste(fwd: Callable[..., jax.Array],
        grad: Callable[..., jax.Array]) -> Callable[..., jax.Array]:
    """custom_vjp wrapper: quantized forward, exact float backward.

    ``fwd`` runs the (non-differentiable) kernel; the backward pass is the
    exact VJP of ``grad`` evaluated at the primal inputs — straight-through
    estimation.  All static configuration must already be bound into both
    callables; the returned function takes arrays only.
    """

    @jax.custom_vjp
    def f(*args):
        return fwd(*args)

    def f_fwd(*args):
        return fwd(*args), args

    def f_bwd(args, g):
        _, vjp = jax.vjp(grad, *args)
        return vjp(g)

    f.defvjp(f_fwd, f_bwd)
    return f


# ---------------------------------------------------------------------------
# Fused backward kernels
# ---------------------------------------------------------------------------


def fused_backward_enabled() -> bool:
    """Global switch for the fused Pallas backward passes.

    On by default; ``REPRO_FUSED_BWD=0`` forces every family back onto the
    STE fallback (the exact VJP of the float reference) — the escape hatch
    while debugging a backward kernel on device.
    """
    env = os.environ.get("REPRO_FUSED_BWD")
    if env is None:
        return True
    return env.lower() not in ("0", "false", "no")


def fused_vjp(fwd: Callable[..., jax.Array],
              grad: Callable[..., jax.Array],
              fwd_res: Optional[Callable[..., Any]] = None,
              bwd: Optional[Callable[..., Any]] = None
              ) -> Callable[..., jax.Array]:
    """custom_vjp wrapper generalising :func:`ste` to fused backwards.

    ``fwd`` runs the kernel; when the family registers a fused backward
    pair — ``fwd_res(*args) -> (out, residuals)`` (the kernel forward also
    emitting its O(S) residuals) and ``bwd(residuals, g) -> cotangents`` —
    differentiation goes through it.  Without the pair, or with
    ``REPRO_FUSED_BWD=0``, this *is* :func:`ste`: quantized/kernel forward,
    exact float backward via ``grad``.  As with ``ste``, all static
    configuration must already be bound in; the callables take arrays only.
    """
    if fwd_res is None or bwd is None or not fused_backward_enabled():
        return ste(fwd, grad)

    @jax.custom_vjp
    def f(*args):
        return fwd(*args)

    def f_fwd(*args):
        return fwd_res(*args)

    def f_bwd(res, g):
        return bwd(res, g)

    f.defvjp(f_fwd, f_bwd)
    return f
