"""Operations and bytes the algorithm needs, from a configuration's sizes.

These count what the computation requires, not what the program happens
to do: a decode step reads the weights once and the cache or state of
its live rows only, and computes its live rows only.  A multiply-add is
two operations.  The sizes that differ between families come from the
family's reference module (``bench/reference/<family>.py``), whose
``cost_terms(m)`` gives, per layer:

* ``matmul_params``: weights every token multiplies;
* ``other_params``: the other weights it reads (norm gains, biases, ...);
* ``ctx_flops``: operations per position a token attends to;
* ``step_flops``: operations per token independent of its context;
* ``ctx_bytes``: cache bytes per position, read (and one written);
* ``state_bytes``: fixed per-row state bytes, read and written.
"""
from __future__ import annotations

from typing import Iterable, Optional

BF16 = 2


def weight_bytes(t: dict, m: dict, n_layers: int) -> int:
    """Bytes of weights one forward pass reads (bf16), the head included;
    the embedding table is gathered, not read whole."""
    per_layer = t["matmul_params"] + t["other_params"]
    return BF16 * (n_layers * per_layer + m["d_model"] * m["vocab_size"])


def token_flops(t: dict, m: dict, n_layers: int, ctx: int,
                head: bool = True) -> float:
    """Operations for one token that sees ``ctx`` positions (itself
    included), through every layer and, if ``head``, the output head."""
    per_layer = 2 * t["matmul_params"] + t["ctx_flops"] * ctx + t["step_flops"]
    return n_layers * per_layer + (
        2 * m["d_model"] * m["vocab_size"] if head else 0)


def state_bytes(t: dict, n_layers: int, ctx: int) -> float:
    """Per-row cache or state bytes one decode step reads and writes."""
    return n_layers * (t["ctx_bytes"] * (ctx + 1) + 2 * t["state_bytes"])


def decode_step_cost(t: dict, m: dict, n_layers: int,
                     ctxs: Iterable[int]) -> tuple:
    """(operations, bytes) of one decode step whose live rows see
    ``ctxs`` positions each."""
    ctxs = list(ctxs)
    flops = sum(token_flops(t, m, n_layers, c) for c in ctxs)
    nbytes = (weight_bytes(t, m, n_layers)
              + sum(state_bytes(t, n_layers, c) for c in ctxs)
              + len(ctxs) * m["d_model"] * BF16)      # embedding rows
    return flops, nbytes


def least_seconds(flops: float, nbytes: float, peaks: dict) -> tuple:
    """The roofline's least time and which bound sets it."""
    t_c = flops / peaks["bf16_flops"]
    t_m = nbytes / peaks["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")


def decode_bound(run) -> Optional[str]:
    """Which roofline bound sets the least time of the window's middle
    decode step (a ``record.Run``); None without decode steps."""
    ctxs = run.step_contexts()
    if not ctxs:
        return None
    mid = sorted(ctxs)[len(ctxs) // 2]
    f, b = decode_step_cost(run.terms, run.model, run.n_layers, ctxs[mid])
    return least_seconds(f, b, run.peaks)[1]
