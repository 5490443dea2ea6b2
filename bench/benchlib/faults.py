"""Faults planted in the timed path, to see ``correct`` come out false.

Each is a hook on a built engine, applied before its warm-up so that
what it compiles is compiled in set-up.  ``bench/run.py --fault <name>``
runs a cell with one planted; the benchmark's tests drive them on tiny
cells.  Warm-up requests have rids from 10**9; a fault that waits for
"the window" waits for the first admission of a smaller rid.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def _window_admitted(engine) -> list:
    return [e for e in engine.events if e[0] == "admit" and e[1] < 10 ** 9]


def token_altered(engine) -> None:
    """The first decode step after the window's first admission emits a
    different id in that slot."""
    pull = engine._pull_logits
    done = []

    def altered(logits, sampling):
        ids, rows = pull(logits, sampling)
        admits = _window_admitted(engine)
        if admits and not done:
            done.append(admits[0][2])
            ids = ids.copy()
            ids[done[0]] = (ids[done[0]] + 1) % 256
        return ids, rows
    engine._pull_logits = altered


def state_unchanged(engine) -> None:
    """Once the window's requests are admitted, every decode step hands
    back the cache or state it was given, unchanged."""
    decode = engine._decode

    def unchanged(params, state, batch):
        logits, new = decode(params, state, batch)
        return logits, (state if _window_admitted(engine) else new)
    engine._decode = unchanged


def state_bf16(engine) -> None:
    """Every float32 leaf of the decode state (rwkv6's wkv state) is
    rounded to bfloat16 before each decode step: a state kept in bf16."""
    decode = engine._decode
    rnd = jax.jit(lambda s: jax.tree.map(
        lambda a: (a.astype(jnp.bfloat16).astype(a.dtype)
                   if a.dtype == jnp.float32 else a), s))

    def rounded(params, state, batch):
        return decode(params, rnd(state), batch)
    engine._decode = rounded


FAULTS = {"token-altered": token_altered,
          "state-unchanged": state_unchanged,
          "state-bf16": state_bf16}
