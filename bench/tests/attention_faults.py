"""Faults planted in the attention branch alone, as engine hooks in the
form of ``benchlib/faults.py``'s (``bench/run.py``'s ``engine_hook``), to
see whether a hybrid cell's comparison sees attention and its KV cache
and not only the recurrent branch:

* ``rope-off``: queries and keys are not rotated;
* ``kv-read-shifted``: decode reads the K/V of position t-1 where it
  means t's (so it drops its own and reads an empty slot at 0);
* ``kv-write-early``: each decode step writes its K/V one position
  early, over the previous token's, and attends up to that position;
* ``kv-heads-swapped``: query-head group g reads KV head Hkv-1-g.

The first three re-trace the engine's compiled steps with one function of
the program replaced while they trace; the last reverses the KV heads
of the served wk and wv.  Apply a hook before the engine's warm-up.
"""
from __future__ import annotations

from unittest import mock

import jax
import jax.numpy as jnp

STEPS = {"_prefill": (), "_decode": (1,), "_extend": (1,), "_verify": (1,),
         "_verify_greedy": (1,)}


def _retrace(engine, patch) -> None:
    """Re-jit the engine's model steps so that they trace under
    ``patch`` (donating as the engine does on an accelerator)."""
    donate = jax.devices()[0].platform != "cpu"
    for name, argnums in STEPS.items():
        fn = getattr(engine, name, None)
        if fn is None:
            continue

        def traced(*a, _fn=fn.__wrapped__):
            with patch():
                return _fn(*a)
        setattr(engine, name, jax.jit(
            traced, donate_argnums=argnums if donate else ()))


def rope_off(engine) -> None:
    from repro.models import layers as L

    _retrace(engine, lambda: mock.patch.object(
        L, "apply_rope", lambda x, angles: x))


def kv_write_early(engine) -> None:
    from repro.models import attention as A

    decode = A.decode_attention

    def early(q, k, v, ck, cv, pos, *a, **kw):
        return decode(q, k, v, ck, cv, pos - 1, *a, **kw)

    _retrace(engine, lambda: mock.patch.object(A, "decode_attention",
                                               early))


def kv_read_shifted(engine) -> None:
    from repro.models import attention as A

    attend = A._attend_decode

    def shifted(q, keys, vals, *a, **kw):
        return attend(q, jnp.roll(keys, 1, axis=1), jnp.roll(vals, 1, axis=1),
                      *a, **kw)

    _retrace(engine, lambda: mock.patch.object(A, "_attend_decode",
                                               shifted))


def kv_heads_swapped(engine) -> None:
    attn = engine.params["blocks"]["attn"]
    dh = engine.model.cfg.head_dim_
    for name in ("wk", "wv"):
        w = attn[name]
        attn[name] = w.reshape(w.shape[:-1] + (-1, dh))[..., ::-1, :] \
            .reshape(w.shape)


FAULTS = {"rope-off": rope_off, "kv-read-shifted": kv_read_shifted,
          "kv-write-early": kv_write_early,
          "kv-heads-swapped": kv_heads_swapped}
