"""Persistent tuned-block table: the retained-configuration layer.

The paper's SYCore earns its throughput by *configuring* the RPE array per
workload; the software analogue is the per-(kernel, shape, dtype) block
cache in :mod:`repro.kernels.common`.  That cache is in-process only —
every serving boot would re-derive (or never measure) its tiles.  This
module persists measured winners to disk so tuning is paid once per
(jax version, platform) and every later process boots warm:

  * **format** — one JSON document: a ``version`` stamp plus an
    ``entries`` list of ``{kernel, shape, dtype, block}`` records, keyed
    exactly like the in-process cache.
  * **versioning** — the stamp is (schema int, jax version, platform,
    device kind).  A table written by a different jax release, for a
    different accelerator or for another chip generation is *stale*:
    :func:`load` silently discards it, because a block measured under
    another compiler/backend is at best noise and at worst illegal.
  * **location** — ``REPRO_TUNE_CACHE`` if set, else the XDG cache dir
    (``$XDG_CACHE_HOME/repro/tuned_blocks.json``, defaulting to
    ``~/.cache/repro``).
  * **robustness** — a corrupt or truncated file loads as an empty table
    (serving must never fail on a bad cache); :func:`save` writes
    atomically (tmp + rename) and by default merges with the valid
    entries already on disk, so concurrent tuners lose at most a race,
    never the file.

Producers: ``benchmarks/tune_bench.py`` (the sweep CLI) and any direct
:func:`repro.kernels.common.autotune` caller that snapshots its winners.
Consumer: the three-level lookup in ``common.pick_block_*`` (in-process →
this table → heuristic) and ``runtime/serve_loop.py``'s warm boot.

Kept dependency-light (jax + stdlib only) so :mod:`repro.kernels.common`
can import it without cycles.
"""
from __future__ import annotations

import json
import os
import tempfile
from typing import Any, Dict, Optional, Tuple

import jax

# Bump when the on-disk layout changes; old files are then ignored.
SCHEMA_VERSION = 1

# Same key structure as common._BLOCK_CACHE.
Key = Tuple[str, Tuple[int, ...], str]
Table = Dict[Key, Tuple[int, ...]]

_ENV_VAR = "REPRO_TUNE_CACHE"


def version_stamp() -> Dict[str, Any]:
    """The validity domain of a tuned table: blocks tuned on one chip
    never load on another (``device_kind`` tells v5e from v6e)."""
    dev = jax.devices()[0]
    return {
        "schema": SCHEMA_VERSION,
        "jax": jax.__version__,
        "platform": dev.platform,
        "device_kind": dev.device_kind,
    }


def default_path() -> str:
    """``REPRO_TUNE_CACHE`` if set, else the XDG cache location."""
    env = os.environ.get(_ENV_VAR)
    if env:
        return env
    xdg = os.environ.get("XDG_CACHE_HOME") or os.path.join(
        os.path.expanduser("~"), ".cache")
    return os.path.join(xdg, "repro", "tuned_blocks.json")


def _entry_to_key(entry: Any) -> Optional[Tuple[Key, Tuple[int, ...]]]:
    """Validate one on-disk record; None if malformed (skipped, not fatal)."""
    if not isinstance(entry, dict):
        return None
    kernel = entry.get("kernel")
    shape = entry.get("shape")
    dtype = entry.get("dtype")
    block = entry.get("block")
    if not (isinstance(kernel, str) and isinstance(dtype, str)
            and isinstance(shape, (list, tuple))
            and isinstance(block, (list, tuple)) and block):
        return None
    try:
        key = (kernel, tuple(int(s) for s in shape), dtype)
        val = tuple(int(b) for b in block)
    except (TypeError, ValueError):
        return None
    if any(b < 1 for b in val):
        return None
    return key, val


def load(path: Optional[str] = None) -> Table:
    """Read the tuned table; {} on missing, corrupt or stale-version files.

    Never raises on bad content: a cache must degrade to "no cache".
    """
    path = path or default_path()
    try:
        with open(path, "r", encoding="utf-8") as f:
            doc = json.load(f)
    except (OSError, ValueError):
        return {}
    if not isinstance(doc, dict):
        return {}
    if doc.get("version") != version_stamp():
        return {}  # stale: different schema, jax release, or platform
    table: Table = {}
    for entry in doc.get("entries") or []:
        kv = _entry_to_key(entry)
        if kv is not None:
            table[kv[0]] = kv[1]
    return table


def save(table: Table, path: Optional[str] = None,
         merge: bool = True) -> str:
    """Write ``table`` (atomically); returns the path written.

    With ``merge`` (default), valid same-version entries already on disk
    are kept and ``table`` overrides on key collisions — so incremental
    tuning runs accumulate instead of clobbering each other.  A stale or
    corrupt existing file contributes nothing and is replaced.
    """
    path = path or default_path()
    merged: Table = load(path) if merge else {}
    merged.update(table)
    doc = {
        "version": version_stamp(),
        "entries": [
            {"kernel": k[0], "shape": list(k[1]), "dtype": k[2],
             "block": list(v)}
            for k, v in sorted(merged.items())
        ],
    }
    parent = os.path.dirname(os.path.abspath(path))
    os.makedirs(parent, exist_ok=True)
    fd, tmp = tempfile.mkstemp(dir=parent, suffix=".tmp")
    try:
        with os.fdopen(fd, "w", encoding="utf-8") as f:
            json.dump(doc, f, indent=1, sort_keys=True)
        os.replace(tmp, path)
    except BaseException:
        try:
            os.unlink(tmp)
        except OSError:
            pass
        raise
    return path
