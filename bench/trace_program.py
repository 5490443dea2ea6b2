"""What the program records about itself in a profiler trace, on the
device's clock.

``trace_reduce`` reads the spans the benchmark wraps around the engine's
methods (``bench.*``) and joins the host and device clocks by a fitted
offset.  This module reads what the program writes itself:

* the ``serve.*`` host spans of ``ServeEngine`` (``runtime/serve_loop.py``):
  ``serve.admit`` (arguments ``step``, ``rows``, and ``bucket`` where it
  launches a prefill or extend program), ``serve.decode`` (``step``, the
  decode step it takes), ``serve.pull``, ``serve.pages``, ``serve.idle``;
* the named scope of each device operation: ``wkv``, ``time_mix``,
  ``channel_mix``, ``attention``, ``mlp`` and ``head``
  (``models/ssm.py``, ``attention.py``, ``layers.py``,
  ``transformer.py``).  On a v5e the operation's event carries no scope;
  the event's metadata does, as its ``tf_op`` stat (the jit name, then
  the scopes and primitives it was traced under:
  ``jit(_prefill_fn)/while/body/closed_call/time_mix/wkv/while:``), and the
  device plane has no name-scope line.  ``ProfileData`` does not expose
  metadata stats, so ``op_scopes`` reads them from the serialized trace.

``load(path)`` returns ``trace_reduce.load``'s ``devices`` and ``host``,
each device with ``scopes`` besides (the innermost scope of each
operation, None outside every scope), and ``serve``: the ``serve.*``
spans as ``(name, start ns, duration ns, arguments)``.

``reduce(trace)`` returns ``trace_reduce.reduce``'s keys, unchanged, and:

* ``tied``: the share of the traced executions of the prefill, extend and
  decode programs tied to the span that launched them.  Each
  ``serve.decode`` span launches one decode (or verify) program and each
  ``serve.admit`` span with a ``bucket`` one prefill or extend program,
  and the device runs them in the order the host launched them, so each
  kind is tied by dispatch order: of the alignments of executions to
  spans that differ by a few at the ends of the trace (an execution or a
  span cut off by its start or stop), the one whose median distance from
  span start to execution start is least;
* ``shift_s``: how far the device's clock is moved later, the least that
  leaves no tied execution starting before the span that launched it (0
  where none does);
* ``scopes``: device seconds of each program by innermost scope, by self
  time as ``device_ops`` counts them (``other`` for operations under no
  scope);
* ``idle_by_span``: device-idle seconds, on the shifted clock, put down
  to the innermost ``serve.*`` span open at each gap's middle; a gap
  whose middle lies before the first recorded ``serve.*`` span or after
  the last goes to its own label, since a span open when the profiler
  started or stopped is not recorded.
"""
from __future__ import annotations

import collections
import statistics
from typing import Dict, Iterator, List, Optional, Tuple

import trace_reduce as TR

SPAN_PREFIX = "serve."
SCOPES = ("wkv", "time_mix", "channel_mix", "attention", "mlp", "head")
NO_SCOPE = "other"
NO_SPAN = "outside every serve span"
# a span open when the profiler starts or stops is not recorded, so a gap
# before the first recorded serve span or after the last one may lie in a
# span the trace cut
CUT = "before or after every recorded serve span"
# the programs a launching span starts, one execution per span
LAUNCHES = {"serve.decode": ("_decode_fn", "_verify_greedy_fn",
                             "_verify_fn"),
            "serve.admit": ("_prefill_fn", "_extend_fn")}
# alignments tried at each end of the trace
EDGE = 3

Span = Tuple[str, float, float, dict]   # name, start ns, duration ns, args


# ---------------------------------------------------------------------------
# the serialized trace (an XSpace protobuf), read for its metadata only
# ---------------------------------------------------------------------------

def _varint(buf, i: int) -> Tuple[int, int]:
    x = shift = 0
    while True:
        b = buf[i]
        i += 1
        x |= (b & 0x7F) << shift
        if b < 0x80:
            return x, i
        shift += 7


def _fields(buf) -> Iterator[Tuple[int, object]]:
    """(field number, value) of each field of one protobuf message: an
    int for varint and fixed-width fields, a memoryview of the bytes for
    length-delimited ones."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(buf, i)
        elif wire == 1:
            v, i = int.from_bytes(buf[i:i + 8], "little"), i + 8
        elif wire == 2:
            size, i = _varint(buf, i)
            v, i = buf[i:i + size], i + size
        elif wire == 5:
            v, i = int.from_bytes(buf[i:i + 4], "little"), i + 4
        else:
            raise ValueError(f"unexpected protobuf wire type {wire}")
        yield key >> 3, v


def _map_value(entry):
    """The value of a protobuf map entry (its field 2)."""
    return next((v for f, v in _fields(entry) if f == 2), b"")


def op_scopes(data: bytes) -> Dict[str, Dict[str, str]]:
    """For each device plane of a serialized XSpace, each operation's
    event name (its metadata's name, which ``ProfileData`` reports) ->
    its ``tf_op`` stat.  XSpace.planes is field 1; XPlane.name 2,
    event_metadata 4, stat_metadata 5; XEventMetadata.name 2, stats 5;
    XStatMetadata.id 1, name 2; XStat.metadata_id 1, str_value 5,
    ref_value 7 (a string kept once, as a stat metadata's name)."""
    out: Dict[str, Dict[str, str]] = {}
    for f, plane in _fields(memoryview(data)):
        if f != 1:
            continue
        name, events, stat_names = "", [], {}
        for pf, v in _fields(plane):
            if pf == 2:
                name = bytes(v).decode()
            elif pf == 4:
                events.append(_map_value(v))
            elif pf == 5:
                meta = dict(_fields(_map_value(v)))
                stat_names[meta.get(1, 0)] = bytes(meta.get(2, b"")).decode()
        if not name.startswith("/device:"):
            continue
        tf_op = [k for k, s in stat_names.items() if s == "tf_op"]
        ops: Dict[str, str] = {}
        for ev in events:
            ev_name, value = None, None
            for ef, v in _fields(ev):
                if ef == 2:
                    ev_name = bytes(v).decode()
                elif ef == 5:
                    stat = dict(_fields(v))
                    if stat.get(1) in tf_op:
                        value = (bytes(stat[5]).decode() if 5 in stat
                                 else stat_names.get(stat.get(7)))
            if ev_name is not None and value is not None:
                ops[ev_name] = value
        out[name] = ops
    return out


def innermost_scope(tf_op: Optional[str]) -> Optional[str]:
    """``jit(_prefill_fn)/while/body/closed_call/time_mix/wkv/while:`` ->
    ``wkv``: the last of the program's named scopes in the path."""
    for part in reversed((tf_op or "").rstrip(":").split("/")):
        if part in SCOPES:
            return part
    return None


def load(path: str) -> dict:
    """The parts of an ``.xplane.pb`` file both reductions read."""
    from jax.profiler import ProfileData

    with open(path, "rb") as f:
        data = f.read()
    pd = ProfileData.from_serialized_xspace(data)
    scopes = op_scopes(data)
    devices, host, serve = [], [], []
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            lines = {ln.name: list(ln.events) for ln in plane.lines
                     if ln.name in (TR.OPS_LINE, TR.MODULES_LINE)}
            if lines:
                names = scopes.get(plane.name, {})
                ops = lines.get(TR.OPS_LINE, [])
                devices.append({
                    "name": plane.name,
                    "ops": [(TR.op_name(e.name), float(e.start_ns),
                             float(e.duration_ns)) for e in ops],
                    "modules": [(TR.op_name(e.name), float(e.start_ns),
                                 float(e.duration_ns))
                                for e in lines.get(TR.MODULES_LINE, [])],
                    "scopes": [innermost_scope(names.get(e.name))
                               for e in ops]})
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                for e in ln.events:
                    if e.name.startswith(TR.SPAN_PREFIX):
                        host.append((e.name, float(e.start_ns),
                                     float(e.duration_ns)))
                    elif e.name.startswith(SPAN_PREFIX):
                        serve.append((e.name, float(e.start_ns),
                                      float(e.duration_ns), dict(e.stats)))
    return {"devices": devices, "host": sorted(host, key=lambda e: e[1]),
            "serve": sorted(serve, key=lambda e: e[1])}


# ---------------------------------------------------------------------------
# the reduction
# ---------------------------------------------------------------------------

def _launching(serve: List[Span], kind: str) -> List[Span]:
    """The spans of one kind that launch a program, in launch order."""
    return [s for s in serve if s[0] == kind
            and (kind != "serve.admit" or "bucket" in s[3])]


def tie(executions: List[TR.Event], spans: List[Span]
        ) -> List[Tuple[int, int]]:
    """(execution index, span index) pairs: execution i with span i + j,
    for the offset j within EDGE of the two lists' ends whose median
    start-to-start distance is least."""
    best: Optional[Tuple[float, int]] = None
    lo, hi = -EDGE, len(spans) - len(executions) + EDGE
    for j in range(min(lo, hi), max(lo, hi) + 1):
        lags = [abs(executions[i][1] - spans[i + j][1])
                for i in range(len(executions))
                if 0 <= i + j < len(spans)]
        if lags:
            score = statistics.median(lags)
            if best is None or score < best[0]:
                best = (score, j)
    if best is None:
        return []
    j = best[1]
    return [(i, i + j) for i in range(len(executions))
            if 0 <= i + j < len(spans)]


def reduce(trace: dict, top: int = 10) -> dict:
    out = TR.reduce(trace, top)
    dev = next(d for d in trace["devices"] if d["ops"])
    serve = sorted((tuple(s) for s in trace.get("serve", [])),
                   key=lambda s: s[1])
    mods = sorted((tuple(m) for m in dev["modules"]), key=lambda e: e[1])

    pairs, n_exec = [], 0
    for kind, programs in LAUNCHES.items():
        execs = [m for m in mods if TR.program_name(m[0]) in programs]
        n_exec += len(execs)
        spans = _launching(serve, kind)
        pairs += [(execs[i], spans[k]) for i, k in tie(execs, spans)]
    shift = max([0.0] + [s[1] - e[1] for e, s in pairs])

    ops = [(n, s + shift, d) for n, s, d in dev["ops"]]
    mods = [(n, s + shift, d) for n, s, d in mods]
    mod_starts = [m[1] for m in mods]
    scoped: Dict[str, Dict[str, float]] = collections.defaultdict(
        lambda: collections.defaultdict(float))
    names = dev.get("scopes") or [None] * len(ops)
    for (_, s, _), own, scope in zip(ops, TR._self_times(ops), names):
        prog = TR._owner(mods, mod_starts, s) or "?"
        scoped[prog][scope or NO_SCOPE] += own * 1e-9

    host = [tuple(e) for e in trace["host"]]
    ends = [s + d for _, s, d in ops] + [s + d for _, s, d in host] + \
        [s[1] + s[2] for s in serve]
    starts = [s for _, s, _ in ops] + [s for _, s, _ in host] + \
        [s[1] for s in serve]
    lo, hi = min(starts), max(ends)
    merged = TR._union([(s, s + d) for _, s, d in ops])
    gaps = [(lo, merged[0][0])] + \
        [(a[1], b[0]) for a, b in zip(merged, merged[1:])] + \
        [(merged[-1][1], hi)]
    gaps = [(s, e) for s, e in gaps if e > s]
    idle: Dict[str, float] = collections.defaultdict(float)
    mids = [0.5 * (s + e) for s, e in gaps]
    first = min((s[1] for s in serve), default=hi)
    last = max((s[1] + s[2] for s in serve), default=lo)
    for (s, e), m, span in zip(gaps, mids,
                               TR._innermost_at([s[:3] for s in serve],
                                                mids)):
        label = CUT if not first <= m <= last else span or NO_SPAN
        idle[label] += (e - s) * 1e-9

    out.update(
        tied=len(pairs) / n_exec if n_exec else None,
        shift_s=shift * 1e-9,
        scopes={p: dict(v) for p, v in scoped.items()},
        idle_by_span=sorted(([k, v] for k, v in idle.items()),
                            key=lambda kv: -kv[1]))
    return out
