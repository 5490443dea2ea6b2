"""Reduce a profiler trace of the measured window to device metrics.

``load(path)`` reads the ``.xplane.pb`` that ``jax.profiler`` wrote and
keeps only what the metrics need, as plain lists (so a small sample can
be committed and the reduction tested without a chip):

* per device plane (``/device:TPU:n``): the ``XLA Ops`` line (every
  operation the device ran) and the ``XLA Modules`` line (one event per
  program execution, named ``jit_<function>(<id>)``);
* on the host: the benchmark's own spans, the events named ``bench.*``
  that ``benchlib/driver.py`` opens around the engine's methods.

``reduce(trace)`` gives, over the traced window:

* ``window_s``: from the first to the last event kept;
* ``busy_s``: the union of the operation intervals, averaged over the
  devices;
* ``modules``: device seconds of each program execution, by program;
* ``decode_calls``: each decode execution's device seconds and the
  decode step whose host span holds it;
* ``device_ops``: the 10 operations that took most device time, named
  ``<program>/<op>``, by self time (a ``while`` loop's time less the
  operations of its body, which the trace lists as well);
* ``idle_gaps``: device-idle seconds summed by the innermost host span
  open at each gap's middle, the 10 largest.

The device's clock in the trace runs a little ahead of the host's (on a
v5e about 1.6 ms: a decode program appears to start before the host
span that dispatched it).  ``reduce`` measures that offset as the median
distance from each decode execution's start to the nearest decode
span's start, and moves the device events back by it before relating
them to host spans.
"""
from __future__ import annotations

import bisect
import collections
import re
from typing import Dict, List, Optional, Tuple

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "bench."
NO_SPAN = "serve loop outside the benchmark's spans"

Event = Tuple[str, float, float]        # name, start ns, duration ns


def op_name(hlo: str) -> str:
    """``%fusion.12 = bf16[16,4096]{...} fusion(...)`` -> ``fusion.12``."""
    return hlo.split(" = ", 1)[0].lstrip("%")


def load(path: str) -> dict:
    """The parts of an ``.xplane.pb`` file the reduction reads."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    devices, host = [], []
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            lines = {ln.name: [(op_name(e.name), float(e.start_ns),
                                float(e.duration_ns)) for e in ln.events]
                     for ln in plane.lines
                     if ln.name in (OPS_LINE, MODULES_LINE)}
            if lines:
                devices.append({"name": plane.name,
                                "ops": lines.get(OPS_LINE, []),
                                "modules": lines.get(MODULES_LINE, [])})
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                host.extend((e.name, float(e.start_ns), float(e.duration_ns))
                            for e in ln.events
                            if e.name.startswith(SPAN_PREFIX))
    return {"devices": devices, "host": sorted(host, key=lambda e: e[1])}


def program_name(module_event_name: str) -> str:
    """``jit__decode_fn(123)`` -> ``_decode_fn``."""
    name = re.sub(r"\(\d+\)$", "", module_event_name)
    return name[4:] if name.startswith("jit_") else name


def span_label(name: str) -> str:
    """``bench.decode.118`` -> ``decode``."""
    return re.sub(r"\.\d+$", "", name[len(SPAN_PREFIX):])


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def _innermost_at(spans: List[Event], times: List[float]
                  ) -> List[Optional[str]]:
    """For each time, the name of the shortest host span open then (one
    sweep over span starts and ends; spans nest a few deep)."""
    marks = [(s, 0, i) for i, (_, s, _) in enumerate(spans)]
    marks += [(s + d, 2, i) for i, (_, s, d) in enumerate(spans)]
    marks += [(t, 1, j) for j, t in enumerate(times)]
    marks.sort()
    open_: Dict[int, float] = {}
    out: List[Optional[str]] = [None] * len(times)
    for _, kind, i in marks:
        if kind == 0:
            open_[i] = spans[i][2]
        elif kind == 2:
            open_.pop(i, None)
        elif open_:
            out[i] = spans[min(open_, key=open_.get)][0]
    return out


def _owner(modules: List[Event], starts: List[float], t: float
           ) -> Optional[str]:
    i = bisect.bisect_right(starts, t) - 1
    if i >= 0 and t <= modules[i][1] + modules[i][2]:
        return program_name(modules[i][0])
    return None


def _self_times(ops: List[Event]) -> List[float]:
    """Each operation's duration less that of the operations nested in
    it (a loop's body runs inside the loop's own event)."""
    order = sorted(range(len(ops)), key=lambda i: (ops[i][1], -ops[i][2]))
    own = [ops[i][2] for i in range(len(ops))]
    stack: List[int] = []
    for i in order:
        s, d = ops[i][1], ops[i][2]
        while stack and ops[stack[-1]][1] + ops[stack[-1]][2] <= s:
            stack.pop()
        if stack and s + d <= ops[stack[-1]][1] + ops[stack[-1]][2]:
            own[stack[-1]] -= d
        stack.append(i)
    return own


def clock_offset(modules: List[Event], host: List[Event]) -> float:
    """How far (ns) the device clock runs ahead of the host's: the median
    distance from each decode execution's start to the nearest decode
    span's start; 0 when the trace holds no decode."""
    spans = sorted(s for n, s, _ in host
                   if re.fullmatch(r"bench\.decode\.\d+", n))
    diffs = []
    for name, s, _ in modules:
        if spans and program_name(name) == "_decode_fn":
            i = bisect.bisect_left(spans, s)
            near = [spans[j] for j in (i - 1, i) if 0 <= j < len(spans)]
            diffs.append(min((s - h for h in near), key=abs))
    return sorted(diffs)[len(diffs) // 2] if diffs else 0.0


def reduce(trace: dict, top: int = 10) -> dict:
    devices = [d for d in trace["devices"] if d["ops"]]
    if not devices:
        raise ValueError("the trace holds no device operation")
    host = sorted((tuple(e) for e in trace["host"]), key=lambda e: e[1])
    delta = clock_offset(devices[0]["modules"], host)
    devices = [{"ops": [(n, s - delta, d) for n, s, d in dev["ops"]],
                "modules": [(n, s - delta, d) for n, s, d in dev["modules"]]}
               for dev in devices]
    starts = [e[1] for d in devices for e in d["ops"]] + [e[1] for e in host]
    ends = [e[1] + e[2] for d in devices for e in d["ops"]] + \
        [e[1] + e[2] for e in host]
    lo, hi = min(starts), max(ends)

    busy = []
    for d in devices:
        merged = _union([(s, s + du) for _, s, du in d["ops"]])
        busy.append(sum(e - s for s, e in merged))

    dev = devices[0]
    mods = sorted(dev["modules"], key=lambda e: e[1])
    mod_starts = [e[1] for e in mods]
    modules: Dict[str, List[float]] = collections.defaultdict(list)
    for name, _, du in mods:
        modules[program_name(name)].append(du * 1e-9)

    # with the clocks aligned, a decode execution starts just after its
    # span does: match each to the span whose start is nearest
    steps = sorted((s, int(n.rsplit(".", 1)[1])) for n, s, _ in host
                   if re.fullmatch(r"bench\.decode\.\d+", n))
    step_starts = [s for s, _ in steps]
    decode_calls = []
    for name, s, du in mods:
        if program_name(name) != "_decode_fn":
            continue
        i = bisect.bisect_left(step_starts, s)
        near = [j for j in (i - 1, i) if 0 <= j < len(steps)]
        j = min(near, key=lambda j: abs(step_starts[j] - s), default=None)
        decode_calls.append({"step": steps[j][1] if j is not None else None,
                             "seconds": du * 1e-9})

    per_op: Dict[str, float] = collections.defaultdict(float)
    for (name, s, _), own in zip(dev["ops"], _self_times(dev["ops"])):
        prog = _owner(mods, mod_starts, s) or "?"
        per_op[f"{prog}/{name}"] += own * 1e-9
    device_ops = sorted(per_op.items(), key=lambda kv: -kv[1])[:top]
    merged = _union([(s, s + du) for _, s, du in dev["ops"]])
    gaps = [(a[1], b[0]) for a, b in zip(merged, merged[1:])]
    if merged:
        gaps = [(lo, merged[0][0])] + gaps + [(merged[-1][1], hi)]
    gaps = [(s, e) for s, e in gaps if e > s]
    idle: Dict[str, float] = collections.defaultdict(float)
    owners = _innermost_at(host, [0.5 * (s + e) for s, e in gaps])
    for (s, e), span in zip(gaps, owners):
        idle[span_label(span) if span else NO_SPAN] += (e - s) * 1e-9
    idle_gaps = sorted(idle.items(), key=lambda kv: -kv[1])[:top]

    return {"window_s": (hi - lo) * 1e-9,
            "busy_s": sum(busy) / len(busy) * 1e-9,
            "modules": dict(modules),
            "decode_calls": decode_calls,
            "device_ops": [[n, v] for n, v in device_ops],
            "idle_gaps": [[n, v] for n, v in idle_gaps]}
