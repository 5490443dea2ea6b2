"""The record of one measured window, as the metric readers see it.

It is rebuilt from what the engine leaves behind after ``serve()``:
each ``Request``'s ``submitted_at`` / ``admitted_at`` / ``done_at``, the
scheduler's ``events`` (admit and retire, each tagged with the decode
step count at that moment) and ``step_walls`` (host time after each
decode step).  A request admitted when the step count was ``a`` emits
its first token from its prefill and its k-th further token in step
``a + k``, so every output token has a host time without a change to
the program.  On the paged path ``admitted_at`` is stamped before the
extend call, so there the first token is dated by the end of the
decode step that follows its admission (at most one step late).
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional

import numpy as np

COUNTERS = ("prefill_tokens", "decode_tokens", "decode_steps",
            "prefix_hit_tokens")


@dataclasses.dataclass
class Req:
    rid: int
    prompt_len: int
    max_new: int
    arrival_s: float
    status: str
    n_out: int
    submitted_at: float
    admitted_at: float
    done_at: float
    admit_step: Optional[int]          # decode-step count when admitted
    token_times: np.ndarray            # host time of each output token

    @property
    def admitted(self) -> bool:
        return self.admit_step is not None

    @property
    def ttft_s(self) -> float:
        return float(self.token_times[0] - self.submitted_at)

    @property
    def tpot_s(self) -> Optional[float]:
        if self.n_out < 2:
            return None
        return float(self.token_times[-1] - self.token_times[0]) / (
            self.n_out - 1)


@dataclasses.dataclass
class Run:
    cell: str
    terms: dict                  # the family's cost terms (costs.py)
    model: dict                  # the configuration's sizes
    n_layers: int
    max_batch: int
    paged: bool
    seconds: float               # the window
    t0: float                    # serve() start, host monotonic seconds
    requests: List[Req]
    step_walls: np.ndarray       # host time after each decode step
    d0: int                      # decode steps taken before the window
    counters: Dict[str, float]   # ServeMetrics over the window
    slot_occupancy: float
    peaks: dict
    min_bucket: int = 16
    bucket_cap: int = 2 ** 30
    setup_s: float = 0.0
    trace: Optional[dict] = None
    # prompt tokens each request found in the prefix cache (traced runs)
    matched: Optional[Dict[int, int]] = None

    @property
    def window_end(self) -> float:
        return self.t0 + self.seconds

    def bucket(self, n: int) -> int:
        """The engine's prompt bucket for n tokens (a power of two)."""
        b = 1
        while b < n:
            b *= 2
        return min(max(self.min_bucket, b), self.bucket_cap)

    def step_time(self, step: int) -> float:
        """Host time after decode step ``step`` (a cumulative count)."""
        return float(self.step_walls[step - self.d0 - 1])

    def step_contexts(self) -> Dict[int, List[int]]:
        """For each decode step, the positions each live row attends to
        (its prompt plus the tokens before it, itself included)."""
        out: Dict[int, List[int]] = {}
        for r in self.requests:
            if not r.admitted:
                continue
            for k in range(1, r.n_out):
                out.setdefault(r.admit_step + k, []).append(
                    r.prompt_len + k)
        return out

    def admission_groups(self) -> Dict[int, List[Req]]:
        """Requests admitted together, keyed by the decode-step count at
        their admission (one prefill or extend call each)."""
        out: Dict[int, List[Req]] = {}
        for r in self.requests:
            if r.admitted:
                out.setdefault(r.admit_step, []).append(r)
        return out

    def tokens_in_window(self) -> int:
        """Output tokens the host held by the window's close."""
        end = self.window_end
        return int(sum((r.token_times <= end).sum() for r in self.requests))

    def stall_gaps(self) -> List[tuple]:
        """(step, seconds) between consecutive decode steps, kept only
        where some admitted row waited through the gap for its next token
        (a gap after which no row was live is idle time, not a stall)."""
        live = self.live_after_step()
        first = self.d0 + 1
        out = []
        for i in range(1, len(self.step_walls)):
            step = first + i
            if live.get(step - 1, 0):
                out.append((step, float(self.step_walls[i]
                                        - self.step_walls[i - 1])))
        return out

    def mean_device_ms(self, program: str) -> Optional[float]:
        """Device time per execution of a traced program (its jit name)."""
        if self.trace is None:
            return None
        vals = self.trace["modules"].get(program, [])
        return 1e3 * sum(vals) / len(vals) if vals else None

    def live_after_step(self) -> Dict[int, int]:
        """How many rows, admitted before a decode step, still wait for a
        token of a later step once it ends."""
        live: Dict[int, int] = {}
        for r in self.requests:
            # tokens come from steps a+1 .. a+n_out-1; the row is admitted
            # only after step a ends
            for s in range(r.admit_step + 1 if r.admitted else 0,
                           r.admit_step + r.n_out - 1 if r.admitted else 0):
                live[s] = live.get(s, 0) + 1
        return live


def build(engine, reqs, *, cell, terms, model, n_layers, seconds,
          d0, before, peaks) -> Run:
    """The window's record from the engine's state after ``serve()``."""
    admit = {}
    retire = {}
    for ev in engine.events:
        if ev[0] == "admit":
            admit[ev[1]] = ev[3]
        elif ev[0] == "retire":
            retire[ev[1]] = ev[3]
    walls = np.asarray(engine.step_walls, np.float64)
    t0 = reqs[0].submitted_at - reqs[0].arrival_s
    run = Run(cell=cell, terms=terms, model=model, n_layers=n_layers,
              max_batch=engine.max_batch, paged=bool(engine.paged),
              seconds=seconds, t0=t0, requests=[], step_walls=walls, d0=d0,
              counters={k: float(engine.metrics[k] - before[k])
                        for k in COUNTERS},
              slot_occupancy=float(engine.metrics["slot_occupancy"]),
              peaks=peaks, min_bucket=engine.min_bucket,
              bucket_cap=engine._bucket_cap)
    for r in reqs:
        n_out = 0 if r.output is None else len(r.output)
        a = admit.get(r.rid)
        times = np.zeros(n_out)
        if a is not None and n_out:
            if a + n_out - 1 != retire.get(r.rid, a + n_out - 1):
                raise RuntimeError(
                    f"request {r.rid}: admitted at step {a} with {n_out} "
                    f"tokens but retired at step {retire[r.rid]}; the "
                    f"scheduler no longer emits one token per step")
            for k in range(1, n_out):
                times[k] = run.step_time(a + k)
            if run.paged:
                nxt = a + 1 - d0 - 1
                times[0] = walls[nxt] if nxt < len(walls) else r.done_at
            else:
                times[0] = r.admitted_at
        run.requests.append(Req(
            rid=r.rid, prompt_len=len(r.prompt), max_new=r.max_new_tokens,
            arrival_s=r.arrival_s, status=r.status, n_out=n_out,
            submitted_at=r.submitted_at, admitted_at=r.admitted_at,
            done_at=r.done_at, admit_step=a, token_times=times))
    return run
