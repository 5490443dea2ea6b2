"""decode_stall_p99_ms.chat: Scheduler: 99th percentile of the host gaps
between decode steps that a live row waited through (ms)."""

from benchlib.stats import percentile


def read(run):
    p = percentile([g for _, g in run.stall_gaps()], 99)
    return None if p is None else 1e3 * p
