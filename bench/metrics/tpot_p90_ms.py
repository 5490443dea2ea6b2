"""tpot_p90_ms: Time per output token after the first, 90th percentile
(ms), host clock: (last token time - first token time) / (output tokens
- 1) of each request of the window."""

from benchlib.stats import percentile


def read(run):
    vals = [r.tpot_s for r in run.requests if r.tpot_s is not None]
    p = percentile(vals, 90)
    return None if p is None else 1e3 * p
