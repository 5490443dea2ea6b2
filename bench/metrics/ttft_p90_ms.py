"""ttft_p90_ms: Time to first token, 90th percentile (ms), host clock: from each
request's scheduled arrival to the host holding its first token, over
every request of the window (one never served counts as infinite)."""

from benchlib.stats import percentile


def read(run):
    vals = [r.ttft_s if r.admitted and r.n_out else float("inf")
            for r in run.requests]
    p = percentile(vals, 90)
    return None if p is None else 1e3 * p
