"""queue_wait_p90_ms.chat: Scheduler: 90th percentile (ms), over the
window's admitted requests, of the wait from each request's scheduled
arrival to the start of the admission that placed it (the program's own
stamps: ``admit_started_at - submitted_at``).  None where the record
carries no ``admit_started_at``."""

from benchlib.stats import percentile


def read(run):
    waits = [r.admit_started_at - r.submitted_at for r in run.requests
             if r.admitted
             and getattr(r, "admit_started_at", None) is not None]
    p = percentile(waits, 90)
    return None if p is None else 1e3 * p
