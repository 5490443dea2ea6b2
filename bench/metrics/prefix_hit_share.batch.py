"""prefix_hit_share.batch: Cache backend: prompt tokens served from the
radix prefix cache over all prompt tokens admitted in the window."""


def read(run):
    hit = run.counters["prefix_hit_tokens"]
    total = hit + run.counters["prefill_tokens"]
    return hit / total if total else None
