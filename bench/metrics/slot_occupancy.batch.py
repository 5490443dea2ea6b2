"""slot_occupancy.batch: Scheduler: live rows over max_batch, averaged
over the window's decode steps (the engine's ServeMetrics.slot_occupancy)."""


def read(run):
    return run.slot_occupancy if run.counters["decode_steps"] else None
